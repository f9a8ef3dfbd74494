"""Round-phase tracer — host span ring buffer + Chrome trace-event export.

One fleet round decomposes into the phases the round kernels are built
from: ``schedule`` (wake/elect tasks) → ``execute`` (the micro-slice) →
``router`` (collective mailbox delivery) → ``io_service`` (host FIOS
servicing, when it happens) → ``warp`` (virtual-clock advance).  With
``ObsConfig(trace=True)`` the fleet wraps each phase in a
:meth:`RoundTracer.span`, which records wall-clock begin/duration into a
bounded host ring buffer (a ``deque`` — old rounds fall off, memory stays
constant at ``trace_ring`` events).

Honesty note: JAX dispatch is async, so a span's wall time is only
meaningful if the phase's outputs are synced inside it.  The fleet does
exactly that when tracing is on (one ``block_until_ready`` per phase) —
which is why tracing is opt-in and the default round loop stays fully
async with zero extra syncs.

Export is the Chrome trace-event format (the ``traceEvents`` JSON both
``chrome://tracing`` and https://ui.perfetto.dev open directly): one "X"
(complete) event per span with microsecond ``ts``/``dur``, phases mapped
to ``tid`` lanes per round.  :func:`validate_chrome_trace` is the
schema check CI runs on the exported artifact.
"""

from __future__ import annotations

import json
import time
from collections import deque
from contextlib import contextmanager

from jax.profiler import TraceAnnotation

PHASES = ("schedule", "execute", "router", "io_service", "warp")


class RoundTracer:
    """Ring-buffered span recorder for the fleet round loop.

    ``enabled=False`` builds a no-op tracer (``span`` yields immediately,
    records nothing) so call sites never need to branch.  Each recorded
    event is a dict ``{name, round, t0, dt}`` with ``t0`` in seconds from
    the tracer's epoch and ``dt`` the span duration in seconds.
    """

    def __init__(self, ring: int = 1024, enabled: bool = True,
                 profiler: bool = False):
        self.enabled = bool(enabled)
        self.profiler = bool(profiler)
        self.events: deque = deque(maxlen=max(int(ring), 1))
        self.round = 0
        self.epoch = time.perf_counter()

    @contextmanager
    def span(self, name: str):
        """Record one phase span (no-op when disabled)."""
        if not self.enabled:
            yield
            return
        ann = None
        if self.profiler:
            ann = TraceAnnotation(f"fleet/{name}")
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            if ann is not None:
                ann.__exit__(None, None, None)
            self.events.append(
                {"name": name, "round": self.round, "t0": t0 - self.epoch,
                 "dt": dt}
            )

    def tick(self):
        """Advance the round counter (called once per fleet round)."""
        if self.enabled:
            self.round += 1

    def snapshot(self) -> list[dict]:
        return list(self.events)


def export_chrome_trace(tracer_or_events, path=None, pid: int = 1):
    """Serialize spans as Chrome trace-event JSON.

    Accepts a :class:`RoundTracer` or a raw event list.  Each span becomes
    an "X" (complete) event with microsecond ``ts``/``dur``; phases get
    stable ``tid`` lanes so Perfetto stacks them consistently; a process
    metadata ("M") event names the track.  Returns the payload dict; when
    ``path`` is given, also writes it there as JSON.
    """
    events = (tracer_or_events.snapshot()
              if isinstance(tracer_or_events, RoundTracer)
              else list(tracer_or_events))
    lanes = {name: i + 1 for i, name in enumerate(PHASES)}
    out = [{
        "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
        "args": {"name": "fleet-round"},
    }]
    for ev in events:
        out.append({
            "name": ev["name"],
            "ph": "X",
            "ts": round(ev["t0"] * 1e6, 3),
            "dur": round(ev["dt"] * 1e6, 3),
            "pid": pid,
            "tid": lanes.get(ev["name"], len(PHASES) + 1),
            "args": {"round": ev["round"]},
        })
    payload = {"traceEvents": out, "displayTimeUnit": "ms"}
    if path is not None:
        with open(path, "w") as f:
            json.dump(payload, f)
    return payload


def validate_chrome_trace(trace) -> int:
    """Validate a Chrome trace-event payload; return the "X" span count.

    ``trace`` may be a file path, a payload dict, or a raw event list.
    Raises ``ValueError`` on schema violations (missing required keys,
    non-numeric timestamps, unknown structure) — used as the CI gate on
    the exported benchmark artifact.
    """
    if isinstance(trace, (str, bytes)):
        with open(trace) as f:
            trace = json.load(f)
    if isinstance(trace, dict):
        if "traceEvents" not in trace:
            raise ValueError("trace object missing 'traceEvents'")
        events = trace["traceEvents"]
    elif isinstance(trace, list):
        events = trace
    else:
        raise ValueError(f"unsupported trace payload: {type(trace).__name__}")
    if not isinstance(events, list):
        raise ValueError("'traceEvents' must be a list")
    n_spans = 0
    for i, ev in enumerate(events):
        if not isinstance(ev, dict) or "ph" not in ev:
            raise ValueError(f"event {i}: not a trace event object")
        if ev["ph"] != "X":
            continue
        for key in ("name", "ts", "dur", "pid", "tid"):
            if key not in ev:
                raise ValueError(f"event {i}: X event missing '{key}'")
        for key in ("ts", "dur"):
            if not isinstance(ev[key], (int, float)):
                raise ValueError(f"event {i}: '{key}' must be numeric")
        n_spans += 1
    return n_spans
