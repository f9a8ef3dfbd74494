"""Fleet span tracer — profiler annotations, a host span ring, Chrome export.

Every :class:`~repro.core.vm.fleet.FleetVM` owns one :class:`RoundTracer`
and wraps its host work in :meth:`RoundTracer.span`, on every executor
path.  The spans are per fleet call, never per node:

``start``  ⊃ ``audit`` (the Auditor, ``executor="auto"``), ``restack``
(stacking the node states and the host-to-device copy);
``run``    ⊃ ``dispatch`` (each round's enqueue), ``probe``, ``io_service``,
``sync`` (the device-to-host copy), ``decode`` (statuses and outputs).

Each span always enters a ``jax.profiler.TraceAnnotation`` named
``fleet.<name>`` carrying the read-out id and the span's counts as stats,
so a ``jax.profiler`` trace shows the host spans on the same clock as the
device operations.  With no profiler session a span costs a few
microseconds of host time and waits for nothing.

With ``ObsConfig(trace=True)`` the tracer also keeps a bounded ring of
records (a ``deque``: old spans fall off, memory stays at ``trace_ring``
events) for :meth:`FleetVM.export_trace`.  A record holds the span's name,
its id and its parent's id (the enclosing recorded span), the read-out id
(advanced by each ``start()``/``push()``), the round, its counts, and
``t0_ns``/``dt_ns`` read from ``time.time_ns()``, the clock the profiler
stamps its events with.  The observed round path (``obs=...``) adds the
round phases ``schedule`` → ``execute`` → ``router`` → ``warp`` as spans
and syncs the device after each one so their walls are honest; no other
path adds a sync.

On the device the round program names its phases with ``jax.named_scope``:
``fleet.schedule``, ``fleet.execute`` (around the vmloop kernel, with
``vmloop.pack`` and ``vmloop.unpack`` around building and cutting its
rows), ``fleet.router`` and ``fleet.warp``.  The scopes land in each
operation's ``op_name`` metadata and change nothing that is computed.

Export is the Chrome trace-event format (the ``traceEvents`` JSON both
``chrome://tracing`` and https://ui.perfetto.dev open directly): one "X"
(complete) event per span with microsecond ``ts``/``dur`` from the
tracer's epoch, each span name on its own ``tid`` lane.
:func:`validate_chrome_trace` is the schema check CI runs on the exported
artifact.
"""

from __future__ import annotations

import json
import time
from collections import deque
from contextlib import contextmanager

from jax.profiler import TraceAnnotation

PHASES = ("schedule", "execute", "router", "io_service", "warp")
HOST_SPANS = ("start", "audit", "restack", "run", "dispatch", "probe",
              "io_service", "sync", "decode")
PREFIX = "fleet."


class Span:
    """The open span a ``with tracer.span(...)`` yields: ``set`` adds
    counts known only at its end."""

    __slots__ = ("counts", "_ann")

    def __init__(self, ann, counts: dict):
        self._ann = ann
        self.counts = counts

    def set(self, **counts) -> None:
        self.counts.update(counts)
        self._ann.set_metadata(**counts)


class RoundTracer:
    """Span recorder of one fleet.

    ``span`` always annotates the profiler; ``enabled`` (``ObsConfig.trace``)
    adds the ring records.  ``round`` and ``readout`` tag every record.
    """

    def __init__(self, ring: int = 1024, enabled: bool = True):
        self.enabled = bool(enabled)
        self.events: deque = deque(maxlen=max(int(ring), 1))
        self.round = 0
        self.readout = 0
        self.epoch_ns = time.time_ns()
        self._open: list[int] = []
        self._ids = 0

    @contextmanager
    def span(self, name: str, **counts):
        """One span named ``fleet.<name>`` in the profiler, with
        ``readout`` and ``counts`` as its stats; a ring record too when
        enabled."""
        with TraceAnnotation(PREFIX + name, readout=self.readout,
                             **counts) as ann:
            sp = Span(ann, counts)
            if not self.enabled:
                yield sp
                return
            sid = self._ids
            self._ids += 1
            parent = self._open[-1] if self._open else None
            self._open.append(sid)
            t0 = time.time_ns()
            try:
                yield sp
            finally:
                dt = time.time_ns() - t0
                self._open.pop()
                self.events.append({
                    "name": name, "id": sid, "parent": parent,
                    "readout": self.readout, "round": self.round,
                    "t0_ns": t0, "dt_ns": dt, "counts": dict(sp.counts),
                })

    def next_readout(self) -> None:
        """Advance the read-out id (called by each ``start()``/``push()``)."""
        self.readout += 1

    def tick(self, rounds: int = 1):
        """Advance the round counter by the rounds just dispatched."""
        self.round += rounds

    def snapshot(self) -> list[dict]:
        return list(self.events)


def export_chrome_trace(tracer_or_events, path=None, pid: int = 1):
    """Serialize spans as Chrome trace-event JSON.

    Accepts a :class:`RoundTracer` or a raw event list.  Each span becomes
    an "X" (complete) event with microsecond ``ts``/``dur`` from the
    tracer's epoch (the earliest span of a raw list); each span name gets
    a stable ``tid`` lane so Perfetto stacks them consistently; a process
    metadata ("M") event names the track and gives the epoch in
    nanoseconds of the profiler's clock.  Returns the payload dict; when
    ``path`` is given, also writes it there as JSON.
    """
    if isinstance(tracer_or_events, RoundTracer):
        events = tracer_or_events.snapshot()
        epoch = tracer_or_events.epoch_ns
    else:
        events = list(tracer_or_events)
        epoch = min((ev["t0_ns"] for ev in events), default=0)
    names = PHASES + tuple(n for n in HOST_SPANS if n not in PHASES)
    lanes = {name: i + 1 for i, name in enumerate(names)}
    out = [{
        "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
        "args": {"name": "fleet", "epoch_ns": epoch},
    }]
    for ev in events:
        out.append({
            "name": ev["name"],
            "ph": "X",
            "ts": round((ev["t0_ns"] - epoch) / 1e3, 3),
            "dur": round(ev["dt_ns"] / 1e3, 3),
            "pid": pid,
            "tid": lanes.get(ev["name"], len(names) + 1),
            "args": {"round": ev["round"], "readout": ev["readout"],
                     "id": ev["id"], "parent": ev["parent"],
                     **ev["counts"]},
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
