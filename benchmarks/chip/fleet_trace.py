"""Reduce the traced window to the fleet's own spans and device phases.

``FleetVM`` annotates its host work as ``fleet.*`` events
(``repro.obs.tracing``): ``start`` ⊃ ``audit``, ``restack``; ``run`` ⊃
``dispatch``, ``probe``, ``io_service``, ``sync``, ``decode``.  Each
carries the read-out id and its counts as stats.  The round program names
its phases with ``jax.named_scope``: ``fleet.schedule``,
``fleet.execute`` (⊃ ``vmloop.pack``, ``vmloop.unpack``),
``fleet.router``, ``fleet.warp``.  Host events and device operations share
the profiler's clock.

The window is the harness's: from the first ``bench.*`` span's start to
the last one's end (``xplane.reduce``).  In it:

- ``span_ms``: per ``fleet.*`` name, the summed milliseconds, the number
  of spans, and the sums of their numeric counts;
- ``phase_s``: device seconds per innermost phase scope of each operation
  on the first device, plus ``unscoped``; ``kernel_s`` is the part of
  ``fleet.execute`` that the ``vmloop`` kernel took;
- ``idle_by_span``: the device's idle seconds (no operation running),
  each put down to the innermost ``fleet.*`` span that covers it, else
  to the ``bench.*`` span, else ``none``.

An operation's scope comes from the ``op_name`` metadata of its HLO
instruction.  The trace carries each program's ``HloProto`` in the event
metadata of its ``/host:metadata`` plane (named ``<module>(<program
id>)``), which ``jax.profiler.ProfileData`` does not expose, so
:func:`hlo_scopes` decodes it from the ``.xplane.pb`` itself.  A TPU
operation belongs to the program whose event on the device's ``XLA
Modules`` line encloses it; a CPU operation names its program in a
``program_id`` stat.  An instruction takes the innermost scope of its own
``op_name``.  XLA passes leave some instructions without metadata, among
them many fusions whose root is a scatter: a fusion without a scope of
its own takes the one scope that the instructions inside it name.  An
operation with none, or with several, counts as ``unscoped``; where no
operation has a scope (a program without them), ``phase_s`` is empty.

Readers call :func:`reduction`, which loads the trace once per run and
keeps the result in the readers' context.  ``python3
benchmarks/chip/fleet_trace.py <trace dir> [tpu|cpu]`` prints the whole
reduction of a TPU run's trace (or a CPU run's).
"""

from __future__ import annotations

import json
import re
import sys
from pathlib import Path

import xplane

SPAN = "fleet."
BENCH = "bench."
SCOPES = ("fleet.schedule", "fleet.execute", "vmloop.pack", "vmloop.unpack",
          "fleet.router", "fleet.warp")
KERNEL = "vmloop"


def scope_of(text: str) -> str | None:
    """The innermost phase scope named in an ``op_name`` path."""
    for part in reversed(text.split("/")):
        if part in SCOPES:
            return part
    return None


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        c = buf[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(buf: bytes, lo: int = 0, hi: int | None = None):
    """``(field, value)`` of a protobuf message in ``buf[lo:hi]``: an int
    for a scalar, a ``(lo, hi)`` span for a length-delimited field."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif kind in (1, 5):
            width = 8 if kind == 1 else 4
            value, i = int.from_bytes(buf[i:i + width], "little"), i + width
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, value


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _packed(buf: bytes, value) -> list[int]:
    if isinstance(value, int):
        return [value]
    out, i = [], value[0]
    while i < value[1]:
        x, i = _varint(buf, i)
        out.append(x)
    return out


def _module_scopes(buf: bytes, span) -> dict[str, str]:
    """Instruction name -> phase scope in one serialized ``HloProto``
    (hlo_module = 1; a module's computations = 3; a computation's
    instructions = 2 and id = 5; an instruction's name = 1, metadata = 7
    with op_name = 2, called_computation_ids = 38)."""
    module = dict(_fields(buf, *span)).get(1)
    if module is None:
        return {}
    own, called, inside = {}, {}, {}
    for field, comp in _fields(buf, *module):
        if field != 3:
            continue
        cid, names = None, set()
        for f, v in _fields(buf, *comp):
            if f == 5:
                cid = v
            elif f == 2:
                name, scope, calls = None, None, []
                for g, w in _fields(buf, *v):
                    if g == 1:
                        name = _text(buf, w)
                    elif g == 7:
                        meta = dict(_fields(buf, *w))
                        if 2 in meta:
                            scope = scope_of(_text(buf, meta[2]))
                    elif g == 38:
                        calls += _packed(buf, w)
                own[name] = scope
                called[name] = calls
                if scope:
                    names.add(scope)
        inside[cid] = names
    scopes = {}
    for name, scope in own.items():
        if scope is None:
            names = set().union(*(inside.get(c, set()) for c in called[name]))
            scope = names.pop() if len(names) == 1 else None
        if scope:
            scopes[name] = scope
    return scopes


def hlo_scopes(path: Path) -> dict[int, dict[str, str]]:
    """Program id -> (instruction name -> phase scope), from the
    ``HloProto`` of every program in the trace's ``/host:metadata`` plane
    (an ``XSpace``'s planes = 1; a plane's name = 2 and event_metadata = 4,
    a map of id to message; an event metadata's name = 2 and stats = 5; a
    stat's bytes = 6)."""
    buf = Path(path).read_bytes()
    out = {}
    for field, plane in _fields(buf):
        if field != 1:
            continue
        plane = list(_fields(buf, *plane))
        if not any(f == 2 and _text(buf, v) == "/host:metadata"
                   for f, v in plane):
            continue
        for f, entry in plane:
            if f != 4:
                continue
            meta = dict(_fields(buf, *entry)).get(2)
            if meta is None:
                continue
            name, proto = "", None
            for g, v in _fields(buf, *meta):
                if g == 2:
                    name = _text(buf, v)
                elif g == 5:
                    proto = dict(_fields(buf, *v)).get(6, proto)
            m = re.search(r"\((-?\d+)\)$", name)
            if m and proto is not None:
                out[int(m.group(1))] = _module_scopes(buf, proto)
    return out


def load(path: Path, platform: str = "tpu"):
    """``(ops, spans)``: the first device's operations ``(start_ns,
    end_ns, instruction name, scope)`` and the host spans ``(start_ns,
    end_ns, name, stats)`` named ``fleet.*`` or ``bench.*``.  ``platform``
    is the one the run used: ``tpu``, whose trace must hold a TPU plane,
    or, in tests, ``cpu``, whose operations are the host events that carry
    an ``hlo_op`` stat."""
    if platform not in ("tpu", "cpu"):
        raise ValueError(f"no trace reduction for platform {platform!r}")
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    programs = hlo_scopes(path)
    tpu, cpu_ops, spans = {}, [], []
    for plane in data.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            tpu[int(m.group(1))] = plane
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith((SPAN, BENCH)):
                    spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                  e.name, dict(e.stats)))
                    continue
                stats = dict(e.stats)
                if "hlo_op" in stats:
                    op = str(stats["hlo_op"])
                    scopes = programs.get(stats.get("program_id"), {})
                    cpu_ops.append((e.start_ns, e.start_ns + e.duration_ns,
                                    op, scopes.get(op)))
    if platform == "cpu":
        return sorted(cpu_ops), sorted(spans)
    if not tpu:
        raise ValueError("the trace of a TPU run has no TPU device plane")
    lines = {line.name: list(line.events) for line in tpu[min(tpu)].lines}
    modules = []
    for e in lines.get("XLA Modules", []):
        m = re.search(r"\((-?\d+)\)$", e.name)
        modules.append((e.start_ns, e.start_ns + e.duration_ns,
                        programs.get(int(m.group(1)), {}) if m else {}))
    modules.sort(key=lambda mod: mod[0])
    ops, k = [], 0
    for e in sorted(lines.get("XLA Ops", []), key=lambda e: e.start_ns):
        while k + 1 < len(modules) and modules[k + 1][0] <= e.start_ns:
            k += 1
        name = e.name.split(" = ", 1)[0].lstrip("%")
        mod = modules[k] if modules else None
        inside = mod is not None and mod[0] <= e.start_ns < mod[1]
        ops.append((e.start_ns, e.start_ns + e.duration_ns, name,
                    mod[2].get(name) if inside else None))
    return ops, sorted(spans)


def _innermost(spans, lo, hi):
    """Segments ``(s, e, label)`` tiling ``[lo, hi]``: in each, the
    innermost covering ``fleet.*`` span (the latest-starting one), else
    the ``bench.*`` span, else ``none``."""
    cuts = sorted({lo, hi} | {t for s, e, _, _ in spans for t in (s, e)
                              if lo < t < hi})
    segs = []
    for a, b in zip(cuts, cuts[1:]):
        cover = [sp for sp in spans if sp[0] <= a and b <= sp[1]]
        fleet = [sp for sp in cover if sp[2].startswith(SPAN)]
        pick = max(fleet or cover, key=lambda sp: (sp[0], -sp[1]),
                   default=None)
        segs.append((a, b, pick[2] if pick else "none"))
    return segs


def reduce(ops, spans) -> dict:
    """The fleet numbers of one traced window (seconds, milliseconds)."""
    bench = [sp for sp in spans if sp[2].startswith(BENCH)]
    if not bench:
        raise ValueError("the trace holds no bench.* host span")
    lo, hi = bench[0][0], max(sp[1] for sp in bench)
    fleet = [sp for sp in spans
             if sp[2].startswith(SPAN) and lo <= sp[0] and sp[1] <= hi]

    span_ms: dict[str, dict] = {}
    for s, e, name, stats in fleet:
        rec = span_ms.setdefault(name, {"ms": 0.0, "n": 0, "counts": {}})
        rec["ms"] += (e - s) / 1e6
        rec["n"] += 1
        for k, v in stats.items():
            if k != "readout" and isinstance(v, (int, float)):
                rec["counts"][k] = rec["counts"].get(k, 0) + v

    phase: dict[str, float] = {}
    kernel = 0.0
    scoped = False
    for s, e, name, scope in ops:
        d = min(e, hi) - max(s, lo)
        if d <= 0:
            continue
        scoped = scoped or scope is not None
        key = scope or "unscoped"
        phase[key] = phase.get(key, 0.0) + d
        if KERNEL in name:
            kernel += d
    phase_s = ({k: v / 1e9 for k, v in phase.items()} if scoped else {})

    busy = xplane.union([(s, e) for s, e, _, _ in ops], lo, hi)
    idle, t = [], lo
    for s, e in busy + [(hi, hi)]:
        if s > t:
            idle.append((t, s))
        t = max(t, e)
    idle_by: dict[str, float] = {}
    segs = _innermost(fleet + bench, lo, hi)
    i = 0
    for a, b in idle:
        while i < len(segs) and segs[i][1] <= a:
            i += 1
        j = i
        while j < len(segs) and segs[j][0] < b:
            d = min(b, segs[j][1]) - max(a, segs[j][0])
            if d > 0:
                idle_by[segs[j][2]] = idle_by.get(segs[j][2], 0.0) + d
            j += 1
    return {
        "window_s": (hi - lo) / 1e9,
        "span_ms": span_ms,
        "phase_s": phase_s,
        "kernel_s": kernel / 1e9 if scoped else None,
        "idle_s": sum(b - a for a, b in idle) / 1e9,
        "idle_by_span": {k: v / 1e9 for k, v in
                         sorted(idle_by.items(), key=lambda kv: -kv[1])},
    }


def reduction(ctx: dict, reader_file: str) -> dict | None:
    """The reduction of this run's trace, or ``None`` untraced.  The trace
    is the one the harness wrote under the checkout that holds the reader
    (``<root>/.bench_trace``), on the platform of the process that ran it;
    the result is kept in ``ctx["fleet"]``."""
    if ctx.get("trace") is None:
        return None
    if "fleet" not in ctx:
        import jax

        root = Path(reader_file).resolve().parents[3]
        ctx["fleet"] = reduce(*load(xplane.find_trace(root / ".bench_trace"),
                                    jax.default_backend()))
    return ctx["fleet"]


def readouts(ctx: dict) -> int:
    return len(ctx["spans"].get("start", ()))


def span_per_readout(ctx: dict, reader_file: str, name: str,
                     count: str | None = None) -> float | None:
    """Milliseconds of ``fleet.<name>`` per read-out of the window, or with
    ``count`` that count summed over the spans per read-out; ``None``
    where the program has no such span."""
    red = reduction(ctx, reader_file)
    if red is None or not readouts(ctx):
        return None
    rec = red["span_ms"].get(SPAN + name)
    if rec is None:
        return None
    total = rec["ms"] if count is None else rec["counts"].get(count)
    return None if total is None else total / readouts(ctx)


def phase_per_round(ctx: dict, reader_file: str,
                    *scopes: str) -> float | None:
    """Device milliseconds per round of the window under ``scopes``;
    ``None`` where no operation carries a phase scope."""
    red = reduction(ctx, reader_file)
    if red is None or not red["phase_s"] or not ctx["rounds"]:
        return None
    return sum(red["phase_s"].get(s, 0.0) for s in scopes) \
        / ctx["rounds"] * 1e3


if __name__ == "__main__":
    # fleet_trace.py <trace dir> [tpu|cpu]
    print(json.dumps(reduce(*load(xplane.find_trace(Path(sys.argv[1])),
                                  *sys.argv[2:3])), indent=1))
