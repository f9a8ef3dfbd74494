"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

Device operations are the events on the ``XLA Ops`` line of each
``/device:TPU:<i>`` plane.  Host spans are the harness's own
``jax.profiler.TraceAnnotation`` events, named ``bench.<phase>``.  The
traced window runs from the first host span's start to the last one's end.

- busy: the union of the device-operation intervals inside the window,
  averaged over the devices used; idle share is ``1 - busy / window``;
- kernel time: the summed durations of the operations whose name (or
  ``hlo_op`` stat) contains the kernel's name, on the first device.  On a
  TPU an operation's event is named by its whole HLO instruction
  (``%vmloop.1 = s32[...] custom-call(...)``); only the instruction's own
  name, before `` = ``, counts, so an operation that takes the kernel's
  output as an operand is not kernel time;
- breakdown: the operations that took most time in total, and the
  longest idle gaps, each named by the host span that overlaps it most.

A run on a TPU has to find a ``/device:TPU:<i>`` plane for every chip it
used, or the reduction raises: busy time is never read from host events
there.  Only a run whose platform is ``cpu`` (the CPU tests) reads its
operations from the host events that carry an ``hlo_op`` stat.
"""

from __future__ import annotations

import re
from pathlib import Path

SPAN_PREFIX = "bench."


def find_trace(log_dir: Path) -> Path:
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _names(event) -> list[str]:
    """The operation's own name first, then its ``hlo_op`` stat."""
    names = [event.name.split(" = ", 1)[0].lstrip("%")]
    for key, value in event.stats:
        if key == "hlo_op" and isinstance(value, str):
            names.append(value)
    return names


def load(path: Path, n_devices: int = 1, platform: str = "tpu"):
    """``(devices, spans)``: per device a list of ``(start_ns, end_ns,
    names)`` operations, and the host spans ``(start_ns, end_ns, name)``.
    ``platform`` is the one the run used: ``tpu`` or, in tests, ``cpu``."""
    if platform not in ("tpu", "cpu"):
        raise ValueError(f"no trace reduction for platform {platform!r}")
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    tpu = {}
    spans, cpu_ops = [], []
    for plane in data.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            tpu[int(m.group(1))] = [
                (e.start_ns, e.start_ns + e.duration_ns, _names(e))
                for line in plane.lines if line.name == "XLA Ops"
                for e in line.events
            ]
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                  e.name[len(SPAN_PREFIX):]))
                elif any(k == "hlo_op" for k, _ in e.stats):
                    cpu_ops.append((e.start_ns, e.start_ns + e.duration_ns,
                                    _names(e)))
    if platform == "cpu":
        devices = [cpu_ops]
    elif len(tpu) < n_devices:
        raise ValueError(f"the trace has {len(tpu)} TPU device plane(s); "
                         f"the run used {n_devices}")
    else:
        devices = [tpu[i] for i in sorted(tpu)[:n_devices]]
    return devices, sorted(spans)


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged, sorted intervals clipped to ``[lo, hi]``."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _overlap(a0, a1, b0, b1) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def reduce(devices, spans, kernels=("vmloop",), top: int = 10) -> dict:
    """The numbers of one traced window (seconds)."""
    if not spans:
        raise ValueError("the trace holds no bench.* host span")
    lo, hi = spans[0][0], max(e for _, e, _ in spans)
    busy = []
    for ops in devices:
        merged = union([(s, e) for s, e, _ in ops], lo, hi)
        busy.append(sum(e - s for s, e in merged))
    first = union([(s, e) for s, e, _ in devices[0]], lo, hi)
    gaps, t = [], lo
    for s, e in first + [(hi, hi)]:
        if s > t:
            label = max(spans, key=lambda sp: _overlap(t, s, sp[0], sp[1]))
            name = label[2] if _overlap(t, s, label[0], label[1]) > 0 else "none"
            gaps.append((s - t, name))
        t = max(t, e)
    per_op: dict[str, float] = {}
    kernel_s = {k: 0.0 for k in kernels}
    for s, e, names in devices[0]:
        d = min(e, hi) - max(s, lo)
        if d <= 0:
            continue
        per_op[names[0]] = per_op.get(names[0], 0.0) + d
        for k in kernels:
            if any(k in nm for nm in names):
                kernel_s[k] += d
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    gaps.sort(key=lambda g: -g[0])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9,
        "kernel_s": {k: v / 1e9 for k, v in kernel_s.items()},
        "device_ops": [[name, d / 1e9] for name, d in ops],
        "idle_gaps": [[name, d / 1e9] for d, name in gaps[:top]],
    }
