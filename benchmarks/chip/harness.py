"""Run one benchmark cell: build the fleet, warm up, measure, check.

The window drives ``FleetVM.run`` as a user does, in a closed loop of
read-outs: ``start()`` (restack the host states, the Auditor picks the
executor), ``run(max_rounds=R)`` (device rounds, ending in ``sync()``),
then read every node's committed counters and printed outputs.  It keeps
going until ``seconds`` have passed; the read-out that straddles the end
is completed and counted.  A rate is all the work of all read-outs over
all of their time.

Set-up (``setup_s``) is building the nodes from the seed, compiling each
distinct firmware once, and the traffic's warm-up read-outs, which trace
and compile (or load from the persistent cache) every program the window
runs.

After the window the fleet is freed and every answer of every read-out,
warm-up included, is compared with the node program's numpy reference.
"""

from __future__ import annotations

import gc
import json
import shutil
import sys
import time
from dataclasses import dataclass
from pathlib import Path

import numpy as np

import manifest
import xplane

# Every number compared with the reference, with its limit.  All are exact
# comparisons, so every limit is 0.
LIMITS = {
    "wrong_outputs": 0,    # printed values that differ from the reference
    "lost_outputs": 0,     # committed results never printed (ring overflow)
    "wrong_checksums": 0,  # nodes whose committed checksum differs
    "stalled_reads": 0,    # read-outs in which the fleet made no progress
    "idle_nodes": 0,       # nodes that made no progress in the whole run
}


class CompileClock:
    """Counts backend compiles and traces that JAX's monitoring reports,
    and sums their seconds."""

    EVENTS = {
        "/jax/core/compile/backend_compile_duration": "compiles",
        "/jax/core/compile/jaxpr_trace_duration": "traces",
    }

    def __init__(self):
        import jax

        self.counts = {"compiles": 0, "traces": 0}
        self.seconds = {"compiles": 0.0, "traces": 0.0}

        def listen(name, secs, **_):
            kind = self.EVENTS.get(name)
            if kind:
                self.counts[kind] += 1
                self.seconds[kind] += secs
        jax.monitoring.register_event_duration_secs_listener(listen)


@dataclass
class Readout:
    seconds: float
    rounds: int
    state: np.ndarray                 # (n, k) the committed STATE array
    outputs: list[str]
    spans: dict                       # host seconds of start, run, read


def cell_params(cfg_file: dict, mix: dict) -> dict:
    """The node program's parameters: the configuration's, the traffic
    mix's, and the mailbox size the program has to fit its messages in."""
    return {**cfg_file["params"], **mix.get("params", {}),
            "mbox_size": cfg_file["vm"]["mbox_size"]}


def build_fleet(cfg_file: dict, params: dict, prog, seed: int,
                executor: str):
    """A fleet with every node programmed: each distinct firmware is
    compiled once into a template node, and each node restores its
    template's checkpoint and gets its identity as DIOS data."""
    from repro.config import VMConfig
    from repro.core.vm import FleetVM, REXAVM

    cfg = VMConfig(**cfg_file["vm"])
    n = cfg_file["fleet"]["nodes"]
    which, dios = prog.node_data(params, seed, n)
    ckpts = []
    for text in prog.firmwares(params, seed):
        tmpl = REXAVM(cfg)
        for name, data in dios.items():
            tmpl.dios_add(name, data.shape[1])
        tmpl.launch(tmpl.load(text))
        ckpts.append(tmpl.checkpoint())
    fleet = FleetVM(cfg, n=n, executor=executor)
    for i, node in enumerate(fleet.nodes):
        node.restore(ckpts[which[i]])
        for name, data in dios.items():
            node.dios_add(name, data[i])
    return fleet


def readout(fleet, rounds: int, state: str) -> Readout:
    """One closed-loop read-out, with a host span around each call."""
    from jax.profiler import TraceAnnotation

    t0 = time.perf_counter()
    with TraceAnnotation("bench.start"):
        fleet.start()
    t1 = time.perf_counter()
    with TraceAnnotation("bench.run"):
        res = fleet.run(max_rounds=rounds)
    t2 = time.perf_counter()
    with TraceAnnotation("bench.read"):
        st = np.array([node.dios_read(state) for node in fleet.nodes])
        outputs = res.outputs
    t3 = time.perf_counter()
    spans = {"start": t1 - t0, "run": t2 - t1, "read": t3 - t2}
    return Readout(t3 - t0, res.rounds, st, outputs, spans)


def auditor(fleet) -> dict:
    a = fleet.analysis_stats()
    return {"executor": a["executor"], "elide_checks": a["elide_checks"],
            "reasons": a["reasons"]}


def parse(out: str) -> np.ndarray:
    return np.array(out.split(), np.int64)


def compare(printed: list[np.ndarray], counts: np.ndarray,
            checksums: np.ndarray, ref_printed: np.ndarray,
            ref_checksums: np.ndarray) -> dict:
    """The numbers of ``LIMITS`` for answers ``printed`` (per node, every
    read-out's output in order), committed ``counts`` (read-outs x nodes)
    and final ``checksums``, against the reference."""
    n = counts.shape[1]
    final = counts[-1]
    total = counts.sum(axis=1)
    wrong = lost = 0
    for i in range(n):
        got = printed[i]
        c = min(len(got), ref_printed.shape[1])
        wrong += int(np.count_nonzero(got[:c] != ref_printed[i, :c]))
        wrong += len(got) - c
        lost += max(0, int(final[i]) - len(got) - 1)
    idx = np.clip(final, 0, ref_checksums.shape[1] - 1)
    bad_sum = checksums != ref_checksums[np.arange(n), idx]
    return {
        "wrong_outputs": wrong,
        "lost_outputs": lost,
        "wrong_checksums": int(np.count_nonzero(bad_sum)),
        "stalled_reads": int(np.count_nonzero(np.diff(total, prepend=0) <= 0)),
        "idle_nodes": int(np.count_nonzero(final <= 0)),
    }


def check(prog, params: dict, seed: int, reads: list[Readout],
          control: bool = False) -> dict:
    """Compare every read-out's answers with the reference.  With
    ``control`` the reference's lower-precision twin stands in for the
    program, at the program's own counts."""
    n = reads[0].state.shape[0]
    counts = np.stack([r.state[:, 0] for r in reads]).astype(np.int64)
    total = int(max(counts.max(), 0))
    ref_p, ref_c = prog.reference(params, seed, n, total)
    if control:
        got_p, got_c = prog.reference(params, seed, n, total, control=True)
        lens = [sum(len(r.outputs[i].split()) for r in reads) for i in range(n)]
        printed = [got_p[i, :lens[i]] for i in range(n)]
        checksums = got_c[np.arange(n), counts[-1]]
    else:
        printed = [
            np.concatenate([parse(r.outputs[i]) for r in reads])
            for i in range(n)
        ]
        checksums = reads[-1].state[:, 1].astype(np.int64)
    return compare(printed, counts, checksums, ref_p, ref_c)


def device_info() -> dict:
    import jax

    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    return {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
        "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0)),
    }


def measure(root: Path, name: str, seed: int, seconds: float, trace: bool,
            executor: str = "auto", log=sys.stderr) -> dict:
    """Set up, warm up and measure one cell; return the raw run (the fleet
    is freed before returning).  ``executor`` is ``"auto"``, as users run
    the fleet; CPU tests pass ``"oracle"``, since the Pallas interpreter
    takes minutes there."""
    import jax

    bench = manifest.load(root)
    cell = manifest.workload(bench, name)
    cfg_file = manifest.config(root, bench, cell["config"])
    mix = manifest.traffic(root, cell["traffic"])
    prog = manifest.program(root, cfg_file["program"])
    params = cell_params(cfg_file, mix)
    rounds = mix["rounds_per_readout"]
    clock = CompileClock()

    t0 = time.perf_counter()
    fleet = build_fleet(cfg_file, params, prog, seed, executor)
    build_s = time.perf_counter() - t0
    print(f"build: {build_s:.3f} s", file=log, flush=True)
    warm = []
    for _ in range(mix["warmup_readouts"]):
        warm.append(readout(fleet, rounds, prog.STATE))
        print(f"warm-up read-out: {warm[-1].seconds:.3f} s, "
              f"auditor {json.dumps(auditor(fleet))}", file=log, flush=True)
    setup_s = time.perf_counter() - t0
    steps0 = fleet.pallas_stats()["kernel_steps"]
    compiles0 = dict(clock.counts)
    print(f"set-up: {setup_s:.3f} s; compiles {clock.counts['compiles']} "
          f"({clock.seconds['compiles']:.3f} s), traces "
          f"{clock.counts['traces']} ({clock.seconds['traces']:.3f} s)",
          file=log, flush=True)

    trace_dir = Path(root) / ".bench_trace"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    reads = []
    t0 = time.perf_counter()
    while True:
        reads.append(readout(fleet, rounds, prog.STATE))
        if time.perf_counter() - t0 >= seconds:
            break
    window_s = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    in_window = {k: clock.counts[k] - compiles0[k] for k in clock.counts}
    kernel_steps = (fleet.pallas_stats()["kernel_steps"] - steps0) % (1 << 32)
    device = device_info()
    plan = auditor(fleet)
    print(f"auditor (window): {json.dumps(plan)}", file=log)
    print(f"window: {len(reads)} read-outs, {window_s:.3f} s "
          f"({', '.join(f'{r.seconds:.3f}' for r in reads)}); compiles in "
          f"window: {in_window['compiles']}, traces in window: "
          f"{in_window['traces']}", file=log, flush=True)
    del fleet
    gc.collect()

    base = warm[-1].state[:, 0].astype(np.int64) if warm else 0
    work = int((reads[-1].state[:, 0] - base).sum())
    run = {
        "bench": bench, "cell": cell, "params": params, "prog": prog,
        "seed": seed, "reads": warm + reads,
        # What the metric readers read.
        "ctx": {
            "setup_s": setup_s,
            "window_s": window_s,
            "work": {prog.WORK: work},
            "rounds": sum(r.rounds for r in reads),
            "spans": {k: [r.spans[k] for r in reads] for k in reads[0].spans},
            "kernel_steps": kernel_steps,
            "trace": None,
        },
        "device": device,
    }
    if trace:
        devices, spans = xplane.load(xplane.find_trace(trace_dir),
                                     cell["chips"], device["platform"])
        run["ctx"]["trace"] = xplane.reduce(devices, spans)
    return run


def result(root: Path, run: dict, trace: bool, log=sys.stderr) -> dict:
    """The result line: metrics read by each metric's reader, the device,
    and the comparison with the reference (its numbers last)."""
    ctx = run["ctx"]
    metrics = {}
    for m in manifest.cell_metrics(run["bench"], run["cell"]["name"], trace):
        value = manifest.reader(root, m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    numbers = check(run["prog"], run["params"], run["seed"], run["reads"])
    attempted = int(run["reads"][-1].state[:, 0].astype(np.int64).sum())
    failed = numbers["wrong_outputs"] + numbers["lost_outputs"] + \
        numbers["wrong_checksums"] + numbers["idle_nodes"]
    correct = attempted > 0 and all(
        numbers[k] <= LIMITS[k] for k in LIMITS
    )
    device = dict(run["device"])
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": int(failed), "metrics": metrics, "device": device}
    tr = ctx["trace"]
    if trace and tr is not None:
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        out["breakdown"] = {"device_ops": tr["device_ops"],
                            "idle_gaps": tr["idle_gaps"]}
    out["check"] = {k: {"value": numbers[k], "limit": LIMITS[k]}
                    for k in LIMITS}
    for k in LIMITS:
        print(f"check {k}: {numbers[k]} (limit {LIMITS[k]})", file=log)
    return out
