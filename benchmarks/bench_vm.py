"""Paper Tab. 9 — VM interpreter throughput (MWPS) and compiler throughput
(MCPS), for the oracle ("software") and jitted ("hardware") backends, the
vmapped Parallel-VM ensemble (paper §3.4), the device-resident fleet
runtime (steps/s and host<->device transfer counts vs. the seed's
per-slice host loop), and the Pallas vmloop-kernel fleet
(``vm_fleet64_pallas``: steps/s + in-kernel vs lax-tail step split +
bail-out counts; ``vm_fleet64_pallas_msg``: the message-bound ring through
the fused ``rounds_aux`` fast path, rounds/s + msgs/s;
``vm_fleet64_pallas_ann``: the vecfold/dotprod tiny-ML workload — both
gated in CI at bailed_frac < 5%).  ``vm_fleet64_obs_overhead`` measures the
telemetry plane (PR 8): obs-on vs obs-off steps/s on the pallas ring
(CI-gated < 5% overhead), round-latency percentiles, deadline misses, and a
Chrome trace-event export validated and uploaded as a CI artifact.
``vm_fleet64_exec`` measures the Executive (PR 9): tasks/s and context
switches/s on a multi-task 64-node fleet, plus the vectorized-vs-per-node
syscall service comparison (CI-gated: one batched handler call per syscall
wave, not O(nodes) Python callbacks).  ``vm_fleet64_verified`` measures the
Auditor (PR 10): checks-elided vs always-checked steps/s on a statically
VERIFIED fleet under ``executor="auto"``."""

from __future__ import annotations

import os
import time

import jax
import numpy as np

from repro.config import VMConfig
from repro.core.vm import (
    Compiler,
    EnsembleVM,
    FleetVM,
    FrameManager,
    REXAVM,
    reference_round,
    replicate_state,
)
from repro.core.vm import vmstate as vms
from repro.core.vm.spec import ST_DONE, ST_ERR, ST_HALT

BENCH_PROG = ": work 0 begin 1+ dup 1000 >= until drop ; work work work work"

# Structured results filled by run() — benchmarks/run.py dumps this to
# BENCH_vm.json so the perf trajectory (steps/s, transfers, bytes) is
# tracked across PRs.
METRICS: dict = {}


def mwps(backend: str, steps_budget: int = 200_000) -> tuple[float, int]:
    """Returns (MWPS, full-state host<->device transfers)."""
    cfg = VMConfig(cs_size=2048, steps_per_slice=8192)
    vm = REXAVM(cfg, backend=backend)
    # Warm up compile path.
    vm.eval("1 drop", max_slices=4)
    t0_xfer = vm.executor.h2d + vm.executor.d2h
    t0 = time.perf_counter()
    res = vm.eval(BENCH_PROG, max_slices=steps_budget // 8192 + 50, steps=8192)
    dt = time.perf_counter() - t0
    return res.steps / dt / 1e6, vm.executor.h2d + vm.executor.d2h - t0_xfer


def mwps_ensemble(n: int = 32) -> tuple[float, float]:
    """Aggregate MWPS of an n-instance vmapped ensemble (one decode loop,
    n lock-stepped VMs — the paper's Parallel VM)."""
    cfg = VMConfig(cs_size=2048, steps_per_slice=8192)
    vm = REXAVM(cfg, backend="oracle")
    frame = vm.load(BENCH_PROG)
    vm.launch(frame)
    ens = EnsembleVM(cfg, n=n)
    batched = replicate_state(vms.to_device(vm.state), n)
    batched = ens.run_slice(batched)  # compile
    t0 = time.perf_counter()
    iters = 6
    for _ in range(iters):
        batched = ens.run_slice(batched)
    jax.block_until_ready(batched.steps)
    dt = time.perf_counter() - t0
    per_slice = 8192
    total = n * per_slice * iters
    return total / dt / 1e6, per_slice * iters / dt / 1e6


def _obs_latency(build, rounds: int, steps: int | None = None,
                 deadline_ms: int = 50, **run_kw) -> dict:
    """Short obs-instrumented rerun of a fleet row's workload: wall-latency
    percentiles and virtual-clock deadline misses, attached as columns to
    the row's METRICS entry.  Bounded rounds — the latency distribution
    does not need workload completion — and a separate fleet, so the
    row's timed obs-off measurement is untouched."""
    from repro.obs import ObsConfig

    kw = dict(run_kw)
    if steps is not None:
        kw["steps"] = steps
    # Warm the obs round's compiled path on a throwaway fleet so the
    # one-time compile doesn't land in the latency histogram.
    build(ObsConfig(deadline_ms=deadline_ms)).run(max_rounds=2, **kw)
    fleet = build(ObsConfig(deadline_ms=deadline_ms))
    fleet.run(max_rounds=rounds, **kw)
    m = fleet.metrics().as_dict()
    return {
        "latency_p50_ms": m["latency"]["p50_ms"],
        "latency_p99_ms": m["latency"]["p99_ms"],
        "latency_max_ms": m["latency"]["max_ms"],
        "deadline_ms": deadline_ms,
        "deadline_miss_total": m["counters"]["deadline_miss_total"],
    }


def bench_fleet(n: int = 64) -> tuple[float, float, int, int, int, int]:
    """Sensor-network message round: a token circles an n-node ring, each
    hop incrementing it — the paper's message-bound distributed regime
    (nodes mostly suspended on ``receive``, micro-slicing).  The same
    programs run

      * device-resident (FleetVM: vmapped slices + on-device mailbox routing,
        state syncs host<->device exactly twice), and
      * through the seed per-slice loop (`reference_round`: one REXAVM per
        node, full state copied host<->device every micro-slice, messages
        routed in Python).

    Returns (fleet steps/s, host-loop steps/s, fleet transfers, host-loop
    transfers, fleet bytes, host-loop bytes).
    Note: on CPU the vmapped decoder serialises compute-bound
    lanes, so the fleet's edge is the eliminated per-slice transfer + host
    service overhead; on accelerators the lanes parallelise as well."""
    cfg = VMConfig(cs_size=2048, steps_per_slice=64)

    def prog(i: int) -> str:
        if i == 0:
            return f"1 {1 % n} send receive swap drop . halt"
        return f"receive swap drop 1+ {(i + 1) % n} send halt"

    def build(kind, obs=None):
        if kind == "fleet":
            fleet = FleetVM(cfg, n=n, obs=obs)
            for i, node in enumerate(fleet.nodes):
                node.launch(node.load(prog(i)))
            return fleet
        nodes = [REXAVM(cfg, backend="jit", seed=1 + i) for i in range(n)]
        for i, node in enumerate(nodes):
            node.launch(node.load(prog(i)))
        return nodes

    # Warm both compiled paths (fleet round kernel + single-VM run_slice).
    warm = build("fleet")
    warm.run(max_rounds=2, steps=cfg.steps_per_slice)
    warm_vm = REXAVM(cfg, backend="jit")
    warm_vm.eval("1 drop", max_slices=2, steps=cfg.steps_per_slice)

    fleet = build("fleet")
    t0 = time.perf_counter()
    res = fleet.run(max_rounds=4 * n)
    dt_fleet = time.perf_counter() - t0
    fleet_steps = int(res.steps.sum())
    fleet_xfer = fleet.h2d + fleet.d2h
    fleet_bytes = fleet.h2d_bytes + fleet.d2h_bytes

    nodes = build("host")
    steps0 = sum(int(vm.state.steps) for vm in nodes)
    t0 = time.perf_counter()
    for _ in range(res.rounds):
        reference_round(nodes, cfg.steps_per_slice)
        if all(int(vm.state.tstatus[0]) in (ST_DONE, ST_HALT, ST_ERR)
               for vm in nodes):
            break
    dt_host = time.perf_counter() - t0
    host_steps = sum(int(vm.state.steps) for vm in nodes) - steps0
    host_xfer = sum(vm.executor.h2d + vm.executor.d2h for vm in nodes)
    host_bytes = sum(
        vm.executor.h2d_bytes + vm.executor.d2h_bytes for vm in nodes
    )
    METRICS["vm_fleet64_network"] = {
        "nodes": n,
        "fleet_steps_per_s": fleet_steps / dt_fleet,
        "host_steps_per_s": host_steps / dt_host,
        "fleet_transfers": fleet_xfer,
        "host_transfers": host_xfer,
        "fleet_bytes": fleet_bytes,
        "host_bytes": host_bytes,
    }
    METRICS["vm_fleet64_network"].update(
        _obs_latency(lambda o: build("fleet", obs=o), rounds=res.rounds)
    )
    return (fleet_steps / dt_fleet, host_steps / dt_host,
            fleet_xfer, host_xfer, fleet_bytes, host_bytes)


def bench_fleet_pallas(n: int = 64, lax_steps_per_s: float | None = None):
    """The same n-node ring as :func:`bench_fleet`, executed by the Pallas
    vmloop kernel (``FleetVM(executor="pallas")``): the fetch/dispatch/stack
    loop runs on chip, bailing to the lax tail on the ``send``/``receive``
    suspensions.  Records steps/s plus the kernel/bail split so
    ``BENCH_vm.json`` tracks how much of the workload the kernel owns.  On
    this CPU container the kernel runs through the Pallas interpreter —
    the row tracks the trajectory, not a TPU speedup."""
    cfg = VMConfig(cs_size=2048, steps_per_slice=64)

    def prog(i: int) -> str:
        if i == 0:
            return f"1 {1 % n} send receive swap drop . halt"
        return f"receive swap drop 1+ {(i + 1) % n} send halt"

    def build() -> FleetVM:
        fleet = FleetVM(cfg, n=n, executor="pallas")
        for i, node in enumerate(fleet.nodes):
            node.launch(node.load(prog(i)))
        return fleet

    warm = build()
    warm.run(max_rounds=2, steps=cfg.steps_per_slice)

    fleet = build()
    t0 = time.perf_counter()
    res = fleet.run(max_rounds=4 * n)
    dt = time.perf_counter() - t0
    steps = int(res.steps.sum())
    stats = fleet.pallas_stats()
    METRICS["vm_fleet64_pallas"] = {
        "nodes": n,
        "steps_per_s": steps / dt,
        "lax_steps_per_s": lax_steps_per_s,
        "kernel_steps": stats["kernel_steps"],
        "fallback_steps": steps - stats["kernel_steps"],
        "bailed_node_rounds": stats["bailed_node_rounds"],
        "rounds": res.rounds,
    }
    return steps / dt, stats, steps


def bench_fleet_pallas_msg(n: int = 64, laps: int = 4, service_every: int = 8):
    """Message-bound fast path: a token makes ``laps`` full circuits of an
    n-node ring, every hop an in-kernel ``send``/``receive`` suspension
    delivered by the collective router inside ``FleetKernels.rounds_aux``
    (``run(service_every=8)`` chunks 8 whole rounds per host probe).
    Records rounds/s, msgs/s and the in-kernel vs lax-tail step split —
    the acceptance gate (CI) holds ``bailed_frac`` under 5%."""
    cfg = VMConfig(cs_size=2048, steps_per_slice=64)

    def prog(i: int) -> str:
        nxt = (i + 1) % n
        if i == 0:
            return (f"1 {nxt} send {laps - 1} 0 do receive swap drop 1+ "
                    f"{nxt} send loop receive swap drop drop halt")
        return f"{laps} 0 do receive swap drop 1+ {nxt} send loop halt"

    def build(obs=None) -> FleetVM:
        fleet = FleetVM(cfg, n=n, executor="pallas", obs=obs)
        for i, node in enumerate(fleet.nodes):
            node.launch(node.load(prog(i)))
        return fleet

    warm = build()
    warm.run(max_rounds=2 * service_every, service_every=service_every)

    fleet = build()
    t0 = time.perf_counter()
    res = fleet.run(max_rounds=16 * n, service_every=service_every)
    dt = time.perf_counter() - t0
    assert res.statuses == ["halt"] * n, res.statuses
    steps = int(res.steps.sum())
    # Every delivered message bumps exactly one mbox_wr cursor.
    msgs = sum(int(np.asarray(vm.state.mbox_wr)) for vm in fleet.nodes)
    stats = fleet.pallas_stats()
    METRICS["vm_fleet64_pallas_msg"] = {
        "nodes": n,
        "rounds": res.rounds,
        "rounds_per_s": res.rounds / dt,
        "msgs": msgs,
        "msgs_per_s": msgs / dt,
        "steps_per_s": steps / dt,
        "service_every": service_every,
        "kernel_steps": stats["kernel_steps"],
        "fallback_steps": stats["fallback_steps"],
        "bailed_frac": stats["bailed_frac"],
        "bailed_node_rounds": stats["bailed_node_rounds"],
        "bail_hist": stats["bail_hist"],
    }
    # Latency columns from a short obs-instrumented slice of the same
    # workload (obs rounds run unchunked, so this is bounded, not a lap).
    METRICS["vm_fleet64_pallas_msg"].update(_obs_latency(build, rounds=24))
    return res.rounds / dt, msgs / dt, stats


def bench_fleet_pallas_ann(n: int = 64):
    """Vector/DSP regime: every node grinds a 4->4 fixed-point ANN layer
    (``vecfold``) plus a ``dotprod`` reduction per
    iteration — the paper's tiny-ML node workload, fully claimed by the
    kernel.  Records steps/s and the in-kernel vs lax-tail split; the CI
    gate holds ``bailed_frac`` under 5%."""
    cfg = VMConfig(cs_size=2048, steps_per_slice=64)
    prog = (
        "array x { 10 20 30 40 } "
        "array w { 1 2 3 4 5 6 7 8 9 10 11 12 13 14 15 16 } "
        "array y { 0 0 0 0 } "
        "0 begin 1+ x w y 0 vecfold x y dotprod drop dup 200 >= until "
        "drop halt"
    )

    def build(obs=None) -> FleetVM:
        fleet = FleetVM(cfg, n=n, executor="pallas", obs=obs)
        for node in fleet.nodes:
            node.launch(node.load(prog))
        return fleet

    warm = build()
    warm.run(max_rounds=2)

    fleet = build()
    t0 = time.perf_counter()
    res = fleet.run(max_rounds=120)
    dt = time.perf_counter() - t0
    assert res.statuses == ["halt"] * n, res.statuses
    steps = int(res.steps.sum())
    stats = fleet.pallas_stats()
    METRICS["vm_fleet64_pallas_ann"] = {
        "nodes": n,
        "rounds": res.rounds,
        "steps_per_s": steps / dt,
        "kernel_steps": stats["kernel_steps"],
        "fallback_steps": stats["fallback_steps"],
        "bailed_frac": stats["bailed_frac"],
        "bailed_node_rounds": stats["bailed_node_rounds"],
        "bail_hist": stats["bail_hist"],
    }
    METRICS["vm_fleet64_pallas_ann"].update(_obs_latency(build, rounds=16))
    return steps / dt, stats


def bench_fleet_obs(n: int = 64):
    """Telemetry-plane overhead: the :func:`bench_fleet_pallas` ring run
    twice — obs off (the plain fused round) vs obs on (phased round with
    on-device retirement counters, mailbox watermarks, and the deterministic
    deadline clock) — plus a short span-traced run that exports a Chrome
    trace-event file.  The CI gate holds ``overhead_frac`` (obs-on steps/s
    cost) under 5% and validates the exported trace."""
    from repro.obs import ObsConfig, validate_chrome_trace

    cfg = VMConfig(cs_size=2048, steps_per_slice=64)

    def prog(i: int) -> str:
        if i == 0:
            return f"1 {1 % n} send receive swap drop . halt"
        return f"receive swap drop 1+ {(i + 1) % n} send halt"

    def build(obs=None) -> FleetVM:
        fleet = FleetVM(cfg, n=n, executor="pallas", obs=obs)
        for i, node in enumerate(fleet.nodes):
            node.launch(node.load(prog(i)))
        return fleet

    # The gated config is the leave-on-in-production plane: on-device
    # counters + the deterministic virtual-clock deadline, no per-round
    # host sync (time_rounds=False keeps the round chain async).
    obs_cfg = ObsConfig(deadline_ms=50, time_rounds=False)
    # Warm both compiled paths (plain round kernel + counting kernel).
    build().run(max_rounds=2)
    build(obs_cfg).run(max_rounds=2)

    fleet_off = build()
    t0 = time.perf_counter()
    res_off = fleet_off.run(max_rounds=4 * n)
    dt_off = time.perf_counter() - t0
    sps_off = int(res_off.steps.sum()) / dt_off

    fleet_on = build(obs_cfg)
    t0 = time.perf_counter()
    res_on = fleet_on.run(max_rounds=4 * n)
    dt_on = time.perf_counter() - t0
    sps_on = int(res_on.steps.sum()) / dt_on
    overhead = 1.0 - sps_on / sps_off
    m = fleet_on.metrics().as_dict()

    # Wall-latency percentiles come from a separate timed run — per-round
    # wall timing blocks the async chain by construction, so it is
    # reported but not part of the overhead gate.
    fleet_lat = build(ObsConfig(deadline_ms=50))
    fleet_lat.run(max_rounds=4 * n)
    m_lat = fleet_lat.metrics().as_dict()
    lat = m_lat["latency"]
    # Same workload/executor as the vm_fleet64_pallas row — attach its
    # latency / deadline columns there instead of rerunning it.
    if "vm_fleet64_pallas" in METRICS:
        METRICS["vm_fleet64_pallas"].update({
            "latency_p50_ms": lat["p50_ms"],
            "latency_p99_ms": lat["p99_ms"],
            "latency_max_ms": lat["max_ms"],
            "deadline_ms": m_lat["counters"]["deadline_ms"],
            "deadline_miss_total": m_lat["counters"]["deadline_miss_total"],
        })

    # Short span-traced run: one Chrome trace-event file for the artifact
    # (tracing syncs every phase, so it gets its own few rounds, untimed).
    tr_fleet = build(ObsConfig(trace=True, deadline_ms=50))
    tr_fleet.run(max_rounds=8)
    trace_path = os.path.join(
        os.environ.get("REPRO_TRACE_DIR", "."), "TRACE_vm_fleet64_obs.json"
    )
    payload = tr_fleet.export_trace(trace_path)
    spans = validate_chrome_trace(payload)

    METRICS["vm_fleet64_obs_overhead"] = {
        "nodes": n,
        "steps_per_s_off": sps_off,
        "steps_per_s_on": sps_on,
        "overhead_frac": overhead,
        "rounds_observed": m["counters"]["rounds_observed"],
        "instructions": m["counters"]["instructions"],
        "mbox_high": m["counters"]["mbox_high"],
        "mbox_drops": m["counters"]["mbox_drops"],
        "io_susp": m["counters"]["io_susp"],
        "deadline_ms": m["counters"]["deadline_ms"],
        "deadline_miss_total": m["counters"]["deadline_miss_total"],
        "latency_p50_ms": lat["p50_ms"],
        "latency_p99_ms": lat["p99_ms"],
        "latency_max_ms": lat["max_ms"],
        "latency_mean_ms": lat["mean_ms"],
        "trace_file": trace_path,
        "trace_spans": spans,
    }
    return sps_on, sps_off, overhead, m


def bench_fleet_trace(n: int = 64, network_steps_per_s: float | None = None):
    """Hot single-program fleet: every node grinds the same compute loop
    (``BENCH_PROG``), the trace-JIT's best case — one program group, one
    recorded trace, the whole-fleet fast path, no dispatch.  The same
    workload also runs on the generic vmapped interpreter
    (``executor="batched"``), whose vmapped ``lax.switch`` evaluates every
    opcode branch per step, so the row captures the specialized-vs-generic
    steps/s split plus the guard-exit count and specialized fraction."""
    cfg = VMConfig(cs_size=2048, steps_per_slice=64)
    # A shortened BENCH_PROG: the generic vmapped interpreter grinds this
    # ~2 orders of magnitude slower than the specialized path, so the
    # comparison leg budgets the row's wall time.
    prog = ": work 0 begin 1+ dup 500 >= until drop ; work work halt"

    def build(executor: str, obs=None) -> FleetVM:
        fleet = FleetVM(cfg, n=n, executor=executor, obs=obs)
        for node in fleet.nodes:
            node.launch(node.load(prog))
        return fleet

    results = {}
    stats = None
    warm_stats = None
    rounds = 0
    for executor in ("batched", "trace"):
        warm = build(executor)          # compile / record+compile once
        warm.run(max_rounds=2, steps=cfg.steps_per_slice)
        fleet = build(executor)
        t0 = time.perf_counter()
        res = fleet.run(max_rounds=1200)
        dt = time.perf_counter() - t0
        results[executor] = int(res.steps.sum()) / dt
        if executor == "trace":
            # The timed fleet hits the warm fleet's shared trace cache
            # for the hot entries (late preemption points still record),
            # so the workload's one-time record/compile cost is the sum
            # of both fleets' deltas; guards and specialized fraction
            # come from the timed run alone.
            warm_stats = warm.trace_stats()
            stats = fleet.trace_stats()
            rounds = res.rounds
    METRICS["vm_fleet64_trace"] = {
        "nodes": n,
        "steps_per_s": results["trace"],
        "generic_steps_per_s": results["batched"],
        "network_steps_per_s": network_steps_per_s,
        "specialized_frac": stats["specialized_frac"],
        "guard_exits": stats["guard_exits"],
        "traces_recorded": warm_stats["traces_recorded"]
        + stats["traces_recorded"],
        "traces_compiled": warm_stats["traces_compiled"]
        + stats["traces_compiled"],
        "rounds": rounds,
    }
    METRICS["vm_fleet64_trace"].update(
        _obs_latency(lambda o: build("trace", obs=o), rounds=120)
    )
    stats = dict(
        stats,
        traces_recorded=METRICS["vm_fleet64_trace"]["traces_recorded"],
        traces_compiled=METRICS["vm_fleet64_trace"]["traces_compiled"],
    )
    return results["trace"], results["batched"], stats


def bench_fleet_exec(n: int = 64):
    """Executive fleet: every node time-slices a boot daemon plus two
    spawned tasks (a syscall-chatty worker and a compute job) through the
    preemptive priority scheduler, while a fleet-shared ``tick`` syscall is
    serviced by the vectorized SVC plane — one batched handler invocation
    per syscall wave instead of one Python callback per node.  The row
    reports tasks/s and context switches/s plus the batched-vs-per-node
    syscall service comparison (same movement, same bytes; only the host
    dispatch differs), which is the CI gate's proof that the vector plane
    replaced O(nodes) FIOS dispatch."""
    from repro.exec import Executive, ExecutiveConfig

    cfg = VMConfig(cs_size=2048, steps_per_slice=64)
    ecfg = ExecutiveConfig(quantum=16, slices=4)
    WAVES = 4
    MAIN = ": d 0 begin 1+ dup 300 >= until drop ; d halt"
    WORKER = f"0 {WAVES} 0 do tick loop drop"
    COMPUTE = ": c 0 begin 1+ dup 200 >= until drop ;\nc"

    def handler_vec(rows, svc):
        return [r.args[0] + 1 for r in rows]

    def handler_scalar(v):
        return v + 1

    def build(vectorized: bool) -> tuple[FleetVM, object]:
        fleet = FleetVM(cfg, n=n, executor="batched", executive=ecfg)
        ex = Executive(fleet)
        fn = handler_vec if vectorized else handler_scalar
        for i, node in enumerate(fleet.nodes):
            node.svc_add("tick", fn, args=1, ret=1, vectorized=vectorized)
            node.launch(node.load(MAIN))
            ex.spawn(i, WORKER, prio=1)
            ex.spawn(i, COMPUTE, prio=0)
        return fleet, ex

    build(True)[0].run(max_rounds=4)            # warm the compiled round
    legs = {}
    for vectorized in (True, False):
        fleet, ex = build(vectorized)
        t0 = time.perf_counter()
        res = fleet.run(max_rounds=600)
        dt = time.perf_counter() - t0
        e = fleet.executive_stats()
        legs[vectorized] = (fleet, res, e, dt)
    fleet, res, e, dt = legs[True]
    _, _, e_s, dt_s = legs[False]
    tasks = n + e["spawns_admitted"]            # boot daemons + spawned
    assert e["svc_batches"] > 0 and e["svc_scalar_calls"] == 0
    assert e_s["svc_scalar_calls"] == e_s["syscalls"] > 0
    METRICS["vm_fleet64_exec"] = {
        "nodes": n,
        "tasks": tasks,
        "tasks_per_s": tasks / dt,
        "task_switches": e["task_switches"],
        "switches_per_s": e["task_switches"] / dt,
        "preemptions": e["preemptions"],
        "steps_per_s": int(res.steps.sum()) / dt,
        "rounds": res.rounds,
        "syscalls": e["syscalls"],
        "svc_batches": e["svc_batches"],
        "svc_services": fleet.io_service.services,
        "scalar_calls_baseline": e_s["svc_scalar_calls"],
        "vector_us_per_syscall": dt * 1e6 / max(e["syscalls"], 1),
        "scalar_us_per_syscall": dt_s * 1e6 / max(e_s["syscalls"], 1),
        "quantum": ecfg.quantum,
        "slices_per_round": ecfg.slices,
    }
    return METRICS["vm_fleet64_exec"]


def bench_fleet_verified(n: int = 64):
    """The Auditor's fast path (PR 10): a 64-node compute-bound fleet under
    ``executor="auto"`` — every program statically VERIFIED, so the Pallas
    kernel compiles with the per-step stack checks elided — vs the same
    workload on the always-checked kernel.  Records the checked/elided
    steps/s pair, the auto-resolved backend split, and the verifier's WCET
    bound (the row is the acceptance evidence that verification pays for
    itself at run time)."""
    cfg = VMConfig(cs_size=2048, steps_per_slice=256)
    # Long enough that the kernel step loop dominates host round overhead;
    # bounded (counted do-loop), bail-free, and statically VERIFIED.
    prog = ": work 0 2000 0 do 7 + 3 - loop drop ; work halt"

    def build(executor: str) -> FleetVM:
        fleet = FleetVM(cfg, n=n, executor=executor)
        for node in fleet.nodes:
            node.launch(node.load(prog))
        return fleet

    def timed(executor: str) -> tuple[float, FleetVM]:
        build(executor).run(max_rounds=2)            # warm the kernel build
        fleet = build(executor)
        fleet.start()   # static analysis + kernel resolution: admission-time
        t0 = time.perf_counter()
        res = fleet.run(max_rounds=64)
        dt = time.perf_counter() - t0
        return int(res.steps.sum()) / dt, fleet

    checked_sps, _ = timed("pallas")
    elided_sps, fleet = timed("auto")
    a = fleet.analysis_stats()
    assert a["executor"] == "pallas" and a["elide_checks"], a
    METRICS["vm_fleet64_verified"] = {
        "nodes": n,
        "steps_per_s": elided_sps,
        "checked_steps_per_s": checked_sps,
        "speedup": elided_sps / checked_sps,
        "executor": a["executor"],
        "elide_checks": a["elide_checks"],
        "verdicts": a["verdicts"],
        "predicted_bail_words": a["predicted_bail_words"],
        "wcet_instrs": a["wcet"][0],
    }
    return elided_sps, checked_sps, a


def bench_fleet_io(n: int = 8, n_suspended: int = 2) -> tuple[int, int]:
    """The partial-IO win: ``n_suspended`` of ``n`` nodes block on a FIOS
    call while the rest compute.  Returns IO-service bytes for the
    partial-state path vs PR 1's full-state sync on the same workload."""
    cfg = VMConfig(cs_size=2048, steps_per_slice=64)

    def build(io_mode: str) -> FleetVM:
        fleet = FleetVM(cfg, n=n, io_mode=io_mode)
        for i, node in enumerate(fleet.nodes):
            if i < n_suspended:
                node.dios_add("ready", np.array([0], np.int32))
                node.fios_add(
                    "ping", lambda node=node: node.dios_write("ready", [1])
                )
                node.launch(node.load("ping 1000 1 ready await drop 5 . halt"))
            else:
                node.launch(node.load("0 50 0 do 1+ loop . halt"))
        return fleet

    partial = build("partial")
    partial.run(max_rounds=60)
    partial_bytes = partial.io_d2h_bytes + partial.io_h2d_bytes
    full = build("full")
    base_h2d, base_d2h = full.h2d_bytes, full.d2h_bytes
    full.run(max_rounds=60)
    # Full-sync IO bytes = everything beyond the one start + one final sync.
    from repro.core.vm.vmstate import state_nbytes
    full_state = state_nbytes(full.nodes[0].state) * n
    full_bytes = (full.h2d_bytes + full.d2h_bytes
                  - base_h2d - base_d2h - 2 * full_state)
    METRICS["vm_fleet_io_partial"] = {
        "nodes": n,
        "suspended": n_suspended,
        "partial_io_bytes": partial_bytes,
        "full_sync_io_bytes": full_bytes,
        "io_services": partial.io_service.services,
    }
    return partial_bytes, full_bytes


def mcps(lookup: str = "pht") -> float:
    comp = Compiler(lookup=lookup)
    frames = FrameManager(1 << 20)
    frames.allocate(1)
    cs = np.zeros(1 << 20, np.int32)
    prog = ": f dup * over + swap drop ; " + "1 2 f drop drop " * 200
    t0 = time.perf_counter()
    n = 0
    reps = 20
    for _ in range(reps):
        before = comp.words_compiled
        comp.compile_frame(prog, cs, frames)
        n += comp.words_compiled - before
    dt = time.perf_counter() - t0
    return n / dt / 1e6


def run() -> list[tuple[str, float, str]]:
    rows = []
    m_o, _ = mwps("oracle")
    rows.append(("vm_mwps_oracle", 1.0 / m_o, f"{m_o:.3f} MWPS (python oracle)"))
    m_j, xfer_j = mwps("jit")
    rows.append(("vm_mwps_jit", 1.0 / m_j,
                 f"{m_j:.3f} MWPS (XLA single VM; {xfer_j} host<->device "
                 f"transfers in the per-slice loop)"))
    agg, single = mwps_ensemble(32)
    rows.append(("vm_mwps_ensemble32", 1.0 / agg,
                 f"{agg:.3f} MWPS aggregate over 32 lock-stepped VMs "
                 f"({single:.3f} per instance)"))
    f_sps, h_sps, f_xfer, h_xfer, f_bytes, h_bytes = bench_fleet(64)
    mn = METRICS["vm_fleet64_network"]
    rows.append(("vm_fleet64_network", 1e6 / f_sps,
                 f"{f_sps:.0f} steps/s device-resident 64-node network "
                 f"({f_xfer} full-state transfers / {f_bytes} B) vs "
                 f"{h_sps:.0f} steps/s ({h_xfer} transfers / {h_bytes} B) "
                 f"seed per-slice host loop; round latency p50 "
                 f"{mn['latency_p50_ms']:.2f} ms, "
                 f"{mn['deadline_miss_total']} deadline misses"))
    pk_sps, pk_stats, pk_steps = bench_fleet_pallas(64, lax_steps_per_s=f_sps)
    rows.append(("vm_fleet64_pallas", 1e6 / pk_sps,
                 f"{pk_sps:.0f} steps/s pallas-vmloop 64-node network "
                 f"({pk_stats['kernel_steps']} in-kernel steps / "
                 f"{pk_steps - pk_stats['kernel_steps']} lax-tail steps / "
                 f"{pk_stats['bailed_node_rounds']} bail-outs) vs "
                 f"{f_sps:.0f} steps/s lax interpreter fleet"))
    m_rps, m_mps, m_stats = bench_fleet_pallas_msg(64)
    mm = METRICS["vm_fleet64_pallas_msg"]
    rows.append(("vm_fleet64_pallas_msg", 1.0 / m_mps,
                 f"{m_mps:.0f} msgs/s, {m_rps:.0f} rounds/s message-bound "
                 f"64-node ring (service_every=8 fused rounds; "
                 f"{mm['kernel_steps']} in-kernel / {mm['fallback_steps']} "
                 f"lax-tail steps, bailed_frac={mm['bailed_frac']:.4f})"))
    a_sps, a_stats = bench_fleet_pallas_ann(64)
    ma = METRICS["vm_fleet64_pallas_ann"]
    rows.append(("vm_fleet64_pallas_ann", 1e6 / a_sps,
                 f"{a_sps:.0f} steps/s 64-node vecfold/dotprod ANN fleet "
                 f"({ma['kernel_steps']} in-kernel / {ma['fallback_steps']} "
                 f"lax-tail steps, bailed_frac={ma['bailed_frac']:.4f})"))
    o_on, o_off, o_frac, o_m = bench_fleet_obs(64)
    mo = METRICS["vm_fleet64_obs_overhead"]
    rows.append(("vm_fleet64_obs_overhead", 1e6 / o_on,
                 f"{o_on:.0f} steps/s obs-on vs {o_off:.0f} steps/s obs-off "
                 f"64-node pallas ring (overhead {o_frac:.2%}; round latency "
                 f"p50 {mo['latency_p50_ms']:.2f} ms / p99 "
                 f"{mo['latency_p99_ms']:.2f} ms, "
                 f"{mo['deadline_miss_total']} deadline misses @ "
                 f"{mo['deadline_ms']} ms, mbox high {mo['mbox_high']}, "
                 f"{mo['trace_spans']} trace spans exported)"))
    t_sps, g_sps, t_stats = bench_fleet_trace(64, network_steps_per_s=f_sps)
    rows.append(("vm_fleet64_trace", 1e6 / t_sps,
                 f"{t_sps:.0f} steps/s trace-specialized hot 64-node fleet "
                 f"vs {g_sps:.0f} steps/s generic vmapped interpreter on the "
                 f"same workload ({t_stats['specialized_frac']:.1%} "
                 f"specialized, {t_stats['guard_exits']} guard exits, "
                 f"{t_stats['traces_compiled']} traces compiled)"))
    v_sps, vc_sps, v_a = bench_fleet_verified(64)
    mv = METRICS["vm_fleet64_verified"]
    rows.append(("vm_fleet64_verified", 1e6 / v_sps,
                 f"{v_sps:.0f} steps/s checks-elided (auto -> "
                 f"{v_a['executor']}, all {v_a['verdicts']['verified']} "
                 f"programs statically VERIFIED, wcet "
                 f"{mv['wcet_instrs']} instrs) vs {vc_sps:.0f} steps/s "
                 f"always-checked pallas kernel "
                 f"({mv['speedup']:.2f}x) on a 64-node verified fleet"))
    me = bench_fleet_exec(64)
    rows.append(("vm_fleet64_exec", 1.0 / me["tasks_per_s"],
                 f"{me['tasks_per_s']:.0f} tasks/s, "
                 f"{me['switches_per_s']:.0f} context switches/s on the "
                 f"64-node Executive fleet ({me['tasks']} tasks, "
                 f"{me['preemptions']} preemptions; {me['syscalls']} "
                 f"syscalls in {me['svc_batches']} vectorized batches vs "
                 f"{me['scalar_calls_baseline']} per-node callbacks: "
                 f"{me['vector_us_per_syscall']:.0f} vs "
                 f"{me['scalar_us_per_syscall']:.0f} us/syscall)"))
    p_bytes, fs_bytes = bench_fleet_io(8, 2)
    rows.append(("vm_fleet_io_partial", float(p_bytes),
                 f"{p_bytes} B partial-state IO service vs {fs_bytes} B "
                 f"full-state sync (2 of 8 nodes suspended)"))
    c_pht = mcps("pht")
    rows.append(("compiler_mcps_pht", 1.0 / c_pht, f"{c_pht:.3f} MCPS (perfect hash)"))
    c_lst = mcps("lst")
    rows.append(("compiler_mcps_lst", 1.0 / c_lst, f"{c_lst:.3f} MCPS (linear search table)"))
    METRICS["vm_mwps"] = {"oracle": m_o, "jit": m_j, "jit_transfers": xfer_j,
                          "ensemble32_aggregate": agg}
    METRICS["compiler_mcps"] = {"pht": c_pht, "lst": c_lst}
    return rows
