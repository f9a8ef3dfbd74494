"""Telemetry-plane suite (the fleet's one observability namespace).

The obs plane (``repro.obs``) must be *measurement*, not behaviour: the
counters are defined by the operational semantics (every retired
instruction bins once, the router's drop/watermark rules are
``reference_round``'s), so they must come out byte-identical from every
engine.  This suite pins:

  * the full-ISA retirement-histogram sweep — every opcode program from
    tests/test_vm_pallas.py through all four single-VM executors
    (jit / oracle / pallas-interpret / trace) with obs on, asserting the
    per-opcode ``op_hist`` deltas are identical and total exactly the
    retired steps;
  * fleet-level ``FleetVM.metrics()`` — schema-stable key structure and
    counter parity across all four fleet executors (batched / oracle /
    pallas / trace) on a messaging ring;
  * mailbox telemetry (``mbox_drops`` / ``mbox_high``) against the
    host-routed ``reference_round`` with an ``obs`` dict — the drop and
    watermark ground truth;
  * deterministic deadline misses — the virtual-clock deadline
    (``ObsConfig.deadline_ms``) produces the *same* per-node miss vector
    under every backend (it is derived from retired steps, not wall
    time);
  * round-phase tracing — ``export_trace()`` emits valid Chrome
    trace-event JSON with one span per phase per observed round;
  * the serve monitor's ``metrics()`` passthrough and the obs-off
    zero-cost contract (same schema, zero device outputs).
"""

import numpy as np
import pytest

import test_vm_pallas as T

from repro.core.vm import FleetVM, REXAVM, reference_round
from repro.core.vm.executor import make_executor
from repro.core.vm.vmstate import VMState
from repro.obs import (
    DeadlineMonitor,
    FleetMetrics,
    ObsConfig,
    RoundTracer,
    export_chrome_trace,
    validate_chrome_trace,
)
from repro.obs.metrics import bin_names, n_bins, normalize_obs

CFG = T.CFG

SINGLE_BACKENDS = ("jit", "oracle", "pallas", "trace")
FLEET_EXECUTORS = ("batched", "oracle", "pallas", "trace")


# ---------------------------------------------------------------------------
# Full-ISA sweep: identical per-opcode retirement counts on all four engines
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def obs_engines():
    """One obs-counting executor of each kind (compile once, like the
    pallas sweep's ``engines`` fixture — same CFG, shared jit caches)."""
    return {b: make_executor(b, CFG, obs=True) for b in SINGLE_BACKENDS}


@pytest.mark.parametrize(
    "word,prog,pure", T.SWEEP,
    ids=[f"{i:03d}-{w}" for i, (w, _, _) in enumerate(T.SWEEP)],
)
def test_op_hist_parity_full_isa(word, prog, pure, obs_engines):
    """Acceptance: per-opcode retired counts identical across the four
    executors on every sweep program, and the histogram total is exactly
    the number of retired steps (nothing counted twice, nothing missed —
    including the invalid-pc trap and bail-out tails)."""
    st0 = T._initial_state(prog)
    hists = {}
    final_steps = {}
    for kind, ex in obs_engines.items():
        h0 = ex.op_hist.copy()
        st = T._copy(st0)
        for _ in range(3):
            st = ex.run_slice(st, CFG.steps_per_slice)
        hists[kind] = ex.op_hist - h0
        final_steps[kind] = int(st.steps)
    base = hists["oracle"]
    retired = final_steps["oracle"] - int(st0.steps)
    assert int(base.sum()) == retired, (word, base.sum(), retired)
    assert retired > 0
    names = bin_names(obs_engines["oracle"].oracle.isa)
    for kind in ("jit", "pallas", "trace"):
        if not np.array_equal(hists[kind], base):
            diff = {
                names[i]: (int(hists[kind][i]), int(base[i]))
                for i in np.flatnonzero(hists[kind] != base)
            }
            raise AssertionError(
                f"{word}: {kind} op_hist diverged from oracle: {diff}"
            )


# ---------------------------------------------------------------------------
# Fleet metrics: schema + counter parity across the four fleet executors
# ---------------------------------------------------------------------------

def _ring_progs(n: int) -> list[str]:
    return [T.ring_program(i, n) for i in range(n)]


def _obs_fleet(executor: str, progs: list[str], obs) -> FleetVM:
    fleet = FleetVM(CFG, n=len(progs), executor=executor, obs=obs)
    for node, prog in zip(fleet.nodes, progs):
        node.launch(node.load(prog))
    return fleet


@pytest.fixture(scope="module")
def ring_metrics():
    """The 4-node ring run to completion under each fleet executor with
    the full obs plane on; shared by the parity/schema/trace tests."""
    out = {}
    for executor in FLEET_EXECUTORS:
        fleet = _obs_fleet(
            executor, _ring_progs(4),
            ObsConfig(trace=True, deadline_ms=1, deadline_wall_ms=1e9),
        )
        res = fleet.run(max_rounds=16)
        out[executor] = (fleet, res, fleet.metrics())
    return out


def test_fleet_counter_parity(ring_metrics):
    """op_retired / mailbox / io / deadline counters are semantic, so the
    four engines must agree exactly.  (``deopts`` is engine-specific —
    pallas bail-outs vs trace guard exits — and excluded.)"""
    base = ring_metrics["batched"][2].as_dict()["counters"]
    assert base["instructions"] > 0
    assert base["io_susp"] != 0 or base["mbox_high"] > 0
    for executor in ("oracle", "pallas", "trace"):
        c = ring_metrics[executor][2].as_dict()["counters"]
        for key in ("op_retired", "instructions", "mbox_high", "mbox_drops",
                    "io_susp", "deadline_miss", "deadline_miss_total",
                    "rounds_observed"):
            assert c[key] == base[key], (executor, key, c[key], base[key])


def test_fleet_metrics_schema_stable(ring_metrics):
    """metrics() presents the same key structure under every executor."""
    dicts = {ex: m.as_dict() for ex, (_, _, m) in ring_metrics.items()}
    base = dicts["batched"]
    for ex, d in dicts.items():
        assert set(d) == set(base), ex
        for section in ("counters", "latency", "pallas", "trace",
                        "transfers", "executive"):
            assert set(d[section]) == set(base[section]), (ex, section)
        assert set(d["counters"]["op_retired"]) == set(
            base["counters"]["op_retired"]
        ), ex
        assert isinstance(ring_metrics[ex][2], FleetMetrics)


def test_stats_schema_parity_across_executors(ring_metrics):
    """The satellite contract on the pre-existing stats dicts: the full
    pallas_stats()/trace_stats() key set (zeroed) under every backend,
    and transfer_stats() self-describing with executor + rounds."""
    fleets = {ex: f for ex, (f, _, _) in ring_metrics.items()}
    p_keys = set(fleets["pallas"].pallas_stats())
    t_keys = set(fleets["trace"].trace_stats())
    x_keys = set(fleets["batched"].transfer_stats())
    e_keys = set(fleets["batched"].executive_stats())
    for ex, fleet in fleets.items():
        assert set(fleet.pallas_stats()) == p_keys, ex
        assert set(fleet.trace_stats()) == t_keys, ex
        assert set(fleet.transfer_stats()) == x_keys, ex
        assert set(fleet.executive_stats()) == e_keys, ex
        assert fleet.transfer_stats()["executor"] == ex
        assert fleet.transfer_stats()["rounds"] > 0
        if ex != "pallas":
            assert fleet.pallas_stats()["kernel_steps"] == 0
        if ex != "trace":
            assert fleet.trace_stats()["traces_compiled"] == 0


def test_executive_counters_zeroed_without_executive(ring_metrics):
    """Satellite contract: the task/syscall counter keys exist and are
    zeroed under every backend when no Executive is configured — a
    schema-stable namespace, not a conditional one."""
    for ex, (fleet, _, m) in ring_metrics.items():
        e = fleet.executive_stats()
        assert e["enabled"] is False, ex
        for key in ("exec_slices", "task_switches", "preemptions",
                    "spawns_admitted", "spawns_rejected",
                    "task_deadline_misses", "tasks_missed", "syscalls",
                    "svc_batches", "svc_scalar_calls", "svc_posts",
                    "svc_post_drops"):
            assert e[key] == 0, (ex, key, e[key])
        d = m.as_dict()
        assert d["executive"]["enabled"] is False
        assert d["pallas"]["exec_slices"] == 0, ex
        assert d["trace"]["exec_slices"] == 0, ex
        assert d["transfers"]["io_syscalls"] == 0, ex
        assert d["transfers"]["io_svc_batches"] == 0, ex


# ---------------------------------------------------------------------------
# Mailbox telemetry vs the host-routed reference
# ---------------------------------------------------------------------------

_DROP_PROGS = [
    "7 99 send 8 1 send halt",       # one dropped send, one delivered
    "receive swap drop . halt",
    "1 2 + halt",
]


def test_mailbox_drops_and_watermark_match_reference():
    """``mbox_drops``/``mbox_high`` equal the counts ``reference_round``
    accumulates into its ``obs`` dict on the same programs — the router
    telemetry is pinned to the operational spec, not to an engine."""
    ref = [REXAVM(CFG) for _ in _DROP_PROGS]
    for vm, prog in zip(ref, _DROP_PROGS):
        vm.launch(vm.load(prog))
    obs_ref: dict = {}
    for _ in range(6):
        reference_round(ref, CFG.steps_per_slice, obs=obs_ref)
    assert obs_ref["drops"] == 1
    assert obs_ref["depth_peak"] >= 1

    for executor in ("batched", "pallas"):
        fleet = _obs_fleet(
            executor, _DROP_PROGS, ObsConfig(time_rounds=False)
        )
        fleet.run(max_rounds=6)
        c = fleet.metrics().as_dict()["counters"]
        assert c["mbox_drops"] == obs_ref["drops"], executor
        assert c["mbox_high"] == obs_ref["depth_peak"], executor


# ---------------------------------------------------------------------------
# Deterministic deadline misses
# ---------------------------------------------------------------------------

def test_deadline_misses_deterministic_across_executors():
    """The deadline clock is virtual (retired steps x us_per_instr), so a
    1 ms deadline with 256-step slices must produce the *identical*
    per-node miss vector under every backend — busy nodes miss, the
    already-halted one does not."""
    progs = [
        "0 begin 1+ dup 2000 >= until drop halt",
        "0 begin 1+ dup 1500 >= until drop halt",
        "1 2 + halt",                # finishes in round 1, then idles
    ]
    miss = {}
    for executor in FLEET_EXECUTORS:
        fleet = _obs_fleet(
            executor, progs, ObsConfig(deadline_ms=1, time_rounds=False)
        )
        fleet.run(max_rounds=12, steps=256)
        c = fleet.metrics().as_dict()["counters"]
        assert c["deadline_ms"] == 1
        miss[executor] = c["deadline_miss"]
        assert c["deadline_miss_total"] == sum(c["deadline_miss"])
    base = miss["batched"]
    assert sum(base) > 0, base
    assert base[2] < base[0], base    # idle node misses less than busy
    for executor in ("oracle", "pallas", "trace"):
        assert miss[executor] == base, (executor, miss[executor], base)


# ---------------------------------------------------------------------------
# Round-phase tracing
# ---------------------------------------------------------------------------

def test_trace_export_one_span_per_phase_per_round(ring_metrics, tmp_path):
    """export_trace() emits valid Chrome trace-event JSON with exactly one
    schedule/execute/router/warp span per observed round, each inside that
    round's dispatch span, and the fleet's host spans: one start (with its
    restack), run, sync and decode per read-out, one probe per probe."""
    for executor, (fleet, res, m) in ring_metrics.items():
        path = tmp_path / f"trace_{executor}.json"
        payload = fleet.export_trace(str(path))
        n_spans = validate_chrome_trace(payload)
        assert validate_chrome_trace(str(path)) == n_spans
        rounds = m.as_dict()["counters"]["rounds_observed"]
        by_name: dict = {}
        by_id = {}
        for ev in payload["traceEvents"]:
            if ev.get("ph") == "X":
                by_name[ev["name"]] = by_name.get(ev["name"], 0) + 1
                by_id[ev["args"]["id"]] = ev
                assert ev["dur"] >= 0
                assert "round" in ev["args"]
                assert ev["args"]["readout"] == 1
        for phase in ("schedule", "execute", "router", "warp", "dispatch"):
            assert by_name.get(phase, 0) == rounds, (executor, phase, by_name)
        for name in ("start", "restack", "run", "sync", "decode"):
            assert by_name.get(name, 0) == 1, (executor, name, by_name)
        assert by_name["probe"] == fleet.transfer_stats()["probes"]
        for ev in by_id.values():
            parent = by_id.get(ev["args"]["parent"], {}).get("name")
            if ev["name"] in ("schedule", "execute", "router", "warp"):
                assert parent == "dispatch", (executor, ev)
            elif ev["name"] in ("dispatch", "probe", "sync", "decode"):
                assert parent == "run", (executor, ev)


def test_validate_chrome_trace_rejects_garbage(tmp_path):
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [{"ph": "X", "name": "x"}]})
    with pytest.raises(ValueError):
        validate_chrome_trace({"nope": []})
    tracer = RoundTracer(ring=4, enabled=True)
    with tracer.span("schedule"):
        pass
    payload = export_chrome_trace(tracer, str(tmp_path / "t.json"))
    assert validate_chrome_trace(payload) == 1


def test_tracer_ring_bounds_memory():
    tracer = RoundTracer(ring=8, enabled=True)
    for r in range(50):
        with tracer.span("execute"):
            pass
        tracer.tick()
    events = tracer.snapshot()
    assert len(events) == 8
    assert events[-1]["round"] == 49


# ---------------------------------------------------------------------------
# Deadline monitor (host wall-clock histogram)
# ---------------------------------------------------------------------------

def test_deadline_monitor_histogram():
    mon = DeadlineMonitor(deadline_wall_ms=1.0)
    for dt in (0.1, 0.5, 2.0, 8.0):
        mon.record(dt)
    snap = mon.snapshot()
    assert snap["rounds_timed"] == 4
    assert snap["deadline_misses"] == 2
    assert snap["max_ms"] == 8.0
    assert snap["p50_ms"] <= snap["p99_ms"] <= 10.1
    assert len(snap["counts"]) == len(snap["buckets_ms"]) + 1


# ---------------------------------------------------------------------------
# Obs off: same schema, zero device outputs; serve-monitor passthrough
# ---------------------------------------------------------------------------

def test_obs_off_schema_and_zero_cost(ring_metrics):
    fleet = _obs_fleet("batched", _ring_progs(4), obs=None)
    res = fleet.run(max_rounds=16)
    m = fleet.metrics().as_dict()
    base = ring_metrics["batched"][2].as_dict()
    assert set(m) == set(base)
    assert set(m["counters"]) == set(base["counters"])
    assert m["counters"]["instructions"] == 0
    assert m["counters"]["rounds_observed"] == 0
    assert m["rounds"] == res.rounds
    payload = fleet.export_trace()
    assert validate_chrome_trace(payload) == 0
    assert normalize_obs(None) is None and normalize_obs(False) is None
    assert normalize_obs(True) == ObsConfig()
    with pytest.raises(TypeError):
        normalize_obs(42)


def test_serve_monitor_metrics_passthrough():
    from repro.serve.engine import ServeStats
    from repro.serve.vmhook import FleetServeMonitor

    monitor = FleetServeMonitor(n=2, obs=True)
    for step in range(1, 3):
        monitor(ServeStats(steps=step, decode_tokens=4 * step))
    m = monitor.metrics()
    assert isinstance(m, FleetMetrics)
    d = m.as_dict()
    assert d["counters"]["instructions"] > 0
    assert d["counters"]["rounds_observed"] > 0
    assert monitor.reports()[0], "measuring job reported nothing"
    # Off by default: same schema, zeroed counters.
    plain = FleetServeMonitor(n=1)
    d0 = plain.metrics().as_dict()
    assert set(d0) == set(d)
    assert d0["counters"]["instructions"] == 0
