"""The fleet's own spans and device phase scopes.

``FleetVM`` annotates its host work as ``fleet.*`` spans on every path,
``obs`` or not, so a ``jax.profiler`` trace shows where ``start()`` and
``run()`` spend their time; the round program names its phases with
``jax.named_scope``.  This suite pins, on the CPU:

  * with ``obs=None`` and a profiler session: the spans, their nesting,
    read-out ids and counts, and that the session changes no probe or
    transfer count;
  * with ``ObsConfig(trace=True)``: the same spans in the ring, with
    parents and on the profiler's clock;
  * the lowered round program: every phase scope, and the kernel still
    named ``vmloop``.

The Auditor's plan is pinned to the oracle engine where a test runs a
fleet, so only the router and the warp compile.
"""

import re

import jax
import numpy as np
import pytest

from repro.analysis import feasibility
from repro.config import VMConfig
from repro.core.vm import FleetVM
from repro.obs import ObsConfig

CFG = VMConfig(cs_size=2048, steps_per_slice=64, mbox_size=4)
# Two distinct programs, long enough that every read-out runs all its
# rounds.
SUM1 = ": w 0 1000 0 do i + loop . ; w halt"
SUM2 = ": w 0 2000 0 do i + loop . ; w halt"
PROGS = [SUM1, SUM1, SUM2, SUM1]


@pytest.fixture
def oracle_plan(monkeypatch):
    """Let the Auditor verify as usual but hand the oracle engine the fleet."""
    real = feasibility.plan_backend

    def plan(reports, branch_sets=None):
        p = real(reports, branch_sets)
        return feasibility.BackendPlan(
            "oracle", False, p.reasons, p.bail_words, p.branch_sets
        )

    monkeypatch.setattr(feasibility, "plan_backend", plan)


def make_fleet(obs=None) -> FleetVM:
    fleet = FleetVM(CFG, n=len(PROGS), executor="auto", obs=obs)
    for node, prog in zip(fleet.nodes, PROGS):
        node.launch(node.load(prog))
    return fleet


def readout(fleet):
    fleet.start()
    res = fleet.run(max_rounds=6)
    return res, fleet.transfer_stats()


def profiled(fn, log_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        return fn()
    finally:
        jax.profiler.stop_trace()


def host_spans(log_dir):
    """``[(start_ns, end_ns, name, stats)]`` of the ``fleet.*`` events, and
    the session's start on the profiler clock."""
    from jax.profiler import ProfileData

    path = sorted(log_dir.glob("plugins/profile/*/*.xplane.pb"))[-1]
    data = ProfileData.from_file(str(path))
    spans, t0 = [], None
    for plane in data.planes:
        for key, value in plane.stats:
            if key == "profile_start_time":
                t0 = int(value)
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("fleet."):
                    spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                  e.name, dict(e.stats)))
    return sorted(spans), t0


def inside(inner, outer) -> bool:
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def test_spans_on_the_default_path(oracle_plan, tmp_path):
    fleet = make_fleet()
    readout(fleet)                                  # compile outside the trace
    plain = readout(fleet)
    traced = profiled(lambda: readout(fleet), tmp_path)
    assert traced[1]["probes"] - plain[1]["probes"] == plain[1]["probes"] / 2
    for key in ("h2d", "d2h", "h2d_bytes", "d2h_bytes"):
        assert traced[1][key] - plain[1][key] == plain[1][key] / 2, key
    assert traced[0].outputs == plain[0].outputs
    assert fleet.export_trace()["traceEvents"][1:] == []   # no ring

    spans, _ = host_spans(tmp_path)
    by = {}
    for sp in spans:
        by.setdefault(sp[2], []).append(sp)
    (start,), (run,) = by["fleet.start"], by["fleet.run"]
    (audit,), (restack,) = by["fleet.audit"], by["fleet.restack"]
    assert inside(audit, start) and inside(restack, start)
    assert audit[1] <= restack[0] and start[1] <= run[0]
    for name in ("fleet.dispatch", "fleet.probe", "fleet.sync",
                 "fleet.decode"):
        assert by[name] and all(inside(sp, run) for sp in by[name]), name
    rounds = run[3]["rounds"]
    assert rounds == 6 and len(by["fleet.dispatch"]) == rounds
    assert len(by["fleet.probe"]) == traced[1]["probes"] - plain[1]["probes"]
    assert {sp[3]["readout"] for sp in spans} == {3}
    # The Auditor verifies afresh only nodes whose entries or decoded code
    # cells it has no verdict for.  From the second read-out on, all four
    # nodes resume at one pc inside the loop body.  SUM1 and SUM2 differ
    # only in the loop limit, which lies before that pc and is not decoded
    # from it.  So the second read-out verified one node, and the third
    # reuses that verdict for all four.
    assert audit[3]["nodes"] == 0 and audit[3]["reused"] == 4
    assert audit[3]["executor"] == "oracle" and audit[3]["switched"] == 0
    nbytes = plain[1]["h2d_bytes"] // 2
    assert restack[3]["bytes"] == by["fleet.sync"][0][3]["bytes"] == nbytes
    assert by["fleet.decode"][0][3]["nodes"] == 4


def test_ring_records_nest_on_the_profiler_clock(oracle_plan, tmp_path):
    fleet = make_fleet(ObsConfig(trace=True, time_rounds=False))
    profiled(lambda: readout(fleet), tmp_path)
    ring = fleet._tracer.snapshot()
    byid = {ev["id"]: ev for ev in ring}

    def parent(ev):
        return byid[ev["parent"]]["name"] if ev["parent"] is not None else None

    for ev in ring:
        assert ev["readout"] == 1
        want = {"audit": "start", "restack": "start", "start": None,
                "run": None, "dispatch": "run", "probe": "run",
                "sync": "run", "decode": "run", "schedule": "dispatch",
                "execute": "dispatch", "router": "dispatch",
                "warp": "dispatch"}[ev["name"]]
        assert parent(ev) == want, ev
    audit = next(ev for ev in ring if ev["name"] == "audit")
    # The first start() swaps the fleet's initial kernels for the plan's,
    # and verifies SUM1 and SUM2 once each.
    assert audit["counts"] == {"nodes": 2, "reused": 2, "executor": "oracle",
                               "elide_checks": False, "switched": True}
    # The ring's clock is the profiler's: each record lands on its event.
    spans, t0 = host_spans(tmp_path)
    events = {(sp[2], sp[0]) for sp in spans}
    for ev in ring:
        start = ev["t0_ns"] - t0
        assert any(name == "fleet." + ev["name"] and abs(s - start) < 2e6
                   for name, s in events), ev


def test_lowered_round_names_its_phases():
    """The pallas round for a TPU, lowered on the CPU (nothing compiles):
    every phase scope is in its locations and the kernel is ``vmloop``."""
    from repro.core.vm.fleet import FleetKernels
    from repro.core.vm.vmstate import VMState, init_state
    from repro.kernels import set_kernels

    set_kernels("on")
    try:
        kernels = FleetKernels(CFG, executor="pallas")
        one = init_state(CFG)
        S = VMState(*[
            jax.ShapeDtypeStruct((4,) + np.shape(x), np.asarray(x).dtype)
            for x in one
        ])
        text = kernels.round_aux.trace(S, steps=CFG.steps_per_slice).lower(
            lowering_platforms=("tpu",)
        ).as_text(debug_info=True)
    finally:
        set_kernels("auto")
    for scope in ("fleet.schedule", "fleet.execute", "vmloop.pack",
                  "vmloop.unpack", "fleet.router", "fleet.warp"):
        assert f"/{scope}/" in text or f'"{scope}/' in text, scope
    assert re.findall(r'kernel_name = "(\w+)"', text) == ["vmloop"]
