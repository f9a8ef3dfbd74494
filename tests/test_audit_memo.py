"""The Auditor's verdict memo (``repro.analysis.memo``).

``FleetVM`` verifies each distinct program once and reuses the verdict for
every node whose entries, tables and decoded code cells match.  This
suite pins, on the CPU:

  * exactness: for a mix of small programs, an 8 x 8 sensor grid after a
    read-out (nodes mid-word) and a mixed Tab. 10 fleet, the memoized
    audit gives node by node the fields of a fresh ``analyze_vm`` and the
    same ``predict_branch_sets``, and the same ``BackendPlan`` follows;
  * invalidation: rewriting DIOS data or code cells the analysis never
    decoded is a hit; a changed decoded cell is a miss at the next
    ``push()`` and changes the plan; a fleet of distinct programs verifies
    every node in time linear in the fleet; the memo keeps only the
    program mix of the last audit.
"""

import importlib
import json
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.analysis import ERROR, VERIFIED, analyze_vm, feasibility, memo
from repro.analysis.cfg import decode
from repro.analysis.memo import node_key, verify
from repro.config import VMConfig
from repro.core.vm import FleetVM
from repro.core.vm.spec import TAG_OP
from repro.obs import ObsConfig

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))
import harness  # noqa: E402

# Same config and programs as test_vm_analysis.py.
CFG = VMConfig(cs_size=2048, steps_per_slice=64, mbox_size=4)
CLEAN = ": work 1 2 + 3 * drop ; work halt"
LOOPED = ": work 0 10 0 do i + loop drop ; work halt"
SPIN_RND = ": spin begin 1 rnd drop again ; spin"
UNDERFLOW = ": bad + ; bad halt"
SEED = 3800000001
TRAFFIC = {"sensor_grid_128": "bulk_r32_skew", "tinyml_tab10_16k": "bulk_r8"}


def small_fleet(progs, executor="oracle", obs=None) -> FleetVM:
    fleet = FleetVM(CFG, n=len(progs), executor=executor, obs=obs)
    for node, prog in zip(fleet.nodes, progs):
        node.launch(node.load(prog))
    return fleet


def bench_fleet(config, nodes, rounds, executor="oracle", **params):
    """A fleet of the chip benchmark's node program, cut to ``nodes``,
    after one read-out of ``rounds`` rounds (none for 0)."""
    cfg = json.loads((CHIP / "configs" / f"{config}.json").read_text())
    cfg["fleet"] = {"nodes": nodes}
    cfg["params"] = {**cfg["params"], **params}
    mix = json.loads(
        (CHIP / "traffic" / f"{TRAFFIC[config]}.json").read_text())
    prog = importlib.import_module(f"programs.{cfg['program']}")
    fleet = harness.build_fleet(cfg, harness.cell_params(cfg, mix), prog,
                                SEED, executor)
    if rounds:
        fleet.start()
        fleet.run(max_rounds=rounds)
    return fleet


def grid(rounds=4, executor="oracle"):
    return bench_fleet("sensor_grid_128", 64, rounds, executor, side=8)


FLEETS = {
    "programs": lambda: small_fleet(
        [CLEAN, UNDERFLOW, SPIN_RND, CLEAN, SPIN_RND, UNDERFLOW]),
    "grid": grid,
    "tab10": lambda: bench_fleet("tinyml_tab10_16k", 18, 1),
}


def fresh(vm):
    rep = analyze_vm(vm)
    sets = (
        feasibility.predict_branch_sets(vm.state.cs, rep.entries[0].pc,
                                        vm.isa)
        if rep.entries else ()
    )
    return rep, sets


def fields(rep) -> tuple:
    entries = [
        (e.pc, e.function, e.verdict, [str(d) for d in e.diagnostics],
         e.wcet, e.max_ds, e.max_fs, e.rs_need, e.returns)
        for e in rep.entries
    ]
    return (rep.verdict, entries, [str(d) for d in rep.diagnostics],
            rep.wcet, rep.words, rep.kinds, rep.has_fios)


def plan(reports, sets) -> tuple:
    p = feasibility.plan_backend(
        reports, [s[0] if s else None for s in sets])
    return p.executor, p.elide_checks, p.reasons, p.bail_words, p.branch_sets


def audit_counts(fleet) -> dict:
    """The ``fleet.audit`` span's counts of the last ``start()``."""
    ring = fleet._tracer.snapshot()
    return [ev for ev in ring if ev["name"] == "audit"][-1]["counts"]


@pytest.mark.parametrize("name", sorted(FLEETS))
def test_memoized_audit_is_exact(name):
    fleet = FLEETS[name]()
    want = [fresh(vm) for vm in fleet.nodes]
    want_reports = [r for r, _ in want]
    want_sets = [s for _, s in want]
    verified = []
    # The first audit verifies each distinct program and reuses it within
    # the fleet; the second reuses the first's verdicts for every node.
    for _ in range(2):
        reports, sets, n = fleet._analyze_nodes()
        verified.append(n)
        assert [fields(r) for r in reports] == [fields(r) for r in want_reports]
        assert list(sets) == want_sets
        assert plan(reports, sets) == plan(want_reports, want_sets)
    assert 0 < verified[0] < fleet.n and verified[1] == 0
    if name == "programs":
        assert verified[0] == 3


def test_undecoded_cells_are_a_hit():
    fleet = grid()
    keys = [node_key(vm) for vm in fleet.nodes]
    _, _, verified = fleet._analyze_nodes()
    # One firmware: one verdict a distinct entry tuple, although the nodes
    # sharing one differ in their frames' variables and arrays.
    assert verified == len(set(keys)) < fleet.n
    first = fleet.nodes[0]
    twins = [vm for vm, k in zip(fleet.nodes, keys) if k == keys[0]]
    assert any(not np.array_equal(vm.state.cs, first.state.cs)
               for vm in twins[1:])

    rng = np.random.default_rng(0)
    for vm in fleet.nodes:
        vm.dios_write("stc", rng.integers(0, 1000, 2))
    addrs = verify(first).addrs
    cs = first.state.cs
    free = np.setdiff1d(np.arange(len(cs)), addrs)
    cs[free] = rng.integers(-2**31, 2**31, len(free), dtype=np.int64)
    reports, sets, verified = fleet._analyze_nodes()
    assert verified == 0
    rep, bs = fresh(first)
    assert fields(reports[0]) == fields(rep) and sets[0] == bs


def break_cell(node, cell: str) -> None:
    """Change one decoded cell of ``work`` so that the program is wrong:
    its first cell (``lit``) becomes a ``drop``, which underflows, or its
    ``doloop`` operand points past the code segment."""
    pc = node.compiler.dictionary.entries["work"].addr
    cs = node.state.cs
    if cell == "opcode":
        cs[pc] = (node.isa.opcode["drop"] << 2) | TAG_OP
        return
    while (ins := decode(cs, pc, node.isa)).name != "doloop":
        pc = ins.next_pc
    cs[pc + 1] = len(cs) + 7


@pytest.mark.parametrize("prog,cell", [(CLEAN, "opcode"),
                                       (LOOPED, "operand")],
                         ids=["opcode", "operand"])
def test_changed_code_cell_is_a_miss_on_push(prog, cell):
    fleet = small_fleet([prog] * 4, executor="auto",
                        obs=ObsConfig(trace=True, time_rounds=False))
    fleet.start()
    assert audit_counts(fleet)["nodes"] == 1
    assert audit_counts(fleet)["reused"] == 3
    stats = fleet.analysis_stats()
    assert stats["verdicts"]["verified"] == 4 and stats["elide_checks"]

    node = fleet.nodes[2]
    break_cell(node, cell)
    fleet.push()
    counts = audit_counts(fleet)
    assert (counts["nodes"], counts["reused"]) == (1, 3)
    assert counts["executor"] == "batched" and not counts["elide_checks"]
    stats = fleet.analysis_stats()
    assert stats["verdicts"] == {"verified": 3, "flagged": 0, "error": 1}
    assert stats["reasons"][0].startswith("verifier errors")
    assert fields(fleet._node_reports[2]) == fields(analyze_vm(node))
    assert fleet._node_reports[2].verdict == ERROR
    assert fleet._node_reports[0].verdict == VERIFIED


def test_distinct_programs_verify_every_node(monkeypatch):
    """Nodes that differ only in a decoded literal are distinct programs
    under one key and one footprint: an audit verifies every one, and
    takes at most two footprint slices a node (this audit's verdicts, then
    the last one's), however many nodes there are."""
    n = 300
    fleet = small_fleet([f": work {i} 2 + drop ; work halt" for i in range(n)])
    assert len({node_key(vm) for vm in fleet.nodes}) == 1
    slices = []
    real = memo._cells

    def counted(cs, addrs):
        slices.append(len(addrs))
        return real(cs, addrs)

    monkeypatch.setattr(memo, "_cells", counted)
    for want in (n, 0):
        del slices[:]
        reports, _, verified = fleet._analyze_nodes()
        assert verified == want and len(slices) <= 2 * n
        assert len(fleet._verdicts) == n
    assert fields(reports[7]) == fields(analyze_vm(fleet.nodes[7]))


def test_memo_keeps_the_last_program_mix(monkeypatch):
    real = feasibility.plan_backend

    def oracle(reports, branch_sets=None):
        p = real(reports, branch_sets)
        return feasibility.BackendPlan(
            "oracle", False, p.reasons, p.bail_words, p.branch_sets)

    monkeypatch.setattr(feasibility, "plan_backend", oracle)
    fleet = grid(rounds=0, executor="auto")
    for _ in range(5):
        fleet.start()
        keys = {node_key(vm) for vm in fleet.nodes}
        assert len(fleet._verdicts) <= len(keys)
        fleet.run(max_rounds=4)
