"""The Auditor (PR 10): static bytecode verification, backend feasibility,
WCET-backed admission.

* the verifier proves EXC_STACK unreachable on clean programs (VERIFIED),
  pins stack under/overflow to a source-mapped pc (ERROR), bounds counted
  loops (WCET) and leaves unbounded loops honest (``wcet=None``);
* satellite 1 — every runtime ISA word carries a machine-readable declared
  stack effect, FIOS opcodes derive theirs from the syscall table;
* satellite 2 — ``CompileError`` carries token/char-position/frame;
* ``executor="auto"`` resolves VERIFIED fleets to the checks-elided pallas
  kernel (byte-exact vs ``reference_round``), predictable-bail fleets to
  the trace engine with AOT-compiled branch sets, and broken programs to
  the always-checked batched engine;
* the statically predicted bail-word footprint equals the observed
  ``pallas_stats()["bail_hist"]`` key set — prediction is telemetry-exact;
* ``Executive.spawn`` admission uses the verifier's WCET bound when the
  caller declares no duration: statically-infeasible deadlines reject
  before launch;
* property tests (hypothesis, skipped when absent): well-formed random
  programs verify; a random single-cell corruption is either caught
  statically or provably harmless (no EXC_STACK on the checked Oracle).
"""

import random

import numpy as np
import pytest

from repro.analysis import (
    ERROR,
    FLAGGED,
    VERIFIED,
    analyze_program,
    analyze_source,
    analyze_vm,
    bail_words,
    plan_backend,
    predict_branch_set,
)
from repro.config import VMConfig
from repro.core.vm import REXAVM, FleetVM, reference_round
from repro.core.vm.compiler import CompileError
from repro.core.vm.interp import get_interpreter
from repro.core.vm.spec import (
    EXC_STACK,
    ST_ERR,
    ST_HALT,
    STACK_EFFECTS,
    fios_stack_effect,
    get_isa,
)
from repro.core.vm.vmstate import VMState
from repro.exec.executive import Executive

# Same config as test_vm_fleet.py so the per-VMConfig kernel caches are
# shared when the suite runs in one process.
CFG = VMConfig(cs_size=2048, steps_per_slice=64, mbox_size=4)

CLEAN = ": work 1 2 + 3 * drop ; work halt"
LOOPED = ": work 0 10 0 do i + loop drop ; work halt"
SPIN_RND = ": spin begin 1 rnd drop again ; spin"
UNDERFLOW = ": bad + ; bad halt"


def make_fleet(progs, executor="batched") -> FleetVM:
    fleet = FleetVM(CFG, n=len(progs), executor=executor)
    for node, prog in zip(fleet.nodes, progs):
        node.launch(node.load(prog))
    return fleet


def make_reference(progs) -> list[REXAVM]:
    nodes = [REXAVM(CFG, backend="jit", seed=1 + i) for i in range(len(progs))]
    for node, prog in zip(nodes, progs):
        node.launch(node.load(prog))
    return nodes


def assert_states_equal(fleet: FleetVM, ref: list[REXAVM]):
    for i, (a, b) in enumerate(zip(fleet.nodes, ref)):
        for f in VMState._fields:
            av, bv = np.asarray(getattr(a.state, f)), np.asarray(getattr(b.state, f))
            assert np.array_equal(av, bv), (
                f"node {i} field {f} diverged:\n{av}\n{bv}"
            )


# ---------------------------------------------------------------------------
# Verifier verdicts
# ---------------------------------------------------------------------------


class TestVerifier:
    def test_clean_program_verified_with_wcet(self):
        rep = analyze_source(CLEAN, CFG)
        assert rep.verdict == VERIFIED
        assert rep.errors == []
        assert rep.wcet is not None and rep.wcet > 0
        assert {"halt"} <= rep.words

    def test_underflow_is_source_mapped_error(self):
        rep = analyze_source(UNDERFLOW, CFG)
        assert rep.verdict == ERROR
        msgs = [str(d) for d in rep.diagnostics]
        assert any("underflow" in m for m in msgs), msgs
        # Source-mapped: the diagnostic names a pc and the call site.
        assert any("pc " in m for m in msgs), msgs

    def test_overflow_is_error(self):
        deep = " ".join(["1"] * (CFG.ds_size + 8)) + " halt"
        rep = analyze_source(deep, CFG)
        assert rep.verdict == ERROR
        assert any("overflow" in str(d) for d in rep.diagnostics)

    def test_counted_loop_wcet_scales_with_trips(self):
        small = analyze_source(": w 0 10 0 do 1 + loop drop ; w halt", CFG)
        big = analyze_source(": w 0 100 0 do 1 + loop drop ; w halt", CFG)
        assert small.verdict == big.verdict == VERIFIED
        assert small.wcet is not None and big.wcet is not None
        assert big.wcet > small.wcet >= 10  # at least one instr per trip

    def test_unbounded_loop_is_verified_but_unbounded(self):
        rep = analyze_source(": w begin 1 drop again ; w", CFG)
        assert rep.verdict == VERIFIED
        assert rep.wcet is None

    def test_corrupted_call_target_is_error(self):
        vm = REXAVM(CFG, backend="oracle")
        frame = vm.load(CLEAN)
        cs = np.asarray(vm.state.cs).copy()
        # Replace the entry instruction with a call way out of bounds.
        cs[frame.entry] = ((CFG.cs_size + 100) << 2) | 2  # TAG_CALL
        rep = analyze_program(cs, [frame.entry], vm.isa, CFG)
        assert rep.verdict == ERROR

    def test_predict_branch_set_on_straightline_code(self):
        vm = REXAVM(CFG, backend="oracle")
        frame = vm.load(CLEAN)
        bs = predict_branch_set(np.asarray(vm.state.cs), frame.entry, vm.isa)
        assert bs is not None and len(bs) > 0
        assert all(isinstance(k, tuple) and len(k) == 2 for k in bs)

    def test_plan_backend_policy(self):
        clean = analyze_source(CLEAN, CFG)
        spin = analyze_source(SPIN_RND, CFG)
        bad = analyze_source(UNDERFLOW, CFG)
        assert plan_backend([clean], [None]).executor == "pallas"
        assert plan_backend([clean], [None]).elide_checks is True
        plan = plan_backend([bad], [None])
        assert plan.executor == "batched" and plan.elide_checks is False
        assert "rnd" in bail_words(spin)


# ---------------------------------------------------------------------------
# Satellite 1: declared stack effects
# ---------------------------------------------------------------------------


class TestDeclaredStackEffects:
    def test_every_runtime_word_declares_effect(self):
        for word in get_isa().words:
            assert word.stack is not None, f"{word.name} missing .stack"
            assert word.stack == STACK_EFFECTS[word.name]
            din, dout, fin, fout = word.stack
            assert min(din, dout, fin, fout) >= 0

    def test_fios_effects_come_from_syscall_table(self):
        vm = REXAVM(CFG, backend="oracle")
        vm.svc_add("sensor", lambda: 7, args=0, ret=1)
        vm.svc_add("emit", lambda v: None, args=1, ret=0)
        entries = [e for e in vm.fios.entries if e is not None]
        assert len(entries) == 2
        for e in entries:
            assert fios_stack_effect(e.args, e.ret) == (e.args, e.ret, 0, 0)
        # The verifier consumes exactly this table via analyze_vm: a
        # program calling `sensor` needs no cells and rises by one.
        frame = vm.load(f"{'sensor'} drop halt")
        rep = analyze_vm(vm, entries=[(frame.entry, 0, 0, 0, 0)])
        assert rep.verdict == VERIFIED
        assert rep.has_fios

    def test_compile_only_words_carry_no_opcode_effect(self):
        from repro.core.vm.spec import COMPILE_WORDS

        assert all(w.stack is None for w in COMPILE_WORDS)

    def test_isa_regeneration_is_stable(self):
        from repro.core.vm.spec import ISA

        a, b = get_isa(), ISA()
        assert a.num_ops == b.num_ops
        assert a.opcode == b.opcode
        assert [w.stack for w in a.words] == [w.stack for w in b.words]


# ---------------------------------------------------------------------------
# Satellite 2: CompileError source positions
# ---------------------------------------------------------------------------


class TestCompileErrorPositions:
    def test_unknown_word_is_source_mapped(self):
        vm = REXAVM(CFG, backend="oracle")
        text = ": f 1 2 + ; f bogus halt"
        with pytest.raises(CompileError) as ei:
            vm.load(text)
        err = ei.value
        assert err.token == "bogus"
        assert err.pos == text.index("bogus")
        assert "bogus" in str(err) and "char" in str(err)

    def test_error_inside_definition_names_the_frame(self):
        vm = REXAVM(CFG, backend="oracle")
        text = ": f 1 nosuch ; f halt"
        with pytest.raises(CompileError) as ei:
            vm.load(text)
        err = ei.value
        assert err.token == "nosuch"
        assert err.pos == text.index("nosuch")
        assert err.frame is not None  # the compilation frame is named
        assert f"frame {err.frame!r}" in str(err)


# ---------------------------------------------------------------------------
# Tentpole: executor="auto" backend resolution
# ---------------------------------------------------------------------------


class TestAutoBackend:
    def test_verified_fleet_elides_checks_on_pallas(self):
        fleet = make_fleet([CLEAN] * 4, executor="auto")
        fleet.start()
        a = fleet.analysis_stats()
        assert a["auto"] and a["requested"] == "auto"
        assert a["executor"] == "pallas"
        assert a["elide_checks"] is True
        assert a["verdicts"] == {"verified": 4, "flagged": 0, "error": 0}
        assert a["predicted_bail_words"] == []
        assert all(w is not None for w in a["wcet"])
        fleet.run(max_rounds=8)
        assert all(int(n.state.tstatus[0]) == ST_HALT for n in fleet.nodes)

    def test_error_fleet_falls_back_to_checked_batched(self):
        fleet = make_fleet([UNDERFLOW], executor="auto")
        fleet.run(max_rounds=4)
        a = fleet.analysis_stats()
        assert a["executor"] == "batched"
        assert a["elide_checks"] is False
        assert a["verdicts"]["error"] == 1
        # The runtime check (still on) caught what the verifier predicted.
        st = fleet.nodes[0].state
        assert int(st.tstatus[0]) == ST_ERR
        assert int(st.last_exc[0]) == EXC_STACK

    def test_predictable_bails_pick_trace_with_aot(self):
        fleet = make_fleet([SPIN_RND] * 2, executor="auto")
        fleet.start()
        a = fleet.analysis_stats()
        assert a["executor"] == "trace"
        assert a["predicted_bail_words"] == ["rnd"]
        assert a["aot_branch_sets"] == 2
        eng = fleet.kernels.executor.engine
        compiled_before = eng.traces_compiled
        assert compiled_before >= 1  # AOT happened at start()
        for _ in range(4):
            fleet._S = fleet.kernels.round(fleet._S, CFG.steps_per_slice)
        fleet.sync()
        # No new compiles during the run: every trace was predicted.
        assert eng.traces_compiled == compiled_before

    def test_elided_auto_fleet_matches_reference_byte_exact(self):
        progs = [
            ": w 0 10 0 do i + loop . ; w halt",
            ": w 1 2 + 3 * dup . drop ; w halt",
            CLEAN,
            LOOPED,
        ]
        fleet, ref = make_fleet(progs, executor="auto"), make_reference(progs)
        fleet.start()
        assert fleet.analysis_stats()["elide_checks"] is True
        rounds = 6
        for _ in range(rounds):
            fleet._S = fleet.kernels.round(fleet._S, CFG.steps_per_slice)
        fleet.sync()
        for _ in range(rounds):
            reference_round(ref, CFG.steps_per_slice)
        assert_states_equal(fleet, ref)
        assert int(fleet.nodes[0].state.tstatus[0]) == ST_HALT  # not vacuous

    def test_predicted_bails_match_pallas_bail_hist_exactly(self):
        fleet = make_fleet([SPIN_RND] * 2, executor="pallas")
        predicted = fleet.analysis_stats()["predicted_bail_words"]
        assert predicted == ["rnd"]
        fleet.run(max_rounds=4)
        observed = sorted(fleet.pallas_stats()["bail_hist"])
        assert observed == predicted

    def test_bail_prediction_is_engine_invariant(self):
        """Four-engine sweep: the static footprint is a property of the
        program, not of the executor that runs it."""
        footprints = {}
        for executor in ("batched", "trace", "pallas", "auto"):
            fleet = make_fleet([SPIN_RND], executor=executor)
            footprints[executor] = tuple(
                fleet.analysis_stats()["predicted_bail_words"]
            )
        assert set(footprints.values()) == {("rnd",)}, footprints


# ---------------------------------------------------------------------------
# WCET-backed admission
# ---------------------------------------------------------------------------


class TestWcetAdmission:
    def test_infeasible_deadline_rejected_statically(self):
        fleet = make_fleet([CLEAN])
        ex = Executive(fleet)
        # WCET ~509 instrs * 10 us = ~6 virtual ms > a 2 ms deadline.
        slow = ": w 0 100 0 do 1 + loop drop ; w halt"
        assert ex.spawn(0, slow, deadline=2) == -1
        assert ex.log[-1].reason == "infeasible"
        # Same program, feasible deadline: admitted.
        assert ex.spawn(0, slow, deadline=10_000) > 0
        assert ex.log[-1].reason == "ok"

    def test_unbounded_program_stays_deadline_only(self):
        fleet = make_fleet([CLEAN])
        ex = Executive(fleet)
        # No static bound -> duration stays 0 -> deadline-only admission
        # (the run-time deadline monitor covers it).
        assert ex.spawn(0, ": w begin 1 drop again ; w", deadline=2) > 0
        assert ex.log[-1].reason == "ok"

    def test_declared_duration_overrides_wcet(self):
        fleet = make_fleet([CLEAN])
        ex = Executive(fleet)
        assert ex.spawn(0, CLEAN, deadline=2, duration_ms=1) > 0

    def test_wcet_matches_verifier_bound(self):
        fleet = make_fleet([CLEAN])
        vm = fleet.nodes[0]
        frame = vm.load(": w 0 50 0 do 1 + loop drop ; w halt")
        rep = analyze_vm(vm, entries=[(frame.entry, 0, 0, 0, 0)])
        assert rep.wcet is not None
        ms = Executive(fleet)._wcet_ms(vm, frame.entry)
        assert ms == -(-rep.wcet * CFG.us_per_instr // 1000)  # ceil


# ---------------------------------------------------------------------------
# Satellite 3: corruption robustness (deterministic seed; hypothesis below)
# ---------------------------------------------------------------------------


def _corruption_caught_or_harmless(idx: int, value: int):
    """Flip one code cell; the Auditor must catch it statically or the
    checked Oracle must agree it cannot raise EXC_STACK (the class of
    fault the elided kernels stop checking for)."""
    vm = REXAVM(CFG, backend="oracle")
    frame = vm.load(CLEAN)
    lo, hi = frame.start, frame.end
    pc = lo + idx % max(hi - lo, 1)
    vm.state.cs[pc] = np.int32(value)
    rep = analyze_program(
        np.asarray(vm.state.cs), [frame.entry], vm.isa, CFG
    )
    if rep.verdict != VERIFIED:
        return  # caught (ERROR) or demoted to the checked path (FLAGGED)
    vm.launch(frame)
    vm.run(max_slices=20, steps=CFG.steps_per_slice)
    st = vm.state
    stack_fault = (
        int(st.tstatus[0]) == ST_ERR and int(st.last_exc[0]) == EXC_STACK
    )
    assert not stack_fault, (
        f"verifier said VERIFIED but cell {pc}={value} raised EXC_STACK"
    )


class TestCorruption:
    def test_single_cell_corruption_caught_or_harmless(self):
        rng = random.Random(0)
        for _ in range(25):
            _corruption_caught_or_harmless(
                rng.randrange(0, 64), rng.randrange(-(2**31), 2**31)
            )


# ---------------------------------------------------------------------------
# Hypothesis property tests (dev-only dependency; CI installs .[test])
# ---------------------------------------------------------------------------


def _well_formed_program(lits, ops):
    """Push enough literals that the op suffix can never underflow."""
    return " ".join(str(v) for v in lits) + " " + " ".join(ops) + " halt"


class TestProperties:
    def test_compiler_output_verifies(self):
        pytest.importorskip("hypothesis")
        from hypothesis import given, settings, strategies as st

        safe_ops = st.sampled_from(
            ["+", "-", "*", "dup", "drop", "swap", "over", "1+", "negate"]
        )

        @settings(max_examples=40, deadline=None)
        @given(
            lits=st.lists(
                st.integers(-1000, 1000), min_size=4, max_size=10
            ),
            ops=st.lists(safe_ops, min_size=0, max_size=2),
        )
        def check(lits, ops):
            # <=2 ops popping <=2 cells each over >=4 pushed literals can
            # neither underflow nor overflow: must verify.
            rep = analyze_source(_well_formed_program(lits, ops), CFG)
            assert rep.verdict == VERIFIED
            assert rep.wcet is not None

        check()

    def test_random_corruption_caught_or_harmless(self):
        pytest.importorskip("hypothesis")
        from hypothesis import given, settings, strategies as st

        @settings(max_examples=25, deadline=None)
        @given(
            idx=st.integers(0, 63),
            value=st.integers(-(2**31), 2**31 - 1),
        )
        def check(idx, value):
            _corruption_caught_or_harmless(idx, value)

        check()

    def test_verified_programs_run_identically_with_checks_elided(self):
        pytest.importorskip("hypothesis")
        from hypothesis import given, settings, strategies as st

        checked = get_interpreter(CFG, elide_checks=False)
        elided = get_interpreter(CFG, elide_checks=True)
        safe_ops = st.sampled_from(
            ["+", "-", "*", "dup", "drop", "swap", "over", "1+"]
        )

        @settings(max_examples=20, deadline=None)
        @given(
            lits=st.lists(st.integers(-100, 100), min_size=4, max_size=8),
            ops=st.lists(safe_ops, min_size=0, max_size=2),
        )
        def check(lits, ops):
            prog = _well_formed_program(lits, ops)
            assert analyze_source(prog, CFG).verdict == VERIFIED
            vm = REXAVM(CFG, backend="oracle")
            vm.launch(vm.load(prog))
            st_a, found_a = checked.run_slice(vm.state, steps=256)
            st_b, found_b = elided.run_slice(vm.state, steps=256)
            assert bool(found_a) == bool(found_b)
            for f in VMState._fields:
                assert np.array_equal(
                    np.asarray(getattr(st_a, f)), np.asarray(getattr(st_b, f))
                ), f"field {f} diverged with checks elided"

        check()
