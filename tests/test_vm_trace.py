"""Trace-JIT executor equivalence suite — the four-engine sweep.

The trace engine (``repro.core.vm.trace``) records a program's hot path
once with the Oracle and replays it as guarded, dispatch-narrowed XLA;
its entire contract is byte-exactness with the other three engines, *via
the guards* — a failed guard deoptimizes into the generic interpreter
tail, so stale traces, shared traces and self-modified code may only cost
speed, never bytes.  This suite:

  * sweeps EVERY opcode of the ISA (reusing tests/test_vm_pallas.py's
    claim-complete program tables) through ``TraceJitExecutor``,
    ``BatchedSliceExecutor``, ``OracleExecutor`` and the interpret-mode
    ``PallasSliceExecutor`` with byte-exact state comparison;
  * forces the deopt paths: a data-divergent branch against a shared
    trace, per-node divergence inside one program group, and a trace made
    stale by the program mutating between recordings — each must take
    guard exits AND stay byte-exact;
  * re-runs the 64-node ring ``reference_round`` comparison with
    ``FleetVM(executor="trace")`` (sharded variant in the slow subprocess
    test below) and checks ``trace_stats()`` on a hot single-program
    fleet (> 90 % of steps specialized);
  * pins ``make_executor``'s unknown-backend error to list every valid
    backend name;
  * property-tests (hypothesis) that recompiling / incrementally loading
    a node's program re-keys its trace-cache entry and the fleet still
    matches ``reference_round`` byte-exactly.
"""

import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.config import VMConfig
from repro.core.vm import (
    FleetVM,
    REXAVM,
    make_executor,
    reference_round,
)
from repro.core.vm.executor import (
    BatchedSliceExecutor,
    OracleExecutor,
    PallasSliceExecutor,
)
from repro.core.vm.trace import TraceJitExecutor, program_key
from repro.core.vm import vmstate as vms
from repro.core.vm.vmstate import VMState

from test_vm_pallas import (
    BAIL_PROGRAMS,
    PURE_PROGRAMS,
    assert_states_equal,
    make_reference,
    ring_program,
    run_lockstep,
)

# Same config as test_vm_fleet / test_vm_pallas so every jitted kernel and
# engine cache is shared across the VM test module set.
CFG = VMConfig(cs_size=2048, steps_per_slice=64, mbox_size=4)


@pytest.fixture(scope="module")
def engines():
    """One executor of each kind, shared by the sweep (compile once)."""
    return {
        "trace": TraceJitExecutor(CFG),
        "batched": BatchedSliceExecutor(CFG),
        "oracle": OracleExecutor(CFG),
        "pallas": PallasSliceExecutor(CFG, interpret=True),
    }


SWEEP = [
    (w, p)
    for table in (PURE_PROGRAMS, BAIL_PROGRAMS)
    for w, ps in table.items()
    for p in ps
]


# ---------------------------------------------------------------------------
# The four-engine byte-exact sweep
# ---------------------------------------------------------------------------

def _initial_state(prog: str) -> VMState:
    vm = REXAVM(CFG, backend="oracle")
    vm.launch(vm.load(prog))
    return vm.state


def _copy(st: VMState) -> VMState:
    return VMState(*[np.array(np.asarray(x)) for x in st])


def _one_slice(kind: str, ex, st: VMState) -> VMState:
    steps = CFG.steps_per_slice
    if kind == "batched":
        S = VMState(*[vms.stack1(x) for x in st])
        out = ex.run_slice(S, steps)
        return VMState(*[np.array(x[0]) for x in out])
    return ex.run_slice(st, steps)


@pytest.mark.parametrize(
    "word,prog", SWEEP,
    ids=[f"{i:03d}-{w}" for i, (w, _) in enumerate(SWEEP)],
)
def test_opcode_sweep_byte_exact(word, prog, engines):
    st0 = _initial_state(prog)
    finals = {}
    for kind, ex in engines.items():
        st = _copy(st0)
        for _ in range(3):
            st = _one_slice(kind, ex, st)
        finals[kind] = st
    for kind in ("batched", "oracle", "pallas"):
        for f in VMState._fields:
            av = np.asarray(getattr(finals["trace"], f))
            bv = np.asarray(getattr(finals[kind], f))
            assert np.array_equal(av, bv), (
                f"{word}: trace vs {kind} diverged on field {f}:\n{av}\n{bv}"
            )


def test_make_executor_unknown_backend_lists_valid_names():
    with pytest.raises(ValueError) as ei:
        make_executor("bogus", CFG)
    msg = str(ei.value)
    assert "bogus" in msg
    for name in ("jit", "oracle", "pallas", "trace"):
        assert name in msg, f"error message must list backend {name!r}: {msg}"


def test_fleet_unknown_executor_lists_valid_names():
    with pytest.raises(ValueError, match="trace"):
        FleetVM(CFG, n=2, executor="bogus")


# ---------------------------------------------------------------------------
# Guard failure / deoptimization
# ---------------------------------------------------------------------------

# Branches on a DIOS cell: two machines with identical code segments (one
# program hash) but different data take different paths.
_BRANCH_PROG = (
    "0 v get 10 < if 1 2 + drop else 3 4 * drop endif "
    "0 v get 1+ 0 v put 5 . halt"
)


def _dios_vm(backend: str, v: int) -> REXAVM:
    vm = REXAVM(CFG, backend=backend)
    vm.dios_add("v", np.asarray([v], np.int32))
    vm.launch(vm.load(_BRANCH_PROG))
    return vm


class TestTraceDeopt:
    def test_shared_trace_data_divergence(self):
        """One program hash, two data environments: the second machine
        reuses the first's trace, fails the branch guard, deoptimizes —
        and still matches the Oracle byte-for-byte."""
        ex = TraceJitExecutor(CFG)
        guards0 = ex.stats()["guard_exits"]
        for v in (0, 100):          # records the v<10 path, then diverges
            vt, vo = _dios_vm("oracle", v), _dios_vm("oracle", v)
            st_t = _copy(vt.state)
            st_o = _copy(vo.state)
            for _ in range(2):
                st_t = ex.run_slice(st_t, CFG.steps_per_slice)
                st_o, _ = OracleExecutor(CFG).oracle.run_slice(
                    st_o, CFG.steps_per_slice
                )
            for f in VMState._fields:
                assert np.array_equal(
                    np.asarray(getattr(st_t, f)), np.asarray(getattr(st_o, f))
                ), (v, f)
        assert ex.stats()["guard_exits"] > guards0

    def test_group_divergence_in_fleet(self):
        """Four nodes share one program (one group, one trace) but their
        DIOS data sends them down different branches: the representative's
        trace deopts on the others, byte-exact vs reference_round."""
        def build(n):
            fleet = FleetVM(CFG, n=n, executor="trace")
            ref = [REXAVM(CFG, backend="jit", seed=1 + i) for i in range(n)]
            for i, (a, b) in enumerate(zip(fleet.nodes, ref)):
                for vm in (a, b):
                    vm.dios_add("v", np.asarray([i * 50], np.int32))
                    vm.launch(vm.load(_BRANCH_PROG))
            return fleet, ref

        fleet, ref = build(4)
        ex = fleet.kernels.executor
        guards0 = ex.stats()["guard_exits"]
        run_lockstep(fleet, ref, rounds=4)
        assert_states_equal(fleet, ref)
        assert ex.stats()["guard_exits"] > guards0

    # `21 $ f !` stores the encoded literal-5 instruction over f's first
    # cell, so later calls of f compute 5+1, not 1+1; the patch fires on
    # loop iteration 3, *after* the loop's trace was recorded.
    _SELFMOD_PROG = (
        ": f 1 1 + drop ; "
        "0 begin f 1+ dup 3 = if 21 $ f ! endif dup 6 >= until . halt"
    )

    def test_self_modifying_code_single_node(self):
        """Self-modifying code through the single-node protocol: the green
        key is re-hashed from the CS every slice, so the patch re-keys the
        cache and the run stays byte-exact vs the Oracle."""
        vt = REXAVM(CFG, backend="trace")
        vo = REXAVM(CFG, backend="oracle")
        # Tiny slices force recordings on both sides of the patch point.
        rt = vt.run(vt.load(self._SELFMOD_PROG), max_slices=200, steps=8)
        ro = vo.run(vo.load(self._SELFMOD_PROG), max_slices=200, steps=8)
        assert rt.status == ro.status == "halt"
        assert rt.output == ro.output
        for f in VMState._fields:
            assert np.array_equal(
                np.asarray(getattr(vt.state, f)), np.asarray(getattr(vo.state, f))
            ), f

    def test_self_modifying_code_fleet_stale_trace(self):
        """In a fleet the green keys freeze at start()/push(), so the
        in-VM patch makes the cached loop trace stale under its old key —
        the recorded if-branch flips on iteration 3, the pc guard exits,
        and the per-cell guards keep the rest byte-exact vs reference."""
        fleet = make_trace_fleet([self._SELFMOD_PROG])
        ref = make_reference([self._SELFMOD_PROG])
        ex = fleet.kernels.executor
        guards0 = ex.stats()["guard_exits"]
        run_lockstep(fleet, ref, rounds=6)
        assert_states_equal(fleet, ref)
        assert ex.stats()["guard_exits"] > guards0


# ---------------------------------------------------------------------------
# Fleet-level equivalence + stats
# ---------------------------------------------------------------------------

def make_trace_fleet(progs: list[str]) -> FleetVM:
    fleet = FleetVM(CFG, n=len(progs), executor="trace")
    for node, prog in zip(fleet.nodes, progs):
        node.launch(node.load(prog))
    return fleet


class TestTraceFleet:
    def test_64_node_ring_matches_reference(self):
        """Acceptance: the 64-node ring on the trace executor — byte-exact
        vs reference_round, state resident on device (one full sync each
        way), traces actually recorded and compiled."""
        n = 64
        progs = [ring_program(i, n) for i in range(n)]
        fleet = make_trace_fleet(progs)
        res = fleet.run(max_rounds=300)
        assert fleet.h2d == 1 and fleet.d2h == 1
        assert res.statuses == ["halt"] * n
        assert res.outputs[0] == f"{n - 1} {n} "
        stats = fleet.trace_stats()
        assert stats["executor"] == "trace"
        assert stats["traces_recorded"] > 0
        assert stats["spec_steps"] > 0
        ref = make_reference(progs)
        for _ in range(res.rounds):
            reference_round(ref, CFG.steps_per_slice)
        for i in range(n):
            for f in VMState._fields:
                if f in ("out", "outp"):   # fleet.run() drained its rings
                    continue
                av = np.asarray(getattr(fleet.nodes[i].state, f))
                bv = np.asarray(getattr(ref[i].state, f))
                assert np.array_equal(av, bv), f"node {i} field {f}"
        assert res.outputs == [vm.output() for vm in ref]

    def test_hot_single_program_fleet_specializes(self):
        """Acceptance: a hot single-program fleet forms ONE program group
        (the full-fleet fast path) and > 90 % of its executed instructions
        run specialized."""
        n = 8
        prog = ": w 0 begin 1+ dup 2000 >= until drop ; w w halt"
        fleet = make_trace_fleet([prog] * n)
        # The engine (and its per-group telemetry) is shared across every
        # trace executor of this CFG, so measure by delta.
        before = {
            k: v["node_slices"]
            for k, v in fleet.kernels.executor.engine.group_stats.items()
        }
        res = fleet.run(max_rounds=400)
        assert res.statuses == ["halt"] * n
        stats = fleet.trace_stats()
        assert stats["specialized_frac"] > 0.9, stats
        assert stats["guard_exits"] <= stats["total_steps"]
        # One program -> one green key: exactly one group grew, by full
        # n-node slices (the whole-fleet fast path).
        grown = {
            k: v["node_slices"] - before.get(k, 0)
            for k, v in fleet.kernels.executor.engine.group_stats.items()
            if v["node_slices"] != before.get(k, 0)
        }
        assert len(grown) == 1, grown
        assert next(iter(grown.values())) % n == 0

    def test_trace_stats_zero_for_other_executors(self):
        # Schema-stable under every backend (PR 8): the full key set with
        # zeroed values, not a truncated dict.
        fleet = FleetVM(CFG, n=2)
        stats = fleet.trace_stats()
        assert stats["executor"] == "batched"
        assert stats["traces_recorded"] == 0
        assert stats["traces_compiled"] == 0
        assert stats["spec_steps"] == 0
        assert stats["guard_exits"] == 0
        assert stats["total_steps"] == 0
        assert stats["specialized_frac"] == 0.0
        assert stats["groups"] == {}
        trace_keys = set(FleetVM(CFG, n=2, executor="trace").trace_stats())
        assert set(stats) == trace_keys


# ---------------------------------------------------------------------------
# Program mutation invalidates the trace-cache entry (hypothesis)
# ---------------------------------------------------------------------------

_MUTATION_PROGRAMS = [
    "0 10 0 do 1+ loop . halt",
    "1 5 0 do dup + loop . halt",
    ": f 2 * ; 3 f f . halt",
    "7 . 42 . halt",
]


def _mutation_case(extra_prog: str, rounds_before: int, rounds_after: int):
    n = 3
    base = [f"{i} . 0 8 0 do 1+ loop . halt" for i in range(n)]
    fleet = make_trace_fleet(base)
    ref = make_reference(base)
    run_lockstep(fleet, ref, rounds=rounds_before)
    assert_states_equal(fleet, ref)

    ex = fleet.kernels.executor
    old_key = program_key(fleet.nodes[1].state.cs)
    assert ex._prog_keys[1] == old_key
    # Incremental code load + relaunch on node 1, mirrored on the
    # reference node — the recompile path a live fleet node takes.
    for vm in (fleet.nodes[1], ref[1]):
        vm.launch(vm.load(extra_prog))
    new_key = program_key(fleet.nodes[1].state.cs)
    assert new_key != old_key

    run_lockstep(fleet, ref, rounds=rounds_after)  # start() re-keys via push
    assert_states_equal(fleet, ref)
    # The stale entry is unreachable (re-keyed) and the mutated program
    # got its own cache entries under the new key.
    assert ex._prog_keys[1] == new_key
    assert any(k[0] == new_key for k in ex.engine.traces)


def test_program_mutation_rekeys_trace_cache():
    _mutation_case(_MUTATION_PROGRAMS[0], rounds_before=2, rounds_after=4)


try:
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=6, deadline=None)
    @given(
        prog=st.sampled_from(_MUTATION_PROGRAMS),
        rounds_before=st.integers(1, 3),
        rounds_after=st.integers(1, 4),
    )
    def test_program_mutation_property(prog, rounds_before, rounds_after):
        """Any recompile / incremental load re-keys the node's trace-cache
        entry and the fleet stays byte-exact vs reference_round."""
        _mutation_case(prog, rounds_before, rounds_after)
except ImportError:      # pragma: no cover - hypothesis always in CI
    pass


# ---------------------------------------------------------------------------
# Sharded fleet (slow, own process)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_sharded_trace_ring_subprocess():
    """The 64-node ring, 8-way node-sharded, trace executor: per-group
    gathers/scatters and the full-fleet fast path run over a partitioned
    node axis and must stay byte-exact vs reference_round.  Own process so
    the forced device count cannot leak."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import sys; sys.path.insert(0, "src")
        import numpy as np
        import jax
        from repro.config import VMConfig
        from repro.core.vm import FleetVM, REXAVM, reference_round
        from repro.core.vm.vmstate import VMState
        from repro.launch.mesh import make_node_mesh

        assert len(jax.devices()) == 8
        mesh = make_node_mesh()
        CFG = VMConfig(cs_size=2048, steps_per_slice=64, mbox_size=4)
        n = 64

        def prog(i):
            if i == 0:
                return f"1 {1 % n} send receive swap . . halt"
            return f"receive swap . 1+ {(i + 1) % n} send halt"

        fleet = FleetVM(CFG, n=n, mesh=mesh, executor="trace")
        for i, node in enumerate(fleet.nodes):
            node.launch(node.load(prog(i)))
        fleet.start()
        shapes = {s.data.shape for s in fleet._S.pc.addressable_shards}
        assert shapes == {(n // 8, CFG.max_tasks)}, shapes
        res = fleet.run(max_rounds=300)
        assert res.statuses == ["halt"] * n
        assert res.outputs[0] == f"{n - 1} {n} "
        stats = fleet.trace_stats()
        assert stats["traces_recorded"] > 0 and stats["spec_steps"] > 0
        print("TRACE_SHARDED_RUN_OK")

        ref = [REXAVM(CFG, backend="jit", seed=1 + i) for i in range(n)]
        for i, node in enumerate(ref):
            node.launch(node.load(prog(i)))
        for _ in range(res.rounds):
            reference_round(ref, CFG.steps_per_slice)
        for i in range(n):
            for f in VMState._fields:
                if f in ("out", "outp"):
                    continue
                av = np.asarray(getattr(fleet.nodes[i].state, f))
                bv = np.asarray(getattr(ref[i].state, f))
                assert np.array_equal(av, bv), (i, f)
        assert res.outputs == [vm.output() for vm in ref]
        print("TRACE_SHARDED_BYTE_EXACT_OK")
    """)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=900, cwd=".",
    )
    for marker in ("TRACE_SHARDED_RUN_OK", "TRACE_SHARDED_BYTE_EXACT_OK"):
        assert marker in out.stdout, (out.stdout[-2000:], out.stderr[-4000:])
