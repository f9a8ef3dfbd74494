"""Pallas vmloop kernel equivalence suite.

The vmloop kernel (``repro.kernels.vmloop``) claims a core opcode set and
bails out on everything else; byte-exactness with the lax interpreter and
the Python Oracle is its entire contract (the paper's software/hardware
operational equivalence, now across *three* engines).  This suite:

  * sweeps EVERY opcode of the ISA — each claimed opcode through
    ``PallasSliceExecutor`` (interpret mode), ``BatchedSliceExecutor`` and
    ``OracleExecutor`` with byte-exact state comparison, asserting the
    kernel really executed it (no silent bail-out = no opcode silently
    missing from the branch table), and each declined opcode through the
    bail-out + lax-tail path.  The claim now covers printing, the
    IO-suspending words (executed as in-kernel suspensions), the LUT DSP
    scalars and the vector/ANN ops — only ``task`` spawn and ``rnd``
    still bail;
  * forces total classification: a word added to the ISA without a
    SUPPORTED/BAILOUT claim fails here;
  * re-runs the 64-node ring ``reference_round`` comparison (now fully
    in-kernel: zero bail-outs) and the randomized messaging programs with
    ``FleetVM(executor="pallas")`` (sharded variant in the slow subprocess
    test below), plus the message-bound round mode (``service_every > 1``
    chunks through ``FleetKernels.rounds_aux``);
  * checks the per-opcode bail histogram (``pallas_stats()["bail_hist"]``
    / ``executor.bail_hist``) names the declining opcode;
  * property-tests mailbox ring wraparound/backpressure byte-exactness
    (kernel vs ``reference_round``) under random send/receive
    interleavings (hypothesis, skipped when unavailable).
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
    reference_round,
)
from repro.core.vm.executor import (
    BatchedSliceExecutor,
    OracleExecutor,
    PallasSliceExecutor,
)
from repro.core.vm import vmstate as vms
from repro.core.vm.spec import ISA, WORDS, Word, ST_HALT
from repro.core.vm.vmstate import VMState
from repro.kernels.vmloop import BAILOUT_WORDS, SUPPORTED_WORDS, supported_mask

# Same config as test_vm_fleet so the per-(cfg, n) kernel/jit caches are
# shared across the whole VM test module set.
CFG = VMConfig(cs_size=2048, steps_per_slice=64, mbox_size=4)


@pytest.fixture(scope="module")
def engines():
    """One executor of each kind, shared by the sweep (compile once)."""
    return {
        "pallas": PallasSliceExecutor(CFG, interpret=True),
        "batched": BatchedSliceExecutor(CFG),
        "oracle": OracleExecutor(CFG),
    }


# ---------------------------------------------------------------------------
# Opcode sweep programs.  Keys must exactly cover the kernel's claim lists;
# "pure" programs compile to claimed opcodes only (the kernel must finish
# them without bailing), "bail" programs contain at least one declined word.
# ---------------------------------------------------------------------------

PURE_PROGRAMS: dict[str, list[str]] = {
    # stack
    "nop": ["nop halt"],
    "dup": ["5 dup halt"],
    "drop": ["5 6 drop halt"],
    "swap": ["1 2 swap halt"],
    "over": ["1 2 over halt"],
    "rot": ["1 2 3 rot halt"],
    "nip": ["1 2 nip halt"],
    "tuck": ["1 2 tuck halt"],
    "pick": ["10 20 30 1 pick halt", "5 99 pick halt"],   # incl. EXC_STACK
    "2dup": ["1 2 2dup halt"],
    "2drop": ["1 2 2drop halt"],
    "depth": ["1 2 depth halt"],
    # arithmetic
    "+": ["7 3 + halt"],
    "-": ["7 3 - halt"],
    "*": ["7 3 * halt"],
    "/": ["7 -3 / halt", "1 0 / halt"],                   # incl. divbyzero
    "mod": ["7 3 mod halt", "1 0 mod halt"],
    "*/": ["12345 678 1000 */ halt", "-12345 678 1000 */ halt"],
    "negate": ["5 negate halt"],
    "abs": ["-5 abs halt"],
    "min": ["3 9 min halt"],
    "max": ["3 9 max halt"],
    "1+": ["41 1+ halt"],
    "1-": ["41 1- halt"],
    "2*": ["21 2* halt"],
    "2/": ["-7 2/ halt"],
    # comparison
    "=": ["3 3 = halt"],
    "<>": ["3 4 <> halt"],
    "<": ["3 4 < halt"],
    ">": ["3 4 > halt"],
    "<=": ["4 4 <= halt"],
    ">=": ["3 4 >= halt"],
    "0=": ["0 0= halt"],
    "0<": ["-2 0< halt"],
    "0>": ["2 0> halt"],
    # bitwise
    "and": ["12 10 and halt"],
    "or": ["12 10 or halt"],
    "xor": ["12 10 xor halt"],
    "invert": ["12 invert halt"],
    "lshift": ["3 4 lshift halt"],
    "rshift": ["-16 2 rshift halt"],
    # scalar memory
    "@": ["var x 7 x ! x @ halt", "9999999 @ halt"],      # incl. EXC_BOUNDS
    "!": ["var x 7 x ! halt"],
    "+!": ["var x 5 x ! 3 x +! x @ halt"],
    "get": ["array a { 3 1 4 } 1 a get halt", "array a { 3 1 4 } 9 a get halt"],
    "put": ["array a { 3 1 4 } 9 1 a put halt", "array a { 3 1 4 } 9 7 a put halt"],
    "push": ["array s 8 1 s push 2 s push halt"],
    "pop": ["array s 8 1 s push s pop halt", "array s 8 s pop halt"],
    "len": ["array a { 3 1 4 } a len halt"],
    # control flow
    "branch": ["0 if 1 else 2 endif halt"],
    "0branch": ["1 if 1 else 2 endif halt"],
    "ret": [": f 5 ; f halt"],
    "exit": [": f 1 exit 2 ; f halt"],
    "exec": [": f 7 ; $ f exec halt"],
    "doinit": ["0 3 0 do i + loop halt"],
    "doloop": ["1 4 1 do i * loop halt"],
    "i": ["0 5 0 do i + loop halt"],
    "j": ["0 3 0 do 2 0 do j + loop loop halt"],
    "unloop": [": f 5 0 do i 2 >= if unloop 77 exit endif loop 99 ; f halt"],
    "halt": ["halt"],
    "end": ["1 2"],                                       # implicit frame end
    "dlit": ["1000000000 halt"],                          # > 30-bit literal
    # tasks (non-spawning)
    "yield": ["yield 1 halt"],
    "sleep": ["5 sleep 1 halt"],
    "await": ["50 1 2 await halt"],
    "taskid": ["taskid halt"],
    "ms": ["ms halt"],
    "steps": ["steps halt"],
    # exceptions
    "exception": [": h 7 ; $ h exception user halt"],
    "catch": ["catch halt"],
    "throw": [
        ": h 7 ; $ h exception user catch 0= if 8 throw endif halt",
        "3 throw halt",                                   # no handler -> error
    ],
    # printing (out ring writes, in-kernel)
    ".": ["5 . halt"],
    "emit": ["65 emit halt"],
    "cr": ["cr halt"],
    "prstr": ['." hi" halt'],
    "vecprint": ["array a { 1 2 } a vecprint halt"],
    # IO-suspending words: the kernel executes the suspension itself
    # (pc rewind + io_op + ST_IOWAIT) and exits clean — no bail-out;
    # delivery stays with the host service / collective router.
    "out": ["7 out halt"],
    "in": ["in halt"],
    "send": ["7 1 send halt"],
    "receive": ["receive halt"],
    # LUT fixed-point DSP scalars (VMEM table gathers)
    "sin": ["1571 sin halt"],
    "log": ["100 log halt"],
    "sigmoid": ["500 sigmoid halt"],
    "relu": ["-3 relu halt"],
    "sqrt": ["50000 sqrt halt"],
    # vector / ANN ops (vecfold & dotprod contract via lax.dot_general)
    "fill": ["array a { 1 2 3 } 7 a fill halt"],
    "vecload": ["array a { 1 2 3 } array b 3 a 0 b vecload halt"],
    "vecscale": ["array a { 100 -200 } array sc { -2 3 } array d 2 a d sc vecscale halt"],
    "vecadd": ["array a { 1 2 3 } array b { 4 5 6 } array c 3 a b c 0 vecadd halt"],
    "vecmul": ["array a { 1 2 3 } array b { 4 5 6 } array c 3 a b c 0 vecmul halt"],
    "vecfold": ["array x { 10 20 } array w { 1 2 3 4 5 6 } array y 3 x w y 0 vecfold halt"],
    "vecmap": ["array a { 1 2 3 } array b 3 a b 1 0 vecmap halt"],
    "dotprod": ["array a { 1 2 3 } array b { 4 5 6 } a b dotprod halt"],
    "vecmax": ["array a { 3 1 4 1 5 } a vecmax halt"],
    "hull": ["array a { 1000 -500 250 0 } a 0 4 300 hull halt"],
    "lowp": ["array a { 1000 500 250 0 } a 0 4 300 lowp halt"],
    "highp": ["array a { 1000 500 250 0 } a 0 4 300 highp halt"],
}

BAIL_PROGRAMS: dict[str, list[str]] = {
    "task": [": w end ; 0 0 $ w task halt"],
    "rnd": ["7 rnd halt"],
}

SWEEP = (
    [(w, p, True) for w, ps in PURE_PROGRAMS.items() for p in ps]
    + [(w, p, False) for w, ps in BAIL_PROGRAMS.items() for p in ps]
)


class TestClassification:
    def test_isa_totally_classified(self):
        """Every ISA word is claimed or declined, never both — and the
        sweep tables above cover the claim lists exactly."""
        names = {w.name for w in WORDS}
        sup, bail = set(SUPPORTED_WORDS), set(BAILOUT_WORDS)
        assert sup & bail == set()
        assert sup | bail == names
        assert set(PURE_PROGRAMS) == sup
        assert set(BAIL_PROGRAMS) == bail

    def test_mask_flags_unclassified_words(self):
        """A new ISA word without a claim/decline must fail loudly."""
        isa = ISA(WORDS + [Word("bogus", "( -- )", "unclassified", "test")])
        with pytest.raises(RuntimeError, match="bogus"):
            supported_mask(isa)

    def test_mask_shape(self):
        mask = supported_mask()
        assert mask.shape == (len(WORDS) + 1,)
        assert not mask[-1]        # FIOS/out-of-table opcodes always bail


# ---------------------------------------------------------------------------
# The three-engine byte-exact sweep
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
    "word,prog,pure", SWEEP,
    ids=[f"{i:03d}-{w}" for i, (w, _, _) in enumerate(SWEEP)],
)
def test_opcode_sweep_byte_exact(word, prog, pure, engines):
    st0 = _initial_state(prog)
    bail0 = engines["pallas"].bailouts
    ksteps0 = engines["pallas"].kernel_steps
    finals = {}
    for kind, ex in engines.items():
        st = _copy(st0)
        for _ in range(3):
            st = _one_slice(kind, ex, st)
        finals[kind] = st
    for kind in ("batched", "oracle"):
        for f in VMState._fields:
            av = np.asarray(finals["pallas"].__getattribute__(f))
            bv = np.asarray(finals[kind].__getattribute__(f))
            assert np.array_equal(av, bv), (
                f"{word}: pallas vs {kind} diverged on field {f}:\n{av}\n{bv}"
            )
    bails = engines["pallas"].bailouts - bail0
    ksteps = engines["pallas"].kernel_steps - ksteps0
    if pure:
        # A bail-out here means the opcode is missing from the kernel's
        # branch table despite being claimed.
        assert bails == 0, f"kernel bailed on claimed opcode {word!r}"
        assert ksteps > 0, f"kernel retired no instructions for {word!r}"
    else:
        assert bails >= 1, f"kernel failed to bail on declined opcode {word!r}"


# ---------------------------------------------------------------------------
# Fleet-level equivalence (ring + randomized messaging + mixed IO)
# ---------------------------------------------------------------------------

def ring_program(i: int, n: int) -> str:
    if i == 0:
        return f"1 {1 % n} send receive swap . . halt"
    return f"receive swap . 1+ {(i + 1) % n} send halt"


def make_pallas_fleet(progs: list[str]) -> FleetVM:
    fleet = FleetVM(CFG, n=len(progs), executor="pallas")
    for node, prog in zip(fleet.nodes, progs):
        node.launch(node.load(prog))
    return fleet


def make_reference(progs: list[str]) -> list[REXAVM]:
    nodes = [REXAVM(CFG, backend="jit", seed=1 + i) for i in range(len(progs))]
    for node, prog in zip(nodes, progs):
        node.launch(node.load(prog))
    return nodes


def run_lockstep(fleet: FleetVM, ref: list[REXAVM], rounds: int):
    fleet.start()
    for _ in range(rounds):
        fleet._S = fleet.kernels.round(fleet._S, CFG.steps_per_slice)
    fleet.sync()
    for _ in range(rounds):
        reference_round(ref, CFG.steps_per_slice)


def assert_states_equal(fleet: FleetVM, ref: list[REXAVM]):
    for i, (a, b) in enumerate(zip(fleet.nodes, ref)):
        for f in VMState._fields:
            av = np.asarray(getattr(a.state, f))
            bv = np.asarray(getattr(b.state, f))
            assert np.array_equal(av, bv), (
                f"node {i} field {f} diverged:\n{av}\n{bv}"
            )


class TestPallasFleet:
    def test_randomized_programs_match_reference(self):
        """Seeded-random messaging/compute programs through the pallas
        executor stay byte-exact vs the host-routed reference — including
        mid-slice IO suspensions (send/receive/print bail-outs)."""
        n = 3
        rng = np.random.default_rng(11)
        for _ in range(3):
            progs = []
            for _i in range(n):
                units = []
                for _u in range(int(rng.integers(2, 6))):
                    kind = int(rng.integers(0, 4))
                    if kind == 0:
                        v = int(rng.integers(0, 100))
                        dst = int(rng.integers(-1, n + 2))
                        units.append(f"{v} {dst} send")
                    elif kind == 1:
                        units.append("receive drop drop")
                    elif kind == 2:
                        units.append(f"{int(rng.integers(0, 50))} .")
                    else:
                        units.append(f"0 {int(rng.integers(1, 20))} 0 do 1+ loop drop")
                progs.append(" ".join(units) + " halt")
            fleet, ref = make_pallas_fleet(progs), make_reference(progs)
            run_lockstep(fleet, ref, rounds=12)
            assert_states_equal(fleet, ref)

    def test_64_node_ring_matches_reference(self):
        """Acceptance: the 64-node ring on the pallas executor — byte-exact
        vs reference_round, state resident on device, and every round fully
        in-kernel (send/receive suspensions no longer bail)."""
        n = 64
        progs = [ring_program(i, n) for i in range(n)]
        fleet = make_pallas_fleet(progs)
        res = fleet.run(max_rounds=300)
        assert fleet.h2d == 1 and fleet.d2h == 1
        assert res.statuses == ["halt"] * n
        assert res.outputs[0] == f"{n - 1} {n} "
        stats = fleet.pallas_stats()
        assert stats["executor"] == "pallas"
        assert stats["kernel_steps"] > 0
        assert stats["bailed_node_rounds"] == 0    # IO words run in-kernel
        assert stats["bail_hist"] == {}
        assert stats["bailed_frac"] < 0.05
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


class TestPallasHostIO:
    def test_mid_slice_out_suspension(self):
        """Compute runs in-kernel, `out` suspends mid-slice *in-kernel*
        (no bail-out), the host services it — identical to the oracle end
        to end."""
        prog = "0 30 0 do 1+ loop out halt"
        vp = REXAVM(CFG, backend="pallas")
        vo = REXAVM(CFG, backend="oracle")
        rp = vp.run(vp.load(prog), max_slices=50)
        ro = vo.run(vo.load(prog), max_slices=50)
        assert rp.status == ro.status == "halt"
        assert vp.out_stream == vo.out_stream == [30]
        for f in VMState._fields:
            assert np.array_equal(
                np.asarray(getattr(vp.state, f)), np.asarray(getattr(vo.state, f))
            ), f
        assert vp.executor.bailouts == 0
        assert vp.executor.bail_hist == {}
        assert vp.executor.kernel_steps > 0

    def test_fios_call_bails_to_host(self):
        """FIOS opcodes (>= num_ops) bail; the host services the call and
        the resumed state matches the oracle byte-for-byte."""
        def build(backend):
            vm = REXAVM(CFG, backend=backend)
            vm.fios_add("seven", lambda: 7, args=0, ret=1)
            return vm

        vp, vo = build("pallas"), build("oracle")
        rp = vp.run(vp.load("seven 1+ halt"), max_slices=50)
        ro = vo.run(vo.load("seven 1+ halt"), max_slices=50)
        assert rp.status == ro.status == "halt"
        for f in VMState._fields:
            assert np.array_equal(
                np.asarray(getattr(vp.state, f)), np.asarray(getattr(vo.state, f))
            ), f
        assert vp.executor.bailouts >= 1
        assert vp.executor.bail_hist.get("fios/trap", 0) >= 1

    def test_multitask_sleep_await_full_run(self):
        """Scheduler interplay (task spawn bails, wake-ups, time warp) under
        the pallas backend matches the oracle across a whole run."""
        prog = (
            "var flag : w 1 flag ! end ; "
            "0 0 $ w task drop 100 1 flag await . flag @ . halt"
        )
        vp = REXAVM(CFG, backend="pallas")
        vo = REXAVM(CFG, backend="oracle")
        rp = vp.run(vp.load(prog), max_slices=100)
        ro = vo.run(vo.load(prog), max_slices=100)
        assert rp.status == ro.status
        assert rp.output == ro.output
        for f in VMState._fields:
            assert np.array_equal(
                np.asarray(getattr(vp.state, f)), np.asarray(getattr(vo.state, f))
            ), f
        # The per-opcode histogram names `task` as the declining word.
        assert vp.executor.bail_hist.get("task", 0) >= 1


class TestMessageBoundMode:
    """``run(service_every=k)`` with the pallas executor chunks k whole
    rounds — kernel slice, collective router, warp — through the jitted
    ``FleetKernels.rounds_aux`` loop without host probes in between."""

    def test_ring_service_every_matches_batched(self):
        """The 8-node ring driven in service_every=8 chunks is byte-exact
        vs the batched executor under the same probe cadence, and never
        reaches the lax tail."""
        n = 8
        progs = [ring_program(i, n) for i in range(n)]

        def build(executor):
            fleet = FleetVM(CFG, n=n, executor=executor)
            for node, prog in zip(fleet.nodes, progs):
                node.launch(node.load(prog))
            return fleet

        fp, fb = build("pallas"), build("batched")
        assert fp.kernels.rounds_aux is not None
        rp = fp.run(max_rounds=80, service_every=8)
        rb = fb.run(max_rounds=80, service_every=8)
        assert rp.statuses == rb.statuses == ["halt"] * n
        assert rp.outputs == rb.outputs
        for i in range(n):
            for f in VMState._fields:
                av = np.asarray(getattr(fp.nodes[i].state, f))
                bv = np.asarray(getattr(fb.nodes[i].state, f))
                assert np.array_equal(av, bv), f"node {i} field {f}"
        stats = fp.pallas_stats()
        assert stats["kernel_steps"] > 0
        assert stats["bailed_node_rounds"] == 0
        assert stats["bail_hist"] == {}

    def test_rounds_aux_matches_reference_round(self):
        """The fused multi-round loop itself (no FleetVM.run orchestration)
        is byte-exact vs reference_round over the same round count."""
        n = 4
        progs = [ring_program(i, n) for i in range(n)]
        fleet = make_pallas_fleet(progs)
        ref = make_reference(progs)
        fleet.start()
        S, n_sum, b_sum, hist = fleet.kernels.rounds_aux(
            fleet._S, CFG.steps_per_slice, 12
        )
        fleet._S = S
        fleet.sync()
        for _ in range(12):
            reference_round(ref, CFG.steps_per_slice)
        assert_states_equal(fleet, ref)
        assert int(n_sum) > 0 and int(b_sum) == 0
        assert int(np.asarray(hist).sum()) == 0

    def test_bail_hist_names_rnd_in_fleet(self):
        """A declined word inside a fleet shows up in the stats histogram
        under its ISA name."""
        fleet = make_pallas_fleet(["7 rnd drop halt", "1 2 + drop halt"])
        fleet.run(max_rounds=10)
        stats = fleet.pallas_stats()
        assert stats["bail_hist"].get("rnd", 0) >= 1
        assert stats["bailed_node_rounds"] >= 1


class TestMailboxProperties:
    """Randomized send/receive interleavings: ring wraparound (rd/wr far
    past mbox_size) and overflow backpressure must stay byte-exact between
    the in-kernel suspensions + collective router and reference_round."""

    N = 3
    ROUNDS = 10

    def _units(self, kinds):
        progs = []
        for node_kinds in kinds:
            units = []
            for kind, v, dst in node_kinds:
                if kind == 0:
                    units.append(f"{v} {dst} send")
                elif kind == 1:
                    units.append("receive drop drop")
                else:
                    units.append(f"{v} 1+ drop")
            progs.append(" ".join(units) + " halt")
        return progs

    def _check(self, kinds):
        progs = self._units(kinds)
        fleet = make_pallas_fleet(progs)
        ref = make_reference(progs)
        run_lockstep(fleet, ref, rounds=self.ROUNDS)
        assert_states_equal(fleet, ref)

    def test_overflow_backpressure_exact(self):
        """Deterministic worst case: everyone floods node 0's 4-slot ring
        (overflow => backpressure), node 0 drains it (rd/wr wrap)."""
        kinds = [
            [(1, 0, 0)] * 8,                         # node 0: drain
            [(0, v, 0) for v in range(6)],           # node 1: flood 0
            [(0, v + 100, 0) for v in range(6)],     # node 2: flood 0
        ]
        self._check(kinds)

    def test_random_interleavings_exact(self):
        hyp = pytest.importorskip("hypothesis")
        st_ = pytest.importorskip("hypothesis.strategies")
        unit = st_.tuples(
            st_.integers(min_value=0, max_value=2),
            st_.integers(min_value=0, max_value=99),
            # out-of-range destinations (drop path) included
            st_.integers(min_value=-1, max_value=self.N),
        )
        node = st_.lists(unit, min_size=1, max_size=6)
        fleets = st_.lists(node, min_size=self.N, max_size=self.N)

        @hyp.given(kinds=fleets)
        @hyp.settings(
            max_examples=15,
            deadline=None,
            suppress_health_check=[hyp.HealthCheck.too_slow],
        )
        def run(kinds):
            self._check(kinds)

        run()


@pytest.mark.slow
def test_sharded_pallas_ring_subprocess():
    """The 64-node ring, 8-way node-sharded, pallas executor: the kernel
    runs under shard_map (local shard only) and must stay byte-exact vs
    reference_round.  Own process so the forced device count cannot leak."""
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

        fleet = FleetVM(CFG, n=n, mesh=mesh, executor="pallas")
        for i, node in enumerate(fleet.nodes):
            node.launch(node.load(prog(i)))
        fleet.start()
        shapes = {s.data.shape for s in fleet._S.pc.addressable_shards}
        assert shapes == {(n // 8, CFG.max_tasks)}, shapes
        res = fleet.run(max_rounds=300)
        assert res.statuses == ["halt"] * n
        assert res.outputs[0] == f"{n - 1} {n} "
        stats = fleet.pallas_stats()
        assert stats["kernel_steps"] > 0 and stats["bailed_node_rounds"] == 0
        print("PALLAS_SHARDED_RUN_OK")

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
        print("PALLAS_SHARDED_BYTE_EXACT_OK")
    """)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=900, cwd=".",
    )
    for marker in ("PALLAS_SHARDED_RUN_OK", "PALLAS_SHARDED_BYTE_EXACT_OK"):
        assert marker in out.stdout, (out.stdout[-2000:], out.stderr[-4000:])
