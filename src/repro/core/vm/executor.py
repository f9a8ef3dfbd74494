"""Executor protocol — the pluggable slice engine behind every VM frontend.

The paper's core claim is *operationally equivalent* software and hardware
implementations of one VM.  This module turns that into an explicit seam:
an :class:`Executor` advances a host-canonical ``VMState`` by one micro-slice
(``schedule -> vmloop -> preempt``, Fig. 10) and every frontend — the single
:class:`~repro.core.vm.machine.REXAVM`, the batched
:class:`~repro.core.vm.fleet.FleetVM`, and the voting
:class:`~repro.core.vm.ensemble.EnsembleVM` — drives whichever backend it is
given:

  * :class:`JitExecutor`    — the lax interpreter compiled by XLA
                              ("hardware" role); state crosses host<->device
                              around each slice and both directions are
                              counted (``h2d``/``d2h``) so benchmarks can
                              report the transfer cost the fleet avoids;
  * :class:`OracleExecutor` — the plain-Python reference ("software" role),
                              mutating the numpy state in place;
  * :class:`PallasSliceExecutor`
                            — the on-chip Pallas vmloop kernel
                              (``repro.kernels.vmloop``) with a lax-
                              interpreter tail for instructions outside the
                              kernel's claimed opcode set — the closest
                              analogue of the paper's FPGA backend;
  * :class:`~repro.core.vm.trace.TraceJitExecutor`
                            — the trace-JIT engine (``backend="trace"``):
                              nodes grouped by program hash, hot paths
                              recorded once by the Oracle and compiled to
                              guarded straight-line XLA, deoptimizing into
                              the generic interpreter tail — the closest
                              analogue of the paper's integrated JIT.

All produce byte-identical states (tests/test_vm_equivalence.py,
tests/test_vm_pallas.py, tests/test_vm_trace.py).
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Protocol, runtime_checkable

import numpy as np

from repro.config import VMConfig
from repro.core.vm.spec import ISA, ST_IOWAIT, ST_RUN, ST_YIELD, get_isa
from repro.core.vm import vmstate as vms
from repro.core.vm.vmstate import VMState


@runtime_checkable
class Executor(Protocol):
    """One micro-slice of one VM over a host-canonical (numpy) state."""

    backend: str

    def run_slice(self, state: VMState, steps: int) -> VMState:
        """Advance ``state`` by at most ``steps`` instructions of one task."""
        ...


class JitExecutor:
    """XLA-compiled interpreter behind the host<->device copy boundary.

    This is the seed repo's per-slice round trip, kept as the simple
    single-node path: the whole machine state is pushed to the device,
    one ``run_slice`` runs jitted, and the state is pulled back so the
    host can service FIOS suspensions.  ``h2d``/``d2h`` count the copies —
    the cost :class:`~repro.core.vm.fleet.FleetVM` exists to amortise.
    """

    backend = "jit"

    def __init__(self, cfg: VMConfig, isa: ISA | None = None, obs=None):
        self.cfg = cfg
        from repro.core.vm.interp import interp_for
        from repro.obs.metrics import normalize_obs
        self.interp = interp_for(cfg, isa)
        self.obs = normalize_obs(obs)
        self.op_hist = None        # numpy (num_ops + 4,) when obs is on
        self._slice_obs = None
        if self.obs is not None:
            import jax
            from repro.obs.metrics import make_counting_slice, n_bins
            self.op_hist = np.zeros(n_bins(self.interp.isa), np.int64)
            self._slice_obs = jax.jit(
                make_counting_slice(self.interp), static_argnums=1
            )
        self.h2d = 0               # host -> device full-state transfers
        self.d2h = 0               # device -> host full-state transfers
        self.h2d_bytes = 0         # bytes moved host -> device
        self.d2h_bytes = 0         # bytes moved device -> host

    def run_slice(self, state: VMState, steps: int) -> VMState:
        nbytes = vms.state_nbytes(state)
        dev = vms.to_device(state)
        self.h2d += 1
        self.h2d_bytes += nbytes
        if self._slice_obs is not None:
            dev, _, hist = self._slice_obs(dev, steps)
            self.op_hist += np.asarray(hist)
        else:
            dev, _ = self.interp.run_slice(dev, steps)
        out = vms.to_numpy(dev)
        self.d2h += 1
        self.d2h_bytes += nbytes
        return out


class BatchedSliceExecutor:
    """Vmapped ``run_slice`` over a leading node axis — the fleet's layer 1.

    Device state in, device state out: unlike :class:`JitExecutor` there is
    no host<->device boundary here; the stacked ``VMState`` stays resident
    (and, under a node-sharded ``NamedSharding``, stays *partitioned* — the
    per-node slice is embarrassingly parallel, so XLA runs each shard's
    nodes without any cross-device traffic).  Shared by ``FleetKernels``
    (sensor networks) and ``EnsembleVM`` (lock-stepped replicas)."""

    backend = "batched"

    def __init__(
        self,
        cfg: VMConfig,
        isa: ISA | None = None,
        elide_checks: bool = False,
    ):
        import jax

        self.cfg = cfg
        from repro.core.vm.interp import interp_for
        self.elide_checks = elide_checks
        self.interp = interp_for(cfg, isa, elide_checks)
        single = self.interp.run_slice_fn

        def batched(S: VMState, steps: int):
            return jax.vmap(lambda s: single(s, steps))(S)

        # (state, steps) -> (state, found-per-node); steps is static.
        self.run_slice_batched = jax.jit(batched, static_argnames=("steps",))

    def run_slice(self, state: VMState, steps: int) -> VMState:
        out, _ = self.run_slice_batched(state, steps)
        return out

    # -- observability (lazy: zero cost unless the fleet asks) ---------------

    def ensure_obs(self):
        """Build the phased counting variants of this engine's slice:
        ``obs_schedule(S) -> (S, found)`` and ``obs_execute(S, steps, found)
        -> (S, ExecAux)``.  Splitting schedule from execute lets the fleet's
        obs round wrap each phase in a tracer span; their composition is the
        byte-exact counting mirror of ``run_slice_batched``."""
        if hasattr(self, "obs_schedule"):
            return
        import jax
        import jax.numpy as jnp
        from repro.obs.metrics import make_counting_finish, zero_exec_aux

        interp = self.interp
        finish = make_counting_finish(interp)
        zero = zero_exec_aux(interp.isa)

        def exec_b(S: VMState, steps: int, found):
            # The counting loop no-ops on nodes the scheduler left un-woken
            # (their tstatus[cur] is never ST_RUN), so `found` needs no
            # explicit gate — same argument as the pallas engine's tail.
            iow0 = (S.tstatus == ST_IOWAIT).sum()
            S, hists = jax.vmap(lambda s: finish(s, steps))(S)
            iow1 = (S.tstatus == ST_IOWAIT).sum()
            aux = zero._replace(
                op_hist=hists.sum(0).astype(jnp.int32),
                io_susp=(iow1 - iow0).astype(jnp.int32),
            )
            return S, aux

        self.obs_schedule = jax.jit(
            lambda S: jax.vmap(self.interp._schedule)(S)
        )
        self.obs_execute = jax.jit(exec_b, static_argnames=("steps",))

    # -- Executive (lazy: zero cost unless the fleet schedules tasks) --------

    def ensure_exec(self):
        """Build the Executive micro-slice: ``run_slice_exec_batched(S,
        quantum) -> (S, found, switched, preempted)`` — the vmapped
        ``interp.run_slice_exec_fn`` (priority scheduler + per-quantum
        preemption counters)."""
        if hasattr(self, "run_slice_exec_batched"):
            return
        import jax

        single = self.interp.run_slice_exec_fn

        def exec_b(S: VMState, steps: int):
            return jax.vmap(lambda s: single(s, steps))(S)

        self.run_slice_exec_batched = jax.jit(exec_b, static_argnames=("steps",))


class _PallasEngine(NamedTuple):
    """Jitted batched-slice functions shared by every PallasSliceExecutor
    with the same (cfg, mesh, interpret) — tracing the kernel + the lax
    fallback is expensive, so they are cached like ``interp_for``."""

    plain: Callable      # (S, steps) -> (S, found)
    aux: Callable        # (S, steps) -> (S, found, n_exec, bailed, bail_op)
    exec_aux: Callable   # Executive micro-slice:
                         # (S, steps) -> (S, found, switched, preempted,
                         #                n_exec, bailed, bail_op)


def _build_pallas_engine(
    cfg: VMConfig, isa: ISA | None, mesh, interpret: bool,
    elide_checks: bool = False,
) -> _PallasEngine:
    import jax
    import jax.numpy as jnp
    from jax import lax

    from repro.core.vm.interp import interp_for
    from repro.kernels.vmloop.ops import fleet_vmloop

    interp = interp_for(cfg, isa, elide_checks)
    schedule = interp._schedule
    step_instr = interp._step_instr

    def vmloop_rest(st: VMState, remaining):
        """Finish a slice after a kernel bail-out: the lax interpreter's
        vmloop with a *traced* step bound (``interp._vmloop``'s bound is
        static).  A no-op for nodes that suspended or exhausted the budget
        in-kernel (status != RUN / remaining == 0)."""
        def cond(carry):
            s, n = carry
            return (n < remaining) & (s.tstatus[s.cur] == ST_RUN)

        def body(carry):
            s, n = carry
            return step_instr(s), n + 1

        st, _ = lax.while_loop(cond, body, (st, jnp.int32(0)))
        return st

    def preempt(st: VMState):
        """run_slice's tail: a task that exhausted its slice stays ready."""
        still = st.tstatus[st.cur] == ST_RUN
        return lax.cond(
            still,
            lambda s: s._replace(tstatus=s.tstatus.at[s.cur].set(ST_YIELD)),
            lambda s: s,
            st,
        )

    def batched_aux(S: VMState, steps: int):
        # schedule -> on-chip vmloop -> lax tail -> preempt, per node.
        # Byte-equivalent to vmapping interp.run_slice_fn: the kernel stops
        # before the first unclaimed opcode, so the lax tail continues from
        # an identical intermediate state, and nodes the scheduler left
        # un-woken never satisfy the loops' ST_RUN condition.
        with jax.named_scope("fleet.schedule"):
            S, found = jax.vmap(schedule)(S)
        with jax.named_scope("fleet.execute"):
            S, n_exec, bailed, bail_op = fleet_vmloop(
                S, steps, cfg, isa, mesh=mesh, interpret=interpret,
                elide_checks=elide_checks,
            )
            S = jax.vmap(vmloop_rest)(S, steps - n_exec)
            S = jax.vmap(preempt)(S)
        return S, found, n_exec, bailed, bail_op

    aux = jax.jit(batched_aux, static_argnames=("steps",))

    def batched(S: VMState, steps: int):
        S, found, _, _, _ = batched_aux(S, steps)
        return S, found

    plain = jax.jit(batched, static_argnames=("steps",))

    schedule_prio = interp._schedule_prio

    def batched_exec_aux(S: VMState, steps: int):
        # The Executive micro-slice: same kernel + lax-tail motion as
        # batched_aux, but scheduled by priority/round-robin and reporting
        # the task-level counters.  `task`-class words still bail to the
        # tail; the bail lands on the same state under every engine.
        prev = S.cur
        with jax.named_scope("fleet.schedule"):
            S, found = jax.vmap(schedule_prio)(S)
            switched = (found & (S.cur != prev)).astype(jnp.int32)
        with jax.named_scope("fleet.execute"):
            S, n_exec, bailed, bail_op = fleet_vmloop(
                S, steps, cfg, isa, mesh=mesh, interpret=interpret,
                elide_checks=elide_checks,
            )
            S = jax.vmap(vmloop_rest)(S, steps - n_exec)
            preempted = jax.vmap(
                lambda s: (s.tstatus[s.cur] == ST_RUN).astype(jnp.int32)
            )(S)
            S = jax.vmap(preempt)(S)
        return S, found, switched, preempted, n_exec, bailed, bail_op

    exec_aux = jax.jit(batched_exec_aux, static_argnames=("steps",))
    return _PallasEngine(plain=plain, aux=aux, exec_aux=exec_aux)


@functools.lru_cache(maxsize=16)
def _cached_pallas_engine(
    cfg: VMConfig, mesh, interpret: bool, elide_checks: bool = False
) -> _PallasEngine:
    return _build_pallas_engine(cfg, None, mesh, interpret, elide_checks)


def get_pallas_engine(
    cfg: VMConfig,
    isa: ISA | None = None,
    mesh=None,
    interpret: bool = True,
    elide_checks: bool = False,
) -> _PallasEngine:
    """Engine-selection policy mirroring ``interp_for``: cached for the
    default ISA, fresh build for a custom one.  ``elide_checks`` is part of
    the cache key — the checked and verified-fast-path kernels are distinct
    compiled artifacts."""
    if isa is None or isa is get_isa():
        return _cached_pallas_engine(cfg, mesh, interpret, elide_checks)
    return _build_pallas_engine(cfg, isa, mesh, interpret, elide_checks)


class _PallasObsEngine(NamedTuple):
    """Counting twin of :class:`_PallasEngine`: the obs variant of the
    kernel (a retirement histogram in each node's row) plus a counting
    lax tail, phased as schedule/execute so the fleet's obs round can
    trace each phase."""

    schedule: Callable   # jit: S -> (S, found)
    execute: Callable    # jit (static steps): (S, steps, found) -> (S, ExecAux)


def _build_pallas_obs(
    cfg: VMConfig, isa: ISA | None, mesh, interpret: bool
) -> _PallasObsEngine:
    import jax
    import jax.numpy as jnp

    from repro.core.vm.interp import interp_for
    from repro.kernels.vmloop.ops import fleet_vmloop
    from repro.obs.metrics import ExecAux, make_counting_finish

    interp = interp_for(cfg, isa)
    finish = make_counting_finish(interp)
    num_ops = interp.isa.num_ops

    def exec_b(S: VMState, steps: int, found):
        # In-kernel counting excludes the bailing instruction (the kernel
        # stops *before* it); the counting tail retires and bins it, so
        # kernel + tail histograms equal a pure-lax slice's exactly.
        iow0 = (S.tstatus == ST_IOWAIT).sum()
        S, n_exec, bailed, bail_op, op_hist = fleet_vmloop(
            S, steps, cfg, isa, mesh=mesh, interpret=interpret, obs=True
        )
        S, tail_h = jax.vmap(finish)(S, steps - n_exec)
        iow1 = (S.tstatus == ST_IOWAIT).sum()
        bailed_i = bailed.astype(jnp.int32)
        bail_hist = jnp.zeros(num_ops + 1, jnp.int32).at[
            jnp.clip(bail_op, 0, num_ops)
        ].add(bailed_i)
        aux = ExecAux(
            op_hist=(op_hist.sum(0) + tail_h.sum(0)).astype(jnp.int32),
            io_susp=(iow1 - iow0).astype(jnp.int32),
            deopts=bailed_i.sum(),
            kernel_steps=n_exec.sum().astype(jnp.int32),
            bailed=bailed_i.sum(),
            bail_hist=bail_hist,
        )
        return S, aux

    return _PallasObsEngine(
        schedule=jax.jit(lambda S: jax.vmap(interp._schedule)(S)),
        execute=jax.jit(exec_b, static_argnames=("steps",)),
    )


@functools.lru_cache(maxsize=16)
def _cached_pallas_obs(cfg: VMConfig, mesh, interpret: bool) -> _PallasObsEngine:
    return _build_pallas_obs(cfg, None, mesh, interpret)


def get_pallas_obs(
    cfg: VMConfig, isa: ISA | None = None, mesh=None, interpret: bool = True
) -> _PallasObsEngine:
    if isa is None or isa is get_isa():
        return _cached_pallas_obs(cfg, mesh, interpret)
    return _build_pallas_obs(cfg, isa, mesh, interpret)


class PallasSliceExecutor:
    """On-chip Pallas vmloop + lax tail — the fleet's third slice engine.

    Like :class:`BatchedSliceExecutor` it is device state in / device state
    out over a stacked node axis (``run_slice_batched``), plus an
    ``run_slice_batched_aux`` variant exposing per-node kernel step counts
    and bail-out flags for ``FleetVM.pallas_stats()``/benchmarks.  The
    single-node :class:`Executor` protocol (``run_slice`` over the
    host-canonical numpy state) is provided for ``REXAVM(backend="pallas")``
    and the ISA coverage sweep; it counts transfers like ``JitExecutor``.

    ``interpret=None`` auto-selects: compiled on TPU (or when
    ``repro.kernels.set_kernels("on")`` forces kernels), Pallas interpreter
    otherwise — the CPU-testable path pinned byte-exact by
    tests/test_vm_pallas.py.  Interpret mode on a TPU backend raises.
    """

    backend = "pallas"

    def __init__(
        self,
        cfg: VMConfig,
        isa: ISA | None = None,
        mesh=None,
        interpret: bool | None = None,
        obs=None,
        elide_checks: bool = False,
    ):
        self.cfg = cfg
        self.mesh = mesh
        from repro.core.vm.interp import interp_for
        from repro.obs.metrics import normalize_obs
        self.elide_checks = elide_checks
        self.interp = interp_for(cfg, isa, elide_checks)
        self._isa_arg = isa
        from repro.kernels import check_interpret, use_kernels
        if interpret is None:
            interpret = not use_kernels()
        self.interpret = check_interpret(interpret)
        engine = get_pallas_engine(cfg, isa, mesh, interpret, elide_checks)
        self.run_slice_batched = engine.plain
        self.run_slice_batched_aux = engine.aux
        self.run_slice_exec_batched_aux = engine.exec_aux
        self.obs = normalize_obs(obs)
        self.op_hist = None
        if self.obs is not None:
            from repro.obs.metrics import n_bins
            self.op_hist = np.zeros(n_bins(self.interp.isa), np.int64)
            self.ensure_obs()
        self.h2d = 0
        self.d2h = 0
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        self.kernel_steps = 0      # instructions retired inside the kernel
        self.fallback_steps = 0    # instructions retired by the lax tail
        self.bailouts = 0          # slices that hit an unclaimed opcode
        self.bail_hist: dict[str, int] = {}   # bailing word -> bail count

    def ensure_obs(self):
        """Attach the counting engine (see ``BatchedSliceExecutor.ensure_obs``
        for the phase contract) — the obs kernel is a distinct compiled
        artifact, cached per (cfg, mesh, interpret) like the plain one."""
        if hasattr(self, "obs_schedule"):
            return
        eng = get_pallas_obs(self.cfg, self._isa_arg, self.mesh, self.interpret)
        self.obs_schedule = eng.schedule
        self.obs_execute = eng.execute

    def _bail_word(self, code: int) -> str:
        isa = self.interp.isa
        return isa.name[code] if 0 <= code < isa.num_ops else "fios/trap"

    def run_slice(self, state: VMState, steps: int) -> VMState:
        nbytes = vms.state_nbytes(state)
        stacked = VMState(*[vms.stack1(x) for x in state])
        self.h2d += 1
        self.h2d_bytes += nbytes
        if self.obs is not None:
            stacked, found = self.obs_schedule(stacked)
            out, aux = self.obs_execute(stacked, steps, found)
            self.op_hist += np.asarray(aux.op_hist)
            n_exec = aux.kernel_steps
            n_bailed = int(np.asarray(aux.bailed))
            bail_h = np.asarray(aux.bail_hist)
        else:
            out, _, n_exec, bailed, bail_op = self.run_slice_batched_aux(
                stacked, steps
            )
            n_exec = n_exec[0]
            n_bailed = int(np.asarray(bailed)[0])
            bail_h = None
        host = VMState(*[np.array(x[0]) for x in out])
        self.d2h += 1
        self.d2h_bytes += nbytes
        kernel_steps = int(np.asarray(n_exec))
        self.kernel_steps += kernel_steps
        self.fallback_steps += int(host.steps) - int(state.steps) - kernel_steps
        if n_bailed:
            self.bailouts += n_bailed
            if bail_h is None:
                word = self._bail_word(int(np.asarray(bail_op)[0]))
                self.bail_hist[word] = self.bail_hist.get(word, 0) + 1
            else:
                for code in np.flatnonzero(bail_h):
                    word = self._bail_word(int(code))
                    self.bail_hist[word] = (
                        self.bail_hist.get(word, 0) + int(bail_h[code])
                    )
        return host


class OracleExecutor:
    """Plain-Python reference interpreter (no device, no transfers)."""

    backend = "oracle"

    def __init__(self, cfg: VMConfig, isa: ISA | None = None, obs=None):
        self.cfg = cfg
        from repro.core.vm.oracle import Oracle
        from repro.obs.metrics import normalize_obs
        self.oracle = Oracle(cfg, isa)
        self.obs = normalize_obs(obs)
        self.op_hist = None
        if self.obs is not None:
            from repro.obs.metrics import n_bins
            self.op_hist = np.zeros(n_bins(self.oracle.isa), np.int64)
        self.h2d = 0
        self.d2h = 0
        self.h2d_bytes = 0
        self.d2h_bytes = 0

    def run_slice(self, state: VMState, steps: int) -> VMState:
        if self.op_hist is not None:
            from repro.obs.metrics import classify_host
            num_ops = self.oracle.num_ops

            def hook(pc_ok, instr):
                self.op_hist[classify_host(pc_ok, instr, num_ops)] += 1

            self.oracle.step_hook = hook
            try:
                state, _ = self.oracle.run_slice(state, steps)
            finally:
                self.oracle.step_hook = None
            return state
        state, _ = self.oracle.run_slice(state, steps)
        return state


class OracleFleetExecutor:
    """Host-driven fleet slice over the plain-Python Oracle.

    The fourth fleet backend (``FleetVM(executor="oracle")``): each round
    pulls the stacked state to host, runs every node's micro-slice through
    the reference interpreter, and restacks — slow by construction, but it
    makes the Oracle a first-class fleet citizen so ``FleetVM.metrics()``
    can be compared across all four executors (and gives tests a fleet
    whose counters come from the operational specification itself).  Like
    the trace engine it is ``host_driven``: the post-slice layers (clock,
    router, warp) stay jitted in ``FleetKernels``.
    """

    backend = "oracle"
    host_driven = True

    def __init__(self, cfg: VMConfig, isa: ISA | None = None, mesh=None):
        self.cfg = cfg
        from repro.core.vm.interp import interp_for
        from repro.core.vm.oracle import Oracle
        self.oracle = Oracle(cfg, isa)
        self.interp = interp_for(cfg, isa)

    @staticmethod
    def _host_nodes(S: VMState):
        import jax
        host = jax.device_get(S)
        N = host.pc.shape[0]
        return [VMState(*[np.array(f[i]) for f in host]) for i in range(N)]

    @staticmethod
    def _restack(states: list[VMState]):
        import jax.numpy as jnp
        stacked = vms.stack_states(states)
        return VMState(*[jnp.asarray(x) for x in stacked])

    def run_slice_batched(self, S: VMState, steps: int):
        import jax.numpy as jnp
        states = self._host_nodes(S)
        founds = np.zeros(len(states), bool)
        for i, st in enumerate(states):
            states[i], founds[i] = self.oracle.run_slice(st, steps)
        return self._restack(states), jnp.asarray(founds)

    def run_slice_exec_batched(self, S: VMState, steps: int):
        """Executive micro-slice through the reference interpreter."""
        import jax.numpy as jnp
        states = self._host_nodes(S)
        n = len(states)
        founds = np.zeros(n, bool)
        switched = np.zeros(n, np.int32)
        preempted = np.zeros(n, np.int32)
        for i, st in enumerate(states):
            states[i], founds[i], switched[i], preempted[i] = (
                self.oracle.run_slice_exec(st, steps)
            )
        return (
            self._restack(states),
            jnp.asarray(founds),
            jnp.asarray(switched),
            jnp.asarray(preempted),
        )

    # -- observability -------------------------------------------------------

    def ensure_obs(self):
        if hasattr(self, "obs_schedule"):
            return
        self.obs_schedule = self._obs_schedule
        self.obs_execute = self._obs_execute

    def _obs_schedule(self, S: VMState):
        import jax.numpy as jnp
        states = self._host_nodes(S)
        founds = np.zeros(len(states), bool)
        for i, st in enumerate(states):
            states[i], founds[i] = self.oracle.schedule(st)
        # Keep the slice on host between phases (avoids a useless restack/
        # re-pull round trip); obs_execute accepts either representation.
        self._staged = states
        return states, jnp.asarray(founds)

    def _obs_execute(self, states, steps: int, found):
        from repro.obs.metrics import classify_host, n_bins, zero_exec_aux
        import jax.numpy as jnp

        if isinstance(states, VMState):       # called without obs_schedule
            states = self._host_nodes(states)
        oracle = self.oracle
        num_ops = oracle.num_ops
        hist = np.zeros(n_bins(oracle.isa), np.int64)

        def hook(pc_ok, instr):
            hist[classify_host(pc_ok, instr, num_ops)] += 1

        iow0 = iow1 = 0
        oracle.step_hook = hook
        try:
            for i, st in enumerate(states):
                iow0 += int((st.tstatus == ST_IOWAIT).sum())
                # schedule already ran; vmloop only advances a task the
                # scheduler actually woke (tstatus[cur] == ST_RUN).
                st = oracle.vmloop(st, steps)
                if int(st.tstatus[int(st.cur)]) == ST_RUN:
                    st.tstatus[int(st.cur)] = ST_YIELD
                iow1 += int((st.tstatus == ST_IOWAIT).sum())
                states[i] = st
        finally:
            oracle.step_hook = None
        self._staged = None
        aux = zero_exec_aux(oracle.isa)._replace(
            op_hist=jnp.asarray(hist.astype(np.int32)),
            io_susp=jnp.int32(iow1 - iow0),
        )
        return self._restack(states), aux


# Frontend-selectable single-VM backends (REXAVM(backend=...)); the fleet
# additionally accepts "batched" for its default vmapped engine.
VM_BACKENDS = ("jit", "oracle", "pallas", "trace")


def make_executor(
    backend: str, cfg: VMConfig, isa: ISA | None = None, obs=None
) -> Executor:
    """``obs`` (None | bool | ObsConfig) turns on per-slice counting: the
    executor accumulates a numpy ``op_hist`` retirement histogram across
    ``run_slice`` calls.  Off (the default) adds zero device outputs."""
    if backend == "jit":
        return JitExecutor(cfg, isa, obs=obs)
    if backend == "oracle":
        return OracleExecutor(cfg, isa, obs=obs)
    if backend == "pallas":
        return PallasSliceExecutor(cfg, isa, obs=obs)
    if backend == "trace":
        from repro.core.vm.trace import TraceJitExecutor
        return TraceJitExecutor(cfg, isa, obs=obs)
    raise ValueError(
        f"unknown VM backend {backend!r}: valid backends are "
        + ", ".join(repr(b) for b in VM_BACKENDS)
    )
