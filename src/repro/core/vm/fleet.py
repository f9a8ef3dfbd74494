"""Device-resident VM fleet runtime — N cooperating REXAVM nodes, one executor.

The paper's end state is a *distributed sensor network* of VM nodes
exchanging active messages (§2, §3.4).  The seed repo could only run one
``REXAVM`` through a host loop that copied the whole machine state
host<->device every micro-slice; this module turns that into a fleet:

  * ``FleetVM`` holds N heterogeneous node states as ONE stacked
    :class:`~repro.core.vm.vmstate.VMState` with a leading node axis.  The
    stack lives on the device — and, given a mesh, is *partitioned* across
    it: a ``NamedSharding`` over the ``"node"`` mesh axis (wired through
    ``sharding.rules.make_fleet_rules`` + ``sharding.api.logical_leading``)
    splits the fleet so thousand-node networks span devices.  Whole rounds
    run jitted; host IO is serviced by gathering only the suspended nodes'
    slices (:class:`~repro.core.vm.ios.FleetIOService`).
  * The round is three layers:  (1) the vmapped per-node slice
    (:class:`~repro.core.vm.executor.BatchedSliceExecutor` — embarrassingly
    parallel, zero cross-shard traffic);  (2) on-device ``send``/``receive``
    routing through per-node mailbox rings
    (:mod:`repro.core.vm.routing` — under sharding, the mailbox exchange is
    a node-axis collective gather/scatter);  (3) the virtual clock advance +
    time warp (elementwise per node).  A full destination mailbox applies
    backpressure (the sender stays suspended and retries next round); an
    out-of-range destination drops the message.
  * ``reference_round`` is the operational specification: the same round
    semantics over N *independent* ``REXAVM`` instances exchanging messages
    via the host.  tests/test_vm_fleet.py asserts byte-exact state equality
    between the two — sharded or not — the fleet-level restatement of the
    paper's software/hardware equivalence claim.

Round semantics (mirrors ``REXAVM.run``, applied per node, lockstep):

  1. one micro-slice per node (``schedule -> vmloop -> preempt``);
  2. virtual clock advance: ``now += max(1, executed * us_per_instr // 1000)``;
  3. message routing: all sends in (node, task) order, then all receives;
  4. virtual-time warp to the earliest wake-up for nodes with no runnable
     task, no routing progress and no IO suspension.

The ensemble (paper §3.4 Parallel VM) is the degenerate fleet case: replicas
of one program along the node axis with voting instead of routing — see
:class:`repro.core.vm.ensemble.EnsembleVM`.
"""

from __future__ import annotations

import functools
import warnings
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import VMConfig
from repro.core.vm.machine import REXAVM
from repro.core.vm.spec import (
    ISA,
    ST_DONE,
    ST_ERR,
    ST_EVENT,
    ST_FREE,
    ST_HALT,
    ST_IOWAIT,
    ST_SLEEP,
    ST_YIELD,
    get_isa,
)
from repro.core.vm import vmstate as vms
from repro.core.vm.vmstate import VMState

I32 = jnp.int32
_I32_MAX = np.iinfo(np.int32).max


# ---------------------------------------------------------------------------
# Jitted fleet kernels (shared per VMConfig, like get_interpreter)
# ---------------------------------------------------------------------------

class FleetKernels:
    """Batched slice + routing + clock for one (VMConfig, ISA, mesh) triple.

    The round is composed from the three refactored layers:

    ``executor``       — the batched slice engine:
                         :class:`BatchedSliceExecutor` (vmapped lax
                         interpreter — also the ensemble's lockstep
                         executor; ``batched_slice`` is its jitted form) or
                         :class:`PallasSliceExecutor` (the on-chip Pallas
                         vmloop kernel with a lax tail for unclaimed
                         opcodes, ``executor="pallas"``);
    ``route``          — :func:`repro.core.vm.routing.build_router`: the
                         on-device mailbox collective;
    ``round``          — one full fleet round (slice, clock, routing, warp),
                         pure JAX, state in / state out, device resident
                         (``round_aux`` additionally reports the Pallas
                         kernel's per-node step counts, bail-outs, and the
                         per-opcode bail histogram);
    ``rounds_aux``     — the *message-bound round mode*: ``n_rounds`` whole
                         rounds fused into one jitted ``lax.fori_loop``
                         (kernel slice -> router -> warp per iteration), so
                         a send/receive-bound fleet ping-pongs between the
                         kernel and the collective router without the host
                         in the loop; ``FleetVM.run(service_every=k)``
                         drives it in chunks of ``k``.

    With a mesh, every layer boundary re-asserts the node-axis partition via
    the logical-rules layer, so XLA keeps per-node work shard-local and only
    the mailbox exchange crosses shards (the Pallas kernel runs under
    ``shard_map``, seeing only the local node shard).
    """

    def __init__(
        self,
        cfg: VMConfig,
        isa: ISA | None = None,
        mesh=None,
        executor: str = "batched",
        executive=None,
        elide_checks: bool = False,
    ):
        self.cfg = cfg
        self.isa = isa or get_isa()
        self.mesh = mesh
        self.executor_kind = executor
        self.executive = executive     # ExecutiveConfig | None
        # Static-verifier fast path: compile the batched/pallas slice
        # engines without per-step stack checks.  Sound only when every
        # program in the fleet passed repro.analysis (FleetVM's auto mode
        # enforces that); the trace/oracle engines always keep checks.
        self.elide_checks = bool(elide_checks)
        if executor == "pallas":
            from repro.core.vm.executor import PallasSliceExecutor
            self.executor = PallasSliceExecutor(
                cfg, isa, mesh=mesh, elide_checks=self.elide_checks
            )
        elif executor == "batched":
            from repro.core.vm.executor import BatchedSliceExecutor
            self.executor = BatchedSliceExecutor(
                cfg, isa, elide_checks=self.elide_checks
            )
        elif executor == "trace":
            from repro.core.vm.trace import TraceJitExecutor
            self.executor = TraceJitExecutor(cfg, isa, mesh=mesh)
        elif executor == "oracle":
            from repro.core.vm.executor import OracleFleetExecutor
            self.executor = OracleFleetExecutor(cfg, isa, mesh=mesh)
        else:
            raise ValueError(
                f"unknown fleet executor {executor!r}: valid executors are "
                "'batched', 'oracle', 'pallas', 'trace'"
            )
        self.interp = self.executor.interp
        self._obs_kernels = None
        self._build()
        self._build_exec()

    def _build(self):
        cfg = self.cfg
        from repro.core.vm.routing import build_router

        batched_slice = self.executor.run_slice_batched
        self.batched_slice = batched_slice
        aux_slice = getattr(self.executor, "run_slice_batched_aux", None)
        route = build_router(cfg, self.isa)
        self.route = route

        if self.mesh is not None:
            from repro.sharding.api import logical_leading, logical_rules
            from repro.sharding.rules import make_fleet_rules
            rules = make_fleet_rules(self.mesh, self.mesh.axis_names[0])

            def constrain(S: VMState) -> VMState:
                with logical_rules(rules):
                    return logical_leading(S, "node")
        else:
            def constrain(S: VMState) -> VMState:
                return S

        self._constrain = constrain

        def warp_fn(S: VMState, progress):
            with jax.named_scope("fleet.warp"):
                # Virtual-time warp to the earliest wake-up (REXAVM.run
                # step 4).
                runnable = (S.tstatus == ST_YIELD).any(axis=1)
                iowait = (S.tstatus == ST_IOWAIT).any(axis=1)
                waiting = (S.tstatus == ST_SLEEP) | (S.tstatus == ST_EVENT)
                wake = jnp.min(
                    jnp.where(waiting, S.timeout, _I32_MAX), axis=1
                ).astype(I32)
                warp = (
                    (~runnable)
                    & (~progress)
                    & (~iowait)
                    & waiting.any(axis=1)
                    & (wake > S.now)
                )
                return constrain(S._replace(now=jnp.where(warp, wake, S.now)))

        self._warp_fn = warp_fn

        def post_slice(S: VMState, steps0):
            with jax.named_scope("fleet.router"):
                # Virtual clock from the calibrated per-instruction time
                # (REXAVM.run step 2, per node).
                inc = jnp.maximum(
                    1, (S.steps - steps0) * cfg.us_per_instr // 1000
                )
                S = S._replace(now=S.now + inc)
                S, progress = route(constrain(S))
            return warp_fn(S, progress)

        self._post_slice = post_slice

        if getattr(self.executor, "host_driven", False):
            # Trace-JIT engine: the slice itself is host-orchestrated (a
            # per-slice probe groups nodes by program and applies compiled
            # traces), so the round cannot be one jitted function.  The
            # post-slice layers (clock, routing, warp) stay jitted; the
            # sharding constraint lives inside them, where it is legal.
            executor = self.executor
            post = jax.jit(post_slice)

            def fleet_round_host(S: VMState, steps: int):
                steps0 = S.steps
                S, _ = executor.run_slice_batched(S, steps)
                return post(S, steps0)

            self.round = fleet_round_host
            self.round_aux = None
            self.rounds_aux = None
            return

        def fleet_round(S: VMState, steps: int):
            S = constrain(S)
            steps0 = S.steps
            S, _ = batched_slice(S, steps)
            return post_slice(S, steps0)

        self.round = jax.jit(fleet_round, static_argnames=("steps",))

        if aux_slice is not None:
            from jax import lax

            nops = self.isa.num_ops

            def round_body(S: VMState, steps: int):
                S = constrain(S)
                steps0 = S.steps
                S, _, n_exec, bailed, bail_op = aux_slice(S, steps)
                with jax.named_scope("fleet.execute"):
                    # Per-opcode bail histogram: non-bailed nodes carry
                    # bail_op == -1 and add 0 (clipped to slot 0).
                    hist = jnp.zeros(nops + 1, I32).at[
                        jnp.clip(bail_op, 0, nops)
                    ].add(bailed.astype(I32))
                return post_slice(S, steps0), n_exec, bailed, hist

            self.round_aux = jax.jit(round_body, static_argnames=("steps",))

            def fleet_rounds_aux(S: VMState, steps: int, n_rounds: int):
                # Message-bound round mode: whole rounds — kernel slice,
                # collective router, warp — fused into one compiled loop.
                def body(_, carry):
                    S, n_sum, b_sum, hist = carry
                    S, n_exec, bailed, h = round_body(S, steps)
                    return (
                        S,
                        n_sum + n_exec.sum(),
                        b_sum + bailed.sum(),
                        hist + h,
                    )

                init = (
                    S,
                    jnp.int32(0),
                    jnp.int32(0),
                    jnp.zeros(nops + 1, I32),
                )
                return lax.fori_loop(0, n_rounds, body, init)

            self.rounds_aux = jax.jit(
                fleet_rounds_aux, static_argnames=("steps", "n_rounds")
            )
        else:
            self.round_aux = None
            self.rounds_aux = None

    def _build_exec(self):
        """Build ``round_exec`` — one fleet round under the Executive.

        ``ExecutiveConfig.slices`` micro-slices of ``quantum`` instructions
        each (priority schedule -> vmloop -> preempt per sub-slice), then
        the ordinary post-slice (clock once per round, router, warp).  The
        uniform return is ``(S, task_switches, preemptions, kernel_steps,
        bailed, bail_hist)`` — zeros where an engine has no kernel
        telemetry — so ``FleetVM.run`` accumulates one way for all four
        executors.  ``None`` when no Executive was configured: the plain
        round is untouched.
        """
        ecfg = self.executive
        if ecfg is None:
            self.round_exec = None
            return
        from jax import lax

        nops = self.isa.num_ops
        q, k = ecfg.quantum, ecfg.slices
        constrain = self._constrain
        post_slice = self._post_slice
        ex = self.executor

        if getattr(ex, "host_driven", False):
            # Trace/oracle engines orchestrate each micro-slice from the
            # host; the post-slice layers stay jitted.
            post = jax.jit(post_slice)

            def round_exec_host(S: VMState):
                steps0 = S.steps
                sw = jnp.int32(0)
                pe = jnp.int32(0)
                for _ in range(k):
                    S, _, sw_i, pe_i = ex.run_slice_exec_batched(S, q)
                    sw = sw + sw_i.sum()
                    pe = pe + pe_i.sum()
                S = post(S, steps0)
                return S, sw, pe, jnp.int32(0), jnp.int32(0), jnp.zeros(
                    nops + 1, I32
                )

            self.round_exec = round_exec_host
            return

        if self.executor_kind == "pallas":
            exec_aux = ex.run_slice_exec_batched_aux

            def sub_slice(S: VMState):
                S, _, sw, pe, n_exec, bailed, bail_op = exec_aux(S, q)
                with jax.named_scope("fleet.execute"):
                    hist = jnp.zeros(nops + 1, I32).at[
                        jnp.clip(bail_op, 0, nops)
                    ].add(bailed.astype(I32))
                return (
                    S,
                    sw.sum(),
                    pe.sum(),
                    n_exec.sum().astype(I32),
                    bailed.astype(I32).sum(),
                    hist,
                )
        else:
            ex.ensure_exec()
            exec_b = ex.run_slice_exec_batched

            def sub_slice(S: VMState):
                S, _, sw, pe = exec_b(S, q)
                return (
                    S,
                    sw.sum(),
                    pe.sum(),
                    jnp.int32(0),
                    jnp.int32(0),
                    jnp.zeros(nops + 1, I32),
                )

        def round_exec(S: VMState):
            S = constrain(S)
            steps0 = S.steps

            def body(_, carry):
                S, sw_s, pe_s, ne_s, bl_s, hist_s = carry
                S, sw, pe, ne, bl, hist = sub_slice(S)
                return (
                    S, sw_s + sw, pe_s + pe, ne_s + ne, bl_s + bl,
                    hist_s + hist,
                )

            init = (
                S, jnp.int32(0), jnp.int32(0), jnp.int32(0), jnp.int32(0),
                jnp.zeros(nops + 1, I32),
            )
            S, sw, pe, ne, bl, hist = lax.fori_loop(0, k, body, init)
            S = post_slice(S, steps0)
            return S, sw, pe, ne, bl, hist

        self.round_exec = jax.jit(round_exec)

    def obs(self) -> "_ObsKernels":
        """Lazy phased-round kernels for the observability plane.

        The obs round is the same round split at its phase seams so the
        fleet can trace/count each phase: executor ``obs_schedule`` +
        ``obs_execute`` (slice), then ``clock_route`` (virtual clock + the
        obs router, returning per-node clock increments, message drops and
        the mailbox high-watermark), ``warp`` (the identical warp tail) and
        ``accum`` (fold one round's measurements into the device-resident
        :class:`~repro.obs.metrics.ObsCounters`).  clock_route + warp
        compose to exactly ``post_slice`` — byte-exactness is inherited,
        not re-proven.  Built on first use: obs-off fleets never trace
        these."""
        if self._obs_kernels is None:
            from repro.core.vm.routing import build_router
            from repro.obs.metrics import ObsCounters

            cfg = self.cfg
            constrain = self._constrain
            warp_fn = self._warp_fn
            route_obs = build_router(cfg, self.isa, obs=True)

            def clock_route(S: VMState, steps0):
                with jax.named_scope("fleet.router"):
                    inc = jnp.maximum(
                        1, (S.steps - steps0) * cfg.us_per_instr // 1000
                    )
                    S = S._replace(now=S.now + inc)
                    S, progress, (drops, depth) = route_obs(constrain(S))
                return S, inc, drops, depth, progress

            def accum(acc: ObsCounters, aux, inc, drops, depth, deadline_ms):
                # Virtual-clock deadline: a node misses when its round's
                # clock increment exceeds the budget — a pure function of
                # executed instructions, so byte-exact across executors.
                miss = ((inc > deadline_ms) & (deadline_ms > 0)).astype(I32)
                return ObsCounters(
                    op_retired=acc.op_retired + aux.op_hist,
                    mbox_high=jnp.maximum(acc.mbox_high, depth),
                    mbox_drops=acc.mbox_drops + drops,
                    io_susp=acc.io_susp + aux.io_susp,
                    deopts=acc.deopts + aux.deopts,
                    deadline_miss=acc.deadline_miss + miss,
                    rounds=acc.rounds + 1,
                )

            def post(S: VMState, steps0, acc: ObsCounters, aux, deadline_ms):
                # Fused clock_route + warp + accum: the untraced obs round
                # pays one dispatch for the whole post-slice, not three —
                # the per-phase kernels exist for span tracing only.
                S, inc, drops, depth, progress = clock_route(S, steps0)
                S = warp_fn(S, progress)
                return S, accum(acc, aux, inc, drops, depth, deadline_ms)

            ex = self.executor
            if not getattr(ex, "host_driven", False):
                # Pure-jax executors (batched, pallas): the whole untraced
                # obs round — schedule + slice + post — as ONE dispatch,
                # matching the plain round's dispatch count so counters
                # cost compute, not call overhead.  Host-driven executors
                # (oracle, trace) keep the phased fallback.
                def round1(S: VMState, acc: ObsCounters, deadline_ms,
                           steps: int):
                    steps0 = S.steps
                    S, found = ex.obs_schedule(S)
                    S, aux = ex.obs_execute(S, steps, found)
                    S, acc = post(S, steps0, acc, aux, deadline_ms)
                    return S, acc, aux

                round1 = jax.jit(round1, static_argnames=("steps",))
            else:
                round1 = None

            self._obs_kernels = _ObsKernels(
                clock_route=jax.jit(clock_route),
                warp=jax.jit(warp_fn),
                accum=jax.jit(accum),
                post=jax.jit(post),
                round1=round1,
            )
        return self._obs_kernels


class _ObsKernels:
    """Jitted phase kernels of the obs round (see ``FleetKernels.obs``)."""

    def __init__(self, clock_route, warp, accum, post, round1=None):
        self.clock_route = clock_route
        self.warp = warp
        self.accum = accum
        self.post = post
        self.round1 = round1


@functools.lru_cache(maxsize=8)
def _get_fleet_kernels(
    cfg: VMConfig, mesh, executor: str, executive, elide_checks: bool
) -> FleetKernels:
    return FleetKernels(
        cfg, mesh=mesh, executor=executor, executive=executive,
        elide_checks=elide_checks,
    )


def get_fleet_kernels(
    cfg: VMConfig,
    mesh=None,
    executor: str = "batched",
    executive=None,
    elide_checks: bool = False,
) -> FleetKernels:
    """Fleet kernels are expensive to trace — share per (VMConfig, mesh,
    executor, executive, elide_checks).  Normalizes the optional mesh so
    ``f(cfg)`` and ``f(cfg, None)`` hit the same cache entry (EnsembleVM and
    FleetVM must share kernels).  ``executive`` (a frozen
    ``ExecutiveConfig``) keys the Executive round variant like any other
    compiled artifact; ``elide_checks`` keys the verified-program fast-path
    build (a distinct kernel, so checked and elided fleets coexist)."""
    return _get_fleet_kernels(cfg, mesh, executor, executive, bool(elide_checks))


# ---------------------------------------------------------------------------
# FleetVM — the batched frontend
# ---------------------------------------------------------------------------

@dataclass
class FleetResult:
    rounds: int
    steps: np.ndarray          # (N,) instructions executed per node
    statuses: list[str]        # task-0 status per node
    outputs: list[str]         # decoded output ring per node


_STATUS_NAME = {
    ST_DONE: "done",
    ST_HALT: "halt",
    ST_ERR: "error",
}


class FleetVM:
    """N heterogeneous VM nodes as one device-resident stacked state.

    Usage::

        fleet = FleetVM(cfg, n=64, mesh=make_node_mesh())
        for i, node in enumerate(fleet.nodes):   # nodes are real REXAVMs
            node.launch(node.load(program_for(i)))
        res = fleet.run(max_rounds=200)
        print(res.outputs[0])

    Nodes are programmed through their ordinary host frontends (``load``,
    ``launch``, ``dios_add``, ``fios_add``); ``run`` stacks the states onto
    the device(s) and keeps them there across rounds.  With ``mesh`` the
    leading node axis is partitioned via ``NamedSharding`` over the mesh's
    node axis (replicated fallback when ``n`` is not divisible).  ``send
    dst`` addresses node ``dst`` by fleet index; messages route on device
    (see module doc).  Host IO (FIOS calls, ``out``/``in``) is detected by a
    cheap per-round status probe and serviced by the partial-state
    :class:`~repro.core.vm.ios.FleetIOService` (``io_mode="partial"``,
    the default) which moves only the suspended nodes' slices; by the
    vectorized syscall plane (``io_mode="vector"`` —
    :class:`~repro.exec.syscalls.VectorSyscallService`, same partial
    movement but ONE batched handler call per distinct syscall number
    instead of one Python callback per node; the default when an
    ``executive`` is set); or by PR 1's full sync+push (``io_mode="full"``,
    kept for byte-count comparison).  ``h2d``/``d2h`` count full-state
    syncs; ``h2d_bytes``/``d2h_bytes`` count all bytes moved either way;
    ``io_h2d_bytes``/``io_d2h_bytes`` count just the IO-service share.

    ``executive`` (an :class:`~repro.exec.executive.ExecutiveConfig`)
    switches the round to the Executive shape: ``slices`` preemptive
    micro-slices of ``quantum`` instructions each, dispatched by the
    priority scheduler (class, then ``prio``, then round-robin rotation),
    with the clock/router/warp tail once per round.  Spawn tasks through
    :class:`~repro.exec.executive.Executive`; telemetry lands in
    ``executive_stats()`` / ``metrics()["executive"]``.

    ``executor`` selects the per-node slice engine: ``"batched"`` (vmapped
    lax interpreter, the default), ``"pallas"`` (the on-chip
    ``kernels/vmloop`` fetch/dispatch/stack kernel; unclaimed opcodes bail
    to a lax tail — see ``pallas_stats()``), or ``"trace"`` (the trace-JIT
    engine: nodes grouped by program hash, hot paths compiled to guarded
    straight-line XLA, guard failures deoptimize into the generic tail —
    see ``trace_stats()``).  All are byte-exact vs ``reference_round``.
    """

    def __init__(
        self,
        cfg: VMConfig | None = None,
        n: int = 2,
        lookup: str = "pht",
        seed: int = 1,
        nodes: list[REXAVM] | None = None,
        mesh=None,
        io_mode: str | None = None,
        executor: str = "batched",
        obs=None,
        executive=None,
    ):
        if nodes is not None:
            assert len(nodes) >= 1
            cfgs = {vm.cfg for vm in nodes}
            if len(cfgs) != 1:
                raise ValueError("fleet nodes must share one VMConfig")
            self.cfg = nodes[0].cfg
            self.nodes = list(nodes)
        else:
            self.cfg = cfg or VMConfig()
            self.nodes = [
                REXAVM(self.cfg, backend="jit", lookup=lookup, seed=seed + i)
                for i in range(n)
            ]
        if executive is not None and obs is not None:
            # The obs plane's phased round and the Executive's sub-sliced
            # round are distinct round shapes; composing them is a ROADMAP
            # follow-up, not a silent half-measure.
            raise ValueError(
                "executive and obs are mutually exclusive; Executive "
                "counters are reported via metrics()['executive'] instead"
            )
        self.executive = executive      # ExecutiveConfig | None
        if io_mode is None:
            # Executive fleets default to the batched syscall plane.
            io_mode = "vector" if executive is not None else "partial"
        if io_mode not in ("partial", "full", "vector"):
            raise ValueError(f"unknown io_mode {io_mode!r}")
        self.io_mode = io_mode
        self.n = len(self.nodes)
        self.mesh = mesh
        self._sharding = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            ndev = int(np.prod(mesh.devices.shape))
            # Non-divisible fleets replicate (same rule as logical()).
            spec = (
                PartitionSpec(mesh.axis_names[0])
                if self.n % ndev == 0
                else PartitionSpec()
            )
            if self.n % ndev:
                warnings.warn(
                    f"{self.n} nodes do not divide over {ndev} devices: the "
                    f"whole fleet state is replicated on every device",
                    stacklevel=2,
                )
            self._sharding = NamedSharding(mesh, spec)
        isa = self.nodes[0].isa
        if any(vm.isa is not isa for vm in self.nodes):
            raise ValueError("fleet nodes must share one ISA")
        # executor="auto": the Auditor (repro.analysis) picks the engine at
        # start()/push() time from the verified static footprint of the
        # loaded programs; until then run the safe default with checks on.
        self.executor_requested = executor
        self._auto = executor == "auto"
        self._elide = False
        self._analysis = None          # BackendPlan | None (auto mode)
        self._node_reports = None      # list[ProgramReport] | None
        from repro.analysis.memo import VerdictMemo
        self._verdicts = VerdictMemo()  # verdicts reused across start()s
        if self._auto:
            executor = "batched"
        self.kernels = self._make_kernels(executor, False)
        self.executor_kind = executor
        self._op_send = isa.opcode["send"]
        self._op_recv = isa.opcode["receive"]
        self._S: VMState | None = None     # device-resident stacked state
        if io_mode == "vector":
            from repro.exec.syscalls import VectorSyscallService
            self.io_service = VectorSyscallService(self.nodes)
        else:
            from repro.core.vm.ios import FleetIOService
            self.io_service = FleetIOService(self.nodes)
        self.h2d = 0                       # full-state host -> device syncs
        self.d2h = 0                       # full-state device -> host syncs
        self.h2d_bytes = 0                 # all bytes host -> device
        self.d2h_bytes = 0                 # all bytes device -> host
        self.probes = 0                    # small status probes (tstatus/io_op)
        # Pallas-executor telemetry (device-side lazy accumulators so the
        # round loop stays async; see pallas_stats()).
        self._kernel_steps_acc = 0         # instrs retired inside the kernel
        self._bailed_acc = 0               # node-rounds that hit a bail-out
        self._bail_hist_acc = 0            # (num_ops+1,) per-opcode bail counts
        self._total_steps_acc = 0          # instrs executed across run()s
        # Trace-executor telemetry: the engine's counters are monotonic and
        # shared (kernels are lru-cached), so remember this fleet's baseline
        # and report deltas (see trace_stats()).
        self._trace0 = (
            self.kernels.executor.stats() if executor == "trace" else None
        )
        self._trace_steps_total = 0        # instrs executed across run()s
        self.rounds_total = 0              # fleet rounds across run()s
        # Executive telemetry (device-side lazy accumulators like the
        # pallas ones; see executive_stats()).
        self._task_switches_acc = 0        # dispatches to a different slot
        self._preempts_acc = 0             # quanta exhausted while ST_RUN
        self._exec_slices = 0              # Executive micro-slices driven
        self._spawns_admitted = 0          # Executive.spawn admissions
        self._spawns_rejected = 0
        # Sticky per-(node, task-slot) deadline-miss flags, cleared when a
        # slot frees; total counts each occupancy's first miss once.
        self._deadline_missed = np.zeros(
            (self.n, self.cfg.max_tasks), bool
        )
        self._task_deadline_miss_total = 0
        # Observability plane (repro.obs): fully off by default — no extra
        # device outputs, no per-phase syncs, nothing accumulated.  The
        # span tracer always annotates the profiler; ObsConfig(trace=True)
        # adds its ring.
        from repro.obs.metrics import normalize_obs
        from repro.obs.tracing import RoundTracer
        self.obs = normalize_obs(obs)
        self._counters = None              # device ObsCounters (lazy adds)
        self._deadline = None
        self._tracer = (
            RoundTracer(ring=self.obs.trace_ring, enabled=self.obs.trace)
            if self.obs is not None else RoundTracer(enabled=False)
        )
        self.io_service.tracer = self._tracer
        if self.obs is not None:
            from repro.obs.deadline import DeadlineMonitor
            from repro.obs.metrics import zero_counters
            self._counters = zero_counters(self.n, isa)
            self._deadline = DeadlineMonitor(self.obs.deadline_wall_ms)
            # Attach the executor's counting engine (a no-op if another
            # fleet sharing these cached kernels already did).
            self.kernels.executor.ensure_obs()

    @classmethod
    def from_nodes(cls, nodes: list[REXAVM], **kw) -> "FleetVM":
        """Stack pre-configured REXAVM nodes into one fleet."""
        return cls(nodes=nodes, **kw)

    # -- transfer accounting ---------------------------------------------------

    @property
    def io_h2d_bytes(self) -> int:
        """IO-service bytes host -> device (partial mode only)."""
        return self.io_service.h2d_bytes

    @property
    def io_d2h_bytes(self) -> int:
        """IO-service bytes device -> host (partial mode only)."""
        return self.io_service.d2h_bytes

    def pallas_stats(self) -> dict:
        """Kernel-executor telemetry: instructions retired inside the
        Pallas vmloop vs. the lax tail (zeros under the batched executor).

        ``bailed_frac`` is the fraction of executed instructions that fell
        to the lax tail; ``bail_hist`` maps each bailing word (``task``,
        ``rnd``, or ``fios/trap``) to how many node-rounds it bailed —
        coverage gaps are observable, not inferred."""
        kernel = int(self._kernel_steps_acc)
        total = int(self._total_steps_acc)
        fallback = max(total - kernel, 0)
        isa = self.kernels.isa
        hist = np.asarray(self._bail_hist_acc)
        bail_hist: dict[str, int] = {}
        if hist.ndim:                      # still 0 before any pallas round
            for code in np.flatnonzero(hist):
                word = (
                    isa.name[int(code)]
                    if int(code) < isa.num_ops
                    else "fios/trap"
                )
                bail_hist[word] = bail_hist.get(word, 0) + int(hist[code])
        return {
            "executor": self.executor_kind,
            "kernel_steps": kernel,
            "fallback_steps": fallback,
            "total_steps": total,
            "bailed_frac": fallback / total if total else 0.0,
            "bailed_node_rounds": int(self._bailed_acc),
            "bail_hist": bail_hist,
            # Executive micro-slices the kernel engine drove (zero under
            # every other executor and when no Executive is configured).
            "exec_slices": (
                int(self._exec_slices) if self.executor_kind == "pallas" else 0
            ),
        }

    def trace_stats(self) -> dict:
        """Trace-executor telemetry (zeros under other executors): traces
        recorded/compiled, guard exits (deopts into the generic tail), and
        the fraction of executed instructions that ran specialized —
        counted since this fleet was created, across its run()s."""
        if self._trace0 is None:
            # Schema-stable under every executor: same keys, zeroed.
            return {
                "executor": self.executor_kind,
                "traces_recorded": 0,
                "traces_compiled": 0,
                "spec_steps": 0,
                "guard_exits": 0,
                "total_steps": 0,
                "specialized_frac": 0.0,
                "groups": {},
                "exec_slices": 0,
            }
        now = self.kernels.executor.stats()
        base = self._trace0
        spec = now["spec_steps"] - base["spec_steps"]
        total = self._trace_steps_total
        return {
            "executor": self.executor_kind,
            "traces_recorded": now["traces_recorded"] - base["traces_recorded"],
            "traces_compiled": now["traces_compiled"] - base["traces_compiled"],
            "spec_steps": spec,
            "guard_exits": now["guard_exits"] - base["guard_exits"],
            "total_steps": total,
            "specialized_frac": spec / total if total else 0.0,
            "groups": now["groups"],
            # Executive micro-slices this (trace) engine drove.
            "exec_slices": int(self._exec_slices),
        }

    def executive_stats(self) -> dict:
        """Executive + syscall-plane telemetry, schema-stable: the same
        keys come back zeroed when no Executive is configured and under
        the per-node ``FleetIOService`` (where ``svc_batches`` has no
        meaning).  ``task_switches``/``preemptions`` are the device-side
        accumulators of the Executive round; ``task_deadline_misses``
        counts each task-slot occupancy's first virtual-clock deadline
        miss; ``svc_batches`` vs ``svc_scalar_calls`` is the vectorized-
        service proof (one handler call per distinct syscall per service,
        not one Python callback per node)."""
        svc = self.io_service
        ecfg = self.executive
        return {
            "executor": self.executor_kind,
            "enabled": ecfg is not None,
            "quantum": int(ecfg.quantum) if ecfg else 0,
            "slices_per_round": int(ecfg.slices) if ecfg else 0,
            "exec_slices": int(self._exec_slices),
            "task_switches": int(self._task_switches_acc),
            "preemptions": int(self._preempts_acc),
            "spawns_admitted": int(self._spawns_admitted),
            "spawns_rejected": int(self._spawns_rejected),
            "task_deadline_misses": int(self._task_deadline_miss_total),
            "tasks_missed": int(self._deadline_missed.sum()),
            "syscalls": int(getattr(svc, "syscalls", 0)),
            "svc_batches": int(getattr(svc, "svc_batches", 0)),
            "svc_scalar_calls": int(getattr(svc, "scalar_calls", 0)),
            "svc_posts": int(getattr(svc, "posts", 0)),
            "svc_post_drops": int(getattr(svc, "post_drops", 0)),
        }

    def transfer_stats(self) -> dict:
        """All movement counters in one dict (serve monitor / benchmarks),
        self-describing: ``executor`` and ``rounds`` identify which engine
        moved these bytes over how many fleet rounds."""
        svc = self.io_service
        return {
            "executor": self.executor_kind,
            "rounds": self.rounds_total,
            "h2d": self.h2d,
            "d2h": self.d2h,
            "h2d_bytes": self.h2d_bytes,
            "d2h_bytes": self.d2h_bytes,
            "io_services": svc.services,
            "io_nodes_serviced": svc.nodes_serviced,
            "io_h2d_bytes": svc.h2d_bytes,
            "io_d2h_bytes": svc.d2h_bytes,
            # Syscall-plane shape of the same movement: zeroed under the
            # per-node FleetIOService, populated by VectorSyscallService.
            "io_syscalls": int(getattr(svc, "syscalls", 0)),
            "io_svc_batches": int(getattr(svc, "svc_batches", 0)),
            "probes": self.probes,
        }

    def metrics(self):
        """One schema-stable telemetry snapshot — the unified namespace
        over today's per-backend stats dicts plus the on-device obs
        counters and the round-latency monitor.  Identical key structure
        under every executor and under obs on/off (zeroed where nothing
        was measured); the only device sync is the counter pull, and only
        when obs is on."""
        from repro.obs.deadline import DeadlineMonitor
        from repro.obs.metrics import FleetMetrics, hist_to_dict, n_bins

        isa = self.kernels.isa
        if self._counters is not None:
            c = jax.device_get(self._counters)
            op = np.asarray(c.op_retired)
            miss = np.asarray(c.deadline_miss)
            mbox_high, mbox_drops = int(c.mbox_high), int(c.mbox_drops)
            io_susp, deopts = int(c.io_susp), int(c.deopts)
            rounds_observed = int(c.rounds)
        else:
            op = np.zeros(n_bins(isa), np.int64)
            miss = np.zeros(self.n, np.int64)
            mbox_high = mbox_drops = io_susp = deopts = rounds_observed = 0
        counters = {
            "op_retired": hist_to_dict(op, isa),
            "instructions": int(op.sum()),
            "mbox_high": mbox_high,
            "mbox_drops": mbox_drops,
            "io_susp": io_susp,
            "deopts": deopts,
            "deadline_ms": int(self.obs.deadline_ms) if self.obs else 0,
            "deadline_miss": [int(x) for x in miss],
            "deadline_miss_total": int(miss.sum()),
            "rounds_observed": rounds_observed,
        }
        latency = (
            self._deadline if self._deadline is not None else DeadlineMonitor()
        ).snapshot()
        pallas = self.pallas_stats()
        pallas.pop("executor", None)
        trace = self.trace_stats()
        trace.pop("executor", None)
        transfers = self.transfer_stats()
        transfers.pop("executor", None)
        transfers.pop("rounds", None)
        executive = self.executive_stats()
        executive.pop("executor", None)
        return FleetMetrics(
            executor=self.executor_kind,
            rounds=self.rounds_total,
            counters=counters,
            latency=latency,
            pallas=pallas,
            trace=trace,
            transfers=transfers,
            executive=executive,
        )

    def export_trace(self, path=None):
        """Write the recorded round-phase spans as Chrome trace-event JSON
        (open in chrome://tracing or ui.perfetto.dev).  Requires
        ``obs=ObsConfig(trace=True)``; without it the export is valid but
        empty.  Returns the payload dict."""
        from repro.obs.tracing import export_chrome_trace

        return export_chrome_trace(self._tracer, path)

    # -- static analysis (the Auditor) -----------------------------------------

    def _make_kernels(self, executor: str, elide_checks: bool):
        isa = self.nodes[0].isa
        # The cached kernels are built for the default ISA; a custom-ISA
        # fleet needs its own build (opcode numbering differs).
        if isa is get_isa():
            return get_fleet_kernels(
                self.cfg, self.mesh, executor, self.executive, elide_checks
            )
        return FleetKernels(
            self.cfg, isa, self.mesh, executor, self.executive, elide_checks
        )

    def _analyze_nodes(self):
        """Run the static verifier and the branch-set walk over every
        node's live task entries (host-side, against the states about to
        be stacked), reusing a verdict of this or the last audit where a
        node's entries, tables and decoded code cells match it:
        ``(reports, branch sets per node, nodes verified)``."""
        return self._verdicts.audit(self.nodes)

    def _resolve_auto(self) -> None:
        """executor="auto": verify, pick the backend, and swap kernels.

        Runs at every start()/push() — exactly when host-side compiles or
        incremental code loads land — so the backend decision always
        reflects the program set about to execute.  Programs with verifier
        errors are *not* rejected here (the CLI gate is the reject path);
        they run on the always-checked batched engine.

        Spanned as ``fleet.audit`` with ``nodes`` verified afresh and
        ``reused`` (nodes given a memoized verdict; the two sum to the
        fleet), the plan, and ``switched`` (the kernels were swapped, so
        the next dispatch traces and compiles).
        """
        from repro.analysis.feasibility import plan_backend

        with self._tracer.span("audit") as sp:
            # node_sets: every set the engine will record per node (entry
            # + steady-state loop re-entries, any rotation); the first is
            # the entry-trace compile key.
            reports, node_sets, verified = self._analyze_nodes()
            branch_sets = [sets[0] if sets else None for sets in node_sets]
            plan = plan_backend(reports, branch_sets)
            self._node_reports = reports
            self._analysis = plan
            switched = (plan.executor, plan.elide_checks) != (
                self.executor_kind, self._elide
            )
            if switched:
                self.kernels = self._make_kernels(
                    plan.executor, plan.elide_checks
                )
                self.executor_kind = plan.executor
                self._elide = plan.elide_checks
                if self.obs is not None:
                    self.kernels.executor.ensure_obs()
                if plan.executor == "trace" and self._trace0 is None:
                    self._trace0 = self.kernels.executor.stats()
            if plan.executor == "trace":
                # AOT: compile each predicted branch set now, so the first
                # slice dispatches a warm specialized trace (traces_compiled
                # stops moving during run — the equivalence tests assert it).
                eng = self.kernels.executor.engine
                for sets in node_sets:
                    for bs in sets:
                        eng.fn_for(bs)
            sp.set(nodes=verified, reused=self.n - verified,
                   executor=self.executor_kind, elide_checks=self._elide,
                   switched=switched)

    def analysis_stats(self) -> dict:
        """Auditor telemetry, schema-stable like the other stats planes.

        Under ``executor="auto"`` this reflects the plan of the last
        start()/push(); other executors analyze lazily on first call (a
        host-side snapshot — it never touches device state).
        """
        from repro.analysis.feasibility import bail_words

        if self._node_reports is None:
            self._node_reports = self._analyze_nodes()[0]
        reports = self._node_reports
        plan = self._analysis
        verdicts = {"verified": 0, "flagged": 0, "error": 0}
        for r in reports:
            verdicts[r.verdict] += 1
        predicted = sorted(
            frozenset().union(*(bail_words(r) for r in reports))
            if reports else frozenset()
        )
        return {
            "executor": self.executor_kind,
            "requested": self.executor_requested,
            "auto": self._auto,
            "elide_checks": self._elide,
            "verdicts": verdicts,
            "predicted_bail_words": predicted,
            "wcet": [r.wcet for r in reports],
            "aot_branch_sets": (
                sum(1 for bs in plan.branch_sets if bs is not None)
                if plan else 0
            ),
            "reasons": list(plan.reasons) if plan else [],
            "diagnostics": [
                str(d) for r in reports for d in r.diagnostics
            ][:64],
        }

    # -- state movement --------------------------------------------------------

    def start(self) -> None:
        """Stack per-node host states into the device-resident fleet state
        (sharded over the node mesh axis when a mesh was given).  Under
        ``executor="auto"`` the Auditor runs first: verify the loaded
        programs, resolve the backend, and AOT-compile predicted traces.

        Starts a new read-out id; spanned as ``fleet.start`` around
        ``fleet.audit`` and ``fleet.restack``."""
        from repro.core.vm.vmstate import stack_states

        tr = self._tracer
        tr.next_readout()
        with tr.span("start"):
            if self._auto:
                self._resolve_auto()
            with tr.span("restack") as sp:
                stacked = stack_states([vm.state for vm in self.nodes])
                if self._sharding is not None:
                    self._S = VMState(
                        *[jax.device_put(x, self._sharding)
                          for x in stacked]
                    )
                else:
                    self._S = VMState(*[jnp.asarray(x) for x in stacked])
                self.h2d += 1
                self.h2d_bytes += vms.state_nbytes(stacked)
                if self.executor_kind == "trace":
                    # Refresh the green keys: push()/start() is exactly when
                    # host-side recompiles or incremental code loads land,
                    # and a changed code segment must re-key (content hash)
                    # its trace-cache entries.  Stale keys would still be
                    # byte-safe (per-step CS guards), just slower.
                    from repro.core.vm.trace import program_key
                    self.kernels.executor.set_program_keys(
                        [program_key(vm.state.cs) for vm in self.nodes]
                    )
                sp.set(bytes=vms.state_nbytes(self._S))

    def sync(self) -> None:
        """Pull the stacked state back into the per-node host frontends
        (spanned as ``fleet.sync`` with the bytes copied)."""
        assert self._S is not None, "fleet not started"
        nbytes = vms.state_nbytes(self._S)
        with self._tracer.span("sync", bytes=nbytes):
            host = [np.array(x) for x in self._S]
            for i, vm in enumerate(self.nodes):
                # np.array keeps 0-d fields as mutable 0-d arrays, not
                # scalars.
                vm.state = VMState(*[np.array(f[i]) for f in host])
        self.d2h += 1
        self.d2h_bytes += nbytes

    def push(self) -> None:
        """Re-stack (possibly host-mutated) node states onto the device."""
        self.start()

    # -- execution -------------------------------------------------------------

    def _probe(self):
        """Cheap device->host peek at scheduler-visible state (not a full
        sync); spanned as ``fleet.probe``, which waits for the rounds
        dispatched before it."""
        self.probes += 1
        # One batched fetch: separate np.asarray calls would each block on
        # their own device round trip.  now/deadline ride along for the
        # Executive's deadline-miss accounting (same rows, negligible bytes).
        with self._tracer.span("probe"):
            return jax.device_get(
                (
                    self._S.tstatus,
                    self._S.io_op,
                    self._S.steps,
                    self._S.now,
                    self._S.deadline,
                )
            )

    def _service_host_io(self, node_mask: np.ndarray) -> bool:
        """Service host-IO suspensions of the masked nodes.

        ``partial`` gathers/scatters only those nodes' slices through
        :class:`FleetIOService`; ``vector`` does the same movement but
        executes FIOS suspensions through the batched syscall plane
        (:class:`~repro.exec.syscalls.VectorSyscallService` — one handler
        call per distinct syscall number, not one per node); ``full`` is
        PR 1's whole-state sync + push.
        """
        if self.io_mode in ("partial", "vector"):
            svc = self.io_service
            d2h0, h2d0 = svc.d2h_bytes, svc.h2d_bytes
            self._S, progress = svc.service(
                self._S, np.flatnonzero(node_mask)
            )
            # The headline byte counters include the IO-service share, so
            # partial vs full mode compare like for like.
            self.d2h_bytes += svc.d2h_bytes - d2h0
            self.h2d_bytes += svc.h2d_bytes - h2d0
            return progress
        self.sync()
        progress = False
        for vm in self.nodes:
            progress |= vm._service_io(route_net=False)
        self.push()
        return progress

    def _round_obs(self, steps: int) -> None:
        """One observed fleet round: the phased round (schedule -> execute
        -> clock+router -> warp) plus counter accumulation.

        Stays as async as the plain round — every phase output chains
        lazily and ``accum`` only *adds* device scalars — except when span
        tracing or round timing is on, where each phase (or the round)
        must sync to make its wall time honest.  Untraced rounds on
        pure-jax executors take ``_ObsKernels.round1``: the whole round as
        one dispatch, same count as the plain round."""
        import time as _time

        ob = self.kernels.obs()
        ex = self.kernels.executor
        tr = self._tracer
        cfg_obs = self.obs
        timing = cfg_obs.time_rounds or cfg_obs.deadline_wall_ms > 0
        t0 = _time.perf_counter() if timing else 0.0
        S = self._S
        steps0 = S.steps
        if tr.enabled:
            with tr.span("schedule"):
                S, found = ex.obs_schedule(S)
                jax.block_until_ready(S)
            with tr.span("execute"):
                S, aux = ex.obs_execute(S, steps, found)
                jax.block_until_ready(S)
            with tr.span("router"):
                S, inc, drops, depth, progress = ob.clock_route(S, steps0)
                jax.block_until_ready(S)
            with tr.span("warp"):
                S = ob.warp(S, progress)
                jax.block_until_ready(S)
            self._S = S
            self._counters = ob.accum(
                self._counters, aux, inc, drops, depth, cfg_obs.deadline_ms
            )
        elif ob.round1 is not None:
            S, self._counters, aux = ob.round1(
                S, self._counters, cfg_obs.deadline_ms, steps=steps
            )
            self._S = S
        else:
            S, found = ex.obs_schedule(S)
            S, aux = ex.obs_execute(S, steps, found)
            S, self._counters = ob.post(
                S, steps0, self._counters, aux, cfg_obs.deadline_ms
            )
            self._S = S
        if self.executor_kind == "pallas":
            # pallas_stats() accumulators ride the same ExecAux.
            self._kernel_steps_acc = self._kernel_steps_acc + aux.kernel_steps
            self._bailed_acc = self._bailed_acc + aux.bailed
            self._bail_hist_acc = self._bail_hist_acc + aux.bail_hist
        if timing:
            jax.block_until_ready(self._S)
            self._deadline.record((_time.perf_counter() - t0) * 1e3)

    def run(
        self,
        max_rounds: int = 10_000,
        steps: int | None = None,
        service_every: int = 1,
    ) -> FleetResult:
        """Run whole fleet rounds on device until all nodes finish.

        ``service_every`` controls how often the host probes for pending host
        IO; with pure compute + on-device messaging the state never leaves
        the device between ``start`` and the final ``sync``.  Under the
        pallas executor, ``service_every > 1`` selects the message-bound
        round mode: chunks of ``service_every`` whole rounds (kernel slice +
        collective router + warp each) run as one compiled
        ``FleetKernels.rounds_aux`` loop between host probes.

        Spanned as ``fleet.run``; each round's ``fleet.dispatch`` span
        covers only enqueueing it (JAX dispatch is async; the observed path
        with span tracing or round timing on syncs).  The body stays in
        this frame: JAX traces the round program from here, and on a TPU
        v5e host a deeper Python stack above that call made the trace
        measurably slower (not so on a CPU host).
        """
        steps = steps or self.cfg.steps_per_slice
        if self._S is None:
            self.start()
        tr = self._tracer
        with tr.span("run") as sp:
            steps0 = np.asarray(self._S.steps).copy()
            rounds = 0
            stall = 0
            last_steps_sum = -1
            round_aux = self.kernels.round_aux
            rounds_aux = self.kernels.rounds_aux
            round_exec = self.kernels.round_exec if self.executive else None
            while rounds < max_rounds:
                done = rounds
                with tr.span("dispatch"):
                    if self.obs is not None:
                        # Observed rounds run phased (counters, spans,
                        # deadlines); message-bound chunking is bypassed so
                        # every round is individually accounted.
                        self._round_obs(steps)
                        rounds += 1
                    elif round_exec is not None:
                        # Executive round: ExecutiveConfig.slices preemptive
                        # micro-slices of .quantum instructions (priority
                        # schedule per sub-slice), clock/router/warp once.
                        # Task/kernel telemetry accumulates lazily on device.
                        self._S, sw, pe, ne, bl, hist = round_exec(self._S)
                        self._task_switches_acc = self._task_switches_acc + sw
                        self._preempts_acc = self._preempts_acc + pe
                        self._kernel_steps_acc = self._kernel_steps_acc + ne
                        self._bailed_acc = self._bailed_acc + bl
                        self._bail_hist_acc = self._bail_hist_acc + hist
                        self._exec_slices += self.executive.slices
                        rounds += 1
                    elif rounds_aux is not None and service_every > 1:
                        # Message-bound round mode: probe only at chunk
                        # boundaries.
                        chunk = min(service_every, max_rounds - rounds)
                        self._S, n_sum, b_sum, hist = rounds_aux(
                            self._S, steps, chunk
                        )
                        self._kernel_steps_acc = self._kernel_steps_acc + n_sum
                        self._bailed_acc = self._bailed_acc + b_sum
                        self._bail_hist_acc = self._bail_hist_acc + hist
                        rounds += chunk
                    elif round_aux is not None:
                        self._S, n_exec, bailed, hist = round_aux(self._S, steps)
                        # Lazy device-side sums: no sync until pallas_stats().
                        self._kernel_steps_acc = (
                            self._kernel_steps_acc + n_exec.sum()
                        )
                        self._bailed_acc = self._bailed_acc + bailed.sum()
                        self._bail_hist_acc = self._bail_hist_acc + hist
                        rounds += 1
                    else:
                        self._S = self.kernels.round(self._S, steps)
                        rounds += 1
                tr.tick(rounds - done)
                if rounds % service_every != 0 and rounds < max_rounds:
                    continue
                tstatus, io_op, steps_now, now_v, deadline_v = self._probe()
                if self.executive is not None:
                    # Task-level deadline misses: a live slot whose virtual
                    # clock has run past its (nonzero) deadline.  The sticky
                    # flag counts each slot occupancy's first miss once and
                    # clears when the slot frees.
                    active = tstatus != ST_FREE
                    missed_now = (
                        (deadline_v > 0) & (now_v[:, None] > deadline_v) & active
                    )
                    self._task_deadline_miss_total += int(
                        (missed_now & ~self._deadline_missed).sum()
                    )
                    self._deadline_missed = (
                        self._deadline_missed | missed_now
                    ) & active
                host_io = (
                    (tstatus == ST_IOWAIT)
                    & (io_op != 0)
                    & (io_op != self._op_send)
                    & (io_op != self._op_recv)
                )
                serviced = False
                if host_io.any():
                    serviced = self._service_host_io(host_io.any(axis=1))
                # A node is finished only when task 0 is terminal AND no other
                # task is runnable, waiting, or IO-suspended (REXAVM.run's
                # "done" condition) — background workers keep the fleet alive.
                task0_term = np.isin(tstatus[:, 0], (ST_DONE, ST_HALT, ST_ERR))
                runnable = (tstatus == ST_YIELD).any(axis=1)
                waiting = np.isin(tstatus, (ST_SLEEP, ST_EVENT)).any(axis=1)
                iowait = (tstatus == ST_IOWAIT).any(axis=1)
                if (task0_term & ~runnable & ~waiting & ~iowait).all():
                    break
                steps_sum = int(steps_now.sum())
                if steps_sum == last_steps_sum and not serviced:
                    stall += 1
                    if stall >= 3:
                        break              # fleet-wide deadlock / quiescence
                else:
                    stall = 0
                last_steps_sum = steps_sum
            self.sync()
            with tr.span("decode", nodes=self.n):
                self.rounds_total += rounds
                executed = np.asarray(self._S.steps) - steps0
                self._trace_steps_total += int(executed.sum())
                self._total_steps_acc += int(executed.sum())
                # Host frontends are canonical again; a later run() restacks
                # them.
                self._S = None
                task0 = np.asarray(
                    [int(vm.state.tstatus[0]) for vm in self.nodes]
                )
                res = FleetResult(
                    rounds=rounds,
                    steps=executed,
                    statuses=[_STATUS_NAME.get(s, "running") for s in task0],
                    outputs=[vm.output() for vm in self.nodes],
                )
            sp.set(rounds=rounds)
        return res


# ---------------------------------------------------------------------------
# Host-routed reference (the operational specification of one fleet round)
# ---------------------------------------------------------------------------

_REF_ORACLES: dict = {}


def _reference_oracle(cfg: VMConfig, isa: ISA):
    """Shared plain-Python Oracle for reference_round's Executive mirror
    (the fleet nodes' own executors are typically jit-backed)."""
    from repro.core.vm.oracle import Oracle

    key = (cfg, id(isa))
    if key not in _REF_ORACLES:
        _REF_ORACLES[key] = Oracle(cfg, isa)
    return _REF_ORACLES[key]


def reference_round(
    nodes: list[REXAVM],
    steps: int | None = None,
    obs: dict | None = None,
    executive=None,
) -> list[bool]:
    """One fleet round over independent host-looped REXAVMs.

    Numpy mirror of :meth:`FleetKernels.round`: slice every node, advance its
    virtual clock, route all sends then all receives through the host (same
    (node, task) order, same mailbox rings, same backpressure/drop rules),
    then apply the per-node time warp.  ``FleetVM`` must match this
    byte-exactly (tests/test_vm_fleet.py).  Returns the per-node progress
    flags (mirrors the routing progress vector).

    ``obs``, when given, is a dict the round's router counters accumulate
    into — ``drops`` (messages to out-of-range destinations) and
    ``depth_peak`` (deepest mailbox after the send phase) — the reference
    semantics for ``ObsCounters.mbox_drops``/``mbox_high``; under an
    Executive it additionally grows ``task_switches``/``preemptions``.

    ``executive`` (an :class:`repro.exec.executive.ExecutiveConfig`) mirrors
    :meth:`FleetKernels._build_exec`: ``slices`` preemptive micro-slices of
    ``quantum`` instructions each through the plain-Python Oracle's
    priority scheduler, with the virtual clock advanced ONCE per round from
    the round's total executed instructions.
    """
    cfg = nodes[0].cfg
    isa = nodes[0].isa
    N, T = len(nodes), cfg.max_tasks
    MB, DS = cfg.mbox_size, cfg.ds_size
    op_send, op_recv = isa.opcode["send"], isa.opcode["receive"]
    steps = steps or cfg.steps_per_slice

    if executive is not None:
        oracle = _reference_oracle(cfg, isa)
        for vm in nodes:
            st = vm.state
            before = int(st.steps)
            for _ in range(executive.slices):
                st, found, switched, preempted = oracle.run_slice_exec(
                    st, executive.quantum
                )
                if obs is not None:
                    obs["task_switches"] = (
                        obs.get("task_switches", 0) + int(switched)
                    )
                    obs["preemptions"] = (
                        obs.get("preemptions", 0) + int(preempted)
                    )
            vm.state = st
            executed = int(st.steps) - before
            st.now[...] = int(st.now) + max(
                1, executed * cfg.us_per_instr // 1000
            )
    else:
        for vm in nodes:
            before = int(vm.state.steps)
            vm._slice(steps)
            executed = int(vm.state.steps) - before
            vm.state.now[...] = int(vm.state.now) + max(
                1, executed * cfg.us_per_instr // 1000
            )

    progress = [False] * N
    # Phase 1: all sends, (node, task) order.
    for i, vm in enumerate(nodes):
        st = vm.state
        for t in range(T):
            if int(st.tstatus[t]) != ST_IOWAIT or int(st.io_op[t]) != op_send:
                continue
            dsp = int(st.dsp[t])
            dst = int(st.ds[t, max(dsp - 1, 0)])
            v = int(st.ds[t, max(dsp - 2, 0)])
            if 0 <= dst < N:
                mst = nodes[dst].state
                if int(mst.mbox_wr) - int(mst.mbox_rd) >= MB:
                    continue           # backpressure: sender stays suspended
                slot = int(mst.mbox_wr) % MB
                mst.mbox[2 * slot] = i
                mst.mbox[2 * slot + 1] = v
                mst.mbox_wr[...] = int(mst.mbox_wr) + 1
            elif obs is not None:
                obs["drops"] = obs.get("drops", 0) + 1
            st.dsp[t] = dsp - 2
            st.pc[t] = int(st.pc[t]) + 1
            st.io_op[t] = 0
            st.tstatus[t] = ST_YIELD
            progress[i] = True
    if obs is not None:
        depth = max(
            int(vm.state.mbox_wr) - int(vm.state.mbox_rd) for vm in nodes
        )
        obs["depth_peak"] = max(obs.get("depth_peak", 0), depth)
    # Phase 2: all receives.
    for i, vm in enumerate(nodes):
        st = vm.state
        for t in range(T):
            if int(st.tstatus[t]) != ST_IOWAIT or int(st.io_op[t]) != op_recv:
                continue
            if int(st.mbox_wr) <= int(st.mbox_rd):
                continue               # empty mailbox: stay suspended
            slot = int(st.mbox_rd) % MB
            src, v = int(st.mbox[2 * slot]), int(st.mbox[2 * slot + 1])
            # Same two-sided clamp as the device router's jnp.clip (a negative
            # dsp must not wrap to the top of the numpy array).
            st.ds[t, min(max(int(st.dsp[t]), 0), DS - 1)] = src
            st.ds[t, min(max(int(st.dsp[t]) + 1, 0), DS - 1)] = v
            st.dsp[t] = int(st.dsp[t]) + 2
            st.mbox_rd[...] = int(st.mbox_rd) + 1
            st.pc[t] = int(st.pc[t]) + 1
            st.io_op[t] = 0
            st.tstatus[t] = ST_YIELD
            progress[i] = True
    # Per-node time warp.
    for i, vm in enumerate(nodes):
        st = vm.state
        sts = [int(s) for s in st.tstatus]
        runnable = any(s == ST_YIELD for s in sts)
        iowait = any(s == ST_IOWAIT for s in sts)
        waiting = [k for k, s in enumerate(sts) if s in (ST_SLEEP, ST_EVENT)]
        if not runnable and not progress[i] and not iowait and waiting:
            wake = min(int(st.timeout[k]) for k in waiting)
            if wake > int(st.now):
                st.now[...] = wake
    return progress
