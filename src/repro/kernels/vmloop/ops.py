"""Public vmloop op: stacked ``VMState`` in/out, node-mesh shard_map,
interpret switch.

``fleet_vmloop`` is what :class:`repro.core.vm.executor.PallasSliceExecutor`
calls inside its jitted batched slice: it extracts the kernel-visible
:class:`~repro.kernels.vmloop.ref.CoreState` fields from the stacked fleet
state, dispatches the Pallas kernel, and merges the mutated fields back.

Message-bound round mode: ``send``/``receive`` execute their IO suspension
*in-kernel* (pc rewind + ``io_op`` + ST_IOWAIT) and the collective router
(``core.vm.routing``) runs between kernel invocations — ``FleetVM.run``
with ``service_every > 1`` fuses whole (kernel slice -> route -> warp)
rounds into one jitted ``lax.fori_loop`` (``FleetKernels.rounds_aux``), so
a message-bound ring completes entire rounds without reaching the lax tail
or the host.

Sharding: when the fleet's node axis is mesh-partitioned (PR 2), the kernel
must only ever see the *local shard* — a ``pl.pallas_call`` is opaque to
XLA's SPMD partitioner, so the call is wrapped in ``shard_map`` over the
mesh's node axis (every CoreState field is node-leading, so a single
``P(node)`` prefix spec covers the whole pytree).  Non-divisible fleets are
replicated by ``FleetVM`` (same rule as ``sharding.api.logical``) and take
the direct path.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.config import VMConfig
from repro.core.vm.spec import ISA
from repro.core.vm.vmstate import VMState
from repro.kernels.vmloop.ref import core_of, merge_core
from repro.kernels.vmloop.vmloop import vmloop_call


def fleet_vmloop(
    S: VMState,
    steps: int,
    cfg: VMConfig,
    isa: ISA | None = None,
    *,
    mesh=None,
    interpret: bool = False,
    obs: bool = False,
    elide_checks: bool = False,
):
    """Advance every node of a stacked fleet state by at most ``steps``
    in-kernel instructions (bailing per node on unclaimed opcodes).

    Returns ``(S', n_exec (N,) int32, bailed (N,) bool, bail_op (N,)
    int32)``; fields outside the kernel's CoreState (mailboxes, rng, ...)
    pass through untouched.  ``bail_op`` is -1 on non-bailed nodes, else
    the declined opcode (``num_ops`` for FIOS/trap).  ``obs=True`` selects
    the counting kernel and appends ``op_hist (N, num_ops + 4) int32``
    (per-node retirement histogram, sharded like the other outputs).
    """
    core = core_of(S)
    N = core.pc.shape[0]
    n_out = 5 if obs else 4
    if mesh is not None:
        ndev = int(np.prod(mesh.devices.shape))
        if ndev > 1 and N % ndev == 0:
            ax = mesh.axis_names[0]
            sharded = jax.shard_map(
                lambda c: vmloop_call(
                    c, steps, cfg, isa, interpret=interpret, obs=obs,
                    elide_checks=elide_checks,
                ),
                mesh=mesh,
                in_specs=(P(ax),),
                out_specs=(P(ax),) * n_out,
                check_vma=False,
            )
            core, *rest = sharded(core)
            return (merge_core(S, core), *rest)
    core, *rest = vmloop_call(
        core, steps, cfg, isa, interpret=interpret, obs=obs,
        elide_checks=elide_checks,
    )
    return (merge_core(S, core), *rest)


__all__ = ["fleet_vmloop"]
