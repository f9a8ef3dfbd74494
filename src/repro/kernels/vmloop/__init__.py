"""vmloop — Pallas fetch/dispatch/stack engine for the fleet's inner
interpreter loop.

Three-file convention (see ``repro.kernels``):
  vmloop.py — ``pl.pallas_call`` kernel: grid ``(nodes_per_shard,)``, one
              node's machine state (a flat int32 row) DMA'd into SMEM,
              ``steps`` on-chip fetch/decode/execute iterations over a
              branch table;
  ops.py    — ``fleet_vmloop``: stacked-VMState wrapper with node-mesh
              ``jax.shard_map`` and the interpret switch;
  ref.py    — the kernel's step semantics (also defines the
              SUPPORTED/BAILOUT opcode claim).

Claimed vs declined (``ref.SUPPORTED_WORDS`` / ``ref.BAILOUT_WORDS``): the
kernel now claims essentially the whole ISA — stack/arith/cmp/bit/mem/ctl/
exc words, printing into the out ring, the IO-suspending words
(``send``/``receive``/``out``/``in`` execute their suspension in-kernel;
delivery stays with the host service and the collective router), the LUT
fixed-point DSP scalars (table loads), and the vector/ANN ops
(``vecfold``/``dotprod`` accumulate int32 products element by element;
``lowp``/``highp``/``hull`` are short on-chip IIR scans).  Only
``task`` spawn, ``rnd``, and FIOS host calls still bail to the lax tail —
``FleetVM.pallas_stats()`` reports the split plus a per-opcode bail
histogram.

Message-bound round mode: with ``FleetVM(executor="pallas")`` and
``run(service_every=k)``, ``FleetKernels.rounds_aux`` fuses ``k`` whole
rounds (kernel slice -> collective router -> warp) into one compiled loop,
so message-bound fleets complete entire rounds without reaching the lax
tail or the host.

Pick ``executor="pallas"`` for fleets dominated by the claimed set —
compute, messaging, DSP/ANN vector work (the paper's hardware-role
workloads); pick ``"batched"`` for task-spawn/``rnd``/FIOS-heavy mixes,
or ``"trace"`` for hot program-homogeneous fleets.  Or let the Auditor
decide: the claimed/declined split above is consumed *statically* by
``repro.analysis`` — ``FleetVM(executor="auto")`` intersects each
program's opcode footprint with ``BAILOUT_WORDS`` at ``start()`` and
routes the fleet accordingly (bail-free -> pallas, predictable bails ->
trace, otherwise batched), eliding the per-step stack pre-check when
every program verified.

Selected as a fleet backend via ``FleetVM(executor="pallas")`` /
``REXAVM(backend="pallas")``.
"""

from repro.kernels.vmloop.ops import fleet_vmloop
from repro.kernels.vmloop.ref import (
    BAILOUT_WORDS,
    SUPPORTED_WORDS,
    CoreState,
    supported_mask,
)

__all__ = [
    "fleet_vmloop",
    "CoreState",
    "SUPPORTED_WORDS",
    "BAILOUT_WORDS",
    "supported_mask",
]
