"""Pallas VM-loop kernel: on-chip fetch/dispatch/stack engine per node.

One grid program per fleet node.  The node's entire kernel-visible machine
state — code segment, DIOS memory, the per-task data/return/loop stacks,
pointers, and exception table — travels as one flat int32 row
(:class:`repro.kernels.vmloop.ref.Layout`).  The program DMAs its row from
HBM into scalar memory (SMEM), runs up to ``steps`` fetch/decode/execute
iterations of :func:`repro.kernels.vmloop.ref.make_run_core` *entirely on
chip* — a ``lax.while_loop`` around a branch table over the opcode space
(the paper's branch look-up table decoder, §3.10) with zero HBM traffic
between instructions — and DMAs the row back in place.  This is the repo's
analogue of the paper's FPGA implementation of the very same VM: one
bytecode semantics, one software (lax/oracle) engine and one "hardware"
(Pallas) engine, byte-exact.

Bail-out protocol: the loop stops *before* the first instruction outside
the claimed opcode set (now only ``task`` spawn, ``rnd``, and FIOS calls —
see ``ref.SUPPORTED_WORDS``/``ref.BAILOUT_WORDS``) and reports per node how
many instructions it executed, a bailed flag, and the bailing opcode
(``bail_op``, -1 when clean — the per-opcode bail histogram's raw feed).
The caller finishes the slice with the lax interpreter from the
byte-identical intermediate state (``executor.PallasSliceExecutor``), so
mixed slices stay exact.  IO-suspending words (``send``/``receive``/
``out``/``in``) are *claimed*: their suspension (pc rewind + ``io_op`` +
ST_IOWAIT) executes in-kernel and the loop exits on the status change with
``bailed`` false; delivery belongs to the host service loop and the
collective router between kernel invocations.

Layout: the rows are one ``(N, 1, width)`` array left in HBM
(``memory_space=ANY``) and aliased from input to output; grid ``(N,)``,
program ``i`` copies row ``i`` to a ``(1, width)`` SMEM scratch and back.
The unit axis keeps each node's row whole under the TPU's tiling, and the
tables ride as one small whole-array SMEM operand.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.config import VMConfig
from repro.core.vm.spec import ISA, get_isa
from repro.kernels.vmloop.ref import CoreState, Layout, flat_tables, make_run_core


def vmloop_call(
    core: CoreState,
    steps: int,
    cfg: VMConfig,
    isa: ISA | None = None,
    *,
    interpret: bool = False,
    obs: bool = False,
    elide_checks: bool = False,
):
    """Run the on-chip vmloop over a stacked (node-leading) ``CoreState``.

    Returns ``(core', n_exec (N,) int32, bailed (N,) bool, bail_op (N,)
    int32)``.  ``steps`` is static (the micro-slice budget).
    ``interpret=True`` lowers the kernel through the Pallas interpreter —
    the CPU-testable path the equivalence suite pins byte-exactly against
    the lax interpreter and the Oracle.

    ``obs=True`` compiles the *counting* run_core variant: the kernel
    additionally accumulates a per-node ``(num_ops + 4,)`` retirement
    histogram in its row and it is returned as a fifth result ``op_hist
    (N, num_ops + 4) int32``.  This is a distinct kernel — the default
    path is unchanged.

    ``elide_checks=True`` compiles the verified-program fast path: the
    per-step stack pre-check disappears from the kernel body at build time
    (see ``ref.make_core_step``) — sound only when every program in the
    fleet passed the static verifier.

    Building the rows is named ``vmloop.pack`` and cutting them apart
    ``vmloop.unpack`` (``jax.named_scope``); the kernel keeps the name
    ``vmloop``.
    """
    isa = isa or get_isa()
    N = core.pc.shape[0]
    lay = Layout(cfg, isa)
    run_core = make_run_core(cfg, isa, obs=obs, elide_checks=elide_checks)
    tab = jnp.asarray(flat_tables(isa)[0])
    with jax.named_scope("vmloop.pack"):
        packed = lay.pack(core)

    def kernel(tab_ref, rows_in, rows_out, row):
        i = pl.program_id(0)
        pltpu.sync_copy(rows_in.at[i], row)
        run_core(row, tab_ref, steps)
        pltpu.sync_copy(row, rows_out.at[i])

    rows = pl.pallas_call(
        kernel,
        grid=(N,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((N, 1, lay.width), jnp.int32),
        scratch_shapes=[pltpu.SMEM((1, lay.width), jnp.int32)],
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
        name="vmloop",
    )(tab, packed)

    with jax.named_scope("vmloop.unpack"):
        core, n_exec, bailed, bail_op, op_hist = lay.unpack(rows)
    if obs:
        return core, n_exec, bailed, bail_op, op_hist
    return core, n_exec, bailed, bail_op
