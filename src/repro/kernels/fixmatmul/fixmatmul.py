"""Fixed-point (int8 x int8 -> int32) matmul kernel with per-channel scale
vectors — the TPU-native generalization of the paper's ``vecfold`` (C4).

The paper's scheme: integer data, 32-bit accumulation, per-output scale
vector applied after the fold.  On the MXU that becomes a tiled int8 GEMM
with an int32 accumulator in VMEM and fp32 row/column scales applied on the
final K step:

    out[m, n] = (sum_k xq[m, k] * wq[k, n])_int32 * sx[m] * sw[n]

Grid: (M/bm, N/bn, K/bk), K innermost (sequential accumulation).
BlockSpecs keep one (bm, bk) x-tile, one (bk, bn) w-tile, the (bm, bn)
accumulator scratch, and the scale slivers in VMEM.  MXU-aligned tile
defaults: 256 x 256 x 256 (int8 tiles want >= (32, 128); 256^2 int32
accumulator = 256 KiB VMEM).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(x_ref, w_ref, sx_ref, sw_ref, out_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...],
        w_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == n_k - 1)
    def _flush():
        sx = sx_ref[...].astype(jnp.float32)          # (bm, 1)
        sw = sw_ref[...].astype(jnp.float32)          # (1, bn)
        out_ref[...] = (acc_ref[...].astype(jnp.float32) * sx * sw).astype(
            out_ref.dtype
        )


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "out_dtype", "interpret"),
)
def fixmatmul(
    xq: jax.Array,          # (M, K) int8
    wq: jax.Array,          # (K, N) int8
    sx: jax.Array,          # (M,) f32 per-row scale
    sw: jax.Array,          # (N,) f32 per-col scale
    *,
    bm: int = 256,
    bn: int = 256,
    bk: int = 256,
    out_dtype=jnp.float32,
    interpret: bool = False,
):
    M, K = xq.shape
    K2, N = wq.shape
    assert K == K2 and sx.shape == (M,) and sw.shape == (N,)
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    n_k = K // bk

    grid = (M // bm, N // bn, n_k)
    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xq, wq, sx.reshape(M, 1), sw.reshape(1, N))
