"""LUT-based fixed-point activation kernel (paper §4.2 / Alg. 2, C5).

Computes the improved interpolated fixed-point sigmoid (EXPERIMENTS.md "LUT
accuracy": the faithful Alg. 2 reproduction measures 2.2 % error; this
33-entry uniform LUT + lerp meets the paper's <1 % target) over int32
tensors, y scale 1:1000.

TPU adaptation of the LUT gather: dynamic per-element gathers don't map to
the VPU, so the bucket lookup is computed as a one-hot (bn, 33) x (33, 1)
matmul on the MXU — the TPU-native equivalent of the paper's "one look-up
table access".  Blocks of (bm, bn) int32 live in VMEM; the LUT rides along
replicated to every block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.fixedpoint.luts import _SIG_INTERP_LUT, _SIG_INTERP_MAX, _SIG_INTERP_N

_STEP = _SIG_INTERP_MAX // _SIG_INTERP_N  # 250
_NLUT = _SIG_INTERP_N + 1


def _kernel(x_ref, lut_ref, out_ref):
    x = x_ref[...]
    lut = lut_ref[...].astype(jnp.float32)            # (1, NLUT)
    mirror = x < 0
    ax = jnp.abs(x)
    i = jnp.clip(ax // _STEP, 0, _SIG_INTERP_N - 1)
    r = ax - i * _STEP
    # One-hot gathers on the MXU: y0 = onehot(i) @ lut, y1 = onehot(i+1) @ lut
    iota = jax.lax.broadcasted_iota(jnp.int32, x.shape + (_NLUT,), x.ndim)
    oh0 = (iota == i[..., None]).astype(jnp.float32)
    oh1 = (iota == (i + 1)[..., None]).astype(jnp.float32)
    y0 = jax.lax.dot_general(
        oh0.reshape(-1, _NLUT), lut.reshape(_NLUT, 1),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).reshape(x.shape).astype(jnp.int32)
    y1 = jax.lax.dot_general(
        oh1.reshape(-1, _NLUT), lut.reshape(_NLUT, 1),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).reshape(x.shape).astype(jnp.int32)
    y = y0 + ((y1 - y0) * r) // _STEP
    y = jnp.where(ax >= _SIG_INTERP_MAX, 1000, y)
    out_ref[...] = jnp.where(mirror, 1000 - y, y)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def lut_sigmoid(
    x: jax.Array,            # (M, N) int32, x scale 1:1000
    *,
    bm: int = 256,
    bn: int = 256,
    interpret: bool = False,
):
    M, N = x.shape
    bm, bn = min(bm, M), min(bn, N)
    assert M % bm == 0 and N % bn == 0
    lut = jnp.asarray(_SIG_INTERP_LUT, jnp.int32).reshape(1, _NLUT)
    return pl.pallas_call(
        _kernel,
        grid=(M // bm, N // bn),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((1, _NLUT), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(x, lut)
