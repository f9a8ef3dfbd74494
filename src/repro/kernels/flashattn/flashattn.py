"""Flash attention forward kernel: causal / sliding-window, GQA-aware.

Grid: (B, H, nq, nk) with the KV-block axis innermost ("arbitrary" =
sequential), online-softmax state (running max m, denominator l, output
accumulator) in VMEM scratch that persists across the sequential axis.
GQA is expressed in the BlockSpec index maps: query head h reads KV head
h // group_size — no KV replication in HBM.

Per-block masking handles the causal frontier and the sliding window; fully
masked blocks short-circuit via @pl.when (block-sparse skip on the causal
upper triangle — the FLOPs the jnp oracle still spends; see §Perf).

VMEM at defaults (bq = bk = 512, hd = 128): q/k/v tiles 3 x 128 KiB (bf16),
acc 256 KiB f32 — comfortably under the ~16 MiB/core budget.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(
    q_ref, k_ref, v_ref, out_ref,
    acc_ref, m_ref, l_ref,
    *, bq: int, bk: int, n_k: int, causal: bool, window: int | None, sk: int,
    scale: float,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * bq
    k_start = ki * bk

    # Block-level skip: causal blocks entirely above the diagonal and
    # window blocks entirely below the horizon do nothing.
    run = jnp.bool_(True)
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + bq - 1)
    if window is not None:
        run = jnp.logical_and(run, k_start + bk - 1 >= q_start - window + 1)

    @pl.when(run)
    def _block():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                        # (bq, bk)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = k_pos < sk
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        if window is not None:
            mask = jnp.logical_and(mask, q_pos - k_pos < window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        m_ref[...] = m_new
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * alpha[:, None] + pv

    @pl.when(ki == n_k - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        out_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "bq", "bk", "interpret"),
)
def flash_attention(
    q: jax.Array,            # (B, H, Sq, hd)
    k: jax.Array,            # (B, KV, Sk, hd)
    v: jax.Array,            # (B, KV, Sk, hd)
    *,
    causal: bool = True,
    window: int | None = None,
    bq: int = 512,
    bk: int = 512,
    interpret: bool = False,
):
    B, H, Sq, hd = q.shape
    _, KV, Sk, _ = k.shape
    G = H // KV
    bq, bk = min(bq, Sq), min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0
    n_k = Sk // bk
    scale = 1.0 / (hd ** 0.5)

    grid = (B, H, Sq // bq, n_k)
    return pl.pallas_call(
        functools.partial(
            _kernel, bq=bq, bk=bk, n_k=n_k, causal=causal,
            window=window, sk=Sk, scale=scale,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
