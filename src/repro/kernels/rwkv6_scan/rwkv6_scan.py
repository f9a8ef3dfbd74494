"""RWKV6 chunked-recurrence kernel (data-dependent-decay linear attention).

Implements the same chunk algorithm as models.rwkv6.chunked_wkv (its oracle):
intra-chunk via a decay-weighted (L, L, K) contraction in log space,
inter-chunk via the carried (K, K) state.

Grid: (B, H, nc) with the chunk axis innermost and sequential; the
(K, K) fp32 state lives in VMEM scratch and persists across the sequential
axis (re-initialized from the state input at chunk 0, flushed to the state
output at the last chunk) — the standard Pallas-TPU scan-carry pattern.

VMEM at L = K = 64: chunk tiles 4 x 16 KiB, the (L, L, K) exp-diff
intermediate 1 MiB f32, state 16 KiB — well under budget.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(
    r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref,
    out_ref, s1_ref,
    state,                      # VMEM (K, K) f32 scratch
    *, L: int, K: int, n_c: int,
):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        state[...] = s0_ref[0, 0]

    r = r_ref[0, 0].astype(jnp.float32)       # (L, K)
    kk = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    lw = lw_ref[0, 0].astype(jnp.float32)     # log decay <= 0
    u = u_ref[0].astype(jnp.float32)          # (K,)

    cum_in = jnp.cumsum(lw, axis=0)           # inclusive
    cum_ex = cum_in - lw                      # exclusive

    S0 = state[...]
    # inter-chunk
    r_dec = r * jnp.exp(cum_ex)
    out_inter = jax.lax.dot_general(
        r_dec, S0, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    # intra-chunk: A[t,i] = sum_k r[t,k] k[i,k] exp(cum_ex[t,k]-cum_in[i,k])
    diff = jnp.clip(cum_ex[:, None, :] - cum_in[None, :, :], -60.0, 0.0)
    A = jnp.sum(r[:, None, :] * kk[None, :, :] * jnp.exp(diff), axis=-1)
    ti = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    ii = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    A = jnp.where(ti > ii, A, 0.0)
    out_intra = jax.lax.dot_general(
        A, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    # bonus diagonal
    bonus = jnp.sum(r * u[None, :] * kk, axis=-1)
    out = out_inter + out_intra + bonus[:, None] * v
    out_ref[0, 0] = out.astype(out_ref.dtype)

    # state update: S1 = diag(exp(total)) S0 + sum_i exp(total-cum_in[i]) k_i (x) v_i
    total = cum_in[-1, :]                      # (K,)
    k_dec = kk * jnp.exp(jnp.clip(total[None, :] - cum_in, -60.0, 0.0))
    state[...] = S0 * jnp.exp(total)[:, None] + jax.lax.dot_general(
        k_dec, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(c == n_c - 1)
    def _flush():
        s1_ref[0, 0] = state[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_scan(
    r: jax.Array,             # (B, H, S, K)
    k: jax.Array,
    v: jax.Array,
    logw: jax.Array,          # (B, H, S, K) log decay (<= 0), f32
    u: jax.Array,             # (H, K) bonus, f32
    state0: jax.Array,        # (B, H, K, K) f32
    *,
    chunk: int = 64,
    interpret: bool = False,
):
    B, H, S, K = r.shape
    L = min(chunk, S)
    assert S % L == 0
    n_c = S // L
    grid = (B, H, n_c)
    chunk_spec = pl.BlockSpec((1, 1, L, K), lambda b, h, c: (b, h, c, 0))
    state_spec = pl.BlockSpec((1, 1, K, K), lambda b, h, c: (b, h, 0, 0))
    out, s1 = pl.pallas_call(
        functools.partial(_kernel, L=L, K=K, n_c=n_c),
        grid=grid,
        in_specs=[
            chunk_spec, chunk_spec, chunk_spec, chunk_spec,
            pl.BlockSpec((1, K), lambda b, h, c: (h, 0)),
            state_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, L, K), lambda b, h, c: (b, h, c, 0)),
            state_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, K), r.dtype),
            jax.ShapeDtypeStruct((B, H, K, K), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((K, K), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(r, k, v, logw, u, state0)
    return out, s1
