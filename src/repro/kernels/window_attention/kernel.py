"""Pallas TPU kernel: causal sliding-window flash attention.

The SRAM local layer L_t (paper Eq. 13-14 left term) as a standalone
softmax attention, also used natively by Mixtral's SWA.  Complexity
O(T·W·d): the kv-block grid axis only covers the W-wide band, so doubling
context length does not change per-token work — the dataplane line-rate
property.

Tiling: grid = (BH, T/Bq, (W+Bq)/Bk) with the kv axis innermost and
sequential; online-softmax running (max, sum, acc) live in VMEM scratch.
Rectangular tiles are supported for Bq a multiple of Bk: q block i covers
rows [i·Bq, (i+1)·Bq), so its band needs kv blocks
[(i·Bq − W)/Bk, ((i+1)·Bq)/Bk) — the kv block index is
(i+1)·Bq/Bk − n_k_steps + j, clamped to 0 for the BlockSpec and masked out
arithmetically when the unclamped index is negative (avoids
double-counting block 0 at the left edge).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(
    q_ref,  # (Bq, d)
    k_ref,  # (Bk, d)
    v_ref,  # (Bk, dv)
    o_ref,  # (Bq, dv)
    m_ref,  # scratch (Bq, 128)
    l_ref,  # scratch (Bq, 128)
    acc_ref,  # scratch (Bq, dv)
    *,
    blk_q: int,
    blk_k: int,
    window: int,
    n_k_steps: int,
):
    i = pl.program_id(1)
    j = pl.program_id(2)
    d = q_ref.shape[-1]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kb = (i + 1) * (blk_q // blk_k) - n_k_steps + j  # unclamped kv block index
    rows = i * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
    cols = kb * blk_k + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
    delta = rows - cols
    band = (delta >= 0) & (delta < window) & (kb >= 0)

    s = jnp.einsum(
        "id,jd->ij", q_ref[...], k_ref[...], preferred_element_type=jnp.float32
    ) * (1.0 / math.sqrt(d))
    s = jnp.where(band, s, NEG_INF)

    m_prev = m_ref[:, 0]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    l_ref[:, 0] = l_ref[:, 0] * alpha + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.einsum(
        "ij,jd->id", p, v_ref[...], preferred_element_type=jnp.float32
    )
    m_ref[:, 0] = m_cur

    @pl.when(j == n_k_steps - 1)
    def _emit():
        l = l_ref[:, 0]
        o_ref[...] = (acc_ref[...] / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "blk_q", "blk_k", "interpret")
)
def window_attention_pallas(
    q: jax.Array,  # (BH, T, d)
    k: jax.Array,
    v: jax.Array,
    *,
    window: int,
    blk_q: int = 128,
    blk_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    BH, T, d = q.shape
    dv = v.shape[-1]
    assert T % blk_q == 0 and T % blk_k == 0
    assert window % blk_k == 0, "window must be a multiple of blk_k"
    assert blk_q % blk_k == 0, "blk_q must be a multiple of blk_k"
    n_k_steps = (window + blk_q) // blk_k  # band cover for one q block
    grid = (BH, T // blk_q, n_k_steps)

    def kv_index(b, i, j):
        kb = (i + 1) * (blk_q // blk_k) - n_k_steps + j
        return (b, jnp.maximum(kb, 0), 0)

    return pl.pallas_call(
        functools.partial(
            _kernel,
            blk_q=blk_q,
            blk_k=blk_k,
            window=window,
            n_k_steps=n_k_steps,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, blk_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, blk_k, d), kv_index),
            pl.BlockSpec((None, blk_k, dv), kv_index),
        ],
        out_specs=pl.BlockSpec((None, blk_q, dv), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, 128), jnp.float32),
            pltpu.VMEM((blk_q, 128), jnp.float32),
            pltpu.VMEM((blk_q, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
