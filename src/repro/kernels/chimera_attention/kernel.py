"""Pallas TPU kernel: chunked Chimera attention (local exact + φ-stream).

Tiling (Partition, Eq. 1): grid = (B·Hkv, T/L) with the chunk axis
*sequential* ("arbitrary") so the (S, Z) stream state persists in VMEM
scratch across chunk steps — the TPU realization of the paper's stateful-ALU
register array (Eqs. 9-10).  Per grid step the kernel:

  1. Map: computes exact exp-kernel causal scores for the resident chunk
     (the SRAM local layer) on the MXU,
  2. reads the carried state for the compressed-history contribution
     (Eq. 6 readout),
  3. SumReduce: folds the chunk's φ(k)vᵀ outer products into scratch.

VMEM working set per step (fp32):
  q/k/v/φq/φk blocks: L·(2d + d_v + (Gq+1)·m) plus scratch m·(d_v+1)
with L=chunk, all last-dims padded to the 128-lane requirement by the
caller.  For the paper's operating point (L=128, d=d_v=128, m=128, Gq≤8)
that is ≈ 1.2 MB — comfortably inside a v5e core's VMEM, and the analogue
of the paper's Eq. 11 per-flow budget check (enforced in ops.py).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(
    q_ref,  # (Gq*L, d)
    k_ref,  # (L, d)
    v_ref,  # (L, dv)
    pq_ref,  # (Gq*L, m)
    pk_ref,  # (L, m)
    num_ref,  # (Gq*L, dv)
    den_ref,  # (Gq*L, 128) — den broadcast into lanes, col 0 significant
    S_ref,  # scratch (m, dv)
    Z_ref,  # scratch (1, m)
    *,
    chunk_size: int,
    gq: int,
    use_local: bool,
    use_stream: bool,
):
    c = pl.program_id(1)
    L = chunk_size
    d = q_ref.shape[-1]

    @pl.when(c == 0)
    def _init():
        S_ref[...] = jnp.zeros_like(S_ref)
        Z_ref[...] = jnp.zeros_like(Z_ref)

    q = q_ref[...].reshape(gq, L, d)
    k = k_ref[...]
    v = v_ref[...]
    pq = pq_ref[...].reshape(gq, L, pq_ref.shape[-1])
    pk = pk_ref[...]

    num = jnp.zeros((gq, L, v.shape[-1]), jnp.float32)
    den = jnp.zeros((gq, L), jnp.float32)

    if use_local:
        # exact exp-kernel causal attention inside the SRAM chunk (MXU matmul)
        s = jnp.einsum(
            "gid,jd->gij", q, k, preferred_element_type=jnp.float32
        ) * (1.0 / math.sqrt(d))
        causal = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0) >= jax.lax.broadcasted_iota(
            jnp.int32, (L, L), 1
        )
        s = jnp.where(causal[None], jnp.exp(s), 0.0)
        num += jnp.einsum("gij,jd->gid", s, v, preferred_element_type=jnp.float32)
        den += jnp.sum(s, axis=-1)

    if use_stream:
        # compressed-history readout against the carried register state
        S = S_ref[...]
        Z = Z_ref[0, :]
        num += jnp.einsum("gim,md->gid", pq, S, preferred_element_type=jnp.float32)
        den += jnp.einsum("gim,m->gi", pq, Z, preferred_element_type=jnp.float32)
        # stateful-ALU increments (Eqs. 9-10): fold the chunk leaving SRAM
        S_ref[...] = S + jnp.einsum(
            "jm,jd->md", pk, v, preferred_element_type=jnp.float32
        )
        Z_ref[0, :] = Z + jnp.sum(pk, axis=0)

    num_ref[...] = num.reshape(gq * L, v.shape[-1]).astype(num_ref.dtype)
    den_ref[...] = jnp.broadcast_to(
        den.reshape(gq * L, 1), (gq * L, den_ref.shape[-1])
    ).astype(den_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("chunk_size", "use_local", "use_stream", "interpret"),
)
def chimera_attention_pallas(
    q: jax.Array,  # (BH, Gq, T, d) normalized queries, BH = B*Hkv
    k: jax.Array,  # (BH, T, d)
    v: jax.Array,  # (BH, T, dv)
    phi_q: jax.Array,  # (BH, Gq, T, m)
    phi_k: jax.Array,  # (BH, T, m)
    *,
    chunk_size: int,
    use_local: bool = True,
    use_stream: bool = True,
    interpret: bool = False,
):
    BH, Gq, T, d = q.shape
    m = phi_q.shape[-1]
    dv = v.shape[-1]
    L = chunk_size
    assert T % L == 0, (T, L)
    n_chunks = T // L
    LANES = 128

    # fold Gq into the row dimension ((chunk, gq, L) contiguity) so every
    # block is 2-D and lane-aligned
    qf = (
        q.reshape(BH, Gq, n_chunks, L, d)
        .transpose(0, 2, 1, 3, 4)
        .reshape(BH, n_chunks * Gq * L, d)
    )
    pqf = (
        phi_q.reshape(BH, Gq, n_chunks, L, m)
        .transpose(0, 2, 1, 3, 4)
        .reshape(BH, n_chunks * Gq * L, m)
    )

    grid = (BH, n_chunks)
    out_shapes = (
        jax.ShapeDtypeStruct((BH, n_chunks * Gq * L, dv), q.dtype),
        jax.ShapeDtypeStruct((BH, n_chunks * Gq * L, LANES), q.dtype),
    )
    num, den = pl.pallas_call(
        functools.partial(
            _kernel,
            chunk_size=L,
            gq=Gq,
            use_local=use_local,
            use_stream=use_stream,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, Gq * L, d), lambda b, c: (b, c, 0)),
            pl.BlockSpec((None, L, d), lambda b, c: (b, c, 0)),
            pl.BlockSpec((None, L, dv), lambda b, c: (b, c, 0)),
            pl.BlockSpec((None, Gq * L, m), lambda b, c: (b, c, 0)),
            pl.BlockSpec((None, L, m), lambda b, c: (b, c, 0)),
        ],
        out_specs=(
            pl.BlockSpec((None, Gq * L, dv), lambda b, c: (b, c, 0)),
            pl.BlockSpec((None, Gq * L, LANES), lambda b, c: (b, c, 0)),
        ),
        out_shape=out_shapes,
        scratch_shapes=[
            pltpu.VMEM((m, dv), jnp.float32),
            pltpu.VMEM((1, m), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qf, k, v, pqf, phi_k)
    num = (
        num.reshape(BH, n_chunks, Gq, L, dv)
        .transpose(0, 2, 1, 3, 4)
        .reshape(BH, Gq, T, dv)
    )
    den = (
        den[..., 0]
        .reshape(BH, n_chunks, Gq, L)
        .transpose(0, 2, 1, 3)
        .reshape(BH, Gq, T)
    )
    return num, den
