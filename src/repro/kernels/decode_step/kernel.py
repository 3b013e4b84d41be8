"""Pallas TPU kernel: fused streaming decode step (stateful-ALU analogue).

One grid step per (batch·kv-head) "flow".  The kernel performs, in a single
VMEM-resident pass, the paper's per-packet runtime program (Alg. 1):

  1. write the arriving (k, v) into the SRAM ring buffer at ``count``,
  2. exact exp-kernel readout over the valid buffer slots (local layer),
  3. φ-state readout against the (S, Z) registers (Eq. 6),
  4. merge numerator/denominator partials (SumReduce),
  5. fold-on-full: when the ring fills, add Σφ(k)vᵀ / Σφ(k) into (S, Z)
     and clear the ring (Eqs. 9-10, circular-overwrite → compressed stream).

The (S, Z) updates are expressed as in-place aliased outputs
(``input_output_aliases``) — the TPU equivalent of the switch's atomic
register-array update.  ``count`` arrives via scalar prefetch (SMEM), like a
PHV metadata field.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(
    count_ref,  # SMEM (1,) int32 — scalar prefetch
    q_ref,  # (Gq, d)
    kt_ref,  # (1, d)
    vt_ref,  # (1, dv)
    pq_ref,  # (Gq, m)
    pbuf_ref,  # (L, m) φ of buffer incl. the new token at slot count
    kbuf_ref,  # (L, d) in/out aliased
    vbuf_ref,  # (L, dv) in/out aliased
    S_ref,  # (m, dv) in/out aliased
    Z_ref,  # (1, m) in/out aliased
    out_ref,  # (Gq, dv)
    kbuf_out,
    vbuf_out,
    S_out,
    Z_out,
    count_out,  # (1, 1) int32
    *,
    chunk_size: int,
    gamma: float,
):
    L = chunk_size
    d = q_ref.shape[-1]
    c = count_ref[pl.program_id(0)]  # per-flow fill level (PHV metadata)

    # 1. SRAM ring write at slot c
    kbuf = kbuf_ref[...]
    vbuf = vbuf_ref[...]
    slot = jax.lax.broadcasted_iota(jnp.int32, (L, 1), 0) == c
    kbuf = jnp.where(slot, kt_ref[...], kbuf)
    vbuf = jnp.where(slot, vt_ref[...], vbuf)

    # 2. exact local readout over valid slots (incl. the one just written)
    valid = (jax.lax.broadcasted_iota(jnp.int32, (1, L), 1) <= c).astype(jnp.float32)
    s_loc = jnp.exp(
        jnp.einsum("gd,jd->gj", q_ref[...], kbuf, preferred_element_type=jnp.float32)
        * (1.0 / math.sqrt(d))
    ) * valid
    num = jnp.einsum("gj,jd->gd", s_loc, vbuf, preferred_element_type=jnp.float32)
    den = jnp.sum(s_loc, axis=-1)

    # 3. φ-state readout (Eq. 6) against the register arrays
    S = S_ref[...]
    Z = Z_ref[0, :]
    num += jnp.einsum("gm,md->gd", pq_ref[...], S, preferred_element_type=jnp.float32)
    den += jnp.einsum("gm,m->g", pq_ref[...], Z, preferred_element_type=jnp.float32)

    # 4. merge
    out_ref[...] = (num / (den[:, None] + gamma)).astype(out_ref.dtype)

    # 5. fold-on-full (Eqs. 9-10)
    full = (c + 1 >= L).astype(jnp.float32)
    pbuf = pbuf_ref[...]
    S_fold = S + jnp.einsum("jm,jd->md", pbuf, vbuf, preferred_element_type=jnp.float32)
    Z_fold = Z + jnp.sum(pbuf, axis=0)
    S_out[...] = (S + full * (S_fold - S)).astype(S_out.dtype)
    Z_out[0, :] = (Z + full * (Z_fold - Z)).astype(Z_out.dtype)
    kbuf_out[...] = ((1.0 - full) * kbuf).astype(kbuf_out.dtype)
    vbuf_out[...] = ((1.0 - full) * vbuf).astype(vbuf_out.dtype)
    # a (1, 1) vector store: Mosaic cannot store a scalar to VMEM
    count_out[...] = jnp.full((1, 1), jnp.where(c + 1 >= L, 0, c + 1), jnp.int32)


@functools.partial(jax.jit, static_argnames=("chunk_size", "gamma", "interpret"))
def decode_step_pallas(
    q: jax.Array,  # (BH, Gq, d)
    k_t: jax.Array,  # (BH, d)
    v_t: jax.Array,  # (BH, dv)
    phi_q: jax.Array,  # (BH, Gq, m)
    phi_buf: jax.Array,  # (BH, L, m)
    k_buf: jax.Array,  # (BH, L, d)
    v_buf: jax.Array,  # (BH, L, dv)
    S: jax.Array,  # (BH, m, dv)
    Z: jax.Array,  # (BH, m)
    count: jax.Array,  # (BH,) int32 (same value per flow here; per-flow ok)
    *,
    chunk_size: int,
    gamma: float = 1e-6,
    interpret: bool = False,
):
    BH, Gq, d = q.shape
    dv = v_t.shape[-1]
    m = phi_q.shape[-1]
    L = chunk_size

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BH,),
        in_specs=[
            pl.BlockSpec((None, Gq, d), lambda b, cnt: (b, 0, 0)),
            pl.BlockSpec((None, 1, d), lambda b, cnt: (b, 0, 0)),
            pl.BlockSpec((None, 1, dv), lambda b, cnt: (b, 0, 0)),
            pl.BlockSpec((None, Gq, m), lambda b, cnt: (b, 0, 0)),
            pl.BlockSpec((None, L, m), lambda b, cnt: (b, 0, 0)),
            pl.BlockSpec((None, L, d), lambda b, cnt: (b, 0, 0)),
            pl.BlockSpec((None, L, dv), lambda b, cnt: (b, 0, 0)),
            pl.BlockSpec((None, m, dv), lambda b, cnt: (b, 0, 0)),
            pl.BlockSpec((None, 1, m), lambda b, cnt: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, Gq, dv), lambda b, cnt: (b, 0, 0)),
            pl.BlockSpec((None, L, d), lambda b, cnt: (b, 0, 0)),
            pl.BlockSpec((None, L, dv), lambda b, cnt: (b, 0, 0)),
            pl.BlockSpec((None, m, dv), lambda b, cnt: (b, 0, 0)),
            pl.BlockSpec((None, 1, m), lambda b, cnt: (b, 0, 0)),
            pl.BlockSpec((None, 1, 1), lambda b, cnt: (b, 0, 0)),
        ],
    )
    out_shape = [
        jax.ShapeDtypeStruct((BH, Gq, dv), q.dtype),
        jax.ShapeDtypeStruct((BH, L, d), k_buf.dtype),
        jax.ShapeDtypeStruct((BH, L, dv), v_buf.dtype),
        jax.ShapeDtypeStruct((BH, m, dv), S.dtype),
        jax.ShapeDtypeStruct((BH, 1, m), Z.dtype),
        jax.ShapeDtypeStruct((BH, 1, 1), jnp.int32),
    ]
    outs = pl.pallas_call(
        functools.partial(_kernel, chunk_size=L, gamma=gamma),
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases={6: 1, 7: 2, 8: 3, 9: 4},  # bufs & state in-place
        interpret=interpret,
    )(
        count.astype(jnp.int32),
        q,
        k_t[:, None, :],
        v_t[:, None, :],
        phi_q,
        phi_buf,
        k_buf,
        v_buf,
        S,
        Z[:, None, :],
    )
    out, k_buf2, v_buf2, S2, Z2, count2 = outs
    return out, (S2, Z2[:, 0], k_buf2, v_buf2, count2[:, 0, 0])
