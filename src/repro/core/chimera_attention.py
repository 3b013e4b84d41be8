"""Chimera attention: the paper's full neuro-symbolic attention primitive.

Composes (§3.3-3.5):

* **Local layer L_t** — exact exp-kernel causal attention inside the current
  SRAM chunk (length L = the per-flow circular buffer).
* **Stream** — the compressed history: all tokens older than the current
  chunk aggregated into the incremental state (S, Z) via φ (Eqs. 9-10).
  When a token leaves the SRAM buffer it is folded into the state — the
  dataplane's circular-overwrite becoming "compressed token summaries".
* **Static global layer G** — learned static tokens with TCAM-style ternary
  signature matching (Eq. 14 right term).

All three contribute (numerator, denominator) partials in the shared
exp-kernel space (Eq. 5) and are merged by a single SumReduce
(:func:`repro.core.key_selection.merge_partials`).  Coverage is exact — each
past token contributes to exactly one of {local, stream}, so Thm A.4's
retained-mass guarantee holds with α = (approximation error of φ on the
stream part) only.

Train/prefill use the chunk-parallel formulation; decode uses the bounded
state (ring buffer + (S, Z)) with fold-on-full semantics that reproduce the
training chunk boundaries bit-exactly.  Total decode state per head:
L·(d+d_v) + m·(d_v+1) scalars — independent of context length, which is the
paper's entire point (Eq. 11/13 budgets; enforced via
:mod:`repro.core.hardware_model`).

GQA is supported natively (queries grouped over KV heads; stream state and
buffers are per-KV-head, matching how a switch would track per-flow state
once per flow, not once per parallel query pipeline).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import key_selection as ks
from repro.core.feature_maps import (
    FeatureMapConfig,
    _normalize,
    apply_feature_map,
    init_feature_map,
)

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class ChimeraAttentionConfig:
    feature_map: FeatureMapConfig = FeatureMapConfig(kind="exp_prf", m=64)
    chunk_size: int = 128  # L: the SRAM window / Partition size
    n_global: int = 32  # |G| static TCAM-indexed tokens (0 disables)
    sig_bits: int = 32
    match_hamming: int = 12
    use_local: bool = True  # ablation: Local-Only / Global-Only (Table 3)
    use_stream: bool = True
    gamma: float = 1e-6
    use_pallas: bool = False  # TPU kernels; False = pure-jnp (XLA) path
    # kernel backend when use_pallas is set: "auto" | "pallas-tpu" |
    # "pallas-interpret" | "reference" (see repro.kernels.dispatch)
    backend: str = "auto"
    # repeat KV to the query-head count so head-sharded TP works when
    # n_kv_heads doesn't divide the model axis (e.g. kv=8 on 16-way TP);
    # per-head stream state grows Gq-fold but shards TP-fold — net win.
    # Set by the launcher (build_cell) based on the mesh, not by hand.
    expand_kv: bool = False

    def state_scalars(self, d_head: int, d_v: int) -> int:
        """Per-(flow, head) decalar state for the hardware model (Eq. 11/13)."""
        m = self.feature_map.feature_dim(d_head)
        return self.chunk_size * (d_head + d_v) + m * (d_v + 1)


def init_chimera_attention(
    cfg: ChimeraAttentionConfig,
    n_kv_heads: int,
    d_head: int,
    d_v: int,
    key: jax.Array,
) -> Params:
    kfm, ksig, kg1, kg2 = jax.random.split(key, 4)
    params: Params = {"fm": init_feature_map(cfg.feature_map, d_head, kfm)}
    if cfg.n_global > 0:
        params["sig_proj"] = ks.init_signature_projection(ksig, d_head, cfg.sig_bits)
        params["k_global"] = (
            jax.random.normal(kg1, (n_kv_heads, cfg.n_global, d_head)) / math.sqrt(d_head)
        )
        params["v_global"] = (
            jax.random.normal(kg2, (n_kv_heads, cfg.n_global, d_v)) / math.sqrt(d_v)
        )
    return params


def _group_queries(q: jax.Array, n_kv_heads: int) -> jax.Array:
    """(B, H, T, d) -> (B, Hkv, G, T, d) without materializing repeats."""
    B, H, T, d = q.shape
    return q.reshape(B, n_kv_heads, H // n_kv_heads, T, d)


def _global_partials(
    cfg: ChimeraAttentionConfig,
    params: Params,
    qh: jax.Array,  # (B, Hkv, Gq, T, d) normalized queries
    phi_q: jax.Array,  # (B, Hkv, Gq, T, m)
) -> Tuple[jax.Array, jax.Array]:
    """Static-global contribution with TCAM ternary gating (Eq. 14)."""
    kg = params["k_global"]
    vg = params["v_global"]
    n_kv_q = qh.shape[1]
    if kg.shape[0] != n_kv_q:  # expand_kv repeated the kv heads
        rep = n_kv_q // kg.shape[0]
        kg = jnp.repeat(kg, rep, axis=0)
        vg = jnp.repeat(vg, rep, axis=0)
    kg = _normalize(kg, cfg.feature_map.input_scale)  # (Hkv,G,d)
    phi_kg = apply_feature_map(cfg.feature_map, params["fm"], kg)
    sig_q = ks.make_signature(qh, params["sig_proj"])  # (B,Hkv,Gq,T,W)
    sig_k = ks.make_signature(kg, params["sig_proj"])  # (Hkv,G,W)
    match = ks.ternary_match_mask(
        sig_q.reshape(sig_q.shape[:-1] + (sig_q.shape[-1],)),
        sig_k[None, :, None],
        cfg.match_hamming,
    )  # (B,Hkv,Gq,T,G)
    scores = jnp.einsum("bhgtm,hcm->bhgtc", phi_q, phi_kg) * match
    num = jnp.einsum("bhgtc,hcd->bhgtd", scores, vg)
    den = jnp.sum(scores, axis=-1)
    return num, den


def chimera_attention(
    cfg: ChimeraAttentionConfig,
    params: Params,
    q: jax.Array,  # (B, H, T, d)
    k: jax.Array,  # (B, Hkv, T, d)
    v: jax.Array,  # (B, Hkv, T, d_v)
) -> jax.Array:
    """Train/prefill path: chunk-parallel Chimera attention.  Causal."""
    B, H, T, d = q.shape
    n_kv = k.shape[1]
    if cfg.expand_kv and n_kv < H:
        rep = H // n_kv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
        n_kv = H
    d_v = v.shape[-1]
    L = cfg.chunk_size
    if T % L != 0:
        raise ValueError(f"T={T} must be divisible by chunk_size={L}")
    n_chunks = T // L
    scale = cfg.feature_map.input_scale

    from repro.core.annotate import constrain

    qh = _normalize(_group_queries(q, n_kv), scale)  # (B,Hkv,Gq,T,d)
    kh = _normalize(k, scale)  # (B,Hkv,T,d)
    phi_q = apply_feature_map(cfg.feature_map, params["fm"], qh)
    phi_k = apply_feature_map(cfg.feature_map, params["fm"], kh)
    qh = constrain(qh, ("batch", "kv_heads", None, None, None))
    kh = constrain(kh, ("batch", "kv_heads", None, None))
    phi_q = constrain(phi_q, ("batch", "kv_heads", None, None, None))
    phi_k = constrain(phi_k, ("batch", "kv_heads", None, None))
    v = constrain(v, ("batch", "kv_heads", None, None))
    m = phi_q.shape[-1]
    Gq = H // n_kv

    if cfg.use_pallas:
        from repro.kernels.chimera_attention import ops as _kops

        num, den = _kops.chimera_attention_partials(
            qh, kh, v, phi_q, phi_k, chunk_size=L,
            use_local=cfg.use_local, use_stream=cfg.use_stream,
            backend=cfg.backend,
        )
        if cfg.n_global > 0:
            gnum, gden = _global_partials(cfg, params, qh, phi_q)
            num = num + gnum
            den = den + gden
        out = num / (den[..., None] + cfg.gamma)
        return out.reshape(B, H, T, d_v)
    else:
        # Partition over time into SRAM-sized chunks
        qc = qh.reshape(B, n_kv, Gq, n_chunks, L, d)
        pqc = phi_q.reshape(B, n_kv, Gq, n_chunks, L, m)
        kc = kh.reshape(B, n_kv, n_chunks, L, d)
        pkc = phi_k.reshape(B, n_kv, n_chunks, L, m)
        vc = v.reshape(B, n_kv, n_chunks, L, d_v)
        causal = jnp.tril(jnp.ones((L, L), q.dtype))
        inv_sqrt_d = 1.0 / math.sqrt(d)

        def chunk_step(carry, xs):
            S, Z = carry  # (B,Hkv,m,dv), (B,Hkv,m): state before this chunk
            q_c, pq_c, k_c, pk_c, v_c = xs
            num = jnp.zeros((B, n_kv, Gq, L, d_v), q.dtype)
            den = jnp.zeros((B, n_kv, Gq, L), q.dtype)
            if cfg.use_local:
                # Map: exact exp-kernel causal attention within the chunk
                s_loc = jnp.exp(
                    jnp.einsum("bhgid,bhjd->bhgij", q_c, k_c) * inv_sqrt_d
                ) * causal
                num = num + jnp.einsum("bhgij,bhjd->bhgid", s_loc, v_c)
                den = den + jnp.sum(s_loc, axis=-1)
            if cfg.use_stream:
                # compressed-history readout (Eq. 6 against carried S, Z)
                num = num + jnp.einsum("bhgim,bhmd->bhgid", pq_c, S)
                den = den + jnp.einsum("bhgim,bhm->bhgi", pq_c, Z)
            # SumReduce: fold the chunk leaving SRAM into the stream state
            S = S + jnp.einsum("bhjm,bhjd->bhmd", pk_c, v_c)
            Z = Z + jnp.sum(pk_c, axis=2)
            # scan carries lose propagated shardings; re-pin per-head state
            S = constrain(S, ("batch", "kv_heads", None, None))
            Z = constrain(Z, ("batch", "kv_heads", None))
            return (S, Z), (num, den)

        # nested remat: recompute intra-chunk scores in the backward pass
        # instead of stashing (n_chunks, B, H, L, L) score tensors
        chunk_step = jax.checkpoint(chunk_step, prevent_cse=False)
        S0 = jnp.zeros((B, n_kv, m, d_v), q.dtype)
        Z0 = jnp.zeros((B, n_kv, m), q.dtype)
        xs = (
            jnp.moveaxis(qc, 3, 0),
            jnp.moveaxis(pqc, 3, 0),
            jnp.moveaxis(kc, 2, 0),
            jnp.moveaxis(pkc, 2, 0),
            jnp.moveaxis(vc, 2, 0),
        )
        _, (nums, dens) = jax.lax.scan(chunk_step, (S0, Z0), xs)
        num = jnp.moveaxis(nums, 0, 3).reshape(B, n_kv, Gq, T, d_v)
        den = jnp.moveaxis(dens, 0, 3).reshape(B, n_kv, Gq, T)

        if cfg.n_global > 0:
            gnum, gden = _global_partials(cfg, params, qh, phi_q)
            num = num + gnum
            den = den + gden
        out = num / (den[..., None] + cfg.gamma)
        return out.reshape(B, H, T, d_v)


# --------------------------------------------------------------------------
# Bounded-state decode (serve path)
# --------------------------------------------------------------------------

@dataclasses.dataclass
class ChimeraState:
    """Per-request bounded decode state (a pytree)."""

    S: jax.Array  # (B, Hkv, m, d_v)
    Z: jax.Array  # (B, Hkv, m)
    k_buf: jax.Array  # (B, Hkv, L, d) normalized keys in the SRAM ring
    v_buf: jax.Array  # (B, Hkv, L, d_v)
    count: jax.Array  # () int32 — fill level of the ring buffer


jax.tree_util.register_pytree_node(
    ChimeraState,
    lambda s: ((s.S, s.Z, s.k_buf, s.v_buf, s.count), None),
    lambda _, c: ChimeraState(*c),
)


def init_decode_state(
    cfg: ChimeraAttentionConfig,
    batch: int,
    n_kv_heads: int,
    d_head: int,
    d_v: int,
    dtype=jnp.float32,
) -> ChimeraState:
    m = cfg.feature_map.feature_dim(d_head)
    L = cfg.chunk_size
    return ChimeraState(
        S=jnp.zeros((batch, n_kv_heads, m, d_v), dtype),
        Z=jnp.zeros((batch, n_kv_heads, m), dtype),
        k_buf=jnp.zeros((batch, n_kv_heads, L, d_head), dtype),
        v_buf=jnp.zeros((batch, n_kv_heads, L, d_v), dtype),
        count=jnp.zeros((batch,), jnp.int32),  # per-sequence fill level
    )


def prefill_into_state(
    cfg: ChimeraAttentionConfig,
    params: Params,
    k: jax.Array,  # (B, Hkv, T, d) raw keys of the prompt
    v: jax.Array,
) -> ChimeraState:
    """Build decode state from a prompt: full chunks fold into (S, Z),
    the residual tail occupies the ring buffer — identical boundaries to the
    chunked train path."""
    B, n_kv, T, d = k.shape
    d_v = v.shape[-1]
    L = cfg.chunk_size
    n_full = T // L
    tail = T - n_full * L
    kh = _normalize(k, cfg.feature_map.input_scale)
    phi_k = apply_feature_map(cfg.feature_map, params["fm"], kh)
    m = phi_k.shape[-1]
    if n_full > 0:
        pk = phi_k[:, :, : n_full * L].reshape(B, n_kv, n_full, L, m)
        vv = v[:, :, : n_full * L].reshape(B, n_kv, n_full, L, d_v)
        S = jnp.einsum("bhnjm,bhnjd->bhmd", pk, vv)
        Z = jnp.sum(pk, axis=(2, 3))
    else:
        S = jnp.zeros((B, n_kv, m, d_v), k.dtype)
        Z = jnp.zeros((B, n_kv, m), k.dtype)
    k_buf = jnp.zeros((B, n_kv, L, d), k.dtype)
    v_buf = jnp.zeros((B, n_kv, L, d_v), k.dtype)
    if tail:
        k_buf = k_buf.at[:, :, :tail].set(kh[:, :, n_full * L :])
        v_buf = v_buf.at[:, :, :tail].set(v[:, :, n_full * L :])
    return ChimeraState(
        S=S, Z=Z, k_buf=k_buf, v_buf=v_buf,
        count=jnp.full((B,), tail, jnp.int32),
    )


def decode_kernel_gap(arch) -> Optional[str]:
    """Why a model's per-token decode under ``arch`` (an ``ArchConfig``)
    runs the jnp (XLA) path instead of the fused ``decode_step`` kernel;
    ``None`` when it takes the kernel.  The one place that makes this
    choice, so the compiler's ledger and the engines report what actually
    runs."""
    if not arch.use_chimera:
        return "softmax attention has no decode_step kernel"
    return _chimera_kernel_gap(arch.chimera)


def _chimera_kernel_gap(cfg: ChimeraAttentionConfig) -> Optional[str]:
    """:func:`decode_kernel_gap` for a Chimera layer; what
    :func:`chimera_decode_step` branches on."""
    if not cfg.use_pallas:
        return "no kernel backend selected"
    if not (cfg.use_local and cfg.use_stream):
        return "the decode_step kernel fuses local + stream; this config ablates one"
    if cfg.n_global > 0:
        return f"the global TCAM tier (n_global={cfg.n_global}) has no decode_step kernel"
    return None


def chimera_decode_step(
    cfg: ChimeraAttentionConfig,
    params: Params,
    q_t: jax.Array,  # (B, H, d)
    k_t: jax.Array,  # (B, Hkv, d)
    v_t: jax.Array,  # (B, Hkv, d_v)
    state: ChimeraState,
) -> Tuple[jax.Array, ChimeraState]:
    """One non-iterative decode step: buffer write, exact local readout,
    stream readout, global match, merge — then fold-on-full (Eqs. 6/9/10/14).
    """
    B, H, d = q_t.shape
    n_kv = k_t.shape[1]
    if cfg.expand_kv and n_kv < H:
        rep = H // n_kv
        k_t = jnp.repeat(k_t, rep, axis=1)
        v_t = jnp.repeat(v_t, rep, axis=1)
        n_kv = H
    Gq = H // n_kv
    d_v = v_t.shape[-1]
    L = cfg.chunk_size
    scale = cfg.feature_map.input_scale
    inv_sqrt_d = 1.0 / math.sqrt(d)

    qh = _normalize(q_t.reshape(B, n_kv, Gq, d), scale)
    kh = _normalize(k_t, scale)
    phi_q = apply_feature_map(cfg.feature_map, params["fm"], qh)  # (B,Hkv,Gq,m)
    phi_k = apply_feature_map(cfg.feature_map, params["fm"], kh)  # (B,Hkv,m)

    # write the arriving token into the SRAM ring (per-sequence position):
    # each batch slot carries its own fill level so continuous-batching
    # engines can start/stop requests independently
    c = state.count  # (B,)
    slot = (jnp.arange(L)[None, :] == c[:, None])[:, None, :, None]  # (B,1,L,1)
    k_buf = jnp.where(slot, kh[:, :, None, :], state.k_buf)
    v_buf = jnp.where(slot, v_t[:, :, None, :], state.v_buf)

    if _chimera_kernel_gap(cfg) is None:
        # fused per-packet program through the dispatch registry: the kernel
        # performs ring write / local / stream / merge / fold in one pass
        # (it receives the PRE-write buffers and redoes the slot write)
        from repro.kernels.decode_step import ops as _dops

        phi_buf = apply_feature_map(cfg.feature_map, params["fm"], k_buf)
        m = phi_q.shape[-1]
        BH = B * n_kv
        out, (S2, Z2, kb2, vb2, c2) = _dops.decode_step(
            qh.reshape(BH, Gq, d),
            kh.reshape(BH, d),
            v_t.reshape(BH, d_v),
            phi_q.reshape(BH, Gq, m),
            phi_buf.reshape(BH, L, m),
            state.k_buf.reshape(BH, L, d),
            state.v_buf.reshape(BH, L, d_v),
            state.S.reshape(BH, m, d_v),
            state.Z.reshape(BH, m),
            jnp.repeat(c, n_kv),
            chunk_size=L,
            gamma=cfg.gamma,
            backend=cfg.backend,
        )
        new_state = ChimeraState(
            S=S2.reshape(B, n_kv, m, d_v),
            Z=Z2.reshape(B, n_kv, m),
            k_buf=kb2.reshape(B, n_kv, L, d),
            v_buf=vb2.reshape(B, n_kv, L, d_v),
            count=c2.reshape(B, n_kv)[:, 0],
        )
        return out.reshape(B, H, d_v), new_state

    num = jnp.zeros((B, n_kv, Gq, d_v), q_t.dtype)
    den = jnp.zeros((B, n_kv, Gq), q_t.dtype)
    if cfg.use_local:
        valid = (jnp.arange(L)[None, :] <= c[:, None]).astype(q_t.dtype)  # (B,L)
        s_loc = jnp.exp(jnp.einsum("bhgd,bhjd->bhgj", qh, k_buf) * inv_sqrt_d)
        s_loc = s_loc * valid[:, None, None, :]
        num = num + jnp.einsum("bhgj,bhjd->bhgd", s_loc, v_buf)
        den = den + jnp.sum(s_loc, axis=-1)
    if cfg.use_stream:
        num = num + jnp.einsum("bhgm,bhmd->bhgd", phi_q, state.S)
        den = den + jnp.einsum("bhgm,bhm->bhg", phi_q, state.Z)
    if cfg.n_global > 0:
        gnum, gden = _global_partials(
            cfg, params, qh[:, :, :, None, :], phi_q[:, :, :, None, :]
        )
        num = num + gnum[:, :, :, 0]
        den = den + gden[:, :, :, 0]
    out = num / (den[..., None] + cfg.gamma)

    # fold-on-full (per sequence): compress the full ring into (S, Z)
    full = c + 1 >= L  # (B,)
    phi_buf = apply_feature_map(cfg.feature_map, params["fm"], k_buf)
    S_fold = state.S + jnp.einsum("bhjm,bhjd->bhmd", phi_buf, v_buf)
    Z_fold = state.Z + jnp.sum(phi_buf, axis=2)
    f4 = full[:, None, None, None]
    f3 = full[:, None, None]
    new_state = ChimeraState(
        S=jnp.where(f4, S_fold, state.S),
        Z=jnp.where(f3, Z_fold, state.Z),
        k_buf=jnp.where(f4, jnp.zeros_like(k_buf), k_buf),
        v_buf=jnp.where(f4, jnp.zeros_like(v_buf), v_buf),
        count=jnp.where(full, 0, c + 1).astype(jnp.int32),
    )
    return out.reshape(B, H, d_v), new_state


def reference_attention(
    cfg: ChimeraAttentionConfig,
    params: Params,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
) -> jax.Array:
    """O(T²) oracle with identical semantics, built from explicit masks.

    Token i attends: exactly (exp kernel) to keys in its own chunk (j ≤ i,
    same chunk); via φ to all earlier chunks; plus matched globals.  Used by
    unit tests to validate both the chunked path and the decode path."""
    B, H, T, d = q.shape
    n_kv = k.shape[1]
    if cfg.expand_kv and n_kv < H:
        rep = H // n_kv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
        n_kv = H
    Gq = H // n_kv
    scale = cfg.feature_map.input_scale
    qh = _normalize(_group_queries(q, n_kv), scale)
    kh = _normalize(k, scale)
    phi_q = apply_feature_map(cfg.feature_map, params["fm"], qh)
    phi_k = apply_feature_map(cfg.feature_map, params["fm"], kh)
    idx = jnp.arange(T)
    same_chunk = (idx[:, None] // cfg.chunk_size) == (idx[None, :] // cfg.chunk_size)
    causal = idx[:, None] >= idx[None, :]
    local_mask = (same_chunk & causal).astype(q.dtype)
    stream_mask = ((~same_chunk) & causal).astype(q.dtype)
    num = jnp.zeros((B, n_kv, Gq, T, v.shape[-1]), q.dtype)
    den = jnp.zeros((B, n_kv, Gq, T), q.dtype)
    if cfg.use_local:
        s_loc = jnp.exp(
            jnp.einsum("bhgid,bhjd->bhgij", qh, kh) / math.sqrt(d)
        ) * local_mask
        num = num + jnp.einsum("bhgij,bhjd->bhgid", s_loc, v)
        den = den + jnp.sum(s_loc, axis=-1)
    if cfg.use_stream:
        s_str = jnp.einsum("bhgim,bhjm->bhgij", phi_q, phi_k) * stream_mask
        num = num + jnp.einsum("bhgij,bhjd->bhgid", s_str, v)
        den = den + jnp.sum(s_str, axis=-1)
    if cfg.n_global > 0:
        gnum, gden = _global_partials(cfg, params, qh, phi_q)
        num = num + gnum
        den = den + gden
    out = num / (den[..., None] + cfg.gamma)
    return out.reshape(B, H, T, v.shape[-1])


def chimera_prefill(
    cfg: ChimeraAttentionConfig,
    params: Params,
    q: jax.Array,  # (B, H, T, d) — T may be ragged (not a chunk multiple)
    k: jax.Array,  # (B, Hkv, T, d)
    v: jax.Array,  # (B, Hkv, T, d_v)
) -> Tuple[jax.Array, ChimeraState]:
    """Serving prefill: outputs for every prompt position AND the decode
    state, in one chunk-parallel pass.  Ragged tails (T mod L ≠ 0) are
    handled as a single partial chunk: exact local attention over the tail +
    stream readout against the folded state; the tail occupies the ring
    buffer unfolded — bit-identical to token-by-token decode (tested)."""
    B, H, T, d = q.shape
    n_kv = k.shape[1]
    if cfg.expand_kv and n_kv < H:
        rep = H // n_kv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
        n_kv = H
    L = cfg.chunk_size
    n_full = T // L
    tail = T - n_full * L
    Gq = H // n_kv
    d_v = v.shape[-1]
    scale = cfg.feature_map.input_scale
    inv_sqrt_d = 1.0 / math.sqrt(d)

    outs = []
    if n_full:
        out_full = chimera_attention(
            cfg, params, q[:, :, : n_full * L], k[:, :, : n_full * L], v[:, :, : n_full * L]
        )
        outs.append(out_full)
    state = prefill_into_state(cfg, params, k, v)

    if tail:
        # partial chunk: exact exp-kernel attention within the tail + stream
        # readout against the state of the folded full chunks
        qh = _normalize(_group_queries(q[:, :, n_full * L :], n_kv), scale)
        kh = _normalize(k[:, :, n_full * L :], scale)
        v_t = v[:, :, n_full * L :]
        phi_q = apply_feature_map(cfg.feature_map, params["fm"], qh)
        num = jnp.zeros((B, n_kv, Gq, tail, d_v), q.dtype)
        den = jnp.zeros((B, n_kv, Gq, tail), q.dtype)
        if cfg.use_local:
            causal = jnp.tril(jnp.ones((tail, tail), q.dtype))
            s_loc = jnp.exp(
                jnp.einsum("bhgid,bhjd->bhgij", qh, kh) * inv_sqrt_d
            ) * causal
            num = num + jnp.einsum("bhgij,bhjd->bhgid", s_loc, v_t)
            den = den + jnp.sum(s_loc, axis=-1)
        if cfg.use_stream and n_full:
            kh_full = _normalize(k[:, :, : n_full * L], scale)
            phi_k_full = apply_feature_map(cfg.feature_map, params["fm"], kh_full)
            S_full = jnp.einsum("bhjm,bhjd->bhmd", phi_k_full, v[:, :, : n_full * L])
            Z_full = jnp.sum(phi_k_full, axis=2)
            num = num + jnp.einsum("bhgim,bhmd->bhgid", phi_q, S_full)
            den = den + jnp.einsum("bhgim,bhm->bhgi", phi_q, Z_full)
        if cfg.n_global > 0:
            gnum, gden = _global_partials(cfg, params, qh, phi_q)
            num = num + gnum
            den = den + gden
        out_tail = (num / (den[..., None] + cfg.gamma)).reshape(B, H, tail, d_v)
        outs.append(out_tail)
    out = jnp.concatenate(outs, axis=2) if len(outs) > 1 else outs[0]
    return out, state
