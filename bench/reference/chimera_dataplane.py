"""Plain reference of the Chimera traffic classifier, for checking outputs.

Straightforward ``jax.numpy`` over whole 64-token chunks of one flow at a
time, with no flow table, no ring buffer and no kernels.  It imports
nothing of the program.  Per flow, from its first packet in the table:

* each of the ``n_layers`` blocks is pre-norm attention then a SwiGLU MLP,
  with RMSNorm (eps 1e-6) and a learned scale;
* q, k, v are dense projections, q to ``n_heads`` heads and k, v to
  ``n_kv_heads``; query head h reads kv head h // (n_heads / n_kv_heads),
  its keys, values, state and global keys and values; q and k get rotary
  embeddings over interleaved pairs at the flow's token position (theta
  ``rope_theta``), then are scaled to norm ``input_scale``;
* Chimera attention of token i: exact exp(q.k / sqrt(d_head)) attention
  over the earlier tokens of i's own chunk (``chunk_size`` tokens, i
  included), plus the linearized readout phi(q).S / phi(q).Z of every
  earlier chunk, plus the static global keys whose 64-bit sign-LSH
  signature lies within ``match_hamming`` bits of q's; the three
  (numerator, denominator) pairs are summed and divided (+ ``gamma``);
* phi is the positive random-feature map exp(w.x' - |x'|^2 / 2) / sqrt(m)
  with x' = x / d_head ** 0.25 after scaling x to norm ``input_scale``;
* the final-norm hidden states are averaged over every token of the flow so
  far; the class logits and the anomaly logit s_nn are dense heads of that
  mean.

The symbolic path (marker signature, TCAM match, veto, fusion) is exact and
is worked out on the host by the output check.

The layers are public, for another family's reference to import
(``lib.spec.load_module("reference", "chimera_dataplane")``):
``chimera_attention`` and ``swiglu_mlp`` each take a block's leaves and
add their residual, and ``rms``, ``to_norm``, ``rope`` and ``phi`` are the
pieces they are built of.

``dtype`` float32 runs every product at ``highest`` precision, the
reference proper.  ``dtype`` bfloat16 runs the same equations with every
weight, activation and state in bfloat16: the lower-precision control.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def rms(x, scale, eps=1e-6):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def to_norm(x, r):
    n = jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True))
    return x * (r / jnp.maximum(n, 1e-6))


def rope(x, pos, theta):
    """x (..., T, dh) with pairs (0,1), (2,3), ...; pos (..., T)."""
    dh = x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh))
    ang = pos[..., None].astype(jnp.float32) * jnp.asarray(freqs, jnp.float32)
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).reshape(x.shape)


def phi(x, w, r):
    """Random-feature map of (..., dh) inputs with rows w (m, dh)."""
    xs = to_norm(x, r) / (x.shape[-1] ** 0.25)
    sq = 0.5 * jnp.sum(xs * xs, axis=-1, keepdims=True)
    return jnp.exp(xs @ w.T - sq) / math.sqrt(w.shape[0])


def _per_query_head(x, group: int, axis: int):
    """Each of the ``group`` query heads of a kv head reads that kv head:
    query head h reads kv head h // group."""
    return x if group == 1 else jnp.repeat(x, group, axis=axis)


def chimera_attention(model, p, x, S, Z, pos):
    """Pre-norm Chimera attention over a chunk, with its residual: the
    block's ``ln1`` and ``attn`` leaves.  x (B, L, d); S (B, Hkv, m, dv);
    Z (B, Hkv, m); returns (x, S, Z), the state with the chunk folded in."""
    B, L, d = x.shape
    H, Hkv, dh = model["n_heads"], model["n_kv_heads"], model["d_head"]
    group = H // Hkv
    fm = model["feature_map"]
    r = fm["input_scale"]
    a, ch = p["attn"], p["attn"]["chimera"]

    h = rms(x, p["ln1"]["scale"])

    def heads(w, n):
        return (h @ w).reshape(B, L, n, dh).transpose(0, 2, 1, 3)  # (B, n, L, dh)

    q = rope(heads(a["wq"]["w"], H), pos[:, None, :], model["rope_theta"])
    k = rope(heads(a["wk"]["w"], Hkv), pos[:, None, :], model["rope_theta"])
    v = heads(a["wv"]["w"], Hkv)
    qh, kh = to_norm(q, r), to_norm(k, r)
    pq, pk = phi(qh, ch["fm"]["w"], r), phi(kh, ch["fm"]["w"], r)
    kq, vq = _per_query_head(kh, group, 1), _per_query_head(v, group, 1)

    # local: exact exp kernel over the chunk, causal
    causal = jnp.tril(jnp.ones((L, L), x.dtype))
    s = jnp.exp(jnp.einsum("bhid,bhjd->bhij", qh, kq) / math.sqrt(dh)) * causal
    num = jnp.einsum("bhij,bhjd->bhid", s, vq)
    den = jnp.sum(s, axis=-1)
    # stream: the folded earlier chunks
    num = num + jnp.einsum("bhim,bhmd->bhid", pq, _per_query_head(S, group, 1))
    den = den + jnp.einsum("bhim,bhm->bhi", pq, _per_query_head(Z, group, 1))
    # static globals behind the signature match
    kg = to_norm(ch["k_global"], r)  # (Hkv, G, dh)
    pg = _per_query_head(phi(kg, ch["fm"]["w"], r), group, 0)
    sig_q = (qh @ ch["sig_proj"] > 0).astype(jnp.int32)  # (B, H, L, bits)
    sig_k = _per_query_head((kg @ ch["sig_proj"] > 0).astype(jnp.int32), group, 0)  # (H, G, bits)
    ham = jnp.sum(jnp.abs(sig_q[:, :, :, None, :] - sig_k[None, :, None, :, :]), -1)
    match = (ham <= model["match_hamming"]).astype(x.dtype)
    sg = jnp.einsum("bhim,hgm->bhig", pq, pg) * match
    num = num + jnp.einsum("bhig,hgd->bhid", sg, _per_query_head(ch["v_global"], group, 0))
    den = den + jnp.sum(sg, axis=-1)
    o = num / (den[..., None] + model["gamma"])
    x = x + o.transpose(0, 2, 1, 3).reshape(B, L, H * dh) @ a["wo"]["w"]
    S = S + jnp.einsum("bhjm,bhjd->bhmd", pk, v)
    Z = Z + jnp.sum(pk, axis=2)
    return x, S, Z


def swiglu_mlp(p, x):
    """Pre-norm SwiGLU MLP with its residual: the block's ``ln2`` and
    ``mlp`` leaves.  x (B, L, d)."""
    h = rms(x, p["ln2"]["scale"])
    mlp = p["mlp"]
    y = (jax.nn.silu(h @ mlp["wg"]["w"]) * (h @ mlp["wi"]["w"])) @ mlp["wo"]["w"]
    return x + y


def init_carry(model, lanes: int, dtype):
    nl, Hkv, dh = model["n_layers"], model["n_kv_heads"], model["d_head"]
    m = model["feature_map"]["m"]
    return (jnp.zeros((nl, lanes, Hkv, m, dh), dtype), jnp.zeros((nl, lanes, Hkv, m), dtype),
            jnp.zeros((lanes,), jnp.int32), jnp.zeros((lanes, model["d_model"]), dtype))


def make_block(model, dtype):
    """``block(params, carry, tok (B, C, L), reset (B, C)) -> (carry, (logits
    (B, C, L, K), s_nn (B, C, L)))``: C chunks of every lane in turn, a lane's
    state zeroed where ``reset`` starts a new flow."""
    L = model["chunk_size"]
    nl = model["n_layers"]

    def chunk(params, carry, xs):
        S, Z, pos0, hs = carry
        tok, reset = xs
        keep = (~reset).astype(dtype)
        S = S * keep[None, :, None, None, None]
        Z = Z * keep[None, :, None, None]
        pos0 = jnp.where(reset, 0, pos0)
        hs = hs * keep[:, None]
        bb = params["backbone"]
        x = bb["embed"]["table"][tok]  # (B, L, d)
        pos = pos0[:, None] + jnp.arange(L, dtype=jnp.int32)
        S_new, Z_new = [], []
        for layer in range(nl):
            p = jax.tree_util.tree_map(lambda w: w[layer], bb["blocks"]["b0"])
            x, s_l, z_l = chimera_attention(model, p, x, S[layer], Z[layer], pos)
            x = swiglu_mlp(p, x)
            S_new.append(s_l)
            Z_new.append(z_l)
        hf = rms(x, bb["final_norm"]["scale"])
        cum = hs[:, None, :] + jnp.cumsum(hf, axis=1)
        pooled = cum / (pos + 1).astype(dtype)[..., None]
        logits = pooled @ params["cls"]["w"]
        s_nn = (pooled @ params["anom"]["w"])[..., 0]
        carry = (jnp.stack(S_new), jnp.stack(Z_new), pos0 + L, cum[:, -1])
        return carry, (logits.astype(jnp.float32), s_nn.astype(jnp.float32))

    def block(params, carry, tok, reset):
        carry, (logits, s_nn) = jax.lax.scan(
            lambda c, xs: chunk(params, c, xs), carry,
            (jnp.moveaxis(tok, 1, 0), jnp.moveaxis(reset, 1, 0)))
        return carry, (jnp.moveaxis(logits, 0, 1), jnp.moveaxis(s_nn, 0, 1))

    return jax.jit(block)


def _pack(segments: Sequence[np.ndarray], L: int, lanes: int, block: int):
    """Lay flows end to end on ``lanes`` lanes, each flow starting a chunk;
    returns tokens (lanes, C, L), reset (lanes, C) and each flow's
    (lane, first token) place."""
    chunks = [-(-len(s) // L) for s in segments]
    load = [0] * lanes
    place: List[Tuple[int, int]] = [(0, 0)] * len(segments)
    for i in sorted(range(len(segments)), key=lambda i: -chunks[i]):
        lane = int(np.argmin(load))
        place[i] = (lane, load[lane] * L)
        load[lane] += chunks[i]
    C = max(-(-max(load) // block) * block, block)
    tok = np.zeros((lanes, C, L), np.int32)
    reset = np.zeros((lanes, C), bool)
    for s, (lane, t0) in zip(segments, place):
        flat = tok[lane].reshape(-1)
        flat[t0:t0 + len(s)] = s
        reset[lane, t0 // L] = True
    return tok, reset, place


def run(model: Dict[str, Any], params, segments: Sequence[np.ndarray], *,
        dtype=jnp.float32, lanes: int = 8, block: int = 16):
    """Per-token (class logits (n, K), s_nn (n,)) of every flow, each flow a
    token sequence from its first packet in the table."""
    L = model["chunk_size"]
    tok, reset, place = _pack(segments, L, lanes, block)
    cast = jax.tree_util.tree_map(
        lambda w: w.astype(dtype) if jnp.issubdtype(w.dtype, jnp.floating) else w, params)
    fn = make_block(model, dtype)
    precision = "highest" if dtype == jnp.float32 else "default"
    carry = init_carry(model, lanes, dtype)
    outs_l, outs_s = [], []
    with jax.default_matmul_precision(precision):
        for c0 in range(0, tok.shape[1], block):
            carry, (lg, sn) = fn(cast, carry, jnp.asarray(tok[:, c0:c0 + block]),
                                 jnp.asarray(reset[:, c0:c0 + block]))
            outs_l.append(np.asarray(lg))
            outs_s.append(np.asarray(sn))
    logits = np.concatenate(outs_l, 1).reshape(lanes, -1, outs_l[0].shape[-1])
    s_nn = np.concatenate(outs_s, 1).reshape(lanes, -1)
    return [(logits[lane, t0:t0 + len(s)], s_nn[lane, t0:t0 + len(s)])
            for s, (lane, t0) in zip(segments, place)]
