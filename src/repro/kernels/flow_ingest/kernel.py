"""Pallas TPU kernel: the streaming-score stage of fused flow ingest.

The ``flow_ingest`` family keeps the flow table resident on-device and
consumes a whole packet batch in one launch (see
:func:`repro.serve.flow_engine.make_fused_ingest`).  Of the fused step's
stages — slot gather, token-decode scan, streaming scores + TCAM veto, slot
scatter — the score stage is the one with kernel-shaped arithmetic (two
dense heads on the MXU, a wide ternary match on the VPU), so that is what
the Pallas backends replace; gather/scan/scatter stay on the shared jnp
path where XLA's dynamic-slice machinery is already optimal.

Layout: the lane axis (packets in flight) is tiled by ``lane_tile`` and
pipelined through the grid — Pallas double-buffers the per-lane-block
streams (pooled features, signatures, sticky bits) into VMEM while the
previous block computes.  The TCAM tables ride along as whole-array blocks
(every lane block revisits them; Pallas keeps revisited blocks resident).
``state_tile`` chunks the ternary match over the rule axis to bound the
VPU working set per iteration.

Bit-exactness contract (vs :func:`repro.train.classifier.streaming_scores`):
the kernel re-invokes the *library* score functions — ``layers.dense``,
``symbolic.ternary_match`` / ``hard_hit`` / ``soft_score``,
``fusion.cascade_fusion`` — on views reconstructed inside the kernel.  The
per-``state_tile`` match chunks produce exact booleans, are concatenated
and sliced back to the true rule count ``M`` *before* any reduction, so
every float reduction runs at the oracle's own shape and order.  Padded
lanes (to a ``lane_tile`` multiple) and padded rules (to a ``state_tile``
multiple) are sliced off the same way.  Bool values cross the pallas_call
boundary as int32 (Mosaic-friendly); biases are wired only when present in
the params pytree — the classifier heads carry none, and adding a zero
bias could flip ``-0.0`` bits.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import fusion as fusion_mod
from repro.core import symbolic
from repro.models import layers


def _round_up(n: int, k: int) -> int:
    return ((n + k - 1) // k) * k


def flow_ingest_scores_pallas(
    ccfg,
    params,
    rules: symbolic.RuleSet,
    pooled,  # (B, d) f32 — running mean of decoded features
    sig,  # (B, W) uint32 — cumulative packed marker signature
    sticky,  # (B,) bool — lifetime veto bit
    *,
    lane_tile: int = 128,
    state_tile: int = 128,
    interpret: bool = False,
):
    """Streaming scores + TCAM veto for one chunk of lanes.

    Same contract as :func:`repro.train.classifier.streaming_scores`:
    returns ``({class_logits, s_nn, s_sym, hard_hit, trust}, new_sticky)``.
    """
    B, d = pooled.shape
    W = sig.shape[1]
    M = int(rules.weights.shape[0])
    cls_w = params["cls"]["w"]
    anom_w = params["anom"]["w"]
    K = cls_w.shape[1]
    has_cls_b = "b" in params["cls"]
    has_anom_b = "b" in params["anom"]

    lt = min(lane_tile, _round_up(B, 8))
    Bp = _round_up(B, lt)
    st = min(state_tile, _round_up(M, 8))
    Mp = _round_up(M, st)
    nb = Mp // st

    if Bp != B:
        pooled = jnp.pad(pooled, ((0, Bp - B), (0, 0)))
        sig = jnp.pad(sig, ((0, Bp - B), (0, 0)))
        sticky = jnp.pad(sticky, (0, Bp - B))
    vals, msks = rules.values, rules.masks
    if Mp != M:
        # padded rules are mask-0 (match-everything) but never *read*: the
        # kernel slices hits back to [:, :M] before any reduction
        vals = jnp.pad(vals, ((0, Mp - M), (0, 0)))
        msks = jnp.pad(msks, ((0, Mp - M), (0, 0)))
    sticky_i = sticky.astype(jnp.int32)[:, None]  # (Bp, 1)
    wts2 = rules.weights[:, None]  # (M, 1)
    hard2 = rules.hard.astype(jnp.int32)[:, None]  # (M, 1)
    fuse = jnp.stack(
        [
            jnp.asarray(params["fusion"]["alpha"], jnp.float32),
            jnp.asarray(params["fusion"]["beta"], jnp.float32),
        ]
    ).reshape(1, 2)

    def kernel(*refs):
        it = iter(refs)
        fuse_ref = next(it)
        pooled_ref, sig_ref, sticky_ref = next(it), next(it), next(it)
        cls_w_ref = next(it)
        cls_b_ref = next(it) if has_cls_b else None
        anom_w_ref = next(it)
        anom_b_ref = next(it) if has_anom_b else None
        vals_ref, msks_ref, wts_ref, hard_ref = next(it), next(it), next(it), next(it)
        logits_ref, s_nn_ref, s_sym_ref, trust_ref, hard_out_ref = (
            next(it), next(it), next(it), next(it), next(it),
        )

        pooled_b = pooled_ref[...]
        sig_b = sig_ref[...]
        sticky_b = sticky_ref[...][:, 0] != 0  # (lt,)

        # TCAM ternary match, chunked over the rule axis.  Each chunk is an
        # exact boolean computation, so chunking cannot perturb bits; the
        # concat+slice restores the oracle's (lt, M) hits layout.
        v_all, m_all = vals_ref[...], msks_ref[...]
        chunks = []
        for b in range(nb):
            blk = symbolic.RuleSet(
                values=v_all[b * st : (b + 1) * st],
                masks=m_all[b * st : (b + 1) * st],
                weights=jnp.zeros((st,), jnp.float32),
                hard=jnp.zeros((st,), bool),
            )
            chunks.append(symbolic.ternary_match(sig_b, blk))
        hits = (chunks[0] if nb == 1 else jnp.concatenate(chunks, -1))[:, :M]

        rs = symbolic.RuleSet(
            values=v_all[:M],
            masks=m_all[:M],
            weights=wts_ref[...][:, 0],
            hard=hard_ref[...][:, 0] != 0,
        )
        hard_b = symbolic.hard_hit(hits, rs) | sticky_b  # (lt,)
        s_sym = symbolic.soft_score(hits, rs)  # (lt,)

        cls_p = {"w": cls_w_ref[...]}
        if has_cls_b:
            cls_p["b"] = cls_b_ref[...][0]
        anom_p = {"w": anom_w_ref[...]}
        if has_anom_b:
            anom_p["b"] = anom_b_ref[...][0]
        logits = layers.dense(cls_p, pooled_b)  # (lt, K)
        s_nn = layers.dense(anom_p, pooled_b)[..., 0]  # (lt,)

        fp = {"alpha": fuse_ref[0, 0], "beta": fuse_ref[0, 1]}
        trust = fusion_mod.cascade_fusion(
            fp, s_nn, s_sym, hard_b, lambda_h=ccfg.lambda_h
        )

        logits_ref[...] = logits
        s_nn_ref[...] = s_nn[:, None]
        s_sym_ref[...] = s_sym[:, None]
        trust_ref[...] = trust[:, None]
        hard_out_ref[...] = hard_b.astype(jnp.int32)[:, None]

    lane = lambda i: (i, 0)
    whole = lambda i: (0, 0)
    in_specs = [pl.BlockSpec((1, 2), whole)]  # fusion (alpha, beta)
    inputs = [fuse]
    in_specs += [
        pl.BlockSpec((lt, d), lane),
        pl.BlockSpec((lt, W), lane),
        pl.BlockSpec((lt, 1), lane),
    ]
    inputs += [pooled, sig, sticky_i]
    in_specs.append(pl.BlockSpec((d, K), whole))
    inputs.append(cls_w)
    if has_cls_b:
        in_specs.append(pl.BlockSpec((1, K), whole))
        inputs.append(params["cls"]["b"].reshape(1, K))
    in_specs.append(pl.BlockSpec((d, 1), whole))
    inputs.append(anom_w)
    if has_anom_b:
        in_specs.append(pl.BlockSpec((1, 1), whole))
        inputs.append(params["anom"]["b"].reshape(1, 1))
    in_specs += [
        pl.BlockSpec((Mp, W), whole),
        pl.BlockSpec((Mp, W), whole),
        pl.BlockSpec((M, 1), whole),
        pl.BlockSpec((M, 1), whole),
    ]
    inputs += [vals, msks, wts2, hard2]

    out_shape = (
        jax.ShapeDtypeStruct((Bp, K), jnp.float32),
        jax.ShapeDtypeStruct((Bp, 1), jnp.float32),
        jax.ShapeDtypeStruct((Bp, 1), jnp.float32),
        jax.ShapeDtypeStruct((Bp, 1), jnp.float32),
        jax.ShapeDtypeStruct((Bp, 1), jnp.int32),
    )
    out_specs = (
        pl.BlockSpec((lt, K), lane),
        pl.BlockSpec((lt, 1), lane),
        pl.BlockSpec((lt, 1), lane),
        pl.BlockSpec((lt, 1), lane),
        pl.BlockSpec((lt, 1), lane),
    )

    logits_p, s_nn_p, s_sym_p, trust_p, hard_p = pl.pallas_call(
        kernel,
        grid=(Bp // lt,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*inputs)

    hard_out = hard_p[:B, 0] != 0
    out = {
        "class_logits": logits_p[:B],
        "s_nn": s_nn_p[:B, 0],
        "s_sym": s_sym_p[:B, 0],
        "hard_hit": hard_out,
        "trust": trust_p[:B, 0],
    }
    return out, hard_out


def make_pallas_score_fn(ccfg, tiles=None, interpret: bool = False):
    """Close the autotuned tile choice over the canonical score-stage hook
    ``(params, rules, pooled, sig, sticky) -> (outputs, new_sticky)``."""
    tiles = tiles or {}
    lane_tile = int(tiles.get("lane_tile", 128))
    state_tile = int(tiles.get("state_tile", 128))

    def score_fn(params, rules, pooled, sig, sticky):
        return flow_ingest_scores_pallas(
            ccfg, params, rules, pooled, sig, sticky,
            lane_tile=lane_tile, state_tile=state_tile, interpret=interpret,
        )

    return score_fn


def fused_ingest_pallas(
    ccfg, n_slots: int, max_len: int, int_plan=None, *, tiles=None,
    interpret: bool = False,
):
    """``flow_ingest`` builder for the Pallas backends.

    Shares the fused gather/scan/scatter structure with the reference
    builder and swaps in the Pallas score stage.  Under int-emulation the
    score path is the lowered int32 program (no float kernel applies), so
    the builder degrades to the reference structure — the backend choice
    then still governs the *backbone* kernels via ``apply_kernel_backend``.
    """
    from repro.serve.flow_engine import make_fused_ingest

    if int_plan is not None:
        return make_fused_ingest(ccfg, n_slots, max_len, int_plan=int_plan)
    return make_fused_ingest(
        ccfg, n_slots, max_len,
        score_fn=make_pallas_score_fn(ccfg, tiles=tiles, interpret=interpret),
    )
