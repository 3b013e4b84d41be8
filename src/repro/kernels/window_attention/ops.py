"""Public wrapper for the sliding-window flash attention kernel.

Backend selection goes through :mod:`repro.kernels.dispatch`; tile sizes
default to the autotuner (:mod:`repro.kernels.autotune`) — a cache hit
returns benchmark-tuned (blk_q, blk_k), a miss returns the MXU-aligned
heuristic.  Shapes no admissible tile covers (T or window not divisible by
any tile) fall back to the exact reference, as does ``backend="reference"``
— except under an explicit ``backend="pallas-tpu"``, which raises rather
than run something other than the kernel it asked for.

Like the chimera ops, the Pallas forward is wrapped in ``jax.custom_vjp``
with the reference formulation as the backward pass (pallas_call is not
reverse-differentiable; training backward through XLA's fused softmax chain
is fine — see DESIGN.md §7), so SWA models train under any backend.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels import autotune, dispatch
from repro.kernels.window_attention.ref import window_attention_ref


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _window_attention(q, k, v, window, blk_q, blk_k, backend):
    # q/k/v are (BH, T, d)-flattened
    impl = dispatch.resolve("window_attention", backend)
    return impl(q, k, v, window=window, blk_q=blk_q, blk_k=blk_k)


def _fwd(q, k, v, window, blk_q, blk_k, backend):
    return _window_attention(q, k, v, window, blk_q, blk_k, backend), (q, k, v)


def _bwd(window, blk_q, blk_k, backend, res, g):
    q, k, v = res
    _, vjp = jax.vjp(lambda q, k, v: window_attention_ref(q, k, v, window), q, k, v)
    return vjp(g)


_window_attention.defvjp(_fwd, _bwd)


def sliding_window_attention(
    q: jax.Array,  # (B, H, T, d)
    k: jax.Array,  # (B, H, T, d) — pre-expanded to H query heads
    v: jax.Array,
    window: int,
    blk: Optional[int] = None,
    *,
    blk_q: Optional[int] = None,
    blk_k: Optional[int] = None,
    backend: str = "auto",
    tile_cache: Optional[autotune.AutotuneCache] = None,
) -> jax.Array:
    B, H, T, d = q.shape
    dv = v.shape[-1]
    concrete = dispatch.resolve_backend(backend)
    if blk is not None:
        blk_q = blk if blk_q is None else blk_q
        blk_k = blk if blk_k is None else blk_k
    if concrete != "reference" and (blk_q is None or blk_k is None):
        tiles = autotune.get_tiles(
            "window_attention",
            {"T": T, "d": d, "dv": dv, "window": window},
            backend=concrete,
            dtype=q.dtype,
            cache=tile_cache,
        )
        if tiles is not None:
            blk_q = tiles["blk_q"] if blk_q is None else blk_q
            blk_k = tiles["blk_k"] if blk_k is None else blk_k
    untileable = (
        blk_q is None
        or blk_k is None
        or T % blk_q != 0
        or T % blk_k != 0
        or window % blk_k != 0
        or blk_q % blk_k != 0
    )
    if untileable and backend == "pallas-tpu":
        raise ValueError(
            f"window_attention: backend='pallas-tpu' was requested but no "
            f"tile covers T={T}, window={window} (blk_q={blk_q}, "
            f"blk_k={blk_k}); use backend='auto' to allow the reference path"
        )
    if concrete == "reference" or untileable:
        # shape fallback: exact reference (still O(T·T); used for tiny tests)
        concrete, blk_q, blk_k = "reference", 0, 0
    out = _window_attention(
        q.reshape(B * H, T, d),
        k.reshape(B * H, T, d),
        v.reshape(B * H, T, dv),
        window,
        blk_q,
        blk_k,
        concrete,
    )
    return out.reshape(B, H, T, dv)
