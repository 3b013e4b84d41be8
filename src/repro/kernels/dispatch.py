"""Unified kernel backend registry (DESIGN.md §8).

Every performance-critical kernel family is exposed as ONE callable with an
explicit backend axis, replacing the per-file ``jax.default_backend()``
checks the seed repo scattered across the ``ops.py`` wrappers:

  family               semantics
  ------------------   ----------------------------------------------------
  chimera_attention    chunked local + φ-stream partials (train/prefill)
  window_attention     causal sliding-window flash attention (SWA)
  decode_step          fused per-token streaming decode (serve hot path)
  flow_score           streaming trust/class scoring over the per-flow
                       (Σh, count, signature) aggregates (FlowEngine)
  flow_ingest          fused whole-batch flow ingest: table-resident
                       gather → decode → score/veto → scatter, one launch
                       per pre-packed chunk stack (FlowEngine --fused)

  backend              implementation
  ------------------   ----------------------------------------------------
  pallas-tpu           pl.pallas_call compiled to Mosaic (TPU hosts)
  pallas-interpret     the same kernel under the Pallas interpreter (CPU)
  reference            the pure-jnp oracle from the family's ref.py
  int-emulation        the integer-lowered score path (compile/int_lowering
                       — int32 jnp ops only; flow_score family)

Not every family implements every backend: the backbone kernel families are
float-only (pallas-tpu / pallas-interpret / reference), while ``flow_score``
ships the integer lowering plus its float reference oracle.  The invariant
every family MUST satisfy is a registered ``reference`` implementation —
the conformance tiers differentiate every other backend against it.

``resolve_backend("auto")`` is the single place in the codebase that
inspects ``jax.default_backend()``.  Everything above this module — models,
serving engine, launcher, benchmarks — names a backend string (or "auto")
and gets the right implementation; new backends (e.g. a GPU Triton port)
register here and become reachable end-to-end with no call-site changes.

All registered implementations of a family share one canonical signature
(documented per family below), so tests can sweep (family, backend) pairs
mechanically.
"""

from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax

from repro.kernels.chimera_attention.kernel import chimera_attention_pallas
from repro.kernels.chimera_attention.ref import chimera_attention_partials_ref
from repro.kernels.decode_step.kernel import decode_step_pallas
from repro.kernels.decode_step.ref import decode_step_ref
from repro.kernels.window_attention.kernel import window_attention_pallas
from repro.kernels.window_attention.ref import window_attention_ref

BACKENDS: Tuple[str, ...] = (
    "pallas-tpu", "pallas-interpret", "reference", "int-emulation"
)

_REGISTRY: Dict[Tuple[str, str], Callable] = {}


def register(family: str, backend: str):
    """Decorator: register ``fn`` as the ``backend`` impl of ``family``."""
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected one of {BACKENDS}")

    def deco(fn: Callable) -> Callable:
        _REGISTRY[(family, backend)] = fn
        return fn

    return deco


def families() -> Tuple[str, ...]:
    return tuple(sorted({f for f, _ in _REGISTRY}))


def backends(family: str) -> Tuple[str, ...]:
    """Registered backends for ``family`` in canonical order."""
    got = {b for f, b in _REGISTRY if f == family}
    if not got:
        raise KeyError(f"unknown kernel family {family!r}; have {families()}")
    return tuple(b for b in BACKENDS if b in got)


def resolve_backend(backend: str = "auto") -> str:
    """Map "auto" to the concrete backend for this host.

    The ONLY ``jax.default_backend()`` check in the kernel stack."""
    if backend == "auto":
        return "pallas-tpu" if jax.default_backend() == "tpu" else "pallas-interpret"
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown backend {backend!r}; expected 'auto' or one of {BACKENDS}"
        )
    return backend


def resolve(family: str, backend: str = "auto") -> Callable:
    """Return the registered implementation of (family, backend)."""
    b = resolve_backend(backend)
    impl = _REGISTRY.get((family, b))
    if impl is None:
        raise KeyError(
            f"no {b!r} implementation registered for kernel family {family!r} "
            f"(registered: {backends(family) if any(f == family for f, _ in _REGISTRY) else '∅'})"
        )
    return impl


def apply_kernel_backend(cfg, backend):
    """Rewrite an ArchConfig for an explicit kernel-path selection.

    The one place that maps a backend string onto config fields (shared by
    ServeEngine and build_cell).  ``None`` keeps cfg as-is; ``"xla"`` pins
    the pure-jnp paths; any dispatch backend routes Chimera partials, the
    fused decode and SWA through this registry.  Returns
    ``(cfg, effective_backend)``.
    """
    import dataclasses

    if backend is None:
        return cfg, (cfg.chimera.backend if cfg.chimera.use_pallas else "xla")
    if backend in ("xla", "int-emulation"):
        # int-emulation lowers the *score* path (the flow_score family); the
        # backbone feature extractor stays on the plain-jnp float path, kept
        # bit-identical to an "xla" deployment so differential conformance
        # isolates the integer region
        cfg = dataclasses.replace(
            cfg,
            swa_backend="xla",
            chimera=dataclasses.replace(cfg.chimera, use_pallas=False),
        )
    else:
        resolve_backend(backend)  # fail fast on typos
        cfg = dataclasses.replace(
            cfg,
            swa_backend=backend,
            chimera=dataclasses.replace(
                cfg.chimera, use_pallas=True, backend=backend
            ),
        )
    return cfg, backend


# ==========================================================================
# chimera_attention — canonical signature:
#   (q (B,Hkv,Gq,T,d), k (B,Hkv,T,d), v (B,Hkv,T,dv),
#    phi_q (B,Hkv,Gq,T,m), phi_k (B,Hkv,T,m),
#    *, chunk_size, use_local=True, use_stream=True)
#   -> (num (B,Hkv,Gq,T,dv), den (B,Hkv,Gq,T)) unnormalized partials
# ==========================================================================

def _chimera_pallas(interpret: bool):
    def impl(q, k, v, phi_q, phi_k, *, chunk_size, use_local=True, use_stream=True):
        B, Hkv, Gq, T, d = q.shape
        num, den = chimera_attention_pallas(
            q.reshape(B * Hkv, Gq, T, d),
            k.reshape(B * Hkv, T, k.shape[-1]),
            v.reshape(B * Hkv, T, v.shape[-1]),
            phi_q.reshape(B * Hkv, Gq, T, phi_q.shape[-1]),
            phi_k.reshape(B * Hkv, T, phi_k.shape[-1]),
            chunk_size=chunk_size,
            use_local=use_local,
            use_stream=use_stream,
            interpret=interpret,
        )
        return (
            num.reshape(B, Hkv, Gq, T, v.shape[-1]),
            den.reshape(B, Hkv, Gq, T),
        )

    return impl


register("chimera_attention", "pallas-tpu")(_chimera_pallas(interpret=False))
register("chimera_attention", "pallas-interpret")(_chimera_pallas(interpret=True))


@register("chimera_attention", "reference")
def _chimera_reference(q, k, v, phi_q, phi_k, *, chunk_size, use_local=True,
                       use_stream=True):
    return chimera_attention_partials_ref(
        q, k, v, phi_q, phi_k, chunk_size, use_local, use_stream
    )


# ==========================================================================
# window_attention — canonical signature:
#   (q (BH,T,d), k (BH,T,d), v (BH,T,dv), *, window, blk_q, blk_k)
#   -> out (BH,T,dv)
# The reference impl ignores the tile sizes (they are pure performance
# knobs; ``window`` alone fixes the semantics).
# ==========================================================================

def _window_pallas(interpret: bool):
    def impl(q, k, v, *, window, blk_q=128, blk_k=128):
        return window_attention_pallas(
            q, k, v, window=window, blk_q=blk_q, blk_k=blk_k, interpret=interpret
        )

    return impl


register("window_attention", "pallas-tpu")(_window_pallas(interpret=False))
register("window_attention", "pallas-interpret")(_window_pallas(interpret=True))


@register("window_attention", "reference")
def _window_reference(q, k, v, *, window, blk_q=0, blk_k=0):
    return window_attention_ref(q, k, v, window)


# ==========================================================================
# decode_step — canonical signature:
#   (q (BH,Gq,d), k_t (BH,d), v_t (BH,dv), phi_q (BH,Gq,m),
#    phi_buf (BH,L,m), k_buf (BH,L,d), v_buf (BH,L,dv),
#    S (BH,m,dv), Z (BH,m), count () or (BH,) int32,
#    *, chunk_size, gamma=1e-6)
#   -> (out (BH,Gq,dv), (S, Z, k_buf, v_buf, count))
# ==========================================================================

def _decode_pallas(interpret: bool):
    def impl(q, k_t, v_t, phi_q, phi_buf, k_buf, v_buf, S, Z, count, *,
             chunk_size, gamma=1e-6):
        import jax.numpy as jnp

        c = jnp.asarray(count)
        scalar_count = c.ndim == 0
        if scalar_count:
            c = jnp.broadcast_to(c, (q.shape[0],))
        out, (S2, Z2, kb2, vb2, c2) = decode_step_pallas(
            q, k_t, v_t, phi_q, phi_buf, k_buf, v_buf, S, Z, c,
            chunk_size=chunk_size, gamma=gamma, interpret=interpret,
        )
        if scalar_count:  # mirror the reference: scalar in -> scalar out
            c2 = c2[0]
        return out, (S2, Z2, kb2, vb2, c2)

    return impl


register("decode_step", "pallas-tpu")(_decode_pallas(interpret=False))
register("decode_step", "pallas-interpret")(_decode_pallas(interpret=True))


@register("decode_step", "reference")
def _decode_reference(q, k_t, v_t, phi_q, phi_buf, k_buf, v_buf, S, Z, count, *,
                      chunk_size, gamma=1e-6):
    return decode_step_ref(
        q, k_t, v_t, phi_q, phi_buf, k_buf, v_buf, S, Z, count,
        chunk_size, gamma=gamma,
    )


# ==========================================================================
# flow_score — canonical signature:
#   (plan: IntScorePlan, tables: {name: int32 array}, rules: RuleSet,
#    hidden_sum (B,d), count (B,) int32, sig (B,W) uint32, sticky (B,) bool)
#   -> (outputs dict, new_sticky (B,) bool)
# ``int-emulation`` runs the lowered int32 program (hidden_sum is the
# quantized feature accumulator; outputs carry *_q fixed-point scores);
# ``reference`` is the float oracle over the SAME compiled tables
# (dequantize-then-score), the upper arm of the conformance differential.
# Imports are lazy: compile/int_lowering imports core modules that import
# this registry.
# ==========================================================================

@register("flow_score", "int-emulation")
def _flow_score_int(plan, tables, rules, hidden_sum, count, sig, sticky):
    from repro.compile.int_lowering import int_flow_score

    return int_flow_score(plan, tables, rules, hidden_sum, count, sig, sticky)


@register("flow_score", "reference")
def _flow_score_reference(plan, tables, rules, hidden_sum, count, sig, sticky):
    from repro.compile.int_lowering import reference_flow_score

    return reference_flow_score(
        plan, tables, rules, hidden_sum, count, sig, sticky
    )


# ==========================================================================
# flow_ingest — canonical signature (a BUILDER, not the kernel itself):
#   (ccfg: ClassifierConfig, n_slots: int, max_len: int, int_plan=None, *,
#    tiles=None)
#     -> fused(params, rules, caches, positions, sig, hidden_sum, vetoed,
#              idx (C,w) int32, tokens (C,w,pkt_len) int32, fresh (C,w) bool,
#              n_chunks () int32)
#        -> (caches, positions, sig, hidden_sum, vetoed, outs)
# The engine jits the built callable once (donating the table state) and
# feeds it pow2-bucketed chunk stacks; ``n_chunks`` is traced, so varying
# round counts never retrace.  ``reference`` scans the unmodified
# make_flow_step body (bit-exact to the per-round path by construction);
# the Pallas backends swap in the flow_ingest/kernel.py score stage, tuned
# by ``tiles`` = {"lane_tile", "state_tile"} from the autotuner.
# ``int-emulation`` reuses the reference structure — the lowered int32
# score program rides ``int_plan``.  Imports are lazy: the builders live
# next to the engine, which imports this registry.
# ==========================================================================

@register("flow_ingest", "reference")
def _flow_ingest_reference(ccfg, n_slots, max_len, int_plan=None, *,
                           tiles=None):
    from repro.kernels.flow_ingest.ref import fused_ingest_ref

    return fused_ingest_ref(ccfg, n_slots, max_len, int_plan=int_plan,
                            tiles=tiles)


@register("flow_ingest", "int-emulation")
def _flow_ingest_int(ccfg, n_slots, max_len, int_plan=None, *, tiles=None):
    from repro.kernels.flow_ingest.ref import fused_ingest_ref

    return fused_ingest_ref(ccfg, n_slots, max_len, int_plan=int_plan,
                            tiles=tiles)


def _flow_ingest_pallas(interpret: bool):
    def impl(ccfg, n_slots, max_len, int_plan=None, *, tiles=None):
        from repro.kernels.flow_ingest.kernel import fused_ingest_pallas

        return fused_ingest_pallas(
            ccfg, n_slots, max_len, int_plan=int_plan, tiles=tiles,
            interpret=interpret,
        )

    return impl


register("flow_ingest", "pallas-tpu")(_flow_ingest_pallas(interpret=False))
register("flow_ingest", "pallas-interpret")(_flow_ingest_pallas(interpret=True))
