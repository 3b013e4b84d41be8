"""Kernel & serving micro-benchmarks (Figures 7/8 analogues).

All kernel invocations go through the dispatch registry
(:mod:`repro.kernels.dispatch`), timing each family on every backend that
runs on this host.  ``tile_sweep`` prints the autotuner's tile-sweep table
and populates the on-disk autotune cache.  Wall times are CPU-reference
numbers (interpret-mode Pallas / XLA-CPU jnp); the TPU projection columns
come from the roofline model.  CSV: name,us_per_call,derived.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_row, timeit_us, tiny_backbone
from repro.core.hardware_model import DEFAULT_TPU
from repro.kernels import autotune, dispatch

SEED = 0


def _host_backends():
    """Backends benchmarkable on this host ("pallas-tpu" needs TPU
    hardware); asked at run time, never on import."""
    if jax.default_backend() == "tpu":
        return ("pallas-tpu", "pallas-interpret", "reference")
    return ("pallas-interpret", "reference")


def _chimera_args(B=1, Hkv=2, Gq=1, T=512, d=32, m=64, dv=32):
    ks = jax.random.split(jax.random.PRNGKey(SEED), 5)
    return (
        jax.random.normal(ks[0], (B, Hkv, Gq, T, d)),
        jax.random.normal(ks[1], (B, Hkv, T, d)),
        jax.random.normal(ks[2], (B, Hkv, T, dv)),
        jax.nn.elu(jax.random.normal(ks[3], (B, Hkv, Gq, T, m))) + 1,
        jax.nn.elu(jax.random.normal(ks[4], (B, Hkv, T, m))) + 1,
    )


def _decode_args(BH=8, Gq=1, L=128, d=32, m=64, dv=32):
    ks2 = jax.random.split(jax.random.PRNGKey(SEED), 9)
    return (
        jax.random.normal(ks2[0], (BH, Gq, d)),
        jax.random.normal(ks2[1], (BH, d)),
        jax.random.normal(ks2[2], (BH, dv)),
        jax.nn.elu(jax.random.normal(ks2[3], (BH, Gq, m))) + 1,
        jax.nn.elu(jax.random.normal(ks2[4], (BH, L, m))) + 1,
        jax.random.normal(ks2[5], (BH, L, d)),
        jax.random.normal(ks2[6], (BH, L, dv)),
        jax.random.normal(ks2[7], (BH, m, dv)),
        jax.nn.relu(jax.random.normal(ks2[8], (BH, m))) + 1,
        jnp.zeros((BH,), jnp.int32),
    )


def kernel_benchmarks() -> List[str]:
    rows = []
    backends = _host_backends()
    B, Hkv, Gq, T, d, m, dv, L = 1, 2, 1, 512, 32, 64, 32, 128
    q, k, v, pq, pk = _chimera_args(B, Hkv, Gq, T, d, m, dv)

    for backend in backends:
        impl = dispatch.resolve("chimera_attention", backend)
        fn = jax.jit(lambda *a, _i=impl: _i(*a, chunk_size=L))
        us = timeit_us(fn, q, k, v, pq, pk, iters=5)
        rows.append(csv_row(f"kernel/chimera_attention/{backend}", us,
                            f"T={T};L={L}"))
    flops = 2 * T * L * (d + dv) + 2 * T * m * dv  # per head, approx
    # TPU projection: VMEM-resident chunk kernel is compute-bound
    proj_us = flops * B * Hkv / DEFAULT_TPU.peak_flops_bf16 * 1e6
    rows.append(csv_row("kernel/chimera_attention/tpu-projected", proj_us,
                        "roofline=compute-bound"))

    kw = k.reshape(B * Hkv, T, d)
    vw = v.reshape(B * Hkv, T, dv)
    for backend in backends:
        impl = dispatch.resolve("window_attention", backend)
        fn = jax.jit(lambda *a, _i=impl: _i(*a, window=128, blk_q=128, blk_k=128))
        us = timeit_us(fn, kw, kw, vw, iters=5)
        rows.append(csv_row(f"kernel/window_attention/{backend}", us, "W=128"))

    BH = 8
    args = _decode_args(BH, Gq, L, d, m, dv)
    for backend in backends:
        impl = dispatch.resolve("decode_step", backend)
        fn = jax.jit(lambda *a, _i=impl: _i(*a, chunk_size=L))
        us = timeit_us(fn, *args, iters=5)
        rows.append(csv_row(f"kernel/decode_step/{backend}", us, f"flows={BH}"))
    state_bytes = BH * (L * (d + dv) + m * (dv + 1)) * 4
    # dataplane-analogue projection: the decode step touches only the
    # bounded state -> memory-bound at HBM speed on TPU
    proj = state_bytes / DEFAULT_TPU.hbm_bandwidth * 1e6
    rows.append(csv_row("kernel/decode_step/tpu-projected", proj,
                        f"roofline=memory-bound;state_bytes={state_bytes}"))
    return rows


def tile_sweep() -> List[str]:
    """Autotuner tile-sweep table: every Eq. 11-admissible tile per family,
    timed on this host's kernel backend; winners populate the on-disk
    autotune cache so subsequent dispatch calls pick them up."""
    backend = dispatch.resolve_backend("auto")
    cache = autotune.AutotuneCache()
    rows = []

    B, Hkv, Gq, T, d, m, dv = 1, 2, 1, 256, 32, 64, 32
    q, k, v, pq, pk = _chimera_args(B, Hkv, Gq, T, d, m, dv)
    impl = dispatch.resolve("chimera_attention", backend)
    dims = {"T": T, "d": d, "dv": dv, "m": m, "gq": Gq}

    def make_chimera(tiles):
        fn = jax.jit(lambda *a: impl(*a, chunk_size=tiles["chunk_size"]))
        return lambda: fn(q, k, v, pq, pk)

    for tiles, us in autotune.sweep(
        "chimera_attention", dims, make_chimera, backend, cache=cache
    ):
        rows.append(csv_row(
            f"autotune/chimera_attention/L={tiles['chunk_size']}", us,
            f"backend={backend};vmem_kb="
            f"{autotune.vmem_bytes('chimera_attention', tiles, dims) // 1024}"))

    W = 128
    kw = k.reshape(B * Hkv, T, d)
    vw = v.reshape(B * Hkv, T, dv)
    wimpl = dispatch.resolve("window_attention", backend)
    wdims = {"T": T, "d": d, "dv": dv, "window": W}

    def make_window(tiles):
        fn = jax.jit(lambda *a: wimpl(*a, window=W, **tiles))
        return lambda: fn(kw, kw, vw)

    for tiles, us in autotune.sweep(
        "window_attention", wdims, make_window, backend, cache=cache
    ):
        rows.append(csv_row(
            f"autotune/window_attention/bq={tiles['blk_q']},bk={tiles['blk_k']}",
            us, f"backend={backend};W={W}"))

    ddims = {"d": d, "dv": dv, "m": m, "gq": Gq}
    dimpl = dispatch.resolve("decode_step", backend)

    def make_decode(tiles):
        L = tiles["chunk_size"]
        args = _decode_args(8, Gq, L, d, m, dv)
        fn = jax.jit(lambda *a: dimpl(*a, chunk_size=L))
        return lambda: fn(*args)

    for tiles, us in autotune.sweep(
        "decode_step", ddims, make_decode, backend, cache=cache
    ):
        rows.append(csv_row(
            f"autotune/decode_step/L={tiles['chunk_size']}", us,
            f"backend={backend}"))
    rows.append(csv_row("autotune/cache", len(cache), f"path={cache.path}"))
    return rows


def serving_benchmarks() -> List[str]:
    """Figure 7/8 analogue: engine throughput & latency on CPU (reference)."""
    from repro.models import model as M
    from repro.serve.engine import Request, ServeEngine

    rows = []
    cfg = tiny_backbone()
    params, _ = M.init_model(cfg, jax.random.PRNGKey(SEED))
    import time

    for slots in (1, 4, 8):
        eng = ServeEngine(cfg, params, batch_slots=slots, max_len=128)
        rng = np.random.default_rng(0)
        n_req = slots * 2
        for rid in range(n_req):
            eng.submit(Request(rid=rid, prompt=rng.integers(0, 256, 8).tolist(),
                               max_new_tokens=16))
        eng.step()  # warmup tick: jit compile excluded from percentiles
        lat = []
        t0 = time.perf_counter()
        while eng.pending or any(r is not None for r in eng.active):
            ts = time.perf_counter()
            eng.step()
            lat.append(time.perf_counter() - ts)
        dt = time.perf_counter() - t0
        toks = n_req * 24
        lat_us = np.percentile(np.array(lat) * 1e6, [50, 99])
        rows.append(csv_row(
            f"serving/slots{slots}", dt / max(len(lat), 1) * 1e6,
            f"tok_per_s={toks/dt:.0f};p50_us={lat_us[0]:.0f};p99_us={lat_us[1]:.0f}",
        ))
    # fast batched prefill vs token-by-token prompt ingestion (same output,
    # tested equivalent in tests/test_fast_prefill.py)
    rng = np.random.default_rng(1)
    prompt_len, new = 96, 8
    for mode in ("token-by-token", "fast-prefill"):
        eng = ServeEngine(cfg, params, batch_slots=4, max_len=256)
        reqs = [Request(rid=i, prompt=rng.integers(0, 256, prompt_len).tolist(),
                        max_new_tokens=new) for i in range(4)]
        if mode == "fast-prefill":
            eng.prefill_batch(reqs)  # includes one-off jit compile
            eng.step()
            t0 = time.perf_counter()
            eng.run_until_done()
            dt = time.perf_counter() - t0
        else:
            for r in reqs:
                eng.submit(r)
            eng.step()
            t0 = time.perf_counter()
            eng.run_until_done()
            dt = time.perf_counter() - t0
        toks = 4 * (prompt_len + new)
        rows.append(csv_row(f"serving/prefill-{mode}", dt * 1e6,
                            f"prompt={prompt_len};tok_per_s={toks/max(dt,1e-9):.0f}"))
    return rows
