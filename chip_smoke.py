"""Bring-up check: serve the paper's classifier on a TPU at its published widths.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the sharded deploy over four chips

One chip.  The flow-serving path is built as ``python -m
repro.launch.flow_serve --fused --backend pallas-tpu`` builds it
(``classifier_config`` -> ``compile_flow_program`` -> ``program.deploy``) for
``chimera-dataplane`` at full width, with random weights from ``--seed``.  A
fused ``pallas-tpu`` FlowEngine of 4096 flows is warmed and ingests 8
``FlowScenario("mix")`` batches.  The same stream is then replayed through
an ``xla``-backend FlowEngine on the same chip and the decisions compared.

Four chips.  ``DeploySpec(engine="sharded", num_shards=4)`` at 4096 flows
per shard replays the stream, then a one-chip FlowEngine replays it again,
and the decisions are compared as in ``tests/test_sharded_flow_engine.py``.

The script exits non-zero, and prints no result, when JAX finds no TPU or
any phase fails.  On success the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

# Trust tolerance between two engines on one chip.  Both run the same XLA
# backbone; they differ in the score heads (Mosaic kernel vs XLA dot) and in
# how XLA fuses the step around them.  TPU matmuls round f32 operands to
# bf16 (relative error 2^-9 each), so the anomaly logit s_nn = p . w moves by
# at most 2^-8 * sum|p_i w_i|; trust = sigmoid(alpha s_nn + beta s_sym) has
# slope <= |alpha| / 4.  With the fusion init alpha = 1, sum|p_i w_i| came to
# 9.4 at these widths in a CPU rehearsal of this stream (the script prints
# the value it sees), so one rounding moves trust by at most
# 2^-8 * 9.4 / 4 < 0.01; 2^-6 leaves the rest for rounding differences
# between the two compiled backbone steps.
# Veto bits and S = 1.0 pinning are exact (integer TCAM match, select), so
# they are compared for equality.
TRUST_TOL = 2.0 ** -6

# the deployment under test: flows per chip, lanes per launch, and the
# traffic (batches of up to PACKETS packets of 16 tokens)
CAPACITY, LANES, BATCHES, PACKETS = 4096, 256, 8, 1024


def stream(ccfg, batches: int, packets: int, seed: int):
    from repro.data.pipeline import FlowScenario

    sc = FlowScenario(kind="mix", vocab_size=ccfg.arch.vocab_size, pkt_len=16,
                      packets_per_batch=packets, seed=seed)
    return sc.anomaly_signature, [sc.next_batch() for _ in range(batches)]


def replay(engine, batches):
    """Ingest every batch; returns the outputs and (first, rest) seconds —
    the first batch of a per-round engine includes its compile."""
    import jax

    outs, times = [], []
    for b in batches:
        t = time.perf_counter()
        outs.append(engine.ingest(b["flow_ids"], b["tokens"]))
        jax.block_until_ready(engine.hidden_sum)
        times.append(time.perf_counter() - t)
    return outs, times[0], sum(times[1:])


def kernel_calls(engine, width: int = 8, pkt_len: int = 16) -> int:
    """Mosaic kernel calls in the lowered hot-path step (lowered, not run)."""
    import jax.numpy as jnp

    entry = engine.jit_entry_points()
    if "fused" in entry:
        args = (jnp.zeros((8, width), jnp.int32),
                jnp.zeros((8, width, pkt_len), jnp.int32),
                jnp.zeros((8, width), bool), jnp.int32(0))
        fn = entry["fused"]
    else:
        lanes = engine.fcfg.lanes
        args = (jnp.zeros((lanes,), jnp.int32),
                jnp.zeros((lanes, pkt_len), jnp.int32),
                jnp.zeros((lanes,), bool))
        fn = entry["step"]
    text = fn.lower(engine.params, engine.rules, engine.caches,
                    engine.positions, engine.sig, engine.hidden_sum,
                    engine.vetoed, *args).as_text()
    return text.count("tpu_custom_call")


def expected_kernel_calls(engine) -> int:
    """Mosaic calls the engine's stage report promises (``pallas-tpu``)."""
    return sum("(pallas-tpu)" in v for v in engine.stage_impls.values())


def describe(label: str, engine) -> None:
    per_flow = engine.per_flow_state_bytes()
    cap = getattr(engine, "aggregate_capacity", engine.fcfg.capacity)
    print(f"{label}: backend={engine.backend} "
          + " ".join(f"{k}={v}" for k, v in engine.stage_impls.items()))
    print(f"{label}: capacity {cap} flows x {per_flow} B/flow = "
          f"{engine.resident_state_bytes()} B of table "
          f"(budget {engine.state_budget_bytes} B per device)")


def memory_line(label: str, devices) -> str:
    parts = []
    for d in devices:
        st = d.memory_stats() or {}
        parts.append(f"{d.id}: in_use={st.get('bytes_in_use')} "
                     f"peak={st.get('peak_bytes_in_use')} "
                     f"limit={st.get('bytes_limit')}")
    return f"{label}: memory_stats " + "; ".join(parts)


def compare(label: str, got, want, exact=("vetoed",)) -> bool:
    """``exact`` outputs and S = 1.0 pinning equal, trust within TRUST_TOL,
    everything finite; prints each finding."""
    import numpy as np

    def cat(outs, k):
        return np.concatenate([o[k] for o in outs])

    ok = True
    for k in exact:
        same = np.array_equal(cat(got, k), cat(want, k))
        print(f"{label}: {k} identical: {same}")
        ok &= same
    for name, outs in (("got", got), ("want", want)):
        t, v = cat(outs, "trust"), cat(outs, "vetoed")
        pinned = bool(np.all(t[v] == 1.0))
        print(f"{label}: {name} pins S = 1.0 on all {int(v.sum())} vetoed "
              f"packets: {pinned}")
        ok &= pinned
    same_pin = np.array_equal(cat(got, "trust") == 1.0, cat(want, "trust") == 1.0)
    print(f"{label}: S = 1.0 pinning identical: {same_pin}")
    ok &= same_pin
    for k in ("trust", "s_nn", "s_sym"):
        a, b = cat(got, k), cat(want, k)
        finite = bool(np.isfinite(a).all() and np.isfinite(b).all())
        print(f"{label}: {k} max |diff| {float(np.max(np.abs(a - b)))!r} "
              f"(bit-identical: {np.array_equal(a, b)}, finite: {finite})")
        ok &= finite
    diff = float(np.max(np.abs(cat(got, "trust") - cat(want, "trust"))))
    ok &= diff <= TRUST_TOL
    print(f"{label}: trust within tolerance {TRUST_TOL!r}: {diff <= TRUST_TOL}")
    agree = float(np.mean(cat(got, "pred") == cat(want, "pred")))
    print(f"{label}: pred agreement {agree!r}")
    return bool(ok)


def score_magnitude(engine) -> float:
    """Largest sum_i |p_i w_i| of the anomaly head over resident flows — the
    quantity the TRUST_TOL bound scales with."""
    import jax.numpy as jnp

    slots = jnp.asarray(list(engine.table.slot_of.values()), jnp.int32)
    pooled = engine.hidden_sum[slots] / jnp.maximum(engine.positions[slots], 1)[:, None]
    w = engine.params["anom"]["w"][:, 0]
    return float(jnp.max(jnp.sum(jnp.abs(pooled * w), axis=-1)))


def one_chip(*, seed: int = 0, smoke: bool = False,
             backend: str = "pallas-tpu", batches: int = BATCHES) -> bool:
    """The fused ``backend`` engine against an ``xla`` engine on one device
    (``smoke`` and ``backend="pallas-interpret"`` rehearse it on a CPU)."""
    import jax

    from repro.launch.flow_serve import classifier_config, compile_flow_program
    from repro.serve.deploy import DeploySpec
    from repro.serve.flow_engine import FlowEngineConfig

    t0 = time.perf_counter()
    ccfg = classifier_config(smoke=smoke)
    a = ccfg.arch
    print(f"config: chimera-dataplane{' (smoke)' if smoke else ''} "
          f"layers={a.n_layers} d_model={a.d_model} m={a.chimera.feature_map.m} "
          f"d_v={a.head_dim} L={a.chimera.chunk_size} "
          f"n_global={a.chimera.n_global} vocab={a.vocab_size}")
    signature, data = stream(ccfg, batches, PACKETS, seed)
    n_pkts = sum(len(b["flow_ids"]) for b in data)
    program = compile_flow_program(ccfg, signature, backend=backend,
                                   smoke=smoke, seed=seed)
    print(f"compile_program: {time.perf_counter() - t0!r} s; ledger:")
    print(program.ledger.as_table())

    fcfg = FlowEngineConfig(capacity=CAPACITY, lanes=LANES, fused=True)
    engine = program.deploy(DeploySpec(flow=fcfg))
    describe("fused", engine)
    calls, want = kernel_calls(engine), expected_kernel_calls(engine)
    print(f"fused: lowered step holds {calls} Mosaic kernel call(s), "
          f"stages name {want}")
    ok = calls == want
    t = time.perf_counter()
    widths = engine.warm_fused(16)
    jax.block_until_ready(engine.hidden_sum)
    print(f"fused: compile (warm_fused, {widths} widths) "
          f"{time.perf_counter() - t!r} s")
    fused, first, rest = replay(engine, data)
    s = engine.stats
    print(f"fused: ingest {batches} batches, {n_pkts} packets in "
          f"{first + rest!r} s; resident {engine.resident_flows} flows, "
          f"{s.flows_created} created, {s.flows_evicted} evicted")
    print(memory_line("fused", jax.devices()[:1]))
    ok &= s.flows_evicted == 0
    del engine
    gc.collect()

    ref = program.deploy(DeploySpec(
        flow=FlowEngineConfig(capacity=CAPACITY, lanes=LANES), backend="xla",
    ))
    describe("xla", ref)
    calls = kernel_calls(ref)
    print(f"xla: lowered step holds {calls} Mosaic kernel call(s)")
    ok &= calls == 0
    want_outs, first, rest = replay(ref, data)
    print(f"xla: first batch (incl. compile) {first!r} s, "
          f"other {batches - 1} batches {rest!r} s; "
          f"{ref.stats.flows_evicted} evicted")
    print(f"xla: largest sum|p_i w_i| of the anomaly head {score_magnitude(ref)!r}")
    ok &= ref.stats.flows_evicted == 0
    del ref
    gc.collect()
    ok &= compare("fused pallas vs xla", fused, want_outs)
    print(f"one chip: {'PASS' if ok else 'FAIL'} in {time.perf_counter() - t0!r} s")
    return ok


def four_chips(*, seed: int = 0, smoke: bool = False, shards: int = 4,
               batches: int = BATCHES) -> bool:
    """The ``shards``-way sharded deploy against a one-device FlowEngine
    (``smoke`` with one shard rehearses it on a CPU)."""
    import jax

    from repro.launch.flow_serve import classifier_config, compile_flow_program
    from repro.serve.deploy import DeploySpec
    from repro.serve.flow_engine import FlowEngineConfig

    t0 = time.perf_counter()
    ccfg = classifier_config(smoke=smoke)
    signature, data = stream(ccfg, batches, PACKETS, seed)
    program = compile_flow_program(ccfg, signature, smoke=smoke, seed=seed)
    fcfg = FlowEngineConfig(capacity=CAPACITY, lanes=LANES)
    sharded = program.deploy(DeploySpec(
        engine="sharded", flow=fcfg, num_shards=shards,
    ))
    describe("sharded", sharded)
    placed = {}
    for leaf in jax.tree_util.tree_leaves(sharded.caches):
        for sh in leaf.addressable_shards:
            placed.setdefault(sh.device.id, set()).add(sh.data.shape[0])
    rows_ok = len(placed) == shards and all(v == {1} for v in placed.values())
    print(f"sharded: table rows on devices {sorted(placed)} "
          f"(one shard-row block each: {rows_ok})")
    print(memory_line("sharded after deploy", jax.devices()[:shards]))
    got, first, rest = replay(sharded, data)
    print(f"sharded: first batch (incl. compile) {first!r} s, "
          f"other {batches - 1} batches {rest!r} s; resident per shard "
          f"{sharded.resident_flows_per_shard()}, "
          f"{sharded.stats.flows_evicted} evicted")
    print(memory_line("sharded after ingest", jax.devices()[:shards]))
    ok = rows_ok and sharded.stats.flows_evicted == 0
    created = sharded.stats.flows_created
    del sharded
    gc.collect()

    single = program.deploy(DeploySpec(flow=fcfg))
    describe("one-chip", single)
    want, first, rest = replay(single, data)
    print(f"one-chip: first batch (incl. compile) {first!r} s, "
          f"other {batches - 1} batches {rest!r} s; "
          f"{single.stats.flows_created} created, "
          f"{single.stats.flows_evicted} evicted")
    ok &= single.stats.flows_evicted == 0
    ok &= single.stats.flows_created == created
    del single
    gc.collect()
    ok &= compare("sharded vs one-chip", got, want, exact=("vetoed", "pred"))
    print(f"four chips: {'PASS' if ok else 'FAIL'} in {time.perf_counter() - t0!r} s")
    return bool(ok)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded phase over four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips, found {len(devices)}",
              file=sys.stderr)
        return 1
    from repro.core.hardware_model import device_tpu_spec
    from repro.launch.jax_cache import enable_compile_cache

    cache = enable_compile_cache()
    spec = device_tpu_spec()
    print(f"device: {dev.platform} {dev.device_kind!r} x{len(devices)} "
          f"({spec.name}: {spec.source}); compile cache {cache}")
    try:
        ok = (four_chips(seed=args.seed) if args.four_chips
              else one_chip(seed=args.seed))
    except Exception:  # noqa: BLE001 — any failed phase fails the smoke
        import traceback

        traceback.print_exc()
        ok = False
    if not ok:
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
