"""A cell at a size the CPU runs in seconds: the tiny configuration in
``tests/data`` (2 layers, d_model 64, 64 flows, 32 lanes) under the real
traffic files, with 32-packet calls and limits set from CPU readings
(program and reference agree to ~1e-6 there).  Its signature match admits
every global (``match_hamming`` = ``sig_bits``): with a threshold inside
the range, a product that rounds across zero now and then flips a
signature bit in one of the two, and the answers of that token then
differ by up to ~1e-2, which would make the tests depend on which calls
a window happens to hold."""

import os

from lib import spec

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def tiny_cell(mix: str = "zipf.backlog", shards: int = 1, kv_heads: int = 0) -> spec.Cell:
    """The tiny cell; ``kv_heads`` (when given) shares each kv head among
    ``n_heads / kv_heads`` query heads."""
    cfg = spec.load_json(os.path.join(DATA, "tiny.json"))
    if kv_heads:
        cfg["model"]["n_kv_heads"] = kv_heads
    if shards > 1:
        cfg["deploy"].update(engine="sharded", fused=False, num_shards=shards)
    tr = dict(spec.load_json(os.path.join(spec.BENCH_DIR, "traffic", f"{mix}.json")))
    tr.update(batch=32, ceiling_pkts_per_s=2000)
    tr["warmup"] = {**tr["warmup"], "min_calls": 2, "quiet_calls": 2}
    bench = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    e2e = [m for m in bench["end_to_end"] if m["name"] in ("pkts_per_s", "setup_s")]
    return spec.Cell(name="tiny", chips=shards, config=cfg, family=spec.load_family(cfg),
                     traffic=tr, end_to_end=e2e, per_layer=[],
                     limits=spec.load_json(os.path.join(DATA, "tiny_limits.json")))


def args(seed: int, seconds: float = 2.0):
    return ["--workload", "tiny", "--seed", str(seed), "--seconds", str(seconds), "--trace", "0"]
