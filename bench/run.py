"""Chip benchmark of the Chimera flow classifier: one run of one cell.

    python3 bench/run.py --workload dp1.zipf.backlog --seed 7 --seconds 20 --trace 0

Builds the cell's deployment through ``program.deploy(DeploySpec(...))``
with weights made from ``--seed``, generates the cell's traffic, warms up,
runs the cell's loop for ``--seconds`` and checks the window's answers
against the plain reference.  The last line of stdout is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics read from a profiler
trace of the window's first seconds), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its limit.
The same numbers end standard error.

Exits non-zero and prints no result when JAX finds no TPU or fewer chips
than the cell asks for, or when the run cannot complete.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_info() -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(jax.devices())}


def result_line(*, correct: bool, attempted: int, failed: int, metrics: dict, device: dict,
                checks: dict, breakdown=None) -> str:
    """The contract's last line.  Refuses any device but a TPU: a number from
    another platform is never reported under a device metric's name."""
    if device.get("platform") != "tpu":
        raise ValueError(f"refusing to report a run on {device.get('platform')!r}")
    out = {"correct": bool(correct), "attempted": int(attempted), "failed": int(failed),
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return json.dumps(out)


def check_outputs(r, dtype=None):
    """The numbers compared for ``correct`` (and, with ``dtype`` bfloat16,
    the same numbers for the lower-precision control)."""
    import jax.numpy as jnp
    import numpy as np

    from lib import check, spec

    d = r.cell.config["deploy"]
    ref = spec.load_module("reference", r.cell.config["reference"])
    w = r.window
    seg = check.segments(r.stream.fids, [(c.lo, c.hi) for c in r.calls], d["capacity"],
                         d.get("num_shards", 1))
    chosen = check.choose(np.arange(w.lo, w.hi), seg, r.seed, int(r.cell.limits["sample"]))
    mask = r.window_mask()
    f32 = check.reference_answers(ref, r.model, r.params, r.stream, seg, chosen, w.hi,
                                  r.classes, r.rule, jnp.float32)
    numbers = check.compare(r.out, f32, mask)
    if dtype is None:
        return numbers
    low = check.reference_answers(ref, r.model, r.params, r.stream, seg, chosen, w.hi,
                                  r.classes, r.rule, dtype)
    return numbers, check.compare(check.control_answers(f32, low, len(mask)), f32, mask)


def load_peaks(kind: str) -> dict:
    """The chip's peaks from ``bench/peaks.json``; an unknown kind is an error."""
    from lib import spec

    table = spec.load_json(os.path.join(BENCH_DIR, "peaks.json"))
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


def per_layer(r):
    """(metrics, device busy_s/window_s, breakdown) of the traced window."""
    from lib import readers, spec, trace

    traced = r.window.traced
    events = trace.load(traced.dir)
    shutil.rmtree(traced.dir, ignore_errors=True)
    lo, hi = trace.window(events)
    peaks = load_peaks(r.devices[0].device_kind)
    ctx = readers.Context(events=events, lo=lo, hi=hi, chips=r.cell.chips,
                          family=r.cell.family, model=r.model,
                          classes=r.classes, pkt_len=r.stream.pkt_len, peaks=peaks,
                          fids=r.stream.fids, window=r.window,
                          traced_calls=traced.calls)
    metrics = {}
    for m in r.cell.per_layer:
        v = spec.load_module("metrics", m["name"]).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    busy = trace.busy_ns(events, lo, hi)
    dev = {"busy_s": sum(busy.values()) / max(len(busy), 1) / 1e9, "window_s": (hi - lo) / 1e9}
    breakdown = {"device_ops": trace.top_ops(events, lo, hi, chips=r.cell.chips),
                 "idle_gaps": trace.idle_breakdown(events, lo, hi)}
    print(f"bench: traced window {dev['window_s']!r} s, {len(ctx.traced_calls)} calls, "
          f"{len(events)} events; device ops {json.dumps(breakdown['device_ops'][:3])}",
          file=sys.stderr)
    return metrics, dev, breakdown


def run(argv=None, *, cell=None, require_tpu: bool = True, fault=None) -> dict:
    """One run; returns the result fields (``run`` prints nothing to stdout).
    ``cell`` replaces the one ``--workload`` names in ``BENCHMARK.json``."""
    from lib import check, spec
    from lib.runner import Runner

    args = parse(argv)
    cell = cell or spec.load_cell(args.workload)
    r = Runner(cell, args.seed, args.seconds, bool(args.trace), t_start=T_START,
               require_tpu=require_tpu, fault=fault)
    setup_s = r.setup()
    print(f"bench: set-up {setup_s!r} s; {len(r.calls)} calls before the window; "
          f"{r.compiles} lowerings", file=sys.stderr)
    e2e = r.loop.measure(r, args.seconds)
    w = r.window
    device = device_info()
    device["memory_peak_bytes"] = r.memory_peak()
    print(f"bench: window {w.t1 - w.t0!r} s, {w.hi - w.lo} packets in {len(w.calls)} calls, "
          f"{w.compiles} lowerings inside the window", file=sys.stderr)
    r.release()
    metrics, breakdown = {}, None
    if args.trace:
        metrics, busy, breakdown = per_layer(r)
        device.update(busy)
    else:
        e2e["setup_s"] = setup_s
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        metrics = {k: {"value": v, "unit": units[k]} for k, v in e2e.items() if k in units}
    t = time.perf_counter()
    numbers = check_outputs(r)
    correct = check.verdict(numbers, cell.limits)
    checks = {k: {"value": numbers[k], "limit": cell.limits[k]}
              for k in check.EXACT + check.GAPS}
    print(f"bench: checked {int(numbers['compared'])} answers "
          f"({int(numbers['vetoed_compared'])} vetoed) in "
          f"{time.perf_counter() - t!r} s", file=sys.stderr)
    for k, v in checks.items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}", file=sys.stderr)
    failed = int((~r.out["have"][w.lo:w.hi]).sum())
    return dict(correct=correct, attempted=w.hi - w.lo, failed=failed,
                metrics=metrics, device=device, checks=checks, breakdown=breakdown)


def main(argv=None) -> int:
    res = run(argv)
    print(result_line(**res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
