"""Record a cell's traced window as a small flat event list (JSON).

    python3 bench/record_trace.py --workload dp1.zipf.backlog --seed 3 \
        --seconds 4 --keep-ms 400 --out chiprun_out/trace.json

Runs the cell's set-up and a traced window as ``run.py --trace 1`` does,
then keeps the first ``--keep-ms`` of it: every device event and
every host event of the window's thread in that span, with the
``bench.window`` span cut to it.  Prints the planes, lines and the most
frequent event names: what the trace readers in ``bench/metrics`` match
against, and a small recording for checking the trace reduction.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    from lib import spec, trace
    from lib.runner import Runner

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--keep-ms", type=float, default=400.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    cell = spec.load_cell(args.workload)
    r = Runner(cell, args.seed, args.seconds, True, t_start=T_START)
    r.setup()
    r.loop.measure(r, args.seconds)
    traced = r.window.traced
    events = trace.load(traced.dir)
    shutil.rmtree(traced.dir, ignore_errors=True)
    lo, hi = trace.window(events)
    cut = min(hi, lo + int(args.keep_ms * 1e6))
    plane, line = trace.host_line(events)
    kept = []
    for e in events:
        if e.end <= lo or e.start >= cut:
            continue
        if e.name == trace.WINDOW_SPAN:
            e = trace.Event(e.plane, e.line, e.name, lo, cut - lo, e.detail)
        if e.plane.startswith("/device:") or (e.plane, e.line) == (plane, line):
            kept.append(e)
    lines = collections.Counter((e.plane, e.line) for e in events)
    names = collections.Counter((e.line, e.name, e.detail[:160]) for e in events
                                if e.plane.startswith("/device:"))
    for (p, ln), n in sorted(lines.items()):
        print(f"plane {p!r} line {ln!r}: {n} events")
    for (ln, nm, det), n in names.most_common(60):
        print(f"device {ln!r} {nm!r} [{det}]: {n}")
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"window_calls": [[c.lo, c.hi, c.rounds] for c in traced.calls],
                   "events": trace.to_rows(kept)}, f)
    print(f"kept {len(kept)} of {len(events)} events in {(cut - lo) / 1e6} ms -> {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
