"""Readings that set the output limits: the program's numbers and the
lower-precision control's, for several seeds in one process.

    python3 bench/control.py --workload dp1.zipf.backlog --seconds 20 \
        --seeds 11 12 13

For each seed this runs the cell as ``run.py`` does (set-up, the window at
the cell's own load), then compares the window's answers with the float32
reference at ``highest`` precision, and compares the same reference run in
bfloat16 (every weight, activation and state) with it too.  One JSON line
per seed: ``program`` and ``control``, each the numbers ``correct`` is
decided on.  The benchmark's own runs never run the control.
"""

from __future__ import annotations

import time

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def readings(cell, seed: int, seconds: float, *, require_tpu: bool = True):
    import jax.numpy as jnp

    import run as R
    from lib.runner import Runner

    r = Runner(cell, seed, seconds, False, t_start=time.perf_counter(), require_tpu=require_tpu)
    r.setup()
    r.loop.measure(r, seconds)
    r.release()
    program, control = R.check_outputs(r, dtype=jnp.bfloat16)
    del r
    gc.collect()
    return program, control


def main() -> int:
    from lib import spec

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    cell = spec.load_cell(args.workload)
    for seed in args.seeds:
        program, control = readings(cell, seed, args.seconds)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "program": program, "control": control}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
