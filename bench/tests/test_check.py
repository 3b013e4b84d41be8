"""The output check: its directory model against the program's directory,
the last-line builder, and ``correct`` on sound runs and on planted faults."""

import numpy as np
import pytest

import run as R
from lib import check

from helpers import args, tiny_cell


def test_directory_model_matches_the_program():
    """Which packets start their flow afresh, on a churning stream with
    evictions, as the program's FlowTableDirectory resolves them."""
    import sys

    from lib import spec

    sys.path.insert(0, f"{spec.ROOT}/src")
    from repro.serve.flow_engine import FlowTableDirectory

    g = np.random.default_rng(4)
    fids = g.zipf(1.3, size=3000) % 97
    calls = [(lo, min(lo + 37, len(fids))) for lo in range(0, len(fids), 37)]
    seg = check.segments(fids, calls, capacity=16)
    table = FlowTableDirectory(16)
    fresh = []
    for tick, (lo, hi) in enumerate(calls, start=1):
        for f in set(fids[lo:hi].tolist()):
            table.touch(f, tick)
        fresh += [table.slot_for(int(f), tick)[1] for f in fids[lo:hi]]
    first = np.r_[True, np.zeros(len(fids) - 1, bool)]
    seen = set()
    for i, s in enumerate(seg):
        first[i] = s not in seen
        seen.add(s)
    assert np.array_equal(first, np.asarray(fresh))
    assert np.sum(fresh) > 97  # flows were evicted and came back


def test_shard_routing_is_splitmix64():
    import sys

    from lib import spec

    sys.path.insert(0, f"{spec.ROOT}/src")
    from repro.data.pipeline import flow_shard

    f = np.arange(10_000, dtype=np.int64) * 7919
    assert np.array_equal(check.flow_shard(f, 4), flow_shard(f, 4))


def test_result_line_refuses_a_cpu_device():
    kw = dict(correct=True, attempted=1, failed=0, metrics={}, checks={})
    with pytest.raises(ValueError):
        R.result_line(device={"platform": "cpu", "kind": "cpu", "count": 1}, **kw)
    line = R.result_line(device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1}, **kw)
    assert list(__import__("json").loads(line))[-1] == "checks"


def test_the_harness_refuses_a_host_without_a_tpu():
    with pytest.raises(SystemExit):
        R.run(args(1), cell=tiny_cell())


@pytest.mark.parametrize("mix", ["zipf.backlog", "flood.backlog"])
def test_sound_run_is_correct(mix):
    res = R.run(args(2**31 + 3), cell=tiny_cell(mix), require_tpu=False)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("mix", ["zipf.backlog", "flood.backlog"])
def test_sound_grouped_query_run_is_correct(mix):
    """Four query heads over two kv heads: the family's weights, the
    program's grouped attention and the reference's agree."""
    res = R.run(args(2**31 + 5), cell=tiny_cell(mix, kv_heads=2), require_tpu=False)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


def test_sound_sharded_run_is_correct():
    res = R.run(args(2**31 + 4), cell=tiny_cell(shards=4), require_tpu=False)
    assert res["correct"], res["checks"]
