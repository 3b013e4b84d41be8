"""The trace reduction on an event list small enough to check by hand."""

import pytest

from lib import trace
from lib.trace import Event

DEV0, DEV1 = "/device:TPU:0", "/device:TPU:1"
HOST = "/host:CPU"


def _hand():
    """Window [100, 200] ns; chip 0 busy [90,130] U [120,150] U [180,210];
    chip 1 busy [110,140]; host thread spans and functions."""
    return [
        Event(HOST, "python", "bench.window", 100, 100),
        Event(HOST, "python", "bench.ingest", 100, 60),
        Event(HOST, "python", "bench.directory", 101, 7),
        Event(HOST, "python", "bench.directory", 104, 8),
        Event(HOST, "python", "PjitFunction(fused)", 112, 46),
        Event(HOST, "python", "bench.finalize", 160, 30),
        Event(DEV0, "XLA Ops", "fusion.1", 90, 40),
        Event(DEV0, "XLA Ops", "flow_ingest_scores_pallas", 120, 30),
        Event(DEV0, "XLA Ops", "fusion.1", 180, 30),
        Event(DEV0, "XLA Modules", "jit_fused(123)", 90, 120),
        Event(DEV1, "XLA Ops", "fusion.2", 110, 30),
    ]


def test_window_and_planes():
    ev = _hand()
    assert trace.window(ev) == (100, 200)
    assert trace.device_planes(ev) == [DEV0, DEV1]


def test_busy_union_and_idle_share():
    ev = _hand()
    assert trace.busy(ev, DEV0, 100, 200) == [(100, 150), (180, 200)]
    assert trace.busy_ns(ev, 100, 200) == {DEV0: 70, DEV1: 30}
    assert trace.gaps([(100, 150), (180, 200)], 100, 200) == [(150, 180)]


def test_device_time_by_name_is_clipped_to_the_window():
    ev = _hand()
    ns, n = trace.device_time(ev, lambda s: "flow_ingest_scores" in s, 100, 200)
    assert (ns, n) == (30, 1)
    ns, n = trace.device_time(ev, lambda s: s.startswith("jit_fused"), 100, 200,
                              line=trace.MODULES_LINE)
    assert (ns, n) == (100, 1)
    top = trace.top_ops(ev, 100, 200, chips=2)
    assert top[0] == ["fusion.1", 50e-9 / 2]


def test_idle_gaps_are_named_by_the_host_thread():
    ev = _hand()
    # chip 0 idle [150,180] (middle 165: bench.finalize); chip 1 idle [100,110]
    # (middle 105: bench.directory) and [140,200] (middle 170: finalize)
    gaps = dict((k, v) for k, v in trace.idle_breakdown(ev, 100, 200))
    assert gaps["bench.finalize"] == pytest.approx((30 + 60) * 1e-9 / 2)
    assert gaps["bench.directory"] == pytest.approx(10e-9 / 2)


def test_span_time_is_the_union_of_named_spans():
    ev = _hand()
    assert trace.span_ns(ev, "bench.directory", 100, 200) == 11
    assert trace.span_ns(ev, "bench.directory", 100, 103) == 2
    assert trace.span_ns(ev, "bench.other", 100, 200) == 0


def test_a_profiler_trace_loads_and_reduces(tmp_path):
    """A real profiler trace (CPU here: no device planes) through ``load``:
    the window span, the host thread and a span's time."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    f = jax.jit(lambda a: a @ a)
    a = jnp.ones((64, 64))
    f(a).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("bench.window"):
        for i in range(3):
            with TraceAnnotation("bench.ingest"):
                with TraceAnnotation("bench.directory"):
                    sum(range(1000))
                f(a).block_until_ready()
    jax.profiler.stop_trace()
    ev = trace.load(str(tmp_path))
    lo, hi = trace.window(ev)
    assert hi > lo
    plane, line = trace.host_line(ev)
    assert plane.startswith("/host:")
    assert 0 < trace.span_ns(ev, "bench.directory", lo, hi) < hi - lo
    assert trace.name_points(ev, [lo + 1])[0].startswith("bench.")
    assert trace.from_rows(trace.to_rows(ev)) == ev


def test_the_traced_run_reads_the_directory_span_without_the_python_tracer(monkeypatch):
    """A traced run puts the directory metric's span around the engine's
    slot resolution, traces the window's first calls with the Python tracer
    off, and reads the directory time from that one trace."""
    import run as R
    from lib import readers, runner, spec

    from helpers import tiny_cell

    monkeypatch.setattr(runner, "TRACE_S", 0.3)
    seen = {}
    real = readers.Context

    def keep(**kw):
        seen["ctx"] = real(**kw)
        return seen["ctx"]

    monkeypatch.setattr(readers, "Context", keep)
    monkeypatch.setattr(R, "load_peaks", lambda kind: spec.load_json(
        f"{spec.BENCH_DIR}/peaks.json")["TPU v5 lite"])
    cell = tiny_cell()
    bench = spec.load_json(f"{spec.ROOT}/BENCHMARK.json")
    cell.per_layer = [m for m in bench["per_layer"] if m["name"] == "directory_us_per_pkt"]
    argv = ["--workload", "tiny", "--seed", "9", "--seconds", "12", "--trace", "1"]
    res = R.run(argv, cell=cell, require_tpu=False)
    ctx = seen["ctx"]
    window_calls = len(ctx.window.calls)
    assert 0 < len(ctx.traced_calls) < window_calls
    assert not [e for e in ctx.events if e.name.startswith("$")]  # no Python-tracer events
    spans = [e for e in ctx.events if e.name == "bench.directory"]
    assert len(spans) == len(ctx.traced_calls)
    assert res["metrics"]["directory_us_per_pkt"]["value"] > 0


def test_a_recorded_chip_trace_reduces():
    """The first 30 ms of a traced ``dp1.flood.backlog`` window on a TPU v5e
    (``bench/record_trace.py --workload dp1.flood.backlog --seed 2200000271
    --seconds 4 --keep-ms 30``): the window opens with no call in flight, so
    the chip idles until the host has sent the first one, then runs one
    107 ms fused launch past the end of the recording."""
    import json
    import os

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "trace_flood.json")
    with open(path) as f:
        ev = trace.from_rows(json.load(f)["events"])
    lo, hi = trace.window(ev)
    assert hi - lo == 30_000_000
    assert trace.device_planes(ev) == ["/device:TPU:0"]
    assert trace.busy_ns(ev, lo, hi) == {"/device:TPU:0": 26_067_239}
    assert trace.gaps(trace.busy(ev, "/device:TPU:0", lo, hi), lo, hi)[0] == (lo, lo + 3_932_727)
    ns, n = trace.device_time(ev, lambda s: s.startswith("jit_fused"), lo, hi,
                              line=trace.MODULES_LINE)
    assert (ns, n) == (26_068_359, 1)
    assert trace.device_time(ev, lambda s: s.startswith("%copy.301 "), lo, hi) == (6_341_981, 1)
    assert trace.top_ops(ev, lo, hi, n=1)[0][0].startswith("%while.90 ")
    gaps = trace.idle_breakdown(ev, lo, hi)
    assert gaps[0] == ["bench.ingest > shard_args > DevicePutWithSharding", 0.003932727]
    assert trace.span_ns(ev, "bench.directory", lo, hi) == 9_849_450
