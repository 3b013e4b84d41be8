"""The readers of the program's host spans (``pack_us_per_pkt``,
``launch_us_per_pkt``, ``finalize_us_per_pkt``) on event lists small enough
to check by hand, and once on a traced run of the tiny cell."""

import numpy as np
import pytest

from lib import readers, spec, trace
from lib.runner import Call, Window
from lib.trace import Event

HOST, HOST2 = "/host:CPU", "/host:CPU2"
DEV = "/device:TPU:0"
READERS = ("pack_us_per_pkt", "launch_us_per_pkt", "finalize_us_per_pkt")


def _ctx(events, traced=((0, 100), (100, 250)), lo=0, hi=10_000):
    calls = [Call(a, b, 1) for a, b in traced]
    window = Window(calls=calls + [Call(250, 1000, 1)])
    return readers.Context(events=events, lo=lo, hi=hi, chips=1, family=None, model={},
                           classes={}, pkt_len=16, peaks={}, fids=np.zeros(1000, np.int64),
                           window=window, traced_calls=calls)


def _read(name, ctx):
    return spec.load_module("metrics", name).read(ctx)


def test_overlapping_spans_count_once():
    ev = [Event(HOST, "main", "flow.pack", 1000, 500),
          Event(HOST, "main", "flow.pack", 1200, 500),  # overlaps the first
          Event(HOST2, "worker", "flow.pack", 1600, 400),  # another thread, overlapping
          Event(HOST, "main", "flow.pack", 3000, 250),
          Event(DEV, "XLA Ops", "flow.pack", 0, 9000)]  # not a host span
    # union [1000, 2000] + [3000, 3250] = 1250 ns over 250 traced packets
    assert _read("pack_us_per_pkt", _ctx(ev)) == pytest.approx(1250 / 1e3 / 250)
    ev = [Event(HOST, "main", "flow.launch", 9900, 500)]  # clipped at hi
    assert _read("launch_us_per_pkt", _ctx(ev)) == pytest.approx(100 / 1e3 / 250)


def test_a_wait_is_subtracted_only_where_it_nests():
    ev = [Event(HOST, "main", "flow.finalize", 1000, 1000),
          Event(HOST, "main", "flow.wait", 1100, 300),
          Event(HOST, "main", "flow.wait", 1900, 400),  # half inside
          Event(HOST, "main", "flow.wait", 5000, 700),  # outside any finalize
          Event(HOST, "main", "flow.finalize", 6000, 200)]
    # finalize 1200 ns, less the waits inside it: 300 + 100
    assert _read("finalize_us_per_pkt", _ctx(ev)) == pytest.approx(800 / 1e3 / 250)
    # a finalize with no wait in it counts whole
    ev = [Event(HOST, "main", "flow.finalize", 1000, 1000)]
    assert _read("finalize_us_per_pkt", _ctx(ev)) == pytest.approx(1000 / 1e3 / 250)


def test_the_dispatch_is_subtracted_from_the_launch():
    ev = [Event(HOST, "main", "flow.launch", 1000, 1000),
          Event(HOST, "main", "flow.dispatch", 1400, 600),
          Event(HOST, "main", "flow.launch", 1500, 1000),  # overlaps the first
          Event(HOST, "main", "flow.dispatch", 2200, 300),
          Event(HOST, "main", "flow.finalize", 2000, 300),  # another span: ignored
          Event(DEV, "XLA Ops", "flow.dispatch", 0, 9000)]  # not a host span
    # launches [1000, 2500], less dispatches [1400, 2000] and [2200, 2500]
    assert _read("launch_us_per_pkt", _ctx(ev)) == pytest.approx(600 / 1e3 / 250)
    # a launch that is all dispatch reads 0, not None
    ev = [Event(HOST, "main", "flow.launch", 1000, 500),
          Event(HOST, "main", "flow.dispatch", 1000, 500)]
    assert _read("launch_us_per_pkt", _ctx(ev)) == 0


def test_none_on_a_trace_without_the_spans():
    ev = [Event(HOST, "main", "bench.window", 0, 10_000),
          Event(HOST, "main", "bench.ingest", 100, 5000),
          Event(HOST, "main", "bench.directory", 200, 300),
          Event(DEV, "XLA Ops", "fusion.1", 100, 900)]
    for name in READERS:
        assert _read(name, _ctx(ev)) is None
    # spans but no traced packets
    ev = [Event(HOST, "main", "flow.finalize", 100, 50)]
    assert _read("finalize_us_per_pkt", _ctx(ev, traced=())) is None


def test_the_base_is_the_traced_calls_packets():
    ev = [Event(HOST, "main", "flow.launch", 0, 6000)]
    assert _read("launch_us_per_pkt", _ctx(ev)) == pytest.approx(6000 / 1e3 / 250)
    one_call = _ctx(ev, traced=((0, 100),))
    assert _read("launch_us_per_pkt", one_call) == pytest.approx(6000 / 1e3 / 100)


def test_the_readers_read_the_programs_own_spans():
    """No reader puts a span around the program: the spans are its own."""
    bench = spec.load_json(f"{spec.ROOT}/BENCHMARK.json")
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        assert not hasattr(spec.load_module("metrics", name), "instrument")
        assert listed[name]["source"] == "program_span"
        assert listed[name]["unit"] == "us/pkt"


def test_a_traced_run_reads_the_flow_spans(monkeypatch):
    """A traced run of the tiny cell reads the three metrics from the
    program's spans, and names a point inside a call's packing by the span
    that was open there."""
    import run as R
    from lib import runner

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
    cell.per_layer = [m for m in bench["per_layer"] if m["name"] in READERS]
    argv = ["--workload", "tiny", "--seed", "11", "--seconds", "4", "--trace", "1"]
    res = R.run(argv, cell=cell, require_tpu=False)
    for name in READERS:
        assert res["metrics"][name]["value"] > 0, name
    ev = seen["ctx"].events
    pack = next(e for e in ev if e.name == "flow.pack")
    assert trace.name_points(ev, [pack.start + pack.dur // 2]) == ["bench.ingest > flow.pack"]
