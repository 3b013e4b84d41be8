"""Host spans of the flow-serving path, read back from a profiler trace.

Each engine serves a small ``FlowScenario`` stream with the JAX profiler
off, is reset, and serves it again inside ``jax.profiler.trace``; the trace's
``.xplane.pb`` is read with ``ProfileData``.  The spans must describe every
ingest call (one ``flow.resolve``, one ``flow.launch`` per launch with its
``flow.dispatch`` inside, every ``flow.wait`` inside a ``flow.finalize``,
one ``call`` stat per call), sit
on the same clock as the CPU's XLA ops, and leave the answers bit-identical.

The fused engine runs through ``AsyncIngestPipeline`` on the ``reference``
backend; the sharded engine runs one shard (tier 1 has one CPU device).
"""

import glob
import os
from dataclasses import dataclass
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.data.pipeline import FlowScenario
from repro.serve.flow_engine import FlowEngine, FlowEngineConfig
from repro.serve.ingest_pipeline import AsyncIngestPipeline
from repro.serve.sharded_flow_engine import ShardedFlowEngine
from repro.train import classifier as C

KEY = jax.random.PRNGKey(0)
OUT_KEYS = ("trust", "vetoed", "pred", "s_nn", "s_sym", "sig")
BATCHES = 5
LANES = 16
KINDS = ("fused", "rounds", "sharded")
# the jitted program each engine launches, as the trace's ``hlo_module`` stat
MODULE = {"fused": "jit_fused", "rounds": "jit_step", "sharded": "jit_shard_step"}


@dataclass
class Span:
    name: str
    start: int
    end: int
    stats: Dict
    thread: Tuple[str, str]

    def holds(self, other: "Span") -> bool:
        return (self.thread == other.thread and self.start <= other.start
                and other.end <= self.end)


@dataclass
class Served:
    answers: List[Dict[str, np.ndarray]]
    launches: List[List[Tuple[int, int]]]  # per call: (width, chunks) of each launch


@dataclass
class Traced:
    off: Served
    on: Served
    spans: List[Span]
    op_starts: Dict[str, List[int]]  # hlo_module -> start of each CPU XLA op


@pytest.fixture(scope="module")
def classifier(tiny_classifier_cfg):
    params, _ = C.init_classifier(tiny_classifier_cfg, KEY)
    return tiny_classifier_cfg, params


def _scenario():
    return FlowScenario(kind="mix", vocab_size=512, pkt_len=8,
                        packets_per_batch=48, seed=11)


def _engine(classifier, kind):
    ccfg, params = classifier
    rules = C.default_rules(ccfg, jnp.asarray(_scenario().anomaly_signature))
    if kind == "sharded":
        return ShardedFlowEngine(ccfg, params, rules,
                                 FlowEngineConfig(capacity=32, lanes=LANES),
                                 num_shards=1)
    fcfg = FlowEngineConfig(capacity=32, lanes=LANES, fused=kind == "fused",
                            backend="reference", ring_slots=2)
    eng = FlowEngine(ccfg, params, rules, fcfg)
    if kind == "fused":
        eng.warm_fused(pkt_len=8)
    return eng


def _serve(eng, kind) -> Served:
    sc = _scenario()
    batches = [sc.next_batch() for _ in range(BATCHES)]
    launches: List[List[Tuple[int, int]]] = []
    if kind == "fused":
        dispatch = eng._dispatch_fused

        def spy(*a, **kw):
            pending = dispatch(*a, **kw)
            launches.append([(int(outs["trust"].shape[1]), len(chunks))
                             for outs, chunks in pending.launches])
            return pending

        eng._dispatch_fused = spy
        pipe = AsyncIngestPipeline(eng)
        for b in batches:
            pipe.submit(b["flow_ids"], b["tokens"])
        answers = pipe.drain()
        del eng._dispatch_fused
    else:
        answers = []
        for b in batches:
            r0 = eng.stats.rounds
            answers.append(eng.ingest(b["flow_ids"], b["tokens"]))
            launches.append([(LANES, 1)] * (eng.stats.rounds - r0))
    return Served(answers, launches)


def _read(trace_dir) -> Tuple[List[Span], Dict[str, List[int]]]:
    path, = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    spans, ops = [], {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                stats = dict(ev.stats)
                start = int(ev.start_ns)
                if ev.name.startswith("flow."):
                    spans.append(Span(ev.name, start, start + int(ev.duration_ns),
                                      stats, (plane.name, line.name)))
                elif "hlo_module" in stats:
                    ops.setdefault(str(stats["hlo_module"]), []).append(start)
    return sorted(spans, key=lambda s: (s.start, -s.end)), ops


@pytest.fixture(scope="module", params=KINDS)
def traced(request, classifier, tmp_path_factory):
    kind = request.param
    eng = _engine(classifier, kind)
    off = _serve(eng, kind)
    eng.reset()  # the same stream again, every flow fresh, nothing recompiled
    trace_dir = tmp_path_factory.mktemp(f"trace-{kind}")
    with jax.profiler.trace(str(trace_dir)):
        on = _serve(eng, kind)
    spans, ops = _read(trace_dir)
    return kind, Traced(off, on, spans, ops)


def _named(t: Traced, name: str) -> List[Span]:
    return [s for s in t.spans if s.name == name]


def test_one_resolve_per_call(traced):
    _, t = traced
    resolves = _named(t, "flow.resolve")
    assert [s.stats["call"] for s in resolves] == list(range(1, BATCHES + 1))


def test_one_launch_per_width_group(traced):
    _, t = traced
    got: Dict[int, List[Tuple[int, int]]] = {}
    for s in _named(t, "flow.launch"):
        got.setdefault(s.stats["call"], []).append((s.stats["width"], s.stats["chunks"]))
    assert [got.get(k, []) for k in range(1, BATCHES + 1)] == t.on.launches
    assert any(len(c) > 1 for c in t.on.launches)


def test_every_dispatch_nests_in_a_launch(traced):
    _, t = traced
    launches, dispatches = _named(t, "flow.launch"), _named(t, "flow.dispatch")
    assert len(dispatches) == len(launches) == sum(map(len, t.on.launches))
    for d in dispatches:
        outer = [L for L in launches if L.holds(d)]
        assert len(outer) == 1 and outer[0].stats["call"] == d.stats["call"]
        assert outer[0].start < d.start  # the puts come first


def test_every_wait_nests_in_a_finalize(traced):
    kind, t = traced
    finals, waits = _named(t, "flow.finalize"), _named(t, "flow.wait")
    assert len(waits) == sum(map(len, t.on.launches))
    for w in waits:
        outer = [f for f in finals if f.holds(w)]
        assert len(outer) == 1 and outer[0].stats["call"] == w.stats["call"]
    # a fused call is read back once, a per-round call after each launch
    for k in range(1, BATCHES + 1):
        n = sum(f.stats["call"] == k for f in finals)
        assert n == (1 if kind == "fused" else len(t.on.launches[k - 1]))


def test_a_calls_spans_share_its_call_stat(traced):
    _, t = traced
    assert all("call" in s.stats for s in t.spans)
    starts = [s.start for s in _named(t, "flow.resolve")] + [float("inf")]
    for s in t.spans:
        k = s.stats["call"]
        assert starts[k - 1] <= s.start
        if s.name in ("flow.resolve", "flow.pack", "flow.launch", "flow.dispatch"):
            assert s.start < starts[k]  # before the next call is resolved
    launches = _named(t, "flow.launch")
    for f in _named(t, "flow.finalize"):
        assert any(L.stats["call"] == f.stats["call"] and L.start < f.start
                   for L in launches)


def test_device_ops_start_after_the_first_launch(traced):
    kind, t = traced
    ops = [v for m, v in t.op_starts.items() if m.startswith(MODULE[kind])]
    assert ops, sorted(t.op_starts)
    first_launch = min(s.start for s in _named(t, "flow.launch"))
    first_dispatch = min(s.start for s in _named(t, "flow.dispatch"))
    assert min(min(v) for v in ops) > first_dispatch > first_launch


def test_answers_bit_identical_with_the_profiler_on(traced):
    _, t = traced
    assert len(t.on.answers) == len(t.off.answers) == BATCHES
    for a, b in zip(t.off.answers, t.on.answers):
        for k in OUT_KEYS:
            np.testing.assert_array_equal(a[k], b[k], err_msg=k)


def test_a_table_swap_records_one_swap_span(classifier, tmp_path):
    eng = _engine(classifier, "rounds")
    with jax.profiler.trace(str(tmp_path)):
        eng.swap_tables(weights=eng.rules.weights * 0.5)
    spans, _ = _read(tmp_path)
    assert [(s.name, s.stats["tick"]) for s in spans] == [("flow.swap", 0)]
