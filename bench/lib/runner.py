"""One run of one cell: set-up, the loop's window, and what the readers and
the output check need afterwards.

Set-up builds the weights from the seed by the cell's family, compiles and
deploys the program, generates the whole traffic stream, pre-fills the flow
table and lets the cell's loop warm the shapes its traffic uses.  The loop
(``bench/loops``) then submits calls for the window.  A fused engine is
driven as ``flow_serve --fused`` drives it, through the program's
``AsyncIngestPipeline``: a call is dispatched while up to ``in_flight``
earlier calls (the traffic file) are still on the device, and its answers
are read when its ring slot comes round again.  Every call is recorded (its
stream range and the engine's round counter), and every answer is kept by
stream index for the check.
"""

from __future__ import annotations

import collections
import os
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any, Callable, List, Optional

import numpy as np

from . import spec, system, traffic, weights
from .flops import sig_words

CACHE_DIR = os.path.join(spec.ROOT, ".jax_cache")
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
# The model's dtype is float32, and its products are run at float32 precision.
# A TPU's default (what `flow_serve` deploys) rounds every product's operands
# to bfloat16 in one pass; the program could then not be told from the
# bfloat16 control that `correct` has to reject (PERF.md 2.1).
MATMUL_PRECISION = "highest"
# A traced run traces the window's first whole calls that span this long
# (with the Python tracer off); the rest of the window runs untraced.
TRACE_S = 6.0


class RanDry(RuntimeError):
    """The window needed more packets than the stream was generated with."""


ANSWERS = ("trust", "vetoed", "pred", "s_nn", "s_sym", "sig")


@dataclass
class Call:
    lo: int
    hi: int
    rounds: int


@dataclass
class Traced:
    """The traced part of the window: its trace directory and its calls."""
    dir: str
    calls_from: int
    calls: List[Call] = field(default_factory=list)


@dataclass
class Window:
    lo: int = 0  # first stream index of the window
    hi: int = 0  # one past the last
    t0: float = 0.0
    t1: float = 0.0
    calls_from: int = 0  # index of the window's first call in Runner.calls
    calls: List[Call] = field(default_factory=list)
    traced: Optional[Traced] = None
    compiles: int = 0


def enable_cache() -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where set, else ``.jax_cache`` at the checkout's root (a fixed path, so
    every run of a checkout finds what the first one compiled)."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def devices(chips: int, require_tpu: bool = True):
    """The chips the cell asks for; raises where JAX finds no TPU or too few."""
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise SystemExit(f"bench: JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


class Runner:
    def __init__(self, cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
                 t_start: float, require_tpu: bool = True,
                 fault: Optional[Callable[[Any], Any]] = None):
        self.cell, self.seed, self.seconds, self.trace = cell, seed, seconds, trace
        self.t_start, self.require_tpu, self.fault = t_start, require_tpu, fault
        self.model = cell.config["model"]
        self.classes = {**cell.config["classifier"], "vocab_size": self.model["vocab_size"]}
        self.traffic = cell.traffic
        self.batch = int(self.traffic["batch"])
        self.loop = spec.load_module("loops", self.traffic["loop"])
        self.compiles = 0
        self.calls: List[Call] = []
        self.window = Window()
        self._window_span = None

    # ---------------------------------------------------------------- set-up
    def setup(self) -> float:
        import jax

        self.devices = devices(self.cell.chips, self.require_tpu)
        enable_cache()
        jax.config.update("jax_default_matmul_precision", MATMUL_PRECISION)
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        d = self.cell.config["deploy"]
        capacity = d["capacity"] * d.get("num_shards", 1)
        self.stream = traffic.generate(self.traffic, self.classes, capacity, self.seed,
                                       self.stream_packets())
        self.params = self.cell.family.make_params(self.model, self.cell.config["classifier"],
                                                   self.seed)
        self.ccfg = system.classifier_config(self.cell.config, self.cell.family)
        weights.check_layout(self.params, system.program_layout(self.ccfg))
        self.rule = weights.anomaly_rule(self.stream.anomaly_sig,
                                         sig_words(self.model, self.classes),
                                         self.classes["marker_base"])
        self.program, self.engine = system.deploy(self.cell.config, self.ccfg, self.params,
                                                  self.rule)
        if self.trace:
            for m in self.cell.per_layer:
                reader = spec.load_module("metrics", m["name"])
                if hasattr(reader, "instrument"):
                    reader.instrument(self.engine)
        if self.fault is not None:
            self.engine = self.fault(self.engine)
        self.pipe = system.pipeline(self.engine, int(self.traffic["in_flight"]))
        self._waiting: collections.deque = collections.deque()  # calls not yet answered
        N = len(self.stream.fids)
        self.out = {"have": np.zeros(N, bool), "trust": np.zeros(N, np.float32),
                    "vetoed": np.zeros(N, bool), "pred": np.zeros(N, np.int64),
                    "s_nn": np.zeros(N, np.float32), "s_sym": np.zeros(N, np.float32),
                    "sig": np.zeros((N, sig_words(self.model, self.classes)), np.uint32)}
        self.pos = 0
        while self.pos < self.stream.prefill:
            self.submit(min(self.batch, self.stream.prefill - self.pos))
        self.loop.warm(self)
        self.drain()
        return time.perf_counter() - self.t_start

    def warm_buckets(self) -> None:
        """Trace the fused step at every chunk bucket the stream can reach,
        through the engine's own ``warm_fused``: a call whose busiest flow
        sends k packets has k arrival rounds, and launches at most k chunks
        of one width together, padded to a power of two (8 at least)."""
        warm = getattr(self.engine, "warm_fused", None)
        if warm is None:
            return
        rest = self.stream.fids[self.stream.prefill:]
        rounds = max(int(np.unique(rest[lo:lo + self.batch], return_counts=True)[1].max())
                     for lo in range(0, len(rest), self.batch))
        c = 8
        while True:
            warm(self.stream.pkt_len, c)
            if c >= rounds:
                break
            c *= 2

    def stream_packets(self) -> int:
        """Packets after the pre-fill: the most the warm-up may take, the
        window at the traffic file's ceiling rate, and two calls over."""
        warm = int(self.traffic["warmup"]["max_calls"]) * self.batch
        return warm + int(self.traffic["ceiling_pkts_per_s"] * self.seconds) + 2 * self.batch

    def _on_event(self, event: str, duration: float, **_: Any) -> None:
        if event == LOWERING_EVENT:
            self.compiles += 1

    # ---------------------------------------------------------------- ingest
    def submit(self, n: int) -> Call:
        """Send the next ``n`` packets as one call; keep the answers of the
        calls that have come back."""
        from jax.profiler import TraceAnnotation

        lo, hi = self.pos, self.pos + n
        if hi > len(self.stream.fids):
            raise RanDry(f"the window needed more than the {len(self.stream.fids)} packets "
                         f"generated: the traffic's ceiling_pkts_per_s is too low")
        fids, tokens = self.stream.fids[lo:hi], self.stream.tokens[lo:hi]
        rounds0 = self.engine.stats.rounds
        with TraceAnnotation("bench.ingest"):
            if self.pipe is None:
                done = [self.engine.ingest(fids, tokens)]
            else:
                self.pipe.submit(fids, tokens)
                done = self.pipe.poll()
        call = Call(lo, hi, self.engine.stats.rounds - rounds0)
        self.calls.append(call)
        self._waiting.append(call)
        self.pos = hi
        self._keep(done)
        return call

    def drain(self) -> None:
        """Wait for every call sent and keep its answers."""
        from jax.profiler import TraceAnnotation

        if self.pipe is not None:
            with TraceAnnotation("bench.drain"):
                done = self.pipe.drain()
            self._keep(done)

    def _keep(self, answers) -> None:
        from jax.profiler import TraceAnnotation

        with TraceAnnotation("bench.finalize"):
            for ans in answers:
                c = self._waiting.popleft()
                for k in ANSWERS:
                    self.out[k][c.lo:c.hi] = ans[k]
                self.out["have"][c.lo:c.hi] = True

    # ---------------------------------------------------------------- window
    def start_window(self) -> float:
        w = self.window
        w.lo, w.compiles = self.pos, self.compiles
        w.calls_from = len(self.calls)
        if self.trace:
            self._start_trace()
        w.t0 = time.perf_counter()
        return w.t0

    def after_call(self) -> None:
        """Stop tracing once the traced calls span ``TRACE_S``, after they
        have all come back, so the trace holds all of their device work."""
        if self._window_span is not None and time.perf_counter() - self.window.t0 >= TRACE_S:
            self.drain()
            self._stop_trace()

    def _start_trace(self) -> None:
        import jax
        from jax.profiler import ProfileOptions, TraceAnnotation

        opts = ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        self.window.traced = Traced(tempfile.mkdtemp(prefix="bench-trace-"), len(self.calls))
        jax.profiler.start_trace(self.window.traced.dir, profiler_options=opts)
        self._window_span = TraceAnnotation("bench.window")
        self._window_span.__enter__()

    def _stop_trace(self) -> None:
        import jax

        self._window_span.__exit__(None, None, None)
        self._window_span = None
        jax.profiler.stop_trace()
        t = self.window.traced
        t.calls = self.calls[t.calls_from:]

    def end_window(self) -> None:
        """Send nothing more, wait for every call sent, then read the clock."""
        self.drain()
        w = self.window
        w.t1 = time.perf_counter()
        w.hi = self.pos
        w.calls = self.calls[w.calls_from:]
        w.compiles = self.compiles - w.compiles
        if self._window_span is not None:
            self._stop_trace()

    def window_mask(self) -> np.ndarray:
        m = np.zeros(len(self.stream.fids), bool)
        m[self.window.lo:self.window.hi] = True
        return m

    # ---------------------------------------------------------------- after
    def memory_peak(self) -> int:
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in self.devices)

    def release(self) -> None:
        """Drop the program and its device state before the reference runs."""
        import gc

        self.engine = self.program = None
        gc.collect()
