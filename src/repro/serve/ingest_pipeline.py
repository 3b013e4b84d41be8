"""Async host-side ingest pipeline over a fused FlowEngine (DESIGN.md §15).

The fused ``flow_ingest`` path splits an ingest call into two halves with
very different hardware owners:

  host   — directory lookup, LRU/idle eviction, arrival-round packing into
           the pinned staging buffers (``FlowEngine._dispatch_fused``),
  device — the single-launch fused step per width group.

Run synchronously, the host half and the device half serialize.  This
pipeline overlaps them with a ring of ``depth`` staging slots: ``submit``
packs batch k+1 into slot (k+1) % depth and dispatches it while the device
is still chewing on batch k — JAX's async dispatch returns before the
computation completes, and each ring slot owns a private host buffer pool,
so packing never races the in-flight transfer sourced from another slot.

Ordering and state are untouched: slot resolution happens in ``submit`` in
arrival order (the flow directory is host state, mutated synchronously),
and the device launches are enqueued in order on one stream, so the fused
path remains bit-identical to synchronous ingest.  The ring only bounds
how far the *host* runs ahead; ``submit`` applies backpressure by
finalizing the batch that last used the slot it is about to reuse (in a
profiler trace, the ``flow.wait`` span inside that batch's ``flow.finalize``).
The TPU runtime bounds it too: it holds at most 32 executions in flight
(two per fused launch, the step and its chunk count), and a dispatch past
that blocks until the device finishes one (the ``flow.dispatch`` span).
With more launches in the ring than that, the host waits there, and the
batch that ``submit`` finalizes is already done.

    pipe = AsyncIngestPipeline(engine)         # engine built with fused=True
    for batch in scenario:
        pipe.submit(batch["flow_ids"], batch["tokens"])
    results = pipe.drain()                     # per-batch output dicts

``ingest(...)`` is a drop-in synchronous wrapper (submit + finalize) for
call sites that need each batch's outputs immediately but still want the
pre-packed staging path.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np


class AsyncIngestPipeline:
    """Ring-buffered double-ended ingest: host packs ahead, device drains."""

    def __init__(self, engine, depth: Optional[int] = None):
        if getattr(engine, "_jit_fused", None) is None:
            raise ValueError(
                "AsyncIngestPipeline requires a fused engine "
                "(FlowEngineConfig(fused=True))"
            )
        self.engine = engine
        self.depth = depth or engine.fcfg.ring_slots
        if self.depth < 1:
            raise ValueError(f"ring depth must be >= 1, got {self.depth}")
        # one private staging-buffer pool per ring slot (allocated lazily by
        # _dispatch_fused and reused across batches — pinned host memory in
        # the ring-DMA sense: stable buffers the transfers source from)
        self._pools: List[Dict] = [{} for _ in range(self.depth)]
        self._pending: List[Optional[object]] = [None] * self.depth
        self._seq = 0  # batches submitted
        self._results: List[Dict[str, np.ndarray]] = []

    @property
    def in_flight(self) -> int:
        return sum(p is not None for p in self._pending)

    def submit(self, flow_ids, tokens) -> None:
        """Pack and dispatch one batch; returns without blocking on device
        results (beyond ring backpressure)."""
        eng = self.engine
        flow_ids = np.asarray(flow_ids)
        tokens = np.asarray(tokens, np.int32)
        P, _ = tokens.shape
        assert flow_ids.shape == (P,), (flow_ids.shape, P)

        slot = self._seq % self.depth
        prev = self._pending[slot]
        if prev is not None:
            # ring full for this slot: harvest before reusing its buffers
            self._results.append(prev.finalize())
            self._pending[slot] = None

        slots, fresh = eng._resolve_slots(flow_ids)
        self._pending[slot] = eng._dispatch_fused(
            flow_ids, tokens, slots, fresh, staging=self._pools[slot]
        )
        self._seq += 1

    def poll(self) -> List[Dict[str, np.ndarray]]:
        """Harvest every completed/ordered result accumulated so far."""
        out, self._results = self._results, []
        return out

    def drain(self) -> List[Dict[str, np.ndarray]]:
        """Finalize all in-flight batches; returns results in submit order."""
        for k in range(max(self._seq - self.depth, 0), self._seq):
            slot = k % self.depth
            p = self._pending[slot]
            if p is not None:
                self._results.append(p.finalize())
                self._pending[slot] = None
        return self.poll()

    def ingest(self, flow_ids, tokens) -> Dict[str, np.ndarray]:
        """Synchronous drop-in for ``engine.ingest`` through the ring path."""
        self.submit(flow_ids, tokens)
        slot = (self._seq - 1) % self.depth
        res = self._pending[slot].finalize()
        self._pending[slot] = None
        return res
