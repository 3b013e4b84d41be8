"""What a per-layer metric's reader gets: one object holding the traced
window's events and the run's records.  A reader (``bench/metrics/<name>.py``)
defines ``read(ctx) -> float | None`` and returns None when it finds nothing
to read; the harness then leaves the metric out of the result line.  A
reader may also define ``instrument(engine)``, which a traced run calls on
the deployed engine before its first call, to put the spans it reads
around the program's calls.

``events``, ``lo``, ``hi`` and ``traced_calls`` are the traced part of the
window (``lib.runner``), with the Python tracer off."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List

import numpy as np

from .runner import Call, Window
from .trace import Event


@dataclass
class Context:
    events: List[Event]  # the device part's trace
    lo: int  # traced window on the trace clock, ns
    hi: int
    chips: int
    family: Any  # bench/models/<family>.py: the backbone's counts
    model: Dict[str, Any]
    classes: Dict[str, Any]
    pkt_len: int
    peaks: Dict[str, float]
    fids: np.ndarray  # flow id of every stream packet
    window: Window  # the measured window: its calls and the loop's records
    traced_calls: List[Call]

    @property
    def traced_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def traced_packets(self) -> int:
        return sum(c.hi - c.lo for c in self.traced_calls)

    def distinct_flows(self, call: Call) -> int:
        return int(np.unique(self.fids[call.lo:call.hi]).size)

