"""From a profiler trace to numbers.

``load`` reads the ``.xplane.pb`` the JAX profiler writes into a flat list
of events (plane, line, name, start, duration in ns).  Everything else
works on that list, so a small hand-made list (``bench/tests/test_trace.py``)
checks the arithmetic.

* The traced window is the harness's ``bench.window`` span.
* A device plane is a plane named ``/device:<kind>:<n>``; its operations
  are the events of its ``XLA Ops`` line.  Busy time is the union of those
  intervals inside the window; the idle share is 1 - busy / window.
* Kernel or program time is the summed duration of the device events whose
  name matches, inside the window.
* An idle gap of a device is named by what the host's main thread was doing
  at its middle: the chain of enclosing host spans, from the innermost
  ``bench.*`` span down two levels.
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass
from typing import Callable, Dict, Iterable, List, Sequence, Tuple

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DETAIL_STATS = ("long_name", "tf_op", "hlo_category", "source")


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start: int  # ns
    dur: int  # ns
    detail: str = ""  # a device op's textual stats (its long name, source op)

    @property
    def end(self) -> int:
        return self.start + self.dur


def load(trace_dir: str) -> List[Event]:
    """Events of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    events = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            for ev in line.events:
                detail = ""
                if device:
                    detail = " ".join(f"{k}={v}" for k, v in ev.stats
                                      if k in DETAIL_STATS and isinstance(v, str))
                events.append(Event(plane.name, line.name, ev.name,
                                    int(ev.start_ns), int(ev.duration_ns), detail))
    return events


def to_rows(events: Iterable[Event]) -> List[list]:
    return [[e.plane, e.line, e.name, e.start, e.dur, e.detail] for e in events]


def from_rows(rows: Iterable[Sequence]) -> List[Event]:
    return [Event(r[0], r[1], r[2], int(r[3]), int(r[4]), r[5] if len(r) > 5 else "")
            for r in rows]


def window(events: Sequence[Event]) -> Tuple[int, int]:
    spans = [e for e in events if e.name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    return min(e.start for e in spans), max(e.end for e in spans)


def device_planes(events: Sequence[Event]) -> List[str]:
    return sorted({e.plane for e in events
                   if e.plane.startswith("/device:") and e.line == OPS_LINE})


def merge(intervals: Iterable[Tuple[int, int]], lo: int, hi: int) -> List[Tuple[int, int]]:
    """Union of intervals, clipped to [lo, hi], as sorted disjoint pairs."""
    out: List[List[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy(events: Sequence[Event], plane: str, lo: int, hi: int) -> List[Tuple[int, int]]:
    return merge(((e.start, e.end) for e in events
                  if e.plane == plane and e.line == OPS_LINE), lo, hi)


def busy_ns(events: Sequence[Event], lo: int, hi: int) -> Dict[str, int]:
    """Busy nanoseconds of each device plane inside [lo, hi]."""
    return {p: sum(e - s for s, e in busy(events, p, lo, hi))
            for p in device_planes(events)}


def gaps(merged: Sequence[Tuple[int, int]], lo: int, hi: int) -> List[Tuple[int, int]]:
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def device_time(events: Sequence[Event], match: Callable[[str], bool], lo: int, hi: int,
                line: str = OPS_LINE) -> Tuple[int, int]:
    """(summed ns, count) of device events on ``line`` whose name or detail
    matches, clipped to [lo, hi]."""
    total, n = 0, 0
    for e in events:
        if (e.plane.startswith("/device:") and e.line == line
                and (match(e.name) or match(e.detail))):
            d = min(e.end, hi) - max(e.start, lo)
            if d > 0:
                total += d
                n += 1
    return total, n


def top_ops(events: Sequence[Event], lo: int, hi: int, n: int = 10,
            chips: int = 1) -> List[list]:
    """The ``n`` device operations with the most time, seconds per chip."""
    acc: Dict[str, int] = {}
    for e in events:
        if e.plane.startswith("/device:") and e.line == OPS_LINE:
            d = min(e.end, hi) - max(e.start, lo)
            if d > 0:
                acc[e.name] = acc.get(e.name, 0) + d
    return [[k, v / 1e9 / chips] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def host_line(events: Sequence[Event]) -> Tuple[str, str]:
    """(plane, line) of the host thread that ran the window span."""
    for e in events:
        if e.name == WINDOW_SPAN:
            return e.plane, e.line
    raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")


def name_points(events: Sequence[Event], points: Sequence[int]) -> List[str]:
    """What the window's host thread was doing at each time point."""
    plane, line = host_line(events)
    host = sorted((e for e in events if e.plane == plane and e.line == line),
                  key=lambda e: (e.start, -e.dur))
    order = sorted(range(len(points)), key=lambda i: points[i])
    names = [""] * len(points)
    stack: List[Event] = []
    j = 0
    for i in order:
        t = points[i]
        while j < len(host) and host[j].start <= t:
            while stack and stack[-1].end <= host[j].start:
                stack.pop()
            stack.append(host[j])
            j += 1
        while stack and stack[-1].end <= t:
            stack.pop()
        chain = [e.name for e in stack if e.start <= t < e.end]
        at = max((k for k, nm in enumerate(chain) if nm.startswith("bench.")), default=0)
        names[i] = " > ".join(chain[at:at + 3]) or "(no host span)"
    return names


def idle_breakdown(events: Sequence[Event], lo: int, hi: int, n: int = 10) -> List[list]:
    """Idle device time by what the host was doing, seconds per chip, the
    ``n`` largest."""
    planes = device_planes(events)
    spans = [g for p in planes for g in gaps(busy(events, p, lo, hi), lo, hi)]
    names = name_points(events, [(s + e) // 2 for s, e in spans])
    acc: Dict[str, int] = {}
    for (s, e), nm in zip(spans, names):
        acc[nm] = acc.get(nm, 0) + (e - s)
    k = max(len(planes), 1)
    return [[nm, v / 1e9 / k] for nm, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def span_ns(events: Sequence[Event], name: str, lo: int, hi: int) -> int:
    """Nanoseconds inside [lo, hi] covered by host spans named ``name``."""
    iv = [(e.start, e.end) for e in events
          if not e.plane.startswith("/device:") and e.name == name]
    return sum(e - s for s, e in merge(iv, lo, hi))
