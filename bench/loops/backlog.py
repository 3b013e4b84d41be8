"""Closed loop under overload: the next full batch is always queued.

The whole stream was generated in set-up, so a batch is ready the moment
the server takes one; the server never waits for traffic.  Calls are sent
until ``--seconds`` have passed, with ``in_flight`` of them on the device at
most (``Runner.submit``); then nothing more is sent, every call sent is
waited for, and the window closes.  ``pkts_per_s`` is every packet of the
window over the window.
"""

from __future__ import annotations

import time


def warm(r) -> None:
    """Every chunk bucket the stream can reach, then full batches until
    ``quiet_calls`` in a row compile nothing."""
    r.warm_buckets()
    w = r.traffic["warmup"]
    quiet = calls = 0
    while calls < w["max_calls"] and not (calls >= w["min_calls"] and quiet >= w["quiet_calls"]):
        before = r.compiles
        r.submit(r.batch)
        calls += 1
        quiet = quiet + 1 if r.compiles == before else 0


def measure(r, seconds: float) -> dict:
    t0 = r.start_window()
    while True:
        r.submit(r.batch)
        r.after_call()
        if time.perf_counter() - t0 >= seconds:
            break
    r.end_window()
    w = r.window
    return {"pkts_per_s": (w.hi - w.lo) / (w.t1 - w.t0)}
