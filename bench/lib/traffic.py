"""The one traffic generator: flow arrivals and packet tokens from a seed.

A traffic file (``bench/traffic/<name>.json``) sets the parameters; this
module never changes per mix.

Flow arrivals.  A population of ``population_per_capacity`` x (flows the
deployment tracks) ranks.  Each packet picks a rank with probability
proportional to ``1 / (rank + 1) ** zipf_s`` (``zipf_s`` 0 is uniform).
The flow at a rank lives for a Pareto(``life.shape``) number of stream
packets, at least ``life.min`` and at most ``life.cap``; the first packet
drawn for the rank after that opens a fresh flow id there.  ``life`` 0
makes every packet a fresh flow (a spoofed-source flood).

Where the traffic file gives ``arrival_seed``, the arrivals (ranks and
lifetimes) are drawn from it and not from the run's seed, so every run
carries the same flows in the same calls, and the same work; the run's seed
then shuffles the packets within each call-sized block (``batch``), so the
order of arrivals, the flows' contents and the sample checked still differ
from seed to seed.

Packet tokens.  The class-conditional chains of the repository's
``FlowScenario`` (handshake prefix, periodic signature markers, a per-class
kernel over a 64-state chain), copied here so the yardstick does not move
with the program.  One departure: the chain state is re-drawn from the seed
at the start of each packet instead of carrying over from the flow's
previous packet, so every packet is generated independently of the others.
A share ``anomaly_share`` of flows carries the 4-token anomaly signature at
a token position drawn from ``anomaly_at``; the TCAM rule compiled against
that signature then vetoes those flows.

The stream starts with a pre-fill: one packet for every flow of the initial
population, coldest rank first, so that the table holds the hottest flows
when traffic begins.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict

import numpy as np


def rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(np.array([seed, *stream], dtype=np.uint64))


@dataclass
class Stream:
    fids: np.ndarray  # (N,) int64 flow ids in arrival order
    tokens: np.ndarray  # (N, pkt_len) int32
    prefill: int  # the first `prefill` packets are the table pre-fill
    anomaly_sig: np.ndarray  # (4,) the anomaly signature tokens
    pkt_len: int


def token_tables(seed: int, n_classes: int, vocab: int, marker_base: int):
    """(handshake (C,8), kernel (C,64,8), signature (C,4), anomaly_sig (4,))."""
    g = rng(seed, 0xF10)
    handshake = g.integers(marker_base, vocab, size=(n_classes, 8))
    kernel = g.integers(0, marker_base, size=(n_classes, 64, 8))
    signature = g.integers(marker_base, vocab, size=(n_classes, 4))
    anomaly_sig = g.choice(np.arange(marker_base, vocab), size=4, replace=False)
    return handshake, kernel, signature, anomaly_sig


def flow_arrivals(traffic: Dict[str, Any], capacity: int, seed: int, n: int):
    """Flow ids of the pre-fill and of ``n`` packets after it."""
    pop = int(traffic["population_per_capacity"] * capacity)
    g = rng(int(traffic.get("arrival_seed", seed)), 0xA22)
    w = 1.0 / np.arange(1, pop + 1, dtype=np.float64) ** float(traffic["zipf_s"])
    cdf = np.cumsum(w / w.sum())
    ranks = np.minimum(np.searchsorted(cdf, g.random(n)), pop - 1)
    life = traffic["life"]

    def lifetimes(k: int) -> np.ndarray:
        if life["cap"] <= 0:
            return np.zeros(k, np.int64)
        u = 1.0 - g.random(k)  # (0, 1]
        draw = np.floor(life["min"] * u ** (-1.0 / life["shape"]))
        return np.minimum(draw, life["cap"]).astype(np.int64)

    # the initial population: fid r at rank r, born with the pre-fill, one
    # stream packet before traffic starts
    cur = np.arange(pop, dtype=np.int64)
    end = lifetimes(pop) - 1
    fresh_life = lifetimes(n)  # the lifetime of a flow opened by packet t
    fids = np.empty(n, np.int64)
    next_fid = pop
    cur_l, end_l, ranks_l = cur.tolist(), end.tolist(), ranks.tolist()
    for t in range(n):
        r = ranks_l[t]
        if t > end_l[r]:
            cur_l[r] = next_fid
            end_l[r] = t + int(fresh_life[t])
            next_fid += 1
        fids[t] = cur_l[r]
    if "arrival_seed" in traffic:
        b = int(traffic["batch"])
        gs = rng(seed, 0x5F1)
        for lo in range(0, n, b):
            fids[lo:lo + b] = gs.permutation(fids[lo:lo + b])
    prefill = np.arange(pop - 1, -1, -1, dtype=np.int64)  # coldest first
    return np.concatenate([prefill, fids]), pop


def packet_tokens(fids: np.ndarray, traffic: Dict[str, Any], classes: Dict[str, int],
                  seed: int):
    """Tokens of every packet; a flow's k-th packet continues its token
    positions at 16 k (its chain re-drawn per packet, see module doc)."""
    T = int(traffic["pkt_len"])
    C, vocab, base = classes["n_classes"], classes["vocab_size"], classes["marker_base"]
    hs_tab, kern, sig_tab, anom_sig = token_tables(seed, C, vocab, base)
    N = len(fids)
    # a flow's attributes, drawn per flow id (ids are dense from 0)
    n_flows = int(fids.max()) + 1
    gf = rng(seed, 0xF70)
    label = gf.integers(0, C, size=n_flows)
    anom = gf.random(n_flows) < float(traffic["anomaly_share"])
    lo, hi = traffic["anomaly_at"]
    anom_at = gf.integers(lo, hi + 1, size=n_flows)
    # the k-th packet of its flow: occurrence count in arrival order
    order = np.argsort(fids, kind="stable")
    sf = fids[order]
    starts = np.r_[0, np.nonzero(sf[1:] != sf[:-1])[0] + 1]
    run_start = np.repeat(starts, np.diff(np.r_[starts, N]))
    occ = np.empty(N, np.int64)
    occ[order] = np.arange(N) - run_start
    gp = rng(seed, 0xB0D)
    state = gp.integers(0, 64, size=N)
    choice = gp.integers(0, 8, size=(N, T))
    lab, an, at = label[fids], anom[fids], anom_at[fids]
    toks = np.empty((N, T), np.int32)
    for t in range(T):
        a = occ * T + t  # absolute token position in the flow
        hs = hs_tab[lab, np.minimum(a, 7)]
        sg = sig_tab[lab, a % 4]
        body = kern[lab, state % 64, choice[:, t]]
        tok = np.where(a < 8, hs, np.where(a % 17 == 0, sg, body))
        inject = an & (a >= at) & (a < at + 4)
        tok = np.where(inject, anom_sig[np.clip(a - at, 0, 3)], tok)
        state = np.where(a >= 8, (state * 5 + tok) % 64, state)
        toks[:, t] = tok
    return toks, anom_sig


def generate(traffic: Dict[str, Any], classes: Dict[str, int], capacity: int,
             seed: int, n_packets: int) -> Stream:
    """The pre-fill plus ``n_packets`` packets of the mix, from ``seed``."""
    fids, pop = flow_arrivals(traffic, capacity, seed, n_packets)
    toks, anom_sig = packet_tokens(fids, traffic, classes, seed)
    return Stream(fids=fids, tokens=toks, prefill=pop,
                  anomaly_sig=np.asarray(anom_sig), pkt_len=int(traffic["pkt_len"]))
