"""The output check that decides ``correct``.

The program's answers for the packets of the measured window are compared
with the plain reference, once the window has closed:

1. A directory model, written here from the flow table's documented
   semantics, replays every ``ingest`` call of the run (pre-fill, warm-up,
   window) and says which earlier packets each packet's flow state holds:
   flows are resident until evicted; each call first refreshes the LRU
   stamp (the call's tick) of every resident flow it carries, then
   resolves its packets in order, a missing flow taking the lowest free
   slot or else the slot with the oldest stamp (lowest slot on a tie),
   which starts that flow afresh.  On several shards a flow lives on shard
   splitmix64(fid) mod shards, each shard a table of its own.
2. A sample of the window's packets, drawn from the seed, and the window
   packet whose flow has the longest history, pick the flows to check; every
   window packet of those flows is compared.
3. The reference recomputes those flows from their first packet; the
   symbolic path (marker signature, TCAM rule, sticky veto, cascade fusion
   with S = 1 pinning) is worked out here in numpy, exactly.

Numbers compared (each with its limit from ``bench/limits/<config>.json``):
``missing`` answers, ``veto_mismatch``, ``sig_mismatch``,
``s_sym_mismatch`` and ``pin_violation`` counts (exact: limit 0), and
``s_nn_gap`` (largest |s_nn - reference|), ``trust_gap`` (largest |trust -
reference|) and ``pred_gap`` (largest amount by which the reference's logit
of the served class lies below its best).
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

from .traffic import rng

EXACT = ("missing", "veto_mismatch", "sig_mismatch", "s_sym_mismatch", "pin_violation")
GAPS = ("s_nn_gap", "trust_gap", "pred_gap")


def flow_shard(fids: np.ndarray, shards: int) -> np.ndarray:
    """splitmix64(fid) mod shards."""
    z = np.atleast_1d(np.asarray(fids)).astype(np.uint64)
    z = z + np.uint64(0x9E3779B97F4A7C15)
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    z = z ^ (z >> np.uint64(31))
    return (z % np.uint64(shards)).astype(np.int64)


def segments(fids: np.ndarray, calls: Sequence[Tuple[int, int]], capacity: int,
             shards: int = 1) -> np.ndarray:
    """Segment id of every packet ingested by ``calls`` ((lo, hi) stream
    ranges in call order): packets share a segment when the later one
    continues the state the earlier one left."""
    owner = flow_shard(fids, shards) if shards > 1 else np.zeros(len(fids), np.int64)
    never = np.iinfo(np.int64).max
    slot_of: List[Dict[int, int]] = [{} for _ in range(shards)]
    fid_of = [np.full(capacity, -1, np.int64) for _ in range(shards)]
    stamp = [np.full(capacity, never, np.int64) for _ in range(shards)]
    free = [list(range(capacity - 1, -1, -1)) for _ in range(shards)]
    seg_of: Dict[int, int] = {}
    seg = np.full(len(fids), -1, np.int64)
    n_seg = 0
    fl, ol = fids.tolist(), owner.tolist()
    for tick, (lo, hi) in enumerate(calls, start=1):
        for i in range(lo, hi):
            s = slot_of[ol[i]].get(fl[i])
            if s is not None:
                stamp[ol[i]][s] = tick
        for i in range(lo, hi):
            f, o = fl[i], ol[i]
            s = slot_of[o].get(f)
            if s is None:
                if free[o]:
                    s = free[o].pop()
                else:
                    s = int(np.argmin(stamp[o]))
                    victim = int(fid_of[o][s])
                    del slot_of[o][victim]
                    del seg_of[victim]
                slot_of[o][f] = s
                fid_of[o][s] = f
                seg_of[f] = n_seg
                n_seg += 1
            stamp[o][s] = tick
            seg[i] = seg_of[f]
    return seg


def packet_signature(tokens: np.ndarray, sig_words: int, marker_base: int) -> np.ndarray:
    """(P, T) tokens -> (P, W) uint32 presence bitmap of marker tokens."""
    P = tokens.shape[0]
    bits = np.zeros((P, 32 * sig_words), bool)
    m = tokens.astype(np.int64) - marker_base
    rows = np.repeat(np.arange(P), tokens.shape[1])
    keep = m.reshape(-1) >= 0
    bits[rows[keep], np.minimum(m.reshape(-1)[keep], 32 * sig_words - 1)] = True
    words = bits.reshape(P, sig_words, 32).astype(np.uint64) << np.arange(32, dtype=np.uint64)
    return words.sum(-1).astype(np.uint32)


def choose(window: np.ndarray, seg: np.ndarray, seed: int, sample: int) -> np.ndarray:
    """Segment ids to check: those of ``sample`` window packets drawn from
    the seed, plus that of the window packet with the longest history."""
    g = rng(seed, 0xC4E)
    pick = g.choice(window, size=min(sample, len(window)), replace=False)
    # history length of a window packet: packets of its segment up to it
    order = np.argsort(seg, kind="stable")
    first = np.r_[0, np.nonzero(np.diff(seg[order]))[0] + 1]
    depth = np.empty(len(seg), np.int64)
    depth[order] = np.arange(len(seg)) - np.repeat(first, np.diff(np.r_[first, len(seg)]))
    longest = window[np.argmax(depth[window])]
    return np.unique(np.r_[seg[pick], seg[longest]])


def reference_answers(ref, model, params, stream, seg: np.ndarray, chosen: np.ndarray,
                      last: int, classes: Dict[str, Any], rule, dtype) -> Dict[str, Any]:
    """Reference answers for every packet (stream index < ``last``) of the
    chosen segments."""
    T = stream.pkt_len
    values, masks, weights, hard_rule = rule
    W = values.shape[1]
    idx_of = [np.nonzero(seg[:last] == s)[0] for s in chosen]
    seqs = [stream.tokens[ix].reshape(-1) for ix in idx_of]
    outs = ref.run(model, params, seqs, dtype=dtype)
    alpha = float(np.asarray(params["fusion"]["alpha"]))
    beta = float(np.asarray(params["fusion"]["beta"]))
    ans = {k: [] for k in ("idx", "logits", "s_nn", "sig", "vetoed", "s_sym", "trust")}
    for ix, (logits, s_nn) in zip(idx_of, outs):
        ends = np.arange(1, len(ix) + 1) * T - 1
        sig = np.bitwise_or.accumulate(
            packet_signature(stream.tokens[ix], W, classes["marker_base"]), axis=0)
        hits = np.all((sig[:, None, :] & masks) == (values & masks), axis=-1)  # (n, M)
        hard = np.logical_or.accumulate(np.any(hits & hard_rule, axis=-1))
        s_sym = (hits.astype(np.float64) * weights).sum(-1)
        sn = s_nn[ends].astype(np.float64)
        trust = np.where(hard, 1.0, 1.0 / (1.0 + np.exp(-(alpha * sn + beta * s_sym))))
        for k, v in (("idx", ix), ("logits", logits[ends]), ("s_nn", sn), ("sig", sig),
                     ("vetoed", hard), ("s_sym", s_sym), ("trust", trust)):
            ans[k].append(v)
    return {k: np.concatenate(v) for k, v in ans.items()}


def compare(got: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
            window_mask: np.ndarray) -> Dict[str, float]:
    """The numbers compared, over the reference's packets in the window.
    ``got`` holds the answers by stream index (``have`` marks answered)."""
    ix = ref["idx"]
    w = window_mask[ix]
    ix = ix[w]
    r = {k: v[w] for k, v in ref.items()}
    have = got["have"][ix]
    out = {"missing": float(np.sum(~have)), "compared": float(len(ix))}
    ix, r = ix[have], {k: v[have] for k, v in r.items()}
    vet = got["vetoed"][ix]
    out["vetoed_compared"] = float(np.sum(r["vetoed"]))
    out["veto_mismatch"] = float(np.sum(vet != r["vetoed"]))
    out["sig_mismatch"] = float(np.sum(np.any(got["sig"][ix] != r["sig"], axis=-1)))
    out["s_sym_mismatch"] = float(np.sum(got["s_sym"][ix] != r["s_sym"]))
    out["pin_violation"] = float(np.sum(vet & (got["trust"][ix] != 1.0)))
    if len(ix):
        out["s_nn_gap"] = float(np.max(np.abs(got["s_nn"][ix] - r["s_nn"])))
        out["trust_gap"] = float(np.max(np.abs(got["trust"][ix] - r["trust"])))
        lg = r["logits"]
        served = lg[np.arange(len(ix)), got["pred"][ix]]
        out["pred_gap"] = float(np.max(lg.max(-1) - served))
    else:
        out.update(s_nn_gap=float("inf"), trust_gap=float("inf"), pred_gap=float("inf"))
    return out


def control_answers(ref: Dict[str, np.ndarray], ctrl: Dict[str, np.ndarray],
                    n: int) -> Dict[str, np.ndarray]:
    """The lower-precision control's answers, laid out like the program's:
    it serves the class it ranks first."""
    got = {"have": np.zeros(n, bool), "vetoed": np.zeros(n, bool),
           "sig": np.zeros((n, ref["sig"].shape[1]), np.uint32), "s_sym": np.zeros(n),
           "trust": np.zeros(n), "s_nn": np.zeros(n), "pred": np.zeros(n, np.int64)}
    ix = ctrl["idx"]
    got["have"][ix] = True
    for k in ("vetoed", "sig", "s_sym", "trust", "s_nn"):
        got[k][ix] = ctrl[k]
    got["pred"][ix] = np.argmax(ctrl["logits"], -1)
    return got


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(numbers[k] <= limits[k] for k in EXACT + GAPS)
