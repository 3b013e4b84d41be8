"""Operations and bytes the classifier's work needs, from its shapes.

These count what the algorithm needs, not what an implementation moves:
multiply-adds of the products (2 operations each; element-wise work is not
counted), and bytes as one read and one write of each distinct flow's
table row a batch touches plus one read of the weights the path uses.  A
later implementation that pads, copies or re-reads more is measured
against the same need.

The backbone's counts belong to its family (``bench/models/<family>.py``:
``token_flops``, ``row_bytes``, ``weight_bytes``); what is here holds for
every family.  The score stage of one packet: the class and anomaly heads,
2 d (K + 1).
"""

from __future__ import annotations

from typing import Any, Dict


def score_flops(model: Dict[str, Any], classes: Dict[str, Any]) -> float:
    return 2 * model["d_model"] * (classes["n_classes"] + 1)


def packet_flops(family, model: Dict[str, Any], classes: Dict[str, Any], pkt_len: int) -> float:
    return pkt_len * family.token_flops(model) + score_flops(model, classes)


def sig_words(model: Dict[str, Any], classes: Dict[str, Any]) -> int:
    return max(-(-(model["vocab_size"] - classes["marker_base"]) // 32), 1)


def batch_least_s(family, model, classes, pkt_len: int, packets: int, flows: int,
                  peaks: Dict[str, float]) -> Dict[str, float]:
    """Least time of one ingest call on one chip's peaks: the larger of its
    operations over peak FLOP/s and its bytes over peak bytes/s."""
    f = packets * packet_flops(family, model, classes, pkt_len)
    by = 2 * flows * family.row_bytes(model, classes) + family.weight_bytes(model, classes)
    return {"flops_s": f / peaks["flops_per_s"], "bytes_s": by / peaks["bytes_per_s"]}


def score_stage_bytes(model, classes, packets: int, calls: int) -> int:
    """Bytes the score stage needs: per packet its pooled features,
    signature and veto bit in and its K + 4 outputs out; per call the heads'
    weights and the one-rule TCAM table."""
    d, K, W = model["d_model"], classes["n_classes"], sig_words(model, classes)
    return packets * (4 * d + 4 * W + 4 + 4 * (K + 4)) + calls * (4 * d * (K + 1) + 8 * W + 8)
