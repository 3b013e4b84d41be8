"""Operations and bytes the classifier's work needs, from its shapes.

These count what the algorithm needs, not what an implementation moves:
multiply-adds of the products (2 operations each; element-wise work is not
counted), and bytes as one read and one write of each distinct flow's
table row a batch touches plus one read of the weights the path uses.  A
later implementation that pads, copies or re-reads more is measured
against the same need.

Chimera decode of one token, per layer (H heads of d_head, m random
features, G globals, L-token chunks, b signature bits):

* q, k, v and output projections: 4 x 2 d (H d_head)
* feature maps of q and k: 2 x 2 H d_head m
* local attention over the (L + 1) / 2 chunk tokens it sees on average:
  2 x 2 H d_head (L + 1) / 2
* stream readout phi(q).S and phi(q).Z: 2 H m (d_head + 1)
* globals: the signature 2 H d_head b, scores 2 H m G, values 2 H G d_head
* folding the token into S and Z: 2 H m (d_head + 1)
* SwiGLU MLP: 3 x 2 d d_ff

The score stage of one packet: the class and anomaly heads, 2 d (K + 1).
"""

from __future__ import annotations

from typing import Any, Dict


def token_flops(model: Dict[str, Any]) -> float:
    d, H, dh, dff = model["d_model"], model["n_heads"], model["d_head"], model["d_ff"]
    m, G, L, b = model["feature_map"]["m"], model["n_global"], model["chunk_size"], model["sig_bits"]
    per_layer = (
        4 * 2 * d * H * dh
        + 2 * 2 * H * dh * m
        + 2 * 2 * H * dh * (L + 1) / 2
        + 2 * H * m * (dh + 1)
        + 2 * H * dh * b + 2 * H * m * G + 2 * H * G * dh
        + 2 * H * m * (dh + 1)
        + 3 * 2 * d * dff
    )
    return model["n_layers"] * per_layer


def score_flops(model: Dict[str, Any], classes: Dict[str, Any]) -> float:
    return 2 * model["d_model"] * (classes["n_classes"] + 1)


def packet_flops(model: Dict[str, Any], classes: Dict[str, Any], pkt_len: int) -> float:
    return pkt_len * token_flops(model) + score_flops(model, classes)


def sig_words(model: Dict[str, Any], classes: Dict[str, Any]) -> int:
    return max(-(-(model["vocab_size"] - classes["marker_base"]) // 32), 1)


def row_bytes(model: Dict[str, Any], classes: Dict[str, Any]) -> int:
    """One flow's device state at float32: per layer S, Z, the key and value
    ring of one chunk and its fill count; the cumulative signature, the
    hidden-state sum, the position and the veto bit."""
    d, H, dh = model["d_model"], model["n_heads"], model["d_head"]
    m, L = model["feature_map"]["m"], model["chunk_size"]
    layer = 4 * (H * m * dh + H * m + 2 * H * L * dh) + 4
    return model["n_layers"] * layer + 4 * sig_words(model, classes) + 4 * d + 4 + 1


def weight_bytes(model: Dict[str, Any], classes: Dict[str, Any]) -> int:
    """Weights the flow path reads, at float32: embedding table, blocks,
    final norm and the two heads (the language-model head is not read)."""
    d, H, dh, dff = model["d_model"], model["n_heads"], model["d_head"], model["d_ff"]
    m, G, b = model["feature_map"]["m"], model["n_global"], model["sig_bits"]
    V = -(-model["vocab_size"] // model["vocab_pad_multiple"]) * model["vocab_pad_multiple"]
    layer = 2 * d + 4 * d * H * dh + m * dh + dh * b + 2 * H * G * dh + 3 * d * dff
    return 4 * (V * d + model["n_layers"] * layer + d + d * (classes["n_classes"] + 1))


def batch_least_s(model, classes, pkt_len: int, packets: int, flows: int,
                  peaks: Dict[str, float]) -> Dict[str, float]:
    """Least time of one ingest call on one chip's peaks: the larger of its
    operations over peak FLOP/s and its bytes over peak bytes/s."""
    f = packets * packet_flops(model, classes, pkt_len)
    by = 2 * flows * row_bytes(model, classes) + weight_bytes(model, classes)
    return {"flops_s": f / peaks["flops_per_s"], "bytes_s": by / peaks["bytes_per_s"]}


def score_stage_bytes(model, classes, packets: int, calls: int) -> int:
    """Bytes the score stage needs: per packet its pooled features,
    signature and veto bit in and its K + 4 outputs out; per call the heads'
    weights and the one-rule TCAM table."""
    d, K, W = model["d_model"], classes["n_classes"], sig_words(model, classes)
    return packets * (4 * d + 4 * W + 4 + 4 * (K + 4)) + calls * (4 * d * (K + 1) + 8 * W + 8)
