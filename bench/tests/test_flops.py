"""The operation and byte counts against numbers worked by hand, and at the
published widths against the numbers the harness counted before the
counts moved into the configuration's family."""

import json
import os

from lib import flops, spec

CHIMERA = spec.load_module("models", "chimera_dataplane")

# d 8, 2 heads of 4, d_ff 16, m 4 features, 2 globals, chunks of 3, 4-bit signatures
TINY = {"n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv_heads": 2, "d_head": 4, "d_ff": 16,
        "vocab_size": 96, "vocab_pad_multiple": 32, "feature_map": {"m": 4}, "n_global": 2,
        "chunk_size": 3, "sig_bits": 4}
# the same with both query heads on one kv head
TINY_GQA = {**TINY, "n_kv_heads": 1}
CLASSES = {"n_classes": 3, "marker_base": 32}


def _published():
    path = os.path.join(os.path.dirname(__file__), "..", "configs", "chimera-dp-1chip.json")
    with open(path) as f:
        cfg = json.load(f)
    return cfg["model"], {**cfg["classifier"], "vocab_size": cfg["model"]["vocab_size"]}


def test_token_flops_by_hand():
    per_layer = (
        4 * 2 * 8 * 8         # q, k, v, o projections: 512
        + 2 * 2 * 2 * 4 * 4   # phi of q and k: 128
        + 2 * 2 * 2 * 4 * 2   # local over (3 + 1) / 2 = 2 keys: 64
        + 2 * 2 * 4 * 5       # stream readout: 80
        + 2 * 2 * 4 * 4       # signature: 64
        + 2 * 2 * 4 * 2       # global scores: 32
        + 2 * 2 * 2 * 4       # global values: 32
        + 2 * 2 * 4 * 5       # fold: 80
        + 3 * 2 * 8 * 16      # MLP: 768
    )
    assert per_layer == 1760
    assert CHIMERA.token_flops(TINY) == 2 * 1760


def test_packet_and_score_flops():
    assert flops.score_flops(TINY, CLASSES) == 2 * 8 * 4
    assert flops.packet_flops(CHIMERA, TINY, CLASSES, 5) == 5 * 3520 + 64


def test_row_bytes_by_hand():
    # per layer: S 2*4*4, Z 2*4, k and v rings 2*(2*3*4) floats, + count
    layer = 4 * (32 + 8 + 48) + 4  # 356
    sig_words = 2  # (96 - 32) markers -> 2 words
    assert flops.sig_words(TINY, CLASSES) == sig_words
    assert CHIMERA.row_bytes(TINY, CLASSES) == 2 * layer + 4 * sig_words + 4 * 8 + 4 + 1


def test_row_bytes_at_published_widths():
    """1,590,397 B per flow in the engine's own accounting, which adds an
    8-byte host LRU stamp to the device row."""
    model, classes = _published()
    assert CHIMERA.row_bytes(model, classes) + 8 == 1_590_397


def test_counts_at_published_widths():
    """The five counts ``ingest_mfu`` and ``score_kernel_roofline`` read,
    as the harness counted them before they moved into the family."""
    model, classes = _published()
    assert CHIMERA.token_flops(model) == 8_275_968
    assert flops.packet_flops(CHIMERA, model, classes, 16) == 132_420_096
    assert CHIMERA.row_bytes(model, classes) == 1_590_389
    assert CHIMERA.weight_bytes(model, classes) == 12_404_736
    assert flops.score_flops(model, classes) == 4_608


def test_weight_bytes_by_hand():
    layer = 2 * 8 + 4 * 8 * 8 + 4 * 4 + 4 * 4 + 2 * 2 * 2 * 4 + 3 * 8 * 16  # 712
    assert CHIMERA.weight_bytes(TINY, CLASSES) == 4 * (96 * 8 + 2 * layer + 8 + 8 * 4)


def test_grouped_query_counts_by_hand():
    """Two query heads on one kv head: k, v, their feature map, the fold,
    the state and the global keys and values are counted once per kv head."""
    per_layer = (
        2 * 2 * 8 * 8 + 2 * 2 * 8 * 4  # q, o; k, v: 384
        + 2 * 2 * 4 * 4 + 2 * 1 * 4 * 4  # phi of q; of k: 96
        + 2 * 2 * 2 * 4 * 2  # local, per query head: 64
        + 2 * 2 * 4 * 5  # stream readout: 80
        + 2 * 2 * 4 * 4 + 2 * 2 * 4 * 2 + 2 * 2 * 2 * 4  # signature, global scores, values: 128
        + 2 * 1 * 4 * 5  # fold, per kv head: 40
        + 3 * 2 * 8 * 16  # MLP: 768
    )
    assert per_layer == 1560
    assert CHIMERA.token_flops(TINY_GQA) == 2 * 1560
    layer = 4 * (16 + 4 + 24) + 4  # S 1*4*4, Z 1*4, rings 2*(1*3*4), + count: 180
    assert CHIMERA.row_bytes(TINY_GQA, CLASSES) == 2 * layer + 4 * 2 + 4 * 8 + 4 + 1
    attn = 4 * (8 + 2 * 8 * 8 + 2 * 8 * 4 + 4 * 4 + 4 * 4 + 2 * 1 * 2 * 4)  # 992
    mlp = 4 * (8 + 3 * 8 * 16)  # 1568
    assert CHIMERA.weight_bytes(TINY_GQA, CLASSES) == 4 * (96 * 8 + 8 + 8 * 4) + 2 * (attn + mlp)


def test_batch_least_time_binds_on_bytes():
    peaks = {"flops_per_s": 1e6, "bytes_per_s": 1e3}
    t = flops.batch_least_s(CHIMERA, TINY, CLASSES, 5, packets=10, flows=4, peaks=peaks)
    assert t["flops_s"] == 10 * (5 * 3520 + 64) / 1e6
    by = 2 * 4 * CHIMERA.row_bytes(TINY, CLASSES) + CHIMERA.weight_bytes(TINY, CLASSES)
    assert t["bytes_s"] == by / 1e3


def test_score_stage_bytes():
    per_pkt = 4 * 8 + 4 * 2 + 4 + 4 * 7
    per_call = 4 * 8 * 4 + 8 * 2 + 8
    assert flops.score_stage_bytes(TINY, CLASSES, packets=6, calls=2) == 6 * per_pkt + 2 * per_call
