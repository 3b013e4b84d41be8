"""The operation and byte counts against numbers worked by hand."""

from lib import flops

# d 8, 2 heads of 4, d_ff 16, m 4 features, 2 globals, chunks of 3, 4-bit signatures
TINY = {"n_layers": 2, "d_model": 8, "n_heads": 2, "d_head": 4, "d_ff": 16, "vocab_size": 96,
        "vocab_pad_multiple": 32, "feature_map": {"m": 4}, "n_global": 2, "chunk_size": 3,
        "sig_bits": 4}
CLASSES = {"n_classes": 3, "marker_base": 32}


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
    assert flops.token_flops(TINY) == 2 * 1760


def test_packet_and_score_flops():
    assert flops.score_flops(TINY, CLASSES) == 2 * 8 * 4
    assert flops.packet_flops(TINY, CLASSES, 5) == 5 * 3520 + 64


def test_row_bytes_by_hand():
    # per layer: S 2*4*4, Z 2*4, k and v rings 2*(2*3*4) floats, + count
    layer = 4 * (32 + 8 + 48) + 4  # 356
    sig_words = 2  # (96 - 32) markers -> 2 words
    assert flops.sig_words(TINY, CLASSES) == sig_words
    assert flops.row_bytes(TINY, CLASSES) == 2 * layer + 4 * sig_words + 4 * 8 + 4 + 1


def test_row_bytes_at_published_widths():
    """1,590,397 B per flow in the engine's own accounting, which adds an
    8-byte host LRU stamp to the device row."""
    import json
    import os

    cfg = json.load(open(os.path.join(os.path.dirname(__file__), "..", "configs",
                                      "chimera-dp-1chip.json")))
    assert flops.row_bytes(cfg["model"], cfg["classifier"]) + 8 == 1_590_397


def test_weight_bytes_by_hand():
    layer = 2 * 8 + 4 * 8 * 8 + 4 * 4 + 4 * 4 + 2 * 2 * 2 * 4 + 3 * 8 * 16  # 712
    assert flops.weight_bytes(TINY, CLASSES) == 4 * (96 * 8 + 2 * layer + 8 + 8 * 4)


def test_batch_least_time_binds_on_bytes():
    peaks = {"flops_per_s": 1e6, "bytes_per_s": 1e3}
    t = flops.batch_least_s(TINY, CLASSES, 5, packets=10, flows=4, peaks=peaks)
    assert t["flops_s"] == 10 * (5 * 3520 + 64) / 1e6
    by = 2 * 4 * flops.row_bytes(TINY, CLASSES) + flops.weight_bytes(TINY, CLASSES)
    assert t["bytes_s"] == by / 1e3


def test_score_stage_bytes():
    per_pkt = 4 * 8 + 4 * 2 + 4 + 4 * 7
    per_call = 4 * 8 * 4 + 8 * 2 + 8
    assert flops.score_stage_bytes(TINY, CLASSES, packets=6, calls=2) == 6 * per_pkt + 2 * per_call
