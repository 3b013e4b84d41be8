import numpy as np

from lib import spec, traffic

CLASSES = {"n_classes": 8, "vocab_size": 1024, "marker_base": 256}


def _mix(name):
    return spec.load_json(f"{spec.BENCH_DIR}/traffic/{name}.json")


def test_same_seed_same_stream_other_seed_other_stream():
    tr = _mix("zipf.backlog")
    a = traffic.generate(tr, CLASSES, 256, 2**31 + 5, 4000)
    b = traffic.generate(tr, CLASSES, 256, 2**31 + 5, 4000)
    c = traffic.generate(tr, CLASSES, 256, 2**31 + 6, 4000)
    assert np.array_equal(a.fids, b.fids) and np.array_equal(a.tokens, b.tokens)
    assert np.array_equal(a.anomaly_sig, b.anomaly_sig)
    assert not np.array_equal(a.fids, c.fids)


def test_arrivals_are_the_same_for_every_seed_in_another_order():
    """With ``arrival_seed`` every seed carries the same flows in each
    call-sized block (the same work), shuffled within the block."""
    tr = _mix("zipf.backlog")
    a = traffic.generate(tr, CLASSES, 256, 2**31 + 5, 4096)
    c = traffic.generate(tr, CLASSES, 256, 2**31 + 6, 4096)
    b = tr["batch"]
    main_a, main_c = a.fids[a.prefill:], c.fids[c.prefill:]
    for lo in range(0, len(main_a), b):
        assert np.array_equal(np.sort(main_a[lo:lo + b]), np.sort(main_c[lo:lo + b]))
    assert not np.array_equal(main_a, main_c)
    assert np.array_equal(a.fids[:a.prefill], c.fids[:c.prefill])


def test_zipf_rank_shares():
    """Packets pick ranks with P(rank r) = (1 / (r + 1)) / H(pop)."""
    tr = {**_mix("zipf.backlog"), "life": {"shape": 1.2, "min": 10**9, "cap": 10**9}}
    pop = 2 * 1024
    n = 200_000
    fids, got_pop = traffic.flow_arrivals(tr, 1024, 11, n)
    assert got_pop == pop
    ranks = fids[pop:]  # no flow ever ends, so a flow id is its rank
    share = np.bincount(ranks, minlength=pop) / n
    h = np.sum(1.0 / np.arange(1, pop + 1))
    for r in (0, 1, 9):
        want = 1.0 / (r + 1) / h
        assert abs(share[r] - want) < 4 * np.sqrt(want / n), (r, share[r], want)


def test_zipf_flows_end_and_are_replaced():
    tr = {k: v for k, v in _mix("zipf.backlog").items() if k != "arrival_seed"}  # unshuffled
    fids, pop = traffic.flow_arrivals(tr, 256, 3, 20_000)
    main = fids[pop:]
    assert main.max() >= pop  # fresh flow ids appeared
    # a fresh id, once opened, belongs to one rank: ids are increasing in order of first use
    first = np.unique(main[main >= pop], return_index=True)[1]
    assert np.all(np.diff(main[main >= pop][np.sort(first)]) > 0)


def test_flood_every_packet_is_a_new_flow():
    s = traffic.generate(_mix("flood.backlog"), CLASSES, 512, 9, 5000)
    main = s.fids[s.prefill:]
    assert len(np.unique(main)) == len(main)
    assert main.min() >= s.prefill  # none continues a pre-filled flow


def test_prefill_is_one_packet_per_flow_coldest_first():
    s = traffic.generate(_mix("zipf.backlog"), CLASSES, 64, 1, 100)
    assert s.prefill == 128
    assert np.array_equal(s.fids[:128], np.arange(127, -1, -1))


def test_anomalous_flows_carry_the_signature():
    s = traffic.generate(_mix("zipf.backlog"), CLASSES, 256, 5, 8000)
    has = np.isin(s.tokens, s.anomaly_sig)
    flows = np.unique(s.fids[np.any(has, axis=1)])
    assert len(flows) > 0
    assert s.tokens.min() >= 0 and s.tokens.max() < CLASSES["vocab_size"]

