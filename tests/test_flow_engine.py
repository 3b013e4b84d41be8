"""FlowEngine runtime: interleaved-vs-sequential equivalence, budget-bounded
eviction, hard-veto on the hot path (Eq. 15), two-timescale table swaps
without retracing, and traffic-scale flow churn (slow tier)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.pipeline import FlowScenario, arrival_rounds
from repro.models import model as M
from repro.serve.flow_engine import (
    FlowEngine,
    FlowEngineConfig,
    init_flow_caches,
    stored_slot_shape,
)
from repro.serve.sharded_flow_engine import ShardedFlowEngine
from repro.train import classifier as C

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def classifier(tiny_classifier_cfg):
    params, _ = C.init_classifier(tiny_classifier_cfg, KEY)
    return tiny_classifier_cfg, params


def _engine(classifier, rules=None, **fkw):
    ccfg, params = classifier
    if rules is None:
        rules = C.default_rules(ccfg, jnp.asarray([400, 401, 402, 403]))
    fkw.setdefault("capacity", 16)
    fkw.setdefault("lanes", 8)
    return FlowEngine(ccfg, params, rules, FlowEngineConfig(**fkw))


class TestArrivalRounds:
    def test_rounds_are_duplicate_free_and_order_preserving(self):
        keys = [5, 7, 5, 5, 9, 7]
        rounds = arrival_rounds(keys)
        assert rounds == [[0, 1, 4], [2, 5], [3]]
        for r in rounds:
            assert len({keys[i] for i in r}) == len(r)


class TestFlowScenario:
    def test_max_flow_pkts_is_a_hard_cap(self):
        sc = FlowScenario(kind="rule-violating", pkt_len=16,
                          packets_per_batch=64, seed=1, max_flow_pkts=2)
        counts = {}
        for _ in range(6):
            b = sc.next_batch()
            for fid in b["flow_ids"].tolist():
                counts[fid] = counts.get(fid, 0) + 1
        assert max(counts.values()) <= 2  # anomaly bump must not exceed cap

    def test_cap_too_tight_for_signature_downgrades_to_benign(self):
        sc = FlowScenario(kind="rule-violating", pkt_len=8,
                          packets_per_batch=64, seed=1, max_flow_pkts=1)
        for _ in range(4):
            assert not sc.next_batch()["anomalous"].any()

    def test_burst_active_population_bounded(self):
        """Burst kinds spawn faster than retirement; the active flow set
        must saturate at max_active, not grow for the generator's life."""
        sc = FlowScenario(kind="burst", pkt_len=8, packets_per_batch=64,
                          seed=2, max_active=500)
        for _ in range(12):
            sc.next_batch()
            assert sc.active_flows <= 500
        assert sc.active_flows >= 400  # saturated near the cap, still serving

    def test_wide_marker_vocab_needs_matching_sig_words(self, tiny_arch):
        """packet_signature must give every marker its own TCAM bit when
        sig_words covers the vocab (the flow_serve driver derives it)."""
        import dataclasses as dc

        arch = dc.replace(tiny_arch, vocab_size=1024)
        ccfg = C.ClassifierConfig(arch=arch, n_classes=8, marker_base=256,
                                  sig_words=-(-(1024 - 256) // 32))
        toks = jnp.asarray([[600, 0, 0, 0], [1023, 0, 0, 0]], jnp.int32)
        sig = C.packet_signature(ccfg, toks)
        bits = np.unpackbits(
            np.asarray(sig).view(np.uint8), axis=-1, bitorder="little"
        )
        np.testing.assert_array_equal(np.nonzero(bits[0])[0], [600 - 256])
        np.testing.assert_array_equal(np.nonzero(bits[1])[0], [1023 - 256])


class TestEquivalence:
    def test_interleaved_equals_sequential_replay(self, classifier):
        """Same per-flow scores whether packets arrive interleaved (with
        same-flow repeats inside one ingest call) or one flow at a time."""
        rng = np.random.default_rng(0)
        pkt = 8
        flows = {f: rng.integers(0, 512, (3, pkt)).astype(np.int32) for f in range(3)}
        order = [(0, 0), (1, 0), (2, 0), (0, 1), (1, 1), (0, 2), (2, 1), (1, 2), (2, 2)]
        fids = np.array([f for f, _ in order])
        toks = np.stack([flows[f][p] for f, p in order])

        eng = _engine(classifier)
        eng.ingest(fids[:5], toks[:5])
        eng.ingest(fids[5:], toks[5:])
        interleaved = {f: eng.flow_scores(f) for f in flows}

        for f, pkts in flows.items():
            solo = _engine(classifier)
            solo.ingest(np.full((3,), f), pkts)
            seq = solo.flow_scores(f)
            for k, v in seq.items():
                np.testing.assert_allclose(
                    interleaved[f][k], v, atol=1e-6,
                    err_msg=f"flow {f} key {k} diverged",
                )

    def test_streaming_matches_batch_classifier(self, classifier):
        """Per-packet streaming over the decode path reproduces the batch
        classifier_forward on the concatenated flow (same pooled features,
        same signature, same fusion) to decode-vs-forward tolerance."""
        ccfg, params = classifier
        L = ccfg.arch.chimera.chunk_size
        n_pkts, pkt = 4, L // 2  # total tokens divisible by the chunk size
        rng = np.random.default_rng(1)
        toks = rng.integers(0, 512, (n_pkts, pkt)).astype(np.int32)

        eng = _engine(classifier)
        eng.ingest(np.zeros((n_pkts,), np.int64), toks)
        stream = eng.flow_scores(0)

        batch = {"tokens": jnp.asarray(toks.reshape(1, -1))}
        rules = C.default_rules(ccfg, jnp.asarray([400, 401, 402, 403]))
        out = C.classifier_forward(ccfg, params, rules, batch)
        np.testing.assert_allclose(stream["s_nn"], out["s_nn"][0], atol=2e-3)
        np.testing.assert_allclose(stream["trust"], out["trust"][0], atol=2e-3)
        assert stream["vetoed"] == bool(out["hard_hit"][0])


def _table(kind, ccfg, params, rules, fcfg):
    if kind == "sharded":
        return ShardedFlowEngine(ccfg, params, rules, fcfg, num_shards=1)
    return FlowEngine(ccfg, params, rules, fcfg)


class TestTableLayout:
    @pytest.mark.parametrize("kind", ["flow", "sharded"])
    def test_slotted_leaves_lead_with_the_slot_axis(self, classifier, kind):
        """Every cache leaf is stored slot-major, ``(slots, ...)`` after a
        sharded table's shard axis, like the table's other arrays: exactly
        the elements of ``M.init_caches`` moved slot-major, each slot's row
        reshaped by :func:`stored_slot_shape` -- lane-dense where both minor
        dimensions are narrower than a lane tile and their product fills
        whole tiles (here S, k_buf and v_buf, 16 x 16), unchanged otherwise
        (Z, count)."""
        ccfg, params = classifier
        fcfg = FlowEngineConfig(capacity=16, lanes=8)
        eng = _table(kind, ccfg, params, C.default_rules(ccfg, jnp.asarray([400])),
                     fcfg)
        lead, n = (() if kind == "flow" else (1,)), fcfg.capacity + 1
        model = M.init_caches(ccfg.arch, n, fcfg.max_flow_tokens,
                              dtype=jnp.float32)
        stored = jax.tree_util.tree_map(lambda c: c.shape[len(lead) + 1:],
                                        eng.caches)
        assert vars(stored["b0"]) == {
            "S": (2, 2, 2, 128), "Z": (2, 2, 16), "k_buf": (2, 2, 2, 128),
            "v_buf": (2, 2, 2, 128), "count": (2,),
        }
        leaves = jax.tree_util.tree_leaves(eng.caches)
        assert len(leaves) == len(jax.tree_util.tree_leaves(model))
        for leaf, ref in zip(leaves, jax.tree_util.tree_leaves(model)):
            layers, slots, *rest = ref.shape
            assert leaf.shape == lead + (slots,) + stored_slot_shape(
                (layers, *rest))
            assert leaf.dtype == ref.dtype
            np.testing.assert_array_equal(
                np.asarray(leaf).reshape(lead + (slots, layers, *rest)),
                np.broadcast_to(np.moveaxis(np.asarray(ref), 1, 0),
                                lead + (slots, layers, *rest)))
        for a in (eng.positions, eng.sig, eng.hidden_sum, eng.vetoed):
            assert a.shape[: len(lead) + 1] == lead + (n,)

    @pytest.mark.parametrize("logical,stored", [
        ((4, 4, 64, 64), (4, 4, 32, 128)),  # the published k/v rings
        ((2, 2, 16, 16), (2, 2, 2, 128)),
        ((4, 32), (1, 128)),
        ((4, 4, 256, 64), (4, 4, 256, 64)),  # S: m fills the tile already
        ((4, 4, 256), (4, 4, 256)),  # Z
        ((4, 4, 64, 128), (4, 4, 64, 128)),  # minor dimension fills a tile
        ((2, 2, 16), (2, 2, 16)),  # 32 elements: no whole tile
        ((4, 48, 24), (4, 9, 128)),
        ((4,), (4,)),  # count: one dimension
    ])
    def test_stored_slot_shape_rule(self, logical, stored):
        assert stored_slot_shape(logical) == stored

    def test_ring_leaves_lane_dense_at_published_widths(self):
        """At the served widths the 64 x 64 k/v rings are stored in rows of
        128 lanes; S (m = 256) and Z, whose minor dimensions already fill a
        lane tile, keep their shapes."""
        from repro.launch.flow_serve import classifier_config

        arch = classifier_config().arch
        caches = jax.eval_shape(lambda: init_flow_caches(arch, 3, 1024))
        shapes = jax.tree_util.tree_map(lambda c: c.shape, caches)
        assert vars(shapes["b0"]) == {
            "S": (3, 4, 4, 256, 64), "Z": (3, 4, 4, 256),
            "k_buf": (3, 4, 4, 32, 128), "v_buf": (3, 4, 4, 32, 128),
            "count": (3, 4),
        }

    def test_stored_rows_hold_the_logical_decode_state(self, classifier):
        """A flow's stored row, read back through the logical shape, is the
        decode state of its tokens scanned one at a time through
        ``decode_hidden_step`` on ``M.init_caches`` leaves: the lane-dense
        fold keeps the row-major element order."""
        ccfg, params = classifier
        eng = _engine(classifier)
        rng = np.random.default_rng(4)
        toks = rng.integers(0, 512, (3, 8)).astype(np.int32)
        eng.ingest(np.array([7, 9, 7]), toks)
        slot = eng.table.slot_of[7]

        caches = M.init_caches(ccfg.arch, 1, eng.fcfg.max_flow_tokens,
                               dtype=jnp.float32)
        flow = np.concatenate([toks[0], toks[2]])
        for pos, tok in enumerate(flow):
            _, caches = M.decode_hidden_step(
                ccfg.arch, params["backbone"], jnp.asarray([tok]),
                jnp.asarray([pos]), caches)
        for leaf, ref in zip(jax.tree_util.tree_leaves(eng.caches),
                             jax.tree_util.tree_leaves(caches)):
            logical = ref.shape[:1] + ref.shape[2:]
            np.testing.assert_allclose(
                np.asarray(leaf)[slot].reshape(logical),
                np.asarray(ref)[:, 0], rtol=1e-5, atol=1e-6)

    def test_lane_dense_replay_identical_fused_per_round_and_sharded(
        self, classifier
    ):
        """Where the storage rule folds leaves (S, k_buf, v_buf here), the
        fused ingest, the per-round ingest and a one-shard sharded replay
        serve bit-identical answers and leave bit-identical tables."""
        ccfg, params = classifier
        sc = FlowScenario(kind="mix", vocab_size=512, pkt_len=8,
                          packets_per_batch=48, seed=5)
        rules = C.default_rules(ccfg, jnp.asarray(sc.anomaly_signature))
        engines = [
            FlowEngine(ccfg, params, rules,
                       FlowEngineConfig(capacity=128, lanes=16)),
            FlowEngine(ccfg, params, rules,
                       FlowEngineConfig(capacity=128, lanes=16, fused=True)),
            ShardedFlowEngine(ccfg, params, rules,
                              FlowEngineConfig(capacity=128, lanes=16),
                              num_shards=1),
        ]
        assert any(leaf.shape[-1] == 128
                   for leaf in jax.tree_util.tree_leaves(engines[0].caches))
        for _ in range(3):
            b = sc.next_batch()
            outs = [e.ingest(b["flow_ids"], b["tokens"]) for e in engines]
            for o in outs[1:]:
                for k in ("trust", "vetoed", "pred", "s_nn", "s_sym", "sig"):
                    np.testing.assert_array_equal(outs[0][k], o[k], err_msg=k)
        assert all(e.stats.flows_evicted == 0 for e in engines)
        single, fused, sharded = engines
        rows = np.array(sorted(single.table.slot_of.values()))
        assert sorted(fused.table.slot_of.values()) == rows.tolist()
        assert sorted(sharded.tables[0].slot_of.values()) == rows.tolist()
        for a, b, c in zip(jax.tree_util.tree_leaves(single.caches),
                           jax.tree_util.tree_leaves(fused.caches),
                           jax.tree_util.tree_leaves(sharded.caches)):
            np.testing.assert_array_equal(np.asarray(a)[rows], np.asarray(b)[rows])
            np.testing.assert_array_equal(np.asarray(a)[rows],
                                          np.asarray(c)[0][rows])

    @pytest.mark.parametrize("kind", ["flow", "sharded"])
    def test_per_flow_state_bytes_at_published_widths(self, kind):
        """One flow row of the served chimera-dataplane config is
        1,590,397 B (device row + 8-byte host LRU stamp), whatever the
        table's axis order."""
        from repro.compile.passes import required_sig_words
        from repro.launch.flow_serve import classifier_config

        ccfg = classifier_config()
        ccfg = dataclasses.replace(ccfg, sig_words=required_sig_words(
            ccfg.arch.vocab_size, ccfg.marker_base))
        params, _ = C.init_classifier(ccfg, KEY)
        eng = _table(kind, ccfg, params, C.default_rules(ccfg, jnp.asarray([400])),
                     FlowEngineConfig(capacity=1, lanes=8, backend="xla"))
        assert eng.per_flow_state_bytes() == 1_590_397


class TestBoundedState:
    def test_eviction_keeps_table_at_capacity(self, classifier):
        eng = _engine(classifier, capacity=8, lanes=8)
        sc = FlowScenario(kind="port-scan", pkt_len=8, packets_per_batch=64, seed=2)
        for _ in range(3):
            b = sc.next_batch()
            eng.ingest(b["flow_ids"], b["tokens"])
            assert eng.resident_flows <= 8
        assert eng.stats.flows_evicted_lru > 0
        assert eng.resident_state_bytes() <= eng.state_budget_bytes

    def test_budget_violation_rejected_at_construction(self, classifier):
        with pytest.raises(ValueError, match="Eq. 11"):
            _engine(classifier, capacity=64, state_budget_bytes=1024)

    def test_resident_bytes_invariant_under_churn(self, classifier):
        """The table is preallocated: resident bytes never grow with flow
        count or flow length (the Eq. 11 per-flow bound times capacity)."""
        eng = _engine(classifier, capacity=8, lanes=8)
        base = eng.resident_state_bytes()
        sc = FlowScenario(kind="heavy-churn", pkt_len=8, packets_per_batch=32, seed=3)
        for _ in range(3):
            b = sc.next_batch()
            eng.ingest(b["flow_ids"], b["tokens"])
        assert eng.resident_state_bytes() == base

    def test_lru_evicts_least_recently_touched(self, classifier):
        eng = _engine(classifier, capacity=4, lanes=4)
        pkt = np.zeros((1, 8), np.int32)
        for fid in [0, 1, 2, 3]:
            eng.ingest(np.array([fid]), pkt)
        eng.ingest(np.array([0]), pkt)  # refresh flow 0; LRU is now flow 1
        eng.ingest(np.array([9]), pkt)
        assert 1 not in eng.flow_ids()
        assert {0, 2, 3, 9} <= set(eng.flow_ids())

    def test_lru_never_evicts_in_batch_flow_when_avoidable(self, classifier):
        """A resident (vetoed) flow with a packet pending in the current
        batch must not be the LRU victim while an out-of-batch flow exists —
        otherwise the sticky veto silently resets mid-batch."""
        ccfg, params = classifier
        rules = C.default_rules(ccfg, jnp.asarray([400, 401, 402, 403]))
        eng = _engine(classifier, rules=rules, capacity=2, lanes=4)
        sig_pkt = np.asarray([[400, 401, 402, 403, 0, 0, 0, 0]], np.int32)
        benign = np.zeros((1, 8), np.int32)
        out = eng.ingest(np.array([1]), sig_pkt)  # flow 1 vetoed (oldest)
        assert bool(out["vetoed"][0])
        eng.ingest(np.array([2]), benign)  # flow 2 is fresher than flow 1
        # new flow 3 needs a slot; flow 1 is LRU but has a packet here, so
        # flow 2 must be the victim and flow 1's veto must survive
        out = eng.ingest(np.array([3, 1]), np.concatenate([benign, benign]))
        assert bool(out["vetoed"][1]) and float(out["trust"][1]) == 1.0
        assert 2 not in eng.flow_ids()

    def test_reset_clears_table_but_keeps_compiled_step(self, classifier):
        eng = _engine(classifier, capacity=8, lanes=4)
        pkt = np.zeros((2, 8), np.int32)
        out1 = eng.ingest(np.array([1, 2]), pkt)
        traces = eng._jit_step._cache_size()
        eng.reset()
        assert eng.resident_flows == 0 and eng.stats.packets == 0
        out2 = eng.ingest(np.array([1, 2]), pkt)  # dirty slots re-zeroed
        assert eng._jit_step._cache_size() == traces
        np.testing.assert_allclose(out1["s_nn"], out2["s_nn"], atol=1e-6)

    def test_idle_timeout_evicts(self, classifier):
        eng = _engine(classifier, capacity=8, lanes=4, idle_timeout=2)
        pkt = np.zeros((1, 8), np.int32)
        eng.ingest(np.array([7]), pkt)
        for _ in range(4):
            eng.ingest(np.array([8]), pkt)
        assert 7 not in eng.flow_ids()
        assert eng.stats.flows_evicted_idle == 1

    def test_idle_sweep_spares_flow_transmitting_this_tick(self, classifier):
        """A flow whose idle timer expired but that has a packet in the
        current batch must survive the sweep with its state intact."""
        eng = _engine(classifier, capacity=8, lanes=4, idle_timeout=2)
        pkt = np.zeros((1, 8), np.int32)
        eng.ingest(np.array([7]), pkt)  # tick 1
        eng.ingest(np.array([8]), pkt)  # tick 2
        eng.ingest(np.array([8]), pkt)  # tick 3
        eng.ingest(np.array([7]), pkt)  # tick 4: idle-expired but transmitting
        assert eng.stats.flows_evicted_idle == 0
        assert eng.flow_scores(7)["tokens"] == 16  # state continued, not fresh


class TestHardVetoHotPath:
    def test_rule_violating_flows_veto_with_trust_one(self, classifier):
        """TCAM hit ⇒ vetoed ⇒ S = 1.0 exactly, regardless of neural score;
        and the veto is sticky for the flow's lifetime."""
        ccfg, params = classifier
        sc = FlowScenario(kind="rule-violating", pkt_len=16,
                          packets_per_batch=64, seed=5)
        rules = C.default_rules(ccfg, jnp.asarray(sc.anomaly_signature))
        eng = _engine(classifier, rules=rules, capacity=512, lanes=32)
        anom_flows, veto_flows = set(), set()
        for _ in range(8):
            b = sc.next_batch()
            out = eng.ingest(b["flow_ids"], b["tokens"])
            # the hot-path invariant: every vetoed packet reports S = 1.0
            assert (out["trust"][out["vetoed"]] == 1.0).all()
            # benign flows never hit the anomaly rule
            benign_veto = out["vetoed"][~b["anomalous"]]
            assert not benign_veto.any()
            anom_flows |= set(b["flow_ids"][b["anomalous"]].tolist())
            veto_flows |= set(out["flow_ids"][out["vetoed"]].tolist())
        assert veto_flows, "no rule-violating flow was vetoed"
        assert veto_flows <= anom_flows
        # stickiness: a vetoed resident flow stays vetoed on a benign packet
        fid = next(f for f in veto_flows if f in eng.flow_ids())
        out = eng.ingest(np.array([fid]),
                         np.zeros((1, 16), np.int32))
        assert bool(out["vetoed"][0]) and float(out["trust"][0]) == 1.0


class TestSwapTables:
    def test_swap_changes_decisions_next_tick_without_retrace(self, classifier):
        ccfg, params = classifier
        sig_toks = jnp.asarray([300, 301, 302, 303])
        live = C.default_rules(ccfg, sig_toks)
        # same-shape ruleset that can never fire (cares about a marker bit
        # pattern the stream below does not emit)
        dead = C.default_rules(ccfg, jnp.asarray([500, 501, 502, 503]))
        eng = _engine(classifier, rules=dead, capacity=8, lanes=4)

        pkt = np.asarray([[300, 301, 302, 303, 0, 0, 0, 0]], np.int32)
        out = eng.ingest(np.array([1]), pkt)
        assert not out["vetoed"][0]
        traces_before = eng._jit_step._cache_size()

        rec = eng.swap_tables(ruleset=live)
        out = eng.ingest(np.array([1]), pkt)
        assert bool(out["vetoed"][0]) and float(out["trust"][0]) == 1.0
        assert eng._jit_step._cache_size() == traces_before, "hot path retraced"
        assert eng.swap_history[-1] is rec and rec.churn_ok

    def test_swap_weights_from_quantized_table(self, classifier):
        from repro.core.quantization import FixedPointSpec
        from repro.core.symbolic import compile_weights_to_table

        eng = _engine(classifier, capacity=8, lanes=4)
        w = jnp.asarray([2.5])
        table, spec = compile_weights_to_table(
            w, FixedPointSpec(bits=16), budget_bits=16)
        eng.swap_tables(weights=table, weight_spec=spec)
        np.testing.assert_allclose(eng.rules.weights, w, atol=float(spec.scale))

    def test_shape_changing_swap_rejected(self, classifier, make_ruleset):
        eng = _engine(classifier, capacity=8, lanes=4)
        W = eng.rules.values.shape[1]
        grown = make_ruleset(
            values=np.zeros((3, W), np.uint32), masks=np.zeros((3, W), np.uint32),
            hard=[True, False, False],
        )
        with pytest.raises(ValueError, match="retrace"):
            eng.swap_tables(ruleset=grown)


class TestDonationRollbackAudit:
    """Regression for the donate_argnums audit (flow_engine.py): the jitted
    steps donate the table-state argnums (2-6) but NOT ``rules`` (argnum 1),
    and ``atomic_swap`` never donates — so the adaptive rollback recipe
    (capture ``prev_rules``, install a candidate, observe an Eq. 18 t_cp
    violation, re-install the captured pytree) must stay safe while ingest
    keeps donating state buffers in between.  These tests interleave failing
    installs + rollbacks with live ingest and require bit-equality with a
    control engine that never swapped; a reuse-after-donation of the
    captured rules would surface as a deleted-buffer error or corrupt
    decisions."""

    OUT_KEYS = ("trust", "vetoed", "pred", "s_nn", "s_sym", "sig")

    def _interleave(self, classifier, **fkw):
        ccfg, params = classifier
        base = C.default_rules(ccfg, jnp.asarray([400, 401, 402, 403]))
        dead = C.default_rules(ccfg, jnp.asarray([500, 501, 502, 503]))
        # a t_cp epoch no host can meet: every install violates Eq. 18
        eng = _engine(classifier, rules=base, t_cp_s=1e-12, **fkw)
        ctl = _engine(classifier, rules=base, **fkw)

        rng = np.random.default_rng(3)
        for i in range(6):
            fids = rng.integers(0, 6, (12,))
            toks = rng.integers(0, 512, (12, 8)).astype(np.int32)
            a = eng.ingest(fids, toks)
            b = ctl.ingest(fids.copy(), toks.copy())
            for k in self.OUT_KEYS:
                np.testing.assert_array_equal(
                    a[k], b[k], err_msg=f"tick {i} {k}"
                )
            prev = eng.rules  # the AdaptiveLoop rollback capture
            rec = eng.swap_tables(ruleset=dead)
            assert not rec.churn_ok  # the install DID violate t_cp
            eng.swap_tables(ruleset=prev)  # reuse-after-donation bait
        # captured-rules buffers were never donated: per-flow state and
        # scores agree exactly after six failed-install/rollback cycles
        for f in sorted(int(x) for x in eng.table.slot_of):
            assert eng.flow_scores(f) == ctl.flow_scores(f), f

    def test_failing_install_rollback_interleaved_with_ingest(self, classifier):
        self._interleave(classifier)

    def test_rollback_interleaved_with_fused_ingest(self, classifier):
        # same audit against the fused single-launch path: _jit_fused
        # donates the same state argnums (2-6)
        self._interleave(classifier, fused=True)


@pytest.mark.slow
class TestTrafficScale:
    def test_10k_interleaved_flows_bounded_table(self, classifier):
        """Acceptance: ≥10k distinct flows stream through a 512-entry table;
        resident set and bytes stay bounded the whole time."""
        eng = _engine(classifier, capacity=512, lanes=128)
        sc = FlowScenario(kind="port-scan", pkt_len=8, packets_per_batch=512, seed=11)
        while eng.stats.flows_created < 10_000:
            b = sc.next_batch()
            eng.ingest(b["flow_ids"], b["tokens"])
            assert eng.resident_flows <= 512
        assert eng.stats.flows_created >= 10_000
        assert eng.resident_state_bytes() <= eng.state_budget_bytes
        assert eng.stats.flows_evicted_lru > 0
