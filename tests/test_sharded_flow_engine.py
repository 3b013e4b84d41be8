"""ShardedFlowEngine: deterministic routing, sharded ≡ single-device
bit-exact replay, aggregated eviction/churn stats, replicated table swaps,
per-shard budgets, and the sharded deploy path.

Multi-shard in-process tests need multiple devices — the CI ``multidevice``
lane provides 8 via ``XLA_FLAGS=--xla_force_host_platform_device_count=8``;
on a single-device host they skip and the subprocess test (slow tier)
covers the same equivalence under forced devices.
"""

import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.pipeline import FlowScenario, flow_shard
from repro.serve.deploy import DeploySpec
from repro.serve.flow_engine import FlowEngine, FlowEngineConfig
from repro.serve.sharded_flow_engine import ShardedFlowEngine
from repro.train import classifier as C

KEY = jax.random.PRNGKey(0)

needs_devices = lambda n: pytest.mark.skipif(  # noqa: E731
    jax.device_count() < n,
    reason=f"needs {n} devices (CI multidevice lane forces 8 on CPU)",
)


@pytest.fixture(scope="module")
def classifier(tiny_classifier_cfg):
    params, _ = C.init_classifier(tiny_classifier_cfg, KEY)
    return tiny_classifier_cfg, params


def _rules(ccfg, anomaly_tokens=(400, 401, 402, 403)):
    return C.default_rules(ccfg, jnp.asarray(list(anomaly_tokens)))


def _single(classifier, rules=None, **fkw):
    ccfg, params = classifier
    fkw.setdefault("capacity", 32)
    fkw.setdefault("lanes", 8)
    rules = rules if rules is not None else _rules(ccfg)
    return FlowEngine(ccfg, params, rules, FlowEngineConfig(**fkw))


def _sharded(classifier, num_shards, rules=None, **fkw):
    ccfg, params = classifier
    fkw.setdefault("capacity", 32)
    fkw.setdefault("lanes", 8)
    rules = rules if rules is not None else _rules(ccfg)
    return ShardedFlowEngine(
        ccfg, params, rules, FlowEngineConfig(**fkw),
        num_shards=num_shards,
    )


class TestRouting:
    def test_deterministic_and_in_range(self):
        fids = np.arange(512)
        owners = flow_shard(fids, 4)
        assert owners.min() >= 0 and owners.max() < 4
        np.testing.assert_array_equal(owners, flow_shard(fids, 4))

    def test_stable_across_batch_resizes(self):
        """A flow's owner depends only on (fid, num_shards) — never on the
        batch it arrived in."""
        fids = np.arange(100)
        whole = flow_shard(fids, 8)
        pieces = np.concatenate([flow_shard(fids[i : i + 7], 8)
                                 for i in range(0, 100, 7)])
        np.testing.assert_array_equal(whole, pieces)
        assert flow_shard([42], 8)[0] == whole[42]

    def test_roughly_balanced(self):
        counts = np.bincount(flow_shard(np.arange(4096), 4), minlength=4)
        assert counts.min() > 4096 / 4 * 0.8, counts

    def test_num_shards_one_routes_everything_to_zero(self):
        assert not flow_shard(np.arange(64), 1).any()


class TestShardedScenario:
    def test_shard_streams_union_to_single_stream(self):
        """The num_shards generators emit exactly the num_shards=1 packets,
        partitioned by owner, tokens bit-identical, per-shard order
        preserved."""
        kw = dict(kind="mix", pkt_len=8, packets_per_batch=64, seed=11)
        full = FlowScenario(**kw)
        parts = [FlowScenario(**kw, shard_id=s, num_shards=3) for s in range(3)]
        for _ in range(4):
            b = full.next_batch()
            owners = flow_shard(b["flow_ids"], 3)
            for s, part in enumerate(parts):
                bs = part.next_batch()
                keep = owners == s
                for key in b:
                    np.testing.assert_array_equal(
                        bs[key], b[key][keep], err_msg=f"shard {s} key {key}"
                    )

    def test_generators_stay_in_lockstep(self):
        """Filtering must not perturb generator state: flow populations and
        retirement counters match the unsharded run step for step."""
        kw = dict(kind="heavy-churn", pkt_len=8, packets_per_batch=64, seed=5)
        full = FlowScenario(**kw)
        part = FlowScenario(**kw, shard_id=1, num_shards=4)
        for _ in range(5):
            full.next_batch()
            part.next_batch()
            assert part.active_flows == full.active_flows
            assert part.flows_retired == full.flows_retired

    def test_bad_shard_id_rejected(self):
        with pytest.raises(ValueError, match="shard_id"):
            FlowScenario(shard_id=2, num_shards=2)


def _assert_replay_identical(classifier, num_shards, kind="rule-violating",
                             batches=3, **fkw):
    """Replay one FlowScenario through both engines; everything observable
    must be bit-identical (acceptance: sharded replay == single-device).

    Capacity is sized so neither engine evicts: under pressure the two
    legitimately pick different LRU victims (global vs shard-local), which
    is eviction policy, not replay math — covered separately below."""
    sc = FlowScenario(kind=kind, pkt_len=8, packets_per_batch=48, seed=3)
    rules = _rules(classifier[0], sc.anomaly_signature)
    fkw.setdefault("capacity", 256)
    single = _single(classifier, rules=rules, **fkw)
    sharded = _sharded(classifier, num_shards, rules=rules, **fkw)
    for _ in range(batches):
        b = sc.next_batch()
        o1 = single.ingest(b["flow_ids"], b["tokens"])
        o2 = sharded.ingest(b["flow_ids"], b["tokens"])
        for k in ("trust", "vetoed", "pred", "s_nn", "s_sym"):
            np.testing.assert_array_equal(o1[k], o2[k], err_msg=k)
    assert sorted(single.flow_ids()) == sorted(sharded.flow_ids())
    for fid in single.flow_ids():
        assert single.flow_scores(fid) == sharded.flow_scores(fid), fid
    s1, s2 = single.stats, sharded.stats
    assert s1.flows_evicted == s2.flows_evicted == 0  # precondition held
    assert (s1.packets, s1.ticks, s1.flows_created) == (
        s2.packets, s2.ticks, s2.flows_created)
    return single, sharded


class TestEquivalenceSingleDevice:
    """num_shards=1 exercises the full shard_map path on any host."""

    def test_one_shard_replay_bit_identical(self, classifier):
        _assert_replay_identical(classifier, num_shards=1)

    def test_one_shard_veto_decisions_match(self, classifier):
        single, sharded = _assert_replay_identical(
            classifier, num_shards=1, kind="rule-violating", batches=4)
        vet = [f for f in single.flow_ids() if single.flow_scores(f)["vetoed"]]
        assert vet, "rule-violating scenario must veto some flows"
        for f in vet:
            assert sharded.flow_scores(f)["vetoed"]


class TestEquivalenceMultiShard:
    @needs_devices(2)
    def test_two_shard_replay_bit_identical(self, classifier):
        _assert_replay_identical(classifier, num_shards=2)

    @needs_devices(4)
    def test_four_shard_replay_bit_identical(self, classifier):
        _assert_replay_identical(classifier, num_shards=4)

    @needs_devices(2)
    def test_swap_mid_stream_stays_identical(self, classifier):
        """Replicated installs: swap the same weight column into both
        engines mid-stream; scores stay bit-identical and the measured
        install is recorded."""
        ccfg, _ = classifier
        single = _single(classifier)
        sharded = _sharded(classifier, 2)
        sc = FlowScenario(kind="protocol-mix", pkt_len=8,
                          packets_per_batch=32, seed=9)
        b = sc.next_batch()
        single.ingest(b["flow_ids"], b["tokens"])
        sharded.ingest(b["flow_ids"], b["tokens"])
        w = np.asarray(_rules(ccfg).weights) * 2.0
        r1, r2 = single.swap_tables(weights=w), sharded.swap_tables(weights=w)
        assert r1.source == r2.source == "manual"
        assert sharded.swap_history == [r2] and r2.install_s >= 0
        b = sc.next_batch()
        o1 = single.ingest(b["flow_ids"], b["tokens"])
        o2 = sharded.ingest(b["flow_ids"], b["tokens"])
        for k in ("trust", "vetoed", "pred", "s_nn", "s_sym"):
            np.testing.assert_array_equal(o1[k], o2[k], err_msg=k)

    @needs_devices(2)
    def test_swap_shape_mismatch_rejected(self, classifier):
        sharded = _sharded(classifier, 2)
        with pytest.raises(ValueError, match="swap_tables"):
            sharded.swap_tables(weights=np.ones((3,), np.float32))


class TestShardedTableManagement:
    def test_lru_eviction_aggregates_per_shard(self, classifier):
        """Over-subscribe tiny per-shard tables: every fresh allocation is
        either still resident or was LRU-evicted, in aggregate and per
        shard (churn accounting correctness)."""
        eng = _sharded(classifier, 1, capacity=4, lanes=4)
        for start in (0, 100, 200):  # 16 distinct flows per wave
            fids = np.arange(start, start + 16)
            toks = np.zeros((16, 8), np.int32)
            eng.ingest(fids, toks)
        st = eng.stats
        assert st.flows_created == 48
        assert st.flows_evicted_lru == st.flows_created - eng.resident_flows
        assert eng.resident_flows == sum(t.resident for t in eng.tables)
        assert eng.resident_flows <= eng.aggregate_capacity
        for t in eng.tables:
            assert t.resident <= eng.fcfg.capacity

    def test_idle_eviction_aggregates(self, classifier):
        eng = _sharded(classifier, 1, capacity=16, lanes=4, idle_timeout=1)
        toks = np.zeros((4, 8), np.int32)
        eng.ingest(np.arange(4), toks)  # tick 1
        eng.ingest(np.arange(10, 14), toks)  # tick 2
        eng.ingest(np.arange(20, 24), toks)  # tick 3: flows 0..3 now stale
        assert eng.stats.flows_evicted_idle >= 4
        assert all(f >= 10 for f in eng.flow_ids())

    def test_reset_preserves_jitted_step(self, classifier):
        eng = _sharded(classifier, 1, capacity=8, lanes=4)
        toks = np.zeros((4, 8), np.int32)
        o1 = eng.ingest(np.arange(4), toks)
        eng.reset()
        assert eng.resident_flows == 0 and eng.stats.packets == 0
        o2 = eng.ingest(np.arange(4), toks)
        np.testing.assert_array_equal(o1["trust"], o2["trust"])

    def test_per_shard_budget_enforced_at_construction(self, classifier):
        with pytest.raises(ValueError, match="budget"):
            _sharded(classifier, 1, capacity=32, state_budget_bytes=1024)

    def test_mesh_without_data_axis_rejected(self, classifier):
        from repro.launch.mesh import _mesh

        ccfg, params = classifier
        with pytest.raises(ValueError, match="data"):
            ShardedFlowEngine(ccfg, params, _rules(ccfg),
                              FlowEngineConfig(capacity=8, lanes=4),
                              mesh=_mesh((1,), ("model",)))


class TestShardedDeploy:
    def test_program_deploy_records_per_shard_ledger_entry(self, classifier):
        from repro.compile import compile_program

        ccfg, params = classifier
        program = compile_program(ccfg, params, rules=_rules, backend="xla")
        eng = program.deploy(DeploySpec(
            engine="sharded", flow=FlowEngineConfig(capacity=16, lanes=8),
            num_shards=1,
        ))
        assert isinstance(eng, ShardedFlowEngine)
        assert eng.program is program and eng.backend == "xla"
        entries = [e for e in program.ledger.entries
                   if e.stage == "flow-table-sharding"]
        assert len(entries) == 1
        e = entries[0]
        assert e.ok and e.used == eng.shard_state_bytes()
        assert e.budget == eng.state_budget_bytes
        assert f"aggregate capacity {eng.aggregate_capacity}" in e.detail
        # re-deploys refresh rather than duplicate the placement entry
        program.deploy(DeploySpec(
            engine="sharded", flow=FlowEngineConfig(capacity=16, lanes=8),
            num_shards=1,
        ))
        assert len([e for e in program.ledger.entries
                    if e.stage == "flow-table-sharding"]) == 1

    def test_program_deploy_default_is_single_device(self, classifier):
        from repro.compile import compile_program

        ccfg, params = classifier
        program = compile_program(ccfg, params, rules=_rules, backend="xla")
        assert isinstance(
            program.deploy(DeploySpec(flow=FlowEngineConfig(
                capacity=16, lanes=8))), FlowEngine
        )


SUBPROCESS_EQUIVALENCE = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import numpy as np, jax, jax.numpy as jnp
    from repro.configs import smoke_config
    from repro.data.pipeline import FlowScenario
    from repro.serve.deploy import DeploySpec
    from repro.serve.flow_engine import FlowEngine, FlowEngineConfig
    from repro.serve.sharded_flow_engine import ShardedFlowEngine
    from repro.train import classifier as C

    arch = dataclasses.replace(
        smoke_config("chimera-dataplane"), n_layers=2, d_model=32, d_ff=64,
        n_heads=2, n_kv_heads=2, d_head=16, vocab_size=512)
    ccfg = C.ClassifierConfig(arch=arch, n_classes=8, marker_base=256)
    params, _ = C.init_classifier(ccfg, jax.random.PRNGKey(0))
    sig = FlowScenario(kind="rule-violating", seed=3).anomaly_signature
    rules = C.default_rules(ccfg, jnp.asarray(sig))
    # capacity sized so neither engine evicts (global vs shard-local LRU
    # pick different victims under pressure; replay math is what's under test)
    fcfg = FlowEngineConfig(capacity=256, lanes=8)

    single = FlowEngine(ccfg, params, rules, fcfg)
    for S in (2, 4):
        sharded = ShardedFlowEngine(ccfg, params, rules, fcfg, num_shards=S)
        single.reset()
        sc = FlowScenario(kind="rule-violating", pkt_len=8,
                          packets_per_batch=48, seed=3)
        for _ in range(3):
            b = sc.next_batch()
            o1 = single.ingest(b["flow_ids"], b["tokens"])
            o2 = sharded.ingest(b["flow_ids"], b["tokens"])
            for k in ("trust", "vetoed", "pred", "s_nn", "s_sym"):
                assert np.array_equal(o1[k], o2[k]), (S, k)
        for fid in single.flow_ids():
            assert single.flow_scores(fid) == sharded.flow_scores(fid), (S, fid)
        assert single.stats.flows_created == sharded.stats.flows_created
    print("OK")
    """
)


@pytest.mark.slow
def test_sharded_equivalence_subprocess_8_devices():
    """2- and 4-shard replay is bit-identical to single-device on a forced
    8-device host (covers the multi-shard path when the main process only
    sees one device)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-c", SUBPROCESS_EQUIVALENCE],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OK" in proc.stdout
