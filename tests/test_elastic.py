"""ElasticFlowService (DESIGN.md §17): live resharding bit-equivalence,
Eq. 18 rollback, checkpoint/restore, kill-a-shard recovery with bounded
replay, heartbeat liveness, and per-tenant admission control.

Multi-shard in-process tests need multiple devices — the CI ``multidevice``
lane provides 8 via ``XLA_FLAGS=--xla_force_host_platform_device_count=8``;
single-device hosts skip them and the slow-tier subprocess test covers the
reshard equivalence under forced devices.
"""

import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compile import compile_program
from repro.data.pipeline import FlowScenario
from repro.runtime.fault_tolerance import HeartbeatMonitor, plan_shard_recovery
from repro.serve.deploy import DeploySpec, ElasticConfig, TenantSpec
from repro.serve.elastic import (
    ElasticFlowService,
    concat_snapshots,
    install_flow_state,
    select_rows,
    snapshot_flow_state,
)
from repro.serve.flow_engine import FlowEngineConfig
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


# compile the hard rules against the signature the seed-3 scenario actually
# injects, so rule-violating flows trip real sticky vetoes in these tests
SCENARIO_SIG = tuple(
    int(t) for t in
    FlowScenario(kind="rule-violating", seed=3).anomaly_signature
)


def _program(classifier):
    ccfg, params = classifier
    return compile_program(
        ccfg, params,
        rules=lambda c: C.default_rules(c, jnp.asarray(SCENARIO_SIG)),
        backend="xla",
    )


def _service(classifier, *, num_shards=1, capacity=64, lanes=8, t_cp_s=60.0,
             ecfg=ElasticConfig(), program=None):
    program = program if program is not None else _program(classifier)
    svc = program.deploy(DeploySpec(
        engine="elastic", num_shards=num_shards,
        flow=FlowEngineConfig(capacity=capacity, lanes=lanes, t_cp_s=t_cp_s),
        elastic=ecfg,
    ))
    return svc


def _batches(n, *, kind="rule-violating", pkt_len=8, packets_per_batch=48,
             seed=3):
    sc = FlowScenario(kind=kind, pkt_len=pkt_len,
                      packets_per_batch=packets_per_batch, seed=seed)
    return [sc.next_batch() for _ in range(n)]


OUT_KEYS = ("trust", "vetoed", "pred", "s_nn", "s_sym")


def _assert_outputs_equal(a, b, context=""):
    for k in OUT_KEYS:
        np.testing.assert_array_equal(
            np.asarray(a[k]), np.asarray(b[k]), err_msg=f"{context}: {k}"
        )


def _all_scores(svc):
    return {fid: svc.flow_scores(fid) for fid in svc.flow_ids()}


# --------------------------------------------------------------------------
# snapshot / install primitives (single device)
# --------------------------------------------------------------------------

class TestSnapshotInstall:
    def test_snapshot_rows_keyed_by_fid_sorted(self, classifier):
        svc = _service(classifier)
        for b in _batches(3):
            svc.ingest(b["flow_ids"], b["tokens"])
        snap = snapshot_flow_state(svc.engine)
        assert len(snap["fids"]) == svc.resident_flows
        assert (np.diff(snap["fids"]) > 0).all()
        assert snap["positions"].shape == snap["fids"].shape

    def test_select_concat_roundtrip(self, classifier):
        svc = _service(classifier)
        for b in _batches(3):
            svc.ingest(b["flow_ids"], b["tokens"])
        snap = snapshot_flow_state(svc.engine)
        mask = snap["fids"] % 2 == 0
        evens, odds = select_rows(snap, mask), select_rows(snap, ~mask)
        merged = concat_snapshots(evens, odds)
        assert sorted(merged["fids"].tolist()) == snap["fids"].tolist()
        with pytest.raises(ValueError, match="overlapping"):
            concat_snapshots(evens, evens)

    def test_install_over_capacity_raises_eq11(self, classifier):
        svc = _service(classifier, capacity=64)
        for b in _batches(4):
            svc.ingest(b["flow_ids"], b["tokens"])
        assert svc.resident_flows > 4
        snap = snapshot_flow_state(svc.engine)
        tiny = _program(classifier).deploy(DeploySpec(
            engine="sharded", num_shards=1,
            flow=FlowEngineConfig(capacity=4, lanes=8),
        ))
        with pytest.raises(ValueError, match="Eq. 11"):
            install_flow_state(tiny, snap, tick=svc.engine._tick)

    def test_install_roundtrip_preserves_scores(self, classifier):
        """snapshot → install onto a FRESH same-shape engine reproduces
        every per-flow score bit-exactly, and replays the same future
        bit-exactly (capacity to spare: the install assigns fresh slots, so
        an LRU tie under pressure could pick another victim).  The
        snapshot's cache rows are ``(n, layers, ...)`` whatever the table's
        own axis order."""
        svc = _service(classifier, capacity=256)
        batches = _batches(6)
        for b in batches[:4]:
            svc.ingest(b["flow_ids"], b["tokens"])
        want = _all_scores(svc)
        snap = snapshot_flow_state(svc.engine)
        n, layers = len(snap["fids"]), classifier[0].arch.n_groups
        for rows, leaf in zip(jax.tree_util.tree_leaves(snap["caches"]),
                              jax.tree_util.tree_leaves(svc.engine.caches)):
            assert rows.shape == (n, layers) + leaf.shape[3:]
        fresh = _program(classifier).deploy(DeploySpec(
            engine="sharded", num_shards=1,
            flow=FlowEngineConfig(capacity=256, lanes=8),
        ))
        install_flow_state(fresh, snap, tick=svc.engine._tick)
        assert sorted(fresh.flow_ids()) == sorted(want)
        for fid, scores in want.items():
            assert fresh.flow_scores(fid) == scores, fid
        for i, b in enumerate(batches[4:]):
            _assert_outputs_equal(
                svc.ingest(b["flow_ids"], b["tokens"]),
                fresh.ingest(b["flow_ids"], b["tokens"]),
                context=f"post-install batch {i}",
            )
        assert svc.engine.stats.flows_evicted == fresh.stats.flows_evicted == 0


# --------------------------------------------------------------------------
# reshard records + quiesce (single device)
# --------------------------------------------------------------------------

    def test_install_takes_cache_rows_in_the_logical_shape(self, classifier):
        """A snapshot whose cache rows are in the model's logical per-slot
        shape (``M.init_caches``, as a table without the lane-dense fold
        holds them) installs to the same table: the fold keeps the elements
        and their order, so scores and the replayed future are bit-exact."""
        from repro.models import model as M

        svc = _service(classifier, capacity=256)
        batches = _batches(6)
        for b in batches[:4]:
            svc.ingest(b["flow_ids"], b["tokens"])
        want = _all_scores(svc)
        snap = snapshot_flow_state(svc.engine)
        model = jax.eval_shape(lambda: M.init_caches(
            classifier[0].arch, 1, svc.engine.fcfg.max_flow_tokens,
            dtype=jnp.float32))
        logical = jax.tree_util.tree_map(
            lambda rows, m: rows.reshape((len(rows),) + m.shape[:1]
                                         + m.shape[2:]),
            snap["caches"], model)
        assert any(a.shape != b.shape for a, b in zip(
            jax.tree_util.tree_leaves(logical),
            jax.tree_util.tree_leaves(snap["caches"])))
        fresh = _program(classifier).deploy(DeploySpec(
            engine="sharded", num_shards=1,
            flow=FlowEngineConfig(capacity=256, lanes=8),
        ))
        install_flow_state(fresh, dict(snap, caches=logical),
                           tick=svc.engine._tick)
        for a, b in zip(jax.tree_util.tree_leaves(fresh.caches),
                        jax.tree_util.tree_leaves(svc.engine.caches)):
            assert a.shape == b.shape
        for fid, scores in want.items():
            assert fresh.flow_scores(fid) == scores, fid
        for i, b in enumerate(batches[4:]):
            _assert_outputs_equal(
                svc.ingest(b["flow_ids"], b["tokens"]),
                fresh.ingest(b["flow_ids"], b["tokens"]),
                context=f"post-install batch {i}",
            )


class TestReshardControl:
    def test_same_count_reshard_is_noop(self, classifier):
        svc = _service(classifier)
        b = _batches(1)[0]
        svc.ingest(b["flow_ids"], b["tokens"])
        before = svc.engine
        rec = svc.reshard(1)
        assert svc.engine is before
        assert rec.reason.endswith("(no-op)") and rec.churn_ok
        assert rec.migrated_flows == 0 and not rec.rolled_back
        assert svc.reshard_history[-1] is rec
        d = rec.as_dict()
        assert d["old_shards"] == d["new_shards"] == 1

    def test_ingest_during_quiesce_raises(self, classifier):
        svc = _service(classifier)
        b = _batches(1)[0]
        svc._resharding = True
        try:
            with pytest.raises(RuntimeError, match="quiesce"):
                svc.ingest(b["flow_ids"], b["tokens"])
        finally:
            svc._resharding = False
        out = svc.ingest(b["flow_ids"], b["tokens"])  # unfrozen again
        assert out["admitted"].all()

    def test_entry_points_namespaced(self, classifier):
        svc = _service(classifier)
        assert set(svc.jit_entry_points()) == {"shards1.step"}


# --------------------------------------------------------------------------
# checkpoint / restore (single device, real Checkpointer directory)
# --------------------------------------------------------------------------

class TestCheckpointRestore:
    def test_roundtrip_and_divergent_future_bit_exact(self, classifier,
                                                      tmp_path):
        svc = _service(classifier, ecfg=ElasticConfig(
            checkpoint_dir=str(tmp_path)
        ))
        batches = _batches(6)
        for b in batches[:4]:
            svc.ingest(b["flow_ids"], b["tokens"])
        want_scores = _all_scores(svc)
        step = svc.checkpoint()
        tail_a = [svc.ingest(b["flow_ids"], b["tokens"]) for b in batches[4:]]

        got = svc.restore_checkpoint(step)
        assert got == step
        assert _all_scores(svc) == want_scores
        # the restored service replays the SAME future bit-exactly
        tail_b = [svc.ingest(b["flow_ids"], b["tokens"]) for b in batches[4:]]
        for i, (a, b) in enumerate(zip(tail_a, tail_b)):
            _assert_outputs_equal(a, b, context=f"post-restore batch {i}")

    def test_restore_composes_with_swap_tables(self, classifier, tmp_path):
        svc = _service(classifier, ecfg=ElasticConfig(
            checkpoint_dir=str(tmp_path)
        ))
        batches = _batches(4)
        for b in batches[:3]:
            svc.ingest(b["flow_ids"], b["tokens"])
        step = svc.checkpoint()
        svc.restore_checkpoint(step)
        # rules are live state, not checkpoint state: a swap after restore
        # lands on the restored topology and ingest keeps serving
        ccfg, _ = classifier
        rec = svc.swap_tables(
            ruleset=C.default_rules(ccfg, jnp.asarray([410, 411]))
        )
        assert svc.swap_history[-1] is rec
        out = svc.ingest(batches[3]["flow_ids"], batches[3]["tokens"])
        assert len(out["trust"]) == len(batches[3]["flow_ids"])

    def test_restore_without_dir_raises(self, classifier):
        svc = _service(classifier)
        with pytest.raises(RuntimeError, match="checkpoint_dir"):
            svc.restore_checkpoint()

    def test_checkpoint_every_autosaves(self, classifier, tmp_path):
        svc = _service(classifier, ecfg=ElasticConfig(
            checkpoint_dir=str(tmp_path), checkpoint_every=2
        ))
        for b in _batches(4):
            svc.ingest(b["flow_ids"], b["tokens"])
        assert svc._ckpt_seq == 2  # ticks 2 and 4
        assert svc._last_ckpt is not None


# --------------------------------------------------------------------------
# heartbeats + recovery planning (pure host logic)
# --------------------------------------------------------------------------

class TestLiveness:
    def test_heartbeat_timeout_detection(self):
        mon = HeartbeatMonitor(timeout_s=10.0)
        t0 = time.monotonic()
        mon.beat(0, step=1, t=t0)
        mon.beat(1, step=1, t=t0 + 8.0)
        assert mon.dead_workers(now=t0 + 9.0) == []
        assert mon.dead_workers(now=t0 + 11.0) == [0]
        assert mon.dead_workers(now=t0 + 30.0) == [0, 1]

    def test_service_merges_killed_and_lapsed(self, classifier):
        svc = _service(classifier, ecfg=ElasticConfig(
            heartbeat_timeout_s=1e-9
        ))
        b = _batches(1)[0]
        svc.ingest(b["flow_ids"], b["tokens"])
        time.sleep(0.01)
        assert svc.dead_shards() == [0]

    def test_plan_shard_recovery(self):
        plan = plan_shard_recovery(4, [2], checkpoint_tick=7)
        assert plan.valid
        assert plan.new_num_shards == 3
        assert plan.surviving == (0, 1, 3)
        assert plan.replay_from_tick == 7
        assert not plan_shard_recovery(2, [0, 1], checkpoint_tick=0).valid

    def test_recover_without_checkpoint_raises(self, classifier):
        svc = _service(classifier)
        b = _batches(1)[0]
        svc.ingest(b["flow_ids"], b["tokens"])
        svc.kill_shard(0)
        with pytest.raises(RuntimeError, match="no checkpoint"):
            svc.recover()

    def test_kill_shard_validates_index(self, classifier):
        svc = _service(classifier)
        with pytest.raises(ValueError, match="no shard"):
            svc.kill_shard(3)


# --------------------------------------------------------------------------
# admission control (single device)
# --------------------------------------------------------------------------

class TestAdmission:
    def _svc(self, classifier):
        return _service(classifier, capacity=8, ecfg=ElasticConfig(tenants=(
            TenantSpec("bronze", priority=0, share=0.5),
            TenantSpec("gold", priority=2, share=1.0),
        )))

    @staticmethod
    def _pkts(fids):
        fids = np.asarray(fids, np.int64)
        return fids, np.full((len(fids), 8), 300, np.int32)

    def test_share_budget_caps_admission(self, classifier):
        svc = self._svc(classifier)
        assert svc.tenant_budget_flows("bronze") == 4  # 0.5 × 8 aggregate
        fids, toks = self._pkts(np.arange(6))
        out = svc.ingest(fids, toks, tenant="bronze")
        assert out["admitted"].sum() == 4
        assert svc.tenant_resident("bronze") == 4
        # shed packets keep alignment with null outputs
        shed = ~out["admitted"]
        assert (out["trust"][shed] == 0).all()
        assert (out["pred"][shed] == -1).all()
        assert not out["vetoed"][shed].any()

    def test_pressure_sheds_lowest_priority_first(self, classifier):
        svc = self._svc(classifier)
        bf, bt = self._pkts(np.arange(6))
        svc.ingest(bf, bt, tenant="bronze")
        gf, gt = self._pkts(np.arange(100, 108))
        out = svc.ingest(gf, gt, tenant="gold")
        # gold's full-share budget wins the whole table: bronze is evicted
        assert out["admitted"].all()
        assert svc.tenant_resident("gold") == 8
        assert svc.tenant_resident("bronze") == 0
        assert svc.shed_flows["bronze"] >= 4
        # gold past its own budget is shed too (no higher tier to raid)
        extra = self._pkts(np.arange(200, 203))
        out2 = svc.ingest(*extra, tenant="gold")
        assert not out2["admitted"].any()
        assert svc.shed_flows["gold"] == 3

    def test_resident_flows_always_admitted(self, classifier):
        svc = self._svc(classifier)
        fids, toks = self._pkts(np.arange(4))
        assert svc.ingest(fids, toks, tenant="bronze")["admitted"].all()
        # same flows again, even at budget: they already hold slots
        assert svc.ingest(fids, toks, tenant="bronze")["admitted"].all()
        assert svc.shed_packets.get("bronze", 0) == 0

    def test_unknown_tenant_lists_registered(self, classifier):
        svc = self._svc(classifier)
        fids, toks = self._pkts([1])
        with pytest.raises(KeyError, match="silver"):
            svc.ingest(fids, toks, tenant="silver")

    def test_per_packet_tenant_list(self, classifier):
        svc = self._svc(classifier)
        fids, toks = self._pkts([1, 2])
        out = svc.ingest(fids, toks, tenant=["bronze", "gold"])
        assert out["admitted"].all()
        assert svc.tenant_resident("bronze") == 1
        assert svc.tenant_resident("gold") == 1
        with pytest.raises(ValueError, match="per-packet"):
            svc.ingest(fids, toks, tenant=["bronze"])

    def test_ledger_reflects_admission(self, classifier):
        svc = self._svc(classifier)
        fids, toks = self._pkts(np.arange(6))
        svc.ingest(fids, toks, tenant="bronze")
        svc._record_admission_entries()
        entries = {
            e.resource: e for e in svc.program.ledger.entries
            if e.stage == "admission-control"
        }
        bronze = entries["tenant[bronze]-flows"]
        assert bronze.used == 4 and bronze.budget == 4
        assert "shed 2 flow(s)" in bronze.detail


# --------------------------------------------------------------------------
# live resharding (multidevice lane)
# --------------------------------------------------------------------------

@needs_devices(4)
class TestReshardEquivalence:
    def test_reshard_2_4_2_bit_identical_to_unsharded(self, classifier):
        """The tentpole correctness bar: a replay through reshard(2→4→2) is
        bit-identical to an unsharded replay in the no-eviction regime —
        scores, sticky veto bits, and Eq. 36 S=1.0 pinning included."""
        program = _program(classifier)
        svc = _service(classifier, num_shards=2, capacity=256,
                       program=program)
        ref = _program(classifier).deploy(DeploySpec(
            flow=FlowEngineConfig(capacity=256, lanes=8)
        ))
        batches = _batches(12)
        plan = {3: 4, 7: 2}
        for i, b in enumerate(batches):
            if i in plan:
                rec = svc.reshard(plan[i])
                assert not rec.rolled_back and rec.churn_ok, rec
                assert rec.install_s > 0.0 and rec.t_cp_s == 60.0
                assert svc.num_shards == plan[i]
            got = svc.ingest(b["flow_ids"], b["tokens"])
            want = ref.ingest(b["flow_ids"], b["tokens"])
            _assert_outputs_equal(want, got, context=f"batch {i}")
        ref_scores = {fid: ref.flow_scores(fid) for fid in ref.flow_ids()}
        assert _all_scores(svc) == ref_scores
        # vetoed flows stay pinned to S=1.0 across topologies (Eq. 36:
        # cascade fusion forces the fused score on a hard hit)
        pinned = [f for f, s in ref_scores.items() if s["vetoed"]]
        assert pinned, "scenario produced no hard-vetoed flows"
        assert all(ref_scores[f]["trust"] == 1.0 for f in pinned)

    def test_reshard_refreshes_single_ledger_entry(self, classifier):
        program = _program(classifier)
        svc = _service(classifier, num_shards=2, program=program)
        for b in _batches(2):
            svc.ingest(b["flow_ids"], b["tokens"])
        svc.reshard(4)
        entries = [e for e in program.ledger.entries
                   if e.stage == "flow-table-sharding"]
        assert len(entries) == 1
        assert "4 shard(s)" in entries[0].detail

    def test_reshard_back_never_retraces(self, classifier):
        """keep_topologies caches the per-shard-count jitted step: a second
        2→4→2 cycle runs entirely on warm traces."""
        from repro.analysis.retrace_sentry import RetraceSentry

        svc = _service(classifier, num_shards=2)
        batches = _batches(8)

        def cycle(bs):
            svc.ingest(bs[0]["flow_ids"], bs[0]["tokens"])
            svc.reshard(4)
            svc.ingest(bs[1]["flow_ids"], bs[1]["tokens"])
            svc.reshard(2)
            svc.ingest(bs[2]["flow_ids"], bs[2]["tokens"])
            svc.ingest(bs[3]["flow_ids"], bs[3]["tokens"])

        cycle(batches[:4])  # warmup traces both topologies
        sentry = RetraceSentry.for_engine(svc)
        assert set(sentry.counts()) == {"shards2.step", "shards4.step"}
        with sentry.expect_no_retrace():
            cycle(batches[4:])

    def test_t_cp_violation_rolls_back(self, classifier):
        svc = _service(classifier, num_shards=2, t_cp_s=1e-12)
        for b in _batches(3):
            svc.ingest(b["flow_ids"], b["tokens"])
        want = _all_scores(svc)
        rec = svc.reshard(4)
        assert rec.rolled_back and not rec.churn_ok
        assert "rolled back" in rec.error
        # old topology untouched and still serving
        assert svc.num_shards == 2
        assert _all_scores(svc) == want
        b = _batches(4)[-1]
        assert len(svc.ingest(b["flow_ids"], b["tokens"])["trust"]) \
            == len(b["flow_ids"])


# --------------------------------------------------------------------------
# kill-a-shard chaos (multidevice lane)
# --------------------------------------------------------------------------

@needs_devices(4)
class TestChaosRecovery:
    def test_kill_and_recover_bit_exact(self, classifier, tmp_path):
        """Checkpoint → lose a shard → recover: survivors reshard live,
        lost flows restore from the checkpoint, the bounded replay window
        re-ingests their post-checkpoint packets — final scores and every
        sticky hard-veto bit match a never-killed replay exactly."""
        ecfg = ElasticConfig(checkpoint_dir=str(tmp_path), replay_window=64)
        svc = _service(classifier, num_shards=4, capacity=256, ecfg=ecfg)
        ref = _service(classifier, num_shards=4, capacity=256)
        batches = _batches(10)
        for b in batches[:5]:
            svc.ingest(b["flow_ids"], b["tokens"])
            ref.ingest(b["flow_ids"], b["tokens"])
        svc.checkpoint()
        for b in batches[5:8]:
            svc.ingest(b["flow_ids"], b["tokens"])
            ref.ingest(b["flow_ids"], b["tokens"])

        lost = svc.kill_shard(2)
        assert lost and svc.dead_shards() == [2]
        rec = svc.recover()
        assert rec.reason == "recovery"
        assert rec.new_shards == 3 and svc.num_shards == 3
        assert rec.failed_shards == (2,)
        # flows spawned after the checkpoint are rebuilt purely from replay,
        # so restored (checkpoint) rows may undercount the lost set
        assert 0 < rec.restored_flows <= len(lost)
        assert rec.replayed_packets > 0
        assert svc.dead_shards() == []

        for b in batches[8:]:
            svc.ingest(b["flow_ids"], b["tokens"])
            ref.ingest(b["flow_ids"], b["tokens"])
        ref_scores = _all_scores(ref)
        got_scores = _all_scores(svc)
        assert got_scores == ref_scores
        # zero hard-veto flips: the sticky bits survived the shard loss
        assert {f for f, s in got_scores.items() if s["vetoed"]} \
            == {f for f, s in ref_scores.items() if s["vetoed"]}

    def test_replay_window_gap_refuses_then_allows_partial(self, classifier,
                                                           tmp_path):
        ecfg = ElasticConfig(checkpoint_dir=str(tmp_path), replay_window=2)
        svc = _service(classifier, num_shards=2, capacity=256, ecfg=ecfg)
        batches = _batches(8)
        for b in batches[:2]:
            svc.ingest(b["flow_ids"], b["tokens"])
        svc.checkpoint()
        for b in batches[2:8]:  # 6 batches > 2-deep replay buffer
            svc.ingest(b["flow_ids"], b["tokens"])
        svc.kill_shard(1)
        with pytest.raises(RuntimeError, match="replay window"):
            svc.recover()
        assert svc.num_shards == 2  # nothing committed
        rec = svc.recover(allow_partial=True)
        assert rec.new_shards == 1 and svc.num_shards == 1
        assert rec.replayed_packets >= 0


# --------------------------------------------------------------------------
# subprocess variant: full 8-device reshard equivalence on any host (slow)
# --------------------------------------------------------------------------

ELASTIC_SUBPROCESS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=8 "
        + os.environ.get("XLA_FLAGS", "")
    )
    import dataclasses
    import jax, numpy as np, jax.numpy as jnp
    assert jax.device_count() == 8, jax.device_count()
    from repro.compile import compile_program
    from repro.configs import smoke_config
    from repro.data.pipeline import FlowScenario
    from repro.serve.deploy import DeploySpec
    from repro.serve.flow_engine import FlowEngineConfig
    from repro.train import classifier as C
    from repro.train.classifier import ClassifierConfig

    arch = dataclasses.replace(
        smoke_config("chimera-dataplane"),
        n_layers=2, d_model=32, d_ff=64, n_heads=2, n_kv_heads=2, d_head=16,
        vocab_size=512,
    )
    ccfg = ClassifierConfig(arch=arch, n_classes=8, marker_base=256)
    params, _ = C.init_classifier(ccfg, jax.random.PRNGKey(0))
    sig = FlowScenario(kind="rule-violating", seed=3).anomaly_signature
    rules = lambda c: C.default_rules(c, jnp.asarray(sig))
    fcfg = FlowEngineConfig(capacity=256, lanes=8, t_cp_s=60.0)

    svc = compile_program(ccfg, params, rules=rules, backend="xla").deploy(
        DeploySpec(engine="elastic", num_shards=2, flow=fcfg))
    ref = compile_program(ccfg, params, rules=rules, backend="xla").deploy(
        DeploySpec(flow=FlowEngineConfig(capacity=256, lanes=8)))

    sc = FlowScenario(kind="rule-violating", pkt_len=8,
                      packets_per_batch=48, seed=3)
    plan = {3: 8, 7: 2}
    for i in range(10):
        b = sc.next_batch()
        if i in plan:
            rec = svc.reshard(plan[i])
            assert rec.churn_ok and not rec.rolled_back, rec.as_dict()
        got = svc.ingest(b["flow_ids"], b["tokens"])
        want = ref.ingest(b["flow_ids"], b["tokens"])
        for k in ("trust", "vetoed", "pred", "s_nn", "s_sym"):
            np.testing.assert_array_equal(
                np.asarray(want[k]), np.asarray(got[k]), err_msg=f"{i}:{k}")
    for fid in ref.flow_ids():
        assert svc.flow_scores(fid) == ref.flow_scores(fid), fid
    print("ELASTIC_EQUIVALENCE_OK", svc.num_shards)
""")


@pytest.mark.slow
def test_elastic_reshard_equivalence_subprocess(classifier):
    env = dict(os.environ)
    env["PYTHONPATH"] = (
        "src" + os.pathsep + env["PYTHONPATH"]
        if env.get("PYTHONPATH") else "src"
    )
    proc = subprocess.run(
        [sys.executable, "-c", ELASTIC_SUBPROCESS],
        capture_output=True, text=True, timeout=900, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "ELASTIC_EQUIVALENCE_OK 2" in proc.stdout
