"""Float<->int differential conformance tier (DESIGN.md §14).

The integer lowering's contract has three layers, each tested here:

* **Structural**: the `int-emulation` score path contains zero float ops —
  asserted by a recursive jaxpr dtype scan, not by inspection.  Trust
  *decisions* (hard-veto bits, S = 1.0 pinning, class argmax) are
  bit-identical to the float engines because the veto is the same uint32
  ternary match and the sigmoid LUT is clamped below ``one_q``.
* **Numeric**: float<->int *score* divergence stays inside the Thm A.3
  composed bound that ``lower_scores`` records in the ledger.
* **Pinned**: the canonical int score history (quantized trust, argmax,
  veto bits) is frozen by a golden fixture — regenerate with
  ``REGEN_GOLDEN=1 pytest tests/test_int_conformance.py -k golden``.

Replays cover one FlowScenario and one DriftScenario stream through float
and int engines in the fast lane; the full 3-way DriftScenario sweep
(reference / pallas-interpret / int-emulation) is slow-tier.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compile import (
    BudgetError,
    IntLoweringConfig,
    ResourceLedger,
    assert_integer_jaxpr,
    compile_program,
    lower_scores,
)
from repro.compile.int_lowering import (
    STAGE,
    dequantize_scores,
    float_ops_in_jaxpr,
    requantize_rule_weights,
    score_jaxpr,
)
from repro.data.pipeline import DriftPhase, DriftScenario, FlowScenario
from repro.kernels import dispatch
from repro.serve.deploy import DeploySpec
from repro.serve.flow_engine import FlowEngineConfig
from repro.train import classifier as C

pytestmark = pytest.mark.conformance

KEY = jax.random.PRNGKey(0)
GOLDEN = os.path.join(
    os.path.dirname(__file__), "fixtures", "golden_int_score_history.json"
)
N_BATCHES = 12  # "mix" cycles its kinds; hard vetoes first fire ~batch 10
# decision outputs that must be bit-identical across float and int engines;
# trust/s_nn/s_sym are score outputs, bounded but not bit-equal
DECISION_KEYS = ("vetoed", "pred", "sig")

DRIFT_PHASES = (
    DriftPhase(kind="protocol-mix", batches=3, anomaly_rate=0.3),
    DriftPhase(kind="rule-violating", batches=4, anomaly_rate=0.6,
               sig_rotation=1),
    DriftPhase(kind="heavy-churn", batches=3, anomaly_rate=0.3,
               sig_rotation=1),
)


def flow_scenario():
    return FlowScenario(kind="mix", vocab_size=512, pkt_len=8,
                        packets_per_batch=48, seed=11)


def drift_scenario():
    return DriftScenario(phases=DRIFT_PHASES, pkt_len=8,
                         packets_per_batch=32, seed=11)


@pytest.fixture(scope="module")
def classifier(tiny_classifier_cfg):
    params, _ = C.init_classifier(tiny_classifier_cfg, KEY)
    return tiny_classifier_cfg, params


def build_engine(classifier, backend, capacity=512):
    ccfg, params = classifier
    sc = flow_scenario()
    program = compile_program(
        ccfg, params,
        rules=lambda c: C.default_rules(c, jnp.asarray(sc.anomaly_signature)),
        backend=backend,
    )
    return program.deploy(
        DeploySpec(flow=FlowEngineConfig(capacity=capacity, lanes=16))
    )


def replay(engine, scenario, batches=N_BATCHES):
    outs = []
    for _ in range(batches):
        b = scenario.next_batch()
        outs.append(engine.ingest(b["flow_ids"], b["tokens"]))
    assert engine.stats.flows_evicted == 0  # replay precondition
    return outs


@pytest.fixture(scope="module")
def lowered(classifier):
    ccfg, params = classifier
    rules = C.default_rules(
        ccfg, jnp.asarray(flow_scenario().anomaly_signature)
    )
    plan, tables, entries = lower_scores(ccfg, params, rules)
    return plan, tables, entries, rules


@pytest.fixture(scope="module")
def int_replay(classifier):
    eng = build_engine(classifier, "int-emulation")
    return eng, replay(eng, flow_scenario())


@pytest.fixture(scope="module")
def float_replay(classifier):
    eng = build_engine(classifier, "xla")
    return eng, replay(eng, flow_scenario())


def assert_decisions_identical(float_outs, int_outs, plan):
    """Decision equality + bounded score divergence, batch by batch."""
    assert len(float_outs) == len(int_outs)
    div = 0.0
    for i, (f, g) in enumerate(zip(float_outs, int_outs)):
        for k in DECISION_KEYS:
            np.testing.assert_array_equal(f[k], g[k], err_msg=f"batch {i} {k}")
        # S = 1.0 pinning is structural on both sides: exactly the vetoed
        # packets score 1.0, everything else strictly below
        np.testing.assert_array_equal(f["trust"] == 1.0, f["vetoed"])
        np.testing.assert_array_equal(g["trust"] == 1.0, g["vetoed"])
        div = max(div, float(np.max(np.abs(f["trust"] - g["trust"]))))
    assert div <= plan.divergence, (div, plan.divergence)
    return div


# ==========================================================================
# structural: the lowered score path is integer-only
# ==========================================================================

class TestIntegerJaxpr:
    def test_score_path_has_zero_float_ops(self, lowered):
        plan, tables, _, rules = lowered
        assert_integer_jaxpr(plan, tables, rules)
        jx = score_jaxpr(plan, tables, rules, batch=4,
                         d_model=int(tables["cls_w"].shape[0]))
        assert float_ops_in_jaxpr(jx) == []

    def test_audit_detects_float_ops(self):
        """The dtype scan is not vacuous: a float op anywhere — including
        nested under jit/scan — is flagged."""
        jx = jax.make_jaxpr(
            lambda x: (x.astype(jnp.float32) * 0.5).astype(jnp.int32)
        )(jax.ShapeDtypeStruct((4,), jnp.int32))
        assert float_ops_in_jaxpr(jx)

        def nested(x):
            def body(c, t):
                return c, jnp.sin(t.astype(jnp.float32))
            return jax.lax.scan(body, 0, x)[1]

        jx = jax.make_jaxpr(nested)(jax.ShapeDtypeStruct((4,), jnp.int32))
        assert float_ops_in_jaxpr(jx)

    def test_engine_score_backend_is_registered(self, lowered):
        """The engine's int score step IS the registry's int-emulation
        flow_score impl (one audited implementation, not a private copy)."""
        plan, tables, _, rules = lowered
        impl = dispatch.resolve("flow_score", "int-emulation")
        hs = jnp.ones((2, tables["cls_w"].shape[0]), jnp.int32)
        cnt = jnp.ones((2,), jnp.int32)
        sg = jnp.zeros((2, rules.values.shape[1]), jnp.uint32)
        st = jnp.zeros((2,), bool)
        out, _ = impl(plan, tables, rules, hs, cnt, sg, st)
        for k in ("class_logits", "s_nn_q", "s_sym_q", "trust_q"):
            assert out[k].dtype == jnp.int32, k


# ==========================================================================
# the lowering pass: derivation, ledger audit, BudgetError
# ==========================================================================

class TestLoweringAudit:
    def test_ledger_records_every_stage_width(self, classifier):
        ccfg, params = classifier
        sc = flow_scenario()
        program = compile_program(
            ccfg, params,
            rules=lambda c: C.default_rules(c, jnp.asarray(sc.anomaly_signature)),
            backend="int-emulation",
        )
        entries = [e for e in program.ledger.entries if e.stage == STAGE]
        got = {e.resource for e in entries}
        assert got == {
            "feature-frac-bits", "feature-acc-bits", "overflow-horizon",
            "class-matmul-bits", "anom-matmul-bits", "sym-acc-bits",
            "fusion-preact-bits", "trust-divergence",
        }
        assert all(e.ok for e in entries)
        for e in entries:
            if e.resource.endswith("-bits") and e.resource != "feature-frac-bits":
                assert e.budget == 32

    def test_float_backend_records_no_lowering(self, classifier):
        ccfg, params = classifier
        program = compile_program(ccfg, params, backend="xla")
        assert not any(e.stage == STAGE for e in program.ledger.entries)

    def test_overwide_program_raises_budget_error(self, classifier):
        """16-bit weights with a 12-bit feature-LSB floor cannot keep the
        d=32 MAC inside int32: the compile pass refuses to lower it."""
        ccfg, params = classifier
        bad = IntLoweringConfig(weight_bits=16, min_feature_frac=12)
        with pytest.raises(BudgetError, match=STAGE):
            compile_program(ccfg, params, backend="int-emulation", int_cfg=bad)

    def test_overwide_deploy_raises_budget_error(self, lowered):
        """The same audit trips at deploy time from raw entries."""
        plan, tables, entries, rules = lowered
        ledger = ResourceLedger()
        ledger.extend(entries)
        ledger.raise_if_over()  # the canonical lowering fits

    def test_divergence_bound_within_budget(self, lowered):
        plan, _, entries, _ = lowered
        (e,) = [x for x in entries if x.resource == "trust-divergence"]
        assert e.used == plan.divergence
        assert plan.divergence <= IntLoweringConfig().max_divergence

    def test_lowering_is_deterministic(self, classifier, lowered):
        """Deploy sites re-derive the plan instead of serializing it; the
        derivation must therefore be a pure function of its inputs."""
        plan, tables, _, rules = lowered
        ccfg, params = classifier
        plan2, tables2, _ = lower_scores(ccfg, params, rules)
        assert plan2 == plan
        for k in tables:
            np.testing.assert_array_equal(
                np.asarray(tables[k]), np.asarray(tables2[k]), err_msg=k
            )

    def test_one_q_dequantizes_to_exactly_one(self, lowered):
        plan, tables, _, _ = lowered
        assert plan.one_q == 1 << plan.trust_frac
        assert float(plan.one_q * 2.0 ** -plan.trust_frac) == 1.0
        # LUT clamp: no soft score can reach the pinned value
        assert int(np.max(np.asarray(tables["lut"]))) <= plan.one_q - 1
        assert int(np.min(np.asarray(tables["lut"]))) >= 0


# ==========================================================================
# differential replay: FlowScenario
# ==========================================================================

class TestFlowScenarioConformance:
    def test_decisions_bit_identical_scores_bounded(self, float_replay,
                                                    int_replay):
        feng, fouts = float_replay
        ieng, iouts = int_replay
        div = assert_decisions_identical(fouts, iouts, ieng._int_plan)
        assert div > 0.0  # the engines genuinely differ below decision level

    def test_replay_exercises_both_branches(self, int_replay):
        """The stream must cover vetoed AND clean packets, or decision
        equality is vacuous."""
        _, iouts = int_replay
        veto = np.concatenate([o["vetoed"] for o in iouts])
        assert veto.any() and not veto.all()

    def test_flow_scores_read_path_conformant(self, float_replay, int_replay):
        feng, _ = float_replay
        ieng, _ = int_replay
        plan = ieng._int_plan
        common = set(feng.flow_ids()) & set(ieng.flow_ids())
        assert common
        for fid in sorted(common)[:8]:
            sf, si = feng.flow_scores(fid), ieng.flow_scores(fid)
            assert sf["pred"] == si["pred"], fid
            assert sf["vetoed"] == si["vetoed"], fid
            assert (sf["trust"] == 1.0) == (si["trust"] == 1.0), fid
            assert abs(sf["trust"] - si["trust"]) <= plan.divergence, fid

    def test_swap_tables_requantizes_and_stays_conformant(self, classifier):
        """A weight swap re-lowers the HL-MRF column at the installed LSB;
        post-swap decisions still agree with a float engine given the same
        swap."""
        feng = build_engine(classifier, "xla")
        ieng = build_engine(classifier, "int-emulation")
        sf, si = flow_scenario(), flow_scenario()
        assert_decisions_identical(
            replay(feng, sf, 2), replay(ieng, si, 2), ieng._int_plan
        )
        before = np.asarray(ieng._int_tables["rule_w"]).copy()
        new_w = ieng.rules.weights * 0.5
        feng.swap_tables(weights=new_w)
        ieng.swap_tables(weights=new_w)
        after = np.asarray(ieng._int_tables["rule_w"])
        assert not np.array_equal(before, after)
        np.testing.assert_array_equal(
            after,
            np.asarray(requantize_rule_weights(ieng._int_plan, new_w)),
        )
        assert_decisions_identical(
            replay(feng, sf, 2), replay(ieng, si, 2), ieng._int_plan
        )

    def test_int_engine_ledger_and_state(self, int_replay):
        ieng, _ = int_replay
        assert ieng.backend == "int-emulation"
        assert ieng.hidden_sum.dtype == jnp.int32
        entries = [e for e in ieng.program.ledger.entries if e.stage == STAGE]
        assert len(entries) == 8 and all(e.ok for e in entries)
        # the hot path compiled once; swaps/batches never retrace it
        assert ieng._jit_step._cache_size() == 1


# ==========================================================================
# differential replay: DriftScenario
# ==========================================================================

class TestDriftScenarioConformance:
    def test_drift_decisions_bit_identical(self, classifier):
        """The same drift schedule (signature rotation + churn) through
        float and int engines: decisions identical, divergence bounded."""
        feng = build_engine(classifier, "xla")
        ieng = build_engine(classifier, "int-emulation")
        fouts = replay(feng, drift_scenario(), 10)
        iouts = replay(ieng, drift_scenario(), 10)
        assert_decisions_identical(fouts, iouts, ieng._int_plan)

    @pytest.mark.slow
    def test_three_way_drift_sweep(self, classifier):
        """The full conformance triangle: reference and pallas-interpret are
        bit-exact on every output (float engines agree to the bit on this
        host), and int-emulation matches both on decisions within the
        divergence bound."""
        ref = build_engine(classifier, "reference")
        interp = build_engine(classifier, "pallas-interpret")
        ieng = build_engine(classifier, "int-emulation")
        n = sum(p.batches for p in DRIFT_PHASES)
        router = replay(ref, drift_scenario(), n)
        iouts = replay(interp, drift_scenario(), n)
        for i, (a, b) in enumerate(zip(router, iouts)):
            for k in ("trust", "vetoed", "pred", "s_nn", "s_sym", "sig"):
                np.testing.assert_array_equal(
                    a[k], b[k], err_msg=f"batch {i} {k}"
                )
        qouts = replay(ieng, drift_scenario(), n)
        assert_decisions_identical(router, qouts, ieng._int_plan)


# ==========================================================================
# golden int score history
# ==========================================================================

def _int_fingerprint(outs, plan):
    """The canonical replay reduced to exact integers: quantized trust
    (recovered exactly — 2^-f_t dequantization is lossless in fp32),
    argmax, veto bits."""
    hist = []
    for o in outs:
        trust_q = np.round(o["trust"] * plan.one_q).astype(np.int64)
        hist.append({
            "trust_q": trust_q.tolist(),
            "pred": o["pred"].astype(np.int64).tolist(),
            "vetoed": np.asarray(o["vetoed"], np.int64).tolist(),
        })
    return hist


class TestGoldenIntHistory:
    def test_history_matches_golden_fixture(self, int_replay):
        ieng, iouts = int_replay
        got = {
            "plan": {
                "feature_frac": ieng._int_plan.feature_frac,
                "score_frac": ieng._int_plan.score_frac,
                "trust_frac": ieng._int_plan.trust_frac,
                "one_q": ieng._int_plan.one_q,
            },
            "history": _int_fingerprint(iouts, ieng._int_plan),
        }
        if os.environ.get("REGEN_GOLDEN"):
            os.makedirs(os.path.dirname(GOLDEN), exist_ok=True)
            with open(GOLDEN, "w") as f:
                json.dump(got, f, indent=2, sort_keys=True)
                f.write("\n")
        with open(GOLDEN) as f:
            want = json.load(f)
        assert got["plan"] == want["plan"]
        assert len(got["history"]) == len(want["history"])
        for i, (g, w) in enumerate(zip(got["history"], want["history"])):
            assert g["pred"] == w["pred"], f"batch {i} pred"
            assert g["vetoed"] == w["vetoed"], f"batch {i} vetoed"
            assert g["trust_q"] == w["trust_q"], f"batch {i} trust_q"


# ==========================================================================
# sharded deployment: the lowered int tables replicate per shard
# ==========================================================================

needs_two_devices = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs >=2 devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=2)",
)


@needs_two_devices
class TestShardedIntEmulation:
    """int-emulation over ShardedFlowEngine: the plan/tables are pure
    functions of (ccfg, params, rules, horizon) — flow-independent — so
    they deploy by replication while only the flow rows shard.  Decisions
    must match a single-device int deploy bit-for-bit."""

    def _engines(self, classifier, capacity=512):
        ccfg, params = classifier
        sc = flow_scenario()
        program = compile_program(
            ccfg, params,
            rules=lambda c: C.default_rules(c, jnp.asarray(sc.anomaly_signature)),
            backend="int-emulation",
        )
        single = program.deploy(
            DeploySpec(flow=FlowEngineConfig(capacity=capacity, lanes=16))
        )
        shard = program.deploy(DeploySpec(
            engine="sharded",
            flow=FlowEngineConfig(capacity=capacity, lanes=16),
            num_shards=2,
        ))
        return single, shard

    def test_two_shard_decisions_match_single_device(self, classifier):
        single, shard = self._engines(classifier)
        assert shard.backend == "int-emulation"
        assert shard._int_plan is not None and shard._int_tables is not None
        assert shard.hidden_sum.dtype == jnp.int32
        s1, s2 = flow_scenario(), flow_scenario()
        for i in range(N_BATCHES):
            b1, b2 = s1.next_batch(), s2.next_batch()
            f = single.ingest(b1["flow_ids"], b1["tokens"])
            g = shard.ingest(b2["flow_ids"], b2["tokens"])
            for k in DECISION_KEYS:
                np.testing.assert_array_equal(
                    f[k], g[k], err_msg=f"batch {i} {k}"
                )
            # S = 1.0 pinning holds shard-side too
            np.testing.assert_array_equal(g["trust"] == 1.0, g["vetoed"])
        assert shard.stats.flows_evicted == 0
        # control-plane read path agrees flow-by-flow (dequantized scores)
        for fid in list(single.table.slot_of)[:8]:
            a, b = single.flow_scores(fid), shard.flow_scores(fid)
            assert a == b, fid

    def test_swap_requantizes_rule_weights_on_every_shard(self, classifier):
        import dataclasses as dc

        single, shard = self._engines(classifier)
        s1, s2 = flow_scenario(), flow_scenario()
        for _ in range(4):
            b1, b2 = s1.next_batch(), s2.next_batch()
            single.ingest(b1["flow_ids"], b1["tokens"])
            shard.ingest(b2["flow_ids"], b2["tokens"])
        new = dc.replace(
            jax.device_get(single.rules),
            weights=jax.device_get(single.rules).weights * 1.5,
        )
        old_rule_w = np.asarray(shard._int_tables["rule_w"])
        single.swap_tables(ruleset=new)
        shard.swap_tables(ruleset=new)
        # the int score path reads the NEW quantized weight column
        assert not np.array_equal(
            np.asarray(shard._int_tables["rule_w"]), old_rule_w
        )
        np.testing.assert_array_equal(
            np.asarray(shard._int_tables["rule_w"]),
            np.asarray(single._int_tables["rule_w"]),
        )
        for i in range(4):
            b1, b2 = s1.next_batch(), s2.next_batch()
            f = single.ingest(b1["flow_ids"], b1["tokens"])
            g = shard.ingest(b2["flow_ids"], b2["tokens"])
            for k in DECISION_KEYS:
                np.testing.assert_array_equal(
                    f[k], g[k], err_msg=f"post-swap batch {i} {k}"
                )
