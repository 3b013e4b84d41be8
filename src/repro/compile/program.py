"""DataplaneProgram: the single deployable artifact of the repo
(DESIGN.md §11).

``compile_program`` runs the pass pipeline in :mod:`repro.compile.passes`
over a trained classifier and returns a :class:`DataplaneProgram` — model
parameters, packed TCAM rules, the quantized HL-MRF SRAM weight table, the
streaming-state fixed-point format, the kernel backend/tile selection, and
the per-stage :class:`ResourceLedger` proving it all fits the
:class:`DataplaneSpec` budget (or recording which stages were waived).

Deployment is ``program.deploy(DeploySpec(...))`` — one front door
dispatching to the flow, sharded, elastic or LM serving runtimes
(:mod:`repro.serve.deploy`, DESIGN.md §17); slow-timescale updates are
:class:`ProgramDelta` objects (emitted by ``TwoTimescaleController
.maybe_recluster`` or :func:`compile_delta` directly) that
``FlowEngine.swap_tables`` installs atomically — every table that ever
reaches the dataplane flows through the same audited compile path.
Programs serialize via :class:`repro.checkpoint.Checkpointer` (atomic,
fsync'd) and reload bit-exactly.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.checkpoint import Checkpointer
from repro.compile import passes
from repro.compile.ledger import ResourceLedger
from repro.configs.base import ArchConfig
from repro.core import symbolic
from repro.core.chimera_attention import ChimeraAttentionConfig
from repro.core.feature_maps import FeatureMapConfig
from repro.core.hardware_model import DEFAULT_DATAPLANE, DataplaneSpec, device_tpu_spec
from repro.core.quantization import FixedPointSpec
from repro.core.state_quant import StateQuantConfig
from repro.train.classifier import ClassifierConfig

RulesLike = Union[symbolic.RuleSet, Callable[[ClassifierConfig], symbolic.RuleSet], None]


@dataclasses.dataclass
class DataplaneProgram:
    """Everything a deployment needs, with its audit trail attached."""

    ccfg: ClassifierConfig  # sig_words finalized by the signature pass
    params: Any  # classifier params {"backbone", "cls", "anom", "fusion"}
    rules: symbolic.RuleSet  # packed to the compiled signature width
    weight_table: jax.Array  # Eq. 19 fixed-point SRAM image of rules.weights
    weight_spec: FixedPointSpec
    state_quant: StateQuantConfig  # (S, Z) at-rest bit widths
    s_scale: float  # S-accumulator LSB (overflow-safe at `horizon`)
    horizon: int  # Eq. 39 flow-length horizon the format covers
    backend: Optional[str]  # kernel backend ("xla" | dispatch name | None)
    tiles: Optional[Dict[str, int]]  # autotuned decode tiles (dispatch only)
    ledger: ResourceLedger
    spec: DataplaneSpec

    @property
    def arch(self) -> ArchConfig:
        return self.ccfg.arch

    # ------------------------------------------------------------------
    # deployment (the one front door onto the serving runtimes)
    # ------------------------------------------------------------------
    def deploy(self, spec=None, *, mesh=None, num_shards: Optional[int] = None):
        """Deploy this program onto a serving runtime.

        The supported surface is a :class:`repro.serve.deploy.DeploySpec`
        naming the engine kind and its knobs (DESIGN.md §17)::

            program.deploy(DeploySpec())                       # FlowEngine
            program.deploy(DeploySpec(engine="sharded", num_shards=4))
            program.deploy(DeploySpec(engine="elastic", num_shards=2,
                                      elastic=ElasticConfig(...)))
            program.deploy(DeploySpec(engine="lm", batch_slots=8))

        ``deploy()`` with no arguments is the default single-device flow
        deploy.  The legacy form ``deploy(fcfg, mesh=..., num_shards=...)``
        still works but emits :class:`DeprecationWarning` and will be
        removed one release cycle after the DeploySpec surface landed.
        """
        from repro.serve.deploy import DeploySpec, deploy_program

        if spec is None and mesh is None and num_shards is None:
            return deploy_program(self, DeploySpec())
        if isinstance(spec, DeploySpec):
            if mesh is not None or num_shards is not None:
                raise ValueError(
                    "pass mesh/num_shards inside the DeploySpec, not "
                    "alongside it"
                )
            return deploy_program(self, spec)
        # legacy surface: deploy(fcfg, mesh=..., num_shards=...)
        import warnings

        from repro.serve.flow_engine import FlowEngineConfig

        warnings.warn(
            "DataplaneProgram.deploy(fcfg, mesh=..., num_shards=...) is "
            "deprecated; pass a DeploySpec instead — deploy(DeploySpec("
            "engine='sharded', flow=fcfg, num_shards=...)) (DESIGN.md "
            "§17.4)",
            DeprecationWarning, stacklevel=2,
        )
        fcfg = spec if spec is not None else FlowEngineConfig()
        if mesh is None and num_shards is None:
            legacy = DeploySpec(engine="flow", flow=fcfg)
        else:
            legacy = DeploySpec(
                engine="sharded", flow=fcfg, mesh=mesh, num_shards=num_shards
            )
        return deploy_program(self, legacy)

    # ------------------------------------------------------------------
    # serialization (atomic, via the Checkpointer)
    # ------------------------------------------------------------------
    def _array_tree(self) -> Dict[str, Any]:
        return {
            "params": self.params,
            "rules": self.rules,
            "weight_table": self.weight_table,
        }

    def save(self, directory: str, step: int = 0) -> None:
        ckpt = Checkpointer(directory, keep=3)
        extra = {
            "program": {
                "ccfg": _ccfg_to_dict(self.ccfg),
                "n_rules": int(self.rules.n_rules),
                "weight_spec": {"bits": self.weight_spec.bits,
                                "scale": self.weight_spec.scale},
                "state_quant": dataclasses.asdict(self.state_quant),
                "s_scale": self.s_scale,
                "horizon": self.horizon,
                "backend": self.backend,
                "tiles": self.tiles,
                "ledger": self.ledger.as_dict(),
                "spec": dataclasses.asdict(self.spec),
            }
        }
        ckpt.save(step, self._array_tree(), extra=extra, blocking=True)

    @classmethod
    def load(cls, directory: str, step: Optional[int] = None) -> "DataplaneProgram":
        from repro.train.classifier import init_classifier

        ckpt = Checkpointer(directory)
        step = step if step is not None else ckpt.latest_step()
        if step is None:
            raise FileNotFoundError(f"no program checkpoints in {directory}")
        with open(os.path.join(directory, f"step_{step:08d}", "manifest.json")) as f:
            meta = json.load(f)["extra"]["program"]
        ccfg = _ccfg_from_dict(meta["ccfg"])
        wspec = FixedPointSpec(**meta["weight_spec"])
        # rebuild the target tree structure only — eval_shape traces the
        # initializer without materializing (or randomly filling) any weights
        params = jax.eval_shape(
            lambda k: init_classifier(ccfg, k)[0], jax.random.PRNGKey(0)
        )
        M, W = meta["n_rules"], ccfg.sig_words
        target = {
            "params": params,
            "rules": symbolic.RuleSet(
                values=jnp.zeros((M, W), jnp.uint32),
                masks=jnp.zeros((M, W), jnp.uint32),
                weights=jnp.zeros((M,), jnp.float32),
                hard=jnp.zeros((M,), bool),
            ),
            "weight_table": jnp.zeros((M,), wspec.dtype),
        }
        tree, _, _ = ckpt.restore(target, step=step)
        return cls(
            ccfg=ccfg,
            params=tree["params"],
            rules=tree["rules"],
            weight_table=tree["weight_table"],
            weight_spec=wspec,
            state_quant=StateQuantConfig(**meta["state_quant"]),
            s_scale=meta["s_scale"],
            horizon=meta["horizon"],
            backend=meta["backend"],
            tiles=meta["tiles"],
            ledger=ResourceLedger.from_dict(meta["ledger"]),
            spec=DataplaneSpec(**meta["spec"]),
        )


@dataclasses.dataclass(frozen=True)
class ProgramDelta:
    """A slow-timescale table update, compiled through the same audited
    passes as the program it amends.  ``FlowEngine.swap_tables(delta=...)``
    installs it atomically between ticks."""

    step: int
    weight_table: jax.Array  # quantized Eq. 19 SRAM image
    weight_spec: FixedPointSpec
    ruleset: Optional[symbolic.RuleSet]  # None = weights-only delta
    ledger: ResourceLedger


# --------------------------------------------------------------------------
# the compiler driver
# --------------------------------------------------------------------------

def _null_rules(ccfg: ClassifierConfig) -> symbolic.RuleSet:
    """A single all-don't-care soft rule with zero weight: matches every
    signature but contributes nothing (the LM-serving / rule-free case)."""
    W = ccfg.sig_words
    z = jnp.zeros((1, W), jnp.uint32)
    return symbolic.RuleSet(
        values=z, masks=z, weights=jnp.zeros((1,)), hard=jnp.zeros((1,), bool)
    )


def compile_program(
    ccfg: ClassifierConfig,
    params: Any,
    rules: RulesLike = None,
    *,
    spec: DataplaneSpec = DEFAULT_DATAPLANE,
    backend: Optional[str] = None,
    qcfg: StateQuantConfig = StateQuantConfig(),
    weight_bits: int = 16,
    horizon: int = 1024,
    flows: int = 8192,
    waivers: Tuple[str, ...] = (),
    tpu=None,
    int_cfg=None,
    verify: bool = True,
) -> DataplaneProgram:
    """Lower (config, params, rules) into a deployable DataplaneProgram.

    ``rules`` may be a RuleSet, ``None`` (a no-op ruleset is compiled), or a
    callable ``ccfg -> RuleSet`` invoked *after* the signature-layout pass —
    use the callable form when rule signatures reference marker tokens, so
    they are built against the final (aliasing-free) ``sig_words``.

    Raises :class:`BudgetError` naming the offending stage when any pass
    exceeds ``spec``, unless that stage is listed in ``waivers`` (the
    violation is then recorded in the ledger instead).

    ``tpu`` is the chip the kernel pass budgets against; ``None`` looks it
    up by the present device's ``device_kind``
    (:func:`repro.core.hardware_model.device_tpu_spec`).

    ``verify`` (on by default) runs the static-verification battery
    (:func:`repro.analysis.verify.verify_program`) as a final pass: TCAM
    rule-table lint, hot-path jaxpr lint and — for int-emulation — the
    interval-analysis int32 overflow proof at ``horizon``.  Findings land
    as ``static-verification`` ledger entries; error-severity findings
    raise :class:`repro.analysis.AnalysisError` unless the
    ``"static-verification"`` stage is waived.  Pass ``verify=False`` to
    opt out (the entries are then simply absent from the ledger).
    """
    ledger = ResourceLedger()

    # pass 1 — signature/TCAM layout (needs the rule width only if the
    # ruleset is pre-built; callables see the final layout)
    pre_rules = rules if isinstance(rules, symbolic.RuleSet) else None
    ccfg, entries = passes.signature_layout(ccfg, pre_rules, spec)
    ledger.extend(entries)
    if rules is None:
        rules = _null_rules(ccfg)
    elif callable(rules) and not isinstance(rules, symbolic.RuleSet):
        rules = rules(ccfg)

    # pass 2 — rule packing + HL-MRF weight table (Eq. 16/19)
    rules, weight_table, weight_spec, entries = passes.pack_rules(
        ccfg, rules, spec, weight_bits
    )
    ledger.extend(entries)

    # pass 3 — streaming-state fixed point (Eq. 7/11/13/39)
    s_scale, entries = passes.quantize_state(ccfg, qcfg, spec, horizon)
    ledger.extend(entries)

    # pass 4 — kernel backend + tiles
    effective_backend, tiles, entries = passes.select_backend(
        ccfg, backend, tpu if tpu is not None else device_tpu_spec()
    )
    ledger.extend(entries)

    # pass 4b — integer score lowering (int-emulation targets only): derive
    # the per-stage fixed-point formats from the Eq. 39 analysis and audit
    # every intermediate bit-width at compile time, so a program that cannot
    # run in int32 fails HERE, not at deploy.  The plan/tables themselves are
    # re-derived deterministically by the engine (pure function of the
    # program contents), so nothing extra is serialized.
    eff = backend if backend is not None else effective_backend
    if eff == "int-emulation":
        from repro.compile.int_lowering import IntLoweringConfig, lower_scores

        _, _, entries = lower_scores(
            ccfg, params, rules,
            cfg=int_cfg if int_cfg is not None else IntLoweringConfig(),
            horizon=horizon,
        )
        ledger.extend(entries)

    # pass 5 — aggregate shared-resource report (Table 2)
    report, entries = passes.assemble_ledger(
        ccfg, rules, qcfg, weight_bits, flows, spec
    )
    ledger.extend(entries)
    ledger.report = report

    program = DataplaneProgram(
        ccfg=ccfg,
        params=params,
        rules=rules,
        weight_table=weight_table,
        weight_spec=weight_spec,
        state_quant=qcfg,
        s_scale=s_scale,
        horizon=horizon,
        backend=backend if backend is not None else effective_backend,
        tiles=tiles,
        ledger=ledger,
        spec=spec,
    )

    # pass 6 — static verification (opt-out).  Findings are recorded as
    # ledger rows either way; error-severity findings fail the compile
    # louder than a budget line (AnalysisError) unless the stage is waived.
    if verify:
        from repro.analysis.verify import STAGE as VERIFY_STAGE
        from repro.analysis.verify import verify_program

        ledger.extend(verify_program(program, int_cfg=int_cfg, strict=False))
        ledger.apply_waivers(tuple(waivers))
        bad = [e for e in ledger.violations() if e.stage == VERIFY_STAGE]
        if bad:
            from repro.analysis.intervals import AnalysisError

            lines = "; ".join(f"{e.resource}: {e.detail}" for e in bad)
            raise AnalysisError(
                f"static verification failed — {lines}. Pass "
                f"waivers=('static-verification',) to record-and-accept, "
                f"or verify=False to skip the pass.",
                report=ledger,
            )
    else:
        ledger.apply_waivers(tuple(waivers))
    ledger.raise_if_over()

    return program


def compile_delta(
    program: DataplaneProgram,
    *,
    weights: Optional[jax.Array] = None,
    ruleset: Optional[symbolic.RuleSet] = None,
    step: int = 0,
    weight_bits: Optional[int] = None,
    waivers: Optional[Tuple[str, ...]] = None,
) -> ProgramDelta:
    """Compile a slow-timescale table update against an installed program.

    Re-runs the rule-packing pass (budget checks included) on the new
    tables, so a delta carries the same audit guarantees as a full compile.
    Raises :class:`BudgetError` if the update no longer fits.  ``waivers``
    defaults to the stages already waived at program compile time (a
    violation the operator accepted once does not re-fail on every delta).
    """
    base = ruleset if ruleset is not None else program.rules
    if weights is not None:
        base = symbolic.RuleSet(
            values=base.values,
            masks=base.masks,
            weights=jnp.asarray(weights, jnp.float32),
            hard=base.hard,
        )
    bits = weight_bits if weight_bits is not None else program.weight_spec.bits
    ledger = ResourceLedger()
    packed, table, wspec, entries = passes.pack_rules(
        program.ccfg, base, program.spec, bits
    )
    ledger.extend(entries)
    if waivers is None:
        waivers = tuple({e.stage for e in program.ledger.waived()})
    ledger.apply_waivers(tuple(w for w in waivers if w in ledger.stages()))
    ledger.raise_if_over()
    return ProgramDelta(
        step=step,
        weight_table=table,
        weight_spec=wspec,
        ruleset=packed if ruleset is not None else None,
        ledger=ledger,
    )


# --------------------------------------------------------------------------
# config (de)serialization — plain dicts, JSON-safe
# --------------------------------------------------------------------------

def _ccfg_to_dict(ccfg: ClassifierConfig) -> Dict:
    return dataclasses.asdict(ccfg)


def _ccfg_from_dict(d: Dict) -> ClassifierConfig:
    d = dict(d)
    arch = dict(d.pop("arch"))
    chim = dict(arch.pop("chimera"))
    fm = FeatureMapConfig(**chim.pop("feature_map"))
    chimera = ChimeraAttentionConfig(feature_map=fm, **chim)
    arch["block_pattern"] = tuple(arch["block_pattern"])
    return ClassifierConfig(arch=ArchConfig(chimera=chimera, **arch), **d)
