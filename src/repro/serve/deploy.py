"""One front door onto every serving runtime (DESIGN.md §17).

Historically the repo grew four deploy entry points — ``FlowEngine
.from_program``, ``ShardedFlowEngine.from_program``, ``ServeEngine
.from_program`` and ``DataplaneProgram.deploy(fcfg, mesh=|num_shards=)`` —
each with its own kwargs and its own ledger side effects.  This module
collapses them into a single declarative surface:

    from repro.serve.deploy import DeploySpec

    engine = program.deploy(DeploySpec())                      # FlowEngine
    engine = program.deploy(DeploySpec(engine="sharded",
                                       num_shards=4))          # sharded
    service = program.deploy(DeploySpec(engine="elastic",
                                        num_shards=2,
                                        elastic=ElasticConfig(
                                            checkpoint_dir="/tmp/ck")))
    lm = program.deploy(DeploySpec(engine="lm", batch_slots=8))

:class:`DeploySpec` names the engine kind, shard/mesh placement, fused and
ring options (via the embedded :class:`~repro.serve.flow_engine
.FlowEngineConfig`), a kernel-backend override, and the elasticity /
checkpoint knobs of the :class:`~repro.serve.elastic.ElasticFlowService`.
Every engine the dispatcher can return satisfies the structural
:class:`Engine` protocol (``ingest`` / ``flow_scores`` / ``swap_tables`` /
``jit_entry_points`` / ``stats``), so control-plane code — the adaptive
loop, the retrace sentry, the benchmarks — is engine-kind agnostic.

The legacy ``from_program`` classmethods and the positional
``deploy(fcfg, mesh=, num_shards=)`` form still work as thin shims that
emit :class:`DeprecationWarning` and delegate to the builders below; they
are scheduled for removal one release cycle after the DeploySpec surface
landed (see DESIGN.md §17.4).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Protocol, Tuple, runtime_checkable

from repro.serve.flow_engine import FlowEngineConfig

ENGINE_KINDS = ("flow", "sharded", "elastic", "lm")

#: where a deploy's flow-table budget came from when it named none: the
#: device's HBM (see :func:`device_table_budget`)
TABLE_BUDGET_STAGE = "flow-table-budget"

#: share of a device's HBM a flow table may take when the deploy names no
#: budget.  The rest is for weights and the hot-path step's temporaries,
#: which scale with the table: compiled ahead of time for v5e at 4096 flows
#: of the full arch, the fused step reserves ~1.3x the table and the
#: per-round step ~0.7x; on the chip the fused run peaked at 1.6x the table
#: in all.  At 40% both fit.
TABLE_HBM_SHARE = 0.4

#: deploy-scoped ledger stages refreshed (never duplicated) on re-deploys,
#: so the program's audit trail always describes the ACTIVE deployment
DEPLOY_STAGES = (
    "flow-table-sharding", "int-lowering", "admission-control",
    TABLE_BUDGET_STAGE,
)


# --------------------------------------------------------------------------
# elasticity / admission knobs (config-only: importable without jax state)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TenantSpec:
    """Admission-control identity: a traffic class holding a bounded share
    of the aggregate flow table.  Under pressure, new flows of
    lower-priority tenants are shed first (DESIGN.md §17.3)."""

    name: str
    priority: int = 0  # higher priority survives longer under pressure
    share: float = 1.0  # fraction of aggregate flow capacity this tenant may hold

    def __post_init__(self):
        if not (0.0 < self.share <= 1.0):
            raise ValueError(f"tenant {self.name!r}: share must be in (0, 1], "
                             f"got {self.share}")


@dataclasses.dataclass(frozen=True)
class ElasticConfig:
    """Knobs of :class:`~repro.serve.elastic.ElasticFlowService`."""

    checkpoint_dir: Optional[str] = None  # flow-state checkpoints (None = in-memory)
    checkpoint_every: int = 0  # ticks between automatic checkpoints (0 = manual)
    replay_window: int = 64  # ingest batches buffered for post-recovery replay
    heartbeat_timeout_s: float = 60.0  # shard liveness horizon (HeartbeatMonitor)
    keep_topologies: bool = True  # cache engines per shard count: reshard-back never retraces
    tenants: Tuple[TenantSpec, ...] = ()
    default_tenant: str = "default"


# --------------------------------------------------------------------------
# the one deployment surface
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DeploySpec:
    """Declarative deployment request for :meth:`repro.compile
    .DataplaneProgram.deploy` — names WHAT to run, the program supplies the
    compiled tables and the builders below decide HOW.

    ``flow`` carries the deployment-site flow-table knobs (capacity, lanes,
    fused/ring options, t_cp); for sharded/elastic deploys ``capacity`` is
    per shard.  ``backend`` overrides both ``flow.backend`` and the
    program's pass-selected kernel backend.  ``batch_slots`` / ``max_len``
    / ``temperature`` / ``seed`` only apply to the ``"lm"`` slot engine.
    """

    engine: str = "flow"  # "flow" | "sharded" | "elastic" | "lm"
    flow: FlowEngineConfig = FlowEngineConfig()
    num_shards: Optional[int] = None
    mesh: Any = None
    backend: Optional[str] = None
    elastic: ElasticConfig = ElasticConfig()
    # LM slot-engine knobs (engine="lm")
    batch_slots: int = 8
    max_len: int = 4096
    temperature: float = 0.0
    seed: int = 0

    def __post_init__(self):
        if self.engine not in ENGINE_KINDS:
            raise ValueError(
                f"unknown engine kind {self.engine!r}; expected one of "
                f"{ENGINE_KINDS}"
            )
        if self.engine in ("flow", "lm") and (
            self.num_shards is not None or self.mesh is not None
        ):
            raise ValueError(
                f"engine={self.engine!r} is single-placement; num_shards/mesh "
                f"require engine='sharded' or engine='elastic'"
            )


@runtime_checkable
class Engine(Protocol):
    """The structural contract every deployed serving runtime satisfies.

    ``ingest``/``flow_scores``/``swap_tables`` may raise
    ``NotImplementedError`` on engines whose modality does not support them
    (the LM slot engine has no flow table), but the surface is uniform so
    control-plane code can be written once against this protocol.
    """

    stats: Any

    def ingest(self, flow_ids, tokens) -> Dict[str, Any]: ...

    def flow_scores(self, fid: int) -> Dict[str, float]: ...

    def swap_tables(self, ruleset=None, weights=None, weight_spec=None,
                    delta=None): ...

    def jit_entry_points(self) -> Dict[str, Any]: ...


# --------------------------------------------------------------------------
# builders — the real construction paths (non-deprecated; the legacy
# ``from_program`` classmethods are shims over these)
# --------------------------------------------------------------------------

def device_table_budget() -> Optional[int]:
    """Per-device flow-table budget from the HBM the device reports
    (``memory_stats()["bytes_limit"]``), or None where the backend reports
    no memory stats (the CPU)."""
    import jax

    limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
    return None if limit is None else int(limit * TABLE_HBM_SHARE)


def _site_fcfg(program, fcfg: FlowEngineConfig,
               backend: Optional[str]) -> FlowEngineConfig:
    """Resolve the deployment-site flow config against the program: backend
    precedence is spec override > fcfg.backend > program's pass selection;
    the Eq. 39 horizon always comes from the program.  An unnamed table
    budget (0) becomes the device's HBM share where the device reports its
    memory: the engines' own default is a switch's 120 MB of SRAM, which the
    full arch's ~1.6 MB/flow state cannot meet at any useful capacity, while
    on a TPU host the table lives in HBM."""
    eff = backend if backend is not None else fcfg.backend
    eff = eff if eff is not None else program.backend
    budget = fcfg.state_budget_bytes or device_table_budget() or 0
    return dataclasses.replace(fcfg, backend=eff, horizon=program.horizon,
                               state_budget_bytes=budget)


def record_table_budget(program, requested: FlowEngineConfig, eng) -> None:
    """Ledger row for a table budget taken from device HBM (``requested``
    named none), recorded the way a waiver is: the substitution is part of
    the deployment's audit trail."""
    if requested.state_budget_bytes or not eng.fcfg.state_budget_bytes:
        return
    used = getattr(eng, "shard_state_bytes", eng.resident_state_bytes)
    program.ledger.add(
        TABLE_BUDGET_STAGE, "device-hbm-bytes", used=used(),
        budget=eng.state_budget_bytes,
        detail=f"per-device table budget = {TABLE_HBM_SHARE:g} x the "
               f"device's memory_stats() bytes_limit, replacing the "
               f"DataplaneSpec's switch SRAM; weights and the step's "
               f"temporaries take the rest",
    )


def _reset_deploy_stages(program) -> None:
    program.ledger.entries = [
        e for e in program.ledger.entries if e.stage not in DEPLOY_STAGES
    ]


def build_flow_engine(program, fcfg: FlowEngineConfig = FlowEngineConfig(),
                      *, backend: Optional[str] = None):
    """Deploy ``program`` on a single-device :class:`~repro.serve
    .flow_engine.FlowEngine`.  Drops any stale sharded-placement /
    int-lowering ledger entries and records this deploy's own lowering, so
    the ledger describes the active deployment."""
    from repro.serve.flow_engine import FlowEngine, _engine_kwargs_from_program

    kw = _engine_kwargs_from_program(
        program, backend=backend if backend is not None else fcfg.backend
    )
    eng = FlowEngine(kw["ccfg"], kw["params"], kw["rules"],
                     _site_fcfg(program, fcfg, backend))
    eng.program = program
    _reset_deploy_stages(program)
    program.ledger.entries.extend(eng._int_entries)
    record_table_budget(program, fcfg, eng)
    return eng


def build_sharded_engine(program, fcfg: FlowEngineConfig = FlowEngineConfig(),
                         *, mesh=None, num_shards: Optional[int] = None,
                         backend: Optional[str] = None, record: bool = True):
    """Deploy ``program`` sharded over the mesh ``data`` axis.

    The per-shard Eq. 11 flow-table budget check runs at construction; with
    ``record`` (the default) the per-shard usage and the shards × budget
    aggregate are refreshed in the program's ledger.  The elastic service
    passes ``record=False`` when building provisional reshard targets and
    refreshes the ledger itself only on commit.
    """
    from repro.serve.flow_engine import _engine_kwargs_from_program
    from repro.serve.sharded_flow_engine import ShardedFlowEngine

    kw = _engine_kwargs_from_program(
        program, backend=backend if backend is not None else fcfg.backend
    )
    eng = ShardedFlowEngine(
        kw["ccfg"], kw["params"], kw["rules"],
        _site_fcfg(program, fcfg, backend),
        mesh=mesh, num_shards=num_shards,
    )
    eng.program = program
    if record:
        _reset_deploy_stages(program)
        program.ledger.entries.extend(eng._int_entries)
        record_table_budget(program, fcfg, eng)
        record_sharding_entry(program, eng)
        program.ledger.raise_if_over()
    return eng


def record_sharding_entry(program, eng, note: str = "") -> None:
    """Refresh the ``flow-table-sharding`` StageEntry to describe ``eng``
    (the active sharded placement).  Reshards call this on commit."""
    program.ledger.entries = [
        e for e in program.ledger.entries if e.stage != "flow-table-sharding"
    ]
    program.ledger.add(
        "flow-table-sharding", "per-shard-table-bytes",
        used=eng.shard_state_bytes(), budget=eng.state_budget_bytes,
        detail=(
            f"{eng.num_shards} shard(s) x {eng.fcfg.capacity} flows/shard; "
            f"aggregate capacity {eng.aggregate_capacity} flows, "
            f"aggregate budget {eng.aggregate_state_budget_bytes} B"
            + (f"; {note}" if note else "")
        ),
    )


def build_serve_engine(program, *, batch_slots: int = 8, max_len: int = 4096,
                       temperature: float = 0.0, seed: int = 0,
                       backend: Optional[str] = None):
    """Deploy ``program``'s backbone as an LM-style slot engine
    (:class:`~repro.serve.engine.ServeEngine`)."""
    from repro.serve.engine import ServeEngine
    from repro.serve.flow_engine import _engine_kwargs_from_program

    kw = _engine_kwargs_from_program(program, backend=backend)
    return ServeEngine(
        kw["ccfg"].arch, kw["params"]["backbone"],
        batch_slots=batch_slots, max_len=max_len,
        temperature=temperature, seed=seed, backend=kw["backend"],
    )


def deploy_program(program, spec: DeploySpec = DeploySpec()):
    """Dispatch a :class:`DeploySpec` onto the matching builder — the
    implementation behind :meth:`repro.compile.DataplaneProgram.deploy`."""
    if not isinstance(spec, DeploySpec):
        raise TypeError(
            f"deploy_program expects a DeploySpec, got {type(spec).__name__}"
        )
    if spec.engine == "flow":
        return build_flow_engine(program, spec.flow, backend=spec.backend)
    if spec.engine == "sharded":
        return build_sharded_engine(
            program, spec.flow, mesh=spec.mesh, num_shards=spec.num_shards,
            backend=spec.backend,
        )
    if spec.engine == "elastic":
        from repro.serve.elastic import ElasticFlowService

        return ElasticFlowService(
            program, spec.flow, spec.elastic,
            mesh=spec.mesh, num_shards=spec.num_shards, backend=spec.backend,
        )
    assert spec.engine == "lm"
    return build_serve_engine(
        program, batch_slots=spec.batch_slots, max_len=spec.max_len,
        temperature=spec.temperature, seed=spec.seed, backend=spec.backend,
    )
