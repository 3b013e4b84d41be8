"""Elastic multi-shard flow serving (DESIGN.md §17).

:class:`ElasticFlowService` wraps the sharded flow engine with the three
capabilities that separate "one host's mesh" from a service:

* **Live resharding** (§17.1) — ``reshard(new_num_shards)`` quiesces
  ingest for the migrating key ranges (:func:`repro.data.pipeline
  .reshard_moves`), snapshots every resident flow row on the host (and
  through the :class:`~repro.checkpoint.Checkpointer` when a checkpoint
  directory is configured), deterministically re-routes each flow with
  :func:`repro.data.pipeline.flow_shard` under the new shard count, and
  installs the rows onto the target topology inside one measured
  ``atomic_swap``/``measure_install_time`` window.  A reshard is therefore
  just another Eq. 18-budgeted install: if it exceeds ``fcfg.t_cp_s`` it
  is ROLLED BACK (the old topology keeps serving, untouched) and the
  violation is recorded; on commit the program ledger's
  ``flow-table-sharding`` StageEntry is refreshed and an
  AdaptationRecord-style :class:`ReshardRecord` is appended to
  ``reshard_history``.  Because the copied rows feed the *same*
  :func:`~repro.serve.flow_engine.make_flow_step` traced function, a
  scenario replayed through ``reshard(2→4→2)`` is bit-identical to an
  unsharded replay in the no-eviction regime.

* **Shard fault tolerance** (§17.2) — periodic flow-state checkpoints
  (every ``ElasticConfig.checkpoint_every`` ticks) through the same
  Checkpointer the trainer uses, a per-shard
  :class:`~repro.runtime.fault_tolerance.HeartbeatMonitor`, and a
  kill-a-shard recovery path (:meth:`recover`) that reshards the
  survivors' live rows onto the shrunk mesh, restores failed-shard flows
  from the last checkpoint, and replays the bounded
  ``ElasticConfig.replay_window`` of buffered post-checkpoint batches for
  exactly the lost key ranges — so recovered flows (including sticky
  hard-veto bits) are bit-identical to a never-killed replay whenever the
  window covers the gap.

* **Admission control** (§17.3) — per-tenant flow budgets derived from
  the ResourceLedger's sharding entry (``share × aggregate capacity``,
  byte-bounded by the Eq. 11 budget), with new flows of lowest-priority
  tenants shed first under pressure.  Shed packets come back marked
  ``admitted=False`` in the ingest output (alignment preserved).

Topology cache: one engine per shard count is kept (``keep_topologies``),
so resharding back to a previously-seen count reuses its jitted step —
``jit_entry_points`` exposes every cached engine's entries under a
``shards<N>.`` namespace, which is how ``repro.analysis.gate`` audits that
a reshard never retraces steady-state ingest.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Any, Deque, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.checkpoint import Checkpointer
from repro.core import hardware_model
from repro.core.two_timescale import atomic_swap, measure_install_time
from repro.data.pipeline import flow_shard, reshard_moves
from repro.runtime.fault_tolerance import HeartbeatMonitor, plan_shard_recovery
from repro.serve.deploy import (
    ElasticConfig,
    TenantSpec,
    _reset_deploy_stages,
    build_sharded_engine,
    record_sharding_entry,
    record_table_budget,
)
from repro.serve.flow_engine import FlowEngineConfig
from repro.serve.sharded_flow_engine import ShardedFlowEngine


@dataclasses.dataclass
class ReshardRecord:
    """One elastic topology change, AdaptationRecord-style: what moved,
    how long the install took, and its Eq. 18 verdict."""

    tick: int
    old_shards: int
    new_shards: int
    reason: str  # "scale" | "recovery"
    migrated_flows: int  # resident rows carried to the new topology
    moved_flows: int  # subset whose owner shard changed (quiesced ranges)
    install_s: float  # measured wall-clock install (device-ready)
    t_cp_s: float  # the control-plane epoch the install was held to
    churn_ok: bool  # Eq. 18: install completed within the epoch
    rolled_back: bool = False
    failed_shards: Tuple[int, ...] = ()
    restored_flows: int = 0  # recovery: flows restored from checkpoint
    replayed_packets: int = 0  # recovery: bounded-window packets re-ingested
    error: Optional[str] = None

    def as_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


# --------------------------------------------------------------------------
# flow-state snapshots (host-side, Checkpointer-compatible pytrees)
# --------------------------------------------------------------------------

def snapshot_flow_state(eng: ShardedFlowEngine) -> Dict[str, Any]:
    """Host snapshot of every resident flow's table row, in deterministic
    (sorted fid) order: decode-cache rows, positions, packed signature,
    pooled-feature accumulator, sticky veto bit and LRU stamp.  The
    snapshot is placement-free — rows are keyed by flow ID, so they can be
    installed onto ANY shard count (:func:`install_flow_state`)."""
    entries = []
    for s, t in enumerate(eng.tables):
        for fid, slot in t.slot_of.items():
            entries.append((int(fid), s, int(slot), int(t.last_seen[slot])))
    entries.sort()
    fids = np.array([e[0] for e in entries], np.int64)
    s_idx = np.array([e[1] for e in entries], np.intp)
    sl_idx = np.array([e[2] for e in entries], np.intp)
    last_seen = np.array([e[3] for e in entries], np.int64)
    n_slots = eng._n_slots

    def rows(arr):
        return np.asarray(arr)[s_idx, sl_idx]

    def cache_rows(leaf):
        h = np.asarray(leaf)
        if h.ndim >= 3 and h.shape[1] == n_slots:
            # sharded slotted leaf (S, n_slots, groups, ...): rows (n, groups, ...)
            return h[s_idx, sl_idx]
        # non-slotted leaves are never written back by the flow step (see
        # make_flow_step's put()) — every shard still holds the init value,
        # so a zero-length placeholder keeps the tree structure without
        # snapshotting constants
        return np.zeros((0,), h.dtype)

    return {
        "fids": fids,
        "last_seen": last_seen,
        "positions": rows(eng.positions),
        "sig": rows(eng.sig),
        "hidden_sum": rows(eng.hidden_sum),
        "vetoed": rows(eng.vetoed),
        "caches": jax.tree_util.tree_map(cache_rows, eng.caches),
    }


def snapshot_template(eng: ShardedFlowEngine) -> Dict[str, Any]:
    """Structure-only snapshot (zero flows) — the restore target tree for
    :meth:`Checkpointer.restore` (leaf values are replaced wholesale)."""
    z = np.zeros((0,), np.int64)
    return {
        "fids": z, "last_seen": z,
        "positions": np.zeros((0,), np.int32),
        "sig": np.zeros((0, eng.ccfg.sig_words), np.uint32),
        "hidden_sum": np.zeros((0,), np.float32),
        "vetoed": np.zeros((0,), bool),
        "caches": jax.tree_util.tree_map(
            lambda leaf: np.zeros((0,), leaf.dtype), eng.caches
        ),
    }


def select_rows(snap: Dict[str, Any], mask: np.ndarray) -> Dict[str, Any]:
    """Row-filter a snapshot (cache placeholders pass through)."""

    def pick(leaf):
        leaf = np.asarray(leaf)
        if leaf.ndim >= 1 and leaf.shape[0] == len(mask):
            return leaf[mask]
        return leaf  # zero-length non-slotted placeholder

    return {
        k: (jax.tree_util.tree_map(pick, v) if k == "caches" else pick(v))
        for k, v in snap.items()
    }


def concat_snapshots(a: Dict[str, Any], b: Dict[str, Any]) -> Dict[str, Any]:
    """Merge two disjoint snapshots (recovery: live survivors + restored
    failed-shard rows)."""

    def cat(x, y):
        x, y = np.asarray(x), np.asarray(y)
        if x.ndim == 1 and x.shape[0] == 0 and y.ndim == 1 and y.shape[0] == 0:
            return x  # non-slotted placeholders
        return np.concatenate([x, y], axis=0)

    out = {
        k: cat(a[k], b[k])
        for k in ("fids", "last_seen", "positions", "sig", "hidden_sum",
                  "vetoed")
    }
    out["caches"] = jax.tree_util.tree_map(cat, a["caches"], b["caches"])
    if len(np.unique(out["fids"])) != len(out["fids"]):
        raise ValueError("concat_snapshots: overlapping flow IDs")
    return out


def install_flow_state(
    eng: ShardedFlowEngine, snap: Dict[str, Any], tick: int
) -> None:
    """Write a snapshot's rows into ``eng``'s table state (everything else
    zeroed), re-routing each flow to ``flow_shard(fid, eng.num_shards)``.

    The write is whole-table: fresh zero arrays with the snapshot rows
    scattered in, installed via :func:`atomic_swap` so the caller's
    ``measure_install_time`` window covers device-ready placement of every
    shard's rows.  Raises if any shard would exceed its per-shard capacity
    (a reshard is a no-eviction install — silently dropping rows would
    break replay equivalence).
    """
    S, n_slots = eng.num_shards, eng._n_slots
    fids = np.asarray(snap["fids"], np.int64)
    owners = flow_shard(fids, S) if len(fids) else np.zeros((0,), np.int64)
    counts = np.bincount(owners, minlength=S) if len(fids) else np.zeros(S, int)
    if (counts > eng.fcfg.capacity).any():
        worst = int(np.argmax(counts))
        raise ValueError(
            f"reshard to {S} shard(s) would put {int(counts[worst])} flows "
            f"on shard {worst} (> per-shard capacity {eng.fcfg.capacity}, "
            f"Eq. 11); raise capacity or evict before resharding"
        )
    eng.reset()
    s_idx = np.empty((len(fids),), np.intp)
    sl_idx = np.empty((len(fids),), np.intp)
    for i, (fid, own) in enumerate(zip(fids.tolist(), owners.tolist())):
        slot, fresh, evicted = eng.tables[own].slot_for(fid, tick)
        assert fresh and not evicted, (fid, own, slot)
        eng.tables[own].last_seen[slot] = int(snap["last_seen"][i])
        s_idx[i], sl_idx[i] = own, slot

    def scatter(rows, like):
        rows = np.asarray(rows)
        h = np.zeros((S, n_slots) + rows.shape[1:], like.dtype)
        h[s_idx, sl_idx] = rows
        return jax.device_put(jnp.asarray(h), eng._row_sharded)

    def scatter_cache(leaf, rows):
        rows = np.asarray(rows)
        if rows.ndim == 1 and rows.shape[0] == 0:
            return leaf  # non-slotted constant: keep the engine's copy
        h = np.zeros(leaf.shape, leaf.dtype)
        # rows in the table's stored per-slot shape; a snapshot taken before
        # the lane-dense fold holds the same elements in the same order
        h[s_idx, sl_idx] = rows.reshape(rows.shape[:1] + leaf.shape[2:])
        return jax.device_put(jnp.asarray(h), eng._row_sharded)

    new_state = (
        jax.tree_util.tree_map(scatter_cache, eng.caches, snap["caches"]),
        scatter(snap["positions"], eng.positions),
        scatter(snap["sig"], eng.sig),
        scatter(snap["hidden_sum"], eng.hidden_sum),
        scatter(snap["vetoed"], eng.vetoed),
    )
    old_state = (eng.caches, eng.positions, eng.sig, eng.hidden_sum, eng.vetoed)
    (eng.caches, eng.positions, eng.sig, eng.hidden_sum, eng.vetoed) = (
        atomic_swap(old_state, new_state)
    )
    eng._tick = tick


# --------------------------------------------------------------------------
# the service
# --------------------------------------------------------------------------

class ElasticFlowService:
    """Sharded flow serving with live resharding, shard fault tolerance and
    per-tenant admission control.  Satisfies the :class:`repro.serve.deploy
    .Engine` protocol — control-plane code written against the sharded
    engine works unchanged against the service."""

    def __init__(
        self,
        program,
        fcfg: FlowEngineConfig = FlowEngineConfig(),
        ecfg: ElasticConfig = ElasticConfig(),
        *,
        mesh=None,
        num_shards: Optional[int] = None,
        backend: Optional[str] = None,
    ):
        self.program = program
        self.ecfg = ecfg
        eng = build_sharded_engine(
            program, fcfg, mesh=mesh, num_shards=num_shards,
            backend=backend, record=False,
        )
        self.fcfg = eng.fcfg  # site config with resolved backend/horizon
        self._engines: Dict[int, ShardedFlowEngine] = {eng.num_shards: eng}
        self.engine = eng
        self.reshard_history: List[ReshardRecord] = []
        self._resharding = False

        # fault tolerance
        self._ckpt = (
            Checkpointer(ecfg.checkpoint_dir, keep=3)
            if ecfg.checkpoint_dir else None
        )
        self._ckpt_seq = 0
        self._last_ckpt: Optional[Tuple[Dict, Dict]] = None  # (snap, meta)
        self._replay: Deque[Tuple[int, np.ndarray, np.ndarray]] = (
            collections.deque(maxlen=max(1, ecfg.replay_window))
        )
        self.monitor = HeartbeatMonitor(timeout_s=ecfg.heartbeat_timeout_s)
        self._failed: set = set()

        # admission control
        self.tenants: Dict[str, TenantSpec] = {t.name: t for t in ecfg.tenants}
        self.tenants.setdefault(
            ecfg.default_tenant, TenantSpec(ecfg.default_tenant)
        )
        self._tenant_of: Dict[int, str] = {}
        self._tenant_count: Dict[str, int] = {}
        self.shed_packets: Dict[str, int] = {}
        self.shed_flows: Dict[str, int] = {}

        _reset_deploy_stages(program)
        program.ledger.entries.extend(eng._int_entries)
        record_table_budget(program, fcfg, eng)
        record_sharding_entry(program, eng, note="elastic")
        self._record_admission_entries()
        program.ledger.raise_if_over()

    # ------------------------------------------------------------------
    # Engine-protocol passthroughs (the active topology's engine)
    # ------------------------------------------------------------------
    def __getattr__(self, name):
        # the rest of the read-only engine surface (backend, ccfg, params,
        # resident_state_bytes, ...) delegates to the ACTIVE topology, so
        # driver code written against the sharded engine runs unchanged
        if name.startswith("_") or name == "engine":
            raise AttributeError(name)
        return getattr(self.engine, name)

    @property
    def stats(self):
        return self.engine.stats

    @property
    def num_shards(self) -> int:
        return self.engine.num_shards

    @property
    def rules(self):
        return self.engine.rules

    @property
    def swap_history(self):
        return self.engine.swap_history

    @property
    def aggregate_capacity(self) -> int:
        return self.engine.aggregate_capacity

    @property
    def resident_flows(self) -> int:
        return self.engine.resident_flows

    def flow_ids(self) -> List[int]:
        return self.engine.flow_ids()

    def flow_scores(self, fid: int) -> Dict[str, float]:
        return self.engine.flow_scores(fid)

    def swap_tables(self, ruleset=None, weights=None, weight_spec=None,
                    delta=None):
        """Install new tables on the ACTIVE topology (measured, Eq. 18).
        Cached standby topologies get the current tables carried over
        inside the next reshard's measured install."""
        return self.engine.swap_tables(
            ruleset=ruleset, weights=weights, weight_spec=weight_spec,
            delta=delta,
        )

    def jit_entry_points(self) -> Dict[str, Any]:
        """Every cached topology's jitted entries, namespaced
        ``shards<N>.<name>`` — the retrace sentry audits them all, so a
        reshard that retraced steady-state ingest cannot hide."""
        entries: Dict[str, Any] = {}
        for S in sorted(self._engines):
            for name, fn in self._engines[S].jit_entry_points().items():
                entries[f"shards{S}.{name}"] = fn
        return entries

    # ------------------------------------------------------------------
    # ingest (admission control + replay buffer + heartbeats)
    # ------------------------------------------------------------------
    def ingest(self, flow_ids, tokens, tenant=None) -> Dict[str, np.ndarray]:
        """Same contract as :meth:`ShardedFlowEngine.ingest`, plus an
        ``admitted`` mask: packets of shed (not-admitted) new flows keep
        their output rows (trust 0, pred -1) but never reach the table.
        ``tenant`` is a name or a per-packet sequence of names; ``None``
        bills the default tenant."""
        if self._resharding:
            raise RuntimeError(
                "ingest during reshard quiesce — the migrating key ranges "
                "are frozen until the install commits or rolls back"
            )
        flow_ids = np.asarray(flow_ids)
        tokens = np.asarray(tokens, np.int32)
        admit = self._admit_mask(flow_ids, tenant)
        eng = self.engine
        if admit.all():
            out = eng.ingest(flow_ids, tokens)
        else:
            n = len(flow_ids)
            out = {
                "flow_ids": flow_ids,
                "trust": np.zeros((n,), np.float32),
                "vetoed": np.zeros((n,), bool),
                "pred": np.full((n,), -1, np.int32),
                "s_nn": np.zeros((n,), np.float32),
                "s_sym": np.zeros((n,), np.float32),
                "sig": np.zeros((n, eng.ccfg.sig_words), np.uint32),
            }
            if admit.any():
                sub = eng.ingest(flow_ids[admit], tokens[admit])
                for k in ("trust", "vetoed", "pred", "s_nn", "s_sym", "sig"):
                    out[k][admit] = sub[k]
            else:
                eng._tick += 1  # a shed-only batch still advances time
        out["admitted"] = admit
        if admit.any():
            self._replay.append(
                (eng._tick, flow_ids[admit].copy(), tokens[admit].copy())
            )
        for s in range(eng.num_shards):
            if s not in self._failed:
                self.monitor.beat(s, eng._tick)
        if (
            self.ecfg.checkpoint_every
            and eng._tick % self.ecfg.checkpoint_every == 0
        ):
            self.checkpoint()
        return out

    # ------------------------------------------------------------------
    # live resharding (Eq. 18-budgeted, rollback-capable)
    # ------------------------------------------------------------------
    def reshard(self, num_shards: int, *, reason: str = "scale") -> ReshardRecord:
        """Scale the flow table to ``num_shards`` shards without dropping a
        packet: quiesce → snapshot → re-route → measured install → commit
        (or roll back on an Eq. 18 ``t_cp`` violation)."""
        eng = self.engine
        old_S = eng.num_shards
        t_cp = self.fcfg.t_cp_s
        if num_shards == old_S:
            rec = ReshardRecord(
                tick=eng._tick, old_shards=old_S, new_shards=num_shards,
                reason=f"{reason} (no-op)", migrated_flows=0, moved_flows=0,
                install_s=0.0, t_cp_s=t_cp, churn_ok=True,
            )
            self.reshard_history.append(rec)
            return rec
        with TraceAnnotation("flow.reshard", tick=eng._tick, shards=num_shards):
            self._resharding = True  # quiesce: no ingest during the install
            try:
                fids = np.array(sorted(self._all_fids()), np.int64)
                moved = int(reshard_moves(fids, old_S, num_shards).sum())
                snap = snapshot_flow_state(eng)
                if self._ckpt is not None:
                    # reshard snapshots ride the same checkpoint stream (they
                    # are the freshest restore point a recovery could want)
                    self._persist_snapshot(snap, kind=f"reshard->{num_shards}")
                target = self._engine_for(num_shards)

                def _install():
                    self._carry_tables(eng, target)
                    install_flow_state(target, snap, tick=eng._tick)
                    return target.positions

                dt = measure_install_time(_install)
                ok = (
                    hardware_model.install_time_ok(dt, t_cp) if t_cp else True
                )
                rec = ReshardRecord(
                    tick=eng._tick, old_shards=old_S, new_shards=num_shards,
                    reason=reason, migrated_flows=int(len(fids)),
                    moved_flows=moved, install_s=dt, t_cp_s=t_cp, churn_ok=ok,
                )
                if ok:
                    self._commit(target)
                else:
                    rec.rolled_back = True
                    rec.error = (
                        f"reshard install {dt:.6f}s exceeded t_cp {t_cp:.6f}s "
                        f"(Eq. 18); rolled back — old topology keeps serving"
                    )
                    target.reset()  # discard the provisional rows
            finally:
                self._resharding = False
        self.reshard_history.append(rec)
        return rec

    def _commit(self, target: ShardedFlowEngine) -> None:
        old = self.engine
        target._tick = old._tick
        target.stats = old.stats  # service-lifetime counters carry over
        self.engine = target
        record_sharding_entry(self.program, target, note="elastic")
        self._record_admission_entries()

    def _engine_for(self, num_shards: int) -> ShardedFlowEngine:
        eng = self._engines.get(num_shards)
        if eng is None:
            eng = build_sharded_engine(
                self.program, self.fcfg, num_shards=num_shards, record=False
            )
            if self.ecfg.keep_topologies:
                self._engines[num_shards] = eng
        return eng

    def _carry_tables(self, src: ShardedFlowEngine,
                      dst: ShardedFlowEngine) -> None:
        """Bring a (possibly stale) standby topology up to the active
        tables: replicate the current RuleSet onto the target mesh and
        requantize the int-emulation weight column.  Runs inside the
        measured install window — the Eq. 18 budget covers everything the
        reshard deploys."""
        dst.rules = atomic_swap(
            dst.rules, jax.device_put(src.rules, dst._replicated)
        )
        if dst._int_plan is not None:
            from repro.compile.int_lowering import requantize_rule_weights

            dst._int_tables = jax.device_put(
                {
                    **dst._int_tables,
                    "rule_w": requantize_rule_weights(
                        dst._int_plan, dst.rules.weights
                    ),
                },
                dst._replicated,
            )

    def _all_fids(self) -> List[int]:
        return self.engine.flow_ids()

    # ------------------------------------------------------------------
    # checkpoints + kill-a-shard recovery
    # ------------------------------------------------------------------
    def checkpoint(self) -> int:
        """Snapshot every resident flow's state (host + Checkpointer when a
        directory is configured).  Returns the checkpoint step id."""
        snap = snapshot_flow_state(self.engine)
        return self._persist_snapshot(snap, kind="periodic")

    def _persist_snapshot(self, snap: Dict, kind: str) -> int:
        meta = {
            "tick": int(self.engine._tick),
            "num_shards": int(self.engine.num_shards),
            "kind": kind,
            "tenant_of": {str(k): v for k, v in self._tenant_of.items()},
        }
        self._last_ckpt = (snap, meta)
        step = self._ckpt_seq
        if self._ckpt is not None:
            self._ckpt.save(step, snap, extra={"elastic": meta}, blocking=True)
        self._ckpt_seq += 1
        return step

    def restore_checkpoint(self, step: Optional[int] = None) -> int:
        """Load flow state from the checkpoint directory into the active
        topology (bit-exact round trip; composes with later
        ``swap_tables`` — rules are live state, not checkpoint state)."""
        if self._ckpt is None:
            raise RuntimeError(
                "no checkpoint directory configured "
                "(ElasticConfig.checkpoint_dir)"
            )
        snap, extra, step = self._ckpt.restore(
            snapshot_template(self.engine), step=step
        )
        meta = extra["elastic"]
        install_flow_state(self.engine, snap, tick=int(meta["tick"]))
        self._tenant_of = {
            int(k): v for k, v in meta.get("tenant_of", {}).items()
        }
        self._rebuild_tenant_counts()
        return step

    def kill_shard(self, shard: int) -> List[int]:
        """Chaos hook: simulate losing shard ``shard`` — its directory (and
        with it every resident flow it owned) is dropped and its heartbeat
        stops.  Returns the lost flow IDs."""
        eng = self.engine
        if not 0 <= shard < eng.num_shards:
            raise ValueError(f"no shard {shard} in a {eng.num_shards}-shard mesh")
        lost = sorted(eng.tables[shard].slot_of)
        eng.tables[shard].reset()
        self._failed.add(shard)
        return lost

    def dead_shards(self, now: Optional[float] = None) -> List[int]:
        """Shards whose heartbeat lapsed (HeartbeatMonitor view) merged
        with explicitly killed shards."""
        return sorted(set(self.monitor.dead_workers(now)) | self._failed)

    def recover(self, failed: Optional[Sequence[int]] = None, *,
                allow_partial: bool = False) -> ReshardRecord:
        """Kill-a-shard recovery: reshard the survivors' live rows onto the
        shrunk mesh, restore failed-shard flows from the last checkpoint,
        then replay the buffered post-checkpoint batches for exactly the
        lost key ranges (bounded by ``ElasticConfig.replay_window``).

        Raises unless the replay window reaches back to the checkpoint
        (data loss — pass ``allow_partial=True`` to accept the gap).  The
        install is measured like any reshard but commits even on an Eq. 18
        violation: a slow recovery beats serving with a dead shard, and the
        verdict is recorded for the operator.
        """
        eng = self.engine
        old_S = eng.num_shards
        failed_set = set(self._failed if failed is None else
                         (int(f) for f in np.atleast_1d(failed)))
        if not failed_set:
            raise ValueError("recover(): no failed shards")
        if self._last_ckpt is None and self._ckpt is None:
            raise RuntimeError(
                "recover(): no checkpoint to restore from — call "
                "checkpoint() (or set ElasticConfig.checkpoint_every)"
            )
        ck_snap, ck_meta = self._recovery_checkpoint()
        ck_tick = int(ck_meta["tick"])
        plan = plan_shard_recovery(old_S, sorted(failed_set), ck_tick)
        assert plan.valid, plan

        live = snapshot_flow_state(eng)  # killed directories are empty
        owners = flow_shard(ck_snap["fids"], old_S) if len(ck_snap["fids"]) \
            else np.zeros((0,), np.int64)
        lost_mask = np.isin(owners, np.asarray(sorted(failed_set)))
        restored = select_rows(ck_snap, lost_mask)
        merged = concat_snapshots(live, restored)

        # bounded-window coverage check BEFORE committing anything
        replayable = [b for b in self._replay if b[0] > ck_tick]
        window_start = min((b[0] for b in replayable), default=ck_tick + 1)
        gap = window_start > ck_tick + 1 and eng._tick > ck_tick
        if gap and len(self._replay) == self._replay.maxlen and not allow_partial:
            raise RuntimeError(
                f"recovery replay window ({self._replay.maxlen} batches) "
                f"does not reach back to checkpoint tick {ck_tick} "
                f"(earliest buffered tick {window_start}); lost flows would "
                f"come back stale — raise ElasticConfig.replay_window, "
                f"checkpoint more often, or pass allow_partial=True"
            )

        target = self._engine_for(plan.new_num_shards)

        def _install():
            self._carry_tables(eng, target)
            install_flow_state(target, merged, tick=eng._tick)
            return target.positions

        dt = measure_install_time(_install)
        t_cp = self.fcfg.t_cp_s
        ok = hardware_model.install_time_ok(dt, t_cp) if t_cp else True
        rec = ReshardRecord(
            tick=eng._tick, old_shards=old_S, new_shards=plan.new_num_shards,
            reason="recovery", migrated_flows=int(len(merged["fids"])),
            moved_flows=int(
                reshard_moves(merged["fids"], old_S, plan.new_num_shards).sum()
            ),
            install_s=dt, t_cp_s=t_cp, churn_ok=ok,
            failed_shards=plan.failed,
            restored_flows=int(lost_mask.sum()),
        )
        if not ok:
            rec.error = (
                f"recovery install {dt:.6f}s exceeded t_cp {t_cp:.6f}s "
                f"(Eq. 18); committed anyway — a dead shard is worse"
            )
        self._commit(target)
        self._failed.clear()
        # restore tenant billing for flows that only exist in the checkpoint
        ck_tenants = {
            int(k): v for k, v in ck_meta.get("tenant_of", {}).items()
        }
        for fid in restored["fids"].tolist():
            self._tenant_of.setdefault(fid, ck_tenants.get(
                fid, self.ecfg.default_tenant))
        self._rebuild_tenant_counts()

        # bounded replay: re-ingest post-checkpoint packets of LOST keys
        # only (survivors' rows are already current) through the new
        # topology, preserving the original batch order
        replayed = 0
        for btick, fids, toks in replayable:
            mask = np.isin(flow_shard(fids, old_S),
                           np.asarray(sorted(failed_set)))
            if mask.any():
                target.ingest(fids[mask], toks[mask])
                replayed += int(mask.sum())
        rec.replayed_packets = replayed
        self.reshard_history.append(rec)
        return rec

    def _recovery_checkpoint(self) -> Tuple[Dict, Dict]:
        if self._last_ckpt is not None:
            return self._last_ckpt
        snap, extra, _ = self._ckpt.restore(snapshot_template(self.engine))
        return snap, extra["elastic"]

    # ------------------------------------------------------------------
    # admission control (per-tenant budgets from the ResourceLedger)
    # ------------------------------------------------------------------
    def register_tenant(self, spec: TenantSpec) -> None:
        self.tenants[spec.name] = spec
        self._record_admission_entries()

    def tenant_budget_flows(self, name: str) -> int:
        """Tenant flow budget derived from the ledger's sharding entry:
        ``share × aggregate capacity``, additionally bounded by the share
        of the aggregate Eq. 11 byte budget."""
        t = self.tenants[name]
        eng = self.engine
        entry = next(
            (e for e in self.program.ledger.entries
             if e.stage == "flow-table-sharding"), None,
        )
        budget_bytes = (
            entry.budget * eng.num_shards if entry is not None
            else eng.aggregate_state_budget_bytes
        )
        by_flows = int(t.share * eng.aggregate_capacity)
        by_bytes = int(t.share * budget_bytes // eng.per_flow_state_bytes())
        return max(1, min(by_flows, by_bytes))

    def tenant_resident(self, name: str) -> int:
        return self._tenant_count.get(name, 0)

    def _record_admission_entries(self) -> None:
        ledger = self.program.ledger
        ledger.entries = [
            e for e in ledger.entries if e.stage != "admission-control"
        ]
        for t in sorted(self.tenants.values(),
                        key=lambda t: (-t.priority, t.name)):
            ledger.add(
                "admission-control", f"tenant[{t.name}]-flows",
                used=self.tenant_resident(t.name),
                budget=self.tenant_budget_flows(t.name),
                detail=(
                    f"priority {t.priority}, share {t.share:g} of "
                    f"{self.engine.aggregate_capacity}-flow aggregate; "
                    f"shed {self.shed_flows.get(t.name, 0)} flow(s) / "
                    f"{self.shed_packets.get(t.name, 0)} packet(s)"
                ),
            )

    def _rebuild_tenant_counts(self) -> None:
        resident = set(self.engine.flow_ids())
        self._tenant_of = {
            f: t for f, t in self._tenant_of.items() if f in resident
        }
        counts: Dict[str, int] = {}
        for t in self._tenant_of.values():
            counts[t] = counts.get(t, 0) + 1
        self._tenant_count = counts

    def _shed_victim(self, below_priority: int) -> Optional[int]:
        """Evict one resident flow of the lowest-priority tenant strictly
        below ``below_priority`` (deterministic: smallest fid).  Returns
        the evicted fid, or None when no lower-priority tenant has flows."""
        candidates = sorted(
            (t.priority, t.name) for t in self.tenants.values()
            if t.priority < below_priority and self._tenant_count.get(t.name, 0)
        )
        if not candidates:
            return None
        _, victim_tenant = candidates[0]
        fid = min(f for f, t in self._tenant_of.items() if t == victim_tenant)
        self.engine.evict(fid)
        del self._tenant_of[fid]
        self._tenant_count[victim_tenant] -= 1
        self.shed_flows[victim_tenant] = (
            self.shed_flows.get(victim_tenant, 0) + 1
        )
        return fid

    def _admit_mask(self, flow_ids: np.ndarray, tenant) -> np.ndarray:
        n = len(flow_ids)
        if tenant is None:
            names = [self.ecfg.default_tenant] * n
        elif isinstance(tenant, str):
            names = [tenant] * n
        else:
            names = [str(t) for t in tenant]
            if len(names) != n:
                raise ValueError(
                    f"per-packet tenant list has {len(names)} entries for "
                    f"{n} packets"
                )
        unknown = sorted(set(names) - set(self.tenants))
        if unknown:
            raise KeyError(
                f"unknown tenant(s) {unknown}; register a TenantSpec "
                f"(registered: {sorted(self.tenants)})"
            )
        self._rebuild_tenant_counts()
        eng = self.engine
        headroom = eng.aggregate_capacity - eng.resident_flows
        budgets = {nm: self.tenant_budget_flows(nm) for nm in set(names)}
        counts = dict(self._tenant_count)

        # one decision per NEW flow, highest-priority tenants first so the
        # lowest-priority tenants are the ones shed under pressure
        order = []
        seen = set()
        for i, (fid, nm) in enumerate(zip(flow_ids.tolist(), names)):
            if fid in self._tenant_of or fid in seen:
                continue
            seen.add(fid)
            order.append((-self.tenants[nm].priority, i, fid, nm))
        decided: Dict[int, bool] = {}
        for _, _, fid, nm in sorted(order):
            ok = counts.get(nm, 0) < budgets[nm] and headroom > 0
            if not ok and headroom <= 0 and counts.get(nm, 0) < budgets[nm]:
                # global pressure: shed a strictly lower-priority tenant's
                # flow to make room for this one
                if self._shed_victim(self.tenants[nm].priority) is not None:
                    headroom += 1
                    ok = True
            decided[fid] = ok
            if ok:
                counts[nm] = counts.get(nm, 0) + 1
                headroom -= 1
                self._tenant_of[fid] = nm
                self._tenant_count[nm] = self._tenant_count.get(nm, 0) + 1
            else:
                # a shed NEW flow may retry next batch — count the shed
                # attempt now, packets below
                self.shed_flows[nm] = self.shed_flows.get(nm, 0) + 1
        admit = np.ones((n,), bool)
        for i, (fid, nm) in enumerate(zip(flow_ids.tolist(), names)):
            if not decided.get(fid, True):
                admit[i] = False
                self.shed_packets[nm] = self.shed_packets.get(nm, 0) + 1
        return admit
