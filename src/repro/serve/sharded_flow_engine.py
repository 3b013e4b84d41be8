"""Sharded multi-device flow serving (DESIGN.md §12).

Scale-out of the :class:`~repro.serve.flow_engine.FlowEngine` flow table:
the flow-keyed Chimera state is partitioned into ``num_shards`` independent
shards, one per device on the ``data`` axis of a :func:`repro.launch.mesh
.make_flow_mesh` mesh, executed together under ``shard_map``.  Aggregate
resident-flow capacity and packets/sec scale with device count while every
per-flow guarantee of the single-device engine is preserved verbatim:

* **Routing** is deterministic and batch-independent —
  ``flow_shard(fid) % num_shards`` (a fixed splitmix64 mix, stable across
  processes and batch resizes), so a flow's packets always land on the
  same shard and its state never migrates.
* **Per-shard tables**: each shard owns a
  :class:`~repro.serve.flow_engine.FlowTableDirectory` (LRU + idle
  eviction, bounded capacity) and its slice of the slot-batched device
  state.  Sticky TCAM veto bits live in the shard that owns the flow.
* **One batched hot path**: ``ingest`` scatters each arrival round to its
  owner shards as a single ``(num_shards, lanes)`` launch of the *same*
  :func:`~repro.serve.flow_engine.make_flow_step` function the
  single-device engine jits — one ``shard_map``-ped call per round, one
  host gather of the stacked outputs, no per-shard host round trips.
  Because the per-lane math is the identical traced function, sharded
  replay is bit-identical to single-device replay of the same traffic.
* **Replicated control plane**: params and rule tables are placed
  replicated over the mesh; :meth:`ShardedFlowEngine.swap_tables` installs
  a new RuleSet / quantized weight table / audited ``ProgramDelta``
  atomically on *all* shards in one measured install, so the Eq. 18
  ``t_cp`` accounting covers the sharded case end-to-end.
* **Per-shard budgets**: the Eq. 11 flow-table byte budget is enforced per
  shard at construction; aggregate capacity is reported as
  ``num_shards x per-shard budget`` (and recorded in the program's
  :class:`~repro.compile.ledger.ResourceLedger` on deploy).
"""

from __future__ import annotations

import dataclasses
import math
import warnings
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import hardware_model
from repro.core import symbolic
from repro.core.hardware_model import DEFAULT_DATAPLANE
from repro.data.pipeline import arrival_rounds, flow_shard
from repro.launch.mesh import flow_shard_map
from repro.serve.flow_engine import (
    FlowEngineConfig,
    FlowStats,
    FlowTableDirectory,
    SwapRecord,
    init_flow_caches,
    make_flow_step,
    resolve_swap,
    stage_impls,
)
from repro.train import classifier as C


def make_sharded_step(ccfg: C.ClassifierConfig, n_slots: int, max_len: int,
                      mesh, int_plan=None):
    """The per-round flow step of every shard in one ``shard_map`` over the
    mesh ``data`` axis: the single-device :func:`make_flow_step` applied to
    each device's own table rows (leading shard axis), with params and
    rules replicated.  Module-level so it can be compiled ahead of time for
    a described mesh."""
    step = make_flow_step(ccfg, n_slots, max_len, int_plan=int_plan)

    def shard_step(params, rules, caches, positions, sig, hidden_sum,
                   vetoed, idx, tokens, fresh):
        # inside shard_map every table arg carries a leading shard axis
        # of size 1 (this device's rows); params/rules arrive replicated
        def sq(t):
            return jax.tree_util.tree_map(lambda x: x[0], t)

        caches, positions, sig, hidden_sum, vetoed, out = step(
            params, rules, sq(caches), positions[0], sig[0],
            hidden_sum[0], vetoed[0], idx[0], tokens[0], fresh[0],
        )

        def ex(t):
            return jax.tree_util.tree_map(lambda x: x[None], t)

        return (ex(caches), positions[None], sig[None], hidden_sum[None],
                vetoed[None], ex(out))

    return flow_shard_map(
        shard_step, mesh,
        in_specs=(P(), P(), P("data"), P("data"), P("data"), P("data"),
                  P("data"), P("data"), P("data"), P("data")),
        out_specs=(P("data"),) * 6,
    )


class ShardedFlowEngine:
    """Flow-table streaming inference partitioned across a device mesh.

    Drop-in for :class:`~repro.serve.flow_engine.FlowEngine` (same
    ``ingest`` / ``flow_scores`` / ``swap_tables`` / stats surface) with
    the table sharded over the mesh ``data`` axis.  ``fcfg.capacity`` and
    ``fcfg.state_budget_bytes`` are *per shard*; aggregate capacity is
    ``num_shards * fcfg.capacity``.
    """

    def __init__(
        self,
        ccfg: C.ClassifierConfig,
        params,
        rules: symbolic.RuleSet,
        fcfg: FlowEngineConfig = FlowEngineConfig(),
        *,
        mesh=None,
        num_shards: Optional[int] = None,
    ):
        from repro.kernels.dispatch import apply_kernel_backend
        from repro.launch.mesh import make_flow_mesh

        if fcfg.fused:
            # the fused flow_ingest megakernel is a single-device launch;
            # silently falling back to the per-round path here would make
            # `fused=True` a no-op — refuse loudly instead of quietly
            # serving at per-round throughput
            raise NotImplementedError(
                "FlowEngineConfig(fused=True) has no sharded implementation "
                "(the fused flow_ingest launch is single-device). Deploy "
                "with DeploySpec(engine='flow', flow=fcfg) for fused "
                "ingest, or drop fused=True to shard the per-round path."
            )
        if mesh is None:
            mesh = make_flow_mesh(num_shards)
        if "data" not in mesh.axis_names:
            raise ValueError(
                f"flow serving shards over 'data'; mesh axes are {mesh.axis_names}"
            )
        S = int(mesh.shape["data"])
        if math.prod(mesh.devices.shape) != S:
            raise ValueError(
                "flow tables shard only over 'data'; every other mesh axis "
                f"must have size 1 (got mesh shape {dict(mesh.shape)})"
            )
        if num_shards is not None and num_shards != S:
            raise ValueError(
                f"num_shards={num_shards} but the mesh 'data' axis has {S} devices"
            )
        self.mesh = mesh
        self.num_shards = S

        arch, self.backend = apply_kernel_backend(ccfg.arch, fcfg.backend)
        self.ccfg = dataclasses.replace(ccfg, arch=arch)
        self.stage_impls = stage_impls(self.ccfg, self.backend)
        self.fcfg = fcfg
        self.stats = FlowStats()
        self.swap_history: List[SwapRecord] = []
        self.program = None  # set by from_program

        # int-emulation: the lowered plan/tables are pure functions of
        # (ccfg, params, rules, horizon) — flow-independent, so they shard
        # trivially by REPLICATION: every device carries the same int32
        # tables (they ride the jitted step's replicated rules argument,
        # exactly like the float RuleSet), while only the flow state rows
        # split over 'data'.
        self._int_plan = None
        self._int_tables = None
        self._int_entries: List = []
        if self.backend == "int-emulation":
            from repro.compile.int_lowering import lower_scores
            from repro.compile.ledger import ResourceLedger

            self._int_plan, self._int_tables, self._int_entries = lower_scores(
                self.ccfg, params, rules, horizon=fcfg.horizon
            )
            deploy_ledger = ResourceLedger()
            deploy_ledger.extend(self._int_entries)
            deploy_ledger.raise_if_over()

        self._replicated = NamedSharding(mesh, P())
        self._row_sharded = NamedSharding(mesh, P("data"))
        self.params = jax.device_put(params, self._replicated)
        self.rules = jax.device_put(rules, self._replicated)
        if self._int_tables is not None:
            self._int_tables = jax.device_put(self._int_tables, self._replicated)

        # per-shard slot-batched state (capacity real slots + one scratch
        # slot absorbing padding lanes), slot-major as in FlowEngine, stacked
        # on a leading shard axis that shard_map splits over 'data'.  Built
        # by one jit whose output is row-sharded, so each device only ever
        # allocates its own rows.
        self._n_slots = fcfg.capacity + 1
        n = self._n_slots
        W, d = self.ccfg.sig_words, arch.d_model
        hs_dtype = jnp.int32 if self._int_plan is not None else jnp.float32

        def table():
            rows = (
                init_flow_caches(arch, n, fcfg.max_flow_tokens),
                jnp.zeros((n,), jnp.int32),
                jnp.zeros((n, W), jnp.uint32),
                jnp.zeros((n, d), hs_dtype),
                jnp.zeros((n,), bool),
            )
            return jax.tree_util.tree_map(
                lambda c: jnp.broadcast_to(c[None], (S,) + c.shape), rows
            )

        (self.caches, self.positions, self.sig, self.hidden_sum,
         self.vetoed) = jax.jit(table, out_shardings=self._row_sharded)()

        # one host-side directory per shard: allocation, LRU and idle
        # eviction are shard-local (a flow only ever competes for slots
        # with flows routed to the same shard)
        self.tables = [FlowTableDirectory(fcfg.capacity) for _ in range(S)]
        self._tick = 0

        # Eq. 11 budget, enforced PER SHARD at construction: each device's
        # table slice must fit the per-shard SRAM budget on its own
        budget = fcfg.state_budget_bytes or DEFAULT_DATAPLANE.sram_total_bits // 8
        self.state_budget_bytes = budget  # per shard
        hardware_model.check_flow_table_budget(
            self._n_slots, self.per_flow_state_bytes(), budget
        )

        self._jit_step = jax.jit(
            make_sharded_step(self.ccfg, self._n_slots, fcfg.max_flow_tokens,
                              mesh, self._int_plan),
            donate_argnums=(2, 3, 4, 5, 6),
        )

    def jit_entry_points(self):
        """Named jitted hot-path callables, for the retrace sentry."""
        return {"step": self._jit_step}

    # ------------------------------------------------------------------
    # compiled-program deployment (deprecated shim — DESIGN.md §17.4)
    # ------------------------------------------------------------------
    @classmethod
    def from_program(
        cls,
        program,
        fcfg: FlowEngineConfig = FlowEngineConfig(),
        *,
        mesh=None,
        num_shards: Optional[int] = None,
    ) -> "ShardedFlowEngine":
        """Deprecated: deploy through the one front door instead —
        ``program.deploy(DeploySpec(engine="sharded", flow=fcfg,
        num_shards=..., mesh=...))``."""
        warnings.warn(
            "ShardedFlowEngine.from_program is deprecated; use "
            "DataplaneProgram.deploy(DeploySpec(engine='sharded', "
            "flow=fcfg, num_shards=..., mesh=...)) — the shim will be "
            "removed one release cycle after DeploySpec landed "
            "(DESIGN.md §17.4)",
            DeprecationWarning, stacklevel=2,
        )
        from repro.serve.deploy import build_sharded_engine

        return build_sharded_engine(
            program, fcfg, mesh=mesh, num_shards=num_shards
        )

    # ------------------------------------------------------------------
    # routing + state accounting
    # ------------------------------------------------------------------
    def shard_of(self, fid: int) -> int:
        """Owner shard of a flow ID (deterministic, batch-independent)."""
        return int(flow_shard([fid], self.num_shards)[0])

    def _step_rules(self):
        """The replicated ``rules`` argument of the jitted step: the packed
        RuleSet, paired with the lowered int tables under int-emulation."""
        if self._int_plan is not None:
            return (self.rules, self._int_tables)
        return self.rules

    def per_flow_state_bytes(self) -> int:
        """Bytes of one flow-table entry (identical to the single-device
        engine's: Eq. 11/13 decode state + classifier aggregates)."""
        denom = self.num_shards * self._n_slots
        cache_bytes = sum(
            leaf.nbytes // denom
            for leaf in jax.tree_util.tree_leaves(self.caches)
        )
        aux = (
            self.sig.nbytes + self.hidden_sum.nbytes
            + self.positions.nbytes + self.vetoed.nbytes
        ) // denom
        return cache_bytes + aux + 8  # + host LRU timestamp

    def shard_state_bytes(self) -> int:
        """Allocated table bytes on ONE shard (what the per-shard Eq. 11
        budget check is held against)."""
        return hardware_model.flow_table_bytes(
            self._n_slots, self.per_flow_state_bytes()
        )

    def resident_state_bytes(self) -> int:
        """Aggregate allocated table bytes across all shards."""
        return self.num_shards * self.shard_state_bytes()

    @property
    def aggregate_capacity(self) -> int:
        return self.num_shards * self.fcfg.capacity

    @property
    def aggregate_state_budget_bytes(self) -> int:
        return self.num_shards * self.state_budget_bytes

    @property
    def resident_flows(self) -> int:
        return sum(t.resident for t in self.tables)

    def resident_flows_per_shard(self) -> List[int]:
        return [t.resident for t in self.tables]

    def flow_ids(self) -> List[int]:
        return [f for t in self.tables for f in t.slot_of]

    # ------------------------------------------------------------------
    # eviction (shard-local, aggregated stats)
    # ------------------------------------------------------------------
    def reset(self) -> None:
        """Clear every shard's flow table without touching the jitted step
        (device state is lazily zeroed on slot reuse, as single-device)."""
        for t in self.tables:
            t.reset()
        self._tick = 0
        self.stats = FlowStats()

    def evict(self, fid: int) -> bool:
        return self.tables[self.shard_of(fid)].evict(fid)

    def evict_idle(self) -> int:
        if not self.fcfg.idle_timeout:
            return 0
        horizon = self._tick - self.fcfg.idle_timeout
        n = 0
        for t in self.tables:
            for fid in t.idle_victims(horizon):
                t.evict(fid)
                self.stats.flows_evicted_idle += 1
                n += 1
        return n

    # ------------------------------------------------------------------
    # ingest
    # ------------------------------------------------------------------
    def ingest(self, flow_ids: np.ndarray, tokens: np.ndarray) -> Dict[str, np.ndarray]:
        """Stream one batch of packet arrivals through the sharded table.

        Same contract as :meth:`FlowEngine.ingest` — per-packet outputs
        aligned with the input arrival order, same-flow packets serialized,
        distinct flows vectorized — except each arrival round launches ONE
        ``(num_shards, lanes)`` shard_map-ped step covering every shard.
        """
        flow_ids = np.asarray(flow_ids)
        tokens = np.asarray(tokens, np.int32)
        Pk, pkt_len = tokens.shape
        assert flow_ids.shape == (Pk,), (flow_ids.shape, Pk)
        self._tick += 1
        self.stats.ticks += 1
        call = self._tick
        with TraceAnnotation("flow.resolve", call=call):
            owners = flow_shard(flow_ids, self.num_shards)

            # touch resident flows in this batch BEFORE the idle sweep and any
            # allocation (same victim-selection contract as the single-device
            # engine: flows with packets pending here are not eviction victims
            # unless their shard is over-subscribed within this very batch)
            for fid, own in zip(flow_ids.tolist(), owners.tolist()):
                self.tables[own].touch(fid, self._tick)
            self.evict_idle()

            slots = np.empty((Pk,), np.int32)
            fresh = np.zeros((Pk,), bool)
            for i, (fid, own) in enumerate(zip(flow_ids.tolist(), owners.tolist())):
                slot, fr, evicted = self.tables[own].slot_for(fid, self._tick)
                slots[i], fresh[i] = slot, fr
                if fr:
                    self.stats.flows_created += 1
                if evicted:
                    self.stats.flows_evicted_lru += 1

        # shard-local arrival rounds, flattened to fixed-width lane chunks;
        # chunk k of every shard rides the same device launch
        lanes = self.fcfg.lanes
        scratch = self.fcfg.capacity
        with TraceAnnotation("flow.pack", call=call):
            per_shard_chunks: List[List[np.ndarray]] = []
            for s in range(self.num_shards):
                pkt_idx = np.nonzero(owners == s)[0]
                chunks: List[np.ndarray] = []
                for round_lanes in arrival_rounds(slots[pkt_idx].tolist()):
                    sel = pkt_idx[round_lanes]
                    for c0 in range(0, len(sel), lanes):
                        chunks.append(sel[c0 : c0 + lanes])
                per_shard_chunks.append(chunks)
            n_steps = max((len(c) for c in per_shard_chunks), default=0)

        out_trust = np.empty((Pk,), np.float32)
        out_veto = np.empty((Pk,), bool)
        out_pred = np.empty((Pk,), np.int32)
        out_s_nn = np.empty((Pk,), np.float32)
        out_s_sym = np.empty((Pk,), np.float32)
        out_sig = np.zeros((Pk, self.ccfg.sig_words), np.uint32)

        for k in range(n_steps):
            with TraceAnnotation("flow.pack", call=call):
                idx = np.full((self.num_shards, lanes), scratch, np.int32)
                tok = np.zeros((self.num_shards, lanes, pkt_len), np.int32)
                fr = np.zeros((self.num_shards, lanes), bool)
                chunk_of: List[Optional[np.ndarray]] = [None] * self.num_shards
                for s, chunks in enumerate(per_shard_chunks):
                    if k < len(chunks):
                        sel = chunks[k]
                        n = len(sel)
                        idx[s, :n] = slots[sel]
                        tok[s, :n] = tokens[sel]
                        fr[s, :n] = fresh[sel]
                        chunk_of[s] = sel
            with TraceAnnotation("flow.launch", call=call, width=lanes, chunks=1):
                args = tuple(jax.device_put(a, self._row_sharded)
                             for a in (idx, tok, fr))
                with TraceAnnotation("flow.dispatch", call=call):
                    (self.caches, self.positions, self.sig, self.hidden_sum,
                     self.vetoed, out) = self._jit_step(
                        self.params, self._step_rules(), self.caches,
                        self.positions, self.sig, self.hidden_sum,
                        self.vetoed, *args,
                    )
            self.stats.rounds += 1
            # ONE stacked gather per round across every shard (no per-shard
            # host round trips)
            with TraceAnnotation("flow.finalize", call=call):
                with TraceAnnotation("flow.wait", call=call):
                    jax.block_until_ready(out)
                trust = np.asarray(out["trust"], np.float32)
                hard = np.asarray(out["hard_hit"])
                pred = np.asarray(jnp.argmax(out["class_logits"], -1), np.int32)
                s_nn = np.asarray(out["s_nn"], np.float32)
                s_sym = np.asarray(out["s_sym"], np.float32)
                sig_rows = np.asarray(out["sig"])
                for s, sel in enumerate(chunk_of):
                    if sel is None:
                        continue
                    n = len(sel)
                    out_trust[sel] = trust[s, :n]
                    out_veto[sel] = hard[s, :n]
                    out_pred[sel] = pred[s, :n]
                    out_s_nn[sel] = s_nn[s, :n]
                    out_s_sym[sel] = s_sym[s, :n]
                    out_sig[sel] = sig_rows[s, :n]
        self.stats.packets += Pk
        return {
            "flow_ids": flow_ids,
            "trust": out_trust,
            "vetoed": out_veto,
            "pred": out_pred,
            "s_nn": out_s_nn,
            "s_sym": out_s_sym,
            "sig": out_sig,
        }

    # ------------------------------------------------------------------
    # per-flow snapshot
    # ------------------------------------------------------------------
    def flow_scores(self, fid: int) -> Dict[str, float]:
        """Current scores for a resident flow (control-plane read path;
        reads the owner shard's table rows)."""
        s = self.shard_of(fid)
        slot = self.tables[s].slot_of[fid]
        if self._int_plan is not None:
            from repro.compile.int_lowering import dequantize_scores
            from repro.kernels.dispatch import resolve

            out, _ = resolve("flow_score", "int-emulation")(
                self._int_plan, self._int_tables, self.rules,
                self.hidden_sum[s, slot][None], self.positions[s, slot][None],
                self.sig[s, slot][None], self.vetoed[s, slot][None],
            )
            out = dequantize_scores(self._int_plan, out)
        else:
            pooled = (
                self.hidden_sum[s, slot] / jnp.maximum(self.positions[s, slot], 1)
            )
            out, _ = C.streaming_scores(
                self.ccfg, self.params, self.rules,
                pooled[None], self.sig[s, slot][None], self.vetoed[s, slot][None],
            )
        return {
            "trust": float(out["trust"][0]),
            "vetoed": bool(out["hard_hit"][0]),
            "pred": int(jnp.argmax(out["class_logits"][0])),
            "s_nn": float(out["s_nn"][0]),
            "s_sym": float(out["s_sym"][0]),
            "tokens": int(self.positions[s, slot]),
        }

    # ------------------------------------------------------------------
    # two-timescale control-plane hook
    # ------------------------------------------------------------------
    def swap_tables(
        self,
        ruleset: Optional[symbolic.RuleSet] = None,
        weights: Optional[jax.Array] = None,
        weight_spec=None,
        delta=None,
    ) -> SwapRecord:
        """Atomically install new compiled tables on EVERY shard (§3.6).

        Same request surface as :meth:`FlowEngine.swap_tables` (raw
        RuleSet / weight table, or an audited ``ProgramDelta``), resolved
        through the shared :func:`resolve_swap` shape check.  The install
        replicates the new tables to all mesh devices inside one measured
        ``atomic_swap`` — ``measure_install_time`` only returns once every
        shard's copy is device-ready, so the recorded ``install_s`` (and
        its Eq. 18 ``t_cp`` verdict) covers the whole sharded install, not
        the first device.
        """
        from repro.core.two_timescale import atomic_swap, measure_install_time

        old = self.rules
        new, source = resolve_swap(old, ruleset, weights, weight_spec, delta)
        installed = {}

        def _install():
            repl = jax.device_put(new, self._replicated)
            installed["rules"] = atomic_swap(old, repl)
            if self._int_plan is not None:
                # re-lower the soft-rule weight column (replicated, like the
                # RuleSet) so every shard's int score path reads the NEW
                # table; counted inside the measured install — the Eq. 18
                # budget covers everything the swap deploys on every device
                from repro.compile.int_lowering import requantize_rule_weights

                installed["tables"] = {
                    **self._int_tables,
                    "rule_w": jax.device_put(
                        requantize_rule_weights(
                            self._int_plan, installed["rules"].weights
                        ),
                        self._replicated,
                    ),
                }
            return installed["rules"]

        with TraceAnnotation("flow.swap", tick=self._tick):
            dt = measure_install_time(_install)
        self.rules = installed["rules"]
        if "tables" in installed:
            self._int_tables = installed["tables"]
        ok = (
            hardware_model.install_time_ok(dt, self.fcfg.t_cp_s)
            if self.fcfg.t_cp_s
            else True
        )
        rec = SwapRecord(
            tick=self._tick, install_s=dt, churn_ok=ok,
            t_cp_s=self.fcfg.t_cp_s, source=source,
        )
        self.swap_history.append(rec)
        return rec
