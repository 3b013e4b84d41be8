"""Flow-table streaming inference runtime (DESIGN.md §10).

The serving-side realization of Algorithm 1 for the paper's *traffic*
workload: a flow-keyed table maps 5-tuple-style flow IDs to bounded
per-flow Chimera state — the Eq. 11/13 O(L·d + m·d_v) decode state plus the
streaming classifier aggregates (running pooled features, cumulative packed
marker signature, sticky TCAM veto bit).  ``ingest(flow_ids, tokens)``
batches every touched flow through ONE jitted classifier step per arrival
round (same-flow packets are serialized by :func:`arrival_rounds`; distinct
flows vectorize), so millions of interleaved flows stream through a single
compiled program regardless of arrival order.

Trust on the hot path: every packet's cumulative signature is ternary-matched
against the installed :class:`RuleSet`; a hard TCAM hit marks the flow
*vetoed* for its lifetime and cascade fusion (Eq. 15) then pins S = 1
regardless of the neural score.

Two timescales: the data plane only ever *reads* the compiled tables inside
the jitted step; the control plane calls :meth:`FlowEngine.swap_tables`
between ticks to atomically install a new RuleSet / quantized SRAM weight
table.  Installs are shape-checked so the hot path never retraces (Eq. 18).

State is bounded twice over: per-flow by construction (Chimera decode state
never grows with flow length) and table-wide by an explicit byte budget
(:func:`repro.core.hardware_model.check_flow_table_budget`) with LRU and
idle eviction keeping the resident set inside ``capacity``.

Host spans: every ``ingest`` call records ``jax.profiler.TraceAnnotation``
spans at its layer boundaries, each with the call's tick as its ``call``
stat — ``flow.resolve`` (tick, LRU touches, idle sweep, slot assignment),
``flow.pack`` (arrival rounds into launch buffers), ``flow.launch`` (the
host-to-device puts of one launch) and, inside it, ``flow.dispatch`` (the
jitted call, which blocks while the runtime already holds its limit of
executions in flight), ``flow.finalize`` (reading the answers back and
unpacking them per packet) and, inside it, ``flow.wait`` (the host blocked
on the device's outputs).  ``swap_tables`` records ``flow.swap``.  The
spans land in the profiler's trace beside the device's ops when
``jax.profiler.trace`` is on, and cost about a microsecond each when it is
off.  The sharded engine records the same names.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import hardware_model
from repro.core import symbolic
from repro.core.hardware_model import DEFAULT_DATAPLANE
from repro.data.pipeline import arrival_rounds
from repro.models import model as M
from repro.train import classifier as C


@dataclasses.dataclass(frozen=True)
class FlowEngineConfig:
    capacity: int = 4096  # max resident flows (table entries)
    lanes: int = 256  # jit batch width per arrival round (padded, fixed)
    state_budget_bytes: int = 0  # 0 → DataplaneSpec shared-SRAM default
    idle_timeout: int = 0  # ticks without traffic before eviction (0 = off)
    max_flow_tokens: int = 1024  # KV length for non-Chimera archs only
    t_cp_s: float = 0.0  # control-plane epoch for Eq. 18 checks (0 = off)
    backend: Optional[str] = None  # kernel backend ("xla" | dispatch name)
    horizon: int = 1024  # Eq. 39 flow-length horizon (int-emulation lowering)
    fused: bool = False  # single-launch fused ingest (flow_ingest family)
    min_chunk_lanes: int = 8  # smallest padded width for tail arrival rounds
    ring_slots: int = 4  # host staging-ring depth (AsyncIngestPipeline)


@dataclasses.dataclass
class FlowStats:
    packets: int = 0
    ticks: int = 0
    rounds: int = 0
    flows_created: int = 0
    flows_evicted_lru: int = 0
    flows_evicted_idle: int = 0

    @property
    def flows_evicted(self) -> int:
        return self.flows_evicted_lru + self.flows_evicted_idle

    @property
    def eviction_rate(self) -> float:
        """Evictions per engine tick — the flow-churn pressure metric."""
        return self.flows_evicted / max(self.ticks, 1)


@dataclasses.dataclass(frozen=True)
class SwapRecord:
    tick: int
    install_s: float  # measured wall-clock install (device-ready, Eq. 18)
    churn_ok: bool  # Eq. 18: install completed within the control epoch
    t_cp_s: float = 0.0  # the control-plane epoch the install was held to
    source: str = "manual"  # "manual" | "delta" (audited ProgramDelta)


def make_flow_step(
    ccfg: C.ClassifierConfig, n_slots: int, max_len: int, int_plan=None, *,
    score_fn=None,
):
    """Build the jitted flow-table update step over ``n_slots`` table rows
    of decode caches built for ``max_len`` tokens (:func:`init_flow_caches`).

    One arrival round of lanes: gather the touched rows (lazily zeroing
    freshly-allocated slots), scan the packet tokens through
    :func:`repro.models.model.decode_hidden_step`, accumulate the packed
    marker signature, score via :func:`repro.train.classifier
    .streaming_scores`, scatter the rows back.

    The table is slot-major: every slotted cache leaf is stored
    ``(slots, layers, ...)`` (:func:`init_flow_caches`), like ``positions``,
    ``sig``, ``hidden_sum`` and ``vetoed``.  The gather ``c[idx]`` and the
    scatter ``c.at[idx].set`` then index the major axis of a row-major
    array, so the fused loop carries the table in the layout both read and
    write, with no per-chunk relayout of the whole table.  A leaf whose two
    minor dimensions are both narrower than a lane tile is stored with them
    folded into rows of 128 lanes (:func:`stored_slot_shape`), so the whole
    table enters and leaves a launch in that same layout; only the gathered
    rows are reshaped to the leaf's logical per-slot shape (from
    :func:`repro.models.model.init_caches`) and moved to the
    ``(layers, lanes, ...)`` view that ``decode_hidden_step`` takes, and
    back.  The fold keeps the row-major element order, so both reshapes
    are exact.  Module-level so
    :class:`FlowEngine` and :class:`repro.serve.sharded_flow_engine
    .ShardedFlowEngine` run the *same* traced function — one shard of a
    sharded table is exactly a single-device table, which is what makes
    sharded replay bit-identical to single-device replay.

    With an :class:`~repro.compile.int_lowering.IntScorePlan`, the score
    path runs the integer-lowered program instead (the ``int-emulation``
    backend): features are quantized at the Map boundary, ``hidden_sum`` is
    the int32 fixed-point accumulator, and the ``rules`` argument carries
    ``(rules, int_tables)`` so table swaps reuse the traced step.  The
    backbone scan is unchanged (float, bit-identical to the xla path).

    ``score_fn`` (float path only) swaps the streaming-score stage for a
    kernel implementation with the same canonical signature
    ``(params, rules, pooled, sig, sticky) -> (outputs, new_sticky)`` — the
    hook the ``flow_ingest`` Pallas backends use; ``None`` keeps the
    :func:`repro.train.classifier.streaming_scores` oracle.
    """
    arch = ccfg.arch
    if int_plan is not None:
        from repro.compile.int_lowering import dequantize_scores, quantize_features
        from repro.kernels.dispatch import resolve

        int_score = resolve("flow_score", "int-emulation")

    # each cache leaf at one slot, (layers, 1, ...): its logical row shape
    model = jax.eval_shape(
        lambda: M.init_caches(arch, 1, max_len, dtype=jnp.float32)
    )

    def slotted(c) -> bool:
        return c.ndim >= 2 and c.shape[0] == n_slots

    def step(params, rules, caches, positions, sig, hidden_sum, vetoed,
             idx, tokens, fresh):
        if int_plan is not None:
            rules, int_tables = rules

        # gather the touched rows; zero lanes holding newly-alloc'd flows
        # (slot reuse after eviction must look like a fresh table entry)
        def take(c, m):
            if not slotted(c):
                return c
            rows = c[idx].reshape(idx.shape + m.shape[:1] + m.shape[2:])
            f = fresh.reshape((-1,) + (1,) * (rows.ndim - 1))
            return jnp.moveaxis(jnp.where(f, jnp.zeros_like(rows), rows), 0, 1)

        cs = jax.tree_util.tree_map(take, caches, model)
        pos = jnp.where(fresh, 0, positions[idx])
        sg = jnp.where(fresh[:, None], jnp.uint32(0), sig[idx])
        hs_rows = hidden_sum[idx]
        hs = jnp.where(fresh[:, None], jnp.zeros_like(hs_rows), hs_rows)
        vt = jnp.where(fresh, False, vetoed[idx])

        def body(carry, tok_t):
            cs, pos, hs = carry
            h, cs = M.decode_hidden_step(arch, params["backbone"], tok_t, pos, cs)
            if int_plan is not None:  # the one float->int crossing (Map stage)
                h = quantize_features(int_plan, h)
            else:
                h = h.astype(jnp.float32)
            return (cs, pos + 1, hs + h), None

        (cs, pos, hs), _ = jax.lax.scan(body, (cs, pos, hs), tokens.T)
        sg = sg | C.packet_signature(ccfg, tokens)
        if int_plan is not None:
            out, vt = int_score(int_plan, int_tables, rules, hs, pos, sg, vt)
            out = dequantize_scores(int_plan, out)  # engine float contract
        else:
            pooled = hs / jnp.maximum(pos, 1)[:, None].astype(jnp.float32)
            if score_fn is not None:
                out, vt = score_fn(params, rules, pooled, sg, vt)
            else:
                out, vt = C.streaming_scores(ccfg, params, rules, pooled, sg, vt)
        out["sig"] = sg  # cumulative signature after this packet (drift stats)

        def put(c, u):
            if not slotted(c):
                return c
            rows = jnp.moveaxis(u, 1, 0).reshape(idx.shape + c.shape[1:])
            return c.at[idx].set(rows)

        caches = jax.tree_util.tree_map(put, caches, cs)
        positions = positions.at[idx].set(pos)
        sig = sig.at[idx].set(sg)
        hidden_sum = hidden_sum.at[idx].set(hs)
        vetoed = vetoed.at[idx].set(vt)
        return caches, positions, sig, hidden_sum, vetoed, out

    return step


# lanes of a TPU vector register tile (the minor dimension of an (8, 128) tile)
_LANE_TILE = 128


def stored_slot_shape(shape: Tuple[int, ...]) -> Tuple[int, ...]:
    """The per-slot shape a slotted cache leaf is stored in, from its
    logical per-slot shape: where the shape has two dimensions or more, both
    of the last two are narrower than a lane tile and their product is a
    multiple of it, those two are flattened into rows of 128 lanes,
    ``(..., 64, 64)`` -> ``(..., 32, 128)``; every other shape is kept.
    Otherwise the compiler gives a table leaf the compact layout with the
    slot axis minor, and every launch relays the whole table out for its
    gather and scatter by slot and back."""
    if len(shape) >= 2 and max(shape[-2:]) < _LANE_TILE:
        n = shape[-2] * shape[-1]
        if n % _LANE_TILE == 0:
            return shape[:-2] + (n // _LANE_TILE, _LANE_TILE)
    return shape


def init_flow_caches(arch, n_slots: int, max_len: int):
    """The flow table's zeroed decode caches, slot-major: each leaf of
    :func:`repro.models.model.init_caches` with its slot axis moved to the
    front, ``(layers, slots, ...)`` -> ``(slots, layers, ...)`` (the layout
    :func:`make_flow_step` gathers and scatters by slot), and each slot's
    row reshaped to :func:`stored_slot_shape` of its logical shape.  The
    elements and their row-major order are those of the slot-major leaf.
    Call it inside a jit so the layer-major zeros are never materialised."""

    def stored(c):
        c = jnp.moveaxis(c, 1, 0)
        return c.reshape(c.shape[:1] + stored_slot_shape(c.shape[1:]))

    return jax.tree_util.tree_map(
        stored, M.init_caches(arch, n_slots, max_len, dtype=jnp.float32)
    )


def stage_impls(ccfg: C.ClassifierConfig, backend: str,
                score_kernel: Optional[str] = None) -> Dict[str, str]:
    """What each stage of the flow step runs, so a backend name never hides
    an XLA fallback: ``decode`` (the backbone's per-token step) and
    ``score`` (streaming scores + TCAM veto; ``score_kernel`` names the
    Pallas backend of a fused ``flow_ingest`` score stage, if any)."""
    from repro.core.chimera_attention import decode_kernel_gap

    gap = decode_kernel_gap(ccfg.arch)
    decode = (f"decode_step ({ccfg.arch.chimera.backend})" if gap is None
              else f"xla ({gap})")
    if backend == "int-emulation":
        score = "int-emulation (int32 jnp)"
    elif score_kernel is not None:
        score = f"flow_ingest score kernel ({score_kernel})"
    else:
        score = "xla"
    return {"decode": decode, "score": score}


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


# chunk-axis bucket floor for fused launches: the chunk stack is padded to
# max(8, next_pow2(C)).  Padded chunks cost only host-buffer transfer (the
# traced n_chunks trip count skips them on device), while the floor pins the
# launch shape for every group of ≤ 8 chunks — so steady-state serving sees
# ONE trace per width instead of one per (width, chunk-count) pair.
_CHUNK_FLOOR = 8


def pack_width_groups(
    slots: np.ndarray, lanes: int, min_lanes: int = 8
) -> List[Tuple[int, List[np.ndarray]]]:
    """Pre-pack arrival rounds into width-bucketed chunk groups.

    The per-round hot path pads *every* round to the full ``lanes`` width,
    so a heavy-tail flow that forces 8 arrival rounds costs 8 full-width
    launches even when the late rounds hold a handful of packets.  Here
    each round is split into chunks of at most ``lanes`` packets, each
    chunk is assigned the smallest power-of-two width that holds it
    (clamped to ``[min_lanes, lanes]``), and *consecutive* chunks sharing a
    width are grouped so one fused launch scans them all.  Order across
    groups preserves round order — round r+1 of a flow always executes
    after round r (consecutive rounds can never merge: every flow in round
    r+1 also appears in round r by construction).

    Returns ``[(width, [packet-index arrays])]``.
    """
    groups: List[Tuple[int, List[np.ndarray]]] = []
    for round_lanes in arrival_rounds(list(slots)):
        for c0 in range(0, len(round_lanes), lanes):
            ch = np.asarray(round_lanes[c0 : c0 + lanes], np.intp)
            w = min(lanes, _next_pow2(max(len(ch), min_lanes)))
            if groups and groups[-1][0] == w:
                groups[-1][1].append(ch)
            else:
                groups.append((w, [ch]))
    return groups


def make_fused_ingest(
    ccfg: C.ClassifierConfig, n_slots: int, max_len: int, int_plan=None, *,
    score_fn=None,
):
    """Build the fused whole-batch ingest step (``flow_ingest`` family).

    One launch consumes a stack of pre-packed arrival-round chunks: the
    flow table stays resident on-device while an on-device loop runs the
    *identical* :func:`make_flow_step` body — gather by slot, token decode
    scan, streaming scores + TCAM veto, scatter-update — once per chunk.
    Because the loop body is the same traced function the per-round engine
    jits, the fused path is bit-exact to the per-round path by
    construction (the ``reference`` backend's conformance contract).

    Signature of the returned callable::

        fused(params, rules, caches, positions, sig, hidden_sum, vetoed,
              idx (C, w) int32, tokens (C, w, pkt_len) int32,
              fresh (C, w) bool, n_chunks () int32)
          -> (caches, positions, sig, hidden_sum, vetoed, outs)

    ``C`` may exceed ``n_chunks`` (the host pads the chunk axis to a
    power-of-two bucket so varying round counts never retrace); padding
    chunks are *skipped*, not masked — the loop trip count is the traced
    ``n_chunks`` scalar, so they cost nothing.  ``outs`` stacks the
    per-chunk score outputs on a leading ``C`` axis (rows ≥ ``n_chunks``
    stay zero).
    """
    step = make_flow_step(
        ccfg, n_slots, max_len, int_plan=int_plan, score_fn=score_fn
    )

    def fused(params, rules, caches, positions, sig, hidden_sum, vetoed,
              idx, tokens, fresh, n_chunks):
        C = idx.shape[0]
        out_ab = jax.eval_shape(
            step, params, rules, caches, positions, sig, hidden_sum, vetoed,
            idx[0], tokens[0], fresh[0],
        )[5]
        outs0 = jax.tree_util.tree_map(
            lambda a: jnp.zeros((C,) + a.shape, a.dtype), out_ab
        )

        def body(j, carry):
            caches, positions, sig, hidden_sum, vetoed, outs = carry

            def at(x):
                return jax.lax.dynamic_index_in_dim(x, j, 0, keepdims=False)

            caches, positions, sig, hidden_sum, vetoed, out = step(
                params, rules, caches, positions, sig, hidden_sum, vetoed,
                at(idx), at(tokens), at(fresh),
            )
            outs = jax.tree_util.tree_map(
                lambda buf, o: jax.lax.dynamic_update_index_in_dim(buf, o, j, 0),
                outs, out,
            )
            return caches, positions, sig, hidden_sum, vetoed, outs

        return jax.lax.fori_loop(
            0, n_chunks, body,
            (caches, positions, sig, hidden_sum, vetoed, outs0),
        )

    return fused


class _PendingIngest:
    """Handle for a dispatched-but-unharvested fused ingest batch.

    :meth:`FlowEngine._dispatch_fused` returns one of these *before*
    blocking on device results, so the async pipeline can pack and dispatch
    the next batch while the device chews on this one.  ``finalize()``
    blocks on each launch's outputs (the ``flow.wait`` span) and unpacks the
    per-chunk score stacks into the per-packet dict ``ingest`` returns.
    ``call`` is the engine tick of the ingest call, the ``call`` stat of
    every span the call records.
    """

    def __init__(self, engine, flow_ids, n_packets: int, launches, call: int):
        self.engine = engine
        self.flow_ids = flow_ids
        self.n_packets = n_packets
        self.launches = launches  # [(outs pytree, [chunk packet-index arrays])]
        self.call = call
        self._result: Optional[Dict[str, np.ndarray]] = None

    def finalize(self) -> Dict[str, np.ndarray]:
        if self._result is not None:
            return self._result
        with TraceAnnotation("flow.finalize", call=self.call):
            P = self.n_packets
            out = {
                "flow_ids": self.flow_ids,
                "trust": np.empty((P,), np.float32),
                "vetoed": np.empty((P,), bool),
                "pred": np.empty((P,), np.int32),
                "s_nn": np.empty((P,), np.float32),
                "s_sym": np.empty((P,), np.float32),
                "sig": np.zeros((P, self.engine.ccfg.sig_words), np.uint32),
            }
            for outs, chunks in self.launches:
                with TraceAnnotation("flow.wait", call=self.call):
                    jax.block_until_ready(outs)
                trust = np.asarray(outs["trust"], np.float32)
                hard = np.asarray(outs["hard_hit"])
                logits = np.asarray(outs["class_logits"])
                s_nn = np.asarray(outs["s_nn"], np.float32)
                s_sym = np.asarray(outs["s_sym"], np.float32)
                sig = np.asarray(outs["sig"])
                for j, ch in enumerate(chunks):
                    n = len(ch)
                    out["trust"][ch] = trust[j, :n]
                    out["vetoed"][ch] = hard[j, :n]
                    out["pred"][ch] = np.argmax(logits[j, :n], -1).astype(np.int32)
                    out["s_nn"][ch] = s_nn[j, :n]
                    out["s_sym"][ch] = s_sym[j, :n]
                    out["sig"][ch] = sig[j, :n]
        self._result = out
        return out


class FlowTableDirectory:
    """Host-side slot allocator for one flow table (or one shard of one):
    fid → slot map, free list, LRU timestamps.  Owns no device state — the
    caller pairs it with the slot-batched arrays the jitted step updates.
    Extracted from :class:`FlowEngine` so :class:`~repro.serve
    .sharded_flow_engine.ShardedFlowEngine` runs one directory per shard
    with identical allocation/eviction semantics."""

    def __init__(self, capacity: int):
        self.capacity = capacity
        self.slot_of: Dict[int, int] = {}
        self.fid_of: Dict[int, int] = {}
        self.free: List[int] = list(range(capacity - 1, -1, -1))
        self.last_seen = np.full((capacity,), np.iinfo(np.int64).max, np.int64)

    @property
    def resident(self) -> int:
        return len(self.slot_of)

    def touch(self, fid: int, tick: int) -> bool:
        """Refresh a resident flow's LRU stamp; False if not resident."""
        slot = self.slot_of.get(fid)
        if slot is None:
            return False
        self.last_seen[slot] = tick
        return True

    def slot_for(self, fid: int, tick: int) -> Tuple[int, bool, bool]:
        """Resolve ``fid`` to a table slot, allocating (free list, else LRU
        victim) when absent.  Returns ``(slot, fresh, lru_evicted)``."""
        slot = self.slot_of.get(fid)
        if slot is not None:
            self.last_seen[slot] = tick
            return slot, False, False
        evicted = False
        if self.free:
            slot = self.free.pop()
        else:
            slot = int(np.argmin(self.last_seen))  # LRU victim
            del self.slot_of[self.fid_of[slot]]
            evicted = True
        self.slot_of[fid] = slot
        self.fid_of[slot] = fid
        self.last_seen[slot] = tick
        return slot, True, evicted

    def evict(self, fid: int) -> bool:
        slot = self.slot_of.pop(fid, None)
        if slot is None:
            return False
        del self.fid_of[slot]
        self.last_seen[slot] = np.iinfo(np.int64).max
        self.free.append(slot)
        return True

    def idle_victims(self, horizon: int) -> List[int]:
        """Flows whose last packet predates ``horizon`` (exclusive)."""
        return [f for f, s in self.slot_of.items() if self.last_seen[s] < horizon]

    def reset(self) -> None:
        self.slot_of.clear()
        self.fid_of.clear()
        self.free = list(range(self.capacity - 1, -1, -1))
        self.last_seen[:] = np.iinfo(np.int64).max


def resolve_swap(
    old: symbolic.RuleSet,
    ruleset: Optional[symbolic.RuleSet],
    weights,
    weight_spec,
    delta,
) -> Tuple[symbolic.RuleSet, str]:
    """Resolve a ``swap_tables`` request into the RuleSet to install.

    Accepts either raw tables (``ruleset`` and/or ``weights`` — float or a
    quantized Eq. 19 SRAM table plus its ``FixedPointSpec``) or an audited
    :class:`repro.compile.ProgramDelta`, and shape/dtype-checks the result
    against the installed tables so the jitted ingest step is reused
    verbatim — a swap never recompiles the hot path.  Shared by
    :class:`FlowEngine` and the sharded engine (identical install
    semantics; only the placement differs).  Returns ``(new, source)``.
    """
    source = "manual"
    if delta is not None:
        if ruleset is not None or weights is not None:
            raise ValueError("pass either a ProgramDelta or raw tables, not both")
        ruleset = delta.ruleset
        weights, weight_spec = delta.weight_table, delta.weight_spec
        source = "delta"
    new = ruleset if ruleset is not None else old
    if weights is not None:
        w = (
            symbolic.decompile_table(weights, weight_spec)
            if weight_spec is not None
            else jnp.asarray(weights, jnp.float32)
        )
        new = symbolic.RuleSet(
            values=new.values, masks=new.masks,
            weights=w.astype(jnp.float32), hard=new.hard,
        )
    for name in ("values", "masks", "weights", "hard"):
        a, b = getattr(old, name), getattr(new, name)
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(
                f"swap_tables: {name} {b.shape}/{b.dtype} does not match "
                f"installed {a.shape}/{a.dtype}; shape-changing installs "
                f"would retrace the hot path (rebuild the engine instead)"
            )
    return new, source


def _engine_kwargs_from_program(program, backend: Optional[str] = None) -> Dict:
    """The constructor inputs every ``from_program`` deploy path shares
    (:class:`FlowEngine`, :class:`~repro.serve.sharded_flow_engine
    .ShardedFlowEngine`, :class:`~repro.serve.engine.ServeEngine`): the
    program's compiled classifier config, parameters and packed rules, plus
    the kernel backend — the program's pass-selected backend unless the
    deployment site overrides it."""
    return {
        "ccfg": program.ccfg,
        "params": program.params,
        "rules": program.rules,
        "backend": backend if backend is not None else program.backend,
    }


class FlowEngine:
    """Streaming per-flow classification over a bounded flow table.

    The device table holds ``capacity + 1`` slots (the last is scratch for
    padding lanes), every array slot-major: ``caches`` leaves
    ``(slots, layers, ...)`` in the lane-dense per-slot shape of
    :func:`stored_slot_shape`, ``positions``, ``sig``, ``hidden_sum`` and
    ``vetoed`` ``(slots, ...)``.  Each arrival round gathers and scatters
    its rows by slot along that major axis, so the fused ingest loop
    carries the table without relaying it out every chunk, and a launch
    takes it in as stored.
    """

    def __init__(
        self,
        ccfg: C.ClassifierConfig,
        params,
        rules: symbolic.RuleSet,
        fcfg: FlowEngineConfig = FlowEngineConfig(),
    ):
        from repro.kernels.dispatch import apply_kernel_backend

        arch, self.backend = apply_kernel_backend(ccfg.arch, fcfg.backend)
        self.ccfg = dataclasses.replace(ccfg, arch=arch)
        self.fcfg = fcfg
        self.params = params
        self.rules = rules
        self.stats = FlowStats()
        self.swap_history: List[SwapRecord] = []
        self.program = None  # set by from_program

        # int-emulation: lower the score path to fixed point.  The plan is a
        # pure function of (ccfg, params, rules, horizon), so program
        # save/load and swap installs need no extra serialized state.  A
        # >32-bit lowering raises BudgetError here — int32 emulation of a
        # wider program would silently wrap, so it is never deployable.
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

        # slot-batched state: capacity real slots + one scratch slot that
        # absorbs padding lanes (index == capacity); the caches slot-major
        self._n_slots = fcfg.capacity + 1
        self.caches = jax.jit(
            lambda: init_flow_caches(arch, self._n_slots, fcfg.max_flow_tokens)
        )()
        W, d = ccfg.sig_words, arch.d_model
        self.positions = jnp.zeros((self._n_slots,), jnp.int32)
        self.sig = jnp.zeros((self._n_slots, W), jnp.uint32)
        hs_dtype = jnp.int32 if self._int_plan is not None else jnp.float32
        self.hidden_sum = jnp.zeros((self._n_slots, d), hs_dtype)
        self.vetoed = jnp.zeros((self._n_slots,), bool)

        # host-side table bookkeeping
        self.table = FlowTableDirectory(fcfg.capacity)
        self._tick = 0

        # Eq. 11 budget check, enforced at construction so an over-provisioned
        # table cannot even be built; the check covers everything actually
        # allocated (capacity entries + the scratch lane)
        budget = fcfg.state_budget_bytes or DEFAULT_DATAPLANE.sram_total_bits // 8
        self.state_budget_bytes = budget
        hardware_model.check_flow_table_budget(
            self._n_slots, self.per_flow_state_bytes(), budget
        )

        self._jit_step = jax.jit(
            self._make_step(), donate_argnums=(2, 3, 4, 5, 6)
        )

        # fused single-launch ingest (flow_ingest kernel family): one jitted
        # callable shared by every (width, chunk-bucket) shape — the pow2
        # bucketing in _dispatch_fused bounds its trace count.  The kernel
        # backends only differ in the score stage; xla / int-emulation fall
        # back to the reference builder (same fused structure, oracle
        # scores), so --fused composes with every backend.
        self._jit_fused = None
        self._staging: Dict[Tuple[int, int, int, int], Dict[str, np.ndarray]] = {}
        self.stage_impls = stage_impls(self.ccfg, self.backend)
        if fcfg.fused:
            from repro.kernels import autotune
            from repro.kernels.dispatch import resolve

            fam_backend = (
                self.backend
                if self.backend in ("pallas-tpu", "pallas-interpret")
                else "reference"
            )
            tiles = None
            if fam_backend != "reference":
                self.stage_impls = stage_impls(
                    self.ccfg, self.backend, score_kernel=fam_backend
                )
                tiles = autotune.get_tiles(
                    "flow_ingest", self.flow_ingest_dims(), fam_backend,
                    spec=hardware_model.device_tpu_spec(),
                )
            self._jit_fused = jax.jit(
                resolve("flow_ingest", fam_backend)(
                    self.ccfg, self._n_slots, fcfg.max_flow_tokens,
                    int_plan=self._int_plan, tiles=tiles,
                ),
                donate_argnums=(2, 3, 4, 5, 6),
            )

    def jit_entry_points(self) -> Dict[str, Any]:
        """Named jitted hot-path callables, for the retrace sentry
        (:class:`repro.analysis.retrace_sentry.RetraceSentry`)."""
        entries: Dict[str, Any] = {"step": self._jit_step}
        if self._jit_fused is not None:
            entries["fused"] = self._jit_fused
        return entries

    def flow_ingest_dims(self) -> Dict[str, int]:
        """Problem dims the autotuner keys the flow_ingest sweep on."""
        return {
            "lanes": self.fcfg.lanes,
            "d": self.ccfg.arch.d_model,
            "w_words": self.ccfg.sig_words,
            "rules": int(self.rules.weights.shape[0]),
            "n_classes": self.ccfg.n_classes,
        }

    def warm_fused(self, pkt_len: int, max_chunks: int = _CHUNK_FLOOR) -> int:
        """Pre-trace every fused launch shape traffic can produce.

        One dummy scratch-only launch per pow2 width in
        [min_chunk_lanes, lanes] at the chunk-bucket floor — after this,
        steady-state ingest never retraces (until a batch exceeds
        ``max_chunks`` same-width chunks, which escalates the bucket).
        Scratch-row launches don't perturb real flow state.  Returns the
        number of shapes traced.  Optional: serving works without it, at
        the cost of first-contact traces mid-stream.
        """
        if self._jit_fused is None:
            return 0
        scratch = self.fcfg.capacity
        c_pad = max(_CHUNK_FLOOR, _next_pow2(max_chunks))
        # pack_width_groups buckets a chunk to _next_pow2(max(len, min_lanes))
        # clamped to lanes, so the widths traffic can produce are the pow2s
        # from _next_pow2(min_chunk_lanes) up to lanes, plus lanes itself when
        # it is not a power of two.  Start at the rounded-up pow2 so a
        # non-pow2 min_chunk_lanes (e.g. 12) warms the real buckets (16,
        # 32, ...) instead of widths that never occur.
        widths = []
        w = min(
            self.fcfg.lanes, _next_pow2(max(self.fcfg.min_chunk_lanes, 1))
        )
        while w < self.fcfg.lanes:
            widths.append(w)
            w *= 2
        widths.append(self.fcfg.lanes)
        for w in widths:
            idx = jnp.full((c_pad, w), scratch, jnp.int32)
            tok = jnp.zeros((c_pad, w, pkt_len), jnp.int32)
            fr = jnp.zeros((c_pad, w), bool)
            (self.caches, self.positions, self.sig, self.hidden_sum,
             self.vetoed, _) = self._jit_fused(
                self.params, self._step_rules(), self.caches, self.positions,
                self.sig, self.hidden_sum, self.vetoed,
                idx, tok, fr, jnp.int32(0),
            )
        return len(widths)

    # ------------------------------------------------------------------
    # compiled-program deployment (deprecated shim — DESIGN.md §17.4)
    # ------------------------------------------------------------------
    @classmethod
    def from_program(
        cls, program, fcfg: FlowEngineConfig = FlowEngineConfig()
    ) -> "FlowEngine":
        """Deprecated: deploy through the one front door instead —
        ``program.deploy(DeploySpec(engine="flow", flow=fcfg))``."""
        warnings.warn(
            "FlowEngine.from_program is deprecated; use "
            "DataplaneProgram.deploy(DeploySpec(engine='flow', flow=fcfg)) "
            "— the shim will be removed one release cycle after DeploySpec "
            "landed (DESIGN.md §17.4)",
            DeprecationWarning, stacklevel=2,
        )
        from repro.serve.deploy import build_flow_engine

        return build_flow_engine(program, fcfg)

    # ------------------------------------------------------------------
    # state accounting
    # ------------------------------------------------------------------
    def per_flow_state_bytes(self) -> int:
        """Actual bytes of one flow-table entry: Chimera decode state
        (Eq. 11/13: S, Z, ring buffers, fill count) + classifier aggregates
        (signature words, pooled-feature accumulator, counters, veto bit)."""
        cache_bytes = sum(
            leaf.nbytes // self._n_slots
            for leaf in jax.tree_util.tree_leaves(self.caches)
        )
        aux = (
            self.sig.nbytes
            + self.hidden_sum.nbytes
            + self.positions.nbytes
            + self.vetoed.nbytes
        ) // self._n_slots
        return cache_bytes + aux + 8  # + host LRU timestamp

    def resident_state_bytes(self) -> int:
        """Total allocated flow-table bytes (capacity + the scratch lane) —
        constant under churn because nothing is allocated per-packet."""
        return hardware_model.flow_table_bytes(
            self._n_slots, self.per_flow_state_bytes()
        )

    @property
    def resident_flows(self) -> int:
        return self.table.resident

    def flow_ids(self) -> List[int]:
        return list(self.table.slot_of)

    # ------------------------------------------------------------------
    # jitted hot path
    # ------------------------------------------------------------------
    def _make_step(self):
        return make_flow_step(
            self.ccfg, self._n_slots, self.fcfg.max_flow_tokens,
            int_plan=self._int_plan,
        )

    def _step_rules(self):
        """The ``rules`` argument of the jitted step: the packed RuleSet,
        paired with the lowered int tables under int-emulation."""
        if self._int_plan is not None:
            return (self.rules, self._int_tables)
        return self.rules

    # ------------------------------------------------------------------
    # flow-table bookkeeping (host side)
    # ------------------------------------------------------------------
    def _slot_for(self, fid: int) -> Tuple[int, bool]:
        slot, fresh, evicted = self.table.slot_for(fid, self._tick)
        if evicted:
            self.stats.flows_evicted_lru += 1
        if fresh:
            self.stats.flows_created += 1
        return slot, fresh

    def reset(self) -> None:
        """Clear the flow table without touching the jitted step.

        Drops every resident flow and zeroes the stats; device state is NOT
        rewritten — reused slots are lazily zeroed by the per-lane ``fresh``
        flag, so a reset engine keeps its compiled hot path (benchmarks
        sweep scenarios on one engine instead of re-jitting per scenario)."""
        self.table.reset()
        self._tick = 0
        self.stats = FlowStats()

    def evict(self, fid: int) -> bool:
        """Drop a flow's table entry (state is lazily zeroed on slot reuse)."""
        return self.table.evict(fid)

    def evict_idle(self) -> int:
        """Evict flows idle for more than ``idle_timeout`` ticks."""
        if not self.fcfg.idle_timeout:
            return 0
        stale = self.table.idle_victims(self._tick - self.fcfg.idle_timeout)
        for fid in stale:
            self.table.evict(fid)
            self.stats.flows_evicted_idle += 1
        return len(stale)

    # ------------------------------------------------------------------
    # ingest
    # ------------------------------------------------------------------
    def ingest(self, flow_ids: np.ndarray, tokens: np.ndarray) -> Dict[str, np.ndarray]:
        """Stream one batch of packet arrivals through the flow table.

        ``flow_ids`` (P,) int — flow keys in arrival order (repeats allowed:
        same-flow packets are processed sequentially, distinct flows in
        parallel); ``tokens`` (P, pkt_len) int32.  Returns per-packet outputs
        aligned with the input order: ``trust``, ``vetoed``, ``pred``,
        ``s_nn``, ``s_sym`` reflecting each flow's state *after* its packet.

        With ``fcfg.fused`` the batch goes through the single-launch
        ``flow_ingest`` path (:meth:`_dispatch_fused`) instead of one jitted
        launch per arrival round; results are bit-identical by construction.
        """
        flow_ids = np.asarray(flow_ids)
        tokens = np.asarray(tokens, np.int32)
        P, pkt_len = tokens.shape
        assert flow_ids.shape == (P,), (flow_ids.shape, P)
        slots, fresh = self._resolve_slots(flow_ids)
        if self._jit_fused is not None:
            return self._dispatch_fused(flow_ids, tokens, slots, fresh).finalize()
        return self._ingest_rounds(flow_ids, tokens, slots, fresh)

    def _resolve_slots(self, flow_ids: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Host bookkeeping for one batch: tick, LRU touch, idle sweep, slot
        assignment.  Shared verbatim by the per-round and fused paths so both
        observe the identical eviction sequence."""
        self._tick += 1
        self.stats.ticks += 1
        with TraceAnnotation("flow.resolve", call=self._tick):
            # touch every already-resident flow in this batch BEFORE the idle
            # sweep and any allocation: eviction victims (idle or LRU) must come
            # from flows with no packets pending here, or a resident (possibly
            # vetoed) flow could lose its state on the very tick it transmits.
            # Only when the batch itself holds more distinct flows than the
            # table has entries is evicting an in-batch flow unavoidable (state
            # loss on eviction is inherent to a bounded table).
            for fid in set(flow_ids.tolist()):
                self.table.touch(fid, self._tick)
            self.evict_idle()

            P = len(flow_ids)
            slots = np.empty((P,), np.int32)
            fresh = np.zeros((P,), bool)
            for i, fid in enumerate(flow_ids.tolist()):
                slots[i], fresh[i] = self._slot_for(fid)
        return slots, fresh

    def _ingest_rounds(
        self, flow_ids: np.ndarray, tokens: np.ndarray,
        slots: np.ndarray, fresh: np.ndarray,
    ) -> Dict[str, np.ndarray]:
        """Legacy per-round hot path: one jitted launch per arrival round,
        every round padded to the full ``lanes`` width."""
        P, pkt_len = tokens.shape
        out_trust = np.empty((P,), np.float32)
        out_veto = np.empty((P,), bool)
        out_pred = np.empty((P,), np.int32)
        out_s_nn = np.empty((P,), np.float32)
        out_s_sym = np.empty((P,), np.float32)
        out_sig = np.zeros((P, self.ccfg.sig_words), np.uint32)

        lanes = self.fcfg.lanes
        scratch = self.fcfg.capacity
        call = self._tick
        with TraceAnnotation("flow.pack", call=call):
            rounds = arrival_rounds(slots.tolist())
        for round_lanes in rounds:
            for c0 in range(0, len(round_lanes), lanes):
                with TraceAnnotation("flow.pack", call=call):
                    chunk = round_lanes[c0 : c0 + lanes]
                    idx = np.full((lanes,), scratch, np.int32)
                    tok = np.zeros((lanes, pkt_len), np.int32)
                    fr = np.zeros((lanes,), bool)
                    n = len(chunk)
                    idx[:n] = slots[chunk]
                    tok[:n] = tokens[chunk]
                    fr[:n] = fresh[chunk]
                with TraceAnnotation("flow.launch", call=call, width=lanes, chunks=1):
                    args = (jnp.asarray(idx), jnp.asarray(tok), jnp.asarray(fr))
                    with TraceAnnotation("flow.dispatch", call=call):
                        (self.caches, self.positions, self.sig, self.hidden_sum,
                         self.vetoed, out) = self._jit_step(
                            self.params, self._step_rules(), self.caches,
                            self.positions, self.sig, self.hidden_sum,
                            self.vetoed, *args,
                        )
                self.stats.rounds += 1
                with TraceAnnotation("flow.finalize", call=call):
                    with TraceAnnotation("flow.wait", call=call):
                        jax.block_until_ready(out)
                    lanes_idx = np.asarray(chunk, np.intp)
                    out_trust[lanes_idx] = np.asarray(out["trust"], np.float32)[:n]
                    out_veto[lanes_idx] = np.asarray(out["hard_hit"])[:n]
                    out_pred[lanes_idx] = np.asarray(
                        jnp.argmax(out["class_logits"], -1), np.int32
                    )[:n]
                    out_s_nn[lanes_idx] = np.asarray(out["s_nn"], np.float32)[:n]
                    out_s_sym[lanes_idx] = np.asarray(out["s_sym"], np.float32)[:n]
                    out_sig[lanes_idx] = np.asarray(out["sig"])[:n]
        self.stats.packets += P
        return {
            "flow_ids": flow_ids,
            "trust": out_trust,
            "vetoed": out_veto,
            "pred": out_pred,
            "s_nn": out_s_nn,
            "s_sym": out_s_sym,
            "sig": out_sig,
        }

    def _dispatch_fused(
        self, flow_ids: np.ndarray, tokens: np.ndarray,
        slots: np.ndarray, fresh: np.ndarray,
        staging: Optional[Dict] = None,
    ) -> _PendingIngest:
        """Pack this batch's arrival rounds into width-bucketed chunk stacks
        and launch the fused kernel once per width group — then return
        WITHOUT blocking on device results.

        Width bucketing is the dispatch-cost fix: the per-round path pads
        every round to ``lanes``, so the long tail of small rounds (a flow's
        2nd..Nth packet in a batch) pays full-width compute.  Here a round's
        chunks get the smallest pow2 width ≥ its occupancy (floored at
        ``min_chunk_lanes``) and consecutive same-width chunks ride one
        launch.  The chunk axis is also pow2-padded (``fori_loop`` skips the
        padding — its trip count is the traced ``n_chunks``), so the jit
        trace count is bounded by O(log lanes · log chunks) shapes, not by
        traffic shape.

        ``staging`` lets :class:`~repro.serve.ingest_pipeline.AsyncIngestPipeline`
        substitute a ring slot's private buffer pool so host packing of
        batch k+1 never races the in-flight transfer of batch k.
        """
        P, pkt_len = tokens.shape
        lanes, scratch = self.fcfg.lanes, self.fcfg.capacity
        pool = self._staging if staging is None else staging
        launches = []
        # A buffer shape can recur non-consecutively within one batch: every
        # arrival round larger than ``lanes`` emits a full-width group then a
        # smaller tail, so the width sequence looks like [256, 64, 256, 64].
        # Reusing one buffer for both same-shape groups would overwrite data
        # an earlier launch's asynchronous host-to-device transfer may still
        # be reading, so the pool key carries a per-dispatch occurrence index
        # — each use gets its own buffer.  Across dispatches the same
        # (shape, occurrence) sequence maps back to the same buffers, and
        # finalize() (which materializes the launch outputs, hence runs after
        # the input transfers) has completed before a ring slot's pool is
        # reused, so cross-batch reuse stays race-free.
        uses: Dict[Tuple[int, int, int], int] = {}
        call = self._tick
        with TraceAnnotation("flow.pack", call=call):
            groups = pack_width_groups(slots, lanes, self.fcfg.min_chunk_lanes)
        for w, chunks in groups:
            with TraceAnnotation("flow.pack", call=call, width=w,
                                 chunks=len(chunks)):
                c_pad = max(_CHUNK_FLOOR, _next_pow2(len(chunks)))
                shape = (w, c_pad, pkt_len)
                occ = uses.get(shape, 0)
                uses[shape] = occ + 1
                key = (w, c_pad, pkt_len, occ)
                buf = pool.get(key)
                if buf is None:
                    buf = pool[key] = {
                        "idx": np.empty((c_pad, w), np.int32),
                        "tok": np.empty((c_pad, w, pkt_len), np.int32),
                        "fr": np.empty((c_pad, w), bool),
                    }
                idx, tok, fr = buf["idx"], buf["tok"], buf["fr"]
                idx.fill(scratch)
                tok.fill(0)
                fr.fill(False)
                for j, ch in enumerate(chunks):
                    n = len(ch)
                    idx[j, :n] = slots[ch]
                    tok[j, :n] = tokens[ch]
                    fr[j, :n] = fresh[ch]
            with TraceAnnotation("flow.launch", call=call, width=w,
                                 chunks=len(chunks)):
                args = (jnp.asarray(idx), jnp.asarray(tok), jnp.asarray(fr))
                with TraceAnnotation("flow.dispatch", call=call):
                    (self.caches, self.positions, self.sig, self.hidden_sum,
                     self.vetoed, outs) = self._jit_fused(
                        self.params, self._step_rules(), self.caches,
                        self.positions, self.sig, self.hidden_sum,
                        self.vetoed, *args, jnp.int32(len(chunks)),
                    )
            self.stats.rounds += len(chunks)
            launches.append((outs, chunks))
        self.stats.packets += P
        return _PendingIngest(self, flow_ids, P, launches, call)

    # ------------------------------------------------------------------
    # per-flow snapshot
    # ------------------------------------------------------------------
    def flow_scores(self, fid: int) -> Dict[str, float]:
        """Current scores for a resident flow (control-plane read path)."""
        slot = self.table.slot_of[fid]
        if self._int_plan is not None:
            from repro.compile.int_lowering import dequantize_scores
            from repro.kernels.dispatch import resolve

            out, _ = resolve("flow_score", "int-emulation")(
                self._int_plan, self._int_tables, self.rules,
                self.hidden_sum[slot][None], self.positions[slot][None],
                self.sig[slot][None], self.vetoed[slot][None],
            )
            out = dequantize_scores(self._int_plan, out)
        else:
            pooled = self.hidden_sum[slot] / jnp.maximum(self.positions[slot], 1)
            out, _ = C.streaming_scores(
                self.ccfg, self.params, self.rules,
                pooled[None], self.sig[slot][None], self.vetoed[slot][None],
            )
        return {
            "trust": float(out["trust"][0]),
            "vetoed": bool(out["hard_hit"][0]),
            "pred": int(jnp.argmax(out["class_logits"][0])),
            "s_nn": float(out["s_nn"][0]),
            "s_sym": float(out["s_sym"][0]),
            "tokens": int(self.positions[slot]),
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
        """Atomically install new compiled tables between ticks (§3.6).

        ``ruleset`` replaces the whole TCAM/SRAM rule table; ``weights``
        replaces only the soft-rule weight column — pass a float array, or a
        quantized SRAM table plus its ``FixedPointSpec`` as ``weight_spec``
        (decompiled on install, Eq. 19's table encoding).  ``delta`` installs
        an audited :class:`repro.compile.ProgramDelta` (the two-timescale
        slow path: controller → compile passes → here).  Shapes and dtypes
        must match the installed tables so the jitted ingest step is reused
        verbatim — a swap never recompiles the hot path.

        The install is measured end-to-end (``two_timescale.atomic_swap``
        blocks until the new tables are device-ready, Eq. 18's semantics;
        ``measure_install_time`` takes the wall clock) and the record flags
        a ``t_cp`` budget violation instead of silently succeeding.
        """
        from repro.core.two_timescale import atomic_swap, measure_install_time

        old = self.rules
        new, source = resolve_swap(old, ruleset, weights, weight_spec, delta)
        installed = {}

        def _install():
            installed["rules"] = atomic_swap(old, new)
            if self._int_plan is not None:
                # re-lower the soft-rule weight column so the int score path
                # reads the NEW table; counted inside the measured install —
                # the Eq. 18 budget covers everything the swap deploys
                from repro.compile.int_lowering import requantize_rule_weights

                installed["tables"] = {
                    **self._int_tables,
                    "rule_w": requantize_rule_weights(
                        self._int_plan, installed["rules"].weights
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
