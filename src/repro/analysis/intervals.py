"""Integer interval abstract interpretation over lowered score jaxprs
(DESIGN.md §16.2).

The int-lowering pass (:mod:`repro.compile.int_lowering`) *hand-derives*
worst-case bit widths for its accumulators — closed-form bounds recorded as
``int-lowering`` ledger entries against the 32-bit ALU budget.  Those
bounds are only as trustworthy as the algebra behind them.  This module
re-derives them *mechanically*: it walks the actual traced jaxpr of the
lowered score program equation by equation, propagating a sound
``[lo, hi]`` interval per value from the declared input ranges (the Eq. 39
horizon bound on the feature accumulator, the concrete compiled tables'
min/max, full dtype ranges for signatures), and proves that **no integer
equation can mathematically exceed its dtype** — i.e. no int32 wraparound
is reachable at the declared horizon, for any input the contract admits.

Where the hand-derivation and the machine proof disagree, the machine
wins and fails *louder*: a provable overflow raises :class:`AnalysisError`
at verify time — before any execution — rather than recording a ledger row
a waiver could silence.

Soundness over precision: any primitive the transfer functions don't model
falls back to the full dtype range of its outputs (never narrower than the
truth), so an unmodeled op can cause a false *alarm* but never a false
*proof*.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class AnalysisError(ValueError):
    """A static analysis proved (or could not exclude) a safety violation.

    Raised *before any execution* — by the interval analyzer on a provable
    integer overflow, or by the verify pass on a fatal lint finding.
    Carries the machine-readable report so drivers can render the audit."""

    def __init__(self, message: str, report=None):
        super().__init__(message)
        self.report = report


@dataclasses.dataclass(frozen=True)
class Interval:
    """A closed integer interval [lo, hi] in exact (Python int) arithmetic,
    so propagation itself can never overflow."""

    lo: int
    hi: int

    def __post_init__(self):
        if self.lo > self.hi:
            raise ValueError(f"empty interval [{self.lo}, {self.hi}]")

    @property
    def magnitude(self) -> int:
        return max(abs(self.lo), abs(self.hi))

    @property
    def signed_bits(self) -> int:
        """Bits of the smallest signed word holding every value."""
        if self.lo == 0 and self.hi == 0:
            return 1
        need = 1
        while not (-(1 << (need - 1)) <= self.lo and self.hi <= (1 << (need - 1)) - 1):
            need += 1
        return need

    def hull(self, other: "Interval") -> "Interval":
        return Interval(min(self.lo, other.lo), max(self.hi, other.hi))

    def __repr__(self) -> str:
        return f"[{self.lo}, {self.hi}]"


def _dtype_interval(dtype) -> Interval:
    dt = jnp.dtype(dtype)
    if dt == jnp.bool_:
        return Interval(0, 1)
    if jnp.issubdtype(dt, jnp.integer):
        info = jnp.iinfo(dt)
        return Interval(int(info.min), int(info.max))
    # float avals can appear around the audited region's boundary (e.g. the
    # unused f32 rule-weight input); give them a nominal range — overflow
    # checking below only applies to integer dtypes
    return Interval(-(1 << 62), 1 << 62)


def _fits(iv: Interval, dtype) -> bool:
    d = _dtype_interval(dtype)
    return d.lo <= iv.lo and iv.hi <= d.hi


@dataclasses.dataclass(frozen=True)
class EqnBound:
    """One equation's proven output range."""

    primitive: str
    dtype: str
    interval: Interval
    signed_bits: int
    overflows: bool  # mathematical range exceeds the result dtype
    path: str = ""


@dataclasses.dataclass
class IntervalReport:
    """The machine-checked width audit of one lowered score jaxpr."""

    bounds: List[EqnBound]
    inputs: List[EqnBound]  # declared input ranges (checked against dtype too)

    @property
    def max_signed_bits(self) -> int:
        """Widest word any *signed*-integer input or equation needs — the
        machine analog of the ledger's hand-derived accumulator widths.
        (Unsigned signature words are excluded: a full uint32 costs 33
        signed bits by construction, which is not an accumulator claim.)"""
        rows = [b for b in self.bounds + self.inputs
                if b.dtype.startswith("int")]
        return max((b.signed_bits for b in rows), default=1)

    def overflows(self) -> List[EqnBound]:
        return [b for b in self.bounds + self.inputs if b.overflows]

    def proves_no_overflow(self) -> bool:
        return not self.overflows()

    def as_dict(self) -> Dict:
        def row(b: EqnBound) -> Dict:
            return {
                "primitive": b.primitive, "dtype": b.dtype,
                "lo": b.interval.lo, "hi": b.interval.hi,
                "signed_bits": b.signed_bits, "overflows": b.overflows,
                "path": b.path,
            }

        return {
            "max_signed_bits": self.max_signed_bits,
            "proves_no_overflow": self.proves_no_overflow(),
            "inputs": [row(b) for b in self.inputs],
            "eqns": [row(b) for b in self.bounds],
        }


def _is_int_dtype(name: str) -> bool:
    return name.startswith(("int", "uint")) and name != "uint1"


@dataclasses.dataclass(frozen=True)
class SumBound:
    """A relational input fact: invar ``numerator`` is (element-wise) a sum
    of ``denominator``-many terms, each of magnitude ≤ ``term_bound``.

    This is the Eq. 39 streaming invariant — ``hidden_sum`` is
    *definitionally* the sum of ``count`` quantized features — and it is
    exactly the fact a non-relational interval domain loses at the mean
    division ``hidden_sum // max(count, 1)`` (the quotient is bounded by
    ``term_bound``, not by ``acc_bound / 1``).  Declaring it as part of
    the input contract keeps the analyzer sound *and* tight enough to
    reproduce the hand-derived matmul widths."""

    numerator: int  # flat invar index of the running sum
    denominator: int  # flat invar index of the term count
    term_bound: int  # per-term magnitude bound


# --------------------------------------------------------------------------
# transfer functions
# --------------------------------------------------------------------------

def _mul_iv(a: Interval, b: Interval) -> Interval:
    cands = [a.lo * b.lo, a.lo * b.hi, a.hi * b.lo, a.hi * b.hi]
    return Interval(min(cands), max(cands))


def _div_candidates(a: Interval, b: Interval, op) -> Interval:
    """Corner evaluation for division-family ops; divisor values of 0 are
    excluded (lax div by zero is undefined — the lowered program guards
    with max(count, 1))."""
    divisors = [d for d in (b.lo, b.hi, 1, -1) if b.lo <= d <= b.hi and d != 0]
    if not divisors:
        divisors = [1]
    cands = [op(n, d) for n in (a.lo, a.hi, 0) if a.lo <= n <= a.hi
             for d in divisors]
    return Interval(min(cands), max(cands))


def _tdiv(n: int, d: int) -> int:
    """Truncating division (lax.div semantics: round toward zero)."""
    q = abs(n) // abs(d)
    return q if (n >= 0) == (d >= 0) else -q


def _shift_right(a: Interval, k: Interval) -> Interval:
    ks = sorted({max(k.lo, 0), max(k.hi, 0)})
    cands = [v >> s for v in (a.lo, a.hi) for s in ks]
    return Interval(min(cands), max(cands))


def _shift_left(a: Interval, k: Interval) -> Interval:
    ks = sorted({max(k.lo, 0), max(k.hi, 0)})
    cands = [v << s for v in (a.lo, a.hi) for s in ks]
    return Interval(min(cands), max(cands))


def _reduce_size(in_aval, axes) -> int:
    n = 1
    for ax in axes:
        n *= int(in_aval.shape[ax])
    return max(n, 1)


def _dot_contract(eqn) -> int:
    (lhs_c, _), _ = eqn.params["dimension_numbers"]
    shape = eqn.invars[0].aval.shape
    n = 1
    for ax in lhs_c:
        n *= int(shape[ax])
    return max(n, 1)


def _sum_interval(term: Interval, n: int) -> Interval:
    lo = min(term.lo * n, 0)  # an empty/partial sum of positives is ≥ 0 only
    hi = max(term.hi * n, 0)  # when all terms share a sign; keep 0 in hull
    return Interval(min(lo, term.lo * n), max(hi, term.hi * n))


_PASSTHROUGH = {
    "broadcast_in_dim", "reshape", "squeeze", "slice", "transpose",
    "copy", "stop_gradient", "rev", "expand_dims", "dynamic_slice",
}


# --------------------------------------------------------------------------
# the interpreter
# --------------------------------------------------------------------------

def analyze_intervals(
    closed_jaxpr,
    input_ranges: List[Interval],
    relations: Tuple[SumBound, ...] = (),
) -> IntervalReport:
    """Propagate integer intervals through ``closed_jaxpr``.

    ``input_ranges`` gives one declared interval per flat invar (the
    analysis contract: the proof holds for every input inside its range);
    ``relations`` adds :class:`SumBound` facts between invars, applied at
    division sites via dataflow-origin tracking.
    Returns an :class:`IntervalReport`; equations whose *mathematical*
    result range exceeds their output dtype are marked ``overflows`` —
    after marking, the range is clipped to the dtype so downstream bounds
    stay meaningful (one overflow does not cascade into noise).
    """
    jaxpr = closed_jaxpr.jaxpr
    if len(input_ranges) != len(jaxpr.invars):
        raise ValueError(
            f"got {len(input_ranges)} input ranges for "
            f"{len(jaxpr.invars)} jaxpr inputs"
        )
    env: Dict = {}
    origins: Dict = {}
    report = IntervalReport(bounds=[], inputs=[])
    ctx = {(r.numerator, r.denominator): r.term_bound for r in relations}

    def clip_to_dtype(iv: Interval, dtype) -> Interval:
        d = _dtype_interval(dtype)
        return Interval(max(iv.lo, d.lo), min(iv.hi, d.hi))

    for i, (var, iv) in enumerate(zip(jaxpr.invars, input_ranges)):
        dname = str(var.aval.dtype)
        over = _is_int_dtype(dname) and not _fits(iv, var.aval.dtype)
        report.inputs.append(
            EqnBound("input", dname, iv, iv.signed_bits, over)
        )
        env[var] = clip_to_dtype(iv, var.aval.dtype) if over else iv
        origins[var] = i
    for var, const in zip(jaxpr.constvars, closed_jaxpr.consts):
        env[var] = _const_interval(const)

    _walk(jaxpr, env, origins, report, path="", ctx=ctx)
    return report


def _const_interval(x) -> Interval:
    arr = np.asarray(x)
    if arr.dtype == np.bool_:
        return Interval(int(arr.min()), int(arr.max())) if arr.size else Interval(0, 0)
    if np.issubdtype(arr.dtype, np.integer):
        return Interval(int(arr.min()), int(arr.max()))
    if arr.size == 0:
        return Interval(0, 0)
    return Interval(int(math.floor(float(arr.min()))),
                    int(math.ceil(float(arr.max()))))


def _read(env, v) -> Interval:
    from jax.extend import core as jex_core

    if isinstance(v, jex_core.Literal):
        return _const_interval(v.val)
    return env[v]


# ops that carry a value through unchanged element-wise (shape ops) or
# value-preserving enough for origin purposes (widening converts); a
# declared SumBound relation survives them
_ORIGIN_PRESERVING = _PASSTHROUGH | {"convert_element_type"}


def _origin_of(origins: Dict, v) -> Optional[int]:
    from jax.extend import core as jex_core

    if isinstance(v, jex_core.Literal):
        return None
    return origins.get(v)


def _walk(
    jaxpr, env: Dict, origins: Dict, report: IntervalReport, path: str,
    ctx: Dict,
) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        sub = _nested_jaxpr(eqn)
        if sub is not None:
            inner, consts = sub
            inner_env: Dict = {}
            inner_origins: Dict = {}
            for iv_var, outer in zip(inner.jaxpr.invars, eqn.invars):
                inner_env[iv_var] = _read(env, outer)
                o = _origin_of(origins, outer)
                if o is not None:
                    inner_origins[iv_var] = o
            for cv, c in zip(inner.jaxpr.constvars, inner.consts):
                inner_env[cv] = _const_interval(c)
            sub_path = f"{path}/{name}" if path else name
            _walk(inner.jaxpr, inner_env, inner_origins, report, sub_path, ctx)
            for v, ov in zip(inner.jaxpr.outvars, eqn.outvars):
                if _is_inner_literal(v):
                    env[ov] = _const_interval(v.val)
                else:
                    env[ov] = inner_env.get(v, _dtype_interval(ov.aval.dtype))
                    o = inner_origins.get(v)
                    if o is not None:
                        origins[ov] = o
            continue

        ivs = [_read(env, v) for v in eqn.invars]

        # SumBound relation: n // d where n is the declared running sum and
        # d ≥ 1 derives from the declared count — the quotient is bounded
        # by the per-term magnitude (|Σ_c terms| ≤ c·T ⇒ |trunc(Σ/c)| ≤ T)
        rel_hit = None
        if name == "div" and len(eqn.invars) == 2 and ivs[1].lo >= 1:
            key = (_origin_of(origins, eqn.invars[0]),
                   _origin_of(origins, eqn.invars[1]))
            if None not in key and key in ctx:
                t = ctx[key]
                rel_hit = Interval(-t, t)

        outs = [rel_hit] if rel_hit is not None else _transfer(eqn, name, ivs)
        for ov, iv in zip(eqn.outvars, outs):
            dname = str(ov.aval.dtype)
            over = _is_int_dtype(dname) and not _fits(iv, ov.aval.dtype)
            report.bounds.append(
                EqnBound(name, dname, iv, iv.signed_bits, over, path)
            )
            if over:
                d = _dtype_interval(ov.aval.dtype)
                iv = Interval(max(iv.lo, d.lo), min(iv.hi, d.hi))
            env[ov] = iv

        # origin propagation (single-output value-preserving ops, plus
        # max/min against a literal — the `max(count, 1)` guard)
        if len(eqn.outvars) == 1:
            o: Optional[int] = None
            if name in _ORIGIN_PRESERVING:
                o = _origin_of(origins, eqn.invars[0])
            elif name in ("max", "min") and len(eqn.invars) == 2:
                cands = [
                    _origin_of(origins, v)
                    for v, other in ((eqn.invars[0], eqn.invars[1]),
                                     (eqn.invars[1], eqn.invars[0]))
                    if _is_inner_literal(other) or _origin_of(origins, other) is None
                ]
                live = [c for c in cands if c is not None]
                if len(live) == 1:
                    o = live[0]
            if o is not None:
                origins[eqn.outvars[0]] = o


def _is_inner_literal(v) -> bool:
    from jax.extend import core as jex_core

    return isinstance(v, jex_core.Literal)


def _nested_jaxpr(eqn):
    """The single sub-jaxpr of call-like primitives the interpreter
    descends into transparently (jit / closed_call / remat / custom_*).
    Control-flow primitives with *multiple* bodies (cond, scan, while) are
    NOT modeled — they fall to the conservative dtype-range default."""
    from jax.extend import core as jex_core

    if eqn.primitive.name in (
        "jit", "closed_call", "remat", "checkpoint", "custom_jvp_call",
        "custom_vjp_call", "custom_vjp_call_jaxpr",
    ):
        for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
            sub = eqn.params.get(key)
            if isinstance(sub, jex_core.ClosedJaxpr):
                return sub, sub.consts
    return None


def _transfer(eqn, name: str, ivs: List[Interval]) -> List[Interval]:
    a = ivs[0] if ivs else Interval(0, 0)
    b = ivs[1] if len(ivs) > 1 else None

    if name == "add":
        return [Interval(a.lo + b.lo, a.hi + b.hi)]
    if name == "sub":
        return [Interval(a.lo - b.hi, a.hi - b.lo)]
    if name == "mul":
        return [_mul_iv(a, b)]
    if name == "div":
        return [_div_candidates(a, b, _tdiv)]
    if name == "rem":
        m = max(abs(b.lo), abs(b.hi), 1) - 1
        return [Interval(max(a.lo, -m), min(a.hi, m))]
    if name == "sign":
        return [Interval(-1 if a.lo < 0 else 0, 1 if a.hi > 0 else 0)]
    if name == "neg":
        return [Interval(-a.hi, -a.lo)]
    if name == "abs":
        return [Interval(0 if a.lo <= 0 <= a.hi else min(abs(a.lo), abs(a.hi)),
                         a.magnitude)]
    if name == "max":
        return [Interval(max(a.lo, b.lo), max(a.hi, b.hi))]
    if name == "min":
        return [Interval(min(a.lo, b.lo), min(a.hi, b.hi))]
    if name == "clamp":  # (min, operand, max)
        lo_iv, x, hi_iv = ivs
        return [Interval(max(x.lo, lo_iv.lo), min(max(x.hi, lo_iv.lo), hi_iv.hi))]
    if name == "shift_right_arithmetic":
        return [_shift_right(a, b)]
    if name == "shift_right_logical":
        out = _shift_right(a, b)
        return [out if a.lo >= 0 else _dtype_interval(eqn.outvars[0].aval.dtype)]
    if name == "shift_left":
        return [_shift_left(a, b)]
    if name in ("eq", "ne", "lt", "le", "gt", "ge", "is_finite"):
        return [Interval(0, 1)]
    if name in ("reduce_and", "reduce_or"):
        return [Interval(0, 1)]
    if name == "and":
        if a.lo >= 0 and b.lo >= 0:  # bitwise AND of non-negatives shrinks
            return [Interval(0, min(a.hi, b.hi))]
        return [_dtype_interval(eqn.outvars[0].aval.dtype)]
    if name in ("or", "xor"):
        if a.lo >= 0 and b.lo >= 0:
            bits = max(a.hi, b.hi).bit_length()
            return [Interval(0, (1 << bits) - 1)]
        return [_dtype_interval(eqn.outvars[0].aval.dtype)]
    if name == "not":
        if str(eqn.outvars[0].aval.dtype) == "bool":
            return [Interval(0, 1)]
        return [_dtype_interval(eqn.outvars[0].aval.dtype)]
    if name == "select_n":  # (pred, case0, case1, ...)
        out = ivs[1]
        for case in ivs[2:]:
            out = out.hull(case)
        return [out]
    if name == "reduce_sum":
        n = _reduce_size(eqn.invars[0].aval, eqn.params["axes"])
        return [_sum_interval(a, n)]
    if name in ("reduce_max", "reduce_min", "argmax", "argmin"):
        if name.startswith("reduce"):
            return [a]
        hi = max(int(s) for s in eqn.invars[0].aval.shape)
        return [Interval(0, max(hi - 1, 0))]
    if name == "dot_general":
        n = _dot_contract(eqn)
        return [_sum_interval(_mul_iv(a, b), n)]
    if name == "convert_element_type":
        return [a]
    if name in ("gather", "dynamic_slice"):
        return [a]
    if name == "concatenate":
        out = ivs[0]
        for other in ivs[1:]:
            out = out.hull(other)
        return [out]
    if name in ("scatter", "scatter_add", "dynamic_update_slice"):
        if name == "scatter_add":
            upd = ivs[2] if len(ivs) > 2 else Interval(0, 0)
            return [Interval(a.lo + min(upd.lo, 0), a.hi + max(upd.hi, 0))]
        out = ivs[0]
        for other in ivs[1:]:
            out = out.hull(other)
        return [out]
    if name in ("iota",):
        hi = max(int(s) for s in eqn.outvars[0].aval.shape)
        return [Interval(0, max(hi - 1, 0))]
    if name in _PASSTHROUGH:
        return [a for _ in eqn.outvars]
    # conservative default: full dtype range per output (sound, may alarm)
    return [_dtype_interval(ov.aval.dtype) for ov in eqn.outvars]


# --------------------------------------------------------------------------
# the Eq. 39 overflow proof over a lowered score program
# --------------------------------------------------------------------------

def score_input_ranges(
    plan, tables, rules, horizon: int
) -> Tuple[List[Interval], Tuple[SumBound, ...]]:
    """The declared input contract of the lowered score jaxpr, in the flat
    order :func:`repro.compile.int_lowering.score_jaxpr` traces its
    arguments: ``(tables, rules, hidden_sum, count, sig, sticky)``.

    Tables and rules are concrete compiled arrays → their exact min/max.
    ``hidden_sum`` gets the Eq. 39 accumulator bound — ``horizon`` tokens
    of the worst-case quantized feature (round-up included, clipped to the
    feature word) — which is exactly the contract the serving engine
    maintains; ``count`` is [0, horizon]; signatures span uint32.  The
    returned :class:`SumBound` states the streaming invariant that ties
    them (``hidden_sum`` is a sum of ``count`` per-token features), which
    the mean division needs to stay tight.
    """
    # |round(h·2^f)| ≤ floor(B_h·2^f + 0.5), clipped to the feature word
    per_tok = min(
        2 ** (plan.feature_bits - 1) - 1,
        int(math.floor(plan.feature_range * 2.0 ** plan.feature_frac + 0.5)),
    )
    acc = horizon * per_tok
    leaves, _ = jax.tree_util.tree_flatten((tables, rules))
    ranges = [_const_interval(np.asarray(leaf)) for leaf in leaves]
    hidden_idx = len(ranges)
    ranges.append(Interval(-acc, acc))  # hidden_sum
    ranges.append(Interval(0, horizon))  # count
    ranges.append(_dtype_interval(jnp.uint32))  # sig
    ranges.append(Interval(0, 1))  # sticky
    relations = (SumBound(hidden_idx, hidden_idx + 1, per_tok),)
    return ranges, relations


def prove_no_overflow(
    plan,
    tables,
    rules,
    *,
    horizon: Optional[int] = None,
    batch: int = 4,
    d_model: Optional[int] = None,
    ledger_entries=None,
) -> IntervalReport:
    """Statically prove the lowered score program cannot overflow int32 at
    the declared Eq. 39 horizon.

    Traces the program abstractly (:func:`~repro.compile.int_lowering
    .score_jaxpr` — nothing executes), seeds the interval interpreter with
    the Eq. 39 input contract, and checks every integer equation against
    its dtype.  On any provable overflow — including an input whose
    declared range already exceeds its word, the way an overflow-unsafe
    horizon manifests — raises :class:`AnalysisError` carrying the report.

    ``ledger_entries``: the ``int-lowering`` :class:`StageEntry` rows to
    cross-check.  The machine-derived max width must not exceed any
    hand-derived ``*-bits`` row's claim of the *same* quantity it audits
    (the widest accumulator); a disagreement means the closed-form algebra
    under-claimed and also raises :class:`AnalysisError`.
    """
    from repro.compile.int_lowering import score_jaxpr

    horizon = horizon if horizon is not None else plan.horizon
    d = d_model if d_model is not None else int(tables["cls_w"].shape[0])
    jaxpr = score_jaxpr(plan, tables, rules, batch, d)
    ranges, relations = score_input_ranges(plan, tables, rules, horizon)
    report = analyze_intervals(jaxpr, ranges, relations)
    bad = report.overflows()
    if bad:
        rows = "; ".join(
            f"{b.primitive}[{b.dtype}] needs {b.signed_bits} bits "
            f"(range {b.interval})"
            for b in bad[:4]
        )
        raise AnalysisError(
            f"interval analysis proves int32 overflow is reachable at "
            f"horizon={horizon}: {rows}",
            report=report,
        )
    if ledger_entries is not None:
        hand = [
            e for e in ledger_entries
            if e.stage == "int-lowering" and e.resource.endswith("-bits")
            and e.resource != "feature-frac-bits"
        ]
        if hand:
            claimed = max(int(e.used) for e in hand)
            if report.max_signed_bits > claimed:
                raise AnalysisError(
                    f"hand-derived ledger widths under-claim: closed-form "
                    f"max is {claimed} bits but the interval proof needs "
                    f"{report.max_signed_bits} bits",
                    report=report,
                )
    return report
