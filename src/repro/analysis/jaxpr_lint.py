"""Pluggable jaxpr audit framework (DESIGN.md §16.1).

The repo's first jaxpr audit — ``assert_integer_jaxpr`` in
:mod:`repro.compile.int_lowering` — proved exactly one property (no float
ops in the lowered score path) with a hand-rolled recursive walker.  This
module promotes that walker into a general visitor over *every* equation of
a (recursively nested) jaxpr and turns the audits into pluggable checks
that share it:

* :class:`FloatOpCheck` — inexact (float/complex) operands, results,
  constvars or **literals** anywhere in an int-lowered path.
* :class:`HostCallbackCheck` — ``pure_callback`` / ``io_callback`` /
  ``debug_callback`` primitives inside a jitted hot path (a host
  round-trip per launch: correct, but never line-rate).
* :class:`WeakTypeCheck` — weak-typed operands meeting strongly-typed
  operands of a different dtype: the Python-scalar promotion hazard that
  silently upcasts an int32 hot path to float or widens accumulators.
* :func:`donation_safety` — donated-argument audit over a traced
  entry point: donated leaves must be able to alias an output (shape and
  dtype match), must not be donated twice, and must not also be passed as
  a non-donated argument (re-reading a donated buffer after dispatch is
  use-after-free on the device allocation).

The walker recurses through equation params into sub-jaxprs held in
arbitrarily nested tuples / lists / **dicts** (``cond`` branches, ``scan``
bodies, ``jit`` calls, ``custom_vjp`` closures, and any future primitive
that nests them deeper), which the old ``_walk_jaxpr`` only scanned one
container level deep.  ``compile.int_lowering`` re-exports the promoted
helpers so existing imports keep working.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

HOST_CALLBACK_PRIMITIVES = (
    "pure_callback",
    "io_callback",
    "debug_callback",
)


@dataclasses.dataclass(frozen=True)
class Finding:
    """One lint hit: which check fired, where, and why."""

    check: str  # check name, e.g. "float-ops"
    primitive: str  # primitive whose equation triggered the finding
    message: str  # human-readable context (dtype, operand kind, path)
    path: str = ""  # jaxpr nesting path, e.g. "scan/cond"

    def __str__(self) -> str:
        where = f" at {self.path}" if self.path else ""
        return f"[{self.check}] {self.primitive}{where}: {self.message}"


# --------------------------------------------------------------------------
# the walker (promoted from compile/int_lowering._walk_jaxpr, hardened)
# --------------------------------------------------------------------------

def _sub_jaxprs(value) -> Iterable[Tuple[object, bool]]:
    """Yield every (jaxpr, is_closed) reachable inside an eqn param value,
    recursing through arbitrarily nested tuples, lists and dicts."""
    from jax.extend import core as jex_core

    if isinstance(value, jex_core.ClosedJaxpr):
        yield value.jaxpr, True
    elif isinstance(value, jex_core.Jaxpr):
        yield value, False
    elif isinstance(value, (tuple, list)):
        for item in value:
            yield from _sub_jaxprs(item)
    elif isinstance(value, dict):
        for item in value.values():
            yield from _sub_jaxprs(item)


def walk_jaxpr(jaxpr, visit: Callable, path: str = "") -> None:
    """Apply ``visit(eqn, path)`` to every equation of ``jaxpr`` and of
    every sub-jaxpr reachable through equation params — however deeply the
    params nest them in tuples/lists/dicts (``cond`` branch tuples,
    ``scan``/``jit``/``while`` bodies, ``custom_vjp`` closures, ...).

    ``path`` accumulates the primitive nesting ("scan/cond") so findings
    can say *where* in the program they fired.
    """
    for eqn in jaxpr.eqns:
        visit(eqn, path)
        sub_path = f"{path}/{eqn.primitive.name}" if path else eqn.primitive.name
        for p in eqn.params.values():
            for sub, _ in _sub_jaxprs(p):
                walk_jaxpr(sub, visit, sub_path)


# --------------------------------------------------------------------------
# checks
# --------------------------------------------------------------------------

class LintCheck:
    """One pluggable audit: ``on_eqn`` sees every equation (with its
    nesting path), ``on_constvar`` every top-level constvar, ``finish``
    returns the accumulated findings."""

    name = "lint-check"

    def on_eqn(self, eqn, path: str) -> None:  # pragma: no cover - interface
        pass

    def on_constvar(self, var) -> None:  # pragma: no cover - interface
        pass

    def finish(self) -> List[Finding]:  # pragma: no cover - interface
        return []


def _aval_of(v):
    aval = getattr(v, "aval", None)
    return aval if aval is not None and hasattr(aval, "dtype") else None


def _is_literal(v) -> bool:
    from jax.extend import core as jex_core

    return isinstance(v, jex_core.Literal)


class FloatOpCheck(LintCheck):
    """No inexact (float/complex) dtype may appear in the audited jaxpr —
    not as an operand, a result, a constvar, or an eqn-level **literal**
    (a Python float closed over by e.g. a ``mul`` — the operand kind the
    pre-promotion audit reported only via its float output var, making a
    pure-literal crossing invisible when the output was integer)."""

    name = "float-ops"

    def __init__(self):
        self.findings: List[Finding] = []

    def _flag(self, kind: str, prim: str, dtype, path: str) -> None:
        self.findings.append(
            Finding(self.name, prim, f"{kind}[{dtype}]", path)
        )

    def on_eqn(self, eqn, path: str) -> None:
        prim = eqn.primitive.name
        seen = set()
        for v in eqn.invars:
            aval = _aval_of(v)
            if aval is None or not jnp.issubdtype(aval.dtype, jnp.inexact):
                continue
            kind = "literal" if _is_literal(v) else "operand"
            if (kind, str(aval.dtype)) not in seen:
                seen.add((kind, str(aval.dtype)))
                self._flag(kind, prim, aval.dtype, path)
        for v in eqn.outvars:
            aval = _aval_of(v)
            if aval is not None and jnp.issubdtype(aval.dtype, jnp.inexact):
                if ("result", str(aval.dtype)) not in seen:
                    seen.add(("result", str(aval.dtype)))
                    self._flag("result", prim, aval.dtype, path)

    def on_constvar(self, var) -> None:
        aval = _aval_of(var)
        if aval is not None and jnp.issubdtype(aval.dtype, jnp.inexact):
            self.findings.append(
                Finding(self.name, "constvar", f"constvar[{aval.dtype}]")
            )

    def finish(self) -> List[Finding]:
        return self.findings


class HostCallbackCheck(LintCheck):
    """Host callbacks (``pure_callback`` / ``io_callback`` /
    ``debug_callback``) stall the device on a host round-trip every launch
    — deadly on a hot path that is supposed to run at line rate, and
    unrepresentable on a real switch.  Flags every occurrence, however
    deeply nested."""

    name = "host-callback"

    def __init__(self, primitives: Sequence[str] = HOST_CALLBACK_PRIMITIVES):
        self.primitives = tuple(primitives)
        self.findings: List[Finding] = []

    def on_eqn(self, eqn, path: str) -> None:
        name = eqn.primitive.name
        if name in self.primitives:
            self.findings.append(
                Finding(self.name, name,
                        "host round-trip inside a jitted hot path", path)
            )

    def finish(self) -> List[Finding]:
        return self.findings


class WeakTypeCheck(LintCheck):
    """Python scalars trace as *weak-typed* avals; when one meets a
    strongly-typed operand of a different dtype the result silently
    promotes (int32 + 1.0 → float32, int32 << np.int64(1) → int64).  In an
    integer-lowered or width-audited path that promotion voids the ledger's
    bit-width proof, so mixed weak/strong operands of differing dtypes are
    flagged."""

    name = "weak-type"

    def __init__(self):
        self.findings: List[Finding] = []

    def on_eqn(self, eqn, path: str) -> None:
        weak, strong = [], []
        for v in eqn.invars:
            aval = _aval_of(v)
            if aval is None:
                continue
            (weak if getattr(aval, "weak_type", False) else strong).append(aval)
        if not weak or not strong:
            return
        strong_dtypes = {str(a.dtype) for a in strong}
        for a in weak:
            if str(a.dtype) not in strong_dtypes:
                self.findings.append(
                    Finding(
                        self.name, eqn.primitive.name,
                        f"weak {a.dtype} operand promotes against "
                        f"{sorted(strong_dtypes)}", path,
                    )
                )

    def finish(self) -> List[Finding]:
        return self.findings


# --------------------------------------------------------------------------
# the linter
# --------------------------------------------------------------------------

class JaxprLinter:
    """Run a set of :class:`LintCheck` instances over one jaxpr in a single
    recursive walk."""

    def __init__(self, checks: Sequence[LintCheck]):
        self.checks = list(checks)

    def lint(self, closed_jaxpr) -> List[Finding]:
        jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)

        def visit(eqn, path):
            for c in self.checks:
                c.on_eqn(eqn, path)

        walk_jaxpr(jaxpr, visit)
        for var in getattr(jaxpr, "constvars", ()):
            for c in self.checks:
                c.on_constvar(var)
        out: List[Finding] = []
        for c in self.checks:
            out.extend(c.finish())
        return out


def default_linter(*, int_path: bool = True) -> JaxprLinter:
    """The standard audit battery: host callbacks + weak-type promotion
    always; float ops only for integer-lowered paths."""
    checks: List[LintCheck] = [HostCallbackCheck(), WeakTypeCheck()]
    if int_path:
        checks.insert(0, FloatOpCheck())
    return JaxprLinter(checks)


def lint_jaxpr(closed_jaxpr, *, int_path: bool = True) -> List[Finding]:
    """One-shot convenience wrapper over :func:`default_linter`."""
    return default_linter(int_path=int_path).lint(closed_jaxpr)


def float_ops_in_jaxpr(closed_jaxpr) -> List[str]:
    """Labels of every inexact operand/result/literal/constvar in the
    (recursively walked) jaxpr.  The promoted, hardened successor of the
    audit previously local to :mod:`repro.compile.int_lowering`; label
    format ``prim[dtype]`` is preserved for existing callers, with
    ``prim[dtype] literal`` / ``constvar[dtype]`` marking the operand
    kinds the old audit could not distinguish."""
    out: List[str] = []
    for f in JaxprLinter([FloatOpCheck()]).lint(closed_jaxpr):
        kind, dtype = f.message.split("[", 1)
        dtype = dtype.rstrip("]")
        if f.primitive == "constvar":
            out.append(f"constvar[{dtype}]")
        elif kind == "literal":
            out.append(f"{f.primitive}[{dtype}] literal")
        else:
            out.append(f"{f.primitive}[{dtype}]")
    return out


def host_callbacks_in_jaxpr(closed_jaxpr) -> List[Finding]:
    return JaxprLinter([HostCallbackCheck()]).lint(closed_jaxpr)


def weak_type_hazards(closed_jaxpr) -> List[Finding]:
    return JaxprLinter([WeakTypeCheck()]).lint(closed_jaxpr)


# --------------------------------------------------------------------------
# donation safety (entry-point level, not per-eqn)
# --------------------------------------------------------------------------

def donation_safety(
    fn: Callable,
    args: Tuple,
    donate_argnums: Tuple[int, ...],
    kwargs: Optional[dict] = None,
) -> List[Finding]:
    """Audit an entry point's donation contract without executing it.

    Traces ``fn`` abstractly (args may be concrete arrays or
    ``ShapeDtypeStruct``\\ s) and checks, per donated argnum:

    * every donated leaf can alias *some* output leaf of identical shape
      and dtype (donation that can't be consumed is a silent no-op — the
      buffer is freed for nothing and XLA falls back to a copy);
    * no leaf shape/dtype is donated more times than outputs can absorb
      (double donation of one logical buffer);
    * donated avals are arrays (an argnum pointing at a non-array pytree
      is a donation typo).

    Host-side reuse-after-donation cannot be seen in a jaxpr — the
    complementary *dynamic* guard is the engines' rebind-per-launch
    protocol exercised by ``TestDonationRollbackAudit`` — but the static
    contract above catches the donation bugs that produce silent copies or
    device use-after-free.
    """
    kwargs = kwargs or {}
    findings: List[Finding] = []
    out_shape = jax.eval_shape(fn, *args, **kwargs)
    out_avals = [
        (leaf.shape, str(leaf.dtype))
        for leaf in jax.tree_util.tree_leaves(out_shape)
        if hasattr(leaf, "shape")
    ]
    pool: dict = {}
    for key in out_avals:
        pool[key] = pool.get(key, 0) + 1

    for argnum in donate_argnums:
        if argnum >= len(args):
            findings.append(
                Finding("donation", "entry",
                        f"donate_argnums={argnum} beyond positional arity "
                        f"{len(args)}")
            )
            continue
        leaves = jax.tree_util.tree_leaves(args[argnum])
        for leaf in leaves:
            if not hasattr(leaf, "shape") or not hasattr(leaf, "dtype"):
                findings.append(
                    Finding("donation", "entry",
                            f"argnum {argnum} donates a non-array leaf "
                            f"({type(leaf).__name__})")
                )
                continue
            key = (tuple(leaf.shape), str(jnp.dtype(leaf.dtype)))
            if pool.get(key, 0) > 0:
                pool[key] -= 1
            else:
                findings.append(
                    Finding(
                        "donation", "entry",
                        f"argnum {argnum} donates {key[1]}{list(key[0])} "
                        f"but no remaining output can alias it "
                        f"(unused donation → silent copy)",
                    )
                )
    return findings
