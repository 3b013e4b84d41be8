"""A run whose timed path is broken underneath must come out not correct:
the harness runs as on the chip (without its look for a TPU) over an engine
with one planted fault each."""

import jax.numpy as jnp
import numpy as np
import pytest

import run as R
from lib import check

from helpers import args, tiny_cell

KEYS = ("trust", "vetoed", "pred", "s_nn", "s_sym", "sig")


class Wrapped:
    """The deployed engine with a method replaced: its fused dispatch, the
    path the harness drives on one chip through the program's ingest
    pipeline, or the sharded engine's ``ingest``."""

    def __init__(self, engine):
        self._e = engine

    def __getattr__(self, name):
        return getattr(self._e, name)


class Pending:
    """A dispatched call whose answers pass through ``fix`` when read."""

    def __init__(self, pending, fix):
        self._p, self._fix = pending, fix

    def finalize(self):
        return self._fix(self._p.finalize())


class StateUnchanged(Wrapped):
    """Every call answers, then the flow table is put back as it was."""

    def _dispatch_fused(self, fids, tokens, slots, fresh, staging=None):
        e = self._e
        keep = [jnp.copy(x) for x in (e.positions, e.sig, e.hidden_sum, e.vetoed)]
        caches = jax_copy(e.caches)
        pending = e._dispatch_fused(fids, tokens, slots, fresh, staging=staging)
        e.positions, e.sig, e.hidden_sum, e.vetoed = keep
        e.caches = caches
        return pending


class HalfBatch(Wrapped):
    """Only the first half of each call is served; the rest get answers
    repeated from it."""

    def _dispatch_fused(self, fids, tokens, slots, fresh, staging=None):
        n = max(len(fids) // 2, 1)
        pending = self._e._dispatch_fused(fids[:n], tokens[:n], slots[:n], fresh[:n],
                                          staging=staging)
        rest = np.arange(len(fids) - n) % n
        return Pending(pending, lambda out: {k: np.concatenate([out[k], out[k][rest]])
                                             for k in KEYS})


class NoExchange(Wrapped):
    """Answers from every chip but the first never reach the host."""

    def ingest(self, fids, tokens):
        out = self._e.ingest(fids, tokens)
        other = check.flow_shard(np.asarray(fids), self._e.num_shards) != 0
        return {k: np.where(other.reshape((-1,) + (1,) * (out[k].ndim - 1)), 0, out[k])
                for k in KEYS}


class AnswerAltered(Wrapped):
    """Every fifth answer's anomaly logit is moved where it is produced."""

    def _dispatch_fused(self, fids, tokens, slots, fresh, staging=None):
        def alter(out):
            out = dict(out)
            out["s_nn"] = out["s_nn"].copy()
            out["s_nn"][::5] += 0.25
            return out

        return Pending(self._e._dispatch_fused(fids, tokens, slots, fresh, staging=staging),
                       alter)


def jax_copy(tree):
    import jax

    return jax.tree_util.tree_map(jnp.copy, tree)


@pytest.mark.parametrize("fault,shards", [(StateUnchanged, 1), (HalfBatch, 1),
                                          (NoExchange, 4), (AnswerAltered, 1)],
                         ids=["state-unchanged", "half-batch", "no-exchange", "answer-altered"])
def test_fault_is_not_correct(fault, shards):
    res = R.run(args(2**31 + 21), cell=tiny_cell(shards=shards), require_tpu=False, fault=fault)
    assert not res["correct"], res["checks"]
