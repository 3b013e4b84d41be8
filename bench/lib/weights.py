"""What every family's weights need: the key from the seed and the check of
the layout, and the anomaly rule.

The benchmark makes the weights itself, by the family's ``make_params``
(``bench/models/<family>.py``), and hands the same arrays to the program
and to the plain reference, so the reference takes nothing the program
made.  :func:`check_layout` compares them with the program's own layout,
shapes only, before use.
"""

from __future__ import annotations

import jax
import numpy as np


def key_from_seed(seed: int, stream: int) -> jax.Array:
    """A JAX key from any whole-number seed (seeds may exceed 32 bits)."""
    g = np.random.default_rng(np.array([seed, stream], dtype=np.uint64))
    return jax.random.PRNGKey(int(g.integers(0, 2**31 - 1)))


def check_layout(params, program_layout) -> None:
    """Raise unless ``params`` has the program's tree, shapes and dtypes."""
    got = jax.tree_util.tree_structure(params)
    want = jax.tree_util.tree_structure(program_layout)
    if got != want:
        raise ValueError(f"weights tree {got} is not the program's {want}")
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(program_layout)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"weight {a.shape}/{a.dtype} vs program {b.shape}/{b.dtype}")


def anomaly_rule(anomaly_sig, sig_words: int, marker_base: int):
    """The hard TCAM rule over the anomaly signature, as numpy arrays:
    (values (1, W) uint32, masks (1, W) uint32, weights (1,), hard (1,))."""
    bits = np.zeros(32 * sig_words, np.uint64)
    bits[np.asarray(anomaly_sig) - marker_base] = 1
    words = (bits.reshape(sig_words, 32) << np.arange(32, dtype=np.uint64)).sum(-1)
    value = words.astype(np.uint32)[None]
    return value, value.copy(), np.array([4.0], np.float32), np.array([True])
