"""Random classifier weights from the seed, made on the device in one call.

The benchmark makes the weights itself and hands the same arrays to the
program and to the plain reference, so the reference takes nothing the
program made.  The pytree is laid out the way the program's
``compile_program`` takes a classifier's parameters; :func:`check_layout`
compares it with the program's own layout, shapes only, before use.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np


def key_from_seed(seed: int, stream: int) -> jax.Array:
    """A JAX key from any whole-number seed (seeds may exceed 32 bits)."""
    g = np.random.default_rng(np.array([seed, stream], dtype=np.uint64))
    return jax.random.PRNGKey(int(g.integers(0, 2**31 - 1)))


def _shapes(m: Dict[str, Any], classes: Dict[str, Any]) -> Dict[str, Any]:
    nl, d, H, dh, dff = m["n_layers"], m["d_model"], m["n_heads"], m["d_head"], m["d_ff"]
    V = -(-m["vocab_size"] // m["vocab_pad_multiple"]) * m["vocab_pad_multiple"]
    fm, G, bits = m["feature_map"]["m"], m["n_global"], m["sig_bits"]
    return dict(nl=nl, d=d, H=H, dh=dh, dff=dff, V=V, m=fm, G=G, bits=bits,
                K=classes["n_classes"])


def make_params(model: Dict[str, Any], classes: Dict[str, Any], seed: int):
    """All weights, float32, drawn on the default device by one jitted call."""
    s = _shapes(model, classes)
    nl, d, H, dh, dff, V = s["nl"], s["d"], s["H"], s["dh"], s["dff"], s["V"]

    def build(key):
        ks = iter(jax.random.split(key, 24))

        def normal(shape, scale):
            return jax.random.normal(next(ks), shape, jnp.float32) * scale

        def norm_scale(shape):
            return 1.0 + normal(shape, 0.1)

        block = {
            "ln1": {"scale": norm_scale((nl, d))},
            "attn": {
                "wq": {"w": normal((nl, d, H * dh), 1 / math.sqrt(d))},
                "wk": {"w": normal((nl, d, H * dh), 1 / math.sqrt(d))},
                "wv": {"w": normal((nl, d, H * dh), 1 / math.sqrt(d))},
                "wo": {"w": normal((nl, H * dh, d), 1 / math.sqrt(H * dh))},
                "chimera": {
                    "fm": {"w": normal((nl, s["m"], dh), 1.0)},
                    "sig_proj": normal((nl, dh, s["bits"]), 1.0),
                    "k_global": normal((nl, H, s["G"], dh), 1 / math.sqrt(dh)),
                    "v_global": normal((nl, H, s["G"], dh), 1 / math.sqrt(dh)),
                },
            },
            "ln2": {"scale": norm_scale((nl, d))},
            "mlp": {
                "wi": {"w": normal((nl, d, dff), 1 / math.sqrt(d))},
                "wg": {"w": normal((nl, d, dff), 1 / math.sqrt(d))},
                "wo": {"w": normal((nl, dff, d), 1 / math.sqrt(dff))},
            },
        }
        return {
            "backbone": {
                "embed": {"table": normal((V, d), 0.02)},
                "blocks": {"b0": block},
                "final_norm": {"scale": norm_scale((d,))},
                "head": {"w": normal((d, V), 1 / math.sqrt(d))},
            },
            "cls": {"w": normal((d, s["K"]), 1 / math.sqrt(d))},
            "anom": {"w": normal((d, 1), 1 / math.sqrt(d))},
            "fusion": {"alpha": jnp.float32(1.0), "beta": jnp.float32(1.0)},
        }

    return jax.jit(build)(key_from_seed(seed, 0x3E1))


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
