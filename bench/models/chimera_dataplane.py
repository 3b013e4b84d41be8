"""The Chimera dataplane classifier as a family: the paper's dense stack of
pre-norm Chimera attention and SwiGLU MLP blocks, every layer alike.

What the harness needs of one architecture, found by the configuration's
``"family"`` key (``lib.spec.load_family``):

* ``arch_config(config)``: the program's ``ArchConfig`` for the file's widths;
* ``make_params(model, classes, seed)``: every weight, float32, drawn on the
  device from the seed by one jitted call, laid out as the program's
  ``compile_program`` takes a classifier's parameters;
* ``token_flops(model)``, ``row_bytes(model, classes)`` and
  ``weight_bytes(model, classes)``: what one token's decode needs, one flow's
  table row, and the weights the flow path reads (``lib.flops`` turns them
  into a call's least time).

The per-block helpers (``Draws``, ``chimera_attention_weights``,
``swiglu_weights`` and their counts) take grouped-query attention:
``n_kv_heads`` may divide ``n_heads``, and ``wk``, ``wv``, the global keys
and values and the per-flow state are then sized by kv head, as the
program's ``init_attention`` and ``init_decode_state`` size them.  Another
family imports them with ``lib.spec.load_module("models",
"chimera_dataplane")``.

Counts are what the algorithm needs, not what an implementation moves:
multiply-adds of the products (2 operations each; element-wise work is not
counted), and bytes at float32.  Chimera decode of one token, per layer (H
query heads and Hkv kv heads of d_head, m random features, G globals,
L-token chunks, b signature bits):

* q and output projections: 2 x 2 d (H d_head); k and v: 2 x 2 d (Hkv d_head)
* feature maps of q and k: 2 H d_head m + 2 Hkv d_head m
* local attention over the (L + 1) / 2 chunk tokens it sees on average:
  2 x 2 H d_head (L + 1) / 2
* stream readout phi(q).S and phi(q).Z: 2 H m (d_head + 1)
* globals: the signature 2 H d_head b, scores 2 H m G, values 2 H G d_head
* folding the token into S and Z: 2 Hkv m (d_head + 1)
* SwiGLU MLP: 3 x 2 d d_ff
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from lib.flops import sig_words
from lib.weights import key_from_seed


def arch_config(config: Dict[str, Any]):
    """The program's ``ArchConfig`` for the configuration file's widths."""
    from repro.configs.base import ArchConfig
    from repro.core.chimera_attention import ChimeraAttentionConfig
    from repro.core.feature_maps import FeatureMapConfig

    m = config["model"]
    fm = m["feature_map"]
    return ArchConfig(
        name=config["name"], family="dense", n_layers=m["n_layers"],
        d_model=m["d_model"], n_heads=m["n_heads"], n_kv_heads=m["n_kv_heads"],
        d_head=m["d_head"], d_ff=m["d_ff"], vocab_size=m["vocab_size"],
        vocab_pad_multiple=m["vocab_pad_multiple"], rope_theta=m["rope_theta"],
        norm_type="rmsnorm", use_chimera=True,
        chimera=ChimeraAttentionConfig(
            feature_map=FeatureMapConfig(kind=fm["kind"], m=fm["m"],
                                         input_scale=fm["input_scale"]),
            chunk_size=m["chunk_size"], n_global=m["n_global"],
            sig_bits=m["sig_bits"], match_hamming=m["match_hamming"],
            gamma=m["gamma"],
        ),
        dtype=m["dtype"], remat="none",
    )


# ---------------------------------------------------------------- weights
class Draws:
    """Float32 normal draws from ``n`` keys split off ``key``, taken in
    the order they are asked for (call inside the jitted builder)."""

    def __init__(self, key, n: int):
        self._keys = iter(jax.random.split(key, n))

    def normal(self, shape, scale):
        return jax.random.normal(next(self._keys), shape, jnp.float32) * scale

    def norm_scale(self, shape):
        return 1.0 + self.normal(shape, 0.1)


def padded_vocab(model: Dict[str, Any]) -> int:
    return -(-model["vocab_size"] // model["vocab_pad_multiple"]) * model["vocab_pad_multiple"]


def chimera_attention_weights(draw: Draws, model: Dict[str, Any], nl: int):
    """The ``attn`` leaves of ``nl`` stacked Chimera attention layers."""
    d, H, Hkv, dh = model["d_model"], model["n_heads"], model["n_kv_heads"], model["d_head"]
    return {
        "wq": {"w": draw.normal((nl, d, H * dh), 1 / math.sqrt(d))},
        "wk": {"w": draw.normal((nl, d, Hkv * dh), 1 / math.sqrt(d))},
        "wv": {"w": draw.normal((nl, d, Hkv * dh), 1 / math.sqrt(d))},
        "wo": {"w": draw.normal((nl, H * dh, d), 1 / math.sqrt(H * dh))},
        "chimera": {
            "fm": {"w": draw.normal((nl, model["feature_map"]["m"], dh), 1.0)},
            "sig_proj": draw.normal((nl, dh, model["sig_bits"]), 1.0),
            "k_global": draw.normal((nl, Hkv, model["n_global"], dh), 1 / math.sqrt(dh)),
            "v_global": draw.normal((nl, Hkv, model["n_global"], dh), 1 / math.sqrt(dh)),
        },
    }


def swiglu_weights(draw: Draws, model: Dict[str, Any], nl: int):
    """The ``mlp`` leaves of ``nl`` stacked SwiGLU MLPs."""
    d, dff = model["d_model"], model["d_ff"]
    return {
        "wi": {"w": draw.normal((nl, d, dff), 1 / math.sqrt(d))},
        "wg": {"w": draw.normal((nl, d, dff), 1 / math.sqrt(d))},
        "wo": {"w": draw.normal((nl, dff, d), 1 / math.sqrt(dff))},
    }


def make_params(model: Dict[str, Any], classes: Dict[str, Any], seed: int):
    """All weights, float32, drawn on the default device by one jitted call."""
    nl, d, V = model["n_layers"], model["d_model"], padded_vocab(model)

    def build(key):
        draw = Draws(key, 24)
        block = {
            "ln1": {"scale": draw.norm_scale((nl, d))},
            "attn": chimera_attention_weights(draw, model, nl),
            "ln2": {"scale": draw.norm_scale((nl, d))},
            "mlp": swiglu_weights(draw, model, nl),
        }
        return {
            "backbone": {
                "embed": {"table": draw.normal((V, d), 0.02)},
                "blocks": {"b0": block},
                "final_norm": {"scale": draw.norm_scale((d,))},
                "head": {"w": draw.normal((d, V), 1 / math.sqrt(d))},
            },
            "cls": {"w": draw.normal((d, classes["n_classes"]), 1 / math.sqrt(d))},
            "anom": {"w": draw.normal((d, 1), 1 / math.sqrt(d))},
            "fusion": {"alpha": jnp.float32(1.0), "beta": jnp.float32(1.0)},
        }

    return jax.jit(build)(key_from_seed(seed, 0x3E1))


# ---------------------------------------------------------------- counts
def chimera_attention_token_flops(model: Dict[str, Any]) -> float:
    """One token through one Chimera attention layer."""
    d, H, Hkv, dh = model["d_model"], model["n_heads"], model["n_kv_heads"], model["d_head"]
    m, G, b = model["feature_map"]["m"], model["n_global"], model["sig_bits"]
    L = model["chunk_size"]
    return (
        2 * 2 * d * H * dh + 2 * 2 * d * Hkv * dh
        + 2 * H * dh * m + 2 * Hkv * dh * m
        + 2 * 2 * H * dh * (L + 1) / 2
        + 2 * H * m * (dh + 1)
        + 2 * H * dh * b + 2 * H * m * G + 2 * H * G * dh
        + 2 * Hkv * m * (dh + 1)
    )


def swiglu_token_flops(model: Dict[str, Any]) -> float:
    return 3 * 2 * model["d_model"] * model["d_ff"]


def chimera_attention_row_bytes(model: Dict[str, Any]) -> int:
    """One flow's state of one Chimera attention layer: S, Z, the key and
    value ring of one chunk per kv head, and the ring's fill count."""
    Hkv, dh, L = model["n_kv_heads"], model["d_head"], model["chunk_size"]
    m = model["feature_map"]["m"]
    return 4 * (Hkv * m * dh + Hkv * m + 2 * Hkv * L * dh) + 4


def chimera_attention_weight_bytes(model: Dict[str, Any]) -> int:
    """One Chimera attention layer and its pre-norm."""
    d, H, Hkv, dh = model["d_model"], model["n_heads"], model["n_kv_heads"], model["d_head"]
    m, G, b = model["feature_map"]["m"], model["n_global"], model["sig_bits"]
    return 4 * (d + 2 * d * H * dh + 2 * d * Hkv * dh + m * dh + dh * b + 2 * Hkv * G * dh)


def swiglu_weight_bytes(model: Dict[str, Any]) -> int:
    """One SwiGLU MLP and its pre-norm."""
    return 4 * (model["d_model"] + 3 * model["d_model"] * model["d_ff"])


def token_flops(model: Dict[str, Any]) -> float:
    return model["n_layers"] * (chimera_attention_token_flops(model) + swiglu_token_flops(model))


def row_bytes(model: Dict[str, Any], classes: Dict[str, Any]) -> int:
    """One flow's device state at float32: every layer's attention state;
    the cumulative signature, the hidden-state sum, the position and the
    veto bit."""
    return (model["n_layers"] * chimera_attention_row_bytes(model)
            + 4 * sig_words(model, classes) + 4 * model["d_model"] + 4 + 1)


def weight_bytes(model: Dict[str, Any], classes: Dict[str, Any]) -> int:
    """Weights the flow path reads, at float32: embedding table, blocks,
    final norm and the two heads (the language-model head is not read)."""
    d, nl = model["d_model"], model["n_layers"]
    layers = nl * (chimera_attention_weight_bytes(model) + swiglu_weight_bytes(model))
    return 4 * (padded_vocab(model) * d + d + d * (classes["n_classes"] + 1)) + layers
