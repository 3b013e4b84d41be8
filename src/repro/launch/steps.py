"""Dry-run cell construction: abstract params, input specs, shardings and
the jitted step per (arch × shape × mesh).

``input_specs`` returns ShapeDtypeStruct stand-ins for every model input
(weak-type-correct, shardable, no device allocation).  ``build_cell``
assembles everything the dry-run (and the real launcher) needs.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, SHAPES, ShapeConfig
from repro.models import model as M
from repro.optim.optimizer import AdamWConfig
from repro.runtime import sharding as shard
from repro.train import train_step as steps

WHISPER_DECODE_ENC_LEN = 1536  # 30s of audio frames (stub frontend), padded


def abstract_init(cfg: ArchConfig, key: Optional[jax.Array] = None):
    """(ShapeDtypeStruct params, logical axes) without allocating anything."""
    key = key if key is not None else jax.random.PRNGKey(0)
    box: Dict[str, Any] = {}

    def f(k):
        p, a = M.init_model(cfg, k)
        box["axes"] = a
        return p

    shapes = jax.eval_shape(f, key)
    return shapes, box["axes"]


def abstract_opt_state(params_shapes, opt_cfg: Optional[AdamWConfig] = None):
    mdt = (opt_cfg or AdamWConfig())._mdt
    mom = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda p: jax.ShapeDtypeStruct(p.shape, mdt), t
    )
    return {
        "m": mom(params_shapes),
        "v": mom(params_shapes),
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }


def _enc_dec_split(cfg: ArchConfig, seq_len: int) -> Tuple[int, int]:
    te = int(seq_len * cfg.encoder_seq_fraction)
    return te, seq_len - te


def input_specs(cfg: ArchConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for the step inputs of this cell."""
    B, T = shape.global_batch, shape.seq_len
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
    if shape.kind in ("train", "prefill"):
        if cfg.encoder_layers:
            te, td = _enc_dec_split(cfg, T)
            batch = {
                "enc_embeds": f32((B, te, cfg.d_model)),
                "tokens": i32((B, td)),
            }
            if shape.kind == "train":
                batch["labels"] = i32((B, td))
        else:
            batch = {"tokens": i32((B, T))}
            if shape.kind == "train":
                batch["labels"] = i32((B, T))
        return {"batch": batch}
    # decode: one new token against a seq_len-deep context
    caches = abstract_caches(cfg, B, T)
    return {
        "token": i32((B,)),
        "position": i32((B,)),
        "caches": caches,
    }


def abstract_caches(cfg: ArchConfig, batch: int, max_len: int):
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg.dtype]
    if cfg.encoder_layers:
        params_shapes, _ = abstract_init(cfg)
        enc = jax.ShapeDtypeStruct(
            (batch, WHISPER_DECODE_ENC_LEN, cfg.d_model), jnp.float32
        )
        return jax.eval_shape(
            lambda p, e: M.init_encdec_caches(cfg, p, e, batch, max_len, dtype),
            params_shapes,
            enc,
        )
    return jax.eval_shape(
        functools.partial(M.init_caches, cfg, batch, max_len, dtype=dtype)
    )


def batch_specs_sharding(cfg, shape: ShapeConfig, mesh: Mesh, rules):
    """NamedShardings for the input specs of this cell."""
    def tokens_spec(ndim):
        names = ["batch", "seq", None][:ndim]
        return names

    spec = input_specs(cfg, shape)
    if shape.kind in ("train", "prefill"):
        out = {}
        for k, v in spec["batch"].items():
            names = ("batch", "seq", None)[: v.ndim]
            out[k] = NamedSharding(mesh, shard.spec_for(rules, mesh, names, v.shape))
        return {"batch": out}
    # decode
    token_sh = NamedSharding(
        mesh, shard.spec_for(rules, mesh, ("batch",), spec["token"].shape)
    )
    if cfg.encoder_layers:
        axes = shard.encdec_cache_axes(cfg)
    else:
        axes = shard.cache_axes(cfg)
    cache_sh = shard.tree_shardings(mesh, rules, spec["caches"], axes)
    return {"token": token_sh, "position": token_sh, "caches": cache_sh}


@dataclasses.dataclass
class Cell:
    cfg: ArchConfig
    shape: ShapeConfig
    mesh: Mesh
    step_fn: Any
    args: Tuple[Any, ...]
    in_shardings: Tuple[Any, ...]
    out_shardings: Any
    donate_argnums: Tuple[int, ...]
    trip_counts: Dict[str, int]
    kernel_backend: str = "xla"  # effective kernel path for this cell

    def lower(self):
        jitted = jax.jit(
            self.step_fn,
            in_shardings=self.in_shardings,
            out_shardings=self.out_shardings,
            donate_argnums=self.donate_argnums,
        )
        with jax.sharding.set_mesh(self.mesh):
            return jitted.lower(*self.args)


def scan_trip_counts(cfg: ArchConfig, shape: ShapeConfig) -> Dict[str, int]:
    """Known trip counts per named scan scope (roofline attribution)."""
    T = shape.seq_len
    if cfg.encoder_layers and shape.kind in ("train", "prefill"):
        T = _enc_dec_split(cfg, shape.seq_len)[1]
    counts = {
        "layers": cfg.n_groups,
        "enc_layers": cfg.encoder_layers,
        "chimera": max(1, T // cfg.chimera.chunk_size),
        "softmax_blk": max(1, T // cfg.softmax_blk),
        "swa_blk": max(1, T // cfg.softmax_blk),
        "mamba": max(1, T // cfg.mamba_chunk),
        "mlstm": max(1, T // cfg.chimera.chunk_size),
        "slstm": T,
        "accum": 1,
    }
    if shape.kind == "decode":
        for k in ("chimera", "softmax_blk", "swa_blk", "mamba", "mlstm", "slstm"):
            counts[k] = 1
    return counts


def build_cell(
    cfg: ArchConfig,
    shape: ShapeConfig,
    mesh: Mesh,
    rules_mode: str = "fsdp",
    seq_sharded: bool = False,
    act_sp: bool = True,
    microbatches: int = 0,  # 0 = auto (grad accumulation for ≥100B trains)
    opt_cfg: Optional[AdamWConfig] = None,
    kernel_backend: Optional[str] = None,  # None keeps cfg as-is; "xla" pins
    # the pure-jnp paths; dispatch backends route attention through
    # repro.kernels.dispatch end-to-end (Chimera partials + SWA kernel)
) -> Cell:
    from repro.kernels.dispatch import apply_kernel_backend

    cfg, effective_backend = apply_kernel_backend(cfg, kernel_backend)
    rules = shard.make_rules(rules_mode, seq_sharded=seq_sharded, act_sp=act_sp)
    shard.install_activation_constraints(mesh, rules)
    tp = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
    if (
        cfg.use_chimera
        and not cfg.chimera.expand_kv
        and cfg.n_kv_heads % tp != 0
        and cfg.n_heads % tp == 0
    ):
        # kv heads can't shard over the TP axis; repeat kv to query heads so
        # the Chimera stream state shards TP-fold (see ChimeraAttentionConfig)
        cfg = dataclasses.replace(
            cfg, chimera=dataclasses.replace(cfg.chimera, expand_kv=True)
        )
    params_shapes, axes = abstract_init(cfg)
    if shape.kind != "train":
        # inference stores bf16 weights (no fp32 master / optimizer)
        dt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg.dtype]
        params_shapes = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, dt if x.dtype == jnp.float32 else x.dtype),
            params_shapes,
        )
    param_sh = shard.tree_shardings(mesh, rules, params_shapes, axes)
    spec = input_specs(cfg, shape)
    in_batch_sh = batch_specs_sharding(cfg, shape, mesh, rules)

    if shape.kind == "train":
        if opt_cfg is None:
            # ≥100B: bf16 Adam moments (Gopher-style) so optimizer HBM fits
            moments = "bfloat16" if cfg.param_count() > 1e11 else "float32"
            opt_cfg = AdamWConfig(moments_dtype=moments)
        opt_shapes = abstract_opt_state(params_shapes, opt_cfg)
        opt_sh = {
            "m": param_sh,
            "v": param_sh,
            "step": NamedSharding(mesh, P()),
        }
        if microbatches == 0:
            n = cfg.param_count()
            # thresholds chosen from the dry-run memory table: ≥100B needs 8,
            # 20B+ needs 4, 3B+ (MLA archs with unshardable heads) needs 2
            microbatches = 8 if n > 1e11 else (4 if n > 2e10 else (2 if n > 3e9 else 1))
        if microbatches > 1:
            fn = steps.make_train_step_accum(
                cfg, opt_cfg, microbatches, grad_shardings=param_sh
            )
        else:
            fn = steps.make_train_step(cfg, opt_cfg, grad_shardings=param_sh)
        metrics_sh = NamedSharding(mesh, P())
        return Cell(
            cfg=cfg,
            shape=shape,
            mesh=mesh,
            step_fn=fn,
            args=(params_shapes, opt_shapes, spec["batch"]),
            in_shardings=(param_sh, opt_sh, in_batch_sh["batch"]),
            out_shardings=(param_sh, opt_sh, None),
            donate_argnums=(0, 1),
            trip_counts=scan_trip_counts(cfg, shape),
            kernel_backend=effective_backend,
        )
    if shape.kind == "prefill":
        fn = steps.make_prefill_step(cfg)
        logits_shape = None  # let GSPMD choose; constrained in-model
        return Cell(
            cfg=cfg,
            shape=shape,
            mesh=mesh,
            step_fn=fn,
            args=(params_shapes, spec["batch"]),
            in_shardings=(param_sh, in_batch_sh["batch"]),
            out_shardings=logits_shape,
            donate_argnums=(),
            trip_counts=scan_trip_counts(cfg, shape),
            kernel_backend=effective_backend,
        )
    # decode
    fn = steps.make_serve_step(cfg)
    return Cell(
        cfg=cfg,
        shape=shape,
        mesh=mesh,
        step_fn=fn,
        args=(params_shapes, spec["token"], spec["position"], spec["caches"]),
        in_shardings=(
            param_sh,
            in_batch_sh["token"],
            in_batch_sh["position"],
            in_batch_sh["caches"],
        ),
        out_shardings=(None, in_batch_sh["caches"]),
        donate_argnums=(3,),
        trip_counts=scan_trip_counts(cfg, shape),
        kernel_backend=effective_backend,
    )
