"""The system under test, built from a configuration file through the
program's front door: ``compile_program`` then
``program.deploy(DeploySpec(...))``.

The configuration file states every width and every deployment option by
name; this module only maps them onto the program's classes.  Its
``deploy`` keys are today's ``DeploySpec`` / ``FlowEngineConfig`` options
(``engine``, ``fused``, ``backend``, ``capacity``, ``lanes``,
``num_shards``, ``idle_timeout``).
"""

from __future__ import annotations

import os
import sys
from typing import Any, Dict

from . import spec

sys.path.insert(0, os.path.join(spec.ROOT, "src"))


def classifier_config(config: Dict[str, Any]):
    """The program's ``ClassifierConfig`` for the file's widths."""
    from repro.configs.base import ArchConfig
    from repro.core.chimera_attention import ChimeraAttentionConfig
    from repro.core.feature_maps import FeatureMapConfig
    from repro.train.classifier import ClassifierConfig

    m, c = config["model"], config["classifier"]
    fm = m["feature_map"]
    arch = ArchConfig(
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
    return ClassifierConfig(arch=arch, n_classes=c["n_classes"],
                            marker_base=c["marker_base"], lambda_h=c["lambda_h"])


def program_layout(ccfg):
    """Shapes of the classifier parameters the program initialises."""
    import jax

    from repro.train.classifier import init_classifier

    return jax.eval_shape(lambda k: init_classifier(ccfg, k)[0], jax.random.PRNGKey(0))


def deploy(config: Dict[str, Any], params, rule_arrays):
    """Compile the classifier with the benchmark's weights and rule, and
    deploy it as the configuration says.  Returns ``(program, engine)``."""
    import jax.numpy as jnp

    from repro.compile import compile_program
    from repro.core.symbolic import RuleSet
    from repro.serve.deploy import DeploySpec
    from repro.serve.flow_engine import FlowEngineConfig

    ccfg = classifier_config(config)
    values, masks, weights, hard = rule_arrays
    rules = RuleSet(values=jnp.asarray(values), masks=jnp.asarray(masks),
                    weights=jnp.asarray(weights), hard=jnp.asarray(hard))
    d = config["deploy"]
    program = compile_program(ccfg, params, rules=rules, backend=d["backend"],
                              waivers=tuple(d.get("waivers", ())))
    fcfg = FlowEngineConfig(capacity=d["capacity"], lanes=d["lanes"],
                            fused=d["fused"], idle_timeout=d["idle_timeout"])
    if d["engine"] == "sharded":
        ds = DeploySpec(engine="sharded", flow=fcfg, num_shards=d["num_shards"])
    else:
        ds = DeploySpec(engine=d["engine"], flow=fcfg)
    return program, program.deploy(ds)


def pipeline(engine, in_flight: int):
    """The program's ``AsyncIngestPipeline`` over a fused engine, with
    ``in_flight`` ring slots (``flow_serve --fused`` drives the engine so);
    None for an engine without the fused path, which is driven call by call."""
    if getattr(engine, "_jit_fused", None) is None:
        return None
    from repro.serve.ingest_pipeline import AsyncIngestPipeline

    return AsyncIngestPipeline(engine, depth=in_flight)
