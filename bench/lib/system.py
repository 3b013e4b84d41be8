"""The system under test, built from a configuration file through the
program's front door: ``compile_program`` then
``program.deploy(DeploySpec(...))``.

The configuration file states every width and every deployment option by
name; this module only maps them onto the program's classes, the
backbone's through the configuration's family (``bench/models/<family>.py``).
Its ``deploy`` keys are today's ``DeploySpec`` / ``FlowEngineConfig``
options (``engine``, ``fused``, ``backend``, ``capacity``, ``lanes``,
``num_shards``, ``idle_timeout``).
"""

from __future__ import annotations

import os
import sys
from typing import Any, Dict

from . import spec

sys.path.insert(0, os.path.join(spec.ROOT, "src"))


def classifier_config(config: Dict[str, Any], family):
    """The program's ``ClassifierConfig``: the family's ``arch_config`` under
    the file's classifier head."""
    from repro.train.classifier import ClassifierConfig

    c = config["classifier"]
    return ClassifierConfig(arch=family.arch_config(config), n_classes=c["n_classes"],
                            marker_base=c["marker_base"], lambda_h=c["lambda_h"])


def program_layout(ccfg):
    """Shapes of the classifier parameters the program initialises."""
    import jax

    from repro.train.classifier import init_classifier

    return jax.eval_shape(lambda k: init_classifier(ccfg, k)[0], jax.random.PRNGKey(0))


def deploy(config: Dict[str, Any], ccfg, params, rule_arrays):
    """Compile the classifier ``ccfg`` with the benchmark's weights and rule,
    and deploy it as the configuration says.  Returns ``(program, engine)``."""
    import jax.numpy as jnp

    from repro.compile import compile_program
    from repro.core.symbolic import RuleSet
    from repro.serve.deploy import DeploySpec
    from repro.serve.flow_engine import FlowEngineConfig

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
