"""Traffic-serving driver: compile the classifier into a DataplaneProgram,
deploy it on the flow-table runtime, stream FlowScenario packets through it.

    PYTHONPATH=src python -m repro.launch.flow_serve --scenario port-scan \
        --batches 8 --capacity 2048 [--backend pallas-interpret] [--ledger]

Fused ingest: ``--fused`` serves through the single-launch ``flow_ingest``
path (DESIGN.md §15) — one device launch per width group instead of one
per arrival round, pre-traced by ``warm_fused`` and driven through the
:class:`~repro.serve.ingest_pipeline.AsyncIngestPipeline` ring so host
packing overlaps device compute.  Decisions are bit-identical to the
per-round path (see ``tests/test_fused_ingest.py``).

    PYTHONPATH=src python -m repro.launch.flow_serve --smoke --fused \
        --scenario protocol-mix --batches 16

Scale-out: ``--num-shards N`` deploys a ShardedFlowEngine over N devices
(the mesh ``data`` axis).  On CPU hosts pass ``--host-devices N`` (or set
``XLA_FLAGS=--xla_force_host_platform_device_count=N``) to expose N
devices; ``--capacity`` is then per shard.

Elastic serving: ``--elastic`` deploys the
:class:`~repro.serve.elastic.ElasticFlowService` (DESIGN.md §17) —
``--reshard 4:4,12:2`` live-reshards to 4 shards before batch 4 and back
to 2 before batch 12 (each install Eq. 18-measured, bit-identical replay),
``--checkpoint-dir``/``--checkpoint-every`` enable per-shard flow-state
checkpoints for kill-a-shard recovery.

    PYTHONPATH=src python -m repro.launch.flow_serve --smoke --elastic \
        --host-devices 8 --num-shards 2 --reshard 4:4,12:2 --batches 16

Closed-loop adaptation: ``--adapt`` streams a non-stationary
:class:`~repro.data.pipeline.DriftScenario` (``--drift-phases`` schedules
it; the default ends in an adversarial signature surge) through an
:class:`~repro.serve.adaptive_loop.AdaptiveLoop`, which recompiles and
atomically re-installs the symbolic tables when its drift policy fires —
on a background thread unless ``--adapt-sync``.  ``--batches`` then counts
full scenario batches as usual.

    PYTHONPATH=src python -m repro.launch.flow_serve --smoke --adapt \
        --batches 16 [--adapt-sync] [--drift-phases protocol-mix:6,...]

Campaigns and traces: ``--campaign NAME`` replays a named adversarial
campaign from :mod:`repro.data.campaigns` (its pinned geometry, schedule
and detector-policy overrides) under the AdaptiveLoop — the serving-side
view of what the red-team gate (``python -m repro.serve.redteam``) scores.
``--trace PATH`` (or ``--trace sample``) replays a recorded
chimera-trace-v1 file through :class:`~repro.data.traces
.TraceReplayScenario` instead of a generator.

    PYTHONPATH=src python -m repro.launch.flow_serve --smoke \
        --campaign scan-evasion [--adapt-sync]
    PYTHONPATH=src python -m repro.launch.flow_serve --smoke --trace sample
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import time


def classifier_config(arch_name: str = "chimera-dataplane", smoke: bool = False):
    """The served classifier: the registry config (``smoke`` = its reduced
    preset), with the vocab widened to the byte + marker alphabet."""
    from repro.configs import get_config, smoke_config
    from repro.train import classifier as C

    arch = smoke_config(arch_name) if smoke else get_config(arch_name)
    arch = dataclasses.replace(arch, vocab_size=max(arch.vocab_size, 512))
    return C.ClassifierConfig(arch=arch, n_classes=8, marker_base=256)


def compile_flow_program(ccfg, anomaly_signature, *, backend=None,
                         smoke: bool = False, seed: int = 0):
    """Random classifier weights from ``seed``, compiled against the default
    rules for ``anomaly_signature``.  The compiler's signature-layout pass
    sizes sig_words so every marker owns a TCAM bit; the rules callable sees
    the finalized layout.  The full arch intentionally exceeds the 1KB/flow
    switch budget (Table 2 amortizes it over shared SRAM banks), so the
    per-flow stage is waived for this TPU-host deployment — recorded in the
    ledger, not dropped."""
    import jax
    import jax.numpy as jnp

    from repro.compile import compile_program
    from repro.train import classifier as C

    params, _ = C.init_classifier(ccfg, jax.random.PRNGKey(seed))
    return compile_program(
        ccfg, params,
        rules=lambda c: C.default_rules(c, jnp.asarray(anomaly_signature)),
        backend=backend,
        waivers=() if smoke else ("state-quantization",),
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="chimera-dataplane")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (default: full arch; slow on CPU)")
    ap.add_argument("--scenario", default="mix",
                    help="mix | protocol-mix | port-scan | burst | "
                         "heavy-churn | rule-violating")
    ap.add_argument("--batches", type=int, default=8)
    ap.add_argument("--packets", type=int, default=256, help="packets/batch")
    ap.add_argument("--pkt-len", type=int, default=16)
    ap.add_argument("--capacity", type=int, default=2048)
    ap.add_argument("--lanes", type=int, default=256)
    ap.add_argument("--idle-timeout", type=int, default=0)
    ap.add_argument("--fused", action="store_true",
                    help="single-launch fused ingest (DESIGN.md §15): whole "
                         "batch per width group via the flow_ingest kernel "
                         "family, with the async ring pipeline overlapping "
                         "host packing and device compute")
    ap.add_argument("--backend", default=None,
                    help="xla | auto | pallas-tpu | pallas-interpret | "
                         "reference | int-emulation")
    ap.add_argument("--save-program", default=None, metavar="DIR",
                    help="serialize the compiled program via the Checkpointer")
    ap.add_argument("--ledger", action="store_true",
                    help="print the per-stage resource ledger")
    ap.add_argument("--adapt", action="store_true",
                    help="serve a DriftScenario under the closed-loop "
                         "AdaptiveLoop (drift detect -> delta -> install)")
    ap.add_argument("--adapt-sync", action="store_true",
                    help="run the control plane inline at the triggering "
                         "tick instead of on a background thread")
    ap.add_argument("--drift-phases",
                    default="protocol-mix:6,rule-violating:8:1:0.6,"
                            "heavy-churn:6:1",
                    help="DriftScenario schedule: comma-separated "
                         "kind:batches[:sig_rotation[:anomaly_rate]]")
    ap.add_argument("--campaign", default=None, metavar="NAME",
                    help="replay a registered adversarial campaign (see "
                         "repro.data.campaigns) under the AdaptiveLoop with "
                         "its pinned geometry and policy; implies --adapt")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="replay a recorded chimera-trace-v1 file ('sample' "
                         "= the committed fixture) instead of a generator")
    ap.add_argument("--num-shards", type=int, default=0,
                    help="shard the flow table over N devices (mesh 'data' "
                         "axis); 0 = single-device FlowEngine")
    ap.add_argument("--elastic", action="store_true",
                    help="deploy the ElasticFlowService (DESIGN.md §17): "
                         "sharded serving with live resharding, per-shard "
                         "checkpoints and admission control")
    ap.add_argument("--reshard", default="", metavar="B:S,...",
                    help="live-reshard schedule: before batch B, reshard to "
                         "S shards (comma-separated; requires --elastic), "
                         "e.g. 4:4,12:2")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="elastic flow-state checkpoint directory")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="ticks between automatic elastic checkpoints "
                         "(0 = manual)")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="force N XLA host-platform (CPU) devices; must be "
                         "set before jax initializes, so prefer this flag "
                         "over exporting XLA_FLAGS by hand")
    args = ap.parse_args()

    if args.host_devices:
        if "jax" in sys.modules:
            raise RuntimeError(
                "--host-devices must be applied before jax is imported; "
                "set XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{args.host_devices} in the environment instead"
            )
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.host_devices}"
        ).strip()

    from repro.data.pipeline import DriftScenario, FlowScenario, parse_phases
    from repro.launch.jax_cache import enable_compile_cache
    from repro.serve.deploy import DeploySpec, ElasticConfig
    from repro.serve.flow_engine import FlowEngineConfig

    enable_compile_cache()
    ccfg = classifier_config(args.arch, smoke=args.smoke)
    vocab = ccfg.arch.vocab_size

    if args.campaign and args.trace:
        ap.error("--campaign and --trace are mutually exclusive")
    campaign = None
    if args.campaign:
        from repro.data.campaigns import get_campaign

        campaign = get_campaign(args.campaign)
        # the campaign pins its own geometry so scorecards stay comparable
        args.pkt_len = campaign.pkt_len
        args.packets = campaign.packets_per_batch
        args.adapt = True
        scenario = campaign.scenario(vocab_size=vocab)
        if args.batches == ap.get_default("batches"):
            args.batches = campaign.batches
        print(f"campaign {campaign.name!r}: {campaign.goal}")
    elif args.trace:
        from repro.data import traces as TR

        path = None if args.trace == "sample" else args.trace
        trace = TR.load_trace(path or TR.SAMPLE_TRACE)
        args.pkt_len = trace.meta.pkt_len
        scenario = TR.TraceReplayScenario(
            trace, packets_per_batch=args.packets
        )
        if args.batches == ap.get_default("batches"):
            args.batches = scenario.batches_per_cycle
        args.batches = min(args.batches, scenario.batches_per_cycle)
        print(f"trace {args.trace!r}: {len(trace.flow_ids)} packets / "
              f"{scenario.batches_per_cycle} batches")
    elif args.adapt:
        scenario = DriftScenario(
            phases=parse_phases(args.drift_phases), vocab_size=vocab,
            pkt_len=args.pkt_len, packets_per_batch=args.packets, seed=0,
        )
    else:
        scenario = FlowScenario(kind=args.scenario, vocab_size=vocab,
                                pkt_len=args.pkt_len,
                                packets_per_batch=args.packets, seed=0)
    program = compile_flow_program(
        ccfg, scenario.anomaly_signature, backend=args.backend,
        smoke=args.smoke,
    )
    if args.save_program:
        program.save(args.save_program)
        print(f"program saved to {args.save_program}")
    if args.fused and (args.num_shards or args.elastic):
        ap.error("--fused is single-device (ShardedFlowEngine launches "
                 "per-shard rounds); drop --fused or --num-shards/--elastic")
    if args.reshard and not args.elastic:
        ap.error("--reshard needs --elastic (only the ElasticFlowService "
                 "can change num_shards live)")
    if args.elastic and args.adapt:
        ap.error("--adapt drives a fixed engine; combining it with "
                 "--elastic resharding is not supported")
    reshard_plan = {}
    for part in filter(None, args.reshard.split(",")):
        b, s = part.split(":")
        reshard_plan[int(b)] = int(s)
    fcfg = FlowEngineConfig(capacity=args.capacity, lanes=args.lanes,
                            idle_timeout=args.idle_timeout, fused=args.fused)
    if args.elastic:
        spec = DeploySpec(
            engine="elastic", flow=fcfg, num_shards=args.num_shards or 1,
            elastic=ElasticConfig(
                checkpoint_dir=args.checkpoint_dir,
                checkpoint_every=args.checkpoint_every,
            ),
        )
    elif args.num_shards:
        spec = DeploySpec(engine="sharded", flow=fcfg,
                          num_shards=args.num_shards)
    else:
        spec = DeploySpec(flow=fcfg)
    engine = program.deploy(spec)
    if args.ledger:
        print(program.ledger.as_table())
    print("stages: " + ", ".join(
        f"{k}={v}" for k, v in engine.stage_impls.items()
    ))
    loop = None
    if args.adapt:
        from repro.serve.adaptive_loop import (
            AdaptiveLoop, AdaptiveLoopConfig, DriftPolicy,
        )

        policy, loop_cfg = None, {}
        if campaign is not None:
            from repro.serve.redteam import split_policy

            drift, loop_cfg = split_policy(campaign.policy)
            policy = DriftPolicy(**drift)
        loop = AdaptiveLoop(
            engine, policy=policy,
            cfg=AdaptiveLoopConfig(sync=args.adapt_sync, **loop_cfg),
        )

    pipe = None
    if args.fused:
        n = engine.warm_fused(args.pkt_len)  # pre-trace outside the timer
        print(f"fused: warmed {n} width trace(s), "
              f"ring depth {fcfg.ring_slots}")
        if loop is None:
            from repro.serve.ingest_pipeline import AsyncIngestPipeline

            pipe = AsyncIngestPipeline(engine)

    t0 = time.perf_counter()
    pkts = 0
    sink = loop if loop is not None else (pipe or engine)
    for i in range(args.batches):
        if i in reshard_plan:
            rec = engine.reshard(reshard_plan[i])
            print(f"reshard @batch {i}: {rec.old_shards}->{rec.new_shards} "
                  f"shards, {rec.migrated_flows} flows migrated "
                  f"({rec.moved_flows} moved) in {rec.install_s*1e3:.2f}ms "
                  f"{'ok' if rec.churn_ok else 'ROLLED BACK'}")
        batch = scenario.next_batch()
        if pipe is not None:
            pipe.submit(batch["flow_ids"], batch["tokens"])
        else:
            sink.ingest(batch["flow_ids"], batch["tokens"])
        pkts += len(batch["flow_ids"])
    if pipe is not None:
        pipe.drain()
    if loop is not None:
        loop.close()  # drain any in-flight control-plane epoch
    dt = time.perf_counter() - t0
    s = engine.stats
    capacity = getattr(engine, "aggregate_capacity", args.capacity)
    budget = getattr(
        engine, "aggregate_state_budget_bytes", engine.state_budget_bytes
    )
    shards = (
        f" shards={engine.num_shards}"
        if (args.num_shards or args.elastic) else ""
    )
    if campaign is not None:
        label = f"campaign:{campaign.name}"
    elif args.trace:
        label = f"trace:{args.trace}"
    else:
        label = "drift" if args.adapt else args.scenario
    print(
        f"{label}: {pkts} packets / {s.flows_created} flows in "
        f"{dt:.2f}s = {pkts/dt:.0f} pkt/s ({pkts*args.pkt_len/dt:.0f} tok/s) | "
        f"backend={engine.backend}{shards} resident={engine.resident_flows}"
        f"/{capacity} evicted={s.flows_evicted} "
        f"(rate {s.eviction_rate:.2f}/tick) | "
        f"state={engine.resident_state_bytes()/2**20:.1f}MiB "
        f"of {budget/2**20:.0f}MiB budget"
    )
    if loop is not None:
        h = loop.history
        mode = "sync" if args.adapt_sync else "async"
        print(
            f"adaptation ({mode}): {len(h)} trigger(s) at ticks "
            f"{loop.trigger_ticks}, {loop.installs} install(s), "
            f"{loop.installs_within_budget}/{max(loop.installs, 1)} within "
            f"the Eq. 18 t_cp budget ({loop.t_cp_s:g}s), "
            f"{sum(r.rolled_back for r in h)} rollback(s)"
        )
        for r in h:
            verdict = (
                "installed" if r.installed
                else ("ROLLED BACK" if r.rolled_back else f"held ({r.error})")
            )
            print(
                f"  tick {r.tick}: fired {','.join(r.fired_on) or '-'} "
                f"-> {verdict} (install {r.install_s*1e3:.2f}ms at tick "
                f"{r.install_tick})"
            )


if __name__ == "__main__":
    main()
