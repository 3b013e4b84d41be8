"""FlowEngine traffic-serving benchmarks + the CI throughput regression gate.

Streams :class:`FlowScenario` packet arrivals through the flow-table
runtimes and reports packets/sec, resident flows, and eviction rate — per
kernel backend (``serve_flow``), per device count for the sharded engine
(``serve_flow_sharded``: 1/2/4/8 shards, each measured in a subprocess so
``XLA_FLAGS=--xla_force_host_platform_device_count`` can differ per point),
and with the closed adaptation loop on vs off over a non-stationary
:class:`DriftScenario` (``serve_adaptive``: drift-stats overhead,
installs/hour, Eq. 18 budget compliance).  ``serve_elastic`` drives the
:class:`~repro.serve.elastic.ElasticFlowService` through a live reshard
cycle (S → 2S → S, subprocess with forced host devices): steady-state pps
before/during/after the cycle feeds the regression gate, and each
reshard's Eq. 18-measured install cost lands in derived-only rows.  Runs
standalone (the CI smoke + regression gates) or as suites of
``benchmarks.run``:

    PYTHONPATH=src python -m benchmarks.serve_bench --fast
    PYTHONPATH=src python -m benchmarks.serve_bench --fast --json BENCH_serve.json
    PYTHONPATH=src python -m benchmarks.serve_bench \
        --gate BENCH_serve.json --baseline benchmarks/BENCH_serve_baseline.json
    PYTHONPATH=src python -m benchmarks.run --only serve_flow,serve_flow_sharded

CSV: name,us_per_call,derived — us_per_call is wall-µs per packet.  The
``--gate`` mode compares the ``pps`` field of two ``--json`` dumps and
fails on a >30% packets/sec regression on any benchmark present in both.

Each ``serve/flow/{kind}/{backend}`` row is paired with a ``…+fused`` row
(the DESIGN.md §15 single-launch ingest through the AsyncIngestPipeline
ring) and a derived-only ``…+fused-vs-legacy`` speedup row; the latter
carries no ``pps`` field, so the gate compares the fused path against its
own baseline, never against the per-round path.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from typing import Dict, Iterator, List, Optional

import jax
import jax.numpy as jnp

from benchmarks.common import csv_row, tiny_backbone
from repro.compile import compile_program
from repro.data.pipeline import DriftPhase, DriftScenario, FlowScenario
from repro.serve.deploy import DeploySpec, ElasticConfig
from repro.serve.flow_engine import FlowEngineConfig
from repro.train import classifier as C

# backends runnable on this host; "xla" is the pure-jnp decode path, the
# rest route the per-packet step through repro.kernels.dispatch
_BACKENDS_FAST = ("xla", "reference", "int-emulation")


def _backends_full():
    """The full backend sweep; ``pallas-tpu`` only where a TPU is attached
    (asked at run time, never on import)."""
    return ("xla", "reference", "pallas-interpret", "int-emulation") + (
        ("pallas-tpu",) if _on_chip() else ()
    )


def _on_chip() -> bool:
    return jax.default_backend() == "tpu"


class SweepFailed(RuntimeError):
    """Raised by a device sweep once it has yielded all its rows (one ERROR
    row per failed worker among them), so every caller both keeps the
    partial results and fails."""


def _fit_devices(label: str, want: int) -> int:
    """Devices a chip-side point gets: ``want``, or the chip count with a
    note on stderr when the host has fewer."""
    have = jax.device_count()
    if want > have:
        print(f"{label}: {want} devices asked, this host has {have}",
              file=sys.stderr)
    return min(want, have)


_SCENARIOS_FAST = ("protocol-mix", "port-scan")
_SCENARIOS_FULL = (
    "protocol-mix", "port-scan", "burst", "heavy-churn", "rule-violating",
)

# sharded sweep: device counts measured (in-process on a chip; on the CPU
# each in its own subprocess with that many forced host-platform devices)
_SHARDS_FAST = (1, 2)
_SHARDS_FULL = (1, 2, 4, 8)

# >30% pkts/sec drop vs the committed baseline fails the CI gate
# (SERVE_BENCH_GATE_TOLERANCE overrides, e.g. while calibrating a new
# runner class whose absolute throughput differs from the baseline's)
GATE_TOLERANCE = float(os.environ.get("SERVE_BENCH_GATE_TOLERANCE", "0.30"))


def _build():
    # n_global=0 so the fused dispatch decode kernel is reachable (the
    # global-match tier falls back to the jnp path otherwise)
    import dataclasses

    arch = tiny_backbone()
    arch = dataclasses.replace(
        arch, chimera=dataclasses.replace(arch.chimera, n_global=0)
    )
    ccfg = C.ClassifierConfig(arch=arch, n_classes=8, marker_base=256)
    params, _ = C.init_classifier(ccfg, jax.random.PRNGKey(0))
    return ccfg, params


def _emit(name: str, us_per_pkt: float, pps: float, eng, extra: str = "") -> str:
    return csv_row(
        name,
        us_per_pkt,
        f"pps={pps:.0f};resident={eng.resident_flows};"
        f"flows={eng.stats.flows_created};"
        f"evict_rate={eng.stats.eviction_rate:.2f};"
        f"state_bytes={eng.resident_state_bytes()}" + extra,
    )


def serve_flow_benchmarks(fast: bool = False) -> List[str]:
    from repro.serve.ingest_pipeline import AsyncIngestPipeline

    rows: List[str] = []
    backends = _BACKENDS_FAST if fast else _backends_full()
    scenarios = _SCENARIOS_FAST if fast else _SCENARIOS_FULL
    batches = 3 if fast else 6
    ccfg, params = _build()
    fcfg_kw = dict(capacity=512 if fast else 2048,
                   lanes=128 if fast else 256)
    for backend in backends:
        eng = None  # one engine (one jitted step) per backend; reset per kind
        fused_eng = pipe = None
        for kind in scenarios:
            sc = FlowScenario(
                kind=kind, pkt_len=16,
                packets_per_batch=128 if fast else 256, seed=7,
            )
            if eng is None:
                # the deploy path under benchmark IS the compiled artifact:
                # compile once per backend, deploy through the DeploySpec
                # front door
                program = compile_program(
                    ccfg, params,
                    rules=lambda c: C.default_rules(
                        c, jnp.asarray(sc.anomaly_signature)
                    ),
                    backend=backend,
                )
                eng = program.deploy(
                    DeploySpec(flow=FlowEngineConfig(**fcfg_kw))
                )
                # the fused engine shares the program; warm_fused pre-traces
                # the width buckets so the timed region is launch + compute
                fused_eng = program.deploy(
                    DeploySpec(flow=FlowEngineConfig(fused=True, **fcfg_kw))
                )
                fused_eng.warm_fused(pkt_len=16)
                pipe = AsyncIngestPipeline(fused_eng)
            else:
                eng.reset()
                fused_eng.reset()

            def timed(sink, submit=None):
                stream = FlowScenario(
                    kind=kind, pkt_len=16,
                    packets_per_batch=128 if fast else 256, seed=7,
                )
                warm = stream.next_batch()  # compile outside the timed region
                sink.ingest(warm["flow_ids"], warm["tokens"])
                t0 = time.perf_counter()
                pkts = 0
                for _ in range(batches):
                    b = stream.next_batch()
                    if submit is None:
                        sink.ingest(b["flow_ids"], b["tokens"])
                    else:
                        submit(b)  # async ring path; drained below
                    pkts += len(b["flow_ids"])
                if submit is not None:
                    sink.drain()
                return pkts, time.perf_counter() - t0

            pkts, dt = timed(eng)
            legacy_pps = pkts / dt
            rows.append(_emit(
                f"serve/flow/{kind}/{backend}",
                dt / max(pkts, 1) * 1e6, legacy_pps, eng,
            ))
            pkts, dt = timed(
                pipe, submit=lambda b: pipe.submit(b["flow_ids"], b["tokens"])
            )
            fused_pps = pkts / dt
            rows.append(_emit(
                f"serve/flow/{kind}/{backend}+fused",
                dt / max(pkts, 1) * 1e6, fused_pps, fused_eng,
            ))
            # derived-only comparison row (no pps key -> the regression
            # gate never compares it; the speedup is informational)
            rows.append(csv_row(
                f"serve/flow/{kind}/{backend}+fused-vs-legacy", 0.0,
                f"speedup={fused_pps / legacy_pps:.2f}"
                f";fused_pps={fused_pps:.0f};legacy_pps={legacy_pps:.0f}",
            ))
    return rows


# --------------------------------------------------------------------------
# closed-loop adaptation under drift: cost of adaptation on vs off
# --------------------------------------------------------------------------

def _drift_phases(fast: bool):
    b1, b2, b3 = (4, 6, 4) if fast else (6, 10, 6)
    return (
        DriftPhase(kind="protocol-mix", batches=b1, anomaly_rate=0.3),
        DriftPhase(kind="rule-violating", batches=b2, anomaly_rate=0.6,
                   sig_rotation=1),
        DriftPhase(kind="heavy-churn", batches=b3, anomaly_rate=0.3,
                   sig_rotation=1),
    )


def serve_adaptive_benchmarks(fast: bool = False) -> List[str]:
    """Stream one DriftScenario cycle with the AdaptiveLoop on vs off:
    pkts/sec overhead of the drift statistics + background control plane,
    installs/hour, and the fraction of installs inside the Eq. 18 ``t_cp``
    budget (the ``pps`` field feeds the CI regression gate)."""
    from repro.serve.adaptive_loop import (
        AdaptiveLoop, AdaptiveLoopConfig, DriftPolicy,
    )

    rows: List[str] = []
    ccfg, params = _build()
    phases = _drift_phases(fast)
    for mode in ("off", "on"):
        sc = DriftScenario(
            phases=phases, pkt_len=16,
            packets_per_batch=128 if fast else 256, seed=7,
        )
        program = compile_program(
            ccfg, params,
            rules=lambda c: C.default_rules(
                c, jnp.asarray(sc.phase_anomaly_signature(0))
            ),
            backend="xla",
        )
        eng = program.deploy(DeploySpec(
            flow=FlowEngineConfig(capacity=1024 if fast else 2048,
                                  lanes=128 if fast else 256),
        ))
        loop = None
        if mode == "on":
            # async: the recluster/compile epoch rides a background thread,
            # so the measured pps includes only the fast-path overhead
            loop = AdaptiveLoop(
                eng,
                policy=DriftPolicy(warmup_ticks=2, cooldown_ticks=3,
                                   sig_novelty=0.05, churn_shift=0.12),
                cfg=AdaptiveLoopConfig(sync=False),
            )
        sink = loop if loop is not None else eng
        warm = sc.next_batch()  # compile outside the timed region
        sink.ingest(warm["flow_ids"], warm["tokens"])
        t0 = time.perf_counter()
        pkts = 0
        for _ in range(sc.batches_per_cycle - 1):
            b = sc.next_batch()
            sink.ingest(b["flow_ids"], b["tokens"])
            pkts += len(b["flow_ids"])
        # stop the clock BEFORE draining the background epoch: the gated
        # pps is the fast-path overhead, not control-plane compile latency
        dt = time.perf_counter() - t0
        if loop is not None:
            loop.close()
        extra = ""
        if loop is not None:
            n_inst = loop.installs
            extra = (
                f";triggers={len(loop.history)};installs={n_inst}"
                f";installs_per_hour={n_inst / dt * 3600:.1f}"
                f";within_t_cp={loop.installs_within_budget}/{max(n_inst, 1)}"
                f";rollbacks={sum(r.rolled_back for r in loop.history)}"
            )
        rows.append(_emit(
            f"serve/adaptive/{mode}/xla",
            dt / max(pkts, 1) * 1e6, pkts / dt, eng, extra=extra,
        ))
    return rows


def serve_redteam_benchmarks(fast: bool = False) -> List[str]:
    """Adaptive replay throughput per registered red-team campaign
    (``fast`` = the smoke campaign only): pkts/sec with the loop closed,
    plus the scorecard counters the trust gate checks — veto flips and
    pinning violations ride along so a regression here is visible in the
    bench CSV too, not only in the gate artifact."""
    from repro.data.campaigns import SMOKE_CAMPAIGN, get_campaign, list_campaigns
    from repro.serve import redteam as RT

    rows: List[str] = []
    names = (SMOKE_CAMPAIGN,) if fast else list_campaigns()
    cfg = RT.RedTeamConfig(backend="xla")
    for name in names:
        campaign = get_campaign(name)
        (correct, total, _vetoes, _anom, tracker, loop, wall, evicted,
         _hist) = RT._replay_campaign_mode(campaign, cfg, "adaptive")
        pkts = tracker.packets
        acc = float(correct.sum() / max(total.sum(), 1))
        rows.append(csv_row(
            f"serve/redteam/{name}/xla",
            wall / max(pkts, 1) * 1e6,
            f"pps={pkts / wall:.0f}"
            f";installs={loop.installs}"
            f";within_t_cp={loop.installs_within_budget}"
            f"/{max(loop.installs, 1)}"
            f";veto_flips={tracker.veto_flips}"
            f";pinning_violations={tracker.pinning_violations}"
            f";evicted={evicted};accuracy={acc:.4f}",
        ))
    return rows


# --------------------------------------------------------------------------
# sharded sweep: pkts/sec and resident flows vs device count
# --------------------------------------------------------------------------

def _sharded_worker_rows(num_shards: int, fast: bool) -> List[str]:
    """Measure the ShardedFlowEngine at ONE device count (runs inside a
    subprocess whose XLA_FLAGS forced ``num_shards`` host devices)."""
    rows: List[str] = []
    scenarios = ("protocol-mix",) if fast else ("protocol-mix", "heavy-churn")
    batches = 3 if fast else 6
    ccfg, params = _build()
    eng = None
    for kind in scenarios:
        # identical traffic at every device count: the scenario does not
        # depend on num_shards, so pps deltas are placement-only
        sc = FlowScenario(
            kind=kind, pkt_len=16,
            packets_per_batch=256 if fast else 512, seed=7,
        )
        if eng is None:
            program = compile_program(
                ccfg, params,
                rules=lambda c: C.default_rules(
                    c, jnp.asarray(sc.anomaly_signature)
                ),
                backend="xla",
            )
            eng = program.deploy(DeploySpec(
                engine="sharded",
                flow=FlowEngineConfig(capacity=512 if fast else 1024,
                                      lanes=128 if fast else 256),
                num_shards=num_shards,
            ))
        else:
            eng.reset()
        warm = sc.next_batch()
        eng.ingest(warm["flow_ids"], warm["tokens"])
        t0 = time.perf_counter()
        pkts = 0
        for _ in range(batches):
            b = sc.next_batch()
            eng.ingest(b["flow_ids"], b["tokens"])
            pkts += len(b["flow_ids"])
        dt = time.perf_counter() - t0
        rows.append(_emit(
            f"serve/flow_sharded/{kind}/shards{num_shards}",
            dt / max(pkts, 1) * 1e6, pkts / dt, eng,
            extra=(
                f";shards={num_shards}"
                f";resident_per_shard="
                + "/".join(map(str, eng.resident_flows_per_shard()))
                + f";aggregate_capacity={eng.aggregate_capacity}"
            ),
        ))
    return rows


def serve_flow_sharded_benchmarks(fast: bool = False) -> Iterator[str]:
    """Sweep pkts/sec + resident flows vs device count (1/2/4/8 shards).

    On a chip every point that fits ``jax.devices()`` runs in this process
    (a chip belongs to one process).  On the CPU, as a rehearsal, each
    point runs ``--sharded-worker N`` in a subprocess with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` — the device
    count is fixed at jax init, so one process cannot sweep it.  Raises
    :class:`SweepFailed` after the last row if any worker failed."""
    counts = _SHARDS_FAST if fast else _SHARDS_FULL
    if _on_chip():
        for n in counts:
            if _fit_devices(f"serve/flow_sharded/shards{n}: skipped", n) == n:
                yield from _sharded_worker_rows(n, fast)
        return
    failed = []
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for n in counts:
        env = dict(os.environ)
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n}"
        ).strip()
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (os.path.join(repo_root, "src"),
                        env.get("PYTHONPATH", "")) if p
        )
        cmd = [sys.executable, "-m", "benchmarks.serve_bench",
               "--sharded-worker", str(n)] + (["--fast"] if fast else [])
        proc = subprocess.run(
            cmd, capture_output=True, text=True, env=env, cwd=repo_root,
            timeout=1800,
        )
        if proc.returncode != 0:
            # the ERROR row keeps the sweep's partial results printable,
            # and SweepFailed fails the run so a broken ShardedFlowEngine
            # fails the CI smoke gate instead of silently vanishing from
            # the regression gate's name set
            err_lines = (proc.stderr or "").strip().splitlines()
            yield csv_row(
                f"serve/flow_sharded/ERROR/shards{n}", 0.0,
                err_lines[-1] if err_lines else "worker failed",
            )
            failed.append(n)
            continue
        yield from (
            line for line in proc.stdout.splitlines()
            if line.startswith("serve/flow_sharded/")
        )
    if failed:
        raise SweepFailed(f"serve/flow_sharded: worker(s) for shards "
                          f"{failed} failed")


# --------------------------------------------------------------------------
# elastic service: steady-state pps around a live reshard cycle, plus the
# Eq. 18-measured install cost of each reshard
# --------------------------------------------------------------------------

def _elastic_worker_rows(devices: int, fast: bool) -> List[str]:
    """Measure the ElasticFlowService through one reshard cycle
    (S -> 2S -> S with S = devices/2), inside a subprocess whose XLA_FLAGS
    forced ``devices`` host devices.  Emits steady-state pps rows before /
    during / after the cycle (gated) and derived-only reshard-install rows
    (``install_ms``; no ``pps`` key, so the gate never compares them)."""
    lo, hi = max(1, devices // 2), devices
    batches = 3 if fast else 6
    ccfg, params = _build()
    sc = FlowScenario(
        kind="protocol-mix", pkt_len=16,
        packets_per_batch=256 if fast else 512, seed=7,
    )
    program = compile_program(
        ccfg, params,
        rules=lambda c: C.default_rules(c, jnp.asarray(sc.anomaly_signature)),
        backend="xla",
    )
    svc = program.deploy(DeploySpec(
        engine="elastic", num_shards=lo,
        flow=FlowEngineConfig(capacity=512 if fast else 1024,
                              lanes=128 if fast else 256, t_cp_s=60.0),
        elastic=ElasticConfig(keep_topologies=True),
    ))

    def timed(label: str) -> str:
        warm = sc.next_batch()  # trace/warm outside the timed region
        svc.ingest(warm["flow_ids"], warm["tokens"])
        t0 = time.perf_counter()
        pkts = 0
        for _ in range(batches):
            b = sc.next_batch()
            svc.ingest(b["flow_ids"], b["tokens"])
            pkts += len(b["flow_ids"])
        dt = time.perf_counter() - t0
        return _emit(
            f"serve/elastic/protocol-mix/{label}",
            dt / max(pkts, 1) * 1e6, pkts / dt, svc,
            extra=f";shards={svc.num_shards}"
                  f";aggregate_capacity={svc.aggregate_capacity}",
        )

    def reshard_row(label: str, n: int) -> str:
        rec = svc.reshard(n)
        return csv_row(
            f"serve/elastic/reshard/{label}", rec.install_s * 1e6,
            f"install_ms={rec.install_s * 1e3:.3f}"
            f";migrated={rec.migrated_flows};moved={rec.moved_flows}"
            f";churn_ok={int(rec.churn_ok)};t_cp_s={rec.t_cp_s:g}",
        )

    rows = [timed(f"shards{lo}-pre")]
    rows.append(reshard_row(f"shards{lo}-to-{hi}", hi))
    rows.append(timed(f"shards{hi}"))
    rows.append(reshard_row(f"shards{hi}-to-{lo}", lo))
    rows.append(timed(f"shards{lo}-post"))
    return rows


def serve_elastic_benchmarks(fast: bool = False) -> Iterator[str]:
    """Elastic reshard cycle over the chip's own devices, in this process;
    on the CPU, as a rehearsal, in a subprocess with forced host devices
    (2 fast / 8 full), so the sweep runs on single-device CI hosts too.
    Raises :class:`SweepFailed` after its ERROR row if the worker failed."""
    devices = 2 if fast else 8
    if _on_chip():
        yield from _elastic_worker_rows(
            _fit_devices("serve/elastic: cycle shrunk", devices), fast)
        return
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={devices}"
    ).strip()
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(repo_root, "src"),
                    env.get("PYTHONPATH", "")) if p
    )
    cmd = [sys.executable, "-m", "benchmarks.serve_bench",
           "--elastic-worker", str(devices)] + (["--fast"] if fast else [])
    proc = subprocess.run(
        cmd, capture_output=True, text=True, env=env, cwd=repo_root,
        timeout=1800,
    )
    if proc.returncode != 0:
        err_lines = (proc.stderr or "").strip().splitlines()
        yield csv_row(
            f"serve/elastic/ERROR/devices{devices}", 0.0,
            err_lines[-1] if err_lines else "worker failed",
        )
        raise SweepFailed(f"serve/elastic: worker for {devices} devices failed")
    yield from (line for line in proc.stdout.splitlines()
                if line.startswith("serve/elastic/"))


# --------------------------------------------------------------------------
# JSON dump + the >30% pkts/sec regression gate
# --------------------------------------------------------------------------

def rows_to_records(rows: List[str]) -> List[Dict]:
    """Parse ``name,us_per_call,derived`` rows into JSON-able records (the
    ``pps`` field is what the regression gate compares)."""
    records = []
    for row in rows:
        name, us, derived = row.split(",", 2)
        rec: Dict = {"name": name, "us_per_call": float(us)}
        for field in derived.split(";"):
            k, _, v = field.partition("=")
            try:
                rec[k] = float(v) if "." in v else int(v)
            except ValueError:
                rec[k] = v
        records.append(rec)
    return records


def write_json(rows: List[str], path: str) -> None:
    payload = {
        "schema": "serve-bench-v1",
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "records": rows_to_records(rows),
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")


def check_regression(
    new_path: str, baseline_path: str, tolerance: float = GATE_TOLERANCE
) -> List[str]:
    """Compare two ``--json`` dumps; return a list of failure messages
    (empty = gate passes).  Only names present in BOTH files are compared,
    so adding/removing benchmarks never trips the gate."""
    with open(new_path) as f:
        new = {r["name"]: r for r in json.load(f)["records"]}
    with open(baseline_path) as f:
        base = {r["name"]: r for r in json.load(f)["records"]}
    failures = []
    for name in sorted(set(new) & set(base)):
        b, n = base[name].get("pps"), new[name].get("pps")
        if not b or n is None:
            continue
        if n < (1.0 - tolerance) * b:
            failures.append(
                f"{name}: {n:.0f} pkt/s is {(1 - n / b) * 100:.0f}% below "
                f"baseline {b:.0f} pkt/s (tolerance {tolerance * 100:.0f}%)"
            )
    return failures


def main() -> None:
    from repro.launch.jax_cache import enable_compile_cache

    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also dump results as machine-readable JSON")
    ap.add_argument("--suite", default="all",
                    choices=("flow", "sharded", "adaptive", "elastic",
                             "redteam", "all"))
    ap.add_argument("--sharded-worker", type=int, default=0, metavar="N",
                    help="(internal) run the N-shard measurement in-process; "
                         "invoked by the sweep with N forced host devices")
    ap.add_argument("--elastic-worker", type=int, default=0, metavar="N",
                    help="(internal) run the elastic reshard cycle "
                         "in-process; invoked with N forced host devices")
    ap.add_argument("--gate", default=None, metavar="NEW_JSON",
                    help="regression-gate mode: compare NEW_JSON against "
                         "--baseline instead of running benchmarks")
    ap.add_argument("--baseline", default=None, metavar="BASELINE_JSON")
    args = ap.parse_args()

    if args.gate:
        if not args.baseline:
            ap.error("--gate requires --baseline")
        failures = check_regression(args.gate, args.baseline)
        if failures:
            print("serve-bench regression gate FAILED:", file=sys.stderr)
            for msg in failures:
                print(f"  {msg}", file=sys.stderr)
            print(
                "\nIf this slowdown is expected (intentional trade-off, new "
                "workload) or the baseline was measured on different "
                "hardware, refresh it with numbers from the machine class "
                "the gate runs on: download the BENCH_serve artifact from a "
                "known-good CI run and commit it as "
                "benchmarks/BENCH_serve_baseline.json (or regenerate "
                "locally if the gate runs locally:\n"
                "  PYTHONPATH=src python -m benchmarks.serve_bench --fast "
                "--json benchmarks/BENCH_serve_baseline.json).\n"
                "SERVE_BENCH_GATE_TOLERANCE=0.5 relaxes the gate while "
                "calibrating a new runner class.",
                file=sys.stderr,
            )
            sys.exit(1)
        print(f"serve-bench regression gate OK ({args.gate} vs {args.baseline})")
        return

    enable_compile_cache()
    failures: List[str] = []
    if args.sharded_worker:
        rows = _sharded_worker_rows(args.sharded_worker, fast=args.fast)
    elif args.elastic_worker:
        rows = _elastic_worker_rows(args.elastic_worker, fast=args.fast)
    else:
        rows = []
        if args.suite in ("flow", "all"):
            rows += serve_flow_benchmarks(fast=args.fast)
        if args.suite in ("adaptive", "all"):
            rows += serve_adaptive_benchmarks(fast=args.fast)
        if args.suite in ("redteam", "all"):
            rows += serve_redteam_benchmarks(fast=args.fast)
        for suite, sweep in (("sharded", serve_flow_sharded_benchmarks),
                             ("elastic", serve_elastic_benchmarks)):
            if args.suite not in (suite, "all"):
                continue
            try:
                for row in sweep(fast=args.fast):
                    rows.append(row)
            except SweepFailed as e:
                failures.append(str(e))
    print("name,us_per_call,derived")
    for row in rows:
        print(row, flush=True)
    if args.json:
        write_json(rows, args.json)
    if failures:
        print(f"{len(failures)} benchmark sweep(s) FAILED:", file=sys.stderr)
        for msg in failures:
            print(f"  {msg}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
