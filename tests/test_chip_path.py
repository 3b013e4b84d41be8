"""The chip path hides nothing: what a kernel backend actually runs is
recorded and reported, chip constants come from the device kind, the
full-width table budget comes from device HBM, entry points place the
compile cache, and the benchmarks leave the chip alone on import."""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.compile import compile_program
from repro.core import hardware_model as HM
from repro.serve.deploy import TABLE_BUDGET_STAGE, DeploySpec
from repro.serve.flow_engine import FlowEngineConfig
from repro.train import classifier as C

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _classifier(tiny_classifier_cfg, n_global, use_chimera=True):
    arch = tiny_classifier_cfg.arch
    arch = dataclasses.replace(
        arch, use_chimera=use_chimera,
        chimera=dataclasses.replace(arch.chimera, n_global=n_global),
    )
    ccfg = dataclasses.replace(tiny_classifier_cfg, arch=arch)
    params, _ = C.init_classifier(ccfg, jax.random.PRNGKey(0))
    return ccfg, params


def _program(ccfg, params, backend):
    return compile_program(
        ccfg, params,
        rules=lambda c: C.default_rules(c, jnp.asarray([400, 401, 402, 403])),
        backend=backend,
    )


@pytest.mark.parametrize("backend, use_chimera, reason", [
    ("pallas-tpu", True, "global TCAM tier"),
    ("pallas-interpret", True, "global TCAM tier"),
    ("pallas-tpu", False, "softmax attention"),
    ("pallas-interpret", False, "softmax attention"),
], ids=["pallas-tpu", "pallas-interpret", "pallas-tpu-softmax",
        "pallas-interpret-softmax"])
def test_global_tier_decode_on_xla_is_recorded_and_reported(
        tiny_classifier_cfg, backend, use_chimera, reason):
    """A decode that runs on XLA under a kernel backend (the global TCAM
    tier, or a softmax backbone) is both in the ledger and in the engine's
    stage report."""
    ccfg, params = _classifier(tiny_classifier_cfg, n_global=8,
                               use_chimera=use_chimera)
    program = _program(ccfg, params, backend)
    rows = [e for e in program.ledger.entries
            if e.stage == "kernel-backend" and e.resource == "xla-decode-layers"]
    assert len(rows) == 1 and rows[0].used == ccfg.arch.n_layers
    assert reason in rows[0].detail
    engine = program.deploy(
        DeploySpec(flow=FlowEngineConfig(capacity=8, lanes=8, fused=True))
    )
    assert engine.stage_impls["decode"].startswith("xla (")
    assert reason in engine.stage_impls["decode"]
    assert engine.stage_impls["score"] == f"flow_ingest score kernel ({backend})"


def test_decode_kernel_taken_without_global_tier(tiny_classifier_cfg):
    ccfg, params = _classifier(tiny_classifier_cfg, n_global=0)
    program = _program(ccfg, params, "pallas-tpu")
    assert not [e for e in program.ledger.entries
                if e.resource == "xla-decode-layers"]
    engine = program.deploy(DeploySpec(flow=FlowEngineConfig(capacity=8, lanes=8)))
    assert engine.stage_impls == {
        "decode": "decode_step (pallas-tpu)", "score": "xla",
    }


def test_xla_backend_records_no_fallback(tiny_classifier_cfg):
    ccfg, params = _classifier(tiny_classifier_cfg, n_global=8)
    program = _program(ccfg, params, "xla")
    assert not [e for e in program.ledger.entries
                if e.resource == "xla-decode-layers"]


def test_explicit_pallas_tpu_window_attention_refuses_fallback():
    from repro.kernels.window_attention.ops import sliding_window_attention

    q = jnp.zeros((1, 1, 24, 8))  # T=24: no admissible tile covers it
    with pytest.raises(ValueError, match="pallas-tpu"):
        sliding_window_attention(q, q, q, window=8, backend="pallas-tpu")
    out = sliding_window_attention(q, q, q, window=8, backend="auto")
    assert out.shape == (1, 1, 24, 8)


def test_tpu_spec_by_device_kind():
    v5e = HM.tpu_spec_for("TPU v5 lite")
    assert v5e is HM.DEFAULT_TPU and v5e.peak_flops_bf16 == 197e12
    assert "Google Cloud" in v5e.source
    with pytest.raises(ValueError, match="no TPUSpec"):
        HM.tpu_spec_for("TPU v99")
    assert HM.device_tpu_spec() is HM.DEFAULT_TPU  # CPU rehearses the v5e


def test_full_width_deploy_budgets_from_device_hbm(tiny_classifier_cfg,
                                                   monkeypatch):
    from repro.serve import deploy

    ccfg, params = _classifier(tiny_classifier_cfg, n_global=8)
    program = _program(ccfg, params, "xla")
    spec = DeploySpec(flow=FlowEngineConfig(capacity=8, lanes=8))
    # the CPU reports no memory stats: no substitution, no ledger line
    engine = program.deploy(spec)
    assert engine.state_budget_bytes == HM.DEFAULT_DATAPLANE.sram_total_bits // 8
    assert not [e for e in program.ledger.entries if e.stage == TABLE_BUDGET_STAGE]

    monkeypatch.setattr(deploy, "device_table_budget", lambda: 10 ** 9)
    for _ in range(2):  # a re-deploy refreshes the line, never duplicates it
        engine = program.deploy(spec)
    rows = [e for e in program.ledger.entries if e.stage == TABLE_BUDGET_STAGE]
    assert len(rows) == 1 and rows[0].budget == 10 ** 9
    assert rows[0].used == engine.resident_state_bytes()
    assert engine.state_budget_bytes == 10 ** 9
    # sharded deploys budget each shard's device the same way
    engine = program.deploy(DeploySpec(engine="sharded", flow=spec.flow,
                                       num_shards=1))
    rows = [e for e in program.ledger.entries if e.stage == TABLE_BUDGET_STAGE]
    assert len(rows) == 1 and rows[0].used == engine.shard_state_bytes()
    assert engine.state_budget_bytes == 10 ** 9
    # a budget the spec names is kept, and nothing is substituted
    named = HM.DEFAULT_DATAPLANE.sram_total_bits // 8
    engine = program.deploy(DeploySpec(flow=dataclasses.replace(
        spec.flow, state_budget_bytes=named)))
    assert engine.state_budget_bytes == named
    assert not [e for e in program.ledger.entries if e.stage == TABLE_BUDGET_STAGE]


def test_compile_cache_placement(monkeypatch, tmp_path):
    from repro.launch import jax_cache

    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_persistent_cache_min_compile_time_secs,
              jax.config.jax_persistent_cache_min_entry_size_bytes)
    try:
        monkeypatch.delenv(jax_cache.ENV, raising=False)
        path = jax_cache.enable_compile_cache()
        assert path == os.path.join(os.path.abspath(ROOT), ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        monkeypatch.setenv(jax_cache.ENV, str(tmp_path))
        assert jax_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    finally:
        jax.config.update("jax_compilation_cache_dir", before[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs", before[1])
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", before[2])


def _run(code, **env):
    full = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src") + os.pathsep + ROOT,
                JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=full, cwd=ROOT, timeout=300)


def test_benchmarks_leave_jax_backends_alone_on_import():
    proc = _run(
        "import benchmarks.serve_bench, benchmarks.kernels_bench, "
        "benchmarks.run, repro.launch.flow_serve\n"
        "from jax._src import xla_bridge\n"
        "print(sorted(xla_bridge._backends))"
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == "[]"


def test_benchmarks_run_exits_nonzero_on_a_failed_suite():
    proc = _run(
        "import sys\n"
        "from benchmarks import run, tables\n"
        "def boom():\n"
        "    raise RuntimeError('suite broke')\n"
        "tables.table2_resources = boom\n"
        "sys.argv = ['run', '--only', 'table2']\n"
        "run.main()\n"
    )
    assert proc.returncode == 1, proc.stderr[-2000:]
    assert "table2/ERROR" in proc.stdout


@pytest.mark.parametrize("entry", ["benchmarks.run", "benchmarks.serve_bench"])
def test_failed_sweep_keeps_its_rows_and_fails_the_run(entry):
    """A device sweep whose worker failed yields its rows, the ERROR row
    among them, then raises: both entry points print them and exit 1."""
    argv = (["run", "--only", "serve_flow_sharded"] if entry == "benchmarks.run"
            else ["serve_bench", "--suite", "sharded"])
    proc = _run(
        "import sys\n"
        "from benchmarks import serve_bench\n"
        f"import {entry} as entry\n"
        "def sweep(fast=False):\n"
        "    yield 'serve/flow_sharded/xla/shards1,1.0,pps=1'\n"
        "    yield 'serve/flow_sharded/ERROR/shards2,0.0,worker failed'\n"
        "    raise serve_bench.SweepFailed('shards [2] failed')\n"
        "serve_bench.serve_flow_sharded_benchmarks = sweep\n"
        f"sys.argv = {argv!r}\n"
        "entry.main()\n"
    )
    assert proc.returncode == 1, proc.stderr[-2000:]
    assert "serve/flow_sharded/xla/shards1" in proc.stdout
    assert "serve/flow_sharded/ERROR/shards2" in proc.stdout
    assert "shards [2] failed" in proc.stdout + proc.stderr


def test_chip_smoke_refuses_the_cpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    for line in proc.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


@pytest.mark.parametrize("phase", ["one_chip", "four_chips"])
def test_chip_smoke_phases_rehearse_on_cpu(phase, monkeypatch):
    """The smoke's phases at the reduced preset on the CPU: the fused
    interpret-mode engine against the xla engine, and a one-shard sharded
    deploy against a one-device engine."""
    monkeypatch.syspath_prepend(os.path.abspath(ROOT))
    import chip_smoke

    kw = ({"backend": "pallas-interpret"} if phase == "one_chip"
          else {"shards": 1})
    assert getattr(chip_smoke, phase)(smoke=True, batches=3, **kw)
