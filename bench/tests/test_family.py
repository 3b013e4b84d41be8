"""The seam between the harness and a configuration's architecture: the
family module that the configuration's ``"family"`` names holds all that
the harness knows of one backbone, and ``bench/lib`` none of it."""

import hashlib
import importlib.util
import json
import os
import re

import jax
import numpy as np
import pytest

from lib import spec

from helpers import DATA, tiny_cell

# sha256 (first 16 hex digits) of every leaf that make_params draws at
# tiny.json's widths for seed 2**31 + 7, recorded with the harness as it was
# before the weights moved into the family: the same key, the same draws in
# the same order, bit for bit.
TINY_PARAMS_SHA = {
    "['anom']['w']": "0228a61037ffb9e1",
    "['backbone']['blocks']['b0']['attn']['chimera']['fm']['w']": "c3c24f85c026794e",
    "['backbone']['blocks']['b0']['attn']['chimera']['k_global']": "f7e98c1527458084",
    "['backbone']['blocks']['b0']['attn']['chimera']['sig_proj']": "7229ef8bd8151b6a",
    "['backbone']['blocks']['b0']['attn']['chimera']['v_global']": "4614ddfac7a8465e",
    "['backbone']['blocks']['b0']['attn']['wk']['w']": "2b427ed05d08a7be",
    "['backbone']['blocks']['b0']['attn']['wo']['w']": "c346344eb2489eb2",
    "['backbone']['blocks']['b0']['attn']['wq']['w']": "438bc01c681d3b0f",
    "['backbone']['blocks']['b0']['attn']['wv']['w']": "e3314e350da8de08",
    "['backbone']['blocks']['b0']['ln1']['scale']": "6680ff59ec1b3ea1",
    "['backbone']['blocks']['b0']['ln2']['scale']": "e344a215abad23b8",
    "['backbone']['blocks']['b0']['mlp']['wg']['w']": "986cd1366a442fea",
    "['backbone']['blocks']['b0']['mlp']['wi']['w']": "2cabd560b707595b",
    "['backbone']['blocks']['b0']['mlp']['wo']['w']": "0b50059cbe3bb8a0",
    "['backbone']['embed']['table']": "6a9a59265643eeca",
    "['backbone']['final_norm']['scale']": "a1061f646ab8c89d",
    "['backbone']['head']['w']": "89ad0097c8dc67e2",
    "['cls']['w']": "d5f22658e7210bc1",
    "['fusion']['alpha']": "e00e5eb9444182f3",
    "['fusion']['beta']": "e00e5eb9444182f3",
}
CHIMERA_ONLY_KEYS = ("feature_map", "n_global", "chunk_size", "match_hamming", "d_head")


def test_make_params_draws_the_same_bits_as_before():
    cfg = spec.load_json(os.path.join(DATA, "tiny.json"))
    params = spec.load_family(cfg).make_params(cfg["model"], cfg["classifier"], 2**31 + 7)
    got = {jax.tree_util.keystr(path): hashlib.sha256(np.asarray(x).tobytes()).hexdigest()[:16]
           for path, x in jax.tree_util.tree_leaves_with_path(params)}
    assert got == TINY_PARAMS_SHA


@pytest.mark.parametrize("kv_heads", [4, 2, 1])
def test_weights_and_row_take_the_program_layout(kv_heads):
    """At 4 query heads over 4, 2 or 1 kv heads, the family's weights have
    the program's layout, and its row is the deployed engine's own
    accounting of a flow (which adds an 8-byte host LRU stamp)."""
    from lib import flops, system, weights

    cfg = tiny_cell(kv_heads=kv_heads).config
    family = spec.load_family(cfg)
    params = family.make_params(cfg["model"], cfg["classifier"], 5)
    ccfg = system.classifier_config(cfg, family)
    weights.check_layout(params, system.program_layout(ccfg))
    classes = {**cfg["classifier"], "vocab_size": cfg["model"]["vocab_size"]}
    base = cfg["classifier"]["marker_base"]
    rule = weights.anomaly_rule(np.array([base]), flops.sig_words(cfg["model"], classes), base)
    _, engine = system.deploy(cfg, ccfg, params, rule)
    assert engine.per_flow_state_bytes() == family.row_bytes(cfg["model"], classes) + 8


def test_a_configuration_without_a_family_fails_at_load(tmp_path):
    cfg = spec.load_json(os.path.join(spec.BENCH_DIR, "configs", "chimera-dp-1chip.json"))
    del cfg["family"]
    path = tmp_path / "no_family.json"
    path.write_text(json.dumps(cfg))
    with pytest.raises(KeyError, match='"family"'):
        spec.make_cell("dp1.zipf.backlog", 1, str(path), "zipf.backlog", [], [])


def test_every_configuration_names_its_family():
    bench = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    for c in bench["configs"]:
        cfg = spec.load_json(os.path.join(spec.ROOT, c["file"]))
        assert os.path.isfile(os.path.join(spec.BENCH_DIR, "models", f"{cfg['family']}.py"))


def test_lib_names_no_chimera_only_model_key():
    lib = os.path.join(spec.BENCH_DIR, "lib")
    pattern = re.compile(r"\b(" + "|".join(CHIMERA_ONLY_KEYS) + r")\b")
    for name in sorted(os.listdir(lib)):
        if name.endswith(".py"):
            with open(os.path.join(lib, name)) as f:
                found = pattern.findall(f.read())
            assert not found, f"bench/lib/{name} names {sorted(set(found))}"


def test_every_architecture_hook_is_reached_through_the_family(monkeypatch):
    """The tiny cell, traced, under a family that records its hooks: the
    weights, the program's architecture and the three counts all come from
    the family, the counts through ``ingest_mfu``'s reader."""
    import run as R
    from lib import runner

    real = spec.load_module

    def load_module(kind, name):
        if (kind, name) != ("models", "seam_family"):
            return real(kind, name)
        s = importlib.util.spec_from_file_location("bench_tests_seam_family",
                                                   os.path.join(DATA, "seam_family.py"))
        mod = importlib.util.module_from_spec(s)
        s.loader.exec_module(mod)
        return mod

    monkeypatch.setattr(spec, "load_module", load_module)
    monkeypatch.setattr(runner, "TRACE_S", 0.3)
    monkeypatch.setattr(R, "load_peaks", lambda kind: spec.load_json(
        f"{spec.BENCH_DIR}/peaks.json")["TPU v5 lite"])
    cell = tiny_cell()
    cell.config["family"] = "seam_family"
    cell.family = spec.load_family(cell.config)
    bench = spec.load_json(f"{spec.ROOT}/BENCHMARK.json")
    cell.per_layer = [m for m in bench["per_layer"] if m["name"] == "ingest_mfu"]
    argv = ["--workload", "tiny", "--seed", "2147483711", "--seconds", "2", "--trace", "1"]
    res = R.run(argv, cell=cell, require_tpu=False)
    assert res["correct"], res["checks"]
    assert res["metrics"]["ingest_mfu"]["value"] > 0
    reached = {name for name, _ in cell.family.calls}
    assert reached == set(cell.family.HOOKS)
    reader = os.path.join(spec.BENCH_DIR, "metrics", "ingest_mfu.py")
    for count in ("token_flops", "row_bytes", "weight_bytes"):
        assert any(reader in stack for name, stack in cell.family.calls if name == count), count
