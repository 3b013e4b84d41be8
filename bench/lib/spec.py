"""Load ``BENCHMARK.json`` and the files it names, by name.

Everything that belongs to one configuration, traffic mix, loop or
per-layer metric sits in a file of its own, found here from its name:

* configuration ``<name>``: the ``file`` given in ``BENCHMARK.json``;
* traffic mix ``<name>``: ``bench/traffic/<name>.json``;
* loop ``<name>`` (named by the traffic file): ``bench/loops/<name>.py``;
* per-layer metric ``<name>``: ``bench/metrics/<name>.py``;
* model family ``<name>`` (named by the configuration's ``"family"``):
  ``bench/models/<name>.py``, everything the harness knows of one
  architecture (``arch_config``, ``make_params``, ``token_flops``,
  ``row_bytes``, ``weight_bytes``; see ``models/chimera_dataplane.py``);
* plain reference ``<name>`` (named by the configuration's
  ``"reference"``): ``bench/reference/<name>.py``;
* output limits of configuration ``<name>``: ``bench/limits/<name>.json``.

A configuration file's ``model`` holds the family's own keys, and these,
which the harness reads whatever the family: ``vocab_size`` and
``vocab_pad_multiple`` (the token alphabet, and with ``classifier``'s
``marker_base`` the signature words), ``d_model`` (the pooled features the
score stage reads) and ``dtype``.
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    family: Any  # the module bench/models/<config["family"]>.py
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]  # the end-to-end metrics this cell reports
    per_layer: List[Dict[str, Any]]  # the per-layer metrics this cell reports
    limits: Dict[str, float]


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """Import ``bench/<kind>/<name>.py`` (names may hold dots)."""
    path = os.path.join(BENCH_DIR, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_family(config: Dict[str, Any]):
    """The family module the configuration names by its ``"family"`` key."""
    if "family" not in config:
        raise KeyError(f"configuration {config.get('name')!r} names no \"family\": "
                       f"give the module bench/models/<family>.py of its architecture")
    return load_module("models", config["family"])


def _in_cell(metric: Dict[str, Any], cell: str, e2e_names: List[str]) -> bool:
    """A metric with ``workloads`` is reported in the cells it lists; a
    per-layer metric without it in every cell that reports what it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_names


def load_cell(name: str, bench_json: str = os.path.join(ROOT, "BENCHMARK.json")) -> Cell:
    spec = load_json(bench_json)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {sorted(cells)}")
    w = cells[name]
    cfg_file = {c["name"]: c for c in spec["configs"]}[w["config"]]["file"]
    e2e = [m for m in spec["end_to_end"] if _in_cell(m, name, [])]
    e2e_names = [m["name"] for m in e2e]
    per_layer = [m for m in spec["per_layer"] if _in_cell(m, name, e2e_names)]
    return make_cell(name, int(w["chips"]), os.path.join(ROOT, cfg_file), w["traffic"],
                     e2e, per_layer)


def make_cell(name: str, chips: int, config_file: str, traffic: str,
              end_to_end: List[Dict[str, Any]], per_layer: List[Dict[str, Any]]) -> Cell:
    """A cell from its configuration file and traffic name, with the limits
    of its configuration."""
    config = load_json(config_file)
    return Cell(name=name, chips=chips, config=config, family=load_family(config),
                traffic=load_json(os.path.join(BENCH_DIR, "traffic", f"{traffic}.json")),
                end_to_end=end_to_end, per_layer=per_layer,
                limits=load_json(os.path.join(BENCH_DIR, "limits", f"{config['name']}.json")))
