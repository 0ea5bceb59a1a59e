"""BENCHMARK.json and the files it names: every cell, configuration and
per-layer metric is found by name, and the program agrees with the
benchmark's parameter layout for every configuration."""
import importlib.util
import json
import os
import re

import jax
import pytest

import _bench

cells = _bench.module("cells")
check = _bench.module("check")
weights = _bench.module("weights")

ROOT = cells.ROOT
BENCH = cells.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][:2] == ["python3", "bench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_names_units_and_keys():
    names = []
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        names.append(c["name"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert NAME.match(w["traffic"])
        names.append(w["name"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        names.append(m["name"])
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 2)


@pytest.mark.parametrize("entry", BENCH["configs"],
                         ids=lambda c: c["name"])
def test_config_file(entry):
    from repro.configs.base import ModelConfig, get_config
    with open(os.path.join(ROOT, entry["file"])) as f:
        conf = json.load(f)
    assert conf["name"] == entry["name"]
    assert conf["source"] == entry["source"]
    assert sorted(conf["reduced"]) == sorted(entry["reduced"])
    fields = set(ModelConfig.__dataclass_fields__)
    assert set(conf["model"]) <= fields
    for k, r in conf["reduced"].items():
        assert conf["model"][k] == r["here"] != r["source"]
    cfg = get_config(conf["arch"]).replace(**conf["model"])
    assert cfg.n_layers == conf["model"]["n_layers"]


@pytest.mark.parametrize("entry", BENCH["workloads"],
                         ids=lambda w: w["name"])
def test_cell_files_and_layout(entry):
    from repro.configs.base import get_config
    from repro.models import build_model
    cell = cells.load_cell(entry["name"])
    assert cell.workload["config"] == entry["config"]
    assert cell.chips == entry["chips"]
    assert cell.workload["limits"]
    assert set(cell.workload["limits"]) <= set(check.NUMBERS)
    mesh = cell.workload["mesh_shape"]
    assert (mesh is None) == (cell.chips == 1)
    model = build_model(get_config(cell.config["arch"]).replace(
        **cell.config["model"]))
    want = {tuple(k.key for k in p): tuple(s.shape) for p, s in
            jax.tree_util.tree_flatten_with_path(
                model.abstract_params())[0]}
    got = {p: s for p, (s, _) in weights.layout(cell.model).items()}
    assert want == got


@pytest.mark.parametrize("entry", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_reader_found_by_name(entry):
    path = os.path.join(ROOT, "bench", "metrics", entry["name"] + ".py")
    spec = importlib.util.spec_from_file_location("m", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert callable(mod.read)
    for cell in entry.get("workloads", []):
        assert cell in {w["name"] for w in BENCH["workloads"]}
