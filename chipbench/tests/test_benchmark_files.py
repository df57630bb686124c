"""BENCHMARK.json is well formed and every cell resolves its files by name."""
import importlib
import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = {w["name"]: w for w in BENCH["workloads"]}


def test_top_level_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(x["name"] for x in BENCH["workloads"])) == len(CELLS)
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert all((ROOT / p).is_dir() for p in BENCH["paths"])
    assert all(b["bound"] <= 0.25 for b in BENCH["end_to_end"])


@pytest.mark.parametrize("name", sorted(CELLS))
def test_cell_resolves_its_files(name):
    from chipbench import model, traffic
    w = CELLS[name]
    conf = {c["name"]: c for c in BENCH["configs"]}[w["config"]]
    spec = model.load_config(w["config"])
    assert (ROOT / conf["file"]).is_file()
    assert spec["source"] == conf["source"]
    assert (ROOT / "chipbench" / "references"
            / f"{spec['reference']}.py").is_file()
    mix = traffic.load(w["traffic"])
    importlib.import_module(f"chipbench.drivers.{mix['driver']}")
    assert traffic.requests(mix, 2**33 + 1, spec["vocab_size"], 10.0)
    cell = json.loads((ROOT / "chipbench" / "cells"
                       / f"{name}.json").read_text())
    assert cell["limits"]
    reported = {m["name"] for m in BENCH["end_to_end"]
                if name in m.get("workloads", [name])}
    assert "setup_s" in reported and len(reported) >= 2
    layer = [m for m in BENCH["per_layer"]
             if name in m.get("workloads", [name])]
    assert layer and all(m["moves"] in reported for m in layer)


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_every_per_layer_metric_has_a_reader(metric):
    from chipbench import run
    assert callable(run.metric_reader(metric))


@pytest.mark.parametrize("name", sorted({c["name"] for c in
                                         BENCH["configs"]}))
def test_configuration_matches_the_program(name):
    """The program runs the file's sizes, and the harness's weight tree
    has the shapes of the program's own."""
    import jax
    from chipbench import model
    spec = model.load_config(name)
    cfg = model.program_config(spec)
    model.check_layout(spec, cfg, jax.eval_shape(
        lambda: model.make_weights(spec, 0)))
    matmuls = cfg.n_params()          # the program's count leaves out norms
    assert 0 <= model.n_params(spec) - matmuls <= 4 * cfg.n_layers * 2048
