"""``BENCHMARK.json`` agrees with the files the harness finds by name."""
import importlib
import json
import re

import pytest

from conftest import ROOT

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_names_units_and_bounds():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in SPEC[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    # a full check of 24 cells fits its time
    runs = 2 + 14 * 24
    assert runs * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_each_pair_of_config_and_traffic_once():
    pairs = [(c["config"], c["traffic"]) for c in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_cell_files(cell):
    spec = json.loads((ROOT / "bench" / "workloads" /
                       f"{cell['name']}.json").read_text())
    assert {k: spec[k] for k in ("config", "traffic", "chips", "why")} == {
        k: cell[k] for k in ("config", "traffic", "chips", "why")}
    assert (ROOT / "bench" / "traffic" / f"{cell['traffic']}.json").exists()
    assert cell["config"] in {c["name"] for c in SPEC["configs"]}
    # the executor's mesh spans the cell's chips
    assert spec["executor"].get("mesh_data", 1) == cell["chips"]


def test_a_cell_on_more_chips_than_its_mesh_is_refused(tmp_path, monkeypatch):
    from bench import drive

    spec = json.loads((ROOT / "bench" / "workloads" /
                       f"{SPEC['workloads'][0]['name']}.json").read_text())
    spec["chips"] = 4
    (tmp_path / "workloads").mkdir()
    (tmp_path / "workloads" / "four.json").write_text(json.dumps(spec))
    for kind in ("configs", "traffic"):
        (tmp_path / kind).symlink_to(ROOT / "bench" / kind)
    monkeypatch.setattr(drive, "BENCH", tmp_path)
    with pytest.raises(ValueError, match="mesh spans 1"):
        drive.Cell("four")


@pytest.mark.parametrize("config", SPEC["configs"], ids=lambda c: c["name"])
def test_config_files(config):
    cfg = json.loads((ROOT / config["file"]).read_text())
    assert cfg["name"] == config["name"]
    assert cfg["source"] == config["source"]
    assert cfg["reduced"] == config["reduced"]
    # every piece the configuration names has a module of its own
    for kind, name in (("tasks", cfg["model"]["task"]),
                       ("datasets", cfg["data"]["generator"]),
                       ("splits", cfg["data"]["split"])):
        importlib.import_module(f"bench.{kind}.{name}")


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_traffic_names_its_modules(cell):
    t = json.loads((ROOT / "bench" / "traffic" /
                    f"{cell['traffic']}.json").read_text())
    for kind, name in (("policies", t["policy"]), ("merges", t["aggregator"])):
        importlib.import_module(f"bench.{kind}.{name}")


def test_the_stated_precision_is_enforced():
    import jax

    from bench import drive
    from bench.tasks import mlp

    model = {"dim": 4, "hidden": 8, "n_classes": 3}
    task = mlp.build(model)
    params = jax.eval_shape(mlp.init_fn(model), jax.random.PRNGKey(0))
    drive.apply_precision({"params": "float32", "matmul": "default"}, task,
                          params)
    with pytest.raises(ValueError, match="states bfloat16"):
        drive.apply_precision({"params": "bfloat16", "matmul": "default"},
                              task, params)
    jax.config.update("jax_default_matmul_precision", "high")
    try:
        with pytest.raises(ValueError, match="states default matmul precision"):
            drive.apply_precision({"params": "float32", "matmul": "default"},
                                  task, params)
    finally:
        jax.config.update("jax_default_matmul_precision", None)


@pytest.mark.parametrize("metric", SPEC["per_layer"], ids=lambda m: m["name"])
def test_metric_readers(metric):
    reader = importlib.import_module(f"bench.metrics.{metric['name']}")
    assert (reader.LAYER, reader.SOURCE, reader.UNIT, reader.MOVES) == (
        metric["layer"], metric["source"], metric["unit"], metric["moves"])
    cells = {c["name"] for c in SPEC["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
