"""Cells, configurations, traffic mixes and per-layer readers load by
name from BENCHMARK.json."""

import pytest

from bench import roofline, spec

BENCH = spec.load_json(spec.ROOT / "BENCHMARK.json")


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_loads(name):
    cell = spec.cell(name)
    assert cell.chips == 1
    assert 1 <= cell.active_rounds <= cell.rounds
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert cell.per_layer


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_every_per_layer_metric_has_a_reader(metric):
    assert callable(spec.layer_reader(metric))


def test_configs_state_their_cuts_and_guarantees():
    for c in BENCH["configs"]:
        config = spec.load_json(spec.ROOT / c["file"])
        assert config["name"] == c["name"]
        assert set(c["reduced"]) == set(config["reduced"])
        assert config["guarantees"]
        assert config["algorithm"] in roofline.BUFFERS


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_every_configuration_names_its_deployment_file(entry):
    config = spec.load_json(spec.ROOT / entry["file"])
    path = spec.DEPLOYMENTS / f"{config['deployment']}.py"
    assert path.is_file(), path
    mod = spec.deployment(spec.cell(next(
        w["name"] for w in BENCH["workloads"]
        if w["config"] == entry["name"])))
    for name in ("schedule", "store", "reference", "leq", "round_bytes"):
        assert callable(getattr(mod, name)), name
    assert mod.CONTROLS


def test_unknown_cell_raises():
    with pytest.raises(KeyError):
        spec.cell("no-such-cell")


def test_traffic_file_found_by_name(tmp_path):
    """A new mix is a new file and a new entry: nothing else changes."""
    import json

    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "retwis-bprr.drain2",
                               "config": "retwis-bprr", "traffic": "drain",
                               "chips": 1, "why": "test"})
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    cell = spec.cell("retwis-bprr.drain2", bench_file=path)
    assert cell.active_rounds == 10
