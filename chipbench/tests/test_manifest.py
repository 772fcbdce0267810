"""``BENCHMARK.json`` and the data files it names."""
import copy
import json

import pytest

import harness

MAN = harness.load_manifest()


def test_manifest_meets_the_rules():
    assert harness.validate_manifest(MAN) == []


def test_every_per_layer_metric_has_a_reader_moves_and_workloads():
    e2e = {m["name"] for m in MAN["end_to_end"]}
    for m in MAN["per_layer"]:
        assert m["moves"] in e2e and m["workloads"]
        assert len(m["unit"]) <= 16 and m["layer"] and "\n" not in m["layer"]
        assert callable(harness.load_module("metrics", m["name"]).read)


def test_units_and_names():
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        assert harness.UNIT_RE.match(m["unit"]), m
        assert harness.NAME_RE.match(m["name"]), m
    for m in MAN["per_layer"]:
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%" and m["source"] == "device_trace"


@pytest.mark.parametrize("w", MAN["workloads"], ids=lambda w: w["name"])
def test_cell_files_and_configs_load(w):
    cell = harness.load_json(harness.HERE / "cells" / f"{w['name']}.json")
    assert cell["config"] == w["config"]
    assert (harness.HERE / "drivers" / f"{cell['driver']}.py").is_file()
    conf = {c["name"]: c for c in MAN["configs"]}[w["config"]]
    cf = harness.load_json(harness.ROOT / conf["file"])
    assert cf["name"] == conf["name"] and cf["source"] == conf["source"]
    assert sorted(cf["reduced"]) == sorted(conf["reduced"])
    cfg = harness.program_config(cf)
    assert cfg.n_layers == cf["num_hidden_layers"]
    assert cfg.d_model == cf["hidden_size"]


def test_configuration_that_disagrees_with_the_program_is_refused():
    cf = harness.load_json(harness.HERE / "configs" /
                           "granite-3-8b-pp2.json")
    with pytest.raises(ValueError):
        harness.program_config(dict(cf, hidden_size=4000))


@pytest.mark.parametrize("breakage", [
    lambda m: m["per_layer"][0].pop("workloads"),
    lambda m: m["per_layer"][0].update(moves="nothing"),
    lambda m: m["end_to_end"][0].update(unit="tokens per second"),
    lambda m: m["end_to_end"][0].update(bound=0.5),
    lambda m: m["workloads"][0].update(name="has space"),
    lambda m: m["per_layer"][0].update(why="extra key"),
    lambda m: m["end_to_end"].pop(3),                     # setup_s
])
def test_broken_manifests_are_caught(breakage):
    m = copy.deepcopy(MAN)
    breakage(m)
    assert harness.validate_manifest(m)


def test_manifest_is_small_json():
    text = (harness.ROOT / "BENCHMARK.json").read_text()
    assert len(text.encode()) <= 64 * 1024
    assert json.loads(text) == MAN
