"""BENCHMARK.json keeps to its contract, and the harness finds a new
configuration, mix or metric by name alone."""
import json
import os
import re

import pytest

from conftest import BENCH, CHECKOUT
from harness.spec import NAME_RE, UNIT_RE, Bench

with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
    SPEC = json.load(f)
TEXT_RE = re.compile(r"^[^\t\n\r]{1,200}$")
METRIC_KEYS = {"name", "unit", "better", "source", "bound", "workloads",
               "layer", "moves"}


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"][0] == "python3" and len(SPEC["command"]) <= 32
    assert all(not w.startswith("/") and ".." not in w
               for w in SPEC["command"])
    assert 1 <= SPEC["run_seconds"] <= 51
    assert all(re.match(r"^[A-Za-z0-9_./-]{1,200}$", p) for p in SPEC["paths"])
    assert len(json.dumps(SPEC)) < 64 * 1024


def all_names():
    for c in SPEC["configs"]:
        yield c["name"]
        yield from c["reduced"]
    for w in SPEC["workloads"]:
        yield from (w["name"], w["config"], w["traffic"])
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        yield m["name"]


@pytest.mark.parametrize("name", sorted(set(all_names())))
def test_names_use_allowed_characters(name):
    assert NAME_RE.match(name), name


@pytest.mark.parametrize("metric", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entries(metric):
    assert UNIT_RE.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert set(metric) <= METRIC_KEYS
    if "bound" in metric:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        assert TEXT_RE.match(metric["layer"])
    # every metric has a reader the harness finds by name
    assert callable(Bench(CHECKOUT).reader(metric["name"]))


def test_every_cell_reports_what_its_metrics_move():
    names = {m["name"] for m in SPEC["end_to_end"]}
    for w in SPEC["workloads"]:
        assert TEXT_RE.match(w["why"]) and w["chips"] in (1, 4)
        cell = Bench(CHECKOUT).cell(w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in e2e and m["moves"] in names
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_layers_are_named_alike():
    for m in SPEC["per_layer"]:
        same = {x["layer"] for x in SPEC["per_layer"]
                if x["name"].split(".")[0] == m["name"].split(".")[0]}
        assert len(same) == 1


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_config_files(cfg):
    assert cfg["file"].startswith("bench/configs/")
    with open(os.path.join(CHECKOUT, cfg["file"])) as f:
        body = json.load(f)
    assert body["name"] == cfg["name"]
    assert set(cfg["reduced"]) == set(body["reduced"])
    assert TEXT_RE.match(cfg["why"]) and TEXT_RE.match(cfg["source"])
    assert isinstance(body["graph_seed"], int) and body["guarantees"]


def test_drop_in_files_are_found_by_name(tmp_path):
    """A configuration, a mix and a metric added as files, with entries in
    BENCHMARK.json, run through the harness's lookups unchanged."""
    for sub in ("configs", "traffic", "metrics"):
        (tmp_path / sub).mkdir()
    (tmp_path / "configs" / "new-cfg.json").write_text(json.dumps(
        {"name": "new-cfg", "graph": {}, "graph_seed": 3}))
    (tmp_path / "traffic" / "new-mix.json").write_text(json.dumps(
        {"rate_per_s": 1, "pairs": "uniform", "levels": "uniform"}))
    (tmp_path / "peaks.json").write_text(json.dumps({"devices": {}}))
    (tmp_path / "metrics" / "new_metric.lat.py").write_text(
        "def read(run):\n    return 42.0\n")
    spec = {"configs": [{"name": "new-cfg",
                         "file": str(tmp_path / "configs" / "new-cfg.json")}],
            "workloads": [{"name": "new-cell", "config": "new-cfg",
                           "traffic": "new-mix", "chips": 1}],
            "end_to_end": [{"name": "setup_s", "unit": "s"}],
            "per_layer": [{"name": "new_metric.lat", "moves": "setup_s",
                           "workloads": ["new-cell"]}]}
    bench = Bench(CHECKOUT, bench_dir=str(tmp_path), spec=spec)
    cell = bench.cell("new-cell")
    assert cell.config["graph_seed"] == 3
    assert cell.mix["rate_per_s"] == 1
    assert [m["name"] for m in cell.per_layer] == ["new_metric.lat"]
    assert bench.reader("new_metric.lat")(None) == 42.0
    with pytest.raises(KeyError):
        bench.peaks("a device that is not in the table")


def test_real_files_exist():
    for w in SPEC["workloads"]:
        assert os.path.exists(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
    assert Bench(CHECKOUT).peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
