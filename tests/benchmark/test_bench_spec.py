"""BENCHMARK.json keeps to the contract's names and finds its files."""

import json
import re

import pytest

from _bench_paths import BENCH, ROOT

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


def _names():
    out = [("config", c["name"]) for c in SPEC["configs"]]
    out += [("cell", w["name"]) for w in SPEC["workloads"]]
    out += [("traffic", w["traffic"]) for w in SPEC["workloads"]]
    out += [("metric", m["name"]) for m in METRICS]
    out += [("reduced", k) for c in SPEC["configs"] for k in c["reduced"]]
    return out


@pytest.mark.parametrize("kind,name", _names())
def test_name_is_plain(kind, name):
    assert NAME.match(name), (kind, name)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_unit_and_direction(metric):
    assert UNIT.match(metric["unit"]), metric
    assert metric["better"] in ("lower", "higher")


def test_names_are_unique():
    for group in (SPEC["configs"], SPEC["workloads"], METRICS):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda w: w["name"])
def test_cell_finds_its_files(cell):
    configs = {c["name"]: c for c in SPEC["configs"]}
    assert (ROOT / configs[cell["config"]]["file"]).is_file()
    assert (BENCH / "traffic" / f"{cell['traffic']}.json").is_file()
    assert cell["chips"] in (1, 4) and 0 < len(cell["why"]) <= 200


@pytest.mark.parametrize("metric", SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric_has_a_reader(metric):
    assert (BENCH / "metrics" / f"{metric['name']}.py").is_file()
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert metric["moves"] in e2e


def test_end_to_end_bounds():
    names = [m["name"] for m in SPEC["end_to_end"]]
    assert "setup_s" in names
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_run_seconds_fits_a_full_check():
    # 24 cells: 2 + 14 * 24 runs of run_seconds + 60, 2 * 90 s of compile
    # per cell, 1200 s spare, within 43200 s
    rs = SPEC["run_seconds"]
    assert 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200
