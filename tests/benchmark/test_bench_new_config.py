"""A configuration joins the benchmark as new files only: in a copy of the
benchmark, a second config module, its reference, a traffic file and
entries appended to BENCHMARK.json make a cell that a tiny run reads
correct, and a broken timed path reads not correct, with no file that
was there before changed."""

import hashlib
import json
import shutil

import pytest

from _bench_drive import drive
from _bench_paths import ROOT

CONFIG = '''"""gossip_small: the gossip flood on fewer nodes and a smaller graph,
with a reading of its own taken from the data directory."""

import importlib.util
import re
from pathlib import Path

from reference import nodes_digest

_spec = importlib.util.spec_from_file_location(
    "gossip_base", Path(__file__).with_name("gossip_10k.py"))
gossip = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(gossip)

REFERENCE = "reference_small.py"
SIZES = {**gossip.SIZES, "n_nodes": 2000, "graph_nodes": 16}
TINY = {**SIZES, "n_nodes": 240}


def doc(seed, traffic, sizes=SIZES):
    return gossip.doc(seed, traffic, sizes)


def results(ctl, data_dir):
    received, known = gossip.node_results(ctl)
    log = (Path(data_dir) / "shadow.log").read_text()
    return {"nodes_digest": nodes_digest(received, known),
            "hosts_logged": int(re.search(r"starting: (\\d+) hosts",
                                          log).group(1))}
'''

REFERENCE = '''"""The gossip reference, and the host count the log states."""

import reference

IDENTITY_KEYS = reference.IDENTITY_KEYS
KEYS = reference.KEYS + ("hosts_logged",)


def simulate(doc, rounds=20):
    ref = reference.simulate(doc, rounds)
    ref["hosts_logged"] = sum(int(h.get("quantity", 1))
                              for h in doc["hosts"].values())
    return ref


def mismatches(results, ref):
    return {k: sum(r[k] != ref[k] for r in results) for k in KEYS}


def describe(ref):
    return (f"small gossip: events {ref['events']}, "
            f"hosts {ref['hosts_logged']}")
'''

TRAFFIC = {"why": "4 originators, one transaction each, 20 sim-s",
           "originators": 4, "txs_per_originator": 1, "interval_s": 5.0,
           "heartbeat_s": 10, "stop_s": 20, "seeds_per_run": 2}
CELL = "gossip_small.small_flood"


def digests(root, paths):
    out = {}
    for top in paths:
        p = root / top
        files = [p] if p.is_file() else sorted(
            f for f in p.rglob("*")
            if f.is_file() and "__pycache__" not in f.parts)
        for f in files:
            out[f.relative_to(root).as_posix()] = \
                hashlib.sha256(f.read_bytes()).hexdigest()
    return out


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    shutil.copy(ROOT / "BENCHMARK.json", root)
    for d in ("benchmark", "tests/benchmark"):
        shutil.copytree(ROOT / d, root / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for d in ("shadow_tpu", "native"):
        (root / d).symlink_to(ROOT / d, target_is_directory=True)
    tracked = ("BENCHMARK.json", "benchmark", "tests/benchmark")
    before = digests(root, tracked)
    old = json.loads((root / "BENCHMARK.json").read_text())

    (root / "benchmark" / "configs" / "gossip_small.py").write_text(CONFIG)
    (root / "benchmark" / "reference_small.py").write_text(REFERENCE)
    (root / "benchmark" / "traffic" / "small_flood.json").write_text(
        json.dumps(TRAFFIC))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "gossip_small", "source": old["configs"][0]["source"],
        "file": "benchmark/configs/gossip_small.py", "reduced": [],
        "why": "the gossip flood at a smaller size"})
    spec["workloads"].append({
        "name": CELL, "config": "gossip_small", "traffic": "small_flood",
        "chips": 1, "why": "a second configuration added as new files"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec, indent=2))
    return root, tracked, before, old


def test_added_config_runs_correct(copy):
    root = copy[0]
    res, p = drive(CELL, root=root)
    assert res["correct"] is True and res["failed"] == 0
    assert res["checks"]["hosts_logged_mismatch"]["value"] == 0
    assert res["checks"]["experiments_compared"]["value"] >= 1
    assert "small gossip: events" in p.stdout and "hosts 240" in p.stdout


def test_added_config_broken_is_not_correct(copy):
    res, _ = drive(CELL, "flip_flag", root=copy[0])
    assert res["correct"] is False
    assert res["checks"]["nodes_digest_mismatch"]["value"] >= 1


def test_no_file_that_was_there_changed(copy):
    root, tracked, before, old = copy
    after = digests(root, tracked)
    changed = {f for f, h in before.items() if after.get(f) != h}
    assert changed == {"BENCHMARK.json"}
    assert set(after) - set(before) == {
        "benchmark/configs/gossip_small.py", "benchmark/reference_small.py",
        "benchmark/traffic/small_flood.json"}
    new = json.loads((root / "BENCHMARK.json").read_text())
    assert set(new) == set(old)
    for key, value in old.items():
        if isinstance(value, list):
            assert new[key][:len(value)] == value, key
        else:
            assert new[key] == value, key
    assert len(new["configs"]) == len(old["configs"]) + 1
    assert len(new["workloads"]) == len(old["workloads"]) + 1
