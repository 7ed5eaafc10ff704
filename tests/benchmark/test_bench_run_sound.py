"""benchmark/run.py refuses the CPU, refuses a tree without the program,
and, with the look for a chip skipped, a sound tiny run is correct."""

import os
import shutil
import subprocess
import sys

import pytest

from _bench_drive import drive
from _bench_paths import ROOT

ARGS = ["--workload", "gossip_10k.flood", "--seed", "3", "--seconds", "1",
        "--trace", "0"]


def test_cpu_platform_is_refused():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "benchmark/run.py", *ARGS], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "platform 'cpu'" in p.stderr
    assert p.stdout.strip() == "" or not p.stdout.strip().splitlines()[-1] \
        .startswith("{")


def test_tree_without_the_program_is_refused(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "benchmark/run.py", *ARGS],
                       cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout


@pytest.mark.parametrize("seconds", ["0.5", "2"])
def test_sound_run_is_correct(seconds):
    res, p = drive("gossip_10k.flood", seconds=seconds)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"setup_s", "sim_s_per_wall_s",
                                   "peak_rss_mb"}
    assert all(c["value"] == 0 for k, c in res["checks"].items()
               if k.endswith("_mismatch"))
    # the compared numbers are the last lines on standard error
    assert p.stderr.strip().splitlines()[-1].startswith(
        "check experiments_compared")
