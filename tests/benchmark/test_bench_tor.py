"""The Tor cell on the CPU at its tiny size: a run reads correct against
its plain reference (``benchmark/reference_tor.py``); a planted fault of
the Tor path and the 13-round control each read not correct."""

import json
import os
import subprocess
import sys
import sysconfig
import tempfile

import numpy as np
import pytest

from _bench_drive import drive
from _bench_paths import BENCH, ROOT

import reference_tor  # noqa: E402
import tor_1_10  # noqa: E402

CELL = "tor_1_10.web20k"


def drive_tor(fault: str, env=None, seconds: str = "0.5"):
    """A tiny run of the Tor cell with ``fault`` planted."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    script = ROOT / "tests" / "benchmark" / "drive_tor_cpu.py"
    with tempfile.TemporaryDirectory() as work:
        p = subprocess.run([sys.executable, str(script), fault, seconds,
                            work], cwd=ROOT, env=env, capture_output=True,
                           text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def doc(seed: int) -> dict:
    traffic = json.loads((BENCH / "traffic" / "web20k.json").read_text())
    return tor_1_10.doc(seed, traffic, tor_1_10.TINY)


def test_tiny_run_is_correct():
    res, p = drive(CELL)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    ref = [ln for ln in p.stdout.splitlines() if ln.startswith("reference:")]
    # the stop cuts two 5 MiB fetches: their records as they stand match
    assert "fetches completed 87 of 89, open at the stop 2" in ref[0], ref


def assert_not_correct(res):
    assert res["correct"] is False
    assert res["failed"] >= 1
    assert sum(c["value"] for k, c in res["checks"].items()
               if k.endswith("_mismatch") or k in (
                   "process_errors_seen", "experiments_raised")) >= 1


@pytest.mark.parametrize("fault", ["hop_off_by_one", "drop_relayed_byte"])
def test_planted_fault_is_not_correct(fault):
    assert_not_correct(drive_tor(fault))


def test_initial_window_of_nine_mss_is_not_correct(tmp_path):
    """The C engine built with INIT_CWND 9 MSS in place of 10."""
    src = (ROOT / "native" / "colcore" / "colcore.c").read_text()
    line = "#define INIT_CWND_C (10 * MSS_C)"
    assert src.count(line) == 1
    c = tmp_path / "colcore.c"
    c.write_text(src.replace(line, "#define INIT_CWND_C (9 * MSS_C)"))
    so = tmp_path / "_colcore.so"
    subprocess.run(["gcc", "-O1", "-fPIC", "-shared",
                    f"-I{sysconfig.get_paths()['include']}",
                    f"-I{np.get_include()}", "-o", str(so), str(c)],
                   check=True, capture_output=True, timeout=300)
    assert_not_correct(drive_tor("none", {"SHADOW_TPU_COLCORE_SO": str(so)}))


@pytest.mark.parametrize("seed", [3, 2147483659])
def test_control_differs_from_the_reference(seed):
    d = doc(seed)
    ref = reference_tor.simulate(d)
    again = reference_tor.simulate(d)
    ctl = reference_tor.simulate(d, rounds=13)
    assert not any(reference_tor.mismatches([again], ref).values())
    miss = reference_tor.mismatches([ctl], ref)
    # other units lost: the traffic, and so every count, moves
    assert miss["events"] == miss["units_sent"] == 1
    assert miss["fetch_digest"] == 1
    assert ref["fetches_open"] >= 1
    assert ref["fetches_done"] + ref["fetches_open"] == ref["circuits"]
