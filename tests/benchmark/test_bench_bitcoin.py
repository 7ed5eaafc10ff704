"""The Bitcoin cell on the CPU at its tiny size: a run reads correct
against its plain reference (``benchmark/reference_bitcoin.py``); a C
engine built with a planted fault of the Bitcoin half, and the 13-round
control, each read not correct. The Tor bulk cell, a traffic file on the
Tor config, reads correct too."""

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

import bitcoin_10k  # noqa: E402
import reference_bitcoin  # noqa: E402

CELL = "bitcoin_10k.mainnet_block"
SRC = ROOT / "native" / "colcore" / "colcore.c"

#: planted faults: (line of colcore.c, what replaces it)
FAULTS = {
    # every INV leaves at once, its timer set to no delay
    "untrickled_inv": (
        "#define BTC_TRICKLE_DELAY(n, mean) btc_exp((n), (mean))",
        "#define BTC_TRICKLE_DELAY(n, mean) ((void)(n), (mean) * 0)"),
    # a cmpctblock one short id (6 bytes) short
    "short_cmpctblock": (
        "#define BTC_CMPCT_LEN (88 + 6 * BTC_BLOCK_TXS + 250)",
        "#define BTC_CMPCT_LEN (88 + 6 * (BTC_BLOCK_TXS - 1) + 250)"),
    # a node missing transactions takes the block without asking for them
    "skipped_getblocktxn": (
        "      if (!missing) return btc_accept_block(n, now);",
        "      if (1) { n->known |= mask; return btc_accept_block(n, now); }"),
}


def drive_with(so=None, cell=CELL):
    """A tiny run of ``cell`` on the C engine at ``so`` (the built one
    when None)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if so is not None:
        env["SHADOW_TPU_COLCORE_SO"] = str(so)
    script = ROOT / "tests" / "benchmark" / "drive_cpu.py"
    with tempfile.TemporaryDirectory() as work:
        p = subprocess.run([sys.executable, str(script), cell, "none", "0.5",
                            work], cwd=ROOT, env=env, capture_output=True,
                           text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p


def build_planted(tmp_path, fault):
    line, planted = FAULTS[fault]
    src = SRC.read_text()
    assert src.count(line) == 1, line
    c = tmp_path / "colcore.c"
    c.write_text(src.replace(line, planted))
    so = tmp_path / "_colcore.so"
    subprocess.run(["gcc", "-O1", "-fPIC", "-shared",
                    f"-I{sysconfig.get_paths()['include']}",
                    f"-I{np.get_include()}", "-o", str(so), str(c)],
                   check=True, capture_output=True, timeout=300)
    return so


def test_tiny_run_is_correct():
    res, p = drive(CELL)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    ref = [ln for ln in p.stdout.splitlines() if ln.startswith("reference:")]
    assert "reached 300 nodes" in ref[0], ref


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(tmp_path, fault):
    res, _ = drive_with(build_planted(tmp_path, fault))
    assert res["correct"] is False
    assert res["failed"] >= 1
    miss = {k: c["value"] for k, c in res["checks"].items()
            if k.endswith("_mismatch")}
    assert miss["node_digest_mismatch"] + miss["msg_digest_mismatch"] \
        + miss["units_sent_mismatch"] >= 1, miss


def test_bulk_cell_tiny_run_is_correct():
    res, p = drive("tor_1_10.bulk1m")
    assert res["correct"] is True, res["checks"]
    ref = [ln for ln in p.stdout.splitlines() if ln.startswith("reference:")]
    assert "'<= 1 MiB'" in ref[0], ref


@pytest.mark.parametrize("seed", [3, 2147483659])
def test_control_differs_from_the_reference(seed):
    traffic = json.loads((BENCH / "traffic" / "mainnet_block.json")
                         .read_text())
    d = bitcoin_10k.doc(seed, traffic, bitcoin_10k.TINY)
    ref = reference_bitcoin.simulate(d)
    again = reference_bitcoin.simulate(d)
    ctl = reference_bitcoin.simulate(d, rounds=13)
    assert not any(reference_bitcoin.mismatches([again], ref).values())
    miss = reference_bitcoin.mismatches([ctl], ref)
    # other units lost: the retransmissions, and so every count, move
    assert miss["events"] == miss["units_sent"] == 1
    assert ref["block_reached"] == ref["nodes"]
