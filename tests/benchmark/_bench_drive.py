"""Run drive_cpu.py in a child process and read its result line."""

import json
import os
import subprocess
import sys
import tempfile

from _bench_paths import ROOT

DRIVE = ROOT / "tests" / "benchmark" / "drive_cpu.py"


def drive(cell: str, fault: str = "none", seconds: str = "0.5"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    with tempfile.TemporaryDirectory() as work:
        p = subprocess.run([sys.executable, str(DRIVE), cell, fault,
                            seconds, work], cwd=ROOT, env=env,
                           capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p
