"""Run drive_cpu.py in a child process and read its result line."""

import json
import os
import subprocess
import sys
import tempfile

from _bench_paths import ROOT


def drive(cell: str, fault: str = "none", seconds: str = "0.5", root=ROOT):
    """A tiny run of ``cell`` in the checkout at ``root``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    script = root / "tests" / "benchmark" / "drive_cpu.py"
    with tempfile.TemporaryDirectory() as work:
        p = subprocess.run([sys.executable, str(script), cell, fault,
                            seconds, work], cwd=root, env=env,
                           capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p
