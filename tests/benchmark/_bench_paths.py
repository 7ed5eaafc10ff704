"""Paths shared by the benchmark's tests."""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmark"
# appended, so that the standard library's ``trace`` stays ``trace``
for p in (BENCH, BENCH / "configs", BENCH / "metrics"):
    if str(p) not in sys.path:
        sys.path.append(str(p))
