#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``, at a cell's own
size: the cell's reference (the one its config module's ``REFERENCE``
names) put in the program's place with its loss draws one step below the
configured Threefry-2x32-20 (``simulate(doc, rounds=13)``), compared with
the reference as a window experiment is. Each seed runs in its own
process; none touches JAX.

    python3 benchmark/control.py --workload <cell> --seeds <s1,s2,...>

Each ``--seeds`` value is a run's ``--seed``; the control simulates the
first config that run would build. Prints one line per seed with the
mismatch count of every compared number (the control has to differ in at
least one), then the last line: a JSON object with every seed's counts.
"""

from __future__ import annotations

import argparse
import json
import multiprocessing
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def job(args) -> dict:
    cell_name, seed = args
    sys.path.insert(0, str(BENCH))
    from run import derive_seeds, load_cell, load_reference

    cell = load_cell(cell_name)
    reference = load_reference(cell["config"])
    general = derive_seeds(seed, cell["traffic"]["seeds_per_run"])[0]
    doc = cell["config"].doc(general, cell["traffic"])
    t0 = time.perf_counter()
    ref = reference.simulate(doc)
    t1 = time.perf_counter()
    ctl = reference.simulate(doc, rounds=13)
    t2 = time.perf_counter()
    return {"workload": cell_name, "seed": seed, "general_seed": general,
            "reference_s": t1 - t0, "control_s": t2 - t1,
            "units_dropped": [ref["units_dropped"], ctl["units_dropped"]],
            "mismatch": reference.mismatches([ctl], ref)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, action="append")
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    jobs = [(w, s) for w in args.workload for s in seeds]
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(min(len(jobs), 4)) as pool:
        rows = pool.map(job, jobs)
    for r in rows:
        print(json.dumps(r), flush=True)
    print(json.dumps({"control_fails_every_seed": all(
        any(r["mismatch"].values()) for r in rows), "rows": rows}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
