"""Drive benchmark/run.py on the CPU at a tiny size: the look for a chip
is skipped and the configs are built at their config module's ``TINY``
sizes, the rest of a run is not. With a fault named, the timed path is
broken underneath first.

    python drive_cpu.py <cell> <fault> <seconds> <work dir>

Faults: none; flip_flag (every 16th unit of every loss draw, on the device
or its numpy twin, reads back altered: an answer altered where it is
produced); half_batch (the second half of every draw reads "not dropped":
half the batch left out); no_rounds (the round loop returns at once: a
step that returns its state unchanged).
"""

import json
import sys
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "benchmark"))
sys.path.insert(0, str(ROOT))

SEED = 2147483659


def break_draws(flags_edit, mins_edit) -> None:
    """Edit what every loss draw reads back: the flags of a device draw
    window or of the numpy twin, and the per-unit minimum draws of a
    speculative wave (a unit drops iff its minimum is below its link's
    threshold)."""
    from shadow_tpu.network import colplane, engine
    from shadow_tpu.ops import propagate

    for cls, edit in ((propagate.DrawHandle, flags_edit),
                      (propagate.MinDrawHandle, mins_edit)):
        def read(self, _orig=cls.read, _edit=edit):
            out = _orig(self).copy()
            _edit(out)
            return out

        cls.read = read
    for mod in (colplane, engine):
        def twin(*a, _orig=mod.loss_flags):
            out = _orig(*a).copy()
            flags_edit(out)
            return out

        mod.loss_flags = twin


def plant(fault: str) -> None:
    if fault == "flip_flag":
        def flip(flags):
            flags[::16] = ~flags[::16]

        def lower(mins):
            mins[::16] = 0
        break_draws(flip, lower)
    elif fault == "half_batch":
        def drop_half(flags):
            flags[len(flags) // 2:] = False

        def drop_half_mins(mins):
            mins[len(mins) // 2:] = 0xFFFFFFFF
        break_draws(drop_half, drop_half_mins)
    elif fault == "no_rounds":
        from shadow_tpu.core.controller import Controller

        Controller._round_loop = lambda self, now, stop, *a, **kw: stop
    elif fault != "none":
        raise SystemExit(f"unknown fault {fault!r}")


def tiny_doc(mod, seed: int, traffic: dict) -> dict:
    """The config at its module's TINY, with every draw window sent to
    the device (here the CPU), as most are on the chip: the C engine's
    own host draws are out of Python's reach."""
    doc = mod.doc(seed, traffic, mod.TINY)
    doc.setdefault("experimental", {})["tpu_device_floor"] = 1
    return doc


def shrink(run, work: str) -> None:
    """Skip the look for a chip, build the cell's configs small, and work
    in a directory of this run's own (tests drive runs side by side). The
    config module's ``REFERENCE``, ``results`` and ``TINY`` pass through;
    only ``doc`` is made small."""
    def check_device(jax, chips):
        d = jax.devices()[0]
        peaks = json.loads((run.BENCH / "peaks.json").read_text())
        return ({"platform": d.platform, "kind": d.device_kind,
                 "count": len(jax.devices())}, peaks["TPU v5 lite"])

    load_cell = run.load_cell

    def load_tiny(name):
        c = load_cell(name)
        mod = c["config"]
        c["config"] = types.SimpleNamespace(
            REFERENCE=mod.REFERENCE, results=mod.results, TINY=mod.TINY,
            doc=lambda seed, traffic: tiny_doc(mod, seed, traffic))
        return c

    run.check_device = check_device
    run.load_cell = load_tiny
    run.WORK = Path(work)


if __name__ == "__main__":
    cell, fault, seconds, work = sys.argv[1:5]
    import run

    shrink(run, work)
    plant(fault)
    sys.exit(run.main(["--workload", cell, "--seed", str(SEED),
                       "--seconds", seconds, "--trace", "0"]))
