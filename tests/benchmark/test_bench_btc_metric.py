"""The Bitcoin cell's per-layer reader (``btc_s_per_sim_s``) on a
recorded record: the C engine's ``events.btc`` timer per simulated
second, and nothing where an experiment lacks it (a program without the
timer, or a cell with no Bitcoin nodes)."""

import importlib.util

import pytest

from _bench_paths import BENCH

#: the C engine's timed parts of a small Bitcoin experiment (CPU, 10 sim-s)
PHASE_DETAIL = {"events.py_callback": 0.0177, "events.select": 0.0093,
                "events.stream": 0.0717, "events.btc": 0.0356}


def read(c):
    spec = importlib.util.spec_from_file_location(
        "btc_s_per_sim_s", BENCH / "metrics" / "btc_s_per_sim_s.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(c)


def ctx(details):
    exps = [{"sim_s": 10.0, "phase_detail": d} for d in details]
    return {"experiments": exps, "sim_s": 10.0 * len(exps)}


def test_reader_sums_its_part_over_sim_seconds():
    other = {**PHASE_DETAIL, "events.btc": 0.25}
    assert read(ctx([PHASE_DETAIL, other])) == pytest.approx(
        (0.0356 + 0.25) / 20.0, rel=1e-12)


@pytest.mark.parametrize("detail", [
    {"events.py_callback": 0.2712, "barrier.handoff": 0.0171},  # gossip
    {"events.stream": 0.1432, "events.relay": 0.0617},  # Tor
    {}])
def test_reader_without_its_part_reads_nothing(detail):
    assert read(ctx([detail])) is None
    assert read(ctx([PHASE_DETAIL, detail])) is None


def test_empty_window_reads_nothing():
    assert read(ctx([])) is None
