"""The Tor cell's per-layer readers (``stream_s_per_sim_s``,
``relay_s_per_sim_s``) on a recorded record: the C engine's
``events.stream`` and ``events.relay`` timers per simulated second, and
nothing where an experiment lacks them (a program without the timers, or
a gossip run, which has neither)."""

import importlib.util

import pytest

from _bench_paths import BENCH

#: the C engine's timed parts of a small Tor experiment (CPU, 40 sim-s)
PHASE_DETAIL = {"events.py_callback": 0.0151, "barrier.handoff": 0.0,
                "events.stream": 0.1432, "events.relay": 0.0617}
PARTS = {"stream_s_per_sim_s": "events.stream",
         "relay_s_per_sim_s": "events.relay"}


def read(name, c):
    spec = importlib.util.spec_from_file_location(
        name, BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(c)


def ctx(details):
    exps = [{"sim_s": 40.0, "phase_detail": d} for d in details]
    return {"experiments": exps, "sim_s": 40.0 * len(exps)}


@pytest.mark.parametrize("name", sorted(PARTS))
def test_reader_sums_its_part_over_sim_seconds(name):
    other = {**PHASE_DETAIL, PARTS[name]: 0.25}
    assert read(name, ctx([PHASE_DETAIL, other])) == pytest.approx(
        (PHASE_DETAIL[PARTS[name]] + 0.25) / 80.0, rel=1e-12)


@pytest.mark.parametrize("name", sorted(PARTS))
def test_reader_without_its_part_reads_nothing(name):
    gossip = {"events.py_callback": 0.2712, "barrier.handoff": 0.0171}
    assert read(name, ctx([gossip])) is None
    assert read(name, ctx([PHASE_DETAIL, gossip])) is None
    assert read(name, ctx([{}])) is None


@pytest.mark.parametrize("name", sorted(PARTS))
def test_empty_window_reads_nothing(name):
    assert read(name, ctx([])) is None
