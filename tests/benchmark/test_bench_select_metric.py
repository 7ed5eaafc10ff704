"""The round loop's selection reader (``select_s_per_sim_s``) on recorded
experiments: the C engine's ``events.select`` timer summed over the
window's experiments per simulated second, and nothing where an experiment
lacks the timer (a program without the due-time index, or no C engine)."""

import importlib.util

import pytest

from _bench_paths import BENCH

NAME = "select_s_per_sim_s"
#: the C engine's timed parts of a small Tor experiment (CPU, 40 sim-s)
PHASE_DETAIL = {"events.py_callback": 0.0151, "barrier.handoff": 0.0,
                "events.stream": 0.1432, "events.relay": 0.0617,
                "events.select": 0.0033}
#: the same parts as a program without the index records them
PARENT = {k: v for k, v in PHASE_DETAIL.items() if k != "events.select"}


def read(c):
    spec = importlib.util.spec_from_file_location(
        NAME, BENCH / "metrics" / f"{NAME}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(c)


def ctx(details):
    exps = [{"sim_s": 40.0, "phase_detail": d} for d in details]
    return {"experiments": exps, "sim_s": 40.0 * len(exps)}


@pytest.mark.parametrize("selects", [[0.0033], [0.0033, 0.25], [0.0]],
                         ids=["one", "two", "zero"])
def test_reader_sums_the_select_part_over_sim_seconds(selects):
    details = [{**PHASE_DETAIL, "events.select": v} for v in selects]
    assert read(ctx(details)) == pytest.approx(
        sum(selects) / (40.0 * len(selects)), rel=1e-12)


@pytest.mark.parametrize("details", [[PARENT], [PHASE_DETAIL, PARENT], [{}],
                                     []],
                         ids=["parent", "mixed", "no_parts", "empty_window"])
def test_reader_without_the_part_reads_nothing(details):
    assert read(ctx(details)) is None
