"""The round loop's collect reader (``gc_s_per_sim_s``) on recorded
experiments: ``phase_wall.gc`` summed over the window's experiments per
simulated second, kept apart from ``unattributed_s_per_sim_s``, which
reads the loop wall that no phase covers."""

import importlib.util

import pytest

from _bench_paths import BENCH

#: a Tor experiment's loop phases on the chip (PR 26's split, seconds)
PHASE_WALL = {"events": 4.91, "gc": 0.97, "barrier": 0.64, "extract": 0.25,
              "ingress_deferred": 0.22}
LOOP_S = 7.41


def read(name, c):
    spec = importlib.util.spec_from_file_location(
        name, BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(c)


def ctx(gcs):
    exps = [{"sim_s": 40.0, "loop_s": LOOP_S,
             "phase_wall": {**PHASE_WALL, "gc": g}} for g in gcs]
    return {"experiments": exps, "sim_s": 40.0 * len(exps)}


@pytest.mark.parametrize("gcs", [[0.97], [0.97, 0.41], [0.0]],
                         ids=["one", "two", "none_collected"])
def test_reader_sums_the_gc_phase_over_sim_seconds(gcs):
    assert read("gc_s_per_sim_s", ctx(gcs)) == pytest.approx(
        sum(gcs) / (40.0 * len(gcs)), rel=1e-12)


def test_gc_is_not_in_the_unattributed_remainder():
    """Moving seconds from the loop's remainder into ``gc`` moves them
    from one reader to the other."""
    lo, hi = ctx([0.5]), ctx([0.9])
    d_gc = read("gc_s_per_sim_s", hi) - read("gc_s_per_sim_s", lo)
    d_rest = (read("unattributed_s_per_sim_s", lo)
              - read("unattributed_s_per_sim_s", hi))
    assert d_gc == pytest.approx(0.4 / 40.0, rel=1e-12)
    assert d_rest == pytest.approx(d_gc, rel=1e-12)


def test_empty_window_reads_nothing():
    assert read("gc_s_per_sim_s", {"experiments": [], "sim_s": 0.0}) is None
