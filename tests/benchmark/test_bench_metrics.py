"""The per-layer readers on a recorded summary: the tpu_batch auto phase of
a chip smoke run (Tor 1/10, one TPU v5 lite), as one experiment of a
window, and the chip probe trace."""

import importlib.util

import pytest

from _bench_paths import BENCH

PHASE_WALL = {"events": 3.0837, "barrier": 0.3935, "draw_flush": 0.0,
              "extract": 0.3774, "ingress_deferred": 0.0012,
              "window_build": 0.0019, "window_dispatch": 0.0035,
              "window_readback": 0.0, "transport_tick": 0.0}
#: the C engine's timed parts (a gossip experiment's on the chip)
PHASE_DETAIL = {"events.py_callback": 0.2712, "barrier.handoff": 0.0171}
TRACE = {"busy_s": 5.9506e-05, "window_s": 0.039968907,
         "kernels": {"_draw_kernel": {"seconds": 51437e-9, "calls": 3,
                                      "bytes": 1197144},
                     "_min_draw_kernel": {"seconds": 10944e-9, "calls": 1,
                                          "bytes": 262152}}}


def ctx(trace=TRACE):
    # build 1.371 s + run 6.197 s of PR 21's row, as one window
    exp = {"wall_s": 1.371346348 + 6.196586143, "loop_s": 5.235110836,
           "sim_s": 8.0, "phase_wall": PHASE_WALL,
           "phase_detail": PHASE_DETAIL, "units_sent": 226095,
           "window_units": 20608}
    return {"experiments": [exp], "window_s": exp["wall_s"], "sim_s": 8.0,
            "trace": trace, "peak": {"hbm_bytes_per_s": 819e9}}


def read(name, c):
    spec = importlib.util.spec_from_file_location(
        name, BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(c)


EXPECTED = {
    "build_finalize_share":
        100 * (1.371346348 + 6.196586143 - 5.235110836)
        / (1.371346348 + 6.196586143),
    "dispatch_s_per_sim_s": 3.0837 / 8,
    "unattributed_s_per_sim_s":
        (5.235110836 - sum(PHASE_WALL.values())) / 8,
    "colplane_s_per_sim_s": (0.3935 + 0.3774 + 0.0012) / 8,
    "window_s_per_sim_s": (0.0019 + 0.0035) / 8,
    "device_unit_share": 100 * 20608 / 226095,
    "draw_kernel_roofline":
        100 * ((1197144 + 262152) / 819e9) / (51437e-9 + 10944e-9),
    "device_idle_share": 100 * (1 - 5.9506e-05 / 0.039968907),
    "py_callback_s_per_sim_s": 0.2712 / 8,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_recorded_summary(name):
    assert read(name, ctx()) == pytest.approx(EXPECTED[name], rel=1e-12)


@pytest.mark.parametrize("name", ["draw_kernel_roofline",
                                  "device_idle_share"])
def test_trace_readers_without_trace_read_nothing(name):
    assert read(name, ctx(trace=None)) is None


def test_roofline_without_kernel_time_reads_nothing():
    assert read("draw_kernel_roofline",
                ctx(trace={**TRACE, "kernels": {}})) is None


@pytest.mark.parametrize("name", ["dispatch_s_per_sim_s",
                                  "device_unit_share",
                                  "build_finalize_share",
                                  "py_callback_s_per_sim_s"])
def test_empty_window_reads_nothing(name):
    c = ctx()
    c.update(experiments=[], sim_s=0.0)
    assert read(name, c) is None


def test_py_callback_without_the_detail_reads_nothing():
    c = ctx()
    c["experiments"][0]["phase_detail"] = {"barrier.handoff": 0.0171}
    assert read("py_callback_s_per_sim_s", c) is None
    del c["experiments"][0]["phase_detail"]
    assert read("py_callback_s_per_sim_s", c) is None
