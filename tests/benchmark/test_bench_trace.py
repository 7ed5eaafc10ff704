"""The trace reduction, on a trace recorded on the chip (PR 22: four draw
calls at P = 512, 8192, 65536 and one min-draw at 16384 inside one
``bench.window`` span) and on made-up planes."""

import importlib.util

import pytest

from _bench_paths import BENCH

import roofline

_spec = importlib.util.spec_from_file_location("bench_trace",
                                               BENCH / "trace.py")
bench_trace = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench_trace)

PROBE = BENCH.parent / "tests" / "benchmark" / "draw_probe.xplane.pb"


@pytest.fixture(scope="module")
def probe():
    return bench_trace.reduce_file(PROBE)


def test_probe_window_and_busy(probe):
    assert probe["window_s"] == pytest.approx(0.039968907, abs=1e-12)
    assert probe["busy_s"] == pytest.approx(5.9506e-05, abs=1e-12)


def test_probe_kernel_times_and_bytes(probe):
    k = probe["kernels"]
    assert k["_draw_kernel"]["calls"] == 3
    assert k["_draw_kernel"]["seconds"] == pytest.approx(
        (3746 + 8041 + 39650) * 1e-9, abs=1e-15)
    assert k["_draw_kernel"]["bytes"] == sum(
        roofline.draw_kernel_bytes(p) for p in (512, 8192, 65536))
    assert k["_min_draw_kernel"]["calls"] == 1
    assert k["_min_draw_kernel"]["seconds"] == pytest.approx(10944e-9,
                                                             abs=1e-15)
    assert k["_min_draw_kernel"]["bytes"] == \
        roofline.min_draw_kernel_bytes(16384)


def test_probe_breakdown(probe):
    ops = probe["breakdown"]["device_ops"]
    assert ops[0][0] == "_draw_kernel:%fusion.4"
    assert len(ops) <= 10 and len(probe["breakdown"]["idle_gaps"]) <= 10
    idle = sum(s for _, s in probe["breakdown"]["idle_gaps"])
    assert idle <= probe["window_s"] - probe["busy_s"] + 1e-12


def test_draw_kernel_bytes_at_65536():
    # (4, 65536) uint32 in, (2,) uint32 key in, 65536 / 8 bytes out
    assert roofline.draw_kernel_bytes(65536) == 1_048_576 + 8 + 8192


def test_gaps_split_at_harness_spans():
    planes = {
        "/host:CPU": {"main": [
            ("bench.window", 0, 1000), ("bench.build", 0, 100),
            ("bench.run", 100, 900), ("bench.loop", 110, 800),
            ("bench.finalize", 800, 900), ("bench.teardown", 900, 1000)]},
        "/device:TPU:0": {
            "XLA Modules": [("jit__draw_kernel(1)", 200, 300)],
            "XLA Ops": [("%fusion.5 = u32[1,256] fusion(u32[4,256] %p)",
                         200, 250),
                        ("%fusion.4 = pred[256] fusion()", 240, 300)]},
    }
    r = bench_trace.reduce_planes(planes)
    assert r["busy_s"] == pytest.approx(100e-9)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["kernels"]["_draw_kernel"]["bytes"] == \
        roofline.draw_kernel_bytes(256)
    gaps = {(n, round(s * 1e9)) for n, s in r["breakdown"]["idle_gaps"]}
    assert gaps == {("build", 100), ("run", 10), ("loop", 90),
                    ("loop", 500), ("finalize", 100), ("teardown", 100)}


def test_no_window_span_is_an_error():
    with pytest.raises(RuntimeError, match="bench.window"):
        bench_trace.reduce_planes({"/device:TPU:0": {}})
