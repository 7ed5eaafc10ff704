"""The span recorder (core/spans.py) and the partition it makes of a run.

- The recorder's arithmetic: a phase keeps its self time, a part
  (``<phase>.<part>``) splits its phase without taking time out of it.
- On whole runs: ``phase_wall`` adds up to at most ``wall_seconds``, no
  phase is negative, the parts of a phase add up to at most the phase, and
  ``device.routes`` adds up to ``units_sent`` -- with draw windows forced
  onto the (CPU) device, with the default routing, and with device draws
  off.
- Spans land on a profiler trace as ``shadow.<name>``.
"""

import glob
import threading
from pathlib import Path

import pytest

from shadow_tpu.config import load_config
from shadow_tpu.core import spans
from shadow_tpu.core.controller import Controller

ROOT = Path(__file__).resolve().parents[1]
GOSSIP = str(ROOT / "examples" / "gossip_churn.yaml")
TGEN = str(ROOT / "examples" / "tgen_100host.yaml")


class _Clock:
    def __init__(self) -> None:
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


def test_self_time_of_nested_and_sibling_spans():
    clk = _Clock()
    rec = spans.Recorder(clock=clk, trace=False)
    with rec.installed():
        with spans.span("a"):              # 0 .. 10
            clk.t = 1.0
            with spans.span("b"):          # 1 .. 7
                clk.t = 3.0
                with spans.span("b.x"):    # 3 .. 6.5, a part of b
                    clk.t = 4.0
                    with spans.span("c"):  # 4 .. 6, a phase inside it
                        clk.t = 6.0
                    clk.t = 6.5
                clk.t = 7.0
            clk.t = 8.0
            with spans.span("a.y"):        # 8 .. 9
                clk.t = 9.0
            clk.t = 10.0
        with spans.span("a"):              # 10 .. 11, a sibling
            clk.t = 11.0
    assert rec.phase_wall() == {"a": 5.0, "b": 4.0, "c": 2.0}
    assert sum(rec.phase_wall().values()) == clk.t  # a partition
    assert rec.phase_detail() == {"b.x": 1.5, "a.y": 1.0}
    assert rec.phase_calls() == {"a": 2, "b": 1, "b.x": 1, "c": 1,
                                 "a.y": 1}


def test_folded_sections_and_declared_phases():
    rec = spans.Recorder(trace=False)
    rec.declare(("events", "barrier"))
    rec.add("events.py_callback", 0.25, 3)
    rec.count("events.gossip", 7)
    assert rec.phase_wall() == {"events": 0.0, "barrier": 0.0}
    assert rec.phase_detail() == {"events.py_callback": 0.25}
    assert rec.phase_calls() == {"events.py_callback": 3,
                                 "events.gossip": 7}


def test_spans_record_only_on_the_installing_thread():
    rec = spans.Recorder(trace=False)
    assert spans.span("x") is spans.span("y")  # the shared no-op
    seen = []

    def other():
        seen.append(spans.current())
        with spans.span("elsewhere"):
            pass

    with rec.installed():
        assert spans.current() is rec
        t = threading.Thread(target=other)
        t.start()
        t.join()
        with spans.span("here"):
            pass
    assert spans.current() is None
    assert seen == [None]
    assert rec.phase_calls() == {"here": 1}


def test_spans_land_on_the_profiler_trace(tmp_path):
    import jax
    from jax.profiler import ProfileData

    rec = spans.Recorder()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with rec.installed():
            with spans.span("outer"):
                with spans.span("outer.inner"):
                    sum(range(10000))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    ev = {e.name: (e.start_ns, e.start_ns + e.duration_ns)
          for pl in ProfileData.from_file(path).planes
          if pl.name.startswith("/host:")
          for ln in pl.lines for e in ln.events
          if e.name.startswith("shadow.")}
    assert set(ev) == {"shadow.outer", "shadow.outer.inner"}
    (o0, o1), (i0, i1) = ev["shadow.outer"], ev["shadow.outer.inner"]
    assert o0 <= i0 <= i1 <= o1


def _run(tmp_path, config, tag, **overrides):
    over = {"general.data_directory": str(tmp_path / tag),
            "experimental.scheduler_policy": "tpu_batch"}
    over.update(overrides)
    return Controller(load_config(config, over), mirror_log=False).run()


def _check_partition(s):
    pw, pd = s["phase_wall"], s["phase_detail"]
    assert all(v >= 0 for v in pw.values()), pw
    assert all(v >= 0 for v in pd.values()), pd
    assert sum(pw.values()) <= s["wall_seconds"], (pw, s["wall_seconds"])
    for phase in {k.split(".", 1)[0] for k in pd}:
        parts = sum(v for k, v in pd.items()
                    if k.split(".", 1)[0] == phase)
        assert parts <= pw[phase], (phase, parts, pw[phase])
    routes = s["device"]["routes"]
    assert sum(routes.values()) == s["units_sent"], routes


@pytest.mark.parametrize("floor", [64, 0, -1],
                         ids=["forced", "auto", "off"])
def test_gossip_phases_partition_the_loop(tmp_path, floor):
    s = _run(tmp_path, GOSSIP, f"g{floor}", **{
        "general.stop_time": "10s",
        "experimental.tpu_device_floor": floor})
    _check_partition(s)
    calls, routes = s["phase_calls"], s["device"]["routes"]
    # the nodes' start and timer events are Python callables the C loop
    # invokes
    assert calls["events.py_callback"] > 0
    assert calls["events.gossip"] > 0 and calls["events.emit"] > 0
    assert routes["inline"] > 0
    if floor == 64:
        assert s["device_windows_dispatched"] > 0
        assert routes["window"] > 0
        assert calls["window_dispatch.launch"] > 0
        assert calls["window_readback.wait"] > 0
        assert calls["barrier.handoff"] > 0
        assert s["device"]["handoff_units"] >= routes["window"]
    if floor == 0:  # the background attach reports its steps
        attach = s["device"]["attach"]
        assert set(attach) == {"attach.plane", "attach.calibrate",
                               "attach.warm_shapes"}
        assert all(a["seconds"] > 0 and a["rss_mb_after"] > 0
                   for a in attach.values())
    if floor == -1:
        assert routes["window"] == routes["spec_hit"] == 0
        assert s["device"]["handoff_units"] == 0


@pytest.mark.parametrize("native", [True, False], ids=["c", "python"])
def test_stream_phases_partition_the_loop(tmp_path, native):
    """Streams over the C engine and over the Python planes, with draw
    windows forced onto the device: the Python barrier's lazy batches
    resolve in windows or on the numpy twin at flush."""
    s = _run(tmp_path, TGEN, f"t{native}", **{
        "general.stop_time": "5s",
        "experimental.tpu_device_floor": 64,
        "experimental.native_colcore": native})
    _check_partition(s)
    routes = s["device"]["routes"]
    assert routes["window"] > 0
    if not native:
        assert routes["inline"] == routes["spec_hit"] == 0


def test_mesh_phases_partition_the_loop(tmp_path):
    s = _run(tmp_path, GOSSIP, "mesh", **{
        "general.stop_time": "10s",
        "experimental.scheduler_policy": "tpu_mesh",
        "experimental.tpu_mesh_floor": 0})
    _check_partition(s)
    assert s["device"]["routes"]["mesh"] > 0
    assert s["phase_calls"]["mesh_dispatch"] == s["device"]["mesh_windows"]
    assert "mesh_windows" not in s["phase_wall"]  # seconds only


def test_thread_policy_routes_add_up(tmp_path):
    s = _run(tmp_path, GOSSIP, "tpc", **{
        "general.stop_time": "10s",
        "experimental.scheduler_policy": "thread_per_core"})
    _check_partition(s)
    assert set(s["phase_wall"]) >= {"events", "gc"}


def _bench_doc(config: str, traffic: str, **sizes) -> dict:
    """A benchmark configuration's document at its CPU size."""
    import importlib.util
    import json
    import sys

    bench = ROOT / "benchmark"
    if str(bench) not in sys.path:
        sys.path.append(str(bench))
    spec = importlib.util.spec_from_file_location(
        config, bench / "configs" / f"{config}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    tr = json.loads((bench / "traffic" / f"{traffic}.json").read_text())
    return mod.doc(5, tr, {**mod.TINY, **sizes})


def _run_doc(tmp_path, doc, tag):
    from shadow_tpu.config.schema import parse_config

    return Controller(parse_config(doc, {
        "general.data_directory": str(tmp_path / tag),
        "experimental.scheduler_policy": "tpu_batch"}),
        mirror_log=False).run()


def test_gossip_runs_no_stream_or_relay_section(tmp_path):
    """Datagrams only: neither C section runs, and neither is reported."""
    doc = _bench_doc("gossip_10k", "flood")
    doc["general"]["stop_time"] = "5s"
    s = _run_doc(tmp_path, doc, "gossip")
    _check_partition(s)
    assert not {"events.stream", "events.relay"} & set(s["phase_detail"])
    assert "tor.circuits" not in s["phase_calls"]


def test_tor_sections_partition_the_loop(tmp_path):
    """A small Tor run of the benchmark's configuration: the C endpoints'
    and relays' sections join phase_detail as parts of ``events``, with
    the control cells handed to Python and the circuits counted, and the
    phases still partition the loop."""
    s = _run_doc(tmp_path, _bench_doc("tor_1_10", "web20k", web_clients=12,
                                      perf_clients=0), "tor")
    assert s["process_errors"] == []
    _check_partition(s)
    pd, calls = s["phase_detail"], s["phase_calls"]
    assert pd["events.stream"] > 0 and pd["events.relay"] > 0
    assert calls["events.stream"] > calls["events.relay"] > 0
    # 24 circuits: each hands Python its telescoping's end and its END
    # at the client, and its EXTENDs and BEGIN at the relays
    assert calls["tor.circuits"] == 24
    assert calls["tor.ctrl_to_py"] == 24 * 5
