"""Fused multi-round device windows (network/devroute.py + colplane.py).

The load-bearing property: window fusion is pure wall-clock routing
policy. Whatever K (experimental.device_window_rounds) says, whether the
window machinery dispatches one program per round (K=1), per K rounds,
adaptively (auto), or speculates prefix-min draws for future uids under
the C engine — the output tree and every simulation-semantic summary
field are bit-identical to the twin that never touches the device. That
must hold across scheduler policies, under fault churn (transitions land
at round boundaries inside an open window), and across checkpoint/resume.
"""

import hashlib
import os
from pathlib import Path

import numpy as np
import pytest
import yaml

from shadow_tpu import checkpoint as ckpt
from shadow_tpu.config import load_config, parse_config
from shadow_tpu.core.controller import Controller

ROOT = Path(__file__).resolve().parents[1]
TGEN_1K = str(ROOT / "examples" / "tgen_1k.yaml")

from shadow_tpu.core.controller import VOLATILE_SUMMARY_KEYS as VOLATILE


def _strip(summary):
    for k in VOLATILE:
        summary.pop(k, None)
    return summary


def _tree(data_dir) -> dict:
    out = {}
    hosts_dir = Path(data_dir) / "hosts"
    for root, _, files in os.walk(hosts_dir):
        for f in sorted(files):
            p = os.path.join(root, f)
            rel = os.path.relpath(p, data_dir)
            out[rel] = hashlib.sha256(open(p, "rb").read()).hexdigest()
    assert out, f"no host output under {data_dir}"
    return out


def _run(tmp_path, tag, policy="tpu_batch", stop="5s", **overrides):
    over = {
        "general.data_directory": str(tmp_path / tag),
        "general.stop_time": stop,
        "experimental.scheduler_policy": policy,
    }
    over.update(overrides)
    cfg = load_config(TGEN_1K, over)
    summary = Controller(cfg, mirror_log=False).run()
    return summary, _tree(tmp_path / tag)


def test_min_draw_kernel_is_threshold_factored_bitmatch():
    """The speculative primitive: dropped == (prefix-min draw < thresh)
    for ANY thresh — one speculated row must serve every destination a
    host later picks. Cross-check dispatch_min against the committed
    numpy twin (fluid.loss_flags) over random identities and thresholds."""
    from shadow_tpu.network.fluid import loss_flags
    from shadow_tpu.ops.propagate import DeviceDrawPlane

    rng = np.random.default_rng(3)
    n = 4096
    lo = rng.integers(0, 1 << 32, n, dtype=np.uint64).astype(np.uint32)
    hi = rng.integers(0, 1 << 32, n, dtype=np.uint64).astype(np.uint32)
    npk = rng.integers(1, 11, n).astype(np.uint32)
    plane = DeviceDrawPlane(seed=5, max_batch=1 << 16)
    mins = plane.dispatch_min(lo, hi, npk).read()
    for th_val in (0, 1 << 8, 1 << 14, 1 << 20):
        th = np.full(n, th_val, np.uint32)
        assert ((mins < th) == loss_flags(5, lo, hi, npk, th)).all(), th_val
    # per-row thresholds too
    th = rng.integers(0, 1 << 20, n).astype(np.uint32)
    assert ((mins < th) == loss_flags(5, lo, hi, npk, th)).all()


def test_window_k_matrix_bit_identical(tmp_path):
    """Python-plane deferred windows: K in {1, 4, 16, auto} with a forced
    floor all produce the baseline output tree while actually dispatching
    fused windows (windows end at round boundaries for every K)."""
    base_s, base_t = _run(tmp_path, "base",
                          **{"experimental.tpu_device_floor": -1,
                             "experimental.native_colcore": False})
    for k in (1, 4, 16, "auto"):
        s, t = _run(tmp_path, f"k{k}",
                    **{"experimental.tpu_device_floor": 1,
                       "experimental.native_colcore": False,
                       "experimental.device_window_rounds": k})
        assert s["device_windows_dispatched"] > 0, k
        assert t == base_t, f"output tree diverged at K={k}"
        assert _strip(s) == _strip(dict(base_s)), f"summary diverged K={k}"


def test_spec_windows_c_plane_bit_identical(tmp_path):
    """C-plane speculative forward windows: the default tpu_batch path
    (C engine + auto device) serves draws from speculative min-draw
    tables and stays bit-identical to the device-off twin."""
    from shadow_tpu.ops.propagate import DeviceDrawPlane

    pytest.importorskip("shadow_tpu.native._colcore")
    # warm the process-wide attach cache so the device publishes at round
    # 0 (tgen_1k general.seed is 2; unit_mtus default 10)
    DeviceDrawPlane.attach_cached(2, 65536, 0, 10)
    base_s, base_t = _run(tmp_path, "cbase", stop="8s",
                          **{"experimental.tpu_device_floor": -1})
    s, t = _run(tmp_path, "cspec", stop="8s")
    assert t == base_t
    assert _strip(dict(s)) == _strip(dict(base_s))
    assert s["device_windows_dispatched"] > 0
    assert s["device"]["spec_hits"] > 0


def test_policies_bit_identical_with_windows(tmp_path):
    """Window fusion on tpu_batch vs the two reference thread policies:
    one simulation, three schedulers, identical trees."""
    _, tpc = _run(tmp_path, "tpc", policy="thread_per_core", stop="3s")
    _, tph = _run(tmp_path, "tph", policy="thread_per_host", stop="3s")
    _, tpu = _run(tmp_path, "tpu", stop="3s",
                  **{"experimental.tpu_device_floor": 1,
                     "experimental.native_colcore": False,
                     "experimental.device_window_rounds": 4})
    assert tpc == tph == tpu


def test_checkpoint_resume_with_windows(tmp_path):
    """Fused windows + checkpoint/resume: windows end at round
    boundaries, so round-boundary snapshots stay valid and a resumed run
    reproduces the uninterrupted output tree exactly."""
    ov = {"experimental.tpu_device_floor": 1,
          "experimental.device_window_rounds": 4,
          "experimental.native_colcore": False}
    full_s, full_t = _run(tmp_path, "full", **ov)
    src_s, src_t = _run(tmp_path, "src",
                        **{"general.checkpoint_every": "2s", **ov})
    assert src_t == full_t
    paths = sorted((tmp_path / "src" / "checkpoints").glob("*.ckpt"))
    assert paths, "no checkpoints written"
    cfg = load_config(TGEN_1K, {
        "general.data_directory": str(tmp_path / "res"),
        "general.stop_time": "5s",
        "experimental.scheduler_policy": "tpu_batch",
        **{k: str(v) for k, v in ov.items()},
    })
    ctl, resume_at = ckpt.load_checkpoint(paths[0], cfg, mirror_log=False)
    res_s = ctl.run(resume_at=resume_at)
    assert res_s["device_windows_dispatched"] > 0  # machinery reattached
    assert _tree(tmp_path / "res") == full_t
    assert _strip(dict(res_s)) == _strip(dict(full_s))


FAULT_DOC = """
general:
  stop_time: 30s
  seed: 9
network:
  graph:
    type: gml
    inline: |
      graph [
        directed 0
        node [ id 0 host_bandwidth_up "50 Mbit" host_bandwidth_down "50 Mbit" ]
        node [ id 1 host_bandwidth_up "20 Mbit" host_bandwidth_down "20 Mbit" ]
        edge [ source 0 target 1 latency "25 ms" packet_loss 0.01 ]
        edge [ source 0 target 0 latency "5 ms" ]
        edge [ source 1 target 1 latency "5 ms" ]
      ]
hosts:
  server:
    network_node_id: 0
    processes:
      - path: pyapp:shadow_tpu.models.tgen:TGenServer
        args: ["8080"]
  client:
    network_node_id: 1
    processes:
      - path: pyapp:shadow_tpu.models.tgen:TGenClient
        args: ["4 MB", "2", serial, "8080", server]
        start_time: 1s
faults:
  churn:
    - {hosts: [client], mean_uptime: 6s, mean_downtime: 2s, start_time: 2s}
"""


def _run_faults(tmp_path, tag, **overrides):
    doc = yaml.safe_load(FAULT_DOC)
    over = {"general.data_directory": str(tmp_path / tag),
            "experimental.scheduler_policy": "tpu_batch"}
    over.update(overrides)
    cfg = parse_config(doc, over)
    summary = Controller(cfg, mirror_log=False).run()
    return _strip(summary), _tree(tmp_path / tag)


def test_fault_churn_with_windows_bit_identical(tmp_path):
    """Fused windows under host churn: fault transitions land at round
    boundaries inside an open window (forced flags ride the window's
    batches), and the tree stays byte-identical to the device-off twin."""
    base_s, base_t = _run_faults(tmp_path, "fb",
                                 **{"experimental.tpu_device_floor": -1})
    assert base_s.get("fault_transitions_applied", 0) > 0
    for k in (1, 4):
        s, t = _run_faults(tmp_path, f"fw{k}",
                           **{"experimental.tpu_device_floor": 1,
                              "experimental.device_window_rounds": k})
        assert t == base_t, f"churn tree diverged at K={k}"
        assert s == base_s, f"churn summary diverged at K={k}"


GOSSIP = str(ROOT / "examples" / "gossip_churn.yaml")


def _run_gossip(tmp_path, tag, **overrides):
    over = {"general.data_directory": str(tmp_path / tag),
            "general.stop_time": "10s",
            "experimental.scheduler_policy": "tpu_batch"}
    over.update(overrides)
    cfg = load_config(GOSSIP, over)
    ctl = Controller(cfg, mirror_log=False)
    summary = ctl.run()
    return summary, _tree(tmp_path / tag)


@pytest.fixture(scope="module")
def gossip_base(tmp_path_factory):
    """The pure-Python plane with device draws off: the reference every
    C-plane hand-off route must reproduce."""
    s, t = _run_gossip(tmp_path_factory.mktemp("gbase"), "base",
                       **{"experimental.tpu_device_floor": -1,
                          "experimental.native_colcore": False})
    return _strip(s), t


@pytest.mark.parametrize("route", ["window", "twin", "chunk", "off"])
def test_c_handoff_routes_bit_identical(tmp_path, monkeypatch, gossip_base,
                                        route):
    """Batches the C barrier hands off keep their rows in the core until
    their flags come back: through a device window, through the numpy
    twin when no window slot is free at flush, through an oversized batch
    dispatched in tpu_max_batch chunks — or never, with draws off. Each
    route stores every handed-off unit from the core
    (resident_units == handoff_units) and reproduces the reference."""
    from shadow_tpu.network import colplane

    pytest.importorskip("shadow_tpu.native._colcore")
    ov = {"experimental.tpu_device_floor": -1 if route == "off" else 1}
    if route == "twin":
        monkeypatch.setattr(colplane, "WINDOW_SLOTS", 0)
    if route == "chunk":
        ov["experimental.tpu_max_batch"] = 32
        chunked = []
        init = colplane._ConcatHandle.__init__

        def spy(self, plane, handles):
            chunked.append(len(handles))
            init(self, plane, handles)

        monkeypatch.setattr(colplane._ConcatHandle, "__init__", spy)
    s, t = _run_gossip(tmp_path, route, **ov)
    dev, routes = s["device"], s["device"]["routes"]
    assert dev["resident_units"] == dev["handoff_units"]
    if route == "off":
        assert dev["handoff_units"] == 0 and dev["resident_peak"] == 0
    else:
        assert dev["handoff_units"] > 0 and dev["resident_peak"] > 0
        assert routes["inline"] == 0  # floor 1: every live batch handed off
    if route == "window":
        assert routes["window"] > 0
    if route == "twin":
        assert s["device_windows_dispatched"] == 0
        assert routes["twin"] > 0 and routes["window"] == 0
    if route == "chunk":
        assert chunked and max(chunked) > 1
    assert t == gossip_base[1]
    assert _strip(s) == gossip_base[0]


def test_c_handoff_checkpoint_mid_window(tmp_path, monkeypatch, gossip_base):
    """Checkpoints land while handed-off batches wait for their windows:
    flush_all stores them all before the state walk, so every snapshot is
    taken with no batch left in the C core, and a resume reproduces the
    uninterrupted run."""
    pytest.importorskip("shadow_tpu.native._colcore")
    held = []
    save = ckpt.save_checkpoint

    def spy(controller, now):
        before = controller.engine.pending_batches()
        path = save(controller, now)
        held.append((before, controller.engine.pending_batches()))
        return path

    monkeypatch.setattr(ckpt, "save_checkpoint", spy)
    ov = {"experimental.tpu_device_floor": 1}
    src_s, src_t = _run_gossip(tmp_path, "src",
                               **{"general.checkpoint_every": "3s", **ov})
    assert held and any(b > 0 for b, _ in held), held
    assert all(a == 0 for _, a in held), held
    assert src_t == gossip_base[1]
    paths = sorted((tmp_path / "src" / "checkpoints").glob("*.ckpt"))
    cfg = load_config(GOSSIP, {
        "general.data_directory": str(tmp_path / "res"),
        "general.stop_time": "10s",
        "experimental.scheduler_policy": "tpu_batch",
        **{k: str(v) for k, v in ov.items()},
    })
    ctl, resume_at = ckpt.load_checkpoint(paths[0], cfg, mirror_log=False)
    res_s = ctl.run(resume_at=resume_at)
    dev = res_s["device"]
    assert dev["handoff_units"] > 0
    assert dev["resident_units"] == dev["handoff_units"]
    assert _tree(tmp_path / "res") == gossip_base[1]
    assert _strip(dict(res_s)) == gossip_base[0]


def test_device_window_rounds_config_parse():
    doc = {"general": {"stop_time": "1s"},
           "hosts": {"h": {"network_node_id": 0}}}
    assert parse_config(doc).experimental.device_window_rounds == 0
    doc["experimental"] = {"device_window_rounds": "auto"}
    assert parse_config(doc).experimental.device_window_rounds == 0
    doc["experimental"] = {"device_window_rounds": 8}
    assert parse_config(doc).experimental.device_window_rounds == 8
    doc["experimental"] = {"device_window_rounds": -2}
    with pytest.raises(ValueError):
        parse_config(doc)
