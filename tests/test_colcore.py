"""C engine (native/colcore) bit-identity gates.

The C fast path accelerates functions, not structures (see the module
docstring in native/colcore/colcore.c), so its correctness obligation is
exact: with ``experimental.native_colcore`` toggled, every summary field
and every byte of the output tree must match the pure-Python columnar
plane — which the cross-plane suite (test_colplane.py) already holds
bit-identical to the per-unit reference plane. Transitively the C engine
is therefore pinned to all three Python implementations.
"""

from __future__ import annotations

import hashlib
import os

import numpy as np
import pytest

from shadow_tpu.config.schema import load_config
from shadow_tpu.core.controller import Controller

from shadow_tpu.native import _colcore  # built by conftest.py

from shadow_tpu.core.controller import VOLATILE_SUMMARY_KEYS as VOLATILE


def _run(tmp_path, cfg_path, colcore, overrides=None, policy="tpu_batch"):
    dd = tmp_path / ("c" if colcore else "py")
    ov = {
        "experimental.scheduler_policy": policy,
        "experimental.native_colcore": colcore,
        "general.data_directory": str(dd),
    }
    ov.update(overrides or {})
    cfg = load_config(cfg_path, ov)
    ctl = Controller(cfg, mirror_log=False)
    assert (ctl.engine._c is not None) == colcore
    summary = ctl.run()
    for k in VOLATILE:
        summary.pop(k, None)
    tree = {}
    hosts_dir = dd / "hosts"
    if hosts_dir.is_dir():
        for root, _, files in os.walk(hosts_dir):
            for f in sorted(files):
                p = os.path.join(root, f)
                rel = os.path.relpath(p, dd)
                tree[rel] = hashlib.sha256(open(p, "rb").read()).hexdigest()
    return summary, tree


def _assert_identical(tmp_path, cfg_path, overrides=None):
    a, ta = _run(tmp_path, cfg_path, True, overrides)
    b, tb = _run(tmp_path, cfg_path, False, overrides)
    assert a == b
    assert ta == tb


def test_threefry_twin_exact():
    """C unit_dropped == fluid.loss_flags on randomized units."""
    from shadow_tpu.network.fluid import loss_flags

    rng = np.random.default_rng(7)
    n = 5000
    uid = rng.integers(0, 1 << 62, n, dtype=np.uint64)
    npk = rng.integers(1, 64, n).astype(np.uint32)
    th = rng.integers(0, 1 << 24, n).astype(np.uint32)
    th[rng.random(n) < 0.25] = 0
    seed = 0xDEADBEEF1234
    ref = loss_flags(
        seed,
        (uid & np.uint64(0xFFFFFFFF)).astype(np.uint32),
        (uid >> np.uint64(32)).astype(np.uint32),
        npk,
        th,
    )
    got = np.array(
        [_colcore.unit_dropped(seed, int(u), int(k), int(t))
         for u, k, t in zip(uid, npk, th)]
    )
    assert (ref == got).all()


def test_stream_workload_identical(tmp_path):
    """tgen (stream transport runs through the Python dispatch fallback,
    barrier/store/extract through C)."""
    _assert_identical(tmp_path, "examples/tgen_100host.yaml")


def test_gossip_workload_identical(tmp_path):
    """gossip (full C path: dispatch, datagram delivery, the C model)."""
    _assert_identical(
        tmp_path, "examples/gossip_10k.yaml", {"general.stop_time": "3s"}
    )


def test_tor_workload_identical(tmp_path):
    """tor model: streams + datagrams mixed, loss notifications."""
    _assert_identical(
        tmp_path, "examples/tor_400relay.yaml", {"general.stop_time": "10s"}
    )


def test_pcap_host_python_fallback(tmp_path):
    """A pcap-enabled host forces the per-host Python dispatch path; the C
    engine must keep the rest of the simulation on the C path and stay
    bit-identical (including the pcap file itself)."""
    _assert_identical(
        tmp_path,
        "examples/echo.yaml",
        {"hosts.server.pcap_enabled": True},
    )


def test_fault_filter_python_barrier(tmp_path):
    """fault_filter set -> the barrier falls back to the Python path
    per-round while emission/extraction stay shared; results must match a
    pure-Python run with the same filter."""

    def go(colcore):
        dd = tmp_path / ("fc" if colcore else "fpy")
        cfg = load_config(
            "examples/tgen_100host.yaml",
            {
                "experimental.scheduler_policy": "tpu_batch",
                "experimental.native_colcore": colcore,
                "general.data_directory": str(dd),
                "general.stop_time": "20s",
            },
        )
        ctl = Controller(cfg, mirror_log=False)
        ctl.engine.fault_filter = lambda u: u.dst == 3 and u.kind == 2
        s = ctl.run()
        for k in VOLATILE:
            s.pop(k, None)
        return s

    assert go(True) == go(False)


def test_blackhole_compaction_identical(tmp_path):
    """Partitioned topology: blackholed units exercise the C barrier's
    in-place compaction (review r4 finding #1 — refcount discipline of
    skipped rows). Summaries, counters, and trees must match the Python
    twin, and units_blackholed must be nonzero so the path really ran."""
    import yaml

    from shadow_tpu.config import parse_config
    from tests.test_colplane import PARTITIONED

    def go(colcore):
        dd = tmp_path / ("bc" if colcore else "bpy")
        cfg = parse_config(yaml.safe_load(PARTITIONED), {
            "experimental.scheduler_policy": "tpu_batch",
            "experimental.native_colcore": colcore,
            "general.data_directory": str(dd),
        })
        ctl = Controller(cfg, mirror_log=False)
        s = ctl.run()
        assert ctl.engine.units_blackholed > 0
        for k in VOLATILE:
            s.pop(k, None)
        return s

    assert go(True) == go(False)


LOSSY_PAIR = """
general:
  stop_time: 1s
network:
  graph:
    type: gml
    inline: |
      graph [
        directed 0
        node [ id 0 host_bandwidth_up "50 Mbit" host_bandwidth_down "50 Mbit" ]
        node [ id 1 host_bandwidth_up "50 Mbit" host_bandwidth_down "50 Mbit" ]
        edge [ source 0 target 1 latency "25 ms" packet_loss 0.01 ]
        edge [ source 0 target 0 latency "5 ms" ]
        edge [ source 1 target 1 latency "5 ms" ]
      ]
hosts:
  a:
    network_node_id: 0
  b:
    network_node_id: 1
"""


def test_handoff_teardown_releases_payloads(tmp_path):
    """A device batch the C barrier handed off holds its rows' payload
    refs until store_pending; a run torn down before that (close(), or
    the core simply dropped) must give every ref back."""
    import gc
    import sys

    import yaml

    from shadow_tpu.config import parse_config

    cfg = parse_config(yaml.safe_load(LOSSY_PAIR), {
        "experimental.scheduler_policy": "tpu_batch",
        "experimental.tpu_device_floor": -1,
        "general.data_directory": str(tmp_path / "d"),
    })
    eng = Controller(cfg, mirror_log=False).engine
    payload = bytes(bytearray(b"p" * 64))  # a fresh object of our own
    base = sys.getrefcount(payload)
    eng.device = object()  # stands in for an attached draw plane
    eng.device_floor = 1

    def hand_off(k):
        for i in range(k):
            eng._c.emit_row(0, 0, 1, 100, 0, 1, 2, 64, i, 0, 1, payload)
        r = eng._c.barrier(0, 10**6)
        assert isinstance(r, tuple) and r[1] == k
        eng._dispatch_device_batch(r, 10**6)
        return r[0]

    bid = hand_off(3)
    assert sys.getrefcount(payload) == base + 3
    assert eng.pending_batches() == 1
    with pytest.raises(ValueError):
        eng._c.store_pending(bid, np.zeros(2, dtype=bool), 10**6)
    eng.close()
    assert eng.pending_batches() == 0
    assert sys.getrefcount(payload) == base

    hand_off(5)
    assert sys.getrefcount(payload) == base + 5
    del eng  # the plane, its hosts and the core go: the core's teardown
    gc.collect()
    assert sys.getrefcount(payload) == base


def test_deferred_ingress_reentry(tmp_path):
    """Tight down-links force ingress deferral: the C dispatch parks rows
    in the Python backlog and the drain path re-enters the C gossip state
    (GossipState.on_msg). Equality proves the two entry points share one
    state."""
    _assert_identical(
        tmp_path,
        "examples/gossip_10k.yaml",
        {"general.stop_time": "2s", "general.bootstrap_end_time": 0},
    )
