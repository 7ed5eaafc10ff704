"""Device-resident columnar transport gates (PR 11).

The acceptance matrix for network/devtransport.py +
ops/transport_kernels.py + the colcore column snapshot/adopt ABI:

- identity: device-transport on/off x colcore on/off x scheduler
  policies on the web and tor families — output trees, flows.jsonl,
  metrics.jsonl, digest streams hash-equal, with a vacuity guard (the
  on-leg must actually have advanced cohorts through the batched
  kernel);
- checkpoint/resume mid-run with the columnar transport live;
- the wrong-kernel-guess discipline (PR 3's speculative-window rule,
  applied to transport): force the stage-time classifier to lie and
  assert replay verification rejects every bad row to the scalar twin
  with byte-identical results;
- the three-surface column contract: Core.transport_columns (C) ==
  export_columns (Python) for twin runs, adopt round-trips on both
  planes, refusal on a row naming no live endpoint;
- kernel unit twins: vectorized cc_on_ack/icbrt bit-equal to the
  scalar CongestionControl classes over a randomized input sweep.
"""

from pathlib import Path

import numpy as np
import pytest
import yaml

from shadow_tpu.config import parse_config
from shadow_tpu.core.controller import Controller
from shadow_tpu.network.devtransport import (
    COLUMNS, KEY_COLUMNS, DeviceTransport, adopt_columns, export_columns)
from shadow_tpu.ops import transport_kernels as TK

from tests.test_checkpoint import _strip, _tree
from tests.test_tor_cplane import TOR_CFG

#: a scaled-down web_cdn (clients -> edges -> origin + DNS chain) with
#: enough concurrent bulk transfer that ack-dominated rounds exist —
#: loss-free, so every ack is a clean cumulative advance (the kernel's
#: target regime); the tor leg covers the lossy/SACK interleavings
WEB_CFG = """
general:
  stop_time: 16s
  seed: 23
network:
  graph:
    type: gml
    inline: |
      graph [
        directed 0
        node [ id 0 host_bandwidth_up "200 Mbit" host_bandwidth_down "200 Mbit" ]
        node [ id 1 host_bandwidth_up "50 Mbit" host_bandwidth_down "50 Mbit" ]
        node [ id 2 host_bandwidth_up "50 Mbit" host_bandwidth_down "50 Mbit" ]
        edge [ source 0 target 1 latency "20 ms" ]
        edge [ source 0 target 2 latency "35 ms" ]
        edge [ source 1 target 2 latency "15 ms" ]
        edge [ source 0 target 0 latency "2 ms" ]
        edge [ source 1 target 1 latency "2 ms" ]
        edge [ source 2 target 2 latency "2 ms" ]
      ]
telemetry:
  sample_every: 5s
hosts:
  origin0:
    network_node_id: 0
    processes:
      - path: pyapp:shadow_tpu.models.web:WebOrigin
        args: ["80"]
  dnsroot:
    network_node_id: 0
    processes:
      - path: pyapp:shadow_tpu.models.dns:DnsAuth
        args: ["53"]
  dnsauth:
    network_node_id: 0
    processes:
      - path: pyapp:shadow_tpu.models.dns:DnsAuth
        args: ["53"]
  resolver0:
    network_node_id: 1
    processes:
      - path: pyapp:shadow_tpu.models.dns:DnsResolver
        args: ["53", dnsroot, dnsauth]
  edge0:
    network_node_id: 1
    processes:
      - path: pyapp:shadow_tpu.models.web:WebEdge
        args: ["80", origin0, "80", "60"]
  edge1:
    network_node_id: 2
    processes:
      - path: pyapp:shadow_tpu.models.web:WebEdge
        args: ["80", origin0, "80", "60"]
  c0_:
    network_node_id: 1
    quantity: 5
    processes:
      - path: pyapp:shadow_tpu.models.web:WebClient
        args: ["4", "3", "200 kB", "60 kB", "80", resolver0, edge0, edge1]
        start_time: 300 ms
        environment: {WEB_RETRIES: "2", WEB_THINK_SEC: "1"}
  c1_:
    network_node_id: 2
    quantity: 5
    processes:
      - path: pyapp:shadow_tpu.models.web:WebClient
        args: ["4", "3", "200 kB", "60 kB", "80", resolver0, edge0, edge1]
        start_time: 700 ms
        environment: {WEB_RETRIES: "2", WEB_THINK_SEC: "1"}
"""


def _run(tmp_path, tag, cfg_text, **overrides):
    dd = tmp_path / tag
    ov = {"general.data_directory": str(dd),
          "general.state_digest_every": 50,
          "telemetry": {}}
    ov.update(overrides)
    cfg = parse_config(yaml.safe_load(cfg_text), ov)
    ctl = Controller(cfg, mirror_log=False)
    summary = ctl.run()
    return ctl, _strip(summary), _tree(dd)


DEVT_ON = {"experimental.scheduler_policy": "tpu_batch",
           "experimental.native_colcore": False,
           "experimental.device_transport": True}
DEVT_OFF = {"experimental.scheduler_policy": "tpu_batch",
            "experimental.native_colcore": False}


def test_identity_matrix_web(tmp_path):
    """THE acceptance gate: device-transport on/off x colcore on/off x
    thread policies on the web family — trees, flows, metrics, digests
    hash-equal; the devt leg really advanced cohorts (vacuity guard)."""
    legs = {
        "tpc": {"experimental.scheduler_policy": "thread_per_core",
                "experimental.device_transport": True},  # per-unit: no-op
        "tph": {"experimental.scheduler_policy": "thread_per_host"},
        "c-on": {"experimental.scheduler_policy": "tpu_batch",
                 "experimental.native_colcore": True,
                 "experimental.device_transport": True},  # C twin: no-op
        "py-off": DEVT_OFF,
        "py-on": DEVT_ON,
    }
    runs = {tag: _run(tmp_path, tag, WEB_CFG, **ov)
            for tag, ov in legs.items()}
    base = runs["tpc"][2]
    assert base, "empty output tree"
    for tag in legs:
        assert runs[tag][2] == base, f"{tag} diverged from thread_per_core"
        assert runs[tag][1] == runs["tpc"][1], f"{tag} summary diverged"
    # vacuity guards: the Python devt leg advanced real cohorts through
    # the batched kernel; the C and per-unit legs correctly did not
    ctl_on = runs["py-on"][0]
    devt = ctl_on.engine.devt
    assert devt is not None and devt.cohorts > 0 and devt.acks_batched > 0
    assert runs["c-on"][0].engine.devt is None
    assert getattr(runs["tpc"][0].engine, "devt", None) is None
    # attribution satellite: the columnar path's wall is split out
    assert "transport_tick" in ctl_on.spans.phase_wall()


def test_identity_tor(tmp_path):
    """The lossy/SACK-bearing family: tor_400-shaped config with packet
    loss — recovery episodes, dup acks, and SACK payloads interleave
    with clean advances, so the verifier's fallback paths are exercised
    for real (misguesses may or may not occur; identity must hold)."""
    runs = {tag: _run(tmp_path, tag, TOR_CFG, **ov,
                      **{"general.stop_time": "20s"})
            for tag, ov in (("off", DEVT_OFF), ("on", DEVT_ON))}
    assert runs["on"][2] == runs["off"][2], "tor devt on/off diverged"
    assert runs["on"][1] == runs["off"][1]
    devt = runs["on"][0].engine.devt
    assert devt is not None and devt.cohorts > 0


def test_wrong_kernel_guess_is_verified(tmp_path, monkeypatch):
    """PR 3's speculative-window discipline, applied to transport: force
    the stage-time classifier to stage EVERYTHING (dup acks, recovery
    acks, non-advances) and assert replay verification rejects every bad
    row to the scalar twin — misguesses counted, results byte-identical."""
    monkeypatch.setattr(DeviceTransport, "_stageable",
                        staticmethod(lambda ep, s, cum: True))
    _ctl_off, s_off, t_off = _run(tmp_path, "g-off", WEB_CFG, **DEVT_OFF)
    ctl_on, s_on, t_on = _run(tmp_path, "g-on", WEB_CFG, **DEVT_ON)
    assert t_on == t_off and s_on == s_off
    devt = ctl_on.engine.devt
    assert devt is not None and devt.cohorts > 0
    # the lying classifier stages non-advances (e.g. window-update acks
    # whose cum does not move); every one must have been rejected
    assert devt.misguesses > 0, \
        "the forced mis-stage produced no rejected rows — the test is " \
        "vacuous (classifier not consulted?)"


def test_checkpoint_resume_with_devt_live(tmp_path):
    """Mid-run checkpoint + resume with the columnar transport on: the
    resumed run reproduces the uninterrupted run's host tree and digest
    suffix; the engine reattaches (volatile key, like native_colcore)."""
    from shadow_tpu.checkpoint import load_checkpoint

    _c, s_full, full = _run(tmp_path, "ck-full", WEB_CFG, **DEVT_ON)
    _run(tmp_path, "ck-src", WEB_CFG, **DEVT_ON,
         **{"general.checkpoint_every": "6s",
            "general.checkpoint_dir": str(tmp_path / "cks")})
    cks = sorted((tmp_path / "cks").glob("ckpt_*.ckpt"))
    assert cks, "no checkpoint written"
    dd = tmp_path / "ck-res"
    cfg = parse_config(yaml.safe_load(WEB_CFG), {
        "general.data_directory": str(dd),
        "general.state_digest_every": 50,
        "telemetry": {}, **DEVT_ON})
    ctl, resume_at = load_checkpoint(str(cks[0]), cfg, mirror_log=False)
    assert ctl.engine.devt is not None, "devt did not reattach on resume"
    assert all(h.devt is ctl.engine.devt for h in ctl.hosts)
    r = ctl.run(resume_at=resume_at)
    resumed = _tree(dd)
    full_hosts = {k: v for k, v in full.items() if k.startswith("hosts")}
    res_hosts = {k: v for k, v in resumed.items() if k.startswith("hosts")}
    assert res_hosts == full_hosts, "resumed host tree diverged"
    full_dig = (tmp_path / "ck-full" / "state_digests.jsonl").read_text()
    res_dig = (dd / "state_digests.jsonl").read_text()
    assert res_dig and full_dig.endswith(res_dig)
    assert _strip(r) == s_full


def test_columns_cross_surface(tmp_path):
    """The three-surface column contract: the C snapshot ABI
    (Core.transport_columns) produces the exact arrays the Python
    export produces for twin runs; adopt round-trips on both planes and
    refuses rows naming no live endpoint."""
    stop = {"general.stop_time": "6s"}
    ctl_py, _s1, _t1 = _run(tmp_path, "col-py", WEB_CFG, **DEVT_OFF,
                            **stop)
    ctl_c, _s2, _t2 = _run(
        tmp_path, "col-c", WEB_CFG,
        **{"experimental.scheduler_policy": "tpu_batch",
           "experimental.native_colcore": True}, **stop)
    core = ctl_c.engine._c
    if core is None:
        pytest.skip("colcore not built")
    cols_py = export_columns(ctl_py.hosts)
    cols_c = core.transport_columns()
    names = KEY_COLUMNS + COLUMNS
    assert set(cols_c) == set(names)
    n = len(cols_py["hid"])
    assert n > 0, "no live endpoints at the snapshot instant"
    for name in names:
        assert np.array_equal(cols_py[name], cols_c[name]), name
    # adopt round-trips (identity writeback changes nothing)
    core.adopt_transport_columns(cols_c)
    after = core.transport_columns()
    for name in names:
        assert np.array_equal(after[name], cols_c[name]), name
    assert adopt_columns(ctl_py.hosts, cols_py) == n
    after_py = export_columns(ctl_py.hosts)
    for name in names:
        assert np.array_equal(after_py[name], cols_py[name]), name
    # a genuine writeback lands: halve one endpoint's cwnd via the ABI
    mutated = {k: v.copy() for k, v in cols_c.items()}
    mutated["cwnd"][0] = max(int(mutated["cwnd"][0]) // 2, 2920)
    core.adopt_transport_columns(mutated)
    assert core.transport_columns()["cwnd"][0] == mutated["cwnd"][0]
    # refusal: a row naming no live endpoint fails by name, and refusal
    # is ATOMIC — earlier rows must not have been half-adopted (the bad
    # row is placed LAST and an earlier row carries a sentinel value a
    # non-atomic writeback would have landed)
    bogus = {k: v.copy() for k, v in mutated.items()}
    bogus["cwnd"][0] = 123456789
    bogus["local_port"][-1] = 1  # no such connection key
    with pytest.raises(ValueError, match="no live C endpoint"):
        core.adopt_transport_columns(bogus)
    after_refusal = core.transport_columns()
    for name in names:
        assert np.array_equal(after_refusal[name], mutated[name]), name
    # the Python twin refuses atomically too
    bogus_py = {k: v.copy() for k, v in after_py.items()}
    bogus_py["cwnd"][0] = 123456789
    bogus_py["local_port"][-1] = 1
    with pytest.raises(ValueError, match="no live Python endpoint"):
        adopt_columns(ctl_py.hosts, bogus_py)
    for name in names:
        assert np.array_equal(export_columns(ctl_py.hosts)[name],
                              after_py[name]), name
    # ... and a length-mismatched adopt column refuses up front (the
    # atomicity contract covers malformed snapshots too)
    short = {k: v.copy() for k, v in after_py.items()}
    short["cwnd"] = short["cwnd"][:0]
    with pytest.raises(ValueError, match="missing or not length"):
        adopt_columns(ctl_py.hosts, short)
    for name in names:
        assert np.array_equal(export_columns(ctl_py.hosts)[name],
                              after_py[name]), name


def test_kernel_twins_bit_exact():
    """Randomized sweep: the vectorized cc_on_ack equals the scalar
    CongestionControl classes field for field, and icbrt equals
    transport._icbrt — the numpy half of the third-surface contract
    (twincheck pins the literals; this pins the arithmetic)."""
    from shadow_tpu.network.transport import (
        MIN_CWND, CubicLike, NewReno, _icbrt)

    class _H:
        pass

    class _Ep:
        pass

    class _S:
        pass

    rng = np.random.default_rng(11)
    n = 5000
    cc_id = rng.integers(0, 2, n).astype(np.int64)
    cwnd = rng.integers(MIN_CWND, 1 << 34, n).astype(np.int64)
    ssthresh = np.where(rng.random(n) < 0.5,
                        rng.integers(MIN_CWND, 1 << 34, n),
                        1 << 62).astype(np.int64)
    w_max = rng.integers(0, 1 << 33, n).astype(np.int64)
    eps = np.where(rng.random(n) < 0.3, 0,
                   rng.integers(1, 10 ** 12, n)).astype(np.int64)
    newly = rng.integers(1, 1 << 21, n).astype(np.int64)
    now = rng.integers(10 ** 12, 10 ** 13, n).astype(np.int64)
    kc, kw, ke = TK.cc_on_ack(cc_id, cwnd, ssthresh, w_max, eps, newly,
                              now)
    for i in range(n):
        s = _S()
        s.cwnd, s.ssthresh = int(cwnd[i]), int(ssthresh[i])
        s.w_max, s.epoch_start = int(w_max[i]), int(eps[i])
        s.ep = _Ep()
        s.ep.host = _H()
        s.ep.host._now = int(now[i])
        cc = NewReno() if cc_id[i] == 0 else CubicLike()
        cc.on_ack(s, int(newly[i]))
        assert (s.cwnd, s.w_max, s.epoch_start) == (
            int(kc[i]), int(kw[i]), int(ke[i])), i
    xs = np.concatenate([
        rng.integers(0, 1 << 60, 2000),
        [0, 1, 7, 8, 26, 27, (1 << 20) ** 3 - 1, (1 << 20) ** 3],
    ]).astype(np.int64)
    kv = TK.icbrt(xs)
    for i, x in enumerate(xs):
        assert _icbrt(int(x)) == int(kv[i]), x
    # rto_min_scan: the vectorized expiry scan names the earliest slot
    dl = rng.integers(1, 1 << 60, 64).astype(np.int64)
    t, i = TK.rto_min_scan(dl)
    assert t == int(dl.min()) and int(dl[i]) == t


def test_device_kernel_matches_numpy():
    """The jax.jit twin (pinned bucket shapes, x64) returns the numpy
    twin's exact results — routing between them is pure wall policy.
    attach() raises when the device path cannot come up; it never hands
    back a silent numpy-only kernel."""
    devk = TK.DeviceAckKernel.attach()
    rng = np.random.default_rng(3)
    n = 1000  # pads to the 1024 bucket
    from shadow_tpu.network.transport import MIN_CWND

    cols = (
        rng.integers(0, 2, n), rng.integers(MIN_CWND, 1 << 34, n),
        np.full(n, 1 << 62), rng.integers(0, 1 << 33, n),
        rng.integers(0, 10 ** 12, n), rng.integers(0, 1 << 30, n),
        rng.integers(0, 1 << 40, n),
    )
    cols = tuple(c.astype(np.int64) for c in cols)
    cum = (cols[5] + rng.integers(1, 1 << 20, n)).astype(np.int64)
    now = rng.integers(10 ** 12, 10 ** 13, n).astype(np.int64)
    ref = TK.ack_advance(*cols, cum, now)
    dev = devk.run(*cols, cum, now=now)
    for a, b in zip(ref, dev):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # oversized cohorts CHUNK at the largest pinned bucket (rows are
    # independent — boundaries cannot change results) instead of
    # compiling a fresh shape mid-run
    big = tuple(np.tile(c, 70) for c in cols)  # 70k rows > 65536
    big_cum = np.tile(cum, 70)
    big_now = np.tile(now, 70)
    ref2 = TK.ack_advance(*big, big_cum, big_now)
    dev2 = devk.run(*big, big_cum, now=big_now)
    shapes = set(devk._fns)
    assert shapes <= {2, 256, 1024, 4096, 16384, 65536}, shapes
    for a, b in zip(ref2, dev2):
        assert np.array_equal(np.asarray(a), np.asarray(b))
