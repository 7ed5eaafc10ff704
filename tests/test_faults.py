"""Behavioral tests for the deterministic fault-injection subsystem
(shadow_tpu/faults.py): partitions heal inside the RTO budget, unhealed
partitions surface ETIMEDOUT, host crashes kill peer connections without
stranding endpoint state, and — the load-bearing property — a churn-enabled
config produces byte-identical simulations across every scheduler policy
and across the numpy/device loss twins.
"""

import filecmp
from pathlib import Path

import pytest
import yaml

from shadow_tpu.config import parse_config
from shadow_tpu.core.controller import Controller

TWO_NODE = """
general:
  stop_time: 120s
  seed: 3
network:
  graph:
    type: gml
    inline: |
      graph [
        directed 0
        node [ id 0 host_bandwidth_up "100 Mbit" host_bandwidth_down "100 Mbit" ]
        node [ id 1 host_bandwidth_up "20 Mbit" host_bandwidth_down "20 Mbit" ]
        edge [ source 0 target 1 latency "25 ms" ]
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
        args: ["5 MB", "1", serial, "8080", server]
        start_time: 1s
"""


def _run(doc, tag, faults=None, client_env=None, policy="thread_per_core"):
    d = yaml.safe_load(doc) if isinstance(doc, str) else doc
    if faults:
        d["faults"] = yaml.safe_load(faults)
    if client_env:
        d["hosts"]["client"]["processes"][0]["environment"] = client_env
    cfg = parse_config(d, {
        "general.data_directory": f"/tmp/st-faults-{tag}",
        "experimental.scheduler_policy": policy,
    })
    c = Controller(cfg, mirror_log=False)
    return c, c.run()


def _client_elapsed_ms(tag):
    log = Path(f"/tmp/st-faults-{tag}/hosts/client/client.log").read_text()
    return int(log.split("elapsed_ms=")[1].split()[0])


def test_partition_heals_inside_rto_budget():
    """A 3 s mid-stream partition stalls the sender on RTO exponential
    backoff and the transfer completes after the heal: the added delay is
    at least the partition but bounded by partition + the residual
    backoff step — not a connection reset, not a full-ladder timeout."""
    _, clean = _run(TWO_NODE, "clean")
    assert clean["process_errors"] == []
    clean_ms = _client_elapsed_ms("clean")

    c, r = _run(TWO_NODE, "heal", faults="""
events:
  - {time: 2s, kind: link_down, src_nodes: [0], dst_nodes: [1], duration: 3s}
""")
    assert r["process_errors"] == []
    cl = c.processes[1].app
    assert cl.completed == 1 and cl.failed == 0
    assert r["units_blackholed"] > 0  # units emitted into the cut
    assert r["counters"].get("stream_rto_retransmits", 0) > 0
    delta = _client_elapsed_ms("heal") - clean_ms
    assert delta >= 2500, f"no stall observed (delta {delta} ms)"
    # partition 3000 ms + the worst residual backoff step (the ladder sits
    # at ~3.2 s when the heal lands) — anything beyond ~8 s would mean the
    # recovery waited for more than one post-heal RTO
    assert delta < 8000, f"recovery took {delta} ms — more than one RTO"
    for h in c.hosts:
        assert h._conns == {}, h.name


def test_partition_past_max_retries_surfaces_etimedout():
    """An unhealed partition: the sending side exhausts DATA_RETRIES and
    the receiving side's armed idle timeout fires — both ends see
    ETIMEDOUT, and no endpoint is stranded."""
    c, r = _run(TWO_NODE, "cut", faults="""
events:
  - {time: 2s, kind: link_down, src_nodes: [0], dst_nodes: [1]}
""", client_env={"TGEN_IDLE_TIMEOUT_SEC": "5"})
    cl = c.processes[1].app
    assert cl.completed == 0 and cl.failed == 1
    log = Path("/tmp/st-faults-cut/hosts/client/client.log").read_text()
    assert "ETIMEDOUT" in log
    # server side: data retransmission ladder exhausted -> reset
    assert r["counters"].get("stream_resets", 0) >= 2
    assert r["counters"].get("stream_timeouts", 0) >= 2
    for h in c.hosts:
        assert h._conns == {}, h.name


def test_host_crash_kills_peer_connection_no_stranded_conns():
    """Crashing the receiving host mid-transfer: the sender's RTO ladder
    terminates in ETIMEDOUT, the crashed host's sockets were torn down at
    the crash, and neither side strands an endpoint."""
    c, r = _run(TWO_NODE, "crash", faults="""
events:
  - {time: 2s, kind: host_down, hosts: [client]}
""")
    counters = r["counters"]
    assert counters.get("host_crashes", 0) == 1
    assert counters.get("conns_torn_down", 0) >= 1
    # retransmits arriving at the dead NIC are consumed without response
    assert counters.get("units_teardown_dropped", 0) > 0
    assert counters.get("stream_timeouts", 0) == 1  # the server's sender
    for h in c.hosts:
        assert h._conns == {}, h.name


def test_crash_reboot_and_retry_completes():
    """Crash the server mid-response, reboot it 8 s later: the client's
    idle timeout surfaces ETIMEDOUT, the model's reconnect-on-timeout
    retry connects to the respawned server instance, and the transfer
    completes — the full churn-survival path."""
    c, r = _run(TWO_NODE, "reboot", faults="""
events:
  - {time: 2s, kind: host_down, hosts: [server], duration: 8s}
""", client_env={"TGEN_IDLE_TIMEOUT_SEC": "5", "TGEN_RETRIES": "2"})
    assert r["process_errors"] == []
    cl = c.processes[1].app
    assert cl.completed == 1 and cl.failed == 0 and cl.retried >= 1
    counters = r["counters"]
    assert counters.get("host_crashes", 0) == 1
    assert counters.get("host_boots", 0) == 1
    # the reboot respawned a fresh server instance
    assert counters.get("processes_spawned", 0) == 3
    for h in c.hosts:
        assert h._conns == {}, h.name


def test_link_degrade_adds_loss_and_restores():
    """A degrade window (loss_add) makes units drop where the clean run
    drops none; the window restores and the transfer still completes."""
    _, clean = _run(TWO_NODE, "deg-clean")
    assert clean["units_dropped"] == 0
    c, r = _run(TWO_NODE, "deg", faults="""
events:
  - {time: 1500 ms, kind: link_degrade, src_nodes: [0], dst_nodes: [1],
     latency_factor: 2.0, loss_add: 0.2, duration: 2s}
""")
    assert r["process_errors"] == []
    assert r["units_dropped"] > 0
    assert c.processes[1].app.completed == 1
    assert r["fault_transitions_applied"] == 2  # degrade + restore


def test_overlapping_same_time_degrades_restore_cleanly():
    """Two degrade windows opening at the same instant with multi-node
    sets: the earlier-expiring one must remove ITSELF from the active
    list (identity, not dataclass equality over ndarray fields — a
    generated __eq__ raised 'ambiguous truth value' here)."""
    c, r = _run(TWO_NODE, "deg-pair", faults="""
events:
  - {time: 1s, kind: link_degrade, src_nodes: [0, 1], dst_nodes: [0, 1],
     loss_add: 0.01, duration: 3s}
  - {time: 1s, kind: link_degrade, src_nodes: [0, 1], dst_nodes: [0, 1],
     latency_factor: 1.2, duration: 2s}
""")
    assert r["process_errors"] == []
    assert r["fault_transitions_applied"] == 4
    assert c.processes[1].app.completed == 1


def test_same_round_reboot_then_crash_cancels_respawn():
    """Churn's minimum-1ns downtime draws can land a host_up and the next
    host_down on the same round start; the crash must cancel the pending
    BAND_FAULT respawn or the process would boot on a down host (and the
    next reboot would skip it as already-running)."""
    cfg = parse_config(yaml.safe_load(TWO_NODE), {
        "general.data_directory": "/tmp/st-faults-updown"})
    c = Controller(cfg, mirror_log=False)
    h = c.hosts[0]
    h.crash(0)          # kills the initial spawn event too
    assert len(h.equeue) == 0
    h.reboot(1000)      # schedules the respawn (BAND_FAULT)
    h.crash(1000)       # same round: the respawn must die with the host
    assert len(h.equeue) == 0
    h.reboot(2000)      # a later reboot still respawns normally
    assert len(h.equeue) == 1


CHURN_DOC = """
general:
  stop_time: 30s
  seed: 11
network:
  graph:
    type: gml
    inline: |
      graph [
        directed 0
        node [ id 0 host_bandwidth_up "50 Mbit" host_bandwidth_down "50 Mbit" ]
        node [ id 1 host_bandwidth_up "50 Mbit" host_bandwidth_down "50 Mbit" ]
        edge [ source 0 target 1 latency "30 ms" packet_loss 0.01 ]
        edge [ source 0 target 0 latency "5 ms" ]
        edge [ source 1 target 1 latency "5 ms" ]
      ]
hosts:
  node0_:
    network_node_id: 0
    quantity: 12
    processes:
      - path: pyapp:shadow_tpu.models.gossip:GossipNode
        args: ["7000", "24", "4", "2", "3.0"]
        environment: {GOSSIP_REANNOUNCE_SEC: "4"}
  node1_:
    network_node_id: 1
    quantity: 12
    processes:
      - path: pyapp:shadow_tpu.models.gossip:GossipNode
        args: ["7000", "24", "4", "0", "3.0"]
faults:
  events:
    - {time: 5s, kind: link_down, src_nodes: [0], dst_nodes: [1], duration: 6s}
    - {time: 14s, kind: link_degrade, src_nodes: [0], dst_nodes: [1],
       latency_factor: 2.5, loss_add: 0.05, bandwidth_scale: 0.5, duration: 5s}
  churn:
    - {hosts: ["node1_*"], mean_uptime: 8s, mean_downtime: 2s, start_time: 3s}
"""

EQ_KEYS = ("sim_seconds", "rounds", "events", "units_sent", "units_dropped",
           "units_blackholed", "bytes_sent", "counters",
           "fault_transitions_applied")


def test_churn_byte_identical_across_policies_and_loss_twins():
    """THE determinism gate for faults: the same churn-enabled config under
    thread_per_core, thread_per_host, tpu_batch (C engine ON — the
    default), tpu_batch with the C engine forced off (pure-Python columnar
    twin), and tpu_batch with the device draw kernel forced on
    (numpy/device twins) produces identical summaries and byte-identical
    host output trees."""
    runs = {}
    for policy, tag, over in (
            ("thread_per_core", "det-tpc", None),
            ("thread_per_host", "det-tph", None),
            ("tpu_batch", "det-tpu", None),
            ("tpu_batch", "det-pyc",
             {"experimental.native_colcore": False}),
            ("tpu_batch", "det-dev",
             {"experimental.tpu_device_floor": 1})):
        d = yaml.safe_load(CHURN_DOC)
        cfg = parse_config(d, {
            "general.data_directory": f"/tmp/st-faults-{tag}",
            "experimental.scheduler_policy": policy,
            **(over or {}),
        })
        ctl = Controller(cfg, mirror_log=False)
        if tag in ("det-tpu", "det-dev"):
            # the point of this PR: faults no longer disable the C engine
            assert ctl.engine._c is not None, tag
        elif tag == "det-pyc":
            assert getattr(ctl.engine, "_c", None) is None
        runs[tag] = ctl.run()
    ref = runs["det-tpc"]
    assert ref["counters"].get("host_crashes", 0) > 0  # churn actually ran
    assert ref["units_blackholed"] > 0  # the partition actually cut traffic
    for tag in ("det-tph", "det-tpu", "det-pyc", "det-dev"):
        for k in EQ_KEYS:
            assert runs[tag][k] == ref[k], (tag, k, runs[tag][k], ref[k])
        cmp = filecmp.dircmp("/tmp/st-faults-det-tpc/hosts",
                             f"/tmp/st-faults-{tag}/hosts")
        assert not cmp.diff_files and not cmp.left_only \
            and not cmp.right_only, (tag, cmp.diff_files)


def test_twice_run_byte_identical():
    """Same seed, same churn config, run twice: identical event streams."""
    out = []
    for tag in ("rep-a", "rep-b"):
        cfg = parse_config(yaml.safe_load(CHURN_DOC), {
            "general.data_directory": f"/tmp/st-faults-{tag}"})
        out.append(Controller(cfg, mirror_log=False).run())
    for k in EQ_KEYS:
        assert out[0][k] == out[1][k], k


# -- faults ON the C engine (PR 6) ------------------------------------------

def test_stream_faults_c_engine_byte_identical():
    """C-on fault matrix for the stream scenarios: a healing partition
    (blackhole accounting + RTO recovery) and a crash/reboot cycle (CHost
    teardown + idle timeout + reconnect) produce byte-identical trees and
    summaries with the C engine on vs the Python planes — including the
    fault-accounting counters the CHost teardown deltas feed
    (units_teardown_dropped, units_blackholed, conns_torn_down,
    stream_timeouts, stream_rto_retransmits)."""
    cases = {
        "heal": ("""
events:
  - {time: 2s, kind: link_down, src_nodes: [0], dst_nodes: [1], duration: 3s}
""", None),
        "reboot": ("""
events:
  - {time: 2s, kind: host_down, hosts: [server], duration: 8s}
""", {"TGEN_IDLE_TIMEOUT_SEC": "5", "TGEN_RETRIES": "2"}),
    }
    for name, (faults, env) in cases.items():
        ref_ctl, ref = _run(TWO_NODE, f"cmat-{name}-tpc", faults=faults,
                            client_env=env)
        c_ctl, got = _run(TWO_NODE, f"cmat-{name}-c", faults=faults,
                          client_env=env, policy="tpu_batch")
        assert c_ctl.engine._c is not None
        for k in EQ_KEYS:
            assert got[k] == ref[k], (name, k, got[k], ref[k])
        cmp = filecmp.dircmp(f"/tmp/st-faults-cmat-{name}-tpc/hosts",
                             f"/tmp/st-faults-cmat-{name}-c/hosts")
        assert not cmp.diff_files and not cmp.left_only \
            and not cmp.right_only, (name, cmp.diff_files)
        if name == "reboot":
            # the crash/reboot accounting crossed the C plane: the C-side
            # teardown deltas must reproduce the Python twin's numbers
            for k in ("units_teardown_dropped", "conns_torn_down",
                      "host_crashes", "host_boots", "stream_timeouts"):
                assert got["counters"].get(k) == ref["counters"].get(k), k
            assert got["counters"].get("units_teardown_dropped", 0) > 0


def test_churn_checkpoint_resume_digest_c_engine():
    """Satellite gate: checkpoint/resume mid-churn with the C engine ON.
    The checkpointing run's tree and digest stream equal the
    uninterrupted C-off run's (fast AND robust, not fast OR robust);
    resuming from a mid-churn checkpoint reproduces the uninterrupted
    output tree and continues the digest stream bit-exactly."""
    import hashlib
    import shutil

    from shadow_tpu import checkpoint as ckpt

    for tag in ("ckc-full", "ckc-py", "ckc-src", "ckc-res"):
        # resumed runs APPEND to state_digests.jsonl by design (the
        # continuation of one stream); a stale file from a previous test
        # invocation would concatenate and break the suffix compare
        shutil.rmtree(f"/tmp/st-faults-{tag}", ignore_errors=True)

    def tree(tag):
        out = {}
        base = Path(f"/tmp/st-faults-{tag}")
        for p in sorted((base / "hosts").rglob("*")):
            if p.is_file():
                out[str(p.relative_to(base))] = hashlib.sha256(
                    p.read_bytes()).hexdigest()
        assert out
        return out

    over = {"general.state_digest_every": 50}
    # uninterrupted reference runs: C on and C off (Python columnar twin)
    cfg = parse_config(yaml.safe_load(CHURN_DOC), {
        "general.data_directory": "/tmp/st-faults-ckc-full",
        "experimental.scheduler_policy": "tpu_batch", **over})
    ctl = Controller(cfg, mirror_log=False)
    assert ctl.engine._c is not None
    ctl.run()
    full_tree = tree("ckc-full")
    full_digests = Path(
        "/tmp/st-faults-ckc-full/state_digests.jsonl").read_bytes()
    assert full_digests.count(b"\n") >= 3

    cfg = parse_config(yaml.safe_load(CHURN_DOC), {
        "general.data_directory": "/tmp/st-faults-ckc-py",
        "experimental.scheduler_policy": "tpu_batch",
        "experimental.native_colcore": False, **over})
    Controller(cfg, mirror_log=False).run()
    assert Path("/tmp/st-faults-ckc-py/state_digests.jsonl").read_bytes() \
        == full_digests
    assert tree("ckc-py") == full_tree

    # checkpointing run (C on): transparent, and its checkpoints carry
    # the colcore ABI fingerprint
    cfg = parse_config(yaml.safe_load(CHURN_DOC), {
        "general.data_directory": "/tmp/st-faults-ckc-src",
        "experimental.scheduler_policy": "tpu_batch",
        "general.checkpoint_every": "8s", **over})
    Controller(cfg, mirror_log=False).run()
    assert tree("ckc-src") == full_tree
    paths = sorted(Path("/tmp/st-faults-ckc-src/checkpoints").glob("*.ckpt"))
    assert paths
    from shadow_tpu.native import _colcore
    assert ckpt.read_header(paths[0])["colcore"] == _colcore.ABI

    # resume from a mid-churn checkpoint: the churn timeline has already
    # downed/rebooted hosts by 8s (mean_uptime 8s from t=3s)
    cfg = parse_config(yaml.safe_load(CHURN_DOC), {
        "general.data_directory": "/tmp/st-faults-ckc-res",
        "experimental.scheduler_policy": "tpu_batch",
        "general.checkpoint_every": "8s", **over})
    ctl, resume_at = ckpt.load_checkpoint(paths[0], cfg, mirror_log=False)
    assert ctl.engine._c is not None  # the C core was rebuilt on resume
    assert ctl.faults is not None and ctl.faults.applied > 0
    ctl.run(resume_at=resume_at)
    assert tree("ckc-res") == full_tree
    res_digests = Path(
        "/tmp/st-faults-ckc-res/state_digests.jsonl").read_bytes()
    assert res_digests and full_digests.endswith(res_digests)


def test_c_checkpoint_refuses_python_plane_resume():
    """A checkpoint carrying C-engine state names the problem when the
    resume disables the C engine (instead of diverging or crashing deep
    in the run). Self-contained: writes its own C-state checkpoint."""
    import shutil

    import pytest as _pytest

    from shadow_tpu import checkpoint as ckpt
    from shadow_tpu.native import _colcore

    shutil.rmtree("/tmp/st-faults-refuse-src", ignore_errors=True)
    d = yaml.safe_load(TWO_NODE)
    d["general"]["stop_time"] = "12s"
    cfg = parse_config(d, {
        "general.data_directory": "/tmp/st-faults-refuse-src",
        "experimental.scheduler_policy": "tpu_batch",
        "general.checkpoint_every": "1s"})
    ctl = Controller(cfg, mirror_log=False)
    assert ctl.engine._c is not None
    ctl.run()
    paths = sorted(
        Path("/tmp/st-faults-refuse-src/checkpoints").glob("*.ckpt"))
    assert paths
    assert ckpt.read_header(paths[0])["colcore"] == _colcore.ABI
    d2 = yaml.safe_load(TWO_NODE)
    d2["general"]["stop_time"] = "12s"
    cfg = parse_config(d2, {
        "general.data_directory": "/tmp/st-faults-refuse-res",
        "experimental.scheduler_policy": "tpu_batch",
        "experimental.native_colcore": False,
        "general.checkpoint_every": "1s"})
    with _pytest.raises(ckpt.CheckpointError, match="C-engine state"):
        ckpt.load_checkpoint(paths[0], cfg, mirror_log=False)


# -- schema / validation ----------------------------------------------------

def _parse(doc_update):
    d = yaml.safe_load(TWO_NODE)
    d.update(doc_update)
    return parse_config(d, {})


def test_schema_rejects_bad_fault_configs():
    with pytest.raises(ValueError, match="kind must be one of"):
        _parse({"faults": {"events": [
            {"time": "1s", "kind": "meteor_strike", "hosts": ["server"]}]}})
    with pytest.raises(ValueError, match="needs src_nodes"):
        _parse({"faults": {"events": [{"time": "1s", "kind": "link_down"}]}})
    with pytest.raises(ValueError, match="needs hosts"):
        _parse({"faults": {"events": [{"time": "1s", "kind": "host_down"}]}})
    with pytest.raises(ValueError, match="latency_factor"):
        _parse({"faults": {"events": [
            {"time": "1s", "kind": "link_degrade", "src_nodes": [0],
             "latency_factor": 0.5}]}})
    with pytest.raises(ValueError, match="does not take a duration"):
        _parse({"faults": {"events": [
            {"time": "1s", "kind": "link_up", "src_nodes": [0],
             "duration": "1s"}]}})
    with pytest.raises(ValueError, match="present but empty"):
        _parse({"faults": {}})


def test_removed_loss_models_rejected():
    """Both retired loss models — the engine-notification oracle
    (COMPONENTS.md #13) and the PR-9-replaced one-retransmit-per-RTT
    dupack model — are config errors now: old configs fail loudly
    instead of silently changing semantics."""
    with pytest.raises(ValueError, match="sack"):
        _parse({"experimental": {"stream_loss_recovery": "oracle"}})
    with pytest.raises(ValueError, match="SACK-style"):
        _parse({"experimental": {"stream_loss_recovery": "dupack"}})
    cfg = _parse({"experimental": {"stream_loss_recovery": "sack"}})
    assert cfg.experimental.stream_loss_recovery == "sack"
    # congestion-control knob: valid names parse, unknown names are named
    cfg = _parse({"experimental": {"congestion_control": "cubic"}})
    assert cfg.experimental.congestion_control == "cubic"
    with pytest.raises(ValueError, match="congestion_control"):
        _parse({"experimental": {"congestion_control": "bbr2"}})


def test_removed_device_transport_rejected():
    """The retired columnar transport toggle: asking for it is a config
    error by name, not a silently ignored experimental key."""
    with pytest.raises(ValueError, match="device_transport was removed"):
        _parse({"experimental": {"device_transport": True}})


def test_device_transport_false_still_parses():
    """Configs that spelled out the old default keep parsing, and the
    parsed config carries no trace of the key: same fields, same
    checkpoint config digest as a config that never named it."""
    from shadow_tpu.checkpoint import config_digest

    cfg = _parse({"experimental": {"device_transport": False}})
    assert not hasattr(cfg.experimental, "device_transport")
    assert config_digest(cfg) == config_digest(_parse({}))


def test_unknown_host_and_node_fail_at_build():
    d = yaml.safe_load(TWO_NODE)
    d["faults"] = {"events": [
        {"time": "1s", "kind": "host_down", "hosts": ["nope"]}]}
    cfg = parse_config(d, {"general.data_directory": "/tmp/st-faults-bad"})
    with pytest.raises(ValueError, match="unknown host"):
        Controller(cfg, mirror_log=False)
    d["faults"] = {"events": [
        {"time": "1s", "kind": "link_down", "src_nodes": [99]}]}
    cfg = parse_config(d, {"general.data_directory": "/tmp/st-faults-bad"})
    with pytest.raises(ValueError, match="not in graph"):
        Controller(cfg, mirror_log=False)


def test_committed_fault_examples_parse():
    from shadow_tpu.config import load_config

    root = Path(__file__).resolve().parent.parent
    for name in ("gossip_churn.yaml", "partition_heal.yaml"):
        cfg = load_config(str(root / "examples" / name))
        assert cfg.faults is not None and (cfg.faults.events
                                           or cfg.faults.churn), name
