"""Modern-web workload family (PR 9): web/CDN + DNS + ABR models.

The same gates tor cleared when it joined the roster: byte-identity
across scheduler policies AND the C engine on/off (output trees,
flows.jsonl, digest streams), checkpoint/resume mid-run reproducing the
uninterrupted hashes, and — new for this family — the fleet reducer
pooling the new flow groups' histograms with CI95 across seeds.
"""

import hashlib
import json
from pathlib import Path

import pytest
import yaml

from shadow_tpu.config import parse_config
from shadow_tpu.core.controller import Controller

#: a scaled-down web_cdn: origin + edges + DNS chain + resolver + page
#: clients + an ABR session, under a partition AND a lossy degrade
#: window — every model, every fault interaction, in a couple of sim
#: minutes of events
CFG = """
general:
  stop_time: 25s
  seed: 21
network:
  graph:
    type: gml
    inline: |
      graph [
        directed 0
        node [ id 0 host_bandwidth_up "100 Mbit" host_bandwidth_down "100 Mbit" ]
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
faults:
  events:
    - {time: 6s, kind: link_down, src_nodes: [0], dst_nodes: [2],
       duration: 3s}
    - {time: 12s, kind: link_degrade, src_nodes: [0], dst_nodes: [1, 2],
       loss_add: 0.04, latency_factor: 1.5, duration: 6s}
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
        environment: {DNS_TTL_SEC: "8"}
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
    quantity: 4
    processes:
      - path: pyapp:shadow_tpu.models.web:WebClient
        args: ["3", "3", "120 kB", "30 kB", "80", resolver0, edge0, edge1]
        start_time: 500 ms
        environment: {WEB_RETRIES: "2", WEB_THINK_SEC: "1"}
  c1_:
    network_node_id: 2
    quantity: 4
    processes:
      - path: pyapp:shadow_tpu.models.web:WebClient
        args: ["3", "3", "120 kB", "30 kB", "80", resolver0, edge0, edge1]
        start_time: 900 ms
        environment: {WEB_RETRIES: "2", WEB_THINK_SEC: "1"}
  video0:
    network_node_id: 0
    processes:
      - path: pyapp:shadow_tpu.models.abr:AbrServer
        args: ["8081"]
  viewer0:
    network_node_id: 2
    processes:
      - path: pyapp:shadow_tpu.models.abr:AbrClient
        args: [video0, "8081", "9", "2000", "400000", "1000000",
               "2500000", "5000000"]
        start_time: 1s
        environment: {ABR_RETRIES: "3"}
"""


def _tree(d: str) -> dict:
    out = {}
    for p in sorted(Path(d).glob("hosts/**/*")):
        if p.is_file():
            out[str(p.relative_to(d))] = hashlib.sha256(
                p.read_bytes()).hexdigest()
    for name in ("flows.jsonl", "metrics.jsonl", "state_digests.jsonl"):
        p = Path(d) / name
        if p.exists():
            out[name] = hashlib.sha256(p.read_bytes()).hexdigest()
    return out


def _run(tag, overrides=None):
    import shutil

    d = f"/tmp/st-web-{tag}"
    shutil.rmtree(d, ignore_errors=True)
    cfg = parse_config(yaml.safe_load(CFG), {
        "general.data_directory": d,
        "general.state_digest_every": 100,
        **(overrides or {}),
    })
    c = Controller(cfg, mirror_log=False)
    r = c.run()
    return c, r, _tree(d)


def test_identity_across_policies_and_planes():
    """THE family acceptance gate: all three models byte-identical
    across thread_per_core/thread_per_host/tpu_batch and C on/off —
    trees, flows.jsonl, metrics.jsonl, digest streams."""
    runs = {}
    for tag, ov in {
        "tpc": {"experimental.scheduler_policy": "thread_per_core"},
        "tph": {"experimental.scheduler_policy": "thread_per_host"},
        "tpu-c": {"experimental.scheduler_policy": "tpu_batch",
                  "experimental.native_colcore": True},
        "tpu-py": {"experimental.scheduler_policy": "tpu_batch",
                   "experimental.native_colcore": False},
    }.items():
        runs[tag] = _run(tag, ov)
    base = runs["tpc"][2]
    assert base, "empty output tree"
    for tag in ("tph", "tpu-c", "tpu-py"):
        assert runs[tag][2] == base, f"{tag} diverged from thread_per_core"
    # the run actually exercised the family: all four flow groups + the
    # ABR quality/stall roll-up are live in the summary
    r = runs["tpu-c"][1]
    flows = r["telemetry"]["flows"]
    for kind in ("web.fetch", "web.origin", "dns.resolve", "abr.segment"):
        assert flows.get(kind, {}).get("count", 0) > 0, (kind, flows)
    assert flows["abr.segment"]["x_mean"] > 0  # mean selected rate
    assert r["counters"].get("abr_segments", 0) > 0


def test_checkpoint_resume_reproduces_uninterrupted_hashes():
    """Mid-run checkpoint/resume with the C engine on: the resumed run
    reproduces the uninterrupted run's host trees, telemetry summary,
    and digest-stream suffix (new model state — DNS caches/pending,
    page fan-out closures, ABR session state — and the new CEp SACK/CC
    fields all ride the pickler + C _export_state). Streams on a fresh
    resume directory contain only the post-resume suffix — the
    established checkpoint contract (tests/test_checkpoint.py)."""
    import shutil

    shutil.rmtree("/tmp/st-web-ckpts", ignore_errors=True)
    shutil.rmtree("/tmp/st-web-resume", ignore_errors=True)
    _c, r_full, full = _run("ckpt-full", {
        "experimental.scheduler_policy": "tpu_batch"})
    _run("ckpt-src", {
        "experimental.scheduler_policy": "tpu_batch",
        "general.checkpoint_every": "8s",
        "general.checkpoint_dir": "/tmp/st-web-ckpts",
    })
    cks = sorted(Path("/tmp/st-web-ckpts").glob("ckpt_*.ckpt"))
    assert cks, "no checkpoint written"
    d = "/tmp/st-web-resume"
    cfg = parse_config(yaml.safe_load(CFG), {
        "general.data_directory": d,
        "general.state_digest_every": 100,
        "experimental.scheduler_policy": "tpu_batch",
    })
    from shadow_tpu.checkpoint import load_checkpoint

    ctl, resume_at = load_checkpoint(str(cks[0]), cfg, mirror_log=False)
    r_res = ctl.run(resume_at=resume_at)
    resumed = _tree(d)
    # host logs are complete state (log lines ride the pickle): the
    # whole hosts/ tree must match the uninterrupted run byte-for-byte
    full_hosts = {k: v for k, v in full.items() if k.startswith("hosts")}
    res_hosts = {k: v for k, v in resumed.items()
                 if k.startswith("hosts")}
    assert res_hosts == full_hosts, "resumed host tree diverged"
    # the collector state rode the pickle: the summary roll-up (flow
    # percentiles, ABR quality/stall) matches exactly
    assert r_res["telemetry"]["flows"] == r_full["telemetry"]["flows"]
    # the resumed digest stream is a suffix of the uninterrupted one
    full_dig = (Path("/tmp/st-web-ckpt-full") /
                "state_digests.jsonl").read_text()
    res_dig = (Path(d) / "state_digests.jsonl").read_text()
    assert res_dig and full_dig.endswith(res_dig), \
        "resumed digest stream is not a suffix of the uninterrupted one"


#: checkpoint.config_digest(CFG), recorded from a build whose config
#: schema still had the retired columnar-transport key: a checkpoint
#: written then must still pass load's config-digest check
CFG_DIGEST = "bbe6ab9ce1507a38c83472b8e273e0854f0fd54b329052c35c8f8a90c9046263"


def test_config_digest_matches_older_checkpoints():
    from shadow_tpu.checkpoint import config_digest

    assert config_digest(parse_config(yaml.safe_load(CFG), {})) == CFG_DIGEST


def test_summary_quality_stall_rollup_deterministic():
    """The ABR quality/stall summary (x_mean + abr counters) is
    deterministic run-to-run."""
    _c1, r1, t1 = _run("sum-a")
    _c2, r2, t2 = _run("sum-b")
    assert t1 == t2
    f1 = r1["telemetry"]["flows"]
    f2 = r2["telemetry"]["flows"]
    assert f1["abr.segment"] == f2["abr.segment"]
    for k in ("abr_segments", "abr_rate_sum_bps"):
        assert r1["counters"].get(k) == r2["counters"].get(k)


def test_metrics_report_renders_new_groups_and_abr_rows():
    import sys

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent
                           / "tools"))
    import metrics_report

    d = Path("/tmp/st-web-report")
    _run("report")
    d = Path("/tmp/st-web-report")
    rep = metrics_report.build_report(d / "metrics.jsonl",
                                      d / "flows.jsonl")
    flows_seen = {row["flow"] for row in rep["flow_percentiles"]}
    assert {"web.fetch", "web.origin", "dns.resolve",
            "abr.segment"} <= flows_seen, flows_seen
    assert rep["abr"], "no ABR rows in the report"
    row = rep["abr"][0]
    assert row["segments"] > 0 and row["mean_rate_bps"] > 0
    assert "stall_s" in row


DEAD_ORIGIN_CFG = """
general:
  stop_time: 30s
  seed: 7
network:
  graph:
    type: gml
    inline: |
      graph [
        directed 0
        node [ id 0 host_bandwidth_up "100 Mbit" host_bandwidth_down "100 Mbit" ]
        edge [ source 0 target 0 latency "5 ms" ]
      ]
telemetry: {}
faults:
  events:
    - {time: 100 ms, kind: host_down, hosts: [origin0], duration: 29s}
hosts:
  origin0:
    network_node_id: 0
    processes:
      - path: pyapp:shadow_tpu.models.web:WebOrigin
        args: ["80"]
  edge0:
    network_node_id: 0
    processes:
      - path: pyapp:shadow_tpu.models.web:WebEdge
        args: ["80", origin0, "80", "0"]
  dns0:
    network_node_id: 0
    processes:
      - path: pyapp:shadow_tpu.models.dns:DnsAuth
        args: ["53"]
  client0:
    network_node_id: 0
    processes:
      - path: pyapp:shadow_tpu.models.web:WebClient
        args: ["2", "1", "40 kB", "10 kB", "80", dns0, edge0]
        start_time: 500 ms
        environment: {WEB_THINK_SEC: "0"}
"""


def test_dead_origin_cannot_strand_the_page_loop():
    """Regression: with every object a cache miss (hit_pct 0) and the
    origin down for the whole run, the edge's terminal origin failure
    closes the client connection and the client's on_close counts the
    object failed — the page loop finishes every page instead of
    stalling forever on a never-completing fetch."""
    import shutil

    d = "/tmp/st-web-deadorigin"
    shutil.rmtree(d, ignore_errors=True)
    cfg = parse_config(yaml.safe_load(DEAD_ORIGIN_CFG),
                       {"general.data_directory": d})
    c = Controller(cfg, mirror_log=False)
    r = c.run()
    log = (Path(d) / "hosts" / "client0" / "client0.log").read_text()
    assert "web client done: pages=2" in log, log
    assert "objects_failed=" in log and "objects_failed=0" not in log, log
    flows = r["telemetry"]["flows"].get("web.fetch", {})
    assert flows.get("count", 0) > 0
    assert flows.get("failed", flows.get("count")) > 0 or \
        flows["count"] > flows.get("ok", 0)


def test_model_registry_rejects_typoed_model_paths():
    """config/schema.py MODEL_REGISTRY: a typo'd in-tree model path
    fails at config parse with the roster, not at spawn time mid-build;
    paths outside the shadow_tpu.models namespace stay unvalidated."""
    base = {"general": {"stop_time": "1s"},
            "network": {"graph": {"type": "1_gbit_switch"}}}
    with pytest.raises(ValueError, match="registered:"):
        parse_config({**base, "hosts": {"a": {"processes": [
            {"path": "pyapp:shadow_tpu.models.wbe:WebOrigin"}]}}})
    # external pyapp namespaces are not gated
    cfg = parse_config({**base, "hosts": {"a": {"processes": [
        {"path": "pyapp:my.custom.module:App"}]}}})
    assert cfg.hosts[0].processes[0].path == "pyapp:my.custom.module:App"


@pytest.mark.slow
def test_fleet_sweep_pools_web_flow_groups_with_ci95(tmp_path):
    """Satellite gate: a 3-seed fleet sweep over the committed
    examples/web_cdn.yaml pools the new flow groups' histograms and
    emits t-based CI95 rows for them."""
    from shadow_tpu import fleet

    sweep_dir = tmp_path / "sweep"
    rc = fleet.main([
        "sweep", str(Path(__file__).resolve().parent.parent
                     / "examples" / "web_cdn.yaml"),
        "--seeds", "3", "--seed-base", "300", "--jobs", "2",
        "--sweep-dir", str(sweep_dir),
        "--set", "general.stop_time=12s",
        "--quiet",
    ])
    assert rc == 0
    doc = json.loads((sweep_dir / fleet.SWEEP_SUMMARY).read_text())
    assert doc["completed"] == [300, 301, 302], doc.get("failed")
    for kind in ("web.fetch", "dns.resolve"):
        row = doc["flows"].get(kind)
        assert row is not None, (kind, sorted(doc["flows"]))
        assert row["count"] > 0
        ci = row["ci95"]["p50_ms"]
        assert ci["n"] == 3 and ci["lo"] <= ci["mean"] <= ci["hi"], ci
        assert set(row["pooled"]) >= {"p50_ms", "p99_ms"}
