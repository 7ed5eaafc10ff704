"""C tor control-plane identity gates (PR 5).

The tor hot path is columnar end-to-end now: the C TorSink runs the
circuit-build (telescoping) state machine and the BEGIN/fetch scheduling
natively, and relays/exits ride the C relay data path. These gates pin
the whole tor surface: output trees, telemetry streams (flows.jsonl /
metrics.jsonl), and the determinism-sentinel digest stream must be
byte-identical across scheduler policies, with the C engine (and its tor
control plane) on or off, and across a checkpoint/resume taken
mid-circuit-build.
"""

from pathlib import Path

import yaml

from shadow_tpu import checkpoint as ckpt
from shadow_tpu.config import parse_config
from shadow_tpu.core.controller import Controller

from tests.test_checkpoint import _strip, _tree

TOR_CFG = """
general:
  stop_time: 30s
  seed: 12
network:
  graph:
    type: gml
    inline: |
      graph [
        directed 0
        node [ id 0 host_bandwidth_up "1 Gbit" host_bandwidth_down "1 Gbit" ]
        node [ id 1 host_bandwidth_up "100 Mbit" host_bandwidth_down "100 Mbit" ]
        node [ id 2 host_bandwidth_up "50 Mbit" host_bandwidth_down "50 Mbit" ]
        edge [ source 0 target 1 latency "20 ms" packet_loss 0.004 ]
        edge [ source 0 target 2 latency "40 ms" ]
        edge [ source 1 target 2 latency "30 ms" packet_loss 0.004 ]
        edge [ source 0 target 0 latency "5 ms" ]
        edge [ source 1 target 1 latency "5 ms" ]
        edge [ source 2 target 2 latency "5 ms" ]
      ]
hosts:
  relay:
    network_node_id: 1
    quantity: 6
    processes:
      - path: pyapp:shadow_tpu.models.tor:TorExit
        args: ["9001"]
  web:
    network_node_id: 0
    processes:
      - path: pyapp:shadow_tpu.models.tgen:TGenServer
        args: ["80"]
  user:
    network_node_id: 2
    quantity: 4
    processes:
      - path: pyapp:shadow_tpu.models.tor:TorClient
        args: ["6", "9001", web, "80", "150 kB", "2"]
        start_time: 1s
        expected_final_state: {exited: 0}
"""


def _run(tmp_path, tag, text=TOR_CFG, **overrides):
    dd = tmp_path / tag
    ov = {"general.data_directory": str(dd), "telemetry": {}}
    ov.update(overrides)
    cfg = parse_config(yaml.safe_load(text), ov)
    ctl = Controller(cfg, mirror_log=False)
    summary = ctl.run()
    assert summary["process_errors"] == []
    streams = {
        f: (dd / f).read_bytes()
        for f in ("flows.jsonl", "metrics.jsonl")
        if (dd / f).exists()
    }
    assert "flows.jsonl" in streams, "telemetry produced no flow stream"
    return ctl, _strip(summary), _tree(dd), streams


#: the same network with bandwidth-weighted paths (Guard/Exit flags on
#: relay0..relay5) and a schedule of three sequential fetches per client
SCHEDULE_CFG = TOR_CFG.replace(
    'args: ["6", "9001", web, "80", "150 kB", "2"]',
    'args: ["6", "9001", web, "80", "0", "3", "0", '
    '"5000:GE,20000:G,8000:E,12000:GE,3000:,9000:E", '
    '"delay=uniform:0:2000;size=lognormal:20000:1.0:1024:200000;'
    'think=uniform:500:1500"]')


def test_tor_identity_across_policies_and_planes(tmp_path):
    """Output trees, summaries, and telemetry streams byte-identical with
    the C tor control plane on vs off and across all scheduler policies.
    The C-sink run must actually have exercised the C control plane
    (guards against a silent fallback making this test vacuous)."""
    _identity_across_policies_and_planes(tmp_path, TOR_CFG)


def test_tor_schedule_identity_across_policies_and_planes(tmp_path):
    """The same gate for weighted paths and fetch schedules: every
    client's per-fetch records are identical too."""
    records = _identity_across_policies_and_planes(tmp_path, SCHEDULE_CFG)
    for rec in records[1:]:
        assert rec == records[0]
    assert all(len(f) == 3 and all(r[4] == r[3] for r in f)
               for f in records[0])


def test_tor_schedule_cut_at_stop_identical(tmp_path):
    """A stop that cuts fetches: each open fetch's record as it stands
    (DATA bytes counted so far, done_ns -1) is identical on every plane,
    the C sink's count included."""
    text = SCHEDULE_CFG.replace("stop_time: 30s", "stop_time: 4s")
    text = text.replace("        expected_final_state: {exited: 0}\n", "")
    text = text.replace("size=lognormal:20000:1.0:1024:200000",
                        "size=lognormal:2000000:0.5:500000:4000000")
    records = _identity_across_policies_and_planes(tmp_path, text)
    for rec in records[1:]:
        assert rec == records[0]
    cut = [r for f in records[0] for r in f if r[6] == -1]
    assert any(0 < r[4] < r[3] for r in cut), records[0]


def _identity_across_policies_and_planes(tmp_path, text):
    """Run ``text`` under every plane and policy; the clients' per-fetch
    records of each run."""
    ctl_c, s_c, t_c, f_c = _run(
        tmp_path, "c", text, **{"experimental.scheduler_policy": "tpu_batch",
                                "experimental.native_colcore": True})
    # the C control plane really ran: the model's engagement gate is
    # exactly (core exposes tor_client_sink) and (host.pcap is None) —
    # assert both so a silent fallback to the Python closures cannot
    # make this cross-plane comparison Python-vs-Python
    core = ctl_c.engine._c
    assert core is not None and hasattr(core, "tor_client_sink")
    assert all(h.pcap is None for h in ctl_c.hosts)
    clients = [p.app for h in ctl_c.hosts for p in h.processes
               if type(p.app).__name__ == "TorClient"]
    assert clients, "no tor clients found"

    runs = [
        _run(tmp_path, "py", text,
             **{"experimental.scheduler_policy": "tpu_batch",
                "experimental.native_colcore": False}),
        _run(tmp_path, "tpc", text,
             **{"experimental.scheduler_policy": "thread_per_core"}),
        _run(tmp_path, "tph", text,
             **{"experimental.scheduler_policy": "thread_per_host"}),
    ]
    for _ctl, s, t, f in runs:
        assert s == s_c
        assert t == t_c
        assert f == f_c
    return [[p.app.fetch_records() for h in ctl.hosts for p in h.processes
             if type(p.app).__name__ == "TorClient"]
            for ctl, *_ in [(ctl_c,), *runs]]


def test_tor_digest_stream_identical_across_policies(tmp_path):
    """The determinism-sentinel digest stream on a tor config is
    policy-independent — including tpu_batch with the C engine (and its
    tor control plane) attached: the digest walk reads plane-independent
    observables the C endpoint twin exposes via fingerprint()."""
    streams = {}
    for pol in ("tpu_batch", "thread_per_core", "thread_per_host"):
        dd = tmp_path / f"dig-{pol}"
        cfg = parse_config(yaml.safe_load(TOR_CFG), {
            "general.data_directory": str(dd),
            "general.state_digest_every": 25,
            "experimental.scheduler_policy": pol,
        })
        summary = Controller(cfg, mirror_log=False).run()
        assert summary["process_errors"] == []
        streams[pol] = (dd / ckpt.DIGEST_FILE).read_bytes()
        assert streams[pol], pol
    vals = list(streams.values())
    assert vals[0] == vals[1] == vals[2]


def test_tor_checkpoint_resume_mid_circuit_build(tmp_path):
    """A checkpoint that lands while circuits are still telescoping must
    resume to the exact uninterrupted output tree. The snapshot is
    verified to really be mid-circuit-build (some client has attempted
    circuits whose telescoping has not completed), so the pickled state
    covers half-built circuit tables, pending EXTENDs, and the client
    frame readers."""
    # uninterrupted baseline (default plane wiring: C engine on)
    _, full_summary, full_tree, _ = _run(
        tmp_path, "full",
        **{"experimental.scheduler_policy": "tpu_batch"})

    src = tmp_path / "src"
    cfg = parse_config(yaml.safe_load(TOR_CFG), {
        "general.data_directory": str(src),
        "telemetry": {},
        "general.checkpoint_every": "1200 ms",
        "experimental.scheduler_policy": "tpu_batch",
    })
    Controller(cfg, mirror_log=False).run()
    paths = sorted((src / "checkpoints").glob("*.ckpt"))
    assert paths, "no checkpoints written"

    dd = tmp_path / "resume"
    rcfg = parse_config(yaml.safe_load(TOR_CFG), {
        "general.data_directory": str(dd),
        "telemetry": {},
        "general.checkpoint_every": "1200 ms",
        "experimental.scheduler_policy": "tpu_batch",
    })
    ctl, resume_at = ckpt.load_checkpoint(paths[0], rcfg, mirror_log=False)
    clients = [p.app for h in ctl.hosts for p in h.processes
               if type(p.app).__name__ == "TorClient"]
    assert clients
    mid_build = sum(c.attempted - len(c.build_times) - c.failed
                    for c in clients)
    assert mid_build > 0, (
        "checkpoint did not land mid-circuit-build; move checkpoint_every")
    summary = ctl.run(resume_at=resume_at)
    assert summary["process_errors"] == []
    resumed = _strip(summary)
    tree = _tree(dd)
    assert tree == full_tree
    # summary equality (counters, flow percentiles, event counts) too
    assert resumed == full_summary
    # telemetry contract: a resume into a fresh directory reproduces the
    # exact post-resume SUFFIX of the uninterrupted streams
    import json

    hdr = json.loads(open(paths[0], "rb").readline())

    def suffix(path):
        out = []
        for ln in path.read_text().splitlines(keepends=True):
            rec = json.loads(ln)
            if (rec.get("kind") != "meta"
                    and rec.get("round", 0) > hdr["rounds"]):
                out.append(ln)
        return "".join(out)

    for name in ("flows.jsonl", "metrics.jsonl"):
        got = (dd / name).read_text() if (dd / name).exists() else ""
        assert got == suffix(tmp_path / "full" / name), name
