"""Fleet mode determinism + statistics (shadow_tpu/fleet.py).

THE acceptance gates of the fleet PR:

- a seed run IN-FLEET (jobs=M, shared draw service, pinned workers) is
  byte-identical to the SAME seed run standalone — trees, flow/metric/
  digest streams;
- ``LogHistogram`` merging is order-invariant and associative (shuffled
  merge orders yield identical state), which is what makes the cross-seed
  reducer sound;
- the sweep survives a member failure (the crashed seed is reported, the
  rest complete) and ``--resume`` re-runs only what is missing;
- the shared draw service serves bit-identical flags/min-draws and its
  death degrades to the local twin, never to an error.
"""

from __future__ import annotations

import hashlib
import json
import random
import shutil
from pathlib import Path

import numpy as np
import pytest
import yaml

from shadow_tpu import fleet
from shadow_tpu.config.schema import parse_config
from shadow_tpu.core.controller import Controller
from shadow_tpu.telemetry.histogram import LogHistogram

ROOT = Path(__file__).resolve().parent.parent
CHURN_YAML = ROOT / "examples" / "gossip_churn.yaml"

STOP = "5s"
#: the telemetry/digest surface every leg (fleet + standalone) enables,
#: so the comparison covers all three stream kinds
COMMON = {
    "general.stop_time": STOP,
    "general.state_digest_every": 50,
    "telemetry.sample_every": "10s",
    "experimental.scheduler_policy": "tpu_batch",
}


def _standalone(tag: str, seed: int) -> dict:
    d = f"/tmp/st-fleet-solo-{tag}"
    shutil.rmtree(d, ignore_errors=True)
    doc = yaml.safe_load(CHURN_YAML.read_text())
    cfg = parse_config(doc, {
        **COMMON,
        "general.seed": seed,
        "general.data_directory": d,
    })
    Controller(cfg, mirror_log=False).run()
    return {
        "tree": fleet.output_tree_digest(d),
        "streams": fleet._stream_digests(d),
    }


# -- histogram merge algebra (the reducer's soundness) ------------------------

def _rand_hist(rng: random.Random, n: int) -> LogHistogram:
    h = LogHistogram()
    for _ in range(n):
        h.add(rng.randrange(0, 1 << 40))
    return h


def test_histogram_merge_order_invariance():
    """Shuffled merge orders produce identical state — bucket-wise
    addition is commutative/associative by construction, guarded here so
    a future histogram change cannot silently break the cross-seed
    reducer."""
    rng = random.Random(7)
    hists = [_rand_hist(rng, 500 + 97 * i) for i in range(6)]
    states = [h.state() for h in hists]
    base = LogHistogram.merged(states).state()
    for trial in range(5):
        order = list(range(len(states)))
        rng.shuffle(order)
        assert LogHistogram.merged([states[i] for i in order]).state() \
            == base, f"merge order changed the state (trial {trial})"
    # associativity: (a+b)+c == a+(b+c), via pairwise grouping
    ab = LogHistogram.merged(states[:3])
    cd = LogHistogram.merged(states[3:])
    ab.merge(cd)
    assert ab.state() == base
    # totals conserved
    assert ab.total == sum(h.total for h in hists)


def test_t_ci95_math():
    ci = fleet.t_ci95([10.0, 12.0, 14.0])
    assert ci["n"] == 3 and ci["mean"] == 12.0
    # s = 2, t(df=2) = 4.303 -> hw = 4.303 * 2 / sqrt(3)
    assert ci["half_width"] == pytest.approx(4.303 * 2 / 3 ** 0.5,
                                             abs=1e-3)
    assert ci["lo"] == pytest.approx(12.0 - ci["half_width"], abs=1e-3)
    assert fleet.t_ci95([5.0]) == {"n": 1, "mean": 5.0}
    assert fleet.t_ci95([]) == {"n": 0}


def test_min_draw_np_twin_is_threshold_factored():
    """The proxy's dead-service fallback for speculative waves must obey
    the same identity as the device kernel: dropped == (min_draw <
    thresh) for any thresh (fluid.loss_flags is the committed oracle)."""
    from shadow_tpu.network.fluid import MAX_PKTS, loss_flags

    rng = np.random.default_rng(11)
    n = 512
    lo = rng.integers(0, 1 << 32, n, dtype=np.uint64).astype(np.uint32)
    hi = rng.integers(0, 1 << 32, n, dtype=np.uint64).astype(np.uint32)
    npk = rng.integers(0, MAX_PKTS + 1, n).astype(np.uint32)
    mins = fleet._min_draw_np(9, lo, hi, npk, MAX_PKTS)
    assert (mins[npk == 0] == 0xFFFFFFFF).all()
    for th_val in (0, 1 << 10, 1 << 20):
        th = np.full(n, th_val, np.uint32)
        assert ((mins < th) == loss_flags(9, lo, hi, npk, th)).all()


# -- the sweep itself ---------------------------------------------------------

def test_fleet_seed_identity_and_summary(tmp_path):
    """3-seed sweep at jobs=2: every seed's tree + streams byte-identical
    to the same seed standalone, manifests carry matching hashes, and
    sweep_summary.json has pooled percentiles + per-seed CIs."""
    sweep_dir = tmp_path / "sweep"
    runner = fleet.FleetRunner(
        str(CHURN_YAML), [50, 51, 52], jobs=2, sweep_dir=sweep_dir,
        overrides=dict(COMMON), quiet=True)
    summary = runner.run()
    assert summary["completed"] == [50, 51, 52]
    assert summary["failed"] == {}
    for seed in (50, 51, 52):
        man = json.loads(
            (fleet.seed_dir(sweep_dir, seed)
             / fleet.SEED_MANIFEST).read_text())
        assert man["status"] == "ok"
        solo = _standalone(f"id{seed}", seed)
        d = fleet.seed_dir(sweep_dir, seed)
        assert fleet.output_tree_digest(d) == solo["tree"], \
            f"seed {seed}: in-fleet tree != standalone tree"
        assert fleet._stream_digests(d) == solo["streams"], \
            f"seed {seed}: streams diverged"
        assert man["tree_sha256"] == solo["tree"]
        assert man["streams_sha256"] == solo["streams"]
    # the statistics layer: pooled + CI per flow group, and the pooled
    # histogram equals the merge of the per-seed states by construction
    flows = summary["flows"]
    assert flows, "sweep recorded no flow groups"
    for kind, row in flows.items():
        assert row["count"] == row["ok"] + row["failed"]
        assert set(row["pooled"]) == {"p50_ms", "p90_ms", "p99_ms",
                                      "p99_9_ms"}
        ci = row["ci95"]["p50_ms"]
        assert ci["n"] == 3
        assert ci["lo"] <= ci["mean"] <= ci["hi"]
        assert len(row["per_seed"]["p99_ms"]) == 3
    # report renders without error and names the CI convention
    text = fleet.render_report(summary)
    assert "CI95" in text and "pooled" in text
    # reduction is idempotent (pure function of the on-disk artifacts)
    again = fleet.reduce_sweep(sweep_dir)
    assert again["flows"] == flows


def test_fleet_resume_skips_completed(tmp_path):
    sweep_dir = tmp_path / "sweep"
    over = dict(COMMON)
    r1 = fleet.FleetRunner(str(CHURN_YAML), [60, 61], jobs=2,
                           sweep_dir=sweep_dir, overrides=over,
                           quiet=True)
    s1 = r1.run()
    assert s1["completed"] == [60, 61]
    stamp = {s: (fleet.seed_dir(sweep_dir, s)
                 / fleet.SEED_MANIFEST).stat().st_mtime_ns
             for s in (60, 61)}
    r2 = fleet.FleetRunner(str(CHURN_YAML), [60, 61, 62], jobs=2,
                           sweep_dir=sweep_dir, overrides=over,
                           resume=True, quiet=True)
    s2 = r2.run()
    assert s2["completed"] == [60, 61, 62]
    assert sorted(s2["skipped_resume"]) == [60, 61]
    for s in (60, 61):  # completed seeds were not re-run
        assert (fleet.seed_dir(sweep_dir, s)
                / fleet.SEED_MANIFEST).stat().st_mtime_ns == stamp[s]
    # a changed config invalidates completion: everything re-runs
    over2 = dict(over, **{"general.stop_time": "4s"})
    r3 = fleet.FleetRunner(str(CHURN_YAML), [60], jobs=1,
                           sweep_dir=sweep_dir, overrides=over2,
                           resume=True, quiet=True)
    s3 = r3.run()
    assert s3["skipped_resume"] == []


def test_reap_stale_guests_is_pid_reuse_safe(tmp_path):
    """``_reap_stale_guests`` kills exactly the recorded guests that
    still carry our clock-page path in their environment. A recycled pid
    (live process, unrelated env), a long-dead pid, and a garbage record
    are all left alone — the registry must never let a resume shoot an
    innocent process."""
    import os
    import subprocess

    shm = str(tmp_path / "hosts" / "h" / "p.0.clock")
    ours = subprocess.Popen(["sleep", "300"],
                            env={"SHADOW_TIME_SHM": shm})
    other = subprocess.Popen(["sleep", "300"], env={"PATH": os.environ["PATH"]})
    try:
        reg = tmp_path / "guest_pids.jsonl"
        reg.write_text(
            json.dumps({"pid": ours.pid, "host": "h", "proc": "p.0",
                        "shm": shm}) + "\n"
            + json.dumps({"pid": other.pid, "host": "h", "proc": "q.0",
                          "shm": shm}) + "\n"          # pid recycled
            + json.dumps({"pid": 2 ** 22 + 12345, "host": "h",
                          "proc": "r.0", "shm": shm}) + "\n"  # long dead
            + "not json\n")
        assert fleet._reap_stale_guests(tmp_path) == 1
        assert ours.wait(timeout=10) == -9
        assert other.poll() is None, "reaped an unrelated process!"
    finally:
        other.kill()
        other.wait()
        if ours.poll() is None:
            ours.kill()
            ours.wait()
    # empty dir: a no-op, not an error
    assert fleet._reap_stale_guests(tmp_path / "nope") == 0


def _managed_fleet_yaml(tmp_path) -> Path:
    """managed_smoke.yaml with binary paths made absolute (the example
    keeps them repo-root-relative for ci.sh; fleet workers inherit
    whatever cwd pytest ran from)."""
    doc = yaml.safe_load((ROOT / "examples" / "managed_smoke.yaml")
                         .read_text())
    for h in doc["hosts"].values():
        for p in h["processes"]:
            p["path"] = str(ROOT / p["path"])
    out = tmp_path / "managed_fleet.yaml"
    out.write_text(yaml.safe_dump(doc))
    return out


def test_fleet_managed_sweep_and_partial_run_resume(tmp_path):
    """A multi-seed managed (real-binary) sweep completes end-to-end,
    and --resume treats a seed dir left mid-run by a dead worker (status
    "running" + stale guest pids) as failed: the leaked guest is reaped
    and the seed re-runs to ok."""
    from test_checkpoint import _MANAGED_MISSING

    if _MANAGED_MISSING:
        pytest.skip("managed guest plane unavailable: "
                    + ", ".join(map(str, _MANAGED_MISSING)))
    import subprocess

    cfgp = _managed_fleet_yaml(tmp_path)
    sweep_dir = tmp_path / "sweep"
    over = {"general.state_digest_every": 10}
    s1 = fleet.FleetRunner(str(cfgp), [11, 12], jobs=2,
                           sweep_dir=sweep_dir, overrides=over,
                           quiet=True).run()
    assert s1["completed"] == [11, 12]
    for s in (11, 12):
        man = json.loads((fleet.seed_dir(sweep_dir, s)
                          / fleet.SEED_MANIFEST).read_text())
        assert man["status"] == "ok"
        assert man["process_errors"] == []
    # forge the interrupted-attempt state a SIGKILLed worker leaves
    d = fleet.seed_dir(sweep_dir, 12)
    man = json.loads((d / fleet.SEED_MANIFEST).read_text())
    (d / fleet.SEED_MANIFEST).write_text(json.dumps(
        {"format": man["format"], "seed": 12, "status": "running",
         "config_digest": man["config_digest"]}))
    shm = str(d / "hosts" / "server" / "tgen_srv.0.clock")
    stale = subprocess.Popen(["sleep", "300"],
                             env={"SHADOW_TIME_SHM": shm})
    try:
        (d / "guest_pids.jsonl").write_text(json.dumps(
            {"pid": stale.pid, "host": "server", "proc": "tgen_srv.0",
             "shm": shm}) + "\n")
        s2 = fleet.FleetRunner(str(cfgp), [11, 12], jobs=2,
                               sweep_dir=sweep_dir, overrides=over,
                               resume=True, quiet=True).run()
        assert s2["skipped_resume"] == [11]  # the ok seed stood
        assert s2["completed"] == [11, 12]
        assert stale.wait(timeout=10) == -9, "stale guest not reaped"
    finally:
        if stale.poll() is None:
            stale.kill()
            stale.wait()
    man = json.loads((d / fleet.SEED_MANIFEST).read_text())
    assert man["status"] == "ok"
    assert man["process_errors"] == []


def test_fleet_member_failure_contained(tmp_path, monkeypatch):
    """One crashed seed is reported and the sweep continues — the
    "survives member failure" contract, driven through the chaos hook."""
    monkeypatch.setenv(fleet.CHAOS_ENV, "70")
    sweep_dir = tmp_path / "sweep"
    runner = fleet.FleetRunner(
        str(CHURN_YAML), [70, 71], jobs=2, sweep_dir=sweep_dir,
        overrides=dict(COMMON), quiet=True)
    summary = runner.run()
    assert summary["completed"] == [71]
    assert "70" in summary["failed"]
    assert "chaos hook" in summary["failed"]["70"]
    man = json.loads((fleet.seed_dir(sweep_dir, 70)
                      / fleet.SEED_MANIFEST).read_text())
    assert man["status"] == "failed"
    # resume finishes exactly the failed seed
    monkeypatch.delenv(fleet.CHAOS_ENV)
    r2 = fleet.FleetRunner(str(CHURN_YAML), [70, 71], jobs=2,
                           sweep_dir=sweep_dir,
                           overrides=dict(COMMON), resume=True,
                           quiet=True)
    s2 = r2.run()
    assert s2["completed"] == [70, 71]
    assert s2["skipped_resume"] == [71]


def test_fleet_crashed_seed_retried(tmp_path, monkeypatch):
    """A worker SIGKILLed mid-seed (hard chaos hook) is detected via
    pipe EOF, the worker respawned, and the seed RETRIED within its
    bounded budget — the sweep converges with nothing failed."""
    monkeypatch.setenv(fleet.CHAOS_KILL_ENV, "80")
    sweep_dir = tmp_path / "sweep"
    runner = fleet.FleetRunner(
        str(CHURN_YAML), [80, 81], jobs=2, sweep_dir=sweep_dir,
        overrides=dict(COMMON), quiet=True)
    summary = runner.run()
    assert summary["completed"] == [80, 81]
    assert summary["failed"] == {}
    assert summary["respawns"] == 1
    assert (sweep_dir / "chaos" / "kill.s80.fired").is_file()
    man = json.loads((fleet.seed_dir(sweep_dir, 80)
                      / fleet.SEED_MANIFEST).read_text())
    assert man["status"] == "ok"


def test_fleet_wedged_member_detected_and_retried(tmp_path, monkeypatch):
    """A member that wedges forever (hard chaos hook) trips the fleet
    stall watchdog — killed, NAMED, respawned, seed retried to ok; the
    sweep never hangs on one stuck worker."""
    monkeypatch.setenv(fleet.CHAOS_WEDGE_ENV, "90")
    monkeypatch.setenv(fleet.FLEET_STALL_ENV, "6")
    sweep_dir = tmp_path / "sweep"
    runner = fleet.FleetRunner(
        str(CHURN_YAML), [90], jobs=1, sweep_dir=sweep_dir,
        overrides=dict(COMMON), quiet=True)
    summary = runner.run()
    assert summary["completed"] == [90]
    assert summary["failed"] == {}
    assert summary["respawns"] == 1


def test_fleet_sweep_interrupt_partial_summary(tmp_path):
    """SIGINT mid-sweep: coherent teardown — in-flight members killed,
    their seeds recorded "interrupted" in failed manifests, the partial
    sweep_summary.json written with exit_reason interrupted, and the
    conventional 130 exit status. --resume can finish such a sweep."""
    import os
    import signal as _signal
    import subprocess
    import sys
    import time

    sweep = tmp_path / "sweep"
    proc = subprocess.Popen(
        [sys.executable, "-m", "shadow_tpu.fleet", "sweep",
         str(CHURN_YAML), "--seeds", "2", "--seed-base", "7",
         "--jobs", "2", "--stop-time", "120s", "--sweep-dir", str(sweep),
         "--no-device-service", "--quiet", "--json"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, cwd=str(ROOT))
    try:
        # both seeds dispatched (their "running" manifests exist) means
        # the parent sits in the dispatch loop: the interrupt races
        # in-flight members, not startup
        deadline = time.monotonic() + 90
        while time.monotonic() < deadline:
            mans = list(sweep.glob("seed_*/" + fleet.SEED_MANIFEST))
            if len(mans) == 2:
                break
            assert proc.poll() is None, proc.stderr.read().decode()
            time.sleep(0.05)
        else:
            pytest.fail("seeds not dispatched before the deadline")
        time.sleep(0.5)  # let the members get into their round loops
        os.kill(proc.pid, _signal.SIGINT)
        out, err = proc.communicate(timeout=90)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == 130, (out.decode(), err.decode())
    summary = json.loads((sweep / fleet.SWEEP_SUMMARY).read_text())
    assert summary["exit_reason"] == "interrupted"
    assert summary["failed"]  # the in-flight seeds, named
    for s, why in summary["failed"].items():
        assert why == "interrupted"
        man = json.loads((fleet.seed_dir(sweep, int(s))
                          / fleet.SEED_MANIFEST).read_text())
        assert man["status"] == "failed"
        assert man["error"] == "interrupted"
    # the printed summary is the same valid artifact
    assert json.loads(out)["exit_reason"] == "interrupted"


@pytest.mark.slow
def test_draw_service_round_trip_and_fallback():
    """The shared draw service serves bit-identical flags and min-draws
    for arbitrary member seeds from ONE attach, and a server lost mid-run
    leaves the proxy on the local twin — same results, and the proxy
    marks itself dead so the member's manifest reports it."""
    from shadow_tpu.network.fluid import MAX_PKTS, loss_flags
    from shadow_tpu.ops.propagate import DrawServer

    server = DrawServer(seed=123, max_batch=4096, n_shards=0,
                        max_pkts=MAX_PKTS)
    try:
        rng = np.random.default_rng(2)
        n = 777
        lo = rng.integers(0, 1 << 32, n, dtype=np.uint64).astype(np.uint32)
        hi = rng.integers(0, 1 << 32, n, dtype=np.uint64).astype(np.uint32)
        npk = rng.integers(1, MAX_PKTS + 1, n).astype(np.uint32)
        th = rng.integers(0, 1 << 20, n).astype(np.uint32)
        for member_seed in (123, 9999):  # incl. a seed != the attach seed
            cl = fleet.FleetDrawClient.connect(
                server.address, member_seed, 4096, MAX_PKTS, timeout=120)
            assert cl.device_info["platform"] == "cpu"
            flags = cl.dispatch(lo, hi, npk, th).read()
            assert (flags == loss_flags(member_seed, lo, hi, npk,
                                        th)).all()
            mins = cl.dispatch_min(lo, hi, npk).read()
            assert (mins == fleet._min_draw_np(member_seed, lo, hi, npk,
                                               MAX_PKTS)).all()
            cl.close_client()
        assert server.served_batches >= 4
        # dead-service fallback: the twin carries the draws, identically
        cl = fleet.FleetDrawClient.connect(server.address, 42, 4096,
                                           MAX_PKTS, timeout=120)
        server.close()
        h = cl.dispatch(lo, hi, npk, th)
        assert (h.read() == loss_flags(42, lo, hi, npk, th)).all()
        mins = cl.dispatch_min(lo, hi, npk).read()
        assert (mins == fleet._min_draw_np(42, lo, hi, npk,
                                           MAX_PKTS)).all()
        assert cl._dead  # reported: "lost mid-run" in the summary
    finally:
        server.close()
