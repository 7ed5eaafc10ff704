"""The round loop's garbage collection policy (core/loop_gc.py).

Inside the loop automatic GC is off, the heap that existed at loop entry is
frozen, and the loop's own collects (every ``GC_EVERY_ROUNDS`` rounds and
once at the end) traverse only what the loop made. Every run gives the
frozen objects back, and a caller's own freeze is left alone.

A full collect puts the interpreter's immortal objects into the permanent
generation (CPython 3.12), where the freeze count counts them; so a run
that froze leaves the count at 0, and the tests read it back after a full
collect, which is where an untouched count stands too.
"""

from __future__ import annotations

import gc
import hashlib
import importlib.util
import json
import os
import signal
import sys
import weakref
from pathlib import Path

import pytest
import yaml

from shadow_tpu.config.schema import parse_config
from shadow_tpu.core.controller import VOLATILE_SUMMARY_KEYS, Controller
from shadow_tpu.core.time import NS_PER_SEC
from shadow_tpu.parallel import shards as sh

ROOT = Path(__file__).resolve().parent.parent

#: the tiny Tor run of the commit before the loop froze anything: its
#: summary without the volatile keys, and the sha256 of its clients'
#: fetch records (JSON, in host order) with their count
TOR_SUMMARY = {
    "bytes_sent": 78146676,
    "counters": {"events": 20305, "processes_exited": 40,
                 "processes_spawned": 71,
                 "stream_bytes_queued": 82268550,
                 "stream_bytes_received": 77265084,
                 "units_delivered": 19773, "units_emitted": 19870},
    "events": 20305, "exit_reason": "completed", "partial": False,
    "process_errors": [], "rounds": 11296, "sim_seconds": 40.0,
    "units_blackholed": 0, "units_dropped": 76, "units_sent": 19794}
TOR_RECORDS_SHA = \
    "5508d45e1865d1fb0e7d8a92c757b86e3541473df6dcec701b8a7ce656ff4456"
TOR_RECORDS = 89


def _bench_doc(config: str, traffic: str) -> dict:
    """A benchmark configuration's document at its CPU size."""
    bench = ROOT / "benchmark"
    if str(bench) not in sys.path:
        sys.path.append(str(bench))
    spec = importlib.util.spec_from_file_location(
        config, bench / "configs" / f"{config}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    tr = json.loads((bench / "traffic" / f"{traffic}.json").read_text())
    return mod.doc(5, tr, mod.TINY)


def _tor_doc() -> dict:
    return _bench_doc("tor_1_10", "web20k")


def _gossip_doc() -> dict:
    doc = _bench_doc("gossip_10k", "flood")
    doc["general"]["stop_time"] = "5s"
    return doc


def _controller(doc: dict, data_dir: Path, **over) -> Controller:
    return Controller(parse_config(doc, {
        "general.data_directory": str(data_dir),
        "experimental.scheduler_policy": "tpu_batch", **over}),
        mirror_log=False)


def _settled_freeze_count() -> int:
    gc.collect()
    return gc.get_freeze_count()


def _gc_counts(summary: dict) -> dict:
    calls = summary["phase_calls"]
    return {k: calls.get(k, 0) for k in ("gc", "gc.frozen", "gc.found")}


@pytest.fixture(scope="module")
def tor_run(tmp_path_factory):
    entry = _settled_freeze_count()
    ctl = _controller(_tor_doc(), tmp_path_factory.mktemp("tor"))
    summary = ctl.run()
    after = gc.get_freeze_count()
    return {"ctl": ctl, "summary": summary, "entry": entry,
            "after": after, "settled": _settled_freeze_count()}


def test_tor_run_gives_back_what_it_froze(tor_run):
    counts = _gc_counts(tor_run["summary"])
    assert counts["gc.frozen"] > 0
    # 11,296 rounds: two collects in the loop and the last one
    assert counts["gc"] == 3
    assert tor_run["after"] == 0
    assert tor_run["settled"] == tor_run["entry"]
    assert gc.isenabled()


def test_tor_run_collects_loop_born_cycles(tor_run):
    """Closed circuits leave cycles; the loop's collects find them."""
    assert _gc_counts(tor_run["summary"])["gc.found"] > 0


def test_tor_run_is_unchanged(tor_run):
    s = {k: v for k, v in tor_run["summary"].items()
         if k not in VOLATILE_SUMMARY_KEYS}
    assert s == TOR_SUMMARY
    recs = [h.processes[0].app.fetch_records() for h in tor_run["ctl"].hosts
            if type(h.processes[0].app).__name__ == "TorClient"]
    assert sum(map(len, recs)) == TOR_RECORDS
    assert hashlib.sha256(json.dumps(recs).encode()).hexdigest() \
        == TOR_RECORDS_SHA


def test_gossip_run_gives_back_what_it_froze(tmp_path):
    entry = _settled_freeze_count()
    s = _controller(_gossip_doc(), tmp_path).run()
    counts = _gc_counts(s)
    assert counts["gc.frozen"] > 0 and counts["gc"] == 1
    assert gc.get_freeze_count() == 0
    assert _settled_freeze_count() == entry
    assert gc.isenabled()


def test_caller_freeze_is_left_alone(tmp_path):
    """A caller that froze objects of its own keeps them frozen: the loop
    freezes nothing, collects as before, and leaves the caller's garbage
    frozen, where no collect frees it."""
    gc.collect()
    gc.disable()
    marker = _Cycle()
    marker.me = marker
    ref = weakref.ref(marker)
    del marker
    gc.freeze()
    gc.enable()
    try:
        s = _controller(_gossip_doc(), tmp_path).run()
        gc.collect()
        assert ref() is not None
        assert gc.get_freeze_count() > 0
    finally:
        gc.unfreeze()
    gc.collect()
    assert ref() is None
    counts = _gc_counts(s)
    assert counts["gc.frozen"] == 0 and counts["gc"] == 1
    assert s["process_errors"] == []


class _Cycle:
    pass


class CycleMaker:
    """Makes one reference cycle a sim-second after it starts, inside the
    loop, and looks at it every sim-second after: ``dead_at`` is the sim
    time at which it first found the cycle freed."""

    def __init__(self, api, args, env):
        self.api = api
        self.ref = None
        self.dead_at = None

    def start(self):
        self.api.after(NS_PER_SEC, self._make)

    def _make(self):
        c = _Cycle()
        c.me = c
        self.ref = weakref.ref(c)
        self.api.after(NS_PER_SEC, self._look)

    def _look(self):
        if self.ref() is None:
            self.dead_at = self.api.now
        else:
            self.api.after(NS_PER_SEC, self._look)


def test_loop_born_cycle_is_freed_by_a_collect_in_the_loop(tmp_path):
    """Automatic GC is off in the loop, so only the loop's own collect can
    free the cycle while the loop still runs."""
    doc = _tor_doc()
    doc["hosts"]["cycles"] = {
        "network_node_id": 0,
        "processes": [{"path": "pyapp:tests.test_loop_gc:CycleMaker",
                       "args": [], "expected_final_state": "running"}]}
    ctl = _controller(doc, tmp_path)
    s = ctl.run()
    assert s["process_errors"] == []
    maker = next(p.app for p in ctl.processes
                 if type(p.app).__name__ == "CycleMaker")
    assert maker.ref is not None and maker.ref() is None
    assert maker.dead_at is not None and maker.dead_at < 40 * NS_PER_SEC
    assert _gc_counts(s)["gc.found"] > 0


def test_interrupted_checkpointed_and_resumed_runs_give_back(tmp_path):
    """SIGINT mid-run writes the final checkpoint inside the frozen loop;
    the run and its resume each leave nothing frozen."""
    from shadow_tpu import checkpoint as ckpt

    entry = _settled_freeze_count()
    ctl = _controller(_gossip_doc(), tmp_path / "int",
                      **{"general.checkpoint_every": "1s"})
    ctl.hosts[0].schedule(2 * NS_PER_SEC,
                          lambda: os.kill(os.getpid(), signal.SIGINT))
    s = ctl.run()
    assert s["exit_reason"] == "interrupted"
    assert _gc_counts(s)["gc.frozen"] > 0
    assert gc.get_freeze_count() == 0 and gc.isenabled()
    assert _settled_freeze_count() == entry
    final = sorted((tmp_path / "int" / "checkpoints").glob("*.ckpt"))[-1]
    res, at = ckpt.load_checkpoint(final, parse_config(_gossip_doc(), {
        "general.data_directory": str(tmp_path / "res"),
        "experimental.scheduler_policy": "tpu_batch",
        "general.checkpoint_every": "1s"}), mirror_log=False)
    s2 = res.run(resume_at=at)
    assert s2["exit_reason"] == "completed"
    assert _gc_counts(s2)["gc.frozen"] > 0
    assert _settled_freeze_count() == entry


def test_a_raising_loop_gives_back(tmp_path):
    entry = _settled_freeze_count()
    ctl = _controller(_gossip_doc(), tmp_path)

    def boom():
        raise RuntimeError("raised in the loop")

    ctl.hosts[0].schedule(NS_PER_SEC, boom)
    with pytest.raises(RuntimeError, match="raised in the loop"):
        ctl.run()
    assert gc.get_freeze_count() == 0 and gc.isenabled()
    assert _settled_freeze_count() == entry


def worker_with_gc_report(conn, cfg, shard_id, *rest):
    """A shard worker's entry that writes, before its final summary goes
    out, its loop's gc counters and its settled freeze count at start and
    after the loop into ``gc_<shard>.json`` in the data directory."""
    entry = _settled_freeze_count()
    finalize = sh._ShardWorker._finalize

    def reporting_finalize(self, end_time):
        calls = self.ctl.spans.phase_calls()
        Path(cfg.general.data_directory, f"gc_{shard_id}.json").write_text(
            json.dumps({"entry": entry, "after": _settled_freeze_count(),
                        "gc": calls.get("gc", 0),
                        "frozen": calls.get("gc.frozen", 0)}))
        return finalize(self, end_time)

    sh._ShardWorker._finalize = reporting_finalize
    sh._worker_main(conn, cfg, shard_id, *rest)


def test_shard_workers_give_back_what_they_froze(tmp_path, monkeypatch):
    monkeypatch.setattr(sh, "_worker_main", worker_with_gc_report)
    doc = yaml.safe_load((ROOT / "examples" / "gossip_churn.yaml")
                         .read_text())
    cfg = parse_config(doc, {
        "general.data_directory": str(tmp_path),
        "general.stop_time": "3s",
        "general.sim_shards": 2,
        "experimental.scheduler_policy": "tpu_batch"})
    assert sh.run_sharded(cfg, mirror_log=False)["sim_shards"] == 2
    for k in range(2):
        rep = json.loads((tmp_path / f"gc_{k}.json").read_text())
        assert rep["frozen"] > 0 and rep["gc"] >= 1
        assert rep["after"] == rep["entry"]
