"""The C engine's due-time index (native/colcore/colcore.c).

``Core.run_round`` runs the hosts whose heap root or inbox has work before
the round's end, in host-id order, and nothing else: exactly the hosts and
events of the Python planes' walk of every active host. So

- the C loop and the Python columnar plane give equal summaries, state
  digests and the configurations' compared readings, on the benchmark's
  Tor and gossip configurations at their CPU sizes;
- a push made between rounds that takes the first place of a host's queue
  (a direct ``schedule_in``, a fault transition) runs in that round;
- a host whose first entry is cancelled is selected at that entry's time
  and keyed again at its next live entry;
- ``Core.next_time()`` is the minimum of every host's
  ``EventQueue.next_time()``;
- a resume from a checkpoint of either plane builds the index from the
  restored heaps and stays byte-identical;
- the selection looks at no more than twice the hosts it runs in Tor.
"""

import importlib.util
import json
import sys
from pathlib import Path

import pytest
import yaml

from shadow_tpu import checkpoint as ckpt
from shadow_tpu.config import load_config
from shadow_tpu.config.schema import parse_config
from shadow_tpu.core.controller import VOLATILE_SUMMARY_KEYS, Controller
from shadow_tpu.core.events import EventQueue
from shadow_tpu.core.time import NS_PER_SEC, T_NEVER

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "benchmark"
CHURN = str(ROOT / "examples" / "gossip_churn.yaml")

#: (configuration, traffic, stop) of the benchmark's cells at CPU size
CELLS = {"tor": ("tor_1_10", "web20k", "20s"),
         "gossip": ("gossip_10k", "flood", "5s")}


def _bench(config: str, traffic: str):
    """A benchmark configuration's module and its document at ``TINY``."""
    if str(BENCH) not in sys.path:
        sys.path.append(str(BENCH))
    spec = importlib.util.spec_from_file_location(
        config, BENCH / "configs" / f"{config}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    tr = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
    return mod, mod.doc(5, tr, mod.TINY)


def _cfg(doc, data_dir, **over):
    return parse_config(doc, {"general.data_directory": str(data_dir),
                              "experimental.scheduler_policy": "tpu_batch",
                              **over})


def _strip(summary: dict) -> dict:
    return {k: v for k, v in summary.items()
            if k not in VOLATILE_SUMMARY_KEYS}


def _digests(data_dir) -> list:
    return (Path(data_dir) / "state_digests.jsonl").read_text().splitlines()


def _run_cell(tmp, cell: str, native: bool):
    config, traffic, stop = CELLS[cell]
    mod, doc = _bench(config, traffic)
    doc["general"]["stop_time"] = stop
    dd = tmp / f"{cell}-{native}"
    ctl = Controller(_cfg(doc, dd, **{
        "general.state_digest_every": 400,
        "experimental.native_colcore": native}), mirror_log=False)
    s = ctl.run()
    return s, mod.results(ctl, dd), _digests(dd)


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """Each cell on the C loop and on the Python columnar plane."""
    tmp = tmp_path_factory.mktemp("due")
    return {(cell, native): _run_cell(tmp, cell, native)
            for cell in CELLS for native in (True, False)}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_c_loop_matches_the_python_plane(runs, cell):
    c_s, c_res, c_dig = runs[cell, True]
    py_s, py_res, py_dig = runs[cell, False]
    assert c_s["process_errors"] == []
    assert c_s["events"] > 0 and c_s["rounds"] > 0
    assert _strip(c_s) == _strip(py_s)
    assert c_res == py_res
    assert len(c_dig) > 1 and c_dig == py_dig


def test_tor_selection_examines_at_most_twice_what_it_runs(runs):
    calls = runs["tor", True][0]["phase_calls"]
    run = calls["events.hosts_run"]
    assert 0 < run <= calls["events.hosts_examined"] <= 2 * run
    # one select per round, timed on the C engine's clock
    assert calls["events.select"] == runs["tor", True][0]["rounds"]
    assert runs["tor", True][0]["phase_detail"]["events.select"] > 0


def test_on_first_fires_when_a_push_takes_the_first_place():
    q = EventQueue()
    firsts = []
    q.on_first = firsts.append
    q.push(10, None)
    q.push(20, None)
    q.push(10, None)  # same time, later insertion: not first
    q.push(5, None)
    h = q.push(3, None)
    q.cancel(h)
    q.push(4, None)  # behind a cancelled first entry
    assert firsts == [10, 5, 3]


def _churn_controller(tmp, tag: str, native: bool, stop="8s"):
    return Controller(load_config(CHURN, {
        "general.data_directory": str(tmp / tag),
        "general.stop_time": stop,
        "general.state_digest_every": 100,
        "experimental.scheduler_policy": "tpu_batch",
        "experimental.native_colcore": native}), mirror_log=False)


def _between_rounds(ctl, fn) -> None:
    """Call ``fn(round_start, round_end)`` after the plane's start of
    every round, before its hosts run."""
    start = ctl.engine.start_of_round

    def hooked(now, end):
        start(now, end)
        fn(now, end)

    ctl.engine.start_of_round = hooked


#: rounds at which the push is made
PUSH_ROUNDS = (40, 400, 1500)


def _push_run(tmp, native: bool, how: str):
    """The churn run with a between-round push at PUSH_ROUNDS onto the
    first host whose queue's first entry lies after the round
    (``lower``) or whose queue is empty (``empty``); returns what ran:
    (round pushed, round run, host, clock) per push."""
    ctl = _churn_controller(tmp, f"{how}-{native}", native)
    ran = []

    def push(now, end):
        if ctl.rounds not in PUSH_ROUNDS:
            return
        for h in ctl.hosts:
            heap = h.equeue._heap
            if (how == "lower" and heap and heap[0][0] >= end) \
                    or (how == "empty" and not heap):
                k = ctl.rounds
                h.schedule_in(now - h._now, lambda h=h, k=k: ran.append(
                    (k, ctl.rounds, h.id, h._now)))
                return
        raise AssertionError(f"no {how} host at round {ctl.rounds}")

    _between_rounds(ctl, push)
    s = ctl.run()
    return ran, _strip(s), _digests(tmp / f"{how}-{native}")


@pytest.mark.parametrize("how", ["lower", "empty"])
def test_between_round_push_runs_in_that_round(tmp_path, how):
    ran, s, dig = _push_run(tmp_path, True, how)
    assert [k for k, _, _, _ in ran] == list(PUSH_ROUNDS)
    assert all(k == r for k, r, _, _ in ran)
    assert (ran, s, dig) == _push_run(tmp_path, False, how)


def test_fault_transitions_run_as_on_the_python_plane(tmp_path):
    """Churn crashes and reboots hosts between rounds (clear_band, process
    respawns at BAND_FAULT): every digest equals the Python plane's."""
    runs = []
    for native in (True, False):
        s = _churn_controller(tmp_path, f"f{native}", native).run()
        assert s["counters"]["host_crashes"] > 0
        assert s["counters"]["host_boots"] > 0
        runs.append((_strip(s), _digests(tmp_path / f"f{native}")))
    assert runs[0] == runs[1]


#: two hosts whose processes start at 5 s: their queues hold only those
IDLE = """
general: {stop_time: 10s, seed: 3}
network:
  graph:
    type: gml
    inline: |
      graph [
        directed 0
        node [ id 0 host_bandwidth_up "10 Mbit" host_bandwidth_down "10 Mbit" ]
        edge [ source 0 target 0 latency "5 ms" ]
      ]
hosts:
  server:
    network_node_id: 0
    processes:
      - path: pyapp:shadow_tpu.models.tgen:TGenServer
        args: ["8080"]
        start_time: 5s
  client:
    network_node_id: 0
    processes:
      - path: pyapp:shadow_tpu.models.tgen:TGenClient
        args: ["1 MB", "1", serial, "8080", server]
        start_time: 5s
"""


def test_cancelled_first_entry_is_selected_then_keyed_at_the_next(tmp_path):
    ctl = Controller(_cfg(yaml.safe_load(IDLE), tmp_path / "idle", **{
        "experimental.tpu_device_floor": -1}), mirror_log=False)
    core, host = ctl._c_core, ctl.hosts[1]
    ms = NS_PER_SEC // 1000
    ran = []
    a = host.schedule(200 * ms, lambda: ran.append("a"))
    host.schedule(500 * ms, lambda: ran.append("b"))
    host.cancel(a)
    core.fold_counters()  # drain the build's counts

    def counts():
        c = core.fold_counters()["counts"]
        return c["events.hosts_examined"], c["events.hosts_run"]

    # a round before the cancelled entry: nothing selected
    assert core.run_round(100 * ms) == 0
    assert counts() == (0, 0)
    # a round over the cancelled entry: the host is selected and run
    # (its first entry is due, as the walk's conservative check said),
    # runs nothing, and is keyed again at b
    assert core.run_round(300 * ms) == 0
    assert counts() == (1, 1) and ran == []
    assert host.equeue._heap[0][0] == 500 * ms
    assert core.next_time() == 500 * ms == host.equeue.next_time()
    assert core.run_round(400 * ms) == 0
    assert counts() == (0, 0)
    assert core.run_round(600 * ms) == 1
    assert counts() == (1, 1) and ran == ["b"]
    # skip-ahead over a cancelled first entry: next_time drops it, as
    # EventQueue.next_time does, and keys the host at d, so no round
    # before d selects it
    c = host.schedule(700 * ms, lambda: ran.append("c"))
    host.schedule(800 * ms, lambda: ran.append("d"))
    host.cancel(c)
    assert core.next_time() == 800 * ms
    assert host.equeue._heap[0][0] == 800 * ms
    assert core.run_round(750 * ms) == 0
    assert counts() == (0, 0)
    assert core.run_round(900 * ms) == 1
    assert ran == ["b", "d"]
    assert core.next_time() == 5 * NS_PER_SEC
    ctl.engine.close()


class _NextTimeProbe:
    """Stands in for the controller's C core: after every round, the
    core's ``next_time()`` against the minimum over every host's queue."""

    def __init__(self, core, hosts) -> None:
        self.core, self.hosts, self.checked = core, hosts, []

    def run_round(self, end):
        n = self.core.run_round(end)
        self.check()
        return n

    def next_time(self):
        return self.check()

    def check(self):
        t = self.core.next_time()
        assert t == min((h.equeue.next_time() for h in self.hosts),
                        default=T_NEVER)
        self.checked.append(t)
        return t


def test_next_time_is_the_minimum_over_every_host(tmp_path):
    ctl = _churn_controller(tmp_path, "nt", True)
    probe = ctl._c_core = _NextTimeProbe(ctl._c_core, ctl.hosts)
    s = ctl.run()
    assert len(probe.checked) > s["rounds"]
    assert any(t < T_NEVER for t in probe.checked)


@pytest.mark.parametrize("native", [True, False], ids=["c", "python"])
def test_resume_rebuilds_the_index(tmp_path, native):
    """A Tor run that checkpoints on the C loop or on the Python plane,
    resumed on the C loop (the index built from the restored heaps),
    equals the uninterrupted run."""
    mod, doc = _bench("tor_1_10", "web20k")
    doc["general"]["stop_time"] = "12s"
    dig = {"general.state_digest_every": 400}

    def results(ctl, s, dd):
        return _strip(s), mod.results(ctl, dd), _digests(dd)

    ctl = Controller(_cfg(doc, tmp_path / "full", **dig), mirror_log=False)
    full = results(ctl, ctl.run(), tmp_path / "full")
    ctl = Controller(_cfg(doc, tmp_path / "src", **{
        **dig, "general.checkpoint_every": "4s",
        "experimental.native_colcore": native}), mirror_log=False)
    assert results(ctl, ctl.run(), tmp_path / "src") == full
    paths = sorted((tmp_path / "src" / "checkpoints").glob("*.ckpt"))
    assert len(paths) >= 2
    ctl, at = ckpt.load_checkpoint(
        str(paths[1]), _cfg(doc, tmp_path / "res", **dig), mirror_log=False)
    assert ctl._c_core is not None
    s, res, d = results(ctl, ctl.run(resume_at=at), tmp_path / "res")
    assert (s, res) == full[:2]
    assert d == full[2][-len(d):]


def _connect_run(tmp, native: bool):
    """IDLE with a connect made between rounds, before any process runs,
    to a port nobody listens on: the SYN's retransmit timer becomes the
    client's first entry, pushed by the endpoint outside any host's run
    (by C code where the C engine is attached)."""
    dd = tmp / f"conn-{native}"
    ctl = Controller(_cfg(yaml.safe_load(IDLE), dd, **{
        "general.state_digest_every": 20,
        "experimental.native_colcore": native,
        "experimental.tpu_device_floor": -1}), mirror_log=False)
    client = ctl.hosts[1]

    def connect(now, end):
        if ctl.rounds == 0:
            client.connect(0, 9999).connect()

    _between_rounds(ctl, connect)
    return _strip(ctl.run()), _digests(dd)


def test_between_round_endpoint_timer_is_keyed(tmp_path):
    c = _connect_run(tmp_path, True)
    assert c[0]["counters"]["units_unroutable"] > 1  # SYNs retransmitted
    assert c == _connect_run(tmp_path, False)
