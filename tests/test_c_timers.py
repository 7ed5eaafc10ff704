"""The C engine's timer heap (native/colcore/colcore.c, "C timers").

Timers that C code arms (stream endpoints, Bitcoin nodes) live in a
per-host C heap beside ``EventQueue._heap``; each is the (time, BAND_APP,
seq, seq) entry a push would have made, with its seq from the queue's one
counter. So

- random arms, cancels and re-arms of endpoint timers, mixed with Python
  pushes and cancels of every band, same-nanosecond ties and inbox rows
  (whose arrivals re-arm the endpoints' timers), fire in the order, and
  take the seqs, of a plain ``EventQueue`` given the same calls and merged
  with the same rows as ``Host.run_events`` merges them, whether the C
  loop runs the host or ``Host.run_events`` does (a pcap host);
- ``len``, ``next_time``, ``live_times``, ``live_count`` and
  ``clear_band(BAND_APP)`` count C timers, cancelled ones left out, as the
  one heap did;
- the due-time index keys a host at the lesser of its two roots;
- a pcap host, which the Python loop runs, sees its C timers through the
  queue and runs as on the Python plane;
- on the benchmark's Tor and Bitcoin configurations at their CPU sizes,
  a run whose C timers are folded into the Python heaps and adopted back
  every round equals a run that keeps them all in C (which puts none in
  the Python heap); a Bitcoin trickle timer, which has no Python task,
  refuses the fold, ``head`` and ``pop_until`` by name and leaves the
  heaps as they were; tests/test_due_index.py holds the Tor
  configuration's C loop to the Python plane, whose endpoints keep every
  timer in the Python heap;
- a checkpoint taken with pending and cancelled C timers holds them as the
  Python heap entries they stand for, and resumes to the uninterrupted
  run's finish.
"""

import importlib.util
import json
import pickle
import random
import sys
from pathlib import Path

import pytest
import yaml

from shadow_tpu import checkpoint as ckpt
from shadow_tpu.config.schema import parse_config
from shadow_tpu.core.controller import VOLATILE_SUMMARY_KEYS, Controller
from shadow_tpu.core.events import BAND_APP, BAND_FAULT, BAND_NET, EventQueue
from shadow_tpu.core.time import NS_PER_SEC, T_NEVER
from shadow_tpu.native import _colcore

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "benchmark"
MS = NS_PER_SEC // 1000

#: two hosts whose processes start at 5 s: before then their queues hold
#: only what a test puts there
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

#: the field of an endpoint's export that holds its idle timer's handle
IDLE_HANDLE = 46
#: stream unit kinds (network/unit.py)
TK_DATA, TK_FIN = 2, 4


def _cfg(doc, data_dir, **over):
    return parse_config(doc, {"general.data_directory": str(data_dir),
                              "experimental.scheduler_policy": "tpu_batch",
                              **over})


def _strip(summary: dict) -> dict:
    return {k: v for k, v in summary.items()
            if k not in VOLATILE_SUMMARY_KEYS}


def _idle(tmp_path):
    ctl = Controller(_cfg(yaml.safe_load(IDLE), tmp_path / "idle", **{
        "experimental.tpu_device_floor": -1}), mirror_log=False)
    assert ctl._c_core is not None
    return ctl, ctl._c_core, ctl.hosts[1]


class World:
    """One host's queue run by the C engine, beside a plain
    ``EventQueue`` given the same calls. A C timer is an endpoint's idle
    timer: armed (or re-armed) by set_idle_timeout(delay), cancelled by
    set_idle_timeout(None), seen firing through the endpoint's on_error
    (the expiry resets the endpoint), and re-armed by every arrival on the
    endpoint while it is armed."""

    def __init__(self, ctl, host, n_eps: int, loop: str = "c") -> None:
        self.ctl, self.core, self.host = ctl, ctl._c_core, host
        self.loop = loop  # "c": Core.run_round; "python": Host.run_events
        core = self.core
        # the model starts as the host's queue (its processes' start, at
        # 5 s, past every round these tests run)
        self.model = EventQueue()
        q = host.equeue
        assert not q._ct and all(e[0] >= 5 * NS_PER_SEC for e in q._heap)
        for t, band, key, seq, _ in q._heap:
            self.model._seq = seq
            self.model.push(t, None, band=band, key=key)
        self.model._seq = q._seq
        self.model_now = 0
        self.fired, self.model_fired = [], []
        self.seqs, self.model_seqs = [], []
        self.model_handle, self.model_delay = {}, {}
        self.rows = []  # the round's inbox rows
        self.rearms = 0  # idle re-arms the model's arrivals made
        self.eps = []
        for k in range(n_eps):
            ep = core.make_endpoint(host.id, 40000 + k, 0, 80, True,
                                    1 << 20, 1 << 20)
            ep.on_error = (lambda msg, k=k:
                           self.fired.append(("c", k, self.host._now)))
            self.eps.append(ep)

    # -- each call on the C side and on the model ------------------------
    def real_arm(self, k: int, delay: int) -> None:
        ep = self.eps[k]
        ep.state = 2  # ESTABLISHED: the idle timeout's expiry resets it
        # routable (a reset drops the endpoint from the host's table)
        self.host._conns[(ep.local_port, 0, 80)] = ep
        ep.set_idle_timeout(delay)
        self.seqs.append(ep._export_state()[IDLE_HANDLE])

    def model_arm(self, k: int, delay: int) -> None:
        old = self.model_handle.pop(k, None)
        if old is not None:
            self.model.cancel(old)
        self.model_handle[k] = h = self.model.push(
            self.model_now + delay, lambda: self._model_fire(k))
        self.model_delay[k] = delay
        self.model_seqs.append(h)

    def _model_arrival(self, k: int) -> None:
        # StreamEndpoint.handle_fields: an arrival re-arms an armed idle
        # timer with the endpoint's timeout
        if k in self.model_handle:
            self.model.cancel(self.model_handle.pop(k))
            self.model_handle[k] = self.model.push(
                self.model_now + self.model_delay[k],
                lambda: self._model_fire(k))
            self.rearms += 1

    def add_row(self, t: int, key: int, k: int) -> None:
        """An arrival of one data byte at endpoint k (a duplicate after
        the first: only its timer re-arm matters here)."""
        self.rows.append((t, key, k))

    def _model_fire(self, k: int) -> None:
        self.model_handle.pop(k, None)
        self.model_fired.append(("c", k, self.model_now))

    def arm(self, k: int, delay: int) -> None:
        self.real_arm(k, delay)
        self.model_arm(k, delay)

    def cancel_c(self, k: int) -> None:
        self.eps[k].set_idle_timeout(None)
        old = self.model_handle.pop(k, None)
        if old is not None:
            self.model.cancel(old)

    def push(self, t: int, band: int, key: int, label, then=None) -> int:
        """A Python task; ``then`` = (k, delay) arms a C timer from inside
        the task, while its host runs."""

        def task():
            self.fired.append(("p", label, self.host._now))
            if then is not None:
                self.real_arm(*then)

        def model_task():
            self.model_fired.append(("p", label, self.model_now))
            if then is not None:
                self.model_arm(*then)

        h = self.host.schedule(t, task, band=band, key=key)
        assert self.model.push(t, model_task, band=band, key=key) == h
        return h

    def cancel_py(self, h: int) -> None:
        self.host.cancel(h)
        self.model.cancel(h)

    def set_clock(self, t: int) -> None:
        self.host._now = self.model_now = t

    def run_round(self, end: int) -> None:
        rows = sorted(self.rows)
        self.rows = []
        store = [(t, key, self.host.id, TK_DATA, 0, 80, 40000 + k, 1, 0, 0,
                  1, 41, None) for t, key, k in rows]
        if self.loop == "python":
            # the loop of a host the C engine hands to Python (pcap)
            self.host._inbox = store or None
            n = self.host.run_events(end)
        else:
            if store:
                cb = _colcore.shell("CBatch")
                cb._restore_state((0, store))
                self.ctl.engine.pending.append(cb)
                self.core.extract(end)
            n = self.core.run_round(end)
        assert not self.host.ingress_deferred_rows
        # the model: Host.run_events' merge of the rows (BAND_NET) with
        # the heap
        m, pos, q = 0, 0, self.model
        while True:
            h0 = q.head()
            hv = h0 is not None and h0[0] < end
            if pos < len(rows):
                t, key, k = rows[pos]
                if not hv or (t, BAND_NET, key) < h0[:3]:
                    self.model_now = t
                    self._model_arrival(k)
                    pos += 1
                    m += 1
                    continue
            if not hv:
                break
            self.model_now, task = q.pop_until(end)
            task()
            m += 1
        assert n == m

    def views_agree(self) -> None:
        q, m = self.host.equeue, self.model
        assert len(q) == len(m)
        assert q.next_time() == m.next_time()
        for band in (None, BAND_NET, BAND_APP):
            assert q.live_times(exclude_band=band) == \
                m.live_times(exclude_band=band)
            assert q.live_count(exclude_band=band) == \
                m.live_count(exclude_band=band)


def _drive(world: World, rng: random.Random, rounds: int) -> None:
    """Random calls between rounds and from inside tasks. Times sit on a
    5 ms grid, so timers of both heaps often share a nanosecond."""
    handles = []
    now = 0
    for r in range(rounds):
        end = now + 10 * MS
        world.set_clock(now)
        for _ in range(rng.randrange(1, 8)):
            op = rng.random()
            k = rng.randrange(len(world.eps))
            if op < 0.35:
                # short timeouts too: an arrival's re-arm can take the
                # first place of the C heap, and fire before the round's
                # later arrivals
                world.arm(k, rng.choice((1, 2, 3)) * MS
                          if rng.random() < 0.3
                          else rng.randrange(1, 6) * 5 * MS)
            elif op < 0.5:
                world.cancel_c(k)
            elif op < 0.6:
                # arrivals on a 1 ms grid, within the round
                for _ in range(rng.randrange(1, 4)):
                    world.add_row(now + rng.randrange(10) * MS,
                                  rng.randrange(1 << 20), k)
            elif op < 0.85:
                band = rng.choice((BAND_APP, BAND_APP, BAND_NET, BAND_FAULT))
                key = -1 if band == BAND_APP else rng.randrange(1 << 20)
                t = now + rng.randrange(0, 6) * 5 * MS
                m = world.model
                pend = sorted((e[3], e[0]) for e in m._heap
                              if e[3] in world.model_handle.values()
                              and e[3] not in m._cancelled)
                if band == BAND_APP and pend and rng.random() < 0.3:
                    # a tie with a pending C timer down to the key: the
                    # seqs decide
                    key, t = rng.choice(pend)
                then = ((k, rng.randrange(1, 4) * 5 * MS)
                        if rng.random() < 0.4 else None)
                handles.append(world.push(t, band, key, (r, len(handles)),
                                          then))
            elif handles:
                world.cancel_py(handles[rng.randrange(len(handles))])
        if r == rounds // 2:
            # a crash's teardown: every pending app timer dies
            q, m = world.host.equeue, world.model
            assert q.clear_band(BAND_APP) == m.clear_band(BAND_APP)
        world.views_agree()
        world.run_round(end)
        now = end
    world.set_clock(now)
    world.run_round(now + NS_PER_SEC)  # the rest fires
    world.views_agree()


@pytest.mark.parametrize("loop", ["c", "python"])
@pytest.mark.parametrize("seed", range(6))
def test_random_calls_fire_as_one_heap(tmp_path, seed, loop):
    ctl, core, host = _idle(tmp_path)
    world = World(ctl, host, n_eps=5, loop=loop)
    _drive(world, random.Random(seed), rounds=40)
    assert {f[0] for f in world.fired} == {"c", "p"}
    assert world.fired == world.model_fired
    assert world.seqs == world.model_seqs
    assert host.equeue._seq == world.model._seq
    assert len(host.equeue) == 0 and host.equeue.next_time() == T_NEVER
    counts = core.fold_counters()["counts"]
    assert world.rearms > 0
    assert counts["timers.armed"] == len(world.seqs) + world.rearms
    assert counts["timers.fired"] == sum(f[0] == "c" for f in world.fired)
    assert 0 < counts["timers.cancelled"] < counts["timers.armed"]
    ctl.engine.close()


def test_the_index_keys_a_host_at_the_lesser_root(tmp_path):
    ctl, core, host = _idle(tmp_path)
    ep = core.make_endpoint(host.id, 40000, 0, 80, True, 1 << 20, 1 << 20)
    ran = []
    ep.on_error = lambda msg: ran.append(("c", host._now))
    core.fold_counters()  # drain the build's counts

    def counts():
        c = core.fold_counters()["counts"]
        return c["events.hosts_examined"], c["events.hosts_run"]

    # a C timer before the Python root: the host is due at the C timer
    host.schedule(500 * MS, lambda: ran.append(("p", host._now)))
    ep.state = 2
    ep.set_idle_timeout(200 * MS)
    assert core.next_time() == 200 * MS == host.equeue.next_time()
    assert core.run_round(100 * MS) == 0 and counts() == (0, 0)
    assert core.run_round(300 * MS) == 1 and counts() == (1, 1)
    assert ran == [("c", 200 * MS)]
    # a Python push before the C root
    host._now = 300 * MS
    ep.state = 2
    ep.set_idle_timeout(500 * MS)  # at 800 ms
    assert core.next_time() == 500 * MS
    assert core.run_round(600 * MS) == 1 and counts()[1] == 1
    assert ran[-1] == ("p", 500 * MS)
    assert core.next_time() == 800 * MS
    # a cancelled C root is selected at its time, runs nothing, and the
    # host is keyed again at its next entry; next_time skips it
    host.schedule(900 * MS, lambda: ran.append(("p", host._now)))
    ep.set_idle_timeout(None)
    assert core.run_round(850 * MS) == 0 and counts()[1] == 1
    assert core.next_time() == 900 * MS
    assert core.run_round(950 * MS) == 1
    assert ran[-1] == ("p", 900 * MS)
    assert core.next_time() == 5 * NS_PER_SEC
    ctl.engine.close()


@pytest.mark.parametrize("loop", ["c", "python"])
@pytest.mark.parametrize("root", ["c", "python"])
def test_a_timer_an_arrival_arms_fires_before_later_arrivals(tmp_path,
                                                             root, loop):
    """An arrival that arms a timer earlier than the host's first entry
    (a C or a Python one): the round's later arrivals come after its
    fire. A FIN puts the endpoint in TIME_WAIT with its drop timer 2 ms
    on; the drop takes it out of the host's table, so a data unit at 5 ms
    finds no endpoint."""
    ctl, core, host = _idle(tmp_path)
    far = core.make_endpoint(host.id, 40001, 0, 80, True, 1 << 20, 1 << 20)
    if root == "c":
        far.state = 2
        far.set_idle_timeout(50 * MS)
    else:
        host.schedule(50 * MS, lambda: None)
    ep = core.make_endpoint(host.id, 40000, 0, 80, True, 1 << 20, 1 << 20)
    ep.state = 2
    ep.rto_ns = MS
    host._conns[(40000, 0, 80)] = ep
    core.fold_counters()
    rows = [(t, key, host.id, kind, 0, 80, 40000, 1, 0, 0, 1, 41, None)
            for t, key, kind in ((1 * MS, 7, TK_FIN), (5 * MS, 9, TK_DATA))]
    if loop == "python":
        host._inbox = rows
        assert host.run_events(10 * MS) == 3  # two arrivals and the drop
    else:
        cb = _colcore.shell("CBatch")
        cb._restore_state((0, rows))
        ctl.engine.pending.append(cb)
        core.extract(10 * MS)
        assert core.run_round(10 * MS) == 3
    assert (40000, 0, 80) not in host._conns
    counts = core.fold_counters()["counts"]
    assert counts["timers.armed"] == 1 and counts["timers.fired"] == 1
    assert host.counters.get("units_unroutable") == 1
    ctl.engine.close()


#: ten stream transfers over a lossy link: SYN, RTO and drop timers
TRANSFERS = IDLE.replace('"1 MB", "1"', '"40 KB", "10"') \
    .replace("start_time: 5s", "") \
    .replace('latency "5 ms"', 'latency "5 ms" packet_loss 0.05')


def test_a_pcap_host_pops_its_c_timers_through_the_queue(tmp_path):
    """A pcap host runs through Host.run_events: its endpoints' C timers
    reach it only through the queue's view, and the run equals the Python
    plane's, capture file included."""
    out = []
    for native in (True, False):
        dd = tmp_path / str(native)
        ctl = Controller(_cfg(yaml.safe_load(TRANSFERS), dd, **{
            "general.stop_time": "30s",
            "general.state_digest_every": 10,
            "hosts.server.pcap_enabled": True,
            "experimental.native_colcore": native}), mirror_log=False)
        s = ctl.run()
        pcap = sorted((dd / "hosts").rglob("*.pcap"))
        assert pcap
        out.append((_strip(s), [p.read_bytes() for p in pcap],
                    (dd / "state_digests.jsonl").read_text()))
        if native:
            assert s["phase_calls"]["timers.fired"] > 0
    assert out[0] == out[1]
    assert out[0][0]["counters"]["stream_bytes_received"] >= 10 * 40_000


# -- the benchmark's configurations at their CPU sizes ------------------------
CELLS = {"tor": ("tor_1_10", "web20k", "12s"),
         "bitcoin": ("bitcoin_10k", "mainnet_block", None)}


def _bench(config: str, traffic: str):
    if str(BENCH) not in sys.path:
        sys.path.append(str(BENCH))
    spec = importlib.util.spec_from_file_location(
        config, BENCH / "configs" / f"{config}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    tr = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
    doc = mod.doc(7, tr, mod.TINY)
    return mod, doc


def _c_task(task) -> bool:
    """Is this Python heap task a C timer (an endpoint's, as a checkpoint
    folds it)?"""
    return type(getattr(task, "__self__", None)).__name__ == "Endpoint"


def _refuses(call) -> bool:
    """Does ``call`` refuse a Bitcoin trickle timer by name?"""
    try:
        call()
    except TypeError as e:
        assert "Bitcoin trickle timer" in str(e)
        return True
    return False


def _cell_run(tmp, cell: str, move: bool):
    """A cell at its CPU size. With ``move``, every round starts with the
    C timers folded into the Python heaps and adopted back, as around a
    checkpoint write; a pending Bitcoin trickle timer refuses the fold
    before anything moves. After a Bitcoin run, ``head`` and
    ``pop_until`` of a host whose first timer is a trickle timer refuse
    it too, and leave the queue as it was."""
    config, traffic, stop = CELLS[cell]
    mod, doc = _bench(config, traffic)
    if stop is not None:
        doc["general"]["stop_time"] = stop
    dd = tmp / f"{cell}-{move}"
    ctl = Controller(_cfg(doc, dd, **{"general.state_digest_every": 200}),
                     mirror_log=False)
    core = ctl._c_core
    start = ctl.engine.start_of_round
    seen = {"rounds": 0, "py_c_timers": 0, "c_timers": 0, "folded": 0,
            "refused": 0, "head_refused": 0}

    def hooked(now, end):
        start(now, end)
        seen["rounds"] += 1
        if move:
            if _refuses(core.fold_timers):
                seen["refused"] += 1
                assert all(not _c_task(e[4])
                           for h in ctl.hosts for e in h.equeue._heap)
            else:
                seen["folded"] += 1
            core.adopt_timers()
        elif seen["rounds"] % 25 == 0:
            for h in ctl.hosts:
                seen["py_c_timers"] += sum(
                    _c_task(e[4]) for e in h.equeue._heap)
                seen["c_timers"] += len(h.equeue._ct)

    ctl.engine.start_of_round = hooked
    s = ctl.run()
    dig = (dd / "state_digests.jsonl").read_text().splitlines()
    if move and cell == "bitcoin":
        for h in ctl.hosts:
            q = h.equeue
            n, first = len(q), q.next_time()
            if _refuses(q.head):
                assert _refuses(lambda: q.pop_until(T_NEVER))
                assert (len(q), q.next_time()) == (n, first)
                seen["head_refused"] += 1
    return _strip(s), mod.results(ctl, dd), dig, s["phase_calls"], seen


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_timers_moved_between_heaps_give_one_run(tmp_path, cell):
    kept = _cell_run(tmp_path, cell, move=False)
    moved = _cell_run(tmp_path, cell, move=True)
    assert kept[0]["process_errors"] == []
    assert kept[0]["events"] > 0
    assert kept[:3] == moved[:3]
    calls, seen = kept[3], kept[4]
    assert calls["timers.armed"] > calls["timers.fired"] > 0
    assert calls["timers.cancelled"] > 0
    assert calls["events.hosts_run"] == moved[3]["events.hosts_run"]
    # nothing C arms lands in the Python heap; C timers are pending
    assert seen["py_c_timers"] == 0 and seen["c_timers"] > 0
    # endpoint timers fold in both cells; Bitcoin's trickle timers refuse
    assert moved[4]["folded"] > 0
    assert (moved[4]["refused"] > 0) == (cell == "bitcoin")
    assert (moved[4]["head_refused"] > 0) == (cell == "bitcoin")


def test_checkpoint_with_c_timers_resumes_to_the_same_finish(tmp_path):
    mod, doc = _bench("tor_1_10", "web20k")
    doc["general"]["stop_time"] = "12s"
    dig = {"general.state_digest_every": 400}

    def results(ctl, s, dd):
        dg = (dd / "state_digests.jsonl").read_text().splitlines()
        return _strip(s), mod.results(ctl, dd), dg

    ctl = Controller(_cfg(doc, tmp_path / "full", **dig), mirror_log=False)
    full = results(ctl, ctl.run(), tmp_path / "full")
    ctl = Controller(_cfg(doc, tmp_path / "src", **{
        **dig, "general.checkpoint_every": "5s"}), mirror_log=False)
    assert results(ctl, ctl.run(), tmp_path / "src") == full
    path = sorted((tmp_path / "src" / "checkpoints").glob("*.ckpt"))[0]
    # the file holds the C timers as the Python heap entries they stand
    # for, cancelled ones among them
    with open(path, "rb") as f:
        f.readline()
        snap = pickle.load(f)["controller"]
    ckpt._PENDING_ADOPT = []
    pending = cancelled = 0
    for h in snap.hosts:
        q = h.equeue
        for e in q._heap:
            if _c_task(e[4]):
                assert e[1] == BAND_APP and e[2] == e[3] and e[3] in q._live
                pending += 1
                cancelled += e[3] in q._cancelled
    assert pending > cancelled > 0
    ctl, at = ckpt.load_checkpoint(
        str(path), _cfg(doc, tmp_path / "res", **dig), mirror_log=False)
    assert all(not _c_task(e[4]) for h in ctl.hosts for e in h.equeue._heap)
    s, res, d = results(ctl, ctl.run(resume_at=at), tmp_path / "res")
    assert (s, res) == full[:2]
    assert d == full[2][-len(d):]
