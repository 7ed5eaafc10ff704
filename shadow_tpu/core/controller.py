"""The Controller: owns and drives one simulation.

Reference analog: ``Controller::run()`` -> ``Manager::run()`` -> round loop
(SURVEY.md §3.1). Responsibilities: load the topology, compute the
conservative lookahead (round width = min edge latency, overridable with
``experimental.runahead``), build hosts and their processes, drive the
round loop through the configured scheduler policy, and produce the output
tree + end-of-run summary.

Round-loop structure (the conservative PDES core):

    while now < stop:
        engine.start_of_round(now, end)   # flush due draws, ingress refills
        scheduler.run_round(round_end)    # per-host events, parallel-safe
        engine.end_of_round(now, end)     # the barrier: batched data plane
        now = round_end (or skip ahead through provably idle time)

Skip-ahead: when a round executed zero events and the engine holds no
deferred ingress, the controller jumps the clock to the next scheduled
event (or the earliest event an in-flight draw batch can produce) — idle
sim time costs nothing (the closed-form token buckets account elapsed time
exactly, so results are identical to grinding through empty rounds).
"""

from __future__ import annotations

from functools import partial

import time as _walltime  # detlint: ok(wallclock): loop wall + heartbeat wall costs
from pathlib import Path

import numpy as np

from shadow_tpu.config.schema import ConfigOptions
from shadow_tpu.core import spans as _spans
from shadow_tpu.core.loop_gc import LoopGC
from shadow_tpu.core.scheduler import make_scheduler
from shadow_tpu.core.time import NS_PER_SEC, NS_PER_US, SimTime, T_NEVER, format_time
from shadow_tpu.host.host import Host
from shadow_tpu.host.process import PluginProcess
from shadow_tpu.network.engine import NetworkEngine
from shadow_tpu.network.fluid import NetParams
from shadow_tpu.network.graph import load_graph
from shadow_tpu.utils.counters import Counters
from shadow_tpu.utils.logging import SimLogger
from shadow_tpu.utils.units import parse_bandwidth

DEFAULT_BANDWIDTH = parse_bandwidth("1 Gbit")

#: run-summary keys that are wall-clock / routing telemetry rather than
#: simulation state — strip these when diffing summaries for determinism
#: (the single source of truth for tests and tools/ci.sh; WHICH windows
#: the device served legitimately varies run to run while output trees
#: stay bit-identical)
#: "sim_shards"/"shards" are the scale-out plane's run-shape telemetry
#: (parallel/shards.py): which partition executed a simulation is as
#: immaterial to its results as which windows the device served
VOLATILE_SUMMARY_KEYS = ("wall_seconds", "sim_sec_per_wall_sec",
                         "phase_wall", "phase_detail", "phase_calls",
                         "max_rss_mb", "device",
                         "device_windows_dispatched", "sim_shards",
                         "shards", "supervisor")


class Controller:
    #: multi-process sharding (shadow_tpu/parallel/shards.py): the shard
    #: worker subclass overrides these INSTANCE attrs before calling
    #: __init__; the base controller owns every host. owns() gates which
    #: hosts get processes, scheduler slots, fault lifecycle transitions,
    #: telemetry columns, and digest fingerprints.
    shard_id = 0
    n_shards = 1

    #: live-operations plane (shadow_tpu/live.py). Class-level defaults
    #: keep checkpoints from before the plane restorable: an old snapshot
    #: simply inherits "no live state". ``live`` (the endpoint server) and
    #: ``on_stop_round`` (the time-travel inspector hook) are runtime-only
    #: and nulled by __getstate__.
    live = None
    stop_after_round = None
    on_stop_round = None
    _ckpt_now = False
    _live_paused = False
    _live_seq = 0
    _replay_cmds = ()
    _replay_idx = 0

    #: supervision plane (shadow_tpu/supervise.py). ``_supervised`` is
    #: set by run_supervised: guest-watchdog stalls then escalate to the
    #: supervisor (GuestStallError via ``_stall_escalate`` at the next
    #: boundary) instead of the unsupervised host_down conversion.
    #: ``_chaos`` is the env-armed fault injector (wall-clock plane;
    #: class defaults keep old checkpoints restorable).
    _supervised = False
    _stall_escalate = None
    _chaos = None

    def owns(self, hid: int) -> bool:
        return self.n_shards == 1 or hid % self.n_shards == self.shard_id

    def _sched_hosts(self) -> list:
        """The hosts this controller's scheduler executes: all of them,
        or the owned subset on a shard worker (a scheduler policy's
        host→thread placement cannot change results, so neither can the
        shard partition — same argument, one level up)."""
        if self.n_shards == 1:
            return self.hosts
        return [h for h in self.hosts if self.owns(h.id)]

    def _log_name(self) -> str:
        return "shadow.log"

    def __init__(self, cfg: ConfigOptions, mirror_log: bool = True) -> None:
        self.cfg = cfg
        if cfg.general.checkpoint_every:
            # fail at build, not at the first checkpoint boundary 40
            # minutes in (shadow_tpu/checkpoint.py owns the policy)
            from shadow_tpu.checkpoint import validate_config_checkpointable

            validate_config_checkpointable(cfg)
        self.data_dir = Path(cfg.general.data_directory)
        self.log = SimLogger(cfg.general.log_level,
                             self.data_dir / self._log_name(),
                             mirror_stderr=mirror_log)
        self.graph = load_graph(cfg.network["graph"])

        # conservative lookahead: round width <= min latency keeps every
        # cross-host arrival at least one round in the future (SURVEY.md §2
        # parallelism item 4). An explicit runahead overrides (arrivals then
        # clamp to the next round boundary — coarser, faster, still causal).
        w = self.graph.min_latency_ns
        if cfg.experimental.runahead is not None:
            w = cfg.experimental.runahead
        self.round_ns: SimTime = max(int(w), NS_PER_US)

        self.hosts: list[Host] = []
        self._by_name: dict[str, int] = {}
        self._by_ip: dict[str, int] = {}
        rate_up = np.zeros(len(cfg.hosts), dtype=np.int64)
        rate_down = np.zeros(len(cfg.hosts), dtype=np.int64)
        host_node = np.zeros(len(cfg.hosts), dtype=np.int32)
        for hid, hopts in enumerate(cfg.hosts):
            node_gml_id = hopts.network_node_id
            if node_gml_id not in self.graph.node_id_map:
                raise ValueError(
                    f"host {hopts.name!r}: network_node_id {node_gml_id} not in graph"
                )
            node = self.graph.node_id_map[node_gml_id]
            defaults = self.graph.node_defaults[node]
            up = hopts.bandwidth_up or defaults.bandwidth_up
            down = hopts.bandwidth_down or defaults.bandwidth_down
            if up is None or down is None:
                self.log.warning(
                    f"host {hopts.name!r}: no bandwidth configured on host or "
                    f"graph node; defaulting to 1 Gbit"
                )
                up = up or DEFAULT_BANDWIDTH
                down = down or DEFAULT_BANDWIDTH
            ip = hopts.ip_addr or _default_ip(hid)
            host = Host(hid, hopts.name, ip, node, cfg.general.seed, self,
                        cc=hopts.congestion_control)
            host.log_level = hopts.log_level or cfg.general.log_level
            if hopts.pcap_enabled:
                from shadow_tpu.utils.pcap import PcapWriter

                d = self.data_dir / "hosts" / hopts.name
                d.mkdir(parents=True, exist_ok=True)
                host.pcap = PcapWriter(d / f"{hopts.name}.pcap",
                                       hopts.pcap_capture_size)
            self.hosts.append(host)
            self._by_name[hopts.name] = hid
            self._by_ip[ip] = hid
            rate_up[hid] = up
            rate_down[hid] = down
            host_node[hid] = node

        from shadow_tpu.network.fluid import MTU

        #: fault injection (shadow_tpu/faults.py) runs on EVERY plane,
        #: including the C engine: the injector mutates the effective
        #: latency/loss/rate matrices and bucket arrays IN PLACE, and the
        #: C core holds raw pointers into those same arrays, so a
        #: transition is visible to all planes atomically at the next
        #: barrier. Crash/reboot teardown has explicit C hooks
        #: (Core.host_crash/host_boot); cross-policy and C-on/off
        #: determinism under churn is asserted by tests/test_faults.py.
        #: Checkpoints and the determinism sentinel likewise no longer
        #: force the Python planes — C state exports to plain Python
        #: structures for the pickler, and the digest walk reads only
        #: plane-independent observables the C twin exposes identically.
        faults_cfg = cfg.faults
        have_faults = faults_cfg is not None and (
            faults_cfg.events or faults_cfg.churn)

        params = NetParams.build(
            host_node=host_node,
            rate_up=rate_up,
            rate_down=rate_down,
            latency_ns=self.graph.latency_ns,
            reliability=self.graph.reliability,
            seed=cfg.general.seed,
            round_ns=self.round_ns,
            max_unit=cfg.experimental.unit_mtus * MTU,
        )
        policy = cfg.experimental.scheduler_policy
        backend = {"tpu_batch": "tpu", "tpu_mesh": "mesh"}.get(policy, "numpy")
        # active-host tracking of the Python planes: per-round work is
        # O(hosts with pending events), not O(all hosts) — the difference
        # at 10k mostly-idle hosts. A host (re)activates when a push
        # makes a new first entry of its queue (empty->nonempty among
        # them). The C engine keeps a due-time index instead (_wire_hooks).
        self._active: set = set()  # host IDS (ints sort at C speed)
        if backend in ("tpu", "mesh"):
            # the tpu policies run the array-native columnar plane
            # (network/colplane.py); thread policies keep the per-unit
            # plane as the reference-architecture baseline. Results are
            # bit-identical across planes (tests/test_colplane.py).
            from shadow_tpu.network.colplane import ColumnarPlane

            self.engine = ColumnarPlane(
                self.graph, params, self.hosts, self.round_ns,
                backend=backend, tpu_options=cfg.experimental,
                bootstrap_end=cfg.general.bootstrap_end_time,
            )
        else:
            self.engine = NetworkEngine(
                self.graph, params, self.hosts, self.round_ns,
                backend=backend, tpu_options=cfg.experimental,
                bootstrap_end=cfg.general.bootstrap_end_time,
            )
        for h in self.hosts:
            h.engine = self.engine
        self.scheduler = make_scheduler(policy, self._sched_hosts(),
                                        cfg.general.parallelism)
        # C engine (native colcore): owns the per-round host loop
        self._c_core = getattr(self.engine, "_c", None)
        self._wire_hooks()

        # processes: pyapp: plugins run in-process; any other path is a real
        # executable run under the native preload shim (SURVEY.md §7 phase 4)
        # (sharded workers build processes only for their OWNED hosts — a
        # non-owned host is pure topology here, its simulation lives on
        # the owning shard)
        self.processes: list = []
        for host, hopts in zip(self.hosts, cfg.hosts):
            if not self.owns(host.id):
                continue
            for i, popts in enumerate(hopts.processes):
                if PluginProcess.is_plugin_path(popts.path):
                    proc = PluginProcess(host, popts, i)
                else:
                    from shadow_tpu.native.managed import ManagedProcess, _shim_lib

                    # fail fast at build time, not inside a scheduler event
                    if not Path(popts.path).is_file():
                        raise ValueError(
                            f"host {hopts.name!r}: managed executable "
                            f"{popts.path!r} does not exist")
                    if not _shim_lib().exists():
                        raise ValueError(
                            f"native shim {_shim_lib()} missing — build it "
                            f"first: make -C native")
                    proc = ManagedProcess(host, popts, i)
                host.processes.append(proc)
                self.processes.append(proc)
                host.schedule(popts.start_time, proc.spawn)
                if popts.shutdown_time is not None:
                    host.schedule(popts.shutdown_time, proc.shutdown)

        self.faults = None
        if have_faults:
            from shadow_tpu.faults import FaultInjector

            self.engine.faults_active = True
            for h in self.hosts:
                h.faults_active = True
            if self._c_core is not None:
                # the C core was built before this flag existed: enable
                # its per-host blackhole/teardown accounting and the
                # faults-gated stream recovery counters
                self._c_core.set_faults_active(True)
            self.faults = FaultInjector(self)
            self.log.info(
                f"fault timeline: {len(self.faults.actions)} transitions "
                f"({len(faults_cfg.events)} configured events, "
                f"{len(faults_cfg.churn)} churn groups)")

        #: deterministic simulation telemetry (shadow_tpu/telemetry/): a
        #: telemetry: section builds the collector; hosts carry a direct
        #: reference so flow records cost one attribute check when off.
        #: Unlike faults/checkpoint, telemetry does NOT force the Python
        #: planes — the samplers read only plane-independent observables
        #: (shared numpy arrays, folded C counters, endpoint getters that
        #: the C twin exposes), and the streams are asserted byte-identical
        #: with the C engine on and off (tests/test_telemetry.py).
        self.telemetry = None
        if cfg.telemetry is not None:
            from shadow_tpu.telemetry import TelemetryCollector

            self.telemetry = TelemetryCollector(cfg.telemetry)
            for h in self.hosts:
                h.telemetry = self.telemetry
            if self.faults is not None:
                self.faults.on_apply = self.telemetry.record_fault

        self.counters = Counters()
        self.rounds = 0
        self.events = 0
        self.wall_seconds = 0.0
        #: the span recorder of the current (or last) run(); see
        #: core/spans.py -- the summary's phase_wall is its partition
        self.spans = _spans.Recorder(trace=False)
        # checkpoint/restore + determinism sentinel (shadow_tpu/checkpoint.py)
        self.ckpt_every: SimTime = cfg.general.checkpoint_every or 0
        self.ckpt_dir = (Path(cfg.general.checkpoint_dir)
                         if cfg.general.checkpoint_dir
                         else self.data_dir / "checkpoints")
        self.digest_every = cfg.general.state_digest_every
        #: managed re-execution snapshots (checkpoint format v5): arm the
        #: per-guest observation journal whenever this run could write a
        #: snapshot (grid cadence, or live checkpoint_now via the
        #: endpoint) so every snapshot carries verifiable guest cursors.
        #: The journal is a pure side-plane recorder — it never feeds sim
        #: state — so SHADOW_TPU_GUEST_JOURNAL=1/0 may force it on or off
        #: (the bench's journaling-overhead A/B) without touching results.
        self._reexec_verify = None
        self.guest_journal_dir = None
        self._has_managed = any(
            not PluginProcess.is_plugin_path(p.path)
            for h in cfg.hosts for p in h.processes)
        if self._has_managed:
            import os as _os

            jr = _os.environ.get("SHADOW_TPU_GUEST_JOURNAL")  # detlint: ok(envread): side-plane artifact toggle
            if jr != "0" and (jr == "1" or self.ckpt_every
                              or cfg.general.live_endpoint):
                self.guest_journal_dir = self.data_dir / "guest_oplogs"
        #: set by the SIGINT/SIGTERM handler: the round loop finishes the
        #: current round, writes a final checkpoint (when enabled), and
        #: finalizes a valid partial summary instead of dying mid-round
        self._interrupt = None
        self._partial = False
        self._init_live()
        for w in cfg.warnings:
            self.log.warning(w)

    def _init_live(self) -> None:
        """Build the live-operations plane (shadow_tpu/live.py): load the
        replay command log and bind the endpoint. Both config keys are
        volatile — the plane is pure wall-clock; commands only touch sim
        state via the recorded commands.jsonl. Shard workers never bind:
        the parent owns the socket and feeds commands through the shard-0
        marker path so all workers apply them at the same round."""
        from shadow_tpu import live as _live

        gen = self.cfg.general
        self._replay_cmds = ()
        self._replay_idx = 0
        if gen.replay_commands:
            self._replay_cmds = tuple(
                _live.load_command_log(gen.replay_commands))
            self.log.info(
                f"replaying {len(self._replay_cmds)} recorded command(s) "
                f"from {gen.replay_commands}")
        self.live = None
        if gen.live_endpoint and self.n_shards == 1:
            self.live = _live.LiveServer(
                _live.resolve_endpoint(gen.live_endpoint, self.data_dir),
                log=self.log)

    # -- checkpoint/restore (shadow_tpu/checkpoint.py) --------------------
    def __getstate__(self):
        """Snapshot-time state: everything except runtime plumbing. The
        scheduler (worker threads) and the C core are rebuilt by
        _reattach_runtime on restore; both are result-transparent."""
        d = self.__dict__.copy()
        d["scheduler"] = None
        d["_c_core"] = None
        d["spans"] = None  # each run() starts its own
        # live plane: the server, inspector hook, and replay cursor are
        # runtime plumbing rebuilt by _init_live from the RESUME
        # invocation's (volatile) config keys
        d["live"] = None
        d["on_stop_round"] = None
        d["_live_paused"] = False
        d["_replay_cmds"] = ()
        d["_replay_idx"] = 0
        return d

    def _wire_hooks(self) -> None:
        """Point the hosts' ``on_first`` hooks (and the Python columnar
        plane's inbox ``activate``) at what picks each round's hosts: the
        C core's due-time index, built here from every heap, or the
        Python planes' active set."""
        core = self._c_core
        if core is not None:
            for h in self.hosts:
                h.equeue.on_first = partial(core.activate, h.id)
            core.index_heaps()
            return
        if hasattr(self.engine, "emitters"):  # columnar Python paths
            self.engine.activate = self._active.add
        for h in self.hosts:
            h.equeue.on_first = partial(_activate_id, self._active.add,
                                        h.id)

    def _reattach_runtime(self, mirror_log: bool = True) -> None:
        """Rebuild the runtime-only pieces after a checkpoint restore:
        output location, logger mirroring, scheduler threads, the device
        draw plane, and the C engine (honoring the resume invocation's
        ``experimental.native_colcore`` — a volatile config key).
        Everything simulation-semantic came back through the pickle; any
        checkpoint-restored C objects (endpoints, gossip states, relays)
        are bound to the fresh core via ``checkpoint.finish_colcore_adopt``."""
        from shadow_tpu.utils.logging import LEVELS

        cfg = self.cfg
        self.data_dir = Path(cfg.general.data_directory)
        self.log.path = self.data_dir / self._log_name()
        self.log.mirror = mirror_log
        # log_level is a volatile config key: honor the resume invocation's
        # value on the main log and on hosts without a per-host override
        self.log.level = LEVELS[cfg.general.log_level]
        for h, hopts in zip(self.hosts, cfg.hosts):
            h.log_level = hopts.log_level or cfg.general.log_level
        self.ckpt_every = cfg.general.checkpoint_every or 0
        self.ckpt_dir = (Path(cfg.general.checkpoint_dir)
                         if cfg.general.checkpoint_dir
                         else self.data_dir / "checkpoints")
        self.digest_every = cfg.general.state_digest_every
        self._init_live()
        self.scheduler = make_scheduler(
            cfg.experimental.scheduler_policy, self._sched_hosts(),
            cfg.general.parallelism)
        self.engine.reattach_device(cfg.experimental)
        # C engine: rebuild over the restored structures and REWIRE the
        # activation hooks — the pickled hooks may reference the dead
        # core's placeholder (checkpoint._DeadCoreHandle)
        attach = getattr(self.engine, "attach_colcore", None)
        self._c_core = attach(cfg.experimental) if attach is not None \
            else None
        self._wire_hooks()
        if self._c_core is not None and self.faults is not None:
            self._c_core.set_faults_active(True)
        from shadow_tpu import checkpoint as _ckpt

        _ckpt.finish_colcore_adopt(self)

    # -- managed re-execution restore (checkpoint format v5) --------------
    def guest_journal_cursors(self) -> dict:
        """Per-guest observation-journal cursors for a re-execution
        snapshot: ``{"host/proc": {"n": entries, "sha": running-hash}}``.
        Empty when journaling is off (no managed guests, or neither a
        checkpoint cadence nor a live endpoint armed the journal)."""
        out = {}
        for p in self.processes:
            j = getattr(p, "_journal", None)
            if j is not None:
                out[f"{p.host.name}/{p.name}"] = j.cursor()
        return out

    def note_guest_pid(self, proc) -> None:
        """Side-plane registry of live guest OS pids
        (``<data_dir>/guest_pids.jsonl``, one record per spawn/exec/fork).
        Never part of the determinism surface — fleet's ``--resume`` reads
        a dead run's registry to reap stale guests before re-running the
        seed (the pid is verified against the record's clock-page path in
        /proc/<pid>/environ first, so pid reuse cannot kill a stranger)."""
        import json as _json

        pid = proc.proc.pid if proc.proc is not None else proc.real_pid
        if pid is None:
            return
        # fork children borrow the parent's clock page; their environ
        # still carries the parent's SHADOW_TIME_SHM, so identity checks
        # use the nearest ancestor's page path
        shm, p = proc._time_path, proc
        while shm is None and getattr(p, "parent_proc", None) is not None:
            p = p.parent_proc
            shm = p._time_path
        rec = {"pid": int(pid), "host": proc.host.name, "proc": proc.name,
               "shm": str(shm) if shm else None}
        self.data_dir.mkdir(parents=True, exist_ok=True)
        with open(self.data_dir / "guest_pids.jsonl", "a") as f:
            f.write(_json.dumps(rec, sort_keys=True) + "\n")

    def _verify_reexec(self, now: SimTime) -> None:
        """The deterministic re-execution of a restored managed run has
        reached the snapshot boundary: verify the recomputed state digest
        and every guest's journal cursor against the checkpoint record.
        Any mismatch means the prefix did NOT reproduce the checkpointed
        run — fail by name instead of continuing a silently different
        simulation."""
        info, self._reexec_verify = self._reexec_verify, None
        from shadow_tpu.checkpoint import CheckpointError, state_digest

        if now != info["t"] or self.rounds != info["rounds"]:
            raise CheckpointError(
                f"re-execution diverged from {info['path']}: expected "
                f"round {info['rounds']} at sim {info['t']} ns, but the "
                f"round grid reached sim {now} ns at round {self.rounds} "
                f"— this environment does not reproduce the checkpointed "
                f"run")
        g, _hosts = state_digest(self, now)
        if g != info["digest"]:
            raise CheckpointError(
                f"re-execution diverged from {info['path']}: state digest "
                f"at round {self.rounds} is {g[:16]}, checkpoint recorded "
                f"{info['digest'][:16]} — bisect with "
                f"tools/bisect_divergence.py against the original "
                f"state_digests.jsonl")
        want = info.get("cursors") or {}
        cur = self.guest_journal_cursors()
        if want and cur != want:
            bad = sorted(k for k in set(want) | set(cur)
                         if want.get(k) != cur.get(k))
            raise CheckpointError(
                f"re-execution diverged from {info['path']}: guest "
                f"journal cursor mismatch for {bad} — the re-executed "
                f"guests did not observe the recorded syscall stream")
        self.log.info(
            f"re-execution reached the snapshot boundary (round "
            f"{self.rounds}, sim {format_time(now)}): state digest and "
            f"{len(want)} guest journal cursor(s) verified; continuing")

    def _on_signal(self, signum, frame) -> None:
        """SIGINT/SIGTERM: request a graceful stop at the next round
        boundary. A second signal aborts immediately (the operator means
        it)."""
        import signal as _signal

        if self._interrupt is not None:
            raise KeyboardInterrupt
        self._interrupt = _signal.Signals(signum).name

    # -- naming -----------------------------------------------------------
    def resolve(self, name_or_ip) -> int:
        if isinstance(name_or_ip, int):
            return name_or_ip
        hid = self._by_name.get(name_or_ip)
        if hid is None:
            hid = self._by_ip.get(name_or_ip)
        if hid is None:
            raise KeyError(f"unknown host {name_or_ip!r}")
        return hid

    # -- main loop --------------------------------------------------------
    def run(self, resume_at: SimTime = None) -> dict:
        """Drive the simulation to stop_time. ``resume_at`` (set by
        checkpoint.load_checkpoint) re-enters the round loop at a saved
        round boundary; all loop-carried state (engine, queues, fault
        cursor, active set, counters) came back through the snapshot, so
        the continuation is byte-identical to the uninterrupted run."""
        cfg = self.cfg
        stop = cfg.general.stop_time
        w = self.round_ns
        now: SimTime = resume_at if resume_at is not None else 0
        self.log.info(
            f"simulation {'resuming' if resume_at is not None else 'starting'}: "
            f"{len(self.hosts)} hosts, "
            f"{self.graph.n_nodes} graph nodes, round width {format_time(w)}, "
            f"policy {cfg.experimental.scheduler_policy}, stop {format_time(stop)}"
        )
        hb_interval = cfg.general.heartbeat_interval
        next_hb = ((now // hb_interval) + 1) * hb_interval \
            if hb_interval else T_NEVER
        prog_step = max(stop // 100, 1)
        next_prog = now + prog_step if cfg.general.progress else T_NEVER
        ck_every = self.ckpt_every
        dig = self.digest_every
        _ckpt = None
        if ck_every or dig or self.live is not None or self._replay_cmds:
            # the live plane needs the checkpoint module for the
            # checkpoint_now command even when grid checkpointing is off
            from shadow_tpu import checkpoint as _ckpt
        if (self.live is not None or cfg.general.replay_commands) \
                and resume_at is None:
            # fresh run: a stale command log would concatenate with this
            # run's records and break replay (resumes keep appending — the
            # continuation of one log, same discipline as the digests)
            from shadow_tpu import live as _live
            _live.command_log_path(self.data_dir).unlink(missing_ok=True)
        if resume_at is not None and self._replay_cmds:
            # commands at or before the snapshot boundary are already in
            # the restored state (the command hook runs before the
            # checkpoint write at a shared boundary): skip them without
            # re-applying or re-logging
            while (self._replay_idx < len(self._replay_cmds)
                   and self._replay_cmds[self._replay_idx]["t"] <= resume_at):
                self._replay_idx += 1
        if dig and resume_at is None:
            # fresh run: a stale sentinel stream from a previous run into
            # this data_directory would concatenate and confuse
            # tools/bisect_divergence.py (resumes keep appending — the
            # continuation of one stream)
            (self.data_dir / _ckpt.DIGEST_FILE).unlink(missing_ok=True)
        if resume_at is None and self._has_managed:
            # fresh-run discipline for the managed side planes: stale
            # guest journals or a dead run's pid registry must not
            # concatenate with this run's records (a re-execution restore
            # is a fresh run here — its artifacts regenerate 0..end, which
            # is exactly what makes them comparable to the originals)
            (self.data_dir / "guest_pids.jsonl").unlink(missing_ok=True)
            if self.guest_journal_dir is not None:
                import shutil as _shutil

                _shutil.rmtree(self.guest_journal_dir, ignore_errors=True)
        tel = self.telemetry
        if tel is not None and resume_at is None:
            # same discipline for the telemetry streams: fresh runs
            # truncate + write the meta record; resumes keep appending
            tel.start_fresh(self)
        next_ckpt = ((now // ck_every) + 1) * ck_every if ck_every \
            else T_NEVER
        # graceful shutdown: SIGINT/SIGTERM finish the current round, write
        # a final checkpoint (when enabled), and produce a valid partial
        # summary (main thread only — signals cannot be hooked elsewhere)
        import signal as _signal
        import threading as _threading

        self._partial = False
        self._interrupt = None  # a resumed final-checkpoint carries the
        #                         old signal name; this run starts clean
        installed = {}
        if _threading.current_thread() is _threading.main_thread():
            for s in (_signal.SIGINT, _signal.SIGTERM):
                try:
                    installed[s] = _signal.signal(s, self._on_signal)
                except (ValueError, OSError):
                    pass
        # chaos harness (shadow_tpu/supervise.py): deterministic-round
        # fault injection, armed only through the environment — one dict
        # probe per run when off, one int compare per round when on
        import os as _os

        if _os.environ.get("SHADOW_TPU_CHAOS"):
            from shadow_tpu.supervise import ChaosInjector

            self._chaos = ChaosInjector.from_env(
                self.data_dir, shard=self.shard_id, in_process=True)
        # the spans of this run: phase_wall partitions wall_seconds. A
        # resumed run starts a fresh recorder, as it starts a fresh t0
        rec = self.spans = _spans.Recorder()
        rec.declare(("events", "gc", *getattr(self.engine, "PHASES", ())))
        with rec.installed():
            t0 = _walltime.perf_counter()
            dyn = cfg.experimental.use_dynamic_runahead
            faults = self.faults
            # core/loop_gc.py: automatic GC off and the pre-loop heap
            # frozen; the last collect runs as the block ends
            with LoopGC(rec, self.rounds) as lgc:
                try:
                    now = self._round_loop(now, stop, w, dyn, faults,
                                           next_hb, hb_interval, next_prog,
                                           prog_step, lgc, next_ckpt,
                                           ck_every, dig, _ckpt, tel, t0)
                finally:
                    for s, old in installed.items():
                        _signal.signal(s, old)
                self._partial = self._interrupt is not None and now < stop
                if self._partial:
                    self.log.warning(
                        f"{self._interrupt} received: stopped gracefully at "
                        f"round boundary {format_time(now)} ({self.rounds} "
                        f"rounds); summary is partial")
                    if ck_every:
                        with rec.span("checkpoint"):
                            path = _ckpt.save_checkpoint(self, now)
                        self.log.info(f"final checkpoint written: {path}")
                        if self.live is not None:
                            self.live.publish({"type": "checkpoint",
                                               "path": str(path), "t": now,
                                               "round": self.rounds})
            self.engine.flush_all()  # finalize counters for in-flight batches
            if cfg.general.progress:
                import sys as _sys

                print(file=_sys.stderr)  # end the \r status line
            self.wall_seconds = _walltime.perf_counter() - t0
        self.scheduler.shutdown()
        result = self._finalize(min(now, stop))
        if self.live is not None:
            self.live.publish({"type": "end",
                               "exit_reason": result["exit_reason"],
                               "rounds": self.rounds, "t": min(now, stop)})
            self.live.close()
        return result

    def _round_loop(self, now, stop, w, dyn, faults, next_hb, hb_interval,
                    next_prog, prog_step, lgc, next_ckpt, ck_every,
                    dig, _ckpt, tel, t0) -> SimTime:
        """The conservative round loop (split from run() so the signal
        try/finally stays readable). Returns the final sim time."""
        span = self.spans.span
        sp_events = span("events")
        while now < stop:
            if self._chaos is not None:
                self._chaos.maybe_fire(self.rounds, self)
            if self._stall_escalate is not None:
                # a managed guest stalled past its watchdog deadline
                # under supervision: surface it at this boundary (before
                # anything is emitted for the next round) so the
                # supervisor can tear down and recover by re-execution
                from shadow_tpu.supervise import GuestStallError

                msg, self._stall_escalate = self._stall_escalate, None
                raise GuestStallError(msg)
            if self.live is not None \
                    or self._replay_idx < len(self._replay_cmds):
                # live-operations command plane (shadow_tpu/live.py):
                # due replayed commands, then live client commands, all
                # quantized to THIS boundary and logged — before the
                # interrupt check (a stop command IS the interrupt) and
                # before the checkpoint write (so a same-boundary
                # snapshot already contains the commands' effects)
                faults = self._live_boundary(now, faults)
            if self._interrupt is not None:
                # graceful shutdown: the signal arrived during the last
                # round; stop at this (consistent) round boundary
                break
            if self._reexec_verify is not None \
                    and now >= self._reexec_verify["t"]:
                # managed re-execution restore: the deterministic prefix
                # has reached the snapshot boundary — verify digest +
                # guest cursors HERE, exactly where the original run
                # wrote the snapshot (after the boundary's commands,
                # before fault transitions apply)
                self._verify_reexec(now)
            if now >= next_ckpt or self._ckpt_now:
                self._ckpt_now = False
                with span("checkpoint"):
                    if tel is not None:
                        tel.sync(self)  # streams complete at the boundary
                    path = _ckpt.save_checkpoint(self, now)
                self.log.info(
                    f"checkpoint written: {path} "
                    f"(sim {format_time(now)}, round {self.rounds})")
                if self.live is not None:
                    # the checkpoint_now ack precedes application (it
                    # confirms receipt, not effect) — this post-save
                    # record is how a live client learns the PATH, e.g.
                    # to fork it (shadow_tpu/forks.py)
                    self.live.publish({"type": "checkpoint",
                                       "path": str(path), "t": now,
                                       "round": self.rounds})
                if ck_every:
                    next_ckpt = ((now // ck_every) + 1) * ck_every
            if faults is not None:
                # fault transitions apply at round starts: an action at
                # time t takes effect at the first boundary >= t — the
                # same quantization the conservative barrier imposes on
                # every cross-host effect, so it is policy-independent
                faults.apply_due(now)
            if dyn:
                # widen to the smallest latency traffic has actually used
                # (never narrower than the static conservative window)
                w = max(self.round_ns,
                        min(self.engine.min_used_latency, 10 * self.round_ns))
            round_end = min(now + w, stop)
            self.engine.start_of_round(now, round_end)
            hosts = self.hosts
            with sp_events:
                if self._c_core is not None:
                    # the C loop runs the hosts its due-time index and
                    # the round's inbox rows select, merging each one's
                    # inbox and heap
                    executed = self._c_core.run_round(round_end)
                else:
                    active = [hosts[i] for i in sorted(self._active)]
                    executed = self.scheduler.run_round(round_end, active)
                    for h in active:
                        if not h.equeue._heap:
                            self._active.discard(h.id)
            self.engine.end_of_round(now, round_end)
            if self.engine.device_error is not None:
                # a background device attach failed: the run fails here
                # rather than finishing on the host twin
                self.engine.raise_device_error()
            self.rounds += 1
            self.events += executed
            if dig and self.rounds % dig == 0:
                # determinism sentinel: canonical state digest at this
                # round boundary (flushes in-flight draws first — result-
                # identical, so digesting runs stay byte-identical)
                _ckpt.emit_digest(self, round_end)
            if tel is not None and (tel.dirty
                                    or round_end >= tel.next_sample):
                # telemetry: flush this round's flow closes + fault
                # annotations; take a sample when the sim-time grid says
                # so (the round grid is policy-independent, so the
                # streams are too). One None check when off; idle rounds
                # of a telemetry run skip the call entirely.
                with span("telemetry"):
                    tel.on_round_end(self, round_end)
            if (self.stop_after_round is not None
                    and self.rounds >= self.stop_after_round):
                # time-travel inspection (shadow_tpu/live.py jump): halt
                # AT this boundary — digest/telemetry for the round are
                # already emitted — and hand the inspector the controller
                if self.on_stop_round is not None:
                    self.on_stop_round(self, round_end)
                now = round_end
                break
            if round_end >= next_hb:
                self._heartbeat(round_end, t0)
                # grid-snap, not +=: skip-ahead can cross several
                # intervals at once, and heartbeats must stay ON the
                # sim-time grid to be shard-mergeable (the cadence is
                # sim-round-driven; wall time appears only in the
                # emitted record)
                next_hb = ((round_end // hb_interval) + 1) * hb_interval
            if round_end >= next_prog:
                self._progress(round_end, stop, t0)
                next_prog = round_end + prog_step
            lgc.tick(self.rounds)
            if executed == 0 and not self.engine.has_immediate_work():
                # provably idle: materialize any in-flight draw batch that
                # could produce an event before the next queued one, then
                # skip to the next event. Flushing here (instead of waking a
                # round at the batch deadline) keeps the round grid — and
                # hence 'rounds' and bucket rebase instants — identical to a
                # run whose flags were computed inline (test_bitmatch.py::
                # test_device_floor_cannot_change_results). The columnar
                # plane's resolved-but-undelivered store rows count as
                # queued events here (pending_head). The C core computes
                # the same min natively (identical instants — it drops
                # cancelled heads exactly like next_time, so the round
                # grid cannot move).
                def _next_queued():
                    if self._c_core is not None:
                        nq = self._c_core.next_time()
                    else:
                        nq = min((hosts[i].equeue.next_time()
                                  for i in self._active), default=T_NEVER)
                    return min(nq, self.engine.pending_head())

                nt = _next_queued()
                if faults is not None:
                    # a pending fault transition is a wake-up: skip-ahead
                    # must not jump over it (a reboot creates new events)
                    nt = min(nt, faults.next_time())
                while self.engine.earliest_outstanding() < nt:
                    self.engine.flush_due(nt)
                    nt = _next_queued()
                    if faults is not None:
                        nt = min(nt, faults.next_time())
                if nt >= T_NEVER:
                    self.log.info(
                        f"no further events at {format_time(round_end)}; ending early"
                    )
                    now = stop
                    break
                now = max(round_end, nt)
            else:
                now = round_end
        return now

    def _live_boundary(self, now: SimTime, faults):
        """Drain the command plane at the round boundary ``now``: due
        replayed commands first, then live client commands. Every
        sim-visible command applies HERE with sim timestamp ``now`` and
        is appended to commands.jsonl, so an interactively driven run and
        its replay-from-log execute identical fault timelines — wall time
        only decides WHICH boundary a live command lands on, and that
        choice is recorded. Returns the (possibly just-created) fault
        injector."""
        from shadow_tpu import live as _live

        lines: list = []
        replay = self._replay_cmds
        while self._replay_idx < len(replay) \
                and replay[self._replay_idx]["t"] <= now:
            rec = replay[self._replay_idx]
            self._replay_idx += 1
            if rec.get("wall_only"):
                continue  # pause/resume never touched sim state
            faults = self._apply_cmd(rec["cmd"], now, rec["seq"], lines,
                                     faults, replayed=True)
        srv = self.live
        if srv is not None:
            batch = srv.poll_commands()
            while batch or self._live_paused:
                if not batch:
                    # paused: wall-block at this boundary, sim state
                    # untouched; commands arriving meanwhile still apply
                    # at THIS boundary
                    if self._interrupt is not None:
                        break
                    batch = srv.poll_commands(timeout=0.25)
                    continue
                norm = batch.pop(0)
                self._live_seq += 1
                faults = self._apply_cmd(norm, now, self._live_seq, lines,
                                         faults, replayed=False)
        if lines:
            _live.append_command_lines(self.data_dir, lines)
        return faults

    def _apply_cmd(self, norm, now: SimTime, seq: int, lines: list,
                   faults, replayed: bool):
        """Apply one normalized command at the boundary ``now``, log it,
        and publish it to live followers."""
        from shadow_tpu import live as _live

        kind = norm["cmd"]
        was_paused = self._live_paused
        wall_only = kind in ("pause", "resume")
        applied = True
        if kind == "pause":
            self._live_paused = True
            applied = not was_paused
        elif kind == "resume":
            self._live_paused = False
            applied = was_paused
        elif kind == "stop":
            self._live_paused = False
            self._interrupt = "live_stop"
        elif kind == "checkpoint_now":
            try:
                from shadow_tpu.checkpoint import \
                    validate_config_checkpointable
                validate_config_checkpointable(self.cfg)
                self._ckpt_now = True
            except ValueError as exc:
                self.log.warning(f"live checkpoint_now refused: {exc}")
                applied = False
        else:
            try:
                faults = _live.apply_command(self, norm, now)
            except ValueError as exc:
                # resolution failure against THIS topology (unknown node/
                # host, managed executable): refuse, never half-apply
                self.log.warning(f"live command {kind!r} refused: {exc}")
                applied = False
        if applied:
            lines.append(_live.format_command_record(
                norm, seq, self.rounds, now, wall_only=wall_only))
            self.log.info(
                f"live command {kind!r} applied at round {self.rounds} "
                f"(t={format_time(now)}, seq {seq}"
                f"{', replayed' if replayed else ''})")
            if self.live is not None:
                self.live.publish({"type": "command", "cmd": norm,
                                   "round": self.rounds, "seq": seq,
                                   "t": now, "replayed": replayed,
                                   "paused": self._live_paused})
        return faults

    def _progress(self, sim_now: SimTime, stop: SimTime, t0: float) -> None:
        """Terminal status line (reference: the status bar, SURVEY.md §2)."""
        import sys as _sys

        wall = _walltime.perf_counter() - t0
        pct = 100 * sim_now // stop
        rate = (sim_now / NS_PER_SEC) / wall if wall > 0 else 0.0
        eta = (stop - sim_now) / NS_PER_SEC / rate if rate > 0 else 0.0
        print(f"\r[{pct:3d}%] sim {format_time(sim_now)} / "
              f"{format_time(stop)}  {rate:.2f} sim-s/s  eta {eta:.0f}s   ",
              end="", file=_sys.stderr, flush=True)

    def _heartbeat(self, sim_now: SimTime, t0: float) -> None:
        wall = _walltime.perf_counter() - t0
        rate = (sim_now / NS_PER_SEC) / wall if wall > 0 else 0.0
        # the device-window routing decision rides the heartbeat so a
        # silently clamped/starved device is visible mid-run, not only in
        # the final summary (round-5 Weak #5)
        note = getattr(self.engine, "heartbeat_note", None)
        if self.live is not None:
            # sim-keyed heartbeat record: cadence and ordering are pure
            # sim-time (shard-mergeable); wall cost and the device note
            # ride INSIDE the record and never feed back into the sim
            self.live.publish({
                "type": "hb", "t": sim_now, "round": self.rounds,
                "events": self.events,
                "units_sent": self.engine.units_sent,
                "units_dropped": self.engine.units_dropped,
                "shards": 1,
                **({"dev": note()} if note is not None else {}),
                "wall": {
                    "seconds": round(wall, 3), "rate": round(rate, 3),
                    "phase": {k: round(v, 4) for k, v in
                              self.spans.phase_wall().items()},
                },
            })
        self.log.info(
            f"heartbeat: sim {format_time(sim_now)} wall {wall:.1f}s "
            f"({rate:.2f} sim-sec/wall-sec) rounds {self.rounds} "
            f"events {self.events} units sent {self.engine.units_sent} "
            f"dropped {self.engine.units_dropped}"
            + (f" {note()}" if note is not None else "")
        )

    def _finalize(self, end_time: SimTime) -> dict:
        if self.telemetry is not None:
            # flush the final round's flow closes before processes are
            # reaped (records already buffered; reaping adds none)
            self.telemetry.finalize(self)
        errors = []
        for p in self.processes:
            err = p.check_final_state()
            if err is not None:
                errors.append(err)
                self.log.error(err)
        for p in self.processes:  # reference §3.5: kill remaining managed
            reap = getattr(p, "reap", None)
            if reap is not None:
                reap()
            j = getattr(p, "_journal", None)
            if j is not None:
                # crash-killed guests that were never rebooted still hold
                # an open journal stream; flush + close it here
                j.close()
        for h in self.hosts:  # merge AFTER reaping so its counters land
            h.fold_counters()
            self.counters.merge(h.counters)
        self.engine.close()  # join the device-init thread before teardown
        self.engine.raise_device_error()  # an attach that failed late
        sim_sec = end_time / NS_PER_SEC
        rate = sim_sec / self.wall_seconds if self.wall_seconds > 0 else float("inf")
        self.log.info(
            f"simulation finished: sim {format_time(end_time)} in "
            f"{self.wall_seconds:.2f}s wall ({rate:.2f} sim-sec/wall-sec), "
            f"{self.rounds} rounds, {self.events} events, "
            f"{self.engine.units_sent} units delivered, "
            f"{self.engine.units_dropped} dropped"
        )
        self.log.info(self.counters.summary())
        self.data_dir.mkdir(parents=True, exist_ok=True)
        for h in self.hosts:
            h.flush_logs(self.data_dir)
            if h.pcap is not None:
                h.pcap.close()
        self.log.flush()
        import resource

        return {
            "sim_seconds": sim_sec,
            "wall_seconds": self.wall_seconds,
            "sim_sec_per_wall_sec": rate,
            # graceful-shutdown contract: an interrupted run still emits a
            # VALID summary, marked partial, instead of dying mid-round
            "exit_reason": "interrupted" if self._partial else "completed",
            "partial": self._partial,
            **({"interrupt_signal": self._interrupt}
               if self._partial else {}),
            # linux ru_maxrss is KiB; the process-wide high-water mark, so
            # it is only per-run when each run owns its process
            "max_rss_mb": round(
                resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024, 1),
            "rounds": self.rounds,
            "events": self.events,
            "units_sent": self.engine.units_sent,
            "units_dropped": self.engine.units_dropped,
            # previously a silent bare attribute (VERDICT: blackholed units
            # discarded without surfacing); per-host counts additionally
            # land in the counters under fault injection
            "units_blackholed": self.engine.units_blackholed,
            "bytes_sent": self.engine.bytes_sent,
            "counters": self.counters.as_dict(),
            "process_errors": errors,
            # the loop wall by phase (core/spans.py): each phase's self
            # time, so the phases add up to at most wall_seconds; events =
            # host event execution, the engine's phases (columnar plane:
            # barrier / draw_flush / extract / window_* ...), gc,
            # telemetry and checkpoint where they ran. phase_detail splits
            # a phase into its parts, phase_calls counts every span.
            "phase_wall": self.spans.phase_wall(),
            "phase_detail": self.spans.phase_detail(),
            "phase_calls": self.spans.phase_calls(),
            # fused device windows (round-5 Weak #5): zero here on a
            # tpu_batch run means the device never serviced a window —
            # the numpy/C twin carried the whole run (device.routes says
            # where every sent unit's draw was made). Wall-clock routing
            # telemetry only: never simulation state, so runs that differ
            # here still produce identical output trees.
            "device_windows_dispatched": getattr(
                self.engine, "dev_windows", 0),
            **({"device": self.engine.device_summary()}
               if hasattr(self.engine, "device_summary") else {}),
            **({"fault_transitions_applied": self.faults.applied}
               if self.faults is not None else {}),
            # flow-latency percentiles + sample counts (telemetry/):
            # deterministic reductions of sim-time state — intentionally
            # NOT in VOLATILE_SUMMARY_KEYS, so the determinism gates cover
            # them too
            **({"telemetry": self.telemetry.summary()}
               if self.telemetry is not None else {}),
        }


def _activate_id(add, host_id: int, _time: SimTime) -> None:
    """A Python plane's ``on_first`` hook: the host joins the active
    set (the time is for the C engine's index)."""
    add(host_id)


def _default_ip(host_id: int) -> str:
    # 11.0.0.0/8, sequential, skipping .0 and .255 host-octet edge cases
    n = host_id + 1
    a = 11 + (n >> 24)
    return f"{a}.{(n >> 16) & 0xFF}.{(n >> 8) & 0xFF}.{n & 0xFF}"
