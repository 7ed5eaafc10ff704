"""Fleet mode: M seeded simulations per box behind one shared device plane.

"Once is Never Enough" (Jansen/Tracey/Goldberg, USENIX Security '21 —
PAPERS.md) is the methodology this simulator pairs with: no conclusion
from one run, only from N seeded runs with confidence intervals. This
module is the throughput layer that makes N-seed sweeps cheaper than N
serial walls on one box, plus the statistics layer that turns the per-seed
telemetry into cross-run aggregates:

**The sweep runner** (``FleetRunner`` / ``python -m shadow_tpu.fleet sweep
config.yaml --seeds 10 --jobs M``) packs M concurrent seeded simulations:

- ``jobs`` persistent worker processes, pinned to cores (best-effort
  ``sched_setaffinity``), each running its assigned seeds SEQUENTIALLY in
  one interpreter — so the Python/numpy import wall, the APSP cache, and
  the JAX persistent compile cache amortize across seeds instead of being
  paid ``N`` times (DeviceDrawPlane.attach_cached's per-process discipline,
  one level up).
- Bounded admission: never more than ``jobs`` resident simulations, and an
  RSS guard that delays handing the next seed to an idle worker while the
  fleet's resident-set total is over budget (a big topology's build spike
  should not land while every sibling is at peak).
- ONE process-group device attach: the parent owns a
  ``ops.propagate.DrawServer`` — a single attach+calibrate+warm_shapes —
  and members route their draw windows to it through ``FleetDrawClient``
  (published into the existing ``network/devroute.py`` window machinery
  via ``SHADOW_TPU_DRAW_SERVICE``). The draw kernels take the threefry
  key as *data*, so every member seed shares the same compiled programs.
  Routing is wall-clock policy: the proxy's results are bit-identical to
  the in-process twins. A member never attaches the chip itself (the
  parent holds it): with the service unreachable or lost it runs the
  local numpy twin and its ``fleet_manifest.json`` records that
  (``draw_service``); a parent whose attach failed fails the sweep.
- Per-seed isolation: each seed runs with
  ``data_directory = <sweep_dir>/seed_<s>`` — its host log tree, flow and
  metric streams, and digest stream land there, byte-identical to the
  same seed run standalone (tests/test_fleet.py).
- Failure containment with bounded retries: a seed that raises, a worker
  process that dies, and a member that wedges past the EMA-derived stall
  deadline (``_check_members``) are all routed through one retry budget
  (``--retries``, supervise.py discipline) before counting as failed in
  the manifest; the sweep continues either way. A member over the
  per-member RSS ceiling (``--member-max-rss-mb``) is killed and NOT
  retried — a leak leaks again. SIGINT mid-sweep tears down coherently:
  in-flight members killed, leaked guests reaped, seeds recorded
  ``interrupted``, and the partial summary stays a valid artifact.
- ``--resume``: a partially-completed sweep re-runs only the seeds whose
  per-seed manifest is missing, failed, or was produced under a different
  config (checkpoint.config_digest identity).

**The reducer** (``reduce_sweep`` / ``... report <sweep-dir>``) k-way
merges the per-seed ``LogHistogram`` states (mergeable by construction —
fixed bucket layout, bucket-wise addition) into ``sweep_summary.json``:

- pooled percentiles (all seeds' samples in one histogram), and
- per-seed percentile vectors with t-based 95% confidence intervals per
  flow group — the run-level statistic is computed per seed first, then
  the CI is taken ACROSS seeds (the "repeated experiments" discipline of
  the methodology paper; seeds are the independent unit, not samples).

Nothing here touches simulation semantics: the fleet is process
orchestration plus statistics over streams the runs already produce.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import math
import os
import shutil
import sys
import time as _walltime  # detlint: ok(wallclock): worker admission, RSS polling, sweep wall report
from pathlib import Path

import numpy as np

SEED_MANIFEST = "fleet_manifest.json"
TEL_STATE_FILE = "telemetry_state.json"
SWEEP_SUMMARY = "sweep_summary.json"
MANIFEST_FORMAT = "shadow_tpu-fleet-seed"
SUMMARY_FORMAT = "shadow_tpu-sweep-summary"

#: chaos hook for the failure-path gates (tests/test_fleet.py, ci.sh):
#: comma-separated seeds that raise instead of running — exercising the
#: crashed-member path without needing a genuinely broken config. Unlike
#: the KILL/WEDGE hooks below this one fires on EVERY attempt, so a
#: chaos-failed seed exhausts its retry budget and lands in ``failed``.
CHAOS_ENV = "SHADOW_TPU_FLEET_CHAOS_SEEDS"

#: harder chaos hooks (shadow_tpu/supervise.py discipline): the worker
#: SIGKILLs itself / wedges forever just before running the listed seed.
#: Once-only via an O_EXCL marker under <sweep_dir>/chaos/, so the
#: retried attempt runs clean and the sweep converges — this is how
#: ci.sh proves detection + retry, not just failure accounting.
CHAOS_KILL_ENV = "SHADOW_TPU_FLEET_CHAOS_KILL_SEEDS"
CHAOS_WEDGE_ENV = "SHADOW_TPU_FLEET_CHAOS_WEDGE_SEEDS"

#: fixed member-stall deadline override (wall seconds). Default policy is
#: EMA-derived: max(supervise.stall_deadline_s(completed-seed wall EMA),
#: 60) once at least one seed has completed — before that there is no
#: basis for a deadline and members may run arbitrarily long.
FLEET_STALL_ENV = "SHADOW_TPU_FLEET_STALL_S"

#: member-side service discovery (read by network/devroute.py)
SERVICE_ENV = "SHADOW_TPU_DRAW_SERVICE"


def _dumps(obj) -> str:
    return json.dumps(obj, sort_keys=True, separators=(",", ":"))


# -- the member-side draw proxy ----------------------------------------------
#
# Quacks like ops.propagate.DeviceDrawPlane (dispatch / dispatch_min /
# SPEC_BUCKET) without importing jax: the member ships its draw batches to
# the fleet parent's DrawServer and reads the flags back through handle
# objects that satisfy the window machinery's read()/is_ready() contract.
# Responses arrive FIFO per member but are demuxed by request id, since
# the window pipeline + speculative waves read out of order.

def _min_draw_np(seed: int, uid_lo, uid_hi, npkts, width: int):
    """numpy twin of ops.propagate._min_draw_kernel (prefix-min 24-bit
    draw per unit; 0xFFFFFFFF for npkts == 0) — the dead-service fallback
    for speculative waves. Same integer math as fluid.loss_flags."""
    from shadow_tpu.network.fluid import PKT_SHIFT
    from shadow_tpu.ops.prng import threefry2x32

    pkt = np.arange(width, dtype=np.uint32)[None, :]
    c0 = np.broadcast_to(uid_lo[:, None], (uid_lo.shape[0], width))
    c1 = uid_hi[:, None] | (pkt << np.uint32(PKT_SHIFT))
    k0 = np.uint32(seed & 0xFFFFFFFF)
    k1 = np.uint32((seed >> 32) & 0xFFFFFFFF)
    draws, _ = threefry2x32(k0, k1, c0, c1, xp=np)
    draws = (draws >> np.uint32(8)).astype(np.uint32)
    return np.where(pkt < npkts[:, None], draws,
                    np.uint32(0xFFFFFFFF)).min(axis=1)


class _LocalFallbackHandle:
    """Handle whose result is computed in-process by the bit-identical
    numpy twin (service unreachable). Lazy: computed at first read."""

    __slots__ = ("_fn", "_out")

    def __init__(self, fn) -> None:
        self._fn = fn
        self._out = None

    def read(self):
        if self._out is None:
            self._out = self._fn()
        return self._out

    def is_ready(self) -> bool:
        return True


class _RemoteHandle:
    """An in-flight request to the fleet draw server."""

    __slots__ = ("_cl", "_rid", "_fallback")

    def __init__(self, cl, rid: int, fallback) -> None:
        self._cl = cl
        self._rid = rid
        self._fallback = fallback  # () -> twin result, on transport death

    def read(self):
        out = self._cl._wait(self._rid)
        if out is None:  # connection died mid-flight: twin carries it
            return self._fallback()
        return out

    def is_ready(self) -> bool:
        return self._cl._check(self._rid)


class FleetDrawClient:
    """Member-side proxy for the fleet parent's DrawServer (see module
    doc). Single-threaded by contract: the simulation round loop is the
    only caller (devroute publishes it like a device plane)."""

    name = "fleet"

    def __init__(self, conn, seed: int, dev_s: float, np_per_unit: float,
                 spec_bucket: int, max_batch: int, max_pkts: int,
                 device_info: dict = None) -> None:
        self._conn = conn
        self.seed = int(seed)
        #: the server's device identity (the member's summary reports it)
        self.device_info = device_info
        self.dev_s = dev_s
        self.np_per_unit = np_per_unit
        self.SPEC_BUCKET = spec_bucket
        self.max_batch = max_batch
        self.max_pkts = max_pkts
        self._rid = 0
        self._results: dict = {}
        self._dead = False

    @classmethod
    def connect(cls, address: str, seed: int, max_batch: int,
                max_pkts: int, timeout: float = 60.0,
                abort=None) -> "FleetDrawClient":
        """Connect to the fleet draw service. The socket handshake is
        immediate (the parent accepts before its attach finishes); the
        hello REPLY may take as long as the attach, so it is waited with
        an abortable poll — ``abort()`` returning True (e.g. the member
        run is tearing down) raises instead of blocking. Raises on a
        server that never comes up within ``timeout``."""
        from multiprocessing.connection import Client

        from shadow_tpu.ops.propagate import DRAW_SERVICE_AUTHKEY

        t0 = _walltime.monotonic()
        deadline = t0 + timeout
        # a MISSING socket gets a shorter window than a busy one: the
        # parent publishes the socket path at spawn but only binds it
        # once its jax import finishes (~seconds), and a socket that
        # never appears means the service died
        missing_deadline = t0 + 20.0
        last = None
        while True:
            try:
                conn = Client(address, family="AF_UNIX",
                              authkey=DRAW_SERVICE_AUTHKEY)
                break
            except (FileNotFoundError, ConnectionError, OSError) as exc:
                last = exc
                now = _walltime.monotonic()
                if now > (missing_deadline
                          if isinstance(exc, FileNotFoundError)
                          else deadline):
                    raise TimeoutError(
                        f"fleet draw service at {address!r} not reachable"
                        f": {last}") from last
                if abort is not None and abort():
                    raise TimeoutError("member aborted service connect")
                _walltime.sleep(0.25)
        try:
            conn.send(("hello", int(seed)))
            while not conn.poll(0.25):
                if abort is not None and abort():
                    raise TimeoutError("member aborted service connect")
                if _walltime.monotonic() > deadline:
                    raise TimeoutError(
                        f"fleet draw service at {address!r}: no hello "
                        f"reply within {timeout}s (attach stuck?)")
            op, dev_s, np_per_unit, spec_bucket, srv_max_batch, info = \
                conn.recv()
        except BaseException:
            conn.close()
            raise
        if op != "ok":
            conn.close()
            raise RuntimeError(f"draw service refused: {op!r}")
        return cls(conn, seed, dev_s, np_per_unit, spec_bucket,
                   min(int(max_batch), int(srv_max_batch)), max_pkts,
                   info)

    # -- plane interface (devroute window machinery) -----------------------
    def dispatch(self, uid_lo, uid_hi, npkts, thresh):
        def twin():
            from shadow_tpu.network.fluid import loss_flags

            return loss_flags(self.seed, uid_lo, uid_hi, npkts, thresh)

        if self._dead:
            return _LocalFallbackHandle(twin)
        rid = self._rid = self._rid + 1
        try:
            self._conn.send(("draw", rid, self.seed, uid_lo, uid_hi,
                             npkts, thresh))
        except (OSError, ValueError, BrokenPipeError):
            self._dead = True
            return _LocalFallbackHandle(twin)
        return _RemoteHandle(self, rid, twin)

    def dispatch_min(self, uid_lo, uid_hi, npkts, min_bucket: int = 0):
        def twin():
            return _min_draw_np(self.seed, uid_lo, uid_hi, npkts,
                                self.max_pkts)

        if self._dead:
            return _LocalFallbackHandle(twin)
        rid = self._rid = self._rid + 1
        try:
            self._conn.send(("min", rid, self.seed, uid_lo, uid_hi,
                             npkts, min_bucket))
        except (OSError, ValueError, BrokenPipeError):
            self._dead = True
            return _LocalFallbackHandle(twin)
        return _RemoteHandle(self, rid, twin)

    # -- response demux ----------------------------------------------------
    def _pump(self) -> None:
        """Drain whatever responses already landed (never blocks)."""
        try:
            while self._conn.poll(0):
                rid, out = self._conn.recv()
                self._results[rid] = out
        except (OSError, EOFError, BrokenPipeError):
            self._dead = True

    def _check(self, rid: int) -> bool:
        if rid in self._results:
            return True
        self._pump()
        return rid in self._results or self._dead

    def _wait(self, rid: int):
        """Block until response ``rid`` arrives (stashing any siblings
        that land first). Returns None if the connection died — the
        handle's twin closure takes over."""
        while rid not in self._results:
            if self._dead:
                return None
            try:
                got, out = self._conn.recv()
                self._results[got] = out
            except (OSError, EOFError, BrokenPipeError):
                self._dead = True
                return None
        return self._results.pop(rid)

    def close_client(self) -> None:
        try:
            self._conn.send(("bye",))
        except (OSError, ValueError, BrokenPipeError):
            pass
        try:
            self._conn.close()
        except OSError:
            pass


# -- per-seed execution (worker side) -----------------------------------------

def seed_dir(sweep_dir, seed) -> Path:
    """Per-task directory. A task is a seed (int — a sweep member) or a
    fork branch name (str — shadow_tpu/forks.py); the fleet's dispatch,
    watchdog, and retry machinery treat both as opaque keys."""
    if isinstance(seed, str):
        return Path(sweep_dir) / f"branch_{seed}"
    return Path(sweep_dir) / f"seed_{int(seed)}"


def output_tree_digest(data_dir) -> str:
    """One sha256 over the per-host output tree (path + content, sorted)
    — the identity the fleet gates on: in-fleet == standalone. A raw
    os.scandir walk: the tor-scale tree is ~1000 small files and the
    pathlib rglob + per-file Path machinery cost 3x the actual
    hashing."""
    base = str(data_dir)
    hosts = os.path.join(base, "hosts")
    files = []
    stack = [hosts]
    while stack:
        d = stack.pop()
        try:
            # detlint: ok(unordered-iter): list is .sort()ed before hashing
            with os.scandir(d) as it:
                for e in it:
                    if e.is_dir(follow_symlinks=False):
                        stack.append(e.path)
                    elif e.is_file(follow_symlinks=False):
                        files.append(e.path)
        except FileNotFoundError:
            pass
    files.sort()
    h = hashlib.sha256()
    pfx = len(base) + 1
    for p in files:
        h.update(p[pfx:].encode())
        h.update(b"\0")
        with open(p, "rb") as f:
            h.update(hashlib.sha256(f.read()).digest())
    return h.hexdigest()


def _stream_digests(data_dir) -> dict:
    out = {}
    for name in ("flows.jsonl", "metrics.jsonl", "state_digests.jsonl"):
        p = Path(data_dir) / name
        if p.is_file():
            out[name] = hashlib.sha256(p.read_bytes()).hexdigest()
    return out


def _write_json(path: Path, doc: dict) -> None:
    tmp = path.with_suffix(path.suffix + ".tmp")
    tmp.write_text(json.dumps(doc, sort_keys=True, indent=1))
    os.replace(tmp, path)


def _member_config(config_path: str, overrides: dict, sweep_dir,
                   seed: int):
    from shadow_tpu.config import load_config

    over = dict(overrides or {})
    over["general.seed"] = int(seed)
    over["general.data_directory"] = str(seed_dir(sweep_dir, seed))
    # members never bind a live endpoint: M concurrent seeds would race
    # on one socket path, and a sweep is a batch artifact. The sweep
    # itself can expose a status-only endpoint (--live-endpoint).
    over["general.live_endpoint"] = None
    # cache_doc: one worker parses the (possibly multi-hundred-host)
    # YAML once per process, not once per seed — the compose step alone
    # cost more than the tor_400 round loop
    return load_config(config_path, over, cache_doc=True)


def _reap_stale_guests(d) -> int:
    """SIGKILL real-binary guests leaked by an interrupted managed member
    run. A worker that died mid-run (SIGKILL, OOM) never reaped the
    executables it spawned; their pids live in the seed directory's
    ``guest_pids.jsonl`` side plane. Pids get recycled on a busy box, so
    each one is verified against the recorded clock-page path via
    ``/proc/<pid>/environ`` before the kill: only a process that still
    carries OUR shm path in its environment is one of ours."""
    import signal

    p = Path(d) / "guest_pids.jsonl"
    if not p.is_file():
        return 0
    killed = 0
    for raw in p.read_text().splitlines():
        try:
            rec = json.loads(raw)
            pid, shm = int(rec["pid"]), str(rec.get("shm") or "")
        except (ValueError, KeyError, TypeError):
            continue
        if pid <= 1 or not shm:
            continue
        try:
            env = Path(f"/proc/{pid}/environ").read_bytes()
        except OSError:
            continue  # already gone (the common case)
        if shm.encode() not in env:
            continue  # pid recycled by an unrelated process: hands off
        try:
            os.kill(pid, signal.SIGKILL)
            killed += 1
        except OSError:
            pass
    return killed


def _fleet_chaos(sweep_dir, seed: int) -> None:
    """Worker-side hard-failure injection (CHAOS_KILL_ENV/CHAOS_WEDGE_ENV):
    die or hang just before running the listed seed, once per sweep. The
    O_EXCL marker is claimed BEFORE firing so recovery converges — the
    parent detects the dead/wedged member, respawns, retries the seed,
    and the second attempt finds the marker already claimed."""
    import signal as _signal

    for env, kind in ((CHAOS_KILL_ENV, "kill"), (CHAOS_WEDGE_ENV, "wedge")):
        spec = os.environ.get(env, "")  # detlint: ok(envread): loop var over the SHADOW_TPU_FLEET_CHAOS_* module constants
        if not spec or str(seed) not in spec.split(","):
            continue
        mark_dir = Path(sweep_dir) / "chaos"
        mark_dir.mkdir(parents=True, exist_ok=True)
        try:
            fd = os.open(mark_dir / f"{kind}.s{seed}.fired",
                         os.O_CREAT | os.O_EXCL | os.O_WRONLY)
            os.close(fd)
        except FileExistsError:
            continue  # fired on an earlier attempt: this retry runs clean
        print(f"fleet: CHAOS {kind} firing in worker for seed {seed}",
              file=sys.stderr, flush=True)
        if kind == "kill":
            os.kill(os.getpid(), _signal.SIGKILL)
        while True:  # wedge: hold the seed forever without progress
            _walltime.sleep(3600)


def _run_one_seed(config_path: str, overrides: dict, sweep_dir,
                  seed: int) -> dict:
    """Run one member simulation into its per-seed directory and write
    its manifest + mergeable telemetry state. Raises on failure (the
    worker loop converts that into a failed manifest + report)."""
    from shadow_tpu import checkpoint as _ckpt
    from shadow_tpu.core.controller import (VOLATILE_SUMMARY_KEYS,
                                            Controller)

    chaos = os.environ.get(CHAOS_ENV, "")
    if chaos and str(seed) in chaos.split(","):
        raise RuntimeError(
            f"chaos hook: seed {seed} configured to fail ({CHAOS_ENV})")
    _fleet_chaos(sweep_dir, seed)
    d = seed_dir(sweep_dir, seed)
    # a fresh member run owns its directory: stale partial output from an
    # earlier attempt must not survive into the hashes — and a managed
    # attempt that died mid-run may have leaked real guest processes
    # that would fight the re-run for ptrace/SIGSTOP control; reap them
    # before the tree goes away (the pid registry lives in it)
    stale = _reap_stale_guests(d)
    if stale:
        print(f"fleet: seed {seed}: reaped {stale} stale guest "
              f"process(es) from an interrupted earlier attempt",
              file=sys.stderr, flush=True)
    shutil.rmtree(d, ignore_errors=True)
    t0 = _walltime.perf_counter()
    cfg = _member_config(config_path, overrides, sweep_dir, seed)
    # mark the attempt in-flight BEFORE spawning anything: if this worker
    # dies mid-run, --resume sees status "running" (not "ok") and treats
    # the seed as failed instead of trusting the partial tree
    d.mkdir(parents=True, exist_ok=True)
    _write_json(d / SEED_MANIFEST, {
        "format": MANIFEST_FORMAT,
        "seed": int(seed),
        "status": "running",
        "config_digest": _ckpt.config_digest(cfg),
    })
    ctl = Controller(cfg, mirror_log=False)
    result = ctl.run()
    if ctl.telemetry is not None:
        (d / TEL_STATE_FILE).write_text(
            ctl.telemetry.export_state_json())
    wall = _walltime.perf_counter() - t0
    summary = {k: v for k, v in result.items()
               if k not in VOLATILE_SUMMARY_KEYS}
    man = {
        "format": MANIFEST_FORMAT,
        "seed": int(seed),
        "status": "ok",
        "config_digest": _ckpt.config_digest(cfg),
        "wall_seconds": round(wall, 3),
        "loop_wall_seconds": round(result["wall_seconds"], 3),
        "events": result["events"],
        "rounds": result["rounds"],
        "exit_reason": result["exit_reason"],
        "process_errors": result["process_errors"],
        "tree_sha256": output_tree_digest(d),
        "streams_sha256": _stream_digests(d),
        "summary": summary,
    }
    svc = result.get("device", {}).get("draw_service")
    if svc is not None:
        # how this member reached the parent's shared device plane; not
        # "connected" means the numpy twin served its draws
        man["draw_service"] = svc
    _write_json(d / SEED_MANIFEST, man)
    return man


def _write_failed_manifest(sweep_dir, seed, error: str,
                           tb: str = "") -> dict:
    if isinstance(seed, str):  # a fork branch failed, not a sweep seed
        from shadow_tpu import forks as _forks

        return _forks.write_failed_branch_manifest(sweep_dir, seed,
                                                   error, tb)
    d = seed_dir(sweep_dir, seed)
    d.mkdir(parents=True, exist_ok=True)
    man = {
        "format": MANIFEST_FORMAT,
        "seed": int(seed),
        "status": "failed",
        "error": error,
        "traceback": tb,
    }
    _write_json(d / SEED_MANIFEST, man)
    return man


def _fleet_worker_main(conn, config_path: str, overrides: dict,
                       sweep_dir: str, worker_idx: int,
                       service_addr, pin: bool, fork: dict = None) -> None:
    """Worker process entry: run seeds sequentially as they arrive. One
    interpreter for many seeds is the amortization lever (module doc)."""
    import gc as _gc
    import signal as _signal
    import traceback

    try:  # the parent owns signal policy (the sharded-worker discipline)
        _signal.signal(_signal.SIGINT, _signal.SIG_IGN)
    except (ValueError, OSError):
        pass
    if pin:
        try:
            ncpu = os.cpu_count() or 1
            os.sched_setaffinity(0, {worker_idx % ncpu})
        except (AttributeError, OSError):
            pass  # pinning is a locality hint, never a requirement
    # a member never attaches the chip itself: it reaches the parent's
    # shared plane, or (no service) runs the twins — "off" tells devroute
    os.environ[SERVICE_ENV] = str(service_addr) if service_addr else "off"
    seeds_run = 0
    while True:
        try:
            msg = conn.recv()
        except (EOFError, OSError):
            break
        if msg[0] == "exit":
            break
        seed = str(msg[1]) if fork is not None else int(msg[1])
        try:
            if fork is not None:
                from shadow_tpu import forks as _forks

                man = _forks.run_branch(fork, seed)
            else:
                man = _run_one_seed(config_path, overrides, sweep_dir,
                                    seed)
            conn.send(("done", seed, man))
        except BaseException as exc:
            tb = traceback.format_exc()
            try:
                _write_failed_manifest(sweep_dir, seed, str(exc), tb)
            except OSError:
                pass
            try:
                conn.send(("failed", seed, str(exc), tb))
            except (OSError, ValueError):
                break
            if not isinstance(exc, Exception):
                break  # KeyboardInterrupt/SystemExit: stop the worker
        seeds_run += 1
        if seeds_run % 3 == 0:
            # dead Controller graphs are mostly refcount-reclaimed; a
            # full cycle collection every few seeds bounds the rest
            # without paying ~0.1 s per seed
            _gc.collect()
    # everything durable is already on disk (manifests via os.replace)
    # and every protocol message is sent: skip the interpreter teardown
    # of a multi-GB simulation heap — the kernel reclaims it faster
    try:
        conn.close()
    except OSError:
        pass
    os._exit(0)


# -- the sweep runner (parent side) -------------------------------------------

def _proc_rss_mb(pid: int) -> float:
    try:
        with open(f"/proc/{pid}/statm") as f:
            pages = int(f.read().split()[1])
        return pages * os.sysconf("SC_PAGE_SIZE") / (1 << 20)
    except (OSError, ValueError, IndexError):
        return 0.0


def _default_rss_cap_mb() -> int:
    """80% of MemTotal — the admission guard's default budget."""
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemTotal:"):
                    return int(int(line.split()[1]) * 0.8) // 1024
    except (OSError, ValueError):
        pass
    return 0  # unknown: guard disabled


class FleetRunner:
    """Parent orchestrator: admission-bounded seed dispatch over ``jobs``
    pinned persistent workers + the shared DrawServer (module doc)."""

    def __init__(self, config_path: str, seeds: list, jobs: int,
                 sweep_dir, overrides: dict = None, resume: bool = False,
                 max_rss_mb: int = None, pin_cores: bool = True,
                 device_service: bool = True, quiet: bool = False,
                 live_endpoint: str = None, retries: int = 1,
                 member_max_rss_mb: int = 0, fork: dict = None) -> None:
        self.config_path = str(config_path)
        #: a validated fork plan (shadow_tpu.forks.plan_fork) turns the
        #: fleet into a fork orchestrator: ``seeds`` become branch names
        #: and every worker runs branches of ONE trunk checkpoint
        self.fork = fork
        if fork is not None:
            self.seeds = [str(s) for s in seeds]
            if resume:
                raise ValueError(
                    "a fork cannot --resume: branches are planned from "
                    "the trunk checkpoint each time — just re-run the "
                    "fork (completed branch directories are rebuilt)")
        else:
            self.seeds = [int(s) for s in seeds]
        if not self.seeds:
            raise ValueError("a sweep needs at least one seed")
        if len(set(self.seeds)) != len(self.seeds):
            raise ValueError(f"duplicate seeds in sweep: {self.seeds}")
        self.jobs = max(1, int(jobs))
        self.sweep_dir = Path(sweep_dir)
        self.overrides = dict(overrides or {})
        self.resume = bool(resume)
        self.max_rss_mb = (_default_rss_cap_mb() if max_rss_mb is None
                           else int(max_rss_mb))
        self.pin_cores = bool(pin_cores)
        self.device_service = bool(device_service)
        self.quiet = bool(quiet)
        self._server = None
        self._server_error = None  # DrawServer construction failure
        self._procs: list = []
        self._conns: list = []
        self._inflight: dict = {}  # worker idx -> seed
        self._respawns = 0
        #: bounded retry budget per seed (the supervisor discipline —
        #: supervise.run_supervised): a crashed, wedged, or raising seed
        #: is requeued up to ``retries`` times before it counts as failed
        self.retries = max(0, int(retries))
        #: per-member RSS ceiling (MB, 0 = off): a member over it is
        #: KILLED (failed manifest + crash report, no retry — a leak
        #: leaks again), unlike max_rss_mb which only delays admission
        self.member_max_rss_mb = max(0, int(member_max_rss_mb or 0))
        self._attempts: dict = {}  # seed -> dispatch attempts so far
        self._inflight_t: dict = {}  # worker idx -> dispatch monotonic
        self._seed_wall_ema = 0.0  # completed-seed wall EMA (stall basis)
        self._interrupted = False
        # sweep-level live endpoint (shadow_tpu/live.py): STATUS ONLY —
        # per-seed lifecycle records for dashboards. Runtime commands are
        # refused by name: a sweep is a batch of independent replayable
        # runs, and mutating one seed mid-sweep would fork its identity.
        self.live = None
        if live_endpoint:
            from shadow_tpu import live as _live

            self.live = _live.LiveServer(
                _live.resolve_endpoint(live_endpoint, self.sweep_dir),
                refuse=lambda norm: (
                    f"sweep endpoint is status-only: {norm['cmd']!r} "
                    f"would fork one seed's identity mid-sweep — attach "
                    f"to a single run's live_endpoint instead"))

    def _publish(self, rec: dict) -> None:
        if self.live is None:
            return
        if self.fork is not None and isinstance(rec.get("seed"), str):
            # forked sweeps stream per-BRANCH progress: same lifecycle
            # records, branch-keyed (branch_dispatched/branch_done/...)
            rec = dict(rec)
            rec["branch"] = rec.pop("seed")
            if isinstance(rec.get("type"), str):
                rec["type"] = rec["type"].replace("seed_", "branch_")
        self.live.publish(rec)

    def _log(self, msg: str) -> None:
        if not self.quiet:
            print(f"fleet: {msg}", file=sys.stderr, flush=True)

    # -- resume ------------------------------------------------------------
    def _completed_seeds(self) -> dict:
        """seed -> manifest for every seed already completed under THIS
        config (status ok + config_digest match); everything else
        re-runs."""
        from shadow_tpu import checkpoint as _ckpt

        done = {}
        for seed in self.seeds:
            p = seed_dir(self.sweep_dir, seed) / SEED_MANIFEST
            if not p.is_file():
                continue
            try:
                man = json.loads(p.read_text())
            except ValueError:
                continue
            if (man.get("format") != MANIFEST_FORMAT
                    or man.get("status") != "ok"):
                continue
            cfg = _member_config(self.config_path, self.overrides,
                                 self.sweep_dir, seed)
            if man.get("config_digest") == _ckpt.config_digest(cfg):
                done[seed] = man
        return done

    # -- workers -----------------------------------------------------------
    def _mp_ctx(self):
        """fork when safe (jax not yet imported in this process — the
        parent deliberately defers the DrawServer's jax import until
        after the workers exist), else spawn. A forked worker inherits
        the parsed-config cache and every pre-imported simulation
        module, which removes the per-worker cold start entirely."""
        import multiprocessing as mp

        if "jax" in sys.modules or not hasattr(os, "fork"):
            return mp.get_context("spawn")
        return mp.get_context("fork")

    def _spawn_worker(self, idx: int):
        ctx = self._mp_ctx()
        parent_conn, child_conn = ctx.Pipe()
        p = ctx.Process(
            target=_fleet_worker_main,
            args=(child_conn, self.config_path, self.overrides,
                  str(self.sweep_dir), idx, self._service_addr,
                  self.pin_cores, self.fork),
            name=f"shadow-fleet-{idx}", daemon=True)
        p.start()
        child_conn.close()
        return p, parent_conn

    def _rss_ok(self) -> bool:
        if not self.max_rss_mb or not self._inflight:
            return True  # nothing resident (or guard off): always admit
        total = sum(_proc_rss_mb(p.pid) for p in self._procs
                    if p is not None and p.is_alive())
        return total < self.max_rss_mb

    # -- the sweep ---------------------------------------------------------
    def run(self) -> dict:
        t_sweep = _walltime.perf_counter()
        self.sweep_dir.mkdir(parents=True, exist_ok=True)
        # validate the config up front: a typo should fail the sweep in
        # milliseconds, not once per worker (a fork plan was already
        # validated end to end by forks.plan_fork)
        if self.fork is None:
            _member_config(self.config_path, self.overrides,
                           self.sweep_dir, self.seeds[0])
        failed: dict = {}
        skipped: list = []
        pending = list(self.seeds)
        if self.resume:
            done = self._completed_seeds()
            skipped = sorted(done)
            pending = [s for s in pending if s not in done]
            self._log(f"resume: {len(skipped)} seed(s) already complete, "
                      f"{len(pending)} to run")
        self._service_addr = None
        server_thread = None
        if self.device_service and pending:
            # choose the socket path NOW (workers need it at spawn) but
            # build the server — which imports jax — on a background
            # thread AFTER the workers exist: with jax unimported the
            # workers fork instantly and start their first seeds while
            # the parent compiles. Members retry a not-yet-listening
            # socket (FleetDrawClient.connect), running the numpy twin
            # until the shared plane publishes.
            import tempfile
            import threading

            d = tempfile.mkdtemp(prefix="stpu_draw_")
            os.chmod(d, 0o700)
            self._service_addr = os.path.join(d, "sock")
        # pre-import the simulation stack (no jax in any of it): forked
        # workers inherit warm modules + the parsed-config doc cache
        import shadow_tpu.checkpoint  # noqa: F401
        import shadow_tpu.core.controller  # noqa: F401
        import shadow_tpu.faults  # noqa: F401
        import shadow_tpu.models.echo  # noqa: F401
        import shadow_tpu.models.gossip  # noqa: F401
        import shadow_tpu.models.tgen  # noqa: F401
        import shadow_tpu.models.tor  # noqa: F401
        import shadow_tpu.network.colplane  # noqa: F401
        import shadow_tpu.network.engine  # noqa: F401
        import shadow_tpu.telemetry.collector  # noqa: F401
        try:
            from shadow_tpu.native import _colcore  # noqa: F401
        except ImportError:
            pass
        try:
            if pending:
                n_workers = min(self.jobs, len(pending))
                for k in range(n_workers):
                    p, conn = self._spawn_worker(k)
                    self._procs.append(p)
                    self._conns.append(conn)
                if self._service_addr is not None:
                    def _build_server():
                        try:
                            # the jax import is background amortization:
                            # take it mildly off the members' first
                            # seeds (per-thread nice; the serving path
                            # resets itself — see DrawServer)
                            os.setpriority(os.PRIO_PROCESS,
                                           threading.get_native_id(), 5)
                        except (AttributeError, OSError):
                            pass
                        try:
                            from shadow_tpu.config import load_config
                            from shadow_tpu.ops.propagate import DrawServer

                            if self.fork is not None:
                                # branches share the trunk's plane shape
                                cfg0 = load_config(
                                    self.config_path,
                                    dict(self.fork["overrides"]),
                                    cache_doc=True)
                            else:
                                cfg0 = _member_config(
                                    self.config_path, self.overrides,
                                    self.sweep_dir, self.seeds[0])
                            self._server = DrawServer(
                                cfg0.general.seed,
                                cfg0.experimental.tpu_max_batch,
                                cfg0.experimental.tpu_mesh_shards,
                                cfg0.experimental.unit_mtus,
                                address=self._service_addr)
                        except Exception as exc:
                            # members run the twin (a member never
                            # attaches the chip); the sweep fails with it
                            self._server_error = exc
                            self._log(f"draw service failed: {exc}")

                    server_thread = threading.Thread(
                        target=_build_server, name="fleet-draw-server",
                        daemon=True)
                    server_thread.start()
                try:
                    self._dispatch_loop(pending, failed)
                except KeyboardInterrupt:
                    # mid-sweep interrupt: tear down coherently instead
                    # of unwinding through worker pipes — kill in-flight
                    # members, reap the guests they leaked, record their
                    # seeds as interrupted. The summary below is a valid
                    # partial artifact; --resume finishes the sweep.
                    self._interrupted = True
                    self._log("interrupted — tearing down in-flight "
                              "members")
                    for k in list(self._inflight):
                        seed = self._inflight[k]
                        self._kill_member(k)
                        try:
                            _write_failed_manifest(self.sweep_dir, seed,
                                                   "interrupted")
                        except OSError:
                            pass
                        failed[seed] = "interrupted"
                    self._inflight.clear()
                    self._inflight_t.clear()
        finally:
            if server_thread is not None:
                server_thread.join(timeout=120)
            for k, conn in enumerate(self._conns):
                if conn is not None:
                    try:
                        conn.send(("exit",))
                    except (OSError, ValueError):
                        pass
            for p in self._procs:
                if p is not None:
                    p.join(timeout=10)
                    if p.is_alive():
                        p.terminate()
            if self._server is not None:
                self._server.close()
        wall = _walltime.perf_counter() - t_sweep
        service_doc = ({"draw_service": {
            "served_batches": self._server.served_batches,
            "served_units": self._server.served_units,
            "attach_wall_seconds": round(self._server.attach_wall, 3),
        }} if self._server is not None else {})
        err = self._server_error or getattr(self._server, "error", None)
        if err is not None:
            # the shared plane never came up: reported, and the sweep's
            # exit status says so (sweep_exit_code)
            service_doc.setdefault("draw_service", {})["error"] = (
                f"{type(err).__name__}: {err}")
        if self.fork is not None:
            from shadow_tpu import forks as _forks

            fork_doc = {
                "config": self.config_path,
                "jobs": self.jobs,
                "branches_planned": self.seeds,
                "trunk_checkpoint": self.fork["ckpt"],
                "trunk_dir": self.fork["trunk_dir"],
                "failed": {str(s): failed[s] for s in sorted(failed)},
                "fork_wall_seconds": round(wall, 3),
                "exit_reason": ("interrupted" if self._interrupted
                                else "completed"),
                "retries": self.retries,
                "respawns": self._respawns,
                **service_doc,
            }
            summary = _forks.reduce_fork(self.sweep_dir, extra=fork_doc)
            n_ok = len(summary["completed"])
            self._log(f"fork done: {n_ok}/{len(self.seeds)} branch(es) "
                      f"ok, {len(failed)} failed, wall {wall:.1f}s -> "
                      f"{self.sweep_dir / _forks.FORK_SUMMARY}")
            if self.live is not None:
                self._publish({"type": "end", "ok": n_ok,
                               "failed": len(failed),
                               "wall_seconds": round(wall, 1)})
                self.live.close()
            return summary
        sweep_doc = {
            "config": self.config_path,
            "jobs": self.jobs,
            "seeds": self.seeds,
            "skipped_resume": sorted(skipped),
            "failed": {str(s): failed[s] for s in sorted(failed)},
            "sweep_wall_seconds": round(wall, 3),
            "exit_reason": ("interrupted" if self._interrupted
                            else "completed"),
            "retries": self.retries,
            "respawns": self._respawns,
            **service_doc,
        }
        summary = reduce_sweep(self.sweep_dir, extra=sweep_doc)
        n_ok = len(summary["completed"])
        self._log(f"sweep done: {n_ok}/{len(self.seeds)} seeds ok, "
                  f"{len(failed)} failed, wall {wall:.1f}s -> "
                  f"{self.sweep_dir / SWEEP_SUMMARY}")
        if self.live is not None:
            self._publish({"type": "end", "ok": n_ok,
                           "failed": len(failed),
                           "wall_seconds": round(wall, 1)})
            self.live.close()
        return summary

    def _dispatch_loop(self, pending: list, failed: dict) -> None:
        from multiprocessing.connection import wait as _mpwait

        idle = list(range(len(self._procs)))
        rss_note = 0.0
        while pending or self._inflight:
            # admission: one seed per idle worker, RSS-guarded
            while pending and idle:
                if not self._rss_ok():
                    now = _walltime.monotonic()
                    if now - rss_note > 10:
                        rss_note = now
                        self._log(
                            f"admission paused: fleet RSS over "
                            f"{self.max_rss_mb} MB "
                            f"({len(self._inflight)} resident)")
                    break
                k = idle.pop(0)
                seed = pending.pop(0)
                try:
                    self._conns[k].send(("run", seed))
                except (OSError, ValueError):
                    # worker died before taking the seed: requeue it,
                    # replace the worker, and return the slot to the
                    # idle pool
                    pending.insert(0, seed)
                    self._on_worker_death(k, pending, failed, idle)
                    continue
                self._inflight[k] = seed
                self._inflight_t[k] = _walltime.monotonic()
                self._attempts[seed] = self._attempts.get(seed, 0) + 1
                self._log(f"seed {seed} -> worker {k} "
                          f"({len(pending)} queued, "
                          f"{len(self._inflight)} resident)")
                self._publish({"type": "seed_dispatched", "seed": seed,
                               "worker": k, "queued": len(pending),
                               "resident": len(self._inflight)})
            live = [c for c in self._conns if c is not None]
            if not live:
                break
            ready = _mpwait(live, timeout=0.5)
            self._check_members(pending, failed, idle)
            for conn in ready:
                k = self._conns.index(conn)
                try:
                    msg = conn.recv()
                except (EOFError, OSError):
                    self._on_worker_death(k, pending, failed,
                                          idle)
                    continue
                op = msg[0]
                if op == "done":
                    _, seed, man = msg
                    self._inflight.pop(k, None)
                    t0 = self._inflight_t.pop(k, None)
                    if t0 is not None:
                        # completed-seed wall EMA: the basis the member
                        # stall deadline is derived from
                        dt = _walltime.monotonic() - t0
                        self._seed_wall_ema = (
                            dt if self._seed_wall_ema == 0.0
                            else 0.7 * self._seed_wall_ema + 0.3 * dt)
                    idle.append(k)
                    self._log(f"seed {seed} ok "
                              f"({man['wall_seconds']}s wall, "
                              f"{man['events']} events)")
                    self._publish({"type": "seed_done", "seed": seed,
                                   "wall_seconds": man["wall_seconds"],
                                   "events": man["events"],
                                   "rounds": man["rounds"]})
                elif op == "failed":
                    _, seed, err, tb = msg
                    self._inflight.pop(k, None)
                    self._inflight_t.pop(k, None)
                    idle.append(k)
                    self._seed_failed(seed, err, pending, failed)
                else:
                    self._inflight.pop(k, None)
                    self._inflight_t.pop(k, None)
                    idle.append(k)

    def _seed_failed(self, seed: int, err: str, pending: list,
                     failed: dict) -> None:
        """One attempt at a seed failed (member raised, died, wedged, or
        hit a ceiling). Bounded retry budget, the supervisor discipline:
        requeue while attempts remain, else record failed — the final
        failed manifest is whatever the last attempt wrote."""
        attempts = self._attempts.get(seed, 1)
        if attempts <= self.retries:
            left = self.retries - attempts + 1
            self._log(f"seed {seed} attempt {attempts} failed: {err} — "
                      f"retrying ({left} retr{'y' if left == 1 else 'ies'}"
                      f" left)")
            self._publish({"type": "seed_retry", "seed": seed,
                           "attempt": attempts, "error": err})
            pending.append(seed)
            return
        failed[seed] = err
        self._log(f"seed {seed} FAILED after {attempts} attempt(s): "
                  f"{err} — sweep continues")
        self._publish({"type": "seed_failed", "seed": seed, "error": err,
                       "attempts": attempts})

    def _member_deadline_s(self):
        """Wall seconds an in-flight member may run before it counts as
        wedged; None = no deadline yet (no completed-seed EMA basis)."""
        fixed = float(os.environ.get(FLEET_STALL_ENV, "0") or 0.0)
        if fixed > 0:
            return fixed
        if self._seed_wall_ema <= 0.0:
            return None
        from shadow_tpu.supervise import stall_deadline_s

        # the supervise deadline curve over the seed-wall EMA, floored at
        # a minute: seeds are whole simulations, not rounds
        return max(stall_deadline_s(self._seed_wall_ema), 60.0)

    def _kill_member(self, k: int) -> None:
        """SIGKILL worker k and reap any real-binary guests its in-flight
        managed seed leaked (guest_pids.jsonl side plane)."""
        p = self._procs[k]
        try:
            if p is not None and p.is_alive():
                p.kill()
        except (OSError, AttributeError):
            pass
        if p is not None:
            p.join(timeout=10)
        seed = self._inflight.get(k)
        if seed is not None:
            _reap_stale_guests(seed_dir(self.sweep_dir, seed))

    def _check_members(self, pending: list, failed: dict,
                       idle: list) -> None:
        """Liveness + resource policing of in-flight members, once per
        dispatch-loop tick: (a) a member past the stall deadline is
        wedged — kill it and retry the seed on a fresh worker; (b) a
        member over the per-member RSS ceiling is leaking — kill it,
        write a crash report, and do NOT retry (a leak leaks again)."""
        if not self._inflight:
            return
        deadline = self._member_deadline_s()
        now = _walltime.monotonic()
        for k in list(self._inflight):
            seed = self._inflight[k]
            p = self._procs[k]
            if self.member_max_rss_mb and p is not None and p.is_alive():
                rss = _proc_rss_mb(p.pid)
                if rss > self.member_max_rss_mb:
                    err = (f"member RSS {rss:.0f} MB over the per-member "
                           f"ceiling {self.member_max_rss_mb} MB — killed")
                    self._log(f"seed {seed}: {err}")
                    self._kill_member(k)
                    from shadow_tpu import supervise as _sup

                    d = seed_dir(self.sweep_dir, seed)
                    d.mkdir(parents=True, exist_ok=True)
                    try:
                        _sup.write_crash_report(
                            d, "member_rss_ceiling",
                            extra={"seed": seed if isinstance(seed, str)
                                   else int(seed),
                                   "rss_mb": round(rss, 1),
                                   "ceiling_mb": self.member_max_rss_mb})
                    except OSError:
                        pass
                    # exhaust the budget: an OOM-class failure is not
                    # transient, rerunning it just OOMs the box later
                    self._attempts[seed] = self.retries + 1
                    self._on_worker_death(k, pending, failed, idle,
                                          reason=err)
                    continue
            t0 = self._inflight_t.get(k)
            if deadline is None or t0 is None or now - t0 <= deadline:
                continue
            err = (f"member wedged: no completion after {now - t0:.1f}s "
                   f"(deadline {deadline:.1f}s) — killed by the fleet "
                   f"watchdog")
            self._log(f"seed {seed}: {err}")
            self._kill_member(k)
            self._on_worker_death(k, pending, failed, idle, reason=err)

    def _on_worker_death(self, k: int, pending: list,
                         failed: dict, idle: list,
                         reason: str = None) -> None:
        """A worker process died (hard crash, OOM kill, or the fleet
        watchdog killed it): route its in-flight seed through the retry
        budget and respawn so the rest of the sweep continues — one
        crashed seed never sinks the fleet."""
        p = self._procs[k]
        code = p.exitcode if p is not None else None
        seed = self._inflight.pop(k, None)
        self._inflight_t.pop(k, None)
        if seed is not None:
            err = reason or f"worker process died (exit code {code})"
            try:
                _write_failed_manifest(self.sweep_dir, seed, err)
            except OSError:
                pass
            self._seed_failed(seed, err, pending, failed)
        try:
            self._conns[k].close()
        except OSError:
            pass
        self._conns[k] = None
        self._procs[k] = None
        self._respawns += 1
        if self._respawns > 2 * (len(self.seeds) * (self.retries + 1)
                                 + self.jobs):
            raise RuntimeError(
                "fleet: worker respawn limit exceeded — the environment "
                "is killing workers faster than seeds can run")
        np_, nc = self._spawn_worker(k)
        self._procs[k] = np_
        self._conns[k] = nc
        if k not in idle:
            idle.append(k)


# -- the reducer --------------------------------------------------------------

#: two-sided 95% Student-t critical values by degrees of freedom (the
#: n<=31 sweep sizes this box runs; beyond that the normal 1.96 is within
#: rounding of t). Source: standard t tables, 3 decimals.
_T95 = {1: 12.706, 2: 4.303, 3: 3.182, 4: 2.776, 5: 2.571, 6: 2.447,
        7: 2.365, 8: 2.306, 9: 2.262, 10: 2.228, 11: 2.201, 12: 2.179,
        13: 2.160, 14: 2.145, 15: 2.131, 16: 2.120, 17: 2.110, 18: 2.101,
        19: 2.093, 20: 2.086, 21: 2.080, 22: 2.074, 23: 2.069, 24: 2.064,
        25: 2.060, 26: 2.056, 27: 2.052, 28: 2.048, 29: 2.045, 30: 2.042}


def t_ci95(vals: list) -> dict:
    """t-based 95% CI of the mean of per-seed statistics (the cross-run
    inference "Once is Never Enough" prescribes: the statistic is
    computed per run, the interval across runs)."""
    n = len(vals)
    if n == 0:
        return {"n": 0}
    mean = sum(vals) / n
    if n == 1:
        return {"n": 1, "mean": round(mean, 3)}
    var = sum((v - mean) ** 2 for v in vals) / (n - 1)
    s = math.sqrt(var)
    t = _T95.get(n - 1, 1.960)
    hw = t * s / math.sqrt(n)
    return {"n": n, "mean": round(mean, 3), "stdev": round(s, 3),
            "lo": round(mean - hw, 3), "hi": round(mean + hw, 3),
            "half_width": round(hw, 3)}


def reduce_sweep(sweep_dir, extra: dict = None) -> dict:
    """K-way merge the per-seed histogram states + manifests under
    ``sweep_dir`` into ``sweep_summary.json`` (module doc). Idempotent:
    pure function of the on-disk per-seed artifacts."""
    from shadow_tpu.telemetry.histogram import LogHistogram

    sweep_dir = Path(sweep_dir)
    if extra is None:
        # re-reduction (the report subcommand): carry the original run's
        # orchestration metadata forward instead of erasing it
        try:
            prev = json.loads((sweep_dir / SWEEP_SUMMARY).read_text())
            extra = {k: prev[k] for k in
                     ("config", "jobs", "seeds", "skipped_resume",
                      "sweep_wall_seconds", "draw_service")
                     if k in prev}
        except (OSError, ValueError):
            extra = None
    # a sweep's seed roster bounds the reduction: seed dirs left behind
    # by an earlier, differently-scoped sweep into the same directory
    # must not pollute the pooled histograms or inflate the CIs
    roster = set((extra or {}).get("seeds") or ()) or None
    manifests = []
    for p in sorted(sweep_dir.glob("seed_*/" + SEED_MANIFEST),
                    key=lambda p: int(p.parent.name.split("_", 1)[1])):
        try:
            man = json.loads(p.read_text())
        except ValueError:
            continue
        if man.get("format") != MANIFEST_FORMAT:
            continue
        if roster is not None and man.get("seed") not in roster:
            continue
        manifests.append(man)
    completed = [m for m in manifests if m.get("status") == "ok"]
    failed = {str(m["seed"]): m.get("error", "unknown")
              for m in manifests if m.get("status") != "ok"}
    # per-seed mergeable telemetry states, in seed order
    states = []  # (seed, state)
    for m in completed:
        p = seed_dir(sweep_dir, m["seed"]) / TEL_STATE_FILE
        if p.is_file():
            try:
                states.append((m["seed"], json.loads(p.read_text())))
            except ValueError:
                pass
    flows: dict = {}
    kinds = sorted({k for _s, st in states for k in st["flow_counts"]})
    labels = ("p50_ms", "p90_ms", "p99_ms", "p99_9_ms")
    for kind in kinds:
        pooled = LogHistogram.merged(
            [st["hist"][kind] for _s, st in states
             if kind in st["hist"]])
        per_seed = {lab: [] for lab in labels}
        seeds_with = []
        ok = failed_n = x_sum = x_n = 0
        for s, st in states:
            c = st["flow_counts"].get(kind)
            if c is not None:
                ok += c["ok"]
                failed_n += c["failed"]
                x_sum += c.get("x_sum", 0)
                x_n += c.get("x_n", 0)
            hs = st["hist"].get(kind)
            if hs is None:
                continue
            q = LogHistogram.from_state(hs).quantiles_ns_to_ms()
            seeds_with.append(s)
            for lab in labels:
                per_seed[lab].append(q[lab])
        flows[kind] = {
            "count": ok + failed_n,
            "ok": ok,
            "failed": failed_n,
            "pooled": pooled.quantiles_ns_to_ms(),
            "seeds": seeds_with,
            "per_seed": per_seed,
            "ci95": {lab: t_ci95(per_seed[lab]) for lab in labels},
        }
        if x_n:
            flows[kind]["x_mean"] = x_sum // x_n
    doc = {
        "format": SUMMARY_FORMAT,
        "n_seeds": len(manifests),
        "completed": [m["seed"] for m in completed],
        "failed": failed,
        "per_seed_wall_seconds": {
            str(m["seed"]): m.get("wall_seconds") for m in completed},
        "events_total": sum(m.get("events", 0) for m in completed),
        "flows": flows,
        **(extra or {}),
    }
    _write_json(sweep_dir / SWEEP_SUMMARY, doc)
    return doc


def render_report(summary: dict) -> str:
    """Human-readable sweep report (tools/metrics_report.py lineage)."""
    lines = []
    n_ok = len(summary.get("completed", []))
    failed = summary.get("failed", {})
    lines.append(
        f"sweep: {summary.get('n_seeds', n_ok)} seed(s), {n_ok} ok, "
        f"{len(failed)} failed"
        + (f", jobs={summary['jobs']}" if "jobs" in summary else "")
        + (f", wall {summary['sweep_wall_seconds']}s"
           if "sweep_wall_seconds" in summary else ""))
    for s, err in sorted(failed.items(), key=lambda kv: kv[0]):
        lines.append(f"  FAILED seed {s}: {err}")
    svc = summary.get("draw_service")
    if svc:
        lines.append(
            f"  shared draw service: {svc['served_batches']} batches / "
            f"{svc['served_units']} units served, one attach "
            f"({svc['attach_wall_seconds']}s)")
    flows = summary.get("flows", {})
    if not flows:
        lines.append("  (no flow telemetry recorded — enable telemetry "
                     "for cross-seed percentile CIs)")
        return "\n".join(lines)
    lines.append("")
    hdr = (f"  {'flow group':<18} {'n':>8} {'ok':>8} "
           f"{'pooled p50/p90/p99 ms':>26}   "
           f"{'p50 CI95':>20} {'p99 CI95':>20}")
    lines.append(hdr)
    lines.append("  " + "-" * (len(hdr) - 2))

    def ci_str(ci):
        if ci.get("n", 0) < 2:
            return f"{ci.get('mean', '-')} (n<2)"
        return f"{ci['mean']:.1f} ± {ci['half_width']:.1f}"

    for kind in sorted(flows):
        f = flows[kind]
        pooled = f["pooled"]
        lines.append(
            f"  {kind:<18} {f['count']:>8} {f['ok']:>8} "
            f"{pooled['p50_ms']:>8.1f}/{pooled['p90_ms']:>7.1f}/"
            f"{pooled['p99_ms']:>8.1f}   "
            f"{ci_str(f['ci95']['p50_ms']):>20} "
            f"{ci_str(f['ci95']['p99_ms']):>20}")
    lines.append("")
    lines.append("  CI95: t-based over per-seed percentiles (seeds are "
                 "the independent unit; pooled = all seeds merged into "
                 "one histogram)")
    return "\n".join(lines)


# -- CLI ----------------------------------------------------------------------

def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m shadow_tpu.fleet",
        description="fleet mode: N-seed simulation sweeps with mergeable "
                    "cross-run statistics")
    sub = p.add_subparsers(dest="cmd", required=True)
    ps = sub.add_parser("sweep", help="run an N-seed sweep")
    ps.add_argument("config", help="simulation YAML config file")
    ps.add_argument("--seeds", type=int, default=10, metavar="N",
                    help="number of seeds (base, base+1, ..., base+N-1); "
                    "default 10")
    ps.add_argument("--seed-base", type=int, default=None,
                    help="first seed (default: the config's general.seed)")
    ps.add_argument("--jobs", type=int, default=2, metavar="M",
                    help="concurrent member simulations (default 2)")
    ps.add_argument("--sweep-dir", default=None,
                    help="sweep output root (default: <config-stem>.sweep)")
    ps.add_argument("--resume", action="store_true",
                    help="skip seeds whose per-seed manifest is already "
                    "complete under this config")
    ps.add_argument("--stop-time", help="override general.stop_time")
    ps.add_argument("--set", action="append", default=[],
                    metavar="KEY=VALUE",
                    help="override any config option by dotted path; "
                    "repeatable")
    ps.add_argument("--max-rss-mb", type=int, default=None,
                    help="admission guard: pause handing out new seeds "
                    "while fleet RSS exceeds this (default: 80%% of "
                    "MemTotal; 0 disables)")
    ps.add_argument("--member-max-rss-mb", type=int, default=0,
                    metavar="MB",
                    help="per-member RSS ceiling: a member over it is "
                    "killed, its seed recorded failed with a crash "
                    "report, and NOT retried (default 0 = off)")
    ps.add_argument("--retries", type=int, default=1, metavar="N",
                    help="bounded retry budget per seed: a crashed or "
                    "wedged seed is requeued up to N times before it "
                    "counts as failed (default 1; 0 disables)")
    ps.add_argument("--no-pin", action="store_true",
                    help="do not pin worker processes to cores")
    ps.add_argument("--no-device-service", action="store_true",
                    help="members attach the device individually instead "
                    "of sharing the parent's attach")
    ps.add_argument("--no-telemetry", action="store_true",
                    help="do not auto-enable telemetry (no flow "
                    "percentiles or CIs in the sweep summary)")
    ps.add_argument("--live-endpoint", metavar="PATH",
                    help="bind a STATUS-ONLY AF_UNIX endpoint streaming "
                    "per-seed lifecycle records (dispatched/done/failed); "
                    "runtime commands are refused — 'auto' = "
                    "<sweep-dir>/live.sock")
    ps.add_argument("--quiet", action="store_true",
                    help="no progress lines on stderr")
    ps.add_argument("--json", action="store_true",
                    help="print the sweep summary as one JSON line on "
                    "stdout instead of the report")
    ps.add_argument("--fork-from", metavar="CKPT", default=None,
                    help="fork mode (shadow_tpu/forks.py): restore this "
                    "trunk checkpoint into every worker and run the "
                    "--branches divergence specs instead of seeds")
    ps.add_argument("--branches", metavar="FILE", default=None,
                    help="branches.yaml for --fork-from: the per-branch "
                    "divergence specs")
    ps.add_argument("--trunk-dir", metavar="DIR", default=None,
                    help="the trunk run directory for --fork-from "
                    "(default: derived from the checkpoint path's "
                    "<trunk>/checkpoints/ layout)")
    pr = sub.add_parser("report",
                        help="re-reduce + render a sweep (or fork) "
                        "directory")
    pr.add_argument("sweep_dir")
    pr.add_argument("--json", action="store_true",
                    help="print the summary JSON instead of the report")
    pr.add_argument("--compare", action="store_true",
                    help="fork directories: render only the comparative "
                    "table (per-group percentile deltas vs the trunk "
                    "with CI95)")
    return p


def _sweep_overrides(args, fork: bool = False) -> dict:
    import yaml as _yaml

    over: dict = {}
    if args.stop_time:
        over["general.stop_time"] = args.stop_time
    for item in args.set:
        if "=" not in item:
            print(f"fleet: --set expects KEY=VALUE, got {item!r}",
                  file=sys.stderr)
            raise SystemExit(2)
        k, v = item.split("=", 1)
        over[k] = _yaml.safe_load(v)
    if fork:
        # a fork inherits the trunk's telemetry settings verbatim —
        # auto-enabling here would re-cadence streams the trunk already
        # started (forks.plan_fork refuses explicit telemetry overrides
        # with the full story)
        return over
    if not args.no_telemetry and not any(
            k.startswith("telemetry") for k in over):
        # the whole point of a sweep is cross-seed percentiles: enable
        # the telemetry subsystem (at its default cadence) unless the
        # config/overrides already speak for it — a standalone run with
        # the same telemetry settings stays byte-identical
        from shadow_tpu.config.schema import load_yaml_doc

        if "telemetry" not in (load_yaml_doc(args.config, cache=True)
                               or {}):
            over["telemetry.sample_every"] = "10s"
    return over


def _is_fork_dir(d) -> bool:
    from shadow_tpu import forks as _forks

    d = Path(d)
    return ((d / _forks.FORK_SUMMARY).is_file()
            or any(sorted(d.glob("branch_*/" + _forks.FORK_MANIFEST))))


def sweep_exit_code(summary: dict) -> int:
    """0 when every member succeeded and the shared draw service (if
    any) came up; 1 otherwise."""
    bad = summary["failed"] or summary.get("draw_service", {}).get("error")
    return 1 if bad else 0


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.cmd == "report":
        if _is_fork_dir(args.sweep_dir):
            from shadow_tpu import forks as _forks

            summary = _forks.reduce_fork(args.sweep_dir)
            print(json.dumps(summary) if args.json
                  else _forks.render_compare(summary) if args.compare
                  else _forks.render_fork_report(summary))
            return sweep_exit_code(summary)
        if args.compare:
            print(f"fleet: {args.sweep_dir} is a seed sweep, not a fork "
                  f"— --compare diffs fork branches against their trunk",
                  file=sys.stderr)
            return 2
        summary = reduce_sweep(args.sweep_dir)
        print(json.dumps(summary) if args.json
              else render_report(summary))
        return sweep_exit_code(summary)
    try:
        fork_plan = None
        if args.fork_from or args.branches:
            if not (args.fork_from and args.branches):
                print("fleet: --fork-from and --branches go together "
                      "(a fork needs both the trunk checkpoint and the "
                      "divergence specs)", file=sys.stderr)
                return 2
            if args.resume:
                print("fleet: a fork cannot --resume — just re-run it",
                      file=sys.stderr)
                return 2
        over = _sweep_overrides(args, fork=bool(args.fork_from))
        if args.fork_from:
            from shadow_tpu import forks as _forks

            sweep_dir = (args.sweep_dir
                         or (Path(args.config).stem + ".fork"))
            branches = _forks.load_branches(args.branches)
            fork_plan = _forks.plan_fork(
                args.config, args.fork_from, branches, sweep_dir,
                overrides=over, trunk_dir=args.trunk_dir)
            seeds = fork_plan["order"]
        else:
            if args.seed_base is not None:
                base = int(args.seed_base)
            else:
                from shadow_tpu.config.schema import load_yaml_doc

                doc = load_yaml_doc(args.config, cache=True)
                base = int(((doc or {}).get("general") or {})
                           .get("seed", 1))
            seeds = [base + i for i in range(int(args.seeds))]
            sweep_dir = (args.sweep_dir
                         or (Path(args.config).stem + ".sweep"))
        runner = FleetRunner(
            args.config, seeds, args.jobs, sweep_dir, overrides=over,
            resume=args.resume, max_rss_mb=args.max_rss_mb,
            pin_cores=not args.no_pin,
            device_service=not args.no_device_service, quiet=args.quiet,
            live_endpoint=args.live_endpoint, retries=args.retries,
            member_max_rss_mb=args.member_max_rss_mb, fork=fork_plan)
        summary = runner.run()
    except FileNotFoundError as exc:
        print(f"fleet: config file not found: "
              f"{getattr(exc, 'filename', None) or exc}", file=sys.stderr)
        return 2
    except (ValueError, OSError) as exc:
        print(f"fleet: {exc}", file=sys.stderr)
        return 2
    if fork_plan is not None:
        from shadow_tpu import forks as _forks

        print(json.dumps(summary) if args.json
              else _forks.render_fork_report(summary))
    else:
        print(json.dumps(summary) if args.json
              else render_report(summary))
    if summary.get("exit_reason") == "interrupted":
        return 130  # conventional SIGINT status; the summary above is a
        # valid partial artifact and --resume finishes the sweep
    return sweep_exit_code(summary)


if __name__ == "__main__":
    sys.exit(main())
