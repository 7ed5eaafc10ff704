"""TPU kernel for the data-plane hot math: batched per-packet loss draws.

Round-2 redesign. The bucket/departure math moved to a closed form with ONE
host-side implementation (shadow_tpu/network/fluid.py::TokenBuckets) — it is
O(1) integer work per unit and needs no twin. What remains hot is the loss
sampling: 20-round threefry2x32 × MAX_PKTS counters per unit (hundreds of
integer ops each), a pure function of (seed, uid, npkts, threshold) with no
state and no feedback — exactly the shape the TPU's vector unit wants.

Design notes (TPU-first):
- Stateless kernel: the host gathers each unit's q24 drop threshold from the
  (G,G) table and ships ONE packed (4, P) uint32 array; the kernel returns
  one (P,) bool. No device-resident state, no donation, no coherence with
  the host bucket state — which also makes the kernel trivially shardable
  over a mesh (shadow_tpu/parallel/) and lets small batches route to the
  bit-identical numpy twin (fluid.loss_flags) with no semantic difference.
- Static shapes: batches pad to power-of-two buckets between MIN_BUCKET and
  the configured cap, so at most ~log2(cap) shapes ever compile.
- Deferred readback: results are copied device->host asynchronously
  (copy_to_host_async) and only *consumed* when the simulation clock reaches
  the batch's causal deadline — the earliest time any unit's arrival or
  loss notification can fire, which the engine computes host-side without
  the flags. The readback overlaps subsequent rounds instead of stalling
  each one.
- calibrate() measures the real dispatch+readback latency and the numpy
  twin's per-unit cost once at startup, giving the engine an evidence-based
  floor for routing batches. Because both paths produce identical flags and
  event ordering is canonicalized (core/events.py BAND_NET), the floor can
  NOT affect simulation results — calibration is determinism-safe.
"""

from __future__ import annotations

import functools
import time as _walltime  # detlint: ok(wallclock): device attach/calibration wall measures

import numpy as np

import jax
import jax.numpy as jnp

from shadow_tpu.core.spans import Recorder, rss_bytes, span
from shadow_tpu.network.fluid import MAX_PKTS, PKT_SHIFT, loss_flags

#: padded-bucket floor; buckets are powers of two between MIN and the cap
MIN_BUCKET = 256


def device_info(devices) -> dict:
    """{platform, kind, count} of the devices a plane runs on, as JAX
    reports them — the run summary's ``device`` identity."""
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}


def _bucket(n: int, cap: int) -> int:
    """Next power-of-two >= n (n never exceeds the engine's chunk cap)."""
    b = MIN_BUCKET
    while b < n:
        b <<= 1
    return b


@functools.partial(jax.jit, static_argnames=("width",))
def _min_draw_kernel(packed, seed_key, width=MAX_PKTS):
    """packed: (3, P) uint32 rows [uid_lo, uid_hi, npkts]; returns (P,)
    uint32: the MINIMUM 24-bit draw over each unit's first npkts packet
    lanes (0xFFFFFFFF for npkts == 0, which no threshold can undercut).
    This is the threshold-independent sufficient statistic behind the
    speculative forward windows: ``dropped == (min_draw < thresh)`` for
    ANY thresh, so one speculated row serves every destination a host
    later picks — same integer math as _draw_kernel/fluid.loss_flags.
    ``seed_key`` is the (2,) uint32 threefry key, passed as DATA (not a
    static arg) so ONE compiled program per shape serves EVERY seed —
    fleet mode (shadow_tpu/fleet.py) packs M seeded simulations behind
    one shared device plane, and a baked-in seed would recompile every
    bucket shape per member."""
    from shadow_tpu.ops.prng import threefry2x32

    uid_lo, uid_hi, npkts = packed
    p = uid_lo.shape[0]
    pkt = jnp.arange(width, dtype=jnp.uint32)[None, :]
    c0 = jnp.broadcast_to(uid_lo[:, None], (p, width))
    c1 = uid_hi[:, None] | (pkt << jnp.uint32(PKT_SHIFT))
    draws, _ = threefry2x32(seed_key[0], seed_key[1], c0, c1, xp=jnp)
    draws = (draws >> jnp.uint32(8)).astype(jnp.uint32)
    sentinel = jnp.uint32(0xFFFFFFFF)
    return jnp.min(jnp.where(pkt < npkts[:, None], draws, sentinel), axis=1)


@functools.partial(jax.jit, static_argnames=("width",))
def _draw_kernel(packed, seed_key, width=MAX_PKTS):
    """packed: (4, P) uint32 rows [uid_lo, uid_hi, npkts, thresh]; returns
    (P,) bool dropped flags. Mirrors fluid.loss_flags exactly: a unit drops
    iff any of its first npkts threefry draws is below its q24 threshold.
    (Padded entries carry thresh == 0, which can never hit.) ``seed_key``
    is traced data like in _min_draw_kernel: one program per shape, any
    seed."""
    from shadow_tpu.ops.prng import threefry2x32

    uid_lo, uid_hi, npkts, thresh = packed
    p = uid_lo.shape[0]
    pkt = jnp.arange(width, dtype=jnp.uint32)[None, :]
    c0 = jnp.broadcast_to(uid_lo[:, None], (p, width))
    c1 = uid_hi[:, None] | (pkt << jnp.uint32(PKT_SHIFT))
    draws, _ = threefry2x32(seed_key[0], seed_key[1], c0, c1, xp=jnp)
    draws = (draws >> jnp.uint32(8)).astype(jnp.uint32)
    hit = (draws < thresh[:, None]) & (pkt < npkts[:, None])
    # bit-pack the flags: the device->host readback is the scarce resource
    # (see module doc), so ship 1 bit per unit, not 1 byte
    return jnp.packbits(jnp.any(hit, axis=1), bitorder="little")


class DrawHandle:
    """An in-flight device draw: read() yields the (n,) bool flags."""

    __slots__ = ("_arr", "_n")

    def __init__(self, arr, n: int) -> None:
        self._arr = arr
        self._n = n

    def read(self) -> np.ndarray:
        with span("window_readback.wait"):
            packed = np.asarray(self._arr)
        with span("window_readback.unpack"):
            return np.unpackbits(packed,
                                 bitorder="little")[: self._n].astype(bool)

    def is_ready(self) -> bool:
        """True when the device result has landed host-side — read() will
        not stall."""
        return self._arr.is_ready()


class MinDrawHandle:
    """An in-flight speculative min-draw batch: read() yields (n,) uint32
    prefix-min draws (see _min_draw_kernel)."""

    __slots__ = ("_arr", "_n")

    def __init__(self, arr, n: int) -> None:
        self._arr = arr
        self._n = n

    def read(self) -> np.ndarray:
        return np.asarray(self._arr)[: self._n]

    def is_ready(self) -> bool:
        return self._arr.is_ready()


class DeviceDrawPlane:
    """Dispatches loss-draw batches to the accelerator.

    The numpy twin is fluid.loss_flags; tests/test_bitmatch.py asserts the
    two produce identical flags for identical inputs.
    """

    name = "tpu"
    #: {span: {seconds, rss_mb_before, rss_mb_after}} of the attach that
    #: built this plane (attach_cached); None for a plane built directly
    attach_stats = None

    def __init__(self, seed: int, max_batch: int = 65536,
                 n_shards: int = 0, max_pkts: int = MAX_PKTS) -> None:
        """n_shards > 1 shards each batch over that many local devices
        (experimental.tpu_mesh_shards; 0 = all local devices). The kernel
        is elementwise along the unit axis, so XLA partitions it with no
        communication — data-parallel draws across the mesh."""
        from shadow_tpu.ops.jaxcfg import configure

        configure()
        self.seed = int(seed)
        self.max_batch = int(max_batch)
        self.max_pkts = int(max_pkts)  # kernel packet-lane width
        self._sharding = None
        devs = jax.devices()
        n = n_shards if n_shards > 0 else len(devs)
        n = min(n, len(devs))
        #: what the run reports as ``device`` in its summary: the platform
        #: and kind JAX gave this plane, and how many devices it uses
        self.device_info = device_info(devs[:n])
        if n > 1:
            from jax.sharding import Mesh, NamedSharding, PartitionSpec

            mesh = Mesh(np.array(devs[:n]), ("d",))
            self._sharding = NamedSharding(mesh, PartitionSpec(None, "d"))
            self._n_shards = n

    def _seed_key(self, seed) -> np.ndarray:
        """(2,) uint32 threefry key for ``seed`` (None = the plane's own).
        Shipped to the kernels as data, so serving another simulation's
        seed (fleet mode) reuses the already-compiled programs."""
        s = self.seed if seed is None else int(seed)
        return np.array([s & 0xFFFFFFFF, (s >> 32) & 0xFFFFFFFF],
                        dtype=np.uint32)

    def dispatch(self, uid_lo: np.ndarray, uid_hi: np.ndarray,
                 npkts: np.ndarray, thresh: np.ndarray,
                 seed: int = None) -> DrawHandle:
        """Launch one batch (any length <= max_batch) and start the async
        device->host copy; returns a handle to read when due. ``seed``
        overrides the plane's seed for this batch (the fleet draw server
        serves many member sims' windows from one attach)."""
        n = uid_lo.shape[0]
        with span("window_dispatch.pack"):
            p = _bucket(n, self.max_batch)
            if self._sharding is not None:
                q = 8 * self._n_shards  # packbits + even split over shards
                p = -(-max(p, q) // q) * q
            packed = np.zeros((4, p), dtype=np.uint32)
            packed[0, :n] = uid_lo
            packed[1, :n] = uid_hi
            packed[2, :n] = npkts
            packed[3, :n] = thresh
        with span("window_dispatch.put"):
            dev_in = (jax.device_put(packed, self._sharding)
                      if self._sharding is not None else jnp.asarray(packed))
        with span("window_dispatch.launch"):
            out = _draw_kernel(dev_in, self._seed_key(seed),
                               width=self.max_pkts)
            out.copy_to_host_async()
        return DrawHandle(out, n)

    #: every speculative wave pads to this one bucket so exactly ONE
    #: min-draw program shape ever compiles (warmed at attach_cached);
    #: callers chunk bigger waves at this size
    SPEC_BUCKET = 16384

    def dispatch_min(self, uid_lo: np.ndarray, uid_hi: np.ndarray,
                     npkts: np.ndarray, min_bucket: int = 0,
                     seed: int = None) -> MinDrawHandle:
        """Launch one speculative min-draw batch (threshold-independent;
        see _min_draw_kernel) with the async device->host copy started.
        ``min_bucket`` pins the padded shape (shape stability = no
        mid-run compiles; padded rows carry npkts 0 and can never hit);
        ``seed`` overrides the plane's seed (fleet draw server)."""
        n = uid_lo.shape[0]
        with span("window_dispatch.pack"):
            p = max(_bucket(n, self.max_batch), min_bucket)
            if self._sharding is not None:
                q = 8 * self._n_shards
                p = -(-max(p, q) // q) * q
            packed = np.zeros((3, p), dtype=np.uint32)
            packed[0, :n] = uid_lo
            packed[1, :n] = uid_hi
            packed[2, :n] = npkts
        with span("window_dispatch.put"):
            dev_in = (jax.device_put(packed, self._sharding)
                      if self._sharding is not None else jnp.asarray(packed))
        with span("window_dispatch.launch"):
            out = _min_draw_kernel(dev_in, self._seed_key(seed),
                                   width=self.max_pkts)
            out.copy_to_host_async()
        return MinDrawHandle(out, n)

    _cache: dict = {}  # (seed, max_batch, n_shards, max_pkts) -> entry

    @classmethod
    def attach_cached(cls, seed: int, max_batch: int, n_shards: int,
                      max_pkts: int, should_abort=None):
        """Process-wide attach cache: (plane, dev_s, np_per_unit) for this
        parameter tuple, building + calibrating on first use. A simulation
        binary runs many short Controllers (benchmarks, tests, resumed
        checkpoints); paying the attach + compile + calibrate cost once
        per process instead of once per run is what lets the device come
        online BEFORE the round loop ends on fast configs — round 5's
        device_x < 1.0 was largely a device that published after the loop
        finished. Pure wall-clock policy: the plane is stateless, so
        sharing it across runs cannot change results.

        ``should_abort`` (callable -> bool) is polled between the attach
        phases; when it fires, the partial attach is discarded (nothing
        cached) and None is returned. This bounds how long a teardown
        must wait on an in-flight attach to a single XLA compile."""
        key = (int(seed), int(max_batch), int(n_shards), int(max_pkts))
        hit = cls._cache.get(key)
        if hit is None:
            if should_abort is not None and should_abort():
                return None
            # the attach's own spans (attach.plane / attach.calibrate /
            # attach.warm_shapes) and the host RSS around each, kept on
            # the plane for every run's device summary
            rec = Recorder()
            rss = {}
            with rec.installed():
                rss["before"] = rss_bytes()
                with span("attach.plane"):
                    plane = cls(seed, max_batch, n_shards=n_shards,
                                max_pkts=max_pkts)
                rss["attach.plane"] = rss_bytes()
                if should_abort is not None and should_abort():
                    return None
                with span("attach.calibrate"):
                    dev_s, np_per_unit = plane.calibrate()
                rss["attach.calibrate"] = rss_bytes()
                # warm EVERY program shape this plane can ever dispatch
                # (VERDICT r5 item #7): calibrate() compiles only its
                # probe bucket, so the remaining power-of-two buckets (and
                # the speculative min-draw shape) used to compile lazily
                # INSIDE the first run's measured round loop — the warm-up
                # leak that made the first tpu rep ~2.1x slow in
                # interleaved raws. ~log2(max_batch) shapes, on the attach
                # thread, amortized by the persistent compile cache.
                with span("attach.warm_shapes"):
                    plane.warm_shapes(should_abort=should_abort)
                rss["attach.warm_shapes"] = rss_bytes()
            if should_abort is not None and should_abort():
                return None  # partially warmed: do not cache
            prev = rss["before"]
            seconds = rec.phase_detail()
            plane.attach_stats = {}
            for name in ("attach.plane", "attach.calibrate",
                         "attach.warm_shapes"):
                plane.attach_stats[name] = {
                    "seconds": seconds[name],
                    "rss_mb_before": prev / 2**20,
                    "rss_mb_after": rss[name] / 2**20}
                prev = rss[name]
            if len(cls._cache) >= 4:  # a handful of configs per process
                cls._cache.pop(next(iter(cls._cache)))
            hit = cls._cache[key] = (plane, dev_s, np_per_unit)
        return hit

    def warm_shapes(self, should_abort=None) -> None:
        """Compile every padded bucket shape of the draw kernel plus the
        pinned speculative min-draw shape, so no dispatch ever compiles
        inside a simulation round loop (static shapes bound the set to
        ~log2(max_batch) programs — the module-doc design point). Pure
        wall-clock work: flags are never read for results here.
        ``should_abort`` is polled between shapes (attach teardown)."""
        b = MIN_BUCKET
        while True:
            if should_abort is not None and should_abort():
                return
            z = np.zeros(b, dtype=np.uint32)
            self.dispatch(z, z, z, z).read()
            if b >= self.max_batch:
                break
            b <<= 1
        if should_abort is not None and should_abort():
            return
        k = self.SPEC_BUCKET
        z = np.zeros(k, dtype=np.uint32)
        self.dispatch_min(z, z, z, min_bucket=k).read()

    def calibrate(self, n_probe: int = 4096) -> tuple[float, float]:
        """Measure (device seconds per dispatch+readback at n_probe, numpy
        seconds per unit). Used by the engine to set the routing floor; has
        no effect on simulation results (both paths are bit-identical)."""
        rng = np.random.default_rng(0)
        lo = rng.integers(0, 1 << 32, n_probe, dtype=np.uint64).astype(np.uint32)
        hi = rng.integers(0, 1 << 32, n_probe, dtype=np.uint64).astype(np.uint32)
        npk = np.full(n_probe, self.max_pkts, np.uint32)
        th = np.full(n_probe, 1 << 10, np.uint32)
        self.dispatch(lo, hi, npk, th).read()  # compile + warm
        t0 = _walltime.perf_counter()
        self.dispatch(lo, hi, npk, th).read()
        dev_s = _walltime.perf_counter() - t0
        t0 = _walltime.perf_counter()
        loss_flags(self.seed, lo, hi, npk, th)
        np_per_unit = (_walltime.perf_counter() - t0) / n_probe
        return dev_s, np_per_unit


#: authkey for the fleet draw-service socket (local AF_UNIX only; the
#: socket path lives in a mode-0700 directory — the key is a protocol
#: sanity check, not the access control)
DRAW_SERVICE_AUTHKEY = b"shadow-tpu-draw-service-v1"


class DrawServer:
    """The fleet parent's shared device plane: ONE process-group attach
    (DeviceDrawPlane.attach_cached — compile, calibrate, warm_shapes paid
    once) serving every member simulation's draw windows over an AF_UNIX
    socket (shadow_tpu/fleet.py owns the member-side proxy). Because the
    kernels take the threefry key as data, M members with M different
    seeds share the same compiled programs — one attach for the whole
    fleet, and one process holding the chip (a member never attaches).

    Protocol (multiprocessing.connection, one serving thread per member):
      member -> ("hello", seed)
      server -> ("ok", dev_s, np_per_unit, SPEC_BUCKET, max_batch,
                 device_info)
      member -> ("draw", rid, seed, lo, hi, npk, th)
               | ("min", rid, seed, lo, hi, npk, min_bucket) | ("bye",)
      server -> (rid, result_array)   # FIFO per member; member demuxes

    Routing is pure wall-clock policy (both paths are bit-identical), so
    a dead or slow server can never change results: the member proxy runs
    the in-process numpy twin on a transport error and its manifest
    records that it did. An attach failure here is kept in ``error``;
    the fleet parent fails the sweep with it."""

    def __init__(self, seed: int, max_batch: int = 65536,
                 n_shards: int = 0, max_pkts: int = MAX_PKTS,
                 address: str = None) -> None:
        import os
        import tempfile
        import threading
        from multiprocessing.connection import Listener

        if address is None:
            d = tempfile.mkdtemp(prefix="stpu_draw_")
            os.chmod(d, 0o700)
            address = os.path.join(d, "sock")
        self.address = address
        # the listener accepts IMMEDIATELY while the (multi-second)
        # attach runs on a sibling thread: members connect and complete
        # the socket handshake at once, then their hello waits (with an
        # abortable poll on their side) for the plane to publish — so no
        # member ever blocks uninterruptibly on a server that is still
        # compiling (members run the numpy twin meanwhile, exactly like
        # the background-attach path of a standalone run)
        self._listener = Listener(address, family="AF_UNIX",
                                  backlog=64, authkey=DRAW_SERVICE_AUTHKEY)
        self._attach_args = (int(seed), int(max_batch), int(n_shards),
                             int(max_pkts))
        self.plane = None
        self.dev_s = 0.0
        self.np_per_unit = 0.0
        self.attach_wall = 0.0
        self._closing = False
        self._ready = threading.Event()
        self.served_batches = 0
        self.served_units = 0
        #: the attach's exception, if it failed (FleetRunner reports it
        #: and fails the sweep)
        self.error = None
        self._attach_thread = threading.Thread(
            target=self._attach, name="draw-server-attach", daemon=True)
        self._attach_thread.start()
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name="draw-server-accept",
            daemon=True)
        self._accept_thread.start()

    def _attach(self) -> None:
        import os
        import threading

        try:
            # mildly deprioritize the attach/compile against the fleet's
            # pinned member processes: the shared plane is background
            # amortization. Mild (nice 5), NOT SCHED_IDLE: the XLA host
            # threads created during attach inherit this priority and
            # later serve live member readbacks — starving them turns
            # member window flushes into stalls (measured: SCHED_IDLE
            # here made the whole sweep slower).
            os.setpriority(os.PRIO_PROCESS, threading.get_native_id(), 5)
        except (AttributeError, OSError, PermissionError):
            pass
        t0 = _walltime.perf_counter()
        try:
            hit = DeviceDrawPlane.attach_cached(
                *self._attach_args, should_abort=lambda: self._closing)
        except Exception as exc:
            self.error = exc
            hit = None
        if hit is None:
            # attach failed, or close() raced the attach: close the
            # listener so member proxies get a clean connection error
            # (they run the twin and record it; the parent reports
            # ``error``)
            self._closing = True
            try:
                self._listener.close()
            except OSError:
                pass
            return
        self.plane, self.dev_s, self.np_per_unit = hit
        self.attach_wall = _walltime.perf_counter() - t0
        self._ready.set()

    def _accept_loop(self) -> None:
        import os
        import threading

        try:
            # the accept/serve path answers live member requests: keep it
            # at normal priority (threads spawned here inherit it), while
            # the attach thread — and the XLA pool it creates — idles
            os.sched_setscheduler(0, os.SCHED_OTHER, os.sched_param(0))
        except (AttributeError, OSError, PermissionError):
            pass
        while not self._closing:
            try:
                conn = self._listener.accept()
            except (OSError, EOFError):
                break  # listener closed
            except Exception:
                continue  # failed handshake from one member; keep serving
            threading.Thread(target=self._serve, args=(conn,),
                             name="draw-server-member",
                             daemon=True).start()

    def _serve(self, conn) -> None:
        """Serve one member connection: dispatch requests on the shared
        plane immediately (the device queues programs; concurrent member
        threads interleave naturally under the GIL), answer in FIFO
        order. The blocking read at the bottom only happens when no new
        request is waiting in the pipe — the member that sent it is
        either already blocked on exactly this response or still running
        its rounds, so serving the oldest handle first is always
        progress."""
        from collections import deque

        pending: deque = deque()
        try:
            msg = conn.recv()
            if not (isinstance(msg, tuple) and msg and msg[0] == "hello"):
                return
            # the hello reply waits for the attach (member side polls
            # with its own abort, so a member tearing down mid-attach
            # just disconnects)
            while not self._ready.wait(0.25):
                if self._closing:
                    return
            plane = self.plane
            conn.send(("ok", self.dev_s, self.np_per_unit,
                       plane.SPEC_BUCKET, plane.max_batch,
                       plane.device_info))
            while not self._closing:
                while pending and pending[0][1].is_ready():
                    rid, h = pending.popleft()
                    conn.send((rid, h.read()))
                if conn.poll(0.001 if pending else 0.25):
                    msg = conn.recv()
                    op = msg[0]
                    if op == "bye":
                        break
                    rid, seed, lo, hi, npk, arg = msg[1:7]
                    if op == "draw":
                        h = plane.dispatch(lo, hi, npk, arg, seed=seed)
                    else:  # "min"
                        h = plane.dispatch_min(lo, hi, npk,
                                               min_bucket=arg, seed=seed)
                    pending.append((rid, h))
                    self.served_batches += 1
                    self.served_units += len(lo)
                elif pending:
                    rid, h = pending.popleft()
                    conn.send((rid, h.read()))
        except (EOFError, OSError, BrokenPipeError):
            pass  # member exited
        finally:
            try:
                conn.close()
            except OSError:
                pass

    def close(self) -> None:
        import os
        import shutil

        self._closing = True
        # closing the listener does NOT wake a thread blocked in
        # accept(): poke it with a throwaway connection so the accept
        # loop observes _closing and exits promptly
        try:
            from multiprocessing.connection import Client

            Client(self.address, family="AF_UNIX",
                   authkey=DRAW_SERVICE_AUTHKEY).close()
        except Exception:
            pass
        try:
            self._listener.close()
        except OSError:
            pass
        self._accept_thread.join(timeout=2)
        # join the attach thread before returning: a daemon thread left
        # inside an XLA compile at interpreter exit dies by C++
        # std::terminate (the fleet-smoke SIGABRT). attach_cached polls
        # _closing between phases, so the residual wait is bounded by a
        # single compile; the timeout is a backstop for a wedged backend.
        self._attach_thread.join(timeout=120)
        shutil.rmtree(os.path.dirname(self.address), ignore_errors=True)
