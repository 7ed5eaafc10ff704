"""Shared device-routing machinery for the two data planes.

Both the per-unit plane (network/engine.py) and the columnar plane
(network/colplane.py) route loss-draw batches either to the numpy twin
(fluid.loss_flags) or to the accelerator kernel (ops/propagate.py) — the
paths are bit-identical, so routing is pure wall-clock policy. This base
class carries everything that policy needs and that is identical across
the planes:

- background device attach + floor calibration (attach and compile cost
  seconds; simulations start on the numpy twin and switch over when the
  device publishes). An attach that fails is not a fallback: the error is
  kept and re-raised on the main thread at the next barrier
  (raise_device_error), and the run fails with it,
- the adaptive floor: realized readback stalls are compared against what
  the numpy twin would have cost; the floor backs off ×4 when the device
  is clearly losing and decays back toward the calibrated floor when it
  stops (a starved floor also decays on a round-count cooldown),
- fused multi-round device windows: draw batches accumulate lazily across
  rounds and dispatch as ONE device program per causal window instead of
  one per round, with a two-slot in-flight pipeline (deferred readbacks
  overlap subsequent host rounds) and a live break-even estimate from
  window telemetry deciding when the device is worth engaging at all,
- interpreter-teardown safety (close() joins the init thread: a daemon
  thread mid-JAX-call at exit aborts the process when XLA backend
  destruction races the in-flight computation),
- the latency/deferred/outstanding accessors the controller polls.
"""

from __future__ import annotations

from shadow_tpu.core.time import SimTime, T_NEVER


class DataPlaneError(RuntimeError):
    """A data plane the scheduler policy asks for could not be brought up
    (device attach/compile/calibrate failed, or the C engine is not
    built). Under a device policy this ends the run: it never falls back
    to a host twin in silence."""

#: where a sent unit's loss draw was made (device_summary "routes"): in C
#: or numpy at the barrier, from a C-consulted speculative window, by the
#: numpy twin at flush, in a deferred device window, in the mesh
#: collective, or nowhere (no lossy path)
ROUTES = ("inline", "spec_hit", "twin", "window", "mesh", "none")
#: deferred windows in flight at once (double-buffered handles): window
#: N's device execution overlaps the build of window N+1; a third window
#: waits (stays lazy) rather than queueing unbounded device memory
WINDOW_SLOTS = 2
#: EMA weight for the per-window fixed-cost estimate (dispatch + stall)
_BE_ALPHA = 0.25
#: hysteresis around the break-even unit count: engage above 1.25x,
#: release below 0.8x — so a window size hovering at break-even does not
#: flap the routing decision every window
_BE_ENGAGE = 1.25
_BE_RELEASE = 0.8


class DeviceRoutedPlane:
    """Mixin state + helpers; subclasses populate graph/params/_deferred/
    outstanding and call _init_routes() and _init_device_routing() from
    __init__."""

    spec_rows = 0  # speculative rows dispatched (window_units has them)
    handoff_units = 0  # units the C barrier handed to the window machinery
    resident_units = 0  # of them, units stored from the C core's slots
    resident_peak = 0  # most handed-off units the C core held at once

    def _init_routes(self) -> None:
        """Per-route counts of the units sent: they add up to units_sent,
        and a checkpoint carries them (reattach_device does not reset
        them)."""
        self.routes = dict.fromkeys(ROUTES, 0)

    def _init_device_routing(self, backend: str, tpu_options,
                             params) -> None:
        self.max_batch = int(
            getattr(tpu_options, "tpu_max_batch", 65536) or 65536)
        self.max_pkts = int(getattr(tpu_options, "unit_mtus", 10) or 10)
        self.device = None
        #: an exception from the background attach, re-raised on the main
        #: thread by raise_device_error (the controller polls it per round)
        self.device_error = None
        #: fleet members: how the parent's draw service was reached
        #: (None outside a fleet; reported in the summary and manifest)
        self.draw_service = None
        #: attach calibration (seconds per dispatch+readback of a
        #: 4096-unit probe, numpy seconds per unit) and the background
        #: attach's wall (attach + compile + calibrate + warm_shapes),
        #: for the summary
        self._calib = None
        self._attach_wall = None
        self.device_floor = float("inf")
        self._dev_stall = 0.0
        self._dev_reads = 0
        self._dev_units = 0
        self._dev_warm = False  # first read (compile/attach) is excluded
        self._floor_cooldown = 0  # rounds until a starved floor decays
        self._np_per_unit = 4e-6  # refined by calibration when available
        self._floor0 = float("inf")  # calibrated floor: decay lower bound
        self._floor_forced = False  # explicit tpu_device_floor > 0: the
        #                             operator owns routing; break-even
        #                             estimation and probe clamping yield
        #: fused-window state (experimental.device_window_rounds; 0 = auto)
        self.window_rounds = int(
            getattr(tpu_options, "device_window_rounds", 0) or 0)
        self._win_open_rounds = 0  # barriers since the window opened
        self._win_inflight = 0  # dispatched windows not yet fully read
        self._win_engaged = False  # hysteresis state of the flush gate
        self._win_cost_ema = 0.0  # seconds of host wall per window
        self.dev_windows = 0  # fused windows dispatched to the device
        self.dev_window_units = 0  # units those windows carried
        self.spec_hits = 0  # C-plane speculative-window consults served
        self.spec_draws = 0  # C-plane inline draws (speculation missed)
        self._max_window_units = 0  # biggest window this run has seen
        self._probe_clamped = False  # satellite: probing suppressed
        #: speculative forward windows (C plane only; colplane drives)
        self._spec_on = False
        self._spec_checked = False
        self._spec_clamped = False  # live economics turned speculation off
        self._spec_pending = []
        self._spec_round = 0
        self._spec_spend = 0.0  # wall seconds speculation itself cost
        self._spec_units = 0  # rows speculation itself dispatched
        self.mesh_plane = None
        if backend == "mesh":
            # scheduler_policy: tpu_mesh — the WHOLE per-round network
            # program (closed-form bucket departures, latency gather, loss
            # draws, all_to_all arrival exchange, pmin barrier, psum
            # counters) runs as ONE sharded XLA program per round, hosts
            # sharded over the local device mesh. Bit-identical to the
            # host planes (tests/test_multichip.py), so policy choice
            # cannot change results.
            from shadow_tpu.parallel.mesh import MeshDataPlane
            import jax

            n_shards = int(getattr(tpu_options, "tpu_mesh_shards", 0) or 0)
            n = n_shards or len(jax.devices())
            # per-shard slot width: every scan step pads to (N, C), so C
            # tracks realistic per-barrier chunk sizes, not max_batch —
            # bulk barriers just span more fused steps. Chunk boundaries
            # cannot change results (sequential chunks at one t_now equal
            # one batched call).
            ups = max(256, min(2048, 4096 // n))
            self.mesh_plane = MeshDataPlane(
                params, n_shards=n, units_per_shard=ups,
                max_pkts=self.max_pkts)
        elif backend == "tpu":
            n_shards = int(getattr(tpu_options, "tpu_mesh_shards", 0) or 0)
            floor = int(getattr(tpu_options, "tpu_device_floor", 0) or 0)
            if floor < 0:
                # device draws disabled: the numpy twin serves every batch.
                # This is the published ablation row (BENCH device_off) —
                # results are bit-identical by construction, only wall
                # time moves, so the knob isolates the device's
                # contribution to any config's headline rate.
                pass
            elif floor > 0:
                from shadow_tpu.ops.propagate import DeviceDrawPlane

                self.device = DeviceDrawPlane(params.seed, self.max_batch,
                                              n_shards=n_shards,
                                              max_pkts=self.max_pkts)
                self.device_floor = floor
                self._floor_forced = True
            else:
                # fleet mode (shadow_tpu/fleet.py): a sweep member routes
                # its draw windows to the fleet parent's ONE shared
                # attach instead of attaching its own — the proxy quacks
                # like the device plane and its results are bit-identical
                # to the local twins, so this is pure wall-clock policy.
                # Connection happens on the background thread (the
                # parent's attach may still be warming); the member runs
                # the numpy twin until the proxy publishes — exactly the
                # background-attach discipline below.
                import os as _os

                svc = _os.environ.get("SHADOW_TPU_DRAW_SERVICE")
                if svc == "off":
                    # a fleet member of a sweep without the shared
                    # service: it never attaches the chip itself (one
                    # process per chip), so the twins serve its draws
                    self.draw_service = "off (--no-device-service)"
                    return
                if svc:
                    import threading

                    self._svc_abort = False
                    self._bg_thread = threading.Thread(
                        target=self._bg_connect_service,
                        args=(svc, params.seed, n_shards), daemon=True)
                    self._bg_thread.start()
                    return
                # auto mode: device attach, kernel compile, and floor
                # calibration run on a background thread — except when a
                # previous run of this process already attached this
                # parameter tuple, in which case the cached plane (and its
                # calibration) publishes SYNCHRONOUSLY so the device is
                # live from round 0. Probe the cache via sys.modules so a
                # cold process does NOT pay the multi-second jax import on
                # the main thread just to find an empty cache. Because
                # both paths are bit-identical and event order is
                # canonicalized, WHEN the device comes online cannot
                # affect results — only wall time.
                import sys

                mod = sys.modules.get("shadow_tpu.ops.propagate")
                key = (int(params.seed), self.max_batch, int(n_shards),
                       self.max_pkts)
                hit = (mod.DeviceDrawPlane._cache.get(key)
                       if mod is not None else None)
                if hit is not None:
                    self._publish_device(*hit)
                else:
                    import threading

                    self._bg_thread = threading.Thread(
                        target=self._bg_init_device, name="device-attach",
                        args=(params.seed, n_shards), daemon=True)
                    self._bg_thread.start()

    def _publish_device(self, plane, dev_s: float,
                        np_per_unit: float) -> None:
        self._calib = (dev_s, np_per_unit)
        if np_per_unit > 0:
            self._np_per_unit = np_per_unit
            self.device_floor = max(512, min(
                int(dev_s / np_per_unit), self.max_batch))
            self._floor0 = self.device_floor
        self.device = plane  # publish last (reads are GIL-atomic)

    def _bg_init_device(self, seed: int, n_shards: int) -> None:
        import time as _walltime  # detlint: ok(wallclock): attach wall

        t0 = _walltime.perf_counter()
        try:
            from shadow_tpu.ops.propagate import DeviceDrawPlane

            hit = DeviceDrawPlane.attach_cached(
                seed, self.max_batch, n_shards, self.max_pkts)
            self._attach_wall = _walltime.perf_counter() - t0
            self._publish_device(*hit)
        except Exception as exc:
            self.device_error = exc  # raised at the next barrier

    def raise_device_error(self) -> None:
        """Re-raise, on the calling (main) thread, an attach that failed
        on the background thread."""
        err = self.device_error
        if err is not None:
            raise DataPlaneError(
                f"device plane failed to attach: "
                f"{type(err).__name__}: {err}") from err

    def _bg_connect_service(self, address: str, seed: int,
                            n_shards: int) -> None:
        """Fleet member: connect to the parent's shared draw service and
        publish the proxy as this run's device plane. The parent holds
        the chip, so a member never attaches it itself: when the service
        is unreachable the member runs the numpy twin (bit-identical) and
        ``draw_service`` records why, for the summary and the member's
        manifest."""
        try:
            from shadow_tpu.fleet import FleetDrawClient

            proxy = FleetDrawClient.connect(
                address, seed, self.max_batch, self.max_pkts,
                abort=lambda: self._svc_abort)
        except Exception as exc:
            self.draw_service = f"unreachable: {exc}"
            return
        self.draw_service = "connected"
        self._publish_device(proxy, proxy.dev_s, proxy.np_per_unit)

    def close(self) -> None:
        """Join the background device-init thread (if any) and release a
        fleet draw-service proxy connection. A connect still waiting on
        the service (short member run, slow parent attach) is aborted
        rather than waited out."""
        self._svc_abort = True
        t = getattr(self, "_bg_thread", None)
        if t is not None and t.is_alive():
            t.join()
        d = getattr(self, "device", None)
        if d is not None and hasattr(d, "close_client"):
            d.close_client()

    # -- checkpoint/restore (shadow_tpu/checkpoint.py) ----------------------
    def __getstate__(self):
        """Drop the runtime-only device plumbing from snapshots: the JAX
        device plane, the mesh plane, the init thread, and the C engine
        are all re-creatable (and result-transparent — routing is pure
        wall-clock policy, enforced by test_bitmatch / test_multichip /
        test_colcore)."""
        d = self.__dict__.copy()
        for k in ("device", "mesh_plane", "_bg_thread", "_c",
                  "_spec_pending", "device_error"):
            d.pop(k, None)
        return d

    def __setstate__(self, d):
        self.__dict__.update(d)
        if "routes" not in d:  # a snapshot from before route counts
            self._init_routes()
        self.device = None
        self.device_error = None
        self.mesh_plane = None
        self._c = None
        self._spec_pending = []
        self._spec_on = False
        self._spec_checked = False
        self._spec_clamped = False

    def reattach_device(self, tpu_options) -> None:
        """Restore-time twin of __init__'s device hookup: re-runs attach,
        calibration, and floor state from scratch. Calibration state is
        not carried across a resume — the adaptive floor cannot change
        results, only wall time."""
        self._init_device_routing(self.backend, tpu_options, self.params)

    # -- adaptive floor + window break-even ---------------------------------
    def break_even_units(self) -> int:
        """Units at which one fused window dispatch beats the host twin,
        from live telemetry: the EMA'd per-window host cost (dispatch wall
        + readback stall) divided by the calibrated per-unit host cost.
        Before the first window lands, fall back to the calibrated floor
        (same quantity measured at attach time)."""
        if self._win_cost_ema > 0.0 and self._np_per_unit > 0.0:
            return max(256, int(self._win_cost_ema / self._np_per_unit))
        return int(self._floor0) if self._floor0 != float("inf") else 4096

    def window_gate_units(self, engaged: bool) -> float:
        """The unit count a deferred window must reach to route to the
        device. An explicitly forced tpu_device_floor IS the gate (the
        operator owns routing — tests and A/B runs rely on it); otherwise
        the live break-even estimate applies with hysteresis: 1.25x to
        engage, and a currently-engaged window releases only below 0.8x,
        so a size hovering at break-even does not flap the decision."""
        if self._floor_forced:
            return self.device_floor
        return max(self.device_floor,
                   (_BE_RELEASE if engaged else _BE_ENGAGE)
                   * self.break_even_units())

    def _record_window(self, n_units: int, host_cost: float) -> None:
        """One fused window landed: fold its realized host-side cost
        (dispatch wall + any readback stall) into the break-even EMA and
        the run counters."""
        self.dev_windows += 1
        self.dev_window_units += n_units
        if not self._dev_warm:
            self._dev_warm = True  # compile/attach window: not signal
            return
        if self._win_cost_ema == 0.0:
            self._win_cost_ema = host_cost
        else:
            self._win_cost_ema += _BE_ALPHA * (host_cost - self._win_cost_ema)

    def _note_window_units(self, n_units: int) -> None:
        """Track the biggest causal window this config has produced and
        clamp device probing when the config provably cannot reach
        break-even (round-5 Weak #5 satellite): if even the largest window
        is under 25% of break-even, re-probing the device on a cadence
        only burns dispatches — stop until the traffic shape changes."""
        if self._floor_forced:
            if n_units > self._max_window_units:
                self._max_window_units = n_units
            return
        if n_units > self._max_window_units:
            self._max_window_units = n_units
            if self._probe_clamped and \
                    n_units >= 0.25 * self.break_even_units():
                self._probe_clamped = False
        elif (not self._probe_clamped
              and self._max_window_units > 0
              and self._dev_warm
              and self._max_window_units < 0.25 * self.break_even_units()):
            self._probe_clamped = True

    def _floor_cooldown_tick(self) -> None:
        """Called on barriers that did NOT use the device: a backed-off
        floor must be able to recover even when it now starves the device
        entirely (no reads -> no stall windows). When probing is clamped
        (the config's windows cannot reach break-even) the decay pauses:
        recovering the floor would only re-probe a device that provably
        loses at this config's window sizes."""
        if self._probe_clamped:
            return
        if self.device_floor > self._floor0 and self._floor_cooldown > 0:
            self._floor_cooldown -= 1
            if self._floor_cooldown == 0:
                self.device_floor = max(self._floor0, self.device_floor // 4)
                self._floor_cooldown = 512
                self._dev_stall = 0.0
                self._dev_reads = 0
                self._dev_units = 0

    def _record_dev_read(self, dt: float, n_units: int) -> None:
        if not self._dev_warm:
            self._dev_warm = True  # compile/attach stall: not signal
        else:
            self._dev_stall += dt
            self._dev_reads += 1
            self._dev_units += n_units

    def _floor_settle(self) -> None:
        """Every 8 realized device reads, compare stalls against what the
        numpy twin would have cost for the same units: back off only when
        the device is clearly LOSING, decay back toward the live
        break-even estimate (never below the calibrated floor) when it
        stops (results are identical either way)."""
        if self._dev_reads < 8:
            return
        np_cost = self._np_per_unit * self._dev_units
        lo = max(self._floor0, float(self.break_even_units()))
        if self._dev_stall > 4 * np_cost + 0.02:
            self.device_floor = min(self.device_floor * 4, 1 << 30)
            self._floor_cooldown = 512
        elif self._dev_stall < np_cost and self.device_floor > lo:
            self.device_floor = max(lo, self.device_floor // 4)
        self._dev_stall = 0.0
        self._dev_reads = 0
        self._dev_units = 0

    def device_summary(self) -> dict:
        """Window/speculation telemetry for the run summary (wall-clock
        routing state — volatile across runs, never simulation state),
        led by the device identity {platform, kind, count} when a device
        plane attached."""
        plane = self.mesh_plane if self.mesh_plane is not None \
            else self.device
        info = getattr(plane, "device_info", None)
        extra = {}
        if self._calib is not None:
            extra["calibrate_dispatch_s"] = self._calib[0]
            extra["calibrate_np_per_unit_s"] = self._calib[1]
        if self._attach_wall is not None:
            extra["attach_wall_seconds"] = self._attach_wall
        if self.mesh_plane is not None:
            extra["mesh_windows"] = self.mesh_plane.windows
        attach = getattr(self.device, "attach_stats", None)
        if attach:
            extra["attach"] = attach
        if self.draw_service is not None:
            extra["draw_service"] = self.draw_service
            if self.device is not None and self.device._dead:
                extra["draw_service"] = "lost mid-run (twin served the rest)"
        return {
            **(info or {}),
            **extra,
            "windows_dispatched": self.dev_windows,
            "window_units": self.dev_window_units,
            "spec_rows": self.spec_rows,
            "handoff_units": self.handoff_units,
            "resident_units": self.resident_units,
            "resident_peak": self.resident_peak,
            "routes": dict(self.routes),
            "spec_hits": self.spec_hits,
            "spec_draws": self.spec_draws,
            "break_even_units": self.break_even_units(),
            "max_window_units": self._max_window_units,
            "probe_clamped": self._probe_clamped,
            "spec_clamped": self._spec_clamped,
            "window_rounds": self.window_rounds or "auto",
        }

    def heartbeat_note(self) -> str:
        """One heartbeat-line fragment describing the routing decision."""
        if self.device is None and self.mesh_plane is None:
            return "dev=off"
        state = "clamped" if self._probe_clamped else (
            "engaged" if self.dev_windows else "probing")
        if self._spec_clamped:
            state += "+spec_clamped"
        elif self.spec_hits:
            state += "+spec"
        return (f"dev={state} windows={self.dev_windows} "
                f"be={self.break_even_units()} "
                f"maxwin={self._max_window_units}")

    # -- telemetry (shadow_tpu/telemetry/) ----------------------------------
    def telemetry_sample(self, t_now: SimTime) -> dict:
        """Engine-side half of one telemetry sample: run-global counters
        plus the per-host NIC token-bucket levels, all plane-independent
        (capped egress availability via fluid.TokenBuckets.levels; the
        round-quantized ingress tokens are shared state — the C engine
        mutates the same numpy array). Caller flushes in-flight draws
        first so every plane sits at the same resolution frontier."""
        return {
            "units_sent": self.units_sent,
            "units_dropped": self.units_dropped,
            "units_blackholed": self.units_blackholed,
            "bytes_sent": self.bytes_sent,
            "bucket_up": self.buckets.levels(t_now).tolist(),
            "tokens_down": self.tokens_down.tolist(),
        }

    # -- accessors shared by the controller --------------------------------
    def latency_between(self, src_host: int, dst_host: int) -> SimTime:
        p = self.params
        return int(self.graph.latency_ns[p.host_node[src_host],
                                         p.host_node[dst_host]])

    def has_immediate_work(self) -> bool:
        """True if the next round must run even with empty event queues
        (deferred ingress backlog waiting on token refill)."""
        return bool(self._deferred)

    def earliest_outstanding(self) -> SimTime:
        """Earliest event time any in-flight draw batch can produce."""
        return min((b.deadline for b in self.outstanding), default=T_NEVER)

    def pending_batches(self) -> int:
        """Draw batches whose rows a native core holds (none here)."""
        return 0
