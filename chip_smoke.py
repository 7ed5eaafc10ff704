#!/usr/bin/env python
"""Chip smoke: the device data planes, end to end, on a local TPU.

Runs Tor at 1/10 of config #5 (700 relays, 20 web servers, 10,000
TorClients: 10,720 hosts on the seeded 64-node graph, 8 sim-s, seed 6;
tools/gen_benchmarks.py::tor_doc) through the normal entry (``Controller``)
in ONE process — a chip belongs to one process, so nothing here starts a
child that needs it. Phases, in order:

1. build      ``make -C native``: the C engine and the shim, from the
              committed sources
2. reference  ``thread_per_core`` (the host reference)
3. tpu_batch  auto routing (the default config)
4. tpu_batch  device forced (``experimental.tpu_device_floor: 1``): every
              fused window goes to the chip

``--chips 4`` runs only the reference and ``tpu_mesh`` with
``tpu_mesh_shards: 4`` and ``tpu_mesh_floor: 0`` (the collective on every
window), on four chips.

Earlier lines report, per phase, the device as the run saw it, set-up,
attach and loop walls, the loop rate, device windows and units, compiles
on the loop thread and on the attach thread, and peak device memory; a
JSON copy goes to ``chiprun_out/chip_smoke_<chips>.json``. All runs must
agree on events, units, bytes, counters, process errors and the
``hosts/`` tree.

The last line is ``{"ok": true, "device": {...}}``. Any failed check, a
platform other than ``tpu``, or any exception exits non-zero without it.
"""

from __future__ import annotations

import argparse
import json
import shutil
import subprocess
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

#: the result identity every phase must reproduce
IDENTITY_KEYS = ("events", "units_sent", "units_dropped", "bytes_sent",
                 "counters", "process_errors")


class SmokeError(Exception):
    """A check failed."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


def say(msg: str) -> None:
    print(msg, flush=True)


class CompileCounter:
    """Counts XLA backend compiles per thread name, from jax.monitoring."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax) -> None:
        self.by_thread: dict = {}
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_kw) -> None:
        if event == self.EVENT:
            name = threading.current_thread().name
            with self._lock:
                self.by_thread[name] = self.by_thread.get(name, 0) + 1

    def snapshot(self) -> dict:
        with self._lock:
            return dict(self.by_thread)


def _delta(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def peak_bytes(dev):
    stats = dev.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def run_phase(name: str, doc: dict, overrides: dict, jax, counter,
              data_root: Path) -> dict:
    """One simulation through Controller; returns its report row."""
    from shadow_tpu.config.schema import parse_config
    from shadow_tpu.core.controller import Controller
    from shadow_tpu.fleet import output_tree_digest

    data_dir = data_root / name
    shutil.rmtree(data_dir, ignore_errors=True)
    cfg = parse_config(doc, {"general.data_directory": str(data_dir),
                             **overrides})
    t0 = time.perf_counter()
    ctl = Controller(cfg, mirror_log=False)
    build_wall = time.perf_counter() - t0
    c0 = counter.snapshot()
    t1 = time.perf_counter()
    summary = ctl.run()
    run_wall = time.perf_counter() - t1
    c1 = counter.snapshot()
    dev = summary.get("device", {})
    compiles = _delta(c1, c0)
    row = {
        "phase": name,
        "device": {k: dev[k] for k in ("platform", "kind", "count")
                   if k in dev},
        "build_wall_s": build_wall,
        "attach_wall_s": dev.get("attach_wall_seconds"),
        "calibrate_dispatch_s": dev.get("calibrate_dispatch_s"),
        "loop_wall_s": summary["wall_seconds"],
        "run_wall_s": run_wall,
        "sim_s_per_wall_s": summary["sim_sec_per_wall_sec"],
        "device_windows": summary["device_windows_dispatched"],
        "device_window_units": dev.get("window_units", 0),
        "mesh_windows": dev.get("mesh_windows"),
        "compiles_loop_thread": compiles.get("MainThread", 0),
        "compiles_attach_thread": compiles.get("device-attach", 0),
        "compiles_other": {k: v for k, v in compiles.items()
                           if k not in ("MainThread", "device-attach")},
        "x64": bool(jax.config.jax_enable_x64),
        "peak_bytes_in_use": peak_bytes(jax.devices()[0]),
        "c_engine": getattr(ctl.engine, "_c", None) is not None,
        "max_rss_mb": summary["max_rss_mb"],
        "phase_wall": summary["phase_wall"],
        "identity": {k: summary[k] for k in IDENTITY_KEYS},
        "hosts_digest": output_tree_digest(data_dir),
        "routing": dev,
        "_ctl": ctl,
    }
    shutil.rmtree(data_dir, ignore_errors=True)
    shown = {k: v for k, v in row.items()
             if k not in ("phase_wall", "identity", "routing", "_ctl")}
    say(f"phase {name}: {json.dumps(shown, default=str)}")
    return row


def check_identity(rows: list) -> None:
    ref = rows[0]
    check(ref["identity"]["process_errors"] == [],
          f"{ref['phase']}: process errors "
          f"{ref['identity']['process_errors']}")
    for r in rows[1:]:
        for k in IDENTITY_KEYS:
            check(r["identity"][k] == ref["identity"][k],
                  f"{r['phase']} differs from {ref['phase']} on {k}")
        check(r["hosts_digest"] == ref["hosts_digest"],
              f"{r['phase']}: hosts/ tree differs from {ref['phase']}")
    ident = ref["identity"]
    say(f"identity: {len(rows)} runs agree on events={ident['events']} "
        f"units_sent={ident['units_sent']} "
        f"units_dropped={ident['units_dropped']} "
        f"bytes_sent={ident['bytes_sent']}, counters, process_errors=[] "
        f"and hosts/ sha256 {ref['hosts_digest'][:16]}")


def draw_plane_checks(jax, counter, seed: int) -> dict:
    """On the draw plane the tpu_batch phases attached (process cache):
    calibrate() again with nothing else running — during the auto run
    it shares the GIL with the round loop — then flip jax_enable_x64 on
    (what MeshDataPlane and DeviceAckKernel do in mid-run) and dispatch
    the warmed programs once each: the compiles this causes are the ones
    a mid-run flip would put inside a round loop."""
    import numpy as np

    from shadow_tpu.ops.propagate import DeviceDrawPlane

    plane, _dev_s, _np = DeviceDrawPlane.attach_cached(seed, 65536, 0, 10)
    dev_s, np_per_unit = plane.calibrate()
    z = np.zeros(4096, dtype=np.uint32)
    c0 = counter.snapshot()
    jax.config.update("jax_enable_x64", True)
    try:
        plane.dispatch(z, z, z, z).read()
        k = plane.SPEC_BUCKET
        zk = np.zeros(k, dtype=np.uint32)
        plane.dispatch_min(zk, zk, zk, min_bucket=k).read()
    finally:
        jax.config.update("jax_enable_x64", False)
    return {"calibrate_dispatch_s_idle": dev_s,
            "calibrate_np_per_unit_s_idle": np_per_unit,
            "x64_flip_recompiles": sum(
                _delta(counter.snapshot(), c0).values())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only thread_per_core vs tpu_mesh on 4 chips")
    args = ap.parse_args(argv)
    if not (ROOT / "shadow_tpu").is_dir() or not (ROOT / "native").is_dir():
        raise SmokeError(f"{ROOT} does not hold the shadow_tpu repository")
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "tools"))

    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    say(f"devices: {device} ids={[d.id for d in devs]}")
    check(device["platform"] == "tpu",
          f"JAX found platform {device['platform']!r}, not 'tpu'")
    check(device["count"] == args.chips,
          f"expected {args.chips} chip(s), JAX sees {device['count']}")
    counter = CompileCounter(jax)

    import shadow_tpu

    check(Path(shadow_tpu.__file__).resolve().is_relative_to(ROOT),
          f"shadow_tpu imported from {shadow_tpu.__file__}, not {ROOT}")
    from gen_benchmarks import tor_doc

    t0 = time.perf_counter()
    subprocess.run(["make", "-C", str(ROOT / "native")], check=True,
                   stdout=subprocess.DEVNULL)
    say(f"phase build: make -C native ok in "
        f"{time.perf_counter() - t0:.1f}s")

    doc = tor_doc(700, 10_000, 8)
    n_hosts = sum(int(h.get("quantity", 1)) for h in doc["hosts"].values())
    say(f"workload: tor 1/10 ({n_hosts} hosts, "
        f"{doc['general']['stop_time']}, seed {doc['general']['seed']})")
    data_root = ROOT / "_smoke_data"
    report = {"device": device, "hosts": n_hosts, "phases": []}
    try:
        ref = run_phase("reference_thread_per_core", doc, {
            "experimental.scheduler_policy": "thread_per_core"},
            jax, counter, data_root)
        rows = [ref]
        if args.chips == 4:
            mesh = run_phase("tpu_mesh_4", doc, {
                "experimental.scheduler_policy": "tpu_mesh",
                "experimental.tpu_mesh_shards": 4,
                "experimental.tpu_mesh_floor": 0}, jax, counter, data_root)
            rows.append(mesh)
            plane = mesh["_ctl"].engine.mesh_plane
            ids = sorted({d.id for d in plane.mesh.devices.flat})
            say(f"mesh: devices {ids}; (G,G) tables "
                + ", ".join(f"{t.shape} {t.dtype} on "
                            f"{sorted(d.id for d in t.sharding.device_set)}"
                            for t in plane._tables[1:3]))
            check(len(ids) == 4, f"the mesh spans {len(ids)} devices, not 4")
            check(mesh["mesh_windows"], "no mesh window was dispatched")
        else:
            auto = run_phase("tpu_batch_auto", doc, {
                "experimental.scheduler_policy": "tpu_batch"},
                jax, counter, data_root)
            say(f"routing (auto): {json.dumps(auto['routing'])}")
            forced = run_phase("tpu_batch_forced", doc, {
                "experimental.scheduler_policy": "tpu_batch",
                "experimental.tpu_device_floor": 1}, jax, counter, data_root)
            rows += [auto, forced]
            check(forced["device_windows"] > 0,
                  "forced: no device window was dispatched")
            check(forced["device_window_units"] > 0,
                  "forced: the device windows carried no units")
            for r in (auto, forced):
                check(r["c_engine"], f"{r['phase']}: C engine not attached")
                check(r["device"].get("platform") == "tpu",
                      f"{r['phase']}: ran on {r['device']}")
            dp = draw_plane_checks(jax, counter,
                                   int(doc["general"]["seed"]))
            say(f"calibrate, idle process: 4096-unit dispatch+readback "
                f"{dp['calibrate_dispatch_s_idle']:.6f} s, numpy "
                f"{dp['calibrate_np_per_unit_s_idle']:.3e} s/unit (during "
                f"the auto run: {auto['calibrate_dispatch_s']} s)")
            say(f"x64 flip: {dp['x64_flip_recompiles']} recompiles of the "
                f"warmed draw programs after jax_enable_x64 turns on")
            report.update(dp)
        for r in rows[1:]:
            check(r["device"].get("platform") == "tpu",
                  f"{r['phase']}: summary device {r['device']}")
        check_identity(rows)
    finally:
        shutil.rmtree(data_root, ignore_errors=True)
    report["phases"] = [{k: v for k, v in r.items() if k != "_ctl"}
                        for r in rows]
    out = ROOT / "chiprun_out"
    out.mkdir(exist_ok=True)
    (out / f"chip_smoke_{args.chips}.json").write_text(
        json.dumps(report, indent=1, default=str))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeError as exc:
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr, flush=True)
        sys.exit(1)
