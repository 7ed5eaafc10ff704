#!/usr/bin/env python
"""Benchmark harness (VERDICT.md round-1 item #2; BASELINE.md metric).

Default mode runs the headline benchmark and prints EXACTLY ONE JSON line:

    {"metric": "sim_sec_per_wall_sec_tgen1k_tpu_batch", "value": ...,
     "unit": "sim-sec/wall-sec", "vs_baseline": ...}

where vs_baseline is the ratio against the thread_per_core CPU policy on the
SAME machine and config (BASELINE.md records no absolute reference numbers —
the reference mount was empty — so the baseline is the reference's own
headline CPU policy re-implemented here, per BASELINE.json north_star).

``--all`` additionally measures every committed benchmark config under both
policies plus the raw draw-plane device-vs-numpy throughput, writing
BENCH_DETAIL.json next to this file. Progress goes to stderr; stdout carries
only the single JSON line.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "tools"))
from gen_benchmarks import random_gml, tor_doc  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _chip_child_env() -> dict:
    """Environment for a child process that runs a device policy. A chip
    belongs to one process: once this process has initialized a JAX
    backend it holds the chip and the child cannot attach it. So a leg
    that starts such children must run while this process is still off
    JAX (main() orders the --all legs that way); this refuses otherwise."""
    if "jax" in sys.modules:
        from jax._src import xla_bridge

        if xla_bridge.backends_are_initialized():
            raise RuntimeError(
                "bench.py: this process already holds the device, so a "
                "device-policy child could not attach it; run the legs "
                "that start children before any in-process device run")
    return dict(os.environ)


# -- shared median-of-N aggregation discipline (headline + tor rows) --------
def _median_run(rs: list) -> dict:
    """The median run by rate (ties keep the later run, like sorted())."""
    return sorted(rs, key=lambda r: r["sim_sec_per_wall_sec"])[len(rs) // 2]


def _run_rates(rs: list) -> list:
    return [round(r["sim_sec_per_wall_sec"], 3) for r in rs]


def _spread_rel(runs_by_policy: dict) -> dict:
    """(max-min)/median relative spread per policy — the anti-drift
    number published beside every interleaved median."""
    return {
        pol: round((max(v) - min(v)) / max(v[len(v) // 2], 1e-9), 4)
        for pol, v in ((p, sorted(_run_rates(r)))
                       for p, r in runs_by_policy.items())
    }


#: interleaved tpu spread above this is a warm-up-leak advisory (VERDICT
#: r5 weak #1): warm_shapes + the untimed warm-up run should hold the
#: spread at machine noise; raw per-run rates are published either way
SPREAD_ADVISORY = 0.15


def run_config(path: str, policy: str, tag: str, overrides: dict = None,
               collect=None) -> dict:
    from shadow_tpu.config import load_config
    from shadow_tpu.core.controller import Controller

    over = {
        "experimental.scheduler_policy": policy,
        "general.data_directory": f"/tmp/shadow-bench-{tag}",
    }
    if overrides:
        over.update(overrides)
    cfg = load_config(str(ROOT / path), over)
    t0 = time.perf_counter()
    ctl = Controller(cfg, mirror_log=False)
    result = ctl.run()
    result["total_wall_seconds"] = time.perf_counter() - t0  # incl. build
    # warm-up = everything outside the measured round loop (controller
    # build, device attach, finalize) — published on every row (VERDICT r4
    # weak #4): headline rates are steady-state loop rates BY DESIGN, and
    # this field keeps the excluded wall visible instead of silent.
    result["warmup_wall_seconds"] = round(
        result["total_wall_seconds"] - result["wall_seconds"], 3)
    if collect is not None:
        result.update(collect(ctl))
    if result["process_errors"]:
        log(f"WARNING {tag}: {len(result['process_errors'])} process errors")
    log(
        f"{tag}: {result['sim_sec_per_wall_sec']:.3f} sim-sec/wall-sec "
        f"({result['events']} events, {result['units_sent']} units, "
        f"{result['wall_seconds']:.2f}s loop wall, "
        f"{result['warmup_wall_seconds']:.1f}s warm-up)"
    )
    return result


def tor_client_stats(ctl) -> dict:
    """Tor latency CDFs + the fetch denominator (VERDICT r4 item #6):
    attempted/completed/failed counts and circuit-build + fetch latency
    percentiles, read from the TorClient apps after a run. The identity
    attempted = completed + failed + in-flight-at-stop holds by
    construction (every _build_circuit bumps attempted; every terminal
    path bumps exactly one of completed/failed) and is asserted."""
    import numpy as np

    clients = [p.app for h in ctl.hosts for p in h.processes
               if type(p.app).__name__ == "TorClient"]
    if not clients:
        return {}
    att = sum(c.attempted for c in clients)
    comp = sum(c.completed for c in clients)
    fail = sum(c.failed for c in clients)
    in_flight = att - comp - fail
    assert in_flight >= 0, (att, comp, fail)

    def pct(samples_ns):
        if not samples_ns:
            return None
        v = np.percentile(np.array(samples_ns, dtype=np.int64),
                          [50, 90, 99]) / 1e6
        return {"p50_ms": round(float(v[0]), 1),
                "p90_ms": round(float(v[1]), 1),
                "p99_ms": round(float(v[2]), 1)}

    fetch = [t for c in clients for t in c.completion_times]
    build = [t for c in clients for t in c.build_times]
    return {"tor_fetches": {
        "attempted": att, "completed": comp, "failed": fail,
        "in_flight_at_stop": in_flight,
        "circuit_build": pct(build), "fetch_e2e": pct(fetch),
    }}


def managed_bench(n_servers: int = 10, n_clients: int = 40,
                  nbytes: int = 100_000) -> dict:
    """Real-executable benchmark (VERDICT r2 item #4): N real C server
    binaries x M real C clients as managed processes under the preload
    shim — measures the native layer itself (spawn cost, syscall
    round-trips/sec, sim-s/wall-s) beside the pyapp configs."""
    import subprocess
    import time as _t

    from shadow_tpu.config import parse_config
    from shadow_tpu.core.controller import Controller

    build = ROOT / "native" / "build"
    subprocess.run(["make", "-C", str(ROOT / "native")], check=True,
                   capture_output=True)
    hosts = {}
    for i in range(n_servers):
        hosts[f"srv{i}"] = {
            "network_node_id": 0, "ip_addr": f"11.0.0.{i + 1}",
            "processes": [{
                "path": str(build / "tgen_srv"),
                "args": ["8080", str(n_clients // n_servers)],
                "expected_final_state": {"exited": 0}}]}
    for i in range(n_clients):
        hosts[f"cli{i}"] = {
            "network_node_id": 1,
            "processes": [{
                "path": str(build / "tgen_cli"),
                "args": [f"11.0.0.{(i % n_servers) + 1}", "8080",
                         str(nbytes)],
                "start_time": f"{1000 + i * 37} ms",
                "expected_final_state": {"exited": 0}}]}
    doc = {
        "general": {"stop_time": "30s", "seed": 11},
        "network": {"graph": {"type": "gml", "inline": """graph [
  directed 0
  node [ id 0 host_bandwidth_up "1 Gbit" host_bandwidth_down "1 Gbit" ]
  node [ id 1 host_bandwidth_up "1 Gbit" host_bandwidth_down "1 Gbit" ]
  edge [ source 0 target 1 latency "20 ms" ]
  edge [ source 0 target 0 latency "2 ms" ]
  edge [ source 1 target 1 latency "2 ms" ]
]"""}},
        "hosts": hosts,
    }
    cfg = parse_config(doc, {
        "general.data_directory": "/tmp/shadow-bench-managed"})
    t0 = _t.perf_counter()
    ctl = Controller(cfg, mirror_log=False)
    res = ctl.run()
    wall = _t.perf_counter() - t0
    nproc = n_servers + n_clients
    sysc = res["counters"].get("syscalls", 0)
    out = {
        "processes": nproc,
        "sim_sec_per_wall_sec": res["sim_sec_per_wall_sec"],
        "syscalls": sysc,
        "syscalls_per_wall_sec": round(sysc / res["wall_seconds"], 1),
        "spawn_plus_run_wall_s": round(wall, 3),
        "wall_per_process_ms": round(1000 * wall / nproc, 2),
        "bytes_sent": res["bytes_sent"],
        "errors": len(res["process_errors"]),
    }
    log(f"managed_{nproc}: {out['sim_sec_per_wall_sec']:.2f} sim-s/wall-s, "
        f"{out['syscalls_per_wall_sec']:.0f} syscalls/s, "
        f"{out['wall_per_process_ms']:.1f} ms wall/process")
    return out


def managed_dense_bench(n_procs: int = 4, iters: int = 40000,
                        chunk: int = 512, tag: str = "managed_dense") -> dict:
    """Syscall-DENSE managed benchmark (VERDICT r3 item #5 / weak #4):
    each process does ``iters`` write+read round trips through an
    emulated pipe (>= 30k trapped syscalls/process), so the number is the
    steady-state shim<->worker service rate, not spawn cost. The round-3
    managed_50 figure (1,316 syscalls/s over ~19 syscalls/process) was
    spawn-dominated; this measures the path the shmem fast paths serve."""
    import subprocess
    import time as _t

    from shadow_tpu.config import parse_config
    from shadow_tpu.core.controller import Controller

    build = ROOT / "native" / "build"
    subprocess.run(["make", "-C", str(ROOT / "native")], check=True,
                   capture_output=True)
    doc = {
        "general": {"stop_time": "60s", "seed": 3,
                    "data_directory": _fresh_dir(f"/tmp/shadow-bench-{tag}")},
        "network": {"graph": {"type": "gml", "inline": """graph [
  directed 0
  node [ id 0 host_bandwidth_up "1 Gbit" host_bandwidth_down "1 Gbit" ]
  edge [ source 0 target 0 latency "2 ms" ]
]"""}},
        "hosts": {
            f"box{i}": {"network_node_id": 0, "processes": [
                {"path": str(build / "pump"),
                 "args": [str(iters), str(chunk)],
                 "expected_final_state": {"exited": 0}}]}
            for i in range(n_procs)
        },
    }
    cfg = parse_config(doc, {})
    t0 = _t.perf_counter()
    res = Controller(cfg, mirror_log=False).run()
    wall = _t.perf_counter() - t0
    sysc = res["counters"].get("syscalls", 0)
    out = {
        "processes": n_procs,
        # each serviced syscall is one shim<->worker round trip; a pump
        # iteration is a write + a read = two of them
        "syscall_round_trips_per_process": 2 * iters,
        "syscalls": sysc,
        "syscalls_per_wall_sec": round(sysc / wall, 1),
        "wall_s": round(wall, 3),
        "errors": len(res["process_errors"]),
    }
    log(f"{tag}: {sysc} syscalls / {wall:.2f}s = "
        f"{out['syscalls_per_wall_sec']:.0f}/s steady-state")
    return out


def _count_curl_ok(data_dir: str, n_clients: int, nbytes: int) -> int:
    """Count validated transfers (code=200 + exact byte count) across the
    curl clients' captured stdout. Shared by both real-binary benches."""
    from pathlib import Path as _P

    ok = 0
    for i in range(n_clients):
        out = _P(f"{data_dir}/hosts/cli{i}/curl.0.stdout")
        if out.exists():
            ok += out.read_text().count(f"code=200 bytes={nbytes}")
    return ok


def _fresh_dir(path: str) -> str:
    """Remove-and-return a bench data directory: transfer validation
    counts stdout lines, so stale files from a previous run must not be
    able to satisfy the assertion."""
    import shutil

    shutil.rmtree(path, ignore_errors=True)
    return path


def managed_dense_contended(n_procs: int = 100, iters: int = 4000,
                            chunk: int = 512) -> dict:
    """The contended variant (VERDICT r4 weak #7): 100 concurrent
    managed processes pumping simultaneously, so the number includes
    worker-loop scheduling across many live guests, not just the
    per-round-trip floor the 4-process row measures."""
    return managed_dense_bench(n_procs=n_procs, iters=iters, chunk=chunk,
                               tag="managed_dense_contended")


def real_binary_bench(n_servers: int = 3, n_clients: int = 12,
                      nbytes: int = 400_000) -> dict:
    """Real OFF-THE-SHELF binaries as the workload (VERDICT r3 item #9):
    unmodified CPython http.server instances serve a data file to
    unmodified distro curl clients over the simulated network — the
    whole dynamic-linking / sockets / selectors / file-IO surface of two
    real programs under the shim, validated per run (curl must exit 0
    with the exact byte count; servers must still be running)."""
    import sys as _sys
    import time as _t
    from pathlib import Path as _P

    from shadow_tpu.config import parse_config
    from shadow_tpu.core.controller import Controller

    if not _P("/usr/bin/curl").exists():
        return {"skipped": "no /usr/bin/curl"}
    docroot = _P("/tmp/shadow-bench-docroot")
    docroot.mkdir(exist_ok=True)
    (docroot / "data.bin").write_bytes(b"x" * nbytes)
    hosts = {}
    for i in range(n_servers):
        hosts[f"web{i}"] = {
            "network_node_id": 0, "ip_addr": f"11.0.0.{i + 1}",
            "processes": [{
                "path": _sys.executable,
                "args": ["-u", "-m", "http.server", "--directory",
                         str(docroot), "--bind", "0.0.0.0", "8080"],
                "expected_final_state": "running"}]}
    for i in range(n_clients):
        url = f"http://11.0.0.{(i % n_servers) + 1}:8080/data.bin"
        hosts[f"cli{i}"] = {
            "network_node_id": 1,
            "processes": [{
                "path": "/usr/bin/curl",
                "args": ["-s", "-o", "/dev/null", "-w",
                         "code=%{http_code} bytes=%{size_download}\\n",
                         url, url],  # two sequential fetches per client
                "start_time": f"{1500 + i * 211} ms",
                "expected_final_state": {"exited": 0}}]}
    doc = {
        "general": {"stop_time": "30s", "seed": 13},
        "network": {"graph": {"type": "gml", "inline": """graph [
  directed 0
  node [ id 0 host_bandwidth_up "200 Mbit" host_bandwidth_down "200 Mbit" ]
  node [ id 1 host_bandwidth_up "50 Mbit" host_bandwidth_down "50 Mbit" ]
  edge [ source 0 target 1 latency "25 ms" ]
  edge [ source 0 target 0 latency "2 ms" ]
  edge [ source 1 target 1 latency "2 ms" ]
]"""}},
        "hosts": hosts,
    }
    cfg = parse_config(doc, {
        "general.data_directory": _fresh_dir("/tmp/shadow-bench-curl")})
    t0 = _t.perf_counter()
    ctl = Controller(cfg, mirror_log=False)
    res = ctl.run()
    wall = _t.perf_counter() - t0
    ok = _count_curl_ok("/tmp/shadow-bench-curl", n_clients, nbytes)
    sysc = res["counters"].get("syscalls", 0)
    out = {
        "servers": f"{n_servers}x CPython http.server",
        "clients": f"{n_clients}x /usr/bin/curl (2 fetches each)",
        "transfers_ok": ok,
        "transfers_expected": 2 * n_clients,
        "sim_sec_per_wall_sec": round(res["sim_sec_per_wall_sec"], 3),
        "syscalls": sysc,
        "wall_s": round(wall, 2),
        "errors": len(res["process_errors"]),
    }
    assert ok == 2 * n_clients, (ok, res["process_errors"])
    log(f"real_curl: {ok}/{2*n_clients} transfers, "
        f"{out['sim_sec_per_wall_sec']} sim-s/wall-s, {sysc} syscalls")
    return out


def _device_verdict(tag: str, row: dict, device_x: float) -> bool:
    """Round-5 Weak #5: a sub-1.0 device factor or a device that never
    serviced a window must be LOUD, not buried in a JSON blob. Stamps
    and returns the ``device_engaged`` flag for the row the factor was
    actually measured on."""
    engaged = row.get("device_windows_dispatched", 0) > 0
    row["device_engaged"] = engaged
    if not engaged:
        log(f"WARNING {tag}: device_engaged=false — the tpu_batch run "
            f"serviced ZERO fused windows on the device; the numpy/C twin "
            f"carried the whole run (this is NOT a TPU result)")
    verdict = "WIN" if device_x > 1.0 else (
        "WASH" if device_x >= 0.99 else "LOSS")
    log(f"device is a net {verdict} on {tag}: device_x={device_x} "
        f"(windows={row.get('device_windows_dispatched', 0)}, "
        f"spec_hits={row.get('device', {}).get('spec_hits', 0)})")
    return engaged


def ablation(path: str, tag: str, base: dict, full: dict,
             reps: int = 1, full_rates: list = None) -> dict:
    """Per-config headline decomposition (VERDICT r4 item #1): two extra
    rows isolate what each ingredient of the tpu_batch policy buys —

      tpu_columnar_python_cpu: columnar plane, no C engine, no device
      tpu_columnar_c_cpu:      columnar plane + C engine, no device

    so the published ratio factors as
      total = architecture (columnar-python / per-unit-python)
            x c_engine     (columnar-C / columnar-python)
            x device       (full tpu_batch / columnar-C)
    All rows are asserted result-identical; only wall time moves.

    ``reps`` > 1 measures the ablation rows with the same interleaved
    median-of-N discipline as the headline (shared-machine noise drifts
    on the scale of one run; a single-run device_x is noise-dominated
    exactly where the factor matters). ``full_rates`` carries the
    headline row's raw rates so the published factors' provenance is
    recomputable."""
    cs, ps, fs = [], [], []
    for i in range(reps):
        cs.append(run_config(path, "tpu_batch", f"{tag}-ccpu",
                             {"experimental.tpu_device_floor": -1}))
        # device_x's two sides must share the SAME noise window: a fresh
        # full-path rep rides next to each device-off rep (the headline
        # full rows were measured minutes earlier against the
        # thread_per_core baseline — machine drift between those windows
        # lands straight in the factor otherwise)
        if reps > 1:
            fs.append(run_config(path, "tpu_batch", f"{tag}-devx"))
        ps.append(run_config(path, "tpu_batch", f"{tag}-pycpu",
                             {"experimental.tpu_device_floor": -1,
                              "experimental.native_colcore": False}))

    c_cpu, py_cpu = _median_run(cs), _median_run(ps)
    full_dev = _median_run(fs) if fs else full
    for k in ("events", "units_sent", "units_dropped", "bytes_sent"):
        assert c_cpu[k] == full[k] and py_cpu[k] == full[k], (tag, k)
        assert full_dev[k] == full[k], (tag, k)

    def x(a, b):
        return round(a["sim_sec_per_wall_sec"] / b["sim_sec_per_wall_sec"], 3)

    out = {
        "tpu_columnar_python_cpu": py_cpu,
        "tpu_columnar_c_cpu": c_cpu,
        "factors": {
            "architecture_x": x(py_cpu, base),
            "c_engine_x": x(c_cpu, py_cpu),
            "device_x": x(full_dev, c_cpu),
            "total_x": x(full, base),
        },
    }
    if reps > 1:
        out["ablation_raw_rates"] = {
            "tpu_columnar_c_cpu": [
                round(r["sim_sec_per_wall_sec"], 3) for r in cs],
            "tpu_batch_devx": [
                round(r["sim_sec_per_wall_sec"], 3) for r in fs],
            "tpu_columnar_python_cpu": [
                round(r["sim_sec_per_wall_sec"], 3) for r in ps],
            "tpu_batch_headline": full_rates or [],
            "aggregation": f"median-of-{reps}, interleaved; device_x = "
                           f"median(tpu_batch_devx)/median(c_cpu), "
                           f"same-window pairs",
        }
    out["device_engaged"] = _device_verdict(
        tag, full_dev if fs else full, out["factors"]["device_x"])
    return out


def _shim_audit_table(ctl, counters, top_n: int = 10) -> dict:
    """Per-syscall-number audit of managed-process servicing: where did
    the round trips go, and what completed in-shim. Slow counts come
    from the controller-scoped census (never in fingerprints); fast
    counts from the per-class shim_fast_* counters."""
    from gen_bpf import SYS as _SYS

    names = {v: k for k, v in _SYS.items()}
    slow = getattr(ctl, "_shim_slow_nrs", {})
    top = sorted(slow.items(), key=lambda kv: (-kv[1], kv[0]))[:top_n]
    fast_classes = {
        k.replace("shim_fast_", ""): v for k, v in counters.items()
        if k.startswith("shim_fast_") and k != "shim_fast_syscalls"}
    total = counters.get("syscalls", 0)
    fast = counters.get("shim_fast_syscalls", 0)
    return {
        "syscalls_total": total,
        "in_shim": fast,
        "fast_ratio": round(fast / total, 3) if total else 0.0,
        "in_shim_by_class": fast_classes,
        "worker_round_trips_top": [
            {"nr": nr, "name": names.get(nr, f"sys_{nr}"), "count": k}
            for nr, k in top],
    }


def _curl_1k_doc(n_servers: int, n_clients: int, fetches: int,
                 nbytes: int) -> dict:
    """The real_curl_1k workload document — shared with
    managed_ckpt_overhead, which A/Bs guest journaling on the exact
    workload whose rate the headline real-binary row publishes."""
    import sys as _sys
    from pathlib import Path as _P

    import numpy as np

    assert n_servers <= 254, "server ips are drawn from one /24"

    rng = np.random.default_rng(17)
    g = 64
    gml = random_gml(rng, g, min_lat_ms=5, max_lat_ms=60, max_loss=0.0,
                     bw_choices=("50 Mbit", "100 Mbit", "1 Gbit"))
    docroot = _P("/tmp/shadow-bench-docroot1k")
    docroot.mkdir(exist_ok=True)
    (docroot / "data.bin").write_bytes(b"x" * nbytes)
    hosts = {}
    for i in range(n_servers):
        hosts[f"web{i}"] = {
            "network_node_id": int(rng.integers(0, g)),
            "ip_addr": f"12.0.0.{i + 1}",
            "processes": [{
                "path": _sys.executable,
                "args": ["-u", "-m", "http.server", "--directory",
                         str(docroot), "--bind", "0.0.0.0", "8080"],
                "expected_final_state": "running"}]}
    for i in range(n_clients):
        urls = [f"http://12.0.0.{(i + k) % n_servers + 1}:8080/data.bin"
                for k in range(fetches)]
        hosts[f"cli{i}"] = {
            "network_node_id": int(rng.integers(0, g)),
            "processes": [{
                "path": "/usr/bin/curl",
                "args": (["-s", "-o", "/dev/null", "-w",
                          "code=%{http_code} bytes=%{size_download}\\n"]
                         + urls),
                "start_time": f"{2000 + i * 97} ms",
                "expected_final_state": {"exited": 0}}]}
    return {
        "general": {"stop_time": "60s", "seed": 23},
        "network": {"graph": {"type": "gml", "inline": gml}},
        "hosts": hosts,
    }


def real_curl_1k(n_servers: int = 50, n_clients: int = 200,
                 fetches: int = 5, nbytes: int = 50_000,
                 reps: int = 3) -> dict:
    """Real-binary benchmark at benchmark scale (VERDICT r4 item #5):
    ``n_servers`` unmodified CPython http.server instances serve
    ``n_clients`` unmodified distro curl clients (``fetches`` sequential
    fetches each) over a 64-node random graph — and BOTH benchmark
    policies run it, so the published ratio is architecture-honest for
    managed real-binary workloads too, not just pyapp models. Every
    transfer is validated (code=200 + exact byte count)."""
    import time as _t
    from pathlib import Path as _P

    from shadow_tpu.config import parse_config
    from shadow_tpu.core.controller import Controller

    if not _P("/usr/bin/curl").exists():
        return {"skipped": "no /usr/bin/curl"}
    doc = _curl_1k_doc(n_servers, n_clients, fetches, nbytes)

    def run(policy, tag):
        cfg = parse_config(doc, {
            "general.data_directory": _fresh_dir(f"/tmp/shadow-bench-{tag}"),
            "experimental.scheduler_policy": policy})
        t0 = _t.perf_counter()
        ctl = Controller(cfg, mirror_log=False)
        res = ctl.run()
        wall = _t.perf_counter() - t0
        ok = _count_curl_ok(f"/tmp/shadow-bench-{tag}", n_clients, nbytes)
        row = {
            "sim_sec_per_wall_sec": round(res["sim_sec_per_wall_sec"], 3),
            "wall_seconds": round(res["wall_seconds"], 2),
            "warmup_wall_seconds": round(wall - res["wall_seconds"], 1),
            "transfers_ok": ok,
            "syscalls": res["counters"].get("syscalls", 0),
            "shim_fast_syscalls": res["counters"].get(
                "shim_fast_syscalls", 0),
            "errors": len(res["process_errors"]),
        }
        assert ok == fetches * n_clients, (
            tag, ok, res["process_errors"][:3])
        log(f"real_curl_1k[{policy}]: {ok} transfers, "
            f"{row['sim_sec_per_wall_sec']} sim-s/wall-s, "
            f"{row['wall_seconds']}s loop wall")
        return row, _shim_audit_table(ctl, res["counters"])

    # interleaved median-of-reps: tpc/tpu alternate within each rep so
    # box drift (thermal, page cache) hits both policies alike
    tpc_rows, tpu_rows = [], []
    for rep in range(reps):
        tpc_rows.append(run("thread_per_core", f"curl1k-tpc-{rep}"))
        tpu_rows.append(run("tpu_batch", f"curl1k-tpu-{rep}"))

    def med(rows):
        rates = sorted(r["sim_sec_per_wall_sec"] for r, _ in rows)
        m = rates[len(rates) // 2]
        row, audit = next((r, a) for r, a in rows
                          if r["sim_sec_per_wall_sec"] == m)
        row = dict(row)
        row["raw_rates"] = rates
        row["spread"] = round(rates[-1] - rates[0], 3)
        return row, audit

    tpc, tpc_audit = med(tpc_rows)
    tpu, tpu_audit = med(tpu_rows)
    ratio = tpu["sim_sec_per_wall_sec"] / tpc["sim_sec_per_wall_sec"]
    out = {
        "servers": f"{n_servers}x CPython http.server",
        "clients": f"{n_clients}x /usr/bin/curl ({fetches} fetches each)",
        "transfers": fetches * n_clients,
        "aggregation": f"median-of-{reps}, interleaved",
        "thread_per_core": tpc,
        "tpu_batch": tpu,
        "ratio_tpu_vs_thread_per_core": round(ratio, 2),
        "shim_audit": {"thread_per_core": tpc_audit,
                       "tpu_batch": tpu_audit},
    }
    for pol, audit in (("thread_per_core", tpc_audit),
                       ("tpu_batch", tpu_audit)):
        if audit["syscalls_total"] and audit["in_shim"] == 0:
            # the device_engaged discipline applied to the shim: a
            # managed row whose fast-path never fired is measuring the
            # round-trip plane, not the one this benchmark advertises
            out.setdefault("warnings", []).append(
                f"{pol}: shim fast-path ratio is 0 "
                f"({audit['syscalls_total']} syscalls all took worker "
                f"round trips) — fast plane disabled or broken")
            log(f"real_curl_1k WARNING: {pol} shim fast-path ratio is 0 "
                f"— every managed syscall took a worker round trip")
    log(f"real_curl_1k ratio: {ratio:.2f}x "
        f"({out['transfers']} validated transfers per side; shim fast "
        f"ratio tpu={tpu_audit['fast_ratio']}, tpc={tpc_audit['fast_ratio']})")
    return out


def managed_ckpt_overhead(n_servers: int = 50, n_clients: int = 200,
                          fetches: int = 5, nbytes: int = 50_000,
                          reps: int = 3) -> dict:
    """What does checkpointability COST a real-binary run? (Checkpoint
    format v5 row.) A/Bs the guest syscall journal — the only per-syscall
    work a v5-checkpointable run adds when it never actually snapshots —
    on the real_curl_1k workload itself, journal forced on vs forced off
    via SHADOW_TPU_GUEST_JOURNAL, interleaved median-of-reps. Then times
    one actual checkpoint->resume cycle on examples/managed_smoke.yaml:
    a reexec snapshot re-buys the prefix (O(prefix) restore by design),
    so the row publishes resume wall beside the uninterrupted wall."""
    import os as _os
    import time as _t
    from pathlib import Path as _P

    from shadow_tpu import checkpoint as _ckpt
    from shadow_tpu.config import load_config, parse_config
    from shadow_tpu.core.controller import Controller

    if not _P("/usr/bin/curl").exists():
        return {"skipped": "no /usr/bin/curl"}
    doc = _curl_1k_doc(n_servers, n_clients, fetches, nbytes)

    def run(journal, tag):
        cfg = parse_config(doc, {
            "general.data_directory": _fresh_dir(f"/tmp/shadow-bench-{tag}"),
            "experimental.scheduler_policy": "tpu_batch"})
        prev = _os.environ.get("SHADOW_TPU_GUEST_JOURNAL")
        _os.environ["SHADOW_TPU_GUEST_JOURNAL"] = "1" if journal else "0"
        try:
            ctl = Controller(cfg, mirror_log=False)
            res = ctl.run()
        finally:
            if prev is None:
                del _os.environ["SHADOW_TPU_GUEST_JOURNAL"]
            else:
                _os.environ["SHADOW_TPU_GUEST_JOURNAL"] = prev
        ok = _count_curl_ok(f"/tmp/shadow-bench-{tag}", n_clients, nbytes)
        assert ok == fetches * n_clients, (tag, ok, res["process_errors"][:3])
        oplogs = list(
            _P(f"/tmp/shadow-bench-{tag}/guest_oplogs").glob("*.jsonl"))
        assert bool(oplogs) == journal, (tag, journal, len(oplogs))
        row = {
            "sim_sec_per_wall_sec": round(res["sim_sec_per_wall_sec"], 3),
            "wall_seconds": round(res["wall_seconds"], 2),
            "transfers_ok": ok,
        }
        if journal:
            row["journal_files"] = len(oplogs)
            row["journal_bytes"] = sum(p.stat().st_size for p in oplogs)
        log(f"managed_ckpt_overhead[journal={'on' if journal else 'off'}]: "
            f"{row['sim_sec_per_wall_sec']} sim-s/wall-s, "
            f"{row['wall_seconds']}s loop wall")
        return row

    # interleaved median-of-reps, off/on alternating within each rep so
    # box drift hits both arms alike (the real_curl_1k discipline)
    off_rows, on_rows = [], []
    for rep in range(reps):
        off_rows.append(run(False, f"ckptov-off-{rep}"))
        on_rows.append(run(True, f"ckptov-on-{rep}"))

    def med(rows):
        rates = sorted(r["sim_sec_per_wall_sec"] for r in rows)
        m = rates[len(rates) // 2]
        row = dict(next(r for r in rows if r["sim_sec_per_wall_sec"] == m))
        row["raw_rates"] = rates
        row["spread"] = round(rates[-1] - rates[0], 3)
        return row

    off, on = med(off_rows), med(on_rows)
    overhead = 1.0 - on["sim_sec_per_wall_sec"] / off["sim_sec_per_wall_sec"]
    out = {
        "workload": f"real_curl_1k shape ({n_servers} http.server x "
                    f"{n_clients} curl, {fetches} fetches each)",
        "aggregation": f"median-of-{reps}, interleaved",
        "journal_off": off,
        "journal_on": on,
        "journal_overhead_rel": round(overhead, 4),
    }
    if overhead > 0.10:
        out.setdefault("warnings", []).append(
            f"guest journaling costs {overhead:.1%} of the real-binary "
            f"rate (> 10%) — the per-reply journal append is leaking into "
            f"the syscall service path")
        log(f"managed_ckpt_overhead WARNING: journaling overhead "
            f"{overhead:.1%} > 10% of the real_curl_1k rate")
    log(f"managed_ckpt_overhead: journaling costs {overhead:+.1%} "
        f"({off['sim_sec_per_wall_sec']} -> {on['sim_sec_per_wall_sec']} "
        f"sim-s/wall-s median)")

    # one real checkpoint->resume cycle: how much wall does a v5 reexec
    # restore re-buy? (managed_smoke: 300 kB tgen fetch, ~1.7 s sim)
    smoke = (ROOT / "examples" / "managed_smoke.yaml").read_text().replace(
        "native/build/", str(ROOT / "native" / "build") + "/")
    smoke_yaml = _P("/tmp/shadow-bench-ckptov-smoke.yaml")
    smoke_yaml.write_text(smoke)
    t0 = _t.perf_counter()
    Controller(load_config(str(smoke_yaml), {
        "general.data_directory": _fresh_dir(
            "/tmp/shadow-bench-ckptov-base")}), mirror_log=False).run()
    base_wall = _t.perf_counter() - t0
    src_dir = _fresh_dir("/tmp/shadow-bench-ckptov-src")
    Controller(load_config(str(smoke_yaml), {
        "general.data_directory": src_dir,
        "general.checkpoint_every": "500 ms"}), mirror_log=False).run()
    cks = sorted(_P(src_dir).glob("checkpoints/ckpt_*.ckpt"))
    assert cks, f"no checkpoints written under {src_dir}"
    t0 = _t.perf_counter()
    ctl, resume_at = _ckpt.load_checkpoint(
        cks[-1], cfg=load_config(str(smoke_yaml), {
            "general.data_directory": _fresh_dir(
                "/tmp/shadow-bench-ckptov-res")}), mirror_log=False)
    res = ctl.run(resume_at=resume_at)
    resume_wall = _t.perf_counter() - t0
    assert res["process_errors"] == [], res["process_errors"]
    hdr = _ckpt.read_header(cks[-1])
    out["resume"] = {
        "config": "examples/managed_smoke.yaml",
        "snapshot_sim_ns": int(hdr["sim_time_ns"]),
        "resume_wall_seconds": round(resume_wall, 3),
        "uninterrupted_wall_seconds": round(base_wall, 3),
        # a reexec restore re-runs the prefix, so ratio ~1 is the design
        # point; >>1 would mean restore machinery is adding real cost
        "resume_vs_uninterrupted": round(resume_wall / base_wall, 2),
    }
    log(f"managed_ckpt_overhead resume: v5 reexec restore from sim "
        f"{hdr['sim_time_ns']} ns took {resume_wall:.2f}s wall vs "
        f"{base_wall:.2f}s uninterrupted "
        f"({out['resume']['resume_vs_uninterrupted']}x)")
    return out


def managed_fidelity_audit(n_clients: int = 24,
                           nbytes: int = 100_000) -> dict:
    """Model-fidelity audit (checkpoint-PR headline row): the SAME
    topology runs the tgen protocol twice — once with the real C binaries
    (tgen_srv streaming to ring_probe under the preload shim), once with
    the Python model twins (models.tgen TGenServer/TGenClient) — and the
    row publishes both fetch-latency distributions side by side. Both
    latencies are sim-time observables, so each leg is deterministic and
    runs once: the real client self-times its fetch through the
    virtualized monotonic clock (``fetch_ns=`` on ring_probe stdout,
    t0 before connect, t1 after EOF drain), the twin records
    ``completion_times`` at the last payload byte. Client starts are
    staggered wide enough that transfers never overlap — tgen_srv
    accepts serially while the twin server is concurrent, and queueing
    skew would otherwise masquerade as protocol infidelity."""
    import re as _re
    import subprocess
    from pathlib import Path as _P

    import numpy as np

    from shadow_tpu.config import parse_config
    from shadow_tpu.core.controller import Controller

    build = ROOT / "native" / "build"
    subprocess.run(["make", "-C", str(ROOT / "native")], check=True,
                   capture_output=True)

    rng = np.random.default_rng(7)
    g = 16
    gml = random_gml(rng, g, min_lat_ms=5, max_lat_ms=60, max_loss=0.0,
                     bw_choices=("50 Mbit", "100 Mbit", "1 Gbit"))
    srv_node = int(rng.integers(0, g))
    cli_nodes = [int(rng.integers(0, g)) for _ in range(n_clients)]
    starts = [f"{2000 + i * 400} ms" for i in range(n_clients)]

    def doc(real):
        if real:
            srv = {"path": str(build / "tgen_srv"),
                   "args": ["8080", str(n_clients)],
                   "expected_final_state": {"exited": 0}}
            cli = lambda i: {"path": str(build / "ring_probe"),
                             "args": ["11.0.0.1", "8080", str(nbytes)],
                             "start_time": starts[i],
                             "expected_final_state": {"exited": 0}}
        else:
            srv = {"path": "pyapp:shadow_tpu.models.tgen:TGenServer",
                   "args": ["8080"]}
            cli = lambda i: {
                "path": "pyapp:shadow_tpu.models.tgen:TGenClient",
                "args": [str(nbytes), "1", "serial", "8080", "srv"],
                "start_time": starts[i],
                "expected_final_state": {"exited": 0}}
        hosts = {"srv": {"network_node_id": srv_node,
                         "ip_addr": "11.0.0.1", "processes": [srv]}}
        for i in range(n_clients):
            hosts[f"cli{i}"] = {"network_node_id": cli_nodes[i],
                                "processes": [cli(i)]}
        return {
            "general": {"stop_time": f"{4 + (n_clients * 400) // 1000}s",
                        "seed": 7},
            "network": {"graph": {"type": "gml", "inline": gml}},
            "hosts": hosts,
        }

    # real leg: every client prints its self-timed fetch_ns
    d = _fresh_dir("/tmp/shadow-bench-fidelity-real")
    cfg = parse_config(doc(True), {"general.data_directory": d})
    res = Controller(cfg, mirror_log=False).run()
    assert res["process_errors"] == [], res["process_errors"][:3]
    real_ns = []
    for i in range(n_clients):
        out = _P(f"{d}/hosts/cli{i}/ring_probe.0.stdout").read_text()
        assert f"bytes={nbytes}" in out and "eof=1" in out, (i, out)
        real_ns.append(int(_re.search(r"fetch_ns=(\d+)", out).group(1)))

    # model-twin leg: same nodes, same stagger, same byte counts
    d = _fresh_dir("/tmp/shadow-bench-fidelity-model")
    cfg = parse_config(doc(False), {"general.data_directory": d})
    ctl = Controller(cfg, mirror_log=False)
    res = ctl.run()
    assert res["process_errors"] == [], res["process_errors"][:3]
    model_ns = []
    for h in ctl.hosts:
        if h.name.startswith("cli"):
            (proc,) = h.processes
            (elapsed,) = proc.app.completion_times
            model_ns.append(int(elapsed))
    assert len(model_ns) == n_clients, len(model_ns)

    def pcts(ns):
        s = sorted(ns)
        p = lambda q: round(s[min(len(s) - 1, int(q * len(s)))] / 1e6, 3)
        return {"p50_ms": p(0.50), "p90_ms": p(0.90), "p99_ms": p(0.99),
                "min_ms": round(s[0] / 1e6, 3),
                "max_ms": round(s[-1] / 1e6, 3)}

    # clients pair 1:1 across legs (same index = same graph node, same
    # start, same byte count), so the per-pair error IS the model gap
    rel = sorted(r / m - 1.0 for r, m in zip(real_ns, model_ns))
    out = {
        "workload": f"{n_clients} single-fetch clients x {nbytes} B, "
                    f"one serial tgen server, {g}-node random graph",
        "real_binaries": pcts(real_ns),
        "model_twin": pcts(model_ns),
        "paired_rel_error": {
            "median": round(rel[len(rel) // 2], 4),
            "worst": round(max(rel, key=abs), 4),
        },
        "semantics": "real = ring_probe connect->EOF self-timed via the "
                     "virtualized clock; twin = TGenClient connect->last "
                     "payload byte (completion_times)",
    }
    if abs(out["paired_rel_error"]["median"]) > 0.25:
        out.setdefault("warnings", []).append(
            f"median real-vs-twin fetch latency gap "
            f"{out['paired_rel_error']['median']:+.1%} (> 25%) — the "
            f"Python twin is drifting from what the real protocol does "
            f"on this transport")
        log(f"managed_fidelity_audit WARNING: median real-vs-twin gap "
            f"{out['paired_rel_error']['median']:+.1%} > 25%")
    log(f"managed_fidelity_audit: real p50 "
        f"{out['real_binaries']['p50_ms']} ms vs twin p50 "
        f"{out['model_twin']['p50_ms']} ms "
        f"(median paired gap {out['paired_rel_error']['median']:+.1%}, "
        f"{n_clients} paired fetches)")
    return out


def _tor_churned_doc(stop_s: int = 8) -> dict:
    """The tor 1/10-scale config under production-realistic adversity:
    a healing bipartite partition, a degrade window, and seeded client
    churn, with ONE mid-run checkpoint (4 sim-s cadence on an 8 sim-s
    run). One snapshot per run is the hourly-equivalent discipline at
    this scale: a snapshot's wall is plane-independent pickling of the
    same object graph (~1.3 s for 10.7k hosts, published as
    phase_wall.checkpoint), so at production cadence — one per hour of
    wall — it amortizes to noise, while a sim-time-scaled cadence at
    bench scale would bill the fast plane 3 orders of magnitude more
    snapshot wall per sim-second than the full-scale run ever pays.
    Deterministic from the fixed seeds like the base doc."""
    doc = tor_doc(700, 10_000, stop_s)
    doc["faults"] = {
        "events": [
            {"time": "2500 ms", "kind": "link_down",
             "src_nodes": [0, 1, 2, 3], "dst_nodes": [],
             "duration": "1200 ms"},
            {"time": "4500 ms", "kind": "link_degrade",
             "src_nodes": [4, 5, 6, 7], "dst_nodes": [],
             "latency_factor": 1.5, "loss_add": 0.01,
             "bandwidth_scale": 0.8, "duration": "2s"},
        ],
        "churn": [
            {"hosts": ["u1_*", "u2_*", "u3_*"], "mean_uptime": "5s",
             "mean_downtime": "1s", "start_time": "2s"},
        ],
    }
    doc["general"]["checkpoint_every"] = "4s"
    return doc


def tor_churned_ckpt(base_ratio=None) -> dict:
    """The fast-AND-robust row (PR 6 acceptance): the tor 1/10-scale
    config with faults + periodic checkpoints enabled and the C engine
    ON — the production-realistic scenario that previously force-
    disabled the C plane and ran at ~1/7th speed. Interleaved
    median-of-3 subprocess pairs like the base small-scale rows; the
    published robustness tax is the churned ratio relative to the clean
    12.89x row, with the acceptance bar at 15%."""
    import os
    import subprocess
    import time as _t

    import yaml

    import shutil

    doc = _tor_churned_doc(8)
    ypath = "/tmp/shadow-bench-tor10k-churn.yaml"
    with open(ypath, "w") as f:
        yaml.safe_dump(doc, f, default_style=None)

    def sub(policy, tag):
        # a stale data dir would leave old-cadence checkpoints behind and
        # corrupt the checkpoints_written evidence below
        shutil.rmtree(f"/tmp/shadow-bench-{tag}", ignore_errors=True)
        t0 = _t.perf_counter()
        r = subprocess.run(
            [sys.executable, "-m", "shadow_tpu", ypath,
             "--scheduler-policy", policy,
             "--data-directory", f"/tmp/shadow-bench-{tag}",
             "--json-summary", "--quiet"],
            capture_output=True, text=True, timeout=3600,
            env=_chip_child_env(), cwd=str(ROOT))
        assert r.returncode == 0, (tag, r.stderr[-500:])
        s = json.loads(r.stdout)
        s["subprocess_wall_s"] = round(_t.perf_counter() - t0, 1)
        return s

    N = 3
    reps = {"tpu_batch": [], "thread_per_core": []}
    for i in range(N):
        for pol, tag in (("tpu_batch", "tpu"), ("thread_per_core", "tpc")):
            reps[pol].append(sub(pol, f"tor10kck-{tag}{i}"))
    ref = reps["tpu_batch"][0]
    for pol, rs in reps.items():
        for s in rs:
            for k in ("events", "units_sent", "units_dropped",
                      "bytes_sent", "rounds", "counters",
                      "fault_transitions_applied", "units_blackholed"):
                if pol == "tpu_batch":
                    assert s[k] == ref[k], \
                        f"churned tor determinism: {k} diverged"
                elif k not in ("rounds", "counters"):
                    assert s[k] == ref[k], \
                        f"churned tor policy divergence on {k}"
    # the adversity actually ran, under the C engine, with checkpoints
    assert ref["counters"].get("host_crashes", 0) > 0
    assert ref["units_blackholed"] > 0
    ckpts = sorted(Path("/tmp/shadow-bench-tor10kck-tpu0/checkpoints")
                   .glob("*.ckpt"))
    assert ckpts, "churned tor run wrote no checkpoints"
    sa = _median_run(reps["tpu_batch"])
    sc = _median_run(reps["thread_per_core"])
    ratio = sa["sim_sec_per_wall_sec"] / sc["sim_sec_per_wall_sec"]
    spread = _spread_rel(reps)

    # the snapshot wall is plane-independent (same pickled graph either
    # way), so decompose the ratio: as-measured (snapshot included) and
    # loop-only (snapshot wall excluded on both sides) — the latter is
    # what the C plane is responsible for under adversity
    def _excl_ck(s):
        w = s["wall_seconds"] - s["phase_wall"].get("checkpoint", 0.0)
        return s["sim_seconds"] / w if w > 0 else 0.0

    ratio_loop = _excl_ck(sa) / _excl_ck(sc) if _excl_ck(sc) else 0.0
    out = {
        pol: {
            "sim_sec_per_wall_sec": round(s["sim_sec_per_wall_sec"], 3),
            "events": s["events"],
            "wall_seconds": round(s["wall_seconds"], 2),
            "max_rss_mb": s["max_rss_mb"],
            "phase_wall": s.get("phase_wall"),
            "raw_rates": _run_rates(reps[pol]),
            "spread_rel": spread[pol],
        }
        for pol, s in (("tpu_batch", sa), ("thread_per_core", sc))
    }
    out.update({
        "ratio_tpu_vs_thread_per_core": round(ratio, 2),
        "ratio_excl_checkpoint_wall": round(ratio_loop, 2),
        "checkpoint_wall_seconds": {
            pol: round(s["phase_wall"].get("checkpoint", 0.0), 3)
            for pol, s in (("tpu_batch", sa), ("thread_per_core", sc))},
        "fault_evidence": {
            "fault_transitions_applied": ref["fault_transitions_applied"],
            "host_crashes": ref["counters"].get("host_crashes"),
            "host_boots": ref["counters"].get("host_boots"),
            "units_blackholed": ref["units_blackholed"],
            "units_teardown_dropped": ref["counters"].get(
                "units_teardown_dropped"),
            "checkpoints_written": len(ckpts),
        },
        "aggregation": f"median-of-{N}, interleaved subprocess pairs; "
                       f"ratio = median/median",
        "note": "tor 1/10 scale under partition + degrade + client churn "
                "with one mid-run checkpoint, C engine ON (the scenario "
                "that force-disabled it before PR 6). Snapshot wall is "
                "plane-independent pickling (phase_wall.checkpoint, same "
                "seconds either plane), so the published tax decomposes: "
                "ratio (snapshot included, at this scale's one-per-run "
                "cadence) vs ratio_excl_checkpoint_wall (the adversity "
                "cost the C plane answers for).",
    })
    if base_ratio:
        out["base_ratio_clean"] = base_ratio
        out["robustness_tax_rel"] = round(1 - ratio / base_ratio, 3)
    log(f"tor_1_10_churned_ckpt: tpu {sa['sim_sec_per_wall_sec']:.3f} vs "
        f"tpc {sc['sim_sec_per_wall_sec']:.3f} = {ratio:.2f}x "
        f"({ratio_loop:.2f}x excl. plane-independent snapshot wall; "
        f"clean base {base_ratio}; spread {spread})")
    return out


def tor_400_sweep(n_seeds: int = 10, jobs: int = 2) -> dict:
    """Fleet-mode row (ROADMAP item 5 acceptance): the 10-seed tor_400
    sweep in ONE command vs standalone single runs, interleaved.

    Protocol: 3x (standalone single, 10-seed sweep at jobs=2 with the
    shared draw service) interleaved, plus one no-service sweep (the
    shared-attach ablation) and one jobs=1 sweep (the jobs-efficiency
    leg — on this box's 2 HT vCPUs, packing gains little; amortization
    is the win). Identity evidence rides along at zero extra cost: the
    service and no-service sweeps must agree on every per-seed tree
    hash, and the base seed's in-sweep tree must equal the standalone
    run's tree."""
    import os
    import shutil
    import subprocess
    import time as _t

    from shadow_tpu import fleet as _fleet

    cfg = "examples/tor_400relay.yaml"
    env = _chip_child_env()

    def single(tag):
        d = f"/tmp/shadow-bench-sw-single-{tag}"
        shutil.rmtree(d, ignore_errors=True)
        t0 = _t.perf_counter()
        r = subprocess.run(
            [sys.executable, "-m", "shadow_tpu", cfg, "--quiet",
             "--data-directory", d, "--scheduler-policy", "tpu_batch",
             "--sample-every", "10s"],
            capture_output=True, text=True, timeout=3600, env=env,
            cwd=str(ROOT))
        assert r.returncode == 0, (tag, r.stderr[-500:])
        return round(_t.perf_counter() - t0, 2), d

    def sweep(tag, extra):
        d = f"/tmp/shadow-bench-sw-{tag}"
        shutil.rmtree(d, ignore_errors=True)
        t0 = _t.perf_counter()
        r = subprocess.run(
            [sys.executable, "-m", "shadow_tpu.fleet", "sweep", cfg,
             "--seeds", str(n_seeds), "--sweep-dir", d,
             "--set", "experimental.scheduler_policy=tpu_batch",
             "--quiet", "--json"] + extra,
            capture_output=True, text=True, timeout=3600, env=env,
            cwd=str(ROOT))
        assert r.returncode == 0, (tag, r.stderr[-800:])
        s = json.loads(r.stdout)
        assert len(s["completed"]) == n_seeds, (tag, s["failed"])
        return round(_t.perf_counter() - t0, 2), d, s

    # every leg rides the same interleaved median-of-3 discipline: this
    # box's wall noise is +-20% on runs of this length, so a single-shot
    # ablation leg would publish noise as a finding
    singles = []
    sweeps = []
    nosvcs = []
    j1s = []
    for i in range(3):
        singles.append(single(f"i{i}"))
        sweeps.append(sweep(f"svc{i}", ["--jobs", str(jobs)]))
        nosvcs.append(sweep(
            f"nosvc{i}", ["--jobs", str(jobs), "--no-device-service"]))
        j1s.append(sweep(f"j1-{i}", ["--jobs", "1"]))
        log(f"tor_400_sweep rep {i}: single {singles[-1][0]}s, "
            f"sweep {sweeps[-1][0]}s, no-service {nosvcs[-1][0]}s, "
            f"jobs=1 {j1s[-1][0]}s")

    def _med(runs):
        return sorted(w for w, *_ in runs)[len(runs) // 2]

    med_single = _med(singles)
    med_sweep = _med(sweeps)
    nosvc_wall = _med(nosvcs)
    j1_wall = _med(j1s)
    nosvc_dir = nosvcs[0][1]
    med_i = [w for w, _d, _s in sweeps].index(med_sweep)
    med_sum = sweeps[med_i][2]

    # identity evidence: per-seed trees agree between the shared-service
    # and local-attach sweeps (device routing can never change results),
    # and the base seed in-sweep equals the standalone run
    svc_dir = sweeps[0][1]
    base_seed = med_sum["seeds"][0]
    for seed in med_sum["seeds"]:
        a = _fleet.output_tree_digest(_fleet.seed_dir(svc_dir, seed))
        b = _fleet.output_tree_digest(_fleet.seed_dir(nosvc_dir, seed))
        assert a == b, f"sweep seed {seed}: svc vs no-svc tree diverged"
    solo_tree = _fleet.output_tree_digest(singles[0][1])
    fleet_tree = _fleet.output_tree_digest(
        _fleet.seed_dir(svc_dir, base_seed))
    assert solo_tree == fleet_tree, \
        "base seed: in-sweep tree != standalone tree"

    # the statistics the sweep exists for
    flows = med_sum["flows"]
    assert flows, "sweep produced no flow groups"
    k0 = sorted(flows)[0]
    assert flows[k0]["ci95"]["p50_ms"]["n"] == n_seeds

    ratio = med_sweep / med_single
    serial_est = round(n_seeds * med_single, 1)
    out = {
        "n_seeds": n_seeds,
        "jobs": jobs,
        "single_run_wall_s": {"median": med_single,
                              "raw": [w for w, _ in singles]},
        "sweep_wall_s": {"median": med_sweep,
                         "raw": [w for w, _d, _s in sweeps]},
        "sweep_wall_no_service_s": {
            "median": nosvc_wall, "raw": [w for w, *_ in nosvcs]},
        "sweep_wall_jobs1_s": {
            "median": j1_wall, "raw": [w for w, *_ in j1s]},
        "ratio_sweep_vs_single": round(ratio, 2),
        "target_3x_single": round(3 * med_single, 1),
        "target_3x_met": bool(med_sweep < 3 * med_single),
        "serial_10x_estimate_s": serial_est,
        "speedup_vs_serial": round(serial_est / med_sweep, 2),
        "marginal_wall_per_seed_s": round(
            (med_sweep - med_single) / (n_seeds - 1), 2),
        "shared_attach_savings_rel": round(
            1 - med_sweep / nosvc_wall, 3),
        "jobs_efficiency_note": (
            f"jobs=1 {j1_wall}s vs jobs={jobs} {med_sweep}s: this box's "
            f"2 vCPUs are HT siblings (box_parallel_scaling_2proc in "
            f"tor_100k row), so packing adds little over the "
            f"amortization wins (persistent workers, cached config "
            f"parse, ONE shared device attach)"),
        "per_seed_wall_s": med_sum["per_seed_wall_seconds"],
        "draw_service": med_sum.get("draw_service"),
        "identity": {
            "svc_vs_nosvc_trees": "all seeds byte-identical",
            "base_seed_vs_standalone": "byte-identical",
            "full_per-seed standalone matrix":
                "tests/test_fleet.py + ci.sh fleet gate",
        },
        "flow_ci_sample": {k0: flows[k0]["ci95"]},
        "aggregation": "median-of-3 interleaved (single, sweep) "
                       "subprocess pairs; ablations single-shot",
        "note": (
            "The sweep amortizes the single-run fixed wall "
            f"(~{round(med_single - (med_sweep - med_single) / (n_seeds - 1), 1)}s "
            f"of imports/attach/build per standalone run) down to "
            f"~{round((med_sweep - med_single) / (n_seeds - 1), 2)}s "
            f"marginal per seed. The <3x-single target needs ~2x real "
            f"parallel capacity on top of that; this container's two "
            f"HT-sibling vCPUs provide ~1.1-1.3x (published probe), "
            f"which is also why sim_shards=2 is throughput-parity "
            f"here. On a box with two real cores the same command "
            f"meets the target arithmetically: "
            f"{n_seeds}x{round((med_sweep - med_single) / (n_seeds - 1), 2)}s"
            f"/2 + startup << 3x single."),
    }
    log(f"tor_400_sweep_{n_seeds}seed: sweep {med_sweep}s vs single "
        f"{med_single}s = {ratio:.2f}x single ({out['speedup_vs_serial']}x "
        f"faster than {n_seeds}x serial; 3x target "
        f"{'MET' if out['target_3x_met'] else 'MISSED — 2-HT-vCPU box'}; "
        f"shared attach saves {out['shared_attach_savings_rel']:.0%} vs "
        f"per-member attach)")
    return out


#: per-shard busy-wall imbalance (max/min) above which the sharded row
#: carries a straggler advisory: id-modulo placement assumes statistically
#: uniform load, and a config that concentrates hot hosts on one shard
#: shows up here first
STRAGGLER_ADVISORY = 1.5


def _shard_busy_walls(summary: dict) -> list:
    """Per-shard busy wall (phase_wall sum excluding the exchange and
    barrier-sync walls — waiting on peers is the SYMPTOM of imbalance,
    not the cause)."""
    out = []
    for s in summary.get("shards", {}).get("per_shard", []):
        pw = s.get("phase_wall", {})
        out.append(sum(v for k, v in pw.items()
                       if k not in ("exchange", "sync")))
    return out


def tor_sharded(shard_counts=(1, 2, 4), stop_s: int = 8) -> dict:
    """The scale-out row (sim_shards PR acceptance): the tor 1/10-scale
    config at shards=1/2/4, interleaved median-of-3 subprocess rows like
    the other tor small-scale rows. shards=1 is the unchanged
    single-process controller; every repetition at every shard count
    must agree on all result fields (the byte-identity contract,
    summary-level here — tests/test_shards.py carries the stream-level
    gates). Publishes per-shard phase_wall (including the exchange wall)
    and a straggler advisory when the busy-wall imbalance exceeds
    {STRAGGLER_ADVISORY}x."""
    import os
    import subprocess
    import time as _t

    import yaml

    doc = tor_doc(700, 10_000, stop_s)
    ypath = "/tmp/shadow-bench-tor10k-sharded.yaml"
    with open(ypath, "w") as f:
        yaml.safe_dump(doc, f, default_style=None)

    def sub(shards, tag):
        t0 = _t.perf_counter()
        r = subprocess.run(
            [sys.executable, "-m", "shadow_tpu", ypath,
             "--scheduler-policy", "tpu_batch",
             "--shards", str(shards),
             "--data-directory", f"/tmp/shadow-bench-{tag}",
             "--json-summary", "--quiet"],
            capture_output=True, text=True, timeout=3600,
            env=_chip_child_env(), cwd=str(ROOT))
        assert r.returncode == 0, (tag, r.stderr[-500:])
        s = json.loads(r.stdout)
        s["subprocess_wall_s"] = round(_t.perf_counter() - t0, 1)
        return s

    N = 3
    reps = {n: [] for n in shard_counts}
    for i in range(N):
        for n in shard_counts:
            reps[n].append(sub(n, f"torshard-{n}-{i}"))
    ref = reps[shard_counts[0]][0]
    for n, rs in reps.items():
        for s in rs:
            for k in ("events", "units_sent", "units_dropped",
                      "bytes_sent", "rounds", "counters"):
                assert s[k] == ref[k], \
                    f"sharded tor determinism: {k} diverged at shards={n}"
    log(f"tor_sharded determinism OK: shards={list(shard_counts)} x {N} "
        f"reps agree ({ref['events']} events)")
    out = {}
    base_rate = None
    for n in shard_counts:
        s = _median_run(reps[n])
        busy = _shard_busy_walls(s)
        row = {
            "sim_sec_per_wall_sec": round(s["sim_sec_per_wall_sec"], 3),
            "wall_seconds": round(s["wall_seconds"], 2),
            "max_rss_mb": s["max_rss_mb"],
            "raw_rates": _run_rates(reps[n]),
            "spread_rel": _spread_rel({n: reps[n]})[n],
            "phase_wall_exchange_per_shard": [
                ps["phase_wall"].get("exchange")
                for ps in s.get("shards", {}).get("per_shard", [])],
            "phase_wall_sync": s["phase_wall"].get("sync"),
        }
        if busy and min(busy) > 0:
            imb = max(busy) / min(busy)
            row["shard_busy_wall_imbalance"] = round(imb, 2)
            if imb > STRAGGLER_ADVISORY:
                row["straggler_advisory"] = (
                    f"max/min shard busy wall {imb:.2f}x > "
                    f"{STRAGGLER_ADVISORY}x — id-modulo placement is "
                    f"unbalanced for this config")
                log(f"WARNING tor_sharded shards={n}: "
                    f"{row['straggler_advisory']}")
        if base_rate is None:
            base_rate = s["sim_sec_per_wall_sec"]
        else:
            row["speedup_vs_shards_1"] = round(
                s["sim_sec_per_wall_sec"] / base_rate, 2)
        out[f"shards_{n}"] = row
    out["aggregation"] = (f"median-of-{N}, interleaved subprocess rows "
                          f"across shard counts; all counts "
                          f"result-identical (asserted)")
    out["note"] = ("tor 1/10 scale, tpu_batch + C engine per shard; "
                   "shards=1 is the unchanged single-process controller. "
                   "The peer-to-peer edge barrier + row exchange is the "
                   "published scale-out overhead "
                   "(phase_wall_exchange_per_shard / coordinate).")
    log("tor_sharded: " + ", ".join(
        f"shards={n} {out[f'shards_{n}']['sim_sec_per_wall_sec']}"
        for n in shard_counts))
    return out


def _parallel_scaling_probe() -> float:
    """How much real CPU parallelism this box gives two processes: run
    one CPU-bound task serial, then two in parallel, and report
    2*serial/parallel. 2.0 = two real cores; ~1.3 = shared execution
    resources (the ceiling any 2-shard speedup can reach here)."""
    import multiprocessing as mp
    import time as _t

    n = 20_000_000
    t0 = _t.perf_counter()
    _burn(n)
    serial = _t.perf_counter() - t0
    ctx = mp.get_context("spawn")
    t0 = _t.perf_counter()
    ps = [ctx.Process(target=_burn, args=(n,)) for _ in range(2)]
    for p in ps:
        p.start()
    for p in ps:
        p.join()
    par = _t.perf_counter() - t0
    return round(2 * serial / par, 2)


def _burn(n: int) -> int:
    s = 0
    for i in range(n):
        s += i * i
    return s


def tor_100k_sharded(stop_s: int = 15, shards: int = 2,
                     reps: int = 2) -> dict:
    """Full-scale config #5 through the shard plane, measured HONESTLY:
    interleaved (single-process, sharded) pairs under today's load, both
    raw rate lists published, plus a measured parallel-scaling probe of
    the box — the ceiling any local sharded speedup can reach. The small
    twin carries the byte-identity gates; this row answers 'does
    partitioning pay on THIS hardware at THIS scale'."""
    import os
    import subprocess
    import time as _t

    import shutil

    from shadow_tpu.config import parse_config
    from shadow_tpu.parallel.shards import run_sharded

    doc = tor_doc(7000, 100_000, stop_s)
    singles = []
    shardeds = []
    last = None
    for i in range(reps):
        # single-process leg in a subprocess (per-run RSS/allocator)
        r = subprocess.run(
            [sys.executable, "-c", f"""
import sys; sys.path.insert(0, {str(ROOT)!r})
import json
from bench import tor_doc
from shadow_tpu.config import parse_config
from shadow_tpu.core.controller import Controller
doc = tor_doc(7000, 100_000, {stop_s})
cfg = parse_config(doc, {{"general.data_directory":
    "/tmp/shadow-bench-tor100k-single{i}",
    "experimental.scheduler_policy": "tpu_batch"}})
r = Controller(cfg, mirror_log=False).run()
print(json.dumps([r["sim_sec_per_wall_sec"], r["events"]]))
"""], capture_output=True, text=True, timeout=3600,
            env=_chip_child_env(), cwd=str(ROOT))
        assert r.returncode == 0, r.stderr[-500:]
        rate, events = json.loads(r.stdout.strip().splitlines()[-1])
        singles.append(round(rate, 4))
        tag = f"tor100k-sh{shards}-{i}"
        shutil.rmtree(f"/tmp/shadow-bench-{tag}", ignore_errors=True)
        cfg = parse_config(doc, {
            "general.data_directory": f"/tmp/shadow-bench-{tag}",
            "general.sim_shards": shards,
            "experimental.scheduler_policy": "tpu_batch"})
        rs = run_sharded(cfg, mirror_log=False)
        assert rs["events"] == events, \
            "sharded full-scale events diverged from single-process"
        shardeds.append(round(rs["sim_sec_per_wall_sec"], 4))
        last = rs
    busy = _shard_busy_walls(last)
    scaling = _parallel_scaling_probe()
    out = {
        "relays": 7000, "clients": 100_000, "sim_seconds": stop_s,
        "sim_shards": shards,
        "sim_sec_per_wall_sec": max(shardeds),
        "raw_rates_sharded": shardeds,
        "raw_rates_single_process_interleaved": singles,
        "events": last["events"], "units_sent": last["units_sent"],
        "max_rss_mb_max_shard": last["max_rss_mb"],
        "errors": len(last["process_errors"]),
        "phase_wall_per_shard": [
            ps["phase_wall"]
            for ps in last.get("shards", {}).get("per_shard", [])],
        "shard_busy_wall_imbalance": (
            round(max(busy) / min(busy), 2) if busy and min(busy) > 0
            else None),
        "box_parallel_scaling_2proc": scaling,
        "verdict": (
            "sharded BEATS the contemporaneous single-process rate"
            if max(shardeds) > max(singles) else
            f"sharded LOSES to the contemporaneous single-process rate "
            f"on this box: two parallel CPU-bound processes measure only "
            f"{scaling}x (shared execution resources), below the "
            f"break-even for the barrier+exchange overhead at this "
            f"scale; the byte-identity gates all hold, so the partition "
            f"is a correctness-proven throughput knob awaiting real "
            f"cores (or a second box)"),
        "aggregation": f"interleaved (single, sharded) x{reps}; raw "
                       f"rates published, best-of compared",
    }
    log(f"tor_100k_sharded (shards={shards}): sharded {shardeds} vs "
        f"single {singles} sim-s/wall-s (box 2-proc scaling {scaling}x)")
    return out


def tor_100k(stop_s: int = 15) -> dict:
    """BASELINE config #5 as a real bench row (VERDICT r3 item #6, r4
    item #2): 7,000 relays + 100,000 clients through the columnar plane
    + C engine. Publishes sim-s/wall-s, RSS, events, and the full fetch
    accounting (attempted/completed/failed + latency percentiles).

    The 1/10-scale twin (700 relays + 10k clients) additionally provides
    (a) the determinism gate — every tpu_batch repetition must agree on
    all result fields — and (b) the MEASURED thread_per_core denominator
    the north-star ratio is defined against (VERDICT r4 item #2: config
    #5 had no baseline side). The small rows run INTERLEAVED
    median-of-3 (tpu, tpc, tpu, tpc, ...) in subprocesses, the same
    anti-drift discipline as the headline: shared-machine noise drifts
    on the scale of one run, and per-run subprocesses keep max_rss_mb
    per-run. Each row publishes its raw rates, relative spread, and the
    median run's phase_wall budget (PR 5: the attack on the north-star
    config is measured, not guessed). The full config runs once
    in-process (the machinery is scale-invariant, so the small twin
    carries the gates)."""
    import os
    import resource
    import subprocess
    import time as _t

    import yaml

    from shadow_tpu.config import parse_config
    from shadow_tpu.core.controller import Controller

    small = tor_doc(700, 10_000, 8)
    ypath = "/tmp/shadow-bench-tor10k.yaml"
    with open(ypath, "w") as f:
        yaml.safe_dump(small, f, default_style=None)

    def sub(policy, tag):
        t0 = _t.perf_counter()
        r = subprocess.run(
            [sys.executable, "-m", "shadow_tpu", ypath,
             "--scheduler-policy", policy,
             "--data-directory", f"/tmp/shadow-bench-{tag}",
             "--json-summary", "--quiet"],
            capture_output=True, text=True, timeout=3600,
            env=_chip_child_env(), cwd=str(ROOT))
        assert r.returncode == 0, (tag, r.stderr[-500:])
        s = json.loads(r.stdout)
        s["subprocess_wall_s"] = round(_t.perf_counter() - t0, 1)
        return s

    N = 3
    reps = {"tpu_batch": [], "thread_per_core": []}
    for i in range(N):
        for pol, tag in (("tpu_batch", "tpu"), ("thread_per_core", "tpc")):
            reps[pol].append(sub(pol, f"tor10k-{tag}{i}"))
    # determinism + cross-policy gates over EVERY repetition
    ref = reps["tpu_batch"][0]
    for pol, rs in reps.items():
        for s in rs:
            for k in ("events", "units_sent", "units_dropped",
                      "bytes_sent", "rounds", "counters"):
                if pol == "tpu_batch":
                    assert s[k] == ref[k], f"tor determinism: {k} diverged"
                elif k not in ("rounds", "counters"):
                    assert s[k] == ref[k], f"tor policy divergence on {k}"
    log(f"tor_10k determinism OK across {N} tpu reps ({ref['events']} "
        f"events)")

    sa = _median_run(reps["tpu_batch"])
    sc = _median_run(reps["thread_per_core"])
    ratio = sa["sim_sec_per_wall_sec"] / sc["sim_sec_per_wall_sec"]
    rates = _run_rates
    spread = _spread_rel(reps)
    if spread["tpu_batch"] > SPREAD_ADVISORY:
        log(f"WARNING tor_10k: interleaved tpu spread "
            f"{spread['tpu_batch']} > {SPREAD_ADVISORY} — a one-time "
            f"cost may have escaped the warm-up again (per-run rates "
            f"published)")
    small_rows = {
        pol: {
            "sim_sec_per_wall_sec": round(s["sim_sec_per_wall_sec"], 3),
            "events": s["events"],
            "events_per_wall_sec": round(s["events"] / s["wall_seconds"]),
            "max_rss_mb": s["max_rss_mb"],
            "wall_seconds": round(s["wall_seconds"], 2),
            # NOTE: unlike run_config rows (warm process), this includes
            # the subprocess's Python/JAX cold-start, hence the name
            "warmup_wall_seconds_incl_startup": round(
                s["subprocess_wall_s"] - s["wall_seconds"], 1),
            # the median run's per-phase wall budget: where the
            # remaining tor wall lives (acceptance: the residual is
            # named, not guessed)
            "phase_wall": s.get("phase_wall"),
            "raw_rates": rates(reps[pol]),
            "spread_rel": spread[pol],
        }
        for pol, s in (("tpu_batch", sa), ("thread_per_core", sc))
    }
    log(f"tor_10k ratio: tpu {sa['sim_sec_per_wall_sec']:.3f} vs "
        f"tpc {sc['sim_sec_per_wall_sec']:.3f} = {ratio:.2f}x "
        f"(median-of-{N} interleaved; spread {spread})")
    # the churned row starts device-policy children: run it while this
    # process is still off JAX (the full-scale run below attaches here)
    churned = tor_churned_ckpt(base_ratio=round(ratio, 2))

    def run(doc, tag):
        cfg = parse_config(doc, {
            "general.data_directory": f"/tmp/shadow-bench-{tag}",
            "experimental.scheduler_policy": "tpu_batch"})
        t0 = _t.perf_counter()  # warm-up includes the 107k-host build
        ctl = Controller(cfg, mirror_log=False)
        r = ctl.run()
        wall = _t.perf_counter() - t0
        r.update(tor_client_stats(ctl))
        return r, wall

    # ru_maxrss is a process-wide high-water mark; under --all this
    # process already ran the smaller benches, so publish the pre-run
    # floor beside the peak — if the peak clearly exceeds the floor, the
    # 100k build owns it (the small twins' RSS rows are per-run above)
    rss_before = resource.getrusage(
        resource.RUSAGE_SELF).ru_maxrss / (1024 * 1024)
    doc = tor_doc(7000, 100_000, stop_s)
    r, wall = run(doc, "tor100k")
    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / (1024 * 1024)
    out = {
        "relays": 7000, "clients": 100_000, "sim_seconds": stop_s,
        "wall_s": round(wall, 1),
        "warmup_wall_seconds": round(wall - r["wall_seconds"], 1),
        "sim_sec_per_wall_sec": round(r["sim_sec_per_wall_sec"], 4),
        "events": r["events"], "units_sent": r["units_sent"],
        "fetches": r.get("tor_fetches"),
        "rss_gib_process_peak": round(rss, 2),
        "rss_gib_floor_before_run": round(rss_before, 2),
        "errors": len(r["process_errors"]),
        "small_scale_1_10": {
            **small_rows,
            "ratio_tpu_vs_thread_per_core": round(ratio, 2),
            "aggregation": f"median-of-{N}, interleaved subprocess "
                           f"pairs; ratio = median/median",
            "note": "700 relays + 10k clients, 8 sim-s; the north-star "
                    "denominator measured at 1/10 scale (subprocess rows, "
                    "per-run RSS)",
        },
        # fast AND robust (PR 6): the same 1/10 config under faults +
        # periodic checkpoints with the C engine on, published beside the
        # clean row so the robustness tax is a measured number
        "tor_1_10_churned_ckpt": churned,
    }
    f = out["fetches"] or {}
    log(f"tor_100k: {out['sim_sec_per_wall_sec']} sim-s/wall-s, "
        f"{out['events']} events, {f.get('completed')} fetches "
        f"of {f.get('attempted')} attempted, "
        f"{out['rss_gib_process_peak']} GiB peak RSS")
    return out


def web_cdn_row(reps: int = 3) -> dict:
    """The modern-web family enters the perf trajectory (PR 9): the
    committed examples/web_cdn.yaml (clients -> edge caches -> origin
    over a DNS chain, with a partition + lossy degrade window driving
    SACK recovery) measured with the same interleaved median-of-N
    discipline as the headline rows — (tpu, tpc) pairs so both sides
    share each noise window — plus the standard ablation legs for
    device_engaged. Result fields are asserted identical across every
    leg (the row doubles as a cross-policy identity gate under faults),
    and the flow-latency roll-up (web.fetch/web.origin/dns.resolve
    percentiles) rides along so regressions in the workload itself — not
    just the simulator — show up in BENCH_DETAIL."""
    path = "examples/web_cdn.yaml"
    tpus, tpcs = [], []
    for i in range(reps):
        tpus.append(run_config(path, "tpu_batch", f"webcdn-tpu{i}"))
        tpcs.append(run_config(path, "thread_per_core", f"webcdn-tpc{i}"))
    tpu, tpc = _median_run(tpus), _median_run(tpcs)
    for k in ("events", "units_sent", "units_dropped", "bytes_sent"):
        assert tpu[k] == tpc[k], ("web_cdn", k)
    flows = {
        kind: {k: v[k] for k in ("count", "ok", "failed", "p50_ms",
                                 "p99_ms") if k in v}
        for kind, v in tpu.get("telemetry", {}).get("flows", {}).items()}
    d = {
        "thread_per_core": tpc,
        "tpu_batch": tpu,
        "ratio_tpu_vs_tpc": round(
            tpu["sim_sec_per_wall_sec"] / tpc["sim_sec_per_wall_sec"], 2),
        "raw_rates": {"tpu_batch": _run_rates(tpus),
                      "thread_per_core": _run_rates(tpcs)},
        "spread_rel": _spread_rel({"tpu_batch": tpus,
                                   "thread_per_core": tpcs}),
        "flows": flows,
        "stream_recovery": {
            k: tpu.get("counters", {}).get(k, 0)
            for k in ("stream_fast_retransmits", "stream_sack_retransmits",
                      "stream_rto_retransmits", "stream_timeouts")},
        "aggregation": f"median-of-{reps}, interleaved (tpu, tpc) pairs",
    }
    d.update(ablation(path, "web_cdn", tpc, tpu))
    log(f"web_cdn: tpu {d['raw_rates']['tpu_batch']} vs tpc "
        f"{d['raw_rates']['thread_per_core']} sim-s/wall-s "
        f"(ratio {d['ratio_tpu_vs_tpc']}x, "
        f"device_engaged={d['device_engaged']})")
    return d


def web_cdn_100k_row(reps: int = 3, stop_time: str = "1500ms") -> dict:
    """The device-transport row (PR 11): the committed
    examples/web_cdn_100k.yaml — 100k page-loop clients behind a CDN
    tier, the regime where per-endpoint ticks dominate the round loop —
    measured with ``experimental.device_transport`` on vs off,
    interleaved median-of-N pairs on the Python columnar plane (where
    the columnar transport engages), plus a scalar-C reference leg.
    ``stop_time`` trims the committed config's 6 s to keep a 9-leg
    interleaved row tractable on one box; the config itself is the
    deeper-run artifact.

    Honesty contract (ISSUE 11 acceptance): `phase_wall.transport_tick`
    is published before/after, `device_transport_engaged` gets the same
    loud-fallback warning `device_engaged` got in PR 3, and if this
    box's batched kernel cannot beat the scalar twins the verdict line
    says so plainly — the break-even economics keep the feature a no-op
    by default either way."""
    path = "examples/web_cdn_100k.yaml"
    ov = {"general.stop_time": stop_time}
    offs, ons, cs = [], [], []
    for i in range(reps):
        # interleaved (off, on, C) triples: all three legs share each
        # noise window
        offs.append(run_config(path, "tpu_batch", f"w100k-off{i}", {
            **ov, "experimental.native_colcore": False}))
        ons.append(run_config(path, "tpu_batch", f"w100k-on{i}", {
            **ov, "experimental.native_colcore": False,
            "experimental.device_transport": True}))
        cs.append(run_config(path, "tpu_batch", f"w100k-c{i}", ov))
    off, on, c = _median_run(offs), _median_run(ons), _median_run(cs)
    # the row doubles as a 100k-endpoint identity gate: every leg must
    # be the same simulation
    for k in ("events", "units_sent", "units_dropped", "bytes_sent"):
        assert off[k] == on[k] == c[k], ("web_cdn_100k", k)
    devt = on.get("device_transport", {})
    engaged = bool(on.get("device_transport_engaged"))
    if not engaged:
        log("WARNING web_cdn_100k: device_transport_engaged=false — the "
            "device-transport run advanced ZERO cohorts through the "
            "batched kernel; the scalar twin carried the whole run "
            "(this is NOT a columnar-transport result)")
    devt_x = round(on["sim_sec_per_wall_sec"]
                   / off["sim_sec_per_wall_sec"], 3)
    vs_c = round(on["sim_sec_per_wall_sec"]
                 / c["sim_sec_per_wall_sec"], 3)
    verdict = ("columnar transport is a net WIN vs the scalar Python "
               "twin" if devt_x > 1.0 else
               "columnar transport is a WASH vs the scalar Python twin"
               if devt_x >= 0.99 else
               "columnar transport is a net LOSS vs the scalar Python "
               "twin on this box")
    verdict += ("; it does NOT beat the scalar C twin (colcore remains "
                "the fast plane here)" if vs_c < 1.0 else
                "; it ALSO beats the scalar C twin")
    d = {
        "config": f"{path} @ {stop_time} (committed config is 6s)",
        "scalar_c": c,
        "py_columnar_devt_off": off,
        "py_columnar_devt_on": on,
        "devt_x_vs_python_scalar": devt_x,
        "devt_x_vs_scalar_c": vs_c,
        "device_transport_engaged": engaged,
        "device_transport": devt,
        "transport_tick_wall": {
            "devt_on": on.get("phase_wall", {}).get("transport_tick"),
            "devt_off": off.get("phase_wall", {}).get("transport_tick"),
            "events_wall_on": on.get("phase_wall", {}).get("events"),
            "events_wall_off": off.get("phase_wall", {}).get("events"),
        },
        "raw_rates": {"devt_off": _run_rates(offs),
                      "devt_on": _run_rates(ons),
                      "scalar_c": _run_rates(cs)},
        "spread_rel": _spread_rel({"devt_off": offs, "devt_on": ons,
                                   "scalar_c": cs}),
        "verdict": verdict,
        "aggregation": f"median-of-{reps}, interleaved (off, on, C) "
                       f"triples",
    }
    log(f"web_cdn_100k: devt on {d['raw_rates']['devt_on']} vs off "
        f"{d['raw_rates']['devt_off']} vs C {d['raw_rates']['scalar_c']} "
        f"sim-s/wall-s (devt_x={devt_x}, vs_c={vs_c}, "
        f"engaged={engaged}, cohorts={devt.get('cohorts')}, "
        f"acks={devt.get('acks_batched')})")
    log(f"web_cdn_100k verdict: {verdict}")
    return d


def mesh_scaling(config: str = "examples/tgen_100host.yaml",
                 force_collective: bool = False) -> dict:
    """tpu_mesh scaling table (VERDICT r2 item #2): the whole-round
    sharded program over 1/2/4/8 shards of an 8-virtual-device CPU mesh
    (the image has one real chip; the driver validates the same path via
    dryrun_multichip). Results are bit-identical across shard counts —
    only wall time moves — so each run also cross-checks the previous."""
    import json as _json
    import os
    import subprocess

    # the children run on 8 virtual CPU devices, and their rows say so
    # (platform: cpu) — this leg is the mesh program's CPU stand-in, not
    # a chip measurement (chip_smoke.py --chips 4 runs the mesh on chips)
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip())
    out = {}
    if force_collective:
        # tpu_mesh_floor=1: EVERY window takes the sharded collective
        # (the adaptive floor would route small windows to the numpy
        # twin), so the per-window breakdown attributes the shard tail
        out["note"] = ("tpu_mesh_floor=1 — collective forced on every "
                       "window to expose its wall breakdown; results "
                       "identical to the adaptive run by construction")
    prev = None
    for shards in (1, 2, 4, 8):
        r = subprocess.run(
            [sys.executable, "-m", "shadow_tpu", str(ROOT / config),
             "--scheduler-policy", "tpu_mesh",
             "--set", f"experimental.tpu_mesh_shards={shards}",
             *(["--set", "experimental.tpu_mesh_floor=1"]
               if force_collective else []),
             "--data-directory", f"/tmp/shadow-bench-mesh{shards}",
             "--json-summary", "--quiet"],
            env=env, capture_output=True, text=True, timeout=1200)
        if r.returncode != 0:
            out[f"shards_{shards}"] = {"error": r.stderr[-300:]}
            continue
        s = _json.loads(r.stdout)
        pw = s.get("phase_wall", {})
        out[f"shards_{shards}"] = {
            "platform": s["device"]["platform"],
            "sim_sec_per_wall_sec": round(s["sim_sec_per_wall_sec"], 3),
            "units_sent": s["units_sent"],
            "events": s["events"],
            # per-window collective attribution (VERDICT r4 item #7):
            # where the wall goes as shard count grows
            "collective_wall": {
                k.removeprefix("mesh_"): pw[k]
                for k in ("mesh_build", "mesh_dispatch", "mesh_readback",
                          "mesh_windows") if k in pw},
            "events_wall": pw.get("events"),
            "barrier_wall": pw.get("barrier"),
        }
        if prev is not None:
            for k in ("units_sent", "events"):
                assert s[k] == prev[k], f"shard-count divergence on {k}"
        prev = s
        log(f"tpu_mesh shards={shards}: "
            f"{s['sim_sec_per_wall_sec']:.2f} sim-s/wall-s")
    return out


def draw_plane_throughput(n: int = 1_000_000) -> dict:
    """Raw loss-draw throughput, device vs numpy twin, at a config-#5-scale
    batch — the per-round math a 100k-host simulation would batch."""
    import numpy as np

    from shadow_tpu.network.fluid import MAX_PKTS, loss_flags
    from shadow_tpu.ops.propagate import DeviceDrawPlane

    rng = np.random.default_rng(0)
    lo = rng.integers(0, 1 << 32, n, dtype=np.uint64).astype(np.uint32)
    hi = rng.integers(0, 1 << 32, n, dtype=np.uint64).astype(np.uint32)
    npk = np.full(n, MAX_PKTS, np.uint32)
    th = np.full(n, 1 << 12, np.uint32)

    plane = DeviceDrawPlane(seed=7, max_batch=1 << 20)
    plane.dispatch(lo, hi, npk, th).read()  # warm/compile the full bucket
    t0 = time.perf_counter()
    dev_flags = plane.dispatch(lo, hi, npk, th).read()
    dev_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    np_flags = loss_flags(7, lo, hi, npk, th)
    np_s = time.perf_counter() - t0
    assert (dev_flags == np_flags).all(), "draw-plane bitmatch violated"
    # the per-PROGRAM floor: dispatch+readback of a minimal batch — the
    # fixed cost a window must amortize before the device beats the numpy
    # twin (a simulation round carries tens-to-hundreds of units)
    k = 512
    plane.dispatch(lo[:k], hi[:k], npk[:k], th[:k]).read()  # warm shape
    t0 = time.perf_counter()
    reps = 10
    for _ in range(reps):
        plane.dispatch(lo[:k], hi[:k], npk[:k], th[:k]).read()
    rt_ms = (time.perf_counter() - t0) / reps * 1000
    out = {
        "batch": n,
        "device_units_per_sec": n / dev_s,
        "numpy_units_per_sec": n / np_s,
        "device_speedup": np_s / dev_s,
        "device_round_trip_ms_small_batch": round(rt_ms, 3),
        "numpy_breakeven_units": int(rt_ms / 1000 / max(
            np_s / n, 1e-12)),
    }
    log(f"draw-plane @1M units: device {out['device_units_per_sec']:.3g}/s "
        f"vs numpy {out['numpy_units_per_sec']:.3g}/s "
        f"({out['device_speedup']:.1f}x)")
    return out


def fork_amortization(n_branches: int = 10) -> dict:
    """The scenario-multiverse row (shadow_tpu/forks.py): how much wall
    does restoring ONE trunk checkpoint into N what-if branches re-buy
    over N cold-start runs of the same (config, commands, seed) tuples?

    web_cdn at stop 20s forked from its 15s checkpoint: every branch is
    restore-mode (divergence by injected command only), so each re-buys
    the 15s trunk prefix and simulates only the 5s suffix — the ideal
    amortization is ~4x, and anything under 2x means the fork machinery
    (prefix stream copy, pickle restore, per-branch worker dispatch) is
    eating the prefix it saved. Both arms run serially (jobs=1 vs an
    in-process loop, which if anything flatters the cold arm — no
    worker IPC), and the row spot-checks the honesty gate: branch 0's
    output tree and streams byte-equal its cold twin's."""
    from shadow_tpu import fleet as _fleet
    from shadow_tpu import forks as _forks
    from shadow_tpu.config import load_config
    from shadow_tpu.core.controller import Controller

    base = str(ROOT / "examples" / "web_cdn.yaml")
    common = {"general.stop_time": "20s",
              "general.checkpoint_every": "15s",
              "general.state_digest_every": 200}
    trunk = _fresh_dir("/tmp/shadow-bench-fork-trunk")
    t0 = time.perf_counter()
    Controller(load_config(base, {**common,
                                  "general.data_directory": trunk}),
               mirror_log=False).run()
    trunk_wall = time.perf_counter() - t0
    cks = sorted(Path(trunk).glob("checkpoints/ckpt_*.ckpt"))
    assert cks, f"trunk wrote no checkpoints under {trunk}"
    # N restore-mode branches: each injects one degrade window after the
    # fork point with a different severity (a realistic what-if sweep)
    branches = [{"name": f"w{i}", "commands": [
        {"t": "16s", "cmd": "link_degrade", "src_nodes": [0, 1],
         "dst_nodes": [6, 7], "latency_factor": 1.25 + 0.25 * i,
         "loss_add": 0.004 * i, "bandwidth_scale": 1.0,
         "duration": "3s"}]} for i in range(n_branches)]
    fork_dir = Path(_fresh_dir("/tmp/shadow-bench-fork"))
    plan = _forks.plan_fork(base, cks[0], branches, fork_dir,
                            overrides=dict(common))
    t0 = time.perf_counter()
    summary = _fleet.FleetRunner(base, plan["order"], jobs=1,
                                 sweep_dir=fork_dir,
                                 overrides=dict(common), fork=plan,
                                 quiet=True).run()
    fork_wall = time.perf_counter() - t0
    assert not summary["failed"], summary["failed"]
    log(f"fork_amortization: {n_branches}-branch fork {fork_wall:.1f}s "
        f"(trunk {trunk_wall:.1f}s); running the cold arm")
    cold_wall = 0.0
    cold0 = None
    for i in range(n_branches):
        d = _fresh_dir(f"/tmp/shadow-bench-fork-cold-{i}")
        replay = _forks.branch_dir(fork_dir, f"w{i}") / _forks.REPLAY_FILE
        t0 = time.perf_counter()
        Controller(load_config(base, {
            **common, "general.data_directory": d,
            "general.replay_commands": str(replay)}),
            mirror_log=False).run()
        cold_wall += time.perf_counter() - t0
        if i == 0:
            cold0 = d
    # the honesty spot check: forked == cold-started, byte for byte
    man0 = json.loads((_forks.branch_dir(fork_dir, "w0")
                       / _forks.FORK_MANIFEST).read_text())
    assert man0["tree_sha256"] == _fleet.output_tree_digest(cold0), \
        "branch w0 tree != its cold twin — amortization measured a lie"
    assert all(man0["streams_sha256"][k] == v for k, v in
               _fleet._stream_digests(cold0).items()), "w0 streams diverged"
    speedup = cold_wall / fork_wall
    row = {
        "workload": f"web_cdn.yaml, {n_branches} what-if branches forked "
                    f"from the 15s checkpoint of a 20s trunk",
        "n_branches": n_branches,
        "trunk_wall_seconds": round(trunk_wall, 2),
        "fork_wall_seconds": round(fork_wall, 2),
        "cold_wall_seconds": round(cold_wall, 2),
        "per_branch_wall_seconds": summary["per_branch_wall_seconds"],
        "speedup_fork_vs_cold": round(speedup, 2),
        "speedup_incl_trunk": round(cold_wall / (fork_wall + trunk_wall),
                                    2),
        "identity_spot_check": "w0 tree+streams == cold twin",
    }
    if speedup < 2.0:
        row.setdefault("warnings", []).append(
            f"fork amortization {speedup:.2f}x < 2x — the restore path "
            f"(prefix stream copy + pickle load + worker dispatch) is "
            f"eating the trunk prefix it was supposed to re-buy")
        log(f"fork_amortization WARNING: {speedup:.2f}x < 2x — restore "
            f"overhead is swallowing the amortization win")
    log(f"fork_amortization: {n_branches} branches forked in "
        f"{fork_wall:.1f}s vs {cold_wall:.1f}s cold ({speedup:.2f}x; "
        f"{row['speedup_incl_trunk']}x counting the trunk run)")
    return row


def ensure_native() -> None:
    """Build the native pieces (shim + colcore) the benchmarks rely on; a
    device policy refuses to run without the C engine, so a failed build
    ends the benchmark here."""
    import subprocess

    subprocess.run(["make", "-C", str(ROOT / "native")], check=True,
                   capture_output=True)


def main() -> None:
    ensure_native()
    ap = argparse.ArgumentParser()
    ap.add_argument("--all", action="store_true",
                    help="full matrix + BENCH_DETAIL.json")
    ap.add_argument("--config", default="examples/tgen_1k.yaml",
                    help="headline config (default: BASELINE config #2)")
    ap.add_argument("--tor-churned", action="store_true",
                    help="measure ONLY the tor_1_10_churned_ckpt row and "
                         "merge it into BENCH_DETAIL.json (base ratio "
                         "taken from the published small_scale_1_10 row)")
    ap.add_argument("--sharded", action="store_true",
                    help="measure ONLY the scale-out rows (tor_1_10 at "
                         "shards=1/2/4, interleaved median-of-3, plus the "
                         "full-scale tor_100k at shards=2) and merge them "
                         "into BENCH_DETAIL.json")
    ap.add_argument("--fleet", action="store_true",
                    help="measure ONLY the fleet-mode row (10-seed "
                         "tor_400 sweep vs standalone singles, "
                         "interleaved, with shared-attach and jobs "
                         "ablations) and merge it into BENCH_DETAIL.json")
    ap.add_argument("--fork", action="store_true",
                    help="measure ONLY the fork-amortization row "
                         "(10-branch web_cdn what-if fork vs 10 "
                         "cold-start runs) and merge it into "
                         "BENCH_DETAIL.json")
    args = ap.parse_args()

    if args.fork:
        detail_path = ROOT / "BENCH_DETAIL.json"
        detail = json.loads(detail_path.read_text())
        row = fork_amortization()
        detail["fork_amortization"] = row
        detail_path.write_text(json.dumps(detail, indent=2))
        log("wrote BENCH_DETAIL.json (fork_amortization)")
        print(json.dumps({
            "metric": "fork_amortization_speedup_vs_cold",
            "value": row["speedup_fork_vs_cold"],
            "n_branches": row["n_branches"],
            "speedup_incl_trunk": row["speedup_incl_trunk"],
            "warnings": row.get("warnings", []),
        }), flush=True)
        return

    if args.fleet:
        detail_path = ROOT / "BENCH_DETAIL.json"
        detail = json.loads(detail_path.read_text())
        row = tor_400_sweep()
        detail["tor_400_sweep_10seed"] = row
        detail_path.write_text(json.dumps(detail, indent=2))
        log("wrote BENCH_DETAIL.json (tor_400_sweep_10seed)")
        print(json.dumps({
            "metric": "tor_400_sweep_10seed_ratio_vs_single",
            "value": row["ratio_sweep_vs_single"],
            "speedup_vs_serial": row["speedup_vs_serial"],
            "target_3x_met": row["target_3x_met"],
            "shared_attach_savings_rel":
                row["shared_attach_savings_rel"],
        }), flush=True)
        return

    if args.sharded:
        detail_path = ROOT / "BENCH_DETAIL.json"
        detail = json.loads(detail_path.read_text())
        row = tor_sharded()
        detail.setdefault("tor_100k", {})["tor_1_10_sharded"] = row
        full = tor_100k_sharded(shards=2)
        detail["tor_100k"]["full_scale_sharded"] = full
        detail_path.write_text(json.dumps(detail, indent=2))
        log("wrote BENCH_DETAIL.json (tor_1_10_sharded + "
            "full_scale_sharded)")
        print(json.dumps({
            "metric": "tor_100k_sharded_sim_sec_per_wall_sec",
            "value": full["sim_sec_per_wall_sec"],
            "sim_shards": full["sim_shards"],
            "published_single_process": detail["tor_100k"].get(
                "sim_sec_per_wall_sec"),
        }), flush=True)
        return

    if args.tor_churned:
        detail_path = ROOT / "BENCH_DETAIL.json"
        detail = json.loads(detail_path.read_text())
        base = (detail.get("tor_100k", {}).get("small_scale_1_10", {})
                .get("ratio_tpu_vs_thread_per_core"))
        row = tor_churned_ckpt(base_ratio=base)
        detail.setdefault("tor_100k", {})["tor_1_10_churned_ckpt"] = row
        detail_path.write_text(json.dumps(detail, indent=2))
        log("wrote BENCH_DETAIL.json (tor_1_10_churned_ckpt)")
        print(json.dumps({
            "metric": "tor_1_10_churned_ckpt_ratio",
            "value": row["ratio_tpu_vs_thread_per_core"],
            "base_ratio_clean": row.get("base_ratio_clean"),
            "robustness_tax_rel": row.get("robustness_tax_rel"),
        }), flush=True)
        return

    detail: dict = {"machine_note": "tpu_batch uses the local JAX default "
                    "device; thread_per_core is the CPU baseline policy"}
    child_legs = {}
    if args.all:
        # the legs that start device-policy children run FIRST, while
        # this process is still off JAX (one process per chip;
        # _chip_child_env refuses otherwise). tor_100k ends with its
        # in-process full-scale run, so it goes last.
        child_legs["tor_1_10_sharded"] = tor_sharded()
        child_legs["tor_400_sweep_10seed"] = tor_400_sweep()
        child_legs["tor_100k"] = tor_100k()

    # untimed warm-up pass per policy BEFORE the measured repetitions
    # (VERDICT r5 weak #1): the first tpu run of a process pays one-time
    # costs the steady-state loop never sees again — device attach/floor
    # calibration finishing mid-run, JAX/XLA compile, numpy/module import,
    # allocator growth — which made measured run 1 ~2x slower than runs
    # 2-3 while warmup_wall_seconds (build-phase wall only) reported
    # 0.2-0.7 s. One full throwaway run per policy moves ALL of that
    # outside the measurement; its wall is published, not hidden.
    warmup_runs = {}
    for pol, tag in (("thread_per_core", "tpc"), ("tpu_batch", "tpu")):
        r = run_config(args.config, pol, f"{tag}-warmup")
        warmup_runs[pol] = round(r["total_wall_seconds"], 3)
    log(f"untimed warm-up runs done: {warmup_runs} (excluded from medians)")

    # median-of-3 per policy, INTERLEAVED (VERDICT r3 weak #1): shared-
    # machine load drifts on the scale of one run, so grouping a policy's
    # repetitions correlates the noise with the policy and corrupts the
    # ratio; best-of-N overstates whichever policy got the quiet slot.
    # The ratio of record is median/median, and the raw rates ship in the
    # headline's neighborhood so any reviewer can recompute it.
    N = 3
    runs = {"thread_per_core": [], "tpu_batch": []}
    for _ in range(N):
        for pol, tag in (("thread_per_core", "tpc"), ("tpu_batch", "tpu")):
            runs[pol].append(run_config(args.config, pol, tag))

    med, rates = _median_run, _run_rates
    base, tpu = med(runs["thread_per_core"]), med(runs["tpu_batch"])
    spread = _spread_rel(runs)
    log(f"raw rates (interleaved x{N}): "
        f"tpc={rates(runs['thread_per_core'])} "
        f"tpu={rates(runs['tpu_batch'])} spread={spread}")
    if spread["tpu_batch"] > SPREAD_ADVISORY:
        log(f"WARNING tgen_1k: interleaved tpu spread "
            f"{spread['tpu_batch']} > {SPREAD_ADVISORY} — a one-time "
            f"cost may have escaped the warm-up (see "
            f"first_rep_excess_rel)")
    headline = {
        "metric": "sim_sec_per_wall_sec_tgen1k_tpu_batch",
        "value": round(tpu["sim_sec_per_wall_sec"], 4),
        "unit": "sim-sec/wall-sec",
        "vs_baseline": round(
            tpu["sim_sec_per_wall_sec"] / base["sim_sec_per_wall_sec"], 4),
        "raw_tpu": rates(runs["tpu_batch"]),
        "raw_baseline": rates(runs["thread_per_core"]),
        "aggregation": f"median-of-{N}, interleaved, after one untimed "
                       f"full warm-up run per policy",
        "warmup_run_wall_s": warmup_runs,
    }
    detail["tgen_1k"] = {
        "thread_per_core": base, "tpu_batch": tpu,
        "raw_rates": {p: rates(r) for p, r in runs.items()},
        "spread_rel": spread,
        "warmup_run_wall_s": warmup_runs,
        # VERDICT r5 item #7: the warm-up leak itemized per policy — the
        # first MEASURED rep's shortfall vs the median rep. With every
        # device program shape pre-compiled at attach (DeviceDrawPlane.
        # warm_shapes) plus the untimed full warm-up run, this should sit
        # at machine noise; a recurring large positive value here means a
        # one-time cost escaped the warm-up again.
        "first_rep_excess_rel": {
            pol: round(1 - rates(r)[0] / max(
                sorted(rates(r))[len(r) // 2], 1e-9), 4)
            for pol, r in runs.items()},
    }

    # telemetry overhead on the headline config (telemetry PR acceptance:
    # <= 5% wall at the default sampling cadence; published, not hidden).
    # Two measures: phase_wall["telemetry"] is the directly-attributed
    # in-band cost (exact, noise-free); the wall delta vs the headline
    # median rides shared-machine noise and is published for honesty.
    telr = run_config(args.config, "tpu_batch", "tpu-tel",
                      {"telemetry": {}})
    tel_wall = telr["phase_wall"].get("telemetry", 0.0)
    detail["tgen_1k"]["telemetry_overhead"] = {
        "telemetry_wall_seconds": round(tel_wall, 4),
        "telemetry_pct_of_loop": round(
            100 * tel_wall / telr["wall_seconds"], 2),
        "wall_seconds_with_telemetry": round(telr["wall_seconds"], 3),
        "wall_seconds_median_without": round(tpu["wall_seconds"], 3),
        "wall_delta_pct_noisy": round(
            100 * (telr["wall_seconds"] / tpu["wall_seconds"] - 1), 1),
        "samples": telr.get("telemetry", {}).get("samples", 0),
        "flows_recorded": telr.get("telemetry", {}).get(
            "flows_recorded", 0),
    }
    to = detail["tgen_1k"]["telemetry_overhead"]
    log(f"telemetry overhead on tgen_1k: "
        f"{to['telemetry_pct_of_loop']}% of loop wall attributed "
        f"({to['telemetry_wall_seconds']}s; noisy run-delta "
        f"{to['wall_delta_pct_noisy']}%; {to['samples']} samples, "
        f"{to['flows_recorded']} flows)")

    # live endpoint overhead on the headline config (live-ops PR
    # acceptance: an attached follower must be ~free — the endpoint is a
    # wall-clock plane with drop-oldest queues, so a slow reader sheds
    # records instead of stalling rounds). Same convention as the
    # telemetry row: published on every run, loud when it regresses.
    import threading as _threading

    live_sock = "/tmp/shadow-bench-live.sock"
    live_drained = [0]

    def _live_drain():
        from shadow_tpu import live as _live_mod
        try:
            for _ in _live_mod.stream_records(live_sock, timeout=60):
                live_drained[0] += 1
        except OSError:
            pass

    _live_reader = _threading.Thread(target=_live_drain, daemon=True)
    _live_reader.start()
    liver = run_config(args.config, "tpu_batch", "tpu-live",
                       {"general.live_endpoint": live_sock,
                        "general.heartbeat_interval": "2s"})
    _live_reader.join(timeout=10)
    live_rel = liver["wall_seconds"] / tpu["wall_seconds"] - 1
    detail["tgen_1k"]["live_overhead"] = {
        "live_overhead_rel": round(live_rel, 4),
        "wall_seconds_with_live": round(liver["wall_seconds"], 3),
        "wall_seconds_median_without": round(tpu["wall_seconds"], 3),
        "records_streamed": live_drained[0],
    }
    if live_rel > 0.05:
        log(f"WARNING tgen_1k: live endpoint overhead {live_rel:.1%} > 5% "
            f"— the wall-clock plane is leaking into the round loop "
            f"(an attached follower should be ~free under drop-oldest)")
    log(f"live endpoint overhead on tgen_1k: {live_rel:+.1%} wall vs "
        f"detached median ({live_drained[0]} records streamed to an "
        f"attached follower)")

    # supervised-run overhead on the headline config (self-healing PR
    # acceptance: supervision is a wall-clock wrapper — a liveness page
    # stamped per round plus a restart loop AROUND the same Controller —
    # so a failure-free supervised run must cost ~nothing; loud above
    # 3%). Same convention as the telemetry/live rows: published on
    # every run, loud when it regresses.
    from shadow_tpu.config import load_config as _load_cfg
    from shadow_tpu.supervise import CHAOS_ENV as _CHAOS_ENV
    from shadow_tpu.supervise import run_supervised as _run_sup

    supr = None
    sup_dir = "/tmp/shadow-bench-tpu-sup"
    shutil.rmtree(sup_dir, ignore_errors=True)
    sup_cfg = _load_cfg(str(ROOT / args.config), {
        "experimental.scheduler_policy": "tpu_batch",
        "general.data_directory": sup_dir,
        "general.supervise": {"max_restarts": 2, "backoff": 0.2},
    })
    supr = _run_sup(sup_cfg, mirror_log=False)
    sup_rel = supr["wall_seconds"] / tpu["wall_seconds"] - 1
    detail["tgen_1k"]["supervise_overhead"] = {
        "supervise_overhead_rel": round(sup_rel, 4),
        "wall_seconds_supervised": round(supr["wall_seconds"], 3),
        "wall_seconds_median_without": round(tpu["wall_seconds"], 3),
        "attempts": supr["supervisor"]["attempts"],
        "restarts": len(supr["supervisor"]["restarts"]),
    }
    if sup_rel > 0.03:
        log(f"WARNING tgen_1k: supervised-run overhead {sup_rel:.1%} > 3% "
            f"— the supervisor is a wall-clock wrapper and a failure-free "
            f"supervised run must track the bare run (liveness stamping "
            f"or the watchdog poll is leaking into the round loop)")
    log(f"supervised-run overhead on tgen_1k: {sup_rel:+.1%} wall vs "
        f"bare median (failure-free, "
        f"{supr['supervisor']['attempts']} attempt)")

    # MTTR under real failure: a short supervised 2-shard gossip_churn
    # with one injected worker SIGKILL (the chaos harness), measuring
    # detection -> first post-restart round ready. Published so recovery
    # latency is a tracked number, not a test-only property.
    mttr_dir = "/tmp/shadow-bench-mttr"
    shutil.rmtree(mttr_dir, ignore_errors=True)
    mttr_cfg = _load_cfg(str(ROOT / "examples/gossip_churn.yaml"), {
        "experimental.scheduler_policy": "tpu_batch",
        "general.data_directory": mttr_dir,
        "general.stop_time": "12s",
        "general.sim_shards": 2,
        "general.checkpoint_every": "2s",
        "general.state_digest_every": 500,
        "general.sample_every": "5s",
        "general.supervise": {"max_restarts": 2, "backoff": 0.1},
    })
    os.environ[_CHAOS_ENV] = "s0:kill@r700"
    try:
        mr = _run_sup(mttr_cfg, mirror_log=False)
    finally:
        os.environ.pop(_CHAOS_ENV, None)
    mrs = mr["supervisor"]["restarts"]
    assert len(mrs) == 1, mrs  # the one injected kill, recovered once
    detail["supervised_recovery"] = {
        "workload": "gossip_churn 2-shard, 12s stop, ckpt every 2s",
        "injected": "s0:kill@r700",
        "mttr_s": mrs[0]["mttr_s"],
        "resume": mrs[0]["resume"],
        "restarts": len(mrs),
    }
    log(f"supervised recovery MTTR (gossip_churn, worker SIGKILL): "
        f"{mrs[0]['mttr_s']}s detection->first-round-ready, resumed "
        f"from {mrs[0]['resume']}")

    # results must be identical across policies — a benchmark that diverged
    # would be measuring two different simulations
    for k in ("events", "units_sent", "units_dropped", "bytes_sent"):
        assert base[k] == tpu[k], f"policy divergence on {k}"

    # headline-config ablation (VERDICT r4 item #1): decompose the ratio.
    # The ablation rows run median-of-3 interleaved like the headline
    # (round-5 Weak #5: a single-run device_x is noise where it matters).
    detail["tgen_1k"].update(ablation(args.config, "tgen_1k", base, tpu,
                                      reps=N, full_rates=rates(
                                          runs["tpu_batch"])))
    headline["factors"] = detail["tgen_1k"]["factors"]
    headline["device_engaged"] = detail["tgen_1k"]["device_engaged"]
    log(f"tgen_1k factors: {headline['factors']}")

    if args.all:
        for path, tag, collect in (
                ("examples/tgen_100host.yaml", "tgen_100", None),
                ("examples/tor_400relay.yaml", "tor_400", tor_client_stats),
                ("examples/gossip_10k.yaml", "gossip_10k", None)):
            d = {
                "thread_per_core": run_config(
                    path, "thread_per_core", f"{tag}-tpc", collect=collect),
                "tpu_batch": run_config(
                    path, "tpu_batch", f"{tag}-tpu", collect=collect),
            }
            for k in ("events", "units_sent", "units_dropped"):
                assert d["thread_per_core"][k] == d["tpu_batch"][k], (tag, k)
            d.update(ablation(path, tag, d["thread_per_core"],
                              d["tpu_batch"]))
            detail[tag] = d
        detail["web_cdn"] = web_cdn_row()
        detail["web_cdn_100k"] = web_cdn_100k_row()
        detail["managed_50"] = managed_bench()
        detail["managed_dense"] = managed_dense_bench()
        detail["managed_dense_contended"] = managed_dense_contended()
        detail["real_curl"] = real_binary_bench()
        detail["real_curl_1k"] = real_curl_1k()
        detail["managed_ckpt_overhead"] = managed_ckpt_overhead()
        detail["managed_fidelity_audit"] = managed_fidelity_audit()
        detail["tor_100k"] = child_legs["tor_100k"]
        detail["tor_100k"]["tor_1_10_sharded"] = \
            child_legs["tor_1_10_sharded"]
        detail["tor_400_sweep_10seed"] = child_legs["tor_400_sweep_10seed"]
        detail["tpu_mesh_scaling"] = mesh_scaling()
        detail["tpu_mesh_scaling_forced_collective"] = mesh_scaling(
            force_collective=True)
        # the forced-collective note claims result identity: CHECK it
        for sh in ("shards_1", "shards_2", "shards_4", "shards_8"):
            a = detail["tpu_mesh_scaling"].get(sh)
            b = detail["tpu_mesh_scaling_forced_collective"].get(sh)
            if a and b and "error" not in a and "error" not in b:
                for k in ("units_sent", "events"):
                    assert a[k] == b[k], ("mesh_floor divergence", sh, k)
        detail["draw_plane"] = draw_plane_throughput()
        for tag in ("tgen_1k", "tgen_100", "tor_400", "gossip_10k",
                    "web_cdn"):
            for pol in detail[tag]:
                if isinstance(detail[tag][pol], dict):
                    detail[tag][pol].pop("counters", None)
                    detail[tag][pol].pop("process_errors", None)
        (ROOT / "BENCH_DETAIL.json").write_text(json.dumps(detail, indent=2))
        log("wrote BENCH_DETAIL.json")

    print(json.dumps(headline), flush=True)


if __name__ == "__main__":
    main()
