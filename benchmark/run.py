#!/usr/bin/env python3
"""The chip benchmark: whole experiments back to back, on one process.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up (``setup_s``: from the start of this script to the window's start)
builds the C engine, checks that JAX found the chip the cell asks for,
and generates the cell's configs with ``doc`` of the config module
(``BENCHMARK.json``'s ``file``) from ``benchmark/traffic/<traffic>.json``:
one for each of the traffic's ``seeds_per_run`` values of
``general.seed``, derived from ``--seed``. It runs each of them for a
moment (``stop_time`` cut to 1 ms), which attaches and calibrates that
seed's draw plane in the program's process cache, then the first one
whole: that serves or fills the compile cache.

The window runs experiments back to back, cycling through the seeds --
parse, ``Controller`` build, ``run()`` (round loop and finalize), the
config module's ``results`` read, teardown -- each in a fresh data
directory, and ends at the end of the first one that finishes at or
after ``--seconds``. Every metric is all the work of its completed
experiments over all the wall time of the window.

After the window, peak RSS and device memory are read, the program's
state is freed, and the config's plain reference (the file under
``benchmark/`` that the config module's ``REFERENCE`` names) simulates
one of the window's seeds, drawn from ``--seed``: each of its
experiments must equal the reference on the reference's ``KEYS``, and
every experiment the first of its own seed. The harness knows no model:
what is compared, and how the reference's line reads, come from those
two modules.

With ``--trace 1`` the window runs under ``jax.profiler``, and the
per-layer metrics (``benchmark/metrics/<name>.py``) are printed instead of
the end-to-end ones. Everything but the result goes to earlier lines; the
last line of stdout is the result object.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import gc  # noqa: E402
import hashlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: run-time outputs: experiment trees, the reference's tree, traces
WORK = ROOT / ".bench_work"
for _p in (BENCH, BENCH / "metrics"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

#: the device counters of the summary a record keeps
DEVICE_KEYS = ("platform", "kind", "count", "routes", "handoff_units",
               "resident_units", "resident_peak", "spec_rows", "attach")


class BenchError(Exception):
    """The run cannot measure: no result is printed."""


def say(msg: str) -> None:
    print(msg, flush=True)


def load_module(path: Path, name: str = None):
    spec = importlib.util.spec_from_file_location(name or path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str) -> dict:
    """The cell's entries of BENCHMARK.json, its config module, its
    traffic and the per-layer metrics it reports."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    config = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    per_layer = [m for m in spec["per_layer"]
                 if name in m.get("workloads", [name])]
    end_to_end = [m for m in spec["end_to_end"]
                  if name in m.get("workloads", [name])]
    return {"cell": cell,
            "config": load_module(ROOT / config["file"]),
            "traffic": json.loads(
                (BENCH / "traffic" / f"{cell['traffic']}.json").read_text()),
            "per_layer": per_layer, "end_to_end": end_to_end}


def load_reference(config):
    """The plain reference that the config module names."""
    return load_module(BENCH / config.REFERENCE)


class CompileCounter:
    """Counts XLA backend compiles per thread name, from jax.monitoring
    (a copy of chip_smoke.py's)."""

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


def check_device(jax, chips: int) -> tuple[dict, dict]:
    """The device JAX found, and its peaks; a run on anything but the
    chips the cell asks for fails."""
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    say(f"device: {json.dumps(device)}")
    if device["platform"] != "tpu":
        raise BenchError(f"JAX found platform {device['platform']!r}, "
                         f"not 'tpu': no accelerator to measure")
    if device["count"] < chips:
        raise BenchError(f"the cell asks for {chips} chip(s), JAX sees "
                         f"{device['count']}")
    peaks = json.loads((BENCH / "peaks.json").read_text())
    if device["kind"] not in peaks:
        raise BenchError(f"device kind {device['kind']!r} is not in "
                         f"benchmark/peaks.json")
    return device, peaks[device["kind"]]


class SpanClock:
    """Wall seconds of the program's calls that ``instrument`` wraps,
    summed per label since the last ``take``."""

    def __init__(self) -> None:
        self.sums: dict = {}

    def add(self, label: str, seconds: float) -> None:
        self.sums[label] = self.sums.get(label, 0.0) + seconds

    def take(self) -> dict:
        out, self.sums = self.sums, {}
        return out


def instrument(jax, Controller, clock: SpanClock) -> None:
    """Host spans, from the benchmark, around the program's round loop
    and finalize: a ``TraceAnnotation`` (the trace labels idle gaps with
    it) and the wall time on ``clock``."""
    def wrap(name: str, label: str) -> None:
        orig = getattr(Controller, name)

        def spanned(self, *a, **kw):
            t0 = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation(f"bench.{label}"):
                    return orig(self, *a, **kw)
            finally:
                clock.add(label, time.perf_counter() - t0)

        setattr(Controller, name, spanned)

    wrap("_round_loop", "loop")
    wrap("_finalize", "finalize")


def derive_seeds(seed: int, k: int) -> list:
    """The ``general.seed`` of each of a run's ``k`` configs: 31-bit
    numbers drawn from ``--seed``."""
    return [int.from_bytes(hashlib.sha256(f"{seed}:{j}".encode())
                           .digest()[:4], "little") >> 1 for j in range(k)]


def run_experiment(jax, doc: dict, data_dir: Path, clock: SpanClock,
                   config, reference) -> dict:
    """One experiment through the program's normal entry; its record:
    the compared identity (the reference's ``IDENTITY_KEYS`` of the
    summary and the config's ``results``), timings, and the summary's
    spans and device counters."""
    from shadow_tpu.config.schema import parse_config
    from shadow_tpu.core.controller import Controller

    ann = jax.profiler.TraceAnnotation
    shutil.rmtree(data_dir, ignore_errors=True)
    clock.take()
    t0 = time.perf_counter()
    with ann("bench.build"):
        # a copy: the reference reads ``doc`` after the window
        cfg = parse_config(copy.deepcopy(doc), {
            "general.data_directory": str(data_dir),
            "experimental.scheduler_policy": "tpu_batch"})
        ctl = Controller(cfg, mirror_log=False)
    c_engine = getattr(ctl.engine, "_c", None) is not None
    t1 = time.perf_counter()
    with ann("bench.run"):
        summary = ctl.run()
    t2 = time.perf_counter()
    with ann("bench.teardown"):
        ident = {k: summary[k] for k in reference.IDENTITY_KEYS}
        ident.update(config.results(ctl, data_dir))
        del ctl
        shutil.rmtree(data_dir, ignore_errors=True)
        gc.collect()
    t3 = time.perf_counter()
    dev = summary.get("device", {})
    spans = clock.take()
    return {
        "identity": ident, "seed": doc["general"]["seed"],
        "c_engine": c_engine,
        "wall_s": t3 - t0, "build_s": t1 - t0, "run_s": t2 - t1,
        "teardown_s": t3 - t2, "loop_span_s": spans.get("loop", 0.0),
        "finalize_s": spans.get("finalize", 0.0),
        "loop_s": summary["wall_seconds"],
        "sim_s": summary["sim_seconds"],
        "phase_wall": summary["phase_wall"],
        "phase_detail": summary["phase_detail"],
        "phase_calls": summary["phase_calls"],
        "process_errors": summary["process_errors"],
        "units_sent": summary["units_sent"],
        "window_units": dev.get("window_units", 0),
        "windows": summary.get("device_windows_dispatched", 0),
        "device": {k: dev.get(k) for k in DEVICE_KEYS},
    }


def window(jax, docs: list, seconds: float, counter, clock: SpanClock,
           config, reference) -> dict:
    """Experiments back to back, cycling through ``docs``, until the
    first that ends at or after ``seconds``."""
    exps, failed = [], 0
    c0 = counter.snapshot()
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            i = len(exps) + failed
            try:
                rec = run_experiment(jax, docs[i % len(docs)],
                                     WORK / f"exp{i}", clock, config,
                                     reference)
            except Exception as exc:  # counted, and the run is not correct
                failed += 1
                say(f"experiment {i} raised {type(exc).__name__}: {exc}")
            else:
                exps.append(rec)
            if time.perf_counter() - t0 >= seconds:
                break
    wall = time.perf_counter() - t0
    c1 = counter.snapshot()
    compiles = {k: v - c0.get(k, 0) for k, v in c1.items()
                if v != c0.get(k, 0)}
    return {"experiments": exps, "raised": failed, "window_s": wall,
            "compiles": compiles}


def repeats_differ(exps: list) -> list:
    """For each experiment, whether it differs from the first experiment
    of its own seed in the window."""
    first: dict = {}
    out = []
    for e in exps:
        out.append(first.setdefault(e["seed"], e["identity"])
                   != e["identity"])
    return out


def checks_of(reference, compared: list, ref: dict, exps: list,
              raised: int) -> dict:
    """Each number compared, with its limit."""
    checks = {f"{k}_mismatch": {"value": v, "limit": 0}
              for k, v in reference.mismatches(compared, ref).items()}
    checks["repeat_mismatch"] = {"value": sum(repeats_differ(exps)),
                                 "limit": 0}
    checks["process_errors_seen"] = {
        "value": sum(bool(e["process_errors"]) for e in exps),
        "limit": 0}
    checks["experiments_raised"] = {"value": raised, "limit": 0}
    checks["experiments_compared"] = {"value": len(compared), "limit": 1,
                                      "at_least": True}
    return checks


def passed(checks: dict) -> bool:
    return all(c["value"] >= c["limit"] if c.get("at_least")
               else c["value"] <= c["limit"] for c in checks.values())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise BenchError("--seed must be >= 0")
    cell = load_cell(args.workload)
    if not (ROOT / "shadow_tpu").is_dir() or not (ROOT / "native").is_dir():
        raise BenchError(f"{ROOT} holds no shadow_tpu program to measure")
    sys.path.insert(0, str(ROOT))
    WORK.mkdir(exist_ok=True)
    # the TPU runtime logs to /tmp/tpu_logs unless told: keep it inside
    # the checkout, like everything else a run writes
    os.environ.setdefault("TPU_LOG_DIR", str(WORK / "tpu_logs"))

    import jax

    device, peak = check_device(jax, cell["cell"]["chips"])
    counter = CompileCounter(jax)

    t = time.perf_counter()
    subprocess.run(["make", "-C", str(ROOT / "native")], check=True,
                   stdout=subprocess.DEVNULL)
    say(f"setup: make -C native in {time.perf_counter() - t:.3f} s")

    import shadow_tpu
    from shadow_tpu.core.controller import Controller

    # absolute, not resolved: a checkout may reach the program through a
    # link
    if not Path(os.path.abspath(shadow_tpu.__file__)).is_relative_to(ROOT):
        raise BenchError(f"shadow_tpu imported from {shadow_tpu.__file__}")
    clock = SpanClock()
    instrument(jax, Controller, clock)
    config = cell["config"]
    reference = load_reference(config)
    traffic = cell["traffic"]
    seeds = derive_seeds(args.seed, traffic["seeds_per_run"])
    docs = [config.doc(s, traffic) for s in seeds]
    say(f"setup: general.seed of each config {seeds}")

    t = time.perf_counter()
    for d in docs:
        brief = copy.deepcopy(d)
        brief["general"]["stop_time"] = "1 ms"
        run_experiment(jax, brief, WORK / "warmup", clock, config,
                       reference)
    say(f"setup: {len(docs)} draw planes attached in "
        f"{time.perf_counter() - t:.3f} s")
    t = time.perf_counter()
    warm = run_experiment(jax, docs[0], WORK / "warmup", clock, config,
                          reference)
    say(f"setup: warm-up experiment {time.perf_counter() - t:.3f} s, "
        f"device {json.dumps(warm['device'])}, c_engine {warm['c_engine']}, "
        f"compiles {json.dumps(counter.snapshot())}")
    if not warm["c_engine"]:
        raise BenchError("the C engine did not attach")
    if warm["device"]["platform"] != device["platform"]:
        raise BenchError(f"the draw plane ran on {warm['device']}")
    del warm
    gc.collect()
    setup_s = time.perf_counter() - T_START

    tracer = None
    if args.trace:
        # benchmark/trace.py, under a name that the standard library's
        # ``trace`` module does not shadow
        bench_trace = load_module(BENCH / "trace.py", "bench_trace")
        tracer = bench_trace.Tracer(jax, WORK / "trace")
        tracer.start()
    try:
        w = window(jax, docs, args.seconds, counter, clock, config,
                   reference)
    finally:
        reduced = tracer.stop_and_reduce() if tracer is not None else None
    exps = w["experiments"]
    peak_rss_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024
    stats = jax.devices()[0].memory_stats() or {}
    memory_peak = stats.get("peak_bytes_in_use")

    sim_s = sum(e["sim_s"] for e in exps)
    say(f"window: {len(exps)} experiments completed, {w['raised']} raised, "
        f"{w['window_s']:.3f} s wall, overrun "
        f"{w['window_s'] - args.seconds:.3f} s past --seconds, "
        f"{sim_s} sim-s")
    say(f"window: compiles by thread {json.dumps(w['compiles'])}")
    for i, e in enumerate(exps):
        say(f"experiment {i} (seed {e['seed']}): wall {e['wall_s']:.3f} s: "
            f"build {e['build_s']:.3f}, run {e['run_s']:.3f} (loop span "
            f"{e['loop_span_s']:.3f}, loop {e['loop_s']:.3f}, finalize "
            f"{e['finalize_s']:.3f}), teardown {e['teardown_s']:.3f}; "
            f"device windows {e['windows']}, "
            f"window units {e['window_units']} of {e['units_sent']}, "
            f"routes {json.dumps(e['device']['routes'])}, "
            f"phase_wall {json.dumps(e['phase_wall'])}, "
            f"phase_detail {json.dumps(e['phase_detail'])}")

    shutil.rmtree(WORK / "warmup", ignore_errors=True)
    raised = w["raised"]
    ctx = {"experiments": exps, "window_s": w["window_s"], "sim_s": sim_s,
           "trace": reduced, "peak": peak}
    del w
    gc.collect()

    # the reference simulates one of the window's seeds, drawn from --seed
    present = sorted({e["seed"] for e in exps}, key=seeds.index)
    target = present[args.seed % len(present)] if present else seeds[0]
    compared = [e["identity"] for e in exps if e["seed"] == target]
    t = time.perf_counter()
    ref = reference.simulate(docs[seeds.index(target)])
    checks = checks_of(reference, compared, ref, exps, raised)
    drew = [e["window_units"] for e in exps if e["seed"] == target]
    say(f"reference: seed {target} in {time.perf_counter() - t:.3f} s: "
        f"{reference.describe(ref)}; verdict "
        f"{'equal' if passed(checks) else 'DIFFERENT'} over "
        f"{len(compared)} experiments, whose device drew {drew} units")

    if args.trace:
        readers = {m["name"]: m for m in cell["per_layer"]}
        metrics = {}
        for name, m in readers.items():
            v = load_module(BENCH / "metrics" / f"{name}.py").read(ctx)
            if v is not None:
                metrics[name] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
    else:
        e2e = {"setup_s": setup_s,
               "sim_s_per_wall_s": sim_s / ctx["window_s"],
               "peak_rss_mb": peak_rss_mb}
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"]}
    device["memory_peak_bytes"] = memory_peak
    wrong = repeats_differ(exps)
    result = {"correct": passed(checks),
              "attempted": len(exps) + raised,
              "failed": raised + sum(
                  bad or bool(e["process_errors"])
                  or (e["seed"] == target and any(
                      e["identity"][k] != ref[k] for k in reference.KEYS))
                  for e, bad in zip(exps, wrong)),
              "metrics": metrics, "device": device}
    if args.trace:
        result["breakdown"] = reduced["breakdown"]
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']}"
              f"{', at least' if c.get('at_least') else ''})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BenchError as exc:
        print(f"benchmark: FAILED: {exc}", file=sys.stderr, flush=True)
        sys.exit(1)
