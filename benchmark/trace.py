"""Trace of a window, and its reduction to device time, kernel time and
idle gaps.

What a TPU v5e trace holds (``jax.profiler``, JAX 0.9, looked at by hand
on the chip, PR 22): the plane ``/device:TPU:0`` has the lines ``XLA
Modules`` (one event per executed program, named ``jit_<function>(<fingerprint>)``,
e.g. ``jit__draw_kernel(1512598693649736708)``) and ``XLA Ops`` (one
event per HLO op, named by its HLO text, which carries the operand
shapes, e.g. ``%fusion.5 = (...) fusion(u32[4,65536]...``). The plane
``/host:CPU`` has one line per host thread; the benchmark's
``TraceAnnotation`` spans (``bench.window``, ``bench.build``,
``bench.run``, ``bench.loop``, ``bench.finalize``, ``bench.teardown``)
are on the main thread's line. Host and device events share one clock,
in nanoseconds.
"""

from __future__ import annotations

import bisect
import glob
import re
import shutil
from pathlib import Path

import roofline

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
#: spans that label an idle gap, innermost first
LABELS = ("bench.loop", "bench.finalize", "bench.build", "bench.teardown",
          "bench.run", "bench.window")
_MODULE = re.compile(r"^jit_(.+)\(\d+\)$")
#: the packed (rows, P) uint32 operand of a draw kernel
_PACKED = re.compile(r"u32\[(\d+),(\d+)\]")
_PACKED_ROWS = {"_draw_kernel": 4, "_min_draw_kernel": 3}


class Tracer:
    """``jax.profiler`` around the window, host Python tracing off (it
    would record every model callback)."""

    def __init__(self, jax, out_dir: Path) -> None:
        self.jax = jax
        self.out_dir = Path(out_dir)

    def start(self) -> None:
        shutil.rmtree(self.out_dir, ignore_errors=True)
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        self.jax.profiler.start_trace(str(self.out_dir),
                                      profiler_options=opts)

    def stop_and_reduce(self) -> dict:
        self.jax.profiler.stop_trace()
        files = glob.glob(str(self.out_dir / "**" / "*.xplane.pb"),
                          recursive=True)
        if len(files) != 1:
            raise RuntimeError(f"expected one .xplane.pb, found {files}")
        try:
            return reduce_file(files[0])
        finally:
            shutil.rmtree(self.out_dir, ignore_errors=True)


def reduce_file(path) -> dict:
    from jax.profiler import ProfileData

    return reduce_planes(_planes(ProfileData.from_file(str(path))))


def _planes(pd) -> dict:
    """{plane: {line: [(name, start_ns, end_ns)]}}, plain data."""
    return {pl.name: {ln.name: [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                                for e in ln.events]
                      for ln in pl.lines}
            for pl in pd.planes}


def union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def reduce_planes(planes: dict) -> dict:
    """Device busy and idle time inside the ``bench.window`` span, each
    kernel's time and bytes, and the breakdown: the device ops that took
    most time and the longest idle gaps, labelled by the innermost
    harness span at their middle."""
    spans = {name: [] for name in LABELS}
    for pname, lines in planes.items():
        if pname.startswith("/host:"):
            for events in lines.values():
                for name, s, e in events:
                    if name in spans:
                        spans[name].append((s, e))
    if not spans["bench.window"]:
        raise RuntimeError("the trace holds no bench.window span")
    lo = min(s for s, _ in spans["bench.window"])
    hi = max(e for _, e in spans["bench.window"])
    window_ns = hi - lo

    devices = [lines for pname, lines in planes.items()
               if DEVICE_PLANE.match(pname)]
    if not devices:
        raise RuntimeError(f"no TPU device plane in {sorted(planes)}")
    busy_ns, op_time, kernels, gaps = 0.0, {}, {}, []
    for lines in devices:
        ops = sorted(((s, e, n) for n, s, e in lines.get("XLA Ops", [])
                      if e > lo and s < hi), key=lambda o: o[0])
        starts = [o[0] for o in ops]
        busy = union(clip([(s, e) for s, e, _ in ops], lo, hi))
        busy_ns += sum(e - s for s, e in busy)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        for n, s, e in lines.get("XLA Modules", []):
            if e <= lo or s >= hi:
                continue
            m = _MODULE.match(n)
            prog = m.group(1) if m else n
            inside = ops[bisect.bisect_left(starts, s):
                         bisect.bisect_left(starts, e)]
            for os_, oe, on in inside:
                key = f"{prog}:{on.split(' = ', 1)[0]}"
                op_time[key] = op_time.get(key, 0.0) + (oe - os_)
            if prog not in roofline.KERNELS:
                continue
            k = kernels.setdefault(prog,
                                   {"seconds": 0.0, "calls": 0, "bytes": 0})
            k["seconds"] += (e - s) / 1e9
            k["calls"] += 1
            k["bytes"] += roofline.KERNELS[prog](
                _bucket(inside, _PACKED_ROWS[prog], s))
    n_dev = len(devices)

    # cut each gap where a harness span starts or ends, so that every
    # piece lies in one span, and label it by the innermost span there
    cuts = sorted({x for name in LABELS[:-1] for iv in spans[name]
                   for x in iv if lo < x < hi})
    pieces = []
    for s, e in gaps:
        at = [s] + cuts[bisect.bisect_right(cuts, s):
                        bisect.bisect_left(cuts, e)] + [e]
        pieces += [(at[i], at[i + 1]) for i in range(len(at) - 1)]

    def label(s, e):
        mid = (s + e) / 2
        for name in LABELS:
            if any(a <= mid < b for a, b in spans[name]):
                return name.split(".", 1)[1]
        return "harness"

    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(pieces, key=lambda g: g[0] - g[1])[:10]
    return {
        "busy_s": busy_ns / n_dev / 1e9,
        "window_s": window_ns / 1e9,
        "kernels": kernels,
        "breakdown": {
            "device_ops": [[n, t / n_dev / 1e9] for n, t in top_ops],
            "idle_gaps": [[label(s, e), (e - s) / 1e9] for s, e in top_gaps],
        },
    }


def _bucket(ops, rows: int, at) -> int:
    """The padded P of a kernel call: from the (rows, P) packed operand in
    the HLO text of its ops."""
    for _, _, n in ops:
        for r, p in _PACKED.findall(n):
            if int(r) == rows:
                return int(p)
    raise RuntimeError(f"no u32[{rows},P] operand among the ops of the "
                       f"kernel call at {at} ns")
