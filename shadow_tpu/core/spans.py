"""Host spans of a run: the wall time of the round loop, split by phase.

A span has a name, a start, an end and a parent: the span open on the same
thread when it began. Names without a dot are *phases*; a phase records its
**self time**, the wall inside it that no nested phase covers. Names of the
form ``<phase>.<part>`` are *parts*: they split the phase that encloses them
and never take time out of it. So

- ``phase_wall()`` partitions the loop: a second nested under one phase is
  never also counted under another (a device window dispatched from inside
  a draw flush counts under ``window_dispatch`` and is taken out of
  ``draw_flush``), and the phases add up to at most the wall they ran in;
- ``phase_detail()`` holds the parts, and the parts of a phase add up to at
  most that phase;
- ``phase_calls()`` counts every span, and the counts the C engine keeps
  for its per-unit sections.

Each span is also a ``jax.profiler.TraceAnnotation`` named
``shadow.<name>`` while a profiler trace is being collected: it lands on the
host thread's line of the trace, on the device trace's clock. The recorder
only annotates when ``jax`` was already imported when it was made, so runs
that never touch the device never import it.

A recorder is installed on the thread that runs the loop (``installed()``).
Code below the loop -- the colplane, the device draw plane shared across
runs, its handles -- opens spans through ``span(name)`` without holding the
recorder. Spans opened on any other thread (a background device attach, a
draw server) record nothing there.
"""

from __future__ import annotations

import os
import sys
import threading
import time as _walltime  # detlint: ok(wallclock): span wall times
from contextlib import contextmanager

_tls = threading.local()


class _Null:
    """The span of a thread with no recorder installed."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NULL = _Null()


def current():
    """The recorder installed on this thread, or None."""
    return getattr(_tls, "rec", None)


def span(name: str):
    """A span of the recorder installed on this thread, for a ``with``
    block; a no-op where none is."""
    try:
        return _tls.rec._spans[name]
    except AttributeError:  # no recorder on this thread
        return _NULL
    except KeyError:
        return _tls.rec.span(name)


class _Span:
    """One name's reusable context manager. Spans nest strictly on their
    thread, so a frame stack on the recorder carries the open instances:
    [start, seconds of phases nested inside, annotation]."""

    __slots__ = ("label", "part", "acc", "stack", "clock", "ann", "on")

    def __init__(self, rec: "Recorder", name: str) -> None:
        self.label = "shadow." + name
        self.part = "." in name
        self.acc = rec._acc.setdefault(name, [0.0, 0])
        self.stack = rec._stack
        self.clock = rec._clock
        self.ann = rec._ann
        self.on = rec._ann_on

    def __enter__(self):
        a = None
        on = self.on
        if on is not None and on():
            a = self.ann(self.label)
            a.__enter__()
        self.stack.append([self.clock(), 0.0, a])
        return self

    def __exit__(self, *exc) -> bool:
        t = self.clock()
        stack = self.stack
        t0, nested, a = stack.pop()
        if a is not None:
            a.__exit__(None, None, None)
        d = t - t0
        acc = self.acc
        acc[0] += d - nested
        acc[1] += 1
        if stack:
            # a phase is taken out of whatever encloses it; a part stays
            # in its phase, and hands up only the phases nested in it
            stack[-1][1] += nested if self.part else d
        return False


class Recorder:
    """Self time and call count per span name, for one run."""

    def __init__(self, clock=_walltime.perf_counter,
                 trace: bool = True) -> None:
        self._clock = clock
        self._stack: list = []
        self._acc: dict = {}  # name -> [seconds, calls]
        self._counts: dict = {}  # count-only sections: name -> calls
        self._spans: dict = {}
        # jax only when fully imported (a background attach may be inside
        # its import right now), and never imported from here
        ann = (getattr(sys.modules.get("jax.profiler"), "TraceAnnotation",
                       None) if trace else None)
        self._ann = ann
        self._ann_on = ann.is_enabled if ann is not None else None

    def span(self, name: str) -> _Span:
        sp = self._spans.get(name)
        if sp is None:
            sp = self._spans[name] = _Span(self, name)
        return sp

    def declare(self, names) -> None:
        """Report these phases even where nothing opened them."""
        for n in names:
            self._acc.setdefault(n, [0.0, 0])

    def add(self, name: str, seconds: float, calls: int) -> None:
        """Fold in a section timed elsewhere (the C engine's timers)."""
        acc = self._acc.setdefault(name, [0.0, 0])
        acc[0] += seconds
        acc[1] += calls

    def count(self, name: str, calls: int) -> None:
        """Fold in a section counted, not timed, elsewhere."""
        self._counts[name] = self._counts.get(name, 0) + calls

    @contextmanager
    def installed(self):
        """Make this the current recorder on the calling thread."""
        prev = getattr(_tls, "rec", None)
        _tls.rec = self
        try:
            yield self
        finally:
            _tls.rec = prev

    def phase_wall(self) -> dict:
        return {n: a[0] for n, a in self._acc.items() if "." not in n}

    def phase_detail(self) -> dict:
        return {n: a[0] for n, a in self._acc.items() if "." in n}

    def phase_calls(self) -> dict:
        out = {n: a[1] for n, a in self._acc.items() if a[1]}
        out.update((n, c) for n, c in self._counts.items() if c)
        return out


def rss_bytes() -> int:
    """This process's resident set size, from ``/proc/self/statm``."""
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
