"""The round loop's garbage collection policy.

The loop allocates millions of short-lived objects (units, arrival
closures, heap entries); generational GC scanning them costs ~40% of wall
at 10k-host scale (measured, gossip config). So automatic GC is off inside
the loop, and the loop collects every ``GC_EVERY_ROUNDS`` rounds instead:
reference cycles from closed connections (endpoint <-> callback) are still
reclaimed.

Those collects traverse only what the loop made. At loop entry everything
that already exists -- the build's hosts, the JAX runtime, the caller's own
heap -- is frozen (``gc.freeze``), so a collect skips it. At loop end a
last collect runs while still frozen; then ``gc.unfreeze`` hands the frozen
objects back to the oldest generation, where the next full collection sees
them (build-time garbage included). Objects whose reference count drops to
zero are freed at once, frozen or not.

Where a caller has frozen objects of its own, nothing is frozen or
unfrozen: ``gc.unfreeze`` cannot give back only ours. CPython 3.12 keeps
immortal objects (its static types' MRO tuples) in the permanent generation
too, and a full collection moves there any it finds, so "a caller froze"
means more frozen objects than when this module was imported. Our
``gc.unfreeze`` hands those immortal objects back with ours; no collection
can free them, and the next full one moves them back.
"""

from __future__ import annotations

import gc

#: rounds between the loop's collects
GC_EVERY_ROUNDS = 5000

#: objects the interpreter had frozen before any caller could
_FROZEN_AT_IMPORT = gc.get_freeze_count()


class LoopGC:
    """``with LoopGC(rec, rounds) as lgc:`` around a round loop that calls
    ``lgc.tick(rounds)`` once a round. Collects run in the ``gc`` span of
    ``rec`` (a ``core.spans.Recorder``); the counter ``gc.frozen`` is the
    number of objects frozen at entry (0 where a caller had frozen its
    own), ``gc.found`` the unreachable objects the collects found."""

    def __init__(self, rec, rounds: int) -> None:
        self._rec = rec
        self._gc_span = rec.span("gc")
        self._next = rounds + GC_EVERY_ROUNDS
        self._was_enabled = False
        self._froze = False

    def __enter__(self) -> "LoopGC":
        self._was_enabled = gc.isenabled()
        gc.disable()
        entry = gc.get_freeze_count()
        self._froze = entry <= _FROZEN_AT_IMPORT
        if self._froze:
            gc.freeze()
            self._rec.count("gc.frozen", gc.get_freeze_count() - entry)
        return self

    def tick(self, rounds: int) -> None:
        if rounds >= self._next:
            self._next = rounds + GC_EVERY_ROUNDS
            self.collect()

    def collect(self) -> None:
        with self._gc_span:
            found = gc.collect()
        self._rec.count("gc.found", found)

    def __exit__(self, exc_type, *_) -> bool:
        try:
            if exc_type is None:
                self.collect()
        finally:
            if self._froze:
                gc.unfreeze()
            if self._was_enabled:
                gc.enable()
        return False
