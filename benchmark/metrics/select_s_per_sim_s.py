"""round loop: the C round loop's own time outside the per-host runs
(its ``events.select`` timer in ``phase_detail``: picking the round's hosts
from the due-time index and the inbox hosts, and keying them again after
their runs) per simulated second. Nothing when an experiment has no such
timer (a program without it, or no C engine)."""

from _sums import per_sim_s

PART = "events.select"


def read(ctx):
    exps = ctx["experiments"]
    if not exps or any(PART not in e.get("phase_detail", {}) for e in exps):
        return None
    return per_sim_s(ctx, sum(e["phase_detail"][PART] for e in exps))
