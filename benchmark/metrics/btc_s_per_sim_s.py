"""bitcoin nodes: the C Bitcoin half's self time (message parsing, announce
queues, INV/GETDATA/TX, compact-block build and reconstruction,
getblocktxn handling, trickle timers; the C engine's ``events.btc`` timer
in ``phase_detail``, the endpoints' own work left in ``events.stream``)
per simulated second. Nothing when an experiment has no such timer (a
program without it, or a cell with no Bitcoin nodes)."""

from _sums import per_sim_s

PART = "events.btc"


def read(ctx):
    exps = ctx["experiments"]
    if not exps or any(PART not in e.get("phase_detail", {}) for e in exps):
        return None
    return per_sim_s(ctx, sum(e["phase_detail"][PART] for e in exps))
