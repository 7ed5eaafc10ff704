"""round loop: loop wall that no named ``phase_wall`` entry covers, per
simulated second. ``phase_wall`` is not a partition of the loop, so the
remainder is reported rather than the sum trusted."""

from _sums import per_sim_s


def read(ctx):
    rest = sum(e["loop_s"] - sum(e["phase_wall"].values())
               for e in ctx["experiments"])
    return per_sim_s(ctx, rest)
