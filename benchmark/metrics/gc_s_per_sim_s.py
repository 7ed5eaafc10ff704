"""round loop: the loop's garbage collections (``phase_wall.gc``: the
collects every few thousand rounds and the last one at the loop's end) per
simulated second."""

from _sums import per_sim_s, phase_sum


def read(ctx):
    return per_sim_s(ctx, phase_sum(ctx, ("gc",)))
