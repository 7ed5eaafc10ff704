"""round loop: host event dispatch (``phase_wall.events``: the C round
loop and the Python model callbacks) per simulated second."""

from _sums import per_sim_s, phase_sum


def read(ctx):
    return per_sim_s(ctx, phase_sum(ctx, ("events",)))
