"""columnar plane (``network/colplane.py``): barrier, extract and deferred
ingress per simulated second."""

from _sums import per_sim_s, phase_sum


def read(ctx):
    return per_sim_s(ctx, phase_sum(
        ctx, ("barrier", "extract", "ingress_deferred")))
