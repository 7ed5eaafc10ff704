"""device routing (``network/devroute.py``): window build, dispatch,
readback and the draw flush per simulated second."""

from _sums import per_sim_s, phase_sum


def read(ctx):
    return per_sim_s(ctx, phase_sum(
        ctx, ("window_build", "window_dispatch", "window_readback",
              "draw_flush")))
