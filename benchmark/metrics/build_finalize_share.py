"""experiment layer: the share of the window outside the round loop.

Harness wall around each experiment (config parse, ``Controller``
build, ``run()``'s prelude and finalize, teardown) less the loop wall
the summary reports, over the window's wall."""


def read(ctx):
    outside = sum(e["wall_s"] - e["loop_s"] for e in ctx["experiments"])
    return 100.0 * outside / ctx["window_s"] if ctx["experiments"] else None
