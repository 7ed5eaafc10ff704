"""Sums over the window's completed experiments, shared by the readers."""


def phase_sum(ctx, names) -> float:
    return sum(e["phase_wall"].get(n, 0.0)
               for e in ctx["experiments"] for n in names)


def per_sim_s(ctx, seconds: float):
    """``seconds`` spread over the simulated seconds of the window; None
    when no experiment completed."""
    return seconds / ctx["sim_s"] if ctx["sim_s"] > 0 else None
