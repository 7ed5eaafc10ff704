"""stream transport: the C endpoints' arrival handling (data, acks, SACK,
RTO arm and cancel; the C engine's ``events.stream`` timer in
``phase_detail``, the relay sections inside it and the control cells
handed to Python left out) per simulated second. Nothing when an
experiment has no such timer (a program without it, or no C engine)."""

from _sums import per_sim_s

PART = "events.stream"


def read(ctx):
    exps = ctx["experiments"]
    if not exps or any(PART not in e.get("phase_detail", {}) for e in exps):
        return None
    return per_sim_s(ctx, sum(e["phase_detail"][PART] for e in exps))
