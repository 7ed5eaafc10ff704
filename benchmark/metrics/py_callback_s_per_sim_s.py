"""round loop: the Python callables the C round loop invokes (its
``events.py_callback`` timer in ``phase_detail``: model callbacks such as
process starts) per simulated second. Nothing when an experiment has no
such timer (the C engine did not run it)."""

from _sums import per_sim_s

PART = "events.py_callback"


def read(ctx):
    exps = ctx["experiments"]
    if not exps or any(PART not in e.get("phase_detail", {}) for e in exps):
        return None
    return per_sim_s(ctx, sum(e["phase_detail"][PART] for e in exps))
