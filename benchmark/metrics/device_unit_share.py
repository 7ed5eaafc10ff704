"""device routing: the share of sent units whose loss draws the device
made (``device.window_units`` over ``units_sent``)."""


def read(ctx):
    sent = sum(e["units_sent"] for e in ctx["experiments"])
    if not sent:
        return None
    return 100.0 * sum(e["window_units"] for e in ctx["experiments"]) / sent
