"""draw kernels (``ops/propagate.py`` ``_draw_kernel``,
``_min_draw_kernel``): the bytes their calls must move over the chip's
HBM bandwidth, over their device time in the trace. The v5e publishes no
integer vector peak, so only the bytes bound the least time; the share
is of that bound."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    ks = [tr["kernels"][k] for k in ("_draw_kernel", "_min_draw_kernel")
          if k in tr["kernels"]]
    secs = sum(k["seconds"] for k in ks)
    if secs <= 0:
        return None
    least = sum(k["bytes"] for k in ks) / ctx["peak"]["hbm_bytes_per_s"]
    return 100.0 * least / secs
