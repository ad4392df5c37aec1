"""The device's idle share of the window: 1 - busy / window, busy a mean
over the chips."""


def read(ctx, args):
    s = ctx.trace_summary
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
