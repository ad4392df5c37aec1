"""Device time a step: the union of the busiest device's operation
intervals inside the window, over the window's steps."""


def read(ctx, args):
    steps = ctx.window["steps"]
    if not steps:
        return None
    return ctx.trace_summary["busiest_s"] * 1e3 / steps
