"""A host span the harness timed around a call into the program, in
milliseconds a step. `args["field"]` names the window's total seconds."""


def read(ctx, args):
    steps = ctx.window["steps"]
    if not steps or args["field"] not in ctx.window:
        return None
    return ctx.window[args["field"]] * 1e3 / steps
