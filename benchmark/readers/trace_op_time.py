"""Device time a step of the operations of one kind (`args["kind"]`, the
part of the class name before its shape), a mean over the devices."""

import trace_reduce


def read(ctx, args):
    steps = ctx.window["steps"]
    by_class = trace_reduce.class_seconds(
        ctx.trace_data, lambda cls, name: cls.split("[")[0] == args["kind"])
    if not steps or not by_class:
        return None
    return sum(by_class.values()) * 1e3 / steps
