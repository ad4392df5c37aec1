"""Collective time a step, a mean over the devices, of the collectives
of `args["kinds"]` (e.g. ["all-reduce"]: the gradient sync, not the
in-flight windows of the small permutes and gathers beside it).
`args["part"]` is "all" (the union of their intervals) or "exposed" (the
part of it in which no other operation ran on that device), both
computed from the intervals."""

import trace_reduce


def read(ctx, args):
    steps = ctx.window["steps"]
    got = trace_reduce.collective_seconds(ctx.trace_data,
                                          args.get("kinds"))
    if not steps or got["seconds"] <= 0:
        return None
    key = "seconds" if args["part"] == "all" else "exposed_seconds"
    return got[key] * 1e3 / steps
