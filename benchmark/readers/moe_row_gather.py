"""Device time a step of the routed experts' row copies, a mean over the
devices: the gathers whose result holds one row of the block's width for
every (token, choice) pair of a device's step. Those are `ops/moe.py`'s
`_spread` and `_gather_back`, forward, rematerialised and backward, and
the class name tells them by that shape (`gather[<pairs>x<hidden>]`;
the embedding's gathers have the tables' width). None where the
configuration routes nothing or the trace holds no such gather."""

import trace_reduce


def read(ctx, args):
    steps, config = ctx.window["steps"], ctx.config
    if not steps or "num_experts_per_tok" not in config:
        return None
    pairs = (ctx.window["batch"] // ctx.window["chips"]
             * config["model"]["max_contexts"]
             * config["num_experts_per_tok"])
    wanted = f"gather[{pairs}x{config['hidden_size']}]"
    by_class = trace_reduce.class_seconds(
        ctx.trace_data, lambda cls, name: cls == wanted)
    if not by_class:
        return None
    return sum(by_class.values()) * 1e3 / steps
