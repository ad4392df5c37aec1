"""How long the routed experts' arrays were over the window, as a share
of their full length: per step and expert layer the rows the arrays
between the sort and the sum back held, over the (token, choice) pairs,
in percent. The program's `moe/route` span (`code2vec_tpu/obs/route.py`)
carries `row_bound`, the rows a layer's arrays hold when its live rows
fit (`ops/moe.row_bound`, from shapes), and `compact_layers`, how many
of the step's expert layers ran at that bound (the device's own
decision); a layer that did not ran at the full length, one row a pair.
Under a mesh both are sums over the devices, as the pairs here are the
whole step's. The window's steps are the record's last
`ctx.window["steps"]`, as `moe_route.py` takes them. 100 says that no
layer of the window ran at a bound under its pairs.

A program whose spans carry no such attributes gives None, as does a
record shorter than the window.
"""


def reduce(records: list, steps: int, pairs: int, devices: int = 1):
    """`pairs`: the (token, choice) pairs of one expert layer of a whole
    step, over its `devices`."""
    if not steps or not pairs or len(records) < steps:
        return None
    carried = layers = 0
    for attrs in (r["attrs"] for r in records[-steps:]):
        if "row_bound" not in attrs or "compact_layers" not in attrs:
            return None
        at_bound = attrs["compact_layers"]      # (layer, device) pairs
        full = len(attrs["layers"]) * devices - at_bound
        carried += (at_bound * attrs["row_bound"] + full * pairs) / devices
        layers += len(attrs["layers"])
    return 100.0 * carried / (layers * pairs) if layers else None


def read(ctx, args):
    try:
        from code2vec_tpu.obs.trace import memory_tracer
    except ImportError:     # a program that keeps no record
        return None
    config = ctx.config
    if "num_experts_per_tok" not in config:
        return None
    pairs = (ctx.window["batch"] * config["model"]["max_contexts"]
             * config["num_experts_per_tok"])
    return reduce(memory_tracer().records("moe/route"), ctx.window["steps"],
                  pairs, ctx.window["chips"])
