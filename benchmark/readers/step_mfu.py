"""The whole step's share of the chips' peak: the operations the model
needs, forward and backward, for what the window trained
(`counts/<name>.py`, named by the configuration's `counts`), over the
window's seconds x chips x the bf16 peak of `peaks.json`."""


def read(ctx, args):
    if ctx.peaks is None or not ctx.window["contexts"]:
        return None
    counts = ctx.load_module("counts", ctx.config["counts"],
                             "counts of configuration "
                             f"{ctx.config['name']!r}:")
    needed = counts.flops(ctx.model_sizes, ctx.window)
    available = (ctx.window["seconds"] * ctx.window["chips"]
                 * ctx.peaks["bf16_flops_per_s"])
    return 100.0 * needed / available
