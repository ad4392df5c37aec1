"""A kernel's share of its roofline: the least time the chip could take
for the work the algorithm needs (`counts/<args["counts"]>.py`: the
larger of operations over the bf16 peak and bytes over the HBM peak),
over the device time of the kernel's operations in the trace (those
whose class matches one of `args["match"]`). Says which bound
on standard error."""

import re
import sys

import trace_reduce


def read(ctx, args):
    if ctx.peaks is None or not ctx.window["contexts"]:
        return None
    patterns = [re.compile(p) for p in args["match"]]
    seconds = sum(trace_reduce.class_seconds(
        ctx.trace_data,
        lambda cls, name: any(p.search(cls) for p in patterns)).values())
    if seconds <= 0:
        return None
    counts = ctx.load_module("counts", args["counts"],
                             f"counts of kernel {args['counts']!r}:")
    work = counts.work(ctx.model_sizes, ctx.window)
    chips = ctx.window["chips"]
    by_ops = work["flops"] / chips / ctx.peaks["bf16_flops_per_s"]
    by_bytes = work["bytes"] / chips / ctx.peaks["hbm_bytes_per_s"]
    print(f"roofline {args['counts']}: bound by "
          f"{'operations' if by_ops >= by_bytes else 'bytes'} "
          f"(ops {by_ops:.4g} s, bytes {by_bytes:.4g} s, "
          f"kernel {seconds:.4g} s a device)", file=sys.stderr)
    return 100.0 * max(by_ops, by_bytes) / seconds
