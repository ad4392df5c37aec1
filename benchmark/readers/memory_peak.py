"""Peak device memory on the fullest chip, read after the window and
before the reference runs."""


def read(ctx, args):
    peak = ctx.device["memory_peak_bytes"]
    return peak / 1e9 if peak else None
