"""Programs JAX compiled (or fetched from its cache) inside the window."""


def read(ctx, args):
    return ctx.window["compiles"]
