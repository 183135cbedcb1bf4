"""setup_s: process start until the window opens (host clock)."""


def read(ctx):
    return ctx.setup_s
