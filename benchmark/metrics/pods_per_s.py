"""pods_per_s: binds the watch saw inside the window, over its seconds."""


def read(ctx):
    return ctx.bound / ctx.window_s
