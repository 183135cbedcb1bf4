"""kernel_us_per_pod: device-op time on the TPU plane of the profiler trace,
per pod bound in the traced seconds."""


def read(ctx):
    if ctx.trace is None or not getattr(ctx, "trace_bound", 0):
        return None
    return sum(ctx.trace["op_seconds"].values()) / ctx.trace_bound * 1e6
