"""batch_build_us_per_pod: self time of the batch_build phase (the
drain's pod batch, built before host_build) over the window, per pod
bound in it. Read from scheduler_phase_self_seconds_total; a program
without that family gives nothing."""


def probe(sched):
    c = getattr(sched.metrics, "phase_self_seconds", None)
    return {} if c is None else {"batch_build_s": c.value("batch_build")}


def read(ctx):
    if "batch_build_s" not in ctx.delta or not ctx.bound:
        return None
    return ctx.delta["batch_build_s"] / ctx.bound * 1e6
