"""commit_us_per_pod: the scheduler's commit drain phase (assume, bind
enqueue and flush) over the window, per pod bound in it. Read from
scheduler_drain_phase_seconds{phase=commit}."""


def probe(sched):
    return {"commit_s": sched.metrics.drain_phase.sum("commit")}


def read(ctx):
    if not ctx.bound:
        return None
    return ctx.delta["commit_s"] / ctx.bound * 1e6
