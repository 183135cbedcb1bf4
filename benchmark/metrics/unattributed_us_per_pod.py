"""unattributed_us_per_pod: the loop time no named phase covers — the
self time of the root phase schedule_pending — over the window, per pod
bound in it. Read from scheduler_phase_self_seconds_total; a program
without that family gives nothing."""


def probe(sched):
    c = getattr(sched.metrics, "phase_self_seconds", None)
    return {} if c is None else {"unattributed_s": c.value("schedule_pending")}


def read(ctx):
    if "unattributed_s" not in ctx.delta or not ctx.bound:
        return None
    return ctx.delta["unattributed_s"] / ctx.bound * 1e6
