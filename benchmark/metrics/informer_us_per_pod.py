"""informer_us_per_pod: self time of the scheduler's informer handlers for
created and deleted pods (phases pod_add and pod_delete) over the window,
per pod bound in it. Read from scheduler_phase_self_seconds_total; a
program without that family gives nothing."""


def probe(sched):
    c = getattr(sched.metrics, "phase_self_seconds", None)
    if c is None:
        return {}
    return {"informer_s": c.value("pod_add") + c.value("pod_delete")}


def read(ctx):
    if "informer_s" not in ctx.delta or not ctx.bound:
        return None
    return ctx.delta["informer_s"] / ctx.bound * 1e6
