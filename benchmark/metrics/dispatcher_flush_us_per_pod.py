"""dispatcher_flush_us_per_pod: self time of the dispatcher_flush phase
(the API bind) and of pod_update (the watch echo of the binds, nested in
it) over the window, per pod bound in it. Read from
scheduler_phase_self_seconds_total; a program without that family gives
nothing."""


def probe(sched):
    c = getattr(sched.metrics, "phase_self_seconds", None)
    if c is None:
        return {}
    return {"flush_s": c.value("dispatcher_flush") + c.value("pod_update")}


def read(ctx):
    if "flush_s" not in ctx.delta or not ctx.bound:
        return None
    return ctx.delta["flush_s"] / ctx.bound * 1e6
