"""plan_pad_share: of the plan program's scan steps in the window, the
share that ran on padding (a span's pow2 bucket beyond its pods), in %.
Read from scheduler_plan_scan_steps_total{slot}; a program without that
family, or a window in which the plan program took no step, gives
nothing."""


def probe(sched):
    c = getattr(sched.metrics, "plan_scan_steps", None)
    if c is None:
        return {}
    return {"plan_valid": c.value("valid"), "plan_pad": c.value("pad")}


def read(ctx):
    if "plan_pad" not in ctx.delta:
        return None
    total = ctx.delta["plan_valid"] + ctx.delta["plan_pad"]
    if not total:
        return None
    return 100.0 * ctx.delta["plan_pad"] / total
