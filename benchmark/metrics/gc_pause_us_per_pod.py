"""gc_pause_us_per_pod: seconds the Python garbage collector ran (all
generations, any thread) over the window, per pod bound in it. Read from
scheduler_gc_pause_seconds_total; a program without that family gives
nothing."""

GENERATIONS = ("0", "1", "2")


def probe(sched):
    m = sched.metrics
    if not hasattr(m, "gc_pause_seconds"):
        return {}
    m.sync_gc_pauses()
    return {"gc_pause_s": sum(m.gc_pause_seconds.value(g)
                              for g in GENERATIONS)}


def read(ctx):
    if "gc_pause_s" not in ctx.delta or not ctx.bound:
        return None
    return ctx.delta["gc_pause_s"] / ctx.bound * 1e6
