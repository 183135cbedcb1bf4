"""bookkeeping_us_per_pod: self time of the drain telemetry after each
commit (phase bookkeeping) and of the shadow audit's work on the loop
thread (phase audit: quiesce, capture, device copy) over the window, per
pod bound in it. Read from scheduler_phase_self_seconds_total; a program
without that family gives nothing."""


def probe(sched):
    c = getattr(sched.metrics, "phase_self_seconds", None)
    if c is None:
        return {}
    return {"bookkeeping_s": c.value("bookkeeping") + c.value("audit")}


def read(ctx):
    if "bookkeeping_s" not in ctx.delta or not ctx.bound:
        return None
    return ctx.delta["bookkeeping_s"] / ctx.bound * 1e6
