"""audit_offload_share: of the shadow-audit records replayed in the window,
the share replayed in the audit's worker process, off the scheduler's
interpreter lock, in %. Read from scheduler_shadow_audit_replays_total
{where}; a program without that family gives nothing, and so does a
window that replayed no record."""


def probe(sched):
    c = getattr(sched.metrics, "shadow_audit_replays", None)
    if c is None:
        return {}
    return {"audit_replays_process": c.value("process"),
            "audit_replays_thread": c.value("thread")}


def read(ctx):
    if "audit_replays_process" not in ctx.delta:
        return None
    total = (ctx.delta["audit_replays_process"]
             + ctx.delta["audit_replays_thread"])
    if not total:
        return None
    return 100.0 * ctx.delta["audit_replays_process"] / total
