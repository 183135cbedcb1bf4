"""audit_replays_per_1000_drains: shadow-audit records replayed in the
window per 1000 drains the scheduler dispatched in it. The audit samples
one drain in 64, 15.6 per 1000; a reading below that is an audit falling
behind its sampler. Read from scheduler_shadow_audit_replays_total{where}
and scheduler_device_batch_size; a program without the first gives
nothing, and so does a window without a drain."""


def probe(sched):
    m = sched.metrics
    c = getattr(m, "shadow_audit_replays", None)
    if c is None:
        return {}
    return {"audit_replayed": c.value("process") + c.value("thread"),
            "audit_drains": float(m.device_batch_size.count())}


def read(ctx):
    if "audit_replayed" not in ctx.delta or not ctx.delta["audit_drains"]:
        return None
    return 1000.0 * ctx.delta["audit_replayed"] / ctx.delta["audit_drains"]
