"""delete_delta_share: of the bound pods deleted in the window, the share
the scheduler folded out of its resident device carry instead of
reseeding the carry, in %. Read from
scheduler_pod_delete_carry_total{path}; a program without that family, or
a window with no delete of a bound pod, gives nothing."""


def probe(sched):
    c = getattr(sched.metrics, "pod_delete_carry", None)
    if c is None:
        return {}
    return {"delete_delta": c.value("delta"),
            "delete_reseed": c.value("reseed")}


def read(ctx):
    if "delete_delta" not in ctx.delta:
        return None
    total = ctx.delta["delete_delta"] + ctx.delta["delete_reseed"]
    if not total:
        return None
    return 100.0 * ctx.delta["delete_delta"] / total
