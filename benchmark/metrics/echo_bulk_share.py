"""echo_bulk_share: of the pods whose bind echo the scheduler confirmed
in the window, the share confirmed by the one-pass bulk path, in %.
Read from scheduler_bind_echo_pods_total{path}; a program without that
family gives nothing."""


def probe(sched):
    c = getattr(sched.metrics, "bind_echo_pods", None)
    if c is None:
        return {}
    return {"echo_bulk": c.value("bulk"), "echo_per_pod": c.value("per_pod")}


def read(ctx):
    if "echo_bulk" not in ctx.delta:
        return None
    total = ctx.delta["echo_bulk"] + ctx.delta["echo_per_pod"]
    if not total:
        return None
    return 100.0 * ctx.delta["echo_bulk"] / total
