"""wavescan_share: of the pods the scheduler's placement tiers resolved in
the window, the share resolved by the plan program (run_plan), in %. Read
from scheduler_placement_pods_total{tier}; a program without that family
gives nothing."""


def probe(sched):
    c = getattr(sched.metrics, "placement_pods", None)
    if c is None:
        return {}
    from kubernetes_tpu.metrics import PLACEMENT_TIERS
    return {"placed_" + t: c.value(t) for t in PLACEMENT_TIERS}


def read(ctx):
    if "placed_wavescan" not in ctx.delta:
        return None
    total = sum(v for k, v in ctx.delta.items() if k.startswith("placed_"))
    if not total:
        return None
    return 100.0 * ctx.delta["placed_wavescan"] / total
