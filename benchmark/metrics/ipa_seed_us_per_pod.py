"""ipa_seed_us_per_pod: self time of the ipa_seed phase (the
inter-pod-affinity half of group count seeding, nested in host_group_seed)
over the window, per pod bound in it. Read from
scheduler_phase_self_seconds_total; a program without that phase gives
nothing."""


def probe(sched):
    c = getattr(sched.metrics, "phase_self_seconds", None)
    if c is None:
        return {}
    from kubernetes_tpu.metrics import LOOP_PHASES
    if "ipa_seed" not in LOOP_PHASES:
        return {}
    return {"ipa_seed_s": c.value("ipa_seed")}


def read(ctx):
    if "ipa_seed_s" not in ctx.delta or not ctx.bound:
        return None
    return ctx.delta["ipa_seed_s"] / ctx.bound * 1e6
