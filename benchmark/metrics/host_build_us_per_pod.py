"""host_build_us_per_pod: the scheduler's host_build drain phase (batch
build, snapshot, group seeding: queue/ and ingest/) over the window, per
pod bound in it. Read from scheduler_drain_phase_seconds{phase=host_build}."""


def probe(sched):
    return {"host_build_s": sched.metrics.drain_phase.sum("host_build")}


def read(ctx):
    if not ctx.bound:
        return None
    return ctx.delta["host_build_s"] / ctx.bound * 1e6
