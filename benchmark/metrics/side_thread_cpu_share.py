"""side_thread_cpu_share: CPU seconds of the scheduler's background
threads (shadow-audit replays, host-profiler samples) over the window,
as a share of the window's seconds. Read from
scheduler_thread_cpu_seconds_total; a program without that family gives
nothing."""

THREADS = ("audit", "host_profiler")


def probe(sched):
    c = getattr(sched.metrics, "thread_cpu_seconds", None)
    if c is None:
        return {}
    return {"side_thread_cpu_s": sum(c.value(t) for t in THREADS)}


def read(ctx):
    if "side_thread_cpu_s" not in ctx.delta or ctx.window_s <= 0:
        return None
    return 100.0 * ctx.delta["side_thread_cpu_s"] / ctx.window_s
