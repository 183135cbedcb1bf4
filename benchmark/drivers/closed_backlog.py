"""Closed loop with a standing backlog: the saturated cells.

One iteration is what a busy fleet's scheduler loop sees between two calls:
completions (the oldest bound pods are deleted down to the resident count),
creations (the pending queue is topped up to `backlog` pods in chunks of
`create_chunk`, the repo's create_batch), then one
`Scheduler.schedule_pending(wait=False)`, the call `python -m
kubernetes_tpu` loops on. The window is a whole number of iterations, so a
rate is taken over all the work and all the time in it.

Traffic parameters (the traffic file): `backlog`, `create_chunk`,
`warmup_iterations` and `warmup_drains` (at least this many before the
compile check),
`warmup_max_s`. The seed changes nothing here: every pod of a template is
identical, so each seed runs the same work.
"""

from __future__ import annotations

import time

from jax.profiler import TraceAnnotation


class Driver:
    def __init__(self, system, cfg: dict, traffic: dict, seed: int):
        self.sys = system
        self.cfg = cfg
        self.backlog = int(traffic["backlog"])
        self.chunk = int(traffic["create_chunk"])
        self.warmup_iterations = int(traffic["warmup_iterations"])
        self.warmup_max_s = float(traffic["warmup_max_s"])
        self.resident = int(cfg["resident_pods"])
        self.window_seqs: range = range(0)
        self.iterations = 0

    def _iteration(self) -> None:
        sys_, sched, api = self.sys, self.sys.sched, self.sys.api
        with TraceAnnotation("complete"):
            sys_.complete(self.resident)
        with TraceAnnotation("create"):
            want = self.backlog - len(sched.queue)
            while want > 0:
                n = min(self.chunk, want)
                api.create_pods(sys_.new_pods(n))
                want -= n
        with TraceAnnotation("schedule_pending"):
            sched.schedule_pending(wait=False)
        self.iterations += 1

    def warmup(self, warm) -> int:
        """Run the traffic unmeasured until an iteration compiles nothing."""
        t_end = time.perf_counter() + self.warmup_max_s
        done = 0
        while True:
            warm.mark()
            self._iteration()
            done += 1
            if done >= self.warmup_iterations and warm.settled():
                return done
            if time.perf_counter() > t_end:
                return done

    def window(self, seconds: float, tick) -> tuple[float, float]:
        first = self.sys.created
        t_open = time.perf_counter()
        t_stop = t_open + seconds
        while True:
            now = time.perf_counter()
            tick(now)
            if now >= t_stop:
                break
            self._iteration()
        self.window_seqs = range(first, self.sys.created)
        return t_open, now

    def finish(self, grace_s: float) -> None:
        """No new load: everything created must bind within the grace."""
        sched = self.sys.sched
        deadline = time.perf_counter() + grace_s
        while time.perf_counter() < deadline:
            sched.flush_queues()
            sched.schedule_pending()
            if not len(sched.queue):
                break
            time.sleep(0.05)
        sched.wait_pending()

    def due(self) -> range:
        """Pods whose answer is due: those created in the window."""
        return self.window_seqs

    def close(self) -> None:
        pass
