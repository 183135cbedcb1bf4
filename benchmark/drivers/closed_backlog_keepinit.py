"""Closed loop with a standing backlog whose init pods stay bound: the
`closed_backlog` driver (closed_backlog.py beside this file) with two
changes.

Completion: each iteration deletes the oldest bound *measure* pods until
`resident_pods` are bound; the configuration's first `init_pods` pods are
never deleted, as upstream scheduler_perf never deletes its initPods. Each
delete is recorded in `System.deletes` as `(decided, seq)`, as
`System.complete` records it.

Creation: the backlog counts every pod created and not yet bound, queued
or in flight, where `closed_backlog` counts the queue alone. A deployment
whose pods each need a node of their own then holds at most
`resident_pods + backlog` pods at any decision, whatever the pipeline
holds in flight.

Traffic parameters are `closed_backlog`'s. At the end of the run the
scheduler's unschedulable attempts are printed to standard error: a pod
that finds no node is retried out of creation order, which the
reference's replay does not follow, so a sound run of such a deployment
reads 0 there.

A pod placed by the scheduler's host path (`Scheduler.host_scheduled`)
ends the run with an error after the loop iteration that placed it: the
cell measures the device path, and a program whose device path cannot
run the deployment (a kernel the chip refuses degrades every drain to
the host oracle, about a minute a drain at this size) fails at once
instead of crawling through its warm-up.
"""

from __future__ import annotations

import sys

from jax.profiler import TraceAnnotation

from benchmark.drivers import closed_backlog


class Driver(closed_backlog.Driver):
    def __init__(self, system, cfg: dict, traffic: dict, seed: int):
        super().__init__(system, cfg, traffic, seed)
        self.init_pods = int(cfg["init_pods"])

    def _iteration(self) -> None:
        sys_, sched, api = self.sys, self.sys.sched, self.sys.api
        with TraceAnnotation("complete"):
            self.complete(self.resident)
        with TraceAnnotation("create"):
            want = self.backlog - (sys_.created - len(sys_.watch.node))
            while want > 0:
                n = min(self.chunk, want)
                api.create_pods(sys_.new_pods(n))
                want -= n
        with TraceAnnotation("schedule_pending"):
            sched.schedule_pending(wait=False)
        self.iterations += 1
        host = sched.host_scheduled
        if host:
            raise RuntimeError(
                f"{host} pods were placed by the host path: the device "
                "path did not run this deployment")

    def complete(self, target: int) -> int:
        """Delete the oldest bound measure pods until at most `target`
        pods remain bound; init pods leave the bind order undeleted."""
        sys_ = self.sys
        excess = sys_.bound_now() - target
        if excess <= 0:
            return 0
        decided = sys_.created - len(sys_.sched.queue)
        order = sys_.watch.order
        delete = sys_.api.delete_pod
        done = 0
        while done < excess:
            uid = order.popleft()
            seq = int(uid.rsplit("-", 1)[1])
            if seq < self.init_pods:
                continue
            delete(uid)
            sys_.deletes.append((decided, seq))
            done += 1
        sys_.completed += done
        return done

    def finish(self, grace_s: float) -> None:
        super().finish(grace_s)
        from kubernetes_tpu.metrics import UNSCHEDULABLE
        sched = self.sys.sched
        n = sum(sched.metrics.schedule_attempts.value(UNSCHEDULABLE, prof)
                for prof in sched.profiles)
        print(f"unschedulable_attempts={n:g}", file=sys.stderr)
