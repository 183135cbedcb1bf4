"""The system under test, built the way `python -m kubernetes_tpu` builds it,
and the client side the benchmark drives it through.

`System` holds the in-memory API server, the scheduler with its default
configuration (`KubeSchedulerConfiguration()`: batchSize 512, shadow audit
at 1/64) and the benchmark's own pod watch, which stamps every bind on the
host clock as the watch sees it. Nodes and pods come from the builder of
the configuration's kind (`kinds/<kind>/build.py`).
"""

from __future__ import annotations

import time
from collections import deque

import numpy as np


class BindWatch:
    """A pod watch handler (`APIServer.watch_pods`): the client's view of
    every bind, stamped with `time.perf_counter()` when the watch sees it.
    Handlers run on whichever thread issues the bind; list appends and dict
    stores are atomic under the interpreter lock."""

    def __init__(self):
        self.node: dict[str, str] = {}
        self.at: dict[str, float] = {}
        self.order: deque = deque()       # uids in bind order, for completion
        self.conflicts = 0

    def _bound(self, old, new, now: float) -> None:
        if not new.spec.node_name or old.spec.node_name:
            return
        uid = new.metadata.uid
        if uid in self.node:
            self.conflicts += 1
            return
        self.node[uid] = new.spec.node_name
        self.at[uid] = now
        self.order.append(uid)

    def on_update(self, old, new) -> None:
        self._bound(old, new, time.perf_counter())

    def on_update_bulk(self, pairs) -> None:
        now = time.perf_counter()
        for old, new in pairs:
            self._bound(old, new, now)

    def handlers(self):
        from kubernetes_tpu.backend.apiserver import WatchHandlers
        return WatchHandlers(on_update=self.on_update,
                             on_update_bulk=self.on_update_bulk)


class System:
    """API server + scheduler + the benchmark's watch, and the ledger of
    what the client did: pods created in order, completions issued."""

    def __init__(self, cfg: dict, builder):
        from kubernetes_tpu.backend.apiserver import APIServer
        from kubernetes_tpu.scheduler import Scheduler
        from kubernetes_tpu.utils.tracing import Tracer

        self.cfg = cfg
        self.api = APIServer()
        # as kubernetes_tpu/__main__.py builds it (no --config: defaults)
        self.sched = Scheduler(self.api, config=None,
                               tracer=Tracer(slow_threshold_s=1.0))
        self.watch = BindWatch()
        self.api.watch_pods(self.watch.handlers())
        self.builder = builder
        self.node_index: dict[str, int] = {}
        self.created = 0
        self.deletes: list[tuple[int, int]] = []
        self.completed = 0

    def create_nodes(self) -> None:
        for i, node in enumerate(self.builder.nodes()):
            self.node_index[node.metadata.name] = i
            self.api.create_node(node)
        # informer sync before serving (app/server.go WaitForCacheSync)
        self.sched.prime()

    def new_pods(self, n: int) -> list:
        make = self.builder.pod
        base = self.created
        pods = [make(base + i) for i in range(n)]
        self.created += n
        return pods

    def bound_now(self) -> int:
        return len(self.watch.node) - self.completed

    def complete(self, target: int) -> int:
        """Delete the oldest bound pods until at most `target` remain bound.
        The caller holds whatever lock guards the scheduler's host state.
        Each delete records how many pods the scheduler had already taken
        from its queue: the reference applies the delete after them."""
        excess = self.bound_now() - target
        if excess <= 0:
            return 0
        decided = self.created - len(self.sched.queue)
        order = self.watch.order
        delete = self.api.delete_pod
        for _ in range(excess):
            uid = order.popleft()
            delete(uid)
            self.deletes.append((decided, int(uid.rsplit("-", 1)[1])))
        self.completed += excess
        return excess

    def record(self, due_seqs) -> "Record":
        node_of = np.full(self.created, -1, dtype=np.int64)
        for uid, node in self.watch.node.items():
            seq = int(uid.rsplit("-", 1)[1])
            node_of[seq] = self.node_index[node]
        conflicts = self.watch.conflicts
        # what the API server holds must be what the watch saw bound
        completed = {s for _, s in self.deletes}
        for uid, pod in self.api.pods.items():
            seq = int(uid.rsplit("-", 1)[1])
            seen = self.watch.node.get(uid, "")
            if (pod.spec.node_name or "") != seen and seq not in completed:
                conflicts += 1
        return Record(self.created, node_of, list(self.deletes),
                      list(due_seqs), conflicts)


class Record:
    def __init__(self, created, node_of, deletes, due, bind_conflicts):
        self.created = created
        self.node_of = node_of
        self.deletes = deletes
        self.due = due
        self.bind_conflicts = bind_conflicts
