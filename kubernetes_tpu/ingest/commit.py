"""Batched assume/bind: the columnar commit edge of a drain.

The scheduler's commit used to classify pods one by one
(`_needs_per_pod_hooks` re-deriving profile facts per pod) and
`_fast_commit` then re-walked every pod's object graph inside
`NodeInfo.add_pod` (affinity property chains, request-dict walks, a
container walk for ports) — per pod, per drain, on the throughput-
bounding path.

`CommitEngine.commit` replaces both with one pass driven by the
columnar pod store's commit facts (state/batch.py `row_facts`, one
`CommitFacts` per signature row): the cache assume inlines to the
minimum mutation set with every signature-level fact hoisted, the bind
enqueue is the existing bulk dispatcher extend, and the event /
flight-recorder feeds stay format-free (object refs + node names only).
Behavior is bit-for-bit the serial path's — tests/test_ingest.py proves
cache, dispatcher-queue and event parity against `_fast_commit` /
`_assume_and_bind`, and the `ColumnarIngest` gate (off) restores the
serial path outright.
"""

from __future__ import annotations

from ..backend.cache import _PodState
from ..framework.types import next_generation
from ..metrics import SCHEDULED
from ..obs.journey import EV_ASSIGN


class CommitEngine:
    """Owned by one Scheduler; stateless between drains except the
    per-profile hook-fact memo."""

    def __init__(self, sched):
        self.sched = sched
        # profile name → (always_hooks, has_rp, has_pb); mirrors
        # _needs_per_pod_hooks — the gates must stay in lockstep
        self._profile_facts: dict = {}

    def _hooks(self, profile) -> tuple:
        facts = self._profile_facts.get(profile.name)
        if facts is None:
            fwk = profile.framework
            has_rp = bool(fwk.reserve_plugins or fwk.permit_plugins)
            has_pb = bool(fwk.pre_bind_plugins)
            always = ((has_rp and not profile.gang_only_hooks)
                      or (has_pb and not profile.volume_only_pre_bind))
            facts = (always, has_rp, has_pb)
            self._profile_facts[profile.name] = facts
        return facts

    def commit(self, pd, out, names, gang_fast: bool) -> tuple:
        """One pass over a resolved drain: hook-free pods take the
        columnar assume + bulk bind enqueue; hook pods route through
        `_assume_and_bind` in drain order (same relative order as the
        serial path: hook binds inline, fast binds batched at the end).
        Returns (bound, failures)."""
        sched = self.sched
        profile = pd.profile
        qpis = pd.qpis
        n = pd.n
        always_hooks, has_rp, has_pb = self._hooks(profile)
        cache = sched.cache
        pod_states = cache.pod_states
        nodes_get = cache.nodes.get
        get_or_create = cache._get_or_create
        move_to_head = cache._move_to_head
        assumed_set = cache.assumed_pods
        ttl = cache.ttl
        queue = sched.queue
        nominated = queue.nominator.nominated_pods
        nominator_delete = queue.nominator.delete
        in_flight = queue.in_flight_pods
        in_flight_pop = in_flight.pop
        now = sched.clock()
        facts_list = pd.facts
        n_facts = len(facts_list) if facts_list is not None else 0
        tidx = pd.batch.tidx[:n].tolist() if pd.batch is not None else None
        out_list = out.tolist()
        bound = 0
        failures: list = []
        bound_pods: list = []
        event_refs: list = []
        sli_by_attempts: dict = {}
        for i in range(n):
            a = out_list[i]
            qpi = qpis[i]
            if a < 0:
                failures.append(qpi)
                continue
            pod = qpi.pod
            spec = pod.spec
            if not gang_fast and (
                    always_hooks
                    or (spec.workload_ref and has_rp)
                    or ((spec.volumes or spec.resource_claims)
                        and (has_rp or has_pb))):
                # full reserve/permit/pre-bind chain, in drain order
                sched._assume_and_bind(qpi, names[a])
                bound += 1
                continue
            uid = pod.metadata.uid
            if uid in pod_states:
                in_flight_pop(uid, None)
                continue
            node_name = names[a]
            assumed = pod.with_node_name(node_name)
            # the queue entry's PodInfo becomes the cache's: rebinding its
            # pod to the assumed copy saves an allocation per commit, and
            # nothing reads the entry after the drain resolves
            pi = qpi.pod_info
            pi.pod = assumed
            qpi.pod = assumed   # keep the slot in sync with pod_info
            row = None
            if tidx is not None and tidx[i] < n_facts:
                row = (facts_list, tidx[i])
                f = facts_list[tidx[i]]
            else:  # row minted outside the batch (defensive): derive
                from .columns import commit_facts_for_row
                f = commit_facts_for_row(pod)
            # -- columnar cache assume (NodeInfo.add_pod inlined over the
            # signature facts; field-for-field the serial mutation set) --
            item = nodes_get(node_name)
            if item is None:
                item = get_or_create(node_name)
            info = item.info
            info.pods.append(pi)
            if f.has_affinity:
                info.pods_with_affinity.append(pi)
            if f.has_anti_affinity:
                info.pods_with_required_anti_affinity.append(pi)
            req = info.requested
            req_get = req.get
            for k, v in f.req_items:
                req[k] = req_get(k, 0) + v
            info.non_zero_cpu += f.cpu_nz
            info.non_zero_mem += f.mem_nz
            if f.has_ports:
                info._update_ports(assumed, add=True)
            info.generation = next_generation()
            move_to_head(item)
            st = _PodState(pod=assumed, assumed=True, binding_finished=True,
                           row=row)
            if ttl > 0:
                st.deadline = now + ttl
            pod_states[uid] = st
            assumed_set.add(uid)
            if nominated:
                nominator_delete(pod)
            in_flight_pop(uid, None)
            bound_pods.append((assumed, pod))
            event_refs.append((uid, node_name))
            attempts = qpi.attempts or 1
            slis = sli_by_attempts.get(attempts)
            if slis is None:
                slis = sli_by_attempts[attempts] = []
            slis.append(
                now - (qpi.initial_attempt_timestamp or qpi.timestamp))
            if qpi.unschedulable_plugins:
                qpi.unschedulable_plugins = set()
            qpi.consecutive_errors_count = 0
        if not in_flight:
            queue.in_flight_events.clear()
        nb = len(bound_pods)
        if nb:
            sched.journey.record_bulk(
                [uid for uid, _node in event_refs], EV_ASSIGN, now,
                detail=[node for _uid, node in event_refs])
            sched.dispatcher.add_binds(bound_pods)
            sched.events.scheduled_bulk(event_refs, now=now)
            sched.scheduled_count += nb
            sched.metrics.schedule_attempts.inc(SCHEDULED, profile.name,
                                                by=nb)
            for attempts, values in sli_by_attempts.items():
                sched.metrics.sli_duration.observe_array(values,
                                                         str(attempts))
        return bound + nb, failures
