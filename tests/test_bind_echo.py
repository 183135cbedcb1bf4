"""The bind echo's one-pass bulk confirm while later drains are in flight.

`Scheduler._update_pods` confirms a `bind_all` batch of pods it assumed in
one pass. While another drain is in flight, the per-pod informer path
would append one AssignedPodAdd entry per pod to the queue's in-flight
event log (`move_all_to_active_or_backoff_queue`), and a pod of that
drain rejected later replays the log against its rejecting plugins'
queueing hints. The bulk pass writes the same entries itself. These
tests run one pod stream twice — the echo confirmed by the bulk pass,
then by `_on_pod_update` per pod — and compare the state they reach,
including the requeue-or-park decision of an in-flight pod rejected
after the echo. They also check the counter that says which path
confirmed each echo.
"""

import time

import pytest

from kubernetes_tpu.backend.apiserver import APIServer
from kubernetes_tpu.metrics import BIND_ECHO_PATHS
from kubernetes_tpu.scheduler import Scheduler, _PendingDrain
from kubernetes_tpu.testing.wrappers import make_node, make_pod

ZONE = "topology.kubernetes.io/zone"
HOSTNAME = "kubernetes.io/hostname"
T0 = 1000.0

# case → (labels of the first drain's pods, expected queue of the pod the
# second drain rejects: "backoff" when a logged AssignedPodAdd matches a
# rejecting plugin's hint, "unschedulable" when none does)
CASES = {
    # DoNotSchedule zone spread: zone b's nodes are tainted, so placing in
    # zone a breaks maxSkew; the first drain's app=web pods match its
    # selector (PodTopologySpread hint: Queue)
    "spread_requeue": ({"app": "web"}, "backoff"),
    # the same, with a foreign pod's bind in the middle of the echo batch
    "spread_requeue_foreign_mid_batch": ({"app": "web"}, "backoff"),
    # required hostname anti-affinity against app=db, a db pod resident on
    # every node: the first drain's plain pods match no term (Skip)
    "anti_affinity_park": ({}, "unschedulable"),
    # the same, the first drain's pods labelled app=db (InterPodAffinity
    # hint: Queue)
    "anti_affinity_requeue": ({"app": "db"}, "backoff"),
}


def _cluster(api: APIServer, case: str) -> None:
    if case.startswith("spread"):
        for i in range(2):
            api.create_node(make_node(f"a{i}").capacity(
                {"cpu": 8, "memory": "16Gi", "pods": 110}).zone("za").label(
                HOSTNAME, f"a{i}").obj())
            api.create_node(make_node(f"b{i}").capacity(
                {"cpu": 8, "memory": "16Gi", "pods": 110}).zone("zb").label(
                HOSTNAME, f"b{i}").taint("dedicated", "x").obj())
        for i in range(2):
            api.create_pod(make_pod(f"web-res-{i}").req({"cpu": "100m"})
                           .label("app", "web").node("a0").obj())
    else:
        for i in range(4):
            api.create_node(make_node(f"n{i}").capacity(
                {"cpu": 8, "memory": "16Gi", "pods": 110}).zone(
                f"z{i % 2}").label(HOSTNAME, f"n{i}").obj())
            api.create_pod(make_pod(f"db-res-{i}").req({"cpu": "100m"})
                           .label("app", "db").node(f"n{i}").obj())
    # never this scheduler's: its bind is foreign to the echo
    api.create_pod(make_pod("foreign").req({"cpu": "100m"})
                   .scheduler_name("other-scheduler").obj())


def _rejected_pod(case: str):
    w = make_pod("victim").req({"cpu": "100m"})
    if case.startswith("spread"):
        return w.label("app", "web").spread_constraint(
            1, ZONE, "DoNotSchedule", {"app": "web"}).obj()
    return w.label("app", "db").pod_affinity(
        HOSTNAME, {"app": "db"}, anti=True).obj()


def _commit_head(sched: Scheduler) -> None:
    """Commit the oldest in-flight drain once its results have landed."""
    deadline = time.monotonic() + 120
    while not sched.commit_ready(limit=1):
        assert time.monotonic() < deadline, "drain never landed"
        time.sleep(0.001)


def _inject_foreign(api: APIServer) -> None:
    """The next bind_all carries the foreign pod's bind in the middle of
    the batch, once."""
    real = api.bind_all

    def bind_all(pairs, fence_token=None):
        api.bind_all = real
        stored = api.pods["default/foreign"]
        mid = len(pairs) // 2
        pairs = (pairs[:mid] + [(stored.with_node_name("a1"), stored)]
                 + pairs[mid:])
        return real(pairs, fence_token)
    api.bind_all = bind_all


def _echo_counts(sched: Scheduler) -> dict:
    return {p: sched.metrics.bind_echo_pods.value(p) for p in BIND_ECHO_PATHS}


def _uids(qpis) -> list:
    return sorted(q.pod.uid for q in qpis)


def _run(case: str, per_pod: bool) -> dict:
    labels, _ = CASES[case]
    api = APIServer()
    now = [T0]
    sched = Scheduler(api, batch_size=16, clock=lambda: now[0])
    if per_pod:
        # the informer handler for every pair, as before the bulk pass
        # took in-flight batches
        sched._update_pods = lambda pairs: [
            sched._on_pod_update(old, new) for old, new in pairs]
    _cluster(api, case)
    sched.prime()

    first = []
    for i in range(6):
        w = make_pod(f"first-{i}").req({"cpu": "100m"})
        for k, v in labels.items():
            w = w.label(k, v)
        first.append(w.obj())
    api.create_pods(first)
    assert sched.dispatch_once() == 6
    api.create_pods([_rejected_pod(case)] + [
        make_pod(f"second-{i}").req({"cpu": "100m"}).obj()
        for i in range(2)])
    assert sched.dispatch_once() == 3
    q = sched.queue
    second = {"default/victim", "default/second-0", "default/second-1"}
    assert set(q.in_flight_pods) >= second

    # the first drain commits (a drain with a spread or anti-affinity pod
    # commits its predecessor before dispatching) and its binds flush
    # while the second is in flight: the echo arrives with pods in flight
    while set(q.in_flight_pods) - second:
        _commit_head(sched)
    in_flight = set(q.in_flight_pods)
    if case.endswith("foreign_mid_batch"):
        _inject_foreign(api)
    sched.dispatcher.flush()
    assert set(q.in_flight_pods) >= {"default/victim"}
    log = [(e.seq, e.event, e.old_obj.uid, e.new_obj.spec.node_name)
           for e in q.in_flight_events]
    echo_in_flight = _echo_counts(sched)

    # the second drain commits: the victim is rejected and replays the log
    _commit_head(sched)
    sched.dispatcher.flush()
    assert not q.in_flight_pods and not q.in_flight_events

    dump = sched.cache.dump()
    buckets = sched.timeline.series(seconds=900)["buckets"]
    return {
        "in_flight_at_echo": sorted(in_flight),
        "in_flight_events": log,
        "active": _uids(q.active_q.items()),
        "backoff": _uids(q.backoff_q.items()),
        "unschedulable": sorted(q.unschedulable_pods),
        # NodeInfo generations are a process-global monotonic counter
        "cache_nodes": {n: {k: v for k, v in d.items() if k != "generation"}
                        for n, d in dump["nodes"].items()},
        "assumed": dump["assumed_pods"],
        "pod_count": dump["pod_count"],
        "assignments": {uid: p.spec.node_name for uid, p in api.pods.items()},
        "bind_confirm": sched.metrics.journey_transitions.value(
            "bind_confirm"),
        "timeline_binds": sum(b.get("binds", 0) for b in buckets),
        "echo_in_flight": echo_in_flight,
        "echo": _echo_counts(sched),
    }


class TestInFlightEchoParity:
    @pytest.mark.parametrize("case", list(CASES))
    def test_bulk_matches_per_pod(self, case):
        bulk = _run(case, per_pod=False)
        per_pod = _run(case, per_pod=True)
        foreign = case.endswith("foreign_mid_batch")
        _, where = CASES[case]
        # the first drain's echo, with the second drain in flight, took
        # the bulk pass but for the foreign pod; the second drain's echo
        # goes per pod while the victim is parked
        assert bulk["echo_in_flight"] == {"bulk": 6, "per_pod": foreign}
        assert bulk["echo"] == (
            {"bulk": 8, "per_pod": foreign} if where == "backoff"
            else {"bulk": 6, "per_pod": 2 + foreign})
        assert per_pod["echo"] == {"bulk": 0, "per_pod": 8 + foreign}
        for run in (bulk, per_pod):
            del run["echo_in_flight"], run["echo"]
        assert bulk == per_pod
        # one AssignedPodAdd per echoed pod, the foreign one in pair order
        log = bulk["in_flight_events"]
        assert len(log) == 6 + foreign
        assert [s for s, *_ in log] == sorted(s for s, *_ in log)
        if foreign:
            assert log[3][2] == "default/foreign"
        # the rejected in-flight pod was requeued or parked by the log
        assert "default/victim" in bulk[where]
        assert bulk["bind_confirm"] == bulk["timeline_binds"] == 8 + foreign


class TestBindEchoCounter:
    def test_series_seeded_at_zero(self):
        api = APIServer()
        sched = Scheduler(api, batch_size=16)
        text = sched.metrics.exposition()
        for path in BIND_ECHO_PATHS:
            assert (f'scheduler_bind_echo_pods_total{{path="{path}"}} 0'
                    in text)
        assert set(BIND_ECHO_PATHS) == {"bulk", "per_pod"}

    def test_closed_loop_echo_counts_bulk(self, monkeypatch):
        """schedule_pending(wait=False) flushes a drain's binds with the
        next drain in flight; the echo still takes the bulk pass."""
        api = APIServer()
        sched = Scheduler(api, batch_size=8)
        for i in range(4):
            api.create_node(make_node(f"n{i}").capacity(
                {"cpu": 8, "memory": "16Gi", "pods": 110}).obj())
        sched.prime()
        # a device slower than the loop, as on the chip: the newest drain
        # is still executing whenever the loop looks
        monkeypatch.setattr(_PendingDrain, "ready",
                            lambda pd: pd is not sched._pending[-1])
        seen_in_flight = []
        real = sched._update_pods

        def spy(pairs):
            seen_in_flight.append(bool(sched.queue.in_flight_pods))
            real(pairs)
        sched._update_pods = spy
        for c in range(6):
            api.create_pods([make_pod(f"p{c}-{i}").req({"cpu": "100m"}).obj()
                             for i in range(8)])
            sched.schedule_pending(wait=False)
        sched.schedule_pending()
        assert api.binding_count == 48
        # every flush of the loop but the final barrier's
        assert seen_in_flight == [True] * 5 + [False]
        m = sched.metrics.bind_echo_pods
        assert m.value("bulk") == 48 and m.value("per_pod") == 0

    def test_foreign_and_unschedulable_count_per_pod(self):
        api = APIServer()
        sched = Scheduler(api, batch_size=8)
        api.create_node(make_node("n0").capacity(
            {"cpu": 4, "memory": "8Gi", "pods": 110}).obj())
        sched.prime()
        # a foreign bind: not assumed by this scheduler
        api.create_pod(make_pod("foreign").req({"cpu": "100m"})
                       .scheduler_name("other-scheduler").obj())
        api.bind(api.pods["default/foreign"], "n0")
        m = sched.metrics.bind_echo_pods
        assert (m.value("bulk"), m.value("per_pod")) == (0, 1)
        # an echo while a pod is unschedulable
        api.create_pod(make_pod("huge").req({"cpu": "64"}).obj())
        sched.schedule_pending()
        assert "default/huge" in sched.queue.unschedulable_pods
        api.create_pods([make_pod(f"p{i}").req({"cpu": "100m"}).obj()
                         for i in range(3)])
        sched.schedule_pending()
        assert api.binding_count == 4
        assert (m.value("bulk"), m.value("per_pod")) == (0, 4)
