"""The shadow audit's replay in its worker process (kubernetes_tpu/obs/replay.py).

The worker replays the pickled payload of a captured record through a
framework rebuilt from the default plugins; the audit thread replays the
same payload through the scheduler's live framework. Both must reach the
same verdict on every kind of record. A record goes to the process unless
the audit is synchronous, the profile's replayed plugins are not the
defaults, or a pod carries volumes or resource claims; each replayed
record is counted once under scheduler_shadow_audit_replays_total{where}.
Records of the thread route replay one at a time, on one thread; those of
the process route get a courier thread and worker each while they are
outstanding, within a cap shared by the audits of one process. The worker
runs on the CPU alone, and a worker that dies costs one record (outcome
`error`), not the audit.
"""

import pickle
import threading
import time
import weakref

import pytest

from benchmark.kinds.pod_anti_affinity import reference as anti_ref
from benchmark.kinds.pod_anti_affinity.build import Builder as AntiBuilder
from benchmark.system import System
from kubernetes_tpu.api.types import Volume
from kubernetes_tpu.backend.apiserver import APIServer
from kubernetes_tpu.framework.runtime import Framework
from kubernetes_tpu.obs import audit as audit_mod
from kubernetes_tpu.obs.audit import AuditRecord
from kubernetes_tpu.obs.replay import (ReplayWorker, replay, replay_plugins,
                                       verdict)
from kubernetes_tpu.ops.program import ScoreConfig
from kubernetes_tpu.plugins.noderesources import Fit, FitArgs
from kubernetes_tpu.scheduler import (DEFAULT_WEIGHTS, Profile, Scheduler,
                                      default_plugins)
from kubernetes_tpu.testing.wrappers import make_node, make_pod

REQ = {"cpu": "500m", "memory": "1Gi"}


def _scheduler(sync: bool, profile=None):
    api = APIServer()
    kw = {"profiles": [profile(api)]} if profile is not None else {}
    sched = Scheduler(api, batch_size=64, **kw)
    sched.audit.sample_rate = 1.0
    sched.audit.synchronous = sync
    # strictly heterogeneous capacities: once a pod is placed, scores are
    # strict, so a perturbed decision falls outside the argmax set
    for i in range(3):
        api.create_node(make_node(f"n{i}").capacity(
            {"cpu": 8 + 4 * i, "memory": "16Gi", "pods": 40})
            .zone(f"z{i % 2}").obj())
    return api, sched


def _most_allocated_profile(api) -> Profile:
    plugins = [Fit(FitArgs(scoring_strategy="MostAllocated"))
               if p.name() == "NodeResourcesFit" else p
               for p in default_plugins(api)]
    return Profile(framework=Framework("default-scheduler", plugins,
                                       weights=dict(DEFAULT_WEIGHTS)),
                   score_config=ScoreConfig(strategy="MostAllocated"))


def _drain(api, sched, names, big: bool = False) -> None:
    for n in names:
        api.create_pod(make_pod(n).req(REQ).obj())
    if big:
        api.create_pod(make_pod("big").req(
            {"cpu": "100", "memory": "1Gi"}).obj())
    sched.schedule_pending()


def _perturb_last(pd, out):
    for i in range(len(out) - 1, -1, -1):
        if out[i] >= 0:
            out[i] = (out[i] + 1) % 3
            return


def _decisions(v: dict) -> dict:
    return {uid: (o["host"], o["argmax"]) if o else None
            for uid, o in v["oracle"].items()}


@pytest.fixture(scope="module")
def worker():
    w = ReplayWorker()
    yield w
    w.close()


@pytest.fixture
def pool(monkeypatch):
    """A process-wide courier pool of this test's own, so the couriers of
    audits that earlier tests left for the collector do not count."""
    monkeypatch.setattr(audit_mod, "_couriers", weakref.WeakSet())


def _couriers(audit) -> list:
    return audit._threads["process"]


# ---------------------------------------------------------------------------
# parity: one payload, thread and process


KINDS = {
    "clean": dict(pods=4),
    "divergent": dict(pods=4, perturb=True),
    "truncated": dict(pods=6, cap=2),
    "unschedulable": dict(pods=3, big=True),
}


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_thread_and_process_reach_one_verdict(kind, worker, tmp_path):
    spec = KINDS[kind]
    api, sched = _scheduler(sync=True)
    audit = sched.audit
    audit.dirpath = str(tmp_path)
    audit.max_replay_pods = spec.get("cap", 64)
    if spec.get("perturb"):
        sched._test_assignment_perturb = _perturb_last
    _drain(api, sched, [f"p{i}" for i in range(spec["pods"])],
           big=spec.get("big", False))
    rec = audit.ledger.records()[-1]
    data = next(tmp_path.glob(f"drain_{rec.drain_id:08d}.pkl")).read_bytes()
    cap = audit.max_replay_pods

    fwk = sched.profiles[rec.profile_name].framework
    on_thread = verdict(pickle.loads(data), cap, framework=fwk)
    in_process = worker.replay(data, cap)
    for key in ("outcome", "diffs", "truncated"):
        assert in_process[key] == on_thread[key], key
    # and both are the verdict the live audit published
    assert (rec.outcome, rec.diffs, rec.truncated) == (
        in_process["outcome"], in_process["diffs"], in_process["truncated"])
    assert in_process["cpu_s"] > 0.0

    # the oracle itself: the live framework, and the one the worker
    # rebuilds from the payload, decide and diagnose alike
    live = replay(pickle.loads(data), cap, framework=fwk)
    rebuilt = replay(pickle.loads(data), cap)
    assert _decisions(rebuilt) == _decisions(live)
    assert rebuilt["reasons"] == live["reasons"]

    if kind == "divergent":
        assert in_process["outcome"] == "divergent"
        assert "assignment" in in_process["diffs"]
        # a divergent reply carries the oracle, for the post-mortem
        assert _decisions(in_process) == _decisions(live)
        assert in_process["reasons"] == live["reasons"]
    else:
        assert in_process["outcome"] == "clean"
    assert in_process["truncated"] == (kind == "truncated")
    if kind == "unschedulable":
        assert live["oracle"]["default/big"] is None
        assert "Insufficient cpu" in live["reasons"]["default/big"]
    else:
        assert not live["reasons"]


def test_clean_reply_leaves_the_oracle_behind(worker, tmp_path):
    """A clean record's reply carries no per-node scores: the audit keeps
    them only for a divergent record."""
    api, sched = _scheduler(sync=True)
    sched.audit.dirpath = str(tmp_path)
    _drain(api, sched, ["a", "b"])
    data = next(tmp_path.glob("drain_*.pkl")).read_bytes()
    reply = worker.replay(data, 64)
    assert reply["outcome"] == "clean"
    assert "oracle" not in reply and "reasons" not in reply


# ---------------------------------------------------------------------------
# the audit through its worker


def _replays(sched) -> dict:
    c = sched.metrics.shadow_audit_replays
    return {w: c.value(w) for w in ("process", "thread")}


def test_flush_waits_for_the_workers_verdict():
    api, sched = _scheduler(sync=False)
    _drain(api, sched, ["a", "b", "c"])
    sched.audit.flush(timeout=120.0)
    rec = sched.audit.ledger.records()[-1]
    assert rec.outcome == "clean" and rec.replay_s > 0.0
    m = sched.metrics
    assert _replays(sched) == {"process": 1.0, "thread": 0.0}
    assert m.shadow_audit_drains.value("clean") == 1.0
    assert m.audit_worker_cpu_seconds.value() > 0.0
    assert m.audit_replay_duration.count() == 1
    # the clean record dropped its replay payload, as on the thread
    assert rec.nodes == [] and rec.oracle == {}


def test_a_dead_worker_costs_one_record(pool):
    api, sched = _scheduler(sync=False)
    audit = sched.audit
    _drain(api, sched, ["a"])
    audit.flush(timeout=120.0)
    [courier] = _couriers(audit)
    first = courier.worker
    assert audit.ledger.records()[-1].outcome == "clean"

    # the worker dies as the next record is sent to it
    send = first._conn.send

    def kill_then_send(msg):
        first.proc.kill()
        first.proc.wait()
        send(msg)
    first._conn.send = kill_then_send
    _drain(api, sched, ["b"])
    audit.flush(timeout=120.0)
    rec = audit.ledger.records()[-1]
    assert rec.outcome == "error"
    assert "replay worker died" in rec.skip_reason
    assert sched.metrics.shadow_audit_drains.value("error") == 1.0
    assert courier.worker is None and first.proc.poll() is not None

    # the next record starts a fresh worker and is replayed
    _drain(api, sched, ["c"])
    audit.flush(timeout=120.0)
    assert audit.ledger.records()[-1].outcome == "clean"
    assert _couriers(audit) == [courier]
    assert courier.worker.proc.pid != first.proc.pid
    assert sched.metrics.shadow_audit_drains.value("clean") == 2.0
    assert _replays(sched) == {"process": 3.0, "thread": 0.0}


def test_workers_grow_with_the_backlog(pool, monkeypatch):
    """Records that arrive while earlier ones are still outstanding get
    couriers and worker processes of their own (a worker takes seconds to
    start), and every one is replayed and counted once."""
    monkeypatch.setattr(audit_mod, "MAX_REPLAY_WORKERS", 4)
    api, sched = _scheduler(sync=False)
    audit = sched.audit
    for i in range(3):
        _drain(api, sched, [f"p{i}"])
    audit.flush(timeout=120.0)
    recs = audit.ledger.records()
    assert [r.outcome for r in recs] == ["clean"] * 3
    couriers = _couriers(audit)
    assert 2 <= len(couriers) <= 3
    workers = [c.worker for c in couriers if c.worker is not None]
    assert 2 <= len(workers) <= 3
    assert len({w.proc.pid for w in workers}) == len(workers)
    assert audit._threads["thread"] == []
    assert _replays(sched) == {"process": 3.0, "thread": 0.0}
    assert sched.metrics.shadow_audit_drains.value("clean") == 3.0


def test_counts_hold_under_concurrent_threads():
    """The audit thread publishes to counters the scheduling thread
    counts too: with the thread route (no worker start to wait on) and a
    tiny switch interval, no count is lost."""
    import sys
    api, sched = _scheduler(sync=False, profile=_most_allocated_profile)
    audit = sched.audit
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for i in range(12):
            _drain(api, sched, [f"p{i}", f"q{i}"])
        audit.flush(timeout=120.0)
    finally:
        sys.setswitchinterval(old)
    assert all(q.unfinished_tasks == 0 for q in audit._queues.values())
    assert [r.outcome for r in audit.ledger.records()] == ["clean"] * 12
    assert _replays(sched) == {"process": 0.0, "thread": 12.0}
    m = sched.metrics
    assert m.shadow_audit_drains.value("clean") == 12.0
    assert m.audit_replay_duration.count() == 12


def test_thread_route_replays_one_record_at_a_time(monkeypatch):
    """A thread-route replay holds the interpreter lock for its whole
    length, so however far the records back up, one thread replays them,
    one after the other, and no courier or worker starts."""
    running, peak = [0], [0]
    lock = threading.Lock()
    real = audit_mod.verdict

    def slow_verdict(*a, **k):
        with lock:
            running[0] += 1
            peak[0] = max(peak[0], running[0])
        try:
            time.sleep(0.05)
            return real(*a, **k)
        finally:
            with lock:
                running[0] -= 1
    monkeypatch.setattr(audit_mod, "verdict", slow_verdict)
    api, sched = _scheduler(sync=False, profile=_most_allocated_profile)
    audit = sched.audit
    for i in range(6):
        _drain(api, sched, [f"p{i}"])
    audit.flush(timeout=120.0)
    assert [r.outcome for r in audit.ledger.records()] == ["clean"] * 6
    assert peak[0] == 1
    assert len(audit._threads["thread"]) == 1
    assert _couriers(audit) == []
    assert _replays(sched) == {"process": 0.0, "thread": 6.0}


def test_couriers_are_capped_over_the_process(pool, monkeypatch):
    """The couriers of every audit in one process share MAX_REPLAY_WORKERS,
    and an audit always gets one: the second scheduler's backlog gets a
    courier although the first holds the whole cap."""
    monkeypatch.setattr(audit_mod, "MAX_REPLAY_WORKERS", 2)
    scheds = [_scheduler(sync=False) for _ in range(2)]
    for api, sched in scheds:
        for i in range(3):
            _drain(api, sched, [f"p{i}"])
    for _api, sched in scheds:
        sched.audit.flush(timeout=120.0)
        assert [r.outcome for r in sched.audit.ledger.records()] \
            == ["clean"] * 3
        assert _replays(sched) == {"process": 3.0, "thread": 0.0}
    first, second = (len(_couriers(s.audit)) for _a, s in scheds)
    assert 1 <= first <= 2 and second == 1
    # N audits run at most N + MAX_REPLAY_WORKERS - 1 couriers
    assert first + second <= 2 + 2 - 1
    assert sum(c.is_alive() for c in audit_mod._couriers) == first + second


# ---------------------------------------------------------------------------
# routes


def _volume_record(api, sched, tmp_path):
    """A drain's captured record whose first pod mounts an inline CSI
    volume. Such pods take the host path, so no device drain carries one:
    the record is a real drain's payload, with the volume put on its pod."""
    audit = sched.audit
    audit.synchronous, audit.dirpath = True, str(tmp_path)
    _drain(api, sched, ["a", "b"])
    audit.synchronous, audit.dirpath = False, ""
    payload = pickle.loads(next(tmp_path.glob("drain_*.pkl")).read_bytes())
    pods = payload["pods"]
    pods[0][1].spec.volumes = [Volume(name="data",
                                      csi_driver="ebs.csi.aws.com")]
    fwk = sched.profiles[payload["profile"]].framework
    rec = AuditRecord(
        drain_id=payload["drainId"] + 1, profile_name=payload["profile"],
        strategy=payload["strategy"], weights=payload["weights"], pods=pods,
        nodes=payload["nodes"],
        plugins=replay_plugins(fwk, (), [p for _u, p, _pi in pods]),
        framework=fwk)
    audit.ledger.append(rec)
    names = [ni.name for ni in rec.nodes]
    out = [names.index(payload["device"][uid]) for uid, _p, _pi in pods]
    before = _replays(sched)
    audit.submit(rec, out, names, {})
    return rec, before


@pytest.mark.parametrize("case", ["benchmark_drain", "synchronous",
                                  "non_default_profile", "volume_pod"])
def test_route_follows_the_record(case, tmp_path, pool):
    if case == "benchmark_drain":
        # the antiaffinity cell's pods: explicit namespaces, required
        # anti-affinity on the hostname, the default profile
        cfg = {"name": "anti", "kind": "pod_anti_affinity", "nodes": 32,
               "node": {"capacity": {"pods": "110", "cpu": "4",
                                     "memory": "32Gi"}},
               "node_labels": {},
               "unique_node_labels": ["kubernetes.io/hostname"],
               "init_pods": 4, "measure_pods": 8, "resident_pods": 12}
        for role, ns in (("init_pod", "sched-0"), ("measure_pod", "sched-1")):
            cfg[role] = {
                "namespace": ns, "requests": {"cpu": "100m",
                                              "memory": "500Mi"},
                "labels": {"color": "green"},
                "anti_affinity": [{"topologyKey": "kubernetes.io/hostname",
                                   "matchLabels": {"color": "green"},
                                   "namespaces": ["sched-1", "sched-0"]}]}
        system = System(cfg, AntiBuilder(cfg, anti_ref))
        system.create_nodes()
        sched = system.sched
        sched.audit.sample_rate = 1.0
        for pod in system.new_pods(12):
            system.api.create_pod(pod)
        sched.schedule_pending()
        want, before = "process", {"process": 0.0, "thread": 0.0}
    elif case == "volume_pod":
        api, sched = _scheduler(sync=False)
        rec, before = _volume_record(api, sched, tmp_path)
        assert rec.plugins is None
        want = "thread"
    else:
        custom = case == "non_default_profile"
        api, sched = _scheduler(
            sync=case == "synchronous",
            profile=_most_allocated_profile if custom else None)
        if custom:
            assert sched.profiles["default-scheduler"].score_config \
                .strategy == "MostAllocated"
        _drain(api, sched, ["a", "b", "c"])
        want, before = "thread", {"process": 0.0, "thread": 0.0}
    sched.audit.flush(timeout=120.0)

    rec = sched.audit.ledger.records()[-1]
    assert rec.outcome == "clean", (rec.outcome, rec.skip_reason, rec.diffs)
    after = _replays(sched)
    assert {w: after[w] - before[w] for w in after} == {
        w: float(w == want) for w in after}
    assert bool(_couriers(sched.audit)) == (want == "process")
    assert bool(sched.audit._threads["thread"]) == (
        want == "thread" and case != "synchronous")
    if want == "process":
        assert sched.metrics.audit_worker_cpu_seconds.value() > 0.0
    else:
        assert sched.metrics.audit_worker_cpu_seconds.value() == 0.0


def test_replay_plugins_names_the_defaults_or_nothing():
    api = APIServer()
    fwk = Framework("default-scheduler", default_plugins(api),
                    weights=dict(DEFAULT_WEIGHTS))
    plain = make_pod("p").req(REQ).obj()
    names = replay_plugins(fwk, (), [plain])
    assert names and "InterPodAffinity" in names
    # plugins no replay calls are left out (binder, queue sort, gates)
    assert "DefaultBinder" not in names and "PrioritySort" not in names
    claim = make_pod("q").req(REQ).claim("gpu").obj()
    assert replay_plugins(fwk, (), [plain, claim]) is None
    assert replay_plugins(fwk, ("an-extender",), [plain]) is None
    assert replay_plugins(_most_allocated_profile(api).framework, (),
                          [plain]) is None

    class OutOfTree:
        def name(self):
            return "OutOfTree"

        def filter(self, state, pod, node_info):
            raise AssertionError("never called")
    extra = Framework("default-scheduler",
                      default_plugins(api) + [OutOfTree()],
                      weights=dict(DEFAULT_WEIGHTS))
    assert replay_plugins(extra, (), [plain]) is None


def test_worker_runs_on_the_cpu_alone(monkeypatch, tmp_path):
    """Whatever platform the scheduler's environment names, the worker
    gets JAX_PLATFORMS=cpu, and a replay initializes no JAX backend and
    loads no TPU library there; the scheduler's environment is left as
    it was."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    w = ReplayWorker()
    try:
        import os
        assert os.environ["JAX_PLATFORMS"] == "tpu"
        api, sched = _scheduler(sync=True)
        sched.audit.dirpath = str(tmp_path)
        _drain(api, sched, ["a", "b"])
        data = next(tmp_path.glob("drain_*.pkl")).read_bytes()
        assert w.replay(data, 64)["outcome"] == "clean"
        got = w.probe()
    finally:
        w.close()
    assert got["JAX_PLATFORMS"] == "cpu"
    assert got["backends"] == [] and got["libtpu"] is False
    assert w.proc.poll() is not None        # closed: the process is gone
