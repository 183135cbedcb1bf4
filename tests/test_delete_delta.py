"""Deleting a bound pod folds it out of the resident device carry as a
negative placement (`Scheduler._fold_retires`, ops/program.py
`retire_pods`) instead of dropping the carry.

The parity check: after random drains and bound-pod deletes, some landing
while drains are in flight, the resident carry equals a fresh reseed of
the same snapshot (`build_dev` + `initial_carry`) in every node-usage and
group-count array, for signature mixes that light each group family. The
ports carry is compared as the set of ids per node: its slot order is the
order of placements on the device, sorted on a reseed. The fallbacks
(no row, a volume, a table reset, a mesh) drop the carry and count
`reseed`.
"""

import numpy as np
import pytest

from kubernetes_tpu.backend.apiserver import APIServer
from kubernetes_tpu.ops.groups import GroupCarry, to_device
from kubernetes_tpu.ops.program import initial_carry
from kubernetes_tpu.scheduler import Scheduler, _PendingDrain
from kubernetes_tpu.testing.wrappers import make_node, make_pod

HOST = "kubernetes.io/hostname"
ZONE = "topology.kubernetes.io/zone"
NODES = 16
REQ = {"cpu": "100m", "memory": "256Mi"}


def _plain(i):
    return make_pod(f"plain-{i}").req(REQ).label("app", "plain")


def _zone_dns(i):
    return (make_pod(f"zdns-{i}").req(REQ).label("app", "web")
            .spread_constraint(1, ZONE, "DoNotSchedule", {"app": "web"}))


def _anyway(i):
    return (make_pod(f"any-{i}").req(REQ).label("app", "any")
            .spread_constraint(1, HOST, "ScheduleAnyway", {"app": "any"})
            .spread_constraint(2, ZONE, "ScheduleAnyway", {"app": "any"}))


def _anti(i):
    # the SchedulingPodAntiAffinity template: one pod per host
    return (make_pod(f"anti-{i}", "sched-1").req(REQ)
            .label("color", "green")
            .pod_affinity(HOST, {"color": "green"}, anti=True,
                          namespaces=("sched-1", "sched-0")))


def _affinity(i):
    return (make_pod(f"aff-{i}").req(REQ).label("app", "db")
            .pod_affinity(ZONE, {"app": "db"}))


def _preferred(i):
    return (make_pod(f"pref-{i}").req(REQ).label("app", "cache")
            .preferred_pod_affinity(ZONE, {"app": "web"}, 7)
            .preferred_pod_affinity(HOST, {"app": "cache"}, 3, anti=True))


def _port(i):
    return make_pod(f"port-{i}").req(REQ).host_port(8080)


# each mix: the templates its pods are drawn from, in random order
MIXES = {
    "zone_do_not_schedule": (_zone_dns, _plain),
    "schedule_anyway": (_anyway, _plain),
    "required_anti": (_anti,),
    "required_affinity": (_affinity, _plain),
    "preferred": (_preferred, _zone_dns),
    "host_port": (_port, _plain),
    "plain": (_plain,),
}


def _cluster(mesh=None, batch_size=16, nodes=NODES):
    api = APIServer()
    sched = Scheduler(api, batch_size=batch_size, mesh=mesh)
    for i in range(nodes):
        api.create_node(make_node(f"n{i}")
                        .capacity({"cpu": "8", "memory": "16Gi", "pods": 110})
                        .zone(f"z{i % 4}").label(HOST, f"n{i}").obj())
    return api, sched


def _paths(sched):
    c = sched.metrics.pod_delete_carry
    return c.value("delta"), c.value("reseed")


def _bound(api):
    return sorted(uid for uid, p in api.pods.items() if p.spec.node_name)


def _assert_carry_is_a_reseed(sched):
    """The resident carry against a fresh reseed of the same snapshot."""
    sched.wait_pending()
    carry = sched._device_carry
    assert carry is not None
    if sched._retires:
        carry = sched._fold_retires(carry)
    sched.cache.update_snapshot(sched.snapshot)
    sched.state.apply_snapshot(sched.snapshot)
    na = sched.state.device_arrays()
    gcarry = None
    if carry.groups is not None:
        gcarry = to_device(sched.builder.groups.build_dev(sched.snapshot)[1])
    fresh = initial_carry(na, gcarry)
    for name in ("used", "nonzero_used", "npods"):
        np.testing.assert_array_equal(np.asarray(getattr(carry, name)),
                                      np.asarray(getattr(fresh, name)), name)
    held = [sorted(int(p) for p in row if p)
            for row in np.asarray(carry.ports)]
    assert held == [sorted(int(p) for p in row if p)
                    for row in np.asarray(fresh.ports)]
    assert (carry.groups is None) == (fresh.groups is None)
    if carry.groups is not None:
        for name in GroupCarry._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(carry.groups, name)),
                np.asarray(getattr(fresh.groups, name)), name)


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_carry_after_deletes_equals_reseed(mix):
    # a drain of 3 pods dispatches at once, without waiting for more
    api, sched = _cluster(batch_size=3)
    rng = np.random.default_rng(sorted(MIXES).index(mix))
    templates = MIXES[mix]
    seq = 0

    def create(n):
        nonlocal seq
        for _ in range(n):
            make = templates[int(rng.integers(len(templates)))]
            api.create_pod(make(seq).obj())
            seq += 1

    # at most 8 + 3 + 3 pods resident: one per node fits the anti mix
    create(8)
    sched.schedule_pending()
    for _ in range(4):
        # a drain in flight, then deletes of bound pods, then the next
        # drain folds them before it dispatches
        create(3)
        sched.schedule_pending(wait=False)
        bound = _bound(api)
        for uid in rng.choice(bound, size=len(bound) // 2, replace=False):
            api.delete_pod(uid)
        create(3)
        sched.schedule_pending(wait=False)
        _assert_carry_is_a_reseed(sched)
    delta, reseed = _paths(sched)
    assert delta > 0 and reseed == 0
    sched.schedule_pending()
    assert api.binding_count == seq
    assert sched.reconcile() == []


@pytest.mark.parametrize("fold_at", ["next_drain", "before_any_drain"])
def test_replay_of_a_drain_in_flight_refolds_its_deletes(fold_at,
                                                         monkeypatch):
    """An inexact uniform run replays every later drain in flight at its
    commit; the deletes folded after it must be folded again in the
    replayed chain, whether the next drain's build folded them or they
    were folded before any later drain was dispatched. Few nodes and a
    shallow uniform depth make every uniform run inexact; results never
    count as landed, so drains stay in flight until `wait_pending`."""
    api, sched = _cluster(batch_size=32, nodes=2)
    shape = sched._uniform_shape
    monkeypatch.setattr(sched, "_uniform_shape",
                        lambda na: shape(na)[:2] + (8,))
    replays = []
    replay = sched._replay_downstream
    monkeypatch.setattr(sched, "_replay_downstream",
                        lambda *a: replays.append(
                            (len(sched._pending), len(sched._retire_folds)))
                        or replay(*a))
    for i in range(32):
        api.create_pod(_plain(i).obj())
    sched.schedule_pending()
    replays.clear()
    monkeypatch.setattr(_PendingDrain, "ready", lambda self: False)
    for i in range(32, 64):
        api.create_pod(_plain(i).obj())
    sched.schedule_pending(wait=False)
    for uid in _bound(api)[:12]:
        api.delete_pod(uid)
    if fold_at == "next_drain":
        for i in range(64, 96):
            api.create_pod(_plain(i).obj())
        sched.schedule_pending(wait=False)
        assert len(sched._pending) == 2 and sched._pending[1].retire
    else:
        sched._fold_retires(sched._device_carry)
        assert len(sched._pending) == 1 and sched._retire_folds
    _assert_carry_is_a_reseed(sched)
    # the first drain's replay: (later drains in flight, folds after them)
    assert replays[0] == ((1, 0) if fold_at == "next_drain" else (0, 1))
    assert _paths(sched) == (12, 0)
    assert sched.reconcile() == []


def _resident(api, sched):
    for i in range(8):
        api.create_pod(_zone_dns(i).obj())
    assert sched.schedule_pending() == 8
    assert sched._device_carry is not None


def test_pod_without_a_row_reseeds():
    """A pod bound by someone else has no signature row the device placed
    it from: its delete drops the carry."""
    api, sched = _cluster()
    api.create_pod(_zone_dns(99).node("n3").obj())
    _resident(api, sched)
    api.delete_pod("default/zdns-99")
    assert sched._device_carry is None
    assert _paths(sched) == (0, 1)


def test_pod_with_a_volume_reseeds():
    """A volume's binding lives outside the carry: the delete of a pod
    that has one drops the carry."""
    api, sched = _cluster()
    _resident(api, sched)
    api.create_pod(_plain(99).csi_volume("ebs.csi.aws.com").obj())
    assert sched.schedule_pending() == 1
    # the host path that bound it dropped the carry: the next drain
    # reseeds it
    api.create_pod(_zone_dns(98).obj())
    assert sched.schedule_pending() == 1
    assert sched._device_carry is not None
    pod = api.pods["default/plain-99"]
    # its pre-bind chain left it without a row; given one, its volume
    # alone still refuses the delta
    ps = sched.cache.pod_states[pod.uid]
    assert ps.row is None
    ps.row = sched.cache.pod_states["default/zdns-0"].row
    assert sched._retire_of(pod) is None
    assert sched._retire_of(api.pods["default/zdns-0"]) is not None
    api.delete_pod(pod.uid)
    assert sched._device_carry is None
    assert _paths(sched) == (0, 1)


def test_delete_after_a_table_reset_reseeds():
    api, sched = _cluster()
    _resident(api, sched)
    sched.builder._reset_table()
    api.delete_pod(_bound(api)[0])
    assert sched._device_carry is None
    assert _paths(sched) == (0, 1)


def test_delete_under_a_mesh_reseeds():
    from kubernetes_tpu.parallel.sharding import make_mesh
    api, sched = _cluster(mesh=make_mesh(2))
    _resident(api, sched)
    api.delete_pod(_bound(api)[0])
    assert sched._device_carry is None
    assert _paths(sched) == (0, 1)
