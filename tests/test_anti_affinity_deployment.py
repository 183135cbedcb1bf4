"""The SchedulingPodAntiAffinity deployment on the CPU: required hostname
anti-affinity across two namespaces, one pod per node.

The scheduler is driven the way the benchmark drives it (closed loop,
init pods pinned, the oldest measure pods completed) and judged by the
benchmark's plain reference of the deployment kind
(`benchmark/kinds/pod_anti_affinity/reference.py`); the reference's
anti-affinity filter is checked against the program's InterPodAffinity
plugin on random small states; and the placement-tier counter and the
`ipa_seed` phase are checked where they are incremented.
"""

import time

import numpy as np
import pytest

from benchmark.drivers.closed_backlog_keepinit import Driver
from benchmark.kinds.pod_anti_affinity import reference as ref
from benchmark.kinds.pod_anti_affinity.build import Builder
from benchmark.system import Record, System
from kubernetes_tpu.backend.apiserver import APIServer
from kubernetes_tpu.framework.interface import CycleState
from kubernetes_tpu.framework.types import NodeInfo, PodInfo
from kubernetes_tpu.metrics import PLACEMENT_TIERS
from kubernetes_tpu.plugins.interpodaffinity import InterPodAffinity
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.testing.wrappers import make_node, make_pod
from kubernetes_tpu.utils.tracing import Tracer

HOST = "kubernetes.io/hostname"
ZONE = "topology.kubernetes.io/zone"
GREEN = {"color": "green"}
REQ = {"cpu": "100m", "memory": "500Mi"}


def _template(ns: str, key: str = HOST) -> dict:
    return {"namespace": ns, "requests": dict(REQ), "labels": dict(GREEN),
            "anti_affinity": [{"topologyKey": key, "matchLabels": GREEN,
                               "namespaces": ["sched-1", "sched-0"]}]}


def _config(nodes: int, init: int, measure: int) -> dict:
    return {"name": "anti", "kind": "pod_anti_affinity", "nodes": nodes,
            "node": {"capacity": {"pods": "110", "cpu": "4",
                                  "memory": "32Gi"}},
            "node_labels": {}, "unique_node_labels": [HOST],
            "init_pods": init, "measure_pods": measure,
            "resident_pods": init + measure,
            "init_pod": _template("sched-0"),
            "measure_pod": _template("sched-1")}


TRAFFIC = {"backlog": 32, "create_chunk": 16, "warmup_iterations": 1,
           "warmup_max_s": 60}


def _closed_loop(cfg: dict, seed: int, strip: bool = False,
                 completions: int = 0):
    """24 iterations of the benchmark's loop, then more until at least
    `completions` pods were completed, and the grace after; with `strip`
    the program's pods lose their anti term (the reference keeps it).
    Returns the system and the reference's findings.

    The loop does not wait for a drain: on a loaded machine the first
    drain may still be in flight after 24 iterations, so a run that must
    see completions goes on until it has, pausing while nothing binds."""
    system = System(cfg, Builder(cfg, ref))
    system.create_nodes()
    system.sched.batch_size = 32       # a full drain per call
    if strip:
        make = system.builder.pod

        def stripped(seq):
            pod = make(seq)
            pod.spec.affinity = None
            return pod
        system.builder.pod = stripped
    drv = Driver(system, cfg, TRAFFIC, seed)
    for _ in range(24):
        drv._iteration()
    deadline = time.monotonic() + 120.0
    while len(system.deletes) < completions and time.monotonic() < deadline:
        bound = len(system.watch.node)
        drv._iteration()
        if len(system.watch.node) == bound:
            time.sleep(0.01)
    drv.finish(60.0)
    record = system.record(range(system.created))
    sample = np.random.default_rng(seed).choice(
        system.created, size=system.created // 2, replace=False)
    found = ref.check(cfg, record, sample)
    assert found["checked"] == len(sample)
    return system, record, found


@pytest.mark.parametrize("seed", [2 ** 31 + 5, 7])
def test_closed_loop_matches_reference(seed):
    """128 hostname nodes, 8 init pods pinned, 24 measure pods resident and
    a backlog of 32 pods not yet bound: 8 + 24 + 32 <= 128, so every pod
    finds a node of its own."""
    cfg = _config(128, 8, 24)
    system, record, found = _closed_loop(cfg, seed, completions=48)
    assert {k: found[k] for k in ("unbound", "bind_conflicts", "infeasible",
                                  "off_argmax")} == dict.fromkeys(
        ("unbound", "bind_conflicts", "infeasible", "off_argmax"), 0)
    # the init pods were never completed, the measure pods were, twice
    # over: each delete folded out of the resident carry, none reseeded it
    gone = {s for _, s in record.deletes}
    assert len(gone) >= 48 and min(gone) >= 8
    m = system.sched.metrics
    assert m.pod_delete_carry.value("delta") == len(record.deletes)
    assert m.pod_delete_carry.value("reseed") == 0
    assert m.placement_pods.value("wave_merge") > 0
    assert sum(m.schedule_attempts.value("unschedulable", p)
               for p in system.sched.profiles) == 0


@pytest.mark.parametrize("seed", [2 ** 31 + 13, 11])
def test_stripped_anti_term_is_infeasible(seed):
    """The check sees a program that ignores the anti term only where the
    pods outnumber the free nodes: with a node free for every pod,
    LeastAllocated alone avoids occupied nodes and the two programs decide
    alike. The first drain takes the whole backlog of 32 pods onto empty
    nodes, so 24 nodes are outnumbered there. Later decisions cannot be
    counted on: the loop does not wait for a drain, so a drain may still
    be in flight when the last iteration ends."""
    _, _, found = _closed_loop(_config(24, 8, 24), seed, strip=True)
    assert found["infeasible"] > 0, found


@pytest.mark.parametrize("broken", ["two_sched1_on_one_node",
                                    "sched1_on_a_sched0_node"])
def test_broken_record_is_infeasible(broken):
    cfg = _config(16, 4, 8)
    created = 12
    node_of = np.arange(created, dtype=np.int64)      # one pod per node
    if broken == "two_sched1_on_one_node":
        node_of[10] = node_of[9]
    else:
        node_of[9] = node_of[2]
    for sample in (np.array([], np.int64), np.arange(created)):
        found = ref.check(cfg, Record(created, node_of, [], list(range(8)),
                                      0), sample)
        assert found["infeasible"] == 1, (sample, found)
    sound = ref.check(cfg, Record(created, np.arange(created), [],
                                  list(range(8)), 0), np.arange(created))
    assert sound["infeasible"] == sound["off_argmax"] == 0


def _random_template(rng) -> dict:
    tmpl = {"namespace": str(rng.choice(["sched-0", "sched-1"])),
            "requests": dict(REQ)}
    if rng.random() < 0.6:
        tmpl["labels"] = dict(GREEN)
    if rng.random() < 0.6:
        term = {"topologyKey": HOST if rng.random() < 0.6 else ZONE,
                "matchLabels": dict(GREEN)}
        names = [["sched-0"], ["sched-1"], ["sched-1", "sched-0"], []][
            int(rng.integers(4))]
        if names:
            term["namespaces"] = names
        tmpl["anti_affinity"] = [term]
    return tmpl


@pytest.mark.parametrize("seed", range(4))
def test_reference_filter_equals_plugin(seed):
    """The reference's anti-affinity filter, both directions, against the
    program's InterPodAffinity PreFilter + Filter on the same state."""
    rng = np.random.default_rng(seed)
    cfg = _config(12, 1, 1)
    cfg["node_labels"] = {ZONE: ["za", "zb", "zc"]}
    cfg["measure_pod"] = _template("sched-1", key=ZONE)  # both keys modelled
    build = Builder(cfg, ref)
    nodes = build.nodes()
    plugin = InterPodAffinity()
    for trial in range(12):
        scorer = ref.Scorer(cfg)
        nis = [NodeInfo(node=n) for n in nodes]
        for i in range(int(rng.integers(0, 10))):
            tmpl = _random_template(rng)
            node = int(rng.integers(len(nis)))
            pod = build._proto(tmpl)
            pod.metadata.name = f"e{i}"
            pod.metadata.uid = f"{pod.metadata.namespace}/e{i}"
            pod.spec.node_name = nis[node].name
            nis[node].add_pod(PodInfo.of(pod))
            scorer.move(node, tmpl, +1)
        tmpl = _random_template(rng)
        incoming = build._proto(tmpl)
        state = CycleState()
        _, status = plugin.pre_filter(state, incoming, nis)
        want = np.array([status.is_skip()
                         or plugin.filter(state, incoming, ni).is_success()
                         for ni in nis])
        got = scorer.anti_ok(tmpl)
        assert (got == want).all(), (trial, tmpl, got, want)
        assert [scorer.anti_ok_at(tmpl, i) for i in range(len(nis))] \
            == want.tolist()


def test_placement_tiers_seeded_at_zero():
    sched = Scheduler(APIServer(), batch_size=16)
    text = sched.metrics.exposition()
    for tier in PLACEMENT_TIERS:
        assert f'scheduler_placement_pods_total{{tier="{tier}"}} 0' in text
    assert 'scheduler_phase_self_seconds_total{phase="ipa_seed"} 0' in text


def _anti_cluster(nodes: int, occupied: int, **kw):
    api = APIServer()
    sched = Scheduler(api, batch_size=32, **kw)
    for i in range(nodes):
        api.create_node(make_node(f"n{i}").capacity(
            {"cpu": 4, "memory": "32Gi", "pods": 110}).label(
            HOST, f"n{i}").obj())
    for i in range(occupied):
        api.create_pod(make_pod(f"res-{i}", "sched-0").req(REQ)
                       .labels(GREEN).pod_affinity(
                           HOST, GREEN, anti=True,
                           namespaces=("sched-1", "sched-0"))
                       .node(f"n{i}").obj())
    sched.prime()
    return api, sched


def _anti_pods(n: int) -> list:
    return [make_pod(f"p{i}", "sched-1").req(REQ).labels(GREEN)
            .pod_affinity(HOST, GREEN, anti=True,
                          namespaces=("sched-1", "sched-0")).obj()
            for i in range(n)]


@pytest.mark.parametrize("nodes,occupied,merge,serial", [
    (32, 0, 24, 0),
    # four free nodes: the merge waves place four pods and stall, the
    # serial tier resolves the other twenty (no node: unschedulable)
    (8, 4, 4, 20),
])
def test_wave_span_splits_merge_and_serial(nodes, occupied, merge, serial):
    api, sched = _anti_cluster(nodes, occupied)
    api.create_pods(_anti_pods(24))
    sched.schedule_pending()
    m = sched.metrics.placement_pods
    assert {t: m.value(t) for t in PLACEMENT_TIERS} == dict(
        dict.fromkeys(PLACEMENT_TIERS, 0), wave_merge=merge,
        wave_serial=serial)
    assert api.binding_count == merge


def _walk(sp):
    yield sp
    for c in sp.children:
        yield from _walk(c)


def test_ipa_seed_is_a_phase_inside_host_group_seed():
    api, sched = _anti_cluster(16, 4, tracer=Tracer(
        slow_threshold_s=float("inf"), keep_recent=32))
    api.create_pods(_anti_pods(24))
    sched.schedule_pending()
    assert sched.metrics.phase_self_seconds.value("ipa_seed") > 0
    seeds = [sp for root in sched.tracer.recent for sp in _walk(root)
             if sp.name == "host_group_seed"]
    assert seeds
    assert all([c.name for c in sp.children] == ["ipa_seed"] * len(
        sp.children) for sp in seeds)
    assert any(sp.children for sp in seeds)
