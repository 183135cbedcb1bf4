"""The SchedulingPodAffinity deployment on the CPU: required pod affinity on
the zone across two namespaces.

The scheduler is driven the way the benchmark drives it (closed loop, init
pods pinned, the oldest measure pods completed) and judged by the
benchmark's plain reference of the deployment kind
(`benchmark/kinds/pod_affinity/reference.py`), on three zones so that the
first pod's zone holds every later one; the reference's affinity filter and
normalised InterPodAffinity score are checked against the program's
plugin on seeded random states; and the plan program's placement tier and
scan-step counters are checked where they are incremented.
"""

import time

import numpy as np
import pytest

from benchmark.drivers.closed_backlog_keepinit import Driver
from benchmark.kinds.pod_affinity import reference as ref
from benchmark.kinds.pod_affinity.build import Builder
from benchmark.system import System
from kubernetes_tpu.backend.apiserver import APIServer
from kubernetes_tpu.framework.interface import CycleState
from kubernetes_tpu.framework.types import NodeInfo, PodInfo
from kubernetes_tpu.metrics import PLACEMENT_TIERS, PLAN_SCAN_SLOTS
from kubernetes_tpu.plugins.interpodaffinity import InterPodAffinity
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.testing.wrappers import make_node, make_pod

HOST = "kubernetes.io/hostname"
ZONE = "topology.kubernetes.io/zone"
FOO = {"foo": ""}
REQ = {"cpu": "100m", "memory": "500Mi"}
NAMESPACES = ["sched-1", "sched-0"]
CHECKS = ("unbound", "bind_conflicts", "infeasible", "off_argmax")


def _template(ns: str) -> dict:
    return {"namespace": ns, "requests": dict(REQ), "labels": dict(FOO),
            "affinity": [{"topologyKey": ZONE, "matchLabels": FOO,
                          "namespaces": list(NAMESPACES)}]}


def _config(nodes: int, init: int, measure: int) -> dict:
    return {"name": "affinity", "kind": "pod_affinity", "nodes": nodes,
            "node": {"capacity": {"pods": "110", "cpu": "4",
                                  "memory": "32Gi"}},
            "node_labels": {ZONE: ["za", "zb", "zc"]},
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
    the program's pods lose their affinity term (the reference keeps it).
    Returns the system, the record and the reference's findings."""
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


@pytest.mark.parametrize("seed", [2 ** 31 + 17, 19])
def test_closed_loop_matches_reference(seed):
    """48 nodes in three zones of 16, 8 init pods pinned, 24 measure pods
    resident and 32 pods not yet bound: the first zone holds 640 such
    pods. Every pod lands in the first pod's zone."""
    cfg = _config(48, 8, 24)
    system, record, found = _closed_loop(cfg, seed, completions=48)
    assert {k: found[k] for k in CHECKS} == dict.fromkeys(CHECKS, 0)
    bound = record.node_of[record.node_of >= 0]
    assert len(set(bound % 3)) == 1
    gone = {s for _, s in record.deletes}
    assert len(gone) >= 48 and min(gone) >= 8
    m = system.sched.metrics
    assert m.placement_pods.value("wavescan") > 0
    assert sum(m.schedule_attempts.value("unschedulable", p)
               for p in system.sched.profiles) == 0


@pytest.mark.parametrize("seed", [2 ** 31 + 23, 29])
def test_stripped_affinity_term_is_infeasible(seed):
    """A program without the affinity filter spreads the first drain's 32
    pods over all three zones, where all but the first pod's zone fail
    the reference's filter."""
    _, _, found = _closed_loop(_config(48, 8, 24), seed, strip=True)
    assert found["infeasible"] > 0, found


def _random_template(rng) -> dict:
    tmpl = {"namespace": str(rng.choice(["sched-0", "sched-1"])),
            "requests": dict(REQ)}
    if rng.random() < 0.7:
        tmpl["labels"] = dict(FOO)
    for kind, p in (("affinity", 0.6), ("anti_affinity", 0.2)):
        if rng.random() < p:
            term = {"topologyKey": ZONE if rng.random() < 0.7 else HOST,
                    "matchLabels": dict(FOO)}
            names = [["sched-0"], ["sched-1"], list(NAMESPACES), []][
                int(rng.integers(4))]
            if names:
                term["namespaces"] = names
            tmpl[kind] = [term]
    return tmpl


def _plain(ns: str, labels: bool = True) -> dict:
    tmpl = {"namespace": ns, "requests": dict(REQ)}
    if labels:
        tmpl["labels"] = dict(FOO)
    return tmpl


# (existing pods as (template, node)), incoming template
FIXED = {
    # an incoming pod without terms, selected by existing pods' required
    # terms in two zones (3 pods in za, 1 in zb): scored, not filtered
    "no_terms_selected": ([(_template("sched-0"), 0), (_template("sched-0"), 3),
                           (_template("sched-1"), 6), (_template("sched-1"), 1)],
                          _plain("sched-1")),
    # no pod matches the term anywhere and the pod matches its own: the
    # first pod of its series passes on every node
    "escape_hatch": ([(_plain("sched-0", labels=False), 2)],
                     _template("sched-1")),
    # ... but not when it does not match its own term
    "escape_hatch_not_self": ([], dict(_template("sched-1"), labels={})),
    # once a matching pod exists, only its zone passes
    "peer_zone_only": ([(_template("sched-0"), 4)], _template("sched-1")),
}


@pytest.mark.parametrize("case", [f"random-{s}" for s in range(6)]
                         + sorted(FIXED))
def test_reference_equals_plugin(case):
    """The reference's filter (affinity and anti-affinity) and its
    normalised InterPodAffinity score, against the program's plugin
    (PreFilter + Filter, PreScore + Score + NormalizeScore) on the same
    state."""
    cfg = _config(12, 1, 1)
    cfg["unique_node_labels"] = [HOST]
    # both keys modelled for both kinds of term
    cfg["init_pod"] = dict(cfg["init_pod"], anti_affinity=[
        {"topologyKey": HOST, "matchLabels": FOO}])
    build = Builder(cfg, ref)
    nodes = build.nodes()
    plugin = InterPodAffinity()
    if case.startswith("random"):
        rng = np.random.default_rng(int(case.split("-")[1]))
        trials = []
        for _ in range(12):
            existing = [(_random_template(rng), int(rng.integers(len(nodes))))
                        for _ in range(int(rng.integers(0, 10)))]
            trials.append((existing, _random_template(rng)))
    else:
        trials = [FIXED[case]]
    scored = 0
    for trial, (existing, tmpl) in enumerate(trials):
        scorer = ref.Scorer(cfg)
        nis = [NodeInfo(node=n) for n in nodes]
        for i, (etmpl, node) in enumerate(existing):
            pod = build._proto(etmpl)
            pod.metadata.name = f"e{i}"
            pod.metadata.uid = f"{pod.metadata.namespace}/e{i}"
            pod.spec.node_name = nis[node].name
            nis[node].add_pod(PodInfo.of(pod))
            scorer.move(node, etmpl, +1)
        incoming = build._proto(tmpl)
        state = CycleState()
        _, status = plugin.pre_filter(state, incoming, nis)
        want = np.array([status.is_skip()
                         or plugin.filter(state, incoming, ni).is_success()
                         for ni in nis])
        got = scorer.affinity_ok(tmpl) & scorer.anti_ok(tmpl)
        assert (got == want).all(), (trial, tmpl, got, want)
        assert [scorer.affinity_ok_at(tmpl, i) and scorer.anti_ok_at(tmpl, i)
                for i in range(len(nis))] == want.tolist()
        feasible = [ni for ni, ok in zip(nis, want) if ok]
        plugin_scores = np.zeros(len(nis), np.int64)
        if feasible and plugin.pre_score(state, incoming, feasible,
                                         nis).is_success():
            scores = [plugin.score(state, incoming, ni)[0] for ni in feasible]
            plugin.normalize_scores(state, incoming, scores)
            plugin_scores[want] = scores
        assert (scorer.affinity_score(tmpl, want) == plugin_scores).all(), (
            trial, tmpl, scorer.affinity_raw(tmpl), plugin_scores)
        scored += bool(plugin_scores.any())
    if case == "no_terms_selected":
        assert plugin_scores.tolist() == [100, 33, 0] * 4
    elif case.startswith("escape_hatch"):
        assert want.all() == (case == "escape_hatch") and want.any() == want.all()
    elif case == "peer_zone_only":
        assert want.tolist() == [False, True, False] * 4
    else:
        assert scored > 0       # the random trials reach the score


def _affinity_cluster(nodes: int):
    api = APIServer()
    sched = Scheduler(api, batch_size=32)
    for i in range(nodes):
        api.create_node(make_node(f"n{i}").capacity(
            {"cpu": 4, "memory": "32Gi", "pods": 110}).label(
            ZONE, "zone1").obj())
    sched.prime()
    return api, sched


def _affinity_pods(n: int) -> list:
    return [make_pod(f"p{i}", "sched-1").req(REQ).labels(FOO)
            .pod_affinity(ZONE, FOO, namespaces=tuple(NAMESPACES)).obj()
            for i in range(n)]


def test_plan_scan_steps_seeded_at_zero():
    sched = Scheduler(APIServer(), batch_size=16)
    text = sched.metrics.exposition()
    for slot in PLAN_SCAN_SLOTS:
        assert f'scheduler_plan_scan_steps_total{{slot="{slot}"}} 0' in text


@pytest.mark.parametrize("pods,bucket", [(24, 32), (32, 32)])
def test_self_affine_drain_rides_the_plan_program(pods, bucket):
    """One drain of a self-affine signature: every pod resolved by the
    plan program (the escape hatch places the first), its scan's valid
    and padded steps counted at dispatch."""
    api, sched = _affinity_cluster(16)
    api.create_pods(_affinity_pods(pods))
    sched.schedule_pending()
    m = sched.metrics
    assert {t: m.placement_pods.value(t) for t in PLACEMENT_TIERS} == dict(
        dict.fromkeys(PLACEMENT_TIERS, 0), wavescan=pods)
    assert {s: m.plan_scan_steps.value(s) for s in PLAN_SCAN_SLOTS} == {
        "valid": pods, "pad": bucket - pods}
    assert api.binding_count == pods
