"""Speculative wave placement ↔ serial-order parity (ISSUE 3 standing gate).

The wave kernels (ops/program.py run_wave / run_plan) must produce
assignments BIT-IDENTICAL to the sequential greedy in every scenario —
the merge tier's conflict detection, the exact minimum-level replay, the
domain-veto champion selection, and the in-dispatch serial repair are all
exactness-critical. The fuzz feeds both wave kernels and the oracle-
verified device scan (run_batch, itself fuzzed against the transliterated
Go-semantics host oracle in tests/test_groups_parity.py) the same seeded
clusters; a smaller direct sweep closes the triangle against the host
oracle framework itself, and scheduler-level tests pin the whole wiring
(gate on ≡ gate off, including the async commit pipeline).

Scenario families: spread (DoNotSchedule / ScheduleAnyway / hostname),
required pod anti-affinity (unique and shared domains, existing pods),
required affinity, mixed interleaved signatures, tainted clusters
(PreferNoSchedule → the norm_live kernel variant), capacity-exhausted
tails, and the worst-case all-conflict wave that must degenerate to the
serial scan without error.
"""

import random

import numpy as np
import pytest

import jax.numpy as jnp

from kubernetes_tpu.backend.cache import Cache, Snapshot
from kubernetes_tpu.ops.groups import to_device
from kubernetes_tpu.ops.hostgreedy import static_norm_ok
from kubernetes_tpu.ops.program import (ScoreConfig, WaveXs, initial_carry,
                                        pod_rows_from_batch, run_batch,
                                        run_plan, run_wave, wave_statics)
from kubernetes_tpu.state.batch import BatchBuilder
from kubernetes_tpu.state.tensorize import ClusterState, pow2_at_least
from kubernetes_tpu.testing.wrappers import make_node, make_pod

ZONE = "topology.kubernetes.io/zone"
HOSTNAME = "kubernetes.io/hostname"


def _setup(nodes, existing):
    cache = Cache()
    for nd in nodes:
        cache.add_node(nd)
    for pod, node_name in existing:
        pod.spec.node_name = node_name
        cache.add_pod(pod)
    snap = Snapshot()
    cache.update_snapshot(snap)
    state = ClusterState()
    state.apply_snapshot(snap, full=True)
    return state, snap


def _statics_for(na, table, rows):
    wt = (list(rows) + [rows[-1]] * 4)[:max(
        1 if len(rows) == 1 else (2 if len(rows) == 2 else 4), len(rows))]
    out = wave_statics(na, table, jnp.asarray(np.array(wt, np.int32)))
    return [tuple(f[k] for f in out) for k in range(len(rows))]


def _anti_term_of(mgr, u):
    terms = [t for t in range(mgr.m_ipa_aa.shape[2])
             if mgr.m_ipa_aa[u, u, t] or mgr.m_ipa_exist[u, u, t]]
    return terms[0] if len(terms) == 1 else -1


def wave_vs_scan(nodes, existing, pods, cfg=ScoreConfig(), merge_on=True):
    """Assert the wave kernels reproduce run_batch's assignments exactly;
    returns (assignments, stats dict)."""
    state, snap = _setup(nodes, existing)
    builder = BatchBuilder(state)
    batch = builder.build(pods)
    assert not batch.host_fallback.any(), "fuzz pods must be tensorizable"
    gd_np, gc_np = builder.groups.build_dev(snap)
    gd, gc = to_device(gd_np), to_device(gc_np)
    na = state.device_arrays()
    xs, table = pod_rows_from_batch(batch)
    fam = builder.groups.families(snap)
    n = len(pods)

    _, scan_out = run_batch(cfg, na, initial_carry(na, gc), xs, table,
                            groups=gd, fam=fam)
    scan_out = np.asarray(scan_out)[:n]

    uniq = list(dict.fromkeys(int(t) for t in batch.tidx[:n]))
    norm_live = not all(
        static_norm_ok(state.ensure_arrays(), builder.table.pref_weight[u])
        for u in uniq)
    stats = {}
    if len(uniq) == 1:
        u = uniq[0]
        B = pow2_at_least(n)
        valid = np.zeros((B,), bool)
        valid[:n] = True
        statics = _statics_for(na, table, [u])[0]
        K = min(B, na.cap.shape[0])
        _, packed = run_wave(
            cfg, na, initial_carry(na, gc), jnp.asarray(valid), table,
            jnp.int32(u), gd, statics, K, 8, fam, norm_live,
            anti_term=_anti_term_of(builder.groups, u), merge_on=merge_on,
            Lw=min(512, B))
        packed = np.asarray(packed)
        wave_out = packed[:n]
        stats = dict(waves=int(packed[B]), confs=int(packed[B + 1]),
                     prefix=int(packed[B + 2]), serial=int(packed[B + 3]))
        assert (wave_out == scan_out).all(), (
            "run_wave diverged", scan_out.tolist(), wave_out.tolist(), stats)
    # the multi-signature kernel must match too (also for 1 signature);
    # > 4 distinct signatures routes to the plain scan in production —
    # nothing to verify here
    if len(uniq) > 4:
        return scan_out, stats
    B = pow2_at_least(n)
    S = 2 if len(uniq) <= 2 else 4
    wt_list = (uniq + [uniq[-1]] * S)[:S]
    slot = {}
    for s, u in enumerate(wt_list):
        slot.setdefault(u, s)
    widx = np.zeros((B,), np.int32)
    for k in range(n):
        widx[k] = slot[int(batch.tidx[k])]
    widx[n:] = widx[n - 1]
    valid = np.zeros((B,), bool)
    valid[:n] = True
    st_list = _statics_for(na, table, wt_list)
    statics = tuple(jnp.stack([s[f] for s in st_list]) for f in range(4))
    wxs = WaveXs(valid=jnp.asarray(valid), widx=jnp.asarray(widx))
    _, packed2 = run_plan(
        cfg, na, initial_carry(na, gc), wxs, table,
        jnp.asarray(np.array(wt_list, np.int32)), gd, statics, fam,
        norm_live)
    ws_out = np.asarray(packed2)[:n]
    assert (ws_out == scan_out).all(), (
        "run_plan diverged", scan_out.tolist(), ws_out.tolist())
    return scan_out, stats


def _nodes(n, zones, cpu=16, taints=(), unique_zone=False):
    out = []
    for i in range(n):
        b = (make_node(f"n{i}")
             .capacity({"cpu": cpu, "memory": "32Gi", "pods": 40})
             .zone(f"z{i if unique_zone else i % zones}")
             .label(HOSTNAME, f"n{i}"))
        for (key, val, eff) in taints:
            b = b.taint(key, val, eff)
        out.append(b.obj())
    return out


class TestWaveFamilies:
    def test_spread_tight_skew(self):
        pods = [make_pod(f"p{i}").req({"cpu": "1", "memory": "1Gi"})
                .label("app", "a")
                .spread_constraint(1, ZONE, "DoNotSchedule", {"app": "a"})
                .obj() for i in range(14)]
        out, stats = wave_vs_scan(_nodes(9, 3), [], pods)
        assert (out >= 0).all()
        # tight skew forces conflicts: the serial tier must engage
        assert stats["serial"] > 0 or stats["confs"] > 0

    def test_spread_slack_skew_single_wave(self):
        pods = [make_pod(f"p{i}").req({"cpu": "500m", "memory": "512Mi"})
                .label("app", "a")
                .spread_constraint(5, ZONE, "DoNotSchedule", {"app": "a"})
                .obj() for i in range(24)]
        out, stats = wave_vs_scan(_nodes(12, 4, cpu=64), [], pods)
        assert (out >= 0).all()
        # balanced fill under slack: the exact min-level replay must
        # accept the whole span without conflicts (zero-conflict extreme)
        assert stats == {} or (stats["confs"] == 0 and stats["serial"] == 0)

    def test_spread_hostname_key(self):
        pods = [make_pod(f"p{i}").req({"cpu": "1", "memory": "1Gi"})
                .label("app", "h")
                .spread_constraint(2, HOSTNAME, "DoNotSchedule", {"app": "h"})
                .obj() for i in range(16)]
        wave_vs_scan(_nodes(8, 4), [], pods)

    def test_spread_schedule_anyway_routes_wavescan(self):
        # ScheduleAnyway rows are outside the same-signature kernel's
        # maintained state — the multi-signature kernel must cover them
        pods = [make_pod(f"p{i}").req({"cpu": "1", "memory": "1Gi"})
                .label("app", "s")
                .spread_constraint(2, ZONE, "ScheduleAnyway", {"app": "s"})
                .obj() for i in range(12)]
        wave_vs_scan(_nodes(9, 3), [], pods, merge_on=False)

    def test_anti_affinity_unique_domains(self):
        pods = [make_pod(f"q{i}").req({"cpu": "1", "memory": "1Gi"})
                .label("anti", "y")
                .pod_affinity(ZONE, {"anti": "y"}, anti=True)
                .obj() for i in range(10)]
        out, stats = wave_vs_scan(_nodes(12, 12, unique_zone=True), [], pods)
        assert (out >= 0).all()
        assert stats["confs"] == 0 and stats["serial"] == 0

    def test_anti_affinity_shared_domains_with_existing(self):
        ex = [(make_pod(f"e{i}").req({"cpu": "1", "memory": "1Gi"})
               .label("anti", "y")
               .pod_affinity(ZONE, {"anti": "y"}, anti=True).obj(),
               f"n{i}") for i in range(2)]
        pods = [make_pod(f"q{i}").req({"cpu": "1", "memory": "1Gi"})
                .label("anti", "y")
                .pod_affinity(ZONE, {"anti": "y"}, anti=True)
                .obj() for i in range(10)]
        wave_vs_scan(_nodes(12, 6), ex, pods)

    def test_affinity_routes_to_wavescan(self):
        # self-matching required affinity: the same-signature kernel's
        # merge/serial state can't carry it; run_plan must be exact
        ex = [(make_pod("seed").req({"cpu": "1", "memory": "1Gi"})
               .label("app", "aff").obj(), "n0")]
        pods = [make_pod(f"q{i}").req({"cpu": "1", "memory": "1Gi"})
                .label("app", "aff")
                .pod_affinity(ZONE, {"app": "aff"})
                .obj() for i in range(8)]
        state, snap = _setup(_nodes(6, 3), ex)
        builder = BatchBuilder(state)
        batch = builder.build(pods)
        assert not batch.host_fallback.any()
        gd_np, gc_np = builder.groups.build_dev(snap)
        gd, gc = to_device(gd_np), to_device(gc_np)
        na = state.device_arrays()
        xs, table = pod_rows_from_batch(batch)
        fam = builder.groups.families(snap)
        _, scan_out = run_batch(ScoreConfig(), na, initial_carry(na, gc),
                                xs, table, groups=gd, fam=fam)
        scan_out = np.asarray(scan_out)[:8]
        u = int(batch.tidx[0])
        B = pow2_at_least(8)
        valid = np.zeros((B,), bool)
        valid[:8] = True
        st_list = _statics_for(na, table, [u, u])
        statics = tuple(jnp.stack([s[f] for s in st_list]) for f in range(4))
        wxs = WaveXs(valid=jnp.asarray(valid),
                     widx=jnp.asarray(np.zeros((B,), np.int32)))
        _, packed = run_plan(
            ScoreConfig(), na, initial_carry(na, gc), wxs, table,
            jnp.asarray(np.array([u, u], np.int32)), gd, statics, fam,
            False)
        assert (np.asarray(packed)[:8] == scan_out).all()

    def test_prefer_no_schedule_taints_norm_live(self):
        # PreferNoSchedule taints make the taint normalization shift as
        # nodes saturate: the norm_live kernel variant must stay exact
        nodes = _nodes(8, 4, taints=[("dedic", "x", "PreferNoSchedule")])
        nodes += _nodes(4, 4)[0:0]  # keep list type
        for i in range(4, 8):
            nodes[i].spec.taints = []
        pods = [make_pod(f"p{i}").req({"cpu": "1", "memory": "1Gi"})
                .label("app", "t")
                .spread_constraint(2, ZONE, "DoNotSchedule", {"app": "t"})
                .obj() for i in range(12)]
        wave_vs_scan(nodes, [], pods)

    def test_capacity_exhausted_tail(self):
        pods = [make_pod(f"t{i}").req({"cpu": "7", "memory": "1Gi"})
                .label("app", "b")
                .spread_constraint(2, ZONE, "DoNotSchedule", {"app": "b"})
                .obj() for i in range(12)]
        out, _ = wave_vs_scan(_nodes(3, 3, cpu=8), [], pods)
        assert (out[-4:] == -1).all()

    def test_all_conflict_wave_degenerates_to_serial(self):
        # worst case: skew 1 over 2 zones with alternating capacity — every
        # placement moves the mask, the merge tier can't hold a prefix, and
        # the whole span must fall through to the in-dispatch serial scan
        # WITHOUT error and with exact results
        nodes = _nodes(4, 2, cpu=6)
        pods = [make_pod(f"c{i}").req({"cpu": "2", "memory": "1Gi"})
                .label("app", "c")
                .spread_constraint(1, ZONE, "DoNotSchedule", {"app": "c"})
                .obj() for i in range(10)]
        out, stats = wave_vs_scan(nodes, [], pods)
        assert stats["serial"] + stats["prefix"] + stats["confs"] > 0

    def test_mixed_signatures_interleaved(self):
        a = [make_pod(f"a{i}").req({"cpu": "1", "memory": "1Gi"})
             .label("app", "a")
             .spread_constraint(2, ZONE, "DoNotSchedule", {"app": "a"})
             .obj() for i in range(6)]
        b = [make_pod(f"b{i}").req({"cpu": "500m", "memory": "512Mi"})
             .label("anti", "y")
             .pod_affinity(HOSTNAME, {"anti": "y"}, anti=True)
             .obj() for i in range(6)]
        inter = [p for pair in zip(a, b) for p in pair]
        wave_vs_scan(_nodes(8, 4), [], inter)


def _fuzz_scenario(rng: random.Random, idx: int):
    """One seeded scenario: (nodes, existing, pods)."""
    zones = rng.choice([2, 3, 4])
    n_nodes = rng.choice([6, 9, 12])
    cpu = rng.choice([8, 16, 24])
    taints = ([("d", "x", "PreferNoSchedule")] if rng.random() < 0.2 else [])
    nodes = _nodes(n_nodes, zones, cpu=cpu, taints=taints)
    if taints:
        # only a subset tainted: normalization varies across nodes
        for nd in nodes[n_nodes // 2:]:
            nd.spec.taints = []

    kind = idx % 5
    n_pods = rng.randint(8, 24)
    existing = []
    if rng.random() < 0.4:
        existing = [(make_pod(f"e{idx}_{k}")
                     .req({"cpu": "1", "memory": "1Gi"})
                     .label("app", "f").obj(), f"n{k % n_nodes}")
                    for k in range(rng.randint(1, 4))]

    def spread(i, skew, action, key=ZONE, label="f"):
        return (make_pod(f"f{idx}_{i}")
                .req({"cpu": f"{rng.choice([250, 500, 1000])}m",
                      "memory": "512Mi"})
                .label("app", label)
                .spread_constraint(skew, key, action, {"app": label}).obj())

    def anti(i, key=ZONE, label="v"):
        return (make_pod(f"g{idx}_{i}")
                .req({"cpu": "500m", "memory": "512Mi"})
                .label("anti", label)
                .pod_affinity(key, {"anti": label}, anti=True).obj())

    if kind == 0:
        skew = rng.choice([1, 2, 5])
        pods = [spread(i, skew, "DoNotSchedule") for i in range(n_pods)]
    elif kind == 1:
        pods = [anti(i, key=rng.choice([ZONE, HOSTNAME]))
                for i in range(n_pods)]
    elif kind == 2:
        skew = rng.choice([1, 3])
        pods = [spread(i, skew, "ScheduleAnyway") for i in range(n_pods)]
    elif kind == 3:
        a = [spread(i, rng.choice([1, 2]), "DoNotSchedule", label="m1")
             for i in range(n_pods // 2)]
        b = [anti(i, label="m2") for i in range(n_pods - n_pods // 2)]
        pods = [p for pair in zip(a, b) for p in pair]
        pods += a[len(b):] + b[len(a):]
    else:
        # spread + anti on the SAME signature
        pods = [(make_pod(f"h{idx}_{i}")
                 .req({"cpu": "500m", "memory": "512Mi"})
                 .label("app", "sa")
                 .spread_constraint(rng.choice([2, 4]), ZONE,
                                    "DoNotSchedule", {"app": "sa"})
                 .pod_affinity(HOSTNAME, {"app": "sa"}, anti=True).obj())
                for i in range(n_pods)]
    return nodes, existing, pods


@pytest.mark.parametrize("block", range(8))
def test_wave_fuzz(block):
    """The standing fuzz gate: ≥200 seeded scenarios, wave ≡ serial scan
    (which is itself oracle-verified), across every constraint family,
    mixed signatures, taints, existing pods and capacity pressure."""
    rng = random.Random(1000 + block)
    for k in range(26):
        idx = block * 26 + k
        nodes, existing, pods = _fuzz_scenario(rng, idx)
        wave_vs_scan(nodes, existing, pods)


def test_wave_vs_host_oracle_direct():
    """Close the triangle: the wave kernel against the actual host oracle
    (framework runtime), not just the scan, on an evolving cluster."""
    from kubernetes_tpu.framework.interface import CycleState
    from kubernetes_tpu.framework.runtime import schedule_pod
    from kubernetes_tpu.framework.types import FitError
    from tests.test_groups_parity import full_framework

    nodes = _nodes(9, 3)
    pods = [make_pod(f"p{i}").req({"cpu": "1", "memory": "1Gi"})
            .label("app", "o")
            .spread_constraint(2, ZONE, "DoNotSchedule", {"app": "o"})
            .obj() for i in range(15)]
    out, _ = wave_vs_scan(nodes, [], pods)

    cache = Cache()
    for nd in nodes:
        cache.add_node(nd)
    fwk = full_framework()
    snap = Snapshot()
    for i, pod in enumerate(pods):
        cache.update_snapshot(snap)
        try:
            result = schedule_pod(fwk, CycleState(), pod,
                                  snap.node_info_list)
            chosen = result.suggested_host
        except FitError:
            chosen = None
        if out[i] < 0:
            assert chosen is None, (i, chosen)
        else:
            assert chosen == f"n{out[i]}", (i, chosen, out[i])
            bound = pod.with_node_name(chosen)
            cache.add_pod(bound)


class TestSchedulerWave:
    def _run(self, gate_on, seed):
        from kubernetes_tpu.backend.apiserver import APIServer
        from kubernetes_tpu.scheduler import Scheduler

        rng = random.Random(seed)
        api = APIServer()
        sched = Scheduler(api, batch_size=64)
        sched.feature_gates.set("SpeculativeWavePlacement", gate_on)
        sched.wave_min_span = 4
        for i in range(24):
            api.create_node(make_node(f"n{i}")
                            .capacity({"cpu": 16, "memory": "32Gi",
                                       "pods": 40})
                            .zone(f"z{i % 4}").label(HOSTNAME, f"n{i}").obj())
        sched.prime()
        for i in range(72):
            k = i % 3
            if k == 0:
                p = (make_pod(f"s{i}")
                     .req({"cpu": "500m", "memory": "512Mi"})
                     .label("app", "sp")
                     .spread_constraint(rng.choice([1, 3]), ZONE,
                                        "DoNotSchedule", {"app": "sp"})
                     .obj())
            elif k == 1:
                p = (make_pod(f"a{i}")
                     .req({"cpu": "500m", "memory": "512Mi"})
                     .label("anti", "y")
                     .pod_affinity(HOSTNAME, {"anti": "y"}, anti=True).obj())
            else:
                p = (make_pod(f"p{i}")
                     .req({"cpu": "250m", "memory": "256Mi"}).obj())
            api.create_pod(p)
            if i % 24 == 23:
                sched.schedule_pending(wait=False)
        sched.schedule_pending()
        return ({p.metadata.name: p.spec.node_name
                 for p in api.pods.values()}, sched)

    def test_scheduler_gate_parity(self):
        on, s_on = self._run(True, seed=3)
        off, s_off = self._run(False, seed=3)
        assert on == off
        # the wave path must actually engage (not silently fall back)
        assert s_on.metrics.wave_placement_waves.value() > 0
        assert s_off.metrics.wave_placement_waves.value() == 0

    def test_same_sig_wave_engages_merge(self):
        from kubernetes_tpu.backend.apiserver import APIServer
        from kubernetes_tpu.scheduler import Scheduler

        api = APIServer()
        sched = Scheduler(api, batch_size=64)
        sched.wave_min_span = 4
        for i in range(12):
            api.create_node(make_node(f"n{i}")
                            .capacity({"cpu": 32, "memory": "64Gi",
                                       "pods": 80})
                            .zone(f"z{i % 4}").label(HOSTNAME, f"n{i}").obj())
        sched.prime()
        for i in range(32):
            api.create_pod(make_pod(f"p{i}")
                           .req({"cpu": "500m", "memory": "512Mi"})
                           .label("app", "w")
                           .spread_constraint(5, ZONE, "DoNotSchedule",
                                              {"app": "w"}).obj())
        assert sched.schedule_pending() == 32
        m = sched.metrics
        assert m.wave_placement_waves.value() > 0
        assert m.wave_accepted_prefix.count() > 0
        assert m.drain_phase.count("device") > 0
        assert sched.host_greedy_runs == 0
        # resident carry: the device bookkeeping must match the host cache
        assert sched.reconcile() == []

    def test_wave_respects_min_span(self):
        from kubernetes_tpu.backend.apiserver import APIServer
        from kubernetes_tpu.scheduler import Scheduler

        api = APIServer()
        sched = Scheduler(api, batch_size=64)
        assert sched.wave_min_span > 8
        for i in range(6):
            api.create_node(make_node(f"n{i}")
                            .capacity({"cpu": 16, "memory": "32Gi",
                                       "pods": 40})
                            .zone(f"z{i % 3}").label(HOSTNAME, f"n{i}").obj())
        sched.prime()
        for i in range(8):   # below wave_min_span
            api.create_pod(make_pod(f"p{i}")
                           .req({"cpu": "500m", "memory": "512Mi"})
                           .label("app", "w")
                           .spread_constraint(1, ZONE, "DoNotSchedule",
                                              {"app": "w"}).obj())
        assert sched.schedule_pending() == 8
        assert sched.metrics.wave_placement_waves.value() == 0


class TestDonationAndCompileCount:
    def test_run_batch_no_retrace(self):
        """Buffer-donation satellite: repeated dispatches with identical
        shapes must reuse ONE compiled executable (no re-tracing), and the
        CPU backend must select the non-donating variant (donation is
        unimplemented there and would warn every dispatch)."""
        import jax

        from kubernetes_tpu.ops.program import (_run_batch_fn,
                                                _run_wave_same_fn)

        nodes = _nodes(6, 3)
        pods = [make_pod(f"p{i}").req({"cpu": "1", "memory": "1Gi"})
                .label("app", "d")
                .spread_constraint(3, ZONE, "DoNotSchedule", {"app": "d"})
                .obj() for i in range(8)]
        state, snap = _setup(nodes, [])
        builder = BatchBuilder(state)
        batch = builder.build(pods)
        gd_np, gc_np = builder.groups.build_dev(snap)
        gd, gc = to_device(gd_np), to_device(gc_np)
        na = state.device_arrays()
        xs, table = pod_rows_from_batch(batch)
        fam = builder.groups.families(snap)

        donate = jax.default_backend() != "cpu"
        fn = _run_batch_fn(donate)
        base = fn._cache_size()
        cfg = ScoreConfig()
        for _ in range(3):
            carry = initial_carry(na, gc)
            _, out = run_batch(cfg, na, carry, xs, table, groups=gd,
                               fam=fam)
            np.asarray(out)
        after = fn._cache_size()
        assert after - base <= 1, (base, after)
        # a second round with the SAME shapes must not add cache entries
        carry = initial_carry(na, gc)
        _, out = run_batch(cfg, na, carry, xs, table, groups=gd, fam=fam)
        np.asarray(out)
        assert fn._cache_size() == after
        # same contract for the wave kernel
        wfn = _run_wave_same_fn(donate)
        wbase = wfn._cache_size()
        u = int(batch.tidx[0])
        B = pow2_at_least(len(pods))
        valid = np.zeros((B,), bool)
        valid[:len(pods)] = True
        statics = _statics_for(na, table, [u])[0]
        for _ in range(2):
            carry = initial_carry(na, gc)
            _, packed = run_wave(cfg, na, carry, jnp.asarray(valid), table,
                                 jnp.int32(u), gd, statics,
                                 min(B, na.cap.shape[0]), 8, fam, False,
                                 anti_term=-1, merge_on=True, Lw=B)
            np.asarray(packed)
        assert wfn._cache_size() - wbase <= 1
