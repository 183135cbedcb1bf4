"""The main-path kernels compile for a TPU v5e, and the chip-only branches
run here.

- AOT compiles of `run_batch`, `run_uniform`, `scatter_rows` and `run_gang`
  at the 5000-node bucket (8192 node rows, 8192-pod batch, int64), of
  `run_wave` for a 512-pod hostname anti-affinity span (the inter-pod
  families of its wave fold), of `run_plan` for a 512-pod span of a
  self-affine zone row (required affinity and its hard-weight score), and
  of `retire_pods` folding deleted pods out of the lean carry and out of
  those two carries, for one described v5e device: what the chip's
  compiler refuses fails here, at no chip time. Nothing runs, so these
  say nothing about results or speed.
- chip_smoke.py's cases at their 500-node shapes with the accelerator
  branch of the device path forced (buffer donation), through the
  script's own case runner: every pod bound, nothing on the host, zero
  audit divergence.
- chip_smoke.py refuses to run without a TPU.

The topology is described inside a fixture (never at import): only one
process may hold the TPU library, and every xdist worker imports this file.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from kubernetes_tpu.perf.harness import BATCH_SIZE as BATCH

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NODES = 5000          # the upstream scheduler_perf 5k-node shapes
GANG = 256            # GangTraining/5000Nodes gang size
BATCH_WAVE = 512      # the default batchSize: one wave span per drain
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without the chip: keep these out of it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def cluster():
    """Host-side inputs at the 5000-node bucket: plain and zone-spread
    pods (the SchedulingBasic and TopologySpreading templates)."""
    from kubernetes_tpu.backend.cache import Cache, Snapshot
    from kubernetes_tpu.ops.groups import to_device
    from kubernetes_tpu.ops.program import initial_carry, pod_rows_from_batch
    from kubernetes_tpu.state.batch import BatchBuilder
    from kubernetes_tpu.state.tensorize import ClusterState
    from kubernetes_tpu.testing.wrappers import make_node, make_pod

    cache = Cache()
    for i in range(NODES):
        cache.add_node(make_node(f"n{i}").capacity(
            {"cpu": 32, "memory": "64Gi", "pods": 110})
            .zone(f"z{i % 16}").obj())
    snap = Snapshot()
    cache.update_snapshot(snap)
    state = ClusterState()
    state.apply_snapshot(snap, full=True)
    builder = BatchBuilder(state)
    pods = [make_pod(f"p{i}").req({"cpu": "900m", "memory": "1Gi"}).obj()
            for i in range(8)]
    pods += [make_pod(f"s{i}").req({"cpu": "900m", "memory": "1Gi"})
             .label("app", "spread")
             .spread_constraint(5, "topology.kubernetes.io/zone",
                                "DoNotSchedule", {"app": "spread"}).obj()
             for i in range(8)]
    batch = builder.build(pods, pad_to=BATCH)
    na = state.device_arrays()
    gd_np, gc_np = builder.groups.build_dev(snap)
    xs, table = pod_rows_from_batch(batch)
    return {"state": state, "builder": builder, "batch": batch, "na": na,
            "xs": xs, "table": table, "gd": to_device(gd_np),
            "fam": builder.groups.families(snap),
            "carry": initial_carry(na, to_device(gc_np)),
            "carry0": initial_carry(na)}


@pytest.fixture(scope="module")
def anti_cluster():
    """Host-side inputs at the 5000-node bucket for a same-signature
    required hostname anti-affinity row (the SchedulingPodAntiAffinity
    template) with resident anti pods: run_wave's domain-veto merge tier,
    the ipa_anti and ipa_score families of its wave fold."""
    from kubernetes_tpu.backend.cache import Cache, Snapshot
    from kubernetes_tpu.compiler import DrainCompiler
    from kubernetes_tpu.ops.groups import to_device
    from kubernetes_tpu.ops.program import initial_carry, pod_rows_from_batch
    from kubernetes_tpu.state.batch import BatchBuilder
    from kubernetes_tpu.state.tensorize import ClusterState
    from kubernetes_tpu.testing.wrappers import make_node, make_pod

    host = "kubernetes.io/hostname"

    def anti(name, ns):
        return (make_pod(name, ns).req({"cpu": "100m", "memory": "500Mi"})
                .label("color", "green")
                .pod_affinity(host, {"color": "green"}, anti=True,
                              namespaces=("sched-1", "sched-0")))

    cache = Cache()
    for i in range(NODES):
        cache.add_node(make_node(f"n{i}").capacity(
            {"cpu": 4, "memory": "32Gi", "pods": 110})
            .label(host, f"n{i}").obj())
    for i in range(8):
        cache.add_pod(anti(f"init-{i}", "sched-0").node(f"n{i}").obj())
    snap = Snapshot()
    cache.update_snapshot(snap)
    state = ClusterState()
    state.apply_snapshot(snap, full=True)
    builder = BatchBuilder(state)
    batch = builder.build([anti(f"p{i}", "sched-1").obj()
                           for i in range(512)], pad_to=512)
    na = state.device_arrays()
    gd_np, gc_np = builder.groups.build_dev(snap)
    _xs, table = pod_rows_from_batch(batch)
    row = int(batch.tidx[0])
    compiler = DrainCompiler(state=state, builder=builder, gates=None)
    return {"na": na, "table": table, "row": row, "gd": to_device(gd_np),
            "fam": builder.groups.families(snap),
            "carry": initial_carry(na, to_device(gc_np)),
            "statics": compiler.surfaces.get(na, table, (row,))[0],
            "mode": compiler._wave_same_mode(row)}


@pytest.fixture(scope="module")
def affinity_cluster():
    """Host-side inputs at the 5000-node bucket for a self-affine required
    zone affinity row (the SchedulingPodAffinity template: every node in
    one zone) with resident affinity pods: the plan program's scan and
    fold over the ipa_req and ipa_score families."""
    from kubernetes_tpu.backend.cache import Cache, Snapshot
    from kubernetes_tpu.compiler import DrainCompiler
    from kubernetes_tpu.ops.groups import to_device
    from kubernetes_tpu.ops.program import initial_carry, pod_rows_from_batch
    from kubernetes_tpu.state.batch import BatchBuilder
    from kubernetes_tpu.state.tensorize import ClusterState
    from kubernetes_tpu.testing.wrappers import make_node, make_pod

    zone = "topology.kubernetes.io/zone"

    def peer(name, ns):
        return (make_pod(name, ns).req({"cpu": "100m", "memory": "500Mi"})
                .label("foo", "")
                .pod_affinity(zone, {"foo": ""},
                              namespaces=("sched-1", "sched-0")))

    cache = Cache()
    for i in range(NODES):
        cache.add_node(make_node(f"n{i}").capacity(
            {"cpu": 4, "memory": "32Gi", "pods": 110})
            .label(zone, "zone1").obj())
    for i in range(8):
        cache.add_pod(peer(f"init-{i}", "sched-0").node(f"n{i}").obj())
    snap = Snapshot()
    cache.update_snapshot(snap)
    state = ClusterState()
    state.apply_snapshot(snap, full=True)
    builder = BatchBuilder(state)
    batch = builder.build([peer(f"p{i}", "sched-1").obj()
                           for i in range(512)], pad_to=512)
    na = state.device_arrays()
    gd_np, gc_np = builder.groups.build_dev(snap)
    _xs, table = pod_rows_from_batch(batch)
    row = int(batch.tidx[0])
    compiler = DrainCompiler(state=state, builder=builder, gates=None)
    return {"na": na, "table": table, "row": row, "gd": to_device(gd_np),
            "fam": builder.groups.families(snap),
            "carry": initial_carry(na, to_device(gc_np)),
            "statics": compiler.surfaces.stacked(na, table, (row, row)),
            "mode": compiler._wave_same_mode(row)}


def _uniform_lkj(L: int, n_rows: int):
    """Scheduler._uniform_shape / _gang_dispatch's (L, K, J) choice."""
    from kubernetes_tpu.state.tensorize import pow2_at_least
    K = min(L, n_rows)
    J = min(max(pow2_at_least(4 * L // pow2_at_least(NODES) + 4), 8), L + 1)
    return L, K, J


def _lower(kernel: str, c: dict, spec):
    """(jitted fn).lower(...) for `kernel` with the TPU branch's statics:
    donation on, exactly as the scheduler dispatches it on a chip."""
    import jax
    from kubernetes_tpu.compiler import DrainCompiler
    from kubernetes_tpu.ops import program as P
    from kubernetes_tpu.ops.gang import GangXs, _run_gang_scan_fn

    cfg = P.ScoreConfig()
    na, batch = c["na"], c["batch"]
    n_rows = na.cap.shape[0]
    xone = P.PodXs(valid=np.bool_(True), sig=np.int32(batch.sig[0]),
                   tidx=np.int32(batch.tidx[0]))
    if kernel == "run_batch":
        return P._run_batch_fn(True).lower(
            cfg, spec(na), spec(c["carry"]), spec(c["xs"]),
            spec(c["table"]), spec(c["gd"]), c["fam"], None)
    if kernel == "run_uniform":
        return P._run_uniform_jit.lower(
            cfg, spec(na), spec(c["carry0"]), spec(xone), spec(c["table"]),
            spec(np.int32(BATCH)), *_uniform_lkj(BATCH, n_rows))
    if kernel == "scatter_rows":
        rows = jax.tree_util.tree_map(lambda a: a[:64], na)
        return P._scatter_rows_jit.lower(
            spec(na), spec(np.zeros(64, np.int32)), spec(rows))
    if kernel.startswith("retire_pods"):
        # one deleted signature row (Scheduler._fold_retires's S bucket),
        # out of an inter-pod carry or out of the lean one
        a = c.get("ipa")
        carry = a["carry"] if a else c["carry0"]
        gd, fam = (spec(a["gd"]), a["fam"]) if a else (None, None)
        n_rows, width = carry.used.shape
        rt = P.Retire(wt=np.zeros(1, np.int32),
                      cnt=np.zeros((1, n_rows), np.int32),
                      req=np.zeros((1, width), np.int64),
                      nonzero_req=np.zeros((1, 2), np.int64),
                      port_ids=np.zeros((1, c["table"].port_ids.shape[1]),
                                        np.int32))
        return P._retire_pods_jit.lower(spec(carry), spec(rt), gd, fam)
    if kernel == "run_wave":
        # Scheduler._wave_dispatch's statics for a 512-pod anti span
        a = c["ipa"]
        _mode, anti_term = a["mode"]
        return P._run_wave_same_fn(True).lower(
            cfg, spec(a["na"]), spec(a["carry"]),
            spec(np.ones(BATCH_WAVE, bool)), spec(a["table"]),
            spec(np.int32(a["row"])), spec(a["gd"]), spec(a["statics"]),
            BATCH_WAVE, 1, BATCH_WAVE, a["fam"], False, anti_term, True)
    if kernel == "run_plan":
        # Scheduler._wavescan_dispatch's statics for a 512-pod span of one
        # row: the signature set padded to the lattice's 2, no taints or
        # node affinity (static normalisation)
        a = c["ipa"]
        xs = P.WaveXs(valid=np.ones(BATCH_WAVE, bool),
                      widx=np.zeros(BATCH_WAVE, np.int32))
        return P._run_plan_fn(True).lower(
            cfg, spec(a["na"]), spec(a["carry"]), spec(xs),
            spec(a["table"]), spec(np.array([a["row"]] * 2, np.int32)),
            spec(a["gd"]), spec(a["statics"]), a["fam"], False, True, False)
    assert kernel == "run_gang"
    # the scan tier: the uniform tier shares run_uniform's _uniform_core
    row = int(batch.tidx[0])
    compiler = DrainCompiler(state=c["state"], builder=c["builder"],
                             gates=None)
    statics = compiler.surfaces.stacked(na, c["table"], (row,))
    xs = GangXs(valid=np.ones(GANG, bool), tidx=np.full(GANG, row, np.int32),
                widx=np.zeros(GANG, np.int32))
    return _run_gang_scan_fn(True).lower(
        cfg, spec(na), spec(c["carry0"]), spec(xs), spec(c["table"]),
        spec(np.array([row], np.int32)), spec(np.int32(GANG)),
        spec(np.zeros(n_rows, np.int32)), spec(statics), 0)


@pytest.mark.parametrize("kernel", ["run_batch", "run_uniform",
                                    "scatter_rows", "run_gang", "run_wave",
                                    "run_plan", "retire_pods",
                                    "retire_pods_anti",
                                    "retire_pods_affinity"])
def test_kernel_compiles_for_v5e(kernel, one_chip, no_persistent_cache,
                                 cluster, request):
    import jax

    if kernel in ("run_wave", "retire_pods_anti"):
        cluster = dict(cluster, ipa=request.getfixturevalue("anti_cluster"))
    elif kernel in ("run_plan", "retire_pods_affinity"):
        cluster = dict(cluster,
                       ipa=request.getfixturevalue("affinity_cluster"))
        assert cluster["ipa"]["mode"] == (None, -1)     # off the merge wave
        assert cluster["ipa"]["fam"].ipa_req and cluster["ipa"]["fam"].ipa_score

    def spec(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                           sharding=one_chip), tree)

    compiled = _lower(kernel, cluster, spec).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < HBM_BYTES, (kernel, used)


@pytest.mark.parametrize("case,workload,donating_kernels", [
    ("SchedulingBasic", "500Nodes_1000Pods", set()),
    ("TopologySpreading", "500Nodes", {"run_wave"}),
    ("GangTraining", "500Nodes", set()),
])
def test_device_path_with_donation(case, workload, donating_kernels,
                                   monkeypatch):
    """The device path's accelerator branch (donated carries) on the CPU:
    chip_smoke.py's cases at their 500-node shapes, every drain audited,
    nothing on the host, zero divergence."""
    import jax
    sys.path.insert(0, REPO)
    import chip_smoke
    from kubernetes_tpu.perf.ledger import GLOBAL as LEDGER

    donating = set()
    call = LEDGER.measured_call

    def spy(kernel, fn, *args, donated=None, **kw):
        if donated is not None:
            donating.add(kernel)
        return call(kernel, fn, *args, donated=donated, **kw)

    misses = {k: r.donation_misses for k, r in LEDGER.kernels.items()}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(LEDGER, "measured_call", spy)
    facts, failures = chip_smoke.run_case(case, workload)
    assert failures == [], facts
    assert facts["bound"] == facts["pods"] > 0
    assert facts["device_fallbacks"] == facts["host_scheduled"] == 0
    assert facts["audited"] >= 1 and facts["divergence_total"] == 0
    assert donating_kernels <= donating
    # every donated carry was consumed by its call
    assert {k: r.donation_misses for k, r in LEDGER.kernels.items()
            if r.donation_misses != misses.get(k, 0)} == {}


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_chip_smoke_refuses_cpu(where, tmp_path):
    """No TPU: non-zero exit and no result line — from the repo, and from
    a directory holding chip_smoke.py and nothing else of the repo."""
    script = os.path.join(REPO, "chip_smoke.py")
    if where == "alone":
        with open(script) as src:
            (tmp_path / "chip_smoke.py").write_text(src.read())
        script = str(tmp_path / "chip_smoke.py")
    out = subprocess.run([sys.executable, script], cwd=tmp_path,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
