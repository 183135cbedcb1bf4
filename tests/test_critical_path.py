"""Critical-path observatory + device cost model (ISSUE 20).

Covers the full stack the tentpole ships:

- `attribute_drain`: the verdict argmax over the CAUSES taxonomy, the
  comms-share split of the device window, the CAUSES-order tiebreak,
  the all-zero idle fallback, and the binding chain's segments;
- `aggregate` / `ceiling_factor`: the window histogram, the
  dominant-by-seconds (not modal) rule, and the headroom projection
  with its 100x cap;
- `phase_shares`: THE one stage-share implementation bench.py's
  phase_pct/host_share summary and the pipeline occupancy block both
  call (the ISSUE 20 unification bugfix) — plus the live-pipeline
  agreement regression;
- `attribute_delta`: per-drain-normalized differential attribution
  (tools/bench_compare.py --attribute);
- the device cost model end to end: a forced fresh compile lands
  XLA/host-estimated flops+bytes rows in `cost_view()` and the
  /debug/kernels snapshot;
- verdict stamping end to end: FlightRecords carry `criticalPath`, the
  scheduler_critical_path_seconds / scheduler_bottleneck_drains_total
  families move, and the gate off means no stamp, no movement, 404;
- /debug/criticalpath over a live SchedulerServer (last-N window +
  aggregate, ?limit=N, 404 with the gate off);
- stall attribution under the streaming pipeline (ISSUE 20 satellite):
  backpressure in EACH direction yields a `backpressure` verdict whose
  stall seconds are conserved against the pipeline's own stall clock
  and consistent with scheduler_pipeline_backpressure_total, while
  lock-step drains can NEVER carry one;
- the slow-marked throughput gate: CriticalPathObservatory ON within
  5% of OFF at 5k nodes (the ISSUE 13/14 gate shape).
"""

import json
import os
import random
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from kubernetes_tpu.backend.apiserver import APIServer  # noqa: E402
from kubernetes_tpu.config import KubeSchedulerConfiguration  # noqa: E402
from kubernetes_tpu.perf import costmodel  # noqa: E402
from kubernetes_tpu.perf.costmodel import (CostModel,  # noqa: E402
                                           classify, host_estimate,
                                           modeled_seconds)
from kubernetes_tpu.perf.critical_path import (CAUSES,  # noqa: E402
                                               aggregate, attribute_delta,
                                               attribute_drain,
                                               ceiling_factor, phase_shares)
from kubernetes_tpu.perf.ledger import device_entries  # noqa: E402
from kubernetes_tpu.perf.observatory import GLOBAL as OBS  # noqa: E402
from kubernetes_tpu.pipeline import STAGES, StreamingPipeline  # noqa: E402
from kubernetes_tpu.scheduler import Scheduler  # noqa: E402
from kubernetes_tpu.server import SchedulerServer  # noqa: E402
from kubernetes_tpu.testing.wrappers import make_node, make_pod  # noqa: E402

SEED = 2099


# ---------------------------------------------------------------------------
# helpers (tests/test_pipeline.py idiom)


def _nodes(api, n=8, cpu=64, mem="128Gi"):
    for i in range(n):
        api.create_node(make_node(f"n{i}")
                        .capacity({"cpu": cpu, "memory": mem, "pods": 80})
                        .zone(f"z{i % 3}").obj())


def _specs(n, seed, prefix="p"):
    rng = random.Random(seed)
    return [(f"{prefix}{i}", "default", 250 * rng.randint(1, 6),
             512 * rng.randint(1, 4)) for i in range(n)]


def _pods(specs):
    return [make_pod(name, namespace=ns).req(
        {"cpu": f"{cpu}m", "memory": f"{mem}Mi"}).obj()
        for name, ns, cpu, mem in specs]


def _sched(client, batch_size=64, **kw):
    sched = Scheduler(client, batch_size=batch_size, **kw)
    sched.dispatcher.sleep = lambda _s: None
    return sched


def _await(cond, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.002)
    return False


def _get(port, path):
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=5) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def _warm(api, sched, *seeds):
    """Schedule the stall test's pod shapes once, lock-step, before the
    pipeline starts: a drain that compiles is device-bound and out-votes
    the stall it carries."""
    for seed in seeds:
        api.create_pods(_pods(_specs(16, seed, prefix=f"w{seed}-")))
        sched.schedule_pending()


def _verdicts(sched):
    return [d["criticalPath"] for d in sched.flight.dump()
            if d.get("criticalPath")]


# ---------------------------------------------------------------------------
# attribute_drain


class TestAttributeDrain:
    def test_verdict_is_argmax_over_causes(self):
        cp = attribute_drain({"host_build": 2.0, "device_dispatch": 1.0,
                              "readback": 0.25, "commit": 0.5})
        assert cp["verdict"] == "host_build"
        assert cp["causes"] == {"host_build": 2.0, "device_compute": 1.0,
                                "device_comms": 0.0, "commit": 0.5,
                                "backpressure": 0.0, "idle": 0.25}
        assert set(cp["causes"]) == set(CAUSES)

    def test_comms_share_splits_the_device_window(self):
        cp = attribute_drain({"device_dispatch": 1.0}, comms_share=0.6)
        assert cp["causes"]["device_comms"] == pytest.approx(0.6)
        assert cp["causes"]["device_compute"] == pytest.approx(0.4)
        assert cp["verdict"] == "device_comms"
        # out-of-range shares clamp instead of inventing negative time
        hi = attribute_drain({"device_dispatch": 1.0}, comms_share=1.5)
        assert hi["causes"]["device_comms"] == pytest.approx(1.0)
        assert hi["causes"]["device_compute"] == 0.0
        lo = attribute_drain({"device_dispatch": 1.0}, comms_share=-3.0)
        assert lo["causes"]["device_compute"] == pytest.approx(1.0)

    def test_exact_tie_breaks_in_causes_order(self):
        cp = attribute_drain({"host_build": 1.0, "commit": 1.0})
        assert cp["verdict"] == "host_build"
        cp = attribute_drain({"commit": 1.0, "readback": 1.0})
        assert cp["verdict"] == "commit"

    def test_all_zero_record_is_idle(self):
        cp = attribute_drain({})
        assert cp["verdict"] == "idle"
        assert all(s == 0.0 for s in cp["causes"].values())
        assert cp["chain"] == []

    def test_backpressure_seconds_become_the_verdict(self):
        cp = attribute_drain({"host_build": 0.01, "commit": 0.02},
                             backpressure_s=0.5)
        assert cp["verdict"] == "backpressure"
        assert cp["causes"]["backpressure"] == pytest.approx(0.5)
        spans = {seg["span"]: seg for seg in cp["chain"]}
        assert spans["backpressure_stall"]["cause"] == "backpressure"
        assert spans["backpressure_stall"]["seconds"] == pytest.approx(0.5)

    def test_chain_segments_and_residuals(self):
        phases = {"host_build": 0.10, "host_snapshot": 0.03,
                  "host_tensorize": 0.05, "device_dispatch": 0.20,
                  "readback": 0.04, "commit": 0.06}
        kernels = {"run_uniform": 0.12, "run_wave": 0.05}
        cp = attribute_drain(phases, kernels=kernels)
        spans = {seg["span"]: seg for seg in cp["chain"]}
        # named host children + the residual cover host_build exactly
        assert spans["host_snapshot"]["cause"] == "host_build"
        assert spans["host_other"]["seconds"] == pytest.approx(0.02)
        # kernel lanes + device_other cover device_dispatch exactly
        assert spans["kernel:run_uniform"]["cause"] == "device_compute"
        assert spans["device_other"]["seconds"] == pytest.approx(0.03)
        assert spans["readback"]["cause"] == "idle"
        assert spans["commit"]["cause"] == "commit"
        # zero segments are dropped: no host_group_seed / host_cache rows
        assert "host_group_seed" not in spans
        assert "host_cache" not in spans
        assert all(seg["seconds"] > 0 for seg in cp["chain"])
        # a comms-dominated drain tags the kernel lanes device_comms
        comms = attribute_drain(phases, kernels=kernels, comms_share=0.9)
        spans = {seg["span"]: seg for seg in comms["chain"]}
        assert spans["kernel:run_wave"]["cause"] == "device_comms"


# ---------------------------------------------------------------------------
# aggregate / ceiling_factor


class TestAggregate:
    def test_dominant_is_by_seconds_not_modal(self):
        # two quick host_build drains must not outvote one giant commit
        vs = [attribute_drain({"host_build": 0.01}),
              attribute_drain({"host_build": 0.01}),
              attribute_drain({"commit": 1.0})]
        agg = aggregate(vs)
        assert agg["drains"] == 3
        assert agg["verdicts"] == {"commit": 1, "host_build": 2}
        assert agg["dominant"] == "commit"
        # ceiling: 1.02 total / 0.02 rest = 51x
        assert agg["ceiling_factor"] == pytest.approx(51.0, rel=1e-3)

    def test_empty_and_malformed_entries(self):
        agg = aggregate([])
        assert agg["drains"] == 0 and agg["verdicts"] == {}
        assert "dominant" not in agg and "ceiling_factor" not in agg
        agg = aggregate([None, {}, {"verdict": ""},
                         attribute_drain({"commit": 0.5})])
        assert agg["drains"] == 1
        assert agg["dominant"] == "commit"

    def test_ceiling_factor_formula_and_cap(self):
        causes = {"host_build": 3.0, "commit": 1.0}
        assert ceiling_factor(causes, "host_build") == pytest.approx(4.0)
        # the dominant cause IS the cycle: capped, not infinite
        assert ceiling_factor({"commit": 1.0}, "commit") == 100.0
        assert ceiling_factor({}, "commit") == 1.0


# ---------------------------------------------------------------------------
# phase_shares — the ONE share implementation (ISSUE 20 satellite)


class TestPhaseShares:
    def test_lockstep_shares_sum_to_one(self):
        parts = {"host_build": 0.6, "device": 0.3, "commit": 0.1}
        out = phase_shares(parts)
        assert out["total"] == pytest.approx(1.0)
        assert out["occupancy"] == pytest.approx(1.0)
        assert sum(out["shares"].values()) == pytest.approx(1.0, abs=1e-3)
        assert out["shares"]["host_build"] == pytest.approx(0.6)
        assert out["host_share"] == pytest.approx(0.7)

    def test_wall_denominator_allows_overlap(self):
        # a pipeline window: stages overlap, so busy sums past the wall
        parts = {"ingest": 0.8, "device": 0.9, "commit": 0.5}
        out = phase_shares(parts, wall=1.0)
        assert out["occupancy"] == pytest.approx(2.2)
        assert out["shares"]["device"] == pytest.approx(0.9)
        # zero/None wall falls back to the segments' own sum
        assert phase_shares(parts, wall=0.0)["occupancy"] == 1.0

    def test_bench_and_pipeline_surfaces_agree(self):
        """The regression the satellite exists for: bench.py's
        phase_pct/host_share and the pipeline occupancy block must
        derive from the SAME math over the same window."""
        parts = {"host_build": 0.25, "device": 0.5, "commit": 0.25}
        bench = phase_shares(parts)                 # bench.py summary path
        pipe = phase_shares(parts, wall=1.0)        # pipeline stats path
        # same window (wall == busy sum) → identical shares + host share
        assert bench["shares"] == pipe["shares"]
        assert bench["host_share"] == pipe["host_share"]
        # and bench's percentage rendering is a pure rescale of the same
        # fractions, not a second implementation
        phase_pct = {k: round(100.0 * v, 1)
                     for k, v in bench["shares"].items()}
        assert phase_pct == {"host_build": 25.0, "device": 50.0,
                             "commit": 25.0}

    def test_live_pipeline_stats_use_phase_shares(self):
        """End to end: the /debug/pipeline occupancy block's shares are
        busy/wall under the shared helper — shares, occupancy and busy
        seconds must stay mutually consistent on a real window."""
        api = APIServer()
        _nodes(api)
        sched = _sched(api)
        sched.prime()
        pipe = StreamingPipeline(sched)
        pipe.start()
        try:
            pipe.feed(_pods(_specs(48, SEED)), close=True)
            pipe.drain(timeout=60.0)
        finally:
            pipe.stop()
        st = pipe.stats()
        assert not pipe.errors
        assert set(st["busyShares"]) == set(STAGES)
        busy_sum = sum(st["busySeconds"].values())
        assert busy_sum > 0 and st["occupancy"] > 0
        for stage in STAGES:
            # share[s]/occupancy == busy[s]/sum(busy): both ratios come
            # from the one phase_shares call over the same wall
            assert st["busyShares"][stage] / st["occupancy"] == \
                pytest.approx(st["busySeconds"][stage] / busy_sum, abs=0.02)


# ---------------------------------------------------------------------------
# attribute_delta


class TestAttributeDelta:
    def test_names_the_cause_that_moved_per_drain(self):
        base = aggregate([attribute_drain({"host_build": 0.1,
                                           "commit": 0.1})
                          for _ in range(4)])
        # twice the drains — per-drain normalization must see through it
        new = aggregate([attribute_drain({"host_build": 0.1,
                                          "commit": 0.3})
                         for _ in range(8)])
        moved = attribute_delta(base, new)
        assert moved["cause"] == "commit"
        assert moved["base_s"] == pytest.approx(0.1)
        assert moved["new_s"] == pytest.approx(0.3)
        assert moved["ratio"] == pytest.approx(3.0)
        assert moved["deltas"]["host_build"]["delta_s"] == pytest.approx(0.0)
        assert set(moved["deltas"]) == set(CAUSES)

    def test_empty_when_either_side_lacks_drains(self):
        some = aggregate([attribute_drain({"commit": 0.1})])
        assert attribute_delta({}, some) == {}
        assert attribute_delta(some, {"drains": 0}) == {}
        assert attribute_delta(None, None) == {}


# ---------------------------------------------------------------------------
# device cost model


@pytest.fixture
def fresh_obs():
    OBS.reset()
    OBS.enable(True)
    OBS.enable_cost_model(True)
    yield OBS
    OBS.reset()
    OBS.enable(True)
    OBS.enable_cost_model(True)


class TestCostModelUnits:
    def test_host_estimate_scales_with_cells(self):
        import numpy as np
        a = np.ones((10, 8), np.float32)
        flops, nbytes = host_estimate("run_batch", (a,))
        fpc, bmult = device_entries()["run_batch"].cost
        assert flops == pytest.approx(80 * fpc)
        assert nbytes == pytest.approx(a.nbytes * bmult)
        assert host_estimate("no_such_kernel", (a,)) == (0.0, 0.0)

    def test_host_estimate_skips_static_args(self):
        """A sharded entry's args lead with its Mesh, whose `shape` is a
        {axis: size} dict: only array leaves count (nested NamedTuples
        included)."""
        import jax
        import numpy as np
        from kubernetes_tpu.parallel.sharding import make_mesh
        a = np.ones((10, 8), np.float32)
        b = np.ones((4,), np.int64)
        mesh = make_mesh(min(2, len(jax.devices())))
        flops, nbytes = host_estimate(
            "scatter_rows_sharded", (mesh, (a, (b,)), 3, "cfg"))
        fpc, bmult = device_entries()["scatter_rows_sharded"].cost
        assert flops == pytest.approx(84 * fpc)
        assert nbytes == pytest.approx((a.nbytes + b.nbytes) * bmult)

    def test_modeled_seconds_is_the_binding_wall(self):
        pf, pb = costmodel.peaks("cpu")
        # memory-bound shape: bytes wall dominates
        assert modeled_seconds(pf * 0.001, pb * 1.0, "cpu") == \
            pytest.approx(1.0)
        # compute-bound shape: flops wall dominates
        assert modeled_seconds(pf * 2.0, pb * 0.001, "cpu") == \
            pytest.approx(2.0)

    def test_peaks_keyed_by_device_kind(self):
        # the v5e's published peaks (Google Cloud "TPU v5e")
        assert costmodel.peaks("TPU v5 lite") == (197.0e12, 819.0e9)
        # an unlisted device kind is an error, never a default roofline
        with pytest.raises(KeyError, match="no roofline peaks"):
            costmodel.peaks("tpu")
        with pytest.raises(KeyError):
            modeled_seconds(1.0, 1.0, "Some Future Chip")

    def test_classify_ridge_and_comms(self):
        pf, pb = costmodel.peaks("cpu")
        ridge = pf / pb
        assert classify(ridge * 10.0, 1.0, "cpu") == "compute_bound"
        assert classify(ridge * 0.1, 1.0, "cpu") == "memory_bound"
        # the lane profile overrides intensity entirely
        assert classify(ridge * 10.0, 1.0, "cpu",
                        comms_share=costmodel.COMMS_BOUND_SHARE + 0.01) \
            == "comms_bound"

    def test_record_compile_once_per_plan_key(self):
        import jax
        import jax.numpy as jnp
        fn = jax.jit(lambda x: x * 2 + 1)
        x = jnp.ones((13, 7), jnp.float32)
        cm = CostModel()
        cm.record_compile("run_batch", fn, (x,), {})
        cm.record_compile("run_batch", fn, (x,), {})   # dedup: same key
        rows = cm.kernel_rows("run_batch")
        assert len(rows) == 1
        row = next(iter(rows.values()))
        assert row["source"] in ("xla", "host")
        assert row["flops"] >= 0.0 and row["bytes"] > 0.0
        assert cm.covered() == {"run_batch"}
        cm.reset()
        assert cm.covered() == set()


class TestCostModelEndToEnd:
    @pytest.mark.parametrize("donate", [False, True])
    def test_costing_waits_for_the_drain_window(self, fresh_obs, donate):
        """A compile inside a drain's capture window is costed at
        end_drain, outside the device_dispatch span — a donated
        (deleted) argument included."""
        import jax
        import jax.numpy as jnp
        fn = jax.jit(lambda x: x * 2 + 1,
                     donate_argnums=(0,) if donate else ())
        x = jnp.ones((13, 7), jnp.float32)
        fresh_obs.begin_drain()
        fn(x)
        assert x.is_deleted() == donate
        fresh_obs.on_compile("run_batch", fn, (x,), {})
        assert "run_batch" not in fresh_obs.costs.covered()
        fresh_obs.end_drain()
        row = next(iter(fresh_obs.costs.kernel_rows("run_batch").values()))
        assert row["source"] == "xla" and row["bytes"] > 0.0

    def test_fresh_compiles_land_cost_rows(self, fresh_obs):
        """A drain whose executables are freshly minted (cleared jit
        cache) must land cost rows for its kernels: cost_view() carries
        flops/bytes/ai/bound/source per plan, and the /debug/kernels
        snapshot mirrors them with the gate flag."""
        import jax
        jax.clear_caches()     # force delta > 0 → on_compile fires
        api = APIServer()
        _nodes(api, n=12)
        sched = _sched(api)
        api.create_pods(_pods(_specs(48, SEED + 1)))
        assert sched.schedule_pending() == 48

        view = sched.observatory.cost_view()
        assert view, "no cost rows despite fresh compiles"
        for kernel, rows in view.items():
            assert rows
            for row in rows:
                for fld in ("plan", "flops", "bytes", "ai", "modeledMs",
                            "measuredP50Ms", "achievedFraction", "bound",
                            "source"):
                    assert fld in row, (kernel, fld)
                assert row["source"] in ("xla", "host")
                assert row["bound"] in ("compute_bound", "memory_bound",
                                        "comms_bound")
                assert row["flops"] >= 0.0 and row["bytes"] >= 0.0
        snap = sched.observatory.snapshot()
        assert snap["costModelEnabled"] is True
        costed = [k for k, v in snap["kernels"].items() if v["costModel"]]
        assert set(costed) == set(view)


# ---------------------------------------------------------------------------
# verdict stamping end to end + metric families


class TestVerdictEndToEnd:
    def test_drains_carry_critical_path_and_metrics_move(self):
        api = APIServer()
        _nodes(api, n=12)
        sched = _sched(api)
        assert sched.critical_path_enabled    # Beta gate defaults on
        api.create_pods(_pods(_specs(96, SEED + 2)))
        assert sched.schedule_pending() == 96

        cps = _verdicts(sched)
        assert cps, "no drain carried a criticalPath stamp"
        for cp in cps:
            assert cp["verdict"] in CAUSES
            assert set(cp["causes"]) == set(CAUSES)
            # lock-step operation: backpressure is structurally zero
            assert cp["causes"]["backpressure"] == 0.0
            assert cp["chain"], "a committed drain must bind on something"
        m = sched.metrics
        # the verdict counter ticks once per stamped drain
        assert sum(m.bottleneck_drains.value(c) for c in CAUSES) == len(cps)
        assert m.bottleneck_drains.value("backpressure") == 0.0
        # the seconds family sums what the stamps attributed
        for cause in CAUSES:
            want = sum(cp["causes"][cause] for cp in cps)
            assert m.critical_path_seconds.value(cause) == \
                pytest.approx(want, abs=1e-5)
        assert sum(m.critical_path_seconds.value(c) for c in CAUSES) > 0

    def test_gate_off_means_no_stamp_no_movement(self):
        cfg = KubeSchedulerConfiguration(feature_gates={
            "CriticalPathObservatory": False})
        api = APIServer()
        _nodes(api)
        try:
            sched = _sched(api, config=cfg)
            assert not sched.critical_path_enabled
            api.create_pods(_pods(_specs(32, SEED + 3)))
            assert sched.schedule_pending() == 32
            assert _verdicts(sched) == []
            for d in sched.flight.dump():
                assert d["criticalPath"] == {}
            m = sched.metrics
            for cause in CAUSES:
                assert m.critical_path_seconds.value(cause) == 0.0
                assert m.bottleneck_drains.value(cause) == 0.0
        finally:
            # the gate-off ctor disabled the process-global cost model
            OBS.enable_cost_model(True)


# ---------------------------------------------------------------------------
# /debug/criticalpath


class TestDebugEndpoint:
    def test_serves_window_and_aggregate(self):
        api = APIServer()
        _nodes(api, n=12)
        sched = _sched(api)
        api.create_pods(_pods(_specs(96, SEED + 4)))
        assert sched.schedule_pending() == 96
        n_stamped = len(_verdicts(sched))
        assert n_stamped >= 2

        srv = SchedulerServer(sched).start()
        try:
            code, body = _get(srv.port, "/debug/criticalpath")
            assert code == 200
            out = json.loads(body)
            assert len(out["drains"]) == n_stamped
            for row in out["drains"]:
                assert row["criticalPath"]["verdict"] in CAUSES
                assert {"seq", "drainId", "pods", "profile"} <= set(row)
            agg = out["aggregate"]
            assert agg["drains"] == n_stamped
            assert agg["dominant"] in CAUSES
            assert agg["ceiling_factor"] >= 1.0
            # ?limit=N windows the dump to the most recent N
            code, body = _get(srv.port, "/debug/criticalpath?limit=1")
            assert code == 200
            out = json.loads(body)
            assert len(out["drains"]) == 1
            assert out["aggregate"]["drains"] == 1
            # the endpoint advertises itself at the /debug index
            code, body = _get(srv.port, "/debug")
            assert code == 200
            assert "/debug/criticalpath" in body
        finally:
            srv.stop()

    def test_404_with_gate_off(self):
        cfg = KubeSchedulerConfiguration(feature_gates={
            "CriticalPathObservatory": False})
        api = APIServer()
        _nodes(api)
        try:
            sched = _sched(api, config=cfg)
            srv = SchedulerServer(sched).start()
            try:
                code, body = _get(srv.port, "/debug/criticalpath")
                assert code == 404
                assert "CriticalPathObservatory" in body
            finally:
                srv.stop()
        finally:
            OBS.enable_cost_model(True)


# ---------------------------------------------------------------------------
# stall attribution under the streaming pipeline (ISSUE 20 satellite)


class TestStallAttribution:
    def _assert_stall_attributed(self, sched, pipe, stage):
        cps = _verdicts(sched)
        assert cps
        attributed = sum(cp["causes"]["backpressure"] for cp in cps)
        total = pipe.backpressure_stall_seconds()
        # conservation: every attributed stall second came off the
        # pipeline's own stall clock (per-drain rounding is 1e-6)
        assert 0.0 < attributed <= total + 1e-4 * len(cps)
        stalls = pipe.stats()["backpressureStallSeconds"]
        assert stalls[stage] > 0.0
        # the stall was real wall, not counter noise: bounded by the
        # wait count times the poll horizon (poll_s * 10 per wait)
        assert stalls[stage] <= \
            pipe._backpressure[stage] * pipe.poll_s * 10 + 1.0
        # the blocked window dominates a sub-ms drain: a backpressure
        # verdict must surface
        assert any(cp["verdict"] == "backpressure" for cp in cps)
        # and the stamps agree with the metric families
        m = sched.metrics
        assert m.pipeline_backpressure.value(stage) >= 1.0
        assert m.critical_path_seconds.value("backpressure") == \
            pytest.approx(attributed, abs=1e-4)
        assert m.bottleneck_drains.value("backpressure") >= 1.0

    def test_ingest_stall_lands_backpressure_verdict(self):
        """Dispatch depth caps ingest: the stalled window must land on a
        committed drain as `backpressure` cause seconds conserved
        against the pipeline's stall clock."""
        api = APIServer()
        _nodes(api)
        sched = _sched(api)
        sched.prime()
        _warm(api, sched, SEED + 5, SEED + 6)
        real_commit = sched.commit_ready
        sched.commit_ready = lambda limit=0: 0      # commits stall
        pipe = StreamingPipeline(sched, dispatch_depth=1)
        pipe.start()
        try:
            pipe.feed(_pods(_specs(16, SEED + 5)), close=True)
            blocked = threading.Thread(
                target=pipe.feed,
                args=(_pods(_specs(16, SEED + 6, prefix="q")),),
                kwargs={"close": True})
            blocked.start()
            assert _await(lambda: pipe._backpressure["ingest"] > 0), \
                "ingest never saw backpressure"
            time.sleep(0.05)      # let the stall clock accumulate wall
            sched.commit_ready = real_commit        # commits resume
            blocked.join(timeout=20.0)
            assert not blocked.is_alive()
            pipe.drain(timeout=30.0)
        finally:
            sched.commit_ready = real_commit
            pipe.stop()
        assert not pipe.errors
        self._assert_stall_attributed(sched, pipe, "ingest")

    def test_device_stall_lands_backpressure_verdict(self):
        """Commit backlog caps dispatch: same conservation, other
        direction."""
        api = APIServer()
        _nodes(api)
        sched = _sched(api)
        sched.prime()
        _warm(api, sched, SEED + 7, SEED + 8)
        real_flush = sched.dispatcher.flush
        sched.dispatcher.flush = lambda *a, **k: 0  # echo stalls
        pipe = StreamingPipeline(sched, commit_backlog_pods=1)
        pipe.start()
        try:
            pipe.feed(_pods(_specs(16, SEED + 7)), close=True)
            assert _await(lambda: len(sched.dispatcher) > 0), \
                "commit backlog never formed"
            blocked = threading.Thread(
                target=pipe.feed,
                args=(_pods(_specs(16, SEED + 8, prefix="q")),),
                kwargs={"close": True})
            blocked.start()
            assert _await(lambda: pipe._backpressure["device"] > 0), \
                "dispatch never saw commit-backlog backpressure"
            time.sleep(0.05)
            sched.dispatcher.flush = real_flush     # the echo drains
            blocked.join(timeout=20.0)
            assert not blocked.is_alive()
            pipe.drain(timeout=30.0)
        finally:
            sched.dispatcher.flush = real_flush
            pipe.stop()
        assert not pipe.errors
        self._assert_stall_attributed(sched, pipe, "device")

    def test_lockstep_drains_never_say_backpressure(self):
        """No pipeline → no backpressure cause, structurally: the
        attribution reads the pipeline's stall clock, and a lock-step
        scheduler has none."""
        api = APIServer()
        _nodes(api, n=12)
        sched = _sched(api)
        for chunk in range(4):
            api.create_pods(_pods(_specs(32, SEED + 9 + chunk,
                                         prefix=f"c{chunk}-")))
            sched.schedule_pending()
        cps = _verdicts(sched)
        assert len(cps) >= 4
        for cp in cps:
            assert cp["verdict"] != "backpressure"
            assert cp["causes"]["backpressure"] == 0.0
        # host-side causes carry the cycle (the ISSUE 20 acceptance
        # shape: host_build/idle/commit, never a stall)
        agg = aggregate(cps)
        assert agg["dominant"] in set(CAUSES) - {"backpressure"}
        assert sched.metrics.bottleneck_drains.value("backpressure") == 0.0


# ---------------------------------------------------------------------------
# overhead gate (slow tier)


@pytest.mark.slow
class TestCriticalPathOverheadGate:
    def test_overhead_within_5_percent_at_5k_nodes(self):
        """ISSUE 20 acceptance: SchedulingBasic-shaped 5k-node drains
        with CriticalPathObservatory ON (verdicts + cost model) stay
        within 5% of gate-OFF throughput (median of 3 measured passes
        each, warm shapes — the ISSUE 13/14 gate shape)."""

        def _feed_many(api, n, start=0):
            api.create_pods([make_pod(f"p{start + i}").req(
                {"cpu": "100m", "memory": "64Mi"}).obj() for i in range(n)])

        def one_pass(gate_on):
            cfg = KubeSchedulerConfiguration(feature_gates={
                "CriticalPathObservatory": gate_on})
            api = APIServer()
            sched = Scheduler(api, batch_size=8192, config=cfg)
            for i in range(5000):
                api.create_node(make_node(f"n{i}").capacity(
                    {"cpu": 32, "memory": "64Gi", "pods": 110}).obj())
            sched.prime()
            t0 = time.perf_counter()
            created = 0
            while created < 10000:
                _feed_many(api, 512, start=created)
                created += 512
                sched.schedule_pending(wait=False)
            sched.schedule_pending()
            dt = time.perf_counter() - t0
            assert sched.scheduled_count == created
            return created / dt

        try:
            one_pass(True)   # warm every executable outside the measurement
            off = sorted(one_pass(False) for _ in range(3))[1]
            on = sorted(one_pass(True) for _ in range(3))[1]
        finally:
            OBS.enable(True)
            OBS.enable_cost_model(True)
        assert on >= 0.95 * off, (
            f"critical-path overhead gate: on={on:.0f} off={off:.0f} pods/s "
            f"({on / off - 1:+.1%})")
