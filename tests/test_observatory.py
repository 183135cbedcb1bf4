"""Kernel observatory (ISSUE 14): per-dispatch device-time attribution.

Covers the full stack the tentpole ships:

- the streaming-histogram substrate (bounded buckets, plan-key overflow);
- the `CompileLedger.measured_call` compile/run split and its thread
  safety under concurrent dispatch;
- capture semantics (warm vs compiling routing, the per-drain device
  lane, checkpoint/delta);
- /debug/kernels over a live SchedulerServer, including the acceptance
  cross-check that a drain's per-kernel seconds decompose its
  device_dispatch phase wall;
- the profiler trace: `dispatch:<kernel>` annotations on the host plane,
  nested inside their drain's device_dispatch span;
- sharded-lane profiling on the 8-device test mesh;
- retrace_budget(0) holding over warm re-runs with the observatory ON;
- tools/kernel_sweep.py --self-test, and each entry's kernel label;
- the slow-marked throughput gate: observatory ON within 5% of OFF.
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

from kubernetes_tpu.analysis.rails import GLOBAL as RAILS  # noqa: E402
from kubernetes_tpu.backend.apiserver import APIServer  # noqa: E402
from kubernetes_tpu.config import KubeSchedulerConfiguration  # noqa: E402
from kubernetes_tpu.parallel.sharding import make_mesh  # noqa: E402
from kubernetes_tpu.perf.ledger import (GLOBAL as LEDGER,  # noqa: E402
                                        CompileLedger, KernelRecord,
                                        device_entries)
from kubernetes_tpu.perf.observatory import (GLOBAL as OBS,  # noqa: E402
                                             _KernelStats, _OVERFLOW_KEY,
                                             MAX_PLAN_KEYS, StreamingHist)
from kubernetes_tpu.scheduler import Scheduler  # noqa: E402
from kubernetes_tpu.server import SchedulerServer  # noqa: E402
from kubernetes_tpu.testing.wrappers import make_node, make_pod  # noqa: E402
from kubernetes_tpu.utils.tracing import Tracer  # noqa: E402

KERNELS = tuple(device_entries())


@pytest.fixture
def fresh_obs():
    """Zeroed process-global observatory; restored (re-enabled, zeroed)
    afterwards so absolute-count assertions don't see other tests'
    dispatches and vice versa."""
    OBS.reset()
    OBS.enable(True)
    yield OBS
    OBS.reset()
    OBS.enable(True)


def _mk(nodes=24, **kw):
    """Small drainable cluster with a REAL tracer (the scheduler default
    is NOOP_TRACER, which annotates no phase)."""
    api = APIServer()
    kw.setdefault("tracer", Tracer(slow_threshold_s=999.0, keep_recent=64))
    sched = Scheduler(api, batch_size=64, **kw)
    for i in range(nodes):
        api.create_node(
            make_node(f"n{i}")
            .capacity({"cpu": "8", "memory": "16Gi", "pods": 110})
            .zone(f"z{i % 4}")
            .label("kubernetes.io/hostname", f"n{i}").obj())
    return api, sched


def _feed(api, n, spread=0):
    pods = []
    for i in range(n):
        w = make_pod(f"p{i}").req({"cpu": "100m", "memory": "64Mi"})
        if i < spread:
            w = w.label("app", "obs").spread_constraint(
                1, "topology.kubernetes.io/zone", "ScheduleAnyway",
                {"app": "obs"})
        pods.append(w.obj())
    api.create_pods(pods)


def _get(port, path):
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=5) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


# ---------------------------------------------------------------------------
# streaming histograms


class TestStreamingHist:
    def test_observe_and_quantiles(self):
        h = StreamingHist()
        for _ in range(90):
            h.observe(0.001)
        for _ in range(10):
            h.observe(0.016)
        assert h.count == 100
        assert abs(h.sum - (90 * 0.001 + 10 * 0.016)) < 1e-9
        assert h.max == 0.016
        # p50 sits in the 1ms decade, p99 in the 16ms decade, and the
        # log2 lattice keeps each within ~sqrt(2) of the true value
        assert 0.0005 < h.quantile(0.50) < 0.002
        assert 0.008 < h.quantile(0.99) < 0.032
        assert h.quantile(0.50) <= h.quantile(0.90) <= h.quantile(0.99)

    def test_to_dict_contract(self):
        h = StreamingHist()
        h.observe(0.002)
        d = h.to_dict()
        assert set(d) == {"count", "seconds", "p50_ms", "p90_ms",
                          "p99_ms", "max_ms"}
        assert d["count"] == 1 and d["max_ms"] == 2.0

    def test_overflow_folds_into_last_bucket(self):
        h = StreamingHist()
        h.observe(1e9)  # absurd wall: beyond the ~67s last edge
        assert h.counts[-1] == 1
        assert h.quantile(0.99) > 0  # finite, not an IndexError

    def test_empty_quantile_is_zero(self):
        assert StreamingHist().quantile(0.99) == 0.0

    def test_plan_key_overflow_bounded(self):
        st = _KernelStats()
        for i in range(MAX_PLAN_KEYS + 8):
            st.plan_hist((i,)).observe(0.001)
        assert len(st.plans) == MAX_PLAN_KEYS + 1
        assert st.plans[_OVERFLOW_KEY].count == 8


# ---------------------------------------------------------------------------
# ledger compile/run split + thread safety


class _CompilingFn:
    """Mimics a jitted callable whose first call mints an executable."""

    def __init__(self):
        self.cache = 0

    def _cache_size(self):
        return self.cache

    def __call__(self, *a, **kw):
        if not self.cache:
            self.cache = 1
            time.sleep(0.002)
        return 0


class _WarmFn:
    """A jitted callable with its executable already minted."""

    def _cache_size(self):
        return 1

    def __call__(self, *a, **kw):
        return 0


class TestLedgerSplit:
    def test_compile_vs_run_seconds_split(self, fresh_obs):
        led = CompileLedger()
        fn = _CompilingFn()
        led.measured_call("run_batch", fn)
        led.measured_call("run_batch", fn)
        rec = led.kernels["run_batch"]
        assert rec.calls == 2 and rec.compiles == 1
        assert rec.compile_seconds > 0
        assert rec.run_calls == 1 and rec.run_seconds >= 0
        # the observatory saw both, routed by compile flag
        st = fresh_obs.kernels["run_batch"]
        assert st.dispatches == 2
        assert st.compile_calls == 1 and st.hist.count == 1

    def test_fn_without_cache_probe_counts_warm(self, fresh_obs):
        led = CompileLedger()
        led.measured_call("run_uniform", lambda: 7)
        rec = led.kernels["run_uniform"]
        assert rec.compiles == 0 and rec.run_calls == 1

    def test_compile_overhead_property(self):
        rec = KernelRecord(calls=3, compiles=1, compile_seconds=2.0,
                           run_calls=2, run_seconds=0.2)
        assert abs(rec.compile_overhead_seconds - 1.9) < 1e-9
        # no warm sample yet: the whole compiling wall is overhead
        rec2 = KernelRecord(calls=1, compiles=1, compile_seconds=2.0)
        assert rec2.compile_overhead_seconds == 2.0

    def test_measured_call_thread_safe(self, fresh_obs):
        led = CompileLedger()
        fn = _WarmFn()
        n_threads, n_calls = 8, 200

        def hammer():
            for _ in range(n_calls):
                led.measured_call("run_batch", fn)

        threads = [threading.Thread(target=hammer)
                   for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        rec = led.kernels["run_batch"]
        total = n_threads * n_calls
        assert rec.calls == total
        assert rec.run_calls == total and rec.compiles == 0
        st = fresh_obs.kernels["run_batch"]
        assert st.dispatches == total and st.hist.count == total


# ---------------------------------------------------------------------------
# observatory capture semantics


class TestObservatoryCapture:
    def test_warm_vs_compiled_routing(self, fresh_obs):
        OBS.on_call("run_wave", 0.0, 0.004, False, ())
        OBS.on_call("run_wave", 1.0, 2.500, True, ())
        st = OBS.kernels["run_wave"]
        assert st.dispatches == 2 and st.compile_calls == 1
        assert st.hist.count == 1 and abs(st.hist.sum - 0.004) < 1e-9

    def test_disabled_gate_drops_calls(self, fresh_obs):
        OBS.enable(False)
        OBS.on_call("run_wave", 0.0, 0.004, False, ())
        OBS.enable(True)
        assert OBS.kernels["run_wave"].dispatches == 0

    def test_drain_window_captures_in_order(self, fresh_obs):
        OBS.on_call("run_plan", 0.0, 0.001, False, ())   # outside: dropped
        OBS.begin_drain()
        OBS.on_call("run_uniform", 1.0, 0.010, False, ())
        OBS.on_call("run_wave", 2.0, 0.020, True, ())
        events = OBS.end_drain()
        assert [e[0] for e in events] == ["run_uniform", "run_wave"]
        assert OBS.end_drain() == []  # window closed

    def test_lane_seconds_and_spans(self, fresh_obs):
        events = [("run_uniform", 0.0, 0.5, False),
                  ("run_uniform", 1.0, 0.25, False),
                  ("run_wave", 2.0, 0.125, True)]
        assert OBS.lane_seconds(events) == {"run_uniform": 0.75,
                                            "run_wave": 0.125}
        assert OBS.lane_seconds([]) == {}

    def test_shape_keys_split_plan_histograms(self, fresh_obs):
        OBS.on_call("run_batch", 0.0, 0.001, False, (np.zeros((4, 2)), 3))
        OBS.on_call("run_batch", 0.0, 0.001, False, (np.zeros((8, 2)), 3))
        OBS.on_call("run_batch", 0.0, 0.001, False, (np.zeros((4, 2)), 3))
        st = OBS.kernels["run_batch"]
        assert len(st.plans) == 2
        assert sorted(h.count for h in st.plans.values()) == [1, 2]

    def test_checkpoint_delta(self, fresh_obs):
        OBS.on_call("diagnose", 0.0, 0.002, False, ())
        chk = OBS.checkpoint()
        for _ in range(3):
            OBS.on_call("diagnose", 0.0, 0.004, False, ())
        delta = OBS.delta_since(chk)
        assert set(delta) == {"diagnose"}
        d = delta["diagnose"]
        assert d["calls"] == 3 and d["dispatches"] == 3
        assert abs(d["seconds"] - 0.012) < 1e-9
        assert d["p50_ms"] > 0

    def test_snapshot_preseeds_all_kernels(self, fresh_obs):
        snap = OBS.snapshot()
        assert set(snap["kernels"]) == set(KERNELS)
        assert snap["enabled"] is True and snap["deviceKind"] == "cpu"
        assert snap["shardLanes"] == {}

    def test_snapshot_top_plans_limit(self, fresh_obs):
        for i in range(7):
            OBS.on_call("run_gang", 0.0, 0.001 * (i + 1), False,
                        (np.zeros((i + 1,)),))
        snap = OBS.snapshot(top_plans=3)
        plans = snap["kernels"]["run_gang"]["plans"]
        assert len(plans) == 3
        # ranked by cumulative seconds: the slowest variants survive
        assert all(p["count"] == 1 for p in plans.values())

    def test_metrics_view_covers_all_kernels(self, fresh_obs):
        kernels, shard = OBS.metrics_view()
        assert set(kernels) == set(KERNELS)
        assert shard == {}

    def test_entry_kernels_cover_ledger(self):
        # an entry's kernel label is its function's name, except for the
        # two public wrappers named after what they do
        renamed = {e.fn.__name__: k for k, e in device_entries().items()
                   if e.fn.__name__ != k}
        assert renamed == {"diagnose_row": "diagnose",
                           "dry_run_select_victims": "dry_run"}


# ---------------------------------------------------------------------------
# /debug/kernels + the flight-record decomposition (acceptance)


class TestDebugKernels:
    def test_lists_all_thirteen_after_drain(self, fresh_obs):
        api, sched = _mk()
        _feed(api, 48, spread=12)
        sched.schedule_pending()
        srv = SchedulerServer(sched).start()
        try:
            code, body = _get(srv.port, "/debug/kernels")
            assert code == 200
            snap = json.loads(body)
            assert snap["enabled"] is True
            assert set(snap["kernels"]) == set(KERNELS)
            dispatched = {k: v for k, v in snap["kernels"].items()
                          if v["dispatches"]}
            assert dispatched, snap["kernels"]
            # the drain's mainline kernels ran and have run-time stats
            assert any(v["count"] > 0 or v["compileCalls"] > 0
                       for v in dispatched.values())
            code, body = _get(srv.port, "/debug/kernels?plans=1")
            assert code == 200
            snap = json.loads(body)
            assert all(len(v["plans"]) <= 1
                       for v in snap["kernels"].values())
        finally:
            srv.stop()

    def test_gate_off_404(self, fresh_obs):
        cfg = KubeSchedulerConfiguration(
            feature_gates={"KernelObservatory": False})
        api, sched = _mk(config=cfg)
        srv = SchedulerServer(sched).start()
        try:
            code, body = _get(srv.port, "/debug/kernels")
            assert code == 404 and "KernelObservatory" in body
        finally:
            srv.stop()

    def test_flight_kernels_decompose_device_phase(self, fresh_obs):
        """ISSUE 14 acceptance: a drain's per-kernel seconds cross-check
        against its device_dispatch phase span within 10%."""
        api, sched = _mk()
        _feed(api, 96, spread=24)
        sched.schedule_pending()
        recs = [r for r in sched.flight.dump()
                if r["kernels"] and r["phases"].get("device_dispatch")]
        assert recs, "no device drains recorded"
        rec = max(recs, key=lambda r: r["phases"]["device_dispatch"])
        ksum = sum(rec["kernels"].values())
        dev = rec["phases"]["device_dispatch"]
        assert set(rec["kernels"]) <= set(KERNELS)
        assert ksum <= dev * 1.02 + 1e-6, (ksum, dev)
        assert ksum >= 0.90 * dev, (ksum, dev)


# ---------------------------------------------------------------------------
# the profiler trace


class TestChromeTraceMerge:
    def test_device_lane_spans_merge_into_trace(self, fresh_obs, tmp_path):
        """Each kernel dispatch is a `dispatch:<kernel>` annotation on the
        profiler's host plane, inside its drain's device_dispatch span —
        the names that tie the device ops that follow to a kernel."""
        import glob

        import jax
        api, sched = _mk()
        _feed(api, 48, spread=12)
        with jax.profiler.trace(str(tmp_path)):
            sched.schedule_pending()
        path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                          recursive=True)
        pd = jax.profiler.ProfileData.from_file(path)
        host = next(p for p in pd.planes if p.name == "/host:CPU")
        events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                  for line in host.lines for e in line.events]
        dispatches = [e for e in events if e[0].startswith("dispatch:")]
        devs = [e for e in events if e[0] == "device_dispatch"]
        assert devs and dispatches
        assert {e[0].split(":", 1)[1] for e in dispatches} <= set(KERNELS)
        inside = [e for e in dispatches
                  if any(d[1] <= e[1] and e[2] <= d[2] for d in devs)]
        # the drain's kernels dispatch inside device_dispatch; the
        # cluster probe follows it
        assert {e[0] for e in inside} - {"dispatch:cluster_probe"}, events


# ---------------------------------------------------------------------------
# sharded-lane profile (8-device host mesh from conftest XLA_FLAGS)


class TestShardLanes:
    def test_profile_lands_after_sharded_drain(self, fresh_obs):
        mesh = make_mesh(4)
        api, sched = _mk(nodes=32, mesh=mesh)
        _feed(api, 48)
        sched.schedule_pending()
        prof = sched.observatory.shard_profile()
        assert prof.get("nDevices") == 4, prof
        assert len(prof["laneSeconds"]) == 4
        assert prof["totalSeconds"] > 0
        assert prof["imbalanceRatio"] >= 1.0
        assert 0.0 <= prof["commsShare"] <= 1.0
        # the metric mirror exports it at exposition time
        text = sched.metrics.exposition()
        assert 'scheduler_shard_lane_seconds{lane="0"}' in text
        assert "scheduler_shard_imbalance_ratio" in text

    def test_debug_refresh_reruns_probe(self, fresh_obs):
        mesh = make_mesh(4)
        api, sched = _mk(nodes=32, mesh=mesh)
        _feed(api, 48)
        sched.schedule_pending()
        srv = SchedulerServer(sched).start()
        try:
            code, body = _get(srv.port, "/debug/kernels?lanes=refresh")
            assert code == 200
            snap = json.loads(body)
            assert snap["shardLanes"].get("nDevices") == 4
        finally:
            srv.stop()

    def test_force_reprofile(self, fresh_obs):
        mesh = make_mesh(4)
        api, sched = _mk(nodes=32, mesh=mesh)
        _feed(api, 48)
        sched.schedule_pending()
        first = sched.observatory.shard_profile()
        again = sched.profile_shard_lanes(force=True)
        assert again and again.get("nDevices") == first.get("nDevices")


# ---------------------------------------------------------------------------
# no hidden retraces with the observatory ON


class TestRetraceBudgetWithObservatory:
    WARM_PASSES_MAX = 4

    def test_warm_rerun_fits_zero_budget(self, fresh_obs):
        assert OBS.enabled

        def one_pass():
            api, sched = _mk(nodes=32)
            _feed(api, 48, spread=12)
            sched.schedule_pending()

        for _ in range(self.WARM_PASSES_MAX):
            before = {k: r.compiles for k, r in LEDGER.kernels.items()}
            one_pass()
            deltas = {k: r.compiles - before.get(k, 0)
                      for k, r in LEDGER.kernels.items()
                      if k in KERNELS and r.compiles - before.get(k, 0)}
            if not deltas:
                break
        else:
            pytest.fail(f"kernels still minting after "
                        f"{self.WARM_PASSES_MAX} warm passes: {deltas}")
        # observing every dispatch must not mint a single executable
        with RAILS.retrace_budget(0, kernels=KERNELS):
            one_pass()


# ---------------------------------------------------------------------------
# tools: kernel_sweep self-test + check.py observatory gate


class TestKernelSweep:
    def test_self_test_subprocess(self):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        p = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "kernel_sweep.py"),
             "--self-test"],
            capture_output=True, text=True, env=env, timeout=300)
        assert p.returncode == 0, p.stdout + p.stderr
        assert "self-test: OK" in p.stdout


# ---------------------------------------------------------------------------
# overhead gate (slow tier)


@pytest.mark.slow
class TestObservatoryOverheadGate:
    def test_overhead_within_5_percent_at_5k_nodes(self):
        """ISSUE 14 acceptance: SchedulingBasic-shaped 5k-node drains
        with KernelObservatory ON stay within 5% of gate-OFF throughput
        (median of 3 measured passes each, warm shapes — the ISSUE 13
        gate shape)."""

        def _feed_many(api, n, start=0):
            api.create_pods([make_pod(f"p{start + i}").req(
                {"cpu": "100m", "memory": "64Mi"}).obj() for i in range(n)])

        def one_pass(gate_on):
            cfg = KubeSchedulerConfiguration(feature_gates={
                "KernelObservatory": gate_on})
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
        assert on >= 0.95 * off, (
            f"observatory overhead gate: on={on:.0f} off={off:.0f} pods/s "
            f"({on / off - 1:+.1%})")
