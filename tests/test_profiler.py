"""Continuous host profiling + compile ledger (ISSUE 5 tentpole).

Covers perf/profiler.py (sampling, phase attribution, exports), the
compile ledger (perf/ledger.py: per-kernel compiles, warm-run stability,
h2d accounting), the scheduler wiring (drain ids across logs/spans/
flight/events, hot frames on slow drains, dispatcher_inflight), the
/debug/hostprofile + /debug/compileledger endpoints, and the slow-marked
profiler overhead gate.
"""

import json
import time
import urllib.request

import pytest

from kubernetes_tpu.backend.apiserver import APIServer
from kubernetes_tpu.config import KubeSchedulerConfiguration
from kubernetes_tpu.perf.ledger import GLOBAL as LEDGER
from kubernetes_tpu.perf.ledger import CompileLedger
from kubernetes_tpu.perf.profiler import HostProfiler, _pow2_bucket
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.server import SchedulerServer
from kubernetes_tpu.testing.wrappers import make_node, make_pod
from kubernetes_tpu.utils.tracing import PhaseTrack


def _get(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=5) as r:
        return r.status, r.read().decode()


def _cluster(nodes=8, config=None, batch_size=128):
    api = APIServer()
    sched = Scheduler(api, batch_size=batch_size, config=config)
    for i in range(nodes):
        api.create_node(make_node(f"n{i}").capacity(
            {"cpu": 32, "memory": "64Gi", "pods": 110}).obj())
    return api, sched


def _feed(api, n, start=0, cpu="100m"):
    api.create_pods([make_pod(f"p{start + i}").req(
        {"cpu": cpu, "memory": "64Mi"}).obj() for i in range(n)])


class TestPhaseTrack:
    def test_stack_semantics(self):
        t = PhaseTrack()
        assert t.current() == ""
        t.push("host_build")
        with t.scope("host_tensorize"):
            assert t.current() == "host_tensorize"
        assert t.current() == "host_build"
        t.pop()
        assert t.current() == ""
        t.pop()   # over-pop is a no-op, never raises

    def test_scope_pops_on_exception(self):
        t = PhaseTrack()
        with pytest.raises(RuntimeError):
            with t.scope("commit"):
                raise RuntimeError("boom")
        assert t.current() == ""


class TestLogContext:
    def test_context_appended_and_restored(self):
        import logging

        from kubernetes_tpu.utils.logging import klog, log_context
        records = []
        h = logging.Handler()
        h.emit = lambda rec: records.append(rec.getMessage())
        logger = logging.getLogger("kubernetes_tpu")
        old_level = logger.level
        logger.setLevel(logging.INFO)
        logger.addHandler(h)
        try:
            with log_context(drain=17):
                klog.info("batch committed", pods=3)
                with log_context(drain=18):
                    klog.info("nested")
            klog.info("outside")
        finally:
            logger.removeHandler(h)
            logger.setLevel(old_level)
        assert records[0] == "batch committed pods=3 drain=17"
        assert records[1] == "nested drain=18"
        assert records[2] == "outside"

    def test_explicit_kv_wins_over_context(self):
        from kubernetes_tpu.utils.logging import _fmt, log_context
        with log_context(drain=1):
            assert _fmt("m", {"drain": 9}) == "m drain=9"


class TestHostProfiler:
    def _profiled(self, phases):
        """Deterministic samples: inject the current frame under each
        phase a known number of times."""
        import sys
        track = PhaseTrack()
        prof = HostProfiler(hz=100, phase_fn=track.current)
        for phase, count in phases:
            with track.scope(phase):
                for _ in range(count):
                    assert prof.sample_once(frame=sys._getframe())
        return prof

    def test_counts_and_phase_shares(self):
        prof = self._profiled([("host_tensorize", 30), ("commit", 10)])
        assert prof.sample_count == 40
        shares = prof.phase_shares()
        assert shares["host_tensorize"] == pytest.approx(0.75)
        assert shares["commit"] == pytest.approx(0.25)

    def test_collapsed_format(self):
        prof = self._profiled([("commit", 3)])
        text = prof.collapsed()
        lines = [ln for ln in text.splitlines() if ln]
        assert lines
        for ln in lines:
            stack, _, count = ln.rpartition(" ")
            assert int(count) > 0
            assert stack.split(";")[0].startswith("commit")
        # this very function is on the sampled stack
        assert "test_collapsed_format" in text

    def test_frame_table_and_top_frames(self):
        prof = self._profiled([("commit", 5)])
        table = prof.frame_table()
        assert table
        leaf = table[0]
        assert leaf["self"] >= 1 and leaf["cum"] >= leaf["self"]
        # cum of the root frame covers every sample
        assert any(row["cum"] == 5 for row in table) or \
            sum(r["self"] for r in table) == 5
        top = prof.top_frames(2)
        assert len(top) <= 2 and all("/" in t for t in top)

    def test_speedscope_shape(self):
        prof = self._profiled([("device", 4)])
        doc = prof.speedscope()
        assert doc["profiles"][0]["type"] == "sampled"
        assert len(doc["profiles"][0]["samples"]) == \
            len(doc["profiles"][0]["weights"])
        nframes = len(doc["shared"]["frames"])
        for sample in doc["profiles"][0]["samples"]:
            assert all(0 <= i < nframes for i in sample)
        assert sum(doc["profiles"][0]["weights"]) == 4

    def test_seconds_window(self):
        import sys
        prof = HostProfiler(hz=100)
        prof.sample_once(frame=sys._getframe())
        # a sample stamped "now" is inside any recent window ...
        assert prof.aggregate(seconds=5).total == 1
        # ... and outside a window that ended in the past
        assert prof.aggregate(seconds=-5).total == 0

    def test_pow2_bucket(self):
        assert [_pow2_bucket(n) for n in (0, 1, 2, 3, 4, 5, 9)] == \
            [0, 1, 2, 4, 4, 8, 16]

    def test_bucket_tagging(self):
        import sys
        cell = [3]
        prof = HostProfiler(hz=100, bucket_fn=lambda: cell[0])
        prof.sample_once(frame=sys._getframe())
        ((phase, bucket, _stack), n), = prof.aggregate().counts.items()
        assert (phase, bucket, n) == ("other", 4, 1)

    def test_phase_shares_agree_with_wall_clock(self):
        """ISSUE 5 satellite: per-phase sample shares track the phases'
        wall-clock shares on a synthetic two-phase workload (2:1)."""
        track = PhaseTrack()
        prof = HostProfiler(hz=200, phase_fn=track.current)
        prof.ensure_running()
        wall = {}
        try:
            for phase, dur in (("host_tensorize", 0.5), ("commit", 0.25)):
                t0 = time.perf_counter()
                with track.scope(phase):
                    while time.perf_counter() - t0 < dur:
                        sum(range(500))   # busy: hold a real stack
                wall[phase] = time.perf_counter() - t0
        finally:
            prof.stop()
        shares = prof.phase_shares()
        got = shares.get("host_tensorize", 0.0)
        other = shares.get("commit", 0.0)
        assert got + other > 0, "sampler collected nothing"
        sampled_ratio = got / (got + other)
        wall_ratio = wall["host_tensorize"] / (wall["host_tensorize"]
                                               + wall["commit"])
        assert abs(sampled_ratio - wall_ratio) < 0.2

    def test_thread_lifecycle(self):
        prof = HostProfiler(hz=500)
        prof.ensure_running()
        assert prof.running
        time.sleep(0.05)
        prof.stop()
        assert not prof.running
        assert prof.sample_count > 0


class TestCompileLedger:
    class _FakeJit:
        """Callable with jax's _cache_size surface: 'compiles' on first
        call per distinct arg."""

        def __init__(self):
            self.seen = set()

        def __call__(self, x):
            self.seen.add(x)
            return x

        def _cache_size(self):
            return len(self.seen)

    def test_compiles_and_retraces(self):
        led = CompileLedger()
        fn = self._FakeJit()
        led.measured_call("k", fn, "shape-a")
        led.measured_call("k", fn, "shape-a")   # cached: no compile
        led.measured_call("k", fn, "shape-b")   # retrace
        rec = led.kernels["k"]
        assert rec.calls == 3
        assert rec.compiles == 2
        assert rec.retraces == 1
        assert rec.compile_seconds >= 0.0
        snap = led.snapshot()
        assert snap["kernels"]["k"]["retraces"] == 1
        assert snap["totalCompiles"] == 2

    def test_donation_miss_probe(self):
        led = CompileLedger()
        fn = self._FakeJit()

        class Arr:
            def __init__(self, deleted):
                self._d = deleted

            def is_deleted(self):
                return self._d

        led.measured_call("k", fn, "a", donated=Arr(True))    # consumed
        led.measured_call("k", fn, "b", donated=Arr(False))   # miss
        led.measured_call("k", fn, "c", donated=None)
        assert led.kernels["k"].donation_misses == 1

    def test_h2d_accounting(self):
        import numpy as np
        led = CompileLedger()
        led.note_h2d("host_cache", 100)
        led.note_h2d("host_cache", 20)
        led.note_h2d_tree("host_snapshot",
                          (np.zeros(4, np.int64), np.zeros(2, np.int32)))
        assert led.h2d == {"host_cache": 120, "host_snapshot": 40}


class TestSchedulerProfiling:
    def _run_until_sampled(self, api, sched, deadline_s=20.0):
        """Schedule batches until the profiler holds phase-tagged samples
        (the sampler is asynchronous; more drains = more chances)."""
        start = time.time()
        base = 0
        while time.time() - start < deadline_s:
            _feed(api, 256, start=base)
            base += 256
            sched.schedule_pending()
            shares = sched.profiler.phase_shares()
            if any(p != "other" for p in shares):
                return shares
        raise AssertionError("no phase-tagged samples within deadline")

    def test_profiler_on_by_default_and_samples_drains(self):
        api, sched = _cluster(nodes=32)
        assert sched.profiler is not None
        assert not sched.profiler.running   # lazy: starts on first drain
        shares = self._run_until_sampled(api, sched)
        assert sched.profiler.running
        # phase names come from the loop's PhaseTrack marks
        from kubernetes_tpu.metrics import LOOP_PHASES
        assert set(shares) <= set(LOOP_PHASES) | {"other"}

    def test_gate_off_disables(self):
        cfg = KubeSchedulerConfiguration(
            feature_gates={"ContinuousHostProfiling": False})
        api, sched = _cluster(config=cfg)
        assert sched.profiler is None
        _feed(api, 8)
        assert sched.schedule_pending() == 8

    def test_hz_zero_disables(self):
        cfg = KubeSchedulerConfiguration(host_profiler_hz=0)
        api, sched = _cluster(config=cfg)
        assert sched.profiler is None

    def test_hz_knob_round_trip_and_validation(self):
        cfg = KubeSchedulerConfiguration(host_profiler_hz=97.0)
        cfg.validate()
        again = KubeSchedulerConfiguration.from_dict(cfg.to_dict())
        assert again.host_profiler_hz == 97.0
        assert KubeSchedulerConfiguration().to_dict()["hostProfilerHz"] \
            == 200.0
        with pytest.raises(ValueError, match="hostProfilerHz"):
            KubeSchedulerConfiguration(host_profiler_hz=-1).validate()
        api, sched = _cluster(config=cfg)
        assert sched.profiler.hz == 97.0

    def test_hostprofile_and_compileledger_endpoints(self):
        api, sched = _cluster(nodes=32)
        self._run_until_sampled(api, sched)
        srv = SchedulerServer(sched).start()
        try:
            code, body = _get(srv.port, "/debug/hostprofile")
            assert code == 200 and body.strip()
            line = body.strip().splitlines()[0]
            stack, _, count = line.rpartition(" ")
            assert int(count) > 0 and ";" in stack

            code, body = _get(srv.port,
                              "/debug/hostprofile?format=speedscope"
                              "&seconds=300")
            doc = json.loads(body)
            assert doc["profiles"][0]["samples"]

            code, body = _get(srv.port, "/debug/compileledger")
            led = json.loads(body)
            assert "run_uniform" in led["kernels"] \
                or "run_batch" in led["kernels"]
            assert led["h2dBytes"].get("host_snapshot", 0) > 0
        finally:
            srv.stop()

    def test_hostprofile_endpoint_404_when_off(self):
        cfg = KubeSchedulerConfiguration(
            feature_gates={"ContinuousHostProfiling": False})
        api, sched = _cluster(config=cfg)
        srv = SchedulerServer(sched).start()
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(srv.port, "/debug/hostprofile")
            assert ei.value.code == 404
        finally:
            srv.stop()

    def test_compile_ledger_stable_across_warm_rerun(self):
        """ISSUE 5 satellite: identical shapes on a fresh scheduler must
        mint ZERO new executables (no hidden retraces)."""

        def run():
            api, sched = _cluster(nodes=16, batch_size=128)
            _feed(api, 256)
            assert sched.schedule_pending() == 256

        run()   # possibly-cold pass (this process may already be warm)
        before = {k: r.compiles for k, r in LEDGER.kernels.items()}
        run()   # warm re-run: identical node bucket / batch bucket / L,K,J
        after = {k: r.compiles for k, r in LEDGER.kernels.items()}
        assert after == before

    def test_drain_ids_across_flight_and_events(self):
        api, sched = _cluster(nodes=8)
        _feed(api, 64)
        api.create_pod(make_pod("huge").req(
            {"cpu": "500", "memory": "1Gi"}).obj())
        sched.schedule_pending()
        records = sched.flight.dump()
        ids = [r["drainId"] for r in records]
        assert ids and ids == sorted(ids) and ids[0] >= 1
        dump = sched.events.dump()
        sched_ids = {e["drainId"] for e in dump["events"]
                     if e["reason"] == "Scheduled"}
        fail_ids = {e["drainId"] for e in dump["events"]
                    if e["reason"] == "FailedScheduling"}
        assert sched_ids and sched_ids <= set(ids)
        assert fail_ids and fail_ids <= set(ids)
        # span attribution: drain id rides the host_build span attrs
        from kubernetes_tpu.utils.tracing import Tracer
        tr = Tracer(slow_threshold_s=float("inf"), keep_recent=64)
        sched.tracer = tr
        _feed(api, 32, start=100000)
        sched.schedule_pending()
        hb = next(sp for root in tr.recent
                  for sp in [root.find("host_build")] if sp is not None)
        assert hb.attributes["drain"] in [r["drainId"]
                                          for r in sched.flight.dump()]

    def test_hot_frames_attached_to_slow_drains(self):
        api, sched = _cluster(nodes=16)
        self._run_until_sampled(api, sched)
        sched.profiler.slow_drain_s = 0.0   # every drain counts as slow
        _feed(api, 256, start=200000)
        sched.schedule_pending()
        rec = sched.flight.dump()[-1]
        assert isinstance(rec["hotFrames"], list)
        assert rec["hotFrames"], "no hot frames despite live sampler"
        assert all("/" in f for f in rec["hotFrames"])

    def test_dispatcher_inflight_gauge(self):
        api, sched = _cluster(nodes=8)
        _feed(api, 16)
        sched.schedule_pending()
        text = sched.metrics.exposition()
        assert 'scheduler_dispatcher_inflight{kind="api_calls"} 0' in text
        assert 'scheduler_dispatcher_inflight{kind="drains"} 0' in text
        # live depth while calls are queued
        from kubernetes_tpu.backend.dispatcher import APICall, CallType
        sched.dispatcher.add(APICall(
            CallType.STATUS_PATCH, make_pod("x").obj(), condition={}))
        assert sched._inflight_depths()[("api_calls",)] == 1.0
        sched.dispatcher.flush()

    def test_xla_and_h2d_series_in_exposition(self):
        api, sched = _cluster(nodes=8)
        _feed(api, 64)
        sched.schedule_pending()
        text = sched.metrics.exposition()
        assert 'scheduler_xla_compiles_total{kernel="run_uniform"}' in text
        assert 'scheduler_xla_compile_seconds{kernel="run_uniform"}' in text
        assert 'scheduler_h2d_bytes_total{phase="host_snapshot"}' in text
        # the ledger mirror carries real observations, not just seeds
        snap = LEDGER.snapshot()
        assert snap["h2dBytes"].get("host_snapshot", 0) > 0


@pytest.mark.slow
class TestProfilerOverheadGate:
    def test_overhead_within_5_percent_at_5k_nodes(self):
        """ISSUE 5 acceptance: a SchedulingBasic-shaped 5k-node drain with
        the profiler ON stays within 5% of profiler-OFF throughput
        (median of 3 measured passes each, warm shapes)."""

        def one_pass(gate_on):
            cfg = KubeSchedulerConfiguration(feature_gates={
                "ContinuousHostProfiling": gate_on})
            api = APIServer()
            sched = Scheduler(api, batch_size=8192, config=cfg)
            for i in range(5000):
                api.create_node(make_node(f"n{i}").capacity(
                    {"cpu": 32, "memory": "64Gi", "pods": 110}).obj())
            sched.prime()
            t0 = time.perf_counter()
            created = 0
            while created < 10000:
                _feed(api, 512, start=created)
                created += 512
                sched.schedule_pending(wait=False)
            sched.schedule_pending()
            dt = time.perf_counter() - t0
            assert sched.scheduled_count == created
            return created / dt

        one_pass(False)   # warm every executable outside the measurement
        off = sorted(one_pass(False) for _ in range(3))[1]
        on = sorted(one_pass(True) for _ in range(3))[1]
        assert on >= 0.95 * off, (
            f"profiler overhead gate: on={on:.0f} off={off:.0f} pods/s "
            f"({on / off - 1:+.1%})")
