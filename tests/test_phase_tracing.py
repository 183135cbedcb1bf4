"""The loop's phases on the profiler's clock.

- PhaseTrack self time: nested phases charge only their own seconds, and
  the charges of one outermost phase sum to its wall;
- Tracer.phase: a child span inside a cycle, only an annotation between;
- a CPU `schedule_pending` under `jax.profiler.trace`: the phase spans and
  the `dispatch:<kernel>` annotations on the host plane, nested as the
  code nests them, and self times that sum to the call's wall;
- the informer handlers' phases, the garbage-collector hook and the side
  threads' CPU counters;
- the new metric families, seeded in the exposition.
"""

import gc
import glob
import os
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402

from kubernetes_tpu.backend.apiserver import APIServer  # noqa: E402
from kubernetes_tpu.metrics import (LOOP_PHASES, SIDE_THREADS,  # noqa: E402
                                    Counter, SchedulerMetrics)
from kubernetes_tpu.perf.ledger import device_entries  # noqa: E402
from kubernetes_tpu.perf.profiler import HostProfiler  # noqa: E402
from kubernetes_tpu.scheduler import Scheduler  # noqa: E402
from kubernetes_tpu.testing.wrappers import make_node, make_pod  # noqa: E402
from kubernetes_tpu.utils.tracing import (GC_PAUSES, PhaseTrack,  # noqa: E402
                                          Tracer)


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


def _cluster(nodes=8, **kw):
    api = APIServer()
    sched = Scheduler(api, batch_size=64, **kw)
    for i in range(nodes):
        api.create_node(make_node(f"n{i}").capacity(
            {"cpu": "8", "memory": "16Gi", "pods": 110}).obj())
    return api, sched


def _feed(api, n, start=0):
    api.create_pods([make_pod(f"p{start + i}").req(
        {"cpu": "100m", "memory": "64Mi"}).obj() for i in range(n)])


def _self_seconds(sched):
    return {p: sched.metrics.phase_self_seconds.value(p)
            for p in LOOP_PHASES}


def _host_events(trace_dir):
    """(name, start_ns, end_ns) of every event on the profiler's host
    plane, whichever thread line it is on."""
    path, = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    host = next(p for p in pd.planes if p.name == "/host:CPU")
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for line in host.lines for e in line.events]


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


# ---------------------------------------------------------------------------
# PhaseTrack self time


class TestPhaseTrackSelfTime:
    def test_nested_phases_sum_to_root_wall(self):
        clock = FakeClock()
        sink = Counter("phase_self_seconds_total", "", ("phase",))
        t = PhaseTrack(sink, clock=clock)
        t.push("schedule_pending")
        clock.t += 1.0
        t.push("host_build")
        clock.t += 2.0
        with t.scope("host_snapshot"):
            assert t.current() == "host_snapshot"
            clock.t += 4.0
        clock.t += 0.5
        t.pop()
        clock.t += 0.25
        t.pop()
        assert sink.value("schedule_pending") == 1.25
        assert sink.value("host_build") == 2.5
        assert sink.value("host_snapshot") == 4.0
        wall = 1.0 + 2.0 + 4.0 + 0.5 + 0.25
        assert sum(sink._values.values()) == wall

    def test_time_outside_any_phase_is_not_charged(self):
        clock = FakeClock()
        sink = Counter("phase_self_seconds_total", "", ("phase",))
        t = PhaseTrack(sink, clock=clock)
        clock.t += 10.0          # before the first push
        with t.scope("pod_delete"):
            clock.t += 0.5
        clock.t += 10.0          # between phases
        t.pop()                  # over-pop: no charge, never raises
        with t.scope("pod_delete"):
            clock.t += 0.25
        assert sink._values == {("pod_delete",): 0.75}
        assert t.current() == ""


class TestTracerPhase:
    def test_phase_is_a_child_in_a_cycle_and_no_root_between(self):
        tr = Tracer(slow_threshold_s=float("inf"), keep_recent=8)
        with tr.phase("dispatcher_flush") as sp:
            sp.set(ignored=True)         # the null span takes attributes
        assert not tr.recent             # no cycle of its own
        with tr.span("scheduling_cycle"):
            with tr.phase("dispatcher_flush", pods=3):
                pass
        root, = tr.recent
        assert [c.name for c in root.children] == ["dispatcher_flush"]
        assert root.children[0].attributes == {"pods": 3}


# ---------------------------------------------------------------------------
# the profiler trace of a real schedule_pending


class TestProfilerTrace:
    def test_phases_nest_on_the_host_plane(self, tmp_path):
        api, sched = _cluster(tracer=Tracer(slow_threshold_s=float("inf")))
        _feed(api, 96)
        with jax.profiler.trace(str(tmp_path)):
            assert sched.schedule_pending() == 96
        events = _host_events(tmp_path)
        by = {}
        for e in events:
            by.setdefault(e[0], []).append(e)
        for name in ("schedule_pending", "host_build", "host_tensorize",
                     "device_dispatch", "readback", "commit",
                     "bookkeeping", "dispatcher_flush", "pod_update"):
            assert by.get(name), f"no {name} span on the host plane"
        root, = by["schedule_pending"]
        for name in ("host_build", "device_dispatch", "readback", "commit",
                     "bookkeeping", "dispatcher_flush"):
            assert all(_inside(e, root) for e in by[name]), name
        assert all(any(_inside(e, hb) for hb in by["host_build"])
                   for e in by["host_tensorize"])
        # the bind echo runs inside the flush
        assert all(any(_inside(e, f) for f in by["dispatcher_flush"])
                   for e in by["pod_update"])
        dispatches = [e for e in events if e[0].startswith("dispatch:")]
        assert dispatches
        assert {e[0].split(":", 1)[1] for e in dispatches} <= set(device_entries())
        assert any(_inside(d, dev) for d in dispatches
                   for dev in by["device_dispatch"])

    def test_self_times_sum_to_the_call_wall(self):
        api, sched = _cluster(tracer=Tracer(slow_threshold_s=float("inf")))
        _feed(api, 64)
        sched.schedule_pending()          # compiles outside the reading
        _feed(api, 128, start=1000)
        before = _self_seconds(sched)
        t0 = time.perf_counter()
        sched.schedule_pending()
        wall = time.perf_counter() - t0
        after = _self_seconds(sched)
        delta = {p: after[p] - before[p] for p in LOOP_PHASES}
        total = sum(delta.values())
        assert total <= wall
        assert total >= 0.95 * wall, (total, wall)
        for p in ("schedule_pending", "host_build", "device_dispatch",
                  "readback", "commit", "bookkeeping", "release",
                  "dispatcher_flush", "batch_build", "queue_drain"):
            assert delta[p] > 0.0, p
        # drain_phase keeps its labels: device is the dispatch alone,
        # readback its own series
        m = sched.metrics
        assert m.drain_phase.count("device") >= 2
        assert m.drain_phase.count("readback") == m.drain_phase.count(
            "device")


# ---------------------------------------------------------------------------
# informer handlers, garbage collector, side threads


class TestHostRuntimeCounters:
    def test_informer_handlers_take_their_phases(self):
        api, sched = _cluster()
        _feed(api, 16)
        assert sched.metrics.phase_self_seconds.value("pod_add") > 0.0
        assert sched.schedule_pending() == 16
        assert sched.metrics.phase_self_seconds.value("pod_update") > 0.0
        assert sched.metrics.phase_self_seconds.value("pod_delete") == 0.0
        for uid in list(api.pods)[:4]:
            api.delete_pod(uid)
        assert sched.metrics.phase_self_seconds.value("pod_delete") > 0.0
        assert sched.phase_track.current() == ""

    def test_forced_collection_counts_and_annotates(self, tmp_path):
        api, sched = _cluster(nodes=2)
        m = sched.metrics
        m.sync_gc_pauses()
        before = m.gc_pause_seconds.value("2")
        garbage = [[i] for i in range(20000)]
        del garbage
        with jax.profiler.trace(str(tmp_path)):
            gc.collect()
        m.sync_gc_pauses()
        assert m.gc_pause_seconds.value("2") > before
        assert GC_PAUSES.seconds[2] == m.gc_pause_seconds.value("2")
        assert any(e[0] == "gc" for e in _host_events(tmp_path))

    def test_synchronous_audit_counts_its_cpu(self):
        api, sched = _cluster(nodes=4)
        sched.audit.sample_rate = 1.0
        sched.audit.synchronous = True
        assert sched.metrics.thread_cpu_seconds.value("audit") == 0.0
        _feed(api, 32)
        assert sched.schedule_pending() == 32
        assert sched.metrics.thread_cpu_seconds.value("audit") > 0.0
        assert sched.metrics.shadow_audit_drains.value("clean") >= 1

    def test_host_profiler_counts_its_sampling_cpu(self):
        m = SchedulerMetrics()
        prof = HostProfiler(hz=500, phase_fn=lambda: "",
                            thread_cpu=m.thread_cpu_seconds)
        prof.ensure_running()
        stop = time.perf_counter() + 0.3
        try:
            while (m.thread_cpu_seconds.value("host_profiler") == 0.0
                   and time.perf_counter() < stop):
                sum(range(1000))      # keep the sampled thread busy
        finally:
            prof.stop()
        assert prof.sample_count > 0
        assert m.thread_cpu_seconds.value("host_profiler") > 0.0
        assert not prof.running

    def test_profiler_thread_ends_with_its_scheduler(self):
        """The sampler's CPU counter must not keep its Scheduler alive: a
        dropped Scheduler's profiler thread exits (weakref owner)."""
        import weakref
        api, sched = _cluster(nodes=2)
        _feed(api, 4)
        sched.schedule_pending()
        prof = sched.profiler
        assert prof.running
        ref = weakref.ref(sched)
        del sched, api          # the API server's watch holds it too
        gc.collect()
        assert ref() is None
        prof._thread.join(timeout=5.0)
        assert not prof.running

    def test_new_families_seeded_in_exposition(self):
        text = SchedulerMetrics().exposition()
        for p in LOOP_PHASES:
            assert (f'scheduler_phase_self_seconds_total{{phase="{p}"}}'
                    in text), p
        for gen in ("0", "1", "2"):
            assert (f'scheduler_gc_pause_seconds_total{{generation="{gen}"}}'
                    in text)
        for thread in SIDE_THREADS:
            assert (f'scheduler_thread_cpu_seconds_total{{thread="{thread}"}}'
                    " 0" in text)
        assert 'scheduler_drain_phase_seconds_count{phase="readback"} 0' \
            in text
        for fam in ("phase_self_seconds_total", "gc_pause_seconds_total",
                    "thread_cpu_seconds_total"):
            assert f"# TYPE scheduler_{fam} counter" in text


@pytest.mark.parametrize("phase", ["audit", "cluster_probe"])
def test_drain_phase_spans_carry_the_drain_id(phase):
    api, sched = _cluster(nodes=4,
                          tracer=Tracer(slow_threshold_s=float("inf"),
                                        keep_recent=32))
    sched.audit.sample_rate = 1.0
    sched.audit.synchronous = True
    _feed(api, 16)
    sched.schedule_pending()
    spans = [sp for root in sched.tracer.recent
             for sp in _walk(root) if sp.name == phase]
    assert spans, phase
    ids = {r["drainId"] for r in sched.flight.dump()}
    assert all(sp.attributes["drain"] in ids for sp in spans)


def _walk(sp):
    yield sp
    for c in sp.children:
        yield from _walk(c)
