"""scheduler_perf harness: YAML-driven workloads + throughput collection.

Ports the reference benchmark contract
(test/integration/scheduler_perf/scheduler_perf.go):
- testCases loaded from YAML (`:1217` RunBenchmarkPerfScheduling): each has a
  `workloadTemplate` of ops and parameterized `workloads` with an optional
  `threshold` (minimum average pods/s, the failure gate, `:375-430`).
- op registry (`:518-552`): createNodes, createPods (collectMetrics),
  churn, barrier, sleep.
- throughputCollector (util.go:457-660): average scheduled-pods/s over the
  measured phase, plus percentile summaries of per-batch scheduling rates.

The measured window covers creation + ingestion + scheduling + binds, like
the reference's wall-clock sampler: pods stream in `createBatch`-sized
chunks (default 512), each chunk is dispatched without waiting
(`schedule_pending(wait=False)` — the async commit pipeline), and the
collector samples cumulative scheduled counts per chunk, giving
count/createBatch rate windows for real percentiles.
"""

from __future__ import annotations

import json
import os
import time
from dataclasses import dataclass, field
from typing import Callable, Optional

import yaml

from ..api.types import PodStatus
from ..api.types import _shallow as _SHALLOW
from ..backend.apiserver import APIServer
from ..scheduler import Scheduler
from ..testing.wrappers import _counter
from ..testing.wrappers import make_node, make_pod

LABEL_ZONE = "topology.kubernetes.io/zone"
LABEL_HOSTNAME = "kubernetes.io/hostname"


@dataclass
class Workload:
    name: str
    params: dict
    labels: list[str] = field(default_factory=list)
    threshold: float = 0.0


@dataclass
class TestCase:
    name: str
    workload_template: list[dict]
    workloads: list[Workload]
    default_pod_template: Optional[dict] = None


def load_test_cases(path: str) -> list[TestCase]:
    with open(path) as f:
        raw = yaml.safe_load(f)
    cases = []
    for tc in raw:
        workloads = [Workload(name=w["name"], params=w.get("params", {}),
                              labels=w.get("labels", []),
                              threshold=w.get("threshold", 0.0))
                     for w in tc.get("workloads", [])]
        cases.append(TestCase(name=tc["name"],
                              workload_template=tc["workloadTemplate"],
                              workloads=workloads,
                              default_pod_template=tc.get("defaultPodTemplate")))
    return cases


def _resolve(op: dict, key: str, params: dict, default=None):
    """countParam: $foo indirection (scheduler_perf.go op params)."""
    pkey = op.get(key + "Param")
    if pkey is not None:
        return params[pkey.lstrip("$")]
    return op.get(key, default)


@dataclass
class DataItem:
    """One measured phase (util.go DataItem)."""

    name: str
    average: float          # pods/s over the measured phase
    perc50: float = 0.0     # per-window rate percentiles
    perc95: float = 0.0
    perc99: float = 0.0
    pods: int = 0
    duration_s: float = 0.0
    samples: int = 0        # rate windows behind the percentiles
    # per-op wall times for the WHOLE workload run, as ("opcode[i]", s)
    # pairs — lets bench.py report phases OUTSIDE the measured window
    # (e.g. PreemptionChurn's preemptor wave) without widening it
    op_seconds: list = field(default_factory=list)
    # scheduler-side breakdown (drain phases, wave placement stats) pulled
    # from the metrics registry after the run — bench.py merges these into
    # each case's extras
    extras: dict = field(default_factory=dict)


class ThroughputCollector:
    """Samples cumulative scheduled-pod counts over the measured phase
    (reference throughputCollector, scheduler_perf/util.go:457-660: a
    free-running sampler of scheduled pods/interval). The op loop calls
    `sample()` after every ingest+dispatch step — the measured window
    INCLUDES pod creation and event-handler ingestion, exactly like the
    reference's wall-clock sampling — and percentiles come from the
    per-window rates (one window ≈ one create batch)."""

    def __init__(self) -> None:
        self.samples_: list[tuple[float, int]] = []
        self.start = 0.0
        self.elapsed = 0.0
        self.base = 0
        self.pods = 0

    def begin(self, scheduled_total: int = 0) -> None:
        self.base = scheduled_total
        self.start = time.perf_counter()
        self.samples_ = [(self.start, scheduled_total)]

    def sample(self, scheduled_total: int) -> None:
        self.samples_.append((time.perf_counter(), scheduled_total))

    def end(self, scheduled_total: int) -> None:
        self.sample(scheduled_total)
        self.elapsed = time.perf_counter() - self.start
        self.pods = scheduled_total - self.base

    def item(self, name: str) -> DataItem:
        # rate per commit span: zero-progress windows (the async pipeline
        # holds results in flight for several chunks) are MERGED into the
        # span that finally commits, so a lumpy commit cadence cannot
        # inflate the percentiles — each rate is Δpods/Δt between
        # consecutive points where the scheduled count actually advanced
        rates = []
        t0, c0 = self.samples_[0] if self.samples_ else (0.0, 0)
        for t1, c1 in self.samples_[1:]:
            if c1 > c0 and t1 > t0:
                rates.append((c1 - c0) / (t1 - t0))
                t0, c0 = t1, c1
        rates.sort()

        def perc(p: float) -> float:
            if not rates:
                return 0.0
            return rates[min(len(rates) - 1, int(p * len(rates)))]

        avg = self.pods / self.elapsed if self.elapsed > 0 else 0.0
        return DataItem(name=name, average=avg, perc50=perc(0.50),
                        perc95=perc(0.95), perc99=perc(0.99),
                        pods=self.pods, duration_s=self.elapsed,
                        samples=len(rates))


def _make_nodes(api: APIServer, count: int, start: int, params: dict) -> None:
    cpu = params.get("nodeCpu", 32)
    mem = params.get("nodeMemoryGi", 64)
    zones = params.get("zones", 16)
    for i in range(start, start + count):
        api.create_node(
            make_node(f"node-{i}")
            .capacity({"cpu": cpu, "memory": f"{mem}Gi", "pods": 110})
            .zone(f"zone-{i % zones}")
            .label(LABEL_HOSTNAME, f"node-{i}")
            .obj())


def _pod_from_template(name: str, template: Optional[dict], seq: int = 0,
                       zones: int = 16, gang_size: int = 1):
    w = make_pod(name)
    t = template or {}
    cpu = t.get("cpu", "900m")
    cyc = int(t.get("signatureCycle", 0))
    if cyc:
        # rotate the cpu request over `cyc` distinct values: consecutive
        # pods then interleave `cyc` distinct signatures (every other
        # template field — labels, spread, affinity — identical), the
        # high-signature mixed-drain shape the drain compiler maps to one
        # plan program (MixedHighSignature workload)
        cpu = f"{250 + 50 * (seq % cyc)}m"
    w = w.req({"cpu": cpu, "memory": t.get("memory", "1Gi")})
    if t.get("priority"):
        w = w.priority(int(t["priority"]))
    for k, v in t.get("labels", {}).items():
        w = w.label(k, v)
    if t.get("nodeSelectorZone"):
        w = w.node_selector({LABEL_ZONE: f"zone-{seq % zones}"})
    if "spreadZone" in t:
        w = w.spread_constraint(t.get("maxSkew", 1), LABEL_ZONE,
                                t.get("whenUnsatisfiable", "DoNotSchedule"),
                                t["spreadZone"])
    if "podAntiAffinity" in t:
        w = w.pod_affinity(t.get("topologyKey", LABEL_ZONE),
                           t["podAntiAffinity"], anti=True)
    if "podAffinity" in t:
        w = w.pod_affinity(t.get("topologyKey", LABEL_ZONE), t["podAffinity"])
    if "workloadRef" in t:
        ref = t["workloadRef"].replace("$gang", str(seq // max(gang_size, 1)))
        w = w.workload(ref.replace("$seq", str(seq)))
    return w.obj()


class PodFactory:
    """Stamps pods from shared prototypes: metadata (and status) are fresh
    per pod; the spec and label-dict OBJECTS are shared, per the object
    model's aliasing contract (api/types.py) — which is also what makes
    the builder's identity signature cache hit (state/batch.py). Template
    fields that genuinely vary per pod fall back to full construction."""

    def __init__(self, template: Optional[dict], zones: int = 16,
                 gang_size: int = 1):
        self.template = template or {}
        self.zones = zones
        self.gang_size = max(gang_size, 1)
        t = self.template
        self.per_seq = "workloadRef" in t
        self.zone_protos = None
        if t.get("nodeSelectorZone") and not self.per_seq:
            self.zone_protos = [
                _pod_from_template(f"proto-z{z}", t, seq=z, zones=zones)
                for z in range(zones)]
        self.cycle_protos = None
        cyc = int(t.get("signatureCycle", 0))
        if cyc and not self.per_seq and self.zone_protos is None:
            # one shared prototype per signature in the cycle: pods
            # sharing a prototype share spec identity, so the builder's
            # signature cache hits while the drain still interleaves
            # `cyc` distinct signatures
            self.cycle_protos = [
                _pod_from_template(f"proto-c{c}", t, seq=c, zones=zones)
                for c in range(cyc)]
        self.proto = _pod_from_template("proto", t, seq=0, zones=zones,
                                        gang_size=self.gang_size)

    # every pod stamped from a proto shares this empty status shape; the
    # copies below are safe because status mutations in the object model
    # REPLACE fields (apiserver patch semantics), never mutate the
    # shared conditions list in place
    _STATUS_PROTO = PodStatus()

    def make(self, name: str, seq: int):
        # inlined shallow copies + hoisted imports: this runs once per
        # created pod inside the measured window — the client-side cost
        # the reference benchmark's QPS-bound createPods pays too
        if self.per_seq:
            return _pod_from_template(name, self.template, seq=seq,
                                      zones=self.zones,
                                      gang_size=self.gang_size)
        if self.cycle_protos is not None:
            proto = self.cycle_protos[seq % len(self.cycle_protos)]
        elif self.zone_protos is not None:
            proto = self.zone_protos[seq % self.zones]
        else:
            proto = self.proto
        new = object.__new__
        p = new(type(proto))
        p.__dict__.update(proto.__dict__)
        meta = proto.metadata
        m = new(type(meta))
        m.__dict__.update(meta.__dict__)
        m.name = name
        m.uid = f"{m.namespace}/{name}"
        m.creation_index = next(_counter)
        p.metadata = m
        st = new(PodStatus)
        st.__dict__.update(self._STATUS_PROTO.__dict__)
        p.status = st
        return p


# the standing drain cap of a harness-built Scheduler
BATCH_SIZE = 8192


class WorkloadRunner:
    """Executes one workload's op list against a fresh Scheduler."""

    def __init__(self, scheduler_factory: Optional[Callable[[APIServer], Scheduler]] = None,
                 batch_size: int = BATCH_SIZE, create_batch: int = 512,
                 trace: bool = False):
        # `create_batch` streams pods in realistic chunks (the reference
        # benchmark's createPods ingestion rate is bounded by client
        # QPS/Burst 5000, util.go:123-124); the async commit pipeline
        # overlaps each chunk's device readback with the next chunk's
        # ingestion, so small chunks no longer serialize on the device
        # readback. `batch_size` only caps a single drain.
        self.batch_size = batch_size
        self.create_batch = create_batch
        self.trace = trace
        self.last_tracer = None
        self.last_pipeline_stats: Optional[dict] = None
        self.factory = scheduler_factory or self._default_factory

    def _default_factory(self, api: APIServer) -> Scheduler:
        sched = Scheduler(api, batch_size=self.batch_size)
        # KTPU_AUDIT_SAMPLE=1.0 forces the shadow audit onto every drain
        # (the acceptance sweep: a full bench at 100% sampling must
        # record zero divergences); unset = the config default rate
        rate = os.environ.get("KTPU_AUDIT_SAMPLE")
        if rate and sched.audit is not None:
            sched.audit.sample_rate = float(rate)
        return sched

    def run(self, tc: TestCase, wl: Workload, verbose: bool = False) -> list[DataItem]:
        # serve the measured window with the cyclic collector paused
        # (utils/runtime.py): drain-chunk allocation churn otherwise
        # triggers gen-2 collections inside the commit tail — measured
        # as the dominant commit_s cost. Restored (with a full collect)
        # on exit, so the surrounding process sees normal GC.
        from ..utils.runtime import scheduling_gc_pause
        with scheduling_gc_pause():
            return self._run_ops(tc, wl, verbose)

    def _run_ops(self, tc: TestCase, wl: Workload,
                 verbose: bool = False) -> list[DataItem]:
        api = APIServer()
        sched = self.last_scheduler = self.factory(api)
        self.last_pipeline_stats = None
        if self.trace:
            # capture EVERY cycle's span tree for Chrome-trace export
            # (bench --trace-dir): slow-threshold inf keeps the slow ring
            # quiet, keep_recent retains the full drain history
            from ..utils.tracing import Tracer
            self.last_tracer = sched.tracer = Tracer(
                slow_threshold_s=float("inf"), keep_recent=65536)
        params = wl.params
        items: list[DataItem] = []
        node_seq = 0
        pod_seq = 0
        op_times: list[tuple[str, float]] = []
        # kernel-observatory bracket: the observatory is process-global,
        # so per-run numbers must be deltas, not absolutes
        obs_chk = (sched.observatory.checkpoint()
                   if sched.observatory.enabled else None)
        for op_i, op in enumerate(tc.workload_template):
            code = op["opcode"]
            t_op = time.perf_counter()
            if code == "createNodes":
                count = int(_resolve(op, "count", params))
                _make_nodes(api, count, node_seq, params)
                node_seq += count
                # informer-sync analog (reference WaitForCacheSync runs
                # before the measured phase): build snapshot + device
                # staging now, not inside the first scheduling cycle
                sched.prime()
            elif code == "createPods":
                count = int(_resolve(op, "count", params))
                template = op.get("podTemplate", tc.default_pod_template)
                collect = op.get("collectMetrics", False)
                factory = PodFactory(template,
                                     zones=params.get("zones", 16),
                                     gang_size=int(params.get("gangSize", 1)))
                col = ThroughputCollector() if collect else None
                if col:
                    col.begin(sched.scheduled_count)
                created = 0
                create_batch = int(op.get("createBatch", self.create_batch))
                make = factory.make
                while created < count:
                    n = min(create_batch, count - created)
                    base = pod_seq + created
                    api.create_pods([make(f"pod-{base + i}", base + i)
                                     for i in range(n)])
                    created += n
                    # dispatch without waiting: the device results of this
                    # chunk commit while the next chunk is being created
                    sched.schedule_pending(wait=False)
                    if col:
                        col.sample(sched.scheduled_count)
                    if verbose:
                        print(f"  createPods: {created}/{count} "
                              f"scheduled={sched.scheduled_count}")
                # final full drain: dispatch whatever accumulated under the
                # adaptive batcher, then barrier the commit pipeline
                sched.schedule_pending()
                pod_seq += count
                if col:
                    col.end(sched.scheduled_count)
                    items.append(col.item(f"{tc.name}/{wl.name}"))
            elif code in ("streamPods", "streamTrace"):
                # open-loop streaming load (ISSUE 18): pods ARRIVE on a
                # Poisson clock at a target QPS (or a replayed gang
                # trace) and the streaming pipeline — or the lock-step
                # A/B — absorbs them. Open-loop: a slow scheduler never
                # thins the offered load, the backlog builds.
                items.extend(self._run_stream(
                    code, op, tc, wl, params, api, sched, pod_seq,
                    verbose))
                if code == "streamPods":
                    pod_seq += int(_resolve(op, "count", params))
            elif code == "gangTrace":
                # trace-driven gang traffic (testing/workloads.py): LLM
                # training gangs + co-located inference + gangs-preempt-
                # gangs, streamed in arrival chunks like createPods
                from ..testing.workloads import GangWorkloadGenerator
                gen = GangWorkloadGenerator(
                    seed=int(op.get("seed", params.get("seed", 0))))
                gangs = int(_resolve(op, "gangs", params, 0))
                gang_size = _resolve(op, "gangSize", params, None)
                size = (int(gang_size) if gang_size is not None
                        else (int(op.get("gangSizeMin", 8)),
                              int(op.get("gangSizeMax", 512))))
                specs = gen.training_gangs(
                    gangs, size=size,
                    min_count_frac=float(op.get("minCountFrac", 1.0)),
                    cpu=op.get("gangCpu", "900m"),
                    memory=op.get("gangMemory", "1Gi"),
                    priority=int(op.get("gangPriority", 0)))
                pre_specs = gen.training_gangs(
                    int(_resolve(op, "preemptorGangs", params, 0)),
                    size=int(op.get("preemptorSize", 8)),
                    cpu=op.get("preemptorCpu", "900m"),
                    memory=op.get("gangMemory", "1Gi"),
                    priority=int(op.get("preemptorPriority", 200)),
                    prefix="preemptor")
                contig = op.get("contiguityWeight",
                                params.get("contiguityWeight"))
                if contig is not None:
                    sched.gang_contiguity_weight = int(contig)
                collect = op.get("collectMetrics", False)
                col = ThroughputCollector() if collect else None
                if col:
                    col.begin(sched.scheduled_count)
                create_batch = int(op.get("createBatch", self.create_batch))
                for kind, obj in gen.trace(
                        specs,
                        inference_count=int(
                            _resolve(op, "inferencePods", params, 0)),
                        inference_cpu=op.get("inferenceCpu", "250m"),
                        inference_priority=int(
                            op.get("inferencePriority", 100)),
                        preemptor_gangs=pre_specs,
                        chunk=create_batch):
                    if kind == "workload":
                        api.create_workload(obj)
                        continue
                    api.create_pods(obj)
                    sched.schedule_pending(wait=False)
                    if col:
                        col.sample(sched.scheduled_count)
                    if verbose:
                        print(f"  gangTrace: scheduled="
                              f"{sched.scheduled_count}")
                sched.schedule_pending()
                if col:
                    col.end(sched.scheduled_count)
                    items.append(col.item(f"{tc.name}/{wl.name}"))
            elif code == "createWorkloads":
                from ..api.types import ObjectMeta, PodGroup, Workload
                count = int(_resolve(op, "count", params, 1))
                min_count = int(_resolve(op, "minCount", params, 1))
                prefix = op.get("namePrefix", "wl")
                for i in range(count):
                    api.create_workload(Workload(
                        metadata=ObjectMeta(name=f"{prefix}-{i}"),
                        pod_groups=[PodGroup(name="workers",
                                             min_count=min_count)]))
            elif code == "barrier":
                deadline = time.time() + float(op.get("timeoutSeconds", 60))
                while len(sched.queue) and time.time() < deadline:
                    sched.flush_queues()
                    if sched.schedule_pending() == 0:
                        # nothing schedulable right now: wait for backoffs
                        # instead of spinning the drain loop
                        time.sleep(0.05)
            elif code == "churn":
                # churn mode "recreate" (scheduler_perf.go:870): create and
                # delete pods/nodes repeatedly to exercise event handling
                number = int(_resolve(op, "number", params, 100))
                for i in range(number):
                    name = f"churn-{i}"
                    api.create_pod(_pod_from_template(name, tc.default_pod_template))
                    sched.schedule_pending()
                    api.delete_pod(f"default/{name}")
            elif code == "sleep":
                time.sleep(float(op.get("duration", op.get("seconds", 0.1))))
            else:
                raise ValueError(f"unknown opcode {code}")
            op_times.append((f"{code}[{op_i}]", time.perf_counter() - t_op))
        self.last_op_seconds = op_times
        m = sched.metrics
        extras = {
            "host_build_s": round(m.drain_phase.sum("host_build"), 3),
            # the dispatch and the wait for its results
            "device_s": round(m.drain_phase.sum("device")
                              + m.drain_phase.sum("readback"), 3),
            "commit_s": round(m.drain_phase.sum("commit"), 3),
            # host_build decomposition (this PR's observability layer)
            "host_snapshot_s": round(m.drain_phase.sum("host_snapshot"), 3),
            "host_tensorize_s": round(m.drain_phase.sum("host_tensorize"), 3),
            "host_group_seed_s": round(
                m.drain_phase.sum("host_group_seed"), 3),
            "host_cache_s": round(m.drain_phase.sum("host_cache"), 3),
            # per-attempt latency percentiles from the attempt-duration
            # histogram (all result/profile series merged)
            "attempt_p50_ms": round(
                m.attempt_duration.quantile(0.50) * 1e3, 3),
            "attempt_p99_ms": round(
                m.attempt_duration.quantile(0.99) * 1e3, 3),
            # queue→bind e2e percentiles from the SLI histogram (all
            # attempt-count series merged) — the bench_compare e2e gate
            "e2e_p50_ms": round(m.sli_duration.quantile(0.50) * 1e3, 3),
            "e2e_p99_ms": round(m.sli_duration.quantile(0.99) * 1e3, 3),
        }
        if self.last_pipeline_stats is not None:
            # streaming-pipeline occupancy block (ISSUE 18): stage busy
            # seconds, overlap factor, backpressure + batch-close counts
            extras["pipeline"] = self.last_pipeline_stats
        waves = m.wave_placement_waves.value()
        if waves:
            nconf = m.wave_conflict_ratio.count()
            extras["waves"] = int(waves)
            extras["wave_conflict_ratio"] = round(
                m.wave_conflict_ratio.sum() / max(nconf, 1), 4)
        if obs_chk is not None:
            kernels = sched.observatory.delta_since(obs_chk)
            if kernels:
                # per-kernel device-time breakdown of this run (warm
                # dispatch walls; compile cost lives in the ledger split)
                extras["kernels"] = kernels
            shard = sched.observatory.shard_profile()
            if shard:
                extras["shard_lanes"] = shard
        if getattr(sched, "critical_path_enabled", False):
            # critical-path headroom block (ISSUE 20): fold the run's
            # per-drain verdicts (this scheduler is fresh per run, so the
            # flight ring is exactly this run's last <=256 drains) into
            # the verdict histogram + ceiling factor bench.py projects
            # a pods/s ceiling from
            from .critical_path import aggregate
            cp = aggregate(d.get("criticalPath")
                           for d in sched.flight.dump())
            if cp.get("drains"):
                extras["critical_path"] = cp
        prof = getattr(sched, "profiler", None)
        if prof is not None and prof.sample_count:
            # hottest host frames of the run (continuous profiler): the
            # function-level answer behind the host_*_s phase sums
            extras["host_top_frames"] = prof.top_frames(5)
        # SLO verdict at bench end (obs/slo.py): burn-rate breaches +
        # shadow-audit divergence — the bench_compare --slo gate input.
        # The audit worker must land its in-flight replays first.
        audit = getattr(sched, "audit", None)
        if audit is not None:
            audit.flush(timeout=120.0)
        slo_engine = getattr(sched, "slo", None)
        if slo_engine is not None:
            slo = slo_engine.snapshot(compact=True)
            slo["audited"] = int(
                m.shadow_audit_drains.value("clean")
                + m.shadow_audit_drains.value("divergent"))
            slo["divergence_total"] = int(
                sum(m.oracle_divergence.value(kind)
                    for kind in ("assignment", "reason", "verdict")))
            extras["slo"] = slo
        for item in items:
            item.op_seconds = list(op_times)
            item.extras = dict(extras)
        return items

    def _run_stream(self, code: str, op: dict, tc: TestCase, wl: Workload,
                    params: dict, api: APIServer, sched: Scheduler,
                    pod_seq: int, verbose: bool) -> list[DataItem]:
        """streamPods / streamTrace opcodes: stamp the arrival schedule,
        pace it open-loop against the wall clock, and absorb it through
        the streaming pipeline or the lock-step A/B twin."""
        from ..testing.workloads import (GangWorkloadGenerator, chunked,
                                         poisson_arrivals)
        qps = float(_resolve(op, "qps", params, 10_000))
        mode = str(_resolve(op, "mode", params, "pipeline"))
        chunk = int(op.get("chunk", params.get("arrivalChunk", 128)))
        seed = int(op.get("seed", params.get("seed", 0)))
        budget_s = float(op.get("latencyBudgetMs",
                                params.get("latencyBudgetMs", 5.0))) / 1e3
        workload_objs: list = []
        if code == "streamPods":
            count = int(_resolve(op, "count", params))
            template = op.get("podTemplate", tc.default_pod_template)
            factory = PodFactory(template, zones=params.get("zones", 16),
                                 gang_size=int(params.get("gangSize", 1)))
            make = factory.make
            chunks = chunked([make(f"pod-{pod_seq + i}", pod_seq + i)
                              for i in range(count)], chunk)
        else:   # streamTrace: the gang/inference trace, paced
            gen = GangWorkloadGenerator(seed=seed)
            specs = gen.training_gangs(
                int(_resolve(op, "gangs", params, 0)),
                size=(int(op.get("gangSizeMin", 8)),
                      int(op.get("gangSizeMax", 512))),
                cpu=op.get("gangCpu", "900m"),
                memory=op.get("gangMemory", "1Gi"),
                priority=int(op.get("gangPriority", 0)))
            chunks = []
            for kind, obj in gen.trace(
                    specs,
                    inference_count=int(
                        _resolve(op, "inferencePods", params, 0)),
                    chunk=chunk):
                if kind == "workload":
                    workload_objs.append(obj)
                else:
                    chunks.append(obj)
        events = list(poisson_arrivals(chunks, qps=qps, seed=seed))
        collect = op.get("collectMetrics", False)
        col = ThroughputCollector() if collect else None
        use_pipeline = (mode == "pipeline" and sched.feature_gates.enabled(
            "StreamingDrainPipeline"))
        self.last_pipeline_stats = None
        # per-tier e2e quantiles as deltas from here: the warmup phase's
        # compile-wait outliers must not pollute the tier's p50/p99
        sli_chk = sched.metrics.sli_duration.merged_counts()
        if col:
            col.begin(sched.scheduled_count)
        if use_pipeline:
            from ..pipeline import StreamingPipeline
            pipe = StreamingPipeline(
                sched, latency_budget_s=budget_s,
                dispatch_depth=int(op.get("dispatchDepth", 3)))
            pipe.start()
            try:
                for w in workload_objs:
                    pipe.feed_workload(w)
                t0 = time.perf_counter()
                for due, pods in events:
                    lag = t0 + due - time.perf_counter()
                    if lag > 0:
                        time.sleep(lag)
                    pipe.feed(pods)
                    if col:
                        col.sample(sched.scheduled_count)
                arrival_done = time.perf_counter()
                pipe.drain()
            finally:
                pipe.stop()
            self.last_pipeline_stats = pipe.stats()
        elif mode == "lockstep":
            # the lock-step phase train at the same offered load: with no
            # overlap the device is idle at every decision point, so the
            # adaptive close policy fires on each arrival chunk and runs
            # build -> device -> commit to the barrier before the next.
            # This is the A/B twin the streaming gate compares against.
            for w in workload_objs:
                api.create_workload(w)
            t0 = time.perf_counter()
            for due, pods in events:
                lag = t0 + due - time.perf_counter()
                if lag > 0:
                    time.sleep(lag)
                api.create_pods(pods)
                if sched.dispatch_once():
                    sched.wait_pending()
                if col:
                    col.sample(sched.scheduled_count)
            arrival_done = time.perf_counter()
            deadline = time.time() + 120.0
            while len(sched.queue) and time.time() < deadline:
                sched.flush_queues()
                if sched.dispatch_once():
                    sched.wait_pending()
                else:
                    time.sleep(0.01)
        else:
            # "async": the pre-pipeline schedule_pending(wait=False) path
            # (commit tail detached, adaptive batcher accumulating) at
            # the same offered load
            for w in workload_objs:
                api.create_workload(w)
            t0 = time.perf_counter()
            for due, pods in events:
                lag = t0 + due - time.perf_counter()
                if lag > 0:
                    time.sleep(lag)
                api.create_pods(pods)
                sched.schedule_pending(wait=False)
                if col:
                    col.sample(sched.scheduled_count)
            arrival_done = time.perf_counter()
            sched.schedule_pending()
        m = sched.metrics
        # coordinated-omission guard: how far the arrival driver finished
        # behind the ideal Poisson schedule. The SLI e2e clock starts at
        # enqueue, so a mode that stalls the (single-threaded) driver
        # delays enqueues and understates its own latency — a nonzero lag
        # flags exactly that. The pipeline's feed() returns immediately,
        # so its lag stays ~0 and its e2e is the honest open-loop number.
        lag_s = (max(0.0, arrival_done - (t0 + events[-1][0]))
                 if events else 0.0)
        stream = {
            "mode": mode,
            "offered_qps": qps,
            "arrival_lag_s": round(lag_s, 3),
            "stream_e2e_p50_ms": round(
                m.sli_duration.quantile(0.50, since=sli_chk) * 1e3, 3),
            "stream_e2e_p99_ms": round(
                m.sli_duration.quantile(0.99, since=sli_chk) * 1e3, 3),
        }
        if self.last_pipeline_stats is None:
            self.last_pipeline_stats = stream
        else:
            self.last_pipeline_stats.update(stream)
        if col:
            col.end(sched.scheduled_count)
            if verbose:
                print(f"  {code}[{mode}] qps={qps:g}: "
                      f"scheduled={sched.scheduled_count}")
            return [col.item(f"{tc.name}/{wl.name}")]
        return []



def run_config(path: str, case_filter: str = "", workload_filter: str = "",
               verbose: bool = False, scheduler_factory=None,
               metrics_path: str = "",
               trace_dir: str = "",
               profile_dir: str = "",
               timeline_dir: str = "") -> list[tuple[DataItem, float]]:
    """Run matching (case, workload) pairs; returns [(item, threshold)].
    `metrics_path` appends each run's Prometheus exposition (the reference
    benchmark collects /metrics the same way, scheduler_perf/util.go);
    `trace_dir` writes one Chrome-trace JSON of the run's span trees per
    workload (loadable at chrome://tracing / ui.perfetto.dev);
    `profile_dir` writes one collapsed-stack host profile per workload
    (flamegraph.pl / speedscope.app ingest it directly);
    `timeline_dir` writes one JSON-lines telemetry timeline per workload
    (obs/timeline.py: per-second aggregates over all SLIs + probe)."""
    out = []
    for tc in load_test_cases(path):
        if case_filter and case_filter != tc.name:
            continue
        for wl in tc.workloads:
            if workload_filter and workload_filter != wl.name:
                continue
            runner = WorkloadRunner(scheduler_factory=scheduler_factory,
                                    trace=bool(trace_dir))
            for item in runner.run(tc, wl, verbose=verbose):
                out.append((item, wl.threshold))
            if metrics_path:
                with open(metrics_path, "a") as f:
                    f.write(f"# == {tc.name}/{wl.name} ==\n")
                    f.write(runner.last_scheduler.metrics.exposition())
            if trace_dir and runner.last_tracer is not None:
                os.makedirs(trace_dir, exist_ok=True)
                dest = os.path.join(trace_dir,
                                    f"{tc.name}_{wl.name}.trace.json")
                n = runner.last_tracer.export_chrome_trace(dest)
                if verbose:
                    print(f"  trace: {dest} ({n} events)")
            prof = getattr(runner.last_scheduler, "profiler", None)
            if profile_dir and prof is not None:
                os.makedirs(profile_dir, exist_ok=True)
                dest = os.path.join(profile_dir,
                                    f"{tc.name}_{wl.name}.collapsed.txt")
                n = prof.write_collapsed(dest)
                if verbose:
                    print(f"  profile: {dest} ({n} stacks)")
            tl = getattr(runner.last_scheduler, "timeline", None)
            if timeline_dir and tl is not None:
                os.makedirs(timeline_dir, exist_ok=True)
                dest = os.path.join(timeline_dir,
                                    f"{tc.name}_{wl.name}.timeline.jsonl")
                n = tl.to_jsonl(dest)
                if verbose:
                    print(f"  timeline: {dest} ({n} buckets)")
    return out
