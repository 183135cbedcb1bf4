"""Scheduler metrics: labeled counters/gauges/histograms + text exposition.

Mirrors pkg/scheduler/metrics/metrics.go (:196-460) in spirit and naming —
the ~dozen series the reference dashboards actually read — on a minimal
Prometheus-style registry (component-base/metrics stand-in):

  scheduler_schedule_attempts_total{result,profile}
  scheduler_scheduling_attempt_duration_seconds{result,profile}
  scheduler_pod_scheduling_sli_duration_seconds{attempts}
  scheduler_pending_pods{queue}
  scheduler_preemption_attempts_total / scheduler_preemption_victims
  scheduler_queue_incoming_pods_total{event,queue}
  scheduler_permit_wait_duration_seconds{result}
  scheduler_device_batch_size / scheduler_device_batch_duration_seconds
  scheduler_api_dispatcher_calls_total{call_type,result}

The TPU-specific device_* series replace the reference's goroutines/
plugin-execution timers: on this architecture the device batch IS the
execution unit worth observing.

The reference offloads observations via MetricAsyncRecorder
(metric_recorder.go) so the hot path never touches Prometheus locks; the
single-threaded host loop has no lock contention, so observations are
direct writes into plain dicts (cheaper than the reference's channel hop)
and `Registry.exposition()` renders on scrape.
"""

from __future__ import annotations

import bisect
from dataclasses import dataclass, field
from typing import Callable, Optional

# reference metrics.go:73 SchedulerSubsystem
SUBSYSTEM = "scheduler"


def _escape_label_value(v: str) -> str:
    """Prometheus text-format label-value escaping (exposition format spec):
    backslash, double-quote and line-feed must be escaped — raw values
    break every scrape parser on the first quote or newline."""
    return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _escape_help(h: str) -> str:
    """HELP text escaping: backslash and line-feed only (quotes are legal
    in HELP per the text-format spec)."""
    return h.replace("\\", "\\\\").replace("\n", "\\n")


def _fmt_labels(names: tuple[str, ...], values: tuple[str, ...]) -> str:
    if not names:
        return ""
    inner = ",".join(f'{n}="{_escape_label_value(v)}"'
                     for n, v in zip(names, values))
    return "{" + inner + "}"


@dataclass
class Counter:
    name: str
    help: str
    label_names: tuple[str, ...] = ()
    _values: dict[tuple[str, ...], float] = field(default_factory=dict)

    def inc(self, *labels: str, by: float = 1.0) -> None:
        key = tuple(labels)
        self._values[key] = self._values.get(key, 0.0) + by

    def value(self, *labels: str) -> float:
        return self._values.get(tuple(labels), 0.0)

    def expose(self) -> list[str]:
        out = [f"# HELP {self.name} {_escape_help(self.help)}",
               f"# TYPE {self.name} counter"]
        for key, v in sorted(self._values.items()):
            out.append(f"{self.name}{_fmt_labels(self.label_names, key)} {v:g}")
        return out


@dataclass
class Gauge:
    name: str
    help: str
    label_names: tuple[str, ...] = ()
    # a callback gauge computes its value at scrape time (queue depths)
    callback: Optional[Callable[[], dict[tuple[str, ...], float]]] = None
    _values: dict[tuple[str, ...], float] = field(default_factory=dict)

    def set(self, value: float, *labels: str) -> None:
        self._values[tuple(labels)] = value

    def value(self, *labels: str) -> float:
        if self.callback is not None:
            return self.callback().get(tuple(labels), 0.0)
        return self._values.get(tuple(labels), 0.0)

    def expose(self) -> list[str]:
        values = self.callback() if self.callback is not None else self._values
        out = [f"# HELP {self.name} {_escape_help(self.help)}",
               f"# TYPE {self.name} gauge"]
        for key, v in sorted(values.items()):
            out.append(f"{self.name}{_fmt_labels(self.label_names, key)} {v:g}")
        return out


# metrics.go attempt-duration buckets: exponential 0.001 * 2^i
def exponential_buckets(start: float, factor: float, count: int) -> list[float]:
    return [start * factor ** i for i in range(count)]


@dataclass
class Histogram:
    name: str
    help: str
    buckets: list[float] = field(
        default_factory=lambda: exponential_buckets(0.001, 2, 15))
    label_names: tuple[str, ...] = ()
    _counts: dict[tuple[str, ...], list[int]] = field(default_factory=dict)
    _sums: dict[tuple[str, ...], float] = field(default_factory=dict)
    _totals: dict[tuple[str, ...], int] = field(default_factory=dict)

    def observe(self, value: float, *labels: str) -> None:
        key = tuple(labels)
        counts = self._counts.get(key)
        if counts is None:
            counts = self._counts[key] = [0] * (len(self.buckets) + 1)
        counts[bisect.bisect_left(self.buckets, value)] += 1
        self._sums[key] = self._sums.get(key, 0.0) + value
        self._totals[key] = self._totals.get(key, 0) + 1

    def observe_array(self, values, *labels: str) -> None:
        """Vectorized observe (metric_recorder.go's batched flush analog):
        one numpy bucket-count pass for a whole drain's worth of samples
        instead of a Python observe() per pod."""
        import numpy as np
        v = np.asarray(values, float)
        if v.size == 0:
            return
        key = tuple(labels)
        counts = self._counts.get(key)
        if counts is None:
            counts = self._counts[key] = [0] * (len(self.buckets) + 1)
        idx = np.searchsorted(self.buckets, v, side="left")
        for b, c in zip(*np.unique(idx, return_counts=True)):
            counts[int(b)] += int(c)
        self._sums[key] = self._sums.get(key, 0.0) + float(v.sum())
        self._totals[key] = self._totals.get(key, 0) + int(v.size)

    def seed(self, *labels: str) -> None:
        """Materialize a zero-count series so exposition always carries it
        (dashboards and bench_metrics.prom see the series before the first
        observation)."""
        key = tuple(labels)
        if key not in self._counts:
            self._counts[key] = [0] * (len(self.buckets) + 1)
            self._sums[key] = 0.0
            self._totals[key] = 0

    def count(self, *labels: str) -> int:
        return self._totals.get(tuple(labels), 0)

    def sum(self, *labels: str) -> float:
        return self._sums.get(tuple(labels), 0.0)

    def merged_counts(self) -> list[int]:
        """All label sets' bucket counts merged — a checkpoint for
        quantile(since=): the streaming bench reports per-tier e2e
        quantiles as deltas so the warmup phase can't pollute them."""
        merged = [0] * (len(self.buckets) + 1)
        for counts in self._counts.values():
            for i, c in enumerate(counts):
                merged[i] += c
        return merged

    def quantile(self, q: float, since: Optional[list[int]] = None) -> float:
        """histogram_quantile over ALL label sets merged (bench reporting):
        the value of the bucket upper edge holding the q-th observation,
        linearly interpolated inside the bucket like PromQL. Returns 0.0
        with no observations; the top bucket clamps to its lower edge.
        `since` (a merged_counts() checkpoint) restricts the quantile to
        observations made after the checkpoint."""
        merged = self.merged_counts()
        if since is not None:
            merged = [m - s for m, s in zip(merged, since)]
        total = sum(merged)
        if total == 0:
            return 0.0
        rank = q * total
        cum = 0.0
        for i, c in enumerate(merged):
            prev = cum
            cum += c
            if cum >= rank and c > 0:
                lo = self.buckets[i - 1] if i > 0 else 0.0
                hi = self.buckets[i] if i < len(self.buckets) else lo
                return lo + (hi - lo) * ((rank - prev) / c)
        return self.buckets[-1]

    def expose(self) -> list[str]:
        out = [f"# HELP {self.name} {_escape_help(self.help)}",
               f"# TYPE {self.name} histogram"]
        for key, counts in sorted(self._counts.items()):
            cumulative = 0
            names = self.label_names + ("le",)
            for le, c in zip(self.buckets, counts):
                cumulative += c
                out.append(
                    f"{self.name}_bucket"
                    f"{_fmt_labels(names, key + (f'{le:g}',))} {cumulative}")
            out.append(f"{self.name}_bucket"
                       f"{_fmt_labels(names, key + ('+Inf',))} "
                       f"{self._totals[key]}")
            out.append(f"{self.name}_sum{_fmt_labels(self.label_names, key)} "
                       f"{self._sums[key]:g}")
            out.append(f"{self.name}_count{_fmt_labels(self.label_names, key)} "
                       f"{self._totals[key]}")
        return out


class Registry:
    """component-base metrics registry stand-in + /metrics exposition."""

    def __init__(self) -> None:
        self._metrics: dict[str, object] = {}

    def register(self, metric):
        if metric.name in self._metrics:
            raise ValueError(f"metric {metric.name} already registered")
        self._metrics[metric.name] = metric
        return metric

    def get(self, name: str):
        return self._metrics.get(name)

    def exposition(self) -> str:
        lines: list[str] = []
        for m in self._metrics.values():
            lines.extend(m.expose())
        return "\n".join(lines) + "\n"


# scheduled result labels (metrics.go:76-86)
SCHEDULED = "scheduled"
UNSCHEDULABLE = "unschedulable"
ERROR = "error"

DEFAULT_PROFILE = "default-scheduler"

# the device-modeled plugin set, used to pre-seed per-plugin series (the
# kernel-backed filters/scorers every device batch evaluates)
DEVICE_FILTER_PLUGINS = (
    "NodeUnschedulable", "NodeName", "TaintToleration", "NodeAffinity",
    "NodePorts", "NodeResourcesFit", "PodTopologySpread", "InterPodAffinity")
DEVICE_SCORE_PLUGINS = (
    "TaintToleration", "NodeAffinity", "NodeResourcesFit",
    "NodeResourcesBalancedAllocation", "PodTopologySpread",
    "InterPodAffinity", "ImageLocality")

# cluster_probe gauge label sets (ops/program.py PROBE_STATS columns
# split across the two per-resource families + the domain family); the
# exposition lint asserts these exact sets
CLUSTER_UTIL_STATS = ("p50", "p90", "p99", "max", "mean")
CLUSTER_FRAG_KINDS = ("fragmentation", "stranded")
CLUSTER_DOM_STATS = ("domains", "max", "min", "spread")
# resources every cluster exposes — pre-seeded so the series exist
# before the first probe; the live probe adds the rtable's real set
CLUSTER_SEED_RESOURCES = ("cpu", "memory")

# shard-lane gauge labels pre-seeded at construction (one TPU host's
# worth of lanes); a live profile on a wider mesh adds its real set
SHARD_SEED_LANES = tuple(str(i) for i in range(8))

# sharded control plane (ha/shards.py, ISSUE 17): pre-seeded label sets
# so dashboards see the series before the first split/steal/conflict
SHARD_SEED_IDS = tuple(str(i) for i in range(4))
SHARD_STEAL_REASONS = ("split", "merge", "steal", "rebalance")
# Scheduler._phase names, seeded on phase_self_seconds: the root
# schedule_pending, the drain phases in loop order, the informer handlers
LOOP_PHASES = ("schedule_pending", "queue_drain", "batch_build",
               "host_build", "host_snapshot", "host_tensorize",
               "host_group_seed", "ipa_seed", "host_cache", "audit",
               "device_dispatch",
               "cluster_probe", "readback", "commit", "bookkeeping",
               "release", "dispatcher_flush", "pod_add", "pod_update",
               "pod_delete")
# background threads whose CPU time thread_cpu_seconds counts
SIDE_THREADS = ("audit", "host_profiler")
# where a shadow-audit record's host-oracle replay runs
# (shadow_audit_replays_total)
AUDIT_REPLAY_ROUTES = ("process", "thread")
# the handler paths that confirm a bind echo (bind_echo_pods_total)
BIND_ECHO_PATHS = ("bulk", "per_pod")
# how the resident device carry took a bound pod's delete
# (pod_delete_carry_total)
POD_DELETE_CARRY_PATHS = ("delta", "reseed")
# the placement tiers that resolve a drain's pods (placement_pods_total)
PLACEMENT_TIERS = ("uniform", "wave_merge", "wave_serial", "wavescan", "scan",
                   "gang", "host_greedy", "host")
# the plan program's scan steps, a span's pods or its pow2 padding
# (plan_scan_steps_total)
PLAN_SCAN_SLOTS = ("valid", "pad")
CROSS_SHARD_OUTCOMES = ("conflict", "fenced")


class SchedulerMetrics:
    """The scheduler's series, bound to one Registry (metrics.go Register)."""

    def __init__(self, registry: Optional[Registry] = None,
                 queue_depths: Optional[Callable[[], dict]] = None,
                 inflight: Optional[Callable[[], dict]] = None):
        r = self.registry = registry or Registry()
        n = f"{SUBSYSTEM}_"
        self.schedule_attempts = r.register(Counter(
            n + "schedule_attempts_total",
            "Number of attempts to schedule pods, by result and profile.",
            ("result", "profile")))
        self.attempt_duration = r.register(Histogram(
            n + "scheduling_attempt_duration_seconds",
            "Scheduling attempt latency (scheduling algorithm + binding).",
            label_names=("result", "profile")))
        self.sli_duration = r.register(Histogram(
            n + "pod_scheduling_sli_duration_seconds",
            "E2e latency from first queue add to binding, by attempt count.",
            buckets=exponential_buckets(0.01, 2, 20),
            label_names=("attempts",)))
        self.pending_pods = r.register(Gauge(
            n + "pending_pods",
            "Pending pods by queue (active/backoff/unschedulable/gated).",
            ("queue",), callback=queue_depths))
        self.preemption_attempts = r.register(Counter(
            n + "preemption_attempts_total",
            "Total preemption attempts in the cluster."))
        self.preemption_victims = r.register(Histogram(
            n + "preemption_victims",
            "Number of selected preemption victims.",
            buckets=[1, 2, 4, 8, 16, 32, 64]))
        self.queue_incoming_pods = r.register(Counter(
            n + "queue_incoming_pods_total",
            "Pods added to scheduling queues by event and queue.",
            ("queue", "event")))
        self.permit_wait_duration = r.register(Histogram(
            n + "permit_wait_duration_seconds",
            "Time pods spend parked at WaitOnPermit.",
            label_names=("result",)))
        self.device_batch_size = r.register(Histogram(
            n + "device_batch_size",
            "Pods assigned per device program dispatch.",
            buckets=[1, 8, 32, 128, 512, 1024, 2048, 4096, 8192]))
        self.device_batch_duration = r.register(Histogram(
            n + "device_batch_duration_seconds",
            "Wall time of one device batch (dispatch to readback)."))
        self.api_dispatcher_calls = r.register(Counter(
            n + "api_dispatcher_calls_total",
            "API calls flushed by the dispatcher, by type and result.",
            ("call_type", "result")))
        self.plugin_execution_duration = r.register(Histogram(
            n + "plugin_execution_duration_seconds",
            "Duration of running a plugin at a specific extension point, "
            "sampled on ~10% of host scheduling cycles "
            "(metrics.go:322 PluginExecutionDuration).",
            buckets=exponential_buckets(0.00001, 1.5, 20),
            label_names=("plugin", "extension_point", "status")))
        self.plugin_evaluation_total = r.register(Counter(
            n + "plugin_evaluation_total",
            "Number of plugin evaluations at filter/score extension "
            "points, by plugin and profile "
            "(metrics.go PluginEvaluationTotal).",
            ("plugin", "extension_point", "profile")))
        self.cache_divergence = r.register(Counter(
            n + "cache_divergence_total",
            "Discrepancies found by the cache comparer, by kind."
            , ("kind",)))
        self.api_retries = r.register(Counter(
            n + "api_retries_total",
            "Retried API calls (retriable errors: ServerTimeout/"
            "TooManyRequests/ServiceUnavailable), by call type.",
            ("call_type",)))
        self.device_fallbacks = r.register(Counter(
            n + "device_fallbacks_total",
            "Device batches degraded to the host-oracle path, by reason "
            "(dispatch/commit fault, invalid assignment, open circuit "
            "breaker).",
            ("reason",)))
        self.circuit_breaker_transitions = r.register(Counter(
            n + "device_circuit_breaker_transitions_total",
            "Device-tier circuit breaker state transitions.",
            ("state",)))
        self.resyncs = r.register(Counter(
            n + "resyncs_total",
            "Full cache+queue rebuilds from a fresh LIST (watch-stream "
            "loss recovery)."))
        self.wave_placement_waves = r.register(Counter(
            n + "wave_placement_waves_total",
            "Speculative placement waves executed on device (group "
            "drains: merge waves + wave-scan dispatches)."))
        self.wave_conflict_ratio = r.register(Histogram(
            n + "wave_conflict_ratio",
            "Per-drain fraction of pods whose speculative wave placement "
            "conflicted (prefix cuts + serially repaired pods over the "
            "span).",
            buckets=[0.0, 0.01, 0.05, 0.1, 0.25, 0.5, 0.75, 1.0]))
        self.wave_accepted_prefix = r.register(Histogram(
            n + "wave_accepted_prefix_len",
            "Conflict-free prefix length accepted by the first wave of "
            "each group drain.",
            buckets=[1, 4, 16, 64, 256, 1024, 4096]))
        self.gang_dispatch = r.register(Counter(
            n + "gang_dispatch_total",
            "Whole-gang device dispatches by outcome: placed (all-or-"
            "nothing accept committed), rejected (quorum infeasible, "
            "unwound on device), fallback (gang degraded to the serial "
            "Permit-barrier host path).",
            ("outcome",)))
        self.gang_quorum_wait = r.register(Histogram(
            n + "gang_quorum_wait_seconds",
            "Time a gang's members spent PreEnqueue-gated before quorum "
            "was met (first gated member to un-gate).",
            buckets=exponential_buckets(0.001, 4, 12)))
        # drain compiler (kubernetes_tpu/compiler/): plan-cache traffic +
        # the cost of the pow2 padding lattice
        self.compiler_plan_cache_hits = r.register(Counter(
            n + "compiler_plan_cache_hits_total",
            "Drain-compiler plan cache hits (a drain whose pod-mix "
            "structure matched a previously compiled DrainPlan)."))
        self.compiler_plan_cache_misses = r.register(Counter(
            n + "compiler_plan_cache_misses_total",
            "Drain-compiler plan cache misses (a fresh pod-mix structure "
            "compiled into a new DrainPlan)."))
        self.compiler_pad_waste = r.register(Histogram(
            n + "compiler_pad_waste_ratio",
            "Per-drain fraction of padded work slots in the compiled "
            "plan's device programs (pow2 pod buckets x pow2 signature "
            "lattice): 1 - real/padded.",
            buckets=[0.0, 0.05, 0.1, 0.25, 0.5, 0.75, 0.9, 1.0]))
        # columnar ingest & commit engine (kubernetes_tpu/ingest/):
        # generation-diff snapshot upload traffic — scattered rows vs
        # full matrix re-uploads (the 50k/s-vs-upload-bound split)
        self.ingest_rows_scattered = r.register(Counter(
            n + "ingest_rows_scattered_total",
            "Dirty node rows shipped to the device via the generation-"
            "diff scatter_rows entry instead of a full NodeArrays "
            "re-upload."))
        self.ingest_full_uploads = r.register(Counter(
            n + "ingest_full_uploads_total",
            "Full NodeArrays device uploads (first build, shape growth, "
            "or a dirty-row set too large for the incremental scatter)."))
        self.drain_phase = r.register(Histogram(
            n + "drain_phase_seconds",
            "Per-drain wall time by phase, each inclusive of the phases "
            "nested in it: host_build (snapshot + batch + group seeding), "
            "device (the dispatch enqueue only), readback (waiting for "
            "the device results, plus inexact-run replays), commit "
            "(assume + bind enqueue + failure handling). host_build "
            "decomposes into host_snapshot / host_tensorize / "
            "host_group_seed / host_cache children.",
            label_names=("phase",)))
        # loop phases on the profiler's clock (utils/tracing.py): every
        # second of the host loop, charged to the innermost open phase
        self.phase_self_seconds = r.register(Counter(
            n + "phase_self_seconds_total",
            "Host-loop seconds by the innermost open phase "
            "(Scheduler._phase; PhaseTrack self time): each phase net of "
            "the phases nested in it, so the phases of one "
            "schedule_pending call sum to its wall and its own "
            "schedule_pending share is the loop time no named phase "
            "covers. pod_add / pod_update / pod_delete are the informer "
            "handlers (upstream scheduler_event_handling_duration_seconds).",
            ("phase",)))
        self.gc_pause_seconds = r.register(Counter(
            n + "gc_pause_seconds_total",
            "Seconds the Python garbage collector ran, by generation "
            "(gc.callbacks, process-wide; mirrored at exposition). The "
            "collecting thread holds the interpreter lock throughout.",
            ("generation",)))
        self.thread_cpu_seconds = r.register(Counter(
            n + "thread_cpu_seconds_total",
            "CPU seconds of the scheduler's own background work, by "
            "thread: audit (the shadow audit's thread: replays routed to "
            "it, and for those routed to the worker process their "
            "pickling, the wait and publication), host_profiler (stack "
            "samples). They compete with the host loop for the "
            "interpreter lock.",
            ("thread",)))
        self.bind_echo_pods = r.register(Counter(
            n + "bind_echo_pods_total",
            "Pods whose bind the watch echoed back, by the handler path "
            "that confirmed them: bulk (one pass over a bind_all batch of "
            "pods this scheduler assumed) or per_pod (the informer "
            "handler: foreign binds, and batches arriving while pods are "
            "unschedulable, bind errors are pending or pods wait at "
            "Permit).",
            ("path",)))
        self.pod_delete_carry = r.register(Counter(
            n + "pod_delete_carry_total",
            "Deletes of bound pods, by how the resident device carry took "
            "them: delta (folded out of the carry as a negative placement "
            "before the next drain) or reseed (the carry was dropped, or "
            "was not resident, and the next drain reseeds it from the "
            "host snapshot).",
            ("path",)))
        self.placement_pods = r.register(Counter(
            n + "placement_pods_total",
            "Pods resolved by each placement tier, counted once per span "
            "when its result is read: uniform (run_uniform's closed form), "
            "wave_merge and wave_serial (run_wave's merge waves and the "
            "serial scan that finishes their remainder), wavescan "
            "(run_plan), scan (run_batch), gang (run_gang), host_greedy "
            "(the host's vectorized greedy) and host (the per-pod host "
            "oracle).",
            ("tier",)))
        self.plan_scan_steps = r.register(Counter(
            n + "plan_scan_steps_total",
            "Steps of the plan program's serial scan (run_plan), by slot: "
            "valid (a pod of the span) or pad (the span's pow2 bucket "
            "beyond its pods), counted at each dispatch.",
            ("slot",)))
        self.events_total = r.register(Counter(
            n + "events_total",
            "Scheduling events emitted by the event recorder, by type "
            "(Normal/Warning) and reason (events.go analog).",
            ("type", "reason")))
        self.unschedulable_nodes = r.register(Histogram(
            n + "unschedulable_nodes",
            "Per-FailedScheduling rejected-node count, by the plugin that "
            "rejected them (device mask-derived diagnosis).",
            buckets=[1, 8, 64, 512, 2048, 8192, 32768],
            label_names=("plugin",)))
        # device/compile cost capture (perf/ledger.py): mirrored from the
        # process-global compile ledger at exposition time
        self.xla_compiles = r.register(Counter(
            n + "xla_compiles_total",
            "XLA executables compiled per kernel entry point (fresh "
            "jit-cache entries; >1 per kernel = retraces).",
            ("kernel",)))
        self.xla_compile_seconds = r.register(Counter(
            n + "xla_compile_seconds",
            "Wall seconds spent in dispatches that minted a fresh XLA "
            "executable (trace + lower + compile), per kernel.",
            ("kernel",)))
        self.h2d_bytes = r.register(Counter(
            n + "h2d_bytes_total",
            "Host-device transfer bytes by the drain phase that paid "
            "them (node-array/group/table uploads; device_readback is "
            "the d2h direction).",
            ("phase",)))
        # kernel observatory (perf/observatory.py, ISSUE 14): mirrored
        # from the process-global observatory at exposition time
        self.kernel_device_seconds = r.register(Counter(
            n + "kernel_device_seconds",
            "Cumulative warm dispatch wall seconds per JIT kernel entry "
            "point (compiling calls excluded — xla_compile_seconds "
            "carries those).",
            ("kernel",)))
        self.kernel_dispatch_total = r.register(Counter(
            n + "kernel_dispatch_total",
            "Device dispatches per JIT kernel entry point (warm + "
            "compiling).",
            ("kernel",)))
        self.shard_lane_seconds = r.register(Gauge(
            n + "shard_lane_seconds",
            "Per-device local compute seconds from the latest "
            "sharded-lane profile (parallel/sharding.py "
            "profile_shard_lanes); 0 = unprofiled or unsharded.",
            ("lane",)))
        self.shard_imbalance_ratio = r.register(Gauge(
            n + "shard_imbalance_ratio",
            "Peak-lane over mean-lane local compute time from the "
            "latest sharded-lane profile (1.0 = perfectly balanced; "
            "0 = unprofiled)."))
        # shadow-oracle audit + decision provenance + SLO engine
        # (kubernetes_tpu/obs/, ISSUE 10)
        self.oracle_divergence = r.register(Counter(
            n + "oracle_divergence_total",
            "Shadow-oracle audit divergences between committed device "
            "decisions and the host-oracle replay, by kind: assignment "
            "(both bound, different node), reason (same verdict, "
            "different FailedScheduling histogram), verdict (bound vs "
            "unschedulable).",
            ("kind",)))
        self.shadow_audit_drains = r.register(Counter(
            n + "shadow_audit_drains_total",
            "Drains sampled by the shadow-oracle audit, by outcome "
            "(clean/divergent/skipped/error).",
            ("outcome",)))
        self.audit_replay_duration = r.register(Histogram(
            n + "audit_replay_seconds",
            "Wall time of one shadow-audit host-oracle replay "
            "(background worker, off the hot path)."))
        self.shadow_audit_replays = r.register(Counter(
            n + "shadow_audit_replays_total",
            "Shadow-audit records replayed, by where the host-oracle "
            "replay ran: process (the audit's worker process, off the "
            "scheduler's interpreter lock) or thread (the audit thread: "
            "synchronous audits, plugin sets other than the default, "
            "drains with volumes or resource claims).",
            ("where",)))
        self.audit_worker_cpu_seconds = r.register(Counter(
            n + "audit_worker_cpu_seconds_total",
            "CPU seconds the shadow audit's worker process spent on the "
            "records it replayed. They do not compete for the "
            "scheduler's interpreter lock, so thread_cpu_seconds_total "
            "leaves them out."))
        self.explain_duration = r.register(Histogram(
            n + "explain_seconds",
            "Wall time of one /debug/explain decision decomposition "
            "(prefix replay + explain_row kernel)."))
        self.slo_burn_rate = r.register(Gauge(
            n + "slo_burn_rate",
            "Error-budget burn rate per SLI and look-back window "
            "(obs/slo.py): error_rate / (1 - objective); 1.0 = consuming "
            "exactly the budget.",
            ("sli", "window")))
        # active/standby HA (kubernetes_tpu/ha/, ISSUE 12)
        self.leader_transitions = r.register(Counter(
            n + "leader_election_transitions_total",
            "Leader-elector state transitions, by reason: acquired "
            "(took the lease), released (voluntary handoff), lost "
            "(another holder claimed an expired lease), renew_deadline "
            "(deposed-leader slow path: renews kept failing past the "
            "renew deadline, stepped down before lease expiry).",
            ("reason",)))
        self.ha_failover = r.register(Histogram(
            n + "ha_failover_seconds",
            "Wall time of one standby takeover: final ledger tail drain "
            "+ delta resync + promotion (ha/standby.py). The warm-spare "
            "contract: well under a cold LIST + tensorize + JIT warm-up."))
        self.ha_ledger_tail_lag = r.register(Gauge(
            n + "ha_ledger_tail_lag_drains",
            "Drains the standby's ledger-tail cursor is behind the "
            "leader's drain ledger head, measured at each sync."))
        self.fenced_writes_rejected = r.register(Counter(
            n + "fenced_writes_rejected_total",
            "Dispatcher writes rejected by the API server for carrying "
            "a stale fencing token (lease generation) — a deposed "
            "leader's late flush, unwound through on_bind_error."))
        # sharded control plane (kubernetes_tpu/ha/shards.py, ISSUE 17)
        self.shard_assignments = r.register(Gauge(
            n + "shard_assignments",
            "Explicit profile/namespace keys routed to each shard by the "
            "fenced ShardMap (keys not listed route by stable hash).",
            ("shard",)))
        self.shard_rebalance = r.register(Histogram(
            n + "shard_rebalance_seconds",
            "Wall time of one shard lease handoff (split/merge/steal): "
            "predecessor park + generation-bump acquire + ledger annex + "
            "warm adopt from the parked set (ha/shards.py transfer)."))
        self.shard_steals = r.register(Counter(
            n + "shard_steals_total",
            "Shard lease handoffs, by reason: split (1→N topology "
            "change), merge (N→1 collapse), steal (peer takes a loaded "
            "or dead shard), rebalance (planned move).",
            ("reason",)))
        self.cross_shard_conflicts = r.register(Counter(
            n + "cross_shard_conflicts_total",
            "Cross-shard bind races detected at commit, by outcome: "
            "conflict (pod already bound by a peer — the pod-level "
            "guard) or fenced (stale shard-lease generation — the "
            "ordering primitive). Both unwind through on_bind_error.",
            ("outcome",)))
        self.incidents = r.register(Counter(
            n + "incidents_total",
            "Incident-watchdog evidence-bundle captures, by trigger: "
            "slo_breach (federated SLO ladder trip), divergence "
            "(shadow-oracle divergence growth), fence_storm "
            "(fenced-write burst over threshold), pipeline_stall (no "
            "pipeline forward progress beyond budget). Each capture "
            "writes one bounded bundle to incidentDir "
            "(kubernetes_tpu/obs/incident.py).",
            ("trigger",)))
        # streaming drain pipeline (kubernetes_tpu/pipeline.py, ISSUE 18):
        # per-stage busy walls + backpressure stalls, mirrored from the
        # pipeline's own counters at exposition time (publish_metrics)
        self.pipeline_stage_busy = r.register(Counter(
            n + "pipeline_stage_busy_seconds",
            "Cumulative busy wall seconds per streaming-pipeline stage: "
            "ingest (arrival admit + batch build + plan compile + "
            "dispatch enqueue), device (non-overlapping dispatch-to-"
            "ready execution windows), commit (assume/bind commit + "
            "bulk bind-echo flush). Sum across stages exceeding the "
            "pipeline wall == measured stage overlap.",
            ("stage",)))
        self.pipeline_backpressure = r.register(Counter(
            n + "pipeline_backpressure_total",
            "Streaming-pipeline stalls, labeled by the STALLED stage: "
            "ingest (batch close deferred: dispatch depth at cap), "
            "device (dispatch deferred: commit backlog at cap), commit "
            "(commit worker waited on the host lock).",
            ("stage",)))
        self.dispatcher_inflight = r.register(Gauge(
            n + "dispatcher_inflight",
            "In-flight work of the async commit pipeline at scrape time: "
            "queued api_calls (dispatcher) and dispatched-but-uncommitted "
            "drains.",
            ("kind",), callback=inflight))
        # pod-journey tracing + on-device cluster analytics
        # (kubernetes_tpu/obs/journey.py + ops cluster_probe, ISSUE 13)
        self.e2e_segment = r.register(Histogram(
            n + "e2e_segment_seconds",
            "Queue→bind e2e latency decomposition by segment: queue_wait "
            "(ready in queue to pop), gate_wait (PreEnqueue-gated, incl. "
            "gang quorum), drain (device dispatch to commit), "
            "commit_backlog (dispatcher enqueue to bind-echo confirm).",
            buckets=exponential_buckets(0.0001, 2, 22),
            label_names=("segment",)))
        self.pod_requeues = r.register(Counter(
            n + "pod_requeues_total",
            "Pods re-entering the scheduling queue, by cause (journey "
            "ledger requeue transitions: preemption nomination, "
            "FencedWrite unwind, breaker fallback, gang split, resync, "
            "bind error, plain unschedulable).",
            ("cause",)))
        self.journey_transitions = r.register(Counter(
            n + "journey_transitions_total",
            "Pod lifecycle transitions recorded by the journey ledger, "
            "by event.",
            ("event",)))
        self.cluster_utilization = r.register(Gauge(
            n + "cluster_utilization_ratio",
            "cluster_probe per-resource utilization at the latest drain "
            "sample: nearest-rank percentiles over nodes advertising the "
            "resource, plus the exact aggregate mean (sum used / sum "
            "capacity).",
            ("resource", "stat")))
        self.cluster_fragmentation = r.register(Gauge(
            n + "cluster_fragmentation_index",
            "cluster_probe free-capacity health per resource: "
            "fragmentation = 1 - largest single free block / total free; "
            "stranded = free capacity on bottleneck-tight nodes / total "
            "free.",
            ("resource", "kind")))
        self.cluster_domain_imbalance = r.register(Gauge(
            n + "cluster_domain_imbalance",
            "cluster_probe topology-domain pod-density stats (pods per "
            "valid node per domain) over the gang engine's Tesserae "
            "dom-id column.",
            ("stat",)))
        # critical-path observatory (kubernetes_tpu/perf/critical_path.py,
        # `CriticalPathObservatory` gate, ISSUE 20): per-drain bottleneck
        # attribution stamped on the flight record and summed here
        self.critical_path_seconds = r.register(Counter(
            n + "critical_path_seconds",
            "Seconds attributed to each critical-path cause across "
            "committed drains: host_build (snapshot/tensorize/group-seed/"
            "cache), device_compute / device_comms (device dispatch wall "
            "split by the sharded-lane comms share), commit (assume/bind "
            "+ bind-echo flush), backpressure (streaming-pipeline stage "
            "stalls), idle (lock-step readback wait — the overlap the "
            "pipeline reclaims).",
            ("cause",)))
        self.bottleneck_drains = r.register(Counter(
            n + "bottleneck_drains_total",
            "Committed drains by dominant critical-path verdict (argmax "
            "of the per-cause seconds above; all-zero drains count as "
            "idle).",
            ("cause",)))
        # pre-seed the zero samples so dashboards (and bench_metrics.prom)
        # always carry the fault-path series, faults or not
        from ..backend.dispatcher import CallType
        for ct in CallType:
            self.api_retries.inc(ct.value, by=0)
        for reason in ("dispatch", "commit", "invalid_assignment",
                       "circuit_open"):
            self.device_fallbacks.inc(reason, by=0)
        self.resyncs.inc(by=0)
        for outcome in ("placed", "rejected", "fallback"):
            self.gang_dispatch.inc(outcome, by=0)
        self.gang_quorum_wait.seed()
        self.compiler_plan_cache_hits.inc(by=0)
        self.compiler_plan_cache_misses.inc(by=0)
        self.ingest_rows_scattered.inc(by=0)
        self.ingest_full_uploads.inc(by=0)
        self.compiler_pad_waste.seed()
        self.wave_placement_waves.inc(by=0)
        self.wave_conflict_ratio.seed()
        self.wave_accepted_prefix.seed()
        for phase in ("host_build", "device", "readback", "commit",
                      "host_snapshot", "host_tensorize",
                      "host_group_seed", "host_cache"):
            self.drain_phase.seed(phase)
        for phase in LOOP_PHASES:
            self.phase_self_seconds.inc(phase, by=0)
        from ..utils.tracing import GC_PAUSES
        for gen in range(len(GC_PAUSES.seconds)):
            self.gc_pause_seconds.inc(str(gen), by=0)
        for thread in SIDE_THREADS:
            self.thread_cpu_seconds.inc(thread, by=0)
        for path in BIND_ECHO_PATHS:
            self.bind_echo_pods.inc(path, by=0)
        for path in POD_DELETE_CARRY_PATHS:
            self.pod_delete_carry.inc(path, by=0)
        for tier in PLACEMENT_TIERS:
            self.placement_pods.inc(tier, by=0)
        for slot in PLAN_SCAN_SLOTS:
            self.plan_scan_steps.inc(slot, by=0)
        # remaining registered-but-unseeded series: dashboards and
        # bench_metrics.prom must carry every series even when the run
        # never observes them (no permit waits, no divergence, no events)
        for result in (SCHEDULED, UNSCHEDULABLE, ERROR):
            self.schedule_attempts.inc(result, DEFAULT_PROFILE, by=0)
            self.attempt_duration.seed(result, DEFAULT_PROFILE)
        for result in ("allowed", "rejected"):
            self.permit_wait_duration.seed(result)
        self.sli_duration.seed("1")
        self.device_batch_size.seed()
        self.device_batch_duration.seed()
        self.preemption_victims.seed()
        self.preemption_attempts.inc(by=0)
        for state in ("open", "closed"):
            self.circuit_breaker_transitions.inc(state, by=0)
        for queue, event in (("active", "PodAdd"), ("gated", "PodAdd"),
                             ("unschedulable", "ScheduleAttemptFailure")):
            self.queue_incoming_pods.inc(queue, event, by=0)
        from ..backend.dispatcher import CallType
        for ct in CallType:
            self.api_dispatcher_calls.inc(ct.value, "success", by=0)
        for kind in ("device_vs_host", "host_vs_apiserver"):
            self.cache_divergence.inc(kind, by=0)
        for etype, reason in (("Normal", "Scheduled"),
                              ("Warning", "FailedScheduling")):
            self.events_total.inc(etype, reason, by=0)
        for plugin in DEVICE_FILTER_PLUGINS:
            self.unschedulable_nodes.seed(plugin)
        for plugin in DEVICE_FILTER_PLUGINS:
            self.plugin_execution_duration.seed(plugin, "Filter", "SUCCESS")
            self.plugin_evaluation_total.inc(plugin, "Filter",
                                             DEFAULT_PROFILE, by=0)
        for plugin in DEVICE_SCORE_PLUGINS:
            self.plugin_execution_duration.seed(plugin, "Score", "SUCCESS")
            self.plugin_evaluation_total.inc(plugin, "Score",
                                             DEFAULT_PROFILE, by=0)
        from ..perf.ledger import H2D_PHASES, device_entries
        for kernel in device_entries():
            self.xla_compiles.inc(kernel, by=0)
            self.xla_compile_seconds.inc(kernel, by=0)
            self.kernel_device_seconds.inc(kernel, by=0)
            self.kernel_dispatch_total.inc(kernel, by=0)
        for phase in H2D_PHASES:
            self.h2d_bytes.inc(phase, by=0)
        for lane in SHARD_SEED_LANES:
            self.shard_lane_seconds.set(0.0, lane)
        self.shard_imbalance_ratio.set(0.0)
        # seed the static fallback values; a wired callback (the live
        # scheduler) takes precedence at scrape time
        for kind in ("api_calls", "drains"):
            self.dispatcher_inflight.set(0.0, kind)
        from ..pipeline import STAGES as PIPELINE_STAGES
        for stage in PIPELINE_STAGES:
            self.pipeline_stage_busy.inc(stage, by=0)
            self.pipeline_backpressure.inc(stage, by=0)
        from ..perf.critical_path import CAUSES as CP_CAUSES
        for cause in CP_CAUSES:
            self.critical_path_seconds.inc(cause, by=0)
            self.bottleneck_drains.inc(cause, by=0)
        for kind in ("assignment", "reason", "verdict"):
            self.oracle_divergence.inc(kind, by=0)
        for outcome in ("clean", "divergent", "skipped", "error"):
            self.shadow_audit_drains.inc(outcome, by=0)
        self.audit_replay_duration.seed()
        for where in AUDIT_REPLAY_ROUTES:
            self.shadow_audit_replays.inc(where, by=0)
        self.audit_worker_cpu_seconds.inc(by=0)
        self.explain_duration.seed()
        from ..obs.slo import DEFAULT_OBJECTIVES, WINDOWS
        for sli in DEFAULT_OBJECTIVES:
            for _secs, window in WINDOWS:
                self.slo_burn_rate.set(0.0, sli, window)
        for reason in ("acquired", "released", "lost", "renew_deadline"):
            self.leader_transitions.inc(reason, by=0)
        self.ha_failover.seed()
        self.ha_ledger_tail_lag.set(0.0)
        self.fenced_writes_rejected.inc(by=0)
        for shard in SHARD_SEED_IDS:
            self.shard_assignments.set(0.0, shard)
        self.shard_rebalance.seed()
        for reason in SHARD_STEAL_REASONS:
            self.shard_steals.inc(reason, by=0)
        for outcome in CROSS_SHARD_OUTCOMES:
            self.cross_shard_conflicts.inc(outcome, by=0)
        from ..obs.incident import TRIGGERS
        for trigger in TRIGGERS:
            self.incidents.inc(trigger, by=0)
        from ..obs.journey import CAUSES, EVENTS, SEGMENTS
        for segment in SEGMENTS:
            self.e2e_segment.seed(segment)
        for cause in CAUSES:
            self.pod_requeues.inc(cause, by=0)
        for event in EVENTS:
            self.journey_transitions.inc(event, by=0)
        for res in CLUSTER_SEED_RESOURCES:
            for stat in CLUSTER_UTIL_STATS:
                self.cluster_utilization.set(0.0, res, stat)
            for kind in CLUSTER_FRAG_KINDS:
                self.cluster_fragmentation.set(0.0, res, kind)
        for stat in CLUSTER_DOM_STATS:
            self.cluster_domain_imbalance.set(0.0, stat)

    def sync_compile_ledger(self) -> None:
        """Mirror the process-global compile ledger (perf/ledger.py) into
        the xla_*/h2d series. Absolute assignment, not increment: the
        ledger owns the monotonic totals (jit caches are process-wide, so
        per-Scheduler deltas would under-report shared compiles)."""
        from ..perf.ledger import GLOBAL
        for kernel, rec in GLOBAL.kernels.items():
            self.xla_compiles._values[(kernel,)] = float(rec.compiles)
            self.xla_compile_seconds._values[(kernel,)] = rec.compile_seconds
        for phase, nbytes in GLOBAL.h2d.items():
            self.h2d_bytes._values[(phase,)] = float(nbytes)

    def sync_observatory(self) -> None:
        """Mirror the kernel observatory (perf/observatory.py) into the
        kernel_*/shard_* series — absolute assignment for the same
        process-global reason as the ledger sync above."""
        from ..perf.observatory import GLOBAL
        kernels, shard = GLOBAL.metrics_view()
        for kernel, (dispatches, seconds) in kernels.items():
            self.kernel_dispatch_total._values[(kernel,)] = float(dispatches)
            self.kernel_device_seconds._values[(kernel,)] = seconds
        for i, secs in enumerate(shard.get("laneSeconds", ())):
            self.shard_lane_seconds.set(float(secs), str(i))
        ratio = shard.get("imbalanceRatio")
        if ratio is not None:
            self.shard_imbalance_ratio.set(float(ratio))

    def sync_gc_pauses(self) -> None:
        """Mirror the process-global collector hook (utils/tracing.py
        GC_PAUSES) into gc_pause_seconds — absolute, like the ledger."""
        from ..utils.tracing import GC_PAUSES
        for gen, secs in enumerate(GC_PAUSES.seconds):
            self.gc_pause_seconds._values[(str(gen),)] = secs

    def exposition(self) -> str:
        self.sync_compile_ledger()
        self.sync_observatory()
        self.sync_gc_pauses()
        return self.registry.exposition()
