"""The Scheduler: host orchestrator around the batched device program.

Mirrors pkg/scheduler/scheduler.go (struct :74, New :282, Run :538) and
schedule_one.go, with one structural change (SURVEY §7): the serial
`ScheduleOne` loop becomes `schedule_pending`, which drains the whole activeQ
and assigns it in device-sized batches — one `run_batch` call per segment —
while pods whose constraints have no tensor form yet fall back to the host
oracle (`schedule_one_host`) in queue order, preserving the sequential-greedy
semantics end to end.

Cycle anatomy per batch (device segment):
  update_snapshot (incremental, cache.go:194) → apply_snapshot scatter →
  run_batch scan (ops/program.py) → per pod: assume (cache.go:369) +
  enqueue bind (api_dispatcher) | handleSchedulingFailure
  (schedule_one.go:1038) → adopt carry → flush dispatcher.

Bind failures forget the assumed pod and requeue (schedule_one.go:361-393).
Informer events feed the cache/queue exactly like eventhandlers.go and fire
MoveAllToActiveOrBackoffQueue with the matching ClusterEvent.
"""

from __future__ import annotations

import threading
import time as _time
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass, field
from functools import partial
from typing import Callable, Optional

import jax.numpy as jnp
import numpy as np

from .api.types import DEFAULT_SCHEDULER_NAME, Node, Pod
from .backend.apiserver import APIServer, FencedWrite, WatchHandlers
from .backend.cache import Cache, Snapshot
from .backend.dispatcher import APICall, APIDispatcher, CallType
from .backend.queue import ClusterEventWithHint, SchedulingQueue
from .backend.workloadmanager import (DEFAULT_SCHEDULING_TIMEOUT,
                                      WorkloadManager)
from .framework.interface import Code, CycleState, Status
from .framework.runtime import Framework, schedule_pod
from .framework.types import (ActionType, ClusterEvent, EventResource,
                              FitError, PodInfo, QueuedPodInfo)
from .obs.journey import (EV_ADOPT as _EV_ADOPT, EV_ASSIGN as _EV_ASSIGN,
                          EV_DRAIN as _EV_DRAIN, EV_EVICT as _EV_EVICT,
                          EV_FIT_ERROR as _EV_FIT_ERROR,
                          EV_PARK as _EV_PARK, EV_REQUEUE as _EV_REQUEUE)
from .ops.program import (PROBE_STATS, PodXs, Retire, ScoreConfig, WaveXs,
                          cluster_probe, initial_carry, retire_pods,
                          run_batch, run_plan, run_uniform, run_wave,
                          table_from_batch)
from .plugins import noderesources as nr
from .plugins.node_basics import (NodeName, NodePorts, NodeUnschedulable,
                                  PrioritySort, SchedulingGates,
                                  TaintToleration)
from .plugins.imagelocality import ImageLocality
from .plugins.interpodaffinity import InterPodAffinity
from .plugins.nodeaffinity import NodeAffinity
from .plugins.podtopologyspread import PodTopologySpread
from .state.batch import BatchBuilder, BatchDims
from .state.tensorize import (EFFECT_PREFER_NO_SCHEDULE, ClusterState,
                              pow2_at_least)
from .utils.logging import klog

EVENT_NODE_ADD = ClusterEvent(EventResource.NODE, ActionType.ADD)
EVENT_NODE_UPDATE = ClusterEvent(EventResource.NODE, ActionType.UPDATE)
EVENT_ASSIGNED_POD_DELETE = ClusterEvent(EventResource.ASSIGNED_POD, ActionType.DELETE)
EVENT_ASSIGNED_POD_ADD = ClusterEvent(EventResource.ASSIGNED_POD, ActionType.ADD)
EVENT_POD_UPDATE = ClusterEvent(EventResource.POD, ActionType.UPDATE)


def node_update_action(old: Node, new: Node) -> ActionType:
    """Per-property node update flags (eventhandlers.go:88-99
    nodeSchedulingPropertiesChange): precise flags let queueing hints skip
    pods whose rejection the change cannot fix. Unschedulable flips map to
    the taint flag exactly like the reference (cordon == taint)."""
    flags = ActionType(0)
    if new.status.allocatable != old.status.allocatable:
        flags |= ActionType.UPDATE_NODE_ALLOCATABLE
    if new.metadata.labels != old.metadata.labels:
        flags |= ActionType.UPDATE_NODE_LABEL
    if (new.spec.taints != old.spec.taints
            or new.spec.unschedulable != old.spec.unschedulable):
        flags |= ActionType.UPDATE_NODE_TAINT
    if new.status.declared_features != old.status.declared_features:
        flags |= ActionType.UPDATE_NODE_DECLARED_FEATURE
    return flags


def pod_update_action(old: Pod, new: Pod) -> ActionType:
    """Per-property pod update flags (eventhandlers.go
    podSchedulingPropertiesChange)."""
    flags = ActionType(0)
    if new.metadata.labels != old.metadata.labels:
        flags |= ActionType.UPDATE_POD_LABEL
    if new.spec.scheduling_gates != old.spec.scheduling_gates:
        flags |= ActionType.UPDATE_POD_SCHEDULING_GATES
    if new.spec.tolerations != old.spec.tolerations:
        flags |= ActionType.UPDATE_POD_TOLERATION
    from .api import resources as res
    old_req = res.pod_requests(old)
    new_req = res.pod_requests(new)
    if any(new_req.get(k, 0) < v for k, v in old_req.items()):
        flags |= ActionType.UPDATE_POD_SCALE_DOWN
    return flags

# default plugin weights (apis/config/v1/default_plugins.go:30-93)
DEFAULT_WEIGHTS = {
    "TaintToleration": 3,
    "NodeAffinity": 2,
    "PodTopologySpread": 2,
    "InterPodAffinity": 2,
    "NodeResourcesFit": 1,
    "NodeResourcesBalancedAllocation": 1,
    "ImageLocality": 1,
}


def default_plugin_factories(client=None, ns_lister=None) -> list:
    """Ordered ZERO-ARG factories for the default plugin set. Each factory
    call constructs ONE fresh plugin (plugin objects carry per-scheduler
    handles, so instances must never be shared across profiles), without
    building the whole list — config.default_registry previously rebuilt
    the full default_plugins list per lookup (O(n²) across a registry
    walk)."""
    from .plugins.defaultbinder import DefaultBinder
    from .plugins.gangscheduling import GangScheduling
    from .plugins.volume_basics import (NodeVolumeLimits, VolumeRestrictions,
                                        VolumeZone)
    from .plugins.volumebinding import VolumeBinding
    from .plugins.dynamicresources import DynamicResources
    # filter order mirrors apis/config/v1/default_plugins.go:30
    from .plugins.node_basics import NodeDeclaredFeatures
    factories = [
        SchedulingGates, GangScheduling, PrioritySort,
        NodeDeclaredFeatures,
        NodeUnschedulable, NodeName, TaintToleration, NodeAffinity,
        NodePorts, nr.Fit,
        lambda: VolumeRestrictions(client),
        lambda: NodeVolumeLimits(client),
        lambda: VolumeBinding(client),
        lambda: VolumeZone(client),
        lambda: DynamicResources(client),
        nr.BalancedAllocation, PodTopologySpread,
        lambda: InterPodAffinity(ns_lister=ns_lister),
        ImageLocality,
    ]
    if client is not None:
        factories.append(lambda: DefaultBinder(client))
    return factories


def default_plugins(client=None, ns_lister=None) -> list:
    return [f() for f in default_plugin_factories(client, ns_lister)]


@dataclass
class Profile:
    name: str = DEFAULT_SCHEDULER_NAME
    framework: Optional[Framework] = None
    score_config: ScoreConfig = ScoreConfig()
    # True when every reserve/permit plugin is gang-only: non-gang pods can
    # then skip the per-bind framework hooks entirely (hot path)
    gang_only_hooks: bool = False
    # plugin names the config disabled (auto-wiring must not re-add them)
    disabled_plugins: tuple = ()
    # True when VolumeBinding is the only PreBind plugin: volume-free pods
    # can then skip the PreBind phase entirely (hot path)
    volume_only_pre_bind: bool = False
    # out-of-process extenders (framework/extender.py). Extenders are
    # API-coupled, so a profile with any routes ALL its pods through the
    # host oracle — the analog of the reference disabling batching when
    # extenders are configured (runtime/framework.go:775-780)
    extenders: tuple = ()


def _needs_per_pod_hooks(profile: "Profile", spec) -> bool:
    """True when a pod must run the full reserve/permit/pre-bind chain in
    _assume_and_bind. MUST mirror _assume_and_bind's `run_hooks` gate and
    _run_pre_bind's volume skip — _fast_commit bypasses both for pods
    where this returns False, so any change to either gate changes this
    predicate too."""
    fwk = profile.framework
    return bool(
        ((fwk.reserve_plugins or fwk.permit_plugins)
         and (not profile.gang_only_hooks
              or spec.workload_ref or spec.volumes or spec.resource_claims))
        or (fwk.pre_bind_plugins
            and (not profile.volume_only_pre_bind
                 or spec.volumes or spec.resource_claims)))


@dataclass
class _RunRec:
    """One dispatched device run (a uniform top-L call, a scan segment, or
    a wave) awaiting readback. `carry_in` is the device carry the run
    consumed — kept ONLY for uniform runs (the one kind that can rewind
    and replay); scan/wave runs DONATE their input carry on accelerator
    backends, so holding it would be a dangling reference."""

    kind: str                 # "uniform" | "scan" | "wave" | "wavescan"
    i: int
    j: int
    carry_in: object
    result: object            # device array: packed or assignments
    L: int = 0                # uniform L / wave bucket (packed layout)
    J: int = 0
    uniform: bool = False
    span: tuple = ("scan",)   # full span descriptor (replay re-dispatch)


@dataclass
class _PendingDrain:
    """A dispatched-but-uncommitted queue drain: the device results are in
    flight (copy_to_host_async issued); the host commit (assume + bind +
    failure handling) runs when they arrive. This is the TPU analog of the
    reference's async binding cycle (schedule_one.go:123 bindingCycle
    goroutine): the scheduling algorithm races ahead of the commit I/O."""

    qpis: list
    profile: object
    batch: object             # PodBatch (numpy) — kept for replay
    table: object             # PodTableDev
    na: object                # NodeArrays used at dispatch
    n: int
    groups_needed: bool
    records: list = field(default_factory=list)
    dispatched_at: float = 0.0
    # per-phase wall times + wave stats, accumulated from dispatch through
    # commit; the flight recorder persists them per drain
    phases: dict = field(default_factory=dict)
    wave: dict = field(default_factory=dict)
    # nominated-pod resource overlay active at dispatch (None = none);
    # replays must reproduce the dispatch-time overlay
    ovl: object = None
    # per-pod self-nomination rows (i32 [n], -1 = none) paired with ovl
    nom: object = None
    # monotonic drain id: correlates this drain's log lines, spans,
    # FlightRecorder entry and Scheduled/FailedScheduling events
    drain_id: int = 0
    # whole-gang drain (ops/gang.py): (workload ref, remaining quorum,
    # minCount) when this drain is one gang solved all-or-nothing
    gang: object = None
    # the builder's per-row CommitFacts list at dispatch time (the list
    # object is REPLACED on table reset, so this reference stays aligned
    # with batch.tidx even when later drains reset the table)
    facts: object = None
    gang_accepted: bool = False
    gang_raw: object = None      # raw per-member assignments (pre-unwind)
    gang_placed: int = 0
    # shadow-oracle audit record captured for this drain (obs/audit.py);
    # None = unsampled. Submitted with the committed decisions.
    audit: object = None
    # in-flight cluster_probe result (device arrays, ClusterStateProbe
    # gate): dispatched right after the drain over the post-drain carry,
    # resolved to a snapshot dict when this drain commits
    probe: object = None
    # per-kernel dispatch seconds captured inside this drain's
    # device_dispatch span (perf/observatory.py device lane); {} with the
    # KernelObservatory gate off
    kernels: dict = field(default_factory=dict)
    # the retire folds applied to the carry between the previous drain's
    # dispatch and this one's (Scheduler._fold_retires): a replay of this
    # drain applies them again first
    retire: tuple = ()

    def ready(self) -> bool:
        return all(r.result.is_ready() for r in self.records
                   if hasattr(r.result, "is_ready"))


@dataclass
class _WaitingPodRec:
    """A pod parked at Permit (reference runtime/waiting_pods_map.go): its
    resources stay assumed in the cache until allowed or rejected."""

    qpi: QueuedPodInfo
    assumed: Pod
    node_name: str
    cycle_state: CycleState
    deadline: float
    parked_at: float = 0.0
    wait_plugin: str = ""


class _WaitingPodHandle:
    """The WaitingPod the Permit plugins see (framework.WaitingPod). With a
    single permit plugin per profile, one Allow releases the pod (the
    reference requires every permit plugin's allow; the plugin-set loop in
    run_permit_plugins already serializes them)."""

    def __init__(self, scheduler: "Scheduler", uid: str):
        self._scheduler = scheduler
        self._uid = uid

    def allow(self, plugin_name: str) -> None:
        self._scheduler._allow_waiting(self._uid)

    def reject(self, plugin_name: str, reason: str = "") -> None:
        self._scheduler._reject_waiting(self._uid, reason)


class Scheduler:
    """scheduler.Scheduler (scheduler.go:74)."""

    def __init__(self, client: APIServer,
                 profiles: Optional[list[Profile]] = None,
                 batch_size: Optional[int] = None,
                 batch_dims: Optional[BatchDims] = None,
                 clock: Callable[[], float] = _time.monotonic,
                 percentage_of_nodes_to_score: Optional[int] = None,
                 config=None,
                 metrics=None,
                 tracer=None,
                 mesh=None):
        """`config` is a config.KubeSchedulerConfiguration — when given it
        supplies profiles, batch size, backoffs and sampling percentage;
        explicitly passed arguments win over the config's values.

        `mesh` (a jax.sharding.Mesh) makes multi-chip first-class: every
        drain-plan span — scan buckets, the closed-form uniform tier,
        speculative waves, gang dispatch and the batched preemption
        dry-run — runs the node-axis-sharded program (parallel/sharding.py)
        with XLA collectives over ICI. Decisions are bit-identical to
        single-device scheduling (tests/test_sharding.py +
        tests/test_sharded_mesh_parity.py)."""
        self.client = client
        self.clock = clock
        queue_backoffs = {}
        from .config import apply_compilation_cache
        apply_compilation_cache(
            config.compilation_cache_dir if config is not None else None)
        from .config.features import default_gate
        self.feature_gates = default_gate(
            config.feature_gates if config is not None else None)
        # columnar ingest & commit engine gate (kubernetes_tpu/ingest/):
        # consulted by the event-handler wiring below and the commit path
        self.columnar_ingest = self.feature_gates.enabled("ColumnarIngest")
        if config is not None:
            config.validate()
            from .config import build_profiles
            if profiles is None:
                profiles = build_profiles(config, client)
            if batch_size is None:
                batch_size = config.batch_size
            if percentage_of_nodes_to_score is None:
                percentage_of_nodes_to_score = config.percentage_of_nodes_to_score
            queue_backoffs = dict(
                pod_initial_backoff=config.pod_initial_backoff_seconds,
                pod_max_backoff=config.pod_max_backoff_seconds)
        self.batch_size = 512 if batch_size is None else batch_size
        self.mesh = mesh
        if mesh is not None:
            n_dev = int(mesh.devices.size)
            if n_dev & (n_dev - 1):
                raise ValueError(
                    f"mesh size {n_dev} must be a power of two: the pow2 "
                    "node-bucket padding guarantees shard divisibility "
                    "only then (run_batch_sharded precondition)")

        self._na_sharded = None      # mesh-placed NodeArrays cache
        self._na_sharded_gen = -1    # staging generation it was built from
        # Compatibility knob (types.go:62): the reference samples nodes to
        # bound filter cost; the TPU program filters ALL nodes in one
        # vectorized pass, so 100 is both the default and the fast path.
        # Values < 100 are accepted for config parity and treated as 100 —
        # SURVEY §7: adaptive sampling is deliberately dropped because the
        # full filter is cheaper than the bookkeeping it would save.
        self.percentage_of_nodes_to_score = (
            100 if percentage_of_nodes_to_score is None
            else percentage_of_nodes_to_score)
        if self.percentage_of_nodes_to_score < 100:
            # startup honesty: a config asking for sampling gets the full
            # vectorized pass, which changes decisions vs a sampling
            # reference (different node subset → different winner)
            klog.warning(
                "percentageOfNodesToScore below 100 is treated as 100: "
                "the device program filters and scores every node in one "
                "vectorized pass (SURVEY §7: sampling deliberately dropped)",
                requested=self.percentage_of_nodes_to_score)
        if profiles is None:
            fwk = Framework(DEFAULT_SCHEDULER_NAME, default_plugins(client),
                            weights=dict(DEFAULT_WEIGHTS))
            profiles = [Profile(framework=fwk)]
        self.profiles: dict[str, Profile] = {p.name: p for p in profiles}

        self.cache = Cache(clock=clock)
        self.snapshot = Snapshot()
        self.state = ClusterState()
        if mesh is not None:
            # the node bucket must never be smaller than the mesh
            self.state.dims.nodes = max(self.state.dims.nodes,
                                        int(mesh.devices.size))
        default_plugins_list = next(iter(self.profiles.values())).framework.plugins
        spread_p = next((p for p in default_plugins_list
                         if p.name() == "PodTopologySpread"), None)
        ipa_p = next((p for p in default_plugins_list
                      if p.name() == "InterPodAffinity"), None)
        self.builder = BatchBuilder(self.state, batch_dims,
                                    spread_plugin=spread_p, ipa_plugin=ipa_p)
        # the inter-pod-affinity half of count seeding, nested in
        # host_group_seed
        self.builder.groups.ipa_phase = partial(self._phase, "ipa_seed")
        self.dispatcher = APIDispatcher(
            client=client, on_bind_error=self._on_bind_error)
        self.config = config    # retained: ShardManager reads incident_dir
        if config is not None:
            self.dispatcher.retry_max_attempts = config.api_retry_max_attempts
            self.dispatcher.retry_base_seconds = config.api_retry_base_seconds

        default_fwk = next(iter(self.profiles.values())).framework
        # SchedulerQueueingHints off → empty hint map → every event
        # requeues conservatively (the gate-off behavior in the reference,
        # scheduling_queue.go isPodWorthRequeuing without hints)
        hints = (self._build_queueing_hints(default_fwk)
                 if self.feature_gates.enabled("SchedulerQueueingHints")
                 else {})
        # kept so resync() can rebuild the queue with identical wiring
        self._queue_kwargs = dict(
            pre_enqueue=self._make_pre_enqueue(default_fwk),
            queueing_hints=hints,
            clock=clock, **queue_backoffs)
        self.queue = SchedulingQueue(**self._queue_kwargs)

        from .metrics import SchedulerMetrics
        self.metrics = metrics or SchedulerMetrics(
            queue_depths=self._queue_depths,
            inflight=self._inflight_depths)
        self.dispatcher.metrics = self.metrics
        # generation-diff upload counters (state/tensorize.py): the state
        # layer counts, the registry exposes
        self.state.metrics = self.metrics
        for prof in self.profiles.values():
            prof.framework.metrics = self.metrics
        from .backend.debugger import CacheDebugger
        self.debugger = CacheDebugger(client, self.cache, self.queue,
                                      metrics=self.metrics)
        from .utils.tracing import NOOP_TRACER
        self.tracer = tracer or NOOP_TRACER
        # decision provenance + drain telemetry (events.py): Scheduled /
        # FailedScheduling events and the per-drain flight ring, both
        # served by the SchedulerServer's /debug endpoints
        from .events import EventRecorder, FlightRecorder
        self.events = EventRecorder(clock=clock, metrics=self.metrics)
        self.flight = FlightRecorder()
        # SLO burn-rate engine (obs/slo.py): SLI good/bad streams through
        # multi-window (5m/1h/6h) burn tracking; the burn-rate gauge is a
        # scrape-time callback and /debug/slo serves the full snapshot
        from .obs.slo import SLOEngine
        self.slo = SLOEngine(
            clock=clock,
            objectives=(config.slo_objectives if config is not None
                        else None))
        self.metrics.slo_burn_rate.callback = self.slo.gauge_callback
        # pod-journey tracing (obs/journey.py, `PodJourneyTracing` gate):
        # the columnar lifecycle ring behind /debug/pod and the
        # e2e_segment families. The ledger also OWNS the first-enqueue
        # e2e SLI clock, which stays on even with the gate off (the
        # requeue-restarts-the-clock bugfix must hold regardless), so
        # the ledger object always exists.
        from .obs.journey import JourneyLedger
        from .obs.timeline import Timeline
        self.journey = JourneyLedger(
            clock=clock, metrics=self.metrics,
            enabled=self.feature_gates.enabled("PodJourneyTracing"))
        self.queue.journey = self.journey
        self.dispatcher.journey = self.journey
        # per-second telemetry timeline (obs/timeline.py,
        # `TelemetryTimeline` gate): /debug/timeline + the config-gated
        # JSON-lines exporter; SLO samples stamp each closing bucket
        self.timeline = Timeline(
            horizon=(config.timeline_horizon_seconds
                     if config is not None else 900),
            clock=clock,
            export_path=(config.timeline_export_path
                         if config is not None else ""),
            slo_sample=self._timeline_slo_sample,
            enabled=self.feature_gates.enabled("TelemetryTimeline"))
        self.journey.timeline = self.timeline
        # on-device cluster analytics (ops cluster_probe,
        # `ClusterStateProbe` gate): one reduction over the resident
        # carry per device drain; resolved async at commit
        self._probe_enabled = self.feature_gates.enabled(
            "ClusterStateProbe")
        self._last_probe = None      # latest resolved snapshot (dict)
        # streaming drain pipeline (kubernetes_tpu/pipeline.py): attached
        # by StreamingPipeline.start(); backs /debug/pipeline
        self.pipeline = None
        # external-mutation counter: bumped with every device-state
        # invalidation; the shadow audit compares it across a drain's
        # dispatch→commit window (reason diffs are only valid when the
        # snapshot the device diagnosis read didn't move underneath)
        self._ext_mutations = 0
        # shadow-oracle audit (obs/audit.py, `ShadowOracleAudit` gate):
        # sampled drains are captured into the hash-chained ledger and
        # re-executed through the host oracle on a background worker
        self.audit = None
        if self.feature_gates.enabled("ShadowOracleAudit"):
            from .obs.audit import ShadowOracleAudit
            self.audit = ShadowOracleAudit(
                sample_rate=(config.shadow_audit_sample_rate
                             if config is not None else 1.0 / 64.0),
                max_replay_pods=(config.shadow_audit_max_replay_pods
                                 if config is not None else 64),
                dirpath=(config.shadow_audit_dir
                         if config is not None else ""),
                metrics=self.metrics, slo=self.slo,
                gates=self.feature_gates)
        # test-only decision-perturbation hook (tests/test_chaos.py):
        # a callable(pd, out) mutating resolved assignments in place —
        # proof that the shadow audit can actually fail
        self._test_assignment_perturb = None
        # jax.profiler session directory (config profilerTraceDir; "" = off)
        self.profiler_trace_dir = (
            config.profiler_trace_dir if config is not None else "")
        # the open loop phase (Scheduler._phase): self time per phase into
        # scheduler_phase_self_seconds_total, and the tag of every host
        # profiler sample; the collector's pauses are counted beside it
        from .utils.tracing import GC_PAUSES, PhaseTrack
        self.phase_track = PhaseTrack(self.metrics.phase_self_seconds)
        GC_PAUSES.install()
        # continuous host profiling (perf/profiler.py): a sampling thread
        # follows the host-loop thread, tagging every stack sample with
        # the open loop phase and the dispatching drain's signature-
        # cardinality bucket. The thread starts lazily on the first
        # schedule call and exits when this Scheduler is collected
        # (weakref owner).
        self._drain_seq = 0          # monotonic drain id (drain_id=0: none)
        self._sig_bucket_cell = [0]  # profiler-visible drain sig count
        self.profiler = None
        self.host_profiler_hz = (
            config.host_profiler_hz if config is not None else 200.0)
        if (self.host_profiler_hz > 0
                and self.feature_gates.enabled("ContinuousHostProfiling")):
            from .perf.profiler import HostProfiler
            cell = self._sig_bucket_cell
            self.profiler = HostProfiler(
                hz=self.host_profiler_hz,
                phase_fn=self.phase_track.current,
                bucket_fn=(lambda c=cell: c[0]),
                owner=self, thread_cpu=self.metrics.thread_cpu_seconds)
        # runtime sanitizer rails (analysis/rails.py): like the compile
        # ledger, the instance is process-global (the jit caches and the
        # transfer-guard config it drives are process-global) — the gate
        # of the most recently constructed Scheduler wins
        from .analysis.rails import GLOBAL as _rails
        self.rails = _rails
        self.rails.enable(self.feature_gates.enabled("SanitizerRails"))
        # kernel observatory (perf/observatory.py, `KernelObservatory`
        # gate): per-dispatch run-time attribution fed by the compile
        # ledger's measured_call. Process-global like the rails/ledger —
        # the most recently constructed Scheduler's gate wins.
        from .perf.observatory import GLOBAL as _observatory
        self.observatory = _observatory
        self.observatory.enable(
            self.feature_gates.enabled("KernelObservatory"))
        # critical-path observatory (perf/critical_path.py,
        # `CriticalPathObservatory` gate): per-drain bottleneck verdicts
        # stamped at commit, plus the device cost model fed by compile
        # events (perf/costmodel.py via the observatory — process-global,
        # most recent Scheduler's gate wins like the rails/observatory)
        self.critical_path_enabled = self.feature_gates.enabled(
            "CriticalPathObservatory")
        self.observatory.enable_cost_model(self.critical_path_enabled)
        # pipeline backpressure stall seconds already attributed to a
        # committed drain's verdict (delta baseline; StreamingPipeline
        # .start() zeroes it when a fresh pipeline attaches)
        self._bp_stall_committed = 0.0
        # sharded-lane profile (parallel/sharding.py profile_shard_lanes):
        # the first sharded dispatch stashes its inputs; the profile runs
        # ONCE after that drain commits (and on demand via
        # profile_shard_lanes(force=True) or /debug/kernels?lanes=refresh).
        # shard_profile_auto=False defers the auto-run — the probe
        # re-dispatches the scan-shaped program, so a throughput harness
        # (bench.py) measures first and profiles after the clock stops
        self._shard_profile_args = None
        self._shard_profile_done = False
        self.shard_profile_auto = True

        self.workload_manager = WorkloadManager(clock=clock)
        # pods parked at Permit (WaitOnPermit): uid -> _WaitingPodRec
        self._waiting_pods: dict[str, _WaitingPodRec] = {}
        # gang device placement (ops/gang.py run_gang): whole pod groups
        # solved as ONE all-or-nothing device dispatch once PreEnqueue
        # quorum is met — no Reserve/Permit/Unreserve churn on either the
        # accept or the reject path. Ineligible gangs (host-fallback
        # signatures, group constraints, pending nominations, parked
        # members) keep the reference's Permit-barrier host path.
        self.gang_device_enabled = (
            self.feature_gates.enabled("GenericWorkload")
            and self.feature_gates.enabled("GangDevicePlacement"))
        # Tesserae-style topology-contiguous slice packing: weight of the
        # per-domain member-count score column in the gang scan (0 = off,
        # keeping gang placements bit-identical to the serial oracle)
        self.gang_contiguity_weight = 0
        self._gang_dom = None        # device i32[N] node→domain ids
        self._gang_dom_key = (-1, -1)  # (staging_gen, node bucket) it fits
        self._gang_ndom = 1          # static domain count (probe jit key)
        # first-gated time per workload ref → gang_quorum_wait_seconds
        self._gang_gated_since: dict[str, float] = {}
        # HA role lifecycle (ha/standby.py, ActiveStandbyHA gate):
        # "active" schedules; "standby" only consumes watch events to keep
        # cache/queue/device state warm — schedule_pending refuses to
        # dispatch until promote() flips the role at takeover
        self.ha_role = "active"
        # sharded control plane (ha/shards.py): when shard_filter is set,
        # the watch stream forks — owned unbound pods queue; peers' pods
        # PARK in _shard_parked while workload/cache state still ingests
        # them, so a shard steal is shard_adopt() from the parked set (a
        # warm handoff), not a cold LIST + re-tensorize
        self.shard_filter: Optional[Callable[[Pod], bool]] = None
        self.shard_ids: tuple = ()   # owned shard ids (flight tag/debug)
        self._shard_parked: dict[str, Pod] = {}
        # ingest lock: watch handlers mutate queue/cache/workload state
        # from the API thread while sync()/resync() rebuild the same
        # structures — both sides hold this for their full critical
        # section (reentrant: a handler can fire inside resync's LIST)
        self.ingest_lock = threading.RLock()
        # hand every GangScheduling plugin its Handle (this Scheduler)
        from .plugins.gangscheduling import GangScheduling
        for prof in self.profiles.values():
            for p in prof.framework.plugins:
                if isinstance(p, GangScheduling):
                    p.handle = self
            from .plugins.dynamicresources import DynamicResources
            from .plugins.volumebinding import VolumeBinding
            # "gang_only": every reserve/permit plugin is scoped to gang,
            # volume or claim pods, so a pod with none of those skips the
            # hook chain (paired with _needs_per_pod_hooks)
            prof.gang_only_hooks = all(
                isinstance(p, (GangScheduling, VolumeBinding,
                               DynamicResources))
                for p in (prof.framework.reserve_plugins
                          + prof.framework.permit_plugins))
            prof.volume_only_pre_bind = all(
                isinstance(p, (VolumeBinding, DynamicResources))
                for p in prof.framework.pre_bind_plugins)

        # wire preemption (PostFilter) into every profile: the Evaluator
        # needs live handles (dispatcher, nominator, snapshot) that exist
        # only now — the reference threads the same deps through
        # frameworkImpl (default_preemption.go New)
        from .plugins.defaultpreemption import DefaultPreemption
        for prof in self.profiles.values():
            fwk = prof.framework
            dp = next((p for p in fwk.plugins
                       if isinstance(p, DefaultPreemption)), None)
            if dp is None:
                if "DefaultPreemption" in prof.disabled_plugins:
                    continue  # config turned preemption off for this profile
                dp = DefaultPreemption()
                fwk.plugins.append(dp)
                fwk.post_filter_plugins.append(dp)
            dp.dispatcher = self.dispatcher
            dp.nominator = self.queue.nominator
            dp.snapshot = self.snapshot
            if hasattr(client, "list_pdbs"):
                dp.pdb_lister = client.list_pdbs
            dp.extenders = tuple(prof.extenders)
            # batched device dry-run (SURVEY §7 step 8): the Evaluator's
            # candidate sweep runs as one gathered kernel against the
            # tensorized state; gated for config parity. On a mesh the
            # candidate rows are gathered host-side into a compact
            # single-device block (ISSUE 16) so the dry-run never mints a
            # second full-matrix device copy next to the sharded one
            if self.feature_gates.enabled("BatchedPreemptionDryRun"):
                from .framework.preemption import DeviceDryRunContext
                dp.device_ctx = DeviceDryRunContext(
                    state=self.state, builder=self.builder,
                    snapshot=self.snapshot, mesh=mesh)
            dp.set_framework(fwk)

        self._register_event_handlers()
        # stats (metrics/metrics.go essentials; full registry in metrics/)
        self.schedule_attempts = 0
        self.scheduled_count = 0
        self.unschedulable_count = 0
        self.error_count = 0
        self.device_batches = 0
        self.host_greedy_runs = 0
        self.host_scheduled = 0
        self.preemption_attempts = 0
        # device-tier degradation: an XLA fault (or garbage assignment
        # tensor) falls the batch back to the host oracle; K consecutive
        # faults open a circuit breaker that routes every drain to the
        # host path until a cooldown expires, after which ONE probe drain
        # re-tries the device tier (half-open)
        self.device_fault_threshold = 3
        self.device_fault_cooldown = 30.0
        self.device_fallbacks = 0
        self._device_faults = 0          # consecutive
        self._breaker_open_until = 0.0
        self._breaker_open = False
        # per-pod consecutive bind-error count → escalating error backoff
        self._bind_errors: dict[str, int] = {}
        # Device-resident scan carry, reused across batches while no event
        # outside the device's own placements touches node state. This is
        # what keeps steady-state scheduling free of host→device uploads and
        # device→host carry readbacks (SURVEY §7 hard-part 4: the round-trip
        # budget). Any external mutation invalidates it; the next device
        # segment reseeds from the host snapshot.
        self._device_carry = None
        self._carry_profile = None   # profile whose cfg filled the sig cache
        # nominator version the resident carry's SigCache overlay was
        # computed under (-1 = no nominations): _slow_parts/_row_refresh
        # bake the dispatch-time overlay into the cached fit_ok, so any
        # nomination change must zero the sig exactly like a profile
        # switch (ADVICE r5 high)
        self._carry_ovl_fp = -1
        # dispatched-but-uncommitted drains (async commit pipeline). Depth
        # bounds the optimism: device results stream back via
        # copy_to_host_async while later drains are created/dispatched, so
        # the readback latency pipelines instead of gating every drain
        # (SURVEY §7 hard-part 4; that latency on a local chip: not
        # measured).
        self._pending: deque[_PendingDrain] = deque()
        # SchedulerAsyncAPICalls off = no optimism: every dispatch commits
        # before the next (the reference's synchronous API-call mode)
        self.max_inflight_drains = (
            8 if self.feature_gates.enabled("SchedulerAsyncAPICalls") else 0)
        # device-resident PodTable cache: rows only append and the version
        # bumps on every mutation, so one upload serves every drain until
        # a new signature appears (instead of one host-to-device upload
        # per drain)
        self._table_dev = None
        self._table_dev_version = -1
        # group (spread / inter-pod affinity) device state lifecycle
        self._builder_reset_seen = 0  # builder.reset_count already consumed
        self._gd_dev = None          # GroupsDev (jnp) for the current carry
        self._gd_fam = None          # static active-family mask (jit key)
        self._gd_capacity = None     # (groups.device_rows(), node_bucket)
        #                              the resident group tensors were built
        #                              for; any pow2 crossing of the live
        #                              row count (or node growth) reseeds
        self._seeded_rows = 0        # signature rows whose counts are seeded
        # bound pods deleted while the carry is resident, as (signature
        # row, node row): folded out of the carry before the next dispatch
        # instead of reseeding it (_fold_retires)
        self._retires: list[tuple[int, int]] = []
        # the folds applied since the last drain was dispatched, for a
        # replay of the drains in flight (_replay_downstream)
        self._retire_folds: list = []
        # the drain compiler (kubernetes_tpu/compiler/): maps every
        # drain's pod mix to a static device program over the pow2
        # signature lattice — the case dispatch (uniform/scan/wave/
        # wavescan, the >4-signature cliff, the host-greedy gate) lives
        # there now. It owns the per-signature SurfaceCache (hoisted
        # kernel surfaces, retained across placement-only generations)
        # and the keyed plan cache the compile ledger's fixed retrace
        # point rests on.
        from .compiler import DrainCompiler
        self.compiler = DrainCompiler(state=self.state,
                                      builder=self.builder,
                                      gates=self.feature_gates,
                                      metrics=self.metrics)
        # columnar ingest & commit engine (kubernetes_tpu/ingest/): the
        # batched assume/bind path + the bulk bind-echo confirm; off
        # restores the serial per-pod paths (parity-test oracle)
        from .ingest.commit import CommitEngine
        self.commit_engine = (CommitEngine(self)
                              if self.columnar_ingest else None)
        # below this span length the per-pod scan beats a wave dispatch
        self.wave_min_span = 24

    # -- wiring ---------------------------------------------------------------

    @staticmethod
    def _make_pre_enqueue(fwk: Framework):
        """PreEnqueue gate with a constant-time fast path: when the only
        PreEnqueue plugins are the standard pair (SchedulingGates gates on
        spec.schedulingGates, GangScheduling on spec.workloadRef), a pod
        with neither field set cannot be gated — skip the plugin loop
        entirely (this runs once per created pod, on the ingest hot
        path)."""
        std_only = all(p.name() in ("SchedulingGates", "GangScheduling")
                       for p in fwk.pre_enqueue_plugins)
        if not std_only:
            return fwk.run_pre_enqueue_plugins
        run = fwk.run_pre_enqueue_plugins
        ok = Status.success()

        def pre_enqueue(pod: Pod) -> Status:
            spec = pod.spec
            if not spec.scheduling_gates and not spec.workload_ref:
                return ok
            return run(pod)
        return pre_enqueue

    @staticmethod
    def _build_queueing_hints(fwk: Framework) -> dict[str, list[ClusterEventWithHint]]:
        hints: dict[str, list[ClusterEventWithHint]] = {}
        for p in fwk.plugins:
            if hasattr(p, "events_to_register"):
                hints[p.name()] = list(p.events_to_register())
        return hints

    def _queue_depths(self) -> dict:
        gated = sum(1 for q in self.queue.unschedulable_pods.values()
                    if q.gated)
        return {("active",): float(len(self.queue.active_q)),
                ("backoff",): float(len(self.queue.backoff_q)),
                ("unschedulable",): float(
                    len(self.queue.unschedulable_pods) - gated),
                ("gated",): float(gated)}

    def _inflight_depths(self) -> dict:
        """scheduler_dispatcher_inflight{kind} callback: the async commit
        pipeline's live depth at scrape time."""
        return {("api_calls",): float(len(self.dispatcher)),
                ("drains",): float(len(self._pending))}

    # -- framework.Handle surface for Permit plugins --------------------------

    def get_workload(self, namespace: str, name: str):
        return self.client.get_workload(name)

    def activate(self, pods: list[Pod]) -> None:
        self.queue.activate(pods)

    def now(self) -> float:
        return self.clock()

    def get_waiting_pod(self, uid: str):
        if uid in self._waiting_pods:
            return _WaitingPodHandle(self, uid)
        return None

    def _allow_waiting(self, uid: str) -> None:
        """WaitOnPermit resolved positively: complete the parked pod's
        binding (schedule_one.go:302 onward)."""
        rec = self._waiting_pods.pop(uid, None)
        if rec is None:
            return
        self.metrics.permit_wait_duration.observe(
            max(self.clock() - rec.parked_at, 0.0), "allowed")
        profile = self.profiles.get(rec.qpi.pod.spec.scheduler_name)
        if profile is not None and not self._run_pre_bind(
                profile, rec.cycle_state, rec.qpi, rec.assumed,
                rec.node_name):
            return
        self.cache.finish_binding(rec.assumed)
        self.dispatcher.add(APICall(CallType.BIND, rec.assumed,
                                    node_name=rec.node_name))
        self.scheduled_count += 1
        self.events.scheduled(rec.qpi.pod.uid, rec.node_name)
        from .metrics import SCHEDULED
        pod = rec.qpi.pod
        self.metrics.schedule_attempts.inc(SCHEDULED,
                                           pod.spec.scheduler_name)
        start = rec.qpi.initial_attempt_timestamp or rec.qpi.timestamp
        self.metrics.sli_duration.observe(max(self.clock() - start, 0.0),
                                          str(rec.qpi.attempts or 1))
        rec.qpi.unschedulable_plugins = set()
        rec.qpi.consecutive_errors_count = 0

    def _reject_waiting(self, uid: str, reason: str = "") -> None:
        """WaitOnPermit rejection (timeout or plugin): unreserve, release
        the assumed resources, requeue as unschedulable."""
        rec = self._waiting_pods.pop(uid, None)
        if rec is None:
            return
        self.metrics.permit_wait_duration.observe(
            max(self.clock() - rec.parked_at, 0.0), "rejected")
        pod = rec.qpi.pod
        profile = self.profiles.get(pod.spec.scheduler_name)
        if profile is not None:
            profile.framework.run_reserve_plugins_unreserve(
                rec.cycle_state, rec.assumed, rec.node_name)
        try:
            self.cache.forget_pod(rec.assumed)
        except (KeyError, ValueError):
            pass
        self._invalidate_device_state()
        err = FitError(pod, 0)
        err.diagnosis.unschedulable_plugins = {rec.wait_plugin or "Permit"}
        self._handle_failure(rec.qpi, err, try_preempt=False)

    def _locked(self, fn):
        """Wrap a watch handler so it holds the ingest lock: handlers fire
        on the API thread while sync()/resync() rebuild queue/cache/device
        state — without the lock a watch event interleaves with the
        rebuild and lands on a structure about to be thrown away."""
        def wrapper(*args, **kw):
            with self.ingest_lock:
                return fn(*args, **kw)
        return wrapper

    def _register_event_handlers(self) -> None:
        """eventhandlers.go:499 addAllEventHandlers. Registration order
        matters on a live store: nodes replay before pods so bound pods
        land on real cache entries instead of imputed placeholders."""
        self.client.watch_nodes(WatchHandlers(
            on_add=self._locked(self._on_node_add),
            on_update=self._locked(self._on_node_update),
            on_delete=self._locked(self._on_node_delete)))
        self.client.watch_pods(WatchHandlers(
            on_add=self._locked(self._on_pod_add),
            on_update=self._locked(self._on_pod_update),
            on_delete=self._locked(self._on_pod_delete),
            on_add_bulk=self._locked(self._on_pod_add_bulk),
            on_update_bulk=(self._locked(self._on_pod_update_bulk)
                            if self.columnar_ingest else None)))
        if hasattr(self.client, "watch_workloads"):
            self.client.watch_workloads(WatchHandlers(
                on_add=self._on_workload_add))
        if hasattr(self.client, "watch_pvcs"):
            self.client.watch_pvcs(WatchHandlers(
                on_add=self._on_pvc_change, on_update=self._on_pvc_change))
        if hasattr(self.client, "watch_pvs"):
            self.client.watch_pvs(WatchHandlers(on_add=self._on_pv_add))
        if hasattr(self.client, "watch_pdbs"):
            self.client.watch_pdbs(WatchHandlers(
                on_add=self._on_pdb_change, on_update=self._on_pdb_change,
                on_delete=self._on_pdb_change))
        if hasattr(self.client, "watch_resource_claims"):
            self.client.watch_resource_claims(WatchHandlers(
                on_add=lambda c: self.queue.move_all_to_active_or_backoff_queue(
                    ClusterEvent(EventResource.RESOURCE_CLAIM, ActionType.ADD),
                    None, c),
                on_update=lambda o, n: self.queue.move_all_to_active_or_backoff_queue(
                    ClusterEvent(EventResource.RESOURCE_CLAIM, ActionType.UPDATE),
                    o, n)))
        if hasattr(self.client, "watch_resource_slices"):
            self.client.watch_resource_slices(WatchHandlers(
                on_add=lambda s: self.queue.move_all_to_active_or_backoff_queue(
                    ClusterEvent(EventResource.RESOURCE_SLICE, ActionType.ADD),
                    None, s)))

    def _responsible(self, pod: Pod) -> bool:
        return pod.spec.scheduler_name in self.profiles

    def _shard_owns(self, pod: Pod) -> bool:
        """Does this instance's shard slice cover the pod? Unsharded
        operation (shard_filter unset) owns everything."""
        return self.shard_filter is None or self.shard_filter(pod)

    # -- event handlers (eventhandlers.go) ------------------------------------

    def _invalidate_device_state(self) -> None:
        self._device_carry = None
        self._ext_mutations += 1
        # the reseed reads the snapshot, which has the deletes already
        self._retires, self._retire_folds = [], []

    def _on_pod_add(self, pod: Pod) -> None:
        self.workload_manager.add_pod(pod)
        if pod.spec.node_name:
            self.cache.add_pod(pod)
            self._invalidate_device_state()
            self.queue.move_all_to_active_or_backoff_queue(
                EVENT_ASSIGNED_POD_ADD, None, pod)
        elif self._responsible(pod):
            if not self._shard_owns(pod):
                # a peer shard's pod: stay warm (workload state above,
                # node/cache state via the bind echo) but don't schedule
                self._shard_parked[pod.uid] = pod
                self._journey_park([pod], detail="peer shard's pod")
                return
            self.queue.add(pod)
            gated = (pod.uid in self.queue.unschedulable_pods)
            self.metrics.queue_incoming_pods.inc(
                "gated" if gated else "active", "PodAdd")
            if pod.spec.workload_ref:
                ref = pod.spec.workload_ref
                if gated:
                    self._gang_gated_since.setdefault(ref, self.clock())
                # a new gang member can un-gate ITS group (PreEnqueue
                # quorum); other gangs' quorums are unaffected — and only
                # once the group can actually reach quorum (below
                # minCount the retry cannot move anything, so a 512-pod
                # gang's ingest skips 511 pointless sweeps)
                if self._gang_quorum_possible(pod):
                    self.queue.retry_gated(ref=ref)
                    self._observe_quorum_waits()

    def _on_pod_add_bulk(self, pods: list[Pod]) -> None:
        """Batch ingest (create_pods fan-out): plain unbound pods owned by
        this scheduler take the queue's bulk add; bound or foreign pods
        fall back to the per-pod path. Gang members also bulk-add, but
        their WorkloadManager registration happens FIRST for the whole
        chunk — a gang arriving complete in one chunk then passes
        PreEnqueue quorum at its own add (no gate → un-gate churn at
        all), and the quorum retry runs once per gang, not per member."""
        with self._phase("pod_add", pods=len(pods)):
            self._add_pods(pods)

    def _add_pods(self, pods: list[Pod]) -> None:
        plain: list[Pod] = []
        gang_pods: list[Pod] = []
        parked: list[Pod] = []
        for pod in pods:
            if pod.spec.node_name or not self._responsible(pod):
                self._on_pod_add(pod)
            elif not self._shard_owns(pod):
                self.workload_manager.add_pod(pod)
                self._shard_parked[pod.uid] = pod
                parked.append(pod)
            elif pod.spec.workload_ref:
                self.workload_manager.add_pod(pod)
                gang_pods.append(pod)
            else:
                self.workload_manager.add_pod(pod)
                plain.append(pod)
        if parked:
            self._journey_park(parked, detail="peer shard's pod")
        if plain:
            n = self.queue.add_bulk(plain)
            self.metrics.queue_incoming_pods.inc("active", "PodAdd",
                                                 by=len(plain) - n)
            if n:
                self.metrics.queue_incoming_pods.inc("gated", "PodAdd", by=n)
        if gang_pods:
            n = self.queue.add_bulk(gang_pods)
            self.metrics.queue_incoming_pods.inc("active", "PodAdd",
                                                 by=len(gang_pods) - n)
            if n:
                self.metrics.queue_incoming_pods.inc("gated", "PodAdd",
                                                     by=n)
            now = self.clock()
            refs = dict.fromkeys(p.spec.workload_ref for p in gang_pods)
            gated_refs = self.queue.gated_refs() if n else set()
            for ref in refs:
                if ref in gated_refs:
                    self._gang_gated_since.setdefault(ref, now)
            for ref in refs:
                member = next(p for p in gang_pods
                              if p.spec.workload_ref == ref)
                if self._gang_quorum_possible(member):
                    self.queue.retry_gated(ref=ref)
            self._observe_quorum_waits()

    def _on_pod_update(self, old: Pod, new: Pod) -> None:
        self.workload_manager.update_pod(old, new)
        if new.spec.node_name:
            if old.spec.node_name:
                self.cache.update_pod(old, new)
                self._invalidate_device_state()
                flags = pod_update_action(old, new)
                if flags:
                    self.queue.move_all_to_active_or_backoff_queue(
                        ClusterEvent(EventResource.ASSIGNED_POD, flags),
                        old, new)
            else:
                # became bound. Our own bind echo confirms a pod the device
                # carry already accounts for (it was assumed before the bind
                # was dispatched); anything else is an external mutation.
                if not self.cache.is_assumed_pod(new):
                    self._invalidate_device_state()
                self._bind_errors.pop(new.uid, None)
                self._shard_parked.pop(new.uid, None)  # peer bound it
                self.cache.add_pod(new)
                self.queue.delete(new)
                self._journey_confirm([new.uid])
                self.metrics.bind_echo_pods.inc("per_pod")
                self.queue.move_all_to_active_or_backoff_queue(
                    EVENT_ASSIGNED_POD_ADD, old, new)
        elif self._responsible(new):
            if not self._shard_owns(new) and new.uid in self._shard_parked:
                self._shard_parked[new.uid] = new  # keep the park fresh
                return
            if self._shard_parked.pop(new.uid, None) is not None:
                # ownership arrived between park and this update
                self.queue.add(new)
                return
            self.queue.update(old, new)
            flags = pod_update_action(old, new)
            if flags:
                # gate removal needs no special-casing: queue.update above
                # already re-ran PreEnqueue for the gated entry
                self.queue.move_all_to_active_or_backoff_queue(
                    ClusterEvent(EventResource.POD, flags), old, new)

    def _on_pod_update_bulk(self, pairs: list) -> None:
        """Bulk Binding echo (apiserver.bind_all fan-out): the common
        shape — our own bulk bind confirming pods we assumed — collapses
        to one pass over the batch instead of the per-pod informer dance
        (workload bookkeeping, a fresh _PodState, four queue dict probes
        and a move_all sweep per pod). Anything off-shape, or any state
        the per-pod path would consult (unschedulable pods whose queueing
        hints need the individual pod, pending bind errors, pods waiting
        at Permit), falls back to `_on_pod_update` per pod — semantics
        stay identical by construction. While other drains are in
        flight the pass writes the in-flight event log itself, one
        AssignedPodAdd entry per confirmed pod in pair order, as the
        per-pod move_all calls would."""
        with self._phase("pod_update", pods=len(pairs)):
            self._update_pods(pairs)

    def _update_pods(self, pairs: list) -> None:
        q = self.queue
        if q.unschedulable_pods or self._bind_errors or self._waiting_pods:
            for old, new in pairs:
                self._on_pod_update(old, new)
            return
        assumed = self.cache.assumed_pods
        wm_update = self.workload_manager.update_pod
        active = q.active_q
        backoff = q.backoff_q
        confirm: list = []
        logged = 0      # pairs[:logged] have their in-flight log entries
        for i, (old, new) in enumerate(pairs):
            uid = new.metadata.uid
            if (not new.spec.node_name or old.spec.node_name
                    or uid not in assumed):
                # log the confirmed pairs before it first: event seq
                # numbers follow pair order, as on the per-pod path
                q.log_in_flight_bulk(EVENT_ASSIGNED_POD_ADD,
                                     pairs[logged:i])
                logged = i + 1
                self._on_pod_update(old, new)
                continue
            wm_update(old, new)
            confirm.append(new)
            if uid in active or uid in backoff:
                q.delete(new)
        # EVENT_ASSIGNED_POD_ADD per confirmed pod: the in-flight log
        # entry only — with no unschedulable pods (checked above) the
        # move_all sweep has nothing to move
        q.log_in_flight_bulk(EVENT_ASSIGNED_POD_ADD, pairs[logged:])
        if confirm:
            self.cache.confirm_bound(confirm)
            self._journey_confirm([p.uid for p in confirm])
            self.metrics.bind_echo_pods.inc("bulk", by=len(confirm))

    def _on_pod_delete(self, pod: Pod) -> None:
        # one per pod: the self-time mark alone, no span
        with self.phase_track.scope("pod_delete"):
            self._delete_pod(pod)

    def _delete_pod(self, pod: Pod) -> None:
        self.workload_manager.delete_pod(pod)
        if pod.uid in self._waiting_pods:
            self._reject_waiting(pod.uid, "pod deleted")
        self._bind_errors.pop(pod.uid, None)
        self._shard_parked.pop(pod.uid, None)
        self.journey.forget(pod.uid)
        if pod.spec.node_name:
            retire = self._retire_of(pod)
            self.cache.remove_pod(pod)
            if retire is None:
                self._invalidate_device_state()
                self.metrics.pod_delete_carry.inc("reseed")
            else:
                # still an external event (the audit compares failure
                # reasons only across drains none separates), but the
                # carry stays resident
                self._retires.append(retire)
                self._ext_mutations += 1
                self.metrics.pod_delete_carry.inc("delta")
            self.queue.move_all_to_active_or_backoff_queue(
                EVENT_ASSIGNED_POD_DELETE, pod, None)
        else:
            self.queue.delete(pod)

    def _retire_of(self, pod: Pod) -> Optional[tuple[int, int]]:
        """(signature row, node row) of a bound pod whose delete the
        resident carry takes as a negative placement, or None when the
        carry must be reseeded instead: no resident carry, a mesh, live
        nominations (the overlay), a pod with volumes or claims (their
        bindings live outside the carry), a pod the device did not place
        from a still-current row (host-path pods, pods bound by others, a
        table reset since: it replaces the list the row token holds), a
        row or row capacity the carry's group tensors were not seeded
        for, or a node outside the matrix."""
        carry = self._device_carry
        if (carry is None or self.mesh is not None
                or self.queue.nominator.nominated_pods
                or pod.spec.volumes or pod.spec.resource_claims):
            return None
        ps = self.cache.pod_states.get(pod.uid)
        row = ps.row if ps is not None else None
        b = self.builder
        if row is None or row[0] is not b.row_facts:
            return None
        n = carry.npods.shape[0]
        if carry.groups is not None and (
                row[1] >= self._seeded_rows
                or (b.groups.device_rows(), n) != self._gd_capacity):
            return None
        node = self.state.node_index.get(ps.pod.spec.node_name)
        if node is None or node >= n:
            return None
        return row[1], node

    def _fold_retires(self, carry):
        """Fold the pending retires out of `carry` in one `retire_pods`
        dispatch and make the result the resident carry. The fold is kept
        until the next drain is dispatched: a replay of the drains in
        flight folds it again at the same point of the chain."""
        with self.ingest_lock:   # the delete handlers append under it
            retires, self._retires = self._retires, []
        rows = np.array([r for r, _ in retires], np.int32)
        nodes = np.array([n for _, n in retires], np.int32)
        wt, slot = np.unique(rows, return_inverse=True)
        S = pow2_at_least(len(wt), 1)
        cnt = np.zeros((S, carry.npods.shape[0]), np.int32)
        np.add.at(cnt, (slot, nodes), 1)
        wt = np.resize(wt, S)   # padding repeats rows with zero counts
        t = self.builder.table
        rt = Retire(wt=wt, cnt=cnt, req=t.req[wt],
                    nonzero_req=t.nonzero_req[wt], port_ids=t.port_ids[wt])
        from .perf.ledger import GLOBAL as _ledger
        _ledger.note_h2d_tree("host_snapshot", rt)
        gd, fam = ((self._gd_dev, self._gd_fam) if carry.groups is not None
                   else (None, None))
        fold = (Retire(*(jnp.asarray(x) for x in rt)), gd, fam)
        self._retire_folds.append(fold)
        self._device_carry = retire_pods(carry, *fold)
        return self._device_carry

    def _on_pvc_change(self, *args) -> None:
        """PVC add/update can unblock VolumeBinding rejects
        (volume_binding.go EventsToRegister)."""
        old, new = (args[0], args[1]) if len(args) == 2 else (None, args[0])
        self.queue.move_all_to_active_or_backoff_queue(
            ClusterEvent(EventResource.PVC, ActionType.ADD | ActionType.UPDATE),
            old, new)

    def _on_pv_add(self, pv) -> None:
        """A new PV can satisfy a WFFC claim that had no match
        (volume_binding.go EventsToRegister: PV Add)."""
        self.queue.move_all_to_active_or_backoff_queue(
            ClusterEvent(EventResource.PV, ActionType.ADD), None, pv)

    def _on_pdb_change(self, *args) -> None:
        """A PDB change can alter preemption viability for pods rejected by
        DefaultPreemption (its budget freed up → a candidate now exists).
        Unschedulable pods carry the FILTER plugins as rejectors, whose
        hints don't cover PDB events — so this uses the wildcard event
        (conservative requeue), not EventResource.PDB which every hint map
        would veto. PDB changes are rare; the broad sweep is cheap."""
        old, new = (args[0], args[1]) if len(args) == 2 else (None, args[0])
        self.queue.move_all_to_active_or_backoff_queue(
            ClusterEvent(EventResource.WILDCARD, ActionType.ALL,
                         "PodDisruptionBudgetChange"),
            old, new)

    def _on_workload_add(self, workload) -> None:
        """A Workload's arrival can un-gate its gang's pods (PreEnqueue)
        and requeue unschedulable members (gangscheduling.go:100). Only
        the arriving workload's refs are re-evaluated (gated_by_ref
        index) — other gangs' quorums are unaffected by this event."""
        from .backend.workloadmanager import parse_workload_ref
        name = workload.metadata.name
        for ref in self.queue.gated_refs():
            if parse_workload_ref(ref)[0] == name:
                self.queue.retry_gated(ref=ref)
        self._observe_quorum_waits()
        self.queue.move_all_to_active_or_backoff_queue(
            ClusterEvent(EventResource.WORKLOAD, ActionType.ADD),
            None, workload)

    def _gang_quorum_possible(self, pod: Pod) -> bool:
        """True when the pod's group has reached its minCount in KNOWN
        pods — the only state in which a gated-member retry can move
        anything (PreEnqueue quorum, gangscheduling.go:120-158)."""
        from .backend.workloadmanager import (parse_workload_ref,
                                              pod_group_min_count)
        name, group = parse_workload_ref(pod.spec.workload_ref)
        workload = self.client.get_workload(name)
        if workload is None:
            return False
        min_count = pod_group_min_count(workload, group)
        if min_count is None:
            return False
        info = self.workload_manager.pod_group_info(pod)
        return info is not None and len(info.all_pods) >= min_count

    def _observe_quorum_waits(self) -> None:
        """Record gang_quorum_wait_seconds for every gang whose gated
        members just cleared (quorum met → retry_gated moved them)."""
        if not self._gang_gated_since:
            return
        live = self.queue.gated_refs()
        for ref in list(self._gang_gated_since):
            if ref not in live:
                now = self.clock()
                wait = max(now - self._gang_gated_since.pop(ref), 0.0)
                self.metrics.gang_quorum_wait.observe(wait)
                self.metrics.e2e_segment.observe(wait, "gate_wait")
                self.timeline.segment(now, "gate_wait", wait, 1)
                bad = wait > self.slo.threshold("gang_quorum_wait")
                self.slo.observe("gang_quorum_wait",
                                 good=0 if bad else 1, bad=1 if bad else 0)

    # -- journey / timeline plumbing (obs/journey.py, ISSUE 13) ---------------

    def _journey_confirm(self, uids: list) -> None:
        """Bind-echo confirms: the journey's bind_confirm transition, the
        commit_backlog segment (dispatcher enqueue → echo), and the
        per-pod clock cleanup."""
        now = self.clock()
        waits = self.journey.bind_confirmed(uids, now)
        if waits:
            self.metrics.e2e_segment.observe_array(waits, "commit_backlog")
            self.timeline.segment(now, "commit_backlog", sum(waits),
                                  len(waits))
        # the timeline's binds cell counts CONFIRMED binds (the watch
        # echo), not drain assignments — a bind-error retry must not
        # double-count the pod
        self.timeline.bump(now, "binds", len(uids))

    def _journey_requeue(self, uids: list, cause: str,
                         detail: str = "") -> None:
        """A pod (or batch) re-entered the queue: requeue transition with
        its cause + the requeue counter + the timeline sample."""
        if not uids:
            return
        now = self.clock()
        self.metrics.pod_requeues.inc(cause, by=len(uids))
        self.timeline.requeue(now, cause, by=len(uids))
        # the transition detail always LEADS with the cause so a
        # /debug/pod timeline names it even when an error string rides
        # along ("fence_unwind: write fenced: ...")
        self.journey.record_bulk(uids, _EV_REQUEUE, now,
                                 detail=f"{cause}: {detail}" if detail
                                 else cause)

    def _journey_park(self, pods: list, detail: str = "") -> None:
        """A peer shard's pods parked: first-class park transition AND
        the e2e SLI clock seed — a pod first sighted parked starts its
        clock at park time, so the stitched cross-shard timeline's
        firstEnqueue (the min across instances) anchors at the earliest
        sighting anywhere in the fleet, steal or no steal."""
        if not pods:
            return
        now = self.clock()
        for pod in pods:
            self.journey.first_enqueue(pod.uid, now)
        self.journey.record_bulk([p.uid for p in pods], _EV_PARK, now,
                                 detail=detail)

    def _timeline_slo_sample(self) -> dict:
        """Compact SLO sample stamped onto each closing timeline bucket:
        only the nonzero burn rates, keyed sli:window."""
        return {f"{sli}:{window}": round(rate, 4)
                for (sli, window), rate in
                self.slo.gauge_callback().items() if rate}

    def _resolve_probe(self, pd: "_PendingDrain") -> dict:
        """Resolve a drain's in-flight cluster_probe device result into
        the snapshot dict served at /debug/cluster, and publish it to the
        scheduler_cluster_* gauge families. A failed readback drops the
        sample (the commit itself never aborts on probe faults)."""
        if pd.probe is None:
            return {}
        from .metrics import (CLUSTER_DOM_STATS, CLUSTER_FRAG_KINDS,
                              CLUSTER_UTIL_STATS)
        try:
            per_res = np.asarray(pd.probe[0])
            dom = np.asarray(pd.probe[1])
            valid = int(np.asarray(pd.probe[2]))
        except Exception as e:
            klog.v(2).info("cluster probe readback failed", err=str(e))
            return {}
        rnames = self.state.rtable.names
        resources: dict = {}
        for r in range(min(len(rnames), per_res.shape[0])):
            row = per_res[r]
            resources[rnames[r]] = {
                stat: round(float(row[i]), 6)
                for i, stat in enumerate(PROBE_STATS)}
            for i, stat in enumerate(CLUSTER_UTIL_STATS):
                self.metrics.cluster_utilization.set(
                    float(row[i]), rnames[r], stat)
            for i, kind in enumerate(CLUSTER_FRAG_KINDS):
                self.metrics.cluster_fragmentation.set(
                    float(row[len(CLUSTER_UTIL_STATS) + i]),
                    rnames[r], kind)
        domains = {stat: round(float(dom[i]), 6)
                   for i, stat in enumerate(CLUSTER_DOM_STATS)}
        for i, stat in enumerate(CLUSTER_DOM_STATS):
            self.metrics.cluster_domain_imbalance.set(float(dom[i]), stat)
        return {"t": round(self.clock(), 6), "drainId": pd.drain_id,
                "validNodes": valid, "resources": resources,
                "domains": domains}

    def _on_node_add(self, node: Node) -> None:
        self.cache.add_node(node)
        self._invalidate_device_state()
        self.queue.move_all_to_active_or_backoff_queue(EVENT_NODE_ADD, None, node)

    def _on_node_update(self, old: Node, new: Node) -> None:
        self.cache.update_node(old, new)
        self._invalidate_device_state()
        flags = node_update_action(old, new)
        if flags:
            self.queue.move_all_to_active_or_backoff_queue(
                ClusterEvent(EventResource.NODE, flags), old, new)

    def _on_node_delete(self, node: Node) -> None:
        self.cache.remove_node(node)
        self._invalidate_device_state()

    # -- scheduling: batch path ----------------------------------------------

    def schedule_pending(self, max_batches: int = 0, wait: bool = True) -> int:
        """Drain + schedule everything currently pending. Returns the net
        number of successful binds committed so far (flush failures are not
        counted). With `wait=False` the call returns after dispatching:
        device results still in flight commit on a later call (or
        `wait_pending()`), which is what lets ingestion of the next pod
        chunk overlap the device readback. The call is the root loop
        phase: its own self time is the loop time no named phase covers."""
        if self.ha_role == "standby":
            # a standby never writes: binds from a non-leader would race
            # the active scheduler (and be fenced anyway). Takeover calls
            # promote() before resuming the loop.
            return 0
        with self._phase("schedule_pending"):
            return self._schedule_pending(max_batches, wait)

    def _schedule_pending(self, max_batches: int, wait: bool) -> int:
        if self.profiler is not None:
            self.profiler.ensure_running()
        start = self.scheduled_count
        batches = 0
        while True:
            # commit whatever has already landed
            self.commit_ready()
            with self._phase("queue_drain"):
                self.queue.flush_backoff_completed()
            if not len(self.queue.active_q):
                if not wait or not self._pending:
                    break
                self.wait_pending()
                continue    # a commit may have re-activated pods
            qlen = len(self.queue.active_q)
            if not wait and qlen < self.batch_size:
                # adaptive batching: let the queue accumulate so the next
                # dispatch amortizes the per-execution dispatch and
                # readback cost over more pods (that cost on a local chip:
                # not measured).
                # Dispatch early only to fill an idle pipeline, and only
                # once half a drain is available: a lower bar fragments
                # the workload into more executions than the latency they
                # hide is worth.
                if self._pending or qlen < max(self.batch_size // 2, 1):
                    break
            # device shapes are drain-size independent (uniform L comes
            # from batch_size, scan buckets from pow2 padding), so take
            # everything up to the cap — one execution per drain
            with self._phase("queue_drain"):
                qpis = self.queue.drain(self.batch_size)
            if not qpis:
                break
            with self.tracer.span("scheduling_cycle",
                                  pods=len(qpis)) as cycle:
                before = self.scheduled_count
                with self.tracer.span("schedule_batch"):
                    self._schedule_batch(qpis)
                while len(self._pending) > self.max_inflight_drains:
                    self._commit_next()
                with self._phase("dispatcher_flush"):
                    self.dispatcher.flush()
                cycle.set(bound=self.scheduled_count - before)
            batches += 1
            if max_batches and batches >= max_batches:
                break
        if wait:
            self.wait_pending()
        elif len(self.dispatcher):
            with self._phase("dispatcher_flush"):
                self.dispatcher.flush()
        if (self._shard_profile_args is not None
                and not self._shard_profile_done
                and self.shard_profile_auto
                and not self._pending):
            # one-shot sharded-lane profile (perf/observatory.py), off the
            # dispatch path: the first sharded drain armed it, the quiesced
            # pipeline runs it — per-lane seconds, imbalance and the comms
            # share behind scheduler_shard_* and /debug/kernels
            self.profile_shard_lanes()
        return self.scheduled_count - start

    def commit_ready(self, limit: int = 0) -> int:
        """Commit in-flight drains whose device results have landed, head
        first (commit order IS dispatch order — the carry/ledger contract).
        Stops at the first drain still executing; `limit` caps the number
        of commits (0 = all ready). Returns drains committed. This is the
        commit stage's entry for the streaming pipeline's worker; the
        lock-step loop uses it for its opportunistic head-drain."""
        done = 0
        while self._pending and self._pending[0].ready():
            self._commit_next()
            done += 1
            if limit and done >= limit:
                break
        return done

    def dispatch_once(self, max_pods: int = 0) -> int:
        """Close the current batch and dispatch it as one drain WITHOUT
        committing anything: the ingest stage's entry for the streaming
        pipeline (kubernetes_tpu/pipeline.py), which runs BatchBuilder +
        DrainCompiler work for the next drain while the device executes
        the current one and leaves every commit to the pipeline's commit
        worker. Returns the number of pods taken from the queue (0 =
        nothing eligible). Depth capping is the CALLER's job — the
        pipeline enforces its backpressure before calling; direct users
        get the `max_inflight_drains` safety net."""
        if self.ha_role == "standby":
            return 0
        self.queue.flush_backoff_completed()
        if not len(self.queue.active_q):
            return 0
        qpis = self.queue.drain(max_pods or self.batch_size)
        if not qpis:
            return 0
        with self.tracer.span("scheduling_cycle", pods=len(qpis)) as cycle:
            before = self.scheduled_count
            with self.tracer.span("schedule_batch"):
                self._schedule_batch(qpis)
            while len(self._pending) > self.max_inflight_drains:
                self._commit_next()
            cycle.set(bound=self.scheduled_count - before)
        return len(qpis)

    def profile_shard_lanes(self, force: bool = False):
        """Run the sharded-lane profile on the latest sharded dispatch's
        inputs (parallel/sharding.py profile_shard_lanes). Auto-runs once
        after the first sharded drain; `force=True` re-profiles (the
        /debug/kernels?lanes=refresh hook). Returns the profile dict, or
        None when no sharded dispatch has happened yet."""
        if self._shard_profile_args is None:
            return None
        if self._shard_profile_done and not force:
            return self.observatory.shard_profile() or None
        self._shard_profile_done = True
        from .parallel.sharding import profile_shard_lanes
        prof = profile_shard_lanes(*self._shard_profile_args)
        self.observatory.set_shard_profile(prof)
        return prof

    def wait_pending(self) -> None:
        """Commit every in-flight drain and flush the dispatcher — the
        pipeline barrier (reference WaitForCacheSync-style quiescence)."""
        self._drain_pending()
        with self._phase("dispatcher_flush"):
            self.dispatcher.flush()

    def prime(self) -> None:
        """Pre-build the host snapshot and device staging arrays from the
        current cluster state — the analog of the reference waiting for
        informer cache sync before serving (WaitForCacheSync,
        app/server.go): node ingestion cost lands here, not in the first
        scheduling cycle."""
        self._drain_pending()
        self.cache.update_snapshot(self.snapshot)
        self.state.apply_snapshot(self.snapshot)
        self.state.ensure_arrays()

    def _schedule_batch(self, qpis: list[QueuedPodInfo]) -> int:
        if (self.queue.nominator.nominated_pods
                and not self._overlay_eligible(qpis)):
            # nominated (preemptor) pods change OTHER pods' filter results
            # (two-pass RunFilterPluginsWithNominatedPods). The device
            # path models them as a fit-only resource OVERLAY; drains the
            # overlay cannot represent exactly (host-port or
            # lower-priority nominated pods, a nominated pod inside the
            # drain itself, a sharded mesh) take the host oracle —
            # nominations are short-lived (victim deletes flush at the
            # end of the previous cycle)
            self._drain_pending()
            return sum(1 if self._schedule_one_host(q) else 0 for q in qpis)
        # route per profile (profile.go:46 Map lookup): a drain can mix
        # schedulerNames; each maximal same-profile stretch runs with ITS
        # weights/strategy, in queue order
        bound = 0
        i = 0
        while i < len(qpis):
            name = qpis[i].pod.spec.scheduler_name
            j = i + 1
            while (j < len(qpis)
                   and qpis[j].pod.spec.scheduler_name == name):
                j += 1
            profile = self.profiles.get(name)
            if profile is None:
                self._drain_pending()
                for q in qpis[i:j]:
                    self._schedule_one_host(q)  # drops unowned pods
            else:
                bound += self._schedule_profile_batch(qpis[i:j], profile)
            i = j
        return bound

    def _schedule_profile_batch(self, qpis: list[QueuedPodInfo],
                                profile: Profile) -> int:
        if profile.extenders:
            # no tensor form for webhook hooks: host path, batching off
            self._drain_pending()
            return sum(1 if self._schedule_one_host(q) else 0
                       for q in qpis)
        bound = 0
        gangs, qpis = self._extract_gangs(qpis)
        for members, ref, needed, min_count in gangs:
            # whole pod group → ONE all-or-nothing device dispatch
            bound += self._dispatch_device_drain(
                members, profile, gang=(ref, needed, min_count))
        if not qpis:
            return bound
        pods = [q.pod for q in qpis]
        with self._phase("batch_build", pods=len(pods)):
            batch = self.builder.build(pods, pad_to=self.batch_size)
        if not batch.host_fallback.any():
            # common case: whole drain is device-eligible; reuse this build
            return bound + self._dispatch_device_drain(qpis, profile,
                                                       prebuilt=batch)
        fallback = batch.host_fallback
        i = 0
        while i < len(qpis):
            if fallback[i]:
                self._drain_pending()
                ok = self._schedule_one_host(qpis[i])
                bound += 1 if ok else 0
                i += 1
                continue
            j = i + 1
            while j < len(qpis) and not fallback[j]:
                j += 1
            bound += self._dispatch_device_drain(qpis[i:j], profile)
            # host pods interleave the drain: commit the device stretch now
            # so queue order is preserved end to end
            self._drain_pending()
            i = j
        return bound

    def _extract_gangs(self, qpis: list[QueuedPodInfo]):
        """Partition a profile stretch into whole-gang drains and the
        rest. A gang is extracted when the drain holds at least its
        remaining quorum of members and the group is device-eligible
        (gates on, no parked members, no volumes/claims — the hook chain
        the atomic commit bypasses must be vacuous; mesh drains dispatch
        through run_gang_sharded, ISSUE 16).
        Ineligible gangs stay in the generic flow: per-pod placement with
        the reference's Permit-barrier dance at commit."""
        if (not self.gang_device_enabled
                or self.queue.nominator.nominated_pods
                or not any(q.pod.spec.workload_ref for q in qpis)):
            return [], qpis
        from .backend.workloadmanager import (parse_workload_ref,
                                              pod_group_min_count)
        groups: dict[str, list] = {}
        order: list[str] = []
        rest: list[QueuedPodInfo] = []
        for q in qpis:
            ref = q.pod.spec.workload_ref
            if ref:
                if ref not in groups:
                    groups[ref] = []
                    order.append(ref)
                groups[ref].append(q)
            else:
                rest.append(q)
        out = []
        for ref in order:
            members = groups[ref]
            name, group = parse_workload_ref(ref)
            workload = self.client.get_workload(name)
            min_count = (pod_group_min_count(workload, group)
                         if workload is not None else None)
            if min_count is None:
                rest.extend(members)
                continue
            info = self.workload_manager.pod_group_info(members[0].pod)
            assigned = len(info.assigned) if info is not None else 0
            needed = max(min_count - assigned, 0)
            if needed == 0:
                # quorum already satisfied by bound members: the surplus
                # members schedule individually (Permit passes instantly)
                rest.extend(members)
                continue
            if (len(members) < needed
                    or any(m.pod.uid in self._waiting_pods
                           for m in members)
                    or any(m.pod.spec.volumes or m.pod.spec.resource_claims
                           for m in members)):
                self.metrics.gang_dispatch.inc("fallback")
                rest.extend(members)
                continue
            out.append((members, ref, needed, min_count))
        return out, rest

    def _dispatch_device_drain(self, qpis: list[QueuedPodInfo],
                               profile: Profile, prebuilt=None,
                               gang=None) -> int:
        """Build + dispatch one drain's device programs WITHOUT waiting for
        the results; appends a _PendingDrain whose commit happens when the
        async host copies land. Returns binds committed inside this call
        (only the host-fallback retry path commits synchronously)."""
        from .ops.groups import scatter_new_rows, to_device
        from .utils.logging import log_context

        did = self._drain_seq = self._drain_seq + 1
        if not self._device_available():
            # circuit breaker open: the device tier is sidelined until the
            # cooldown expires; the host oracle takes the drain
            self.device_fallbacks += 1
            self.metrics.device_fallbacks.inc("circuit_open")
            if gang is not None:
                self.metrics.gang_dispatch.inc("fallback")
            self.flight.record(
                profile=profile.name, pods=len(qpis), bound=0, failed=0,
                signatures=0, kinds=(), groups=False, phases={},
                breaker_open=True, consecutive_faults=self._device_faults,
                fallback="circuit_open", drain_id=did)
            self._drain_pending()
            # journey: the drain re-routed to the host oracle — the pods'
            # device attempt is abandoned, not retried
            self._journey_requeue([q.pod.uid for q in qpis],
                                  "breaker_fallback")
            self.journey.record_bulk([q.pod.uid for q in qpis], _EV_DRAIN,
                                     self.clock(), detail="breaker_host",
                                     drain=did)
            return sum(1 if self._schedule_one_host(q) else 0 for q in qpis)

        with log_context(drain=did):
            return self._dispatch_device_drain_inner(qpis, profile, prebuilt,
                                                     did, gang)

    def _dispatch_device_drain_inner(self, qpis, profile, prebuilt, did,
                                     gang=None):
        from .ops.groups import scatter_new_rows, to_device

        ph: dict[str, float] = {}
        # shadow-audit sampling decision: a sampled drain quiesces the
        # commit pipeline FIRST so the snapshot clone captured below is
        # exactly the state the device carry encodes (obs/audit.py) —
        # divergence then means a decision difference, never capture skew.
        # A gang drain is audited when its placement is the serial
        # oracle's (no contiguity column); a rejected gang is abandoned at
        # commit, since the oracle has no quorum unwind
        audit_want = (self.audit is not None
                      and (gang is None or self.gang_contiguity_weight == 0)
                      and self.audit.want())
        audit_rec = None
        with self._phase("host_build", ph, pods=len(qpis), drain=did):
            if audit_want:
                # the commits of the quiesce are phases of their own
                with self._phase("audit", drain=did):
                    self._drain_pending()
                    self.cache.update_snapshot(self.snapshot)
            carry = self._device_carry
            nominator = self.queue.nominator
            ovl_fp = nominator.version if nominator.nominated_pods else -1
            if carry is not None and (self._carry_profile != profile.name
                                      or self._carry_ovl_fp != ovl_fp):
                # the signature cache's s_fit/s_bal were computed under
                # another profile's ScoreConfig — or its fit_ok under a
                # different nominated-pod overlay: invalidate (sig 0 never
                # matches)
                carry = carry._replace(
                    cache=carry.cache._replace(sig=jnp.int32(0)))
                self._device_carry = carry
            self._carry_profile = profile.name
            self._carry_ovl_fp = ovl_fp
            if carry is None:
                # reseed device state from the host snapshot (first batch,
                # or an external event invalidated the resident carry).
                # Pending commits mutate the host cache the snapshot is
                # built from, so they must land first.
                with self._phase("host_snapshot", ph):
                    self._drain_pending()
                    self.cache.update_snapshot(self.snapshot)
                    self.state.apply_snapshot(self.snapshot)
            with self._phase("host_tensorize", ph,
                             cached=prebuilt is not None):
                if (prebuilt is not None
                        and prebuilt.table.req.shape[1]
                        == self.state.dims.resources):
                    segment_batch = prebuilt
                else:
                    segment_batch = self.builder.build(
                        [q.pod for q in qpis], pad_to=self.batch_size)
            if segment_batch.host_fallback.any():
                # state moved between routing and segment build (e.g. a node
                # update surfaced images): honor queue order and let the
                # oracle take the segment
                if gang is not None:
                    self.metrics.gang_dispatch.inc("fallback")
                self._drain_pending()
                return sum(1 if self._schedule_one_host(q) else 0
                           for q in qpis)
            if audit_want:
                # clone + fingerprint + hash-chain append: the snapshot
                # was refreshed at the quiesce above and nothing between
                # there and here mutates the cache
                with self._phase("audit", drain=did):
                    audit_rec = self.audit.capture(
                        did, profile, qpis, self.snapshot, segment_batch,
                        len(qpis), self.state, self.builder,
                        self._ext_mutations)
            if (self.mesh is not None
                    and (self._na_sharded is None
                         or self._na_sharded_gen != self.state.staging_gen)):
                # the mesh-placed node upload is a real drain phase:
                # cover it with the same span/ledger surface as the
                # single-device snapshot uploads (run_batch_sharded
                # previously had no drain_phase/h2d attribution)
                with self._phase("host_snapshot", ph):
                    na = self._node_arrays()
            else:
                na = self._node_arrays()
            # group kernels are needed when any signature row carries spread
            # or inter-pod affinity constraints, or when existing cluster
            # pods do (affinity is symmetric: they veto/score ANY incoming
            # pod)
            groups_needed = (
                self.builder.groups.any_groups()
                or bool(self.snapshot.have_pods_with_affinity_list)
                or bool(
                    self.snapshot.have_pods_with_required_anti_affinity_list))
            if gang is not None and (
                    groups_needed
                    or (segment_batch.sig[:len(qpis)] == 0).any()
                    or not segment_batch.valid[:len(qpis)].all()):
                # group kernels / host-port signatures are outside the gang
                # program: this gang rides the generic path instead (per-pod
                # placement + the Permit barrier at commit)
                self.metrics.gang_dispatch.inc("fallback")
                gang = None
            if groups_needed and self._device_plan(
                    segment_batch, len(qpis), profile).scan_only:
                # host greedy is the FALLBACK tier for group drains no
                # compiled program covers (gate off, short spans, mixes
                # beyond the plan lattice). A sampled drain stays audited
                # — the greedy's decisions face the same oracle replay.
                bound = self._try_host_greedy(qpis, profile, segment_batch,
                                              audit=audit_rec)
                if bound is not None:
                    return bound
            table_reset = self.builder.reset_count != self._builder_reset_seen
            self._builder_reset_seen = self.builder.reset_count
            capacity = (self.builder.groups.device_rows(), na.used.shape[0])
            if carry is not None and (
                    table_reset  # every signature id / group row invalidated
                    or carry.used.shape != na.used.shape
                    or groups_needed != (carry.groups is not None)
                    or (groups_needed and capacity != self._gd_capacity)):
                # structural change: reseed from the host snapshot, which
                # has the pending deletes already
                carry = None
                self._retires, self._retire_folds = [], []
                with self._phase("host_snapshot", ph):
                    self._drain_pending()
                    self.cache.update_snapshot(self.snapshot)
                    self.state.apply_snapshot(self.snapshot)
                na = self._node_arrays()
            if carry is not None and self._retires:
                # before any new rows seed from the live snapshot (which
                # has the deletes already): the fold leaves them alone
                with self._phase("host_snapshot", ph):
                    carry = self._fold_retires(carry)
            with self._phase("host_group_seed", ph, groups=groups_needed):
                if carry is None:
                    gcarry = None
                    if groups_needed:
                        gd_np, gc_np = self.builder.groups.build_dev(
                            self.snapshot)
                        if self.mesh is not None:
                            from .parallel.sharding import (shard_group_carry,
                                                            shard_groups)
                            self._gd_dev = shard_groups(self.mesh,
                                                        to_device(gd_np))
                            gcarry = shard_group_carry(self.mesh,
                                                       to_device(gc_np))
                        else:
                            self._gd_dev = to_device(gd_np)
                            gcarry = to_device(gc_np)
                        self._gd_fam = self.builder.groups.families(
                            self.snapshot)
                    else:
                        self._gd_dev = None
                        self._gd_fam = None
                    self._gd_capacity = capacity
                    self._seeded_rows = self.builder.table_used
                    carry = initial_carry(na, gcarry)
                elif (groups_needed
                      and self.builder.table_used > self._seeded_rows):
                    # new signature rows while the carry is resident: seed
                    # just those rows from the live snapshot (assumes
                    # included) and scatter in. Pending commits must land
                    # first — the seeds count them.
                    self._drain_pending()
                    carry = self._device_carry
                    if carry is None:
                        # a bind error during the drain invalidated the
                        # carry: restart this dispatch against the reseeded
                        # state
                        if audit_rec is not None:
                            self.audit.abandon(audit_rec, "restarted")
                        return self._dispatch_device_drain(qpis, profile,
                                                           prebuilt)
                    if (self.builder.groups.device_rows(),
                            na.used.shape[0]) != self._gd_capacity:
                        # the commits above can intern NEW signature rows
                        # (e.g. preemption's batched dry-run row for a
                        # failed pod): a pow2 capacity crossing means the
                        # resident group tensors are too small to scatter
                        # into — reseed instead
                        self._invalidate_device_state()
                        if audit_rec is not None:
                            self.audit.abandon(audit_rec, "restarted")
                        return self._dispatch_device_drain(qpis, profile,
                                                           prebuilt)
                    self.cache.update_snapshot(self.snapshot)
                    self._gd_dev, gcarry = scatter_new_rows(
                        self._gd_dev, carry.groups, self.builder.groups,
                        self.snapshot, self._seeded_rows,
                        self.builder.table_used, mesh=self.mesh)
                    self._gd_fam = self.builder.groups.families(self.snapshot)
                    carry = carry._replace(groups=gcarry)
                    self._seeded_rows = self.builder.table_used
            with self._phase("host_cache", ph):
                if (self._table_dev is None
                        or self._table_dev_version
                        != segment_batch.table_version):
                    self._table_dev = table_from_batch(segment_batch)
                    self._table_dev_version = segment_batch.table_version
                table = self._table_dev
                n = len(qpis)
                ovl = None
                nom = None
                if self.queue.nominator.nominated_pods:
                    # re-validate at the DISPATCH site: interleaved
                    # host-path scheduling (mixed drains, fallback segments)
                    # can nominate mid-batch, after _schedule_batch's entry
                    # check ran
                    if groups_needed or not self._overlay_eligible(qpis):
                        # groups: nominated pods' labels feed group counts,
                        # which the resource-only overlay cannot represent
                        if audit_rec is not None:
                            self.audit.abandon(audit_rec, "host_path")
                        self._drain_pending()
                        return sum(1 if self._schedule_one_host(q) else 0
                                   for q in qpis)
                    ovl = self._build_overlay(na)
                    nom = self._nominated_rows(qpis)
                    if audit_rec is not None:
                        # the nominated-pod overlay is outside the audit's
                        # replay model (the oracle would need the
                        # nominator state frozen at dispatch)
                        self.audit.abandon(audit_rec, "overlay")
                        audit_rec = None
                    if gang is not None:
                        # the overlay two-pass is outside the gang program
                        self.metrics.gang_dispatch.inc("fallback")
                        gang = None
        for name, dt in ph.items():
            self.metrics.drain_phase.observe(dt, name)
        if self.profiler is not None:
            # profiler tag: this drain's distinct-signature count (pow2
            # bucketed by the profiler) — host cost per cardinality regime
            self._sig_bucket_cell[0] = int(
                np.unique(segment_batch.tidx[:n]).size)
        if audit_rec is not None:
            # keep the PRE-dispatch device inputs (carry copied on
            # device) so /debug/explain can replay any pod's exact step
            with self._phase("audit", drain=did):
                self.audit.attach_device(
                    audit_rec, profile.score_config, na, carry, table,
                    segment_batch, n, self._gd_dev, self._gd_fam,
                    names=self.state.node_names)
        t0 = _time.perf_counter()
        try:
            # kernel observatory: capture every measured_call dispatched
            # inside the device phase — its wall decomposes into named
            # kernel dispatches
            self.observatory.begin_drain()
            with self._phase("device_dispatch", ph, pods=n,
                             groups=groups_needed, drain=did,
                             batch_bucket=len(segment_batch.valid)) as ds:
                # rails: the dispatch region must consume only
                # device-resident (or explicitly staged) inputs — implicit
                # transfers raise here with the SanitizerRails gate on
                with self.rails.guard_dispatch():
                    carry, records = self._dispatch_runs(
                        profile, na, carry, segment_batch, table, n,
                        groups_needed, ovl=ovl, nom=nom,
                        gang=(gang[1] if gang is not None else None))
                if self.rails.active and n > 0:
                    # NaN/inf probe of the drain's first signature row
                    # against the post-dispatch carry
                    self.rails.check_scores(
                        profile.score_config, na, carry, table,
                        int(segment_batch.tidx[0]))
                ds.set(runs=",".join(r.kind for r in records))
        except Exception as e:
            self.observatory.end_drain()
            # a sanitizer rail tripping is a finding, not a device fault:
            # degrading to the host oracle would mask exactly the bug the
            # rails exist to surface
            from .analysis.rails import SanitizerError
            if self.rails.active and (
                    isinstance(e, SanitizerError)
                    or "Disallowed host-to-device" in str(e)):
                raise
            # XLA/dispatch fault: earlier in-flight drains predate the
            # fault and commit normally; THIS drain degrades to the host
            # oracle and the resident carry reseeds on the next dispatch
            self._record_device_fault("dispatch", e)
            if audit_rec is not None:
                self.audit.abandon(audit_rec, "device_fault")
            if gang is not None:
                self.metrics.gang_dispatch.inc("fallback")
            self._drain_pending()
            return sum(1 if self._schedule_one_host(q) else 0 for q in qpis)
        self.metrics.drain_phase.observe(ph["device_dispatch"], "device")
        # close the capture: per-kernel dispatch seconds ride the flight
        # record
        kernels = self.observatory.lane_seconds(self.observatory.end_drain())
        # installing the drain's carry drops the previous one
        with self._phase("release"):
            self._device_carry = carry
        self.device_batches += 1
        self.metrics.device_batch_size.observe(n)
        probe = None
        if self._probe_enabled:
            # on-device cluster analytics over the post-drain carry: every
            # input (na, carry, dom) is already device-resident, so the
            # probe costs zero extra h2d; the result rides the drain's
            # async copy window and resolves at commit
            with self._phase("cluster_probe", drain=did):
                dom = self._gang_domains(na, need=True)
                if self.mesh is not None:
                    # the mesh twin: feeding node-sharded inputs to the
                    # single-device probe jit makes GSPMD reshard around
                    # the cross-node sort — ~10× the whole probe's cost
                    from .parallel.sharding import cluster_probe_sharded
                    probe = cluster_probe_sharded(self.mesh, na, carry,
                                                  dom, self._gang_ndom)
                else:
                    probe = cluster_probe(na, carry, dom, self._gang_ndom)
        self.journey.record_bulk([q.pod.uid for q in qpis], _EV_DRAIN,
                                 self.clock(), detail="device", drain=did)
        self._pending.append(_PendingDrain(
            qpis=qpis, profile=profile, batch=segment_batch, table=table,
            na=na, n=n, groups_needed=groups_needed, records=records,
            dispatched_at=t0, ovl=ovl, nom=nom, phases=ph, drain_id=did,
            gang=gang, facts=self.builder.row_facts, audit=audit_rec,
            probe=probe, kernels=kernels, retire=tuple(self._retire_folds)))
        self._retire_folds = []
        return 0

    @contextmanager
    def _phase(self, name: str, ph: Optional[dict] = None, **attrs):
        """Time one phase of the loop, the one way a phase is timed: the
        PhaseTrack mark (self time into scheduler_phase_self_seconds_total,
        the sampling profiler's tag), a tracer phase span (a profiler
        annotation on the device ops' clock) and, when `ph` is given, the
        inclusive seconds under `name` (flight record, drain_phase)."""
        t0 = _time.perf_counter()
        self.phase_track.push(name)
        try:
            # rails.declared opens a transfer-guard allow window for the
            # host phases whose uploads are part of the drain contract
            # (no-op with the SanitizerRails gate off)
            with self.tracer.phase(name, **attrs) as sp, \
                    self.rails.declared(name):
                yield sp
        finally:
            self.phase_track.pop()
            if ph is not None:
                ph[name] = ph.get(name, 0.0) + (_time.perf_counter() - t0)

    def _nominated_rows(self, qpis: list[QueuedPodInfo]):
        """i32 [n] row index of each drain pod's OWN nomination (-1 =
        none), or None when no drain pod is nominated — the device
        self-exclusion companion to the overlay (PodXs.nom_idx)."""
        nominated = self.queue.nominator.nominated_pods
        out = None
        for i, q in enumerate(qpis):
            node = nominated.get(q.pod.uid)
            if node is None:
                continue
            idx = self.state.node_index.get(node)
            if idx is None:
                continue
            if out is None:
                out = np.full((len(qpis),), -1, np.int32)
            out[i] = idx
        return out

    # below this run length the scan's per-step cost beats the matrix setup
    UNIFORM_RUN_MIN = 16

    def _overlay_eligible(self, qpis: list[QueuedPodInfo]) -> bool:
        """True when the nominated pods' effect on this drain reduces to a
        fit-only resource overlay (the reference adds nominated pods with
        priority >= the incoming pod's to the NodeInfo,
        runtime/framework.go:1183-1200): every nominated pod outranks every
        drain pod and none carries host ports (the ports carry isn't
        overlaid). A drain pod that IS nominated — the renominated
        preemptor wave, the hottest preemption shape — is handled by
        per-pod self-exclusion (PodXs.nom_idx): its own nominated row is
        subtracted back out of the overlay, mirroring the reference
        skipping the pod's own nomination."""
        if self.mesh is not None:
            return False
        nom = self.queue.nominator
        max_prio = max(q.pod.spec.priority for q in qpis)
        for qlist in nom.nominated_per_node.values():
            for q in qlist:
                if q.pod.spec.priority < max_prio:
                    return False
                for c in q.pod.spec.containers:
                    for p in c.ports:
                        if p.host_port > 0:
                            return False
        return True

    def _build_overlay(self, na):
        """(ovl_used [N,R], ovl_npods [N]) from the current nominations —
        fresh per dispatch (nominations are few and short-lived)."""
        N, R = na.used.shape
        ovl_used = np.zeros((N, R), np.int64)
        ovl_npods = np.zeros((N,), np.int32)
        for node_name, qlist in self.queue.nominator.nominated_per_node.items():
            idx = self.state.node_index.get(node_name)
            if idx is None or idx >= N:
                continue
            for q in qlist:
                vec = self.state.rtable.vector(q.pod_info.requests)
                ovl_used[idx, :len(vec)] += vec
                ovl_npods[idx] += 1
        return (jnp.asarray(ovl_used), jnp.asarray(ovl_npods))

    def _try_host_greedy(self, qpis: list[QueuedPodInfo], profile: Profile,
                         batch, audit=None) -> Optional[int]:
        """Host-side vectorized greedy for a SAME-SIGNATURE drain with
        group constraints (ops/hostgreedy.py) — the group analog of the
        closed-form uniform path. The device scan pays one sequential
        device step per pod (its time on a local chip: not measured); the
        host replays the exact oracle formulas with vectorized O(N) numpy
        steps. Returns binds committed, or None when the
        drain isn't eligible (caller continues on the device path)."""
        n = len(qpis)
        # mesh mode is NOT excluded: the greedy reads the full numpy
        # staging arrays, which the host owns regardless of how the device
        # copies are sharded; the post-run invalidation reseeds the shards.
        # Both scoring strategies are supported — the greedy recomputes
        # scores per step, so MostAllocated's non-monotone score sequences
        # (which bar the closed-form uniform path) are exact here.
        if (self.queue.nominator.nominated_pods
                or not self.feature_gates.enabled("OpportunisticBatching")
                or n < self.UNIFORM_RUN_MIN):
            return None
        sig = batch.sig[:n]
        if sig[0] == 0 or not (sig == sig[0]).all():
            return None
        # cheap precondition pre-checks BEFORE quiescing the pipeline: a
        # cluster with PreferNoSchedule taints (or a row with preferred
        # node affinity) would fail hg.ok after paying the full drain +
        # snapshot + group-tensor build on every single drain
        if (self._cluster_has_prefer_taints()
                or batch.table.pref_weight[int(batch.tidx[0])].any()):
            return None
        from .ops.hostgreedy import HostGreedy
        # commits mutate the host cache the greedy reads — quiesce first
        self._drain_pending()
        self.cache.update_snapshot(self.snapshot)
        self.state.apply_snapshot(self.snapshot)
        self.state.ensure_arrays()
        gd, gc = self.builder.groups.build_dev(self.snapshot)
        t0 = _time.perf_counter()
        hg = HostGreedy(profile.score_config, self.state.arrays,
                        batch.table, int(batch.tidx[0]), gd, gc,
                        n_eff=len(self.state.node_names))
        if not hg.ok:
            return None   # normalization preconditions failed: scan path
        with self.tracer.span("host_greedy", pods=n):
            out = hg.run(n)
        self.metrics.placement_pods.inc("host_greedy", by=n)
        # placements live only in the upcoming commits; the resident device
        # carry (if any) knows nothing of them
        self._invalidate_device_state()
        self.device_batches += 1
        self.host_greedy_runs += 1
        self.metrics.device_batch_size.observe(n)
        self.metrics.device_batch_duration.observe(
            max(_time.perf_counter() - t0, 0.0))
        self._drain_seq += 1
        pd = _PendingDrain(qpis=qpis, profile=profile, batch=batch,
                           table=None, na=None, n=n, groups_needed=True,
                           records=[], dispatched_at=t0,
                           drain_id=self._drain_seq,
                           facts=self.builder.row_facts, audit=audit)
        return self._commit_assignments(pd, out)

    def _node_arrays(self):
        """Device (or mesh-placed) node arrays, cached until the staging
        generation moves (adopt_carry and every staging write bump it).
        The resident copies live in ClusterState — device_arrays /
        device_arrays_sharded — and a scheduler only ever uses one
        flavor, so they share the dirty-row diff tracking."""
        if self.mesh is None:
            return self.state.device_arrays()
        if (self._na_sharded is None
                or self._na_sharded_gen != self.state.staging_gen):
            self.state.ensure_arrays()
            self._na_sharded_gen = self.state.staging_gen
            # generation-diff upload (ISSUE 16): small dirty sets ride
            # scatter_rows_sharded instead of a full-matrix re-shard
            self._na_sharded = self.state.device_arrays_sharded(self.mesh)
        return self._na_sharded

    def _cluster_has_prefer_taints(self) -> bool:
        # mask by valid: freed rows of removed nodes keep their taint
        # columns until the slot is rewritten and must not disable the
        # uniform fast path forever
        a = self.state.arrays
        return a is not None and bool(
            ((a.taint_eff == EFFECT_PREFER_NO_SCHEDULE)
             & a.valid[:, None]).any())

    # -- speculative wave placement (group drains) ----------------------------

    def _wave_enabled(self) -> bool:
        return self.feature_gates.enabled("SpeculativeWavePlacement")

    def _device_plan(self, batch, n: int, profile: Profile):
        """The drain compiler's plan for this group drain under the
        current gates (no overlay/nomination/gang context — those route
        at the dispatch site)."""
        return self.compiler.compile_drain(
            batch, n, groups_needed=True,
            mesh=self.mesh is not None,
            strategy=profile.score_config.strategy,
            prefer_taints=self._cluster_has_prefer_taints(),
            wave_min_span=self.wave_min_span,
            uniform_min=self.UNIFORM_RUN_MIN)

    def _wave_norm_static(self, rows: tuple) -> bool:
        from .ops.hostgreedy import static_norm_ok
        pref_w = self.builder.table.pref_weight
        return all(static_norm_ok(self.state.arrays, pref_w[u])
                   for u in rows)

    def _get_wave_statics(self, na, table, rows: tuple) -> list:
        """Hoisted per-signature surfaces ([N] tuples per signature) from
        the compiler's SurfaceCache — retained across placement-only
        generation bumps (compiler/surfaces.py), recomputed only when a
        node's static columns or the signature table move."""
        return self.compiler.surfaces.get(na, table, rows)

    def _wave_dispatch(self, cfg: ScoreConfig, na, carry, batch, i: int,
                       j: int, table, span):
        """Dispatch the same-signature wave kernel over pods [i:j)."""
        _, u, anti_term, merge_on = span
        m = j - i
        bucket = pow2_at_least(m)
        valid = np.zeros((bucket,), bool)
        valid[:m] = batch.valid[i:j]
        statics = self._get_wave_statics(na, table, (u,))[0]
        # spread replay carries an [Lw, Lw, SC] rank tensor — cap the wave
        # width under it; without it wider waves just cut dispatch count
        Lw = min(512 if self._gd_fam.spr_f else 1024, bucket)
        K = min(Lw, na.cap.shape[0])
        if anti_term >= 0 and not self._gd_fam.spr_f:
            # domain-veto waves accept one entry per node (jcap=1): the
            # deeper matrix columns would be masked — don't build them
            J = 1
        else:
            _L, _K, J = self._uniform_shape(na)
        # a wave speculates at most one entry per matrix cell (a cluster
        # smaller than the wave, under jcap=1, has fewer cells than Lw)
        Lw = min(Lw, K * J)
        norm_live = not self._wave_norm_static((u,))
        carry2, packed = run_wave(
            cfg, na, carry, jnp.asarray(valid), table, jnp.int32(u),
            self._gd_dev, statics, K, J, self._gd_fam, norm_live,
            anti_term=anti_term, merge_on=merge_on, Lw=Lw)
        return carry2, packed, bucket

    def _wavescan_dispatch(self, cfg: ScoreConfig, na, carry, batch,
                           i: int, j: int, table, span):
        """Dispatch the compiler's plan program (ops/program.py run_plan)
        over pods [i:j): an arbitrary mixed-signature span — group rows
        ride the resident group tensors, LEAN spans (non-interacting
        signatures of a group-free drain) compile the variant without any
        group state, and spans holding host-port rows compile the
        ports-carry variant. The signature set pads to the compiler's
        pow2 lattice so the executable count stays log-bounded."""
        from .ops.groups import GroupFamilies

        _, uniq, has_ports = span
        uniq = list(uniq)
        m = j - i
        bucket = pow2_at_least(m)
        S = pow2_at_least(len(uniq), 2)
        wt_list = (uniq + [uniq[-1]] * S)[:S]
        slot: dict = {}
        for s, u in enumerate(wt_list):
            slot.setdefault(u, s)
        widx = np.zeros((bucket,), np.int32)
        tid = batch.tidx
        for k in range(m):
            widx[k] = slot[int(tid[i + k])]
        widx[m:] = widx[m - 1]
        valid = np.zeros((bucket,), bool)
        valid[:m] = batch.valid[i:j]
        statics = self.compiler.surfaces.stacked(na, table, tuple(wt_list))
        norm_live = not self._wave_norm_static(tuple(wt_list))
        xs = WaveXs(valid=jnp.asarray(valid), widx=jnp.asarray(widx))
        has_groups = self._gd_dev is not None
        fam = self._gd_fam if has_groups else GroupFamilies(
            False, False, False, False, False)
        self.metrics.plan_scan_steps.inc("valid", by=m)
        self.metrics.plan_scan_steps.inc("pad", by=bucket - m)
        if self.mesh is not None:
            from .parallel.sharding import run_plan_sharded
            carry2, packed = run_plan_sharded(
                cfg, self.mesh, na, carry, xs, table,
                jnp.asarray(np.array(wt_list, np.int32)), self._gd_dev,
                statics, fam, norm_live, has_groups=has_groups,
                has_ports=has_ports)
            return carry2, packed, bucket
        carry2, packed = run_plan(
            cfg, na, carry, xs, table,
            jnp.asarray(np.array(wt_list, np.int32)), self._gd_dev,
            statics, fam, norm_live, has_groups=has_groups,
            has_ports=has_ports)
        return carry2, packed, bucket

    # -- gang placement (whole-group all-or-nothing dispatch) ------------------

    def _gang_domains(self, na, need: bool):
        """Device i32[N] topology-domain id per node row for the gang
        contiguity column: the node's interned zone label, or a unique
        per-node domain when unlabeled (contiguity then has no surface to
        prefer). Cached until node state moves; identity ids when the
        contiguity weight is off (the kernel never reads them).

        Keyed on statics_gen, not staging_gen: zone labels are static
        columns, and the per-commit aggregate bumps that dominate
        steady-state drains must not force the 5k-entry host rebuild
        (the cluster probe reads this EVERY drain — a staging_gen key
        made it one of the largest host costs of a sharded drain)."""
        key = (self.state.statics_gen, na.used.shape[0])
        if self._gang_dom is not None and self._gang_dom_key == key:
            return self._gang_dom
        N = na.used.shape[0]
        dom = np.arange(N, dtype=np.int32)
        if need:
            ids: dict[str, int] = {}
            for name, idx in self.state.node_index.items():
                if idx >= N:
                    continue
                ni = self.snapshot.get(name)
                labels = (ni.node.metadata.labels if ni is not None else {})
                zone = (labels.get("topology.kubernetes.io/zone")
                        or f"\x00{idx}")
                dom[idx] = ids.setdefault(zone, len(ids))
        self._gang_dom = jnp.asarray(dom)
        self._gang_dom_key = key
        # static domain count for the cluster_probe jit cache key: stable
        # per topology (changes only when the id mapping is rebuilt)
        self._gang_ndom = int(dom.max()) + 1 if N else 1
        return self._gang_dom

    def _gang_dispatch(self, cfg: ScoreConfig, na, carry, batch, i: int,
                       j: int, table, span, force_scan: bool = False):
        """Dispatch ops/gang.py run_gang over members [i:j). Returns
        (carry', packed, pack_width, uniform_tier). A single-signature
        gang under LeastAllocated rides the closed-form top-L tier (the
        whole gang is one top_k); anything else — mixed member roles, a
        live contiguity column, MostAllocated, preferred surfaces — takes
        the scan tier with per-signature surfaces hoisted once."""
        from .ops.gang import GangXs, run_gang

        _, needed = span
        m = j - i
        w_contig = int(self.gang_contiguity_weight)
        tid = batch.tidx[i:j]
        uniq = list(dict.fromkeys(int(t) for t in tid))
        # gang-sized matrix, not the full batch bucket: a 256-member gang
        # must not pay an 8192-wide top-L. Gang sizes quantize to pow2, so
        # the executable count stays log-bounded per workload.
        L = pow2_at_least(m, 16)
        K = min(L, na.cap.shape[0])
        n_q = pow2_at_least(max(self.cache.node_count(), 1))
        J = min(max(pow2_at_least(4 * L // n_q + 4), 8), L + 1)
        if (not force_scan and len(uniq) == 1 and w_contig == 0 and m <= L
                and cfg.strategy == "LeastAllocated"
                and self.feature_gates.enabled("OpportunisticBatching")
                and not self._cluster_has_prefer_taints()
                and not self.builder.table.pref_weight[uniq[0]].any()):
            if self.mesh is not None:
                from .parallel.sharding import run_gang_sharded
                c2, packed = run_gang_sharded(
                    cfg, self.mesh, na, carry, self._xone(batch, i),
                    table, needed=np.int32(needed), uniform=True,
                    n_actual=np.int32(m), L=L, K=K, J=J)
            else:
                c2, packed = run_gang(cfg, na, carry, self._xone(batch, i),
                                      table, needed=np.int32(needed),
                                      uniform=True, n_actual=np.int32(m),
                                      L=L, K=K, J=J)
            return c2, packed, L, True
        bucket = pow2_at_least(m)
        S = pow2_at_least(len(uniq), 1)
        wt_list = (uniq + [uniq[-1]] * S)[:S]
        slot: dict = {}
        for s, u in enumerate(wt_list):
            slot.setdefault(u, s)
        widx = np.zeros((bucket,), np.int32)
        for k in range(m):
            widx[k] = slot[int(tid[k])]
        widx[m:] = widx[m - 1]
        tidx = np.full((bucket,), tid[m - 1], np.int32)
        tidx[:m] = tid
        valid = np.zeros((bucket,), bool)
        valid[:m] = batch.valid[i:j]
        xs = GangXs(valid=jnp.asarray(valid), tidx=jnp.asarray(tidx),
                    widx=jnp.asarray(widx))
        dom = self._gang_domains(na, need=w_contig > 0)
        # the gang scan tier consumes the SAME hoisted surfaces as the
        # plan program — its per-dispatch cost collapses to the fit
        # columns + the member scan (ROADMAP item 3's remaining headroom)
        statics = self.compiler.surfaces.stacked(na, table, tuple(wt_list))
        if self.mesh is not None:
            from .parallel.sharding import run_gang_sharded
            c2, packed = run_gang_sharded(
                cfg, self.mesh, na, carry, xs, table,
                wt=jnp.asarray(np.array(wt_list, np.int32)),
                needed=np.int32(needed), dom=dom, statics=statics,
                w_contig=w_contig)
        else:
            c2, packed = run_gang(
                cfg, na, carry, xs, table,
                wt=jnp.asarray(np.array(wt_list, np.int32)),
                needed=np.int32(needed), dom=dom, statics=statics,
                w_contig=w_contig)
        return c2, packed, bucket, False

    def _dispatch_runs(self, profile: Profile, na, carry, batch, table,
                       n: int, groups_needed: bool, ovl=None, nom=None,
                       gang=None):
        """Dispatch the drain through its compiled DrainPlan with ZERO
        host synchronization — results stream back asynchronously and the
        carry chains device-side.

        The drain compiler (kubernetes_tpu/compiler/) maps the pod mix to
        spans: maximal same-signature runs collapse to closed-form top-L
        assignment (run_uniform), group/mixed/host-port spans compile to
        the plan program (run_plan) over the pow2 signature lattice,
        same-signature group spans ride the merge wave (run_wave), gangs
        dispatch all-or-nothing (run_gang), and only the fallback matrix
        (overlays, mesh, short spans, beyond-lattice mixes) keeps the
        per-pod scan. Each uniform record carries its input carry so
        commit-time validation (rare failures: BalancedAllocation
        non-monotonicity, depth-J overflow) can rewind and replay.
        Returns (chain carry, [_RunRec])."""
        cfg = profile.score_config
        plan = self.compiler.compile_drain(
            batch, n, groups_needed=groups_needed, gang_needed=gang,
            overlay=ovl is not None, nominated=nom is not None,
            mesh=self.mesh is not None, strategy=cfg.strategy,
            prefer_taints=self._cluster_has_prefer_taints(),
            wave_min_span=self.wave_min_span,
            uniform_min=self.UNIFORM_RUN_MIN)
        return self._dispatch_spans(cfg, na, batch, table, plan.spans,
                                    carry, ovl=ovl, nom=nom)

    def _uniform_shape(self, na) -> tuple[int, int, int]:
        """(L, K, J) for run_uniform, chosen to be STABLE across drains:
        L is the standing batch bucket (run length only masks via
        n_actual), and J quantizes the node count to its pow2 bucket — so
        the whole workload compiles ONE uniform executable instead of one
        per observed run length. A fresh XLA compile of a 5000-node
        program takes tens of seconds; shape stability is worth more than
        a minimal J."""
        L = pow2_at_least(self.batch_size)
        K = min(L, na.cap.shape[0])
        n_q = pow2_at_least(max(self.cache.node_count(), 1))
        J = min(max(pow2_at_least(4 * L // n_q + 4), 8), L + 1)
        return L, K, J

    def _dispatch_spans(self, cfg: ScoreConfig, na, batch, table,
                        spans, carry, ovl=None, nom=None):
        """Dispatch the given (i, j, kind) spans back-to-back, chaining
        the carry on device; issues async host copies so the device
        readback overlaps whatever the host does next. Only uniform
        records keep their input carry (rewind support) — scan/wave runs
        donate it on accelerator backends."""
        records = []
        for (i, j, kind) in spans:
            tag = kind[0]
            if tag == "uniform":
                L, K, J = self._uniform_shape(na)
                if self.mesh is not None:
                    # overlays never reach the mesh (_overlay_eligible)
                    from .parallel.sharding import run_uniform_sharded
                    c2, packed = run_uniform_sharded(
                        cfg, self.mesh, na, carry, self._xone(batch, i),
                        table, np.int32(j - i), L, K, J)
                else:
                    c2, packed = run_uniform(
                        cfg, na, carry, self._xone(batch, i), table,
                        np.int32(j - i), L, K, J, overlay=ovl)
                records.append(_RunRec("uniform", i, j, carry, packed,
                                       L, J, True, span=kind))
            elif tag == "wave":
                c2, packed, bucket = self._wave_dispatch(
                    cfg, na, carry, batch, i, j, table, kind)
                records.append(_RunRec("wave", i, j, None, packed,
                                       bucket, span=kind))
            elif tag == "wavescan":
                c2, packed, bucket = self._wavescan_dispatch(
                    cfg, na, carry, batch, i, j, table, kind)
                records.append(_RunRec("wavescan", i, j, None, packed,
                                       bucket, span=kind))
            elif tag == "gang":
                c2, packed, Lp, uni = self._gang_dispatch(
                    cfg, na, carry, batch, i, j, table, kind)
                # the uniform tier keeps its input carry (exactness-flag
                # replay on the scan tier); the scan tier donates it
                records.append(_RunRec("gang", i, j, carry if uni else None,
                                       packed, Lp, uniform=uni, span=kind))
            else:
                c2, assigns = self._scan_dispatch(cfg, na, carry, batch,
                                                  i, j, table, ovl=ovl,
                                                  nom=nom)
                records.append(_RunRec("scan", i, j, None, assigns,
                                       span=kind))
            carry = c2
        for rec in records:
            if hasattr(rec.result, "copy_to_host_async"):
                rec.result.copy_to_host_async()
        if (self.mesh is not None and records
                and self.observatory.enabled and not self._shard_profile_done
                and self._shard_profile_args is None):
            # arm the one-shot lane profile even when no span rode the
            # scan (the mesh kernels displaced it, ISSUE 16): the probe
            # times the scan-shaped program on a twin of the first span
            self._arm_shard_profile(cfg, na, carry, batch,
                                    records[0].i, records[0].j, table)
        return carry, records

    def _arm_shard_profile(self, cfg: ScoreConfig, na, carry, batch,
                           i: int, j: int, table) -> None:
        """Arm the one-shot sharded-lane profile (perf/observatory.py)
        with a scan-shaped PodXs twin of pods [i:j) — profile_shard_lanes
        times run_batch_sharded's program, whatever kernel the span
        itself rode, so the compute/comms/imbalance decomposition stays
        comparable across dispatch tiers. The twin is capped at 1024
        pods: the probe samples the per-step lane split, and an
        uncapped twin of a 10^5-pod uniform span would re-dispatch a
        10^5-step scan just to measure it."""
        j = min(j, i + 1024)
        bucket = pow2_at_least(j - i)
        m = j - i
        valid = np.zeros((bucket,), bool)
        valid[:m] = batch.valid[i:j]
        sig = np.full((bucket,), batch.sig[j - 1], np.int32)
        sig[:m] = batch.sig[i:j]
        tidx = np.full((bucket,), batch.tidx[j - 1], np.int32)
        tidx[:m] = batch.tidx[i:j]
        xs = PodXs(valid=valid, sig=sig, tidx=tidx)
        self._shard_profile_args = (cfg, self.mesh, na, carry, xs, table,
                                    self._gd_dev, self._gd_fam)

    # -- device-tier degradation (circuit breaker) ----------------------------

    def _device_available(self) -> bool:
        """False while the circuit breaker is open; once the cooldown
        expires, True again so ONE drain probes the device tier
        (half-open) — its commit outcome closes or re-opens the breaker."""
        if self._device_faults < self.device_fault_threshold:
            return True
        return self.clock() >= self._breaker_open_until

    def _record_device_fault(self, reason: str, err: Exception) -> None:
        self._device_faults += 1
        self.device_fallbacks += 1
        self.metrics.device_fallbacks.inc(reason)
        self.slo.observe("device_fallback", bad=1)
        self._invalidate_device_state()
        self.flight.record(
            profile="", pods=0, bound=0, failed=0, signatures=0, kinds=(),
            groups=False, phases={}, breaker_open=self._breaker_open,
            consecutive_faults=self._device_faults, fallback=reason)
        klog.error("device batch fault; degrading drain to host path",
                   reason=reason, err=str(err),
                   consecutive=self._device_faults)
        if self._device_faults >= self.device_fault_threshold:
            self._breaker_open_until = (self.clock()
                                        + self.device_fault_cooldown)
            if not self._breaker_open:
                self._breaker_open = True
                self.metrics.circuit_breaker_transitions.inc("open")
                klog.warning("device tier circuit breaker OPEN",
                             cooldown_s=self.device_fault_cooldown)

    def _record_device_success(self) -> None:
        if not self._device_faults:
            return
        self._device_faults = 0
        if self._breaker_open:
            self._breaker_open = False
            self.metrics.circuit_breaker_transitions.inc("closed")
            klog.info("device tier circuit breaker closed (probe drain "
                      "committed cleanly)")

    def _device_fault_abort(self, pd: "_PendingDrain", reason: str,
                            err: Exception) -> None:
        """A fault while resolving an in-flight drain: degrade ITS pods —
        and every later pending drain, whose carries chain off the faulted
        device state — to the host-oracle path. No pod is lost: each either
        host-binds or goes through the normal failure handler."""
        self._record_device_fault(reason, err)
        victims = [pd, *self._pending]
        self._pending.clear()
        for d in victims:
            if d.audit is not None:
                self.audit.abandon(d.audit, "device_fault")
            if d.gang is not None:
                # the gang degrades to the serial Permit-barrier path
                self.metrics.gang_dispatch.inc("fallback")
            for q in d.qpis:
                self._schedule_one_host(q)

    def promote(self) -> None:
        """Standby → active (the OnStartedLeading takeover hook —
        ha/standby.py calls this after its ledger-warmed reconcile)."""
        self.ha_role = "active"

    def demote(self) -> None:
        """Active → standby (deposed leader: OnStoppedLeading). Pending
        drains stay in flight — their commits carry the old fencing token
        and are rejected server-side, unwinding through on_bind_error."""
        self.ha_role = "standby"

    # -- shard slice lifecycle (ha/shards.py) ---------------------------------

    def shard_adopt(self) -> int:
        """Move parked pods this shard NOW owns into the queue — the warm
        half of a shard rebalance/steal. No LIST, no re-tensorize: the
        parked pods rode the watch stream the whole time, so adoption is
        one bulk enqueue (gang members re-derive quorum in the same
        pass). Returns the number of pods adopted."""
        with self.ingest_lock:
            owned = [p for p in self._shard_parked.values()
                     if self._shard_owns(p)]
            if not owned:
                return 0
            for p in owned:
                self._shard_parked.pop(p.uid, None)
            # adopt precedes the (re-)enqueue in the stitched timeline;
            # queue.add_bulk below restores each known pod's original
            # first-enqueue e2e clock (parking seeded it), so the SLI
            # clock survives the handoff like it survives requeues
            self.journey.record_bulk(
                [p.uid for p in owned], _EV_ADOPT, self.clock(),
                detail=f"{len(owned)} pod(s) from parked set")
            n_gated = self.queue.add_bulk(owned)
            self.metrics.queue_incoming_pods.inc(
                "active", "PodAdd", by=len(owned) - n_gated)
            if n_gated:
                self.metrics.queue_incoming_pods.inc("gated", "PodAdd",
                                                     by=n_gated)
            now = self.clock()
            for ref in dict.fromkeys(p.spec.workload_ref for p in owned
                                     if p.spec.workload_ref):
                if ref in self.queue.gated_refs():
                    self._gang_gated_since.setdefault(ref, now)
                self.queue.retry_gated(ref=ref)
            return len(owned)

    def shard_evict(self) -> int:
        """Park queued pods this shard no longer owns — the release half
        of a rebalance/steal handoff. In-flight drains commit and the
        dispatcher flushes FIRST, so an evicted pod is never left
        assumed; what remains queued here simply moves to the parked set
        (the new owner's adopt is its mirror image). Returns the number
        of pods evicted."""
        with self.ingest_lock:
            self._drain_pending()
            self.dispatcher.flush()
            pods, _ = self.queue.pending_pods()
            moved = 0
            evicted: list = []
            for pod in pods:
                if pod.spec.node_name or self._shard_owns(pod):
                    continue
                self.queue.delete(pod)
                self._shard_parked[pod.uid] = pod
                evicted.append(pod.uid)
                moved += 1
            if evicted:
                self.journey.record_bulk(evicted, _EV_EVICT, self.clock(),
                                         detail="shard handoff")
            return moved

    def resync(self) -> None:
        """Rebuild cache + queue from a fresh LIST of the API server — the
        reflector relist path (client-go Reflector.ListAndWatch after
        watch-stream loss). Call when the watch layer reports loss (e.g.
        dropped events): in-flight drains commit, the dispatcher flushes,
        parked pods are rejected, then cluster state is rebuilt from the
        store's current truth and the device tier reseeds from scratch.
        Holds the ingest lock end to end: a watch event delivered during
        the rebuild must not land on a structure about to be replaced."""
        with self.ingest_lock:
            self._resync_locked()

    def _resync_locked(self) -> None:
        self._drain_pending()
        self.dispatcher.flush()
        for uid in list(self._waiting_pods):
            self._reject_waiting(uid, "resync")
        self.dispatcher.flush()   # the rejects enqueue status patches
        # gang continuity (HA takeover correctness): the fresh queue
        # re-derives the gated_by_ref index deterministically below, but
        # two pieces of gang state live OUTSIDE the queue and would
        # silently reset with it — the quorum-wait start times (dropping
        # the gang_quorum_wait observation for any gang that ungates
        # after the resync) and each surviving group's scheduling
        # deadline (restarting the Permit timeout from zero). Carry both.
        gated_since = dict(self._gang_gated_since)
        old_wm = self.workload_manager
        self.cache = Cache(clock=self.clock)
        self.snapshot = Snapshot()
        self.queue = SchedulingQueue(**self._queue_kwargs)
        # re-attach the journey ledger BEFORE any add_bulk below: the
        # rebuilt queue mints fresh QueuedPodInfos, and add/add_bulk
        # restore each known pod's first-enqueue e2e clock from the
        # ledger (the SLI must not restart at a watch-loss resync)
        self.queue.journey = self.journey
        self.workload_manager = WorkloadManager(clock=self.clock)
        self._gang_gated_since.clear()
        from .backend.debugger import CacheDebugger
        self.debugger = CacheDebugger(self.client, self.cache, self.queue,
                                      metrics=self.metrics)
        # rewire the preemption plugins' live handles onto the new objects
        from .plugins.defaultpreemption import DefaultPreemption
        for prof in self.profiles.values():
            for p in prof.framework.plugins:
                if isinstance(p, DefaultPreemption):
                    p.nominator = self.queue.nominator
                    p.snapshot = self.snapshot
                    if getattr(p, "device_ctx", None) is not None:
                        p.device_ctx.snapshot = self.snapshot
        self._bind_errors.clear()
        # LIST order matters: nodes before pods so bound pods land on real
        # cache entries instead of imputed placeholders. The pod re-ingest
        # rides the columnar bulk paths (cache.add_pods + queue.add_bulk —
        # the same pipeline the ingest hot path uses), so watch-loss
        # recovery scales with the columnar engine instead of paying the
        # per-pod object walk O(all pods) the serial loop did.
        for node in list(self.client.nodes.values()):
            self.cache.add_node(node)
        bound_pods: list[Pod] = []
        unbound_pods: list[Pod] = []
        wm_add = self.workload_manager.add_pod
        # ORDERING CONTRACT (guarded by the resync regression tests in
        # tests/test_gang_device.py): every pod registers in the fresh
        # WorkloadManager BEFORE queue.add_bulk re-runs PreEnqueue, so
        # gang gating re-derives against complete membership — a gang
        # whose quorum already arrived re-gates then ungates in the same
        # add_bulk pass instead of stranding behind PreEnqueue.
        self._shard_parked.clear()
        reparked: list[Pod] = []
        for pod in self.client.pods.values():
            wm_add(pod)
            if pod.spec.node_name:
                bound_pods.append(pod)
            elif self._responsible(pod):
                if self._shard_owns(pod):
                    unbound_pods.append(pod)
                else:
                    self._shard_parked[pod.uid] = pod
                    reparked.append(pod)
        if reparked:
            self._journey_park(reparked, detail="resync")
        self.cache.add_pods(bound_pods)
        if unbound_pods:
            # journey: every unbound pod re-enters the queue because of
            # the resync itself — record the cause before add_bulk so
            # the requeue precedes the (re-)enqueue in the timeline
            self._journey_requeue(
                [p.uid for p in unbound_pods if
                 self.journey.e2e_start(p.uid) is not None],
                "resync")
            n_gated = self.queue.add_bulk(unbound_pods)
            self.metrics.queue_incoming_pods.inc(
                "active", "PodAdd", by=len(unbound_pods) - n_gated)
            if n_gated:
                self.metrics.queue_incoming_pods.inc("gated", "PodAdd",
                                                     by=n_gated)
        # restore the carried gang state for groups that survived the
        # rebuild: quorum-wait clocks for refs STILL gated (a ref whose
        # gate cleared during the rebuild was already observed or its
        # pods are gone), and Permit deadlines for surviving groups
        now = self.clock()
        for ref in self.queue.gated_refs():
            self._gang_gated_since[ref] = gated_since.get(ref, now)
        for key, info in old_wm.pod_group_infos.items():
            fresh = self.workload_manager.pod_group_infos.get(key)
            if fresh is not None:
                fresh.scheduling_deadline = info.scheduling_deadline
        self._invalidate_device_state()
        self.cache.update_snapshot(self.snapshot)
        # full=True: the fresh cache restarts its generation counters, so
        # incremental row-gen diffing against the old state could alias
        self.state.apply_snapshot(self.snapshot, full=True)
        self.metrics.resyncs.inc()
        klog.warning("resync: cache+queue rebuilt from fresh LIST",
                     nodes=len(self.client.nodes),
                     pods=len(self.client.pods))

    # -- commit pipeline ------------------------------------------------------

    def _drain_pending(self) -> None:
        while self._pending:
            self._commit_next()

    def _commit_next(self) -> None:
        """Commit the oldest in-flight drain: resolve its device results
        (blocking only if the async copy hasn't landed), validate the
        uniform runs' exactness flags, and run the host commit. An inexact
        run rewinds to its input carry, replays synchronously, then
        re-dispatches everything downstream — including later pending
        drains — against the corrected chain."""
        pd = self._pending.popleft()
        out = np.full((pd.n,), -1, np.int32)
        names = self.state.node_names
        try:
            # the wait for the device results (zero when the async copy
            # already landed), any inexact-run replay, and the range check
            with self._phase("readback", pd.phases):
                self._resolve_records(pd, out)
                assigned = out[out >= 0]
                invalid = ((out < -1).any() or (out >= len(names)).any()
                           or any(not names[int(a)] for a in assigned))
        except Exception as e:
            # XLA fault surfacing at readback/replay: degrade this drain
            # (and the chained later ones) to the host oracle
            self._device_fault_abort(pd, "commit", e)
            return
        if invalid:
            # a garbage assignment tensor (the argmax of a non-finite
            # score column lands here) must never reach the cache
            self._device_fault_abort(pd, "invalid_assignment", ValueError(
                f"device assignments out of range: {out.tolist()}"))
            return
        if self._test_assignment_perturb is not None:
            # test-only hook: inject a wrong-but-valid decision AFTER
            # resolution, BEFORE commit — the shadow audit must catch it
            self._test_assignment_perturb(pd, out)
        if pd.records:
            self._record_device_success()
            self.metrics.drain_phase.observe(pd.phases["readback"],
                                             "readback")
        self.metrics.device_batch_duration.observe(
            max(_time.perf_counter() - pd.dispatched_at, 0.0))
        self._commit_assignments(pd, out)
        # the committed drain's last references: its host and device
        # buffers are freed here
        with self._phase("release"):
            del pd, out, assigned

    def _resolve_records(self, pd: "_PendingDrain", out) -> None:
        """Resolve a drain's device results into `out`, replaying inexact
        uniform runs (and everything chained downstream) as needed. Each
        span's pods count once under the tier that resolved them."""
        from .perf.ledger import GLOBAL as _ledger
        placed = self.metrics.placement_pods
        idx = 0
        while idx < len(pd.records):
            rec = pd.records[idx]
            r = np.asarray(rec.result)
            _ledger.note_h2d("device_readback", r.nbytes)
            m = rec.j - rec.i
            if rec.kind == "scan":
                out[rec.i:rec.j] = r[:m]
                placed.inc("scan", by=m)
                idx += 1
                continue
            if rec.kind in ("wave", "wavescan"):
                out[rec.i:rec.j] = r[:m]
                self._observe_wave(rec, r, m, pd)
                idx += 1
                continue
            if rec.kind == "gang":
                Lp = rec.L
                if not (r[Lp + 2] and r[Lp + 3]):
                    # the closed-form tier's exactness preconditions
                    # failed on the data: replay on the scan tier from
                    # the kept input carry and re-chain downstream
                    cfg = pd.profile.score_config
                    carry, packed, Lp, _ = self._gang_dispatch(
                        cfg, pd.na, rec.carry_in, pd.batch, rec.i, rec.j,
                        pd.table, rec.span, force_scan=True)
                    r = np.asarray(packed)
                    _ledger.note_h2d("device_readback", r.nbytes)
                    self._replay_downstream(pd, idx, carry)
                accepted = bool(r[Lp])
                raw = np.array(r[:m], np.int32)
                pd.gang_accepted = accepted
                pd.gang_raw = raw
                pd.gang_placed = int(r[Lp + 1])
                # the all-or-nothing verdict: a rejected gang was already
                # unwound ON DEVICE — the host only masks the assignments
                out[rec.i:rec.j] = raw if accepted else np.int32(-1)
                placed.inc("gang", by=m)
                idx += 1
                continue
            exact, depth = bool(r[rec.L]), bool(r[rec.L + 1])
            if exact and depth:
                out[rec.i:rec.j] = r[:m]
                placed.inc("uniform", by=m)
                idx += 1
                continue
            # rollback: resolve THIS run synchronously from its input carry
            cfg = pd.profile.score_config
            carry = rec.carry_in
            if exact:
                carry = self._uniform_escalate(cfg, pd.na, carry, pd.batch,
                                               rec.i, rec.j, pd.table, out,
                                               rec.J, ovl=pd.ovl)
                placed.inc("uniform", by=m)
            else:
                carry, a = self._scan_dispatch(cfg, pd.na, carry, pd.batch,
                                               rec.i, rec.j, pd.table,
                                               ovl=pd.ovl, nom=pd.nom)
                out[rec.i:rec.j] = np.asarray(a)[:m]
                placed.inc("scan", by=m)
            self._replay_downstream(pd, idx, carry)
            idx += 1

    def _replay_downstream(self, pd: "_PendingDrain", idx: int,
                           carry) -> None:
        """Re-dispatch everything chained after record `idx`: the rest of
        this drain's spans, then every later pending drain, against the
        corrected carry. A profile OR overlay change between drains
        invalidates the sig cache, mirroring the dispatch-site checks."""
        cfg = pd.profile.score_config
        spans = [(q.i, q.j, q.span) for q in pd.records[idx + 1:]]
        carry, new_recs = self._dispatch_spans(cfg, pd.na, pd.batch,
                                               pd.table, spans, carry,
                                               ovl=pd.ovl, nom=pd.nom)
        pd.records[idx + 1:] = new_recs
        prev_profile = pd.profile
        prev_ovl = pd.ovl
        for pd2 in self._pending:
            for fold in pd2.retire:
                carry = retire_pods(carry, *fold)
            if pd2.profile is not prev_profile or pd2.ovl is not prev_ovl:
                carry = carry._replace(
                    cache=carry.cache._replace(sig=jnp.int32(0)))
                prev_profile = pd2.profile
                prev_ovl = pd2.ovl
            carry, pd2.records = self._dispatch_runs(
                pd2.profile, pd2.na, carry, pd2.batch, pd2.table,
                pd2.n, pd2.groups_needed, ovl=pd2.ovl, nom=pd2.nom,
                gang=(pd2.gang[1] if pd2.gang is not None else None))
        for fold in self._retire_folds:
            carry = retire_pods(carry, *fold)
        if self._device_carry is not None:
            self._device_carry = carry

    def _observe_wave(self, rec: _RunRec, r, m: int,
                      pd: Optional["_PendingDrain"] = None) -> None:
        """Record a resolved wave's stats: waves executed, conflict ratio
        (conflict-cut events + serially repaired pods over the span), the
        first wave's accepted conflict-free prefix length, and the span's
        pods by placement tier. Also folds the raw numbers into the
        drain's flight-recorder entry."""
        B = rec.L
        placed = self.metrics.placement_pods
        if rec.kind == "wave":
            waves, confs = int(r[B]), int(r[B + 1])
            prefix, serial = int(r[B + 2]), int(r[B + 3])
            placed.inc("wave_merge", by=m - serial)
            placed.inc("wave_serial", by=serial)
            self.metrics.wave_placement_waves.inc(by=max(waves, 1))
            self.metrics.wave_conflict_ratio.observe(
                min((confs + serial) / max(m, 1), 1.0))
            self.metrics.wave_accepted_prefix.observe(max(prefix, 0))
        else:
            waves, serial = 1, 0
            confs, prefix = int(r[B]), int(r[B + 1])
            placed.inc("wavescan", by=m)
            self.metrics.wave_placement_waves.inc()
            self.metrics.wave_conflict_ratio.observe(
                min(confs / max(m, 1), 1.0))
            self.metrics.wave_accepted_prefix.observe(max(prefix, 0))
        if pd is not None:
            w = pd.wave
            w["waves"] = w.get("waves", 0) + max(waves, 1)
            w["conflicts"] = w.get("conflicts", 0) + confs + serial
            w.setdefault("first_prefix", max(prefix, 0))

    def _commit_assignments(self, pd: _PendingDrain, out) -> int:
        """Host commit of a resolved drain: bulk assume + bind enqueue for
        hook-free pods, the full reserve/permit/pre-bind chain for the
        rest, failure handling for the unassigned. Runs under the drain's
        id (log context + event tagging); the `commit` phase, then the
        `bookkeeping` phase of the drain's telemetry."""
        from .utils.logging import log_context
        self.events.current_drain = pd.drain_id
        # attempt latency = dispatch→commit wall time split over the drain.
        # NOTE: with the async pipeline this includes time the result sat
        # in flight behind other work — an SLI-style number (queue-to-bind),
        # deliberately not a device-busy-time measurement.
        per_pod = (max(_time.perf_counter() - pd.dispatched_at, 0.0)
                   / max(pd.n, 1))
        try:
            with log_context(drain=pd.drain_id):
                with self._phase("commit", pd.phases, drain=pd.drain_id):
                    bound, failures, fail_msgs = self._commit_decisions(
                        pd, out, per_pod)
                self.metrics.drain_phase.observe(pd.phases["commit"],
                                                 "commit")
                with self._phase("bookkeeping", drain=pd.drain_id):
                    self._commit_bookkeeping(pd, out, per_pod, bound,
                                             failures, fail_msgs)
                return bound
        finally:
            self.events.current_drain = 0

    def _backpressure_stall_delta(self) -> float:
        """Pipeline stall seconds not yet attributed to a committed
        drain: the monotonic stall total minus the checkpoint the last
        commit left. Every stall second lands on exactly ONE drain (the
        next to commit), so the per-cause metric sums stay conserved.
        0.0 in lock-step operation — no pipeline, no backpressure."""
        pipe = self.pipeline
        if pipe is None:
            return 0.0
        total = pipe.backpressure_stall_seconds()
        delta = total - self._bp_stall_committed
        self._bp_stall_committed = total
        return max(delta, 0.0)

    def _critical_path_verdict(self, pd: "_PendingDrain") -> dict:
        """Per-drain bottleneck attribution (perf/critical_path.py,
        ISSUE 20), computed at commit when every segment of the drain's
        wall is known: host_build and its children, device_dispatch with
        the sharded lane profile's comms split, the readback wait, the
        commit tail, and the pipeline's backpressure stall delta. The
        verdict rides the FlightRecord and the two
        scheduler_critical_path_* families. {} with the gate off."""
        if not self.critical_path_enabled:
            return {}
        from .perf.critical_path import attribute_drain
        comms = 0.0
        if self.mesh is not None:
            comms = float((self.observatory.shard_profile() or {}).get(
                "commsShare", 0.0) or 0.0)
        cp = attribute_drain(pd.phases, kernels=pd.kernels,
                             comms_share=comms,
                             backpressure_s=self._backpressure_stall_delta())
        m = self.metrics
        for cause, secs in cp["causes"].items():
            if secs > 0.0:
                m.critical_path_seconds.inc(cause, by=secs)
        m.bottleneck_drains.inc(cp["verdict"])
        return cp

    def _commit_decisions(self, pd: _PendingDrain, out, per_pod: float):
        """Assume + bind enqueue for the bound pods, failure handling for
        the rest. Returns (bound, failed qpis, FailedScheduling messages
        for the audit)."""
        qpis = pd.qpis
        profile = pd.profile
        fwk = profile.framework
        n = pd.n
        self.schedule_attempts += n
        from .metrics import SCHEDULED, UNSCHEDULABLE
        n_ok = int((out >= 0).sum())
        if n_ok:
            self.metrics.attempt_duration.observe(per_pod, SCHEDULED,
                                                  profile.name)
        if n - n_ok:
            self.metrics.attempt_duration.observe(per_pod, UNSCHEDULABLE,
                                                  profile.name)
        names = self.state.node_names
        diag_cache: dict = {}
        # an accepted gang commits atomically through the fast path: the
        # quorum the Permit barrier would enforce per pod was already
        # proven by the device verdict, so the Reserve/Permit chain is
        # vacuous (members with volumes/claims never reach a gang drain)
        gang_fast = pd.gang is not None and pd.gang_accepted
        if self.commit_engine is not None:
            # columnar commit engine (ingest/commit.py): one pass, the
            # cache assume driven by the per-signature commit facts
            bound, failures = self.commit_engine.commit(pd, out, names,
                                                        gang_fast)
        else:
            fast: list[tuple[QueuedPodInfo, str]] = []
            bound = 0
            failures = []
            for i in range(n):
                a = out[i]
                qpi = qpis[i]
                if a < 0:
                    failures.append(qpi)
                    continue
                if not gang_fast and _needs_per_pod_hooks(profile,
                                                          qpi.pod.spec):
                    self._assume_and_bind(qpi, names[int(a)])
                    bound += 1
                else:
                    fast.append((qpi, names[int(a)]))
            bound += self._fast_commit(fast, profile)
        # every device batch evaluates every kernel-modeled filter/score
        # plugin for every pod (PluginEvaluationTotal,
        # instrumented_plugins.go:83 — batch-granular here)
        for p in fwk.filter_plugins:
            self.metrics.plugin_evaluation_total.inc(
                p.name(), "Filter", profile.name, by=n)
        for p in fwk.score_plugins:
            self.metrics.plugin_evaluation_total.inc(
                p.name(), "Score", profile.name, by=n)
        if pd.gang is not None:
            self.metrics.gang_dispatch.inc(
                "placed" if pd.gang_accepted else "rejected")
        fail_msgs: dict = {}
        if failures:
            # diagnosis reads the live snapshot (assumes included)
            self.cache.update_snapshot(self.snapshot)
            if pd.gang is not None and not pd.gang_accepted:
                self._fail_rejected_gang(pd, qpis, diag_cache)
            else:
                for qpi in failures:
                    err = self._device_fit_error(qpi, profile, diag_cache)
                    if pd.audit is not None:
                        # the reference-format message the audit diffs
                        # against the oracle replay's
                        fail_msgs[qpi.pod.uid] = str(err)
                    self._handle_failure(qpi, err)
        return bound, failures, fail_msgs

    def _commit_bookkeeping(self, pd: _PendingDrain, out, per_pod: float,
                            bound: int, failures: list,
                            fail_msgs: dict) -> None:
        """The drain's telemetry after its commit: SLO feeds, journey and
        timeline, the cluster-probe sample, flight record, critical path,
        the audit hand-off and the log line."""
        qpis = pd.qpis
        profile = pd.profile
        n = pd.n
        # SLO engine feeds (obs/slo.py): attempt latency, queue→bind e2e
        # and the device-tier health, one observation batch per drain
        slo = self.slo
        bad_a = n if per_pod > slo.threshold("attempt_latency") else 0
        slo.observe("attempt_latency", good=n - bad_a, bad=bad_a)
        thr_e = slo.threshold("e2e_latency")
        now = self.clock()
        bad_e = 0
        for qpi in qpis:
            if now - (qpi.initial_attempt_timestamp
                      or qpi.timestamp) > thr_e:
                bad_e += 1
        slo.observe("e2e_latency", good=n - bad_e, bad=bad_e)
        slo.observe("device_fallback", good=1)
        # journey: the drain segment is the dispatch→commit wall window,
        # shared by every pod in the drain (the device solves them as one
        # batch); plus the per-second timeline counters and the resolved
        # cluster-probe sample
        window = per_pod * max(n, 1)
        self.metrics.e2e_segment.observe_array([window] * n, "drain")
        self.timeline.segment(now, "drain", window * n, n)
        self.timeline.bump(now, "failures", len(failures))
        self.timeline.bump(now, "drains", 1)
        probe_snap = self._resolve_probe(pd)
        if probe_snap:
            self._last_probe = probe_snap
            self.timeline.probe(now, probe_snap)
        hot: tuple = ()
        if self.profiler is not None:
            total_s = sum(pd.phases.values())
            if total_s >= self.profiler.slow_drain_s:
                # pin the hottest frames of the drain's wall window onto
                # the flight entry — "slow drain 17" answers itself
                hot = tuple(self.profiler.top_frames(
                    5, seconds=max(total_s, 1.0) + 1.0))
        cp = self._critical_path_verdict(pd)
        frec = self.flight.record(
            profile=profile.name, pods=n, bound=bound,
            failed=len(failures),
            signatures=(int(np.unique(pd.batch.tidx[:n]).size)
                        if pd.batch is not None else 0),
            kinds=tuple(r.kind for r in pd.records) or ("host_greedy",),
            groups=pd.groups_needed, phases=dict(pd.phases),
            wave=dict(pd.wave), breaker_open=self._breaker_open,
            consecutive_faults=self._device_faults,
            fallback="" if pd.records else "host_greedy",
            events={"Scheduled": bound,
                    "FailedScheduling": len(failures)},
            drain_id=pd.drain_id, hot_frames=hot, probe=probe_snap,
            kernels=dict(pd.kernels), shard=tuple(self.shard_ids),
            critical_path=cp)
        if pd.audit is not None and pd.gang is not None \
                and not pd.gang_accepted:
            self.audit.abandon(pd.audit, "gang_rejected")
        elif pd.audit is not None:
            # hand the committed decisions to the shadow-audit worker;
            # the replay + diff run off the hot path
            self.audit.submit(pd.audit, out=out,
                              names=self.state.node_names,
                              fail_msgs=fail_msgs, flight_rec=frec,
                              ext_gen=self._ext_mutations)
        klog.v(2).info("batch committed", profile=profile.name, pods=n,
                       bound=bound, unschedulable=len(failures),
                       latency_ms=round(per_pod * n * 1e3, 1))
        if klog.v(5).enabled and failures:
            for qpi in failures:
                klog.v(5).info("unschedulable", pod=qpi.pod.uid,
                               plugins=sorted(qpi.unschedulable_plugins
                                              or ()))

    def _fail_rejected_gang(self, pd: _PendingDrain, qpis: list,
                            diag_cache: dict) -> None:
        """All-or-nothing rejection commit: no member binds, none ever
        reserved — the Permit-barrier's partial-failure churn (Reserve →
        park → timeout → Unreserve) collapses to straight failure
        handling. Members with NO feasible node fail with the device mask
        diagnosis (reference-format reasons histogram; preemption runs —
        this is how a higher-priority gang preempts a lower one), while
        members whose placement the quorum verdict unwound fail with the
        gang reason and no preemption (the analog of a Permit rejection,
        which never runs PostFilter)."""
        from .framework.types import Diagnosis
        profile = pd.profile
        ref, _needed, min_count = pd.gang
        raw = pd.gang_raw
        # the infeasible members' rejector plugins become the whole gang's
        # requeue triggers: the cluster event that could fix the stuck
        # member is exactly the event that un-sticks the gang
        plugins: set = {"GangScheduling"}
        infeasible: list = []
        unwound: list = []
        names = self.state.node_names
        for i, qpi in enumerate(qpis):
            if raw is not None and i < len(raw) and raw[i] >= 0:
                unwound.append((qpi, names[int(raw[i])]))
            else:
                infeasible.append(qpi)
        # Diagnose the infeasible members against the state the serial
        # oracle would have seen: the unwound members' placements
        # TEMPORARILY assumed (parked members hold resources there), so
        # the reasons histogram reads "2 Insufficient cpu", not "cluster
        # empty". The assumes are forgotten before any failure handling —
        # preemption must never see the phantom members as victims.
        errs: list = []
        if infeasible:
            temp: list = []
            for qpi, node_name in unwound:
                pi = PodInfo(pod=qpi.pod.with_node_name(node_name),
                             requests=qpi.pod_info.requests,
                             cpu_nonzero=qpi.pod_info.cpu_nonzero,
                             mem_nonzero=qpi.pod_info.mem_nonzero)
                try:
                    self.cache.assume_pod_info(pi)
                    temp.append(pi.pod)
                except KeyError:
                    pass
            try:
                self.cache.update_snapshot(self.snapshot)
                for qpi in infeasible:
                    errs.append(self._device_fit_error(qpi, profile,
                                                       diag_cache))
            finally:
                for pod in temp:
                    try:
                        self.cache.forget_pod(pod)
                    except (KeyError, ValueError):
                        pass
                self.cache.update_snapshot(self.snapshot)
                # the diagnosis context refreshed the staging arrays with
                # the phantom members in them: restore the real truth
                self.state.apply_snapshot(self.snapshot)
        for qpi, err in zip(infeasible, errs):
            plugins |= err.diagnosis.unschedulable_plugins
            self._handle_failure(qpi, err)
        n_nodes = len(self.snapshot.node_info_list)
        msg = (f"gang {ref!r} rejected: {pd.gang_placed} of {min_count} "
               f"required members placeable")
        for qpi, _node in unwound:
            err = FitError(qpi.pod, n_nodes)
            err.diagnosis = Diagnosis(unschedulable_plugins=set(plugins),
                                      pre_filter_msg=msg)
            self._handle_failure(qpi, err, try_preempt=False,
                                 requeue_cause="gang_split")

    def _fast_commit(self, pairs: list, profile: Profile) -> int:
        """Vectorized commit for hook-free pods: the per-pod work of
        assume (cache.go:369) + FinishBinding + bind enqueue collapsed to
        the minimum — this loop bounds the whole scheduler's throughput
        (schedule_one.go:65-136's responsibilities at batch scale)."""
        if not pairs:
            return 0
        from .backend.cache import _PodState
        cache = self.cache
        pod_states = cache.pod_states
        assumed_set = cache.assumed_pods
        ttl = cache.ttl
        nominated = self.queue.nominator.nominated_pods
        in_flight = self.queue.in_flight_pods
        now = self.clock()
        bound_pods: list[tuple[Pod, Pod]] = []
        sli_by_attempts: dict[int, list] = {}
        for qpi, node_name in pairs:
            pod = qpi.pod
            uid = pod.uid
            if uid in pod_states:
                in_flight.pop(uid, None)
                continue
            assumed = pod.with_node_name(node_name)
            pi = PodInfo(pod=assumed, requests=qpi.pod_info.requests,
                         cpu_nonzero=qpi.pod_info.cpu_nonzero,
                         mem_nonzero=qpi.pod_info.mem_nonzero)
            cache._add_pod_info_to_node(pi)
            st = _PodState(pod=assumed, assumed=True, binding_finished=True)
            if ttl > 0:
                st.deadline = now + ttl
            pod_states[uid] = st
            assumed_set.add(uid)
            if nominated:
                self.queue.nominator.delete(pod)
            in_flight.pop(uid, None)
            bound_pods.append((assumed, pod))
            sli_by_attempts.setdefault(qpi.attempts or 1, []).append(
                now - (qpi.initial_attempt_timestamp or qpi.timestamp))
            if qpi.unschedulable_plugins:
                qpi.unschedulable_plugins = set()
            qpi.consecutive_errors_count = 0
        if not in_flight:
            self.queue.in_flight_events.clear()
        self.journey.record_bulk(
            [pod.uid for _assumed, pod in bound_pods], _EV_ASSIGN, now,
            detail=[assumed.spec.node_name for assumed, _pod in bound_pods])
        self.dispatcher.add_binds(bound_pods)
        # Scheduled events, bulk + lazy-formatted (pod.uid is already the
        # "ns/name" object ref — no per-pod string building here)
        self.events.scheduled_bulk(
            [(pod.uid, assumed.spec.node_name)
             for assumed, pod in bound_pods], now=now)
        nb = len(bound_pods)
        self.scheduled_count += nb
        from .metrics import SCHEDULED
        self.metrics.schedule_attempts.inc(SCHEDULED, profile.name, by=nb)
        for attempts, values in sli_by_attempts.items():
            self.metrics.sli_duration.observe_array(values, str(attempts))
        return nb

    def _xone(self, batch, i: int) -> PodXs:
        return PodXs(valid=np.bool_(True), sig=np.int32(batch.sig[i]),
                     tidx=np.int32(batch.tidx[i]))

    def _uniform_escalate(self, cfg: ScoreConfig, na, carry, batch,
                          i: int, j: int, table, out, j_failed: int,
                          ovl=None):
        """Depth-J overflow recovery: retry the run with a deeper matrix
        (synchronous — this path is rare, and the only one that mints
        non-standard J shapes), falling back to the scan if even J=L+1
        reports failure (can't happen semantically, but belt and
        braces)."""
        L, K, _ = self._uniform_shape(na)
        J = j_failed
        while J < L + 1:
            J = min(8 * J, L + 1)
            if self.mesh is not None:
                from .parallel.sharding import run_uniform_sharded
                c2, packed = run_uniform_sharded(
                    cfg, self.mesh, na, carry, self._xone(batch, i),
                    table, np.int32(j - i), L, K, J)
            else:
                c2, packed = run_uniform(cfg, na, carry,
                                         self._xone(batch, i), table,
                                         np.int32(j - i), L, K, J,
                                         overlay=ovl)
            r = np.asarray(packed)
            if r[L] and r[L + 1]:
                out[i:j] = r[:j - i]
                return c2
            if not r[L]:
                break
        carry, a = self._scan_dispatch(cfg, na, carry, batch, i, j, table,
                                       ovl=ovl)
        out[i:j] = np.asarray(a)[:j - i]
        return carry

    def _scan_dispatch(self, cfg: ScoreConfig, na, carry, batch, i: int,
                       j: int, table, ovl=None, nom=None):
        """Dispatch run_batch over pods [i:j) padded to a pow2 bucket;
        returns (carry, device assignments) without synchronizing."""
        bucket = pow2_at_least(j - i)
        m = j - i
        valid = np.zeros((bucket,), bool)
        valid[:m] = batch.valid[i:j]
        sig = np.full((bucket,), batch.sig[j - 1], np.int32)
        sig[:m] = batch.sig[i:j]
        tidx = np.full((bucket,), batch.tidx[j - 1], np.int32)
        tidx[:m] = batch.tidx[i:j]
        # self-nominated pods keep their signature: the cached fit_ok is
        # overlay-pure and the per-pod exclusion is a one-row delta in
        # _eval_pod, so the fast path still serves them
        nom_idx = None
        if nom is not None:
            nom_idx = np.full((bucket,), -1, np.int32)
            nom_idx[:m] = nom[i:j]
        xs = PodXs(valid=valid, sig=sig, tidx=tidx, nom_idx=nom_idx)
        if self.mesh is not None:
            from .parallel.sharding import run_batch_sharded
            c2, a = run_batch_sharded(cfg, self.mesh, na, carry, xs, table,
                                      groups=self._gd_dev, fam=self._gd_fam)
            if self.observatory.enabled and not self._shard_profile_done:
                # arm the one-shot lane profile with this dispatch's inputs
                # (run_batch_sharded does not donate the carry, and c2 keeps
                # the POST-dispatch state alive for the probe)
                self._shard_profile_args = (cfg, self.mesh, na, c2, xs,
                                            table, self._gd_dev, self._gd_fam)
            return c2, a
        return run_batch(cfg, na, carry, xs, table, groups=self._gd_dev,
                         fam=self._gd_fam, overlay=ovl)

    def reconcile(self) -> list:
        """Debug/divergence check (cache debugger analog): pull the resident
        device carry into staging and compare against the host cache truth.
        Returns divergent node names; [] when scan bookkeeping matches."""
        self._drain_pending()
        self.cache.update_snapshot(self.snapshot)
        if self._device_carry is not None:
            c = (self._fold_retires(self._device_carry) if self._retires
                 else self._device_carry)
            gens = {ni.name: ni.generation
                    for ni in self.snapshot.node_info_list}
            self.state.adopt_carry(c.used, c.nonzero_used, c.npods, c.ports,
                                   touched=gens)
        divergent = self.state.reconcile(self.snapshot)
        if divergent:
            self.metrics.cache_divergence.inc("device_vs_host",
                                              by=len(divergent))
            klog.warning("device carry diverges from host cache",
                         nodes=divergent)
        return divergent

    def debug_compare(self) -> dict:
        """Full divergence sweep (cache debugger analog, SIGUSR2 in the
        reference): device-carry vs host cache AND host cache vs
        apiserver truth."""
        return {"device_vs_host": self.reconcile(),
                "host_vs_apiserver": self.debugger.compare()}

    def profile_session(self):
        """jax.profiler session context, gated by the config
        `profilerTraceDir` knob (a no-op context when unset): wrap a
        stretch of scheduling with it to get the XLA/TPU-level trace
        under the host spans."""
        from .utils.tracing import jax_profiler_session
        return jax_profiler_session(self.profiler_trace_dir)

    def _device_fit_error(self, qpi: QueuedPodInfo, profile: Profile,
                          diag_cache: dict) -> FitError:
        """The device reports only global infeasibility; the diagnosis —
        exact per-node statuses and rejecting plugins, which queueing
        hints, preemption's resolvable-node pruning and the
        FailedScheduling event all need — comes from the mask-derived
        device reduction (ops/program.py diagnose_row) when the signature
        is tensorizable, else from a host-oracle filter replay. Identical
        signatures share identical filter outcomes, so the dict lookup
        makes mass failures (a full cluster rejecting a homogeneous tail)
        cost ONE reduction per signature per batch instead of one per
        pod."""
        from .framework.types import Diagnosis
        # content key, not the numeric sig id: host-port pods carry sig 0
        # yet still share identical filter outcomes
        sig = BatchBuilder._sig_key(qpi.pod)
        cached = diag_cache.get(sig)
        if cached is None:
            cached = self._mask_diagnosis(qpi, diag_cache)
            if cached is None:
                cached = self._host_replay_diagnosis(qpi, profile)
            if not cached.unschedulable_plugins:
                cached.unschedulable_plugins = {"NodeResourcesFit"}
            diag_cache[sig] = cached
        err = FitError(qpi.pod, len(self.snapshot.node_info_list))
        err.diagnosis = cached
        return err

    def _host_replay_diagnosis(self, qpi: QueuedPodInfo, profile: Profile):
        """Host-oracle filter replay over the live snapshot — the fallback
        diagnosis tier (gate off, non-tensorizable signature, reduction
        fault)."""
        from .framework.types import Diagnosis
        fwk = profile.framework
        nodes = self.snapshot.node_info_list
        diagnosis = Diagnosis()
        state = CycleState()
        pre_result, status = fwk.run_pre_filter_plugins(
            state, qpi.pod, nodes)
        if not status.is_success():
            diagnosis.pre_filter_msg = "; ".join(status.reasons)
            if status.plugin:
                diagnosis.unschedulable_plugins.add(status.plugin)
        else:
            fwk.find_nodes_that_pass_filters(state, qpi.pod, nodes,
                                             pre_result, diagnosis)
        return diagnosis

    def _mask_diagnosis(self, qpi: QueuedPodInfo, diag_cache: dict):
        """Diagnosis from the device filter masks: one diagnose_row
        reduction against the post-commit node state attributes every
        rejected node to its first failing plugin (host filter order) with
        exact per-reason detail. Returns None when the reduction cannot
        represent the pod (host-fallback signature, gate off, sharded
        mesh) or faults — the caller then replays on the host."""
        if (self.mesh is not None
                or not self.feature_gates.enabled("DeviceMaskDiagnosis")):
            return None
        ent = self.builder._lookup(qpi.pod)
        if ent[0] != "row":
            return None
        tidx = ent[2]
        ctx = diag_cache.get("_device_ctx")
        if ctx is None:
            try:
                ctx = self._diagnosis_context()
            except Exception as e:
                klog.warning("device diagnosis context build failed; "
                             "falling back to host filter replay",
                             err=str(e))
                ctx = False
            diag_cache["_device_ctx"] = ctx
        if ctx is False:
            return None
        na, table, gd, gc, fam = ctx
        try:
            from .ops.program import diagnose_row
            slot, pods_fail, cols_fail = diagnose_row(na, table, tidx,
                                                      gd=gd, gc=gc, fam=fam)
            slot = np.asarray(slot)
            pods_fail = np.asarray(pods_fail)
            cols_fail = np.asarray(cols_fail)
        except Exception as e:
            klog.warning("device diagnosis reduction failed; falling back "
                         "to host filter replay", err=str(e))
            return None
        return self._assemble_diagnosis(qpi, tidx, slot, pods_fail,
                                        cols_fail)

    def _diagnosis_context(self):
        """Post-commit device state for diagnose_row, built once per
        failed drain (cached in the drain's diag_cache): staging node
        arrays refreshed from the live snapshot, the signature table, and
        — when group constraints are live — fresh group tensors."""
        from .ops.groups import to_device
        from .ops.program import PodTableDev
        self.state.apply_snapshot(self.snapshot)
        self.state.ensure_arrays()
        na = self.state.arrays
        table = PodTableDev(*(jnp.asarray(getattr(self.builder.table, f))
                              for f in PodTableDev._fields))
        gd = gc = fam = None
        groups_needed = (
            self.builder.groups.any_groups()
            or bool(self.snapshot.have_pods_with_affinity_list)
            or bool(
                self.snapshot.have_pods_with_required_anti_affinity_list))
        if groups_needed:
            gd_np, gc_np = self.builder.groups.build_dev(self.snapshot)
            gd, gc = to_device(gd_np), to_device(gc_np)
            fam = self.builder.groups.families(self.snapshot)
        return na, table, gd, gc, fam

    def _assemble_diagnosis(self, qpi: QueuedPodInfo, tidx: int, slot,
                            pods_fail, cols_fail):
        """slot/fit arrays → Diagnosis with per-node Statuses carrying the
        host plugins' exact reason strings and codes."""
        from .framework.types import Diagnosis
        from .ops import program as prog
        from .plugins.node_basics import (TaintToleration as TTPlugin,
                                          find_matching_untolerated_taint)
        from .plugins.nodeaffinity import ERR_REASON as NA_ERR
        from .plugins.podtopologyspread import (
            ERR_REASON_CONSTRAINTS_NOT_MATCH, ERR_REASON_NODE_LABEL_NOT_MATCH)
        from .plugins.interpodaffinity import (ERR_AFFINITY,
                                               ERR_ANTI_AFFINITY,
                                               ERR_EXISTING_ANTI_AFFINITY)
        pod = qpi.pod
        diagnosis = Diagnosis()
        names = self.state.node_names
        # one shared Status per identical (slot, detail) — a 5k-node mass
        # rejection allocates a handful of Status objects, not 5k
        shared: dict = {}
        simple = {
            prog.DIAG_NODE_UNSCHEDULABLE: (
                Status.unresolvable, "node(s) were unschedulable",
                "NodeUnschedulable"),
            prog.DIAG_NODE_NAME: (
                Status.unresolvable,
                "node(s) didn't match the requested node name", "NodeName"),
            prog.DIAG_NODE_AFFINITY: (
                Status.unresolvable, NA_ERR, "NodeAffinity"),
            prog.DIAG_PORTS: (
                Status.unschedulable,
                "node(s) didn't have free ports for the requested pod ports",
                "NodePorts"),
            prog.DIAG_SPREAD_LABEL: (
                Status.unresolvable, ERR_REASON_NODE_LABEL_NOT_MATCH,
                "PodTopologySpread"),
            prog.DIAG_SPREAD_SKEW: (
                Status.unschedulable, ERR_REASON_CONSTRAINTS_NOT_MATCH,
                "PodTopologySpread"),
            prog.DIAG_IPA_AFFINITY: (
                Status.unresolvable, ERR_AFFINITY, "InterPodAffinity"),
            prog.DIAG_IPA_ANTI: (
                Status.unschedulable, ERR_ANTI_AFFINITY, "InterPodAffinity"),
            prog.DIAG_IPA_EXISTING_ANTI: (
                Status.unschedulable, ERR_EXISTING_ANTI_AFFINITY,
                "InterPodAffinity"),
        }
        req_row = self.builder.table.req[tidx]
        cap = self.state.arrays.cap
        rnames = self.state.rtable.names
        for i in np.nonzero(slot > 0)[0]:
            i = int(i)
            name = names[i] if i < len(names) else ""
            if not name:
                continue
            s = int(slot[i])
            if s == prog.DIAG_TAINT:
                # reason carries the taint content — resolve it from the
                # node itself, exactly like the host plugin
                ni = self.snapshot.get(name)
                taint = find_matching_untolerated_taint(
                    ni.node.spec.taints, pod.spec.tolerations,
                    TTPlugin.FILTER_EFFECTS) if ni is not None else None
                key = (s, taint.key if taint else "",
                       taint.value if taint else "")
                status = shared.get(key)
                if status is None:
                    reason = (f"node(s) had untolerated taint "
                              f"{{{taint.key}: {taint.value}}}" if taint
                              else "node(s) had untolerated taint")
                    status = shared[key] = Status.unresolvable(
                        reason, plugin="TaintToleration")
            elif s == prog.DIAG_FIT:
                # per-reason fit detail (fit.go insufficient_resources):
                # Too many pods + per-column Insufficient <resource>;
                # unresolvable when a request exceeds this node's raw
                # allocatable (per-node, so it keys the sharing too)
                cols = tuple(int(c) for c in np.nonzero(cols_fail[i])[0])
                unresolvable = any(int(req_row[c]) > int(cap[i, c])
                                   for c in cols)
                key = (s, bool(pods_fail[i]), cols, unresolvable)
                status = shared.get(key)
                if status is None:
                    reasons = []
                    if pods_fail[i]:
                        reasons.append("Too many pods")
                    reasons.extend(
                        "Insufficient " + (rnames[c] if c < len(rnames)
                                           else f"resource-{c}")
                        for c in cols)
                    mk = (Status.unresolvable if unresolvable
                          else Status.unschedulable)
                    status = shared[key] = mk(*reasons,
                                              plugin="NodeResourcesFit")
            else:
                status = shared.get(s)
                if status is None:
                    mk, reason, plugin = simple[s]
                    status = shared[s] = mk(reason, plugin=plugin)
            diagnosis.node_to_status[name] = status
            if status.plugin:
                diagnosis.unschedulable_plugins.add(status.plugin)
        return diagnosis

    # -- scheduling: host path (oracle + fallback) ----------------------------

    def schedule_one(self) -> bool:
        """Reference ScheduleOne: pop + host-schedule a single pod."""
        if self.profiler is not None:
            self.profiler.ensure_running()
        self._drain_pending()
        qpi = self.queue.pop()
        if qpi is None:
            return False
        ok = self._schedule_one_host(qpi)
        self.dispatcher.flush()
        return ok

    def _schedule_one_host(self, qpi: QueuedPodInfo) -> bool:
        self.schedule_attempts += 1
        pod = qpi.pod
        profile = self.profiles.get(pod.spec.scheduler_name)
        if profile is None:
            self.queue.done(pod.uid)
            return False
        if self._skip_pod_schedule(pod):
            self.queue.done(pod.uid)
            return False
        self.metrics.placement_pods.inc("host")
        self.cache.update_snapshot(self.snapshot)
        state = CycleState()
        # plugin_execution_duration sampling: ~10% of host cycles
        # (pluginMetricsSamplePercent, schedule_one.go:51,104-107)
        state.record_plugin_metrics = (self.schedule_attempts % 10 == 0)
        try:
            result = schedule_pod(profile.framework, state, pod,
                                  self.snapshot.node_info_list,
                                  nominator=self.queue.nominator,
                                  extenders=profile.extenders)
        except FitError as err:
            self._handle_failure(qpi, err, state)
            return False
        except Exception:
            # a plugin blew up (schedule_one.go:161 err path): record it —
            # silent requeue makes plugin bugs undebuggable
            klog.exception("scheduling attempt failed with plugin error",
                           pod=pod.uid,
                           errors=qpi.consecutive_errors_count + 1)
            qpi.consecutive_errors_count += 1
            self.error_count += 1
            self.queue.add_unschedulable_if_not_present(qpi)
            return False
        self.host_scheduled += 1
        self._assume_and_bind(qpi, result.suggested_host, state)
        # a host-path assume mutates node state outside the device carry
        self._invalidate_device_state()
        return True

    def _skip_pod_schedule(self, pod: Pod) -> bool:
        """schedule_one.go:404: deleted or already-assumed pods."""
        return self.cache.is_assumed_pod(pod)

    # -- assume + bind (shared) -----------------------------------------------

    def _assume_and_bind(self, qpi: QueuedPodInfo,
                         node_name: str,
                         state: Optional[CycleState] = None) -> None:
        pod = qpi.pod
        assumed = pod.with_node_name(node_name)
        # reuse the queue entry's pre-parsed requests — no quantity
        # re-parsing on the per-bind hot path
        pi = PodInfo(pod=assumed, requests=qpi.pod_info.requests,
                     cpu_nonzero=qpi.pod_info.cpu_nonzero,
                     mem_nonzero=qpi.pod_info.mem_nonzero)
        try:
            self.cache.assume_pod_info(pi)
        except KeyError:
            self.queue.done(pod.uid)
            return
        self.queue.nominator.delete(pod)
        profile = self.profiles.get(pod.spec.scheduler_name)
        fwk = profile.framework
        cs = state or CycleState()
        # volume-free pods under gang-only hooks skip reserve/permit; a pod
        # with PVC volumes always runs the full chain (VolumeBinding holds
        # its per-node decisions in the CycleState from the host filter).
        # Mirrored by _needs_per_pod_hooks — keep the gates in lockstep.
        run_hooks = (fwk.reserve_plugins or fwk.permit_plugins) and (
            pod.spec.workload_ref or pod.spec.volumes
            or pod.spec.resource_claims
            or not profile.gang_only_hooks)
        if run_hooks:
            status = fwk.run_reserve_plugins_reserve(cs, assumed, node_name)
            if not status.is_success():
                fwk.run_reserve_plugins_unreserve(cs, assumed, node_name)
                self.cache.forget_pod(assumed)
                self._invalidate_device_state()
                self._handle_failure(qpi, FitError(pod, 0),
                                     try_preempt=False)
                return
            status, wait_timeout = fwk.run_permit_plugins(cs, assumed,
                                                          node_name)
            if status.code == Code.WAIT and wait_timeout <= 0:
                # the group's scheduling deadline already expired: reject
                # instead of parking for another round (the reference's
                # WaitOnPermit timer fires immediately at timeout 0)
                status = Status.unschedulable(
                    "gang scheduling deadline expired",
                    plugin=status.plugin)
            if not status.is_success() and status.code != Code.WAIT:
                # rejection OR plugin error: either way the pod must not
                # bind — unreserve, release the assumed resources, requeue
                fwk.run_reserve_plugins_unreserve(cs, assumed, node_name)
                self.cache.forget_pod(assumed)
                self._invalidate_device_state()
                if status.code == Code.ERROR:
                    self.error_count += 1
                self._handle_failure(qpi, FitError(pod, 0),
                                     try_preempt=False)
                return
            if status.code == Code.WAIT:
                # WaitOnPermit (schedule_one.go:302): park; resources stay
                # assumed; a later gang member's Permit (or the timeout
                # sweep in flush_queues) resolves it
                self.queue.done(pod.uid)
                now = self.clock()
                self._waiting_pods[pod.uid] = _WaitingPodRec(
                    qpi=qpi, assumed=assumed, node_name=node_name,
                    cycle_state=cs, deadline=now + wait_timeout,
                    parked_at=now, wait_plugin=status.plugin)
                return
        if not self._run_pre_bind(profile, cs, qpi, assumed, node_name):
            return
        self.queue.done(pod.uid)
        self.cache.finish_binding(assumed)
        binder = next((e for e in profile.extenders if e.is_binder()), None)
        if binder is not None:
            # a binder extender takes over the bind call (extender.go
            # IsBinder; schedule_one.go extendersBinding) — synchronously,
            # since the webhook owns the API write
            try:
                binder.bind(assumed, node_name)
            except Exception as e:
                self._on_bind_error(assumed, node_name, e)
                self.scheduled_count += 1   # _on_bind_error decrements
                return
        else:
            self.dispatcher.add(APICall(CallType.BIND, assumed,
                                        node_name=node_name))
        self.scheduled_count += 1
        self.events.scheduled(pod.uid, node_name)
        self.journey.record(pod.uid, _EV_ASSIGN, self.clock(),
                            detail=node_name)
        from .metrics import SCHEDULED
        self.metrics.schedule_attempts.inc(
            SCHEDULED, pod.spec.scheduler_name)
        start = qpi.initial_attempt_timestamp or qpi.timestamp
        self.metrics.sli_duration.observe(
            max(self.clock() - start, 0.0), str(qpi.attempts or 1))
        qpi.unschedulable_plugins = set()
        qpi.consecutive_errors_count = 0

    def _run_pre_bind(self, profile: Profile, cs: CycleState,
                      qpi: QueuedPodInfo, assumed: Pod,
                      node_name: str) -> bool:
        """PreBind (schedule_one.go:327): VolumeBinding's API writes.
        Volume-free pods skip it when VolumeBinding is the only PreBind
        plugin. On failure: unreserve, release the assumed resources,
        requeue — returns False so the caller aborts the bind."""
        fwk = profile.framework
        pod = qpi.pod
        if not fwk.pre_bind_plugins or (
                profile.volume_only_pre_bind
                and not pod.spec.volumes
                and not pod.spec.resource_claims):
            return True
        status = fwk.run_pre_bind_plugins(cs, assumed, node_name)
        if status.is_success():
            return True
        fwk.run_reserve_plugins_unreserve(cs, assumed, node_name)
        try:
            self.cache.forget_pod(assumed)
        except (KeyError, ValueError):
            pass
        self._invalidate_device_state()
        self.error_count += 1
        self._handle_failure(qpi, FitError(pod, 0), try_preempt=False)
        return False

    def _on_bind_error(self, pod: Pod, node_name: str, err: Exception) -> None:
        """schedule_one.go:361-393: forget + requeue via the failure handler.

        The requeue MUST apply error backoff (consecutive_errors_count) — a
        straight activeQ re-add livelocks schedule_pending when the bind
        error is persistent (drain → bind fail → re-add → drain ...)."""
        self.scheduled_count -= 1
        self.error_count += 1
        klog.error("bind failed; forgetting assumed pod and requeueing",
                   pod=pod.uid, node=node_name, err=str(err))
        try:
            self.cache.forget_pod(pod)
        except (KeyError, ValueError):
            pass
        self._invalidate_device_state()
        fresh = pod.with_node_name("")
        errors = self._bind_errors.get(pod.uid, 0) + 1
        self._bind_errors[pod.uid] = errors
        # the fresh QueuedPodInfo must NOT restart the queue→bind e2e SLI
        # clock: the journey ledger holds the pod's first-enqueue time
        # across the unwind (None = never seen, falls back to timestamp)
        qpi = QueuedPodInfo(pod_info=PodInfo.of(fresh),
                            timestamp=self.clock(),
                            initial_attempt_timestamp=self.journey.e2e_start(
                                pod.uid),
                            consecutive_errors_count=errors)
        self._journey_requeue(
            [pod.uid],
            "fence_unwind" if isinstance(err, FencedWrite) else "bind_error",
            detail=str(err)[:120])
        self.queue.add_unschedulable_if_not_present(qpi)
        self.queue.move_all_to_active_or_backoff_queue(
            EVENT_ASSIGNED_POD_DELETE, pod, None)

    # -- failure path ---------------------------------------------------------

    def _handle_failure(self, qpi: QueuedPodInfo, err: FitError,
                        state: Optional[CycleState] = None,
                        try_preempt: bool = True,
                        requeue_cause: str = "") -> None:
        """schedule_one.go:1038 handleSchedulingFailure. A genuine
        scheduling FitError runs the PostFilter (preemption) path first —
        reserve/permit failures pass try_preempt=False, matching the
        reference where PostFilter only follows schedulePod failures
        (schedule_one.go:150-170). `requeue_cause` overrides the journey
        requeue cause (gang unwinds pass "gang_split"); otherwise the
        cause is "preemption" when this failure nominated a node, else
        "unschedulable"."""
        self.unschedulable_count += 1
        qpi.unschedulable_plugins = set(err.diagnosis.unschedulable_plugins)
        qpi.pending_plugins = set(err.diagnosis.pending_plugins)
        pod = qpi.pod
        nominated = pod.status.nominated_node_name
        preempted = False
        profile = self.profiles.get(pod.spec.scheduler_name)
        if (try_preempt and err.num_all_nodes > 0 and profile is not None
                and profile.framework.post_filter_plugins):
            if self._pending:
                # never compute victims on optimistic state that excludes
                # in-flight drains' assignments: an already-dispatched drain
                # may be about to fill the very nodes the Evaluator would
                # evict from (ADVICE r5 medium). Each nested commit pops
                # before it handles failures, so the recursion terminates.
                self._drain_pending()
            self.cache.update_snapshot(self.snapshot)
            result, status = profile.framework.run_post_filter_plugins(
                state or CycleState(), pod, err.diagnosis.node_to_status)
            if status.is_success() and result:
                nominated = result
                pod.status.nominated_node_name = nominated
                self.queue.nominator.add(qpi, nominated)
                self.preemption_attempts += 1
                preempted = True
                self.metrics.preemption_attempts.inc()
                klog.v(2).info("preemption nominated node", pod=pod.uid,
                               node=nominated)
        from .metrics import UNSCHEDULABLE
        self.metrics.schedule_attempts.inc(
            UNSCHEDULABLE, pod.spec.scheduler_name)
        self.metrics.queue_incoming_pods.inc("unschedulable",
                                             "ScheduleAttemptFailure")
        # FailedScheduling event with the reference-format message
        # ("0/N nodes are available: X Insufficient cpu, ...") + the
        # per-plugin rejected-node counts behind it
        from .events import EVENT_WARNING, REASON_FAILED_SCHEDULING
        msg = str(err)
        self.events.event(pod.uid, EVENT_WARNING, REASON_FAILED_SCHEDULING,
                          msg)
        for plugin, count in err.diagnosis.plugin_node_counts().items():
            self.metrics.unschedulable_nodes.observe(count, plugin)
        # journey: the FitError transition (detail = rejector plugins)
        # then the requeue with its cause — the pair /debug/pod renders
        # as "why it failed" + "why it's back in the queue"
        self.journey.record(
            pod.uid, _EV_FIT_ERROR, self.clock(),
            detail=",".join(sorted(qpi.unschedulable_plugins or ())))
        self._journey_requeue(
            [pod.uid],
            requeue_cause or ("preemption" if preempted
                              else "unschedulable"),
            detail=nominated or "")
        self.queue.add_unschedulable_if_not_present(qpi)
        self.dispatcher.add(APICall(
            CallType.STATUS_PATCH, qpi.pod,
            condition={"type": "PodScheduled", "status": "False",
                       "reason": "Unschedulable", "message": msg},
            nominated_node_name=nominated))

    # -- housekeeping ---------------------------------------------------------

    def flush_queues(self) -> None:
        """SchedulingQueue.Run periodic work (scheduling_queue.go:406-413)
        + the WaitOnPermit timeout sweep (waiting_pods_map.go timers)."""
        self._drain_pending()
        now = self.clock()
        for uid, rec in list(self._waiting_pods.items()):
            if rec.deadline <= now:
                self._reject_waiting(uid, "permit wait timeout")
        self.queue.flush_backoff_completed()
        self.queue.flush_unschedulable_leftover()

    def pending_summary(self) -> str:
        return self.queue.pending_pods()[1]
