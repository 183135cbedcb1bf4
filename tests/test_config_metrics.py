"""ComponentConfig (config/) + metrics registry (metrics/).

Mirrors the consumed subset of apis/config/types.go:37 and
metrics/metrics.go:196-460: config round-trip + validation, profile
construction from config (enable/disable/weights/strategy), and the
scheduler's series moving during real scheduling.
"""

import os

import pytest

from kubernetes_tpu.backend.apiserver import APIServer
from kubernetes_tpu.config import (KubeSchedulerConfiguration,
                                   KubeSchedulerProfile, PluginSet,
                                   build_profiles)
from kubernetes_tpu.metrics import (Counter, Gauge, Histogram, Registry,
                                    SchedulerMetrics)
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.testing.wrappers import make_node, make_pod


class TestConfig:
    def test_round_trip(self):
        cfg = KubeSchedulerConfiguration(
            profiles=[
                KubeSchedulerProfile(scheduler_name="default-scheduler"),
                KubeSchedulerProfile(
                    scheduler_name="batch",
                    plugins=PluginSet(disabled=["InterPodAffinity"]),
                    plugin_weights={"TaintToleration": 5},
                    scoring_strategy="MostAllocated"),
            ],
            pod_initial_backoff_seconds=0.5,
            pod_max_backoff_seconds=5.0,
            batch_size=1024)
        cfg.validate()
        again = KubeSchedulerConfiguration.from_dict(cfg.to_dict())
        assert again == cfg

    def test_compilation_cache_knob(self, tmp_path):
        """ISSUE 3 satellite: the persistent XLA compilation cache knob
        round-trips, applies to jax config once, and 'off' disables."""
        import jax

        from kubernetes_tpu import config as config_mod

        cfg = KubeSchedulerConfiguration(
            compilation_cache_dir=str(tmp_path / "xla"))
        cfg.validate()
        again = KubeSchedulerConfiguration.from_dict(cfg.to_dict())
        assert again.compilation_cache_dir == cfg.compilation_cache_dir
        # default present in the dict form: the fixed in-checkout path
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert (KubeSchedulerConfiguration().to_dict()["compilationCacheDir"]
                == os.path.join(repo, ".jax_cache")
                == config_mod.DEFAULT_COMPILATION_CACHE_DIR)
        prev_applied = config_mod._cc_applied
        prev_dir = jax.config.jax_compilation_cache_dir
        try:
            config_mod._cc_applied = False
            assert config_mod.apply_compilation_cache("off") is False
            assert config_mod.apply_compilation_cache(
                str(tmp_path / "xla")) is True
            assert jax.config.jax_compilation_cache_dir == str(tmp_path / "xla")
            # once-guard: a second call is a no-op (returns True, no rewrite)
            assert config_mod.apply_compilation_cache("/nope") is True
            assert jax.config.jax_compilation_cache_dir == str(tmp_path / "xla")
        finally:
            config_mod._cc_applied = prev_applied
            jax.config.update("jax_compilation_cache_dir", prev_dir)

    def test_compilation_cache_env_var_wins(self, tmp_path):
        """JAX_COMPILATION_CACHE_DIR set: JAX's own setting survives
        Scheduler construction; unset: the fixed in-checkout path."""
        import subprocess
        import sys

        from kubernetes_tpu import config as config_mod

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        probe = ("import jax, kubernetes_tpu\n"
                 "from kubernetes_tpu.backend.apiserver import APIServer\n"
                 "from kubernetes_tpu.scheduler import Scheduler\n"
                 "Scheduler(APIServer())\n"
                 "print(jax.config.jax_compilation_cache_dir)\n")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        for want, extra in ((str(tmp_path / "cc"),
                             {"JAX_COMPILATION_CACHE_DIR":
                              str(tmp_path / "cc")}),
                            (config_mod.DEFAULT_COMPILATION_CACHE_DIR, {})):
            out = subprocess.run([sys.executable, "-c", probe], cwd=repo,
                                 env=env | extra, capture_output=True,
                                 text=True, timeout=120)
            assert out.returncode == 0, out.stderr[-2000:]
            assert out.stdout.strip().splitlines()[-1] == want

    def test_profiler_trace_dir_knob(self, tmp_path):
        cfg = KubeSchedulerConfiguration(
            profiler_trace_dir=str(tmp_path / "prof"))
        cfg.validate()
        again = KubeSchedulerConfiguration.from_dict(cfg.to_dict())
        assert again.profiler_trace_dir == cfg.profiler_trace_dir
        assert KubeSchedulerConfiguration().to_dict()[
            "profilerTraceDir"] == ""
        api = APIServer()
        sched = Scheduler(api, batch_size=64, config=cfg)
        assert sched.profiler_trace_dir == cfg.profiler_trace_dir

    def test_yaml_load(self, tmp_path):
        p = tmp_path / "cfg.yaml"
        p.write_text("""
profiles:
- schedulerName: default-scheduler
  pluginWeights: {NodeAffinity: 7}
batchSize: 256
podInitialBackoffSeconds: 2
podMaxBackoffSeconds: 20
""")
        from kubernetes_tpu.config import load
        cfg = load(str(p))
        assert cfg.batch_size == 256
        assert cfg.profiles[0].plugin_weights == {"NodeAffinity": 7}

    def test_validation_rejects(self):
        with pytest.raises(ValueError, match="duplicate"):
            KubeSchedulerConfiguration(profiles=[
                KubeSchedulerProfile(), KubeSchedulerProfile()]).validate()
        with pytest.raises(ValueError, match="unknown plugin"):
            KubeSchedulerConfiguration(profiles=[KubeSchedulerProfile(
                plugins=PluginSet(disabled=["NoSuchPlugin"]))]).validate()
        with pytest.raises(ValueError, match="podMaxBackoff"):
            KubeSchedulerConfiguration(
                pod_initial_backoff_seconds=5,
                pod_max_backoff_seconds=1).validate()
        with pytest.raises(ValueError, match="scoringStrategy"):
            KubeSchedulerConfiguration(profiles=[KubeSchedulerProfile(
                scoring_strategy="Weird")]).validate()

    def test_build_profiles_disable_and_weights(self):
        cfg = KubeSchedulerConfiguration(profiles=[KubeSchedulerProfile(
            plugins=PluginSet(disabled=["InterPodAffinity",
                                        "PodTopologySpread"]),
            plugin_weights={"NodeAffinity": 9})])
        (prof,) = build_profiles(cfg)
        names = {p.name() for p in prof.framework.plugins}
        assert "InterPodAffinity" not in names
        assert "PodTopologySpread" not in names
        assert prof.framework.weights["NodeAffinity"] == 9
        assert prof.score_config.w_node_affinity == 9

    def test_scheduler_consumes_config(self):
        cfg = KubeSchedulerConfiguration(
            batch_size=128, pod_initial_backoff_seconds=2.0,
            pod_max_backoff_seconds=30.0)
        api = APIServer()
        sched = Scheduler(api, config=cfg)
        assert sched.batch_size == 128
        assert sched.queue.pod_initial_backoff == 2.0
        assert sched.queue.pod_max_backoff == 30.0
        api.create_node(make_node("n0").capacity(
            {"cpu": 4, "memory": "8Gi", "pods": 10}).obj())
        api.create_pod(make_pod("p").req({"cpu": "1", "memory": "1Gi"}).obj())
        assert sched.schedule_pending() == 1

    def test_most_allocated_strategy_routes_to_scan(self):
        """MostAllocated packs onto the fewest nodes (the closed form is
        gated off; decisions still match the host oracle's strategy)."""
        cfg = KubeSchedulerConfiguration(profiles=[KubeSchedulerProfile(
            scoring_strategy="MostAllocated")])
        api = APIServer()
        sched = Scheduler(api, batch_size=64, config=cfg)
        for i in range(3):
            api.create_node(make_node(f"n{i}").capacity(
                {"cpu": 8, "memory": "16Gi", "pods": 100}).obj())
        for i in range(6):
            api.create_pod(make_pod(f"p{i}").req(
                {"cpu": "1", "memory": "1Gi"}).obj())
        assert sched.schedule_pending() == 6
        used = {p.spec.node_name for p in api.pods.values()}
        assert len(used) == 1  # bin-packing: all on one node


class TestMetricsPrimitives:
    def test_counter_labels(self):
        c = Counter("x_total", "help", ("a",))
        c.inc("one")
        c.inc("one")
        c.inc("two", by=3)
        assert c.value("one") == 2 and c.value("two") == 3
        text = "\n".join(c.expose())
        assert 'x_total{a="one"} 2' in text

    def test_histogram_buckets(self):
        h = Histogram("lat", "help", buckets=[0.1, 1.0])
        for v in (0.05, 0.5, 5.0):
            h.observe(v)
        assert h.count() == 3 and h.sum() == 5.55
        text = "\n".join(h.expose())
        assert 'lat_bucket{le="0.1"} 1' in text
        assert 'lat_bucket{le="1"} 2' in text
        assert 'lat_bucket{le="+Inf"} 3' in text

    def test_registry_rejects_duplicates(self):
        r = Registry()
        r.register(Counter("dup", "h"))
        with pytest.raises(ValueError):
            r.register(Gauge("dup", "h"))

    def test_label_value_escaping(self):
        """Text-format spec: backslash, quote and newline in label values
        must be escaped (they used to be emitted raw)."""
        c = Counter("esc_total", "h", ("msg",))
        c.inc('say "hi"\nback\\slash')
        line = [ln for ln in c.expose() if not ln.startswith("#")][0]
        assert line == ('esc_total{msg="say \\"hi\\"\\nback\\\\slash"} 1')

    def test_help_escaping(self):
        c = Counter("h_total", "line1\nline2 with \\ backslash")
        help_line = c.expose()[0]
        assert help_line == ("# HELP h_total line1\\nline2 with "
                             "\\\\ backslash")
        assert "\n" not in help_line

    def test_histogram_quantile(self):
        h = Histogram("q", "h", buckets=[0.001, 0.01, 0.1, 1.0])
        for _ in range(90):
            h.observe(0.005, "a")     # second bucket
        for _ in range(10):
            h.observe(0.5, "b")       # fourth bucket (labels merge)
        assert 0.001 <= h.quantile(0.5) <= 0.01
        assert 0.1 <= h.quantile(0.99) <= 1.0
        assert Histogram("empty", "h").quantile(0.5) == 0.0


def _parse_exposition(text: str):
    """Minimal promtool-style parse: returns (series, helps, types) where
    series maps sample name → list of (labels dict, value)."""
    import re
    series: dict = {}
    helps: dict = {}
    types: dict = {}
    lbl_re = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP "):
            name, _, h = line[len("# HELP "):].partition(" ")
            assert name not in helps, f"duplicate HELP for {name}"
            helps[name] = h
            continue
        if line.startswith("# TYPE "):
            name, _, t = line[len("# TYPE "):].partition(" ")
            assert name not in types, f"duplicate TYPE for {name}"
            types[name] = t
            continue
        assert not line.startswith("#"), f"unknown comment: {line}"
        m = re.match(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{.*\})? (\S+)$", line)
        assert m, f"unparseable sample line: {line!r}"
        name, labels_raw, value = m.groups()
        labels = dict(lbl_re.findall(labels_raw or ""))
        for v in labels.values():
            assert "\n" not in v
        series.setdefault(name, []).append((labels, float(value)))
    return series, helps, types


class TestExpositionLint:
    """promtool-style lint over a fully-seeded exposition: every series
    has HELP+TYPE, no duplicates, histogram buckets cumulative and capped
    by +Inf, label values escaped."""

    def test_fully_seeded_exposition_lints_clean(self):
        m = SchedulerMetrics()
        # drive a nasty label value through a real series to prove the
        # parse survives escaping end to end
        m.api_retries.inc('bind "quoted"\nvalue')
        text = m.exposition()
        series, helps, types = _parse_exposition(text)

        base = {n[:-len(suffix)] if n.endswith(suffix) else n
                for n in series
                for suffix in ("_bucket", "_sum", "_count")
                if n.endswith(suffix) or suffix == "_count"}
        # every emitted sample belongs to a declared metric family
        for name in series:
            root = name
            for suffix in ("_bucket", "_sum", "_count"):
                if name.endswith(suffix) and name[:-len(suffix)] in types:
                    root = name[:-len(suffix)]
                    break
            assert root in types, f"sample {name} without TYPE"
            assert root in helps, f"sample {name} without HELP"
            assert base is not None

        # every REGISTERED metric is pre-seeded: at least one sample per
        # family (the satellite requirement — dashboards always see the
        # series)
        for name, t in types.items():
            if t == "histogram":
                assert f"{name}_count" in series, f"{name} unseeded"
                assert f"{name}_sum" in series
            elif name == "scheduler_pending_pods":
                continue   # callback gauge: no callback wired here
            else:
                assert name in series, f"{name} unseeded"

        # histogram buckets: per label set, cumulative and +Inf-capped
        for name, t in types.items():
            if t != "histogram":
                continue
            by_key: dict = {}
            for labels, value in series.get(f"{name}_bucket", []):
                le = labels.pop("le")
                key = tuple(sorted(labels.items()))
                by_key.setdefault(key, []).append((le, value))
            counts = {tuple(sorted(lbl.items())): v
                      for lbl, v in series.get(f"{name}_count", [])}
            for key, buckets in by_key.items():
                les = [le for le, _ in buckets]
                assert les.count("+Inf") == 1, f"{name}{key} missing +Inf"
                assert les[-1] == "+Inf", f"{name}{key} +Inf not last"
                values = [v for _, v in buckets]
                assert values == sorted(values), \
                    f"{name}{key} buckets not cumulative"
                assert values[-1] == counts[key]

    def test_no_duplicate_series_names(self):
        m = SchedulerMetrics()
        seen = set()
        for metric in m.registry._metrics.values():
            assert metric.name not in seen
            seen.add(metric.name)

    def test_issue10_families_covered_by_lint(self):
        """ISSUE 10 satellite: the audit/explain/SLO families are
        registered AND pre-seeded, so the generic lint above (HELP+TYPE,
        escaping, +Inf caps) actually exercises them — plus the exact
        label sets dashboards key on."""
        m = SchedulerMetrics()
        series, helps, types = _parse_exposition(m.exposition())
        assert types["scheduler_oracle_divergence_total"] == "counter"
        assert types["scheduler_shadow_audit_drains_total"] == "counter"
        assert types["scheduler_slo_burn_rate"] == "gauge"
        assert types["scheduler_audit_replay_seconds"] == "histogram"
        assert types["scheduler_explain_seconds"] == "histogram"
        kinds = {lbl["kind"] for lbl, _v in
                 series["scheduler_oracle_divergence_total"]}
        assert kinds == {"assignment", "reason", "verdict"}
        outcomes = {lbl["outcome"] for lbl, _v in
                    series["scheduler_shadow_audit_drains_total"]}
        assert outcomes == {"clean", "divergent", "skipped", "error"}
        burn = {(lbl["sli"], lbl["window"]) for lbl, _v in
                series["scheduler_slo_burn_rate"]}
        from kubernetes_tpu.obs.slo import DEFAULT_OBJECTIVES, WINDOWS
        assert burn == {(sli, w) for sli in DEFAULT_OBJECTIVES
                        for _s, w in WINDOWS}
        # histogram families carry the +Inf cap via the generic lint;
        # assert their zero-seed is present too
        assert ("scheduler_audit_replay_seconds_count" in series
                and "scheduler_explain_seconds_count" in series)

    def test_issue13_families_covered_by_lint(self):
        """ISSUE 13 satellite: the journey/timeline/cluster-probe
        families are registered AND pre-seeded with the EXACT label sets
        the dashboards (and /debug surfaces) key on."""
        from kubernetes_tpu.metrics import (CLUSTER_DOM_STATS,
                                            CLUSTER_FRAG_KINDS,
                                            CLUSTER_SEED_RESOURCES,
                                            CLUSTER_UTIL_STATS)
        from kubernetes_tpu.obs.journey import CAUSES, EVENTS, SEGMENTS
        m = SchedulerMetrics()
        series, helps, types = _parse_exposition(m.exposition())
        assert types["scheduler_e2e_segment_seconds"] == "histogram"
        assert types["scheduler_pod_requeues_total"] == "counter"
        assert types["scheduler_journey_transitions_total"] == "counter"
        assert types["scheduler_cluster_utilization_ratio"] == "gauge"
        assert types["scheduler_cluster_fragmentation_index"] == "gauge"
        assert types["scheduler_cluster_domain_imbalance"] == "gauge"
        # the e2e decomposition's exact segment set
        segments = {lbl["segment"] for lbl, _v in
                    series["scheduler_e2e_segment_seconds_count"]}
        assert segments == set(SEGMENTS)
        assert set(SEGMENTS) == {"queue_wait", "gate_wait", "drain",
                                 "commit_backlog"}
        # the requeue-cause label set (every chaos path maps to one)
        causes = {lbl["cause"] for lbl, _v in
                  series["scheduler_pod_requeues_total"]}
        assert causes == set(CAUSES)
        assert set(CAUSES) == {"preemption", "fence_unwind",
                               "breaker_fallback", "gang_split",
                               "resync", "bind_error", "unschedulable"}
        # every journey transition has a zero-seeded counter series
        events = {lbl["event"] for lbl, _v in
                  series["scheduler_journey_transitions_total"]}
        assert events == set(EVENTS)
        # cluster gauges: (resource, stat/kind) grid seeded for the
        # well-known resources; the probe resolve extends it live
        util = {(lbl["resource"], lbl["stat"]) for lbl, _v in
                series["scheduler_cluster_utilization_ratio"]}
        assert util >= {(r, s) for r in CLUSTER_SEED_RESOURCES
                        for s in CLUSTER_UTIL_STATS}
        frag = {(lbl["resource"], lbl["kind"]) for lbl, _v in
                series["scheduler_cluster_fragmentation_index"]}
        assert frag >= {(r, k) for r in CLUSTER_SEED_RESOURCES
                        for k in CLUSTER_FRAG_KINDS}
        dom = {lbl["stat"] for lbl, _v in
               series["scheduler_cluster_domain_imbalance"]}
        assert dom == set(CLUSTER_DOM_STATS)

    def test_issue14_families_covered_by_lint(self):
        """ISSUE 14 satellite: the kernel-observatory families are
        registered AND pre-seeded with the EXACT label sets — every
        ledger kernel on the kernel-labeled pair, one TPU host's worth
        of lanes on the shard gauge — so the generic lint exercises
        them before the first dispatch."""
        from kubernetes_tpu.metrics import SHARD_SEED_LANES
        from kubernetes_tpu.perf.ledger import device_entries
        m = SchedulerMetrics()
        series, helps, types = _parse_exposition(m.exposition())
        assert types["scheduler_kernel_device_seconds"] == "counter"
        assert types["scheduler_kernel_dispatch_total"] == "counter"
        assert types["scheduler_shard_lane_seconds"] == "gauge"
        assert types["scheduler_shard_imbalance_ratio"] == "gauge"
        for fam in ("scheduler_kernel_device_seconds",
                    "scheduler_kernel_dispatch_total"):
            kernels = {lbl["kernel"] for lbl, _v in series[fam]}
            assert kernels == set(device_entries()), fam
        lanes = {lbl["lane"] for lbl, _v in
                 series["scheduler_shard_lane_seconds"]}
        assert lanes == set(SHARD_SEED_LANES)
        assert set(SHARD_SEED_LANES) == {str(i) for i in range(8)}
        # the unlabeled imbalance gauge carries exactly one sample
        (lbl, val), = series["scheduler_shard_imbalance_ratio"]
        assert lbl == {} and val == 0.0

    def test_issue14_observatory_mirror_syncs_at_exposition(self):
        """The exposition mirrors the process-global observatory the
        same way it mirrors the compile ledger: absolute assignment of
        dispatch counts and warm seconds per kernel."""
        from kubernetes_tpu.perf.observatory import GLOBAL as obs
        obs.reset()
        try:
            obs.on_call("run_batch", 0.0, 0.050, False, ())
            obs.on_call("run_batch", 0.0, 0.030, False, ())
            obs.on_call("run_batch", 0.0, 2.000, True, ())  # compile
            obs.set_shard_profile({"laneSeconds": [0.5, 0.25],
                                   "imbalanceRatio": 1.33,
                                   "nDevices": 2})
            m = SchedulerMetrics()
            series, _h, _t = _parse_exposition(m.exposition())
            vals = {lbl["kernel"]: v for lbl, v in
                    series["scheduler_kernel_dispatch_total"]}
            assert vals["run_batch"] == 3.0
            secs = {lbl["kernel"]: v for lbl, v in
                    series["scheduler_kernel_device_seconds"]}
            # warm walls only: the compiling call's 2s stays out
            assert abs(secs["run_batch"] - 0.080) < 1e-9
            lanes = {lbl["lane"]: v for lbl, v in
                     series["scheduler_shard_lane_seconds"]}
            assert lanes["0"] == 0.5 and lanes["1"] == 0.25
            (_lbl, ratio), = series["scheduler_shard_imbalance_ratio"]
            assert abs(ratio - 1.33) < 1e-9
        finally:
            obs.reset()

    def test_issue17_families_covered_by_lint(self):
        """ISSUE 17 satellite: the sharded-control-plane families are
        registered AND pre-seeded with the EXACT label sets the shard
        dashboards (and bench_metrics.prom) key on."""
        from kubernetes_tpu.metrics import (CROSS_SHARD_OUTCOMES,
                                            SHARD_SEED_IDS,
                                            SHARD_STEAL_REASONS)
        m = SchedulerMetrics()
        series, helps, types = _parse_exposition(m.exposition())
        assert types["scheduler_shard_assignments"] == "gauge"
        assert types["scheduler_shard_rebalance_seconds"] == "histogram"
        assert types["scheduler_shard_steals_total"] == "counter"
        assert types["scheduler_cross_shard_conflicts_total"] == "counter"
        shards = {lbl["shard"] for lbl, _v in
                  series["scheduler_shard_assignments"]}
        assert shards == set(SHARD_SEED_IDS)
        assert set(SHARD_SEED_IDS) == {str(i) for i in range(4)}
        reasons = {lbl["reason"] for lbl, _v in
                   series["scheduler_shard_steals_total"]}
        assert reasons == set(SHARD_STEAL_REASONS)
        assert set(SHARD_STEAL_REASONS) == {"split", "merge", "steal",
                                            "rebalance"}
        outcomes = {lbl["outcome"] for lbl, _v in
                    series["scheduler_cross_shard_conflicts_total"]}
        assert outcomes == set(CROSS_SHARD_OUTCOMES)
        assert set(CROSS_SHARD_OUTCOMES) == {"conflict", "fenced"}
        # the rebalance histogram's zero-seed rides the generic lint
        assert "scheduler_shard_rebalance_seconds_count" in series

    def test_issue18_families_covered_by_lint(self):
        """ISSUE 18 satellite: the streaming-pipeline families are
        registered AND pre-seeded with the EXACT stage label set the
        /debug/pipeline occupancy block and bench_metrics.prom key on —
        ingest | device | commit, nothing else, before the pipeline
        ever starts."""
        from kubernetes_tpu.pipeline import STAGES
        m = SchedulerMetrics()
        series, helps, types = _parse_exposition(m.exposition())
        assert types["scheduler_pipeline_stage_busy_seconds"] == "counter"
        assert types["scheduler_pipeline_backpressure_total"] == "counter"
        for fam in ("scheduler_pipeline_stage_busy_seconds",
                    "scheduler_pipeline_backpressure_total"):
            stages = {lbl["stage"] for lbl, _v in series[fam]}
            assert stages == set(STAGES), fam
            # zero-seeded: every series present before the first drain
            assert all(v == 0.0 for _l, v in series[fam]), fam
        assert set(STAGES) == {"ingest", "device", "commit"}

    def test_issue19_families_covered_by_lint(self):
        """ISSUE 19 satellite: the incident-forensics counter is
        registered AND pre-seeded with the EXACT trigger label set the
        watchdog fires — dashboards can alert on rate() before the
        first capture."""
        from kubernetes_tpu.obs.incident import TRIGGERS
        m = SchedulerMetrics()
        series, helps, types = _parse_exposition(m.exposition())
        assert types["scheduler_incidents_total"] == "counter"
        triggers = {lbl["trigger"] for lbl, _v in
                    series["scheduler_incidents_total"]}
        assert triggers == set(TRIGGERS)
        assert set(TRIGGERS) == {"slo_breach", "divergence",
                                 "fence_storm", "pipeline_stall"}
        assert all(v == 0.0
                   for _l, v in series["scheduler_incidents_total"])

    def test_issue20_families_covered_by_lint(self):
        """ISSUE 20 satellite: the critical-path families are registered
        AND pre-seeded with the EXACT cause taxonomy the verdicts emit
        and bench_metrics.prom keys on — dashboards can rate() both
        before the first drain commits."""
        from kubernetes_tpu.perf.critical_path import CAUSES
        m = SchedulerMetrics()
        series, helps, types = _parse_exposition(m.exposition())
        assert types["scheduler_critical_path_seconds"] == "counter"
        assert types["scheduler_bottleneck_drains_total"] == "counter"
        for fam in ("scheduler_critical_path_seconds",
                    "scheduler_bottleneck_drains_total"):
            causes = {lbl["cause"] for lbl, _v in series[fam]}
            assert causes == set(CAUSES), fam
            # zero-seeded: every cause series present before any verdict
            assert all(v == 0.0 for _l, v in series[fam]), fam
        assert set(CAUSES) == {"host_build", "device_compute",
                               "device_comms", "commit", "backpressure",
                               "idle"}


class TestSchedulerMetrics:
    def test_series_move_during_scheduling(self):
        api = APIServer()
        sched = Scheduler(api, batch_size=64)
        api.create_node(make_node("n0").capacity(
            {"cpu": 4, "memory": "8Gi", "pods": 10}).obj())
        for i in range(3):
            api.create_pod(make_pod(f"p{i}").req(
                {"cpu": "1", "memory": "1Gi"}).obj())
        api.create_pod(make_pod("big").req({"cpu": "64", "memory": "1Gi"}).obj())
        sched.schedule_pending()
        m = sched.metrics
        assert m.schedule_attempts.value("scheduled", "default-scheduler") == 3
        assert m.schedule_attempts.value("unschedulable",
                                         "default-scheduler") == 1
        assert m.device_batch_size.count() >= 1
        assert m.sli_duration.count("1") == 3
        assert m.api_dispatcher_calls.value("pod_binding", "success") == 3
        depths = sched._queue_depths()
        assert depths[("unschedulable",)] == 1.0
        text = m.exposition()
        assert "scheduler_schedule_attempts_total" in text
        assert "scheduler_pending_pods" in text

    def test_disable_preemption_via_config(self):
        cfg = KubeSchedulerConfiguration(profiles=[KubeSchedulerProfile(
            plugins=PluginSet(disabled=["DefaultPreemption"]))])
        api = APIServer()
        sched = Scheduler(api, batch_size=64, config=cfg)
        api.create_node(make_node("n0").capacity(
            {"cpu": 4, "memory": "8Gi", "pods": 10}).obj())
        api.create_pod(make_pod("low").req(
            {"cpu": "4", "memory": "1Gi"}).priority(0).obj())
        assert sched.schedule_pending() == 1
        api.create_pod(make_pod("vip").req(
            {"cpu": "4", "memory": "1Gi"}).priority(100).obj())
        assert sched.schedule_pending() == 0
        # preemption off: no eviction, no nomination
        assert "default/low" in api.pods
        assert api.pods["default/vip"].status.nominated_node_name == ""
        assert sched.preemption_attempts == 0


class TestMultiProfile:
    def test_two_profiles_route_by_scheduler_name(self):
        """profile.go:46: a drain mixing schedulerNames must run each pod
        under ITS profile's strategy — spread pods via LeastAllocated,
        binpack pods via MostAllocated — on the device path."""
        cfg = KubeSchedulerConfiguration(profiles=[
            KubeSchedulerProfile(scheduler_name="default-scheduler"),
            KubeSchedulerProfile(scheduler_name="binpack",
                                 scoring_strategy="MostAllocated"),
        ])
        api = APIServer()
        sched = Scheduler(api, batch_size=64, config=cfg)
        for i in range(4):
            api.create_node(make_node(f"n{i}").capacity(
                {"cpu": 16, "memory": "32Gi", "pods": 100}).obj())
        for i in range(4):
            api.create_pod(make_pod(f"spread{i}").req(
                {"cpu": "1", "memory": "1Gi"}).obj())
        for i in range(4):
            p = make_pod(f"pack{i}").req({"cpu": "1", "memory": "1Gi"}).obj()
            p.spec.scheduler_name = "binpack"
            api.create_pod(p)
        assert sched.schedule_pending() == 8
        spread_nodes = {api.pods[f"default/spread{i}"].spec.node_name
                        for i in range(4)}
        pack_nodes = {api.pods[f"default/pack{i}"].spec.node_name
                      for i in range(4)}
        assert len(spread_nodes) == 4   # LeastAllocated round-robins
        assert len(pack_nodes) == 1     # MostAllocated bin-packs

    def test_unowned_scheduler_name_is_dropped(self):
        api = APIServer()
        sched = Scheduler(api, batch_size=64)
        api.create_node(make_node("n0").capacity(
            {"cpu": 4, "memory": "8Gi", "pods": 10}).obj())
        p = make_pod("alien").req({"cpu": "1", "memory": "1Gi"}).obj()
        p.spec.scheduler_name = "someone-else"
        api.create_pod(p)
        assert sched.schedule_pending() == 0
        assert api.pods["default/alien"].spec.node_name == ""


class TestRegistry:
    def test_factories_build_fresh_instances(self):
        from kubernetes_tpu.config import default_registry
        reg = default_registry()
        a = reg.factories["GangScheduling"]()
        b = reg.factories["GangScheduling"]()
        assert a is not b

    def test_enabled_without_factory_raises(self):
        cfg = KubeSchedulerConfiguration(
            extra_plugins=("MyPlugin",),
            profiles=[KubeSchedulerProfile(
                plugins=PluginSet(enabled=["MyPlugin"]))])
        cfg.validate()   # name is vouched for...
        with pytest.raises(ValueError, match="no registered factory"):
            build_profiles(cfg)  # ...but no factory: must not run without it

    def test_extra_plugins_round_trip(self):
        cfg = KubeSchedulerConfiguration(extra_plugins=("MyPlugin",))
        again = KubeSchedulerConfiguration.from_dict(cfg.to_dict())
        assert again.extra_plugins == ("MyPlugin",)
        again.validate()


class TestFeatureGates:
    """config/features.py: featuregate registry + scheduler consultation
    (kube_features.go:686 OpportunisticBatching, :891 AsyncAPICalls)."""

    def test_defaults_and_overrides(self):
        from kubernetes_tpu.config.features import default_gate
        g = default_gate()
        assert g.enabled("OpportunisticBatching")
        g.set("OpportunisticBatching", False)
        assert not g.enabled("OpportunisticBatching")

    def test_unknown_gate_rejected(self):
        from kubernetes_tpu.config.features import default_gate
        with pytest.raises(ValueError, match="unknown feature gate"):
            default_gate({"NoSuchGate": True})
        cfg = KubeSchedulerConfiguration(feature_gates={"Bogus": True})
        with pytest.raises(ValueError):
            cfg.validate()

    def test_gate_flips_uniform_fast_path(self, monkeypatch):
        """With OpportunisticBatching off, run_uniform must never be
        invoked — every drain takes the scan program."""
        import kubernetes_tpu.scheduler as sched_mod

        def boom(*a, **k):
            raise AssertionError("run_uniform called with gate off")

        api = APIServer()
        cfg = KubeSchedulerConfiguration(
            feature_gates={"OpportunisticBatching": False})
        sched = Scheduler(api, batch_size=64, config=cfg)
        monkeypatch.setattr(sched_mod, "run_uniform", boom)
        for i in range(3):
            api.create_node(make_node(f"n{i}").capacity(
                {"cpu": 32, "memory": "64Gi", "pods": 110}).obj())
        for i in range(40):   # >= UNIFORM_RUN_MIN, would trigger top-L
            api.create_pod(make_pod(f"p{i}").req(
                {"cpu": "100m", "memory": "64Mi"}).obj())
        assert sched.schedule_pending() == 40

    def test_async_api_calls_gate_sets_pipeline_depth(self):
        api = APIServer()
        cfg = KubeSchedulerConfiguration(
            feature_gates={"SchedulerAsyncAPICalls": False})
        sched = Scheduler(api, config=cfg)
        assert sched.max_inflight_drains == 0
        assert Scheduler(APIServer()).max_inflight_drains == 8


class TestPluginArgs:
    """Typed per-plugin args (types_pluginargs.go analog)."""

    def test_most_allocated_via_plugin_args_packs(self):
        cfg = KubeSchedulerConfiguration.from_dict({"profiles": [{
            "pluginArgs": {"NodeResourcesFit": {
                "scoringStrategy": "MostAllocated"}},
        }]})
        cfg.validate()
        api = APIServer()
        sched = Scheduler(api, batch_size=64, config=cfg)
        assert (next(iter(sched.profiles.values()))
                .score_config.strategy == "MostAllocated")
        for i in range(2):
            api.create_node(make_node(f"n{i}").capacity(
                {"cpu": 8, "memory": "16Gi", "pods": 110}).obj())
        # pre-load n1; MostAllocated must PACK subsequent pods onto it
        seed = make_pod("seed").req({"cpu": "2", "memory": "2Gi"}).obj()
        api.create_pod(seed)
        api.bind(seed, "n1")
        for i in range(3):
            api.create_pod(make_pod(f"p{i}").req(
                {"cpu": "1", "memory": "1Gi"}).obj())
        assert sched.schedule_pending() == 3
        assert all(api.pods[f"default/p{i}"].spec.node_name == "n1"
                   for i in range(3))

    def test_unknown_arg_field_rejected(self):
        cfg = KubeSchedulerConfiguration.from_dict({"profiles": [{
            "pluginArgs": {"NodeResourcesFit": {"scoringStratgy": "x"}}}]})
        with pytest.raises(ValueError, match="unknown NodeResourcesFitArgs"):
            cfg.validate()

    def test_args_for_unknown_plugin_rejected(self):
        cfg = KubeSchedulerConfiguration.from_dict({"profiles": [{
            "pluginArgs": {"NoSuchPlugin": {}}}]})
        with pytest.raises(ValueError, match="unknown plugin"):
            cfg.validate()

    def test_gang_timeout_arg_applied(self):
        from kubernetes_tpu.config import build_profiles
        cfg = KubeSchedulerConfiguration.from_dict({"profiles": [{
            "pluginArgs": {"GangScheduling": {
                "schedulingTimeoutSeconds": 42}}}]})
        cfg.validate()
        profs = build_profiles(cfg, APIServer())
        gang = next(p for p in profs[0].framework.plugins
                    if p.name() == "GangScheduling")
        assert gang.scheduling_timeout_seconds == 42


class TestObservability:
    """Leveled logging + sampled plugin metrics + cache comparer
    (metrics.go:322, debugger.go:31-76)."""

    def _tiny_cluster(self):
        api = APIServer()
        sched = Scheduler(api, batch_size=16)
        api.create_node(make_node("n0").capacity(
            {"cpu": 32, "memory": "64Gi", "pods": 110}).obj())
        return api, sched

    def test_plugin_execution_duration_sampled_on_host_path(self):
        api, sched = self._tiny_cluster()
        sched.UNIFORM_RUN_MIN = 10**9
        # host path via extenders-free... force host: use schedule_one
        for i in range(12):
            api.create_pod(make_pod(f"p{i}").req(
                {"cpu": "100m", "memory": "64Mi"}).obj())
        for _ in range(12):
            sched.schedule_one()
        hist = sched.metrics.plugin_execution_duration
        # ~10% sampling over 12 attempts -> at least one Filter sample
        assert hist.count("NodeResourcesFit", "Filter", "SUCCESS") >= 1

    def test_plugin_evaluation_total_counts_device_batches(self):
        api, sched = self._tiny_cluster()
        for i in range(8):
            api.create_pod(make_pod(f"p{i}").req(
                {"cpu": "100m", "memory": "64Mi"}).obj())
        assert sched.schedule_pending() == 8
        assert sched.metrics.plugin_evaluation_total.value(
            "NodeResourcesFit", "Filter", "default-scheduler") == 8

    def test_cache_comparer_clean_and_divergent(self):
        api, sched = self._tiny_cluster()
        api.create_pod(make_pod("p0").req(
            {"cpu": "100m", "memory": "64Mi"}).obj())
        assert sched.schedule_pending() == 1
        assert sched.debugger.compare() == []
        # inject divergence: drop the pod from the cache behind the
        # scheduler's back
        sched.cache.pod_states.pop("default/p0")
        sched.cache.assumed_pods.discard("default/p0")
        problems = sched.debugger.compare()
        assert any("not in cache" in p for p in problems)
        assert sched.metrics.cache_divergence.value("host_vs_apiserver") >= 1

    def test_debug_compare_both_layers(self):
        api, sched = self._tiny_cluster()
        api.create_pod(make_pod("p0").req(
            {"cpu": "100m", "memory": "64Mi"}).obj())
        sched.schedule_pending()
        out = sched.debug_compare()
        assert out == {"device_vs_host": [], "host_vs_apiserver": []}

    def test_klog_levels(self, capsys):
        from kubernetes_tpu.utils.logging import klog, set_verbosity, verbosity
        old = verbosity()
        try:
            set_verbosity(2)
            assert klog.v(2).enabled and not klog.v(5).enabled
            set_verbosity(5)
            assert klog.v(5).enabled
        finally:
            set_verbosity(old)
