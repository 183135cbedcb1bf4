#!/usr/bin/env python
"""jaxsan driver: run the device-path linter + lock checker over the repo.

    python tools/check.py                 # lint, exit 0 iff clean
    python tools/check.py --fix-hints     # include fix-it hints
    python tools/check.py --list-waivers  # audit the waiver baseline
    python tools/check.py --json          # machine-readable findings

The entry points are the package's device entries: the functions
declared with `@device_entry` (perf/ledger.py), which also gives each
its kernel label, donated argument and cost coefficients, so no list
of entries is kept here.

Exit codes: 0 = no unwaived findings; 1 = findings; 2 = configuration
error (a declared device entry no longer reaches a jitted function —
the lint silently lost device-path coverage — or the streaming pipeline
grew a dispatch path that bypasses the device entries —
`pipeline_stages` — or a telemetry surface lost coverage: a registered
metric family without a pre-seeded sample / bench-archive TYPE line, or
a journey event/cause the /debug/pod renderer cannot annotate —
`obs_coverage`).

The same analysis runs in tier-1 via tests/test_jaxsan.py, so CI fails
on any unwaived finding; this CLI is the local/fix-up loop. Waiver
syntax (see kubernetes_tpu/analysis/findings.py):

    risky_line()  # jaxsan: waive[rule-id] why this is safe here
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def run_check(root: str = _REPO, package: str = "kubernetes_tpu",
              entry_points=None):
    """Returns (all findings, analyzer) — import surface for the pytest
    wrapper."""
    from kubernetes_tpu.analysis.findings import apply_waivers, parse_waivers
    from kubernetes_tpu.analysis.jaxsan import JaxsanAnalyzer
    from kubernetes_tpu.analysis.locks import LockChecker

    an = JaxsanAnalyzer(root, package=package,
                        entry_points=entry_points).load()
    findings = an.run()
    findings.extend(LockChecker(an.modules).run())
    waivers = {mi.path: parse_waivers(mi.source)
               for mi in an.modules.values()}
    apply_waivers(findings, waivers)
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings, an


# The streaming pipeline's only sanctioned routes to the device: the
# Scheduler seams, which run every kernel through
# CompileLedger.measured_call under the observatory capture installed by
# the scheduler. A stage thread reaching around them dispatches
# unmeasured work.
PIPELINE_DISPATCH_SEAMS = frozenset({
    "dispatch_once", "commit_ready", "schedule_pending",
    "flush_queues", "flush_backoff_completed",
})


def pipeline_stage_gaps(path: str = None, source: str = None) -> list:
    """ISSUE 18 `pipeline_stages` check: kubernetes_tpu/pipeline.py must
    reach the device ONLY through the Scheduler dispatch seams
    (PIPELINE_DISPATCH_SEAMS) — never by importing jax / the ops or
    parallel kernel modules, calling a declared JIT entry point, or
    invoking measured_call itself (attribution context lives in the
    Scheduler). Returns ["pipeline.py:LINE what (why)", ...]; empty =
    every dispatch path keeps measured_call/observatory attribution."""
    import ast

    from kubernetes_tpu.perf.ledger import device_entries

    if source is None:
        path = path or os.path.join(_REPO, "kubernetes_tpu", "pipeline.py")
        with open(path, encoding="utf-8") as f:
            source = f.read()
    fname = os.path.basename(path or "pipeline.py")
    tree = ast.parse(source, filename=fname)

    entry_names = {e.fn.__name__ for e in device_entries().values()}
    banned_abs = ("jax", "kubernetes_tpu.ops", "kubernetes_tpu.parallel")
    banned_rel = ("ops", "parallel")

    def _banned_module(mod: str, level: int) -> bool:
        if level:                      # relative: from .ops.program import ..
            return any(mod == b or mod.startswith(b + ".")
                       for b in banned_rel)
        return any(mod == b or mod.startswith(b + ".")
                   for b in banned_abs)

    gaps: list[str] = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if _banned_module(alias.name, 0):
                    gaps.append(
                        f"{fname}:{node.lineno} import {alias.name} "
                        "(kernel modules are off-limits to stage threads)")
        elif isinstance(node, ast.ImportFrom):
            if _banned_module(node.module or "", node.level):
                gaps.append(
                    f"{fname}:{node.lineno} from "
                    f"{'.' * node.level}{node.module or ''} import ... "
                    "(kernel modules are off-limits to stage threads)")
        elif isinstance(node, ast.Call):
            fn = node.func
            name = (fn.id if isinstance(fn, ast.Name)
                    else fn.attr if isinstance(fn, ast.Attribute) else None)
            if name in entry_names:
                gaps.append(
                    f"{fname}:{node.lineno} direct JIT entry call "
                    f"{name}() (bypasses the Scheduler dispatch seams "
                    f"{sorted(PIPELINE_DISPATCH_SEAMS)})")
            elif name == "measured_call":
                gaps.append(
                    f"{fname}:{node.lineno} raw measured_call() "
                    "(observatory attribution is installed by the "
                    "Scheduler, not the pipeline)")
    return gaps


def obs_coverage(prom_path: str = None) -> list:
    """ISSUE 19 `obs_coverage` check: the fleet-observatory surfaces must
    stay complete. (a) Every registered metric family is pre-seeded — a
    fresh SchedulerMetrics exposition yields at least one sample per
    family (histograms via their `_count` series; the callback gauge
    `scheduler_pending_pods` resolves only against a live scheduler and
    is exempt, mirroring the tier-1 exposition lint) — AND appears as a
    `# TYPE` family in bench_metrics.prom, so dashboards built on the
    bench archive never miss a series. (b) Every journey transition in
    obs/journey.py EVENTS and every requeue cause in CAUSES has a legend
    note in the /debug/pod stitched renderer (obs/stitch.py EVENT_NOTES
    / CAUSE_NOTES) — a new lifecycle event cannot land unrendered — and
    no stale note survives a removed code. Returns gap strings; empty =
    covered."""
    from kubernetes_tpu.metrics import SchedulerMetrics
    from kubernetes_tpu.obs.journey import CAUSES, EVENTS
    from kubernetes_tpu.obs.stitch import CAUSE_NOTES, EVENT_NOTES

    gaps: list[str] = []
    m = SchedulerMetrics()
    sampled = set()
    for line in m.exposition().splitlines():
        if line and not line.startswith("#"):
            sampled.add(line.partition("{")[0].partition(" ")[0])
    families = sorted(m.registry._metrics)
    for fam in families:
        if fam == "scheduler_pending_pods":
            continue               # callback gauge: no callback wired here
        if fam not in sampled and f"{fam}_count" not in sampled:
            gaps.append(f"{fam} (no pre-seeded sample in a fresh "
                        "exposition)")

    prom = prom_path or os.path.join(_REPO, "bench_metrics.prom")
    try:
        with open(prom, encoding="utf-8") as f:
            typed = {parts[2] for parts in
                     (ln.split() for ln in f if ln.startswith("# TYPE "))
                     if len(parts) >= 3}
    except OSError:
        typed = None
    if typed is None:
        gaps.append(f"{os.path.basename(prom)} unreadable (bench archive "
                    "missing — dashboards have no seed scrape)")
    else:
        for fam in families:
            if fam not in typed:
                gaps.append(f"{fam} (no TYPE family in "
                            f"{os.path.basename(prom)})")

    for ev in EVENTS:
        if ev not in EVENT_NOTES:
            gaps.append(f"journey event {ev!r} (no /debug/pod renderer "
                        "note in obs/stitch.py EVENT_NOTES)")
    for ev in EVENT_NOTES:
        if ev not in EVENTS:
            gaps.append(f"EVENT_NOTES entry {ev!r} (stale: not a journey "
                        "event)")
    for cause in CAUSES:
        if cause not in CAUSE_NOTES:
            gaps.append(f"requeue cause {cause!r} (no /debug/pod renderer "
                        "note in obs/stitch.py CAUSE_NOTES)")
    for cause in CAUSE_NOTES:
        if cause not in CAUSES:
            gaps.append(f"CAUSE_NOTES entry {cause!r} (stale: not a "
                        "requeue cause)")
    return gaps


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--root", default=_REPO)
    ap.add_argument("--package", default="kubernetes_tpu")
    ap.add_argument("--json", action="store_true", dest="as_json")
    ap.add_argument("--fix-hints", action="store_true",
                    help="print a fix-it hint under every finding")
    ap.add_argument("--list-waivers", action="store_true",
                    help="also print findings suppressed by inline waivers")
    ap.add_argument("--entries", action="append", default=None,
                    metavar="MOD:NAME,NAME",
                    help="override the JIT entry points (repeatable); "
                         "default: the package's @device_entry "
                         "declarations")
    args = ap.parse_args(argv)

    entry_points = None
    if args.entries:
        entry_points = {}
        for spec in args.entries:
            mod, _, names = spec.partition(":")
            entry_points[mod] = tuple(n for n in names.split(",") if n)

    findings, an = run_check(args.root, args.package, entry_points)
    live = [f for f in findings if not f.waived]
    waived = [f for f in findings if f.waived]
    # the coverage gates guard the REPO's own surfaces; an ad-hoc
    # --entries override lints someone else's tree
    pipe_gaps = [] if entry_points is not None else pipeline_stage_gaps()
    cov_gaps = [] if entry_points is not None else obs_coverage()

    if args.as_json:
        print(json.dumps({
            "findings": [f.to_dict() for f in live],
            "waived": [f.to_dict() for f in waived],
            "missingEntries": an.missing_entries,
            "pipelineStageGaps": pipe_gaps,
            "obsCoverageGaps": cov_gaps,
            "modules": len(an.modules),
            "tracedFunctions": sum(1 for fi in an.fns.values()
                                   if fi.traced),
        }, indent=2))
    else:
        for f in live:
            print(f.format(fix_hints=args.fix_hints))
        if args.list_waivers:
            for f in waived:
                print(f.format(fix_hints=False))
        print(f"jaxsan: {len(an.modules)} modules, "
              f"{sum(1 for fi in an.fns.values() if fi.traced)} traced "
              f"functions, {len(live)} findings "
              f"({len(waived)} waived)")

    if an.missing_entries:
        print("jaxsan: CONFIG ERROR — entries without jit coverage: "
              + ", ".join(an.missing_entries), file=sys.stderr)
        return 2
    if pipe_gaps:
        print("jaxsan: CONFIG ERROR — pipeline_stages: a dispatch path "
              "bypasses measured_call/observatory attribution: "
              + "; ".join(pipe_gaps), file=sys.stderr)
        return 2
    if cov_gaps:
        print("jaxsan: CONFIG ERROR — obs_coverage: a telemetry surface "
              "lost coverage (unseeded metric family, bench-archive "
              "family missing, or journey code without a renderer note): "
              + "; ".join(cov_gaps), file=sys.stderr)
        return 2
    return 1 if live else 0


if __name__ == "__main__":
    sys.exit(main())
