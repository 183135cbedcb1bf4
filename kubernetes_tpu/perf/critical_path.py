"""Critical-path observatory: per-drain bottleneck verdicts (ISSUE 20).

The measurement rails (drain phases, kernel lanes, pipeline stage
counters, shard profile) are descriptive: they say how long each segment
took, but every "the mesh drain is host-bound" claim in ROADMAP items
1-2 was still derived by hand from three separate surfaces, and item 5's
autotuner has no cost table to search against. This module turns the
rails into VERDICTS:

- `attribute_drain` walks one drain's recorded segments — the host_build
  sub-phases stamped by `Scheduler._phase`, the per-kernel device lanes
  from the kernel observatory, the readback wait, the commit tail, and
  (under `StreamingDrainPipeline`) the stage workers' backpressure
  stalls — and emits the binding chain plus a dominant-bottleneck
  verdict over the CAUSES taxonomy, with per-cause seconds. The
  scheduler stamps the result on the drain's FlightRecord and mirrors
  it into `scheduler_critical_path_seconds{cause}` /
  `scheduler_bottleneck_drains_total{cause}`.
- `aggregate` folds many per-drain verdicts into the bench summary's
  `critical_path` block: a verdict histogram, total per-cause seconds,
  and the ceiling factor — the projected speedup if the dominant cause
  were free (`total / (total - dominant)`, the headroom formula README
  documents). bench.py multiplies it into a projected pods/s ceiling.
- `phase_shares` is the ONE implementation of the stage-share math that
  bench.py's `phase_pct`/`host_share` summary and the pipeline occupancy
  block previously computed independently (the ISSUE 20 bugfix): given
  {segment: seconds} and an optional wall denominator it returns the
  fractional shares plus the host share (host_build + commit over the
  cycle), so both surfaces agree on the same FlightRecorder window.
- `attribute_delta` explains a throughput delta between two aggregated
  blocks by the cause whose per-drain seconds moved most — the
  differential-attribution mode of tools/bench_compare.py.

Everything here is pure stdlib arithmetic over dicts the rails already
record: no jax, no locks, safe to import from metrics/ and tools/.
Gate: `CriticalPathObservatory` (Beta/on), owned by the constructing
Scheduler like the other observability gates.
"""

from __future__ import annotations

# The verdict taxonomy — the exact label set of the
# scheduler_critical_path_seconds / scheduler_bottleneck_drains_total
# families (the exposition lint asserts it). Order breaks ties: an
# earlier cause wins an exact-seconds tie, so a fully idle drain says
# "idle" only when nothing else claimed time.
#
#   host_build     columnar ingest, signature/plan compile, group seeding
#                  (the host_snapshot/tensorize/group_seed/cache children)
#   device_compute the device lanes' local compute share
#   device_comms   the collective/all-reduce share of a sharded dispatch
#                  (the lane profile's commsShare split)
#   commit         assume + bind enqueue + failure handling
#   backpressure   streaming-pipeline stall seconds (a depth cap held the
#                  drain back); structurally zero in lock-step operation
#   idle           host blocked on the device readback with no overlap
#                  (readback) — the seconds the pipeline exists to
#                  reclaim
CAUSES = ("host_build", "device_compute", "device_comms", "commit",
          "backpressure", "idle")

# host_build's named children (Scheduler._phase): part of the chain
# rendering, never separate causes — host_build already covers them
HOST_SUBPHASES = ("host_snapshot", "host_tensorize", "host_group_seed",
                  "host_cache")


def attribute_drain(phases: dict, kernels: dict = None,
                    comms_share: float = 0.0,
                    backpressure_s: float = 0.0) -> dict:
    """One drain's segments → {"verdict", "causes", "chain"}.

    `phases` is the FlightRecord/_PendingDrain phase dict (host_build,
    device_dispatch, readback, commit + the host sub-phases);
    `kernels` the per-kernel device-lane seconds; `comms_share` the
    sharded-lane profile's collective share of the device window (0.0
    unsharded); `backpressure_s` the pipeline stall seconds attributed
    to this drain (0.0 in lock-step operation — a lock-step drain can
    never carry a backpressure verdict).
    """
    phases = phases or {}
    device_s = max(float(phases.get("device_dispatch", 0.0)), 0.0)
    share = min(max(float(comms_share), 0.0), 1.0)
    causes = {
        "host_build": max(float(phases.get("host_build", 0.0)), 0.0),
        "device_compute": device_s * (1.0 - share),
        "device_comms": device_s * share,
        "commit": max(float(phases.get("commit", 0.0)), 0.0),
        "backpressure": max(float(backpressure_s), 0.0),
        "idle": max(float(phases.get("readback", 0.0)), 0.0),
    }
    verdict = max(CAUSES, key=lambda c: (causes[c], -CAUSES.index(c)))
    if causes[verdict] <= 0.0:
        verdict = "idle"             # an empty record binds on nothing
    return {"verdict": verdict,
            "causes": {c: round(s, 6) for c, s in causes.items()},
            "chain": _chain(phases, kernels or {}, causes)}


def _chain(phases: dict, kernels: dict, causes: dict) -> list:
    """The binding chain: the drain's segments in execution order, each
    tagged with the cause that claims it. Zero segments are dropped —
    the chain is what a human reads at /debug/criticalpath."""
    chain: list[dict] = []

    def seg(span: str, seconds: float, cause: str) -> None:
        if seconds > 0.0:
            chain.append({"span": span, "seconds": round(seconds, 6),
                          "cause": cause})

    named = 0.0
    for sub in HOST_SUBPHASES:
        s = float(phases.get(sub, 0.0))
        named += max(s, 0.0)
        seg(sub, s, "host_build")
    seg("host_other", float(phases.get("host_build", 0.0)) - named,
        "host_build")
    lane_total = 0.0
    dev_cause = ("device_comms"
                 if causes.get("device_comms", 0.0)
                 > causes.get("device_compute", 0.0) else "device_compute")
    for kernel in sorted(kernels):
        s = float(kernels[kernel])
        lane_total += max(s, 0.0)
        seg(f"kernel:{kernel}", s, dev_cause)
    seg("device_other",
        float(phases.get("device_dispatch", 0.0)) - lane_total, dev_cause)
    seg("backpressure_stall", causes.get("backpressure", 0.0),
        "backpressure")
    seg("readback", float(phases.get("readback", 0.0)), "idle")
    seg("commit", float(phases.get("commit", 0.0)), "commit")
    return chain


def aggregate(verdicts) -> dict:
    """Fold per-drain `attribute_drain` results (or their FlightRecord
    `criticalPath` dict form) into the bench/debug summary block:
    verdict histogram, per-cause seconds, the modal verdict, and the
    ceiling factor — measured_rate * ceiling_factor is the projected
    rate if the dominant cause were free."""
    hist: dict[str, int] = {}
    causes = {c: 0.0 for c in CAUSES}
    drains = 0
    for v in verdicts:
        if not isinstance(v, dict) or not v.get("verdict"):
            continue
        drains += 1
        hist[v["verdict"]] = hist.get(v["verdict"], 0) + 1
        for c, s in (v.get("causes") or {}).items():
            if c in causes:
                causes[c] += float(s)
    out = {"drains": drains,
           "verdicts": dict(sorted(hist.items())),
           "causes": {c: round(s, 6) for c, s in causes.items()}}
    if drains:
        # the dominant cause of the WINDOW is the one with the most
        # seconds, not the modal per-drain verdict — a long tail of
        # small drains must not outvote one giant commit stall
        dominant = max(CAUSES, key=lambda c: (causes[c], -CAUSES.index(c)))
        out["dominant"] = dominant
        out["ceiling_factor"] = round(
            ceiling_factor(causes, dominant), 4)
    return out


def ceiling_factor(causes: dict, dominant: str) -> float:
    """Headroom projection: with the dominant cause's seconds removed
    from the cycle, throughput scales by total / (total - dominant).
    1.0 when nothing was measured; capped at 100x — a cause that IS the
    whole cycle projects "infinite" speedup, which is noise, not
    headroom."""
    total = sum(max(float(s), 0.0) for s in causes.values())
    freed = max(float(causes.get(dominant, 0.0)), 0.0)
    rest = total - freed
    if total <= 0.0:
        return 1.0
    if rest <= total * 0.01:
        return 100.0
    return total / rest


def phase_shares(parts: dict, wall: float = None) -> dict:
    """THE stage-share math (ISSUE 20 bugfix): bench.py's summary
    `phase_pct`/`host_share` and the pipeline occupancy block previously
    computed shares independently; both now call here. `parts` maps
    segment → seconds; `wall` is the denominator (None = the segments'
    own sum — a lock-step cycle; a pipeline window passes its wall so
    overlapping stages can sum past 1.0). Returns the rounded fractional
    shares, the total, the occupancy (total/wall) and the host share
    (host_build + commit over the denominator — the Python-claims-the-
    cycle number bench_compare gates)."""
    total = sum(max(float(v), 0.0) for v in parts.values())
    base = float(wall) if wall is not None and wall > 0 else total
    shares = {k: (round(max(float(v), 0.0) / base, 4) if base > 0 else 0.0)
              for k, v in parts.items()}
    host = (max(float(parts.get("host_build", 0.0)), 0.0)
            + max(float(parts.get("commit", 0.0)), 0.0))
    return {"total": round(total, 6),
            "shares": shares,
            "occupancy": round(total / base, 4) if base > 0 else 0.0,
            "host_share": round(host / base, 4) if base > 0 else 0.0}


def attribute_delta(base: dict, new: dict) -> dict:
    """Differential attribution (tools/bench_compare.py --attribute):
    explain a throughput delta between two aggregated `critical_path`
    blocks by the cause whose PER-DRAIN seconds moved most. Normalizing
    by drain count makes unequal windows comparable — 2x the drains is
    2x every cause, not a regression. Returns {} when either side lacks
    verdicts; otherwise the moved cause, its per-drain seconds on both
    sides, the growth ratio, and the full per-cause delta table."""
    b_n = int((base or {}).get("drains") or 0)
    n_n = int((new or {}).get("drains") or 0)
    if b_n <= 0 or n_n <= 0:
        return {}
    b_c = (base or {}).get("causes") or {}
    n_c = (new or {}).get("causes") or {}
    deltas = {}
    for c in CAUSES:
        b_s = max(float(b_c.get(c, 0.0)), 0.0) / b_n
        n_s = max(float(n_c.get(c, 0.0)), 0.0) / n_n
        deltas[c] = {"base_s": round(b_s, 6), "new_s": round(n_s, 6),
                     "delta_s": round(n_s - b_s, 6),
                     "ratio": round(n_s / b_s, 4) if b_s > 0 else None}
    moved = max(CAUSES,
                key=lambda c: (abs(deltas[c]["delta_s"]),
                               -CAUSES.index(c)))
    return {"cause": moved, **deltas[moved], "deltas": deltas}
