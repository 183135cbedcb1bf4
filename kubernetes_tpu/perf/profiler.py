"""Continuous sampling host profiler with drain-phase attribution.

The ROADMAP's remaining throughput gap is host-side Python
(pod ingest/commit ≈60% of a SchedulingBasic cycle), and the
`drain_phase` series only says WHICH coarse phase burns the time — not
which functions inside it. This module closes that gap the way
production continuous profilers (pprof, py-spy, Parca) do, without a
native agent:

- a background daemon thread samples the host-loop thread's Python stack
  via `sys._current_frames()` at `hz` (config knob `hostProfilerHz`,
  default ~200Hz; feature gate `ContinuousHostProfiling`);
- every sample is tagged with the currently-open drain phase — the
  innermost `utils/tracing.py` span name, read from the scheduler's
  `PhaseTrack` (host_snapshot / host_tensorize / host_group_seed /
  host_cache / device / commit, "other" outside a drain) — and with the
  dispatching drain's pod-signature cardinality bucket, so host cost is
  attributable per phase AND per signature-cardinality regime;
- samples aggregate into per-second buckets (a bounded ring), so
  `/debug/hostprofile?seconds=N` can render any trailing window without
  keeping raw samples;
- exports: collapsed-stack text (flamegraph.pl / speedscope both ingest
  it), speedscope JSON, a self/cumulative frame table, per-phase sample
  shares (cross-checkable against the `drain_phase` wall-clock shares),
  and top-N hottest frames (attached to slow FlightRecorder drains).

Overhead: one `sys._current_frames()` walk per tick (~10-30µs for a
50-frame stack) — ≈0.5% of one core at 200Hz, which is what keeps the
profiler ALWAYS-ON rather than a debugging session. The thread holds
only a weakref to its owner: when the Scheduler is collected, the
sampler exits on its next tick.
"""

from __future__ import annotations

import os
import sys
import threading
import time as _time
import weakref
from collections import deque
from typing import Callable, Optional


def _pow2_bucket(n: int) -> int:
    """Signature-cardinality bucket: next power of two ≥ n (0 stays 0)."""
    return 1 << (int(n) - 1).bit_length() if n > 0 else 0


class ProfileAggregate:
    """One window's aggregated samples: (phase, sig_bucket, stack) → count.

    Stacks are tuples of frame strings, root-first (the collapsed-stack
    orientation). Merging two aggregates is a dict add — that is what
    makes the per-second ring cheap to query for any trailing window."""

    __slots__ = ("counts", "total")

    def __init__(self) -> None:
        self.counts: dict[tuple, int] = {}
        self.total = 0

    def add(self, key: tuple, n: int = 1) -> None:
        self.counts[key] = self.counts.get(key, 0) + n
        self.total += n

    def merge(self, other: "ProfileAggregate") -> None:
        for key, n in other.counts.items():
            self.add(key, n)


class HostProfiler:
    """Sampling profiler bound to one host-loop thread (see module doc)."""

    def __init__(self, hz: float = 200.0,
                 phase_fn: Optional[Callable[[], str]] = None,
                 bucket_fn: Optional[Callable[[], int]] = None,
                 owner: Optional[object] = None,
                 max_depth: int = 128,
                 window_s: int = 900,
                 thread_cpu=None):
        self.hz = float(hz)
        # the sampler's own CPU seconds land in this Counter
        # (scheduler_thread_cpu_seconds_total{thread="host_profiler"}); a
        # counter, not the metrics object, whose callbacks would hold the
        # owner alive and keep this thread running
        self.thread_cpu = thread_cpu
        self.phase_fn = phase_fn
        self.bucket_fn = bucket_fn
        self._owner_ref = weakref.ref(owner) if owner is not None else None
        self.max_depth = max_depth
        self._lock = threading.Lock()
        # per-second aggregation ring: (epoch_second, ProfileAggregate) —
        # written by the sampler thread, read by the debug HTTP thread
        self._ring: deque[tuple[int, ProfileAggregate]] = deque(
            maxlen=max(int(window_s), 1))               # guarded_by: _lock
        # code object → label memo: sampler-thread-private (built during
        # the stack walk, before the lock is taken)
        self._frame_names: dict[object, str] = {}
        self.target_tid: Optional[int] = None
        self.sample_count = 0      # guarded_by: _lock
        self.dropped = 0           # sampler-thread-private miss counter
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # drains slower than this get their top frames pinned onto the
        # flight-recorder entry (Scheduler reads the attribute)
        self.slow_drain_s = 0.25

    # -- lifecycle ------------------------------------------------------------

    def ensure_running(self) -> None:
        """Start (or retarget) the sampler from the host-loop thread; the
        Scheduler calls this at the top of every schedule entry point, so
        the profiler always follows whichever thread drives the loop."""
        tid = threading.get_ident()
        if self.target_tid != tid:
            self.target_tid = tid
        if self._thread is None and not self._stop.is_set():
            self._thread = threading.Thread(
                target=self._run, name="ktpu-host-profiler", daemon=True)
            self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        t = self._thread
        if t is not None and t.is_alive() \
                and t is not threading.current_thread():
            t.join(timeout=2.0)
        self._thread = None

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def _run(self) -> None:
        interval = 1.0 / max(self.hz, 1e-3)
        while not self._stop.wait(interval):
            if self._owner_ref is not None and self._owner_ref() is None:
                break   # owner collected: nothing left to profile
            cpu0 = _time.thread_time()
            try:
                self.sample_once()
            except Exception:   # pragma: no cover - sampling must not die
                self.dropped += 1
            if self.thread_cpu is not None:
                self.thread_cpu.inc("host_profiler",
                                    by=_time.thread_time() - cpu0)

    # -- sampling -------------------------------------------------------------

    def _frame_label(self, code) -> str:
        label = self._frame_names.get(code)
        if label is None:
            name = getattr(code, "co_qualname", code.co_name)
            label = f"{os.path.basename(code.co_filename)}:{name}"
            self._frame_names[code] = label
        return label

    def sample_once(self, frame=None) -> bool:
        """Take one sample of the target thread (or of an explicitly
        injected `frame`, the deterministic test hook). Returns True when
        a sample was recorded."""
        if frame is None:
            if self.target_tid is None:
                return False
            frame = sys._current_frames().get(self.target_tid)
            if frame is None:
                self.dropped += 1
                return False
        stack = []
        depth = 0
        while frame is not None and depth < self.max_depth:
            stack.append(self._frame_label(frame.f_code))
            frame = frame.f_back
            depth += 1
        stack.reverse()   # root-first
        phase = (self.phase_fn() if self.phase_fn is not None else "") \
            or "other"
        bucket = (_pow2_bucket(self.bucket_fn())
                  if self.bucket_fn is not None else 0)
        key = (phase, bucket, tuple(stack))
        sec = int(_time.time())
        with self._lock:
            if self._ring and self._ring[-1][0] == sec:
                agg = self._ring[-1][1]
            else:
                agg = ProfileAggregate()
                self._ring.append((sec, agg))
            agg.add(key)
            self.sample_count += 1
        return True

    # -- querying -------------------------------------------------------------

    def aggregate(self, seconds: Optional[float] = None) -> ProfileAggregate:
        """Merged aggregate of the trailing `seconds` window (None = the
        whole retained ring)."""
        cutoff = None if seconds is None else int(_time.time() - seconds)
        out = ProfileAggregate()
        with self._lock:
            for sec, agg in self._ring:
                if cutoff is None or sec >= cutoff:
                    out.merge(agg)
        return out

    def phase_shares(self, seconds: Optional[float] = None) -> dict:
        """phase → fraction of samples; the profiler-side number the
        `drain_phase` wall-clock shares must agree with."""
        agg = self.aggregate(seconds)
        if not agg.total:
            return {}
        by_phase: dict[str, int] = {}
        for (phase, _bucket, _stack), n in agg.counts.items():
            by_phase[phase] = by_phase.get(phase, 0) + n
        return {p: n / agg.total for p, n in sorted(by_phase.items())}

    def frame_table(self, seconds: Optional[float] = None,
                    phase: Optional[str] = None) -> list[dict]:
        """Self/cumulative sample counts per frame, hottest-self first."""
        agg = self.aggregate(seconds)
        self_c: dict[str, int] = {}
        cum_c: dict[str, int] = {}
        for (p, _bucket, stack), n in agg.counts.items():
            if phase is not None and p != phase:
                continue
            if not stack:
                continue
            self_c[stack[-1]] = self_c.get(stack[-1], 0) + n
            for f in set(stack):    # cumulative counts each frame once
                cum_c[f] = cum_c.get(f, 0) + n
        return [{"frame": f, "self": s, "cum": cum_c[f]}
                for f, s in sorted(self_c.items(),
                                   key=lambda kv: (-kv[1], kv[0]))]

    def top_frames(self, n: int = 5, seconds: Optional[float] = None,
                   phase: Optional[str] = None) -> list[str]:
        """["frame self_count/total" ...] — the FlightRecorder / bench
        `host_top_frames` form."""
        table = self.frame_table(seconds, phase=phase)
        total = sum(row["self"] for row in table) or 1
        return [f"{row['frame']} {row['self']}/{total}"
                for row in table[:n]]

    # -- export ---------------------------------------------------------------

    def collapsed(self, seconds: Optional[float] = None,
                  tag_phase: bool = True) -> str:
        """flamegraph.pl collapsed-stack format, one line per distinct
        stack: `phase;frame;frame count`. The phase is the ROOT frame so
        the flamegraph's first tier is the drain-phase split."""
        agg = self.aggregate(seconds)
        lines = []
        for (phase, bucket, stack), n in sorted(agg.counts.items()):
            frames = list(stack)
            if tag_phase:
                tag = f"{phase}" + (f"[sigs≤{bucket}]" if bucket else "")
                frames = [tag] + frames
            lines.append(";".join(frames) + f" {n}")
        return "\n".join(lines) + ("\n" if lines else "")

    def speedscope(self, seconds: Optional[float] = None,
                   name: str = "ktpu-host-profile") -> dict:
        """speedscope JSON (sampled evented profile) — load the payload at
        https://www.speedscope.app. Sample weights are whole ticks."""
        agg = self.aggregate(seconds)
        frames: list[dict] = []
        index: dict[str, int] = {}
        samples: list[list[int]] = []
        weights: list[float] = []
        for (phase, bucket, stack), n in sorted(agg.counts.items()):
            tag = f"{phase}" + (f"[sigs≤{bucket}]" if bucket else "")
            ids = []
            for f in (tag, *stack):
                i = index.get(f)
                if i is None:
                    i = index[f] = len(frames)
                    frames.append({"name": f})
                ids.append(i)
            samples.append(ids)
            weights.append(float(n))
        total = sum(weights)
        return {
            "$schema": "https://www.speedscope.app/file-format-schema.json",
            "shared": {"frames": frames},
            "profiles": [{
                "type": "sampled", "name": name, "unit": "none",
                "startValue": 0, "endValue": total,
                "samples": samples, "weights": weights,
            }],
            "exporter": "kubernetes_tpu.perf.profiler",
            "name": name,
        }

    def write_collapsed(self, path: str,
                        seconds: Optional[float] = None) -> int:
        """Write the collapsed profile; returns distinct-stack count."""
        text = self.collapsed(seconds)
        with open(path, "w") as f:
            f.write(text)
        return len(text.splitlines())
