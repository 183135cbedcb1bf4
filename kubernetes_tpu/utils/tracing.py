"""Tracing: span trees, phase self time, profiler annotations.

The reference wires OpenTelemetry through component-base/tracing (spans
around the scheduling cycle, schedule_one.go) and logs slow cycles via
klog verbosity. This is the dependency-free analog, on the profiler's
clock:

- `Tracer.span(name)` context manager builds a per-cycle span tree with
  wall-clock durations and optional attributes, and enters
  `jax.profiler.TraceAnnotation(name)` for the span's extent: inside a
  `jax.profiler` session every span lands on the trace's host plane, on
  the same clock as the device's ops (outside a session the annotation is
  a no-op TraceMe, well under a microsecond). `Tracer.phase(name)` is a
  child span of the open cycle, or only the annotation between cycles,
  so the scheduler's loop phases never become cycles of their own.
- finished root spans whose duration exceeds `slow_threshold_s` are kept in
  `slow_cycles` (ring buffer) and handed to `on_slow` (default: stdlib
  logging at WARNING) with a per-child breakdown — the "why was this cycle
  slow" answer the reference gets from attempt-duration histograms plus
  trace sampling.
- `NOOP_TRACER` keeps the hot path branch-free when tracing is off: span()
  and phase() return a reusable null context and annotate nothing.
- finished span trees export as Chrome-trace / Perfetto JSON
  (`to_chrome_trace` / `export_chrome_trace`): monotonic timestamps, one
  complete ("X") event per span, attributes as args — load the file at
  chrome://tracing or ui.perfetto.dev. `keep_recent` retains the last K
  root spans regardless of duration so a bench run can export its whole
  drain history. Device time is not in this export: it is in the
  `jax.profiler` trace, beside the same spans.
- `jax_profiler_session(dir)` optionally brackets a workload with a
  jax.profiler trace (XLA/TPU-level view under the host spans), gated by
  the `profilerTraceDir` config knob.
- `PhaseTrack` is the stack of open loop phases the Scheduler pushes and
  pops (Scheduler._phase), readable from ANY thread — the sampling host
  profiler (perf/profiler.py) tags every sample with `current()`. It also
  charges each elapsed stretch to the phase on top of the stack, so each
  phase's seconds are net of the phases nested in it and all of them sum
  to the wall (`scheduler_phase_self_seconds_total`). Kept separate from
  Tracer so it works under NOOP_TRACER too.
- `GC_PAUSES` is a `gc.callbacks` hook: collector seconds per generation
  (`scheduler_gc_pause_seconds_total`) and a `gc` annotation from each
  collection's start to its stop, on the collecting thread.
"""

from __future__ import annotations

import gc
import json
import logging
import time
from collections import deque
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass, field
from typing import Callable, Optional

from jax.profiler import TraceAnnotation

logger = logging.getLogger("kubernetes_tpu.tracing")


@dataclass
class Span:
    name: str
    start: float = 0.0
    duration_s: float = 0.0
    attributes: dict = field(default_factory=dict)
    children: list["Span"] = field(default_factory=list)

    def set(self, **attrs) -> None:
        self.attributes.update(attrs)

    def breakdown(self, indent: int = 0) -> str:
        lines = [f"{'  ' * indent}{self.name}: {self.duration_s * 1e3:.1f}ms"
                 + (f" {self.attributes}" if self.attributes else "")]
        for c in self.children:
            lines.append(c.breakdown(indent + 1))
        return "\n".join(lines)

    def to_dict(self) -> dict:
        """Nested-dict form (the /debug/slowcycles serialization)."""
        return {"name": self.name,
                "duration_ms": round(self.duration_s * 1e3, 3),
                "attributes": dict(self.attributes),
                "children": [c.to_dict() for c in self.children]}

    def find(self, name: str) -> Optional["Span"]:
        if self.name == name:
            return self
        for c in self.children:
            hit = c.find(name)
            if hit is not None:
                return hit
        return None


class PhaseTrack:
    """Cross-thread-readable stack of open phase names, with self time.

    The owner (the host loop; one thread at a time) pushes and pops; the
    profiler thread only reads the top — CPython list append/pop/index are
    atomic under the GIL, so no lock is needed and a torn read is
    impossible. Every push and pop charges the time since the previous one
    to the phase that was on top (`sink.inc(name, by=seconds)`, a metrics
    Counter), so a phase's charge excludes the phases nested in it and the
    charges of one outermost phase sum to its wall. Time with the stack
    empty is charged to no phase."""

    __slots__ = ("_stack", "_sink", "_clock", "_mark")

    def __init__(self, sink=None,
                 clock: Callable[[], float] = time.perf_counter) -> None:
        self._stack: list = []
        self._sink = sink
        self._clock = clock
        self._mark = 0.0

    def _charge(self) -> None:
        now = self._clock()
        if self._stack and self._sink is not None:
            self._sink.inc(self._stack[-1], by=now - self._mark)
        self._mark = now

    def push(self, name: str) -> None:
        self._charge()
        self._stack.append(name)

    def pop(self) -> None:
        if self._stack:
            self._charge()
            self._stack.pop()

    def current(self) -> str:
        s = self._stack
        return s[-1] if s else ""

    def scope(self, name: str) -> "_PhaseScope":
        return _PhaseScope(self, name)


class _PhaseScope:
    """`with track.scope(name):` — a class, not a generator, since the
    scheduler opens one per deleted pod."""

    __slots__ = ("_track", "_name")

    def __init__(self, track: PhaseTrack, name: str) -> None:
        self._track = track
        self._name = name

    def __enter__(self) -> None:
        self._track.push(self._name)

    def __exit__(self, *exc) -> bool:
        self._track.pop()
        return False


class _NullSpan:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass


_NULL_SPAN = _NullSpan()


class Tracer:
    """Per-component tracer; single-threaded like the host loop it serves."""

    def __init__(self, slow_threshold_s: float = 1.0, keep: int = 32,
                 on_slow: Optional[Callable[[Span], None]] = None,
                 clock: Callable[[], float] = time.perf_counter,
                 keep_recent: int = 0):
        self.slow_threshold_s = slow_threshold_s
        self.clock = clock
        self.slow_cycles: deque[Span] = deque(maxlen=keep)
        # every finished ROOT span, slow or not (trace export); off at 0
        self.recent: deque[Span] = deque(maxlen=max(keep_recent, 1))
        self.keep_recent = keep_recent
        self.on_slow = on_slow or self._log_slow
        self._stack: list[Span] = []

    @contextmanager
    def span(self, name: str, **attributes):
        sp = Span(name=name, start=self.clock(),
                  attributes=dict(attributes))
        parent = self._stack[-1] if self._stack else None
        if parent is not None:
            parent.children.append(sp)
        self._stack.append(sp)
        try:
            with TraceAnnotation(name):
                yield sp
        finally:
            self._stack.pop()
            sp.duration_s = self.clock() - sp.start
            if parent is None:
                if self.keep_recent:
                    self.recent.append(sp)
                if sp.duration_s >= self.slow_threshold_s:
                    self.slow_cycles.append(sp)
                    self.on_slow(sp)

    @contextmanager
    def phase(self, name: str, **attributes):
        """A child span of the open cycle; between cycles only the profiler
        annotation (a phase never starts a cycle of its own)."""
        if self._stack:
            with self.span(name, **attributes) as sp:
                yield sp
        else:
            with TraceAnnotation(name):
                yield _NULL_SPAN

    def export_chrome_trace(self, path: str) -> int:
        """Write the retained root spans (recent if enabled, else the slow
        ring) as Chrome-trace JSON; returns the event count."""
        spans = list(self.recent if self.keep_recent else self.slow_cycles)
        trace = to_chrome_trace(spans)
        with open(path, "w") as f:
            json.dump(trace, f)
        return len(trace["traceEvents"])

    @staticmethod
    def _log_slow(sp: Span) -> None:
        logger.warning("slow scheduling cycle (%.0fms):\n%s",
                       sp.duration_s * 1e3, sp.breakdown())


class NoopTracer:
    slow_cycles: deque = deque()
    recent: deque = deque()
    keep_recent = 0

    def span(self, name: str, **attributes):
        return _NULL_SPAN

    def phase(self, name: str, **attributes):
        return _NULL_SPAN


NOOP_TRACER = NoopTracer()


class GCPauses:
    """`gc.callbacks` hook (module docstring). Process-global like the
    collector it observes; `install()` is idempotent. CPython collects
    under the interpreter lock and never nests collections, so one start
    mark suffices."""

    def __init__(self) -> None:
        self.seconds = [0.0, 0.0, 0.0]     # by generation
        self._t0 = 0.0
        self._ann: Optional[TraceAnnotation] = None

    def install(self) -> None:
        if self._on_gc not in gc.callbacks:
            gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._ann = TraceAnnotation("gc")
            self._ann.__enter__()
            self._t0 = time.perf_counter()
        elif self._ann is not None:     # not a collection open at install
            self.seconds[info["generation"]] += time.perf_counter() - self._t0
            self._ann.__exit__(None, None, None)
            self._ann = None


GC_PAUSES = GCPauses()


# ---------------------------------------------------------------------------
# Chrome-trace / Perfetto export


def _span_events(sp: Span, out: list, pid: int, tid: int) -> None:
    out.append({"ph": "X", "cat": "scheduler", "name": sp.name,
                "ts": sp.start * 1e6,            # µs, monotonic base
                "dur": max(sp.duration_s, 0.0) * 1e6,
                "pid": pid, "tid": tid,
                "args": {k: (v if isinstance(v, (int, float, bool, str))
                             else str(v))
                         for k, v in sp.attributes.items()}})
    for c in sp.children:
        _span_events(c, out, pid, tid)


def to_chrome_trace(spans: list[Span], process_name: str = "kube-scheduler-tpu"
                    ) -> dict:
    """Span trees → Chrome-trace JSON object (trace_event format, loadable
    at chrome://tracing / ui.perfetto.dev). Every span becomes one complete
    ("X") event; timestamps keep the tracer's monotonic base."""
    events: list[dict] = [
        {"ph": "M", "name": "process_name", "pid": 1, "tid": 1,
         "args": {"name": process_name}},
        {"ph": "M", "name": "thread_name", "pid": 1, "tid": 1,
         "args": {"name": "host-loop"}},
    ]
    for sp in spans:
        _span_events(sp, events, 1, 1)
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def export_chrome_trace(path: str, spans: list[Span]) -> int:
    trace = to_chrome_trace(spans)
    with open(path, "w") as f:
        json.dump(trace, f)
    return len(trace["traceEvents"])


def fleet_chrome_trace(members) -> dict:
    """Merge N instances' span histories onto ONE Chrome trace: each
    instance becomes its own process track (pid = shard index + 1, named
    after the instance) with its host-loop thread underneath. All tracers share the monotonic clock base (in-process
    fleet; the cross-process step will need a clock offset per scrape),
    so per-shard tracks line up timewise — a steal renders as the drain
    span ending on one track and the adopter's drain starting on the
    next. `members` is an iterable of (name, tracer) pairs; each
    tracer's retained root spans (recent if kept, else slow ring) are
    exported."""
    events: list[dict] = []
    for i, (name, tracer) in enumerate(members):
        pid = i + 1
        events.extend([
            {"ph": "M", "name": "process_name", "pid": pid, "tid": 1,
             "args": {"name": f"shard:{name}"}},
            {"ph": "M", "name": "thread_name", "pid": pid, "tid": 1,
             "args": {"name": "host-loop"}},
        ])
        keep_recent = getattr(tracer, "keep_recent", 0)
        spans = list(tracer.recent if keep_recent
                     else getattr(tracer, "slow_cycles", ()))
        for sp in spans:
            _span_events(sp, events, pid, 1)
    return {"traceEvents": events, "displayTimeUnit": "ms"}


@contextmanager
def jax_profiler_session(trace_dir: Optional[str]):
    """Bracket a workload with a jax.profiler trace when `trace_dir` is
    set (the config `profilerTraceDir` knob); a no-op otherwise, and any
    profiler failure (unsupported backend, busy session) degrades to the
    no-op instead of sinking the workload."""
    if not trace_dir:
        with nullcontext():
            yield
        return
    import jax
    started = False
    try:
        jax.profiler.start_trace(trace_dir)
        started = True
    except Exception as e:    # pragma: no cover - backend specific
        logger.warning("jax profiler session unavailable: %s", e)
    try:
        yield
    finally:
        if started:
            try:
                jax.profiler.stop_trace()
            except Exception as e:  # pragma: no cover - backend specific
                logger.warning("jax profiler stop failed: %s", e)
