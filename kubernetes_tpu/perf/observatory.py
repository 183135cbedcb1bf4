"""Kernel observatory: per-dispatch device-time attribution.

`perf/ledger.py` counts compiles and transfer bytes, but until ISSUE 14
nobody recorded how long each of the thirteen JIT kernels actually RUNS
per plan/shape variant — so the `device` phase span was a black box,
ROADMAP item 1's sharded-mesh gap could not be decomposed into
compute-vs-comms-vs-dispatch, and item 6's autotuner had no measurement
substrate to read. This module is that substrate:

- `CompileLedger.measured_call` (which already intercepts every JIT
  entry) reports each dispatch here via `on_call`: kernel name, wall
  seconds, whether the call compiled, and the call's args — from which a
  cheap shape signature is derived (array shapes, NamedTuple field
  shapes, static ints like the uniform L/K/J). Warm dispatch walls feed
  bounded streaming histograms keyed `(kernel, shape-sig)`; compiling
  calls stay out of the run histograms (their wall is trace+compile —
  the split the ledger's `runSeconds` bugfix records).
- a per-drain dispatch capture (`begin_drain`/`end_drain`, thread
  local so the standby scheduler and audit worker don't interleave):
  the scheduler brackets its `device_dispatch` phase with it and stamps
  the per-kernel seconds into the FlightRecorder. These are dispatch
  walls (the enqueue), not device time: device time is in the
  `jax.profiler` trace, where `measured_call`'s `dispatch:<kernel>`
  annotations name the device ops that follow each dispatch.
- the sharded-lane profile (parallel/sharding.py `profile_shard_lanes`)
  parks its latest result here; /debug/kernels and the
  `scheduler_shard_*` metric families read it back.

The observatory is PROCESS-GLOBAL (`GLOBAL`) for the same reason the
ledger is: the jit caches it observes are process-global. The
`KernelObservatory` feature gate (Beta/on) of the most recently
constructed Scheduler wins, mirroring the SanitizerRails pattern.
Memory is bounded: fixed log-spaced histogram buckets, at most
`MAX_PLAN_KEYS` per-plan histograms per kernel (overflow folds into
`~other`).
"""

from __future__ import annotations

import threading

from .costmodel import CostModel, classify, modeled_seconds
from .ledger import device_entries

# log2-spaced bucket edges, 1µs .. ~67s: edge[i] = 1e-6 * 2**i. A
# dispatch landing beyond the last edge folds into the final bucket —
# bounded memory, and nothing a scheduler drain does should take longer.
_EDGES = tuple(1e-6 * (2.0 ** i) for i in range(27))

# distinct per-plan/shape histograms kept per kernel; the tail folds
# into "~other" so shape churn can't grow the observatory unboundedly
MAX_PLAN_KEYS = 32
_OVERFLOW_KEY = "~other"


def _quantile(counts, total: int, q: float) -> float:
    """q-quantile in seconds from bucket counts (geometric bucket
    midpoint — the log2 lattice makes that exact to within ~√2)."""
    if total <= 0:
        return 0.0
    rank = q * total
    seen = 0
    for i, c in enumerate(counts):
        seen += c
        if seen >= rank and c:
            lo = _EDGES[i]
            hi = _EDGES[i + 1] if i + 1 < len(_EDGES) else _EDGES[-1] * 2.0
            return (lo * hi) ** 0.5
    return _EDGES[-1]


class StreamingHist:
    """Bounded streaming histogram over the fixed log2 second lattice."""

    __slots__ = ("counts", "count", "sum", "max")

    def __init__(self) -> None:
        self.counts = [0] * len(_EDGES)
        self.count = 0
        self.sum = 0.0
        self.max = 0.0

    def observe(self, seconds: float) -> None:
        i = 0
        # linear scan beats bisect here: dispatches cluster in the
        # 0.1-10ms decades, ~12 comparisons
        while i + 1 < len(_EDGES) and seconds >= _EDGES[i + 1]:
            i += 1
        self.counts[i] += 1
        self.count += 1
        self.sum += seconds
        if seconds > self.max:
            self.max = seconds

    def quantile(self, q: float) -> float:
        return _quantile(self.counts, self.count, q)

    def to_dict(self) -> dict:
        return {
            "count": self.count,
            "seconds": round(self.sum, 6),
            "p50_ms": round(self.quantile(0.50) * 1e3, 4),
            "p90_ms": round(self.quantile(0.90) * 1e3, 4),
            "p99_ms": round(self.quantile(0.99) * 1e3, 4),
            "max_ms": round(self.max * 1e3, 4),
        }


class _KernelStats:
    """One kernel's run-time profile: the merged histogram plus the
    bounded per-plan/shape breakdown."""

    __slots__ = ("hist", "plans", "dispatches", "compile_calls")

    def __init__(self) -> None:
        self.hist = StreamingHist()
        self.plans: dict = {}
        self.dispatches = 0      # every call, compiling or warm
        self.compile_calls = 0   # calls excluded from the run histogram

    def plan_hist(self, key) -> StreamingHist:
        h = self.plans.get(key)
        if h is None:
            if len(self.plans) >= MAX_PLAN_KEYS:
                key = _OVERFLOW_KEY
                h = self.plans.get(key)
                if h is None:
                    h = self.plans[key] = StreamingHist()
                return h
            h = self.plans[key] = StreamingHist()
        return h


def _shape_key(args) -> tuple:
    """Cheap, hashable shape signature of a dispatch's positional args:
    array shapes, one level of NamedTuple field shapes, and static ints
    (the uniform L/K/J, the gang need). Static config NamedTuples
    contribute an empty tuple; meshes and floats are ignored — they
    never change a kernel's executable without a shape changing too."""
    parts = []
    for a in args:
        sh = getattr(a, "shape", None)
        if sh is not None:
            parts.append(tuple(sh))
            continue
        if hasattr(a, "_fields"):
            parts.append(tuple(
                tuple(s) for s in (getattr(f, "shape", None) for f in a)
                if s is not None))
            continue
        if isinstance(a, (bool, int)):
            parts.append(int(a))
    return tuple(parts)


def _abstract(tree):
    """Donated (deleted) arrays → ShapeDtypeStructs: the buffer is gone by
    the time deferred costing lowers the call, and lowering needs only
    shapes, dtypes and shardings. Live arrays stay as they are, so the
    lowering reuses the trace the dispatch just made."""
    import jax
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)
        if isinstance(a, jax.Array) and a.is_deleted() else a, tree)


class KernelObservatory:
    """Process-wide per-dispatch run-time attribution (module docstring)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._enabled = True
        # pre-seed every device entry's kernel so /debug/kernels (and
        # the metric mirror) list them all before the first dispatch
        self.kernels: dict[str, _KernelStats] = {
            k: _KernelStats() for k in device_entries()}
        self._device_kind = ""
        self._shard_profile: dict = {}
        self._tl = threading.local()
        # device cost model (perf/costmodel.py, ISSUE 20): per-variant
        # flops/bytes rows, filled on compile events. Gated separately
        # (`CriticalPathObservatory`) so the run-time histograms keep
        # working with the cost model off.
        self.costs = CostModel()
        self._cost_enabled = True

    # -- gate -----------------------------------------------------------------

    def enable(self, on: bool = True) -> None:
        self._enabled = bool(on)

    def enable_cost_model(self, on: bool = True) -> None:
        """`CriticalPathObservatory` gate hook (scheduler ctor): the
        most recently constructed Scheduler wins, like `enable`."""
        self._cost_enabled = bool(on)

    @property
    def enabled(self) -> bool:
        return self._enabled

    @property
    def cost_model_enabled(self) -> bool:
        return self._enabled and self._cost_enabled

    # -- capture --------------------------------------------------------------

    def device_kind(self) -> str:
        """The device kind the cost model's roofline is keyed by."""
        if not self._device_kind:
            import jax
            self._device_kind = jax.devices()[0].device_kind
        return self._device_kind

    def on_call(self, kernel: str, start: float, seconds: float,
                compiled: bool, args: tuple) -> None:
        """One dispatch, reported by `CompileLedger.measured_call`.
        `start` is the perf_counter at call entry."""
        if not self._enabled:
            return
        key = _shape_key(args)
        with self._lock:
            stats = self.kernels.get(kernel)
            if stats is None:
                stats = self.kernels[kernel] = _KernelStats()
            stats.dispatches += 1
            if compiled:
                # trace+compile wall stays out of the run histograms —
                # the ledger's compile split records it
                stats.compile_calls += 1
            else:
                stats.hist.observe(seconds)
                stats.plan_hist(key).observe(seconds)
        events = getattr(self._tl, "events", None)
        if events is not None:
            events.append((kernel, start, seconds, compiled))

    def on_compile(self, kernel: str, fn, args: tuple, kw: dict) -> None:
        """A fresh compile, reported by `CompileLedger.measured_call`
        (cache-size delta > 0): cost the new variant. Once per plan key;
        tracing+lowering only — never a second XLA compile. Inside a
        drain's capture window the costing waits for `end_drain`: the
        trace+lower is host work, and timed inside the device_dispatch
        span it read as device time no kernel accounts for."""
        if not (self._enabled and self._cost_enabled):
            return
        pending = getattr(self._tl, "compiles", None)
        if pending is not None:
            pending.append((kernel, fn, _abstract(args), _abstract(kw)))
            return
        self.costs.record_compile(kernel, fn, args, kw)

    # -- per-drain dispatch capture ------------------------------------------

    def begin_drain(self) -> None:
        """Open the calling thread's dispatch capture window (the
        scheduler brackets its device_dispatch span with this)."""
        if self._enabled:
            self._tl.events = []
            self._tl.compiles = []

    def end_drain(self) -> list:
        """Close the capture window and cost the variants it compiled;
        returns [(kernel, start, seconds, compiled)] in dispatch order
        (empty when disabled)."""
        events = getattr(self._tl, "events", None)
        self._tl.events = None
        compiles = getattr(self._tl, "compiles", None) or ()
        self._tl.compiles = None
        for kernel, fn, args, kw in compiles:
            self.costs.record_compile(kernel, fn, args, kw)
        return events or []

    @staticmethod
    def lane_seconds(events: list) -> dict:
        """Per-kernel seconds of one drain's capture — the FlightRecord
        `kernels` stamp."""
        out: dict[str, float] = {}
        for kernel, _start, seconds, _compiled in events:
            out[kernel] = out.get(kernel, 0.0) + seconds
        return {k: round(v, 6) for k, v in out.items()}

    # -- shard lanes ----------------------------------------------------------

    def set_shard_profile(self, profile: dict) -> None:
        with self._lock:
            self._shard_profile = dict(profile or {})

    def shard_profile(self) -> dict:
        with self._lock:
            return dict(self._shard_profile)

    # -- reporting ------------------------------------------------------------

    def _cost_table(self, name: str, st: _KernelStats, device_kind: str,
                    comms_share: float) -> list:
        """One kernel's cost-model rows (perf/costmodel.py), joined with
        the plan histograms' measured warm p50 for the achieved-vs-
        modeled fraction. Caller holds self._lock (the cost model's own
        lock nests inside — no reverse path exists)."""
        rows = []
        for plan, row in sorted(self.costs.kernel_rows(name).items(),
                                key=repr):
            flops = float(row["flops"])
            nbytes = float(row["bytes"])
            h = st.plans.get(plan)
            measured = (h.quantile(0.50)
                        if h is not None and h.count else 0.0)
            model_s = modeled_seconds(flops, nbytes, device_kind)
            rows.append({
                "plan": str(plan),
                "flops": flops,
                "bytes": nbytes,
                # arithmetic intensity (flops/byte) — the roofline x-axis
                "ai": round(flops / nbytes, 4) if nbytes > 0 else 0.0,
                "modeledMs": round(model_s * 1e3, 4),
                "measuredP50Ms": round(measured * 1e3, 4),
                # modeled/measured: the fraction of the device's roofline
                # this variant achieves (0.0 until a warm call lands)
                "achievedFraction": (round(model_s / measured, 4)
                                     if measured > 0 and model_s > 0
                                     else 0.0),
                "bound": classify(flops, nbytes, device_kind,
                                  comms_share=comms_share),
                "source": row["source"],
            })
        return rows

    def cost_view(self) -> dict:
        """{kernel: [cost rows]} for every kernel with at least one
        costed variant — tools/kernel_sweep.py's roofline annotation and
        the /debug/kernels costModel field share this."""
        device_kind = self.device_kind()
        out = {}
        with self._lock:
            shard_comms = float(self._shard_profile.get("commsShare",
                                                        0.0) or 0.0)
            for name, st in self.kernels.items():
                comms = shard_comms if name.endswith("_sharded") else 0.0
                rows = self._cost_table(name, st, device_kind, comms)
                if rows:
                    out[name] = rows
        return out

    def snapshot(self, top_plans: int = 5) -> dict:
        """/debug/kernels payload: per-kernel run-time table (all
        thirteen pre-seeded entries, zeros before the first dispatch),
        the top-N per-plan variants by cumulative seconds, each
        variant's cost-model rows, and the latest sharded-lane
        profile."""
        device_kind = self.device_kind()
        with self._lock:
            shard = dict(self._shard_profile)
            shard_comms = float(shard.get("commsShare", 0.0) or 0.0)
            kernels = {}
            for name in sorted(self.kernels):
                st = self.kernels[name]
                top = sorted(st.plans.items(),
                             key=lambda kv: kv[1].sum, reverse=True)
                comms = shard_comms if name.endswith("_sharded") else 0.0
                kernels[name] = st.hist.to_dict() | {
                    "dispatches": st.dispatches,
                    "compileCalls": st.compile_calls,
                    "plans": {str(k): h.to_dict()
                              for k, h in top[:top_plans]},
                    "costModel": self._cost_table(name, st, device_kind,
                                                  comms),
                }
        return {"enabled": self._enabled, "deviceKind": device_kind,
                "costModelEnabled": self._cost_enabled,
                "kernels": kernels, "shardLanes": shard}

    def metrics_view(self) -> tuple:
        """({kernel: (dispatches, warm seconds)}, shard profile) — the
        scheduler_kernel_*/scheduler_shard_* mirror read at exposition
        time (metrics/__init__.py sync_observatory)."""
        with self._lock:
            return ({k: (st.dispatches, st.hist.sum)
                     for k, st in self.kernels.items()},
                    dict(self._shard_profile))

    def checkpoint(self) -> dict:
        """Opaque marker for `delta_since` (the bench harness brackets a
        run with it — the observatory is process-global, so absolute
        numbers mix warm-up and earlier workloads)."""
        with self._lock:
            return {k: (st.hist.count, st.hist.sum, tuple(st.hist.counts),
                        st.dispatches)
                    for k, st in self.kernels.items()}

    def delta_since(self, chk: dict) -> dict:
        """Per-kernel run-time stats accumulated since `chk`: counts and
        quantiles computed from the bucket-count difference."""
        out = {}
        with self._lock:
            for name, st in self.kernels.items():
                c0, s0, buckets0, d0 = chk.get(
                    name, (0, 0.0, (0,) * len(_EDGES), 0))
                count = st.hist.count - c0
                if count <= 0 and st.dispatches - d0 <= 0:
                    continue
                counts = [a - b for a, b in zip(st.hist.counts, buckets0)]
                out[name] = {
                    "calls": count,
                    "dispatches": st.dispatches - d0,
                    "seconds": round(st.hist.sum - s0, 6),
                    "p50_ms": round(
                        _quantile(counts, count, 0.50) * 1e3, 4),
                    "p99_ms": round(
                        _quantile(counts, count, 0.99) * 1e3, 4),
                }
        return out

    def reset(self) -> None:
        """Test hook, mirroring `CompileLedger.reset`."""
        with self._lock:
            self.kernels = {k: _KernelStats() for k in device_entries()}
            self._shard_profile = {}
        self.costs.reset()


GLOBAL = KernelObservatory()
