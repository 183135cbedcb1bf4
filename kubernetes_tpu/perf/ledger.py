"""Compile ledger: device/compile cost capture for the JIT entry points.

The scheduler's cold-start and tail latency are dominated by a handful of
XLA executables (scan buckets, uniform L/K/J variants, wave kernels) and
by host↔device transfers. The reference has nothing comparable to
instrument — its hot path is host Go — but every measurement-driven
placement system (Gavel, arXiv:2008.09213; topology-aware co-located LLM
scheduling, arXiv:2411.11560) keeps an attributable cost profile of its
own scheduler loop. This module is that profile's device half:

- every device entry (a public function that dispatches a jitted program)
  is declared once, where it is defined, with `@device_entry`. The
  declaration registers the entry's kernel label, donated argument and
  host cost estimate, and runs every dispatch through `measured_call`,
  which detects fresh compiles via the jitted function's `_cache_size()`
  delta and records per-kernel compile wall seconds, call counts,
  retraces (compiles beyond the first) and donated-buffer misses (a
  donated carry whose buffer survived the call — the donation was
  ignored, so the dispatch paid a full carry copy);
- host↔device transfer sites (`state/tensorize.py` node-array uploads,
  `ops/groups.py` group-tensor uploads, the signature-table upload and
  the drain readbacks) report byte counts via `note_h2d`, keyed by the
  drain phase that paid them;
- warm (non-compiling) call walls are recorded separately
  (`runCalls`/`runSeconds`), splitting the trace/compile cost out of
  `compileSeconds` (`compileOverheadSeconds`), and every dispatch is
  forwarded to the kernel observatory (perf/observatory.py) for
  per-plan run-time histograms and the per-drain device lane.

The ledger is PROCESS-GLOBAL (`GLOBAL`) because the jit caches it
observes are process-global; `SchedulerMetrics` mirrors it into
`scheduler_xla_compiles_total{kernel}`,
`scheduler_xla_compile_seconds{kernel}` and
`scheduler_h2d_bytes_total{phase}` at exposition time, and
`/debug/compileledger` serves the full snapshot (retraces and donation
misses included). A warm process re-running identical shapes must show a
ZERO compile delta — that invariant is the "no hidden retraces" test.
"""

from __future__ import annotations

import functools
import threading
import time as _time
from dataclasses import dataclass

import jax
from jax.profiler import TraceAnnotation


@dataclass
class KernelRecord:
    """Per-kernel compile/call accounting."""

    calls: int = 0
    compiles: int = 0            # fresh executables minted (cache-size delta)
    compile_seconds: float = 0.0  # wall time of calls that compiled
    donation_misses: int = 0     # donated carry not consumed by the call
    # warm-call accounting (ISSUE 14 bugfix): compile_seconds conflates
    # tracing+compile with the first execution; recording the run wall of
    # NON-compiling calls separately both fixes the split (the derived
    # compileOverheadSeconds below) and feeds the kernel observatory's
    # run-time histograms (perf/observatory.py)
    run_calls: int = 0
    run_seconds: float = 0.0     # wall time of calls that did NOT compile

    @property
    def retraces(self) -> int:
        """Compiles beyond the first: shape/static-arg churn minting extra
        executables for the same kernel (each one is a fresh XLA compile —
        the thing shape-stable dispatch exists to avoid)."""
        return max(self.compiles - 1, 0)

    @property
    def compile_overhead_seconds(self) -> float:
        """compile_seconds minus the estimated execution share of the
        compiling calls (mean warm run wall × compiles) — the pure
        trace/compile cost, clamped at zero while no warm call has
        calibrated the estimate yet."""
        if not self.run_calls:
            return self.compile_seconds
        warm = self.run_seconds / self.run_calls
        return max(self.compile_seconds - warm * self.compiles, 0.0)

    def to_dict(self) -> dict:
        return {"calls": self.calls, "compiles": self.compiles,
                "retraces": self.retraces,
                "compileSeconds": round(self.compile_seconds, 3),
                "compileOverheadSeconds": round(
                    self.compile_overhead_seconds, 3),
                "runCalls": self.run_calls,
                "runSeconds": round(self.run_seconds, 6),
                "donationMisses": self.donation_misses}


# h2d phase labels, aligned with scheduler_drain_phase_seconds{phase}
# where the transfer is paid (device_readback is the d2h direction of the
# same link — kept in one family so transfer dashboards need one query)
H2D_PHASES = ("host_snapshot", "host_group_seed", "host_cache",
              "device_readback")


class CompileLedger:
    """Process-wide compile + transfer accounting (see module docstring).

    Record and snapshot are thread-safe (ISSUE 14): the standby
    scheduler's warm-up drains and the shadow-audit worker's replays
    dispatch kernels concurrently with the host loop, and all of them
    land here. The lock brackets only the counter updates — never the
    jitted call itself."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.kernels: dict[str, KernelRecord] = {}  # guarded_by: _lock
        self.h2d: dict[str, int] = {}               # guarded_by: _lock

    # -- compile capture ------------------------------------------------------

    def _rec(self, kernel: str) -> KernelRecord:  # jaxsan: holds _lock
        rec = self.kernels.get(kernel)
        if rec is None:
            rec = self.kernels[kernel] = KernelRecord()
        return rec

    @staticmethod
    def _cache_size(fn) -> int:
        probe = getattr(fn, "_cache_size", None)
        if probe is None:
            return -1
        try:
            return int(probe())
        except Exception:  # pragma: no cover - backend specific
            return -1

    def measured_call(self, kernel: str, fn, *args, donated=None, **kw):
        """Call jitted `fn`, attributing any fresh compile (cache-size
        delta) to `kernel`. `donated` is the carry the caller donated (or
        None when the backend compiles without donation): if its buffer
        survives the call, the donation was ignored and the dispatch paid
        a copy of the resident node state — counted as a miss."""
        before = self._cache_size(fn)
        # names the device ops that follow in a jax.profiler trace
        with TraceAnnotation(f"dispatch:{kernel}"):
            t0 = _time.perf_counter()
            out = fn(*args, **kw)
            dt = _time.perf_counter() - t0
        delta = 0
        if before >= 0:
            delta = self._cache_size(fn) - before
        miss = False
        if donated is not None:
            # probe one leaf of the donated pytree; is_deleted() is the
            # jax.Array donation witness (True = buffer consumed)
            leaf = getattr(donated, "used", donated)
            deleted = getattr(leaf, "is_deleted", None)
            miss = deleted is not None and not deleted()
        with self._lock:
            rec = self._rec(kernel)
            rec.calls += 1
            if delta > 0:
                rec.compiles += delta
                rec.compile_seconds += dt
            else:
                rec.run_calls += 1
                rec.run_seconds += dt
            if miss:
                rec.donation_misses += 1
        # per-dispatch run-time attribution (perf/observatory.py): the
        # observatory decides itself whether its gate is on
        obs = _observatory()
        obs.on_call(kernel, t0, dt, delta > 0, args)
        if delta > 0:
            # a fresh executable was minted: cost the new variant
            # (perf/costmodel.py — trace+lower only, once per plan key)
            obs.on_compile(kernel, fn, args, kw)
        return out

    # -- transfer capture -----------------------------------------------------

    def note_h2d(self, phase: str, nbytes: int) -> None:
        with self._lock:
            self.h2d[phase] = self.h2d.get(phase, 0) + int(nbytes)

    def note_h2d_tree(self, phase: str, tree) -> None:
        """Account every array leaf of a NamedTuple/iterable (the upload
        helpers all move whole structs)."""
        total = 0
        for leaf in tree:
            nb = getattr(leaf, "nbytes", None)
            if nb is not None:
                total += int(nb)
        if total:
            self.note_h2d(phase, total)

    # -- reporting ------------------------------------------------------------

    def snapshot(self) -> dict:
        with self._lock:
            recs = {k: r.to_dict() for k, r in sorted(self.kernels.items())}
            h2d = dict(sorted(self.h2d.items()))
            compiles = sum(r.compiles for r in self.kernels.values())
            compile_s = sum(r.compile_seconds
                            for r in self.kernels.values())
            run_s = sum(r.run_seconds for r in self.kernels.values())
            retraces = sum(r.retraces for r in self.kernels.values())
        return {
            "kernels": recs,
            "h2dBytes": h2d,
            "totalCompiles": compiles,
            "totalCompileSeconds": round(compile_s, 3),
            "totalRunSeconds": round(run_s, 6),
            "totalRetraces": retraces,
        }

    def reset(self) -> None:
        """Test hook: forget everything (the jit caches themselves are
        untouched, so a reset ledger on a warm process records zero
        compiles — exactly the warm-run invariant)."""
        with self._lock:
            self.kernels.clear()
            self.h2d.clear()


GLOBAL = CompileLedger()


@dataclass(frozen=True)
class DeviceEntry:
    """One declared device entry (see `device_entry`)."""

    label: str          # the `kernel` label of its metric series
    fn: object          # the public function
    donates: int | None  # position of the argument it donates, if any
    # host cost estimate (perf/costmodel.py host_estimate): flops per
    # array cell of the args, and a multiplier over the args' raw bytes
    cost: tuple


# label → entry, filled as the declaring modules are imported
_ENTRIES: dict[str, DeviceEntry] = {}


def device_entry(label: str | None = None, *, cost: tuple,
                 donates: int | None = None):
    """Declare the decorated function a device entry.

    The function prepares one dispatch and returns `(program, args)`: the
    jitted program and its positional arguments. The declaration does
    the rest: it stages `args` through the sanitizer rails, dispatches
    through `GLOBAL.measured_call` under `label` (default: the function's
    name), and handles donation. An entry that donates `args[donates]`
    returns a donating program as its factory `donate -> jitted program`,
    because only accelerator backends donate; on the CPU the declaration
    poisons the carry instead. A program returned as itself never
    donates.

    jaxsan (analysis/jaxsan.py) reads these declarations from the source:
    they are its entry points and its donating entries."""
    def declare(prepare):
        from ..analysis.rails import GLOBAL as rails
        name = label or prepare.__name__

        @functools.wraps(prepare)
        def dispatch(*a, **kw):
            program, args = prepare(*a, **kw)
            args = rails.stage(args)
            if hasattr(program, "lower"):   # jitted; a factory has no lower
                return GLOBAL.measured_call(name, program, *args)
            donate = jax.default_backend() != "cpu"
            donated = args[donates]
            out = GLOBAL.measured_call(name, program(donate), *args,
                                       donated=donated if donate else None)
            if not donate:
                rails.poison_donated(donated, out)
            return out

        _ENTRIES[name] = DeviceEntry(name, dispatch, donates, tuple(cost))
        return dispatch
    return declare


def device_entries() -> dict[str, DeviceEntry]:
    """Every declared device entry, by label. Imports the declaring
    modules first, so the set does not depend on what the caller has
    imported."""
    from ..ops import gang, program  # noqa: F401
    from ..parallel import sharding  # noqa: F401
    return _ENTRIES


# resolved lazily (observatory imports this module, so a top-level
# import back would be circular); cached after the first call
_OBS = None


def _observatory():
    global _OBS
    if _OBS is None:
        from .observatory import GLOBAL as _g
        _OBS = _g
    return _OBS
