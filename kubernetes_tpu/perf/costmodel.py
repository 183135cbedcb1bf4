"""Device cost model: flops/bytes per compiled kernel variant (ISSUE 20).

The kernel observatory records how long every JIT entry RUNS per
plan/shape variant, but not what the variant COSTS — so a kernel row
could not say whether it is compute-bound or memory-bound, and ROADMAP
item 5's autotuner has no roofline to search against. This module is
that cost table:

- when `CompileLedger.measured_call` detects a fresh compile it reports
  the (kernel, jitted fn, args) here ONCE per plan/shape key. The model
  asks XLA for the variant's cost via `Lowered.cost_analysis()` —
  tracing + lowering only, never a second XLA compile (a few ms for a
  small program on the CPU, paid only on compile events) — and falls
  back to a per-kernel HOST ESTIMATOR (the `cost` coefficients of the
  kernel's `device_entry` declaration, perf/ledger.py, over the args'
  array cells/bytes) where XLA reports nothing (on the TPU,
  `cost_analysis()` is None for the sharded programs). The declaration
  requires the coefficients, so no device entry lands uncosted.
- per (flops, bytes) row the model derives arithmetic intensity and —
  against the device kind's roofline anchors (`PEAKS`) — a modeled runtime
  `max(flops/peak_flops, bytes/peak_bw)`, the achieved-vs-modeled
  fraction once the observatory has a measured warm p50 for the same
  plan key, and a boundness verdict: compute-bound vs memory-bound by
  intensity against the ridge point, comms-bound when the sharded-lane
  profile attributes the majority of the kernel's window to
  collectives.

Rows are bounded by the observatory's own MAX_PLAN_KEYS discipline and
surface at /debug/kernels, in tools/kernel_sweep.py sweep points, and as
the per-device-kind cost table the critical-path verdicts read.
"""

from __future__ import annotations

import threading

import numpy as np

from .ledger import device_entries

# Roofline anchors per device kind (`jax.devices()[0].device_kind`):
# (peak flops/s, peak bytes/s). A device kind missing here is an error,
# not a default: a roofline against the wrong chip's peaks is a wrong
# number, not a coarse one.
PEAKS = {
    # TPU v5e, Google Cloud documentation "TPU v5e": 197 TFLOP/s bf16,
    # 819 GB/s HBM. The key is the device_kind the chip reports.
    "TPU v5 lite": (197.0e12, 819.0e9),
    # XLA:CPU (the test sandbox): a coarse single-socket anchor, ~100
    # GFLOP/s and ~20 GB/s — used only to rank variants in CPU tests
    "cpu": (1.0e11, 2.0e10),
}

BOUND_COMPUTE = "compute_bound"
BOUND_MEMORY = "memory_bound"
BOUND_COMMS = "comms_bound"

# a sharded kernel whose lane profile attributes more than this share of
# the device window to collectives is comms-bound regardless of its
# arithmetic intensity — the roofline it sits under is the interconnect
COMMS_BOUND_SHARE = 0.35


def peaks(device_kind: str):
    """(peak flops/s, peak bytes/s) of `device_kind`; raises KeyError for
    a device kind PEAKS does not list."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no roofline peaks for device kind "
                       f"{device_kind!r}: add it to perf/costmodel.py "
                       f"PEAKS with its published source") from None


def host_estimate(kernel: str, args: tuple) -> tuple:
    """(flops, bytes) from the dispatch args alone — the fallback when
    XLA reports nothing. Cells = total array elements across every array
    leaf of the args; bytes = their raw bytes times the kernel's revisit
    multiplier. The coefficients encode each kernel's work shape — the
    scoring/filter kernels do a few tens of ops per node-pod cell, the
    scan/wave kernels revisit the carry per segment, the probe/diagnose
    reductions are single-pass. Rows costed this way carry
    source="host"."""
    entry = device_entries().get(kernel)
    if entry is None:
        return (0.0, 0.0)
    import jax
    flops_per_cell, byte_mult = entry.cost
    cells = 0
    nbytes = 0
    for leaf in jax.tree_util.tree_leaves(args):
        if not hasattr(leaf, "dtype"):
            continue        # static args: configs, meshes, plain ints
        n = int(np.size(leaf))
        cells += n
        nbytes += n * np.dtype(leaf.dtype).itemsize
    return (float(cells) * flops_per_cell, float(nbytes) * byte_mult)


def xla_cost(fn, args: tuple, kw: dict) -> tuple:
    """(flops, bytes) from XLA's HLO cost analysis of the jitted fn's
    LOWERING (no second compile), or (0, 0) when the backend/API
    reports nothing — the caller falls back to the host estimator."""
    try:
        ca = fn.lower(*args, **kw).cost_analysis()
    except Exception:
        return (0.0, 0.0)
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return (0.0, 0.0)
    flops = float(ca.get("flops", 0.0) or 0.0)
    nbytes = float(ca.get("bytes accessed", 0.0) or 0.0)
    if flops < 0.0:
        flops = 0.0
    if nbytes < 0.0:
        nbytes = 0.0
    return (flops, nbytes)


def modeled_seconds(flops: float, nbytes: float, device_kind: str) -> float:
    """Roofline runtime: whichever of the compute and memory walls is
    binding for the variant on this device kind."""
    pf, pb = peaks(device_kind)
    return max(flops / pf if pf > 0 else 0.0,
               nbytes / pb if pb > 0 else 0.0)


def classify(flops: float, nbytes: float, device_kind: str,
             comms_share: float = 0.0) -> str:
    """compute/memory/comms-bound for one (flops, bytes) row: comms wins
    when the lane profile says collectives own the window; otherwise
    arithmetic intensity against the device kind's ridge point."""
    if comms_share > COMMS_BOUND_SHARE:
        return BOUND_COMMS
    pf, pb = peaks(device_kind)
    ridge = pf / pb if pb > 0 else 0.0
    ai = flops / nbytes if nbytes > 0 else float("inf")
    return BOUND_COMPUTE if ai >= ridge else BOUND_MEMORY


class CostModel:
    """Per-(kernel, plan-key) cost rows, filled once per fresh compile.

    Owned by the KernelObservatory (one instance behind its GLOBAL);
    thread-safe the same way — compiles land from the host loop, the
    standby scheduler and the audit worker."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        # (kernel, plan_key) -> {"flops","bytes","source"}
        self.rows: dict = {}

    def record_compile(self, kernel: str, fn, args: tuple,
                       kw: dict) -> None:
        """One fresh compile: cost the new variant unless its plan key
        is already costed (re-compiles of a known shape are donation/
        cache churn, not new variants)."""
        from .observatory import MAX_PLAN_KEYS, _shape_key
        key = (kernel, _shape_key(args))
        with self._lock:
            if key in self.rows:
                return
            # bound memory like the observatory's plan histograms: past
            # the cap new variants fold into the overflow row
            if sum(1 for k, _p in self.rows if k == kernel) \
                    >= MAX_PLAN_KEYS:
                key = (kernel, "~other")
                if key in self.rows:
                    return
            self.rows[key] = None          # claim under the lock
        flops, nbytes = xla_cost(fn, args, kw)
        source = "xla"
        if flops <= 0.0 and nbytes <= 0.0:
            flops, nbytes = host_estimate(kernel, args)
            source = "host"
        with self._lock:
            self.rows[key] = {"flops": flops, "bytes": nbytes,
                              "source": source}

    def kernel_rows(self, kernel: str) -> dict:
        """{plan_key: row} for one kernel (completed rows only)."""
        with self._lock:
            return {plan: dict(row) for (k, plan), row in self.rows.items()
                    if k == kernel and row is not None}

    def covered(self) -> set:
        """Kernels with at least one completed cost row."""
        with self._lock:
            return {k for (k, _p), row in self.rows.items()
                    if row is not None}

    def reset(self) -> None:
        with self._lock:
            self.rows.clear()
