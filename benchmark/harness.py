"""The benchmark's harness: one run of one cell, driven by data.

`BENCHMARK.json` names a cell's configuration and traffic mix; everything
else is found by name under `benchmark/`:

    configs/<config>.json      the deployment (nodes, templates, sizes) and
                               its `kind`
    kinds/<kind>/reference.py  the plain reference of that kind of
                               deployment: `check(cfg, record, sample)`,
                               `control(cfg, record)` (the lower precision
                               in the program's place)
    kinds/<kind>/build.py      the program's nodes and pods for it (class
                               Builder)
    kinds/<kind>/checks.json   the limit of each number `check` returns
    traffic/<traffic>.json     the mix: its `kind` and parameters
    drivers/<kind>.py          the generator for that kind (class Driver)
    metrics/<name>.py          one reader per metric, by the part of the
                               metric's name before the first '.'

A reader module has `read(ctx) -> float | None` and may have
`probe(sched) -> dict`, read at the window's open and close; `ctx.delta`
holds the differences. A reader that finds nothing returns None and its
metric is left out of the line.
"""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

BENCH_DIR = "benchmark"
# the benchmark's own host spans; idle device gaps are labelled by them
LABELS = ("create", "complete", "schedule_pending")
GRACE_S = 60.0          # how long an answer due in the window may take
TRACE_S = 3.0           # traced seconds, in the middle of the window
CHECK_SAMPLE = 3000     # answers whose argmax the reference recomputes


def use_compile_cache(root: str) -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, whatever the machine sets, so only a cell's first run
    compiles. Its own directory: the chip machine may fill `.jax_cache`
    with entries of its own. Call before JAX is imported."""
    path = os.path.join(root, ".bench_jax_cache")
    os.makedirs(path, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"


class NoDevice(RuntimeError):
    """JAX finds no accelerator of the kind, or too few chips."""


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of `workloads`, resolved to its files."""

    def __init__(self, root: str, workload: str):
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            spec = json.load(f)
        entry = next((w for w in spec["workloads"] if w["name"] == workload),
                     None)
        if entry is None:
            raise KeyError(f"no workload named {workload!r}")
        bdir = os.path.join(root, BENCH_DIR)
        self.name = workload
        self.chips = int(entry["chips"])
        conf = next(c for c in spec["configs"] if c["name"] == entry["config"])
        with open(os.path.join(root, conf["file"])) as f:
            self.config = json.load(f)
        kdir = os.path.join(bdir, "kinds", self.config["kind"])
        self.reference = load_module(os.path.join(kdir, "reference.py"),
                                     f"bench_ref_{self.config['kind']}")
        self.build = load_module(os.path.join(kdir, "build.py"),
                                 f"bench_build_{self.config['kind']}")
        with open(os.path.join(kdir, "checks.json")) as f:
            self.limits = json.load(f)
        with open(os.path.join(bdir, "traffic",
                               entry["traffic"] + ".json")) as f:
            self.traffic = json.load(f)
        kind = self.traffic["kind"]
        self.driver = load_module(os.path.join(bdir, "drivers", kind + ".py"),
                                  f"bench_driver_{kind}")

        def mine(m):
            return "workloads" not in m or workload in m["workloads"]

        self.end_to_end = [m for m in spec["end_to_end"] if mine(m)]
        self.per_layer = [m for m in spec["per_layer"] if mine(m)]
        self.readers = {}
        for m in self.end_to_end + self.per_layer:
            base = m["name"].split(".")[0]
            if base not in self.readers:
                self.readers[base] = load_module(
                    os.path.join(bdir, "metrics", base + ".py"),
                    f"bench_metric_{base}")


class Context:
    """What a metric reader may read about one run."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def bound_between(self, lo: float, hi: float) -> int:
        t = self.bind_times
        return int(((t >= lo) & (t <= hi)).sum())


class CompileCounter:
    """Counts JAX traces and XLA compiles as they happen."""

    def __init__(self):
        import jax
        self.traces = 0
        self.compiles = 0

        def on_duration(name, _secs, **_kw):
            if name == "/jax/core/compile/jaxpr_trace_duration":
                self.traces += 1
            elif name == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def __call__(self) -> int:
        return self.traces


class Warmup:
    """When a warm-up round may end: it compiled nothing, and the scheduler
    has dispatched at least `min_drains` drains (the shadow audit samples
    every 64th drain and compiles its own copies the first time)."""

    def __init__(self, sched, compiles: CompileCounter, min_drains: int):
        self.sched = sched
        self.compiles = compiles
        self.min_drains = min_drains
        self._before = 0

    def mark(self) -> None:
        self._before = self.compiles()

    def settled(self) -> bool:
        drains = self.sched.metrics.device_batch_size.count()
        return self.compiles() == self._before and drains >= self.min_drains


class Tracer:
    """Starts the profiler partway into the window and stops it TRACE_S
    later; a `traced` host span marks the traced seconds."""

    def __init__(self, on: bool, t_start: float, length: float):
        self.on = on
        self.t_start = t_start
        self.length = length
        self.dir = tempfile.mkdtemp(prefix="bench-trace-") if on else None
        self.started = self.stopped = None
        self._mark = None
        self.found = "not started"

    def tick(self, now: float) -> None:
        if not self.on:
            return
        import jax
        if self.started is None and now >= self.t_start:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._mark = jax.profiler.TraceAnnotation("traced")
            self._mark.__enter__()
            self.started = time.perf_counter()
        elif (self.started is not None and self.stopped is None
              and now >= self.started + self.length):
            self.stop()

    def stop(self) -> None:
        import jax
        if self.started is None or self.stopped is not None:
            return
        self.stopped = time.perf_counter()
        self._mark.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def reduce(self):
        from benchmark.trace_reduce import reduce_trace
        if self.started is None:
            return None
        note: dict = {}
        try:
            return reduce_trace(self.dir, LABELS, found=note)
        finally:
            self.found = note.get("found", "")
            shutil.rmtree(self.dir, ignore_errors=True)


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoDevice(f"need {chips} TPU chip(s); JAX has {len(devs)} "
                       f"{devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(chips: int) -> int:
    import jax
    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, t_process: float, require_tpu: bool = True,
             control: bool = False) -> dict:
    """One run. Returns the result line's object; raises NoDevice.
    With `control` (control.py only) the kind's control decides every pod
    of the run in the program's place and `correct` judges its answers;
    the program's own checks go under `program_checks`."""
    from benchmark.system import System

    cell = Cell(root, workload)
    device = device_info(cell.chips, require_tpu)
    compiles = CompileCounter()
    system = System(cell.config, cell.build.Builder(cell.config,
                                                    cell.reference))
    system.create_nodes()
    drv = cell.driver.Driver(system, cell.config, cell.traffic, seed)
    warm = drv.warmup(Warmup(system.sched, compiles,
                             int(cell.traffic["warmup_drains"])))
    probes = {b: r.probe(system.sched) for b, r in cell.readers.items()
              if hasattr(r, "probe")}
    traces0, xla0 = compiles.traces, compiles.compiles
    t_open = time.perf_counter()
    setup_s = t_open - t_process
    trace_len = min(TRACE_S, seconds / 2)
    tracer = Tracer(trace, t_open + (seconds - trace_len) / 2, trace_len)
    t_open, t_close = drv.window(seconds, tracer.tick)
    tracer.stop()
    after = {b: cell.readers[b].probe(system.sched) for b in probes}
    in_window = (compiles.traces - traces0, compiles.compiles - xla0)
    drv.finish(GRACE_S)
    drv.close()
    device["memory_peak_bytes"] = memory_peak(cell.chips)
    reduced = tracer.reduce()

    # the answers: what the watch saw, per pod in creation order
    due_seqs = np.asarray(drv.due(), dtype=np.int64)
    bind_at = np.full(system.created, np.nan)
    for uid, t in system.watch.at.items():
        bind_at[int(uid.rsplit("-", 1)[1])] = t
    record = system.record(due_seqs)
    del system, drv
    rng = np.random.default_rng([seed, 3])
    sample = (rng.choice(due_seqs, size=min(CHECK_SAMPLE, len(due_seqs)),
                         replace=False) if len(due_seqs) else due_seqs)
    ref = cell.reference
    t_ref = time.perf_counter()
    found = ref.check(cell.config, record, sample)
    ref_s = time.perf_counter() - t_ref
    program_checks = None
    if control:
        program_checks = _judge(found, cell.limits)
        record.node_of = ref.control(cell.config, record)
        found = ref.check(cell.config, record, sample)

    delta = {}
    for b, before in probes.items():
        for k, v in after[b].items():
            delta[k] = v - before.get(k, 0.0)
    bind_times = bind_at[~np.isnan(bind_at)]
    ctx = Context(
        setup_s=setup_s, t_open=t_open, t_close=t_close,
        window_s=t_close - t_open, bind_times=bind_times, delta=delta,
        trace=reduced,
        trace_window=((tracer.started, tracer.stopped)
                      if tracer.started is not None else None))
    ctx.bound = ctx.bound_between(t_open, t_close)
    if ctx.trace_window is not None:
        ctx.trace_bound = ctx.bound_between(*ctx.trace_window)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = cell.readers[m["name"].split(".")[0]].read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    checks = _judge(found, cell.limits)
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    print(f"workload={workload} seed={seed} seconds={seconds} trace={int(trace)}"
          f" warmup_rounds={warm} setup_s={setup_s:.3f}"
          f" window_s={ctx.window_s:.3f}", file=sys.stderr)
    print(f"in_window: jax_traces={in_window[0]} xla_compiles={in_window[1]}"
          f" bound={ctx.bound} created={record.created}"
          f" completed={len(record.deletes)}", file=sys.stderr)
    print(f"reference: checked={found.get('checked')} of {len(due_seqs)} due,"
          f" max_score_gap={found.get('max_score_gap')} seconds={ref_s:.3f}",
          file=sys.stderr)
    if trace:
        print(f"trace: {tracer.found} bound={getattr(ctx, 'trace_bound', 0)}"
              + (f" busy_s={reduced['busy_s']:.6f}"
                 f" window_s={reduced['window_s']:.6f}"
                 f" host_seconds={reduced['host_seconds']}" if reduced
                 else ""),
              file=sys.stderr)
    if control:
        print("control: the kind's control decided every pod in the "
              "program's place", file=sys.stderr)
    for k, c in checks.items():
        print(f"check {k} {c['value']} limit {c['limit']}", file=sys.stderr)

    result = {"correct": bool(correct), "attempted": int(len(due_seqs)),
              "failed": int(found["unbound"]), "metrics": metrics,
              "device": device}
    if trace and reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    if program_checks is not None:
        result["program_checks"] = program_checks
    result["checks"] = checks
    return result


def _judge(found: dict, limits: dict) -> dict:
    """Each number compared, beside its limit."""
    return {k: {"value": found[k], "limit": lim} for k, lim in limits.items()}
