"""Profiler trace -> device busy time, time per device op, longest idle gaps.

Reads the `.xplane.pb` that `jax.profiler` writes, with nothing but JAX. A
device is a plane named `/device:TPU:<n>`; its operations are the events on
the plane's `XLA Ops` line. Busy time is the union of their intervals (ops
that overlap count once); time per op is the plain sum of each name's
durations. An idle gap is a stretch of the traced window in which no op
runs on the device; it is labelled with the benchmark's host annotation
(`jax.profiler.TraceAnnotation` on the host plane) that overlaps it most,
or `other`.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


def union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Merge [start, end) intervals; the result is sorted and disjoint."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: list[tuple[int, int]], lo: int, hi: int
         ) -> list[tuple[int, int]]:
    """The stretches of [lo, hi) that no busy interval covers."""
    out, t = [], lo
    for s, e in busy:
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label_gap(gap: tuple[int, int], spans: list[tuple[int, int, str]]) -> str:
    best, best_overlap = "other", 0
    for s, e, name in spans:
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > best_overlap:
            best, best_overlap = name, ov
    return best


def reduce_events(device_ops: list[list[tuple[int, int, str]]],
                  host_spans: list[tuple[int, int, str]],
                  lo: int, hi: int, top: int = 10) -> dict:
    """The reduction itself, on plain (start_ns, end_ns, name) tuples:
    `device_ops` has one list per device. Returns busy seconds averaged
    over the devices, the window's seconds, and the breakdown lists."""
    per_op: dict[str, float] = {}
    busy_total = 0
    all_gaps: list[tuple[int, int]] = []
    for ops in device_ops:
        clipped = [(max(s, lo), min(e, hi), name) for s, e, name in ops
                   if min(e, hi) > max(s, lo)]
        iv = union([(s, e) for s, e, _ in clipped])
        busy_total += sum(e - s for s, e in iv)
        for s, e, name in clipped:
            per_op[name] = per_op.get(name, 0.0) + (e - s) * 1e-9
        all_gaps.extend(gaps(iv, lo, hi))
    host_seconds: dict[str, float] = {}
    for s, e, name in host_spans:
        if min(e, hi) > max(s, lo):
            host_seconds[name] = (host_seconds.get(name, 0.0)
                                  + (min(e, hi) - max(s, lo)) * 1e-9)
    n = max(len(device_ops), 1)
    ops_sorted = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    all_gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": busy_total * 1e-9 / n,
        "window_s": (hi - lo) * 1e-9,
        "devices": len(device_ops),
        "op_seconds": dict(per_op),
        "host_seconds": host_seconds,
        "device_ops": [[name, sec] for name, sec in ops_sorted],
        "idle_gaps": [[label_gap(g, host_spans), (g[1] - g[0]) * 1e-9]
                      for g in all_gaps[:top]],
    }


def op_name(event_name: str) -> str:
    """`fusion.12` from the HLO text the trace names an op by
    (`%fusion.12 = s64[...] fusion(...)`)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def read_xplane(path: str, labels: tuple[str, ...]):
    """(device_ops per device, host spans named in `labels`) from one file."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    devices = []
    host: list[tuple[int, int, str]] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((int(e.start_ns), int(e.end_ns),
                                op_name(e.name)) for e in line.events)
            devices.append(ops)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend((int(e.start_ns), int(e.end_ns), e.name)
                            for e in line.events if e.name in labels)
    return devices, host


def find_xplane(trace_dir: str) -> str | None:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def reduce_trace(trace_dir: str, labels: tuple[str, ...],
                 window_label: str = "traced", found=None) -> dict | None:
    """Reduce the newest trace under `trace_dir`. The window is the host
    span named `window_label`, which the benchmark holds open over the
    traced seconds; idle gaps are labelled from `labels`. None when the
    trace has no device plane or no window span; `found["found"]` then
    says what the file held."""
    found = {} if found is None else found
    path = find_xplane(trace_dir)
    if path is None:
        found["found"] = "no xplane file"
        return None
    devices, host = read_xplane(path, labels + (window_label,))
    marks = [(s, e) for s, e, name in host if name == window_label]
    found["found"] = (f"{os.path.getsize(path)} bytes, {len(devices)} device"
                      f" planes, {sum(map(len, devices))} ops,"
                      f" {len(marks)} window spans, {len(host)} host spans")
    if not devices or not any(devices) or not marks:
        return None
    lo, hi = marks[0]
    spans = [sp for sp in host if sp[2] != window_label]
    return reduce_events(devices, spans, lo, hi)
