"""Profiler trace -> device busy time, program time and named idle gaps.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
JAX's own ``ProfileData``.  A chip's plane is ``/device:<KIND>:<n>``;
their ``XLA Modules`` line holds one event per program run, and their
``XLA Ops`` line one event per operation run — every step of a scan, so
millions a second.  Busy time is the union of the program runs.  The
operations' breakdown reads at most ``MAX_OP_EVENTS`` of them, from the
window's start.  Where the device's trace buffer overflowed (a ``Trace
Buffers Dropped`` event), the window ends where the drop starts.  The
harness's host spans (``bench.*``) sit on the host plane on the same
clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
DROPPED = "Trace Buffers Dropped"
MAX_OP_EVENTS = 300_000


@dataclasses.dataclass
class TraceSummary:
    window_s: float                       # the traced window's length
    busy_s: float                         # union of device op intervals, mean over chips
    module_s: dict[str, float]            # program runs: name -> device seconds
    module_n: dict[str, int]              # program runs: name -> count
    ops_s: dict[str, float]               # operations read: name -> device seconds
    ops_read: int                         # operation events read
    dropped: bool                         # the device's trace buffer overflowed
    gaps: list[tuple[str, float]]         # idle gaps, longest first, named by host span
    idle_by_span: list[tuple[str, float]]  # idle seconds summed per host span name
    devices: int


def find(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def _device_plane(name: str) -> bool:
    """A chip's own plane (``/device:TPU:0``), not the host's, nor a custom
    plane such as ``/device:CUSTOM:Megascale Trace``."""
    m = re.fullmatch(r"/device:([A-Z]+):(\d+)", name)
    return m is not None and m.group(1) != "CPU"


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def reduce(path: str, max_gaps: int = 10) -> TraceSummary:
    """Reduce one trace file, within the harness's ``bench.window`` span."""
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(path)
    spans = []
    window = None
    devices = []
    for plane in prof.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
                        if ev.name == WINDOW_SPAN:
                            window = (ev.start_ns, ev.start_ns + ev.duration_ns)
        elif _device_plane(plane.name):
            devices.append({line.name: line for line in plane.lines})
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN} span in {path}")
    lo, hi = window
    dropped = False
    for lines in devices:
        if "XLA TraceMe" in lines:
            for ev in lines["XLA TraceMe"].events:
                if ev.name == DROPPED and ev.start_ns < hi:
                    hi = max(lo, ev.start_ns)
                    dropped = True
    busy = []
    module_s: dict[str, float] = {}
    module_n: dict[str, int] = {}
    ops_s: dict[str, float] = {}
    n_ops = 0
    union_all = []
    for lines in devices:
        runs = []
        if "XLA Modules" in lines:
            for ev in lines["XLA Modules"].events:
                s, d = ev.start_ns, ev.duration_ns
                if s + d > lo and s < hi:
                    module_s[ev.name] = module_s.get(ev.name, 0.0) + d * 1e-9
                    module_n[ev.name] = module_n.get(ev.name, 0) + 1
                    runs.append((s, s + d))
        if "XLA Ops" in lines:
            for ev in lines["XLA Ops"].events:
                s, d = ev.start_ns, ev.duration_ns
                if s >= hi or n_ops >= MAX_OP_EVENTS:
                    break
                if s + d > lo:
                    name = ev.name.split(" = ", 1)[0]
                    ops_s[name] = ops_s.get(name, 0.0) + d * 1e-9
                    n_ops += 1
        u = _union(_clip(runs, lo, hi))
        busy.append(sum(e - s for s, e in u) * 1e-9)
        union_all.extend(u)
    n_dev = max(len(devices), 1)
    # idle gaps of the fleet of chips, named by the innermost host span over
    # the gap's middle
    merged = _union(union_all)
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    gaps = []
    spans_in = [sp for sp in spans if sp[2] != WINDOW_SPAN]
    for s, e in zip(edges[0::2], edges[1::2]):
        if e <= s:
            continue
        mid = 0.5 * (s + e)
        cover = [sp for sp in spans_in if sp[0] <= mid <= sp[1]]
        name = (min(cover, key=lambda sp: sp[1] - sp[0])[2] if cover
                else "harness")
        gaps.append((name, (e - s) * 1e-9))
    by_span: dict[str, float] = {}
    for name, sec in gaps:
        by_span[name] = by_span.get(name, 0.0) + sec
    gaps.sort(key=lambda g: -g[1])
    return TraceSummary(
        window_s=(hi - lo) * 1e-9, busy_s=sum(busy) / n_dev,
        module_s=module_s, module_n=module_n, ops_s=ops_s, ops_read=n_ops,
        dropped=dropped, gaps=gaps[:max_gaps],
        idle_by_span=sorted(by_span.items(), key=lambda g: -g[1])[:max_gaps],
        devices=len(devices))
