"""The program's own spans (``gf.*``) in a profiler trace, per window.

The placement path opens ``gf.*`` spans on the profiler's host plane
(``repro.core.spans``): ``gf.flush`` per engine window, holding
``gf.prepare``, ``gf.place`` and ``gf.complete``; inside ``gf.place`` the
scheduler's phases ``gf.predict``, ``gf.normalizers``, ``gf.pack``,
``gf.h2d``, ``gf.scan``, ``gf.d2h``, ``gf.winner``, ``gf.commit`` and
``gf.release`` (``gf.soa`` in place of the pack, the transfers, the scan,
the winner and the commit for a window placed on the host); ``gf.gc`` for a full
collection of the garbage collector.  Each carries ``window=<index>``
and its own stats.  This module reads them from a trace that
``bench/run.py --trace 1 --trace-dir <dir>`` kept, within the harness's
``bench.window`` span, and names each idle gap of the device by the
innermost ``gf.*`` span open over its middle, ``outside`` where none is::

    python3 bench/program_spans.py <dir>

prints one JSON object: the per-window breakdown of the windows placed
on the device, the queue wait, the transfers and the idle by span.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import sys

if __package__ in (None, ""):
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import trace  # noqa: E402

PREFIX = "gf."
OUTSIDE = "outside"
PACK = ("gf.predict", "gf.normalizers", "gf.pack")
COMMIT = ("gf.winner", "gf.commit", "gf.release")
TRANSFER = ("gf.h2d", "gf.d2h")


@dataclasses.dataclass
class ProgramSpans:
    window_s: float                          # the traced window's length
    spans: list[tuple[str, int, int, dict]]  # (name, start_ns, end_ns, stats)
    idle_s: dict[str, float]                 # device idle per innermost span


def reduce(path: str) -> ProgramSpans:
    """Read the ``gf.*`` spans of one trace file and name the device's idle
    gaps by them, in the window ``bench.trace.reduce`` reads."""
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(path)
    spans = []
    window = None
    devices = []
    for plane in prof.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    end = ev.start_ns + ev.duration_ns
                    if ev.name.startswith(PREFIX):
                        spans.append((ev.name, ev.start_ns, end, dict(ev.stats)))
                    elif ev.name == trace.WINDOW_SPAN:
                        window = (ev.start_ns, end)
        elif trace._device_plane(plane.name):
            devices.append({line.name: line for line in plane.lines})
    if window is None:
        raise ValueError(f"no {trace.WINDOW_SPAN} span in {path}")
    lo, hi = window
    spans = sorted((sp for sp in spans if lo <= sp[1] and sp[2] <= hi),
                   key=lambda sp: sp[1])
    # the device side ends where its trace buffer overflowed, as in
    # bench.trace.reduce
    for lines in devices:
        if "XLA TraceMe" in lines:
            for ev in lines["XLA TraceMe"].events:
                if ev.name == trace.DROPPED and ev.start_ns < hi:
                    hi = max(lo, ev.start_ns)
    runs = []
    for lines in devices:
        if "XLA Modules" in lines:
            runs += [(ev.start_ns, ev.start_ns + ev.duration_ns)
                     for ev in lines["XLA Modules"].events]
    merged = trace._union(trace._clip(runs, lo, hi))
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    idle: dict[str, float] = {}
    for s, e in zip(edges[0::2], edges[1::2]):
        if e <= s:
            continue
        mid = 0.5 * (s + e)
        cover = [sp for sp in spans if sp[1] <= mid <= sp[2]]
        name = min(cover, key=lambda sp: sp[2] - sp[1])[0] if cover else OUTSIDE
        idle[name] = idle.get(name, 0.0) + (e - s) * 1e-9
    return ProgramSpans((hi - lo) * 1e-9, spans, idle)


def by_window(spans) -> dict[int, list]:
    """Spans grouped by their ``window`` stat."""
    out: dict[int, list] = {}
    for sp in spans:
        w = sp[3].get("window")
        if w is not None:
            out.setdefault(int(w), []).append(sp)
    return out


def device_windows(spans) -> list[list]:
    """The spans of each window whose ``gf.place`` holds a ``gf.scan``."""
    out = []
    for sps in by_window(spans).values():
        places = [sp for sp in sps if sp[0] == "gf.place"]
        if any(p[1] <= sp[1] and sp[2] <= p[2] for p in places
               for sp in sps if sp[0] == "gf.scan"):
            out.append(sps)
    return out


def _ms(sps, names) -> float:
    return 1e-6 * sum(sp[2] - sp[1] for sp in sps if sp[0] in names)


def _stat(sps, names, key) -> float:
    return sum(sp[3].get(key, 0) for sp in sps if sp[0] in names)


def _mean(values):
    return sum(values) / len(values) if values else None


def summary(ps: ProgramSpans) -> dict:
    """Means per device window (ms, bytes); over every flush the mean queue
    wait, engine bookkeeping and host work (a window placed on the host
    counting whole, as the harness's ``host_prep_ms_per_window`` does); and
    the device's idle under the program's own spans."""
    dev = device_windows(ps.spans)
    flushes = [sp for sp in ps.spans if sp[0] == "gf.flush"]
    flushed = [sps for sps in by_window(ps.spans).values()
               if any(sp[0] == "gf.flush" for sp in sps)]
    triggers = sorted({sp[3]["trigger"] for sp in flushes})
    out = {
        "device_windows": len(dev),
        "flushes": len(flushes),
        "triggers": {t: sum(1 for sp in flushes if sp[3]["trigger"] == t)
                     for t in triggers},
        "queue_wait_ms": _mean([sp[3]["wait_ms"] for sp in flushes]),
        "queue_wait_ms_by_trigger": {
            t: _mean([sp[3]["wait_ms"] for sp in flushes if sp[3]["trigger"] == t])
            for t in triggers},
        "engine_ms": _mean([_ms(sps, ("gf.flush",)) - _ms(sps, ("gf.place",))
                            for sps in flushed]),
        "host_ms": _mean([_ms(sps, PACK + COMMIT + ("gf.soa",))
                          for sps in flushed]),
        "timeline_len": [sp[3]["timeline_len"] for sp in ps.spans
                         if sp[0] == "gf.commit"],
        "gc": {"collections": sum(1 for sp in ps.spans if sp[0] == "gf.gc"),
               "seconds": _ms(ps.spans, ("gf.gc",)) * 1e-3},
        "program_idle_pct": (100.0 * sum(v for k, v in ps.idle_s.items()
                                         if k != OUTSIDE) / ps.window_s
                             if ps.window_s > 0 else None),
        "idle_s": dict(sorted(ps.idle_s.items(), key=lambda kv: -kv[1])),
    }
    per = {
        "pack_ms": [_ms(w, PACK) for w in dev],
        "commit_ms": [_ms(w, COMMIT) for w in dev],
        "transfer_ms": [_ms(w, TRANSFER) for w in dev],
        "scan_ms": [_ms(w, ("gf.scan",)) for w in dev],
        "transfer_bytes": [_stat(w, TRANSFER, "bytes") for w in dev],
        "h2d_bytes": [_stat(w, ("gf.h2d",), "bytes") for w in dev],
        "d2h_bytes": [_stat(w, ("gf.d2h",), "bytes") for w in dev],
        "h2d_arrays": [_stat(w, ("gf.h2d",), "arrays") for w in dev],
        "d2h_arrays": [_stat(w, ("gf.d2h",), "arrays") for w in dev],
    }
    out.update({k: _mean(v) for k, v in per.items()})
    out["phase_ms"] = {name: _mean([_ms(w, (name,)) for w in dev])
                       for name in PACK + TRANSFER + ("gf.scan",) + COMMIT}
    return out


if __name__ == "__main__":
    if len(sys.argv) != 2:
        sys.exit("usage: python3 bench/program_spans.py <trace dir>")
    print(json.dumps(summary(reduce(trace.find(sys.argv[1])))))
