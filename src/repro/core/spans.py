"""Named spans of the placement path, on the profiler's clock.

``span("pack")`` opens a ``gf.pack`` event on the host plane of a
``jax.profiler`` trace, the same clock as the device's program and
operation lines, so each idle gap of the device can be named by the
program phase that was running.  Keyword arguments become the event's
stats.  Spans opened inside :func:`window` also carry ``window=<engine
window index>``, so every span of one arrival window shares an id;
parents come from nesting on the thread.

With the profiler off a span costs about a microsecond and records
nothing; the profiler keeps the events in memory and writes them when
the trace stops, so there is no buffer, exporter or switch here.

Full collections of the garbage collector are spans too (``gf.gc``), and
:data:`GC_STATS` counts them whether or not a trace runs.
"""
from __future__ import annotations

import contextlib
import gc
import threading
import time

# jax.profiler.TraceAnnotation is this class plus a docstring; taking it
# from jaxlib keeps ``import repro.core`` clear of importing jax itself
from jaxlib._profiler import TraceMe

PREFIX = "gf."

#: Full (generation-2) collections of the garbage collector: how many ran
#: and the seconds they took.  Cumulative; reset with :func:`reset_gc_stats`.
GC_STATS = {"collections": 0, "seconds": 0.0}

_local = threading.local()


def span(name: str, **stats) -> TraceMe:
    """A context manager recording ``gf.<name>`` with ``stats``; stats known
    only at the end go in through ``set_metadata(**stats)``."""
    w = getattr(_local, "window", None)
    if w is not None:
        stats["window"] = w
    return TraceMe(PREFIX + name, **stats)


@contextlib.contextmanager
def window(index: int, **stats):
    """The ``gf.flush`` span of engine window ``index``; every span opened
    inside it on this thread carries ``window=index``."""
    prev = getattr(_local, "window", None)
    _local.window = index
    try:
        with span("flush", **stats) as sp:
            yield sp
    finally:
        _local.window = prev


def reset_gc_stats() -> None:
    GC_STATS["collections"] = 0
    GC_STATS["seconds"] = 0.0


_gc_open: list = []   # (trace event, start) of the collection running now


def _on_gc(phase: str, info: dict) -> None:
    if info["generation"] != 2:
        return
    if phase == "start":
        ev = TraceMe(PREFIX + "gc")
        ev.__enter__()
        _gc_open.append((ev, time.perf_counter()))
    elif _gc_open:
        ev, t0 = _gc_open.pop()
        ev.__exit__(None, None, None)
        GC_STATS["collections"] += 1
        GC_STATS["seconds"] += time.perf_counter() - t0


gc.callbacks.append(_on_gc)
