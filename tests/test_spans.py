"""The placement path's own spans and counters (``repro.core.spans``,
``ops.WINDOW_STATS``, ``ops.COMPILE_STATS``), read back from a profiler
trace recorded on the host CPU."""
import gc
import glob
import os

import jax
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.profiler import ProfileData

from repro.core import spans
from repro.core.endpoint import scaled_testbed
from repro.core.engine import OnlineEngine
from repro.core.predictor import TaskProfileStore
from repro.core.scheduler import TaskSpec, mhra
from repro.core.testbed import SEBS_FUNCTIONS
from repro.core.transfer import TransferModel
from repro.kernels.placement import ops


def _traced(tmp_path, fn):
    """Run ``fn`` under the profiler; its ``gf.*`` host events, in order."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"), recursive=True)
    evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
           for plane in ProfileData.from_file(path).planes
           if plane.name.startswith("/host:")
           for line in plane.lines for ev in line.events
           if ev.name.startswith(spans.PREFIX)]
    return sorted(evs, key=lambda ev: ev[1])


def _fleet():
    eps = scaled_testbed(2)
    store = TaskProfileStore(eps)
    for fn in SEBS_FUNCTIONS:
        for i, ep in enumerate(eps):
            for _ in range(3):
                store.record(fn, ep.name, 1.0 + 0.1 * i, 20.0 + i)
    return eps, store


def _tasks(prefix, n):
    return [TaskSpec(id=f"{prefix}{i}", fn=SEBS_FUNCTIONS[i % len(SEBS_FUNCTIONS)])
            for i in range(n)]


def test_flush_spans_say_what_closed_the_window_and_how_long_it_waited(tmp_path):
    eps, store = _fleet()
    eng = OnlineEngine(eps, None, policy="mhra", alpha=0.5, engine="soa",
                       window_s=1.0, max_batch=4, monitoring=False, store=store)

    def drive():
        for t, when in zip(_tasks("a", 4), (0.0, 0.1, 0.2, 0.3)):
            eng.submit(t, when=when)               # the fourth fills the batch
        for t, when in zip(_tasks("b", 2), (1.0, 1.5)):
            eng.submit(t, when=when)
        assert eng.tick(1.9) is None
        assert eng.tick(2.2) is not None           # 1.2 s after the first
        eng.submit(_tasks("c", 1)[0], when=3.0)
        assert eng.flush() is not None

    evs = _traced(tmp_path, drive)
    flushes = [ev[3] for ev in evs if ev[0] == "gf.flush"]
    assert [(f["window"], f["tasks"], f["trigger"]) for f in flushes] == [
        (0, 4, "full"), (1, 2, "timer"), (2, 1, "call")]
    assert [f["wait_ms"] for f in flushes] == pytest.approx([300.0, 1200.0, 0.0])
    # every span of a window carries its id; soa windows place on the host
    for ev in evs:
        if ev[0] != "gf.gc":
            assert ev[3]["window"] in (0, 1, 2), ev
    assert {ev[0] for ev in evs} - {"gf.gc"} == {
        "gf.flush", "gf.prepare", "gf.place", "gf.predict", "gf.normalizers",
        "gf.soa", "gf.release", "gf.complete"}


def test_a_device_window_counts_the_bytes_of_the_arrays_it_moves(tmp_path,
                                                                 monkeypatch):
    eps, store = _fleet()
    moved, moved_xs = [], []
    scan = ops._greedy_scan

    def spy(consts, init, xs, **kw):
        out = scan(consts, init, xs, **kw)
        moved.append((jax.tree_util.tree_leaves((consts, init, xs)),
                      jax.tree_util.tree_leaves(out)))
        moved_xs.append(xs)
        return out

    monkeypatch.setattr(ops, "_greedy_scan", spy)
    ops.reset_window_stats()
    evs = _traced(tmp_path, lambda: mhra(_tasks("t", 24), eps, store,
                                         TransferModel(eps), engine="jax"))
    (up, down), = moved
    h2d, = [ev[3] for ev in evs if ev[0] == "gf.h2d"]
    d2h, = [ev[3] for ev in evs if ev[0] == "gf.d2h"]
    scan, = [ev[3] for ev in evs if ev[0] == "gf.scan"]
    assert len(up) == 53
    assert h2d == {"bytes": sum(a.nbytes for a in up), "arrays": 53}
    assert d2h == {"bytes": sum(a.nbytes for a in down), "arrays": 21}
    # 24 tasks, 24 steps; the pass runs where some heuristic starts a run
    xs = moved_xs[0]
    valid = np.asarray(xs["valid"])
    steps = {"basis_steps": int((np.asarray(xs["new_run"]) & valid).any(0).sum()),
             "scan_steps": int(valid.any(0).sum())}
    assert steps["scan_steps"] == 24 and 1 <= steps["basis_steps"] <= 24
    assert scan == steps
    assert ops.WINDOW_STATS == {"device": 1, "soa": 0,
                                "h2d_bytes": h2d["bytes"], "h2d_arrays": 53,
                                "d2h_bytes": d2h["bytes"], "d2h_arrays": 21,
                                **steps}
    # outside an engine no window id; the commit reports the live timeline
    commit, = [ev[3] for ev in evs if ev[0] == "gf.commit"]
    assert commit == {"timeline_len": 24}
    names = [ev[0] for ev in evs if ev[0] != "gf.gc"]
    assert names == ["gf.predict", "gf.normalizers", "gf.pack", "gf.h2d",
                     "gf.scan", "gf.d2h", "gf.winner", "gf.commit", "gf.release"]


def test_normalizers_count_the_inputs_read_and_the_rows_kept(tmp_path):
    """Shared-input deduplication runs once a window: a window whose tasks
    share one input keeps one transfer row of its inputs; a private input
    is a row of its own."""
    eps, store = _fleet()
    shared = (eps[0].name, 1, 2e8, True)
    private = (eps[1].name, 3, 1e6, False)
    tasks = [TaskSpec(id=f"t{i}", fn=SEBS_FUNCTIONS[i % len(SEBS_FUNCTIONS)],
                      inputs=(shared, private) if i == 0 else (shared,))
             for i in range(12)]
    evs = _traced(tmp_path, lambda: mhra(tasks, eps, store, TransferModel(eps),
                                         engine="soa"))
    norm, = [ev[3] for ev in evs if ev[0] == "gf.normalizers"]
    assert norm == {"inputs": 13, "rows": 2}


def test_compile_stats_tell_a_compile_from_a_cache_load(tmp_path):
    eps, store = _fleet()
    tm = TransferModel(eps)

    def place():
        mhra(_tasks("t", 40), eps, store, tm, engine="jax")

    prev = (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compilation_cache.reset_cache()
    try:
        jax.clear_caches()
        ops.reset_compile_stats()
        place()                        # a fresh signature: compiled, cached
        first = dict(ops.COMPILE_STATS)
        assert (first["compiles"], first["cache_loads"]) == (1, 0)
        assert first["seconds"] > 0
        place()                        # the same signature: nothing counted
        assert ops.COMPILE_STATS == first
        jax.clear_caches()
        ops.reset_compile_stats()
        place()                        # a new process's view: from the cache
        loaded = dict(ops.COMPILE_STATS)
        assert (loaded["compiles"], loaded["cache_loads"]) == (0, 1)
        assert loaded["seconds"] > 0
    finally:
        jax.config.update("jax_compilation_cache_dir", prev[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs", prev[1])
        compilation_cache.reset_cache()


def test_full_collections_are_spans_and_counted(tmp_path):
    gc.disable()          # only the collections asked for here
    try:
        spans.reset_gc_stats()
        gc.collect(0)
        gc.collect(1)
        assert spans.GC_STATS == {"collections": 0, "seconds": 0.0}
        evs = _traced(tmp_path, gc.collect)
    finally:
        gc.enable()
    assert spans.GC_STATS["collections"] == 1 and spans.GC_STATS["seconds"] > 0
    assert [ev[0] for ev in evs] == ["gf.gc"]
