"""One run of one cell: set-up, warm-up, the measured window, the check.

The system under test is ``repro.core.engine.OnlineEngine`` in
planner-only mode (no testbed simulator), driven with the cell's traffic
on the wall clock: the engine's clock is the run's clock.  In a traced
run the harness wraps the engine's ``flush``, its policy's ``place`` and
the device call ``repro.kernels.placement.ops.greedy_window`` in host
spans (``bench.*``) that land in the profiler's trace; untraced runs
wrap nothing.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import os
import pathlib
import shutil
import sys
import time

import numpy as np

from bench import check, deployment, traffic

#: The platform a run needs; a run finds no result on any other.
PLATFORM = "tpu"


class NoDevice(RuntimeError):
    """JAX found no chip of the kind, or fewer than the cell asks for."""


@dataclasses.dataclass
class WindowSpan:
    """Host spans of one window in a traced run (seconds)."""
    flush_s: float
    sched_s: float
    device_s: float
    device_calls: int
    tasks: int


@dataclasses.dataclass
class Run:
    """Everything the metric readers under ``bench/metrics/`` read."""
    workload: str
    kind: str
    seconds: float
    setup_s: float
    heuristics: int
    endpoints: int
    placed_in_window: int = 0
    window_s: float = 0.0                # start -> last flush return
    latencies_s: np.ndarray | None = None
    compiles_in_window: int = 0
    traced: bool = False
    spans: list[WindowSpan] = dataclasses.field(default_factory=list)
    trace: object = None                 # bench.trace.TraceSummary
    peak: dict | None = None             # bench/peaks.json entry of the device


@dataclasses.dataclass
class Cell:
    """A workload of ``BENCHMARK.json`` with its files loaded."""
    name: str
    chips: int
    cfg: dict
    traffic: dict
    metrics_e2e: list[dict]
    metrics_layer: list[dict]


def load_cell(root: pathlib.Path, workload: str) -> Cell:
    with open(root / "BENCHMARK.json") as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json: {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]

    def mine(ms):
        return [m for m in ms if workload in m.get("workloads", [workload])]

    return Cell(
        name=workload, chips=int(w["chips"]),
        cfg=deployment.load(root / conf["file"]),
        traffic=traffic.load(root / "bench" / "traffic" / f"{w['traffic']}.json"),
        metrics_e2e=mine(spec["end_to_end"]), metrics_layer=mine(spec["per_layer"]))


def load_module(root: pathlib.Path, kind: str, name: str):
    """``bench/<kind>/<name>.py`` under ``root``, loaded by its path."""
    key = f"bench.{kind}.{name}"
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            key, root / "bench" / kind / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[key]
            raise
    return sys.modules[key]


def read_metrics(root: pathlib.Path, metrics: list[dict], run: Run) -> dict:
    """Each metric's reader, found by its name up to the first dot; a
    reader that finds nothing returns None and the metric is left out."""
    out = {}
    for m in metrics:
        v = load_module(root, "metrics", m["name"].split(".")[0]).read(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# instrumentation
class Compiles:
    """Backend compile requests (persistent-cache hits included), by host
    time, from a ``jax.monitoring`` listener."""

    def __init__(self):
        self.at: list[float] = []
        self.hits = 0

    def _duration(self, event, duration, **kw):
        from jax._src import dispatch
        if event == dispatch.BACKEND_COMPILE_EVENT:
            self.at.append(time.perf_counter())

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.at if t0 <= t <= t1)


@contextlib.contextmanager
def spans(eng, sink: list[WindowSpan]):
    """Wrap the engine's flush, its policy's place and the device call in
    host spans; one ``WindowSpan`` per placed window goes to ``sink``."""
    import jax
    from repro.kernels.placement import ops

    cur: dict = {}
    flush0, place0, call0 = eng.flush, eng.policy.place, ops.greedy_window

    def flush():
        cur.update(device_s=0.0, device_calls=0)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.flush"):
            res = flush0()
        if res is not None:
            sink.append(WindowSpan(time.perf_counter() - t0, res.scheduling_s,
                                   cur["device_s"], cur["device_calls"],
                                   len(res.tasks)))
        return res

    def place(*a, **kw):
        with jax.profiler.TraceAnnotation("bench.place"):
            return place0(*a, **kw)

    def call(*a, **kw):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.greedy_window"):
            out = call0(*a, **kw)
        cur["device_s"] += time.perf_counter() - t0
        cur["device_calls"] += 1
        return out

    eng.flush, eng.policy.place, ops.greedy_window = flush, place, call
    try:
        yield
    finally:
        del eng.flush, eng.policy.place
        ops.greedy_window = call0


# ---------------------------------------------------------------------------
# the system under test and its traffic
class Deployment:
    def __init__(self, cfg: dict):
        self.cfg = cfg
        self.fleet = deployment.machines(cfg)
        self.names = [m.name for m in self.fleet]
        self.profiles = deployment.profiles(cfg, self.fleet)
        self.eps = deployment.endpoint_specs(self.fleet)

    def engine(self, tr: dict):
        from repro.core.engine import OnlineEngine
        from repro.core.policy import get_policy

        pol = self.cfg["policy"]
        policy = get_policy(pol["name"], heuristics=tuple(pol["heuristics"]),
                            engine=pol["engine"])
        return OnlineEngine(
            self.eps, None, policy=policy, alpha=pol["alpha"],
            engine=pol["engine"], window_s=tr["window_s"],
            max_batch=tr["max_batch"], monitoring=False,
            store=deployment.seeded_store(self.cfg, self.eps, self.profiles))


def _specs(rows):
    from repro.core.scheduler import TaskSpec
    return [TaskSpec(id=i, fn=fn, inputs=inp) for i, fn, inp in rows]


def batch_window(dep, tr, seed, k, stream=1):
    rng = np.random.default_rng([seed, stream, k])
    return traffic.tasks(tr, dep.cfg["functions"], dep.names, tr["window_tasks"],
                         rng, prefix=f"w{k}t")


def closed_batch(eng, windows, seconds):
    """Windows back to back until ``seconds`` have passed; returns (tasks
    placed, seconds from start to the last window's return, windows sent)."""
    placed = 0
    t0 = time.perf_counter()
    t_last = t0
    k = 0
    while k < len(windows) and time.perf_counter() - t0 < seconds:
        res = eng.submit_many(windows[k], when=time.perf_counter() - t0)
        t_last = time.perf_counter()
        placed += sum(len(r.tasks) for r in res)
        k += 1
    return placed, t_last - t0, k, t0


def open_loop(eng, pool, arr, window_s):
    """Send ``pool[i]`` at ``arr[i]`` seconds on the wall clock, tick the
    engine's window timer, and wait for the last window after the close.
    Returns (return time of each task's flush, submit offsets, start)."""
    n = len(arr)
    pos = {t.id: i for i, t in enumerate(pool)}
    ret = np.full(n, np.nan)
    sent = np.full(n, np.nan)

    def record(res):
        if res is not None:
            t = time.perf_counter()
            for task in res.tasks:
                ret[pos[task.id]] = t

    i = 0
    first_pending = 0.0
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        while i < n and arr[i] <= now:
            if not eng.pending:
                first_pending = now
            sent[i] = now
            record(eng.submit(pool[i], when=now))
            i += 1
            now = time.perf_counter() - t0
        record(eng.tick(now))
        if i >= n and not eng.pending:
            break
        nxt = arr[i] if i < n else math.inf
        if eng.pending:
            nxt = min(nxt, first_pending + window_s)
        dt = nxt - (time.perf_counter() - t0)
        if dt > 0:
            time.sleep(dt)
    return ret, sent, t0


# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Traffic:
    """One run's traffic, made from the seed before the window opens."""
    rows: list                      # (id, fn, inputs) of every task, in order
    windows: list | None = None     # closed loop: TaskSpec windows
    pool: list | None = None        # open loop: TaskSpecs ...
    arrivals: np.ndarray | None = None   # ... and their offsets (s)


@dataclasses.dataclass
class Measured:
    t0: float
    t1: float
    attempted: int
    submitted: list[str]
    placed_in_window: int
    window_s: float
    latencies_s: np.ndarray | None = None


def make_traffic(dep, tr, seed, window) -> Traffic:
    if tr["kind"] == "closed_batch":
        # enough windows for the run at the shortest window expected
        n_win = int(math.ceil(window / tr["min_window_s"])) + 1
        rows = [r for k in range(n_win) for r in batch_window(dep, tr, seed, k)]
        n = tr["window_tasks"]
        return Traffic(rows, windows=[_specs(rows[k * n:(k + 1) * n])
                                      for k in range(n_win)])
    if tr["kind"] == "open_poisson":
        rng = np.random.default_rng([seed, 3])
        arr = traffic.arrivals(tr["rate_per_s"], window, rng)
        rows = traffic.tasks(tr, dep.cfg["functions"], dep.names, len(arr), rng,
                             prefix="s")
        return Traffic(rows, pool=_specs(rows), arrivals=arr)
    raise ValueError(f"unknown traffic kind {tr['kind']!r}")


def warm_up(dep, tr, seed):
    """The cell's own traffic through an engine of its own, so ``auto``
    resolves on a real first window and the cell's shapes compile."""
    warm = dep.engine(tr)
    if tr["kind"] == "closed_batch":
        for k in range(int(tr["warmup_windows"])):
            warm.submit_many(_specs(batch_window(dep, tr, seed, k, stream=4)), when=0.0)
    else:
        rng = np.random.default_rng([seed, 2])
        arr = traffic.arrivals(tr["rate_per_s"], tr["warmup_seconds"], rng)
        pool = _specs(traffic.tasks(tr, dep.cfg["functions"], dep.names, len(arr),
                                    rng, prefix="u"))
        open_loop(warm, pool, arr, tr["window_s"])
    return warm.engine


def drive(eng, tr, trf: Traffic, window: float, log=print) -> Measured:
    """The measured window."""
    if tr["kind"] == "closed_batch":
        placed, span, sent, t0 = closed_batch(eng, trf.windows, window)
        if sent == len(trf.windows):
            log(f"warning: the pool of {sent} windows ran out before {window} s "
                f"had passed")
        return Measured(t0, t0 + span, sent * tr["window_tasks"],
                        [t.id for w in trf.windows[:sent] for t in w], placed, span)
    ret, sent, t0 = open_loop(eng, trf.pool, trf.arrivals, tr["window_s"])
    # a task never placed waited at least until the loop gave up on it
    ret = np.where(np.isnan(ret), time.perf_counter(), ret)
    arr = trf.arrivals
    late = sent - arr
    log(f"generator lateness: p50 {np.percentile(late, 50) * 1e3:.3f} ms, "
        f"p95 {np.percentile(late, 95) * 1e3:.3f} ms, max {late.max() * 1e3:.3f} ms")
    t1 = float(np.nanmax(ret)) if len(ret) else t0
    return Measured(t0, t1, len(arr), [t.id for t in trf.pool],
                    int(np.sum(ret <= t0 + window)), t1 - t0, ret - (t0 + arr))


def setup_jax(root: pathlib.Path, chips: int):
    """The devices, checked; the persistent compile cache, pointed."""
    import jax

    devs = jax.devices()
    if devs[0].platform != PLATFORM or len(devs) < chips:
        raise NoDevice(f"needs {chips} {PLATFORM} chip(s); JAX found {len(devs)} "
                       f"{devs[0].platform} ({devs[0].device_kind})")
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return devs, cache


def reference_of(root: pathlib.Path, cfg: dict):
    return load_module(root, "references", cfg["reference"]).Reference


def run(root: pathlib.Path, workload: str, seed: int, seconds: float,
        traced: bool, t_process: float, trace_dir: str | None = None,
        log=print) -> dict:
    """One run; returns the result line's object.  Raises ``NoDevice``
    before any result exists when the chips are not there."""
    cell = load_cell(root, workload)
    devs, cache = setup_jax(root, cell.chips)
    import jax
    from repro.kernels.placement import ops

    tr = cell.traffic
    dep = Deployment(cell.cfg)
    window = min(seconds, tr["trace_seconds"]) if traced else seconds
    log(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}; "
        f"compile cache {cache}; {len(dep.eps)} endpoints; window {window} s")
    with Compiles() as compiles:
        ops.reset_window_stats()
        resolved = warm_up(dep, tr, seed)
        log(f"warm-up: engine auto -> {resolved}; windows {dict(ops.WINDOW_STATS)}; "
            f"{len(compiles.at)} backend compile requests, {compiles.hits} cache hits")
        trf = make_traffic(dep, tr, seed, window)
        eng = dep.engine(tr)
        ops.reset_window_stats()
        rec = Run(workload=workload, kind=tr["kind"], seconds=window, setup_s=0.0,
                  heuristics=len(cell.cfg["policy"]["heuristics"]),
                  endpoints=len(dep.eps), traced=traced)
        trace_path = None
        if traced:
            trace_path = trace_dir or str(root / ".bench_trace" / workload)
            shutil.rmtree(trace_path, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(trace_path, profiler_options=opts)
        rec.setup_s = time.perf_counter() - t_process
        with contextlib.ExitStack() as stack:
            if traced:
                stack.enter_context(spans(eng, rec.spans))
                stack.enter_context(jax.profiler.TraceAnnotation("bench.window"))
            m = drive(eng, tr, trf, window, log)
        if traced:
            jax.profiler.stop_trace()
        rec.compiles_in_window = compiles.between(m.t0, m.t1)
    rec.placed_in_window = m.placed_in_window
    rec.window_s = m.window_s
    rec.latencies_s = m.latencies_s
    log(f"window: engine auto -> {eng.engine}; WINDOW_STATS {dict(ops.WINDOW_STATS)}; "
        f"COMPILE_STATS {dict(ops.COMPILE_STATS)}; {len(eng.windows)} windows; "
        f"{rec.compiles_in_window} backend compile requests in the window")

    mem = [d.memory_stats() or {} for d in devs[:cell.chips]]
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": cell.chips,
              "memory_peak_bytes": int(max(m_.get("peak_bytes_in_use", 0) for m_ in mem))}
    placed = check.from_engine(eng.windows)
    del eng
    out = {}
    if traced:
        from bench import trace as trace_mod

        t_read = time.perf_counter()
        rec.trace = trace_mod.reduce(trace_mod.find(trace_path))
        if trace_dir is None:
            shutil.rmtree(trace_path, ignore_errors=True)
        rec.peak = _peak(root, devs[0].device_kind)
        device["busy_s"] = rec.trace.busy_s
        device["window_s"] = rec.trace.window_s
        top = sorted(rec.trace.ops_s.items(), key=lambda kv: -kv[1])[:10]
        out["breakdown"] = {"device_ops": [[k, v] for k, v in top],
                            "idle_gaps": [[k, v] for k, v in rec.trace.idle_by_span]}
        log(f"trace: read in {time.perf_counter() - t_read:.1f} s; window "
            f"{rec.trace.window_s:.6f} s, busy {rec.trace.busy_s:.6f} s, programs "
            f"{rec.trace.module_n}, {rec.trace.ops_read} operation events read, "
            f"buffer dropped: {rec.trace.dropped}; longest idle gaps {rec.trace.gaps[:5]}")
        metrics = read_metrics(root, cell.metrics_layer, rec)
    else:
        metrics = read_metrics(root, cell.metrics_e2e, rec)

    t_ref = time.perf_counter()
    table = {i: (fn, inp, 0.0) for i, fn, inp in trf.rows}
    numbers, margin = check.compare(reference_of(root, cell.cfg)(cell.cfg), placed,
                                    m.submitted, table, tr["max_batch"])
    limits = check.load_limits(root, workload)
    correct = check.verdict(numbers, limits)
    log(f"reference: {len(placed)} windows in {time.perf_counter() - t_ref:.3f} s; "
        f"smallest best/runner-up margin {margin!r}")
    done = {t for w in placed for t in w.ids}
    result = {"correct": bool(correct), "attempted": int(m.attempted),
              "failed": int(sum(1 for i in m.submitted if i not in done)),
              "metrics": metrics, "device": device}
    result.update(out)
    result["checks"] = {k: {"value": numbers[k], "limit": limits[k]} for k in check.NUMBERS}
    return result


def _peak(root: pathlib.Path, kind: str) -> dict:
    with open(root / "bench" / "peaks.json") as f:
        peaks = json.load(f)
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} has no entry in bench/peaks.json")
    return peaks[kind]


def main(argv=None, t_process: float | None = None,
         root: pathlib.Path | None = None) -> int:
    import argparse

    t_process = time.perf_counter() if t_process is None else t_process
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here instead of deleting it")
    args = ap.parse_args(argv)
    root = root or pathlib.Path(__file__).resolve().parents[1]

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    try:
        res = run(root, args.workload, args.seed, args.seconds, bool(args.trace),
                  t_process, args.trace_dir, log)
    except NoDevice as e:
        log(f"bench: {args.workload}: {e}")
        return 1
    for k, v in res["checks"].items():
        log(f"check {k}: {v['value']!r} (limit {v['limit']!r})")
    print(json.dumps(res))
    return 0
