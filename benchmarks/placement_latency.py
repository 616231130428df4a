"""Placement-latency SLO benchmark (per-decision percentiles).

GreenFaaS-as-a-service needs a latency story, not just throughput: a
placement decision sits on the critical path of every function
invocation.  This harness treats per-decision latency as a first-class,
gated metric (the green-microbench Prometheus protocol's p95-per-service
counters are the model).  Two sections, emitted into
``BENCH_latency.json``:

* **latency** — a sustained-Poisson arrival stream through the
  planner-only :class:`OnlineEngine`.  Every window's placement call is
  timestamped; its wall time divided by the window's task count is the
  ms-per-decision sample (one per task, so percentiles weight busy
  windows correctly).  Reports p50/p95/p99 ms-per-decision plus the max
  rank-refresh stall, across engines (delta / soa / jax / auto) and
  fleet sizes (4 -> 32 endpoints).  The jax engine pays a per-window-
  shape XLA compile on first sight; the elementwise-min over repeats
  reports its warm latency (repeat 1 absorbs the compiles), which is
  exactly the sustained-service number the SLO cares about.
* **long_stream** — a multi-epoch fork-join DAG campaign (>= 16k tasks
  on full runs) replayed under the DAG-aware lookahead policy with
  live-state pruning on vs off.  Placements must be *identical* (the
  pruning parity guarantee) and the pruned replay must be strictly
  faster: without pruning every window's timeline snapshot and state
  clone pays O(total-ever-submitted); with it they pay O(live).

Acceptance (full runs; smoke cells check parity only): pruned strictly
faster than unpruned at >= 16k submitted tasks with assignment parity
and bitwise-equal final metrics.

CLI::

    python benchmarks/placement_latency.py                 # full sweep
    python benchmarks/placement_latency.py --tasks 400     # smoke cell
    python benchmarks/placement_latency.py --out BENCH_latency.json
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

if __package__ in (None, ""):   # bare run: python benchmarks/placement_latency.py
    _ROOT = pathlib.Path(__file__).resolve().parents[1]
    try:
        import repro  # noqa: F401
    except ModuleNotFoundError:
        sys.path.insert(0, str(_ROOT / "src"))

from repro.compile_cache import enable_compile_cache
from repro.core.endpoint import scaled_testbed
from repro.core.engine import OnlineEngine
from repro.core.scheduler import TaskSpec, auto_engine
from repro.core.testbed import BASE_PROFILES, SEBS_FUNCTIONS
from repro.core.predictor import TaskProfileStore

# fleet-size sweep: scaled_testbed multiplier -> 4/8/16/32 endpoints
FLEET_SWEEP = (1, 2, 4, 8)
ENGINES = ("delta", "soa", "jax", "auto")
LONG_STREAM_TASKS = 16384


def _base_machine(name: str) -> tuple[str, int]:
    if "_" in name:
        base, k = name.rsplit("_", 1)
        return base, int(k)
    return name, 0


def _seeded_store(eps):
    store = TaskProfileStore(eps)
    for fn in SEBS_FUNCTIONS:
        for ep in eps:
            base, k = _base_machine(ep.name)
            rt, w = BASE_PROFILES[fn][base]
            rt = rt / (1.0 + 0.02 * k)
            for _ in range(3):
                store.record(fn, ep.name, rt, rt * w)
    return store


def _poisson_arrivals(n: int, rate_hz: float, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return np.cumsum(rng.exponential(1.0 / rate_hz, size=n))


# ---------------------------------------------------------------------------
# Section 1: sustained-Poisson per-decision latency percentiles
# ---------------------------------------------------------------------------


def _latency_cell(engine: str, mult: int, n_tasks: int, rate_hz: float,
                  window_s: float, seed: int = 0) -> dict:
    eps = scaled_testbed(mult)
    store = _seeded_store(eps)
    # lookahead policy so the stream exercises the rank-refresh path (the
    # max_stall_ms metric): ~10% of tasks chain onto an earlier one
    eng = OnlineEngine(
        eps, None, policy="lookahead_mhra", alpha=0.5, window_s=window_s,
        max_batch=256, store=store, monitoring=False, engine=engine,
    )
    arrivals = _poisson_arrivals(n_tasks, rate_hz, seed=seed)
    rng = np.random.default_rng(seed + 1)
    dep_draw = rng.random(n_tasks)
    dep_of = rng.integers(1, 64, size=n_tasks)
    inputs = ((eps[0].name, 1, 200e6, True),)
    for i, arr in enumerate(arrivals):
        eng.tick(float(arr))
        deps = ()
        if dep_draw[i] < 0.1 and i > 0:
            deps = (f"t{max(0, i - int(dep_of[i]))}",)
        eng.submit(
            TaskSpec(id=f"t{i}", fn=SEBS_FUNCTIONS[i % len(SEBS_FUNCTIONS)],
                     inputs=inputs, deps=deps,
                     dep_bytes=1e6 if deps else 0.0),
            when=float(arr),
        )
    eng.drain()

    # one sample per *decision*: a window's placement wall time is shared
    # by every task it placed, so busy windows contribute more samples
    per_decision_ms = np.concatenate([
        np.full(len(w.tasks), w.scheduling_s / len(w.tasks) * 1e3)
        for w in eng.windows
    ])
    p50, p95, p99 = np.percentile(per_decision_ms, (50.0, 95.0, 99.0))
    stats = eng.dag.refresh_stats()
    s = eng.summary()
    return dict(
        policy=f"{engine}",                 # diff_eval keys rows on "policy"
        engine=engine,
        resolved=eng.engine,                # what "auto" picked
        n_endpoints=len(eps),
        n_tasks=s.tasks,
        windows=s.windows,
        p50_ms=float(p50), p95_ms=float(p95), p99_ms=float(p99),
        max_stall_ms=float(stats["max_s"] * 1e3),
        rank_refreshes=int(stats["refreshes"]),
        total_scheduling_s=s.scheduling_s,
    )


def run_latency(fleets=FLEET_SWEEP, n_tasks=4096, rate_hz=64.0,
                window_s=0.25, engines=ENGINES, seed=0, repeats=3):
    """workload-shaped payload rows: one workload per fleet size, one row
    per engine (so diff_eval trends each (fleet, engine) cell).  Each
    cell is run ``repeats`` times and reports the elementwise-min
    percentiles — machine noise inflates single-run tails by tens of
    percent at these microsecond scales, and the min is the standard
    capability estimate (same protocol as scheduler_overhead.py)."""
    workloads = []
    auto_ok = True
    for mult in fleets:
        rows = []
        for engine in engines:
            reps = [
                _latency_cell(engine, mult, n_tasks, rate_hz, window_s, seed)
                for _ in range(repeats)
            ]
            best = reps[0]
            for r in reps[1:]:
                for k in ("p50_ms", "p95_ms", "p99_ms", "max_stall_ms"):
                    best[k] = min(best[k], r[k])
                best["total_scheduling_s"] = min(
                    best["total_scheduling_s"], r["total_scheduling_s"]
                )
            rows.append(best)
        by = {r["engine"]: r for r in rows}
        if "auto" in by:
            best = min(r["p50_ms"] for r in rows if r["engine"] != "auto")
            # sanity: auto must never be the *wrong engine*.  Gate on the
            # stable p50 with 10% headroom — single-run p99 tails jitter
            # by tens of percent at these microsecond scales, so the
            # tight 5% acceptance gate lives in the scaling sweep
            # (scheduler_overhead.py), which times min-of-repeats
            auto_ok = auto_ok and by["auto"]["p50_ms"] <= 1.10 * best
        workloads.append(dict(
            workload=f"poisson_{rows[0]['n_endpoints']}ep", rows=rows,
        ))
    return workloads, auto_ok


# ---------------------------------------------------------------------------
# Section 2: long-stream replay, pruning on vs off
# ---------------------------------------------------------------------------


def _epoch_dag_tasks(n_tasks: int, width: int = 127) -> list[TaskSpec]:
    """Fork-join epochs: ``width`` workers fan out of the previous epoch's
    reducer (dep_bytes payloads, so retirement must keep producer records
    alive for transfer billing), then a reducer joins them."""
    tasks: list[TaskSpec] = []
    epoch = 0
    while len(tasks) < n_tasks:
        prev_reduce = f"r{epoch - 1}" if epoch else None
        workers = []
        for j in range(width):
            if len(tasks) >= n_tasks - 1:
                break
            tid = f"e{epoch}_{j}"
            tasks.append(TaskSpec(
                id=tid, fn=SEBS_FUNCTIONS[j % len(SEBS_FUNCTIONS)],
                deps=(prev_reduce,) if prev_reduce else (),
                dep_bytes=5e6,
            ))
            workers.append(tid)
        tasks.append(TaskSpec(
            id=f"r{epoch}", fn=SEBS_FUNCTIONS[epoch % len(SEBS_FUNCTIONS)],
            deps=tuple(workers), dep_bytes=1e6,
        ))
        epoch += 1
    return tasks


def _long_stream_cell(tasks, eps, prune: bool) -> tuple[dict, dict, tuple]:
    store = _seeded_store(eps)
    eng = OnlineEngine(
        eps, None, policy="lookahead_mhra", alpha=0.5, window_s=1e9,
        max_batch=10**9, store=store, monitoring=False, engine="delta",
        prune=prune, retain_windows=8,
    )
    t0 = time.perf_counter()
    eng.submit_many(tasks, when=0.0)
    eng.drain()
    wall = time.perf_counter() - t0
    s = eng.summary()
    assignments = dict.fromkeys([t.id for t in tasks])
    for tid, (ep, _end) in eng.completed.items():
        assignments[tid] = ep
    stats = eng.dag.refresh_stats()
    row = dict(
        policy="pruned" if prune else "unpruned",
        seconds=s.scheduling_s, wall_seconds=wall, tasks=s.tasks,
        windows=s.windows, live_nodes_end=len(eng.dag),
        retired=eng.dag.retired, timeline_end=len(eng.state.timeline),
        rank_refreshes=int(stats["refreshes"]),
        max_stall_ms=float(stats["max_s"] * 1e3),
    )
    return row, assignments, eng.state.metrics()


def run_long_stream(n_tasks=LONG_STREAM_TASKS, mult=2):
    eps = scaled_testbed(mult)
    tasks = _epoch_dag_tasks(n_tasks)
    on, a_on, m_on = _long_stream_cell(tasks, eps, prune=True)
    off, a_off, m_off = _long_stream_cell(tasks, eps, prune=False)
    parity = a_on == a_off and m_on == m_off      # bitwise metrics equality
    speedup = off["seconds"] / max(on["seconds"], 1e-9)
    on["speedup_vs_unpruned"] = speedup
    off["speedup_vs_unpruned"] = 1.0
    return dict(workload="long_stream", rows=[on, off]), parity, speedup


# ---------------------------------------------------------------------------


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tasks", type=int, default=None,
                    help="smoke mode: N Poisson tasks on the 4-endpoint "
                         "testbed and an N-task long-stream cell (speedup "
                         "gates are skipped; parity always counts)")
    ap.add_argument("--rate", type=float, default=64.0,
                    help="Poisson arrival rate, tasks/s (default 64)")
    ap.add_argument("--window", type=float, default=0.25,
                    help="arrival-window seconds (default 0.25)")
    ap.add_argument("--out", default="BENCH_latency.json",
                    help="result JSON path (default: BENCH_latency.json)")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def _run_all(args):
    enable_compile_cache()
    smoke = args.tasks is not None
    if smoke:
        fleets = (1,)
        n_poisson = args.tasks
        n_long = max(args.tasks, 256)
    else:
        fleets = FLEET_SWEEP
        n_poisson = 4096
        n_long = LONG_STREAM_TASKS

    workloads, auto_ok = run_latency(
        fleets=fleets, n_tasks=n_poisson, rate_hz=args.rate,
        window_s=args.window, seed=args.seed,
    )
    print(f"{'fleet':>6}{'engine':>8}{'resolved':>10}{'p50_ms':>9}"
          f"{'p95_ms':>9}{'p99_ms':>9}{'stall_ms':>10}")
    for wl in workloads:
        for r in wl["rows"]:
            print(f"{r['n_endpoints']:>4}ep{r['engine']:>9}"
                  f"{r['resolved']:>10}{r['p50_ms']:>9.3f}{r['p95_ms']:>9.3f}"
                  f"{r['p99_ms']:>9.3f}{r['max_stall_ms']:>10.3f}")
    print(f"auto within 10% of best fixed engine (p50): "
          f"{'OK' if auto_ok else 'FAILED'}\n")

    ls, ls_parity, ls_speedup = run_long_stream(n_tasks=n_long,
                                                mult=1 if smoke else 2)
    for r in ls["rows"]:
        print(f"long_stream {r['policy']:<9} sched={r['seconds']:.3f}s "
              f"windows={r['windows']} live_end={r['live_nodes_end']} "
              f"retired={r['retired']} timeline_end={r['timeline_end']}")
    ls_gate = ls_speedup > 1.0
    print(f"long-stream parity (assignments + bitwise metrics): "
          f"{'OK' if ls_parity else 'FAILED'}; pruned faster: "
          f"{'OK' if ls_gate else 'FAILED'} ({ls_speedup:.2f}x)")

    payload = dict(
        workloads=workloads + [ls],
        gates=dict(
            auto_within_10pct_p50=auto_ok,
            long_stream_parity=ls_parity,
            long_stream_pruned_faster=ls_gate,
            long_stream_speedup=ls_speedup,
        ),
        config=dict(rate_hz=args.rate, window_s=args.window,
                    smoke=smoke, seed=args.seed),
    )
    pathlib.Path(args.out).write_text(json.dumps(payload, indent=2) + "\n")
    print(f"wrote {args.out}")

    # smoke cells are too small/noisy for the speedup and 5% gates
    ok = ls_parity and (smoke or (ls_gate and auto_ok))
    rows = []
    for wl in workloads:
        for r in wl["rows"]:
            rows.append((
                f"latency_{r['engine']}_{r['n_endpoints']}ep",
                r["p99_ms"] * 1e3,
                f"p50={r['p50_ms']:.3f}ms p99={r['p99_ms']:.3f}ms",
            ))
    for r in ls["rows"]:
        rows.append((f"long_stream_{r['policy']}", r["seconds"] * 1e6,
                     f"vs_unpruned={r.get('speedup_vs_unpruned', 1.0):.2f}x"))
    return rows, ok


def main(argv=None):
    """Harness entry (benchmarks/run.py): always returns the row list."""
    rows, _ = _run_all(_parse(argv))
    return rows


def cli(argv=None) -> int:
    """CLI entry: non-zero exit on parity/gate failure."""
    _, ok = _run_all(_parse(argv))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(cli())
