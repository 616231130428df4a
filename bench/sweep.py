"""Find the knee of an open-loop cell: the highest offered rate at which
the tail stays under the limit and no backlog grows.

    python3 bench/sweep.py --workload sebs256-stream --seed <n> --seconds <s> \\
        --rates 1000,1200,1400

Runs in one process on the chip the cell names: one warm-up, then each
rate on a fresh engine with its own arrivals.  For each rate it prints
the offered rate, the tasks placed before the close per second, the p50
and p95 of submit-to-placement over every task due, the p95 of the last
quarter of arrivals (a backlog that grows shows there first) and how
late the generator ran.  The knee it reports is the highest rate whose
p95 is under ``--limit-ms`` and whose last quarter is no worse than that;
the cell's traffic file then fixes its rate as a plain number.
"""
import argparse
import json
import pathlib
import sys
import time

_ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (str(_ROOT), str(_ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from bench import harness, traffic  # noqa: E402


def one_rate(dep, tr, rate, seconds, seed):
    rng = np.random.default_rng([seed, 5, int(rate)])
    arr = traffic.arrivals(rate, seconds, rng)
    pool = harness._specs(traffic.tasks(tr, dep.cfg["functions"], dep.names,
                                        len(arr), rng, prefix=f"r{int(rate)}t"))
    eng = dep.engine(tr)
    ret, sent, t0 = harness.open_loop(eng, pool, arr, tr["window_s"])
    lat = (ret - (t0 + arr)) * 1e3
    q = len(arr) * 3 // 4
    return {
        "offered_per_s": rate,
        "placed_per_s": float(np.sum(ret <= t0 + seconds)) / seconds,
        "p50_ms": float(np.percentile(lat, 50)),
        "p95_ms": float(np.percentile(lat, 95)),
        "p95_last_quarter_ms": float(np.percentile(lat[q:], 95)),
        "late_p95_ms": float(np.percentile((sent - arr) * 1e3, 95)),
        "windows": len(eng.windows),
        "engine": eng.engine,
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True, help="comma-separated tasks/s")
    ap.add_argument("--limit-ms", type=float, default=1000.0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(_ROOT, args.workload)
    import jax

    dev = jax.devices()[0]
    if dev.platform != harness.PLATFORM:
        print(f"sweep: needs a {harness.PLATFORM} chip, found {dev.platform}",
              file=sys.stderr)
        return 1
    import os
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(_ROOT / ".jax_cache"))
    tr = cell.traffic
    dep = harness.Deployment(cell.cfg)
    t0 = time.perf_counter()
    one_rate(dep, tr, tr["rate_per_s"], tr["warmup_seconds"], args.seed)
    print(f"warm-up {time.perf_counter() - t0:.1f} s", file=sys.stderr)
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        row = one_rate(dep, tr, rate, args.seconds, args.seed)
        rows.append(row)
        print(json.dumps(row), flush=True)
    ok = [r["offered_per_s"] for r in rows
          if r["p95_ms"] <= args.limit_ms and r["p95_last_quarter_ms"] <= args.limit_ms]
    print(json.dumps({"knee_per_s": max(ok) if ok else None,
                      "rate_at_0.8_knee": 0.8 * max(ok) if ok else None}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
