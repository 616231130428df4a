"""Benchmark harness — one module per paper table/figure.

    python -m benchmarks.run [--full] [--only NAME]

Runnable bare from the repo root (src/ is added to ``sys.path`` when the
package isn't installed, matching the pyproject ``pythonpath`` the test
suite uses).  Prints ``name,us_per_call,derived`` CSV at the end (one row
per headline metric).  --full uses the paper-size workload (1792 tasks);
the default uses reduced sizes so the whole suite finishes quickly on one
CPU core.
"""
from __future__ import annotations

import argparse
import pathlib
import sys
import time

_ROOT = pathlib.Path(__file__).resolve().parents[1]
try:
    import repro  # noqa: F401
except ModuleNotFoundError:  # bare run from a checkout: add src/ ourselves
    sys.path.insert(0, str(_ROOT / "src"))
if __package__ in (None, ""):
    # invoked by path (python benchmarks/run.py): make the sibling
    # benchmark modules importable as the `benchmarks` package
    sys.path.insert(0, str(_ROOT))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    n_per = 48 if args.quick else 256
    n_alpha = 32 if args.quick else 128

    from benchmarks import (
        alpha_sweep,
        molecular_design,
        monitoring_overhead,
        placement_latency,
        placement_strategies,
        profile_tasks,
        scheduler_overhead,
    )

    def _paper_eval():
        """Tiny paper_eval harness cell: policy table + DAG parity gate
        (the standalone run is `python examples/paper_eval.py`)."""
        from repro.core.evaluate import (
            evaluate_trace, run_policy, verify_dag_order,
        )
        from repro.workloads import moldesign_dag_workload, synthetic_edp_workload

        n = 448 if args.full else 112
        syn_res = evaluate_trace(synthetic_edp_workload(n_tasks=n, seed=0))
        mhra = syn_res.row("mhra")
        best = min(syn_res.single_site_rows(), key=lambda r: r.edp)
        dag = moldesign_dag_workload(waves=2, docks_per_wave=8,
                                     sims_per_wave=8, infers_per_wave=12)
        d, wins = run_policy(dag, "mhra", engine="delta", alpha=0.3,
                             return_windows=True)
        s = run_policy(dag, "mhra", engine="soa", alpha=0.3)
        assert d.assignments == s.assignments, "delta/soa DAG divergence"
        edges = verify_dag_order(wins)
        return [
            ("eval_mhra_edp_vs_best_site", 0.0,
             f"{mhra.edp / best.edp:.2f}x"),
            ("eval_dag_parity", 0.0, f"{edges} edges, engines agree"),
        ]

    suites = {
        "profile_tasks": lambda: profile_tasks.main(),
        "monitoring_overhead": lambda: monitoring_overhead.main(),
        # harness mode: Table-IV sizes only (the 100k scaling sweep is the
        # standalone `python benchmarks/scheduler_overhead.py` run)
        "scheduler_overhead": lambda: scheduler_overhead.main(
            [] if args.full else ["--tasks", "1792"]
        ),
        # per-decision latency SLO cell; --full runs the whole fleet sweep
        # + the 16k long-stream pruning replay, default is one smoke cell
        "placement_latency": lambda: placement_latency.main(
            (["--out", "BENCH_latency.json"] if args.full
             else ["--tasks", "192" if args.quick else "640",
                   "--out", "/tmp/BENCH_latency_smoke.json"])
        ),
        "placement_strategies": lambda: placement_strategies.main(n_per=n_per),
        "alpha_sweep": lambda: alpha_sweep.main() if not args.quick else _alpha(n_alpha),
        "molecular_design": lambda: molecular_design.main(),
        "paper_eval": _paper_eval,
    }

    def _alpha(n):
        from benchmarks import alpha_sweep as a

        rows = a.run(n_per=n)
        lo, hi = rows[0], rows[-1]
        return [
            ("fig6_runtime_ratio_a1_vs_a0", 0.0,
             f"{hi['runtime_s'] / max(lo['runtime_s'], 1e-9):.2f}x"),
            ("fig6_energy_ratio_a1_vs_a0", 0.0,
             f"{hi['energy_kj'] / max(lo['energy_kj'], 1e-9):.2f}x"),
        ]

    rows: list[tuple] = []
    for name, fn in suites.items():
        if args.only and args.only != name:
            continue
        print(f"\n===== {name} =====", flush=True)
        t0 = time.perf_counter()
        try:
            out = fn() or []
        except Exception as e:  # noqa: BLE001 — keep the suite running
            print(f"[bench {name}] FAILED: {e!r}", file=sys.stderr)
            out = [(name, 0.0, f"FAILED:{type(e).__name__}")]
        wall = time.perf_counter() - t0
        rows.append((f"{name}_wall", wall * 1e6, f"{wall:.1f}s"))
        rows.extend(out)

    print("\nname,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
