"""Where the MHRA normalizers' two list-scheduling forms cross over.

``_normalizers_fast`` runs the window's list scheduling on each endpoint
alone either endpoint by endpoint on a heap (``_runs_by_heap``) or for
the whole fleet at once on a slot matrix (``_runs_by_matrix``), picked by
fleet width against ``scheduler.NORMALIZER_MATRIX_MIN_ENDPOINTS``.  This
script times both forms, and the whole function as it stands, over fleet
widths and window depths, checks that the two forms return the same
doubles, and prints one JSON line per (endpoints, tasks).  The crossover
is a property of the host CPU, so run it on the host that places:

    PYTHONPATH=src python benchmarks/normalizer_forms.py
"""
from __future__ import annotations

import json
import time

import numpy as np

from repro.core import scheduler as sched
from repro.core.endpoint import scaled_testbed, table1_testbed
from repro.core.predictor import TaskProfileStore
from repro.core.testbed import SEBS_FUNCTIONS
from repro.core.transfer import TransferModel

WIDTHS = (4, 6, 8, 12, 16, 32)
DEPTHS = (16, 256, 2048, 8192)


def _fleet(width: int):
    """The 4-endpoint Table-I testbed, or the first ``width`` endpoints of
    the replicated one, with one store record per (function, endpoint)."""
    if width == 4:
        eps = table1_testbed()
    else:
        eps = scaled_testbed(max(2, -(-width // 4)))[:width]
    store = TaskProfileStore(eps)
    rng = np.random.default_rng(0)
    for fn in SEBS_FUNCTIONS:
        for ep in eps:
            store.record(fn, ep.name, float(rng.uniform(1, 20)),
                         float(rng.uniform(5, 200)))
    return eps, store


def _best_ms(fn, reps: int) -> float:
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def main() -> None:
    for width in WIDTHS:
        eps, store = _fleet(width)
        tm = TransferModel(eps)
        shared = (eps[0].name, 1, 2e8, True)
        for n in DEPTHS:
            tasks = [sched.TaskSpec(id=f"t{i}", fn=SEBS_FUNCTIONS[i % 7],
                                    inputs=(shared,)) for i in range(n)]
            table = sched.PredictionTable(tasks, eps, store)
            ready = [float(ep.queue_delay_s) for ep in eps]
            nbs = [0.0] * n
            reps = max(3, min(200, 20000 // n))

            def matrix():
                table._rtT = None     # the delta engine builds no transposed table
                return sched._runs_by_matrix(eps, table, ready, nbs)

            def heap():
                return sched._runs_by_heap(eps, table, ready, nbs)

            if heap() != matrix():
                raise SystemExit(f"forms differ at {width} endpoints x {n} tasks")
            print(json.dumps({
                "endpoints": width, "tasks": n,
                "heap_ms": _best_ms(heap, reps),
                "matrix_ms": _best_ms(matrix, reps),
                "normalizers_ms": _best_ms(
                    lambda: sched._normalizers_fast(tasks, eps, table, tm), reps),
            }), flush=True)


if __name__ == "__main__":
    main()
