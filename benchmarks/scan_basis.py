"""What the placement scan costs when its tasks group into runs, and when
every task starts a run of its own.

The scan (``ops._greedy_scan``) recomputes its run basis only on the steps
where some ordering heuristic starts a run: a stretch of tasks with the
same ``(fn, inputs, not_before)`` key.  Every heuristic orders a window by
per-function means, so in a plain window each function is one run (7 a
heuristic for the SeBS functions); distinct ``not_before`` floors make
every task a run of its own, the case in which the pass runs on every step.

For each fleet and window depth this script builds one window of SeBS
tasks on the replicated Table-I fleet (one shared 200 MB input staged on
endpoint 0, functions equally often in a seeded order), captures the
arguments ``mhra(engine="jax")`` hands to ``ops.greedy_window``, and times
two things on whatever device JAX has: ``greedy_window`` whole (transfer
up, scan, readback) and the scan alone on device-resident arguments.  It
prints one JSON line per (endpoints, tasks, form), with the device, the
steps on which a run starts, the first call's seconds (a compile, or a
load from the persistent cache) and the min and median of the repeats:

    PYTHONPATH=src python benchmarks/scan_basis.py
    PYTHONPATH=src python benchmarks/scan_basis.py --shapes 32x512 --reps 3
"""
from __future__ import annotations

import argparse
import json
import statistics
import time

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.endpoint import scaled_testbed
from repro.core.predictor import TaskProfileStore
from repro.core.scheduler import TaskSpec, mhra
from repro.core.testbed import BASE_PROFILES, SEBS_FUNCTIONS
from repro.core.transfer import TransferModel
from repro.kernels import dispatch
from repro.kernels.placement import ops

#: (endpoints, tasks): the batch cell's fleet and window, the stream cell's
SHAPES = ((32, 8192), (256, 256))
FORMS = ("grouped", "every_step")


def _fleet(n_ep: int):
    eps = scaled_testbed(n_ep // 4)
    store = TaskProfileStore(eps)
    for fn in SEBS_FUNCTIONS:
        for ep in eps:
            base, _, k = ep.name.partition("_")
            rt, w = BASE_PROFILES[fn][base]
            rt = rt / (1.0 + 0.02 * int(k or 0))
            store.record(fn, ep.name, rt, rt * w)
    return eps, store


def _tasks(eps, n: int, form: str):
    fns = np.resize(np.arange(len(SEBS_FUNCTIONS)), n)
    np.random.default_rng(0).shuffle(fns)
    shared = ((eps[0].name, 1, 200e6, True),)
    # distinct floors far below any start time: the same placements' work,
    # but no two tasks share a run key
    floor = (lambda i: 1e-9 * (i + 1)) if form == "every_step" else (lambda i: 0.0)
    return [TaskSpec(id=f"t{i}", fn=SEBS_FUNCTIONS[f], inputs=shared,
                     not_before=floor(i)) for i, f in enumerate(fns)]


def _capture(tasks, eps, store):
    """The arguments of the window's ``greedy_window`` call, and the
    seconds of that first call."""
    seen = []
    call = ops.greedy_window

    def spy(n_ep, consts, init, xs):
        t0 = time.perf_counter()
        out = call(n_ep, consts, init, xs)
        seen.append((n_ep, consts, init, xs, time.perf_counter() - t0))
        return out

    ops.greedy_window = spy
    try:
        mhra(tasks, eps, store, TransferModel(eps), alpha=0.5, engine="jax")
    finally:
        ops.greedy_window = call
    (args,) = seen
    return args


def _times(fn, reps: int) -> list[float]:
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        out.append((time.perf_counter() - t0) * 1e3)
    return out


def _row(n_ep: int, n: int, form: str, reps: int) -> dict:
    eps, store = _fleet(n_ep)
    n_ep_, consts, init, xs, first_s = _capture(_tasks(eps, n, form), eps,
                                                store)
    starts = xs["new_run"] & xs["valid"]
    window = _times(lambda: ops.greedy_window(n_ep_, consts, init, xs), reps)
    use_kernel = dispatch.placement_use_pallas()
    with jax.enable_x64(True):
        d = jax.device_put((consts, ops._as_tuple_carry(init), xs))
        jax.block_until_ready(d)

        def scan():
            jax.block_until_ready(ops._greedy_scan(
                *d, n_ep=n_ep_, use_kernel=use_kernel))

        scan()
        scan_ms = _times(scan, reps)
    dev = jax.devices()[0]
    return {
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": jax.device_count()},
        "endpoints": n_ep, "tasks": n, "form": form,
        "heuristics": int(xs["valid"].shape[0]),
        "scan_steps": int(xs["valid"].any(axis=0).sum()),
        "basis_steps": int(starts.any(axis=0).sum()),
        "runs_per_heuristic": [int(r) for r in starts.sum(axis=1)],
        "first_call_s": first_s,
        "window_ms_min": min(window),
        "window_ms_median": statistics.median(window),
        "scan_ms_min": min(scan_ms),
        "scan_ms_median": statistics.median(scan_ms),
        "reps": reps,
    }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", nargs="*", default=[f"{e}x{t}" for e, t in SHAPES],
                    help="ENDPOINTSxTASKS; endpoints a multiple of 4")
    ap.add_argument("--reps", type=int, default=0,
                    help="timed repeats (default: about 4 s of scan each)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    for shape in args.shapes:
        n_ep, n = (int(v) for v in shape.split("x"))
        reps = args.reps or max(5, min(40, 4 * 8192 * 32 // (n * n_ep) + 5))
        for form in FORMS:
            print(json.dumps(_row(n_ep, n, form, reps)), flush=True)


if __name__ == "__main__":
    main()
