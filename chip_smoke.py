"""Chip smoke: OnlineEngine's placement hot path on one TPU, checked
against the host reference.

Deployment: the Table-I testbed replicated by ``scaled_testbed(8)`` (32
heterogeneous endpoints, up to 64-core slots) takes 32,768 synthetic SeBS
tasks, each reading one shared 200 MB input, in 4 windows of 8192.  Each
window is placed, executed on the simulated testbed, attributed and
learned from.  The stream goes through
``OnlineEngine(policy="mhra", engine="auto")`` — which must resolve to the
fused ``lax.scan`` on the device — and, in the same process, through
``engine="soa"`` (host NumPy, the reference).  Every window must place
identically, with objective, energy and makespan within ``RTOL``.

    python chip_smoke.py [--seed N]

Exits non-zero, printing no result, unless JAX's first device is a TPU.
The last line of a passing run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
SRC = ROOT / "src"

PLATFORM = "tpu"
REPLICAS = 8          # scaled_testbed(8): 32 endpoints
WINDOW = 8192         # tasks per window (OnlineEngine max_batch)
N_WINDOWS = 4
INPUT_BYTES = 200e6   # one shared input per task, staged on endpoint 0
RTOL = 1e-9


def _deployment(seed: int):
    from repro.core.endpoint import scaled_testbed
    from repro.core.scheduler import TaskSpec
    from repro.core.testbed import SEBS_FUNCTIONS

    import numpy as np

    eps = scaled_testbed(REPLICAS)
    rng = np.random.default_rng(seed)
    fns = rng.integers(len(SEBS_FUNCTIONS), size=WINDOW * N_WINDOWS)
    inputs = ((eps[0].name, 1, INPUT_BYTES, True),)
    windows = [
        [TaskSpec(id=f"w{w}t{i}", fn=SEBS_FUNCTIONS[fns[w * WINDOW + i]],
                  inputs=inputs)
         for i in range(WINDOW)]
        for w in range(N_WINDOWS)
    ]
    return eps, windows


def _profiles(eps):
    """``{fn: {endpoint: (runtime_s, watts)}}`` for every SeBS function on
    every endpoint: replica k of a Table-I machine runs (1 + 0.02k)x
    faster (``scaled_testbed``'s perf_scale) at the machine's power."""
    from repro.core.testbed import BASE_PROFILES, SEBS_FUNCTIONS

    out = {}
    for fn in SEBS_FUNCTIONS:
        out[fn] = {}
        for ep in eps:
            base, _, k = ep.name.partition("_")
            rt, w = BASE_PROFILES[fn][base]
            out[fn][ep.name] = (rt / (1.0 + 0.02 * int(k or 0)), w)
    return out


def _run(engine: str, eps, windows, seed: int):
    """One stream through a fresh OnlineEngine whose store holds three
    records of every profile and whose simulated testbed runs them; the
    per-window (WindowResult, wall seconds) and the engine."""
    from repro.core.engine import OnlineEngine
    from repro.core.predictor import TaskProfileStore
    from repro.core.testbed import TestbedSim

    profiles = _profiles(eps)
    store = TaskProfileStore(eps)
    for fn, by_ep in profiles.items():
        for ep, (rt, w) in by_ep.items():
            for _ in range(3):
                store.record(fn, ep, rt, rt * w)
    eng = OnlineEngine(eps, TestbedSim(eps, profiles=profiles, seed=seed),
                       policy="mhra", engine=engine, max_batch=WINDOW,
                       monitoring=True, store=store)
    out = []
    for batch in windows:
        t0 = time.perf_counter()
        res = eng.submit_many(batch)
        out.append((res, time.perf_counter() - t0))
    return eng, out


def _rel(a: float, b: float) -> float:
    if a == b:
        return 0.0
    return abs(a - b) / max(abs(a), abs(b))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the task stream and the testbed sim")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != PLATFORM:
        print(f"chip_smoke: needs a {PLATFORM} device, JAX found "
              f"{dev.platform} ({dev.device_kind})", file=sys.stderr)
        return 1

    from repro.compile_cache import enable_compile_cache
    from repro.kernels.placement import ops as pops

    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache: {enable_compile_cache()}")
    eps, windows = _deployment(args.seed)
    print(f"deployment: {len(eps)} endpoints, {N_WINDOWS} windows x "
          f"{WINDOW} SeBS tasks, one shared {INPUT_BYTES / 1e6:.0f} MB "
          f"input each, seed {args.seed}")

    pops.reset_compile_stats()
    pops.reset_window_stats()
    eng_dev, dev_out = _run("auto", eps, windows, args.seed)
    window_stats = dict(pops.WINDOW_STATS)
    _, ref_out = _run("soa", eps, windows, args.seed)

    failures = []
    if eng_dev.engine != "jax":
        failures.append(f"auto resolved to {eng_dev.engine!r}, not 'jax'")
    print(f"engine auto resolved to: {eng_dev.engine}")
    print(f"device windows: {window_stats['device']}, "
          f"handed to soa: {window_stats['soa']}")
    if window_stats != {"device": N_WINDOWS, "soa": 0}:
        failures.append(f"window stats {window_stats}")
    print(f"COMPILE_STATS: {pops.COMPILE_STATS}")

    n_bitwise = n_identical = 0
    max_gap = 0.0
    for w, ((d_res, d_s), (r_res, r_s)) in enumerate(zip(dev_out, ref_out)):
        if len(d_res) != 1 or len(r_res) != 1:
            failures.append(f"window {w}: {len(d_res)}/{len(r_res)} flushes")
            continue
        d, r = d_res[0], r_res[0]
        label = "compile" if w == 0 else "warm"
        print(f"window {w} ({label}): device engine {d_s:.3f} s wall, "
              f"{d.scheduling_s:.3f} s placement | soa {r_s:.3f} s wall, "
              f"{r.scheduling_s:.3f} s placement")
        ds, rs = d.schedule, r.schedule
        vals = [(ds.objective, rs.objective), (ds.energy_j, rs.energy_j),
                (ds.makespan_s, rs.makespan_s)]
        if not all(math.isfinite(a) for a, _ in vals):
            failures.append(f"window {w}: non-finite device result {vals}")
            continue
        gap = max(_rel(a, b) for a, b in vals)
        max_gap = max(max_gap, gap)
        if gap > RTOL:
            failures.append(f"window {w}: relative gap {gap!r} > {RTOL}")
        if d.assignments == r.assignments:
            n_identical += 1
            if (all(a == b for a, b in vals)
                    and ds.transfer_j == rs.transfer_j
                    and ds.timeline == rs.timeline):
                n_bitwise += 1
        else:
            diff = [t for t in r.assignments
                    if d.assignments.get(t) != r.assignments[t]]
            t0 = diff[0]
            failures.append(
                f"window {w}: {len(diff)} placements differ; first {t0}: "
                f"device {d.assignments.get(t0)} "
                f"{ds.timeline.get(t0)} vs soa {r.assignments[t0]} "
                f"{rs.timeline.get(t0)}; winning heuristic device "
                f"{ds.heuristic} vs soa {rs.heuristic}")
    print(f"assignments identical to soa: {n_identical}/{N_WINDOWS} windows")
    print(f"windows bitwise-equal to soa: {n_bitwise}/{N_WINDOWS}")
    print(f"largest relative gap (objective, energy, makespan): {max_gap!r}")
    if n_identical != N_WINDOWS:
        failures.append("assignments differ from soa")

    if failures:
        for f in failures:
            print(f"FAILED: {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
