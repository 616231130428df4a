"""Readings that set a cell's correctness limits: the program's and its
control's, over many seeds, in one process on the chip.

    python3 bench/calibrate.py --workload <cell> --seconds <s> --seeds 1,2,3

For each seed the cell's traffic runs through the program for a window
of ``--seconds``, as a run drives it.  Its windows are compared with the
plain reference (the program's readings), and the same windows are
placed again by the reference computed in float32 — the control, put in
the program's place — and compared the same way (the control's
readings).  One JSON line per seed; the last line holds the largest
program reading and the smallest control reading of each number.  The
benchmark's own runs never run this.
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

from bench import check, harness  # noqa: E402


def control_windows(ref32, placed, table):
    """The program's windows placed again by the float32 reference."""
    out = []
    for w in placed:
        o = ref32.place(w.submitted_at, [table[t] for t in w.ids])
        names = [ref32.names[e] for e in o.endpoint]
        out.append(check.Placed(
            w.submitted_at, list(w.ids), dict(zip(w.ids, names)),
            {t: (float(s), float(e)) for t, s, e in zip(w.ids, o.start, o.end)},
            o.objective, o.energy_j, o.makespan_s))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    args = ap.parse_args(argv)
    cell = harness.load_cell(_ROOT, args.workload)
    try:
        harness.setup_jax(_ROOT, cell.chips)
    except harness.NoDevice as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 1
    tr = cell.traffic
    dep = harness.Deployment(cell.cfg)
    Ref = harness.reference_of(_ROOT, cell.cfg)
    seeds = [int(s) for s in args.seeds.split(",")]
    harness.warm_up(dep, tr, seeds[0])
    prog, ctrl = [], []
    for seed in seeds:
        trf = harness.make_traffic(dep, tr, seed, args.seconds)
        eng = dep.engine(tr)
        m = harness.drive(eng, tr, trf, args.seconds,
                          log=lambda msg: print(msg, file=sys.stderr))
        placed = check.from_engine(eng.windows)
        del eng
        table = {i: (fn, inp, 0.0) for i, fn, inp in trf.rows}
        t = time.perf_counter()
        p, margin = check.compare(Ref(cell.cfg), placed, m.submitted, table,
                                  tr["max_batch"])
        t_ref = time.perf_counter() - t
        c, _ = check.compare(Ref(cell.cfg),
                             control_windows(Ref(cell.cfg, np.float32), placed, table),
                             m.submitted, table, tr["max_batch"])
        prog.append(p)
        ctrl.append(c)
        print(json.dumps({"seed": seed, "windows": len(placed),
                          "tasks": sum(len(w.ids) for w in placed),
                          "reference_s": t_ref, "margin": margin,
                          "program": p, "control": c}), flush=True)
    print(json.dumps({
        "lower": {k: max(r[k] for r in prog) for k in check.NUMBERS},
        "upper": {k: min(r[k] for r in ctrl) for k in check.NUMBERS},
        "seeds": len(seeds)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
