"""Whether the timed windows placed what the plain reference places.

The reference named by the deployment replays every window the timed
run placed, in the order and with the open time the run gave it, from
the benchmark's own record of each task (never the program's copy), and
carries its own state from window to window.  Four numbers are compared,
each with the limit in ``bench/limits/<workload>.json``:

- ``composition_errors``: tasks that were submitted but not placed
  exactly once, plus windows that break submission order or hold more
  than ``max_batch`` tasks;
- ``placement_mismatches``: tasks placed on another endpoint than the
  reference's;
- ``timeline_gap``: the largest relative gap of a task's start or end
  (a value that is not finite reads as a gap of 1);
- ``window_gap``: the largest relative gap of a window's objective,
  energy or makespan.
"""
from __future__ import annotations

import dataclasses
import json
import math
import pathlib


NUMBERS = ("composition_errors", "placement_mismatches", "timeline_gap", "window_gap")


@dataclasses.dataclass
class Placed:
    """One window as the program placed it."""
    submitted_at: float
    ids: list[str]
    assign: dict[str, str]
    timeline: dict[str, tuple[float, float]]
    objective: float
    energy_j: float
    makespan_s: float


def from_engine(windows) -> list[Placed]:
    """The program's ``WindowResult`` list, read once the window has closed."""
    return [Placed(w.submitted_at, [t.id for t in w.tasks], dict(w.assignments),
                   {t.id: w.schedule.timeline[t.id] for t in w.tasks},
                   w.schedule.objective, w.schedule.energy_j, w.schedule.makespan_s)
            for w in windows]


def _rel(a: float, b: float) -> float:
    if a == b:
        return 0.0
    if not (math.isfinite(a) and math.isfinite(b)):
        return 1.0     # a value that is not a number misses by all of itself
    return abs(a - b) / max(abs(a), abs(b))


def load_limits(root: pathlib.Path, workload: str) -> dict[str, float]:
    with open(root / "bench" / "limits" / f"{workload}.json") as f:
        spec = json.load(f)
    return {k: float(spec[k]["limit"]) for k in NUMBERS}


def compare(ref, placed: list[Placed], submitted: list[str],
            table: dict[str, tuple], max_batch: int):
    """The four numbers for one run, and the reference's smallest margin.

    ``ref`` is a fresh reference of the deployment; ``submitted`` is every
    task id the run submitted, in order; ``table`` maps an id to the
    benchmark's own ``(fn, inputs, not_before)``.
    """
    names = ref.names
    comp = 0
    seen: dict[str, int] = {}
    pos = {tid: i for i, tid in enumerate(submitted)}
    nxt = 0
    for w in placed:
        if len(w.ids) > max_batch:
            comp += 1
        idx = [pos.get(t, -1) for t in w.ids]
        if idx != list(range(nxt, nxt + len(idx))):
            comp += 1
        nxt += len(idx)
        for t in w.ids:
            seen[t] = seen.get(t, 0) + 1
    comp += sum(1 for t in submitted if seen.get(t, 0) != 1)
    comp += sum(1 for t in seen if t not in pos)

    mism = 0
    tgap = 0.0
    wgap = 0.0
    margin = math.inf
    for w in placed:
        ids = [t for t in w.ids if t in table]
        out = ref.place(w.submitted_at, [table[t] for t in ids])
        margin = min(margin, out.margin)
        for k, t in enumerate(ids):
            if w.assign.get(t) != names[out.endpoint[k]]:
                mism += 1
            s, e = w.timeline.get(t, (math.nan, math.nan))
            tgap = max(tgap, _rel(s, float(out.start[k])), _rel(e, float(out.end[k])))
        wgap = max(wgap, _rel(w.objective, out.objective),
                   _rel(w.energy_j, out.energy_j), _rel(w.makespan_s, out.makespan_s))
    numbers = {"composition_errors": comp, "placement_mismatches": mism,
               "timeline_gap": tgap, "window_gap": wgap}
    return numbers, margin


def verdict(numbers: dict, limits: dict) -> bool:
    return all(numbers[k] <= limits[k] for k in NUMBERS)
