"""The check that decides ``correct``: it passes the program, fails its
control, and fails a run whose timed path is broken underneath.

The control is the plain reference computed in float32 — the precision
below the float64 the deployment states — put in the program's place on
the windows the program composed.  The faults are planted in the program
for the length of one test; each must turn ``correct`` false.  The cells
run on one chip, so there is no exchange between chips to leave out.
"""
import json

import numpy as np
import pytest

from _bench_tiny import on_cpu  # noqa: F401
from bench import calibrate, check, harness

CELLS = ("sebs32-batch8k", "sebs256-stream")


def _readings(root, workload, seed=7):
    """The program's and the control's numbers for one short window."""
    cell = harness.load_cell(root, workload)
    harness.setup_jax(root, cell.chips)
    tr = cell.traffic
    dep = harness.Deployment(cell.cfg)
    trf = harness.make_traffic(dep, tr, seed, 0.5)
    eng = dep.engine(tr)
    m = harness.drive(eng, tr, trf, 0.5, log=lambda msg: None)
    placed = check.from_engine(eng.windows)
    table = {i: (fn, inp, 0.0) for i, fn, inp in trf.rows}
    Ref = harness.reference_of(root, cell.cfg)
    prog, _ = check.compare(Ref(cell.cfg), placed, m.submitted, table, tr["max_batch"])
    ctrl_windows = calibrate.control_windows(Ref(cell.cfg, np.float32), placed, table)
    ctrl, _ = check.compare(Ref(cell.cfg), ctrl_windows, m.submitted, table,
                            tr["max_batch"])
    return prog, ctrl, check.load_limits(root, workload)


@pytest.mark.parametrize("workload", CELLS)
def test_program_passes_and_float32_control_fails(on_cpu, workload):
    prog, ctrl, limits = _readings(on_cpu, workload)
    assert check.verdict(prog, limits), prog
    assert not check.verdict(ctrl, limits), ctrl
    # the control fails on arithmetic, never on the windows' composition
    assert ctrl["composition_errors"] == 0
    assert ctrl["window_gap"] > 1e3 * max(prog["window_gap"], 1e-16)


def _break_state(monkeypatch):
    """A step that returns its state unchanged: no window's placements
    reach the live state carried to the next window."""
    from repro.core import scheduler as sched
    monkeypatch.setattr(sched.SoAState, "replace_with", lambda self, other: None)


def _drop_half(monkeypatch):
    """Half of each window left out: the engine places the first half of
    its pending tasks and forgets the rest."""
    from repro.core.engine import OnlineEngine
    flush0 = OnlineEngine.flush

    def flush(self):
        del self.pending[(len(self.pending) + 1) // 2:]
        return flush0(self)
    monkeypatch.setattr(OnlineEngine, "flush", flush)


def _alter_answer(monkeypatch):
    """One answer altered where it is produced: the device scan's first
    decision of every heuristic moves to the next endpoint."""
    from repro.kernels.placement import ops
    call0 = ops.greedy_window

    def call(n_ep, consts, init, xs):
        out, (ei, s, e) = call0(n_ep, consts, init, xs)
        ei = ei.copy()
        ei[:, 0] = (ei[:, 0] + 1) % n_ep
        return out, (ei, s, e)
    monkeypatch.setattr(ops, "greedy_window", call)


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("fault", [_break_state, _drop_half, _alter_answer])
def test_a_broken_timed_path_is_not_correct(on_cpu, monkeypatch, capsys,
                                            workload, fault):
    fault(monkeypatch)
    rc = harness.main(["--workload", workload, "--seed", "99", "--seconds", "0.6",
                       "--trace", "0"], root=on_cpu)
    out = capsys.readouterr()
    assert rc == 0, out.err[-2000:]
    res = json.loads(out.out.strip().splitlines()[-1])
    assert res["correct"] is False, res["checks"]
