"""SF1/SF2/SF3: the whole-fleet pass (``_normalizers_fast``) returns the
doubles of the per-endpoint seed arithmetic (``_normalizers``), equal and
not just close, on both of its list-scheduling forms."""
import numpy as np
import pytest

from repro.core import scheduler as sched
from repro.core.carbon import CarbonWeights
from repro.core.endpoint import scaled_testbed, table1_testbed
from repro.core.predictor import TaskProfileStore
from repro.core.scheduler import PredictionTable, TaskSpec
from repro.core.testbed import SEBS_FUNCTIONS
from repro.core.transfer import TransferModel

FLEETS = {"table1": 1, "sebs32": 8, "sebs256": 64}


def _fleet(replicas):
    eps = table1_testbed() if replicas == 1 else scaled_testbed(replicas)
    store = TaskProfileStore(eps)
    rng = np.random.default_rng(replicas)
    # energies over six decades, so that a sum out of task order rounds
    # differently somewhere
    for fn in SEBS_FUNCTIONS:
        for ep in eps:
            rt, en = float(rng.uniform(1, 20)), float(10 ** rng.uniform(-2, 4))
            for _ in range(3):
                store.record(fn, ep.name, rt, en)
    # a function whose runtime predicts below zero on some endpoints, so
    # the slot matrix cannot take every slot to be at least ``ready``
    for i, ep in enumerate(eps):
        store.record("skewed", ep.name, -2.5 if i % 3 == 0 else 4.0, 30.0)
    return eps, store


def _mixed(eps, rng, n, not_before=False, fns=SEBS_FUNCTIONS):
    """Tasks of drawn functions with one shared input staged on the first
    endpoint (so it is the destination there), repeated across the window;
    every third task a private input from a drawn endpoint; every fifth a
    second shared input from the last endpoint, given once as int bytes;
    with ``not_before``, every fourth task a ready floor above zero."""
    first = (eps[0].name, 1, 2e8, True)
    last = (eps[-1].name, 4, 5 * 10**7, True)
    tasks = []
    for i in range(n):
        inputs = [first]
        if i % 3 == 0:
            src = eps[int(rng.integers(len(eps)))].name
            inputs.append((src, int(rng.integers(1, 9)),
                           float(rng.uniform(1e6, 1e9)), False))
        if i % 5 == 0:
            inputs.append(last)
        nb = float(rng.uniform(0.5, 80.0)) if not_before and i % 4 == 1 else 0.0
        tasks.append(TaskSpec(id=f"t{i}", fn=fns[int(rng.integers(len(fns)))],
                              inputs=tuple(inputs), not_before=nb))
    return tasks


WINDOWS = {
    "mixed_inputs": lambda eps, rng: _mixed(eps, rng, 150),
    "not_before": lambda eps, rng: _mixed(eps, rng, 150, not_before=True),
    "negative_runtime": lambda eps, rng: _mixed(
        eps, rng, 90, fns=SEBS_FUNCTIONS[:3] + ("skewed",)),
    "no_inputs": lambda eps, rng: [
        TaskSpec(id=f"t{i}", fn=SEBS_FUNCTIONS[int(rng.integers(7))])
        for i in range(70)],
    "below_zero_only": lambda eps, rng: [
        TaskSpec(id=f"t{i}", fn="skewed") for i in range(3)],
    "one_task": lambda eps, rng: _mixed(eps, rng, 1),
    "empty": lambda eps, rng: [],
}


@pytest.mark.parametrize("form", ["heap", "matrix"])
@pytest.mark.parametrize("carbon", [False, True], ids=["no_carbon", "carbon"])
@pytest.mark.parametrize("window", sorted(WINDOWS))
@pytest.mark.parametrize("fleet", sorted(FLEETS))
def test_fleet_pass_returns_the_seed_doubles(fleet, window, carbon, form,
                                             monkeypatch):
    eps, store = _fleet(FLEETS[fleet])
    rng = np.random.default_rng(7)
    tasks = WINDOWS[window](eps, rng)
    weights = (CarbonWeights(tuple(float(r) for r in rng.uniform(0.05, 0.6, len(eps))))
               if carbon else None)
    tm = TransferModel(eps)
    table = PredictionTable(tasks, eps, store)
    monkeypatch.setattr(sched, "NORMALIZER_MATRIX_MIN_ENDPOINTS",
                        1 if form == "matrix" else len(eps) + 1)
    want = sched._normalizers(tasks, eps, table.per_ep(), tm, weights)
    got = sched._normalizers_fast(tasks, eps, table, tm, weights)
    assert got == want
    assert (got[2] > 1e-9) == (carbon and bool(tasks))


def test_windows_cover_what_they_say():
    """The cases above exercise what they are named for: unequal core
    counts, more tasks than any endpoint has cores, inputs whose source is
    the destination, tasks with several inputs, floors above zero, a
    runtime below zero, and every end below zero on an always-on endpoint
    that no transfer delays."""
    eps, store = _fleet(1)
    assert len({ep.cores for ep in eps}) > 1
    tasks = WINDOWS["not_before"](eps, np.random.default_rng(7))
    assert len(tasks) > max(ep.cores for ep in eps)
    assert any(src == eps[0].name for t in tasks for src, *_ in t.inputs)
    assert max(len(t.inputs) for t in tasks) >= 2
    assert any(t.not_before > 0 for t in tasks)
    assert any(ep.has_batch_scheduler for ep in eps)
    skewed = PredictionTable(WINDOWS["negative_runtime"](eps, np.random.default_rng(7)),
                             eps, store)
    assert (skewed.rt < 0).any()
    below = PredictionTable(WINDOWS["below_zero_only"](eps, None), eps, store)
    assert (below.rt[0] < 0).all() and not eps[0].has_batch_scheduler


@pytest.mark.parametrize("window", sorted(set(WINDOWS) - {"empty"}))
@pytest.mark.parametrize("fleet", sorted(FLEETS))
def test_slot_matrix_runs_equal_the_heap_runs(fleet, window):
    """Every endpoint's first start, last end and dynamic energy, not only
    the largest that SF1/SF2 keep."""
    eps, store = _fleet(FLEETS[fleet])
    tasks = WINDOWS[window](eps, np.random.default_rng(7))
    table = PredictionTable(tasks, eps, store)
    ready = [float(ep.queue_delay_s) for ep in eps]
    nbs = [t.not_before for t in tasks]
    assert (sched._runs_by_matrix(eps, table, ready, nbs)
            == sched._runs_by_heap(eps, table, ready, nbs))
