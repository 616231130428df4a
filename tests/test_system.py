"""End-to-end system tests: the full GreenFaaS pipeline on the simulated
Table-I testbed and the energy report."""
import pytest

from repro.core.database import TaskDB
from repro.core.endpoint import table1_testbed
from repro.core.executor import GreenFaaSExecutor
from repro.core.report import html_report, text_report
from repro.core.scheduler import TaskSpec
from repro.core.testbed import SEBS_FUNCTIONS, TestbedSim


def _workload(n_per=24):
    tasks = []
    i = 0
    for fn in SEBS_FUNCTIONS:
        for _ in range(n_per):
            tasks.append(
                TaskSpec(id=f"t{i}", fn=fn, inputs=(("desktop", 1, 50e6, True),))
            )
            i += 1
    return tasks


def _run(strategy, alpha=0.5, site=None, n_per=24, seed=1):
    eps = table1_testbed()
    sim = TestbedSim(eps, seed=seed)
    ex = GreenFaaSExecutor(eps, sim, alpha=alpha, strategy=strategy, site=site)
    ex.warmup(list(SEBS_FUNCTIONS), per_endpoint=2)
    return ex, ex.run_batch(_workload(n_per))


def test_pipeline_end_to_end_cluster_mhra():
    ex, res = _run("cluster_mhra", alpha=0.5)
    assert res.makespan_s > 0
    assert res.measured_energy_j > 0
    # attribution produced per-task energies for every task
    recs = [r for r in ex.db.records]
    assert len(recs) == len(_workload())
    assert all(r.energy_j is not None and r.energy_j >= 0 for r in recs)
    # measured (monitor) energy within 25% of simulator ground truth
    truth = res.sim.true_energy_j
    assert res.measured_energy_j == pytest.approx(truth, rel=0.25)


def test_cluster_mhra_dominates_round_robin_on_edp():
    _, rr = _run("round_robin")
    _, cm = _run("cluster_mhra", alpha=0.2)
    assert cm.edp() < rr.edp()


def test_alpha_one_matches_single_cheapest_site():
    """Paper: alpha=1.0 reproduces the all-desktop schedule."""
    _, cm = _run("cluster_mhra", alpha=1.0)
    _, ds = _run("single_site", site="desktop")
    assert cm.measured_energy_j == pytest.approx(ds.measured_energy_j, rel=0.1)


def test_online_profiles_converge():
    """After a batch, the store's predictions approximate the sim truth."""
    ex, _ = _run("round_robin")
    sim = ex.backend
    for fn in SEBS_FUNCTIONS[:3]:
        for ep in ["desktop", "faster"]:
            if ex.store.n_obs(fn, ep) == 0:
                continue
            pred = ex.store.predict(fn, ep)
            rt_true, w_true, _ = sim.task_truth(fn, ep)
            assert pred.runtime_s == pytest.approx(rt_true, rel=0.3), (fn, ep)


def test_energy_report(tmp_path):
    ex, _ = _run("cluster_mhra", n_per=8)
    txt = text_report(ex.db, user="user0")
    assert "GreenFaaS energy report" in txt
    assert any(fn in txt for fn in SEBS_FUNCTIONS)
    html = html_report(ex.db, tmp_path / "report.html")
    assert (tmp_path / "report.html").exists()
    assert "endpoint energy usage" in html


def test_db_roundtrip(tmp_path):
    ex, _ = _run("round_robin", n_per=4)
    ex.db.path = tmp_path / "db.json"
    ex.db.save()
    db2 = TaskDB(tmp_path / "db.json")
    assert len(db2.records) == len(ex.db.records)
    assert db2.energy_by_endpoint().keys() == ex.db.energy_by_endpoint().keys()

