"""Endpoint model and predictor fallbacks."""
import pytest

from repro.core.endpoint import table1_testbed
from repro.core.predictor import TaskProfileStore


def test_table1_matches_paper():
    eps = {e.name: e for e in table1_testbed()}
    assert eps["desktop"].cores == 16 and eps["desktop"].idle_power_w == 6.51
    assert eps["theta"].cores == 64 and eps["theta"].idle_power_w == 110.0
    assert eps["ic"].cores == 48 and eps["ic"].idle_power_w == 136.0
    assert eps["faster"].cores == 64 and eps["faster"].idle_power_w == 205.0
    # desktop is always-on: no startup energy to amortize (paper §III-F)
    assert eps["desktop"].startup_energy_j == 0.0
    assert eps["faster"].startup_energy_j > 0


def test_hop_counts_symmetric_defaults():
    eps = table1_testbed()
    desktop = eps[0]
    assert desktop.hop_count(desktop) == 0
    assert desktop.hop_count("theta") == 10
    assert desktop.hop_count("unknown-site") > 0  # default


def test_predictor_cold_start_fallbacks():
    eps = table1_testbed()
    store = TaskProfileStore(eps)
    # never seen anywhere -> exploration prior, not confident
    p = store.predict("newfn", "desktop")
    assert not p.confident and p.runtime_s > 0
    # seen on one endpoint -> perf-scaled estimate elsewhere, not confident
    store.record("newfn", "desktop", 10.0, 100.0)
    q = store.predict("newfn", "faster")
    assert not q.confident
    assert q.runtime_s < 10.0  # faster has higher perf_scale than desktop
    # seen here -> confident
    r = store.predict("newfn", "desktop")
    assert r.confident and r.runtime_s == pytest.approx(10.0)


def test_predictor_drift_sigma():
    store = TaskProfileStore()
    for x in (10.0, 10.1, 9.9, 10.05, 9.95):
        store.record("fn", "ep", x, 1.0)
    assert store.drift_sigma("fn", "ep", 10.0) < 1.0
    assert store.drift_sigma("fn", "ep", 15.0) > 3.0
