"""Scheduler unit + property tests (MHRA, Cluster MHRA, clustering)."""
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core.clustering import agglomerative_cluster
from repro.core.endpoint import table1_testbed
from repro.core.predictor import TaskProfileStore
from repro.core.scheduler import (
    HEURISTICS,
    MEMO_STATS,
    PredictionTable,
    TaskSpec,
    cluster_mhra,
    compute_clusters,
    mhra,
    reset_memo_stats,
    round_robin,
    single_site,
)
from repro.core.transfer import TransferModel


def _setup(n_fns=3, n_tasks=60, seed=0):
    eps = table1_testbed()
    store = TaskProfileStore(eps)
    rng = np.random.default_rng(seed)
    fns = [f"fn{i}" for i in range(n_fns)]
    for fn in fns:
        for ep in eps:
            rt = float(rng.uniform(1, 20))
            en = float(rng.uniform(5, 200))
            for _ in range(3):
                store.record(fn, ep.name, rt, en)
    tasks = [TaskSpec(id=f"t{i}", fn=fns[i % n_fns]) for i in range(n_tasks)]
    return tasks, eps, store, TransferModel(eps)


def test_schedule_covers_all_tasks():
    tasks, eps, store, tm = _setup()
    for strat in (mhra, cluster_mhra):
        s = strat(tasks, eps, store, tm, alpha=0.5)
        assert set(s.assignments) == {t.id for t in tasks}
        names = {e.name for e in eps}
        assert set(s.assignments.values()) <= names


def test_alpha_tradeoff_direction():
    """Higher alpha must not increase energy; lower alpha must not increase
    makespan (paper Fig. 6 trend)."""
    tasks, eps, store, tm = _setup(n_tasks=120)
    s_energy = cluster_mhra(tasks, eps, store, tm, alpha=1.0)
    s_fast = cluster_mhra(tasks, eps, store, tm, alpha=0.0)
    assert s_energy.energy_j <= s_fast.energy_j * 1.001
    assert s_fast.makespan_s <= s_energy.makespan_s * 1.001


def test_cluster_mhra_beats_or_matches_single_sites_on_objective():
    tasks, eps, store, tm = _setup(n_tasks=100, seed=3)
    cm = cluster_mhra(tasks, eps, store, tm, alpha=0.5)
    for ep in eps:
        ss = single_site(tasks, eps, store, tm, ep.name)
        # compare with the same normalizers via EDP as a proxy
        assert cm.edp() <= ss.edp() * 1.05, ep.name


def test_round_robin_balances_counts():
    tasks, eps, store, tm = _setup(n_tasks=80)
    s = round_robin(tasks, eps, store, tm)
    counts = {e.name: 0 for e in eps}
    for v in s.assignments.values():
        counts[v] += 1
    assert max(counts.values()) - min(counts.values()) <= 1


def test_mhra_all_heuristics_evaluated():
    tasks, eps, store, tm = _setup(n_tasks=40)
    best = mhra(tasks, eps, store, tm, alpha=0.5)
    assert best.heuristic in HEURISTICS


def test_cluster_mhra_fewer_decisions_faster():
    """Cluster MHRA makes materially fewer placement decisions than MHRA
    (Table IV): one per cluster per heuristic instead of one per task,
    counted by the SoA greedy's run memo (a hit or a miss per decision)."""
    tasks, eps, store, tm = _setup(n_tasks=512)

    def decisions(place):
        reset_memo_stats()
        s = place(tasks, eps, store, tm, alpha=0.5, engine="soa")
        assert set(s.assignments) == {t.id for t in tasks}
        return MEMO_STATS["hits"] + MEMO_STATS["misses"]

    table = PredictionTable(tasks, eps, store)
    n_clusters = len(compute_clusters(tasks, eps, table))
    assert decisions(mhra) == len(HEURISTICS) * len(tasks)
    assert decisions(cluster_mhra) == len(HEURISTICS) * n_clusters
    assert n_clusters <= len(tasks) // 4


# ---------------------------------------------------------------------------
# clustering properties
# ---------------------------------------------------------------------------


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(1, 120),
    k=st.integers(2, 6),
    cap=st.floats(10.0, 5000.0),
    seed=st.integers(0, 100),
)
def test_clustering_is_a_partition(n, k, cap, seed):
    rng = np.random.default_rng(seed)
    feats = rng.uniform(0, 10, size=(n, k))
    energies = rng.uniform(1, 50, size=n)
    clusters = agglomerative_cluster(feats, energies, cap)
    flat = sorted(i for c in clusters for i in c)
    assert flat == list(range(n))  # exact partition, no loss, no dupes
    for c in clusters:
        assert len(c) >= 1


@settings(max_examples=25, deadline=None)
@given(n=st.integers(2, 100), seed=st.integers(0, 50))
def test_clustering_respects_energy_cap(n, seed):
    rng = np.random.default_rng(seed)
    feats = np.repeat(rng.uniform(0, 1, size=(3, 4)), (n + 2) // 3, axis=0)[:n]
    energies = rng.uniform(1, 10, size=n)
    cap = 30.0
    clusters = agglomerative_cluster(feats, energies, cap)
    for c in clusters:
        if len(c) > 1:
            # multi-task clusters exceed the cap by at most one task's energy
            assert energies[c].sum() <= cap + energies[c].max() + 1e-9


def test_identical_tasks_cluster_together():
    feats = np.ones((30, 4))
    energies = np.full(30, 1.0)
    clusters = agglomerative_cluster(feats, energies, energy_cap=1000.0)
    assert len(clusters) == 1 and len(clusters[0]) == 30


def test_distinct_tasks_stay_apart():
    feats = np.array([[0.0, 0, 0, 0]] * 10 + [[100.0, 100, 100, 100]] * 10)
    energies = np.full(20, 1.0)
    clusters = agglomerative_cluster(feats, energies, energy_cap=1000.0)
    for c in clusters:
        groups = {i < 10 for i in c}
        assert len(groups) == 1  # never mixes the two populations


def test_transfer_energy_affects_placement():
    """A task with huge input data at one endpoint should prefer staying."""
    eps = table1_testbed()
    store = TaskProfileStore(eps)
    for ep in eps:
        store.record("fn", ep.name, 5.0, 50.0)  # identical everywhere
    tm = TransferModel(eps)
    tasks = [
        TaskSpec(id=f"t{i}", fn="fn", inputs=(("faster", 1, 500e9, False),))
        for i in range(8)
    ]
    s = cluster_mhra(tasks, eps, store, tm, alpha=1.0)
    assert set(s.assignments.values()) == {"faster"}
