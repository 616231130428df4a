"""jax-engine parity suite: engine="jax" must reproduce engine="soa"
assignments AND objectives bitwise — the fused scan replays the SoA
float sequence double for double — batch and online, under every scoring
register, handing windows the fused path can't express (clustered units,
multi-input tasks) to soa, visibly (``ops.WINDOW_STATS``)."""
import sys

import numpy as np
import pytest

import repro.kernels.placement
from repro.core import scheduler as sched
from repro.core.carbon import CarbonWeights
from repro.core.dag import LookaheadWeights
from repro.core.endpoint import scaled_testbed, table1_testbed
from repro.core.engine import OnlineEngine
from repro.core.fairness import FairnessWeights
from repro.core.faults import WarmWeights
from repro.core.policy import get_policy
from repro.core.predictor import TaskProfileStore
from repro.core.scheduler import (
    SoAState,
    TaskSpec,
    auto_engine,
    cluster_mhra,
    mhra,
)
from repro.core.testbed import BASE_PROFILES, SEBS_FUNCTIONS, TestbedSim
from repro.core.transfer import TransferModel
from repro.kernels.placement import ops as pops


def _setup(n_per=12, with_inputs=True, replicas=1):
    eps = scaled_testbed(replicas)
    store = TaskProfileStore(eps)
    for fn in SEBS_FUNCTIONS:
        for ep in eps:
            base, _, k = ep.name.partition("_")
            rt, w = BASE_PROFILES[fn][base]
            rt = rt / (1.0 + 0.02 * int(k or 0))
            for _ in range(3):
                store.record(fn, ep.name, rt, rt * w)
    inputs = ((eps[0].name, 1, 200e6, True),) if with_inputs else ()
    tasks = [
        TaskSpec(id=f"t{i}", fn=SEBS_FUNCTIONS[i % len(SEBS_FUNCTIONS)],
                 inputs=inputs)
        for i in range(n_per * len(SEBS_FUNCTIONS))
    ]
    return tasks, eps, store, TransferModel(eps)


def _assert_bitwise(a, b):
    assert a.assignments == b.assignments
    assert a.objective == b.objective          # bitwise, not approx
    assert a.energy_j == b.energy_j
    assert a.makespan_s == b.makespan_s
    assert a.transfer_j == b.transfer_j
    assert a.heuristic == b.heuristic
    assert a.timeline == b.timeline


# ---------------------------------------------------------------------------
# batch parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("alpha", [0.0, 0.2, 0.5, 1.0])
def test_jax_matches_soa_table5(alpha):
    tasks, eps, store, tm = _setup(n_per=12)
    a = mhra(tasks, eps, store, tm, alpha=alpha, engine="soa")
    b = mhra(tasks, eps, store, tm, alpha=alpha, engine="jax")
    _assert_bitwise(a, b)


def test_jax_matches_soa_scaled_fleet():
    tasks, eps, store, tm = _setup(n_per=8, replicas=3)   # 12 endpoints
    a = mhra(tasks, eps, store, tm, alpha=0.3, engine="soa")
    b = mhra(tasks, eps, store, tm, alpha=0.3, engine="jax")
    _assert_bitwise(a, b)


REGISTERS = ("carbon", "fairness", "warm", "alive", "lookahead",
             "not_before")


def _registers(tasks, n_ep, armed):
    """The scoring registers ``armed`` names (or every one, ``"all"``) over
    ``tasks`` on an ``n_ep`` fleet: the tasks, with ``not_before`` floors
    when that register is armed, and the ``mhra`` keywords of the others.
    Every register's values are drawn in one order whatever is armed, so a
    register alone scores exactly as it does among the rest."""
    rng = np.random.default_rng(0)
    on = set(REGISTERS) if armed == "all" else {armed}
    floors = [float(rng.uniform(0.0, 20.0)) for _ in tasks]
    tasks = [
        TaskSpec(id=t.id, fn=t.fn, inputs=t.inputs,
                 not_before=floors[i] if "not_before" in on else 0.0,
                 user=("alice", "bob")[i % 2])
        for i, t in enumerate(tasks)
    ]
    carbon = CarbonWeights(
        rates=tuple(float(rng.uniform(0.0, 1e-3)) for _ in range(n_ep)),
        gamma=0.7,
    )
    fairness = FairnessWeights(debt={"bob": 2.5}, mu=0.6)
    warm = WarmWeights(
        cold_j=tuple(float(rng.uniform(0.0, 40.0)) for _ in range(n_ep)),
        cold_s=tuple(float(rng.uniform(0.0, 4.0)) for _ in range(n_ep)),
    )
    alive = tuple(i != 1 for i in range(n_ep))
    lw = LookaheadWeights(
        tail_w={t.id: float(rng.uniform(0.0, 1.0)) for t in tasks[::2]},
        out_j={t.id: float(rng.uniform(0.0, 50.0)) for t in tasks[::3]},
        hops_mean=tuple(float(rng.uniform(0.5, 3.0)) for _ in range(n_ep)),
        lam=0.8,
    )
    kw = dict(carbon=carbon, fairness=fairness, warm=warm, alive=alive,
              lookahead=lw)
    return tasks, {k: v for k, v in kw.items() if k in on}


@pytest.mark.parametrize("alpha", [0.0, 0.4, 1.0])
@pytest.mark.parametrize("replicas", [1, 3], ids=["table1", "table1x3"])
@pytest.mark.parametrize("register", REGISTERS + ("all",))
def test_jax_matches_soa_registers(register, replicas, alpha):
    """Each scoring register alone, then carbon + fairness + warm + alive +
    lookahead + not_before armed together (the interaction of registers
    is what historically breaks mirrored float sequences), on the 4- and
    12-endpoint fleets, at both ends of the objective and between."""
    tasks, eps, store, tm = _setup(n_per=6, replicas=replicas)
    tasks, kw = _registers(tasks, len(eps), register)
    a = mhra(tasks, eps, store, tm, alpha=alpha, engine="soa", **kw)
    b = mhra(tasks, eps, store, tm, alpha=alpha, engine="jax", **kw)
    _assert_bitwise(a, b)


@pytest.mark.parametrize("n_tasks", [1, 2, 31, 32, 63, 64, 65])
def test_jax_matches_soa_window_sizes(n_tasks, monkeypatch):
    """Windows at and beside the power-of-two pad of the scan's step axis:
    the pad steps must carry every register through unchanged, bitwise."""
    tasks, eps, store, tm = _setup(n_per=10, replicas=3)
    tasks = tasks[:n_tasks]
    seen = []
    call = pops.greedy_window

    def spy(n_ep, consts, init, xs):
        seen.append(np.array(xs["valid"]))
        return call(n_ep, consts, init, xs)

    monkeypatch.setattr(pops, "greedy_window", spy)
    pops.reset_window_stats()
    a = mhra(tasks, eps, store, tm, alpha=0.4, engine="soa")
    b = mhra(tasks, eps, store, tm, alpha=0.4, engine="jax")
    _assert_bitwise(a, b)
    valid, = seen
    assert valid.shape[1] == pops.bucket_pow2(n_tasks)
    assert pops.WINDOW_STATS["scan_steps"] == n_tasks


def _gate_case(case, tasks):
    if case == "staggered_runs":
        # function k has 3k + 2 tasks, so the runtime and energy orders cut
        # the window into runs at different steps
        return [TaskSpec(id=f"s{k}_{j}", fn=fn, inputs=tasks[0].inputs)
                for k, fn in enumerate(SEBS_FUNCTIONS)
                for j in range(3 * k + 2)]
    if case == "every_step_a_run":
        # distinct not_before floors: no two tasks share a run key
        return [TaskSpec(id=t.id, fn=t.fn, inputs=t.inputs,
                         not_before=0.25 * i) for i, t in enumerate(tasks)]
    return tasks[:33]       # one step past 32: 31 pad steps to 64


@pytest.mark.parametrize("case", ["staggered_runs", "every_step_a_run",
                                  "padded_window"])
def test_gated_run_basis_matches_soa(case, monkeypatch):
    """The scan runs its run-basis pass only on steps where some heuristic
    starts a run, and keeps every heuristic's basis elsewhere; placements
    and every double stay soa's, and ``WINDOW_STATS`` counts the steps the
    pass ran on as the host reads them from the streams.  On 12 endpoints
    at this alpha a basis left stale where a heuristic starts a run moves
    placements (the staggered and padded windows)."""
    tasks, eps, store, tm = _setup(n_per=12, replicas=3)
    tasks = _gate_case(case, tasks)
    seen = []
    call = pops.greedy_window

    def spy(n_ep, consts, init, xs):
        seen.append({k: np.array(xs[k]) for k in ("new_run", "valid")})
        return call(n_ep, consts, init, xs)

    monkeypatch.setattr(pops, "greedy_window", spy)
    pops.reset_window_stats()
    a = mhra(tasks, eps, store, tm, alpha=0.4, engine="soa")
    b = mhra(tasks, eps, store, tm, alpha=0.4, engine="jax")
    _assert_bitwise(a, b)
    xs, = seen
    starts = xs["new_run"] & xs["valid"]
    gate = starts.any(axis=0)
    assert pops.WINDOW_STATS["basis_steps"] == int(gate.sum())
    assert pops.WINDOW_STATS["scan_steps"] == int(xs["valid"].any(axis=0).sum())
    assert pops.WINDOW_STATS["scan_steps"] == len(tasks)
    if case == "staggered_runs":
        # the gate opens on steps where other heuristics keep their basis
        assert (gate & ~starts.all(axis=0)).any()
        assert gate.sum() < len(tasks)
    elif case == "every_step_a_run":
        assert starts[:, :len(tasks)].all()
    else:
        assert xs["valid"].shape[1] == 64 and gate.sum() < len(tasks)


# ---------------------------------------------------------------------------
# hand-off paths (fused scan can't express the window -> soa, which is
# parity-locked already)
# ---------------------------------------------------------------------------


def test_jax_falls_back_on_multi_input_tasks():
    tasks, eps, store, tm = _setup(n_per=4)
    inputs = ((eps[0].name, 1, 100e6, True), (eps[1].name, 1, 50e6, False))
    tasks = [TaskSpec(id=t.id, fn=t.fn, inputs=inputs) for t in tasks]
    a = mhra(tasks, eps, store, tm, alpha=0.5, engine="soa")
    b = mhra(tasks, eps, store, tm, alpha=0.5, engine="jax")
    _assert_bitwise(a, b)


def test_jax_falls_back_on_clustered_units():
    tasks, eps, store, tm = _setup(n_per=6)
    a = cluster_mhra(tasks, eps, store, tm, alpha=0.5, max_cluster_size=16,
                     engine="soa")
    b = cluster_mhra(tasks, eps, store, tm, alpha=0.5, max_cluster_size=16,
                     engine="jax")
    assert a.assignments == b.assignments
    assert a.objective == b.objective


def test_jax_empty_window():
    _, eps, store, tm = _setup(n_per=1)
    a = mhra([], eps, store, tm, alpha=0.5, engine="soa")
    b = mhra([], eps, store, tm, alpha=0.5, engine="jax")
    assert a.assignments == b.assignments == {}


# ---------------------------------------------------------------------------
# online mode: jax scan over a live SoA state, windows of varying size
# ---------------------------------------------------------------------------


def _online(engine, alpha, sizes):
    eps = table1_testbed()
    sim = TestbedSim(eps, seed=0)
    eng = OnlineEngine(eps, sim, policy="mhra", alpha=alpha, monitoring=False,
                       window_s=30.0, max_batch=10**6, engine=engine)
    out = []
    for w, n in enumerate(sizes):
        eng.submit_many([
            TaskSpec(id=f"w{w}t{i}", fn=SEBS_FUNCTIONS[i % 7])
            for i in range(n)
        ])
        res = eng.flush()
        out.append((res.assignments, res.schedule.energy_j,
                    res.schedule.makespan_s))
    return out, eng


@pytest.mark.parametrize("sizes", [(70, 3, 41), (1, 1, 1), (200, 64, 129)],
                         ids=["deep-tiny-medium", "single-tasks", "padded"])
@pytest.mark.parametrize("alpha", [0.0, 0.2, 0.5, 1.0])
def test_online_jax_state_matches_soa_state(alpha, sizes):
    """The live state carried across windows of the given sizes stays
    soa's, double for double, at every objective weighting."""
    a, eng_a = _online("soa", alpha, sizes)
    b, eng_b = _online("jax", alpha, sizes)
    assert isinstance(eng_a.state, SoAState)
    assert isinstance(eng_b.state, SoAState)
    for (asg_a, e_a, c_a), (asg_b, e_b, c_b) in zip(a, b):
        assert asg_a == asg_b
        assert e_a == e_b
        assert c_a == c_b
    assert eng_a.state.metrics() == eng_b.state.metrics()
    # input-staging cache must round-trip through the scan identically
    assert eng_a.state.cached == eng_b.state.cached


def test_online_engine_param_builds_jax_policy():
    eps = table1_testbed()
    eng = OnlineEngine(eps, policy="mhra", engine="jax")
    assert eng.policy.engine == "jax"
    assert isinstance(eng.state, SoAState)
    assert get_policy("mhra", engine="jax").engine == "jax"


# ---------------------------------------------------------------------------
# auto crossover
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_endpoints, n_tasks, engine", [
    (8, 8192, "jax"),        # the jax tier's corner: 8 x 8192 = 2^16 cells
    (8, 8191, "soa"),        # one task short
    (7, 10 ** 9, "soa"),     # one endpoint short
    (10 ** 4, None, "soa"),  # streaming never escalates to jax
    (32, 8192, "jax"),       # the batch cell's window
    (256, 256, "jax"),       # a full stream window
    (256, 255, "soa"),       # a timer-closed stream window
    (16, None, "soa"),
    (15, None, "delta"),
    (4, None, "delta"),      # the paper's Table-I testbed, streaming
    (4, 64, "soa"),          # 256 cells
    (4, 63, "delta"),
    (8, 32, "soa"),
    (8, 31, "delta"),
    (16, 1, "soa"),          # wide enough for soa at any depth
    (15, 17, "delta"),
    (15, 18, "soa"),
])
def test_auto_engine_resolves(n_endpoints, n_tasks, engine):
    """Every edge of the fleet-size / window-depth crossover."""
    assert auto_engine(n_endpoints, n_tasks) == engine


def _hide_placement_ops(monkeypatch):
    """Make ``repro.kernels.placement.ops`` un-importable."""
    monkeypatch.setitem(sys.modules, "repro.kernels.placement.ops", None)
    monkeypatch.delattr(repro.kernels.placement, "ops", raising=False)


def test_auto_engine_jax_requires_importable_backend(monkeypatch):
    """auto decides on fleet size and window depth only — it never probes
    the import — so a window it routes to jax raises when the device path
    cannot import, instead of silently running soa."""
    _hide_placement_ops(monkeypatch)
    me, mc = sched.AUTO_JAX_MIN_ENDPOINTS, sched.AUTO_JAX_MIN_CELLS
    assert auto_engine(me, mc // me) == "jax"
    tasks, eps, store, tm = _setup(n_per=3, with_inputs=False, replicas=2)
    monkeypatch.setattr(sched, "AUTO_JAX_MIN_ENDPOINTS", len(eps))
    monkeypatch.setattr(sched, "AUTO_JAX_MIN_CELLS", len(eps) * len(tasks))
    with pytest.raises(ImportError):
        mhra(tasks, eps, store, tm, alpha=0.5, engine="auto")


def test_explicit_jax_raises_when_ops_cannot_import(monkeypatch):
    _hide_placement_ops(monkeypatch)
    tasks, eps, store, tm = _setup(n_per=2)
    with pytest.raises(ImportError):
        mhra(tasks, eps, store, tm, alpha=0.5, engine="jax")
    eng = OnlineEngine(eps, policy="mhra", engine="jax", store=store,
                       monitoring=False)
    eng.submit_many(tasks)
    with pytest.raises(ImportError):
        eng.flush()


def test_window_stats_count_device_and_soa_windows():
    """A clustered window is handed to soa and counted as such; a plain
    window is placed by the scan and counted as a device window, with the
    arrays it moved: 11 task streams, 18 carry seeds, 12 constant tables
    and 12 scalars up; the 18 carry registers and 3 outputs back.  Its 28
    tasks, 4 of each of 7 functions, are 28 scan steps, 7 of which start a
    run (runs are whole functions under every heuristic)."""
    tasks, eps, store, tm = _setup(n_per=4)
    pops.reset_window_stats()
    cluster_mhra(tasks, eps, store, tm, alpha=0.5, max_cluster_size=16,
                 engine="jax")
    zero = {"h2d_bytes": 0, "h2d_arrays": 0, "d2h_bytes": 0, "d2h_arrays": 0,
            "basis_steps": 0, "scan_steps": 0}
    assert pops.WINDOW_STATS == {"device": 0, "soa": 1, **zero}
    mhra(tasks, eps, store, tm, alpha=0.5, engine="jax")
    one = dict(pops.WINDOW_STATS)
    assert (one["device"], one["soa"]) == (1, 1)
    assert (one["h2d_arrays"], one["d2h_arrays"]) == (53, 21)
    assert one["h2d_bytes"] > 0 and one["d2h_bytes"] > 0
    assert (one["basis_steps"], one["scan_steps"]) == (7, 28)
    mhra(tasks, eps, store, tm, alpha=0.5, engine="soa")
    assert pops.WINDOW_STATS == one
    pops.reset_window_stats()
    assert pops.WINDOW_STATS == {"device": 0, "soa": 0, **zero}


def test_auto_batch_escalates_to_jax_and_matches_soa(monkeypatch):
    """engine="auto" above the jax crossover routes to the fused scan and
    stays bitwise-identical to an explicit soa run.  The calibrated
    thresholds need thousands of tasks, so drop them to the fixture size
    — the routing logic is what's under test, the calibration is pinned
    by test_auto_engine_resolves."""
    tasks, eps, store, tm = _setup(n_per=3, with_inputs=False, replicas=2)
    monkeypatch.setattr(sched, "AUTO_JAX_MIN_ENDPOINTS", len(eps))
    monkeypatch.setattr(sched, "AUTO_JAX_MIN_CELLS", len(eps) * len(tasks))
    assert auto_engine(len(eps), len(tasks)) == "jax"
    a = mhra(tasks, eps, store, tm, alpha=0.5, engine="soa")
    b = mhra(tasks, eps, store, tm, alpha=0.5, engine="auto")
    _assert_bitwise(a, b)


# ---------------------------------------------------------------------------
# backend override plumbing (satellite: REPRO_PLACEMENT_BACKEND)
# ---------------------------------------------------------------------------


def test_placement_backend_env_override(monkeypatch):
    from repro.kernels import dispatch
    monkeypatch.delenv("REPRO_PLACEMENT_BACKEND", raising=False)
    # the fused jnp scan everywhere
    assert dispatch.placement_backend() == "xla"
    assert not dispatch.placement_use_pallas()
    assert pops.lane_bucket(32) == 32
    monkeypatch.setenv("REPRO_PLACEMENT_BACKEND", "ref")
    assert dispatch.placement_backend() == "ref"
    assert not dispatch.placement_use_pallas()
    monkeypatch.setenv("REPRO_PLACEMENT_BACKEND", "xla")
    assert dispatch.placement_backend() == "xla"
    monkeypatch.setenv("REPRO_PLACEMENT_BACKEND", "pallas_interpret")
    assert dispatch.placement_backend() == "pallas_interpret"
    assert dispatch.placement_use_pallas()
    assert pops.lane_bucket(32) == 128
    # "pallas" is refused with the reason, never coerced to interpret mode
    monkeypatch.setenv("REPRO_PLACEMENT_BACKEND", "pallas")
    with pytest.raises(ValueError, match="float64"):
        dispatch.placement_backend()
    monkeypatch.setenv("REPRO_PLACEMENT_BACKEND", "mosaic")
    with pytest.raises(ValueError, match="expected one of"):
        dispatch.placement_backend()
    monkeypatch.setenv("REPRO_PLACEMENT_BACKEND", "pallas")
    tasks, eps, store, tm = _setup(n_per=1)
    with pytest.raises(ValueError, match="pallas_interpret"):
        mhra(tasks, eps, store, tm, alpha=0.5, engine="jax")


def test_jax_matches_soa_under_pallas_interpret(monkeypatch):
    """The tiled Pallas score+argmin kernel (interpret mode on CPU) is
    parity-locked too, not just the fused-XLA path."""
    monkeypatch.setenv("REPRO_PLACEMENT_BACKEND", "pallas_interpret")
    tasks, eps, store, tm = _setup(n_per=4)
    a = mhra(tasks, eps, store, tm, alpha=0.3, engine="soa")
    b = mhra(tasks, eps, store, tm, alpha=0.3, engine="jax")
    _assert_bitwise(a, b)
