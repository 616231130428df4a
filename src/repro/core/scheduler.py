"""MHRA + Cluster MHRA schedulers (paper §III-F, Algorithm 1) and the
Round-Robin / single-site baselines evaluated in Table V.

Objective:  O = alpha * E_tot/SF1 + (1-alpha) * C_max/SF2
  E_tot = sum_n [ idle_power * allocated-span(+startup) + sum dyn task E ]
          + transfer energy;  desktop-style endpoints charge idle over the
          whole workflow span (paper: power drawn whether or not tasks run).
  SF1/SF2 = pessimistic all-on-one-machine estimates.

Three greedy engines share the same arithmetic:

  * ``engine="delta"`` (default) scores a candidate endpoint by previewing
    only the *change* it makes to the live state — peek/copy that one
    endpoint's slot heap, delta the idle-span and dynamic-energy terms —
    then commits only the winner.  O(endpoints * log cores) per decision.
  * ``engine="soa"`` lays the state out as structure-of-arrays
    (:class:`SoAState`: one flat float64 array of core free-times with
    per-endpoint offsets plus vector registers) and scores a unit against
    *every* endpoint in a handful of vectorized passes, with run
    memoization making most decisions O(1) scalar work.  Fastest at large
    fleets / task counts; see :func:`_greedy_soa`.
  * ``engine="clone"`` is the original clone-per-candidate greedy kept as
    the reference implementation for parity tests and the overhead
    benchmark.  O(endpoints^2 * cores) copies per decision.

delta and clone perform bitwise-identical floating-point operations, so
they produce identical assignments and objective values
(``tests/test_policy_engine.py``).  soa regroups the candidate-score sum
for vectorization (~1 ulp), which can only reorder *exact ties* — broken
identically by both engines — so assignments match delta exactly and
reported objectives are bitwise-equal in practice, asserted to
rtol=1e-12 (``tests/test_soa_engine.py``).  The delta and soa engines
also accept a live state so the online engine (``repro.core.engine``)
can place arrival windows against the timeline carried over from
previous windows.
"""
from __future__ import annotations

import dataclasses
import heapq
from typing import Callable, Sequence

import numpy as np

from repro.core.carbon import CarbonWeights
from repro.core.clustering import agglomerative_cluster
from repro.core.dag import LookaheadWeights
from repro.core.endpoint import EndpointSpec
from repro.core.fairness import FairnessWeights
from repro.core.faults import WarmWeights
from repro.core.predictor import Prediction, TaskProfileStore
from repro.core.spans import span
from repro.core.transfer import E_INC_J_PER_BYTE, TransferModel

#: Run-memoization counters for the SoA greedy (``_greedy_soa``): a "hit"
#: is a unit scored by reusing the previous unit's vectorized pass (the
#: O(1) fast path), a "miss" is a full vectorized scoring pass.  Promoted
#: DAG children share one ``not_before`` per completion epoch precisely so
#: wide stages stay inside one run — the epoch is threaded into the memo
#: key through that field.  Cumulative across calls; reset with
#: :func:`reset_memo_stats`.
MEMO_STATS = {"hits": 0, "misses": 0}


def reset_memo_stats() -> None:
    MEMO_STATS["hits"] = 0
    MEMO_STATS["misses"] = 0


#: Calibrated ``engine="auto"`` crossover (measured on the scaled SeBS
#: testbed, min-of-30 timings, post constant-factor shave): soa beats
#: delta at *every* batch size from 16 endpoints up (0.98x at the n=4
#: worst case, >1.2x elsewhere); below 16 endpoints its per-call array
#: setup needs endpoints*tasks score cells to amortize — measured break-
#: even at 4 eps x 64 tasks and 8 eps x 32 tasks, i.e. ~256 cells.
AUTO_SOA_MIN_ENDPOINTS = 16
AUTO_SOA_MIN_CELLS = 256

#: ``engine="jax"`` crossover (measured with XLA:CPU on the scaled SeBS
#: testbed, warm timings with the one-off JIT compile accounted
#: separately — not yet measured on a TPU): the fused lax.scan greedy beats soa once the
#: window is deep enough to amortize host array prep and device
#: round-trips — measured from 8 endpoints at 8k-task windows (2^16
#: score cells; jax 0.18s vs soa 0.30s there, and the margin only grows
#: with the fleet).  Smaller windows stay on soa; tiny fleets never
#: switch (the vector passes don't pay for the scan's fixed overhead).
AUTO_JAX_MIN_ENDPOINTS = 8
AUTO_JAX_MIN_CELLS = 1 << 16


def auto_engine(n_endpoints: int, n_tasks: int | None = None) -> str:
    """Resolve ``engine="auto"`` to a concrete greedy backend.

    Fleet-size/window-size crossover: ``soa`` needs enough endpoints for
    its vectorized candidate passes to beat delta's python loop, and (in
    batch mode, where ``n_tasks`` is known) enough score cells to
    amortize its per-call array setup.  ``n_tasks=None`` (streaming:
    window sizes are unknown up front) decides on fleet size alone,
    conservatively — delta is never worse than soa by much at small
    fleets, while soa's setup can triple a tiny window's latency.  Above
    the jax crossover (large fleet *and* a deep window to scan over) the
    fused ``engine="jax"`` backend takes over — batch-size-aware only."""
    if (n_tasks is not None and n_endpoints >= AUTO_JAX_MIN_ENDPOINTS
            and n_endpoints * n_tasks >= AUTO_JAX_MIN_CELLS):
        return "jax"
    if n_endpoints >= AUTO_SOA_MIN_ENDPOINTS:
        return "soa"
    if n_tasks is None:
        return "delta"
    return "soa" if n_endpoints * n_tasks >= AUTO_SOA_MIN_CELLS else "delta"


@dataclasses.dataclass(frozen=True)
class TaskSpec:
    """One task submission.

    ``inputs`` are transfer templates ``(src, n_files, total_bytes,
    shared)`` — src is an endpoint name; shared inputs are cached per
    destination endpoint.  ``deps``/``dep_bytes`` describe DAG edges: the
    task may not start before every parent task id in ``deps`` has
    completed, and it pulls ``dep_bytes`` bytes from each parent's
    *producing endpoint* (the online engine rewrites these into concrete
    ``inputs`` entries once the parents' placements are known).
    ``not_before`` is the resolved ready floor in seconds — every engine
    clamps the task's start time to it.  Instances are frozen; the engine
    promotes a dependent task by building a ``dataclasses.replace`` copy.
    """
    id: str
    fn: str
    inputs: tuple = ()          # tuple of TransferRequest templates (src, files, bytes, shared)
    user: str = "user0"
    deps: tuple = ()            # parent task ids; placeable only once all complete
    dep_bytes: float = 0.0      # bytes pulled from each parent's endpoint
    not_before: float = 0.0     # earliest start (s); set when deps resolve
    deadline: float = float("inf")  # latest completion (s); bounds carbon deferral


@dataclasses.dataclass
class Schedule:
    assignments: dict[str, str]
    objective: float
    energy_j: float
    makespan_s: float
    transfer_j: float
    heuristic: str = ""
    timeline: dict[str, tuple[float, float]] = dataclasses.field(default_factory=dict)
    carbon_g: float | None = None   # scoring-time gCO2 estimate (carbon runs)

    def edp(self) -> float:
        return self.energy_j * self.makespan_s

    def w_ed2p(self) -> float:
        return self.energy_j * self.makespan_s ** 2

    def cdp(self) -> float | None:
        """Carbon-delay product gCO2*s (None outside carbon-aware runs)."""
        if self.carbon_g is None:
            return None
        return self.carbon_g * self.makespan_s


HEURISTICS = (
    "shortest_runtime_first",
    "longest_runtime_first",
    "highest_energy_first",
    "lowest_energy_first",
)


class SchedulerState:
    """Incremental greedy-scheduling state over endpoint timelines.

    Carried across arrival windows by the online engine.  The legacy clone
    engine evaluates candidates with :meth:`clone` + :meth:`assign` +
    :meth:`metrics`; the delta greedy (:func:`_greedy_delta`) unpacks this
    state into flat lists and performs the *same float operations* inline
    — any edit to assign()/metrics() arithmetic must be mirrored there to
    preserve the engines' bitwise parity.
    """

    def __init__(self, endpoints: Sequence[EndpointSpec], transfer: TransferModel):
        self.eps = list(endpoints)
        self.transfer = transfer
        self.slots = {e.name: [0.0] * e.cores for e in endpoints}  # min-heaps
        for h in self.slots.values():
            heapq.heapify(h)
        self.first_start = {e.name: None for e in endpoints}
        self.last_end = {e.name: 0.0 for e in endpoints}
        self.dyn_energy = {e.name: 0.0 for e in endpoints}
        self.transfer_j = 0.0
        self.cached: set[tuple[str, str]] = set()
        self.timeline: dict[str, tuple[float, float]] = {}

    def clone(self, keep_timeline: bool = False) -> "SchedulerState":
        s = SchedulerState.__new__(SchedulerState)
        s.eps, s.transfer = self.eps, self.transfer
        s.slots = {k: list(v) for k, v in self.slots.items()}
        s.first_start = dict(self.first_start)
        s.last_end = dict(self.last_end)
        s.dyn_energy = dict(self.dyn_energy)
        s.transfer_j = self.transfer_j
        s.cached = set(self.cached)
        # candidate previews don't need task-level timelines; scratch states
        # that may become the live state (multi-heuristic search) do
        s.timeline = dict(self.timeline) if keep_timeline else {}
        return s

    def advance_to(self, now: float) -> None:
        """Raise every worker slot's free time to at least ``now`` — the
        online engine calls this when an arrival window opens after an idle
        gap, so placement previews can't schedule starts in the past
        (mirroring the testbed's ``max(slot, now)`` dispatch rule)."""
        for h in self.slots.values():
            changed = False
            for i, v in enumerate(h):
                if v < now:
                    h[i] = now
                    changed = True
            if changed:
                heapq.heapify(h)

    def replace_with(self, other: "SchedulerState") -> None:
        """Adopt another state's contents in place (winner of a heuristic
        search replacing the live online state)."""
        self.slots = other.slots
        self.first_start = other.first_start
        self.last_end = other.last_end
        self.dyn_energy = other.dyn_energy
        self.transfer_j = other.transfer_j
        self.cached = other.cached
        self.timeline = other.timeline

    def drop_timeline(self, task_ids) -> int:
        """Retire finished tasks' timeline entries (live-state pruning:
        the online engine drops a task once it has completed, so per-window
        timeline snapshots and heuristic-search clones stay O(live) instead
        of O(total-ever-placed)).  Scoring never reads the timeline, so
        this cannot affect placement parity.  Returns the count dropped."""
        pop = self.timeline.pop
        n = 0
        for tid in task_ids:
            if pop(tid, None) is not None:
                n += 1
        return n

    # -- transfer bookkeeping shared by assign() and preview() -------------
    def _transfer_delta(self, unit, name: str):
        """(transfer_j_after, ready_s, cache_keys_added) for placing this
        unit's inputs on endpoint ``name`` — no state mutation."""
        return _unit_transfer_delta(
            self.transfer, self.cached, self.transfer_j, unit, name
        )

    def assign(
        self,
        unit: Sequence[TaskSpec],
        ep: EndpointSpec,
        preds: dict[str, Prediction],
        record_timeline: bool = False,
    ) -> None:
        name = ep.name
        transfer_j, ready, new_cached = self._transfer_delta(unit, name)
        self.transfer_j = transfer_j
        self.cached.update(new_cached)
        if ep.has_batch_scheduler:
            ready += ep.queue_delay_s
        slots = self.slots[name]
        for t in unit:
            p = preds[t.id]
            start = max(heapq.heappop(slots), ready)
            if start < t.not_before:
                start = t.not_before
            end = start + p.runtime_s
            heapq.heappush(slots, end)
            if self.first_start[name] is None or start < self.first_start[name]:
                self.first_start[name] = start
            self.last_end[name] = max(self.last_end[name], end)
            self.dyn_energy[name] += p.energy_j
            if record_timeline:
                self.timeline[t.id] = (start, end)

    def metrics(self) -> tuple[float, float, float]:
        """(E_tot, C_max, transfer_j)."""
        c_max = max([v for v in self.last_end.values()] + [0.0])
        e_tot = self.transfer_j
        for ep in self.eps:
            n = ep.name
            if self.first_start[n] is None:
                if not ep.has_batch_scheduler:
                    # always-on endpoint idles through the workflow regardless
                    e_tot += ep.idle_power_w * c_max
                continue
            if ep.has_batch_scheduler:
                span = self.last_end[n] - self.first_start[n]
                e_tot += ep.idle_power_w * span + ep.startup_energy_j
            else:
                e_tot += ep.idle_power_w * c_max
            e_tot += self.dyn_energy[n]
        return e_tot, c_max, self.transfer_j


def _unit_transfer_delta(transfer, cached, transfer_j, unit, name):
    """(transfer_j_after, ready_s, cache_keys_added) for placing ``unit``'s
    inputs on endpoint ``name`` — pure function of the cache contents,
    shared by the heap- and SoA-backed states."""
    t_bytes, t_files = 0.0, 0
    new_cached: list[tuple[str, str]] = []
    for t in unit:
        for src, n_files, nbytes, shared in t.inputs:
            if src == name:
                continue
            key = (name, f"{src}:{n_files}:{nbytes}")
            if shared and (key in cached or key in new_cached):
                continue
            if shared:
                new_cached.append(key)
            transfer_j += transfer.hops(src, name) * nbytes * E_INC_J_PER_BYTE
            t_bytes += nbytes
            t_files += n_files
    ready = transfer.predict_seconds(t_files, t_bytes)
    return transfer_j, ready, new_cached


# kept as an alias: pre-refactor code and tests referred to _State
_State = SchedulerState


class SoAState:
    """Structure-of-arrays scheduling state: the third engine backend.

    Same semantics as :class:`SchedulerState`, different layout: core
    free-times live in ONE flat float64 array segmented by per-endpoint
    ``offsets``, and the per-endpoint registers (``first``/``last``/
    ``dyn``) are vectors, so the SoA greedy (:func:`_greedy_soa`) scores a
    unit against *every* endpoint in a handful of vectorized passes
    instead of a Python loop over candidates.

    ``first_start[i] == np.inf`` encodes the heap state's ``None``
    ("endpoint never used").  A heap pop-min + push(end) becomes
    "overwrite the argmin slot with end" — identical multiset evolution,
    so ``assign``/``metrics`` produce bitwise-identical floats to the
    heap-backed state given the same placement sequence.

    Units: ``free``/``first``/``last`` are seconds, ``dyn``/``transfer_j``
    joules; ``metrics()`` returns ``(E_tot J, C_max s, transfer J)``.
    ``assign`` mutates in place (including the task-start clamp to
    ``TaskSpec.not_before``); ``clone`` deep-copies the arrays but shares
    the immutable endpoint/transfer objects; ``replace_with`` adopts
    another state's arrays *by reference*.  No randomness anywhere in the
    scheduling state — determinism comes for free.
    """

    def __init__(self, endpoints: Sequence[EndpointSpec], transfer: TransferModel):
        self.eps = list(endpoints)
        self.transfer = transfer
        self.names = [e.name for e in self.eps]
        self.ep_index = {n: i for i, n in enumerate(self.names)}
        cores = np.array([e.cores for e in self.eps], dtype=np.intp)
        self.offsets = np.zeros(len(self.eps) + 1, dtype=np.intp)
        np.cumsum(cores, out=self.offsets[1:])
        self.free = np.zeros(int(self.offsets[-1]))      # flat core free-times
        self.first = np.full(len(self.eps), np.inf)      # inf == never used
        self.last = np.zeros(len(self.eps))
        self.dyn = np.zeros(len(self.eps))
        self.transfer_j = 0.0
        self.cached: set[tuple[str, str]] = set()
        self.timeline: dict[str, tuple[float, float]] = {}

    # -- layout helpers ----------------------------------------------------
    def slot_view(self, ei: int) -> np.ndarray:
        """Writable view of endpoint ``ei``'s core free-times."""
        return self.free[self.offsets[ei]:self.offsets[ei + 1]]

    def slot_mins(self) -> np.ndarray:
        """Per-endpoint min free-time in one reduceat pass."""
        return np.minimum.reduceat(self.free, self.offsets[:-1])

    # -- SchedulerState-compatible surface ---------------------------------
    def clone(self, keep_timeline: bool = False) -> "SoAState":
        s = SoAState.__new__(SoAState)
        s.eps, s.transfer = self.eps, self.transfer
        s.names, s.ep_index, s.offsets = self.names, self.ep_index, self.offsets
        s.free = self.free.copy()
        s.first = self.first.copy()
        s.last = self.last.copy()
        s.dyn = self.dyn.copy()
        s.transfer_j = self.transfer_j
        s.cached = set(self.cached)
        s.timeline = dict(self.timeline) if keep_timeline else {}
        return s

    def replace_with(self, other: "SoAState") -> None:
        self.free = other.free
        self.first = other.first
        self.last = other.last
        self.dyn = other.dyn
        self.transfer_j = other.transfer_j
        self.cached = other.cached
        self.timeline = other.timeline

    def drop_timeline(self, task_ids) -> int:
        """Same contract as :meth:`SchedulerState.drop_timeline`."""
        pop = self.timeline.pop
        n = 0
        for tid in task_ids:
            if pop(tid, None) is not None:
                n += 1
        return n

    def advance_to(self, now: float) -> None:
        """Vectorized twin of SchedulerState.advance_to: raise every core's
        free time to at least ``now``."""
        np.maximum(self.free, now, out=self.free)

    def _transfer_delta(self, unit, name: str):
        return _unit_transfer_delta(
            self.transfer, self.cached, self.transfer_j, unit, name
        )

    def assign(
        self,
        unit: Sequence[TaskSpec],
        ep: EndpointSpec,
        preds: dict[str, Prediction],
        record_timeline: bool = False,
    ) -> None:
        ei = self.ep_index[ep.name]
        transfer_j, ready, new_cached = self._transfer_delta(unit, ep.name)
        self.transfer_j = transfer_j
        self.cached.update(new_cached)
        if ep.has_batch_scheduler:
            ready += ep.queue_delay_s
        slots = self.slot_view(ei)
        first = self.first[ei]
        last = self.last[ei]
        dyn = self.dyn[ei]
        for t in unit:
            p = preds[t.id]
            k = int(np.argmin(slots))
            start = slots[k]
            if start < ready:
                start = ready
            if start < t.not_before:
                start = t.not_before
            end = start + p.runtime_s
            slots[k] = end
            if start < first:
                first = start
            if end > last:
                last = end
            dyn += p.energy_j
            if record_timeline:
                self.timeline[t.id] = (start, end)
        self.first[ei] = first
        self.last[ei] = last
        self.dyn[ei] = dyn

    def metrics(self) -> tuple[float, float, float]:
        """(E_tot, C_max, transfer_j) — same accumulation order as
        SchedulerState.metrics, reading the vector registers."""
        c_max = max(float(self.last.max(initial=0.0)), 0.0)
        e_tot = self.transfer_j
        for ei, ep in enumerate(self.eps):
            if self.first[ei] == np.inf:
                if not ep.has_batch_scheduler:
                    e_tot += ep.idle_power_w * c_max
                continue
            if ep.has_batch_scheduler:
                span = float(self.last[ei]) - float(self.first[ei])
                e_tot += ep.idle_power_w * span + ep.startup_energy_j
            else:
                e_tot += ep.idle_power_w * c_max
            e_tot += float(self.dyn[ei])
        return e_tot, c_max, self.transfer_j

    # -- interop with the heap-backed state --------------------------------
    @classmethod
    def from_heap(cls, state: SchedulerState) -> "SoAState":
        s = cls(state.eps, state.transfer)
        for ei, name in enumerate(s.names):
            s.slot_view(ei)[:] = state.slots[name]
            f = state.first_start[name]
            s.first[ei] = np.inf if f is None else f
            s.last[ei] = state.last_end[name]
            s.dyn[ei] = state.dyn_energy[name]
        s.transfer_j = state.transfer_j
        s.cached = set(state.cached)
        s.timeline = dict(state.timeline)
        return s

    def write_back(self, state: SchedulerState) -> None:
        """Adopt this SoA state's contents into a heap-backed state."""
        for ei, name in enumerate(self.names):
            h = self.slot_view(ei).tolist()
            heapq.heapify(h)
            state.slots[name] = h
            f = float(self.first[ei])
            state.first_start[name] = None if f == np.inf else f
            state.last_end[name] = float(self.last[ei])
            state.dyn_energy[name] = float(self.dyn[ei])
        state.transfer_j = self.transfer_j
        state.cached = self.cached
        state.timeline = self.timeline


def _carbon_terms_g(eps, first, last, dyn, rates, c_max) -> float:
    """Carbon-adjusted endpoint energy in gCO2: each endpoint's share of
    E_tot (idle span / always-on idle + startup + dynamic) weighted by its
    g/J rate.  Transfer energy is excluded — its grid locus is ambiguous;
    the evaluation-side footprint bills it at the fleet-mean rate.

    The per-endpoint float expressions here are mirrored verbatim by the
    delta greedy's candidate loop, so the clone and delta engines stay
    bitwise-identical under carbon weighting too.
    """
    g = 0.0
    for j, ep in enumerate(eps):
        w = rates[j]
        f = first[j]
        if f is None:
            if not ep.has_batch_scheduler:
                g += w * (ep.idle_power_w * c_max)
            continue
        if ep.has_batch_scheduler:
            g += w * (ep.idle_power_w * (last[j] - f) + ep.startup_energy_j
                      + dyn[j])
        else:
            g += w * (ep.idle_power_w * c_max + dyn[j])
    return g


def state_carbon_g(state, rates) -> float:
    """gCO2 of a committed scheduling state under per-endpoint g/J
    ``rates`` (aligned with ``state.eps``); works on both the heap- and
    SoA-backed layouts.  See :func:`_carbon_terms_g` for the accounting."""
    if isinstance(state, SoAState):
        c_max = max(float(state.last.max(initial=0.0)), 0.0)
        first = [None if state.first[i] == np.inf else float(state.first[i])
                 for i in range(len(state.eps))]
        last = [float(v) for v in state.last]
        dyn = [float(v) for v in state.dyn]
    else:
        c_max = max([v for v in state.last_end.values()] + [0.0])
        names = [e.name for e in state.eps]
        first = [state.first_start[n] for n in names]
        last = [state.last_end[n] for n in names]
        dyn = [state.dyn_energy[n] for n in names]
    return _carbon_terms_g(state.eps, first, last, dyn, rates, c_max)


class PredictionTable:
    """Per-(task, endpoint) predictions as numpy arrays + flat lists.

    ``store.predict`` depends only on (fn, endpoint), so predictions are
    computed once per unique pair instead of once per task — at 1792 tasks
    over 7 functions that is ~256x fewer predictor calls than the nested
    dicts the clone engine builds.
    """

    def __init__(self, tasks, endpoints, store: TaskProfileStore):
        self.tasks = list(tasks)
        self.endpoints = list(endpoints)
        self.index = {t.id: i for i, t in enumerate(self.tasks)}
        cache: dict[tuple[str, str], Prediction] = {}
        n_ep = len(self.endpoints)
        # one predict per unique (fn, endpoint), expanded to tasks by
        # fancy indexing — same float values task-by-task
        fn_col: dict[str, int] = {}
        fn_ids = np.empty(len(self.tasks), dtype=np.intp)
        for ti, t in enumerate(self.tasks):
            c = fn_col.get(t.fn)
            if c is None:
                c = fn_col[t.fn] = len(fn_col)
            fn_ids[ti] = c
        base_rt = np.empty((n_ep, len(fn_col)))
        base_en = np.empty((n_ep, len(fn_col)))
        for ei, ep in enumerate(self.endpoints):
            for fn, c in fn_col.items():
                p = cache[(fn, ep.name)] = store.predict(fn, ep.name)
                base_rt[ei, c] = p.runtime_s
                base_en[ei, c] = p.energy_j
        self.rt = base_rt[:, fn_ids]
        self.en = base_en[:, fn_ids]
        self._cache = cache
        # python-float rows for the hot greedy loop (numpy scalar indexing
        # is ~5x slower than list indexing in CPython)
        self.rt_rows = self.rt.tolist()
        self.en_rows = self.en.tolist()
        # endpoint-mean predictions used by the ordering heuristics; the
        # axis-0 reduce performs the same sequential adds as the clone
        # engine's per-task np.mean over an endpoint list
        self.rt_mean = self.rt.mean(axis=0)
        self.en_mean = self.en.mean(axis=0)
        self._rtT: np.ndarray | None = None
        self._enT: np.ndarray | None = None

    def transposed(self) -> tuple[np.ndarray, np.ndarray]:
        """(n_tasks, n_ep) C-contiguous views for the SoA greedy: row
        ``ti`` is task ti's prediction across all endpoints (one slice, no
        per-candidate indexing).  Built on first use so the delta/clone
        paths don't pay for it."""
        if self._rtT is None:
            self._rtT = np.ascontiguousarray(self.rt.T)
            self._enT = np.ascontiguousarray(self.en.T)
        return self._rtT, self._enT

    def per_ep(self) -> dict[str, dict[str, Prediction]]:
        """Nested-dict view matching ``_predict_all`` for legacy callers."""
        return {
            ep.name: {t.id: self._cache[(t.fn, ep.name)] for t in self.tasks}
            for ep in self.endpoints
        }


def _unit_stats(unit, preds):
    rt = float(np.mean([preds[t.id].runtime_s for t in unit]))
    en = float(np.mean([preds[t.id].energy_j for t in unit]))
    return rt * len(unit), en * len(unit)


def _sort_units(units, key: str, preds):
    stats = [_unit_stats(u, preds) for u in units]
    if key == "shortest_runtime_first":
        order = np.argsort([s[0] for s in stats])
    elif key == "longest_runtime_first":
        order = np.argsort([-s[0] for s in stats])
    elif key == "highest_energy_first":
        order = np.argsort([-s[1] for s in stats])
    elif key == "lowest_energy_first":
        order = np.argsort([s[1] for s in stats])
    else:
        raise ValueError(key)
    return [units[i] for i in order]


def _sort_order(key: str, table: PredictionTable, unit_indices) -> np.ndarray:
    """Permutation ordering units by the heuristic ``key`` — the ordering
    :func:`_sort_units` produces, computed from the vectorized mean arrays.

    For singleton units the stat is the mean itself (mean of one element
    times one is the identity bitwise), so no per-unit np.mean calls.
    """
    rt_mean, en_mean = table.rt_mean, table.en_mean
    if all(len(ii) == 1 for ii in unit_indices):
        flat = [ii[0] for ii in unit_indices]
        rt_stat = rt_mean[flat]
        en_stat = en_mean[flat]
    else:
        rt_stat = np.empty(len(unit_indices))
        en_stat = np.empty(len(unit_indices))
        for k, ii in enumerate(unit_indices):
            m = len(ii)
            rt_stat[k] = float(np.mean(rt_mean[ii])) * m
            en_stat[k] = float(np.mean(en_mean[ii])) * m
    if key == "shortest_runtime_first":
        return np.argsort(rt_stat)
    if key == "longest_runtime_first":
        return np.argsort(-rt_stat)
    if key == "highest_energy_first":
        return np.argsort(-en_stat)
    if key == "lowest_energy_first":
        return np.argsort(en_stat)
    raise ValueError(key)


def _sort_units_fast(units, key: str, table: PredictionTable, unit_indices):
    """Same ordering as _sort_units from the vectorized mean arrays."""
    return [units[i] for i in _sort_order(key, table, unit_indices)]


def _predict_all(tasks, endpoints, store: TaskProfileStore):
    return {
        ep.name: {t.id: store.predict(t.fn, ep.name) for t in tasks}
        for ep in endpoints
    }


def _normalizers(tasks, endpoints, per_ep, transfer, carbon=None
                 ) -> tuple[float, float, float]:
    """SF1/SF2: pessimistic all-on-one-endpoint estimates (exact seed
    arithmetic — sequential accumulation keeps engine parity bitwise).
    With ``carbon`` given, SF3 is the matching pessimistic carbon estimate
    (all tasks on the endpoint, weighted by its own g/J rate)."""
    sf1 = sf2 = sf3 = 0.0
    for j, ep in enumerate(endpoints):
        st = SchedulerState([ep], transfer)
        st.assign(list(tasks), ep, per_ep[ep.name])
        e, c, _ = st.metrics()
        sf1, sf2 = max(sf1, e), max(sf2, c)
        if carbon is not None:
            sf3 = max(sf3, state_carbon_g(st, (carbon.rates[j],)))
    return max(sf1, 1e-9), max(sf2, 1e-9), max(sf3, 1e-9)


#: Fleets this wide and wider run the normalizers' list scheduling on one
#: slot matrix for every endpoint at once; narrower fleets run it endpoint
#: by endpoint on a heap, where a NumPy call per task costs more than the
#: few heap steps it replaces.  Measured on a TPU v5e host: the heap wins
#: up to 12 endpoints, the two tie at 16, the matrix wins at 32 (PERF.md).
NORMALIZER_MATRIX_MIN_ENDPOINTS = 16


def _normalizers_fast(tasks, endpoints, table: PredictionTable, transfer,
                      carbon=None) -> tuple[float, float, float]:
    """The SF1/SF2/SF3 doubles of :func:`_normalizers`, equal and not just
    close, from one pass over the window for the whole fleet.

    Shared-input deduplication does not depend on the destination: the key
    ``src:n_files:bytes`` names its source, so the inputs that count for
    endpoint X are the window's deduplicated inputs less those whose source
    is X.  The window's inputs are read once; each endpoint then adds the
    kept rows in window order, the same additions as a per-endpoint scan.
    The list scheduling of the window on each endpoint alone runs in
    :func:`_runs_by_matrix` or, on narrow fleets, :func:`_runs_by_heap`;
    the single-endpoint ``metrics()`` and carbon arithmetic follow per
    endpoint.  The ``gf.normalizers`` span reports ``inputs`` (task inputs
    read) and ``rows`` (transfer rows kept after deduplication).
    """
    with span("normalizers") as sp:
        rows: list[tuple[str, int, float]] = []
        seen: set[str] = set()
        n_inputs = 0
        for t in tasks:
            n_inputs += len(t.inputs)
            for src, n_files, nbytes, shared in t.inputs:
                if shared:
                    key = f"{src}:{n_files}:{nbytes}"
                    if key in seen:
                        continue
                    seen.add(key)
                rows.append((src, n_files, nbytes))
        sp.set_metadata(inputs=n_inputs, rows=len(rows))

        tjs, ready = [], []
        for ep in endpoints:
            # transfer delta of the whole workload as one unit, fresh cache
            name = ep.name
            tj, t_bytes, t_files = 0.0, 0.0, 0
            for src, n_files, nbytes in rows:
                if src == name:
                    continue
                tj += transfer.hops(src, name) * nbytes * E_INC_J_PER_BYTE
                t_bytes += nbytes
                t_files += n_files
            r = transfer.predict_seconds(t_files, t_bytes)
            if ep.has_batch_scheduler:
                r += ep.queue_delay_s
            tjs.append(tj)
            ready.append(r)

        nbs = [t.not_before for t in tasks]
        if tasks and len(endpoints) >= NORMALIZER_MATRIX_MIN_ENDPOINTS:
            runs = _runs_by_matrix(endpoints, table, ready, nbs)
        else:
            runs = _runs_by_heap(endpoints, table, ready, nbs)

        sf1 = sf2 = sf3 = 0.0
        for ei, (ep, e, (first, last, dyn)) in enumerate(zip(endpoints, tjs, runs)):
            # single-endpoint metrics(), same accumulation order
            c = last if last > 0.0 else 0.0
            if first is None:
                if not ep.has_batch_scheduler:
                    e += ep.idle_power_w * c
            else:
                if ep.has_batch_scheduler:
                    e += ep.idle_power_w * (last - first) + ep.startup_energy_j
                else:
                    e += ep.idle_power_w * c
                e += dyn
            sf1, sf2 = max(sf1, e), max(sf2, c)
            if carbon is not None:
                # single-endpoint _carbon_terms_g, same expression grouping
                w = carbon.rates[ei]
                if first is None:
                    g = w * (ep.idle_power_w * c) if not ep.has_batch_scheduler else 0.0
                elif ep.has_batch_scheduler:
                    g = w * (ep.idle_power_w * (last - first)
                             + ep.startup_energy_j + dyn)
                else:
                    g = w * (ep.idle_power_w * c + dyn)
                sf3 = max(sf3, g)
    return max(sf1, 1e-9), max(sf2, 1e-9), max(sf3, 1e-9)


def _runs_by_heap(endpoints, table: PredictionTable, ready, nbs):
    """``(first start, last end, dynamic energy)`` of the window's tasks on
    each endpoint alone, from empty: list scheduling over the endpoint's
    cores on a heap, one endpoint after another.  ``first`` is None for an
    empty window."""
    heapreplace = heapq.heapreplace
    runs = []
    for ei, ep in enumerate(endpoints):
        r = ready[ei]
        slots = [0.0] * ep.cores
        first = None
        last = 0.0
        dyn = 0.0
        for rt, en, nb in zip(table.rt_rows[ei], table.en_rows[ei], nbs):
            start = slots[0]
            if start < r:
                start = r
            if start < nb:
                start = nb
            end = start + rt
            heapreplace(slots, end)
            if first is None or start < first:
                first = start
            if end > last:
                last = end
            dyn += en
        runs.append((first, last, dyn))
    return runs


def _runs_by_matrix(endpoints, table: PredictionTable, ready, nbs):
    """What :func:`_runs_by_heap` returns for a window of one task or more,
    every endpoint advanced one task at a time.

    Popping a heap's smallest slot and pushing the task's end leaves the
    multiset that overwriting the smallest slot in place leaves (the
    identity :class:`SoAState` rests on), so the heaps become one
    (endpoints, max cores) slot matrix padded with +inf.  Slots start at
    ``max(0, ready)`` rather than 0: with no negative runtime every later
    slot is at least ``ready`` too, so the smallest slot is already the
    start clamped to ``ready``; the clamp to ``not_before`` is an identity
    when no task's ``not_before`` lies above the smallest ``ready``.
    ``dyn`` is each energy row summed in task order, as the heap adds it.
    """
    rtT, _ = table.transposed()
    n, n_ep = rtT.shape
    ready = np.array(ready)
    width = max(ep.cores for ep in endpoints)
    slots = np.full((n_ep, width), np.inf)
    floor = np.maximum(0.0, ready)
    for ei, ep in enumerate(endpoints):
        slots[ei, :ep.cores] = floor[ei]
    flat = slots.reshape(-1)
    base = np.arange(n_ep) * width
    clamp_ready = bool((rtT < 0.0).any())
    clamp_nb = max(nbs) > ready.min()
    starts = np.empty((n, n_ep))
    ends = np.empty((n, n_ep))
    argmin, add, maximum = slots.argmin, np.add, np.maximum
    for rt, nb, start, end in zip(rtT, nbs, starts, ends):
        k = argmin(1)
        k += base
        start[:] = flat[k]
        if clamp_ready:
            maximum(start, ready, start)
        if clamp_nb:
            maximum(start, nb, start)
        add(start, rt, end)
        flat[k] = end
    return list(zip(starts.min(axis=0).tolist(),
                    np.maximum(ends.max(axis=0), 0.0).tolist(),
                    np.cumsum(table.en, axis=1)[:, -1].tolist()))


def _warm_terms(warm: WarmWeights, alpha: float, sf1: float, sf2: float):
    """Per-endpoint warm-pool penalty added (last) to every candidate
    score: expected cold-start energy and latency normalized like the base
    objective terms.  Computed once per greedy call from the frozen
    :class:`WarmWeights` snapshot, so the three engines add the *same*
    doubles and the SoA run-memoization key is untouched (the penalty is
    constant within a call)."""
    return [
        alpha * cj / sf1 + (1 - alpha) * cs / sf2
        for cj, cs in zip(warm.cold_j, warm.cold_s)
    ]


def mhra(
    tasks: Sequence[TaskSpec],
    endpoints: Sequence[EndpointSpec],
    store: TaskProfileStore,
    transfer: TransferModel,
    alpha: float = 0.5,
    heuristics: Sequence[str] = HEURISTICS,
    clusters: list[list[int]] | None = None,
    engine: str = "delta",
    state: SchedulerState | None = None,
    carbon: CarbonWeights | None = None,
    lookahead: LookaheadWeights | None = None,
    alive: Sequence[bool] | None = None,
    warm: WarmWeights | None = None,
    fairness: FairnessWeights | None = None,
) -> Schedule:
    """Multi-Heuristic Resource Allocation. With clusters given, this is
    Cluster MHRA's greedy stage (one decision per cluster).

    ``state`` (delta/soa engines) places against a live timeline carried
    across arrival windows; the winning heuristic's result is committed
    into it.  ``carbon`` adds a third objective term
    ``gamma * G/SF3`` where G is the carbon-adjusted endpoint energy
    (gCO2) under the snapshot's per-endpoint g/J rates — all three
    engines score it, and ``carbon=None`` (the default) leaves every
    code path bitwise-identical to the carbon-free build.  ``lookahead``
    (a :class:`~repro.core.dag.LookaheadWeights` snapshot) adds the
    DAG-aware shaping term to every *candidate* score — rank-weighted
    finish times plus data-gravity transfer credits — in all three
    engines with the same clone/delta bitwise guarantee; the *reported*
    ``Schedule.objective`` stays the unshaped base objective (E, C are
    real; the shaping term prices hypothetical future placements).
    ``alive`` (per-endpoint booleans) masks dead endpoints out of
    candidate scoring — alive candidates' float sequences are untouched,
    so masking preserves clone/delta bitwise parity; an all-True mask is
    normalized to None (the unmodified hot path).  ``warm`` (a
    :class:`~repro.core.faults.WarmWeights` snapshot) adds a per-endpoint
    expected cold-start penalty as the final term of every candidate
    score — one extra SoA vector register.  ``fairness`` (a
    :class:`~repro.core.fairness.FairnessWeights` snapshot) adds the
    weighted-fair **advantage tax**: each task of an in-debt user is
    charged ``mu * debt`` times the advantage the candidate offers over
    the fleet-mean prediction (``relu(mean - predicted)``, energy and
    runtime terms SF-normalized like the base objective), steering
    over-budget users off premium endpoints.  All three engines add the
    same doubles (clone/delta bitwise, SoA one extra vector register
    whose per-task debt joins the run-memoization key); debt-free tasks
    — and ``fairness=None`` — leave every float sequence untouched.
    """
    if not heuristics:
        raise ValueError("mhra requires at least one ordering heuristic")
    if carbon is not None and len(carbon.rates) != len(endpoints):
        raise ValueError(
            f"carbon weights cover {len(carbon.rates)} endpoints but the "
            f"fleet has {len(endpoints)}"
        )
    if lookahead is not None and len(lookahead.hops_mean) != len(endpoints):
        raise ValueError(
            f"lookahead weights cover {len(lookahead.hops_mean)} endpoints "
            f"but the fleet has {len(endpoints)}"
        )
    if alive is not None:
        alive = tuple(bool(a) for a in alive)
        if len(alive) != len(endpoints):
            raise ValueError(
                f"alive mask covers {len(alive)} endpoints but the fleet "
                f"has {len(endpoints)}"
            )
        if not any(alive):
            raise ValueError("alive mask excludes every endpoint")
        if all(alive):
            alive = None   # no-op mask: keep the unmodified hot path
    if warm is not None and len(warm.cold_j) != len(endpoints):
        raise ValueError(
            f"warm weights cover {len(warm.cold_j)} endpoints but the "
            f"fleet has {len(endpoints)}"
        )
    if fairness is not None and (not fairness.debt or fairness.mu == 0.0):
        fairness = None   # no-op snapshot: keep the unmodified hot path
    if engine == "clone":
        if state is not None:
            raise ValueError("engine='clone' does not support live state")
        return _mhra_clone(tasks, endpoints, store, transfer, alpha,
                           heuristics, clusters, carbon, lookahead,
                           alive, warm, fairness)
    if engine == "auto":
        if state is not None:
            # online mode: match the live state's layout so no window ever
            # pays a from_heap/write_back conversion round-trip.  SoA-backed
            # states may still escalate to the jax scan per window — it
            # reads/writes the SoA layout directly, so the escalation is
            # conversion-free and reverts to soa on small windows.
            if isinstance(state, SoAState):
                engine = auto_engine(len(endpoints), len(tasks))
                if engine == "delta":
                    engine = "soa"
            else:
                engine = "delta"
        else:
            engine = auto_engine(len(endpoints), len(tasks))
    if engine not in ("delta", "soa", "jax"):
        raise ValueError(f"unknown engine {engine!r}")

    tasks = list(tasks)
    with span("predict"):
        table = PredictionTable(tasks, endpoints, store)
        if clusters is None:
            units = [[t] for t in tasks]
        else:
            units = [[tasks[i] for i in c] for c in clusters]
        unit_indices = [[table.index[t.id] for t in u] for u in units]
    sf1, sf2, sf3 = _normalizers_fast(tasks, endpoints, table, transfer, carbon)

    if engine in ("jax", "soa"):
        search = _mhra_jax if engine == "jax" else _mhra_soa
        sched = search(units, unit_indices, endpoints, table, transfer,
                       alpha, heuristics, sf1, sf2, state, carbon, sf3,
                       lookahead, alive, warm, fairness)
        # freeing the window's table and unit lists is a phase of its own at
        # deep windows (~10 ms for the 2 x 32 x 8192 floats of the table's
        # rows on a host CPU), so it happens inside a span
        with span("release"):
            del table, units, unit_indices
        return sched
    soa_live: SoAState | None = None
    if isinstance(state, SoAState):
        # delta engine over a SoA-backed live state: run on a heap view,
        # adopt the result back into the SoA arrays
        soa_live, state = state, SchedulerState(endpoints, transfer)
        soa_live.write_back(state)
    best: Schedule | None = None
    best_state: SchedulerState | None = None
    for h in heuristics:
        ordered = _sort_units_fast(units, h, table, unit_indices)
        sched, end_state = _greedy_delta(
            ordered, endpoints, table, transfer, alpha, sf1, sf2, h, state,
            carbon, sf3, lookahead, alive, warm, fairness,
        )
        if best is None or sched.objective < best.objective:
            best, best_state = sched, end_state
    if state is not None:
        state.replace_with(best_state)
        # the winner's timeline IS the live timeline now; snapshot it so
        # the returned Schedule survives later windows' mutations (losing
        # heuristics' schedules never get copied — one O(live) copy per
        # call instead of one per heuristic)
        best.timeline = dict(best.timeline)
    if soa_live is not None:
        soa_live.replace_with(SoAState.from_heap(state))
    return best


def _mhra_soa(units, unit_indices, endpoints, table, transfer, alpha,
              heuristics, sf1, sf2, state, carbon=None, sf3=1.0,
              lookahead=None, alive=None, warm=None, fairness=None):
    """SoA-engine heuristic search: run :func:`_greedy_soa` per ordering
    heuristic, commit the winner into ``state`` (heap- or SoA-backed)."""
    with span("soa"):
        heap_state: SchedulerState | None = None
        if isinstance(state, SchedulerState):
            heap_state, state = state, SoAState.from_heap(state)
        best: Schedule | None = None
        best_state: SoAState | None = None
        for h in heuristics:
            order = _sort_order(h, table, unit_indices)
            ordered = [units[i] for i in order]
            ordered_idx = [unit_indices[i] for i in order]
            sched, end_state = _greedy_soa(
                ordered, ordered_idx, endpoints, table, transfer, alpha,
                sf1, sf2, h, state, carbon, sf3, lookahead, alive, warm,
                fairness,
            )
            if best is None or sched.objective < best.objective:
                best, best_state = sched, end_state
        if heap_state is not None:
            best_state.write_back(heap_state)
            best.timeline = dict(best.timeline)
        elif state is not None:
            state.replace_with(best_state)
            best.timeline = dict(best.timeline)
        return best


def _mhra_jax(units, unit_indices, endpoints, table, transfer, alpha,
              heuristics, sf1, sf2, state, carbon=None, sf3=1.0,
              lookahead=None, alive=None, warm=None, fairness=None):
    """jax-engine heuristic search: one fused ``lax.scan`` greedy per
    window (all heuristics vmapped into a single device call), committing
    the winner into ``state`` exactly like :func:`_mhra_soa`.

    Parity-locked to the SoA engine: the scan reproduces ``_greedy_soa``'s
    float sequences double for double (see ``repro.kernels.placement``),
    the winning objective is recomputed from ``SoAState.metrics()`` on the
    final registers — the same authoritative accumulation soa reports —
    and first-min argmins break ties like ``np.argmin``.  Windows the fast
    path can't express (clustered units, multi-input tasks — e.g. DAG
    join stages whose promoted children carry several parent transfers)
    are handed to :func:`_mhra_soa`, which is assignment-identical by the
    existing contract, and counted in ``ops.WINDOW_STATS["soa"]``.  The
    live ``SoAState`` is read into device arrays at the window boundary
    and only the winner's registers are written back — no per-decision
    host/device chatter.  If the device path cannot import, this raises.
    """
    from repro.kernels.placement import ops as pops

    if (not units) or any(len(u) != 1 or len(u[0].inputs) > 1 for u in units):
        pops.WINDOW_STATS["soa"] += 1
        return _mhra_soa(units, unit_indices, endpoints, table, transfer,
                         alpha, heuristics, sf1, sf2, state, carbon, sf3,
                         lookahead, alive, warm, fairness)

    with span("pack"):
        heap_state: SchedulerState | None = None
        if isinstance(state, SchedulerState):
            heap_state, state = state, SoAState.from_heap(state)
        base = state if state is not None else SoAState(endpoints, transfer)
        n_ep = len(endpoints)
        names = base.names

        # per-endpoint constants — same host numpy expressions as _greedy_soa,
        # so every scalar entering the scan is the same double
        idle = np.array([ep.idle_power_w for ep in endpoints])
        bt_mask = np.array([ep.has_batch_scheduler for ep in endpoints])
        su = np.array([ep.startup_energy_j for ep in endpoints])
        qd_vec = np.where(bt_mask, [ep.queue_delay_s for ep in endpoints], 0.0)
        idle_bt = np.where(bt_mask, idle, 0.0)
        su_bt = np.where(bt_mask, su, 0.0)
        idle_on_sum = float(idle[~bt_mask].sum())
        c_cur0 = float(max(base.last.max(initial=0.0), 0.0))
        used = base.first < np.inf
        span0 = np.where(used, base.last - base.first, 0.0)
        const0 = np.where(bt_mask & used, idle * span0 + su, 0.0) + base.dyn
        a1 = alpha / sf1
        b1 = (1.0 - alpha) / sf2
        if carbon is not None:
            rates_v = np.asarray(carbon.rates, dtype=float)
            g1 = carbon.gamma / sf3
            w_idle_on = float((rates_v * idle)[~bt_mask].sum())
        else:
            rates_v = np.zeros(n_ep)
            g1 = 0.0
            w_idle_on = 0.0
        const_g0 = rates_v * const0
        if lookahead is not None:
            lk_tail, lk_out = lookahead.tail_w, lookahead.out_j
            lk_ht = lookahead.hops_task
            hm_vec = np.asarray(lookahead.hops_mean, dtype=float)
            lam = lookahead.lam
        else:
            lk_tail = lk_out = lk_ht = None
            hm_vec = np.zeros(n_ep)
            lam = 0.0
        lam_b1 = lam * b1   # lk_c1 = (lam*b1)*u_tw, soa's left-assoc grouping
        lam_a1 = lam * a1
        fdebt = fairness.debt if fairness is not None else None
        f_mu = fairness.mu if fairness is not None else 0.0
        f_beta = 1.0 - alpha
        wt_v = (np.asarray(_warm_terms(warm, alpha, sf1, sf2))
                if warm is not None else np.zeros(n_ep))
        alive_v = (np.ones(n_ep, dtype=bool) if alive is None
                   else np.asarray(alive, dtype=bool))

        # padded shapes: endpoint lanes / cores / tasks / input signatures
        E = pops.lane_bucket(n_ep)
        C = pops.bucket_pow2(max(ep.cores for ep in endpoints))
        n_units = len(units)
        T = pops.bucket_pow2(n_units)
        H = len(heuristics)

        def padv(v, fill=0.0):
            out = np.full(E, fill, dtype=float)
            out[:n_ep] = v
            return out

        # per-input-signature transfer table (slot 0 = the no-input dummy row:
        # zero adds, zero ready, staged everywhere — bitwise-inert)
        sig_index: dict[tuple, int] = {}
        add_rows = [np.zeros(E)]
        ready_list = [0.0]
        shared_list = [False]
        staged_rows = [np.ones(E, dtype=bool)]
        keys_list: list[list] = [[None] * n_ep]
        for u in units:
            t0 = u[0]
            if not t0.inputs:
                continue
            inp = t0.inputs[0]
            if inp in sig_index:
                continue
            src, n_files, nbytes, shared = inp
            ks = f"{src}:{n_files}:{nbytes}"
            keys = [None if n == src else (n, ks) for n in names]
            add = np.array([
                0.0 if k is None
                else transfer.hops(src, n) * nbytes * E_INC_J_PER_BYTE
                for n, k in zip(names, keys)
            ])
            staged = np.array([
                k is None or (shared and k in base.cached) for k in keys
            ])
            sig_index[inp] = len(add_rows)
            add_rows.append(padv(add))
            ready_list.append(transfer.predict_seconds(n_files, nbytes))
            shared_list.append(bool(shared))
            staged_rows.append(np.concatenate(
                [staged, np.ones(E - n_ep, dtype=bool)]))
            keys_list.append(keys)
        n_sigs = len(add_rows)
        S = pops.bucket_pow2(n_sigs)
        staged0 = np.ones((S, E), dtype=bool)
        staged0[:n_sigs] = np.stack(staged_rows)

        # carry seeds from the live state (pad lanes: fresh-endpoint registers
        # with zero slots — finite scores, masked dead before the argmin)
        slots0 = np.full((E, C), np.inf)
        slots0[n_ep:] = 0.0
        for ei in range(n_ep):
            sv = base.slot_view(ei)
            slots0[ei, :len(sv)] = sv
        mins0 = slots0.min(axis=1)
        first0 = padv(base.first, fill=np.inf)
        last0 = padv(base.last)
        dyn0 = padv(base.dyn)

        hm_p = padv(hm_vec)
        rtT, enT = table.transposed()
        en_mean, rt_mean = table.en_mean, table.rt_mean

        def tile(a):
            return np.broadcast_to(a, (H,) + a.shape).copy()

        xs = {
            "ti": np.zeros((H, T), dtype=np.int32),
            "hv_id": np.zeros((H, T), dtype=np.int32),
            "sig": np.zeros((H, T), dtype=np.int32),
            "ready_s": np.zeros((H, T)),
            "shared_s": np.zeros((H, T), dtype=bool),
            "nb": np.zeros((H, T)),
            "new_run": np.zeros((H, T), dtype=bool),
            "u_tw": np.zeros((H, T)),
            "u_oj": np.zeros((H, T)),
            "u_fd": np.zeros((H, T)),
            "valid": np.zeros((H, T), dtype=bool),
        }
        # one pass over the units computes every order-independent per-task
        # quantity; each heuristic then just permutes the shared arrays with
        # fancy indexing (the ordering is the only thing heuristics change)
        ti_all = np.fromiter((ui[0] for ui in unit_indices), dtype=np.intp,
                             count=n_units)
        nb_all = np.empty(n_units)
        sig_all = np.zeros(n_units, dtype=np.int32)
        u_tw_all = np.zeros(n_units)
        u_oj_all = np.zeros(n_units)
        u_fd_all = np.zeros(n_units)
        gid_all = np.empty(n_units, dtype=np.int64)
        key_ids: dict = {}
        # hop-vector table: row 0 is the fleet mean; producer-aware tasks get
        # their own (deduplicated) rows, indexed per task by ``hv_id``
        hv_rows = [hm_p]
        hv_ids: dict = {}
        hv_id_all = np.zeros(n_units, dtype=np.int32)
        tasks0 = [u[0] for u in units]
        if lk_tail is None and fdebt is None:
            # common case (no lookahead, no fairness): tight listcomp path —
            # the same (fn, inputs, not_before) run keys, far fewer dispatches
            key_list = [(t.fn, t.inputs, t.not_before) for t in tasks0]
            nb_all[:] = [k[2] for k in key_list]
            kid = key_ids.setdefault
            gid_all[:] = [kid(k, len(key_ids)) for k in key_list]
            if sig_index:
                sidx = sig_index.get
                sig_all[:] = [sidx(t.inputs[0], 0) if t.inputs else 0
                              for t in tasks0]
        else:
            for i, t0 in enumerate(tasks0):
                nb0 = t0.not_before
                nb_all[i] = nb0
                if lk_tail is not None:
                    u_tw = lk_tail.get(t0.id, 0.0)
                    u_oj = lk_out.get(t0.id, 0.0)
                    u_tw_all[i] = u_tw
                    u_oj_all[i] = u_oj
                    key = (t0.fn, t0.inputs, nb0, u_tw, u_oj)
                    if lk_ht is not None:
                        # same run-key split as the SoA engine: tasks with
                        # different consumer-hop vectors never share a run
                        hv_t = lk_ht.get(t0.id)
                        key = key + (hv_t,)
                        if hv_t is not None:
                            hid = hv_ids.get(hv_t)
                            if hid is None:
                                hid = hv_ids[hv_t] = len(hv_rows)
                                hv_rows.append(padv(np.asarray(hv_t)))
                            hv_id_all[i] = hid
                else:
                    key = (t0.fn, t0.inputs, nb0)
                if fdebt is not None:
                    u_fd = fdebt.get(t0.user, 0.0)
                    u_fd_all[i] = u_fd
                    key = key + (u_fd,)
                if t0.inputs:
                    sig_all[i] = sig_index[t0.inputs[0]]
                gid_all[i] = key_ids.setdefault(key, len(key_ids))
        ready_arr = np.asarray(ready_list)
        shared_arr = np.asarray(shared_list, dtype=bool)

        orders: list[np.ndarray] = []
        memo_misses = 0
        for hi, h in enumerate(heuristics):
            order = np.asarray(_sort_order(h, table, unit_indices),
                               dtype=np.intp)
            orders.append(order)
            xs["ti"][hi, :n_units] = ti_all[order]
            xs["hv_id"][hi, :n_units] = hv_id_all[order]
            xs["valid"][hi, :n_units] = True
            g = gid_all[order]
            nr = xs["new_run"][hi, :n_units]
            nr[0] = True
            np.not_equal(g[1:], g[:-1], out=nr[1:])
            memo_misses += int(nr.sum())
            s = sig_all[order]
            xs["sig"][hi, :n_units] = s
            xs["ready_s"][hi, :n_units] = ready_arr[s]
            xs["shared_s"][hi, :n_units] = shared_arr[s]
            xs["nb"][hi, :n_units] = nb_all[order]
            xs["u_tw"][hi, :n_units] = u_tw_all[order]
            xs["u_oj"][hi, :n_units] = u_oj_all[order]
            xs["u_fd"][hi, :n_units] = u_fd_all[order]
        MEMO_STATS["misses"] += memo_misses
        MEMO_STATS["hits"] += H * n_units - memo_misses

        # per-task (E,) rows enter the scan as gathers into these small
        # constant tables (profile rows / transfer signatures / hop vectors)
        # rather than as (H, T, E) streams — same doubles, ~E× less traffic
        P = pops.bucket_pow2(rtT.shape[0], minimum=1)
        rt_tab = np.zeros((P, E))
        en_tab = np.zeros((P, E))
        rt_tab[:rtT.shape[0], :n_ep] = rtT
        en_tab[:enT.shape[0], :n_ep] = enT
        fen_tab = np.zeros(P)
        frt_tab = np.zeros(P)
        fen_tab[:len(en_mean)] = en_mean
        frt_tab[:len(rt_mean)] = rt_mean
        add_tab = np.zeros((S, E))
        add_tab[:n_sigs] = np.stack(add_rows)
        V = pops.bucket_pow2(len(hv_rows))
        hv_tab = np.zeros((V, E))
        hv_tab[:len(hv_rows)] = np.stack(hv_rows)

        f64 = np.float64
        consts = {
            "idle_bt": padv(idle_bt),
            "su_bt": padv(su_bt),
            "qd": padv(qd_vec),
            "rates": padv(rates_v),
            "wt": padv(wt_v),
            "alive": np.concatenate([alive_v, np.zeros(E - n_ep, dtype=bool)]),
            "rt_tab": rt_tab, "en_tab": en_tab,
            "fen_tab": fen_tab, "frt_tab": frt_tab,
            "add_tab": add_tab, "hv_tab": hv_tab,
            "scalars": {
                "a1": f64(a1), "b1": f64(b1), "g1": f64(g1),
                "idle_on_sum": f64(idle_on_sum), "w_idle_on": f64(w_idle_on),
                "lam_b1": f64(lam_b1), "lam_a1": f64(lam_a1),
                "alpha": f64(alpha), "sf1": f64(sf1), "sf2": f64(sf2),
                "f_beta": f64(f_beta), "f_mu": f64(f_mu),
            },
        }
        init = {
            "mins": tile(mins0), "slots": tile(slots0), "first": tile(first0),
            "last": tile(last0), "dyn": tile(dyn0), "const": tile(padv(const0)),
            "const_g": tile(padv(const_g0)),
            "e_base": np.zeros((H, E)), "nl_r": np.zeros((H, E)),
            "g_base_r": np.zeros((H, E)), "lk_r": np.zeros((H, E)),
            "fw_r": np.zeros((H, E)), "staged": tile(staged0),
            "c_cur": np.full(H, c_cur0), "tj": np.full(H, base.transfer_j),
            "c_sum_b": np.zeros(H), "tj_b": np.zeros(H),
            "cg_sum_b": np.zeros(H),
        }

    out, (ei_y, s_y, e_y) = pops.greedy_window(n_ep, consts, init, xs)

    with span("winner"):
        # winner: objective recomputed from SoAState.metrics() per heuristic —
        # the same authoritative float sequence _greedy_soa reports
        best_hi = -1
        best_obj = None
        best_rec = None
        for hi, h in enumerate(heuristics):
            st_h = base.clone(keep_timeline=False)
            free, offsets = st_h.free, st_h.offsets
            for ei in range(n_ep):
                cores = offsets[ei + 1] - offsets[ei]
                free[offsets[ei]:offsets[ei + 1]] = out["slots"][hi, ei, :cores]
            st_h.first = out["first"][hi, :n_ep].copy()
            st_h.last = out["last"][hi, :n_ep].copy()
            st_h.dyn = out["dyn"][hi, :n_ep].copy()
            st_h.transfer_j = float(out["tj"][hi])
            e_tot, c_max, tjv = st_h.metrics()
            obj_f = alpha * e_tot / sf1 + (1 - alpha) * c_max / sf2
            carbon_g = None
            if carbon is not None:
                carbon_g = state_carbon_g(st_h, carbon.rates)
                obj_f = obj_f + carbon.gamma * carbon_g / sf3
            if best_obj is None or obj_f < best_obj:
                best_hi, best_obj = hi, obj_f
                best_rec = (st_h, obj_f, e_tot, c_max, tjv, carbon_g)

    with span("commit") as sp:
        st_w, obj_f, e_tot, c_max, tjv, carbon_g = best_rec
        h_name = heuristics[best_hi]
        assignments: dict[str, str] = {}
        timeline = dict(base.timeline)
        for t0, ei_v, s_v, e_v in zip(
            (units[i][0] for i in orders[best_hi]), ei_y[best_hi, :n_units],
            s_y[best_hi, :n_units], e_y[best_hi, :n_units],
        ):
            assignments[t0.id] = names[int(ei_v)]
            timeline[t0.id] = (float(s_v), float(e_v))
        st_w.timeline = timeline
        st_w.cached = set(base.cached)
        staged_out = out["staged"][best_hi]
        for si in range(1, n_sigs):
            if not shared_list[si]:
                continue
            row0, rowf, keys = staged_rows[si], staged_out[si], keys_list[si]
            for ei in range(n_ep):
                if rowf[ei] and not row0[ei] and keys[ei] is not None:
                    st_w.cached.add(keys[ei])
        sched = Schedule(assignments, obj_f, e_tot, c_max, tjv, h_name,
                         timeline, carbon_g=carbon_g)
        if heap_state is not None:
            st_w.write_back(heap_state)
            sched.timeline = dict(sched.timeline)
        elif state is not None:
            state.replace_with(st_w)
            sched.timeline = dict(sched.timeline)
        sp.set_metadata(timeline_len=len(sched.timeline))
    return sched


def _greedy_delta(
    units, endpoints, table: PredictionTable, transfer, alpha, sf1, sf2,
    heuristic, base_state: SchedulerState | None = None,
    carbon: CarbonWeights | None = None, sf3: float = 1.0,
    lookahead: LookaheadWeights | None = None,
    alive: tuple | None = None, warm: WarmWeights | None = None,
    fairness: FairnessWeights | None = None,
) -> tuple[Schedule, SchedulerState]:
    """Delta-evaluation greedy: score each candidate endpoint from the
    *change* it makes (peek the slot heap, delta the idle-span / dynamic
    energy / transfer terms) and commit only the winner.

    Every floating-point operation mirrors the clone engine's
    state.assign() + state.metrics() sequence, so objectives (and hence
    assignments) are bitwise identical; the savings are structural — no
    per-candidate copies of every heap, dict, and cache set.  Running
    C_max and per-endpoint span terms are maintained incrementally (exact:
    max() never rounds, and the span term is recomputed from the same
    operands the metrics loop would use).
    """
    state = (
        base_state.clone(keep_timeline=True)
        if base_state is not None
        else SchedulerState(endpoints, transfer)
    )
    n_ep = len(endpoints)
    names = [ep.name for ep in endpoints]
    eps_r = range(n_ep)
    # unpack live state into index-parallel lists for the hot loop
    slots = [state.slots[n] for n in names]
    first = [state.first_start[n] for n in names]
    last = [state.last_end[n] for n in names]
    dyn = [state.dyn_energy[n] for n in names]
    cached = state.cached
    timeline = state.timeline
    transfer_j = state.transfer_j
    # per-endpoint constants
    idle = [ep.idle_power_w for ep in endpoints]
    bt = [ep.has_batch_scheduler for ep in endpoints]
    su = [ep.startup_energy_j for ep in endpoints]
    qd = [ep.queue_delay_s if ep.has_batch_scheduler else 0.0 for ep in endpoints]
    # running C_max (max never rounds: equals max over the last_end values)
    c_cur = 0.0
    for v in last:
        if v > c_cur:
            c_cur = v
    # per-endpoint idle-span terms, recomputed only on commit — the same
    # float expression metrics() evaluates per candidate in the clone engine
    sterm = [
        idle[j] * (last[j] - first[j]) + su[j]
        if (bt[j] and first[j] is not None) else 0.0
        for j in eps_r
    ]
    mins = [h[0] for h in slots]  # heap peeks, refreshed on commit
    rates = carbon.rates if carbon is not None else None
    gamma = carbon.gamma if carbon is not None else 0.0
    lw = lookahead
    if lw is not None:
        lk_tail, lk_out, lk_hm, lam = lw.tail_w, lw.out_j, lw.hops_mean, lw.lam
        lk_ht = lw.hops_task    # producer-aware per-task hop vectors (or None)
    wt = _warm_terms(warm, alpha, sf1, sf2) if warm is not None else None
    fw = fairness
    if fw is not None:
        fdebt = fw.debt
        f_mu = fw.mu
        # fleet-mean predictions: the same doubles the clone engine's
        # per-task np.mean over an endpoint list produces (see
        # PredictionTable.rt_mean)
        frt_mean = table.rt_mean.tolist()
        fen_mean = table.en_mean.tolist()
    idx = table.index
    rt_rows, en_rows = table.rt_rows, table.en_rows
    hops = transfer.hops
    predict_seconds = transfer.predict_seconds
    beta = 1 - alpha
    heappop, heappush, heapreplace = heapq.heappop, heapq.heappush, heapq.heapreplace
    inf = np.inf
    assignments: dict[str, str] = {}
    # per-input caches shared across candidates: the "src:files:bytes" key
    # string, per-endpoint key tuples, hop counts, and transfer-time
    # predictions are all pure functions of their inputs
    key_cache: dict[tuple, str] = {}
    inp_info: dict[tuple, tuple] = {}
    hop_cache: dict[tuple[str, str], float] = {}
    ready_cache: dict[tuple, float] = {}

    for unit in units:
        single = len(unit) == 1
        single_inp = None
        if single:
            t0 = unit[0]
            ti = idx[t0.id]
            nb0 = t0.not_before
            no_inputs = not t0.inputs
            if not no_inputs and len(t0.inputs) == 1:
                inp = t0.inputs[0]
                single_inp = inp_info.get(inp)
                if single_inp is None:
                    src, n_files, nbytes, shared = inp
                    ks = f"{src}:{n_files}:{nbytes}"
                    single_inp = inp_info[inp] = (
                        src, n_files, nbytes, shared,
                        # per-endpoint cache key; None where src == endpoint
                        [None if names[j] == src else (names[j], ks)
                         for j in eps_r],
                    )
        else:
            no_inputs = all(not t.inputs for t in unit)
        if not no_inputs and single_inp is None:
            prep = []
            for t in unit:
                for inp in t.inputs:
                    ks = key_cache.get(inp)
                    if ks is None:
                        src, n_files, nbytes, shared = inp
                        ks = key_cache[inp] = f"{src}:{n_files}:{nbytes}"
                    prep.append((inp[0], ks, inp[1], inp[2], inp[3]))
        if lw is not None:
            if single:
                u_tw = lk_tail.get(t0.id, 0.0)
                u_oj = lk_out.get(t0.id, 0.0)
                if lk_ht is not None:
                    hv_u = lk_ht.get(t0.id, lk_hm)
            else:
                u_oj = 0.0
                for t in unit:
                    u_oj += lk_out.get(t.id, 0.0)
                if lk_ht is not None:
                    lk_rows = [(lk_out.get(t.id, 0.0),
                                lk_ht.get(t.id, lk_hm)) for t in unit]
        if fw is not None:
            if single:
                u_fd = fdebt.get(t0.user, 0.0)
            else:
                u_fidx = [(idx[t.id], fdebt.get(t.user, 0.0)) for t in unit]
        best_obj = inf
        best = None
        for ei in eps_r:
            if alive is not None and not alive[ei]:
                continue   # dead endpoint: masked out of candidate scoring
            # --- transfer delta -------------------------------------------
            if no_inputs:
                tj = transfer_j
                ready = qd[ei]
                new_keys = ()
            elif single_inp is not None:
                src, n_files, nbytes, shared, keys4 = single_inp
                key = keys4[ei]
                if key is None or (shared and key in cached):
                    # local input, or shared data already staged here:
                    # no transfer — identical to the no-input case
                    tj = transfer_j
                    ready = qd[ei]
                    new_keys = ()
                else:
                    new_keys = (key,) if shared else ()
                    h = hop_cache.get(key)
                    if h is None:
                        h = hop_cache[key] = hops(src, names[ei])
                    tj = transfer_j + h * nbytes * E_INC_J_PER_BYTE
                    ready = ready_cache.get(key)
                    if ready is None:
                        ready = ready_cache[key] = predict_seconds(n_files, nbytes)
                    ready = ready + qd[ei]
            else:
                name = names[ei]
                tj = transfer_j
                t_bytes, t_files = 0.0, 0
                new_keys = []
                for src, ks, n_files, nbytes, shared in prep:
                    if src == name:
                        continue
                    key = (name, ks)
                    if shared and (key in cached or key in new_keys):
                        continue
                    if shared:
                        new_keys.append(key)
                    h = hop_cache.get(key)
                    if h is None:
                        h = hop_cache[key] = hops(src, name)
                    tj += h * nbytes * E_INC_J_PER_BYTE
                    t_bytes += nbytes
                    t_files += n_files
                if t_files:
                    rk = (t_files, t_bytes)
                    ready = ready_cache.get(rk)
                    if ready is None:
                        ready = ready_cache[rk] = predict_seconds(t_files, t_bytes)
                    ready = ready + qd[ei]
                else:
                    ready = qd[ei]
            # --- simulate the placement -----------------------------------
            if single:
                s0 = mins[ei]
                start = s0 if s0 >= ready else ready
                if start < nb0:
                    start = nb0
                end = start + rt_rows[ei][ti]
                f = first[ei]
                nf = start if (f is None or start < f) else f
                l = last[ei]
                nl = end if end > l else l
                nd = dyn[ei] + en_rows[ei][ti]
                heap = None
                entries = (t0.id, start, end)
            else:
                heap = list(slots[ei])
                row_rt, row_en = rt_rows[ei], en_rows[ei]
                nf = first[ei]
                nl = last[ei]
                nd = dyn[ei]
                entries = []
                for t in unit:
                    tix = idx[t.id]
                    start = heappop(heap)
                    if start < ready:
                        start = ready
                    if start < t.not_before:
                        start = t.not_before
                    end = start + row_rt[tix]
                    heappush(heap, end)
                    if nf is None or start < nf:
                        nf = start
                    if end > nl:
                        nl = end
                    nd = nd + row_en[tix]
                    entries.append((t.id, start, end))
            # --- objective, same accumulation order as metrics() ----------
            c = nl if nl > c_cur else c_cur
            e = tj
            if rates is None:
                for j in eps_r:
                    if j == ei:
                        if bt[ei]:
                            e += idle[ei] * (nl - nf) + su[ei]
                        else:
                            e += idle[ei] * c
                        e += nd
                    elif bt[j]:
                        if first[j] is not None:
                            e += sterm[j]
                            e += dyn[j]
                    else:
                        e += idle[j] * c
                        if first[j] is not None:
                            e += dyn[j]
                obj = alpha * e / sf1 + beta * c / sf2
            else:
                # carbon twin: accumulate gCO2 beside e with the exact
                # per-endpoint expressions of _carbon_terms_g
                g = 0.0
                for j in eps_r:
                    if j == ei:
                        if bt[ei]:
                            e += idle[ei] * (nl - nf) + su[ei]
                            e += nd
                            g += rates[ei] * (idle[ei] * (nl - nf) + su[ei]
                                              + nd)
                        else:
                            e += idle[ei] * c
                            e += nd
                            g += rates[ei] * (idle[ei] * c + nd)
                    elif bt[j]:
                        if first[j] is not None:
                            e += sterm[j]
                            e += dyn[j]
                            g += rates[j] * (sterm[j] + dyn[j])
                    else:
                        e += idle[j] * c
                        if first[j] is not None:
                            e += dyn[j]
                            g += rates[j] * (idle[j] * c + dyn[j])
                        else:
                            g += rates[j] * (idle[j] * c)
                obj = alpha * e / sf1 + beta * c / sf2 + gamma * g / sf3
            if lw is not None:
                # DAG-aware shaping: rank-weighted finish times + the
                # gravity of shipping this unit's outputs off-endpoint.
                # Same float expression as the clone engine's loop.
                if single:
                    lk_tail_sum = u_tw * end
                else:
                    lk_tail_sum = 0.0
                    for _tid, _s, _e in entries:
                        lk_tail_sum += lk_tail.get(_tid, 0.0) * _e
                if lk_ht is None:
                    grav = u_oj * lk_hm[ei]
                elif single:
                    grav = u_oj * hv_u[ei]
                else:
                    # producer-aware: each task's bytes priced at *its*
                    # predicted-consumer hop vector
                    grav = 0.0
                    for _oj, _hv in lk_rows:
                        grav += _oj * _hv[ei]
                obj = obj + lam * (alpha * grav / sf1
                                   + beta * lk_tail_sum / sf2)
            if fw is not None:
                # advantage tax: each in-debt task pays mu*debt times the
                # advantage this endpoint offers over the fleet-mean
                # prediction.  Same float expression as the clone engine's
                # loop (and re-grouped elementwise by the SoA register).
                f_j = 0.0
                f_s = 0.0
                if single:
                    if u_fd != 0.0:
                        adv_j = fen_mean[ti] - en_rows[ei][ti]
                        if adv_j > 0.0:
                            f_j += u_fd * adv_j
                        adv_s = frt_mean[ti] - rt_rows[ei][ti]
                        if adv_s > 0.0:
                            f_s += u_fd * adv_s
                else:
                    for tix, d in u_fidx:
                        if d != 0.0:
                            adv_j = fen_mean[tix] - row_en[tix]
                            if adv_j > 0.0:
                                f_j += d * adv_j
                            adv_s = frt_mean[tix] - row_rt[tix]
                            if adv_s > 0.0:
                                f_s += d * adv_s
                obj = obj + f_mu * (alpha * f_j / sf1 + beta * f_s / sf2)
            if wt is not None:
                obj = obj + wt[ei]
            if obj < best_obj:
                best_obj = obj
                best = (ei, tj, new_keys, heap, entries, nf, nl, nd)
        # --- commit the winner --------------------------------------------
        if best is None:
            raise RuntimeError(
                "no live endpoint available for placement (alive mask "
                "excludes the whole fleet)"
            )
        ei, tj, new_keys, heap, entries, nf, nl, nd = best
        transfer_j = tj
        if new_keys:
            cached.update(new_keys)
        if heap is None:
            tid, start, end = entries
            heapreplace(slots[ei], end)
            timeline[tid] = (start, end)
            assignments[tid] = names[ei]
        else:
            slots[ei] = heap
            name = names[ei]
            for tid, start, end in entries:
                timeline[tid] = (start, end)
                assignments[tid] = name
        mins[ei] = slots[ei][0]
        first[ei] = nf
        last[ei] = nl
        dyn[ei] = nd
        if nl > c_cur:
            c_cur = nl
        if bt[ei]:
            sterm[ei] = idle[ei] * (nl - nf) + su[ei]

    # write the loop-local state back into the SchedulerState
    for ei in eps_r:
        n = names[ei]
        state.slots[n] = slots[ei]
        state.first_start[n] = first[ei]
        state.last_end[n] = last[ei]
        state.dyn_energy[n] = dyn[ei]
    state.transfer_j = transfer_j
    e, c, tj = state.metrics()
    obj = alpha * e / sf1 + (1 - alpha) * c / sf2
    carbon_g = None
    if rates is not None:
        carbon_g = state_carbon_g(state, rates)
        obj = obj + gamma * carbon_g / sf3
    # the timeline is passed by reference: mhra() snapshots the winning
    # heuristic's copy once, iff a live state adopts it
    sched = Schedule(assignments, obj, e, c, tj, heuristic,
                     state.timeline, carbon_g=carbon_g)
    return sched, state


def _greedy_soa(
    units, unit_indices, endpoints, table: PredictionTable, transfer,
    alpha, sf1, sf2, heuristic, base_state: SoAState | None = None,
    carbon: CarbonWeights | None = None, sf3: float = 1.0,
    lookahead: LookaheadWeights | None = None,
    alive: tuple | None = None, warm: WarmWeights | None = None,
    fairness: FairnessWeights | None = None,
) -> tuple[Schedule, SoAState]:
    """Structure-of-arrays greedy: score a unit against *every* endpoint in
    a fixed handful of vectorized passes instead of a Python loop over
    candidates.

    The per-candidate objective is algebraically identical to the delta
    engine's but regrouped for vectorization::

        e(i) = transfer_j(i) + (C - const_i) + IDLE_ON * c(i) + self(i)

    where ``C = sum_j const_j`` collects every endpoint's standing
    contribution (span term + dynamic energy for batch endpoints, dynamic
    energy for always-on ones), ``IDLE_ON`` is the total always-on idle
    draw (each always-on endpoint charges ``idle * C_max`` whichever
    candidate wins), and ``self(i)`` is candidate i's refreshed span/dyn
    term.  The regrouped sum can differ from the delta engine's sequential
    accumulation by ~1 ulp, so objectives agree to ``rtol << 1e-12`` and
    argmin decisions only diverge on exact ties — which both engines break
    identically (first index).  The *final* objective is recomputed from
    ``state.metrics()``, whose float sequence matches the heap state's
    exactly, so equal assignments imply bitwise-equal reported objectives.

    Slot peeks come from a per-endpoint ``mins`` register over the state's
    flat free-time array; a commit overwrites the argmin slot (same
    multiset evolution as heap pop+push) and refreshes only that
    endpoint's min.
    """
    state = (
        base_state.clone(keep_timeline=True)
        if base_state is not None
        else SoAState(endpoints, transfer)
    )
    n_ep = len(endpoints)
    names = state.names
    eps_r = range(n_ep)
    free = state.free
    offsets = state.offsets
    first, last, dyn = state.first, state.last, state.dyn
    cached = state.cached
    timeline = state.timeline
    transfer_j = state.transfer_j
    mins = state.slot_mins()

    # per-endpoint constants
    idle = np.array([ep.idle_power_w for ep in endpoints])
    bt_mask = np.array([ep.has_batch_scheduler for ep in endpoints])
    su = np.array([ep.startup_energy_j for ep in endpoints])
    qd_vec = np.where(bt_mask, [ep.queue_delay_s for ep in endpoints], 0.0)
    idle_bt = np.where(bt_mask, idle, 0.0)
    su_bt = np.where(bt_mask, su, 0.0)
    idle_on_sum = float(idle[~bt_mask].sum())

    c_cur = float(max(last.max(initial=0.0), 0.0))
    # standing per-endpoint objective contributions (see docstring)
    used = first < np.inf
    span = np.where(used, last - first, 0.0)
    const = np.where(bt_mask & used, idle * span + su, 0.0) + dyn
    static = const.sum() - const

    # python-float mirrors of every register the singleton fast path reads
    # scalar-by-scalar: a numpy scalar index costs ~5x a list index, and at
    # small fleets those constant factors dominate per-decision latency
    # (the 4-endpoint soa-vs-delta regression).  The arrays stay
    # authoritative for the vectorized passes; commits dual-write.  Values
    # are the same float64 doubles either way, so parity is untouched.
    mins_l = mins.tolist()
    first_l = first.tolist()
    last_l = last.tolist()
    dyn_l = dyn.tolist()
    const_l = const.tolist()
    qd_l = qd_vec.tolist()
    idle_bt_l = idle_bt.tolist()
    su_bt_l = su_bt.tolist()
    bt_l = bt_mask.tolist()
    # per-endpoint slot lists are authoritative during this call (python
    # min/index replace np.argmin/np.min reductions on tiny arrays); the
    # flat free array is rebuilt once at the end
    slots_l = [free[offsets[j]:offsets[j + 1]].tolist() for j in eps_r]
    run_rt_l = run_en_l = None
    nl_l = e_base_l = obj_l = g_base_l = lk_l = None

    rtT, enT = table.transposed()
    a1 = alpha / sf1
    b1 = (1.0 - alpha) / sf2
    # carbon term: one extra vector register (const_g = rates*const) and a
    # weighted always-on idle sum; everything else reuses the e machinery
    if carbon is not None:
        rates_v = np.asarray(carbon.rates, dtype=float)
        g1 = carbon.gamma / sf3
        w_idle_on = float((rates_v * idle)[~bt_mask].sum())
        const_g = rates_v * const
        static_g = const_g.sum() - const_g
        g_base = np.empty(n_ep)
        gbuf = np.empty(n_ep)
        rates_l = rates_v.tolist()
        const_g_l = const_g.tolist()
    else:
        rates_v = None
    # lookahead term: one extra vector register computed per run basis —
    # lk = lam*b1*tail_w*end + lam*a1*out_j*hops_mean.  Both factors are
    # part of the run key, so within a run only the committed endpoint's
    # entry needs the scalar refresh (its candidate end moved).
    if lookahead is not None:
        lk_tail = lookahead.tail_w
        lk_out = lookahead.out_j
        hm_vec = np.asarray(lookahead.hops_mean, dtype=float)
        hm_l = hm_vec.tolist()
        lam = lookahead.lam
        lk = np.empty(n_ep)
        lk_tailv = np.empty(n_ep)
        lk_c1 = lk_c2 = 0.0
        u_tw = u_oj = 0.0
        # producer-aware gravity: per-run hop vector (fleet mean unless the
        # task carries its own predicted-consumer vector); the per-task
        # choice joins the memo key so runs never mix vectors
        lk_ht = lookahead.hops_task
        run_hv = hm_vec
        run_hv_l = hm_l
    else:
        lk = None
        lk_ht = None
    # warm-pool term: one extra vector register, constant over the whole
    # call (the WarmWeights snapshot is per-placement-call), added as the
    # final term of every candidate score — same doubles as the delta
    # engine's `obj + wt[ei]`.
    if warm is not None:
        wt_l = _warm_terms(warm, alpha, sf1, sf2)
        wt_v = np.asarray(wt_l)
    else:
        wt_l = wt_v = None
    # fairness term: one extra vector register per run (the advantage tax
    # depends only on the run's predictions and the task's user-debt, so
    # it is constant within a run and the per-task debt joins the memo
    # key).  The elementwise op sequence mirrors the delta engine's
    # scalar accumulation — multiplication commutes bitwise, so the
    # register holds the *same doubles*, not a ~1ulp regroup.
    if fairness is not None:
        fdebt = fairness.debt
        f_mu = fairness.mu
        f_beta = 1.0 - alpha
        frt_mean = table.rt_mean
        fen_mean = table.en_mean
        fw_v = np.zeros(n_ep)
        fjv = np.empty(n_ep)
        fsv = np.empty(n_ep)
        fbuf = np.empty(n_ep)
        fw_l = fw_v.tolist()
        u_fd = 0.0
    else:
        fdebt = fw_l = None
    # dead-endpoint mask: applied *after* every term add so masked entries
    # stay +inf across memo hits (the commit/C_max refreshes below only
    # touch live endpoints); the run memo key is untouched — the mask is
    # constant for the whole call
    if alive is not None:
        alive_l = list(alive)
        dead_idx = np.flatnonzero(~np.asarray(alive, dtype=bool))
    else:
        alive_l = dead_idx = None
    memo_hits = memo_misses = 0
    assignments: dict[str, str] = {}
    # preallocated per-unit buffers
    start = np.empty(n_ep)
    end = np.empty(n_ep)
    nf = np.empty(n_ep)
    nl = np.empty(n_ep)
    nd = np.empty(n_ep)
    c = np.empty(n_ep)
    e = np.empty(n_ep)
    e_base = np.empty(n_ep)   # per-candidate score minus its C_max terms
    obj = np.empty(n_ep)
    tmp = np.empty(n_ep)
    # per-input-signature transfer vectors (single-input singleton units):
    # staged[j] => placing on j transfers nothing (local data, or a shared
    # key already cached); eff_* are the staged-aware add/ready vectors
    sig_cache: dict[tuple, dict] = {}

    def _sig(inp):
        rec = sig_cache.get(inp)
        if rec is None:
            src, n_files, nbytes, shared = inp
            ks = f"{src}:{n_files}:{nbytes}"
            keys = [None if n == src else (n, ks) for n in names]
            add = np.array([
                0.0 if k is None else transfer.hops(src, n) * nbytes * E_INC_J_PER_BYTE
                for n, k in zip(names, keys)
            ])
            ready = transfer.predict_seconds(n_files, nbytes)
            staged = np.array([
                k is None or (shared and k in cached) for k in keys
            ])
            rec = sig_cache[inp] = {
                "keys": keys, "add": add, "ready": ready, "shared": shared,
                "staged": staged,
                "eff_add": np.where(staged, 0.0, add),
                "eff_ready": np.where(staged, 0.0, ready) + qd_vec,
            }
            # python-float mirrors for the scalar commit path (kept in
            # sync with the arrays at every staging update)
            rec["eff_add_l"] = rec["eff_add"].tolist()
            rec["eff_ready_l"] = rec["eff_ready"].tolist()
        return rec

    # --- run memoization over the sorted unit stream ----------------------
    # Sorting makes identical (fn, inputs) singletons consecutive, and a
    # commit touches exactly one endpoint's registers.  Within such a run,
    # every other candidate's score is stale only by a *uniform* shift
    # (the committed endpoint's standing-term delta + any transfer energy
    # are charged to every candidate alike), so the argmin is unchanged:
    # only the committed endpoint's entry needs a scalar refresh, computed
    # against the run's basis (C_sum_b, tj_b) so comparisons stay exact.
    # A commit that raises C_max shifts candidates *non*-uniformly (each
    # candidate's own makespan term saturates differently), so that — or
    # any general-path unit — forces a fresh vectorized pass.
    run_key = None
    need_full = True
    c_sum_b = tj_b = cg_sum_b = 0.0
    run_rec: dict | None = None
    run_rt = run_en = None
    for unit, uidx in zip(units, unit_indices):
        if len(unit) == 1 and len(unit[0].inputs) <= 1:
            # ---- fast path: singleton unit, zero or one input ------------
            t0 = unit[0]
            ti = uidx[0]
            nb0 = t0.not_before
            # not_before is part of the run identity: tasks with different
            # ready floors score differently even with equal (fn, inputs)
            # — epoch-batched DAG promotion exists to keep a wide stage's
            # floors equal so its children coalesce into one run.  Under
            # lookahead the per-task rank/gravity weights join the key.
            if lk is None:
                key = (t0.fn, t0.inputs, nb0)
            else:
                u_tw = lk_tail.get(t0.id, 0.0)
                u_oj = lk_out.get(t0.id, 0.0)
                key = (t0.fn, t0.inputs, nb0, u_tw, u_oj)
                if lk_ht is not None:
                    # tasks with different consumer-hop vectors must not
                    # share a run (the gravity register differs)
                    hv_t = lk_ht.get(t0.id)
                    key = key + (hv_t,)
            if fdebt is not None:
                # tasks taxed differently must not share a run
                u_fd = fdebt.get(t0.user, 0.0)
                key = key + (u_fd,)
            if need_full or key != run_key:
                memo_misses += 1
                run_key = key
                run_rec = rec = _sig(t0.inputs[0]) if t0.inputs else None
                run_rt = rtT[ti]
                run_en = enT[ti]
                c_sum_b = float(const.sum())
                np.subtract(c_sum_b, const, out=static)
                if rates_v is not None:
                    cg_sum_b = float(const_g.sum())
                    np.subtract(cg_sum_b, const_g, out=static_g)
                tj_b = transfer_j
                if rec is None:
                    np.maximum(mins, qd_vec, out=start)
                else:
                    np.maximum(mins, rec["eff_ready"], out=start)
                if nb0 > 0.0:
                    np.maximum(start, nb0, out=start)
                np.add(start, run_rt, out=end)
                np.minimum(first, start, out=nf)
                np.maximum(last, end, out=nl)
                np.add(dyn, run_en, out=nd)
                np.maximum(nl, c_cur, out=c)
                # candidate span/dyn term: idle*(nl-nf)+su batch, 0 else
                np.subtract(nl, nf, out=tmp)
                np.multiply(tmp, idle_bt, out=tmp)
                np.add(tmp, su_bt, out=tmp)
                # e_base: everything except the C_max-dependent terms, so
                # a later C_max advance only refreshes c and recombines
                np.add(static, nd, out=e_base)
                np.add(e_base, tmp, out=e_base)
                if rec is not None:
                    np.add(e_base, rec["eff_add"], out=e_base)
                np.add(e_base, tj_b, out=e_base)
                if rates_v is not None:
                    # carbon base: static_g + rates*(span term + dyn);
                    # tmp still holds the span terms here
                    np.add(tmp, nd, out=gbuf)
                    np.multiply(gbuf, rates_v, out=gbuf)
                    np.add(gbuf, static_g, out=g_base)
                np.multiply(c, idle_on_sum, out=e)
                np.add(e, e_base, out=e)
                np.multiply(e, a1, out=obj)
                np.multiply(c, b1, out=tmp)
                np.add(obj, tmp, out=obj)
                if rates_v is not None:
                    np.multiply(c, w_idle_on, out=gbuf)
                    np.add(gbuf, g_base, out=gbuf)
                    np.multiply(gbuf, g1, out=gbuf)
                    np.add(obj, gbuf, out=obj)
                if lk is not None:
                    if lk_ht is not None:
                        if hv_t is None:
                            run_hv, run_hv_l = hm_vec, hm_l
                        else:
                            run_hv = np.asarray(hv_t, dtype=float)
                            run_hv_l = run_hv.tolist()
                    lk_c1 = lam * b1 * u_tw
                    lk_c2 = lam * a1 * u_oj
                    np.multiply(end, lk_c1, out=lk)
                    np.multiply(run_hv, lk_c2, out=tmp)
                    np.add(lk, tmp, out=lk)
                    np.add(obj, lk, out=obj)
                if fdebt is not None:
                    if u_fd != 0.0:
                        # elementwise the delta scalar loop: debt-scaled
                        # relu(mean - predicted), alpha/beta-weighted,
                        # SF-normalized, times mu
                        np.subtract(fen_mean[ti], run_en, out=fbuf)
                        np.multiply(fbuf, u_fd, out=fjv)
                        fjv[fbuf <= 0.0] = 0.0
                        np.subtract(frt_mean[ti], run_rt, out=fbuf)
                        np.multiply(fbuf, u_fd, out=fsv)
                        fsv[fbuf <= 0.0] = 0.0
                        np.multiply(fjv, alpha, out=fjv)
                        np.divide(fjv, sf1, out=fjv)
                        np.multiply(fsv, f_beta, out=fsv)
                        np.divide(fsv, sf2, out=fsv)
                        np.add(fjv, fsv, out=fw_v)
                        np.multiply(fw_v, f_mu, out=fw_v)
                    else:
                        # debt-free user: the delta engine still adds the
                        # (zero) term, so mirror the add exactly
                        fw_v.fill(0.0)
                    np.add(obj, fw_v, out=obj)
                if wt_v is not None:
                    np.add(obj, wt_v, out=obj)
                if dead_idx is not None:
                    obj[dead_idx] = np.inf
                # refresh the scalar mirrors the hit/commit path works on
                # (arrays go stale between misses; nothing vectorized
                # reads nl/e_base/obj/lk/g_base until the next full pass
                # overwrites them)
                run_rt_l = run_rt.tolist()
                run_en_l = run_en.tolist()
                nl_l = nl.tolist()
                e_base_l = e_base.tolist()
                obj_l = obj.tolist()
                if rates_v is not None:
                    g_base_l = g_base.tolist()
                if lk is not None:
                    lk_l = lk.tolist()
                if fdebt is not None:
                    fw_l = fw_v.tolist()
                need_full = False
            else:
                memo_hits += 1
                rec = run_rec
            ei = obj_l.index(min(obj_l))   # first-min, like np.argmin
            # ---- commit: same scalar float ops as the vectorized pass,
            # read from the python mirrors (identical doubles) ------------
            if rec is None:
                ready_e = qd_l[ei]
            else:
                ready_e = rec["eff_ready_l"][ei]
                transfer_j += rec["eff_add_l"][ei]
                if rec["shared"] and not rec["staged"][ei]:
                    cached.add(rec["keys"][ei])
                    rec["staged"][ei] = True
                    rec["eff_add"][ei] = 0.0
                    rec["eff_add_l"][ei] = 0.0
                    rec["eff_ready"][ei] = qd_l[ei]
                    rec["eff_ready_l"][ei] = qd_l[ei]
            m_e = mins_l[ei]
            start_v = m_e if m_e >= ready_e else ready_e
            if start_v < nb0:
                start_v = nb0
            end_v = start_v + run_rt_l[ei]
            f_e = first_l[ei]
            nf_v = start_v if start_v < f_e else f_e
            l_e = last_l[ei]
            nl_v = end_v if end_v > l_e else l_e
            nd_v = dyn_l[ei] + run_en_l[ei]
            # heap pop-min+push as "overwrite the first min slot": the
            # mins register *is* the slot min, so list.index finds the
            # same slot np.argmin would
            sl_l = slots_l[ei]
            sl_l[sl_l.index(m_e)] = end_v
            m2 = min(sl_l)
            mins[ei] = m2
            mins_l[ei] = m2
            first[ei] = nf_v
            first_l[ei] = nf_v
            last[ei] = nl_v
            last_l[ei] = nl_v
            dyn[ei] = nd_v
            dyn_l[ei] = nd_v
            c_e = (
                (nl_v - nf_v) * idle_bt_l[ei] + su_bt_l[ei] + nd_v
                if bt_l[ei] else nd_v
            )
            const[ei] = c_e
            const_l[ei] = c_e
            if rates_v is not None:
                cg_e = rates_l[ei] * c_e
                const_g[ei] = cg_e
                const_g_l[ei] = cg_e
            # refresh this endpoint's next-task row on the run's basis
            # (same scalar float op order as the vectorized pass)
            ready2 = rec["eff_ready_l"][ei] if rec is not None else ready_e
            s2 = m2 if m2 >= ready2 else ready2
            if s2 < nb0:
                s2 = nb0
            e2 = s2 + run_rt_l[ei]
            nf2 = s2 if s2 < nf_v else nf_v
            nl2 = e2 if e2 > nl_v else nl_v
            nl_l[ei] = nl2
            e_b = (c_sum_b - c_e) + (nd_v + run_en_l[ei])
            e_b = e_b + ((nl2 - nf2) * idle_bt_l[ei] + su_bt_l[ei])
            if rec is not None:
                e_b = e_b + rec["eff_add_l"][ei]
            e_b = e_b + tj_b
            e_base_l[ei] = e_b
            if rates_v is not None:
                g_b = (cg_sum_b - cg_e) + rates_l[ei] * (
                    ((nl2 - nf2) * idle_bt_l[ei] + su_bt_l[ei])
                    + (nd_v + run_en_l[ei])
                )
                g_base_l[ei] = g_b
            if lk is not None:
                # same scalar op order as the vectorized lk pass
                lk_e = e2 * lk_c1 + run_hv_l[ei] * lk_c2
                lk_l[ei] = lk_e
            if end_v > c_cur:
                # C_max advanced: refresh every candidate's makespan terms
                # from the cached e_base (the rest of the score is intact).
                # Scalar loop over the mirrors, element-for-element the
                # ops the vectorized refresh performed — identical floats.
                c_cur = end_v
                for j in eps_r:
                    if alive_l is not None and not alive_l[j]:
                        continue   # dead: leave its score at +inf
                    c2 = nl_l[j]
                    if c2 < c_cur:
                        c2 = c_cur
                    e_s = idle_on_sum * c2 + e_base_l[j]
                    if rates_v is None:
                        o_v = a1 * e_s + b1 * c2
                    else:
                        o_v = (a1 * e_s + b1 * c2
                               + g1 * (w_idle_on * c2 + g_base_l[j]))
                    if lk is not None:
                        o_v = o_v + lk_l[j]
                    if fw_l is not None:
                        # run-constant: predictions and user-debt don't
                        # move on commit
                        o_v = o_v + fw_l[j]
                    if wt_l is not None:
                        o_v = o_v + wt_l[j]
                    obj_l[j] = o_v
            else:
                c2 = nl2 if nl2 > c_cur else c_cur
                e_s = idle_on_sum * c2 + e_b
                if rates_v is None:
                    o_v = a1 * e_s + b1 * c2
                else:
                    o_v = (a1 * e_s + b1 * c2
                           + g1 * (w_idle_on * c2 + g_b))
                if lk is not None:
                    o_v = o_v + lk_e
                if fw_l is not None:
                    o_v = o_v + fw_l[ei]
                if wt_l is not None:
                    o_v = o_v + wt_l[ei]
                obj_l[ei] = o_v
            timeline[t0.id] = (start_v, end_v)
            assignments[t0.id] = names[ei]
            continue
        # ---- general path: clustered / multi-input units -----------------
        run_key = None
        need_full = True
        memo_misses += 1
        np.subtract(const.sum(), const, out=static)
        if rates_v is not None:
            np.subtract(const_g.sum(), const_g, out=static_g)
        heappop, heappush = heapq.heappop, heapq.heappush
        tjv = np.empty(n_ep)
        cand = []
        for ei in eps_r:
            tj_e, ready_e, new_keys = _unit_transfer_delta(
                transfer, cached, transfer_j, unit, names[ei]
            )
            ready_e += qd_vec[ei]
            heap = list(slots_l[ei])   # authoritative slots (see init)
            heapq.heapify(heap)
            f_e = first[ei]
            l_e = last[ei]
            d_e = dyn[ei]
            tl_e = 0.0
            fj_e = fs_e = 0.0
            entries = []
            for t, tix in zip(unit, uidx):
                s_v = heappop(heap)
                if s_v < ready_e:
                    s_v = ready_e
                if s_v < t.not_before:
                    s_v = t.not_before
                e_v = s_v + rtT[tix, ei]
                heappush(heap, e_v)
                if s_v < f_e:
                    f_e = s_v
                if e_v > l_e:
                    l_e = e_v
                d_e = d_e + enT[tix, ei]
                if lk is not None:
                    tl_e += lk_tail.get(t.id, 0.0) * e_v
                if fdebt is not None:
                    # same scalar accumulation as the delta general path
                    d = fdebt.get(t.user, 0.0)
                    if d != 0.0:
                        adv_j = fen_mean[tix] - enT[tix, ei]
                        if adv_j > 0.0:
                            fj_e += d * adv_j
                        adv_s = frt_mean[tix] - rtT[tix, ei]
                        if adv_s > 0.0:
                            fs_e += d * adv_s
                entries.append((t.id, s_v, e_v))
            tjv[ei] = tj_e
            nf[ei] = f_e
            nl[ei] = l_e
            nd[ei] = d_e
            if lk is not None:
                lk_tailv[ei] = tl_e
            if fdebt is not None:
                fjv[ei] = fj_e
                fsv[ei] = fs_e
            cand.append((heap, entries, new_keys))
        np.maximum(nl, c_cur, out=c)
        np.subtract(nl, nf, out=tmp)
        np.multiply(tmp, idle_bt, out=tmp)
        np.add(tmp, su_bt, out=tmp)
        if rates_v is not None:
            np.add(tmp, nd, out=gbuf)
            np.multiply(gbuf, rates_v, out=gbuf)
            np.add(gbuf, static_g, out=g_base)
        np.multiply(c, idle_on_sum, out=e)
        np.add(e, static, out=e)
        np.add(e, nd, out=e)
        np.add(e, tmp, out=e)
        np.add(e, tjv, out=e)
        np.multiply(e, a1, out=obj)
        np.multiply(c, b1, out=tmp)
        np.add(obj, tmp, out=obj)
        if rates_v is not None:
            np.multiply(c, w_idle_on, out=gbuf)
            np.add(gbuf, g_base, out=gbuf)
            np.multiply(gbuf, g1, out=gbuf)
            np.add(obj, gbuf, out=obj)
        if lk is not None:
            u_oj = 0.0
            for t in unit:
                u_oj += lk_out.get(t.id, 0.0)
            np.multiply(lk_tailv, lam * b1, out=lk)
            if lk_ht is None:
                np.multiply(hm_vec, lam * a1 * u_oj, out=tmp)
            else:
                # producer-aware: gravity accumulates per task at each
                # task's own consumer-hop vector
                tmp.fill(0.0)
                for t in unit:
                    _oj = lk_out.get(t.id, 0.0)
                    if _oj != 0.0:
                        _hv = lk_ht.get(t.id)
                        np.add(tmp,
                               np.multiply(
                                   hm_vec if _hv is None
                                   else np.asarray(_hv, dtype=float),
                                   _oj),
                               out=tmp)
                np.multiply(tmp, lam * a1, out=tmp)
            np.add(lk, tmp, out=lk)
            np.add(obj, lk, out=obj)
        if fdebt is not None:
            np.multiply(fjv, alpha, out=fjv)
            np.divide(fjv, sf1, out=fjv)
            np.multiply(fsv, f_beta, out=fsv)
            np.divide(fsv, sf2, out=fsv)
            np.add(fjv, fsv, out=fbuf)
            np.multiply(fbuf, f_mu, out=fbuf)
            np.add(obj, fbuf, out=obj)
        if wt_v is not None:
            np.add(obj, wt_v, out=obj)
        if dead_idx is not None:
            obj[dead_idx] = np.inf
        ei = int(np.argmin(obj))
        heap, entries, new_keys = cand[ei]
        transfer_j = float(tjv[ei])
        cached.update(new_keys)
        if new_keys:
            for rec in sig_cache.values():  # invalidate staged views
                if rec["shared"]:
                    for j, k in enumerate(rec["keys"]):
                        if k in new_keys and not rec["staged"][j]:
                            rec["staged"][j] = True
                            rec["eff_add"][j] = 0.0
                            rec["eff_add_l"][j] = 0.0
                            rec["eff_ready"][j] = qd_vec[j]
                            rec["eff_ready_l"][j] = qd_l[j]
        slots_l[ei] = heap
        mins[ei] = heap[0]
        mins_l[ei] = heap[0]
        nf_v = float(nf[ei])
        nl_v = float(nl[ei])
        nd_v = float(nd[ei])
        first[ei] = nf_v
        first_l[ei] = nf_v
        last[ei] = nl_v
        last_l[ei] = nl_v
        dyn[ei] = nd_v
        dyn_l[ei] = nd_v
        if nl_v > c_cur:
            c_cur = nl_v
        c_e = (
            idle_bt_l[ei] * (nl_v - nf_v) + su_bt_l[ei] + nd_v
            if bt_l[ei] else nd_v
        )
        const[ei] = c_e
        const_l[ei] = c_e
        if rates_v is not None:
            cg_e = rates_l[ei] * c_e
            const_g[ei] = cg_e
            const_g_l[ei] = cg_e
        name = names[ei]
        for tid, s_v, e_v in entries:
            timeline[tid] = (s_v, e_v)
            assignments[tid] = name

    MEMO_STATS["hits"] += memo_hits
    MEMO_STATS["misses"] += memo_misses
    # the python slot lists were authoritative during the loop; restore the
    # flat free array (the state outlives this call)
    for j in eps_r:
        free[offsets[j]:offsets[j + 1]] = slots_l[j]
    state.transfer_j = transfer_j
    e_tot, c_max, tj = state.metrics()
    obj_f = alpha * e_tot / sf1 + (1 - alpha) * c_max / sf2
    carbon_g = None
    if carbon is not None:
        carbon_g = state_carbon_g(state, carbon.rates)
        obj_f = obj_f + carbon.gamma * carbon_g / sf3
    # timeline by reference; _mhra_soa snapshots the winner's once
    sched = Schedule(assignments, obj_f, e_tot, c_max, tj, heuristic,
                     state.timeline, carbon_g=carbon_g)
    return sched, state


# ---------------------------------------------------------------------------
# Reference clone-based engine (the seed implementation, kept verbatim for
# parity tests and benchmarks/scheduler_overhead.py)
# ---------------------------------------------------------------------------


def _mhra_clone(tasks, endpoints, store, transfer, alpha, heuristics, clusters,
                carbon=None, lookahead=None, alive=None, warm=None,
                fairness=None):
    per_ep = _predict_all(tasks, endpoints, store)
    if clusters is None:
        units = [[t] for t in tasks]
    else:
        units = [[tasks[i] for i in c] for c in clusters]
    best: Schedule | None = None
    for h in heuristics:
        # predictions used for ordering: endpoint-mean
        mean_preds = {
            t.id: Prediction(
                float(np.mean([per_ep[e.name][t.id].runtime_s for e in endpoints])),
                float(np.mean([per_ep[e.name][t.id].energy_j for e in endpoints])),
                True,
            )
            for t in tasks
        }
        ordered = _sort_units(units, h, mean_preds)
        sched = _greedy_multi_ep(
            ordered, endpoints, per_ep, transfer, alpha, tasks, h, carbon,
            lookahead, alive, warm, fairness,
        )
        if best is None or sched.objective < best.objective:
            best = sched
    return best


def _greedy_multi_ep(units, endpoints, per_ep, transfer, alpha, tasks,
                     heuristic, carbon=None, lookahead=None, alive=None,
                     warm=None, fairness=None):
    # SF normalizers from endpoint-specific predictions
    sf1, sf2, sf3 = _normalizers(tasks, endpoints, per_ep, transfer, carbon)
    wt = _warm_terms(warm, alpha, sf1, sf2) if warm is not None else None
    if fairness is not None:
        fdebt = fairness.debt
        # fleet-mean predictions per task; the delta/SoA engines read the
        # same doubles from PredictionTable.{rt,en}_mean
        fmean = {
            t.id: (
                float(np.mean([per_ep[e.name][t.id].energy_j for e in endpoints])),
                float(np.mean([per_ep[e.name][t.id].runtime_s for e in endpoints])),
            )
            for t in tasks
        }

    lk_ht = lookahead.hops_task if lookahead is not None else None
    state = SchedulerState(endpoints, transfer)
    assignments: dict[str, str] = {}
    for unit in units:
        u_oj = 0.0
        if lookahead is not None:
            for t in unit:
                u_oj += lookahead.out_j.get(t.id, 0.0)
            if lk_ht is not None:
                lk_rows = [(lookahead.out_j.get(t.id, 0.0),
                            lk_ht.get(t.id, lookahead.hops_mean))
                           for t in unit]
        best_obj, best_ep = np.inf, None
        for ei, ep in enumerate(endpoints):
            if alive is not None and not alive[ei]:
                continue   # dead endpoint: masked out of candidate scoring
            trial = state.clone()
            # candidate timelines start empty, so with lookahead on the
            # trial records exactly this unit's (start, end) pairs
            trial.assign(unit, ep, per_ep[ep.name],
                         record_timeline=lookahead is not None)
            e, c, _ = trial.metrics()
            obj = alpha * e / sf1 + (1 - alpha) * c / sf2
            if carbon is not None:
                obj = obj + carbon.gamma * state_carbon_g(trial, carbon.rates) / sf3
            if lookahead is not None:
                lk_tail_sum = 0.0
                for t in unit:
                    lk_tail_sum += (lookahead.tail_w.get(t.id, 0.0)
                                    * trial.timeline[t.id][1])
                if lk_ht is None:
                    grav = u_oj * lookahead.hops_mean[ei]
                else:
                    # producer-aware: price each task's bytes at the hop
                    # distance of its children's predicted endpoints
                    grav = 0.0
                    for _oj, _hv in lk_rows:
                        grav += _oj * _hv[ei]
                obj = obj + lookahead.lam * (
                    alpha * grav / sf1
                    + (1 - alpha) * lk_tail_sum / sf2
                )
            if fairness is not None:
                # advantage tax (see _greedy_delta: bitwise-identical
                # accumulation, same term position)
                f_j = 0.0
                f_s = 0.0
                for t in unit:
                    d = fdebt.get(t.user, 0.0)
                    if d != 0.0:
                        p = per_ep[ep.name][t.id]
                        m_j, m_s = fmean[t.id]
                        adv_j = m_j - p.energy_j
                        if adv_j > 0.0:
                            f_j += d * adv_j
                        adv_s = m_s - p.runtime_s
                        if adv_s > 0.0:
                            f_s += d * adv_s
                obj = obj + fairness.mu * (
                    alpha * f_j / sf1 + (1 - alpha) * f_s / sf2
                )
            if wt is not None:
                obj = obj + wt[ei]
            if obj < best_obj:
                best_obj, best_ep = obj, ep
        if best_ep is None:
            raise RuntimeError(
                "no live endpoint available for placement (alive mask "
                "excludes the whole fleet)"
            )
        state.assign(unit, best_ep, per_ep[best_ep.name], record_timeline=True)
        for t in unit:
            assignments[t.id] = best_ep.name
    e, c, tj = state.metrics()
    obj = alpha * e / sf1 + (1 - alpha) * c / sf2
    carbon_g = None
    if carbon is not None:
        carbon_g = state_carbon_g(state, carbon.rates)
        obj = obj + carbon.gamma * carbon_g / sf3
    return Schedule(assignments, obj, e, c, tj, heuristic, state.timeline,
                    carbon_g=carbon_g)


def compute_clusters(
    tasks, endpoints, table: PredictionTable, max_cluster_size: int = 40
) -> list[list[int]]:
    """Agglomerative clusters from the vectorized prediction table (same
    features/energies as the clone path's nested-dict construction)."""
    n_ep = len(endpoints)
    feats = np.empty((len(tasks), 2 * n_ep))
    for ei in range(n_ep):
        feats[:, 2 * ei] = table.rt[ei]
        feats[:, 2 * ei + 1] = table.en[ei]
    energies = table.en_mean
    cap = min(
        [ep.startup_energy_j for ep in endpoints if ep.has_batch_scheduler]
        or [np.inf]
    )
    return agglomerative_cluster(
        feats, energies, cap, max_cluster_size=max_cluster_size
    )


def cluster_mhra(
    tasks: Sequence[TaskSpec],
    endpoints: Sequence[EndpointSpec],
    store: TaskProfileStore,
    transfer: TransferModel,
    alpha: float = 0.5,
    heuristics: Sequence[str] = HEURISTICS,
    max_cluster_size: int = 40,
    engine: str = "delta",
    state: SchedulerState | None = None,
    carbon: CarbonWeights | None = None,
    lookahead: LookaheadWeights | None = None,
    alive: Sequence[bool] | None = None,
    warm: WarmWeights | None = None,
    fairness: FairnessWeights | None = None,
) -> Schedule:
    """Algorithm 1: agglomerative clustering + per-cluster greedy MHRA."""
    tasks = list(tasks)
    if engine == "clone":
        per_ep = _predict_all(tasks, endpoints, store)
        feats = np.array(
            [
                [v for ep in endpoints for v in (
                    per_ep[ep.name][t.id].runtime_s, per_ep[ep.name][t.id].energy_j
                )]
                for t in tasks
            ]
        )
        energies = np.array(
            [np.mean([per_ep[ep.name][t.id].energy_j for ep in endpoints]) for t in tasks]
        )
        cap = min(
            [ep.startup_energy_j for ep in endpoints if ep.has_batch_scheduler]
            or [np.inf]
        )
        clusters = agglomerative_cluster(
            feats, energies, cap, max_cluster_size=max_cluster_size
        )
        return mhra(tasks, endpoints, store, transfer, alpha, heuristics,
                    clusters, engine="clone", carbon=carbon,
                    lookahead=lookahead, alive=alive, warm=warm,
                    fairness=fairness)
    table = PredictionTable(tasks, endpoints, store)
    clusters = compute_clusters(tasks, endpoints, table, max_cluster_size)
    return mhra(tasks, endpoints, store, transfer, alpha, heuristics,
                clusters, engine=engine, state=state, carbon=carbon,
                lookahead=lookahead, alive=alive, warm=warm,
                fairness=fairness)


# ---------------------------------------------------------------------------
# Baselines (Table V rows)
# ---------------------------------------------------------------------------


def fixed_assignment(
    tasks, endpoints, store, transfer, pick: Callable[[int, TaskSpec], str],
    state: SchedulerState | None = None,
) -> Schedule:
    tasks = list(tasks)
    per_ep = PredictionTable(tasks, endpoints, store).per_ep()
    by_ep = {e.name: e for e in endpoints}
    state = state if state is not None else SchedulerState(endpoints, transfer)
    assignments = {}
    for i, t in enumerate(tasks):
        name = pick(i, t)
        state.assign([t], by_ep[name], per_ep[name], record_timeline=True)
        assignments[t.id] = name
    e, c, tj = state.metrics()
    return Schedule(assignments, np.nan, e, c, tj, "fixed", dict(state.timeline))


def round_robin(tasks, endpoints, store, transfer,
                state: SchedulerState | None = None, offset: int = 0) -> Schedule:
    names = [e.name for e in endpoints]
    return fixed_assignment(
        tasks, endpoints, store, transfer,
        lambda i, t: names[(i + offset) % len(names)], state=state,
    )


def single_site(tasks, endpoints, store, transfer, site: str,
                state: SchedulerState | None = None) -> Schedule:
    names = {e.name for e in endpoints}
    if site not in names:
        raise ValueError(
            f"single_site requires site to be one of {sorted(names)}, got {site!r}"
        )
    return fixed_assignment(tasks, endpoints, store, transfer,
                            lambda i, t: site, state=state)
