"""jax ``engine="jax"`` placement backend: fused window greedy.

``greedy_window`` runs one arrival window's whole greedy placement — all
ordering heuristics at once — as a jit-compiled ``lax.scan`` over the
window's tasks whose step holds every heuristic (the per-heuristic body
vmapped inside the step).  Each scan step scores the full candidate
fleet as one fused vector pass over the SoA engine's carry registers and
commits via a first-min argmin, reproducing ``_greedy_soa``'s float
sequences double for double:

- The per-step objective is *recomputed* from carried registers
  (``e_base``/``nl``/term registers + the frozen run basis) instead of
  selectively refreshed; the two are bitwise-identical lane by lane
  (multiplication commutes bitwise and the per-element op order matches
  both the SoA miss pass and its scalar refresh paths — see ``ref.py``).
- Run memoization is emulated with host-precomputed ``new_run`` flags:
  on a run boundary the basis scalars (``const`` sums, the transfer
  baseline) refresh — using :func:`ref.pairwise_sum` so the in-scan sum
  matches ``np.sum``'s association bitwise — and stay frozen within the
  run, exactly like the SoA engine's memo basis.  The pass that computes
  the basis runs only on steps where some heuristic starts a run (a
  ``lax.cond`` on ``any(new_run)`` over the heuristic axis); elsewhere
  every heuristic keeps its basis, which the pass would have kept too.
- Disabled term registers (carbon/lookahead/fairness/warm) enter as
  zeros with zero weights; ``+0.0`` is bitwise-inert here, so one traced
  program covers every register combination — no per-flag recompiles.

Shapes are padded: endpoints and cores to power-of-two buckets (lanes to
a 128 multiple under the Pallas kernel), tasks and input signatures to
power-of-two buckets, so a campaign compiles at most ``log2`` variants
per axis.  ``x64`` is scoped to every placement entry point (the whole
parity contract is float64) without flipping the process-global flag —
sibling kernels trace float32 and must keep doing so in the same
process.  Pad endpoint lanes carry all-zero slots with ``first=inf`` and
``alive=False`` (finite scores, masked to ``+inf`` before the argmin),
so no ``inf - inf`` NaN can poison a decision.
"""
from __future__ import annotations

import contextlib
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.spans import span
from repro.kernels import dispatch
from repro.kernels.placement import kernel as _kernel
from repro.kernels.placement import ref as _ref

#: The placement scan's function name.  Its program runs as
#: ``jit__greedy_scan(<hash>)`` in a profiler trace, and the benchmark's
#: trace readers find the scan by this string.
SCAN_PROGRAM = "_greedy_scan"

#: Scan program accounting over the first call of each (shape, backend)
#: signature of ``greedy_window``: ``compiles`` counts backend compiles of
#: the scan, ``cache_loads`` the persistent compile cache's hits, and
#: ``seconds`` the wall time of those first calls (a compile or a cache
#: load, plus one execution), so harnesses report set-up apart from warm
#: windows.  Cumulative; reset with :func:`reset_compile_stats`.
COMPILE_STATS = {"compiles": 0, "cache_loads": 0, "seconds": 0.0}

#: ``engine="jax"`` window accounting: ``device`` counts windows placed by
#: :func:`greedy_window`, ``soa`` counts windows the jax engine handed to
#: the host SoA greedy because the fused scan cannot express them
#: (clustered units, multi-input tasks).  ``h2d_bytes``/``h2d_arrays`` and
#: ``d2h_bytes``/``d2h_arrays`` count what the device windows moved to the
#: device and read back.  ``scan_steps`` counts the device windows' valid
#: scan steps (tasks), ``basis_steps`` those on which some heuristic starts
#: a run, so the scan ran its run-basis pass.  Cumulative; reset with
#: :func:`reset_window_stats`.
WINDOW_STATS = {"device": 0, "soa": 0, "h2d_bytes": 0, "h2d_arrays": 0,
                "d2h_bytes": 0, "d2h_arrays": 0, "basis_steps": 0,
                "scan_steps": 0}

_seen_signatures: set[tuple] = set()

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def reset_compile_stats() -> None:
    COMPILE_STATS["compiles"] = 0
    COMPILE_STATS["cache_loads"] = 0
    COMPILE_STATS["seconds"] = 0.0
    _seen_signatures.clear()


def reset_window_stats() -> None:
    for k in WINDOW_STATS:
        WINDOW_STATS[k] = 0


class _ScanCompiles:
    """Backend compiles of the scan and its persistent-cache hits, from the
    ``jax.monitoring`` events seen while it is open.  JAX reports a cache
    hit as a backend compile too, recording the hit first and the compile
    (with the function's name) when it returns."""

    def __init__(self):
        self.compiles = 0
        self.loads = 0
        self._hit = False

    def _duration(self, event, duration, **kw):
        if event == _BACKEND_COMPILE_EVENT:
            if SCAN_PROGRAM in str(kw.get("fun_name", "")):
                if self._hit:
                    self.loads += 1
                else:
                    self.compiles += 1
            self._hit = False

    def _event(self, event, **kw):
        if event == _CACHE_HIT_EVENT:
            self._hit = True

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


def bucket_pow2(n: int, minimum: int = 1) -> int:
    """Smallest power of two >= max(n, minimum)."""
    b = max(int(minimum), 1)
    n = max(int(n), 1)
    while b < n:
        b <<= 1
    return b


def lane_bucket(n_ep: int) -> int:
    """Padded endpoint-lane count: power-of-two bucket, widened to a
    128-lane multiple when the Pallas score kernel is active (its tile)."""
    if dispatch.placement_use_pallas():
        return ((max(n_ep, 1) + 127) // 128) * 128
    return bucket_pow2(n_ep)


def score_fleet(e_base, nl, g_base, lk, fw, wt, alive, c_cur,
                idle_on_sum, a1, b1, g1, w_idle_on):
    """Standalone fused score+argmin over one candidate fleet.

    Dispatches on :func:`repro.kernels.dispatch.placement_backend`:
    ``ref`` (NumPy oracle), ``xla`` (pure jnp), or ``pallas_interpret``
    (the tiled kernel, emulated).  Returns ``(obj, argmin)`` with
    ``obj`` over the true (unpadded) fleet.  The in-scan twin of this op
    is traced inside :func:`greedy_window`; this entry point exists for
    tests and for scoring outside a jit context.
    """
    be = dispatch.placement_backend()
    if be != "ref":
        # the parity contract is float64: scope x64 to this call instead
        # of flipping the process-global flag (other kernels trace f32)
        with jax.enable_x64(True):
            return _score_fleet_jax(
                e_base, nl, g_base, lk, fw, wt, alive, c_cur,
                idle_on_sum, a1, b1, g1, w_idle_on, be,
            )
    return _ref.score_fleet(
            np.asarray(e_base, dtype=np.float64),
            np.asarray(nl, dtype=np.float64),
            np.asarray(g_base, dtype=np.float64),
            np.asarray(lk, dtype=np.float64),
            np.asarray(fw, dtype=np.float64),
            np.asarray(wt, dtype=np.float64),
            np.asarray(alive, dtype=bool),
            float(c_cur), float(idle_on_sum), float(a1), float(b1),
            float(g1), float(w_idle_on),
        )


def _score_fleet_jax(e_base, nl, g_base, lk, fw, wt, alive, c_cur,
                     idle_on_sum, a1, b1, g1, w_idle_on, be):
    n = len(e_base)
    if be == "pallas_interpret":
        lanes = ((n + 127) // 128) * 128
        pad = lanes - n

        def p(v, fill=0.0):
            return jnp.pad(jnp.asarray(v, dtype=jnp.float64), (0, pad),
                           constant_values=fill)

        scalars = jnp.array(
            [c_cur, idle_on_sum, a1, b1, g1, w_idle_on], dtype=jnp.float64
        )
        alive_f = p(jnp.asarray(alive, dtype=jnp.float64))
        obj, _, idx = _kernel.score_fleet(
            scalars, p(e_base), p(nl), p(g_base), p(lk), p(fw), p(wt),
            alive_f, interpret=True,
        )
        return np.asarray(obj)[:n], int(idx)
    obj = _score_lanes(
        jnp.asarray(e_base, dtype=jnp.float64),
        jnp.asarray(nl, dtype=jnp.float64),
        jnp.asarray(g_base, dtype=jnp.float64),
        jnp.asarray(lk, dtype=jnp.float64),
        jnp.asarray(fw, dtype=jnp.float64),
        jnp.asarray(wt, dtype=jnp.float64),
        jnp.asarray(alive, dtype=bool),
        c_cur, idle_on_sum, a1, b1, g1, w_idle_on,
    )
    return np.asarray(obj), int(jnp.argmin(obj))


def _score_lanes(e_base, nl, g_base, lk, fw, wt, alive, c_cur,
                 idle_on_sum, a1, b1, g1, w_idle_on):
    """The fused objective, pure jnp — op order mirrors ``ref.score_fleet``."""
    c2 = jnp.maximum(nl, c_cur)
    e_s = idle_on_sum * c2 + e_base
    obj = a1 * e_s + b1 * c2
    obj = obj + g1 * (w_idle_on * c2 + g_base)
    obj = obj + lk
    obj = obj + fw
    obj = obj + wt
    return jnp.where(alive, obj, jnp.inf)


@functools.partial(jax.jit, static_argnames=("n_ep", "use_kernel"))
def _greedy_scan(consts, init, xs, *, n_ep, use_kernel):
    """One scan over the window's tasks, every heuristic in each step; see
    ``greedy_window`` for the layout.

    ``n_ep`` (the *true* fleet size) is static: the run-basis scalars are
    summed over exactly the first ``n_ep`` lanes with numpy's pairwise
    association, unrolled at trace time.  ``use_kernel`` scores through
    the Pallas kernel in interpret mode instead of the fused jnp pass.
    """
    sc = consts["scalars"]
    a1, b1, g1 = sc["a1"], sc["b1"], sc["g1"]
    idle_on_sum, w_idle_on = sc["idle_on_sum"], sc["w_idle_on"]
    lam_b1, lam_a1 = sc["lam_b1"], sc["lam_a1"]
    alpha, sf1, sf2 = sc["alpha"], sc["sf1"], sc["sf2"]
    f_beta, f_mu = sc["f_beta"], sc["f_mu"]
    idle_bt, su_bt, qd = consts["idle_bt"], consts["su_bt"], consts["qd"]
    rates, wt = consts["rates"], consts["wt"]
    alive_m = consts["alive"]
    rt_tab, en_tab = consts["rt_tab"], consts["en_tab"]
    fen_tab, frt_tab = consts["fen_tab"], consts["frt_tab"]
    add_tab, hv_tab = consts["add_tab"], consts["hv_tab"]

    def basis(base_regs, staged, tj, regs, x):
        """One heuristic's full vectorized pass (the SoA miss pass, op for
        op), taken into the run basis only where ``new_run`` is set."""
        run_regs, c_sum_b, cg_sum_b, tj_b = regs
        mins, first, last, dyn, const, const_g = base_regs
        sig, ti = x["sig"], x["ti"]
        st_row = staged[sig]
        # per-task (E,) rows are gathered from small constant tables
        # instead of streamed as (H, T, E) xs — same doubles, a fraction
        # of the memory traffic on deep windows
        add_row = add_tab[sig]
        hv_row = hv_tab[x["hv_id"]]
        rt_row, en_row = rt_tab[ti], en_tab[ti]
        eff_add = jnp.where(st_row, 0.0, add_row)
        eff_ready = jnp.where(st_row, 0.0, x["ready_s"]) + qd
        c_sum_f = _ref.pairwise_sum(const, n_ep)
        cg_sum_f = _ref.pairwise_sum(const_g, n_ep)
        static = c_sum_f - const
        static_g = cg_sum_f - const_g
        start = jnp.maximum(mins, eff_ready)
        start = jnp.maximum(start, x["nb"])   # bitwise no-op when nb <= 0
        end = start + rt_row
        nf = jnp.minimum(first, start)
        nl = jnp.maximum(last, end)
        nd = dyn + en_row
        span = (nl - nf) * idle_bt + su_bt
        e_base_f = static + nd
        e_base_f = e_base_f + span
        e_base_f = e_base_f + eff_add
        e_base_f = e_base_f + tj
        g_base_f = (span + nd) * rates + static_g
        lk_c1 = lam_b1 * x["u_tw"]
        lk_c2 = lam_a1 * x["u_oj"]
        lk_f = end * lk_c1 + hv_row * lk_c2
        dj = fen_tab[ti] - en_row
        fjv = jnp.where(dj <= 0.0, 0.0, dj * x["u_fd"])
        ds = frt_tab[ti] - rt_row
        fsv = jnp.where(ds <= 0.0, 0.0, ds * x["u_fd"])
        fjv = fjv * alpha / sf1
        fsv = fsv * f_beta / sf2
        fw_f = (fjv + fsv) * f_mu

        new_run = x["new_run"]
        run_regs = jnp.where(
            new_run,
            jnp.stack([e_base_f, nl, g_base_f, lk_f, fw_f]),
            run_regs,
        )
        return (run_regs, jnp.where(new_run, c_sum_f, c_sum_b),
                jnp.where(new_run, cg_sum_f, cg_sum_b),
                jnp.where(new_run, tj, tj_b))

    def score_commit(base_regs, slots, staged, c_cur, tj, regs, x):
        """One heuristic's fused score, first-min argmin and commit."""
        run_regs, c_sum_b, cg_sum_b, tj_b = regs
        e_base, nl_r, g_base_r, lk_r, fw_r = run_regs
        if use_kernel:
            scalars = jnp.stack(
                [c_cur, idle_on_sum, a1, b1, g1, w_idle_on]
            )
            alive_f = alive_m.astype(jnp.float64)
            _, _, ei = _kernel.score_fleet(
                scalars, e_base, nl_r, g_base_r, lk_r, fw_r, wt, alive_f,
                interpret=True,
            )
        else:
            obj = _score_lanes(e_base, nl_r, g_base_r, lk_r, fw_r, wt,
                               alive_m, c_cur, idle_on_sum, a1, b1, g1,
                               w_idle_on)
            ei = jnp.argmin(obj)

        # ---- commit: the SoA scalar commit, with a refresh of the
        # committed lane against the frozen run basis.  Every scatter
        # value is gated on ``valid`` (pad steps write the old value back
        # bitwise) — an O(1) guard per scatter instead of a full
        # carry-tree where-select, whose O(E*C) slots copy per step
        # dominated the scan on deep windows.  The task's table rows are
        # read at the committed lane only: the same doubles as the rows'
        # entries ------------------------------------------------------------
        valid = x["valid"]

        def sel(new_v, old_v):
            return jnp.where(valid, new_v, old_v)

        sig, ti, ready_s, nb = x["sig"], x["ti"], x["ready_s"], x["nb"]
        st_e = staged[sig, ei]
        add_e = add_tab[sig, ei]
        rt_e, en_e = rt_tab[ti, ei], en_tab[ti, ei]
        ready_e = jnp.where(st_e, 0.0, ready_s) + qd[ei]
        tj2 = sel(tj + jnp.where(st_e, 0.0, add_e), tj)
        staged_e2 = st_e | x["shared_s"]
        staged2 = staged.at[sig, ei].set(sel(staged_e2, st_e))
        bcol = base_regs[:, ei]       # one gather for all six registers
        mins_e, first_e, last_e, dyn_e, const_e, const_g_e = bcol
        start_v = jnp.maximum(mins_e, ready_e)
        start_v = jnp.maximum(start_v, nb)
        end_v = start_v + rt_e
        nf_v = jnp.minimum(start_v, first_e)
        nl_v = jnp.maximum(end_v, last_e)
        nd_v = dyn_e + en_e
        row = slots[ei]
        k = jnp.argmin(row)           # first min slot, like list.index(min)
        row2 = row.at[k].set(end_v)
        m2 = jnp.min(row2)
        slots2 = slots.at[ei, k].set(sel(end_v, row[k]))
        c_e = (nl_v - nf_v) * idle_bt[ei] + su_bt[ei] + nd_v
        cg_e = rates[ei] * c_e
        base_regs2 = base_regs.at[:, ei].set(
            sel(jnp.stack([m2, nf_v, nl_v, nd_v, c_e, cg_e]), bcol)
        )
        ready2 = jnp.where(staged_e2, 0.0, ready_s) + qd[ei]
        s2 = jnp.maximum(m2, ready2)
        s2 = jnp.maximum(s2, nb)
        e2 = s2 + rt_e
        nf2 = jnp.minimum(s2, nf_v)
        nl2 = jnp.maximum(e2, nl_v)
        e_b = (c_sum_b - c_e) + (nd_v + en_e)
        e_b = e_b + ((nl2 - nf2) * idle_bt[ei] + su_bt[ei])
        e_b = e_b + jnp.where(staged_e2, 0.0, add_e)
        e_b = e_b + tj_b
        g_b = (cg_sum_b - cg_e) + rates[ei] * (
            ((nl2 - nf2) * idle_bt[ei] + su_bt[ei])
            + (nd_v + en_e)
        )
        lk_c1 = lam_b1 * x["u_tw"]
        lk_c2 = lam_a1 * x["u_oj"]
        lk_e = e2 * lk_c1 + hv_tab[x["hv_id"], ei] * lk_c2
        # fw_r (row 4) is per-run, never refreshed by a commit
        run_regs2 = run_regs.at[:4, ei].set(
            sel(jnp.stack([e_b, nl2, g_b, lk_e]), run_regs[:4, ei])
        )
        c_cur2 = sel(jnp.maximum(c_cur, end_v), c_cur)

        carry_out = (
            base_regs2, slots2, run_regs2, staged2, c_cur2,
            tj2, c_sum_b, tj_b, cg_sum_b,
        )
        return carry_out, (ei.astype(jnp.int32), start_v, end_v)

    def step(carry, x):
        # x and every carry leaf lead with the heuristic axis.  The run
        # basis changes only on a run's first task, so the pass that
        # computes it runs only on steps where some heuristic starts a run;
        # the predicate is taken over heuristics outside the vmap, since a
        # batched predicate would turn the cond into a select of both sides
        (base_regs, slots, run_regs, staged, c_cur, tj, c_sum_b, tj_b,
         cg_sum_b) = carry
        regs = (run_regs, c_sum_b, cg_sum_b, tj_b)
        regs = lax.cond(
            jnp.any(x["new_run"]),
            lambda: jax.vmap(basis)(base_regs, staged, tj, regs, x),
            lambda: regs,
        )
        return jax.vmap(score_commit)(base_regs, slots, staged, c_cur, tj,
                                      regs, x)

    (mins, slots, first, last, dyn, const, const_g, e_base, nl_r,
     g_base_r, lk_r, fw_r, staged, c_cur, tj, c_sum_b, tj_b,
     cg_sum_b) = init
    # per-endpoint registers ride stacked ((H, 6, E) commit-updated,
    # (H, 5, E) run-basis) so the commit is two column scatters / two column
    # gathers instead of ~20 per-register dynamic ops — storage layout
    # only, every double is the one the unstacked carry would hold
    carry0 = (
        jnp.stack([mins, first, last, dyn, const, const_g], axis=1), slots,
        jnp.stack([e_base, nl_r, g_base_r, lk_r, fw_r], axis=1), staged,
        c_cur, tj, c_sum_b, tj_b, cg_sum_b,
    )
    # scan over tasks: (H, T) streams in, (H, T) outputs back
    xs_t = jax.tree_util.tree_map(lambda v: jnp.swapaxes(v, 0, 1), xs)
    # unroll a few steps per scan iteration: XLA:CPU's per-iteration
    # dispatch overhead dominates on deep windows, and unrolling keeps
    # the op sequence (hence every double) identical
    carry_f, ys = lax.scan(step, carry0, xs_t, unroll=4)
    b, slots_f, r, staged_f, c_cur_f, tj_f, csb, tjb, cgb = carry_f
    return (
        b[:, 0], slots_f, b[:, 1], b[:, 2], b[:, 3], b[:, 4], b[:, 5],
        r[:, 0], r[:, 1], r[:, 2], r[:, 3], r[:, 4], staged_f, c_cur_f, tj_f,
        csb, tjb, cgb,
    ), tuple(jnp.swapaxes(y, 0, 1) for y in ys)


def greedy_window(n_ep: int, consts: dict, init: dict, xs: dict):
    """Run the fused greedy over one window for every ordering heuristic.

    ``consts``: per-fleet constants (padded lanes; see ``_greedy_scan``),
    plus the per-input-signature transfer table.  ``init``: carry seeds
    with a leading heuristic axis.  ``xs``: per-task streams, shape
    ``(H, T_pad, ...)``, permuted per heuristic.  Returns
    ``(final_carry, (ei, start, end))`` as numpy arrays, and maintains
    :data:`COMPILE_STATS` (the first call per shape signature is timed and
    split into compiles and cache loads) and :data:`WINDOW_STATS`.  The
    transfer up, the scan and the readback are spans ``gf.h2d``,
    ``gf.scan`` and ``gf.d2h``; the transfers carry ``bytes`` and
    ``arrays``, the scan ``basis_steps`` and ``scan_steps``.
    """
    use_kernel = dispatch.placement_use_pallas()
    sig = (
        n_ep, use_kernel,
        tuple(sorted((k, np.shape(v)) for k, v in xs.items())),
        tuple(sorted((k, np.shape(v)) for k, v in init.items())),
        tuple(sorted((k, np.shape(v)) for k, v in consts.items()
                     if k != "scalars")),
    )
    first = sig not in _seen_signatures
    _seen_signatures.add(sig)
    t0 = time.perf_counter()
    seen = _ScanCompiles() if first else contextlib.nullcontext()
    host = jax.tree_util.tree_leaves((xs, init, consts))
    h2d_bytes = sum(v.nbytes for v in host)
    valid = np.asarray(xs["valid"])
    scan_steps = int(valid.any(axis=0).sum())
    basis_steps = int((np.asarray(xs["new_run"]) & valid).any(axis=0).sum())
    # x64 is scoped to the placement scan (trace + execute) rather than
    # enabled process-wide: the float64 parity contract is the scan's own,
    # and the caller's other JAX code keeps its default precision
    with seen, jax.enable_x64(True):
        with span("h2d", bytes=h2d_bytes, arrays=len(host)):
            jxs = jax.tree_util.tree_map(jnp.asarray, xs)
            jinit = jax.tree_util.tree_map(jnp.asarray, init)
            jconsts = jax.tree_util.tree_map(jnp.asarray, consts)
        with span("scan", basis_steps=basis_steps, scan_steps=scan_steps):
            carry, ys = _greedy_scan(jconsts, _as_tuple_carry(jinit), jxs,
                                     n_ep=n_ep, use_kernel=use_kernel)
            carry = jax.block_until_ready(carry)
    if first:
        COMPILE_STATS["compiles"] += seen.compiles
        COMPILE_STATS["cache_loads"] += seen.loads
        COMPILE_STATS["seconds"] += time.perf_counter() - t0
    names = ("mins", "slots", "first", "last", "dyn", "const", "const_g",
             "e_base", "nl_r", "g_base_r", "lk_r", "fw_r", "staged",
             "c_cur", "tj", "c_sum_b", "tj_b", "cg_sum_b")
    dev = (*carry, *ys)
    d2h_bytes = sum(v.nbytes for v in dev)
    with span("d2h", bytes=d2h_bytes, arrays=len(dev)):
        out = {k: np.asarray(v) for k, v in zip(names, carry)}
        ei, start, end = (np.asarray(v) for v in ys)
    WINDOW_STATS["device"] += 1
    WINDOW_STATS["h2d_bytes"] += h2d_bytes
    WINDOW_STATS["h2d_arrays"] += len(host)
    WINDOW_STATS["d2h_bytes"] += d2h_bytes
    WINDOW_STATS["d2h_arrays"] += len(dev)
    WINDOW_STATS["basis_steps"] += basis_steps
    WINDOW_STATS["scan_steps"] += scan_steps
    return out, (ei, start, end)


def _as_tuple_carry(init: dict):
    return (
        init["mins"], init["slots"], init["first"], init["last"],
        init["dyn"], init["const"], init["const_g"], init["e_base"],
        init["nl_r"], init["g_base_r"], init["lk_r"], init["fw_r"],
        init["staged"], init["c_cur"], init["tj"], init["c_sum_b"],
        init["tj_b"], init["cg_sum_b"],
    )
