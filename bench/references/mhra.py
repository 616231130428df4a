"""Plain reference of MHRA window placement (GreenFaaS §III-F, Algorithm 1).

Written from the paper's semantics and the deployment file alone; it
imports nothing of the program.  For each arrival window:

- every core slot is raised to the window's open time;
- SF1/SF2 are the energy and makespan of the whole window placed on each
  endpoint alone from an empty state (the largest over endpoints);
- each ordering heuristic sorts the window by its fleet-mean predicted
  runtime or energy and places the tasks greedily, one at a time, on the
  endpoint that minimises ``alpha * E_tot / SF1 + (1 - alpha) * C_max / SF2``
  of the live state after the placement;
- the heuristic whose final state has the lowest objective wins, and its
  state is carried into the next window.

``E_tot`` is transfer energy, plus idle power over each used batch
endpoint's allocated span and its start-up energy, plus idle power of
every always-on endpoint over ``C_max``, plus the dynamic energy of every
task.  A task starts at the earliest free core of its endpoint, no
earlier than its inputs arrive (transfer time plus batch queue delay) and
its ``not_before``.  Shared inputs are staged once per endpoint.

The arithmetic runs vectorised over heuristics and endpoints in the
``dtype`` the reference is built with: float64 for the check, float32 for
its control.  The sort keys are compared as float64 in both, so the two
order ties alike and differ only in arithmetic.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import deployment


@dataclasses.dataclass
class WindowOut:
    """One placed window, rows in the order the tasks were given."""
    endpoint: np.ndarray       # (T,) endpoint index
    start: np.ndarray          # (T,) seconds
    end: np.ndarray            # (T,) seconds
    objective: float
    energy_j: float
    makespan_s: float
    heuristic: str
    margin: float              # smallest relative gap best -> runner-up


_KEYS = {
    "shortest_runtime_first": ("rt", 1.0),
    "longest_runtime_first": ("rt", -1.0),
    "highest_energy_first": ("en", -1.0),
    "lowest_energy_first": ("en", 1.0),
}


class Reference:
    """The live placement state of one fleet, advanced window by window."""

    def __init__(self, cfg: dict, dtype=np.float64):
        self.dtype = f = np.dtype(dtype)
        fleet = deployment.machines(cfg)
        net = cfg["network"]
        pol = cfg["policy"]
        self.names = [m.name for m in fleet]
        self.index = {n: i for i, n in enumerate(self.names)}
        self.functions = list(cfg["functions"])
        self.fn_index = {fn: i for i, fn in enumerate(self.functions)}
        self.heuristics = list(pol["heuristics"])
        self.alpha = float(pol["alpha"])
        E = len(fleet)
        self.cores = np.array([m.cores for m in fleet])
        self.bt = np.array([m.has_batch_scheduler for m in fleet])
        idle = np.array([m.idle_power_w for m in fleet])
        qd = np.where(self.bt, [m.queue_delay_s for m in fleet], 0.0)
        su = np.where(self.bt, idle * (qd + net["release_overhead_s"]), 0.0)
        self.idle = idle.astype(f)
        self.qd = qd.astype(f)
        self.su = su.astype(f)
        self.idle_bt = np.where(self.bt, idle, 0.0).astype(f)
        self.idle_on_sum = f.type(idle[~self.bt].sum())
        profs = deployment.profiles(cfg, fleet)
        self.rt_tab = np.array([[profs[fn][n][0] for fn in self.functions]
                                for n in self.names])          # (E, F) f64
        self.en_tab = np.array([[profs[fn][n][1] for fn in self.functions]
                                for n in self.names])
        # hop counts: the source's table (or the default), plus the file
        # system / transfer node hops at each batch-scheduled end
        extra = np.where(self.bt, net["fs_dtn_extra_hops"], 0)
        hops = np.empty((E, E))
        for i, m in enumerate(fleet):
            for j, n in enumerate(fleet):
                hops[i, j] = 0 if i == j else (
                    m.hops.get(n.name, net["default_hops"]) + extra[i] + extra[j])
        self.hops = hops
        self.e_inc = net["e_inc_j_per_byte"]
        # transfer time: ridge regression t = c0 + c1 * files + c2 * GB
        xtx = np.eye(3) * net["transfer_time_ridge"]
        xty = np.zeros(3)
        for n_files, nbytes, secs in net["transfer_time_prior"]:
            x = np.array([1.0, n_files, nbytes / 1e9])
            xtx += np.outer(x, x)
            xty += x * secs
        self.t_coef = np.linalg.solve(xtx, xty)
        C = int(self.cores.max())
        self.slots = np.where(np.arange(C)[None, :] < self.cores[:, None],
                              0.0, np.inf).astype(f)             # (E, C)
        self.first = np.full(E, np.inf, dtype=f)
        self.last = np.zeros(E, dtype=f)
        self.dyn = np.zeros(E, dtype=f)
        self.tj = f.type(0.0)
        self.staged: dict[tuple, np.ndarray] = {}                  # key -> (E,)

    # -- transfers ---------------------------------------------------------
    def _seconds(self, files, nbytes):
        c0, c1, c2 = self.t_coef
        t = c0 + c1 * files + c2 * (nbytes / 1e9)
        return np.where((files > 0) & (nbytes > 0), np.maximum(t, 0.0), 0.0)

    def _transfer_rows(self, inputs, staged):
        """(energy (E,), ready (E,)) of one task's inputs on every endpoint,
        given ``staged(key) -> (E,) bool``."""
        E = len(self.names)
        add = np.zeros(E)
        files = np.zeros(E)
        nbytes = np.zeros(E)
        seen = set()
        for src, n_files, b, shared in inputs:
            si = self.index[src]
            need = np.ones(E, dtype=bool)
            need[si] = False
            if shared:
                key = (src, n_files, b)
                if key in seen:
                    continue
                seen.add(key)
                need &= ~staged(key)
            add += need * self.hops[si] * b * self.e_inc
            files += need * n_files
            nbytes += need * b
        ready = self._seconds(files, nbytes) + self.qd
        return add.astype(self.dtype), ready.astype(self.dtype)

    # -- one window --------------------------------------------------------
    def place(self, now: float, tasks: list[tuple]) -> WindowOut:
        """Place one window; ``tasks`` are ``(fn, inputs, not_before)``."""
        f = self.dtype
        T = len(tasks)
        E = len(self.names)
        H = len(self.heuristics)
        fn_ids = np.array([self.fn_index[t[0]] for t in tasks])
        rt64 = self.rt_tab[:, fn_ids]                               # (E, T)
        en64 = self.en_tab[:, fn_ids]
        rtT = np.ascontiguousarray(rt64.T).astype(f)                # (T, E)
        enT = np.ascontiguousarray(en64.T).astype(f)
        nbs = np.array([t[2] for t in tasks], dtype=f)
        np.maximum(self.slots, f.type(now), out=self.slots)

        sig_of = np.empty(T, dtype=np.intp)
        sigs: dict[tuple, int] = {}
        for i, t in enumerate(tasks):
            sig_of[i] = sigs.setdefault(tuple(t[1]), len(sigs))
        sig_list = list(sigs)
        sf1, sf2 = self._normalizers(tasks, sig_of, sig_list, rtT, enT, nbs)
        a1 = f.type(self.alpha) / sf1
        b1 = f.type(1.0 - self.alpha) / sf2

        # orders: the heuristics' keys compared as float64, ties as argsort
        stats = {"rt": rt64.mean(axis=0), "en": en64.mean(axis=0)}
        orders = np.stack([
            np.argsort(_KEYS[h][1] * stats[_KEYS[h][0]].astype(f).astype(np.float64))
            for h in self.heuristics])                              # (H, T)

        ar = np.arange(H)
        slots = np.broadcast_to(self.slots, (H,) + self.slots.shape).copy()
        mins = slots.min(axis=2)
        first = np.tile(self.first, (H, 1))
        last = np.tile(self.last, (H, 1))
        dyn = np.tile(self.dyn, (H, 1))
        const = self._energy_terms(first, last, dyn)
        csum = const.sum(axis=1)
        ccur = np.maximum(last.max(axis=1), f.type(0.0))
        tj = np.full(H, self.tj, dtype=f)
        staged = [dict(self.staged) for _ in range(H)]
        # per heuristic and input signature: transfer rows under its staging
        add_tab = np.empty((H, len(sig_list), E), dtype=f)
        rdy_tab = np.empty((H, len(sig_list), E), dtype=f)
        shared_keys = [[(s, n, b) for s, n, b, sh in sig if sh] for sig in sig_list]

        def refresh(h, si):
            st = staged[h]
            add_tab[h, si], rdy_tab[h, si] = self._transfer_rows(
                sig_list[si], lambda k: st.get(k, np.zeros(E, dtype=bool)))

        for h in range(H):
            for si in range(len(sig_list)):
                refresh(h, si)
        out_e = np.empty((H, T), dtype=np.intp)
        out_s = np.empty((H, T), dtype=f)
        out_f = np.empty((H, T), dtype=f)
        margin = np.full(H, np.inf)
        for s in range(T):
            ti = orders[:, s]
            sg = sig_of[ti]
            add = add_tab[ar, sg]
            ready = rdy_tab[ar, sg]
            start = np.maximum(mins, ready)
            start = np.maximum(start, nbs[ti][:, None])
            end = start + rtT[ti]
            nf = np.minimum(first, start)
            nl = np.maximum(last, end)
            nd = dyn + enT[ti]
            cn = (nl - nf) * self.idle_bt + self.su + nd
            c = np.maximum(nl, ccur[:, None])
            etot = (tj[:, None] + add) + (csum[:, None] - const) + cn \
                + self.idle_on_sum * c
            obj = a1 * etot + b1 * c
            ei = obj.argmin(axis=1)
            if E > 1:
                two = np.partition(obj, 1, axis=1)
                margin = np.minimum(margin, (two[:, 1] - two[:, 0]) / np.abs(two[:, 0]))
            sel = (ar, ei)
            e_v = end[sel]
            row = slots[ar, ei]
            k = row.argmin(axis=1)
            slots[ar, ei, k] = e_v
            mins[sel] = slots[ar, ei].min(axis=1)
            first[sel] = nf[sel]
            last[sel] = nl[sel]
            dyn[sel] = nd[sel]
            csum += cn[sel] - const[sel]
            const[sel] = cn[sel]
            np.maximum(ccur, e_v, out=ccur)
            tj += add[sel]
            out_e[:, s] = ei
            out_s[:, s] = start[sel]
            out_f[:, s] = e_v
            for h in range(H):
                for key in shared_keys[sg[h]]:
                    row_st = staged[h].get(key)
                    if row_st is None or not row_st[ei[h]]:
                        row_st = np.zeros(E, dtype=bool) if row_st is None else row_st.copy()
                        row_st[ei[h]] = True
                        staged[h][key] = row_st
                        for si in range(len(sig_list)):
                            if key in shared_keys[si]:
                                refresh(h, si)

        # the winner: the lowest objective of the final states, first wins
        cmax = np.maximum(last.max(axis=1), f.type(0.0))
        etot_f = tj + self.idle_on_sum * cmax + self._energy_terms(first, last, dyn).sum(axis=1)
        obj_f = f.type(self.alpha) * etot_f / sf1 + f.type(1.0 - self.alpha) * cmax / sf2
        w = int(np.argmin(obj_f))
        self.slots = slots[w]
        self.first, self.last, self.dyn = first[w], last[w], dyn[w]
        self.tj = tj[w]
        self.staged = staged[w]
        inv = np.empty(T, dtype=np.intp)
        inv[orders[w]] = np.arange(T)
        return WindowOut(
            endpoint=out_e[w, inv], start=out_s[w, inv], end=out_f[w, inv],
            objective=float(obj_f[w]), energy_j=float(etot_f[w]),
            makespan_s=float(cmax[w]), heuristic=self.heuristics[w],
            margin=float(margin[w]))

    def _energy_terms(self, first, last, dyn):
        """Each endpoint's energy but the always-on idle draw: a used batch
        endpoint's idle power over its span plus start-up, and every
        endpoint's dynamic energy."""
        used = np.isfinite(first)
        span = np.where(used, last - first, 0)
        return np.where(used, self.idle_bt * span + self.su, 0).astype(self.dtype) + dyn

    def _normalizers(self, tasks, sig_of, sig_list, rtT, enT, nbs):
        """SF1/SF2: the window on each endpoint alone, from empty."""
        f = self.dtype
        E = len(self.names)
        # the whole window's inputs as one transfer: shared inputs once
        counts = np.bincount(sig_of, minlength=len(sig_list))
        merged = []
        seen = set()
        for sig, n in zip(sig_list, counts):
            for src, n_files, b, shared in sig:
                if shared:
                    if (src, n_files, b) in seen:
                        continue
                    seen.add((src, n_files, b))
                    merged.append((src, n_files, b, 1))
                else:
                    merged.append((src, n_files, b, int(n)))
        add = np.zeros(E)
        files = np.zeros(E)
        nbytes = np.zeros(E)
        for src, n_files, b, mult in merged:
            need = np.ones(E, dtype=bool)
            need[self.index[src]] = False
            add += need * self.hops[self.index[src]] * b * self.e_inc * mult
            files += need * n_files * mult
            nbytes += need * b * mult
        ready = (self._seconds(files, nbytes) + self.qd).astype(f)
        C = self.slots.shape[1]
        ns = np.where(np.arange(C)[None, :] < self.cores[:, None], 0.0, np.inf).astype(f)
        arE = np.arange(E)
        first = np.full(E, np.inf, dtype=f)
        last = np.zeros(E, dtype=f)
        dyn = np.zeros(E, dtype=f)
        for i in range(len(tasks)):
            k = ns.argmin(axis=1)
            start = np.maximum(np.maximum(ns[arE, k], ready), nbs[i])
            end = start + rtT[i]
            ns[arE, k] = end
            np.minimum(first, start, out=first)
            np.maximum(last, end, out=last)
            dyn += enT[i]
        c = np.maximum(last, f.type(0.0))
        e = add.astype(f) + np.where(self.bt, self.idle * (last - first) + self.su,
                                     self.idle * c) + dyn
        return max(e.max(), f.type(1e-9)), max(c.max(), f.type(1e-9))
