"""A deployment file -> its fleet, function profiles and profile store.

The file under ``bench/configs/`` holds the Table-I machines, the replica
rule, the SeBS profiles and the network model as numbers, so the
yardstick does not move when the program's own copies change.  Only the
program's input types (``EndpointSpec``, ``TaskProfileStore``) are built
from it here; the plain reference reads the same file on its own.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib


@dataclasses.dataclass(frozen=True)
class Machine:
    """One endpoint of the fleet, as the deployment file describes it."""
    name: str
    base: str
    replica: int
    cores: int
    idle_power_w: float
    tdp_w: float
    queue_delay_s: float
    has_batch_scheduler: bool
    perf_scale: float
    hops: dict


def load(path: str | pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def machines(cfg: dict) -> list[Machine]:
    """The fleet: every Table-I machine replicated ``replicas`` times,
    replica-major (``desktop_0, theta_0, ic_0, faster_0, desktop_1, ...``).
    One replica keeps the machines' own names and hop tables; replicas
    drift linearly and fall back to the default hop count."""
    reps = int(cfg["replicas"])
    drift = cfg["replica_drift"]
    out = []
    for k in range(reps):
        for m in cfg["endpoints"]:
            one = reps <= 1
            out.append(Machine(
                name=m["name"] if one else f"{m['name']}_{k}",
                base=m["name"],
                replica=k,
                cores=int(m["cores"]),
                idle_power_w=m["idle_power_w"] * (1.0 + drift["idle_power_w"] * k),
                tdp_w=m["tdp_w"],
                queue_delay_s=m["queue_delay_s"] * (1.0 + drift["queue_delay_s"] * k),
                has_batch_scheduler=bool(m["has_batch_scheduler"]),
                perf_scale=m["perf_scale"] * (1.0 + drift["perf_scale"] * k),
                hops=dict(m["hops"]) if one else {},
            ))
    return out


def profiles(cfg: dict, fleet: list[Machine]) -> dict[str, dict[str, tuple[float, float]]]:
    """``{fn: {endpoint: (runtime_s, energy_j)}}``: replica k runs the base
    machine's runtime divided by ``1 + runtime_divisor * k`` at the base
    machine's dynamic power."""
    d = cfg["replica_drift"]["runtime_divisor"]
    table = cfg["profiles_runtime_s_dynamic_w"]
    out = {}
    for fn in cfg["functions"]:
        out[fn] = {}
        for m in fleet:
            rt, w = table[fn][m.base]
            rt = rt / (1.0 + d * m.replica)
            out[fn][m.name] = (rt, rt * w)
    return out


def endpoint_specs(fleet: list[Machine]):
    """The program's ``EndpointSpec`` for every machine."""
    from repro.core.endpoint import EndpointSpec

    return [EndpointSpec(m.name, cores=m.cores, idle_power_w=m.idle_power_w,
                         tdp_w=m.tdp_w, queue_delay_s=m.queue_delay_s,
                         has_batch_scheduler=m.has_batch_scheduler,
                         perf_scale=m.perf_scale, hops=dict(m.hops))
            for m in fleet]


def seeded_store(cfg: dict, eps, profs):
    """The program's profile store with ``store_records`` records of every
    (function, endpoint) profile."""
    from repro.core.predictor import TaskProfileStore

    store = TaskProfileStore(eps)
    for fn, by_ep in profs.items():
        for ep, (rt, e) in by_ep.items():
            for _ in range(int(cfg["store_records"])):
                store.record(fn, ep, rt, e)
    return store
