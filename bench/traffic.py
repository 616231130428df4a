"""The one generator every traffic mix under ``bench/traffic/`` is read by.

A mix is a JSON file of parameters.  ``kind`` picks the loop:

- ``closed_batch``: windows of ``window_tasks`` tasks go back to back
  through ``submit_many``; each waits for the one before it.
- ``open_poisson``: ``rate_per_s`` arrivals a second on the wall clock,
  sent on schedule whether or not the system keeps up.  The run's
  ``rate * seconds`` arrival times are uniform order statistics on the
  window: a Poisson process conditioned on its count, so every seed
  offers the same amount of work in another order.

``classes`` lists the task classes, each with a ``weight``, a function
``popularity`` (``uniform``, or ``zipf`` with exponent ``s`` over a
``rank_order``) and its ``inputs`` (``src`` endpoint index, ``n_files``,
``bytes``, ``shared``).  Every seed gets the same count of each (class,
function) pair — largest remainders of the weights — in a seeded order.
"""
from __future__ import annotations

import json
import pathlib

import numpy as np


def load(path: str | pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def popularity(spec: dict, functions: list[str]) -> np.ndarray:
    """Probability of each function of the deployment under ``spec``."""
    kind = spec["kind"]
    if kind == "uniform":
        return np.full(len(functions), 1.0 / len(functions))
    if kind == "zipf":
        order = spec["rank_order"]
        if sorted(order) != sorted(functions):
            raise ValueError(f"zipf rank order {order} is not the deployment's "
                             f"functions {functions}")
        w = np.array([1.0 / (order.index(fn) + 1) ** spec["s"] for fn in functions])
        return w / w.sum()
    raise ValueError(f"unknown popularity kind {kind!r}")


def _pairs(traffic: dict, functions: list[str], names: list[str]):
    """``[(probability, fn, inputs)]`` over every (class, function)."""
    classes = traffic["classes"]
    total = sum(c["weight"] for c in classes)
    out = []
    for c in classes:
        inputs = tuple((names[i["src"]], int(i["n_files"]), float(i["bytes"]),
                        bool(i["shared"])) for i in c["inputs"])
        for fn, p in zip(functions, popularity(c["popularity"], functions)):
            out.append((c["weight"] / total * p, fn, inputs))
    return out


def fixed_mix(n: int, probs: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    """``n`` draws with the largest-remainder count of each outcome, in a
    seeded order."""
    raw = probs * n
    counts = np.floor(raw).astype(int)
    rest = n - counts.sum()
    counts[np.argsort(-(raw - counts), kind="stable")[:rest]] += 1
    draws = np.repeat(np.arange(len(probs)), counts)
    rng.shuffle(draws)
    return draws


def tasks(traffic: dict, functions: list[str], names: list[str], n: int,
          rng: np.random.Generator, prefix: str) -> list[tuple[str, str, tuple]]:
    """``n`` tasks ``(id, fn, inputs)`` drawn from the mix."""
    pairs = _pairs(traffic, functions, names)
    draws = fixed_mix(n, np.array([p for p, _, _ in pairs]), rng)
    return [(f"{prefix}{i}", pairs[d][1], pairs[d][2]) for i, d in enumerate(draws)]


def arrivals(rate: float, seconds: float, rng: np.random.Generator) -> np.ndarray:
    """Sorted arrival offsets: ``round(rate * seconds)`` uniform order
    statistics on ``[0, seconds)``."""
    return np.sort(rng.uniform(0.0, seconds, size=int(round(rate * seconds))))
