"""Work of the placement scan, counted from the problem's true shapes.

One score cell is one candidate endpoint scored for one task under one
ordering heuristic.  Its arithmetic, as the plain reference writes it:
the task's start (2 max), end (1 add), the endpoint's new first/last
start (2 min/max) and dynamic energy (1 add), its energy term
``(last - first) * idle + startup + dynamic`` (4), the new makespan
(1 max), the fleet's energy ``transfer + staging + (sum - own) + new +
idle_on * makespan`` (6), the objective ``a * E + b * C`` (3) and its
comparison in the argmin (1): 21 operations.  It reads eleven float64
registers of the endpoint: core minimum, input-ready time, runtime,
energy, first, last, dynamic energy, idle power, start-up energy,
staging energy and energy term: 88 bytes.  Padding, bucket sizes and the
precision the program emulates do not enter the count.
"""
from __future__ import annotations

SCAN_FLOPS_PER_CELL = 21
SCAN_BYTES_PER_CELL = 88


def scan_work(heuristics: int, tasks: int, endpoints: int) -> tuple[float, float]:
    """(operations, bytes) of placing ``tasks`` on ``endpoints`` under
    ``heuristics`` orderings."""
    cells = heuristics * tasks * endpoints
    return float(cells * SCAN_FLOPS_PER_CELL), float(cells * SCAN_BYTES_PER_CELL)


def roofline_s(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_ops = flops / peak["flops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_mem, "bytes") if t_mem >= t_ops else (t_ops, "operations")
