"""repro — GreenFaaS (CS.DC 2024) on JAX/TPU.

Public API:
    repro.core       — the paper: monitoring, attribution, (Cluster) MHRA
                       placement, the online engine and its policies
    repro.workloads  — reproducible workload and trace generators
    repro.kernels    — the device placement path: the fused float64
                       ``lax.scan`` greedy over a window (``placement``)
"""
__version__ = "1.0.0"
