"""Chip benchmark of GreenFaaS placement through ``OnlineEngine``.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` and prints one JSON result line.
Everything a cell is made of is found by name: its deployment under
``bench/configs/``, its traffic mix under ``bench/traffic/``, the limits
of its correctness check under ``bench/limits/``, the plain reference its
deployment names under ``bench/references/`` and each per-layer metric's
reader under ``bench/metrics/``.
"""
