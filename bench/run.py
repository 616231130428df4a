"""Run one benchmark cell and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Needs the chips the cell names in ``BENCHMARK.json``; on any other
device it exits non-zero and prints no result.
"""
import pathlib
import sys
import time

_T_PROCESS = time.perf_counter()
_ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (str(_ROOT), str(_ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_process=_T_PROCESS))
