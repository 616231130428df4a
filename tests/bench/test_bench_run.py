"""The benchmark's command end to end on the host CPU, at tiny size.

The device check is pointed at the CPU by the test (the program has no
option for that); every other step of a run is the one the chip runs.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

from _bench_tiny import ROOT, on_cpu  # noqa: F401
from bench import harness

E2E_ORDER = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def _run(root, workload, trace, capsys, seconds=1.0):
    rc = harness.main(["--workload", workload, "--seed", str(2**31 + 12345),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      root=root)
    out = capsys.readouterr()
    assert rc == 0, out.err[-2000:]
    last = out.out.strip().splitlines()[-1]
    return json.loads(last), out.err


def test_refuses_a_device_that_is_not_the_chip(capsys):
    assert harness.main(["--workload", "sebs32-batch8k", "--seed", "1",
                         "--seconds", "1", "--trace", "0"]) != 0
    out = capsys.readouterr()
    assert out.out == ""
    assert "needs 1 tpu chip(s)" in out.err


def test_refuses_a_checkout_without_the_program(tmp_path):
    """Only BENCHMARK.json and the benchmark's paths: no program to run."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    for p in spec["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    code = ("import sys; sys.path.insert(0, '.'); from bench import harness; "
            "harness.PLATFORM = 'cpu'; sys.exit(harness.main(["
            "'--workload', 'sebs32-batch8k', '--seed', '1', '--seconds', '1', "
            "'--trace', '0']))")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "No module named 'repro'" in proc.stderr


@pytest.mark.parametrize("workload, metrics", [
    ("sebs32-batch8k", {"batch_tasks_per_s", "setup_s"}),
    ("sebs256-stream", {"stream_p95_ms", "setup_s"}),
])
def test_untraced_run_prints_the_end_to_end_line(on_cpu, capsys, workload, metrics):
    res, err = _run(on_cpu, workload, 0, capsys)
    assert list(res) == E2E_ORDER
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == metrics
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["device"] == {"platform": "cpu", "kind": "cpu", "count": 1,
                             "memory_peak_bytes": 0}
    assert set(res["checks"]) == {"composition_errors", "placement_mismatches",
                                  "timeline_gap", "window_gap"}
    assert res["checks"]["placement_mismatches"]["value"] == 0
    # each compared number beside its limit closes standard error
    tail = err.strip().splitlines()[-4:]
    assert [t.split(":")[0] for t in tail] == [f"check {k}" for k in res["checks"]]
    assert "engine auto -> jax" in err


def test_traced_run_prints_the_per_layer_line(on_cpu, capsys):
    res, err = _run(on_cpu, "sebs32-batch8k", 1, capsys)
    assert list(res) == ["correct", "attempted", "failed", "metrics", "device",
                         "breakdown", "checks"]
    assert res["correct"] is True
    got = set(res["metrics"])
    # host spans and the compile counter read on any device; the CPU has no
    # device plane, so the scan's device time and roofline find nothing
    assert {"engine_ms_per_window.batch", "host_prep_ms_per_window.batch",
            "device_call_ms_per_window.batch", "compiles_in_window.batch",
            "device_idle_pct.batch"} == got
    assert res["metrics"]["compiles_in_window.batch"]["value"] == 0
    assert res["device"]["window_s"] > 0 and res["device"]["busy_s"] == 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert "trace: read in" in err
