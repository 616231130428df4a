"""A tiny copy of the benchmark's data for runs on the host CPU.

The benchmark refuses any device but the chip; these helpers point its
device check at the CPU from the test, and shrink the fleet and the
traffic so the whole path — warm-up, the measured window, the metric
readers, the check against the plain reference — runs in seconds.
"""
import json
import pathlib
import shutil
import sys

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def _edit(path, **kw):
    d = json.loads(path.read_text())
    d.update(kw)
    path.write_text(json.dumps(d))


def tiny_root(dst: pathlib.Path, replicas=2, window_tasks=64, rate=400.0,
              stream_batch=32) -> pathlib.Path:
    """``BENCHMARK.json`` and ``bench/`` copied under ``dst``, cut to
    ``replicas`` x 4 endpoints and small windows; the CPU gets a peak."""
    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    for c in ("table1x8-sebs", "table1x64-sebs"):
        _edit(dst / "bench" / "configs" / f"{c}.json", replicas=replicas)
    _edit(dst / "bench" / "traffic" / "batch8k-uniform.json",
          window_tasks=window_tasks, max_batch=window_tasks, min_window_s=0.2,
          trace_seconds=0.5)
    _edit(dst / "bench" / "traffic" / "stream-zipf.json", rate_per_s=rate,
          max_batch=stream_batch, warmup_seconds=0.3, trace_seconds=0.6)
    peaks = json.loads((dst / "bench" / "peaks.json").read_text())
    peaks["cpu"] = peaks["TPU v5 lite"]
    (dst / "bench" / "peaks.json").write_text(json.dumps(peaks))
    return dst


@pytest.fixture
def on_cpu(monkeypatch, tmp_path):
    """The harness's device check pointed at the CPU, every window on the
    device path, and JAX's compile-cache settings restored afterwards."""
    from bench import harness
    from repro.core import scheduler as sched

    monkeypatch.setattr(harness, "PLATFORM", "cpu")
    monkeypatch.setattr(sched, "AUTO_JAX_MIN_CELLS", 1)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    prev = (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs)
    yield tiny_root(tmp_path / "root")
    jax.config.update("jax_compilation_cache_dir", prev[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", prev[1])
    compilation_cache.reset_cache()
