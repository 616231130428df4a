"""chip_smoke.py and the compile-cache helper, rehearsed on the host.

The smoke refuses any device but a TPU; these tests point its device
check at the CPU and cut the stream to a few small windows, so the whole
path (auto -> jax resolution, device/soa window counters, per-window
comparison with the soa reference, the JSON last line) runs here.
"""
import importlib.util
import json
import pathlib

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro import compile_cache
from repro.core import scheduler as sched

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)
    compilation_cache.reset_cache()


def test_smoke_refuses_the_cpu(smoke, capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr()
    assert out.out == ""
    assert "needs a tpu device" in out.err


def test_smoke_end_to_end_with_device_check_on_cpu(
        smoke, capsys, monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setattr(smoke, "PLATFORM", "cpu")
    monkeypatch.setattr(smoke, "REPLICAS", 2)        # 8 endpoints
    monkeypatch.setattr(smoke, "WINDOW", 128)
    monkeypatch.setattr(smoke, "N_WINDOWS", 3)
    monkeypatch.setattr(sched, "AUTO_JAX_MIN_CELLS", 8 * 128)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert smoke.main(["--seed", "3"]) == 0
    lines = capsys.readouterr().out.splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}
    text = "\n".join(lines)
    assert "engine auto resolved to: jax" in text
    assert "device windows: 3, handed to soa: 0" in text
    assert "assignments identical to soa: 3/3 windows" in text
    # on the host the fused scan is bitwise-equal to soa
    assert "windows bitwise-equal to soa: 3/3" in text
    assert "window 0 (compile)" in text and "window 2 (warm)" in text


def test_compile_cache_dir_from_env(monkeypatch, tmp_path,
                                    restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_compile_cache_default_dir_is_ignored_checkout_path(
        monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    ignored = (ROOT / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored
