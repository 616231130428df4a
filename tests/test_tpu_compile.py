"""Compile the placement hot path for a described TPU v5e chip.

No chip is attached: the TPU compiler builds the program for a chip that
is only described, which raises whatever the chip's compiler would raise.
The topology is described inside a module fixture (never at import), and
the persistent compile cache is off around these compiles — a cached
entry for a described chip cannot be read back without one.
"""
import os

import jax
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.core.endpoint import scaled_testbed
from repro.core.predictor import TaskProfileStore
from repro.core.scheduler import TaskSpec, mhra
from repro.core.testbed import BASE_PROFILES, SEBS_FUNCTIONS
from repro.core.transfer import TransferModel
from repro.kernels.placement import kernel as pkernel
from repro.kernels.placement import ops as pops

#: the chip smoke's window: 32 endpoints (scaled_testbed(8), up to 64-core
#: slots) and 8192 single-input SeBS tasks
SMOKE_REPLICAS = 8
SMOKE_WINDOW = 8192


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # the TPU compiler otherwise writes its logs under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


class _Captured(Exception):
    pass


def _smoke_window_args(monkeypatch):
    """The real ``greedy_window`` arguments of one smoke window, captured
    from the host prep of ``mhra(engine="jax")`` before anything runs."""
    eps = scaled_testbed(SMOKE_REPLICAS)
    store = TaskProfileStore(eps)
    for fn in SEBS_FUNCTIONS:
        for ep in eps:
            base, _, k = ep.name.partition("_")
            rt, w = BASE_PROFILES[fn][base]
            rt = rt / (1.0 + 0.02 * int(k or 0))
            store.record(fn, ep.name, rt, rt * w)
    inputs = ((eps[0].name, 1, 200e6, True),)
    tasks = [TaskSpec(id=f"t{i}", fn=SEBS_FUNCTIONS[i % len(SEBS_FUNCTIONS)],
                      inputs=inputs) for i in range(SMOKE_WINDOW)]
    got = {}

    def capture(n_ep, consts, init, xs):
        got.update(n_ep=n_ep, consts=consts, init=init, xs=xs)
        raise _Captured

    monkeypatch.setattr(pops, "greedy_window", capture)
    with pytest.raises(_Captured):
        mhra(tasks, eps, store, TransferModel(eps), alpha=0.5, engine="jax")
    return got


def test_fused_scan_compiles_for_v5e(one_chip, no_persistent_cache,
                                     monkeypatch):
    """The default (xla) placement scan at the smoke's shapes: 4
    heuristics x 8192 steps over 32 endpoint lanes with 64 core slots."""
    args = _smoke_window_args(monkeypatch)
    assert args["init"]["slots"].shape == (4, 32, 64)
    assert args["xs"]["ti"].shape == (4, SMOKE_WINDOW)
    with jax.enable_x64(True):
        def spec(a):
            return jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                        sharding=one_chip)

        consts = jax.tree_util.tree_map(spec, args["consts"])
        init = jax.tree_util.tree_map(spec,
                                      pops._as_tuple_carry(args["init"]))
        xs = jax.tree_util.tree_map(spec, args["xs"])
        compiled = pops._greedy_scan.lower(
            consts, init, xs, n_ep=args["n_ep"], use_kernel=False,
        ).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    mem = compiled.memory_analysis()
    # a few MB of arguments and temporaries: far inside one chip's 16 GB
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("dtype, refusal", [
    # Mosaic has no 64-bit scalars for the SMEM operand
    (np.float64, "32-bit element types can be converted to scalars"),
    # in float32 the (1, 1) running-min outputs are scalar VMEM stores
    (np.float32, "Cannot store scalars to VMEM"),
])
def test_pallas_score_kernel_refused(dtype, refusal, one_chip,
                                     no_persistent_cache):
    """Why ``REPRO_PLACEMENT_BACKEND=pallas`` raises: the chip's compiler
    refuses the placement kernel in either float width."""
    with jax.enable_x64(dtype == np.float64):
        lanes = pkernel.LANE_TILE
        vec = jax.ShapeDtypeStruct((lanes,), dtype, sharding=one_chip)
        scalars = jax.ShapeDtypeStruct((6,), dtype, sharding=one_chip)
        fn = jax.jit(pkernel.score_fleet)
        with pytest.raises(Exception, match=refusal):
            fn.lower(scalars, vec, vec, vec, vec, vec, vec, vec).compile()
