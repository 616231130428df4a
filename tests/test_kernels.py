"""The placement score+argmin pass (the ``engine="jax"`` building blocks):
the NumPy oracle, the fused jnp pass and the Pallas kernel in interpret
mode, against each other and against NumPy's own reductions."""
import numpy as np
import pytest


def _placement_case(seed, n):
    rng = np.random.default_rng(seed)
    kw = dict(
        e_base=rng.uniform(0.0, 5e4, n),
        nl=rng.uniform(0.0, 300.0, n),
        g_base=rng.uniform(0.0, 10.0, n),
        lk=rng.uniform(0.0, 3.0, n),
        fw=rng.uniform(0.0, 2.0, n),
        wt=rng.uniform(0.0, 1.0, n),
        alive=rng.random(n) < 0.8,
        c_cur=float(rng.uniform(0.0, 200.0)),
        idle_on_sum=float(rng.uniform(0.0, 500.0)),
        a1=float(rng.uniform(0.0, 1e-4)),
        b1=float(rng.uniform(0.0, 1e-2)),
        g1=float(rng.uniform(0.0, 1.0)),
        w_idle_on=float(rng.uniform(0.0, 1e-3)),
    )
    kw["alive"][int(rng.integers(n))] = True   # never a dead fleet
    return kw


@pytest.mark.parametrize("seed,n", [
    (0, 4), (1, 12), (2, 128), (3, 200),
    # either side of the 128-lane tile and of the power-of-two buckets
    (4, 1), (5, 127), (6, 129), (7, 256), (8, 257),
])
def test_placement_score_backends_bitwise(seed, n, monkeypatch):
    """ref (NumPy oracle) and xla produce bitwise-equal objectives and the
    identical first-min argmin.  The pallas-interpret leg is compiled as
    one program, where XLA:CPU may contract mul+add chains into FMAs —
    its scores are held to 1-ulp instead (the engine only consumes its
    *argmin*; every committed register is recomputed from the bitwise
    mirrors, so engine parity is unaffected)."""
    from repro.kernels.placement import ops as pops
    kw = _placement_case(seed, n)
    outs = {}
    for be in ("ref", "xla", "pallas_interpret"):
        monkeypatch.setenv("REPRO_PLACEMENT_BACKEND", be)
        obj, idx = pops.score_fleet(**kw)
        outs[be] = (np.asarray(obj), int(idx))
    np.testing.assert_array_equal(outs["ref"][0], outs["xla"][0])
    assert outs["ref"][1] == outs["xla"][1]
    np.testing.assert_allclose(outs["ref"][0], outs["pallas_interpret"][0],
                               rtol=5e-15)
    assert outs["ref"][1] == outs["pallas_interpret"][1]
    # first-min tie-breaking matches np.argmin on the masked objective
    masked = np.where(kw["alive"], outs["ref"][0], np.inf)
    assert outs["ref"][1] == int(np.argmin(masked))


def test_placement_score_first_min_ties():
    """Equal scores across lanes (and across Pallas tiles) resolve to the
    lowest index, like np.argmin."""
    from repro.kernels.placement import ops as pops
    n = 256   # two 128-lane tiles
    kw = _placement_case(7, n)
    for k in ("e_base", "nl", "g_base", "lk", "fw", "wt"):
        kw[k] = np.zeros(n)
    kw["alive"] = np.ones(n, dtype=bool)
    import os
    prev = os.environ.get("REPRO_PLACEMENT_BACKEND")
    for be in ("ref", "xla", "pallas_interpret"):
        os.environ["REPRO_PLACEMENT_BACKEND"] = be
        try:
            _, idx = pops.score_fleet(**kw)
            assert int(idx) == 0, be
        finally:
            if prev is None:
                os.environ.pop("REPRO_PLACEMENT_BACKEND", None)
            else:
                os.environ["REPRO_PLACEMENT_BACKEND"] = prev


@pytest.mark.parametrize("n", [0, 1, 5, 7, 8, 9, 64, 127, 128, 129, 1000])
def test_placement_pairwise_sum_matches_numpy_bitwise(n):
    from repro.kernels.placement.ref import pairwise_sum
    rng = np.random.default_rng(n)
    x = rng.uniform(-1e6, 1e6, max(n, 1) + 3)
    assert pairwise_sum(x, n) == float(np.sum(x[:n]))
    assert pairwise_sum(x, n, base=2) == float(np.sum(x[2:2 + n]))


def test_placement_shape_buckets():
    from repro.kernels.placement import ops as pops
    assert [pops.bucket_pow2(v) for v in (1, 2, 3, 9, 64, 65)] == \
        [1, 2, 4, 16, 64, 128]
    assert pops.bucket_pow2(3, minimum=8) == 8
