"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode
+ hypothesis property tests on the flash-attention invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.kernels.decode_attention.kernel import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.selective_scan.kernel import selective_scan
from repro.kernels.selective_scan.ref import selective_scan_ref
from repro.kernels.ssd.kernel import ssd
from repro.kernels.ssd.ref import ssd_preweighted_ref, ssd_ref

RNG = jax.random.PRNGKey(0)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else dict(atol=2e-5, rtol=2e-5)


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------
FLASH_CASES = [
    # (b, sq, sk, h, kv, d, causal, dtype)
    (2, 128, 128, 4, 2, 64, True, jnp.float32),
    (1, 256, 256, 8, 8, 128, True, jnp.float32),
    (2, 128, 256, 2, 1, 64, False, jnp.float32),
    (1, 128, 128, 4, 4, 128, True, jnp.bfloat16),
    (1, 384, 384, 6, 6, 64, True, jnp.float32),   # whisper-like MHA
    (2, 128, 128, 4, 1, 80, True, jnp.float32),   # zamba-like head_dim 80
]


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_attention_matches_ref(case):
    b, sq, sk, h, kv, d, causal, dtype = case
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (b, sq, h, d), dtype)
    k = jax.random.normal(ks[1], (b, sk, kv, d), dtype)
    v = jax.random.normal(ks[2], (b, sk, kv, d), dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64, interpret=True)
    ref = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **_tol(dtype)
    )


@settings(max_examples=10, deadline=None)
@given(
    bq=st.sampled_from([32, 64, 128]),
    bk=st.sampled_from([32, 64]),
    mult=st.integers(1, 3),
    h=st.sampled_from([2, 4]),
    causal=st.booleans(),
)
def test_flash_attention_block_invariance(bq, bk, mult, h, causal):
    """Output must not depend on block decomposition (property)."""
    sq = bq * mult
    sk = max(128, sq)  # causal sq > sk leaves fully-masked rows (undefined)
    ks = jax.random.split(jax.random.PRNGKey(bq * 7 + bk), 3)
    q = jax.random.normal(ks[0], (1, sq, h, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, sk, h, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, sk, h, 64), jnp.float32)
    a = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=min(bk, sk), interpret=True)
    b_ = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=3e-5, rtol=3e-5)


# --------------------------------------------------------------------------
# decode attention
# --------------------------------------------------------------------------
DECODE_CASES = [
    (2, 256, 4, 2, 64, 64, jnp.float32),
    (1, 512, 8, 1, 128, 128, jnp.float32),
    (3, 128, 4, 4, 64, 64, jnp.bfloat16),
    (1, 256, 8, 8, 80, 128, jnp.float32),
]


@pytest.mark.parametrize("case", DECODE_CASES)
def test_decode_attention_matches_ref(case):
    b, S, h, kv, d, bk, dtype = case
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (b, 1, h, d), dtype)
    kc = jax.random.normal(ks[1], (b, S, kv, d), dtype)
    vc = jax.random.normal(ks[2], (b, S, kv, d), dtype)
    lens = jnp.arange(1, b + 1) * (S // (b + 1)) + 3
    out = decode_attention(q, kc, vc, lens.astype(jnp.int32), block_k=bk, interpret=True)
    ref = decode_attention_ref(q, kc, vc, lens)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **_tol(dtype)
    )


def test_decode_attention_ignores_stale_cache_tail():
    """Garbage past cache_len must not affect the result (masking property)."""
    b, S, h, d = 1, 128, 2, 64
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (b, 1, h, d))
    kc = jax.random.normal(ks[1], (b, S, h, d))
    vc = jax.random.normal(ks[2], (b, S, h, d))
    lens = jnp.array([40], jnp.int32)
    a = decode_attention(q, kc, vc, lens, block_k=32, interpret=True)
    kc2 = kc.at[:, 40:].set(1e4)
    vc2 = vc.at[:, 40:].set(-1e4)
    b_ = decode_attention(q, kc2, vc2, lens, block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-6)


# --------------------------------------------------------------------------
# selective scan (mamba1)
# --------------------------------------------------------------------------
SCAN_CASES = [
    (2, 64, 128, 16, 64, 32, jnp.float32),
    (1, 128, 64, 8, 64, 64, jnp.float32),
    (1, 64, 256, 16, 128, 32, jnp.float32),
]


@pytest.mark.parametrize("case", SCAN_CASES)
def test_selective_scan_matches_ref(case):
    b, L, d, n, bd, ch, dtype = case
    ks = jax.random.split(RNG, 6)
    x = jax.random.normal(ks[0], (b, L, d), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, L, d)) * 0.5 - 1)
    A = -jnp.exp(jax.random.normal(ks[2], (d, n)) * 0.3)
    B = jax.random.normal(ks[3], (b, L, n))
    C = jax.random.normal(ks[4], (b, L, n))
    D = jax.random.normal(ks[5], (d,))
    out = selective_scan(x, dt, A, B, C, D, block_d=bd, chunk=ch, interpret=True)
    ref = selective_scan_ref(x, dt, A, B, C, D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4, rtol=1e-4)


def test_selective_scan_chunk_invariance():
    """Chunk size must not change the result (state carry property)."""
    b, L, d, n = 1, 128, 64, 8
    ks = jax.random.split(RNG, 6)
    x = jax.random.normal(ks[0], (b, L, d))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, L, d)) * 0.3)
    A = -jnp.exp(jax.random.normal(ks[2], (d, n)) * 0.3)
    B = jax.random.normal(ks[3], (b, L, n))
    C = jax.random.normal(ks[4], (b, L, n))
    D = jnp.zeros((d,))
    o32 = selective_scan(x, dt, A, B, C, D, block_d=64, chunk=32, interpret=True)
    o128 = selective_scan(x, dt, A, B, C, D, block_d=64, chunk=128, interpret=True)
    np.testing.assert_allclose(np.asarray(o32), np.asarray(o128), atol=1e-5)


# --------------------------------------------------------------------------
# ssd (mamba2)
# --------------------------------------------------------------------------
SSD_CASES = [
    (2, 64, 4, 64, 32, 32, jnp.float32),
    (1, 128, 2, 64, 64, 64, jnp.float32),
    (1, 128, 8, 128, 64, 32, jnp.float32),
]


@pytest.mark.parametrize("case", SSD_CASES)
def test_ssd_matches_ref(case):
    b, L, nh, hd, n, ch, dtype = case
    ks = jax.random.split(RNG, 5)
    xh = jax.random.normal(ks[0], (b, L, nh, hd), dtype)
    dt = jax.random.normal(ks[1], (b, L, nh)) * 0.5
    A_log = jax.random.normal(ks[2], (nh,)) * 0.3
    B = jax.random.normal(ks[3], (b, L, n))
    C = jax.random.normal(ks[4], (b, L, n))
    dtf = jax.nn.softplus(dt)
    A = -jnp.exp(A_log)
    y, S = ssd(xh * dtf[..., None], dtf * A, B, C, chunk=ch, interpret=True)
    yr, Sr = ssd_ref(xh, dt, A_log, B, C)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=5e-4, rtol=5e-3)
    np.testing.assert_allclose(np.asarray(S), np.asarray(Sr), atol=5e-4, rtol=5e-3)


def test_ssd_xla_chunked_matches_sequential():
    """models/ssm.ssd_chunked (the XLA path) vs the sequential oracle."""
    from repro.models.ssm import ssd_chunked

    b, L, nh, hd, n = 2, 96, 4, 32, 16
    ks = jax.random.split(RNG, 5)
    xh = jax.random.normal(ks[0], (b, L, nh, hd))
    dt = jax.random.normal(ks[1], (b, L, nh)) * 0.5
    A_log = jax.random.normal(ks[2], (nh,)) * 0.3
    B = jax.random.normal(ks[3], (b, L, n))
    C = jax.random.normal(ks[4], (b, L, n))
    y, S = ssd_chunked(xh, dt, A_log, B, C, chunk=32)
    yr, Sr = ssd_ref(xh, dt, A_log, B, C)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=5e-4, rtol=5e-3)
    np.testing.assert_allclose(np.asarray(S), np.asarray(Sr), atol=5e-4, rtol=5e-3)


def test_preweighted_ref_consistent():
    b, L, nh, hd, n = 1, 32, 2, 16, 8
    ks = jax.random.split(RNG, 5)
    xh = jax.random.normal(ks[0], (b, L, nh, hd))
    dt = jax.random.normal(ks[1], (b, L, nh)) * 0.5
    A_log = jax.random.normal(ks[2], (nh,)) * 0.3
    B = jax.random.normal(ks[3], (b, L, n))
    C = jax.random.normal(ks[4], (b, L, n))
    dtf = jax.nn.softplus(dt)
    y1, S1 = ssd_preweighted_ref(xh * dtf[..., None], dtf * -jnp.exp(A_log), B, C)
    y2, S2 = ssd_ref(xh, dt, A_log, B, C)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5)


# ---------------------------------------------------------------------------
# placement score+argmin (the engine="jax" building blocks)
# ---------------------------------------------------------------------------


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


@pytest.mark.parametrize("seed,n", [(0, 4), (1, 12), (2, 128), (3, 200)])
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
