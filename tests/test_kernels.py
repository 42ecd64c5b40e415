"""Per-kernel shape/dtype sweeps: interpret-mode Pallas vs jnp oracles."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.leaf_search.kernel import leaf_search
from repro.kernels.leaf_search.ref import leaf_search_ref
from repro.kernels.rwkv_scan.kernel import wkv6
from repro.kernels.rwkv_scan.ref import wkv6_ref

RNG = np.random.default_rng(7)


@pytest.mark.parametrize("b,h,kv,s,hd,causal,dtype", [
    (2, 4, 2, 256, 64, True, jnp.float32),
    (1, 8, 8, 128, 128, False, jnp.float32),
    (2, 2, 1, 512, 32, True, jnp.float32),
    (1, 4, 4, 256, 64, True, jnp.bfloat16),
    (3, 6, 2, 128, 64, False, jnp.float32),
])
def test_flash_attention(b, h, kv, s, hd, causal, dtype):
    q = jnp.asarray(RNG.standard_normal((b, h, s, hd)), dtype)
    k = jnp.asarray(RNG.standard_normal((b, kv, s, hd)), dtype)
    v = jnp.asarray(RNG.standard_normal((b, kv, s, hd)), dtype)
    out = flash_attention(q, k, v, causal=causal, bq=64, bk=64,
                          interpret=True)
    ref = attention_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("b,f,bt", [(256, 8, 64), (512, 16, 128),
                                    (128, 32, 128), (256, 64, 256),
                                    (4096, 16, 256)])
def test_leaf_search(b, f, bt):
    keys = np.stack([RNG.choice(9_000, f, replace=False)
                     for _ in range(b)]).astype(np.int32)
    vals = RNG.integers(0, 1 << 20, (b, f)).astype(np.int32)
    q = np.where(RNG.random(b) < 0.5,
                 keys[np.arange(b), RNG.integers(0, f, b)],
                 20_000 + np.arange(b)).astype(np.int32)
    fev = RNG.integers(0, 4, (b, f)).astype(np.int32)
    rev = fev.copy()
    rev[: b // 8] += 1
    fnv = RNG.integers(0, 4, b).astype(np.int32)
    rnv = fnv.copy()
    rnv[b // 8: b // 4] += 1
    free = np.zeros(b, np.int32)
    free[b // 4: b // 4 + 4] = 1
    args = [jnp.asarray(a) for a in (q, keys, vals, fev, rev, fnv, rnv,
                                     free)]
    got = leaf_search(*args, bt=bt, interpret=True)
    want = leaf_search_ref(*args)
    for g, w in zip(got, want):
        assert (np.asarray(g) == np.asarray(w)).all()


@pytest.mark.parametrize("b,h,t,n,bt,dtype", [
    (2, 3, 256, 32, 64, jnp.float32),
    (1, 2, 128, 64, 128, jnp.float32),
    (2, 1, 512, 16, 64, jnp.float32),
    (1, 2, 128, 64, 32, jnp.bfloat16),
])
def test_wkv6(b, h, t, n, bt, dtype):
    r = jnp.asarray(RNG.standard_normal((b, h, t, n)), dtype)
    k = jnp.asarray(RNG.standard_normal((b, h, t, n)), dtype)
    v = jnp.asarray(RNG.standard_normal((b, h, t, n)), dtype)
    w = jnp.asarray(RNG.random((b, h, t, n)) * 0.5 + 0.45, dtype)
    u = jnp.asarray(RNG.standard_normal((h, n)), dtype)
    out = wkv6(r, k, v, w, u, bt=bt, interpret=True)
    ref = wkv6_ref(r, k, v, w, u)
    tol = 1e-4 if dtype == jnp.float32 else 0.15
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=tol, rtol=tol)


def test_chunked_sdpa_matches_naive():
    """The jnp flash twin used by the perf configs must equal naive SDPA."""
    from repro.models.attention import _sdpa_chunked, _sdpa_naive
    q = jnp.asarray(RNG.standard_normal((2, 256, 4, 32)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((2, 256, 2, 32)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((2, 256, 2, 32)), jnp.float32)
    for causal in (True, False):
        a = _sdpa_chunked(q, k, v, causal=causal, chunk=64)
        b = _sdpa_naive(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-5)
