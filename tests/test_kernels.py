"""Per-kernel shape/dtype sweeps: interpret-mode Pallas vs jnp oracles."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.leaf_search.kernel import leaf_search
from repro.kernels.leaf_search.ref import leaf_search_ref
from repro.kernels.pool_rows.kernel import pool_rows, stored_transposed
from repro.kernels.pool_rows.ref import pool_rows_ref
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


@pytest.mark.parametrize("n,f,b", [(1024, 16, 100), (4096, 58, 300),
                                   (512, 8, 37), (2048, 58, 512)])
def test_pool_rows(n, f, b):
    """Rows of an int32 and a uint8 column read in place equal the plain
    gather: the first row, the last (park) row, repeated ids, and a batch
    that is no multiple of the kernel's lanes."""
    assert stored_transposed((n, f)) and n % 128 == 0   # the kernel's path
    ints = RNG.integers(-2**31, 2**31 - 1, (n, f), dtype=np.int64)
    cols = (jnp.asarray(ints, jnp.int32),
            jnp.asarray(RNG.integers(0, 256, (n, f)), jnp.uint8))
    idx = np.r_[0, n - 1, n - 1, 0, 5, 5, RNG.integers(0, n, b - 6)]
    idx = jnp.asarray(idx, jnp.int32)
    got = pool_rows(idx, *cols, interpret=True)
    for g, w in zip(got, pool_rows_ref(idx, *cols)):
        assert g.dtype == w.dtype and g.shape == (b, f)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_pool_rows_reads_ids_as_the_gather_does():
    """Ids outside the pool: negative ones count from the end, the rest
    clamp, exactly as ``col[idx]``."""
    n, f = 1024, 16
    col = jnp.asarray(RNG.integers(0, 1 << 30, (n, f)), jnp.int32)
    idx = jnp.asarray([-1, -n, -n - 7, n, n + 300, 3], jnp.int32)
    (got,) = pool_rows(idx, col, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(col[idx]))


def test_cached_lookup_kernels_match_reference_on_a_split_tree():
    """The cached lookup with the Pallas kernels (interpret mode) gives the
    reference's answers and stats on a tree that split, through a stale
    image: hits, B-link chases and root retraversals."""
    from repro.core import ShermanIndex, TreeConfig
    from repro.core.cache import cached_lookup, fill_image
    cfg = TreeConfig(n_ms=2, nodes_per_ms=1024, fanout=8,
                     n_locks_per_ms=512, max_height=8, n_cs=2)
    idx = ShermanIndex.empty(cfg)
    keys = RNG.permutation(50_000)[:2_400].astype(np.int32)
    idx.insert(keys[:600], keys[:600] * 3)
    stale, _ = fill_image(cfg, idx.state)
    idx.insert(keys[600:], keys[600:] * 3)
    assert idx.counters["leaf_splits"] > 0
    fresh, _ = fill_image(cfg, idx.state)
    q = jnp.asarray(np.r_[keys[::5], 60_000 + np.arange(20)], jnp.int32)
    n_stale = []
    for image in (stale, fresh):
        r_ref, s_ref = cached_lookup(cfg, idx.state, image, q,
                                     kernel_mode="ref")
        r_pal, s_pal = cached_lookup(cfg, idx.state, image, q,
                                     kernel_mode="interpret")
        for a, b in zip((*r_ref, *s_ref), (*r_pal, *s_pal)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert np.asarray(r_ref.found).sum() == len(keys[::5])
        n_stale.append(int(np.asarray(s_ref.stale).sum()))
    assert n_stale[0] > 0 and n_stale[1] == 0


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
