"""The functional CS-side index cache (repro.core.cache, paper §4.2.3):
hit/miss/stale accounting, eviction at the byte budget, versioned
invalidation, stale-traversal correctness against the oracle, and the
Pallas leaf-search kernel on the cached hot path."""
import numpy as np
import pytest

from repro.core import OracleIndex, ShermanIndex, TreeConfig
from repro.core.cache import (IndexCache, cached_lookup, descend_image,
                              fill_image)
from repro.workloads import SYSTEMS, build_index, get_preset, run_workload, \
    scramble

CFG = TreeConfig(n_ms=2, nodes_per_ms=2048, fanout=16, n_locks_per_ms=1024,
                 max_height=7, n_cs=4)
KEYSPACE = 1 << 20


def _fresh(records=4_000, **kw):
    return build_index(SYSTEMS["sherman"], CFG, records=records, **kw)


def _ranks(lo, hi):
    return scramble(np.arange(lo, hi), KEYSPACE).astype(np.int32)


# -- hit path --------------------------------------------------------------

def test_read_only_hits_and_single_remote_read():
    """Read-only (YCSB-C shape): every lookup is a cache hit costing one
    remote leaf read — the paper's single-round-trip fast path."""
    idx = _fresh()
    q = _ranks(0, 1_024)
    got, found = idx.lookup(q)
    assert found.all()
    c = idx.counters
    assert c["cache_hits"] == 1_024 and c["cache_misses"] == 0
    assert c["cache_stale"] == 0
    assert c["lookup_reads"] == c["lookup_ops"] == 1_024   # exactly 1 read/op
    assert idx.cache.hit_ratio == 1.0


def test_ycsb_c_acceptance_hit_rate():
    """The acceptance bar: YCSB-C at the default cache size reports >= 90%
    hit rate and ~1 remote read per lookup."""
    spec = get_preset("ycsb-c", load_records=4_000, ops=1_024, batch=512)
    idx = _fresh()
    r = run_workload(idx, spec, system="sherman")
    assert r.cache_hit_rate >= 0.9
    assert r.reads_per_lookup == pytest.approx(1.0, abs=0.1)
    assert r.cache_hits + r.cache_misses + r.cache_stale == r.n_ops


def test_disabled_cache_pays_full_traversals():
    idx = _fresh(cache_bytes=0)
    q = _ranks(0, 256)
    _, found = idx.lookup(q)
    assert found.all()
    c = idx.counters
    assert c["cache_hits"] == 0 and c["cache_misses"] == 256
    height = int(idx.state.height)
    assert c["lookup_reads"] == 256 * height


def test_partial_cache_levels_price_partial_descent():
    """With only the top 2 levels cached, a lookup resumes remotely from
    the first uncached level: reads = height - cached depth, not a full
    traversal."""
    idx = _fresh(records=8_000, cache_levels=2)
    q = _ranks(0, 256)
    _, found = idx.lookup(q)
    assert found.all()
    c = idx.counters
    assert c["cache_hits"] == 0 and c["cache_misses"] == 256
    height = int(idx.state.height)
    assert height > 3                  # deep enough for a partial descent
    assert c["lookup_reads"] == 256 * (height - 2)


# -- stale path ------------------------------------------------------------

def test_stale_cache_lookups_match_oracle():
    """Inserts/splits after the cache fill leave the image stale; lookups
    must still be oracle-correct via the B-link chase, with stale > 0."""
    idx = _fresh(records=2_000)
    oracle = OracleIndex()
    rng = np.random.default_rng(3)
    load_k = _ranks(0, 2_000)
    # overwrite the load-phase values with known ones so the oracle agrees
    load_v = rng.integers(0, 1 << 20, 2_000).astype(np.int32)
    idx.insert(load_k, load_v)
    oracle.insert_batch(load_k, load_v)

    idx.lookup(load_k[:64])                     # warm fill, pre-split image
    fills_before = idx.cache.counters.fills
    stale_seen = 0
    for lo in range(2_000, 2_500, 100):         # interleave inserts + reads
        new_k = _ranks(lo, lo + 100)
        new_v = rng.integers(0, 1 << 20, 100).astype(np.int32)
        idx.insert(new_k, new_v)
        oracle.insert_batch(new_k, new_v)
        probe = np.concatenate([new_k, load_k[rng.integers(0, 2_000, 156)]])
        got, found = idx.lookup(probe)
        assert found.all()
        want = np.asarray([oracle.lookup(int(k)) for k in probe])
        np.testing.assert_array_equal(got, want)
        stale_seen = idx.counters["cache_stale"]
    assert idx.counters["leaf_splits"] > 0
    # the stale path ran unless every split batch forced a refresh
    assert stale_seen > 0 or idx.cache.counters.fills > fills_before


def test_random_op_mix_with_stale_cache_matches_oracle():
    """Seeded pseudo-property test (no hypothesis needed): arbitrary
    insert/delete/lookup interleavings against a deliberately
    never-refreshed cache still return oracle-correct results."""
    idx = _fresh(records=1_000)
    idx.cache.sync_every = 0            # never version-sync
    idx.cache.refresh_frac = 1.1        # never refresh on invalid fraction
    oracle = OracleIndex()
    k0 = _ranks(0, 1_000)
    v0 = np.arange(1_000, dtype=np.int32)
    idx.insert(k0, v0)
    oracle.insert_batch(k0, v0)
    idx.lookup(k0[:32])                 # warm fill
    rng = np.random.default_rng(11)
    cursor = 1_000
    for _ in range(6):
        ins = _ranks(cursor, cursor + 150)
        cursor += 150
        vals = rng.integers(0, 1 << 20, 150).astype(np.int32)
        idx.insert(ins, vals)
        oracle.insert_batch(ins, vals)
        dele = scramble(rng.choice(cursor, 40, replace=False),
                        KEYSPACE).astype(np.int32)
        idx.delete(dele)
        oracle.delete_batch(dele)
        probe = np.concatenate(
            [ins[:50], _ranks(0, cursor)[rng.integers(0, cursor, 100)]])
        got, found = idx.lookup(probe)
        want = [oracle.lookup(int(k)) for k in probe]
        for g, f, w in zip(got, found, want):
            if w is None:
                assert not f
            else:
                assert f and g == w
    assert idx.counters["leaf_splits"] > 0
    assert idx.counters["cache_stale"] > 0      # stale path was exercised


def test_empty_batches_are_noops():
    idx = _fresh(records=2_000)
    got, found = idx.lookup(np.zeros(0, np.int32))
    assert got.size == 0 and found.size == 0
    rk, rv, rn = idx.range(np.zeros(0, np.int32), count=4)
    assert rn.size == 0


def test_lazy_invalidation_targets_covering_entry_once():
    """Repeated stale detections for one key region invalidate the covering
    level-1 entry exactly once — never its (still-correct) neighbors."""
    idx = _fresh(records=4_000)
    idx.lookup(_ranks(0, 32))                   # fill the image
    k = _ranks(100, 101)
    valid_before = idx.cache._valid.sum()
    assert idx.cache.invalidate_covering(k) == 1
    assert idx.cache.invalidate_covering(k) == 0     # no-op, not a neighbor
    assert idx.cache._valid.sum() == valid_before - 1


def test_upper_level_invalidation_forces_refresh():
    """Losing a cached root/upper-level row would cut off every descent;
    the cache must refresh instead of limping at full-miss pricing until
    the bulk invalid-fraction threshold trips."""
    idx = _fresh(records=4_000)
    idx.lookup(_ranks(0, 32))                   # fill
    cache = idx.cache
    v = cache._valid.copy()
    v[cache._rows == cache._root] = False       # as a version sweep would
    cache._set_valid(v)
    fills0 = cache.counters.fills
    misses0 = idx.counters["cache_misses"]
    _, found = idx.lookup(_ranks(0, 64))
    assert found.all()
    assert cache.counters.fills > fills0        # refreshed, not degraded
    assert idx.counters["cache_misses"] == misses0


def test_ops_lookup_batch_consults_cache():
    """ops.lookup_batch with a cache image matches the plain traversal and
    reports the single-remote-read hop count."""
    import jax.numpy as jnp
    from repro.core.ops import lookup_batch
    idx = _fresh(records=3_000)
    img, _ = fill_image(CFG, idx.state)
    q = jnp.asarray(_ranks(0, 128))
    r_c = lookup_batch(CFG, idx.state, q, cache_image=img)
    r_p = lookup_batch(CFG, idx.state, q)
    np.testing.assert_array_equal(np.asarray(r_c.value),
                                  np.asarray(r_p.value))
    np.testing.assert_array_equal(np.asarray(r_c.found),
                                  np.asarray(r_p.found))
    assert (np.asarray(r_c.hops) == 1).all()    # fresh image: 1 remote read


def test_range_start_descent_consults_cache():
    idx = _fresh(records=3_000)
    idx.range(_ranks(0, 32), count=8)
    assert idx.cache.counters.hits >= 32        # start descents hit


def test_cache_maintenance_is_priced():
    """Image fills and version sweeps show up as netsim messages/bytes."""
    idx = _fresh(records=2_000)
    idx.lookup(_ranks(0, 16))                   # triggers the first fill
    assert idx.cache.counters.fill_reads > 0
    assert idx.counters["msgs"] > idx.counters["lookup_reads"]


# -- eviction / budget -----------------------------------------------------

def test_eviction_at_byte_budget():
    """A cache smaller than the internal levels keeps the top levels,
    evicts level-1 nodes, stays under budget, and still answers
    correctly (misses pay full traversals)."""
    budget = 6 * CFG.node_bytes
    idx = _fresh(records=8_000, cache_bytes=budget)
    q = _ranks(0, 512)
    got, found = idx.lookup(q)
    assert found.all()
    cc = idx.cache.counters
    assert cc.evictions > 0
    assert idx.cache.cached_bytes <= budget
    assert idx.counters["cache_misses"] > 0
    # the kept rows are the *top* levels (never a dropped root)
    img = idx.cache._image
    lvl = np.asarray(img["level"])[np.asarray(img["valid"])]
    assert int(np.asarray(idx.state.level)[int(idx.state.root)]) in lvl


def test_counter_accounting_identity():
    """hits + misses + stale == lookups issued; remote reads are >= 1 per
    lookup and exactly 1 for clean hits."""
    idx = _fresh(records=4_000)
    q = _ranks(0, 700)
    idx.lookup(q)
    idx.insert(_ranks(4_000, 4_600),
               np.arange(600, dtype=np.int32))
    idx.lookup(q)
    c = idx.counters
    assert c["cache_hits"] + c["cache_misses"] + c["cache_stale"] \
        == c["lookup_ops"] == 1_400
    assert c["lookup_reads"] >= c["lookup_ops"]


# -- versioned invalidation ------------------------------------------------

def test_version_sync_invalidates_changed_nodes():
    idx = _fresh(records=2_000)
    idx.cache.sync_every = 10**9        # isolate: no automatic sweeps
    idx.lookup(_ranks(0, 64))           # fill
    before = idx.cache.counters.invalidations
    idx.insert(_ranks(2_000, 2_800), np.arange(800, dtype=np.int32))
    assert idx.counters["leaf_splits"] > 0
    n = idx.cache.sync_versions(idx.state)
    # separator inserts bumped parent FNVs => entries must invalidate,
    # unless a root split already forced a full refresh
    assert n > 0 or idx.cache.counters.fills > 1 or \
        idx.cache._needs_refresh
    assert idx.cache.counters.sync_sweeps >= 1
    assert idx.cache.counters.invalidations >= before
    # lookups after the sweep remain correct
    _, found = idx.lookup(_ranks(0, 256))
    assert found.all()


def test_root_split_forces_refresh():
    cfg = TreeConfig(n_ms=2, nodes_per_ms=1024, fanout=4,
                     n_locks_per_ms=512, max_height=7, n_cs=2)
    rng = np.random.default_rng(5)
    keys = np.sort(rng.choice(50_000, 40, replace=False)).astype(np.int32)
    idx = ShermanIndex.build(cfg, keys, np.arange(40, dtype=np.int32))
    idx.lookup(keys[:8])
    fills0 = idx.cache.counters.fills
    extra = np.setdiff1d(np.arange(50_000, dtype=np.int32), keys)
    extra = rng.permutation(extra)[:400].astype(np.int32)
    idx.insert(extra, np.arange(400, dtype=np.int32))
    assert idx.counters["root_splits"] > 0
    _, found = idx.lookup(keys)
    assert found.all()
    assert idx.cache.counters.fills > fills0      # image was rebuilt


def test_donated_state_cycle_matches_oracle():
    """write -> cached lookup -> write -> cached lookup, cache on.  The
    write phases donate the tree state to XLA, so nothing the cache keeps
    across a write may alias it.  The tree starts as one leaf, so the
    writes split the root."""
    cfg = TreeConfig(n_ms=2, nodes_per_ms=1024, fanout=4,
                     n_locks_per_ms=512, max_height=7, n_cs=2)
    idx = ShermanIndex.empty(cfg)
    oracle = OracleIndex()
    keys = np.random.default_rng(11).permutation(5_000)[:600]
    keys = keys.astype(np.int32)
    for step, part in enumerate(np.array_split(keys, 4)):
        vals = (part * 3 + step).astype(np.int32)
        idx.insert(part, vals)
        oracle.insert_batch(part, vals)
        probe = keys[:150 * step + 200]        # some not inserted yet
        got, found = idx.lookup(probe)
        for k, g, f in zip(probe, got, found):
            want = oracle.lookup(int(k))
            assert (want is None and not f) or (f and g == want), (k, g)
    assert idx.counters["root_splits"] >= 1
    assert idx.counters["cache_hits"] > 0


# -- kernel parity ---------------------------------------------------------

def test_cached_lookup_kernel_parity():
    """The Pallas leaf-search kernel (interpret mode) and the jnp reference
    agree on the cached hot path, including non-tile-aligned batches."""
    import jax.numpy as jnp
    idx = _fresh(records=3_000)
    img, _ = fill_image(CFG, idx.state)
    for n in (100, 256, 300, 1024):
        q = jnp.asarray(_ranks(0, n))
        r_ref, s_ref = cached_lookup(CFG, idx.state, img, q,
                                     kernel_mode="ref")
        r_pal, s_pal = cached_lookup(CFG, idx.state, img, q,
                                     kernel_mode="interpret")
        np.testing.assert_array_equal(np.asarray(r_ref.value),
                                      np.asarray(r_pal.value))
        np.testing.assert_array_equal(np.asarray(r_ref.found),
                                      np.asarray(r_pal.found))
        np.testing.assert_array_equal(np.asarray(s_ref.remote_reads),
                                      np.asarray(s_pal.remote_reads))


def test_descend_image_routes_like_traverse():
    """Cache descent lands on the same leaf as the real traversal when the
    image is fresh."""
    import jax.numpy as jnp
    from repro.core.ops import traverse
    idx = _fresh(records=3_000)
    img, _ = fill_image(CFG, idx.state)
    q = jnp.asarray(_ranks(0, 512))
    leaf, hit, depth = descend_image(img, q, CFG.max_height)
    assert np.asarray(hit).all()
    # hits descended through every internal level locally
    assert (np.asarray(depth) == int(idx.state.height) - 1).all()
    tr = traverse(CFG, idx.state, q)
    np.testing.assert_array_equal(np.asarray(leaf), np.asarray(tr.leaf))


# -- hypothesis property test (skipped when hypothesis is absent) ----------

def test_property_stale_cache_oracle():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    cfg = TreeConfig(n_ms=2, nodes_per_ms=1024, fanout=8,
                     n_locks_per_ms=512, max_height=7, n_cs=2)
    KEYS = st.integers(min_value=0, max_value=2_000)
    VALS = st.integers(min_value=0, max_value=1 << 20)
    batch = st.lists(st.tuples(KEYS, VALS), min_size=1, max_size=32)

    @settings(max_examples=15, deadline=None)
    @given(st.lists(batch, min_size=1, max_size=5))
    def inner(batches):
        idx = ShermanIndex.empty(cfg)
        idx.cache.sync_every = 0
        idx.cache.refresh_frac = 1.1
        oracle = OracleIndex()
        seed_k = np.arange(0, 2_000, 7, dtype=np.int32)
        idx.insert(seed_k, seed_k)
        oracle.insert_batch(seed_k, seed_k)
        idx.lookup(seed_k[:16])             # warm the image
        for b in batches:
            ks = np.asarray([k for k, _ in b], np.int32)
            vs = np.asarray([v for _, v in b], np.int32)
            idx.insert(ks, vs)
            oracle.insert_batch(ks.tolist(), vs.tolist())
            probe = np.unique(np.concatenate([ks, seed_k[:64]]))
            got, found = idx.lookup(probe)
            for k, g, f in zip(probe, got, found):
                w = oracle.lookup(int(k))
                assert (w is None and not f) or (f and g == w), (k, g, w)

    inner()
