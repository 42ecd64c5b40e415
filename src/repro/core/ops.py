"""Batched read-path operations: traversal, lookup, range query.

Reads are lock-free (paper §4.2.2): a reader fetches node images via
"one-sided" gathers and validates them with the two-level version protocol of
Fig. 9 — node-level versions (FNV/RNV) guard whole-node consistency,
entry-level versions (FEV/REV) guard each key/value pair.  In the
phase-synchronous batched execution the snapshot is always consistent; the
protocol is still executed faithfully so that the contention simulator (which
interleaves torn write images) exercises the retry path.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.tree import (EMPTY_KEY, NULL_PTR, TreeConfig, TreeState)


class TraceB(NamedTuple):
    """Traversal result: target nodes plus the visited path (for parent
    lookup during splits and for netsim cache accounting)."""
    leaf: jax.Array          # [B] node id at stop level
    path: jax.Array          # [max_height, B] node ids visited (may repeat)
    path_level: jax.Array    # [max_height, B] level of each visited node
    hops: jax.Array          # [B] number of distinct descents (netsim)
    chased: jax.Array        # [B] B-link sibling hops taken on the way


def _descend_once(st: TreeState, node: jax.Array, qkeys: jax.Array,
                  stop_level: jax.Array, chase_hops: int):
    """One traversal step: bounded B-link sibling chase, then one descent.
    Returns ``(node, hops)``: the next node and the sibling hops taken."""
    # --- sibling chase (paper §4.2.1): key beyond the fence => go right ---
    hops = jnp.zeros_like(node)
    for _ in range(chase_hops):
        fh = st.fence_hi[node]
        sib = st.sibling[node]
        chase = (qkeys >= fh) & (sib != NULL_PTR)
        node = jnp.where(chase, sib, node)
        hops = hops + chase.astype(jnp.int32)
    lv = st.level[node].astype(jnp.int32)
    nk = st.keys[node]                       # [B, F]
    nv = st.vals[node]
    valid = nk != EMPTY_KEY
    le = valid & (nk <= qkeys[:, None])
    j = jnp.maximum(jnp.sum(le.astype(jnp.int32), axis=1) - 1, 0)
    child = jnp.take_along_axis(nv, j[:, None], axis=1)[:, 0]
    return jnp.where(lv > stop_level, child, node), hops


def traverse(cfg: TreeConfig, st: TreeState, qkeys: jax.Array,
             stop_level: int = 0, start: jax.Array | None = None,
             stop_level_arr: jax.Array | None = None,
             chase_hops: int = 2) -> TraceB:
    """Route each query key to its node at ``stop_level`` (0 = leaf).

    ``stop_level_arr`` gives a per-lane stop level (used by the split-repair
    cascade, where each pending separator targets a different level).
    """
    b = qkeys.shape[0]
    node0 = jnp.broadcast_to(st.root, (b,)).astype(jnp.int32)
    if start is not None:
        node0 = jnp.where(start != NULL_PTR, start, node0)
    stop = (jnp.full((b,), stop_level, jnp.int32)
            if stop_level_arr is None else stop_level_arr.astype(jnp.int32))

    def body(carry, _):
        node, chased = carry
        nxt, n = _descend_once(st, node, qkeys, stop, chase_hops)
        return (nxt, chased + n), (node, st.level[node].astype(jnp.int32))

    (final, chased), (path, plevel) = lax.scan(
        body, (node0, jnp.zeros_like(node0)), None, length=cfg.max_height)
    hops = 1 + jnp.sum((path[1:] != path[:-1]).astype(jnp.int32), axis=0)
    final, extra = _chase_right(st.fence_hi, st.sibling, qkeys, final)
    return TraceB(leaf=final, path=path, path_level=plevel,
                  hops=hops + extra, chased=chased + extra)


@jax.jit
def _chase_right(fence_hi, sibling, qkeys, node):
    """Finish the B-link chase at the stop level, unbounded.

    The per-step chase is bounded, but a burst of splits whose separators
    have not reached the parents yet can leave a key several siblings to
    the right of where the descent ends.  Every read and write must land on
    the covering node.  Returns ``(node, hops)``.  (Jitted on its own so
    that eager callers compile the loop once per shape, not per call.)
    """
    def beyond(node):
        return (qkeys >= fence_hi[node]) & (sibling[node] != NULL_PTR)

    def chase(carry):
        node, n = carry
        go = beyond(node)
        return jnp.where(go, sibling[node], node), n + go.astype(jnp.int32)

    return lax.while_loop(lambda c: jnp.any(beyond(c[0])), chase,
                          (node, jnp.zeros_like(node)))


def parent_at_level(trace: TraceB, level: jax.Array | int) -> jax.Array:
    """Node visited at ``level`` on each lane's path (NULL if none)."""
    hit = trace.path_level == level
    cand = jnp.where(hit, trace.path, NULL_PTR)
    return jnp.max(cand, axis=0)


class LookupResult(NamedTuple):
    value: jax.Array         # [B] int32 (NULL_PTR when absent)
    found: jax.Array         # [B] bool
    consistent: jax.Array    # [B] bool — two-level version check passed
    leaf: jax.Array          # [B] leaf visited (netsim / cache accounting)
    hops: jax.Array          # [B] descents (netsim)


def leaf_lookup(st: TreeState, leaf: jax.Array, qkeys: jax.Array
                ) -> LookupResult:
    """Search leaf images for ``qkeys`` with the Fig. 9 version protocol.

    The unsorted leaf layout (paper §4.4) forces a full-node scan — the VPU
    analogue of the paper's "traverse the entire targeted leaf node".
    """
    nk = st.keys[leaf]                       # [B, F] snapshot
    nv = st.vals[leaf]
    fev = st.fev[leaf]
    rev = st.rev[leaf]
    node_ok = (st.fnv[leaf] == st.rnv[leaf]) & ~st.free_bit[leaf]

    eq = nk == qkeys[:, None]                # unsorted: compare every slot
    found = jnp.any(eq, axis=1)
    slot = jnp.argmax(eq, axis=1)
    take = lambda a: jnp.take_along_axis(a, slot[:, None], axis=1)[:, 0]
    entry_ok = take(fev) == take(rev)
    value = jnp.where(found, take(nv), NULL_PTR)
    consistent = node_ok & (entry_ok | ~found)
    return LookupResult(value=value, found=found & consistent,
                        consistent=consistent, leaf=leaf,
                        hops=jnp.zeros_like(leaf))


def lookup_batch(cfg: TreeConfig, st: TreeState, qkeys: jax.Array,
                 cache_image: dict | None = None, chase_hops: int = 4,
                 kernel_mode: str = "ref") -> LookupResult:
    """Batched point lookup.

    With a ``cache_image`` (see :mod:`repro.core.cache`) the descent runs
    locally through the replicated CS cache and ``hops`` reports the
    *remote* reads a real CS would issue (1 on a clean hit); without one
    it is the plain root-to-leaf traversal.
    """
    if cache_image is not None:
        from repro.core.cache import cached_lookup
        res, _ = cached_lookup(cfg, st, cache_image, qkeys,
                               chase_hops=chase_hops,
                               kernel_mode=kernel_mode)
        return res
    tr = traverse(cfg, st, qkeys)
    res = leaf_lookup(st, tr.leaf, qkeys)
    return res._replace(hops=tr.hops)


class RangeResult(NamedTuple):
    keys: jax.Array          # [B, count] int32 (EMPTY_KEY padding)
    vals: jax.Array          # [B, count]
    n: jax.Array             # [B] number of valid results
    leaves_read: jax.Array   # [B] leaves fetched (netsim)
    consistent: jax.Array    # [B] bool
    start_hit: jax.Array     # [B] bool — initial descent was a cache hit
    start_leaf: jax.Array    # [B] first leaf of the scan (verb-plane MS
                             #    targeting for the sibling-chain reads)


def range_batch(cfg: TreeConfig, st: TreeState, lo: jax.Array, count: int,
                max_leaves: int,
                cache_image: dict | None = None) -> RangeResult:
    """Fetch the first ``count`` pairs with key >= lo for each lane.

    Mirrors the paper §4.4: the client issues parallel RDMA_READs along the
    sibling chain and version-checks each leaf like a lookup.  With a
    ``cache_image`` the initial descent runs through the CS cache
    (``start_hit``); a stale start leaf is harmless — the sibling chain
    walks right past it, exactly the B-link argument.
    """
    b = lo.shape[0]
    tr = traverse(cfg, st, lo)
    start = tr.leaf
    start_hit = jnp.zeros((b,), bool)
    if cache_image is not None:
        from repro.core.cache import descend_image, leaf_sound
        leaf0, hit, _ = descend_image(cache_image, lo, cfg.max_height)
        sound = hit & leaf_sound(st, leaf0, lo)   # a split start falls back
        start = jnp.where(sound, leaf0, start)
        start_hit = sound
    tr = tr._replace(leaf=start)

    def chain(leaf, _):
        nxt = st.sibling[leaf]
        return jnp.where(nxt != NULL_PTR, nxt, leaf), leaf

    _, leaves = lax.scan(chain, tr.leaf, None, length=max_leaves)
    leaves = jnp.swapaxes(leaves, 0, 1)              # [B, max_leaves]
    # dedupe the tail (sibling chain may saturate at the rightmost leaf)
    dup = jnp.concatenate(
        [jnp.zeros((b, 1), bool), leaves[:, 1:] == leaves[:, :-1]], axis=1)

    nk = st.keys[leaves]                             # [B, L, F]
    nv = st.vals[leaves]
    node_ok = (st.fnv[leaves] == st.rnv[leaves]) & ~st.free_bit[leaves]
    entry_ok = st.fev[leaves] == st.rev[leaves]
    valid = ((nk != EMPTY_KEY) & (nk >= lo[:, None, None])
             & entry_ok & node_ok[:, :, None] & ~dup[:, :, None])
    f = cfg.fanout
    flat = (b, leaves.shape[1] * f)      # explicit: survives empty batches
    flat_k = jnp.where(valid, nk, jnp.int32(2**31 - 1)).reshape(flat)
    flat_v = nv.reshape(flat)
    order = jnp.argsort(flat_k, axis=1)
    sk = jnp.take_along_axis(flat_k, order[:, :count], axis=1)
    sv = jnp.take_along_axis(flat_v, order[:, :count], axis=1)
    got = sk != jnp.int32(2**31 - 1)
    return RangeResult(
        keys=jnp.where(got, sk, EMPTY_KEY),
        vals=jnp.where(got, sv, NULL_PTR),
        n=jnp.sum(got.astype(jnp.int32), axis=1),
        leaves_read=jnp.sum((~dup).astype(jnp.int32), axis=1),
        consistent=jnp.all(node_ok | dup, axis=1),
        start_hit=start_hit,
        start_leaf=tr.leaf,
    )
