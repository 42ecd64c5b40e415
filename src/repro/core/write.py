"""Batched write path: insert / update / delete with B-link node splits.

Faithful to the paper's Fig. 7 flow, adapted to phase-synchronous SIMD
execution (DESIGN.md §8):

* one batch ≡ one wave of concurrent client ops; lane order is arrival order;
* lock/contention structure is computed by :mod:`repro.core.hocl` and priced
  by netsim — data application itself is deterministic;
* without a split, an op touches exactly one entry and bumps its FEV/REV
  (17-byte write-back — the two-level-version win);
* splits sort the (unsorted) leaf, move the upper half to a freshly allocated
  sibling, bump FNV/RNV and write back whole nodes;
* separator insertion into parents may cascade; unfinished cascades are safe
  to defer thanks to the B-link sibling property (Lehman&Yao) and are
  returned as a *repair queue* that the driver completes in later phases —
  the SIMD analogue of the classic half-split state.  The queue has a fixed
  capacity, and a node splits only when the queue has a free slot for its
  separator (:func:`_first`): no separator is ever dropped, and a node that
  must wait keeps its own separator, or its insert, pending.

All functions are shape-static and jit/shard_map friendly.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import hocl
from repro.core.ops import traverse
from repro.core.tree import (EMPTY_KEY, KEY_MIN, NULL_PTR, TreeConfig,
                             TreeState)

INT_MAX = jnp.int32(2**31 - 1)


# --------------------------------------------------------------------------
# small masked-scatter helpers (duplicate writes on the park row all carry
# identical values, so the scatter stays deterministic)
# --------------------------------------------------------------------------

def _park(cfg: TreeConfig, idx: jax.Array, do: jax.Array) -> jax.Array:
    return jnp.where(do, idx, jnp.int32(cfg.park_row))


def _scatter_entry(cfg, arr, row, col, val, do):
    """arr[row, col] = val where do; parked lanes rewrite the park value."""
    r = _park(cfg, row, do)
    c = jnp.where(do, col, 0)
    old = arr[r, c]
    return arr.at[r, c].set(jnp.where(do, val, old).astype(arr.dtype))


def _scatter_row1(cfg, arr, row, val, do):
    """arr[row] = val (per-node scalar field)."""
    r = _park(cfg, row, do)
    old = arr[r]
    return arr.at[r].set(jnp.where(do, val, old).astype(arr.dtype))


def _scatter_rowF(cfg, arr, row, val, do):
    """arr[row, :] = val[lane, :] (whole-node row write)."""
    r = _park(cfg, row, do)
    old = arr[r]
    return arr.at[r].set(jnp.where(do[:, None], val, old).astype(arr.dtype))


def _bump_entry_version(cfg, st: TreeState, row, col, do) -> TreeState:
    fev = _scatter_entry(cfg, st.fev, row, col,
                         (st.fev[_park(cfg, row, do),
                                 jnp.where(do, col, 0)] + 1) % 16, do)
    rev = _scatter_entry(cfg, st.rev, row, col,
                         (st.rev[_park(cfg, row, do),
                                 jnp.where(do, col, 0)] + 1) % 16, do)
    return st._replace(fev=fev, rev=rev)


def _bump_node_version(cfg, st: TreeState, row, do) -> TreeState:
    r = _park(cfg, row, do)
    fnv = st.fnv.at[r].set(jnp.where(do, (st.fnv[r] + 1) % 16, st.fnv[r]))
    rnv = st.rnv.at[r].set(jnp.where(do, (st.rnv[r] + 1) % 16, st.rnv[r]))
    return st._replace(fnv=fnv, rnv=rnv)


def _rank_by(node_key: jax.Array, active: jax.Array, sentinel_base: int):
    """FIFO rank of each active lane within its (node_key) group."""
    b = node_key.shape[0]
    lane = jnp.arange(b, dtype=jnp.int32)
    parked = jnp.where(active, node_key, sentinel_base + lane)
    perm = jnp.lexsort((lane, parked))
    inv = jnp.argsort(perm)
    s = parked[perm]
    newg = s != jnp.concatenate([jnp.full((1,), -7, s.dtype), s[:-1]])
    gid = jnp.cumsum(newg.astype(jnp.int32)) - 1
    start = jax.ops.segment_min(lane, gid, num_segments=b)
    rank_sorted = lane - start[gid]
    return rank_sorted[inv], newg[inv]


# --------------------------------------------------------------------------
# phase statistics
# --------------------------------------------------------------------------

class WriteStats(NamedTuple):
    """Structural counters for one write phase (netsim inputs).

    Per-lane arrays have batch shape [B]; scalars are 0-d.
    """
    applied_update: jax.Array     # [B] entry-granular update/insert applied
    applied_delete: jax.Array     # [B]
    applied_insert: jax.Array     # [B]
    miss_delete: jax.Array        # [B] delete of absent key (no write)
    superseded: jax.Array         # [B] op overwritten by later lane, no-op
    deferred: jax.Array           # [B] must retry next phase
    leaf: jax.Array               # [B] target leaf (cache accounting)
    hops: jax.Array               # [B] traversal descents
    local_size: jax.Array         # [B] HOCL local group size
    local_rank: jax.Array         # [B] FIFO rank inside the local group
    node_size: jax.Array          # [B] per-leaf conflict group size
    node_rank: jax.Array          # [B] FIFO rank among all ops on the leaf
    cs_rank: jax.Array            # [B] serialization rank of own CS group
    lock_cycles: jax.Array        # [B] remote lock cycles of own group
    local_head: jax.Array         # [B] head of local group
    cycle_head: jax.Array         # [B] lane issues the remote LOCK CAS
                                  #    under HOCL (verb plane)
    chain_end: jax.Array          # [B] lane issues the remote UNLOCK
                                  #    under HOCL (verb plane)
    split_mask: jax.Array         # [B] lane performed a leaf split (netsim
                                  #    split-lane pricing; with the split
                                  #    counts below, the cache-invalidation
                                  #    hook input)
    split_same_ms: jax.Array      # [B] lane's sibling landed on the same MS
                                  #    (three-way command combination §4.5)
    split_new_row: jax.Array      # [B] sibling row of the lane's split
                                  #    (park_row when no split) — verb
                                  #    plane targets the SIBLING write
    n_leaf_splits: jax.Array      # []
    n_internal_splits: jax.Array  # []
    n_root_splits: jax.Array      # []
    n_split_same_ms: jax.Array    # [] sibling allocated on same MS => 3-way
                                  #    command combination (paper §4.5)
    hocl_remote_cas: jax.Array    # []
    flat_remote_cas: jax.Array    # [] no-hierarchy baseline CAS count
    handovers: jax.Array          # []
    repair_backlog: jax.Array     # [] separators left in the repair queue
    repair_dropped: jax.Array     # [] separators lost at the queue's
                                  #    capacity (0: splits wait for a slot)
    blink_hops: jax.Array         # [] B-link sibling hops of the active
                                  #    lanes' route to their leaves


class RepairQueue(NamedTuple):
    """Deferred separator insertions (B-link half-splits to complete)."""
    sep: jax.Array       # [Q] separator key
    child: jax.Array     # [Q] right node to link
    level: jax.Array     # [Q] level of the split node (parent is level+1)
    valid: jax.Array     # [Q] bool

    @staticmethod
    def empty(q: int) -> "RepairQueue":
        return RepairQueue(
            sep=jnp.full((q,), EMPTY_KEY, jnp.int32),
            child=jnp.full((q,), NULL_PTR, jnp.int32),
            level=jnp.zeros((q,), jnp.int32),
            valid=jnp.zeros((q,), bool))


def _free_slots(pend: RepairQueue) -> jax.Array:
    return jnp.sum((~pend.valid).astype(jnp.int32))


def _first(mask: jax.Array, n: jax.Array) -> jax.Array:
    """The first ``n`` set lanes of ``mask``, in lane order.  A split is
    allowed only as far as the repair queue has free slots for the
    separators it makes."""
    return mask & (jnp.cumsum(mask.astype(jnp.int32)) <= n)


def _enqueue_pending(pend: RepairQueue, sep: jax.Array, child: jax.Array,
                     level: jax.Array, did: jax.Array):
    """Insert the ``did`` lanes' separators into the queue's free slots.

    The r-th new entry (by lane order) lands in the r-th free slot.
    Returns ``(queue, dropped)``: every caller splits only as many nodes
    as there are free slots, so ``dropped``, the entries beyond the free
    capacity, is 0; it is counted so that a run shows it.  Shared by the
    write phase's split rounds and the repair cascade.
    """
    q = pend.sep.shape[0]
    free = ~pend.valid
    new_rank, _ = _rank_by(jnp.zeros_like(sep), did, 1)
    cumfree = jnp.cumsum(free.astype(jnp.int32)) - free.astype(jnp.int32)
    # index of the r-th free slot: first slot with cumfree == r
    slot_of_rank = jax.ops.segment_min(
        jnp.arange(q, dtype=jnp.int32),
        jnp.where(free, cumfree, q), num_segments=q + 1)[:q]
    can = did & (new_rank < jnp.sum(free.astype(jnp.int32)))
    tgt = jnp.where(can, slot_of_rank[jnp.minimum(new_rank, q - 1)], q)
    pad = lambda a, v: jnp.concatenate([a, jnp.array([v], a.dtype)])
    return RepairQueue(
        sep=pad(pend.sep, 0).at[tgt].set(jnp.where(can, sep, 0),
                                         mode="drop")[:q],
        child=pad(pend.child, 0).at[tgt].set(jnp.where(can, child, 0),
                                             mode="drop")[:q],
        level=pad(pend.level, 0).at[tgt].set(jnp.where(can, level, 0),
                                             mode="drop")[:q],
        valid=pad(pend.valid, False).at[tgt].set(can, mode="drop")[:q]), \
        jnp.sum((did & ~can).astype(jnp.int32))


# --------------------------------------------------------------------------
# entry-granular application (the common, split-free path)
# --------------------------------------------------------------------------

def _apply_updates_deletes(cfg, st, leaf, slot, vals, upd, dele):
    do = upd | dele
    st = st._replace(
        vals=_scatter_entry(cfg, st.vals, leaf, slot, vals, upd),
        keys=_scatter_entry(cfg, st.keys, leaf, slot,
                            jnp.int32(EMPTY_KEY), dele))
    return _bump_entry_version(cfg, st, leaf, slot, do)


def _apply_inserts(cfg, st, leaf, keys, vals, ins):
    """Assign each new key a free slot of its leaf; overflows are returned."""
    rank, _ = _rank_by(leaf, ins, cfg.n_nodes)
    lk = st.keys[leaf]                               # post-update snapshot
    free = lk == EMPTY_KEY
    nfree = jnp.sum(free.astype(jnp.int32), axis=1)
    fits = ins & (rank < nfree)
    cum = jnp.cumsum(free.astype(jnp.int32), axis=1)
    hit = free & (cum == (rank + 1)[:, None])
    slot = jnp.argmax(hit, axis=1).astype(jnp.int32)
    st = st._replace(
        keys=_scatter_entry(cfg, st.keys, leaf, slot, keys, fits),
        vals=_scatter_entry(cfg, st.vals, leaf, slot, vals, fits))
    st = _bump_entry_version(cfg, st, leaf, slot, fits)
    return st, fits, ins & ~fits


# --------------------------------------------------------------------------
# node split (generic over leaf / internal nodes)
# --------------------------------------------------------------------------

def _split_nodes(cfg, st: TreeState, node: jax.Array, rep: jax.Array):
    """Split ``node`` for every lane where ``rep`` (one lane per node).

    Returns (state, sep, new_row, did_split, same_ms).  The split sets the
    sibling pointer atomically with the content move, so the tree is a valid
    B-link structure even before the parent learns about ``new_row``.
    """
    b = node.shape[0]
    f = cfg.fanout
    nk = st.keys[node]
    nv = st.vals[node]
    occupied = nk != EMPTY_KEY
    cnt = jnp.sum(occupied.astype(jnp.int32), axis=1)
    # a rep only splits a genuinely full-ish node (>= 2 entries)
    do = rep & (cnt >= 2)

    skey = jnp.where(occupied, nk, INT_MAX)
    order = jnp.argsort(skey, axis=1)
    sk = jnp.take_along_axis(nk, order, axis=1)      # sorted, EMPTY last
    sv = jnp.take_along_axis(nv, order, axis=1)
    keep = (cnt + 1) // 2                            # left keeps ceil half
    sep = jnp.take_along_axis(sk, keep[:, None], axis=1)[:, 0]

    # ---- allocate sibling rows (two-stage allocator, paper §4.2.4) ----
    rep_rank = jnp.cumsum(do.astype(jnp.int32)) - 1
    ms = ((st.alloc_rr + rep_rank) % cfg.n_ms).astype(jnp.int32)
    off, _ = _rank_by(ms, do, cfg.n_ms)
    new_local = st.alloc_next[ms] + off
    has_room = new_local < cfg.alloc_cap
    do = do & has_room
    new_row = jnp.where(do, ms * cfg.nodes_per_ms + new_local,
                        jnp.int32(cfg.park_row))
    n_alloc = jax.ops.segment_sum(do.astype(jnp.int32), ms,
                                  num_segments=cfg.n_ms)
    st = st._replace(alloc_next=st.alloc_next + n_alloc,
                     alloc_rr=st.alloc_rr + jnp.sum(do.astype(jnp.int32)))

    # ---- write the new (right) node ----
    idx = jnp.arange(f, dtype=jnp.int32)[None, :]
    right_src = jnp.minimum(keep[:, None] + idx, f - 1)
    in_right = (keep[:, None] + idx) < cnt[:, None]
    right_k = jnp.where(in_right, jnp.take_along_axis(sk, right_src, 1),
                        EMPTY_KEY)
    right_v = jnp.where(in_right, jnp.take_along_axis(sv, right_src, 1),
                        NULL_PTR)
    st = st._replace(
        keys=_scatter_rowF(cfg, st.keys, new_row, right_k, do),
        vals=_scatter_rowF(cfg, st.vals, new_row, right_v, do),
        fev=_scatter_rowF(cfg, st.fev, new_row, jnp.zeros((b, f)), do),
        rev=_scatter_rowF(cfg, st.rev, new_row, jnp.zeros((b, f)), do),
        fnv=_scatter_row1(cfg, st.fnv, new_row, jnp.zeros((b,)), do),
        rnv=_scatter_row1(cfg, st.rnv, new_row, jnp.zeros((b,)), do),
        level=_scatter_row1(cfg, st.level, new_row, st.level[node], do),
        fence_lo=_scatter_row1(cfg, st.fence_lo, new_row, sep, do),
        fence_hi=_scatter_row1(cfg, st.fence_hi, new_row,
                               st.fence_hi[node], do),
        sibling=_scatter_row1(cfg, st.sibling, new_row, st.sibling[node],
                              do),
        free_bit=_scatter_row1(cfg, st.free_bit, new_row,
                               jnp.zeros((b,), bool), do),
    )

    # ---- shrink the old (left) node; in-place, then bump FNV/RNV ----
    left_keep = occupied & (nk < sep[:, None])
    left_k = jnp.where(left_keep, nk, EMPTY_KEY)
    st = st._replace(
        keys=_scatter_rowF(cfg, st.keys, node, left_k, do),
        fence_hi=_scatter_row1(cfg, st.fence_hi, node, sep, do),
        sibling=_scatter_row1(cfg, st.sibling, node, new_row, do),
    )
    st = _bump_node_version(cfg, st, node, do)

    same_ms = do & (cfg.ms_of(new_row) == cfg.ms_of(node))
    return st, sep, new_row, do, same_ms


# --------------------------------------------------------------------------
# separator insertion into (sorted) internal nodes, with cascade
# --------------------------------------------------------------------------

def _internal_insert_once(cfg, st: TreeState, parent, sep, child, sel):
    """One sorted insert per distinct parent. Returns (st, ok, full)."""
    f = cfg.fanout
    nk = st.keys[parent]
    nv = st.vals[parent]
    valid = nk != EMPTY_KEY
    cnt = jnp.sum(valid.astype(jnp.int32), axis=1)
    dup = jnp.any(valid & (nk == sep[:, None]), axis=1)   # already repaired
    fits = sel & (cnt < f) & ~dup
    pos = jnp.sum((valid & (nk < sep[:, None])).astype(jnp.int32), axis=1)
    idx = jnp.arange(f, dtype=jnp.int32)[None, :]
    shift_src = jnp.maximum(idx - 1, 0)
    k_shift = jnp.take_along_axis(nk, shift_src, 1)
    v_shift = jnp.take_along_axis(nv, shift_src, 1)
    newk = jnp.where(idx == pos[:, None], sep[:, None],
                     jnp.where(idx > pos[:, None], k_shift, nk))
    newv = jnp.where(idx == pos[:, None], child[:, None],
                     jnp.where(idx > pos[:, None], v_shift, nv))
    st = st._replace(
        keys=_scatter_rowF(cfg, st.keys, parent, newk, fits),
        vals=_scatter_rowF(cfg, st.vals, parent, newv, fits),
    )
    st = _bump_node_version(cfg, st, parent, fits)
    return st, fits | (sel & dup), sel & (cnt >= f) & ~dup


def _root_split(cfg, st: TreeState, pend: RepairQueue):
    """Create a new root for (at most one) pending separator whose split
    node *was* the root."""
    lvl_arr = pend.level + 1
    tr = traverse(cfg, st, jnp.maximum(pend.sep, KEY_MIN),
                  stop_level_arr=lvl_arr)
    no_parent = pend.valid & (st.level[tr.leaf].astype(jnp.int32)
                              != lvl_arr)
    any_rs = jnp.any(no_parent)
    pick = jnp.argmax(no_parent)                      # lowest lane wins
    b = pend.sep.shape[0]
    is_pick = (jnp.arange(b) == pick) & no_parent

    # allocate the new root on the round-robin MS
    ms = (st.alloc_rr % cfg.n_ms).astype(jnp.int32)
    room = st.alloc_next[ms] < cfg.alloc_cap
    do_lane = is_pick & room
    do = jnp.any(do_lane)
    new_root = jnp.where(do, ms * cfg.nodes_per_ms + st.alloc_next[ms],
                         jnp.int32(cfg.park_row))
    f = cfg.fanout
    rk = jnp.full((b, f), EMPTY_KEY, jnp.int32)
    rk = rk.at[:, 0].set(KEY_MIN)
    rk = rk.at[:, 1].set(pend.sep)
    rv = jnp.full((b, f), NULL_PTR, jnp.int32)
    rv = rv.at[:, 0].set(st.root)
    rv = rv.at[:, 1].set(pend.child)
    row = jnp.where(do_lane, new_root, jnp.int32(cfg.park_row))
    st = st._replace(
        keys=_scatter_rowF(cfg, st.keys, row, rk, do_lane),
        vals=_scatter_rowF(cfg, st.vals, row, rv, do_lane),
        level=_scatter_row1(cfg, st.level, row, pend.level + 1, do_lane),
        fence_lo=_scatter_row1(cfg, st.fence_lo, row,
                               jnp.full((b,), KEY_MIN, jnp.int32), do_lane),
        fence_hi=_scatter_row1(cfg, st.fence_hi, row,
                               jnp.full((b,), INT_MAX, jnp.int32), do_lane),
    )
    st = st._replace(
        alloc_next=st.alloc_next.at[ms].add(jnp.where(do, 1, 0)),
        alloc_rr=st.alloc_rr + jnp.where(do, 1, 0),
        root=jnp.where(do, new_root, st.root),
        height=jnp.where(do, st.height + 1, st.height),
    )
    served = do_lane
    return st, pend._replace(valid=pend.valid & ~served), jnp.where(do, 1, 0)


def run_repair(cfg, st: TreeState, pend: RepairQueue, iters: int = 2):
    """Complete half-splits: push pending separators into parents.

    Each iteration handles ≤1 root split and ≤1 separator per parent, and
    splits as many full parents as the queue has free slots for their own
    separators (at the next level).  A separator whose full parent could
    not split stays in the queue for the next iteration, as does the
    remainder — safe under B-link semantics.  Returns ``(state, queue,
    internal splits, root splits, separators dropped)``.
    """
    n_internal = jnp.int32(0)
    n_root = jnp.int32(0)
    n_dropped = jnp.int32(0)
    for _ in range(iters):
        with jax.named_scope("run_repair"):
            st, pend, rs = _root_split(cfg, st, pend)
            n_root = n_root + rs
            tr = traverse(cfg, st, jnp.maximum(pend.sep, KEY_MIN),
                          stop_level_arr=pend.level + 1)
            parent = tr.leaf
            ok_level = st.level[parent].astype(jnp.int32) == pend.level + 1
            rank, _ = _rank_by(parent, pend.valid & ok_level, cfg.n_nodes)
            sel = pend.valid & ok_level & (rank == 0)
            st, done, full = _internal_insert_once(cfg, st, parent,
                                                   pend.sep, pend.child, sel)
            pend = pend._replace(valid=pend.valid & ~done)
            # split the full parents the queue has room for; their
            # separators enter the queue in the slots of lanes that just
            # completed (compaction via free slots)
            st, psep, pchild, did, _ = _split_nodes(
                cfg, st, parent, _first(full, _free_slots(pend)))
            n_internal = n_internal + jnp.sum(did.astype(jnp.int32))
            pend, dropped = _enqueue_pending(
                pend, psep, pchild, st.level[parent].astype(jnp.int32), did)
            n_dropped = n_dropped + dropped
    return st, pend, n_internal, n_root, n_dropped


# --------------------------------------------------------------------------
# the full write phase
# --------------------------------------------------------------------------

def write_phase(cfg: TreeConfig, st: TreeState, keys, vals, is_delete,
                active, cs, repair: RepairQueue | None = None,
                split_rounds: int = 2, repair_iters: int = 2):
    """Apply one batch of write ops. Returns (state, done, stats, repair).

    ``done[i]`` False means lane i must be resubmitted (leaf still
    overflowing after ``split_rounds``, or allocator backpressure) — the
    batched analogue of a client retry.
    """
    b = keys.shape[0]
    lane = jnp.arange(b, dtype=jnp.int32)
    if repair is None:
        repair = RepairQueue.empty(b)

    # -- intra-batch dedupe: last op per key wins (DESIGN.md §8) --
    with jax.named_scope("dedupe"):
        parked_key = jnp.where(active, keys, -10 - lane)
        perm = jnp.lexsort((lane, parked_key))
        inv = jnp.argsort(perm)
        ks = parked_key[perm]
        nxt = jnp.concatenate([ks[1:], jnp.full((1,), -7, ks.dtype)])
        last_of_key = (ks != nxt)[inv]
        act = active & last_of_key
        superseded = active & ~last_of_key

    # -- route + conflict groups (lock plane) --
    # NOTE: groups are computed over ALL active lanes (pre-dedupe): every
    # client op contends for the leaf lock in the real system even when a
    # later op overwrites its value — dedupe is an application-plane
    # equivalence, not a contention reducer.
    with jax.named_scope("traverse"):
        tr = traverse(cfg, st, keys)
    with jax.named_scope("group_locks"):
        groups = hocl.group_by_node(cfg, tr.leaf, cs, active)
        lock_stats = hocl.lock_phase_stats(cfg, groups, active)

    # -- classify against the leaf image --
    with jax.named_scope("apply"):
        lk = st.keys[tr.leaf]
        eq = lk == keys[:, None]
        found = jnp.any(eq, axis=1)
        slot = jnp.argmax(eq, axis=1).astype(jnp.int32)
        upd = act & found & ~is_delete
        dele = act & found & is_delete
        miss_del = act & ~found & is_delete
        ins = act & ~found & ~is_delete

        st = _apply_updates_deletes(cfg, st, tr.leaf, slot, vals, upd,
                                    dele)
        st, ins_done, ins_defer = _apply_inserts(cfg, st, tr.leaf, keys,
                                                 vals, ins)

    n_leaf_splits = jnp.int32(0)
    n_same_ms = jnp.int32(0)
    n_internal = jnp.int32(0)
    n_root = jnp.int32(0)
    n_dropped = jnp.int32(0)
    split_mask = jnp.zeros((b,), bool)
    split_same = jnp.zeros((b,), bool)
    split_row = jnp.full((b,), jnp.int32(cfg.park_row))

    # -- split rounds for overflowing leaves --
    for _ in range(split_rounds):
        with jax.named_scope("split"):
            tr2 = traverse(cfg, st, keys)
            rank0, head = _rank_by(tr2.leaf, ins_defer, cfg.n_nodes)
            # the leaf splits take at most half the free slots: the other
            # half is for the parents they fill (on a packed tree each
            # leaf split splits its parent), so the cascade completes in
            # this phase's repair and, with a slot always left, can always
            # drain.  A leaf left unsplit keeps its insert deferred
            rep = _first(ins_defer & (rank0 == 0), _free_slots(repair) // 2)
            st, sep, new_row, did, same = _split_nodes(cfg, st, tr2.leaf,
                                                       rep)
            n_leaf_splits += jnp.sum(did.astype(jnp.int32))
            n_same_ms += jnp.sum(same.astype(jnp.int32))
            split_mask = split_mask | did
            split_same = split_same | same
            split_row = jnp.where(did, new_row, split_row)
        # enqueue separators in the repair queue (free slots)
        with jax.named_scope("enqueue_repairs"):
            repair, dropped = _enqueue_pending(
                repair, sep, new_row, st.level[new_row].astype(jnp.int32),
                did)
            st, repair, ni, nr, nd = run_repair(cfg, st, repair,
                                                iters=repair_iters)
        n_internal += ni
        n_root += nr
        n_dropped += dropped + nd
        # retry the deferred inserts after the splits
        with jax.named_scope("retry_inserts"):
            tr3 = traverse(cfg, st, keys)
            st, done2, ins_defer = _apply_inserts(cfg, st, tr3.leaf, keys,
                                                  vals, ins_defer)
        ins_done = ins_done | done2

    done = (upd | dele | miss_del | ins_done | superseded | ~active)
    stats = WriteStats(
        applied_update=upd, applied_delete=dele,
        applied_insert=ins_done, miss_delete=miss_del,
        superseded=superseded, deferred=active & ~done,
        leaf=tr.leaf, hops=tr.hops,
        local_size=groups.local_size, local_rank=groups.local_rank,
        node_size=groups.node_size, node_rank=groups.node_rank,
        cs_rank=groups.cs_rank, lock_cycles=groups.lock_cycles,
        local_head=groups.local_head,
        cycle_head=groups.cycle_head, chain_end=groups.chain_end,
        split_mask=split_mask,
        split_same_ms=split_same, split_new_row=split_row,
        n_leaf_splits=n_leaf_splits, n_internal_splits=n_internal,
        n_root_splits=n_root, n_split_same_ms=n_same_ms,
        hocl_remote_cas=lock_stats["hocl_remote_cas"],
        flat_remote_cas=lock_stats["flat_remote_cas"],
        handovers=lock_stats["handovers"],
        repair_backlog=jnp.sum(repair.valid.astype(jnp.int32)),
        repair_dropped=n_dropped,
        blink_hops=jnp.sum(jnp.where(active, tr.chased, 0)),
    )
    return st, done, stats, repair
