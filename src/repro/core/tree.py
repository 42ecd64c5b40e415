"""Sherman tree state: a disaggregated node pool as a JAX pytree.

The disaggregated memory pool of the paper (a set of memory servers, each
exposing registered memory regions) is modelled as a struct-of-arrays node
pool.  Row ``i`` of every array is one tree node; the owning memory server is
``i // nodes_per_ms`` (contiguous blocks, so the pool shards cleanly over the
"mem" mesh axis in :mod:`repro.core.sharded`).

Pointers follow the paper's 64-bit = 16-bit MS id + 48-bit address split —
here a pointer is simply the global row index (int32), from which the MS id
is derived.  ``NULL_PTR`` (-1) is the null pointer.

Node layout (paper Fig. 8):

* leaf:      FNV | [FEV, key, value, REV] * fanout | RNV   (entries UNSORTED)
* internal:  FNV | [key, child] * fanout | RNV             (entries SORTED)

Internal nodes use the *separator* representation: entry ``(k_j, c_j)`` means
child ``c_j`` covers keys in ``[k_j, k_{j+1})``; the first separator equals
the node's lower fence.  Every node carries fence keys and its level so that
readers can detect stale cache entries / freed nodes (paper §4.2.3/§4.2.4).

The global lock table (GLT) — the paper's NIC on-chip lock array — is a small
``uint16`` array per MS (131072 locks by default, 16-bit thanks to masked
CAS), kept separate from the node pool.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

NULL_PTR = jnp.int32(-1)
EMPTY_KEY = jnp.int32(-1)          # slot never used / deleted ("null" key)
KEY_MIN = -(2**31) + 2             # lower fence of leftmost nodes
KEY_MAX = 2**31 - 1                # upper fence of rightmost nodes
VERSION_MOD = 16                   # 4-bit versions


@dataclasses.dataclass(frozen=True)
class TreeConfig:
    """Static configuration of a Sherman tree."""

    n_ms: int = 4                  # number of memory servers (mem shards)
    nodes_per_ms: int = 4096       # node-pool rows per MS
    fanout: int = 16               # entries per node (leaf and internal)
    n_locks_per_ms: int = 131072   # GLT entries per MS (paper: 256KB/16bit)
    max_height: int = 6            # static traversal bound
    handover_max: int = 4          # MAX_DEPTH consecutive lock handovers
    n_cs: int = 4                  # compute servers (data shards)
    # Modeled wire sizes (bytes) for netsim accounting; defaults follow the
    # paper's 1KB nodes with 8B keys / 8B values and 4-bit paired versions.
    key_bytes: int = 8
    value_bytes: int = 8

    @property
    def n_nodes(self) -> int:
        return self.n_ms * self.nodes_per_ms

    @property
    def park_row(self) -> int:
        """Reserved row used as the scatter target of masked-out lanes.

        The last row of every MS is reserved (never allocated) so that
        masked scatters can always be parked on a row that carries no live
        node, on every mem shard.  ``park_row`` is the global instance."""
        return self.n_nodes - 1

    @property
    def alloc_cap(self) -> int:
        """Allocatable rows per MS (last row reserved for parking)."""
        return self.nodes_per_ms - 1

    @property
    def entry_bytes(self) -> int:
        # key + value + FEV/REV pair (1 byte total) — the paper's 17B.
        return self.key_bytes + self.value_bytes + 1

    @property
    def node_bytes(self) -> int:
        # header: FNV/RNV (1B), fences (2 keys), level+free+sibling (10B)
        return self.fanout * self.entry_bytes + 2 * self.key_bytes + 11

    def ms_of(self, node_id):
        return node_id // self.nodes_per_ms

    def lock_index(self, node_id):
        """Hash a node address into its MS's global lock table (paper l.5)."""
        return node_id % self.n_locks_per_ms


class TreeState(NamedTuple):
    """The disaggregated tree: one pytree, shardable over the mem axis."""

    keys: jax.Array          # [N, F] int32; EMPTY_KEY = empty slot
    vals: jax.Array          # [N, F] int32; leaf: value, internal: child ptr
    fev: jax.Array           # [N, F] uint8 front entry versions (4-bit)
    rev: jax.Array           # [N, F] uint8 rear  entry versions (4-bit)
    fnv: jax.Array           # [N]    uint8 front node version
    rnv: jax.Array           # [N]    uint8 rear  node version
    level: jax.Array         # [N]    int8  (0 = leaf, -1 = unallocated)
    fence_lo: jax.Array      # [N]    int32 inclusive lower fence
    fence_hi: jax.Array      # [N]    int32 exclusive upper fence
    sibling: jax.Array       # [N]    int32 right sibling (B-link), NULL_PTR
    free_bit: jax.Array      # [N]    bool  True = node freed (paper §4.2.4)
    glt: jax.Array           # [n_ms, n_locks] uint16 global lock tables
    root: jax.Array          # []     int32
    height: jax.Array        # []     int32 (#levels; 1 = root is a leaf)
    alloc_next: jax.Array    # [n_ms] int32 per-MS bump pointer
    alloc_rr: jax.Array      # []     int32 round-robin MS cursor


def empty_state(cfg: TreeConfig, xp=jnp) -> TreeState:
    """An all-unallocated pool; ``xp=np`` builds it on the host."""
    n, f = cfg.n_nodes, cfg.fanout
    return TreeState(
        keys=xp.full((n, f), EMPTY_KEY, xp.int32),
        vals=xp.full((n, f), NULL_PTR, xp.int32),
        fev=xp.zeros((n, f), xp.uint8),
        rev=xp.zeros((n, f), xp.uint8),
        fnv=xp.zeros((n,), xp.uint8),
        rnv=xp.zeros((n,), xp.uint8),
        level=xp.full((n,), -1, xp.int8),
        fence_lo=xp.zeros((n,), xp.int32),
        fence_hi=xp.zeros((n,), xp.int32),
        sibling=xp.full((n,), NULL_PTR, xp.int32),
        free_bit=xp.zeros((n,), bool),
        glt=xp.zeros((cfg.n_ms, cfg.n_locks_per_ms), xp.uint16),
        root=xp.int32(0),
        height=xp.int32(0),
        alloc_next=xp.zeros((cfg.n_ms,), xp.int32),
        alloc_rr=xp.int32(0),
    )


def bulkload(cfg: TreeConfig, keys: np.ndarray, vals: np.ndarray,
             fill: float = 0.8) -> TreeState:
    """Build a tree from sorted unique keys, each leaf ``fill`` full.

    Host-side (numpy) setup, mirroring the paper's bulkload of 1B entries 80%
    full before each benchmark.  Leaves are written *sorted* here — unsortedness
    only arises from subsequent inserts, which is also true of the paper.
    Nodes are allocated leaves first, then each internal level bottom-up,
    by the two-stage allocator: round-robin over the MSs, a bump pointer
    within each.  On an empty pool that puts the t-th node on MS
    ``t % n_ms`` at local row ``t // n_ms``.
    """
    keys = np.asarray(keys, np.int32)
    vals = np.asarray(vals, np.int32)
    order = np.argsort(keys, kind="stable")
    keys, vals = keys[order], vals[order]
    assert keys.ndim == 1 and keys.shape == vals.shape
    if keys.size and np.any(keys[1:] == keys[:-1]):
        raise ValueError("bulkload requires unique keys")

    f = cfg.fanout
    per_leaf = max(1, min(f, int(round(f * fill))))
    n_leaves = max(1, -(-keys.size // per_leaf))
    widths = [n_leaves]                      # nodes per level, leaves up
    while widths[-1] > 1:
        widths.append(-(-widths[-1] // f))
    total = sum(widths)
    if total > cfg.n_ms * cfg.alloc_cap:
        raise RuntimeError("node pool exhausted during bulkload")
    t = np.arange(total, dtype=np.int64)
    rows = ((t % cfg.n_ms) * cfg.nodes_per_ms + t // cfg.n_ms
            ).astype(np.int32)
    st = empty_state(cfg, np)

    # ---- leaf level: consecutive per_leaf-key chunks ----
    nid = rows[:n_leaves]
    pad = n_leaves * per_leaf - keys.size
    st.keys[nid, :per_leaf] = np.pad(keys, (0, pad), constant_values=-1
                                     ).reshape(n_leaves, per_leaf)
    st.vals[nid, :per_leaf] = np.pad(vals, (0, pad), constant_values=-1
                                     ).reshape(n_leaves, per_leaf)
    lo = np.full(n_leaves, KEY_MIN, np.int64)
    lo[1:] = keys[per_leaf::per_leaf]
    off = n_leaves
    # ---- internal levels bottom-up: groups of f consecutive nodes ----
    for lvl, width in enumerate(widths):
        if lvl:
            nid = rows[off:off + width]
            child, child_lo = rows[off - widths[lvl - 1]:off], lo
            grp = np.arange(child.size) // f
            slot = np.arange(child.size) % f
            st.keys[nid[grp], slot] = child_lo
            st.vals[nid[grp], slot] = child
            lo = child_lo[::f]
            off += width
        st.level[nid] = lvl
        st.fence_lo[nid] = lo
        st.fence_hi[nid] = np.append(lo[1:], KEY_MAX)
        st.sibling[nid[:-1]] = nid[1:]

    return TreeState(
        *[jnp.asarray(a) for a in st[:12]],
        root=jnp.int32(rows[total - 1]), height=jnp.int32(len(widths)),
        alloc_next=jnp.asarray(np.bincount(t % cfg.n_ms,
                                           minlength=cfg.n_ms), jnp.int32),
        alloc_rr=jnp.int32(total))
