"""Public API of the Sherman index.

``ShermanIndex`` is the component a database (or a serving stack such as
the paged-KV integration in ``examples/serve_paged.py``) embeds: batched
insert/delete/lookup/range with the paper's full write path, plus per-phase
netsim pricing so every paper metric (throughput, latency percentiles,
doorbell depth, write bytes, retries) falls out of normal use.

Reads route through the functional CS-side index cache
(:mod:`repro.core.cache`): a cache-hit lookup costs one remote leaf read,
a stale hit pays the B-link chase, and a miss retraverses — all three
outcomes are counted (``cache_hits``/``cache_misses``/``cache_stale``) and
priced.

Shape stability (the jit-cache discipline every driver relies on):

* every batch entering a jitted entry point is **padded to a power-of-two
  bucket** (:func:`bucket_size`) with the padding lanes masked inactive,
  so ``_jit_write_phase``/``_jit_lookup``/``_jit_range``/``_jit_repair``
  each compile once per bucket instead of once per batch length;
* the repair queue has a **fixed capacity** (:data:`REPAIR_CAP`)
  independent of the batch size, so repair steps never trigger a
  shape-churn recompile.  Nothing is dropped at the capacity: a node
  splits only when the queue has a free slot for its separator, so a full
  parent waits (its child's separator stays queued) and a full leaf waits
  (its insert stays deferred to the next phase); a split round's leaf
  splits take at most half the free slots and leave the rest to the
  cascade, so the drain always makes progress;
* the tree state (and the repair queue) are **donated** to the jitted
  phases, so XLA updates them in place instead of copying the pool every
  phase.
"""
from __future__ import annotations

import functools
import itertools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import netsim, ops, write
from repro.core.cache import IndexCache
from repro.core.netsim import FG_PLUS, SHERMAN, Features, NetConfig
from repro.core.ref import OracleIndex
from repro.core.tree import TreeConfig, TreeState, bulkload, empty_state
from repro.core.write import RepairQueue
from repro.obs.host import fetch

__all__ = ["ShermanIndex", "TreeConfig", "Features", "FG_PLUS", "SHERMAN",
           "OracleIndex", "IndexCache", "REPAIR_CAP", "bucket_size",
           "pad_to_bucket"]

#: Fixed capacity of every driver-owned repair queue.  Independent of the
#: batch size so ``_jit_repair``/``_jit_write_phase`` compile once.  Splits
#: wait for a free slot rather than drop a separator (``core/write.py``),
#: so a burst of more splits than this takes more repair steps, not a
#: parent that never learns of its child.
REPAIR_CAP = 256

#: Smallest dispatch bucket; batches below this pad up to it.
BUCKET_MIN = 16


def bucket_size(n: int) -> int:
    """Smallest power-of-two bucket holding ``n`` lanes (>= BUCKET_MIN)."""
    return max(BUCKET_MIN, 1 << max(0, int(n) - 1).bit_length())


def pad_to_bucket(arr, m: int, fill=0):
    """Pad a [n, ...] batch array to bucket length ``m`` with ``fill``.
    A NumPy array is padded on the host, so that a batch of a new length
    compiles nothing on the device."""
    n = arr.shape[0]
    if n == m:
        return arr
    xp = np if isinstance(arr, np.ndarray) else jnp
    pad = xp.full((m - n,) + arr.shape[1:], fill, arr.dtype)
    return xp.concatenate([arr, pad])


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1, 7))
def _jit_write_phase(cfg, st, keys, vals, is_delete, active, cs, repair):
    return write.write_phase(cfg, st, keys, vals, is_delete, active, cs,
                             repair)


@functools.partial(jax.jit, static_argnums=(0,))
def _jit_lookup(cfg, st, keys):
    return ops.lookup_batch(cfg, st, keys)


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def _jit_range(cfg, st, lo, count, max_leaves):
    return ops.range_batch(cfg, st, lo, count, max_leaves)


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def _jit_range_cached(cfg, st, lo, count, max_leaves, cache_image):
    return ops.range_batch(cfg, st, lo, count, max_leaves, cache_image)


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1, 2))
def _jit_repair(cfg, st, repair):
    """One fixed-shape repair step.  Returns the post-step pending count
    so the drain loop can sync the host every k iterations instead of
    forcing a device round trip per iteration, and the separators the
    step dropped (0, see :data:`REPAIR_CAP`)."""
    st, repair, ni, nr, nd = write.run_repair(cfg, st, repair, iters=2)
    pending = jnp.sum(repair.valid.astype(jnp.int32))
    return st, repair, ni, nr, pending, nd


def run_repair_drain(cfg, state, repair, sync_every: int = 4):
    """Drain a repair queue with k-batched host syncs.

    Runs :func:`_jit_repair` steps back-to-back and checks the jitted
    step's pending count and split counters on the host only every
    ``sync_every`` iterations, in one read of the steps' counts.  The
    drain runs until the queue is empty, or until a check finds that the
    steps since the last one neither shrank the queue nor split a node.
    Returns ``(state, repair, n_internal, n_root, backlog, n_dropped)``;
    ``backlog`` is the host-side pending count after the drain (0 when it
    completed).  Shared by ``ShermanIndex.drain_repairs`` and the cluster
    scheduler's wave-scope drain so their sync semantics cannot diverge.
    """
    n_int = n_root = n_drop = 0
    window, last = [], None
    for it in itertools.count():
        state, repair, ni, nr, pending, nd = _jit_repair(cfg, state, repair)
        window.append((ni, nr, nd))
        # one step usually clears a write batch's handful of separators,
        # so check after the first step too, then every sync_every
        if it and (it + 1) % sync_every:
            continue
        counts, backlog = fetch((window, pending), "repair.counts")
        ni_w, nr_w, nd_w = (int(sum(c)) for c in zip(*counts))
        n_int, n_root, n_drop = n_int + ni_w, n_root + nr_w, n_drop + nd_w
        window, backlog = [], int(backlog)
        if not backlog or (last is not None and backlog >= last
                           and not (ni_w or nr_w)):
            return state, repair, n_int, n_root, backlog, n_drop
        last = backlog


def write_phase_progress(before: np.ndarray, active, stats) -> bool:
    """Should the write loop run another phase?

    Deferred lanes are client retries: they go again for as long as each
    phase completes a lane or splits a leaf.  A phase that does neither
    means the node pool has no room left, and the batch fails.  Shared by
    ``ShermanIndex._write`` and the cluster scheduler's write wave.
    """
    left = fetch(active, "write.active")
    if not left.any():
        return False
    if left.sum() == before.sum() and \
            not int(fetch(stats.n_leaf_splits, "stats.n_leaf_splits")):
        raise RuntimeError("write batch made no progress; "
                           "node pool exhausted")
    return True


def write_stats_dict(stats: write.WriteStats, active, route_hits,
                     height: int) -> dict:
    """Numpy view of one write phase's per-lane structure — the verb
    plane's input (netsim.price_write_phase / verbs.write_phase_trace).
    Shared with the trace-conservation tests so the two stay in sync."""
    return dict(
        active=np.asarray(active),
        leaf=fetch(stats.leaf, "stats.leaf"),
        local_rank=fetch(stats.local_rank, "stats.local_rank"),
        node_rank=fetch(stats.node_rank, "stats.node_rank"),
        node_size=fetch(stats.node_size, "stats.node_size"),
        cycle_head=fetch(stats.cycle_head, "stats.cycle_head"),
        chain_end=fetch(stats.chain_end, "stats.chain_end"),
        split_lane=fetch(stats.split_mask, "stats.split_mask"),
        split_same_ms=fetch(stats.split_same_ms, "stats.split_same_ms"),
        split_new_row=fetch(stats.split_new_row, "stats.split_new_row"),
        cache_hit=np.asarray(route_hits),
        height=int(height),
        hocl_remote_cas=int(fetch(stats.hocl_remote_cas,
                                  "stats.hocl_remote_cas")),
        flat_remote_cas=int(fetch(stats.flat_remote_cas,
                                  "stats.flat_remote_cas")),
    )


class ShermanIndex:
    """A write-optimized ordered index over a disaggregated node pool."""

    def __init__(self, cfg: TreeConfig, state: TreeState,
                 features: Features = SHERMAN,
                 net: Optional[NetConfig] = None,
                 cache_bytes: int = 64 << 20,
                 cache_levels: Optional[int] = None,
                 cache_sync_every: int = 8,
                 cache_chase_hops: int = 4,
                 cache_kernel: Optional[str] = None):
        self.cfg = cfg
        self.state = state
        self.features = features
        self.net = net or NetConfig()
        self.cache = IndexCache(cfg, cache_bytes, levels=cache_levels,
                                chase_hops=cache_chase_hops,
                                sync_every=cache_sync_every,
                                kernel_mode=cache_kernel)
        self.counters = {
            "phases": 0, "write_ops": 0, "retried_ops": 0, "read_ops": 0,
            "leaf_splits": 0,
            "internal_splits": 0, "root_splits": 0, "split_same_ms": 0,
            "cas_msgs": 0, "handovers": 0, "msgs": 0, "bytes": 0.0,
            "sim_time_s": 0.0, "cache_hits": 0, "cache_misses": 0,
            "cache_stale": 0, "lookup_ops": 0, "lookup_reads": 0,
            "verbs": 0, "doorbells": 0, "hocl_cas": 0, "flat_cas": 0,
        }
        self.latencies_write: list[np.ndarray] = []
        self.latencies_read: list[np.ndarray] = []
        self.doorbells_write: list[np.ndarray] = []
        self.write_bytes: list[np.ndarray] = []
        self._repair = RepairQueue.empty(REPAIR_CAP)
        self._repair_backlog = 0        # host-side mirror, no device sync
        # opt-in observability plane: attach a repro.obs Recorder here and
        # every priced phase captures its per-verb timeline (DESIGN.md §14)
        self.recorder = None

    # -- constructors --------------------------------------------------
    @classmethod
    def build(cls, cfg: TreeConfig, keys, vals, fill: float = 0.8,
              **kw) -> "ShermanIndex":
        return cls(cfg, bulkload(cfg, keys, vals, fill=fill), **kw)

    @classmethod
    def empty(cls, cfg: TreeConfig, **kw) -> "ShermanIndex":
        return cls(cfg, bulkload(cfg, np.zeros(0), np.zeros(0)), **kw)

    # -- helpers --------------------------------------------------------
    def _cs_of(self, n: int, m: int | None = None) -> jnp.ndarray:
        """Lane -> compute-server assignment (contiguous blocks).

        Block size comes from the *real* batch length ``n`` so the
        distribution over CSs matches the unpadded batch; the returned
        array spans the dispatch bucket ``m`` (padding lanes get a label
        too, but they are inactive everywhere)."""
        per = max(1, -(-n // self.cfg.n_cs))
        return (jnp.arange(m or n, dtype=jnp.int32) // per) % self.cfg.n_cs

    def _rec(self, phase: str):
        """The phase's capture target: label it and place it at the
        accumulated sim time (each closed-loop phase is its own relative
        timeline; the cursor makes the captured segments tile)."""
        r = self.recorder
        if r is not None:
            r.set_phase(phase)
            r.sync_cursor(self.counters["sim_time_s"])
        return r

    def _price_cache_maintenance(self):
        """Charge the image fills / version sweeps the cache performed
        since the last drain by replaying their MAINT/SYNC verbs."""
        node_rd, small_rd = self.cache.take_maintenance()
        if not (node_rd or small_rd):
            return
        sim = netsim.price_maintenance(node_rd, small_rd, self.features,
                                       self.net, self.cfg,
                                       rows_ms=self.cache.rows_ms(),
                                       recorder=self._rec("maint"))
        self._charge(sim)

    def _charge(self, priced: dict):
        """Accumulate one simulated trace's totals into the counters."""
        c = self.counters
        c["msgs"] += priced["msgs"]
        c["verbs"] += priced["verbs"]
        c["doorbells"] += priced["doorbells"]
        c["bytes"] += priced["bytes"]
        c["sim_time_s"] += priced["makespan_s"]

    def _price_write(self, stats: write.WriteStats, active, hits):
        sd = write_stats_dict(stats, active, hits, int(self.state.height))
        priced = netsim.price_write_phase(sd, self.features, self.net,
                                          self.cfg,
                                          recorder=self._rec("write"))
        self.latencies_write.append(priced["latency_s"])
        self.doorbells_write.append(priced["lane_doorbells"])
        self.write_bytes.append(priced["write_bytes"])
        self._charge(priced)
        c = self.counters
        c["phases"] += 1
        c["cas_msgs"] += priced["cas_msgs"]
        c["hocl_cas"] += sd["hocl_remote_cas"]
        c["flat_cas"] += sd["flat_remote_cas"]
        c["leaf_splits"] += int(stats.n_leaf_splits)
        c["internal_splits"] += int(stats.n_internal_splits)
        c["root_splits"] += int(stats.n_root_splits)
        c["split_same_ms"] += int(stats.n_split_same_ms)
        c["handovers"] += int(stats.handovers)

    # -- write ops -------------------------------------------------------
    def _write(self, keys, vals, is_delete):
        keys = jnp.asarray(keys, jnp.int32)
        n = keys.shape[0]
        if n == 0:
            return
        m = bucket_size(n)
        vals = jnp.asarray(vals, jnp.int32) if vals is not None else \
            jnp.zeros((n,), jnp.int32)
        keys = pad_to_bucket(keys, m)
        vals = pad_to_bucket(vals, m)
        is_del = jnp.broadcast_to(jnp.asarray(is_delete, bool), (m,))
        cs = self._cs_of(n, m)
        active = jnp.arange(m) < n           # padding lanes stay inactive
        # the writes' traversal leg routes through the CS cache like a read;
        # probe once per batch (retry phases reuse the same routing)
        if self.cache.enabled:
            route_hits = self.cache.route_hits(self.state, keys, n_valid=n)
        else:
            route_hits = np.zeros(m, bool)
        # each client op counts once; lanes resubmitted by later phases
        # are tracked separately so throughput isn't inflated
        self.counters["write_ops"] += n
        for phase_no in itertools.count():
            self.state, done, stats, self._repair = _jit_write_phase(
                self.cfg, self.state, keys, vals, is_del, active, cs,
                self._repair)
            act_np = np.asarray(active)
            self._price_write(stats, act_np, route_hits)
            if phase_no:
                self.counters["retried_ops"] += int(act_np.sum())
            # invalidation hook: feed this phase's split outputs to the cache
            self.cache.note_splits(int(stats.n_leaf_splits),
                                   int(stats.n_internal_splits),
                                   int(stats.n_root_splits), self.state)
            self._repair_backlog = int(stats.repair_backlog)
            active = active & ~done
            if not write_phase_progress(act_np, active, stats):
                break
        self.drain_repairs()
        self._price_cache_maintenance()

    def drain_repairs(self, sync_every: int = 4):
        """Complete any outstanding B-link half-splits.

        The jitted repair step returns the post-step pending count, so
        the loop touches the host only every ``sync_every`` iterations
        (and not at all when the last write phase reported an empty
        queue) instead of forcing a device sync per iteration.
        """
        if not self._repair_backlog:
            return
        (self.state, self._repair, n_int, n_root,
         self._repair_backlog, _) = run_repair_drain(
            self.cfg, self.state, self._repair, sync_every)
        self.counters["internal_splits"] += n_int
        self.counters["root_splits"] += n_root
        if n_int or n_root:
            self.cache.note_splits(0, n_int, n_root, self.state)
        if self._repair_backlog:
            raise RuntimeError("repair queue did not drain")

    def insert(self, keys, vals):
        """Insert or update (the paper's combined 'insert')."""
        self._write(keys, vals, False)

    def delete(self, keys):
        self._write(keys, None, True)

    # -- read ops ----------------------------------------------------------
    def lookup(self, keys):
        keys = jnp.asarray(keys, jnp.int32)
        n = keys.shape[0]
        m = bucket_size(n)
        kp = pad_to_bucket(keys, m)
        c = self.counters
        active = np.arange(m) < n
        if self.cache.enabled:
            res, cst = self.cache.lookup(self.state, kp, n_valid=n)
            hit, stale = cst["hit"][:n], cst["stale"][:n]
            c["cache_hits"] += int((hit & ~stale).sum())
            c["cache_misses"] += int((~hit).sum())
            c["cache_stale"] += int(stale.sum())
            sd = dict(active=active,
                      cache_hit=cst["hit"] & ~cst["stale"],
                      remote_reads=cst["remote_reads"],
                      leaf=np.asarray(res.leaf),
                      height=int(self.state.height))
        else:
            res = _jit_lookup(self.cfg, self.state, kp)
            c["cache_misses"] += n
            sd = dict(active=active,
                      cache_hit=np.zeros(m, bool),
                      leaf=np.asarray(res.leaf),
                      height=int(self.state.height))
        priced = netsim.price_read_phase(sd, self.features, self.net,
                                         self.cfg,
                                         recorder=self._rec("read"))
        self.latencies_read.append(priced["latency_s"])
        c["read_ops"] += n
        c["lookup_ops"] += n
        c["lookup_reads"] += int(np.asarray(priced["lane_doorbells"]).sum())
        self._charge(priced)
        self._price_cache_maintenance()
        return np.asarray(res.value)[:n], np.asarray(res.found)[:n]

    def range(self, lo, count: int, max_leaves: Optional[int] = None):
        lo = jnp.asarray(lo, jnp.int32)
        n = lo.shape[0]
        m = bucket_size(n)
        lo_p = pad_to_bucket(lo, m)
        if max_leaves is None:
            # Leaves may be sparse (deletes don't merge — §5.3 notes the same
            # partial-occupancy artifact), so scan generously.
            max_leaves = max(4, count)
        # the scan's initial descent consults the CS cache like a lookup
        if self.cache.enabled:
            res = _jit_range_cached(self.cfg, self.state, lo_p, count,
                                    max_leaves,
                                    self.cache.image(self.state))
            hits = np.asarray(res.start_hit)
            self.cache.note_hits(hits[:n])
        else:
            res = _jit_range(self.cfg, self.state, lo_p, count, max_leaves)
            hits = np.zeros(m, bool)
        n_leaves = np.asarray(res.leaves_read)
        priced = netsim.price_read_phase(
            dict(active=np.arange(m) < n, cache_hit=hits,
                 retries=np.maximum(n_leaves - 1, 0),  # empty scans read 0
                 leaf=np.asarray(res.start_leaf), scan=True,
                 height=int(self.state.height)),
            self.features, self.net, self.cfg,
            recorder=self._rec("scan"))
        self.latencies_read.append(priced["latency_s"])
        self.counters["read_ops"] += n
        self._charge(priced)
        self._price_cache_maintenance()
        return (np.asarray(res.keys)[:n], np.asarray(res.vals)[:n],
                np.asarray(res.n)[:n])

    # -- reporting ---------------------------------------------------------
    def latency_percentiles(self, kind: str = "write"):
        arrs = self.latencies_write if kind == "write" else \
            self.latencies_read
        if not arrs:
            return {}
        lat = np.concatenate(arrs)
        return {p: float(np.percentile(lat, p)) * 1e6
                for p in (50, 90, 99)}   # µs

    def throughput_mops(self) -> float:
        """Ops per simulated second.  0.0 before any op has been priced —
        never ``inf``, which would leak non-standard ``Infinity`` tokens
        into the BENCH json exports."""
        t = self.counters["sim_time_s"]
        n = self.counters["write_ops"] + self.counters["read_ops"]
        return n / t / 1e6 if t else 0.0
