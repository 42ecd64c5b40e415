"""CS-side index cache (paper §4.2.3): a functional replicated image of the
internal tree levels, with versioned invalidation.

Each compute server keeps an in-memory image of the internal B+Tree levels
(keys + child pointers + the node version observed at fill time) so that a
lookup descends *locally* and issues exactly **one** remote leaf read on a
cache hit.  The remote read is validated by the two-level version protocol
(FNV/RNV + entry versions, paper Fig. 9) and by the leaf's fence keys; a
stale cache entry — e.g. a leaf that split after the image was taken — is
recovered by the B-link sibling chase, falling back to a full root-to-leaf
retraversal when the chase budget is exhausted (paper §4.2.1/§4.2.3).

Coherence protocol (documented in docs/DESIGN.md §9):

1. **Fill/refresh** — snapshot all internal nodes top-down within the byte
   budget (top levels always cached; level-1 nodes evicted first when the
   budget is short), recording each node's FNV.
2. **Validate-on-read** — every cached descent ends in one remote leaf read
   checked with FNV/RNV, the free bit, the level, and the fence keys.
3. **Stale traversal** — a fence miss triggers the sibling chase
   (``chase_hops`` bound) and then a root retraversal; the detection lazily
   invalidates the covering cached entry, exactly like the paper's CS-side
   invalidation.
4. **Version sync** — split outputs from :mod:`repro.core.write` drive a
   periodic sweep that re-reads the FNVs of all cached rows and invalidates
   entries whose version moved (a root split forces a full refresh).

The descent and the leaf probe are shape-static JAX; on a TPU the hot leaf
search runs through the Pallas kernel in
:mod:`repro.kernels.leaf_search.kernel`.  Elsewhere it runs the pure-jnp
oracle :mod:`repro.kernels.leaf_search.ref`; CPU tests run the kernel in
``interpret`` mode against it.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.ops import LookupResult, traverse
from repro.core.tree import EMPTY_KEY, NULL_PTR, TreeConfig, TreeState

ROW_SENTINEL = np.int32(2**31 - 1)     # "no row" padding in the sorted image


class CacheStats(NamedTuple):
    """Per-lane cache outcome of one batched cached lookup."""
    hit: jax.Array           # [B] bool — descent resolved inside the cache
    stale: jax.Array         # [B] bool — hit, but the leaf image was stale
    remote_reads: jax.Array  # [B] int32 — node reads a real CS would issue


# --------------------------------------------------------------------------
# image construction (rows chosen on the host, gathered on the device)
# --------------------------------------------------------------------------

@jax.jit
def _take_rows(arrays: tuple, rows) -> tuple:
    """Gather ``rows`` of each pool array on the device."""
    return tuple(a[rows] for a in arrays)


def fill_image(cfg: TreeConfig, st: TreeState, levels: Optional[int] = None,
               max_rows: Optional[int] = None) -> tuple[dict, int]:
    """Snapshot the top ``levels`` internal levels into a replicated image.

    Returns ``(image, evicted)``: a dict of jnp arrays (a pytree, so it
    passes through jit and shard_map) and the number of nodes dropped for
    the row budget.  The image holds sorted global ``rows`` (padded with
    ``ROW_SENTINEL``), their
    ``keys``/``vals``/``level``, a ``valid`` mask, the ``fnv`` observed at
    fill time, and the ``root``.  Rows are chosen top-down so the upper
    levels are always cached and level-1 nodes are the first evicted when
    ``max_rows`` is short (paper §4.2.3's two cache types).
    """
    height = int(st.height)
    if levels is None:
        levels = max(0, height - 1)          # every internal level
    level = np.asarray(st.level)
    free = np.asarray(st.free_bit)
    lo_level = max(1, height - levels)
    cand = np.nonzero((level >= lo_level) & ~free)[0].astype(np.int32)
    # top-down: higher levels first, row order within a level
    order = np.lexsort((cand, -level[cand].astype(np.int64)))
    cand = cand[order]
    if max_rows is None:
        max_rows = max(1, cand.shape[0])
    kept = np.sort(cand[:max_rows])
    evicted = max(0, cand.shape[0] - max_rows)
    pad = max_rows - kept.shape[0]
    rows = np.concatenate([kept, np.full(pad, ROW_SENTINEL, np.int32)])
    # gather the kept rows where the pool lives: only the image, never the
    # whole [N, F] pool, crosses to the host
    keys, vals, lv, fnv = _take_rows(
        (st.keys, st.vals, st.level, st.fnv),
        np.clip(rows, 0, cfg.n_nodes - 1))
    img = dict(
        rows=jnp.asarray(rows),
        keys=keys,
        vals=vals,
        level=lv,
        valid=jnp.asarray(rows != ROW_SENTINEL),
        fnv=fnv,
        # a host copy: the write phases donate the tree state, so an alias
        # of ``st.root`` would be deleted under the image by the next write
        root=np.int32(st.root),
    )
    return img, evicted


# --------------------------------------------------------------------------
# cached descent + validated lookup (pure JAX, shape-static)
# --------------------------------------------------------------------------

def descend_image(image: dict, qkeys: jax.Array, max_steps: int
                  ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Route ``qkeys`` through the cached internal levels.

    Returns ``(target, hit, depth)``: for hit lanes (descent stayed inside
    cached+valid nodes down to a level-1 node) ``target`` is the predicted
    leaf; for miss lanes it is the *frontier* — the first uncached node on
    the path (the root when even the root image is gone) — from which a
    real CS resumes its remote descent.  ``depth`` counts the cached
    descents, so a miss is priced as the remaining ``height - depth``
    remote reads.
    """
    crows, cvalid = image["rows"], image["valid"]
    ckeys, cvals, clevel = image["keys"], image["vals"], image["level"]
    b = qkeys.shape[0]
    node = jnp.broadcast_to(image["root"], (b,)).astype(jnp.int32)
    leaf = jnp.zeros((b,), jnp.int32)
    done = jnp.zeros((b,), bool)
    dead = jnp.zeros((b,), bool)
    depth = jnp.zeros((b,), jnp.int32)
    for _ in range(max_steps):
        pos = jnp.clip(jnp.searchsorted(crows, node), 0,
                       crows.shape[0] - 1)
        ok = (crows[pos] == node) & cvalid[pos]
        lv = clevel[pos].astype(jnp.int32)
        nk = ckeys[pos]
        nv = cvals[pos]
        occupied = nk != EMPTY_KEY
        le = occupied & (nk <= qkeys[:, None])
        j = jnp.maximum(jnp.sum(le.astype(jnp.int32), axis=1) - 1, 0)
        child = jnp.take_along_axis(nv, j[:, None], axis=1)[:, 0]
        live = ~done & ~dead
        reach = live & ok & (lv == 1) & (child != NULL_PTR)
        leaf = jnp.where(reach, child, leaf)
        done = done | reach
        dead = dead | (live & (~ok | (ok & (lv <= 0))))
        step = live & ok & (lv >= 1)
        depth = depth + step.astype(jnp.int32)
        node = jnp.where(live & ok & (lv > 1), child, node)
    return jnp.where(done, leaf, node), done, depth


def _leaf_probe(st: TreeState, leaf: jax.Array, qkeys: jax.Array,
                kernel_mode: str) -> LookupResult:
    """Search the fetched leaf images: Pallas kernel or jnp reference.

    ``kernel_mode``: ``"pallas"`` (compiled, TPU), ``"interpret"``
    (Pallas interpreter — used by CPU tests for kernel parity), ``"ref"``
    (the pure-jnp oracle from :mod:`repro.kernels.leaf_search.ref`).
    """
    args = (qkeys, st.keys[leaf], st.vals[leaf], st.fev[leaf], st.rev[leaf],
            st.fnv[leaf].astype(jnp.int32), st.rnv[leaf].astype(jnp.int32),
            st.free_bit[leaf].astype(jnp.int32))
    if kernel_mode == "ref" or qkeys.shape[0] == 0:  # kernel needs a tile
        from repro.kernels.leaf_search.ref import leaf_search_ref
        value, found, cons = leaf_search_ref(*args)
    else:
        from repro.kernels.leaf_search.kernel import leaf_search
        b = qkeys.shape[0]
        bt = 256
        padded = -(-b // bt) * bt if b > bt else b
        if padded != b:                      # pad to the kernel tile
            pad = padded - b
            # pad lanes: query key -2 against all-zero images => no match
            args = tuple(jnp.concatenate(
                [a, jnp.full((pad,) + a.shape[1:], -2 if i == 0 else 0,
                             a.dtype)])
                for i, a in enumerate(args))
        value, found, cons = leaf_search(
            *args, bt=min(bt, padded),
            interpret=(kernel_mode == "interpret"))
        value, found, cons = value[:b], found[:b], cons[:b]
    return LookupResult(value=value, found=found, consistent=cons,
                        leaf=leaf, hops=jnp.zeros_like(leaf))


def leaf_sound(st: TreeState, leaf: jax.Array, keys: jax.Array) -> jax.Array:
    """Is the fetched node a live leaf whose fence range covers ``keys``?
    The shared validation for every cached descent (lookups and scans)."""
    return (st.level[leaf].astype(jnp.int32) == 0) & ~st.free_bit[leaf] & \
        (st.fence_lo[leaf] <= keys) & (keys < st.fence_hi[leaf])


def cached_lookup(cfg: TreeConfig, st: TreeState, image: dict,
                  qkeys: jax.Array, chase_hops: int = 4,
                  kernel_mode: str = "ref"
                  ) -> tuple[LookupResult, CacheStats]:
    """One batched lookup through the cache: local descent, one remote leaf
    read on a hit, B-link chase + root retraversal on staleness.

    Functionally everything is computed full-width (phase-synchronous SIMD);
    ``CacheStats.remote_reads`` counts what a real CS would have issued, and
    is what netsim prices.
    """
    leaf0, hit, depth = descend_image(image, qkeys, cfg.max_height)
    leaf = jnp.where(hit, leaf0, 0)

    # --- the single remote leaf read, validated by fences + B-link chase ---
    chased = jnp.zeros_like(leaf)
    for _ in range(chase_hops):
        beyond = hit & (qkeys >= st.fence_hi[leaf]) & \
            (st.sibling[leaf] != NULL_PTR)
        chased = chased + beyond.astype(jnp.int32)
        leaf = jnp.where(beyond, st.sibling[leaf], leaf)
    sound = hit & leaf_sound(st, leaf, qkeys)

    # --- fallback: full root-to-leaf retraversal for miss/unrecovered
    # lanes; skipped entirely when the whole batch hit (the warm case) ---
    final = lax.cond(
        jnp.all(sound),
        lambda: leaf,
        lambda: jnp.where(sound, leaf, traverse(cfg, st, qkeys).leaf))
    res = _leaf_probe(st, final, qkeys, kernel_mode)

    height = st.height.astype(jnp.int32)
    stale = hit & ((chased > 0) | ~sound)
    # a partial descent resumes remotely from the first uncached level
    miss_reads = jnp.maximum(height - depth, 1)
    reads = jnp.where(sound, 1 + chased,
                      jnp.where(hit, 1 + chased + height, miss_reads))
    return (res._replace(hops=reads),
            CacheStats(hit=hit, stale=stale, remote_reads=reads))


@functools.partial(jax.jit, static_argnums=(0, 4, 5))
def _jit_cached_lookup(cfg, st, image, qkeys, chase_hops, kernel_mode):
    return cached_lookup(cfg, st, image, qkeys, chase_hops, kernel_mode)


@functools.partial(jax.jit, static_argnums=(2,))
def _jit_route(image, qkeys, max_steps):
    return descend_image(image, qkeys, max_steps)


def default_kernel_mode() -> str:
    """Pallas on TPU; the jnp reference oracle elsewhere."""
    return "pallas" if jax.default_backend() == "tpu" else "ref"


# --------------------------------------------------------------------------
# the stateful per-CS cache subsystem
# --------------------------------------------------------------------------

@dataclasses.dataclass
class CacheCounters:
    hits: int = 0            # descent resolved in-cache, leaf read clean
    misses: int = 0          # descent left the cached/valid set
    stale: int = 0           # hit but the leaf image was stale (chase/retrav)
    evictions: int = 0       # nodes dropped at fill for the byte budget
    invalidations: int = 0   # entries invalidated (lazy + version sync)
    fills: int = 0           # full image (re)fills
    sync_sweeps: int = 0     # version-sync sweeps over the cached rows
    remote_reads: int = 0    # leaf/node reads issued by cached lookups
    fill_reads: int = 0      # whole-node reads spent (re)filling the image
    sync_reads: int = 0      # small version reads spent on sync sweeps

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class IndexCache:
    """The per-CS cache: replicated image + counters + coherence policy.

    The single-frontend ``ShermanIndex`` holds one instance standing in
    for every CS's identical replica (modeled footprint is
    ``capacity_bytes`` *per CS*); in the cluster plane each
    ``ClusterNode`` owns its **own** instance with its own staleness
    trajectory (DESIGN.md §11).  ``sync_every`` is the number of
    split-bearing write phases between version sweeps; ``sync_rounds``
    adds a scheduler-round-periodic sweep (see :meth:`end_round`); a
    root split always forces a refresh on the next read.
    """

    def __init__(self, cfg: TreeConfig, capacity_bytes: int = 64 << 20,
                 levels: Optional[int] = None, chase_hops: int = 4,
                 sync_every: int = 8, refresh_frac: float = 0.125,
                 sync_rounds: int = 0,
                 kernel_mode: Optional[str] = None):
        self.cfg = cfg
        self.capacity_bytes = int(capacity_bytes)
        self.capacity_rows = max(1, min(
            self.capacity_bytes // max(cfg.node_bytes, 1), cfg.n_nodes))
        self.levels = levels
        self.chase_hops = int(chase_hops)
        self.sync_every = int(sync_every)
        self.sync_rounds = int(sync_rounds)
        self.refresh_frac = float(refresh_frac)
        self.kernel_mode = kernel_mode or default_kernel_mode()
        self.counters = CacheCounters()
        self._rounds_since_sync = 0
        self._image: Optional[dict] = None
        self._rows = np.zeros(0, np.int32)       # host copy of cached rows
        self._filled = np.zeros(0, bool)
        self._valid = np.zeros(0, bool)
        self._fnv = np.zeros(0, np.uint8)
        self._root = -1
        self._splitty_phases = 0
        self._needs_refresh = True
        self._maint_taken = (0, 0)      # (fill_reads, sync_reads) drained

    # -- image lifecycle ---------------------------------------------------
    @property
    def enabled(self) -> bool:
        return self.capacity_bytes > 0

    @property
    def cached_bytes(self) -> int:
        return int(self._valid.sum()) * self.cfg.node_bytes

    def fill(self, st: TreeState) -> None:
        """(Re)build the image from the current tree state."""
        self._image, evicted = fill_image(
            self.cfg, st, levels=self.levels, max_rows=self.capacity_rows)
        self._rows = np.asarray(self._image["rows"])
        self._filled = self._rows != ROW_SENTINEL
        self._valid = np.asarray(self._image["valid"]).copy()
        self._fnv = np.asarray(self._image["fnv"]).copy()
        self._root = int(st.root)
        self.counters.evictions += evicted
        self.counters.fills += 1
        self.counters.fill_reads += int(self._filled.sum())
        self._splitty_phases = 0
        self._needs_refresh = False

    def image(self, st: TreeState) -> dict:
        if self._image is None or self._needs_refresh or \
                int(st.root) != self._root or self._stale_frac() > \
                self.refresh_frac:
            self.fill(st)
        return self._image

    def _stale_frac(self) -> float:
        n = int(self._filled.sum())
        return (int((self._filled & ~self._valid).sum()) / n) if n else 0.0

    def _set_valid(self, valid: np.ndarray) -> None:
        self._valid = valid
        self._image = dict(self._image, valid=jnp.asarray(valid))
        # an invalid upper-level (or root) row cuts off descent for a huge
        # key range — far more than its 1/rows share of _stale_frac — so
        # losing one forces a refresh rather than waiting on the threshold
        bad = self._filled & ~valid
        if bad.any():
            lv = np.asarray(self._image["level"])
            if (lv[bad] > 1).any() or bad[self._rows == self._root].any():
                self._needs_refresh = True

    # -- invalidation ------------------------------------------------------
    def invalidate_covering(self, keys: np.ndarray) -> int:
        """Lazy invalidation: drop the level-1 entries routing ``keys``
        (the paper's invalidate-on-stale-detection)."""
        if self._image is None or keys.size == 0:
            return 0
        lo = np.asarray(self._image["keys"])[:, 0]   # first separator = lo
        lv = np.asarray(self._image["level"])
        # covering is keyed over ALL filled level-1 entries (valid or
        # already dropped): the entry with max lo <= k covers k
        cand = np.nonzero(self._filled & (lv == 1))[0]
        if cand.size == 0:
            return 0
        order = np.argsort(lo[cand], kind="stable")
        cand = cand[order]
        pos = np.searchsorted(lo[cand], np.unique(keys), side="right") - 1
        cover = np.unique(cand[pos[pos >= 0]])
        hit = cover[self._valid[cover]]
        if hit.size:
            valid = self._valid.copy()
            valid[hit] = False
            self._set_valid(valid)
            self.counters.invalidations += int(hit.size)
        return int(hit.size)

    def sync_versions(self, st: TreeState) -> int:
        """Versioned invalidation: re-read the FNV of every cached row and
        invalidate entries whose version moved since fill.  The sweep's
        wire cost accrues in ``counters.sync_reads`` (one small read per
        cached row) and is drained into netsim by the API's
        ``take_maintenance`` pricing."""
        if self._image is None:
            return 0
        safe = np.clip(self._rows, 0, self.cfg.n_nodes - 1)
        now, freed = jax.device_get(_take_rows((st.fnv, st.free_bit), safe))
        changed = self._valid & ((now != self._fnv) | freed)
        n = int(changed.sum())
        if n:
            self._set_valid(self._valid & ~changed)
            self.counters.invalidations += n
        self.counters.sync_sweeps += 1
        self.counters.sync_reads += int(self._filled.sum())
        self._splitty_phases = 0
        return n

    def end_round(self, st: TreeState) -> None:
        """Cluster-plane coherence tick: one scheduler round elapsed.

        In the multi-CS plane a compute server is *not* fed remote CSs'
        split outputs (``note_splits`` fires only for its own writes); it
        learns of remote structural changes lazily — stale detection on
        its own reads — or through this periodic sweep, one version sync
        every ``sync_rounds`` rounds (0 disables).  The sweep's wire cost
        accrues like any other sync (``counters.sync_reads``) and is
        drained by ``take_maintenance``.
        """
        if not (self.enabled and self.sync_rounds and
                self._image is not None):
            return
        self._rounds_since_sync += 1
        if self._rounds_since_sync >= self.sync_rounds:
            self._rounds_since_sync = 0
            self.sync_versions(st)

    def note_splits(self, n_leaf: int, n_internal: int, n_root: int,
                    st: TreeState) -> None:
        """Invalidation hook: called by the API with the split outputs of
        one write batch (:class:`repro.core.write.WriteStats`)."""
        if not self.enabled or self._image is None:
            return
        if n_root:
            self._needs_refresh = True
            return
        if n_leaf or n_internal:
            self._splitty_phases += 1
            if self.sync_every and self._splitty_phases >= self.sync_every:
                self.sync_versions(st)

    # -- lookups -----------------------------------------------------------
    def lookup(self, st: TreeState, qkeys: jax.Array,
               n_valid: Optional[int] = None) -> tuple[LookupResult, dict]:
        """Batched cached lookup; returns the result plus numpy stats
        (``hit``/``stale``/``remote_reads`` per lane) for netsim.

        ``n_valid`` marks the real batch length when the caller padded
        ``qkeys`` to a dispatch bucket (:func:`repro.core.api.bucket_size`)
        — the returned arrays stay full width, but only the first
        ``n_valid`` lanes touch the counters and the lazy invalidation.
        """
        img = self.image(st)
        res, cst = _jit_cached_lookup(self.cfg, st, img, qkeys,
                                      self.chase_hops, self.kernel_mode)
        hit = np.asarray(cst.hit)
        stale = np.asarray(cst.stale)
        reads = np.asarray(cst.remote_reads)
        k = hit.shape[0] if n_valid is None else int(n_valid)
        self.counters.hits += int((hit[:k] & ~stale[:k]).sum())
        self.counters.misses += int((~hit[:k]).sum())
        self.counters.stale += int(stale[:k].sum())
        self.counters.remote_reads += int(reads[:k].sum())
        if stale[:k].any():                  # lazy invalidation on detection
            self.invalidate_covering(np.asarray(qkeys)[:k][stale[:k]])
        return res, dict(hit=hit, stale=stale, remote_reads=reads)

    def route_hits(self, st: TreeState, qkeys: jax.Array,
                   n_valid: Optional[int] = None) -> np.ndarray:
        """Descent-only hit mask (no state mutation of the counters' stale
        plane) — used to price the traversal leg of write ops.  With
        ``n_valid``, padding lanes beyond it stay out of the counters."""
        if not self.enabled:
            return np.zeros(np.asarray(qkeys).shape[0], bool)
        img = self.image(st)
        _, hit, _ = _jit_route(img, qkeys, self.cfg.max_height)
        hit = np.asarray(hit)
        self.note_hits(hit if n_valid is None else hit[:int(n_valid)])
        return hit

    def note_hits(self, hit: np.ndarray) -> None:
        """Count descent-only hit/miss outcomes (write routing, scans)."""
        hit = np.asarray(hit)
        self.counters.hits += int(hit.sum())
        self.counters.misses += int((~hit).sum())

    def take_maintenance(self) -> tuple[int, int]:
        """Drain the un-priced maintenance traffic since the last call:
        ``(node_reads, small_reads)`` for image fills and version sweeps.
        The API replays these as MAINT/SYNC verbs through netsim."""
        f0, s0 = self._maint_taken
        f1, s1 = self.counters.fill_reads, self.counters.sync_reads
        self._maint_taken = (f1, s1)
        return f1 - f0, s1 - s0

    def rows_ms(self) -> np.ndarray:
        """Owning MS of every filled cache row — the verb plane spreads
        maintenance reads over these instead of a blind round-robin."""
        if self._image is None:
            return np.zeros(0, np.int32)
        return self.cfg.ms_of(self._rows[self._filled]).astype(np.int32)

    # -- chaos plane: cold restart + full-state snapshot -------------------
    def reset(self) -> None:
        """Cold restart: drop the image (a CS that just joined the fleet
        has nothing cached — its first read triggers a full fill, the
        warm-up transient the chaos plane prices; DESIGN.md §13).
        Cumulative counters are kept: they are this CS's *history*, and
        the cluster conservation invariant sums them across the run."""
        self._image = None
        self._rows = np.zeros(0, np.int32)
        self._filled = np.zeros(0, bool)
        self._valid = np.zeros(0, bool)
        self._fnv = np.zeros(0, np.uint8)
        self._root = -1
        self._splitty_phases = 0
        self._rounds_since_sync = 0
        self._needs_refresh = True

    def export_state(self) -> tuple[Optional[dict], dict]:
        """Snapshot the cache's full mutable state as
        ``(image_arrays, scalars)`` — everything a tick-for-tick resume
        needs (the image drives routing and maintenance pricing, so a
        resumed run with a refilled-instead-of-restored cache would
        diverge from the uninterrupted one)."""
        image = None
        if self._image is not None:
            image = {k: np.asarray(v) for k, v in self._image.items()}
        scalars = dict(
            counters=self.counters.as_dict(),
            rounds_since_sync=self._rounds_since_sync,
            splitty_phases=self._splitty_phases,
            needs_refresh=self._needs_refresh,
            maint_taken=list(self._maint_taken),
        )
        return image, scalars

    def import_state(self, image: Optional[dict], scalars: dict) -> None:
        """Restore a snapshot taken by :meth:`export_state`."""
        if image is None:
            self.reset()
        else:
            self._image = {k: jnp.asarray(v) for k, v in image.items()}
            self._image["root"] = np.int32(image["root"])
            self._rows = np.asarray(image["rows"])
            self._filled = self._rows != ROW_SENTINEL
            self._valid = np.asarray(image["valid"]).copy()
            self._fnv = np.asarray(image["fnv"]).copy()
            self._root = int(image["root"])
        self.counters = CacheCounters(**scalars["counters"])
        self._rounds_since_sync = int(scalars["rounds_since_sync"])
        self._splitty_phases = int(scalars["splitty_phases"])
        self._needs_refresh = bool(scalars["needs_refresh"])
        self._maint_taken = tuple(scalars["maint_taken"])

    # -- reporting ---------------------------------------------------------
    @property
    def hit_ratio(self) -> float:
        c = self.counters
        t = c.hits + c.misses + c.stale
        return c.hits / t if t else 1.0
