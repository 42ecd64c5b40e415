"""Distributed execution of the Sherman index on a device mesh.

Two execution paths, mirroring the §Perf story:

* **pjit path (baseline)** — the single-pool phase functions are jitted with
  the node pool sharded over the ``model`` ("mem") axis and the op batch
  sharded over ``data``.  XLA SPMD auto-partitions the gathers/scatters;
  correct everywhere (including splits) but generates all-gather-heavy HLO.

* **routed path (optimized)** — a shard_map program that emulates one-sided
  verbs: a *remote row read* is an all_gather of row requests over the mem
  axis followed by a psum of owner responses (each row served by exactly one
  owner).  Entry-granular writes are routed the same way and applied locally
  by the owner — the collective analogue of RDMA_WRITE.  The CS-side cache
  (paper §4.2.3) is a small replicated image of the top two tree levels, so
  a cache-hit lookup costs exactly one remote read, like the paper.

Structural changes (splits) always run through the pjit path — they are the
paper's rare (≈0.4 %) slow path and reuse the verified single-pool code.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import write as W
from repro.core.ops import leaf_lookup
from repro.core.tree import NULL_PTR, TreeConfig, TreeState

MEM_AXIS = "model"       # the mem pool shards over the TP/model axis
DATA_AXIS = "data"

def tree_pspecs(cfg: TreeConfig) -> TreeState:
    """PartitionSpecs: pool rows over the mem axis, lock tables likewise."""
    row = P(MEM_AXIS)
    return TreeState(
        keys=row, vals=row, fev=row, rev=row, fnv=row, rnv=row,
        level=row, fence_lo=row, fence_hi=row, sibling=row, free_bit=row,
        glt=P(MEM_AXIS, None), root=P(), height=P(),
        alloc_next=P(MEM_AXIS), alloc_rr=P(),
    )


def shard_tree(st: TreeState, mesh: Mesh, cfg: TreeConfig) -> TreeState:
    specs = tree_pspecs(cfg)
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), st, specs)


# --------------------------------------------------------------------------
# routed one-sided primitives (inside shard_map over (DATA_AXIS, MEM_AXIS))
# --------------------------------------------------------------------------

def _remote_read_rows(cfg: TreeConfig, local: TreeState, rows: jax.Array):
    """Read arbitrary global pool rows from their owning mem shards.

    ``local`` holds this device's row block [N/n_ms, ...]; ``rows`` (global
    ids) is replicated over the mem axis, so each owner serves its rows and
    a single psum combines the unique responses — the collective analogue
    of a one-sided RDMA_READ (one "round trip").
    """
    me = lax.axis_index(MEM_AXIS)
    owner = rows // cfg.nodes_per_ms
    local_idx = jnp.where(owner == me, rows % cfg.nodes_per_ms, 0)
    mine = owner == me

    def serve(arr):
        got = arr[local_idx]
        m = mine.reshape(mine.shape + (1,) * (got.ndim - 1))
        return lax.psum(jnp.where(m, got, jnp.zeros_like(got)), MEM_AXIS)

    return dict(
        keys=serve(local.keys), vals=serve(local.vals),
        fev=serve(local.fev), rev=serve(local.rev),
        fnv=serve(local.fnv), rnv=serve(local.rnv),
        level=serve(local.level.astype(jnp.int32)),
        free=serve(local.free_bit.astype(jnp.int8)).astype(bool))


class RoutedLookupResult(NamedTuple):
    value: jax.Array
    found: jax.Array
    consistent: jax.Array
    leaf: jax.Array


def _routed_lookup_body(cfg: TreeConfig, st_local: TreeState, cache: dict,
                        qkeys: jax.Array, depth: int) -> RoutedLookupResult:
    """Per-(data,mem)-device body: traverse the replicated cache image, then
    one routed remote read of the target leaves (the paper's cache-hit
    fast path: a single RDMA_READ)."""
    # --- cache traversal (replicated, no communication) ---
    from repro.core.cache import descend_image
    # miss lanes resume from the frontier (first uncached node on the path)
    node, hit, _ = descend_image(cache, qkeys, max(depth, cfg.max_height))

    # --- remote leaf read: all_gather requests + psum responses ---
    img = _remote_read_rows(cfg, st_local, node)
    nk, nv = img["keys"], img["vals"]
    eq = nk == qkeys[:, None]
    found = jnp.any(eq, axis=1)
    slot = jnp.argmax(eq, axis=1)
    take = lambda a: jnp.take_along_axis(a, slot[:, None], axis=1)[:, 0]
    # a fetched non-leaf (cache too shallow / evicted level-1 node) must
    # not answer: its separators alias real keys and its "values" are
    # child pointers
    node_ok = (img["fnv"] == img["rnv"]) & ~img["free"] & \
        (img["level"] == 0)
    entry_ok = take(img["fev"]) == take(img["rev"])
    consistent = node_ok & (entry_ok | ~found)
    value = jnp.where(found & consistent, take(nv), NULL_PTR)
    return RoutedLookupResult(value=value, found=found & consistent,
                              consistent=consistent, leaf=node)


def build_cache(cfg: TreeConfig, st: TreeState, depth: int = 2,
                max_rows: int | None = None) -> dict:
    """Replicated CS-side image of the top ``depth`` tree levels — a thin
    wrapper over :func:`repro.core.cache.fill_image`, the single source of
    image construction (paper §4.2.3)."""
    from repro.core.cache import fill_image
    if max_rows is None:
        max_rows = 1 + cfg.fanout ** (depth - 1) + cfg.fanout ** depth
    image, _ = fill_image(cfg, st, levels=depth, max_rows=max_rows)
    return image


def routed_lookup_fn(cfg: TreeConfig, mesh: Mesh, depth: int = 2):
    """Build the shard_map'd routed lookup: keys sharded over data, pool
    sharded over mem, cache replicated."""
    specs = tree_pspecs(cfg)
    cache_specs = dict(rows=P(), keys=P(), vals=P(), level=P(), valid=P(),
                       fnv=P(), root=P())

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(specs, cache_specs, P(DATA_AXIS)),
        out_specs=RoutedLookupResult(P(DATA_AXIS), P(DATA_AXIS),
                                     P(DATA_AXIS), P(DATA_AXIS)),
        check_vma=False)
    def fn(st_local, cache, qkeys):
        # responses are identical across the mem axis (psum-combined);
        # one copy per data shard survives
        return _routed_lookup_body(cfg, st_local, cache, qkeys, depth)

    return jax.jit(fn)


# --------------------------------------------------------------------------
# pjit path: the verified single-pool phase under SPMD auto-partitioning
# --------------------------------------------------------------------------

def pjit_phase_fns(cfg: TreeConfig, mesh: Mesh):
    """jit the single-pool write phase with sharded state (baseline path)."""
    specs = tree_pspecs(cfg)
    s = lambda p: NamedSharding(mesh, p)
    st_sh = jax.tree_util.tree_map(s, specs)
    b_sh = s(P(DATA_AXIS))
    rep = s(P())
    rq_sh = W.RepairQueue(sep=b_sh, child=b_sh, level=b_sh, valid=b_sh)

    wp = jax.jit(
        functools.partial(W.write_phase, cfg),
        in_shardings=(st_sh, b_sh, b_sh, b_sh, b_sh, b_sh, rq_sh),
        donate_argnums=(0,))
    return wp
