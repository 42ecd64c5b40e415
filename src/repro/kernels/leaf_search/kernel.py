"""Pallas TPU kernel for Sherman's hot read path: batched unsorted-leaf
search with the two-level version check (paper Fig. 9).

The paper's unsorted leaves force a full-node scan per lookup; on the memory
server this is the NIC's job, on TPU it is a VPU sweep over the leaf image
held in VMEM.  A batch of fetched leaf images is tiled [BT, F] so each grid
step compares BT query keys against all F slots simultaneously — the SIMD
analogue of Sherman's "traverse the entire targeted leaf node", with the
version words (FEV/REV/FNV/RNV — the on-chip-memory resident metadata)
validated in the same pass.

Inputs are the *gathered* leaf rows (HBM -> VMEM by BlockSpec); outputs are
value / found / consistent per lane.  Per-lane operands travel as [B, 1]
columns: a 2-D block is tiled the same way by XLA and Mosaic for any number
of BT-row tiles, where a 1-D block is not.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl


def _leaf_kernel(qk_ref, keys_ref, vals_ref, fev_ref, rev_ref,
                 fnv_ref, rnv_ref, free_ref,
                 val_ref, found_ref, cons_ref):
    keys = keys_ref[...]                     # [BT, F]
    f = keys.shape[1]
    eq = keys == qk_ref[...]                 # [BT, 1] against every slot
    # first matching slot (unsorted full scan; keys unique per leaf,
    # first-match keeps the kernel deterministic regardless)
    slot = lax.broadcasted_iota(jnp.int32, keys.shape, 1)
    first = jnp.min(jnp.where(eq, slot, f), axis=1, keepdims=True)
    found = first < f
    pick = slot == first
    sel = lambda a: jnp.sum(jnp.where(pick, a, 0), axis=1, keepdims=True)
    value = sel(vals_ref[...])
    fev = sel(fev_ref[...].astype(jnp.int32))
    rev = sel(rev_ref[...].astype(jnp.int32))
    node_ok = (fnv_ref[...] == rnv_ref[...]) & (free_ref[...] == 0)
    consistent = node_ok & ((fev == rev) | ~found)
    val_ref[...] = jnp.where(found & consistent, value, jnp.int32(-1))
    found_ref[...] = (found & consistent).astype(jnp.int32)
    cons_ref[...] = consistent.astype(jnp.int32)


def leaf_search(qkeys: jax.Array, keys: jax.Array, vals: jax.Array,
                fev: jax.Array, rev: jax.Array, fnv: jax.Array,
                rnv: jax.Array, free: jax.Array, *,
                bt: int = 256, interpret: bool = False):
    """qkeys [B]; keys/vals/fev/rev [B, F]; fnv/rnv/free [B].

    ``B`` must be a multiple of ``bt`` (or smaller than it); ``bt`` a
    multiple of 8.  Returns (value [B], found [B] bool, consistent [B]
    bool).
    """
    b, f = keys.shape
    bt = min(bt, b)
    assert b % bt == 0
    col = lambda a: a.astype(jnp.int32).reshape(b, 1)
    lane = pl.BlockSpec((bt, 1), lambda i: (i, 0))
    row = pl.BlockSpec((bt, f), lambda i: (i, 0))
    value, found, cons = pl.pallas_call(
        _leaf_kernel,
        grid=(b // bt,),
        in_specs=[lane, row, row, row, row, lane, lane, lane],
        out_specs=[lane] * 3,
        out_shape=[jax.ShapeDtypeStruct((b, 1), jnp.int32)] * 3,
        interpret=interpret,
        name="leaf_search",
    )(col(qkeys), keys, vals, fev, rev, col(fnv), col(rnv), col(free))
    return value[:, 0], found[:, 0].astype(bool), cons[:, 0].astype(bool)
