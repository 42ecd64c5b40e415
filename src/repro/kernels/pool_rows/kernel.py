"""Pallas TPU kernel: read rows of node-pool columns where they lie.

The TPU compiler stores an ``[N, F]`` pool column whose width ``F`` is not a
whole number of 128-lane vregs transposed (layout ``{0,1}``: ``N`` runs
along the lanes), since that pads ``F`` to 8 sublanes where the row-major
layout pads it to 128 lanes.  An XLA gather of rows from such a column first
copies the whole column to row-major (a ~1 GB copy at 4 x 2^20 rows of 58
slots), for a few hundred rows.  This kernel reads the column through its
transposed view ``col.T`` (``[F, N]``, a bitcast of the stored buffer): row
``r`` is lane ``r % 128`` of the ``(F, 128)`` block at ``r // 128``, which
the grid's pipeline DMAs from HBM to VMEM, chosen by the scalar-prefetched
row ids.  Each grid step reads ``LANES`` rows of every column and picks
each row's lane out of its block.

Where the compiler keeps a column row-major, or the pool is no whole number
of 128-row blocks, a row is contiguous and the plain gather reads it without
a copy: :func:`pool_rows` takes that path, decided from the shape alone.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pool_rows.ref import pool_rows_ref

BLOCK = 128       # pool rows per DMA'd block: one vreg's lanes
# rows read per grid step; 8, 16 and 32 read 512 rows of the benchmark's
# pool in the same time on a TPU v5e
LANES = 16


def stored_transposed(shape: tuple) -> bool:
    """Does the TPU compiler store an ``[N, F]`` column as ``{0,1}``?

    It picks the layout that pads less: ``F`` rounded up to 8 sublanes
    (transposed) against ``F`` rounded up to 128 lanes (row-major), with
    ties going row-major (compiled for a described v5e: ``F`` = 8, 16, 58,
    64, 100, 129, 200, 300 transposed; 127, 128, 256 row-major)."""
    f = shape[1]
    return -(-f // 8) * 8 < -(-f // 128) * 128


def _rows_kernel(ncol: int, idx_ref, *refs):
    blocks, outs = refs[:ncol * LANES], refs[ncol * LANES:]
    first = pl.program_id(0) * LANES
    for c, out in enumerate(outs):
        f = out.shape[0]
        lane = lax.broadcasted_iota(jnp.int32, (f, BLOCK), 1)
        dst = lax.broadcasted_iota(jnp.int32, (f, LANES), 1)
        acc = jnp.zeros((f, LANES), jnp.int32)
        for g in range(LANES):
            at = idx_ref[first + g] % BLOCK
            block = blocks[c * LANES + g][...].astype(jnp.int32)
            row = jnp.sum(jnp.where(lane == at, block, 0), axis=1,
                          keepdims=True)                   # [F, 1]
            acc = jnp.where(dst == g, row, acc)
        out[...] = acc


def _pool_rows_pallas(idx: jax.Array, cols: tuple, interpret: bool
                      ) -> tuple:
    b = idx.shape[0]
    n, f = cols[0].shape
    bp = -(-b // LANES) * LANES
    # the row ids the plain gather reads: negative ids count from the end,
    # and every id is clamped into the pool
    idx = idx.astype(jnp.int32)
    idx = jnp.clip(jnp.where(idx < 0, idx + n, idx), 0, n - 1)
    idx = jnp.pad(idx, (0, bp - b))
    ncol = len(cols)

    def block(g):
        return pl.BlockSpec((f, BLOCK),
                            lambda i, ix: (0, ix[i * LANES + g] // BLOCK))

    tile = pl.BlockSpec((None, f, LANES), lambda i, ix: (i, 0, 0))
    out = pl.pallas_call(
        functools.partial(_rows_kernel, ncol),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bp // LANES,),
            in_specs=[block(g) for _ in cols for g in range(LANES)],
            out_specs=[tile] * ncol),
        out_shape=[jax.ShapeDtypeStruct((bp // LANES, f, LANES),
                                        jnp.int32)] * ncol,
        interpret=interpret,
        name="pool_rows",
    )(idx, *(c.T for c in cols for _ in range(LANES)))
    return tuple(o.transpose(0, 2, 1).reshape(bp, f)[:b].astype(c.dtype)
                 for o, c in zip(out, cols))


def pool_rows(idx: jax.Array, *cols: jax.Array, interpret: bool = False
              ) -> tuple:
    """Rows ``idx`` [B] of each ``[N, F]`` column in ``cols`` (one ``N``
    and ``F``): ``tuple(col[idx] for col in cols)``, ``[B, F]`` each, read
    without relayout of the columns, and with the gather's reading of ids
    outside ``[0, N)``."""
    n = cols[0].shape[0]
    if idx.shape[0] == 0 or n % BLOCK or \
            not stored_transposed(cols[0].shape):
        return pool_rows_ref(idx, *cols)
    return _pool_rows_pallas(idx, cols, interpret)
