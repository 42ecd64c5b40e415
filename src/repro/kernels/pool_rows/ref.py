"""Pure-jnp oracle for the pool-row read: a plain row gather."""
from __future__ import annotations


def pool_rows_ref(idx, *cols):
    return tuple(col[idx] for col in cols)
