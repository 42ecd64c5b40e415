"""Breaks of the served path that ``correct`` must catch.

Each takes the built cluster and breaks it underneath the benchmark, the
way a faulty change to the program would.  ``values_in_16_bits`` is the
control: the value column held one precision step below the int32 the
configuration states (``bench/control.py`` runs it on the chip).  The
others are the faults ``bench/tests/test_faults.py`` plants at test size.
"""
from __future__ import annotations

import numpy as np


def values_in_16_bits(cluster) -> None:
    """Control: every lookup answers from a 16-bit value column."""
    lookup = cluster.lookup_wave

    def narrowed(*a, **kw):
        return [(np.asarray(v).astype(np.int16).astype(np.int32), f)
                for v, f in lookup(*a, **kw)]

    cluster.lookup_wave = narrowed


def state_unchanged(cluster) -> None:
    """A write wave that returns without changing the pool."""
    cluster.write_wave = lambda *a, **kw: None


def half_batch(cluster) -> None:
    """A write wave that leaves out the second half of every CS's batch."""
    write = cluster.write_wave

    def half(keys_by_cs, vals_by_cs=None, **kw):
        keys = [k[:len(k) // 2] for k in keys_by_cs]
        vals = (None if vals_by_cs is None
                else [v[:len(v) // 2] for v in vals_by_cs])
        return write(keys, vals, **kw)

    cluster.write_wave = half


def answer_altered(cluster) -> None:
    """One lookup answer of every wave altered where it is produced."""
    lookup = cluster.lookup_wave

    def altered(*a, **kw):
        out = lookup(*a, **kw)
        for cs, (v, f) in enumerate(out):
            if len(v):
                v = np.array(v)
                v[0] += 1
                out[cs] = (v, f)
                break
        return out

    cluster.lookup_wave = altered
