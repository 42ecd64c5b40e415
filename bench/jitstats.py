"""Count XLA compilations inside a region (a copy of the program's
``repro.workloads.jitstats``, kept with the benchmark).

The count hooks ``MeshComputation.compile``, the one funnel every XLA build
passes through; jit-cache hits never reach it, so the tally is distinct
compilations, not dispatches.  Should a JAX upgrade move that funnel, the
import below fails loudly.
"""
from __future__ import annotations

import contextlib
import dataclasses

from jax._src.interpreters import pxla


@dataclasses.dataclass
class CompileStats:
    count: int = 0


@contextlib.contextmanager
def count_compiles():
    """Count XLA compilations (not jit-cache hits) inside the context."""
    stats = CompileStats()
    orig = pxla.MeshComputation.compile

    def counted(self, *a, **kw):
        stats.count += 1
        return orig(self, *a, **kw)

    pxla.MeshComputation.compile = counted
    try:
        yield stats
    finally:
        pxla.MeshComputation.compile = orig
