"""XLA compile-count instrumentation for the shape-stability discipline.

Every jitted entry point in the hot path (:mod:`repro.core.api`) pads its
batch to a power-of-two bucket and keeps the repair queue at a fixed
capacity, so a steady-state workload must not trigger *any* fresh XLA
compilation after its warmup batch.  This module counts compilations so
benchmarks (``benchmarks/run.py --only throughput``), ``chip_smoke.py``
and tests (``tests/test_throughput.py``) can report and regress on them.

The count hooks ``MeshComputation.compile``, the one funnel every XLA
build passes through; jit-cache hits never reach it, so the tally is
*distinct compilations*, not dispatches.  (``jax.monitoring`` events fire
per compile *request*, cache hits included, and listeners cannot be
unregistered.)  Should a JAX upgrade move that funnel, the import below
fails loudly.
"""
from __future__ import annotations

import contextlib
import dataclasses

from jax._src.interpreters import pxla


@dataclasses.dataclass
class CompileStats:
    """Mutable compile tally, readable while the context is active."""
    count: int = 0


@contextlib.contextmanager
def count_compiles():
    """Count XLA compilations (not jit-cache hits) inside the context.

    >>> with count_compiles() as stats:
    ...     run_workload(...)
    >>> stats.count
    """
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
