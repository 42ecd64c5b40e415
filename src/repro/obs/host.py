"""Wall-clock spans and counted device-to-host reads on the served path.

Two traces describe a run, and this module writes the second one
(DESIGN.md §14):

* the :class:`~repro.obs.recorder.Recorder` captures the *model's*
  simulated verb timeline on the picosecond grid;
* :func:`span` marks what the *program* is doing in wall time.  A span is a
  ``jax.profiler.TraceAnnotation`` named ``sherman.<stage>``, so it lands in
  the profiler's trace on the same clock as the device's ``XLA Ops`` line,
  and a device idle gap can be attributed to the host work that caused it.
  With no profiler running a span costs about a microsecond.

:func:`fetch` is the one way the served path reads a device array on the
host.  Each call is one blocking device-to-host read: it opens a
``sherman.fetch`` span whose ``what`` names the site, allows the transfer
under ``jax.transfer_guard_device_to_host("disallow")`` (so a run under
that guard proves every read is counted), and adds 1 to the
``host_fetches`` counter of the :class:`~repro.cluster.Cluster` whose wave
is running (:func:`counting`).  Outside a wave nothing is counted.
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Optional

import jax
from jax.profiler import TraceAnnotation as span

__all__ = ["span", "fetch", "counting"]

#: The counter dict that ``fetch`` adds to: the running wave's Cluster's.
#: A context variable, because the reads happen deep in cache and API code
#: that holds no handle on the cluster.
_counters: contextvars.ContextVar[Optional[dict]] = contextvars.ContextVar(
    "sherman_host_fetches", default=None)


@contextlib.contextmanager
def counting(counters: dict):
    """Count every :func:`fetch` inside the block into
    ``counters["host_fetches"]``."""
    token = _counters.set(counters)
    try:
        yield
    finally:
        _counters.reset(token)


def fetch(x, what: str):
    """Read ``x`` (a ``jax.Array`` or a pytree of them) to the host as
    NumPy, blocking until the device has computed it: one counted sync."""
    with span("sherman.fetch", what=what), \
            jax.transfer_guard_device_to_host("allow"):
        out = jax.device_get(x)
    counters = _counters.get()
    if counters is not None:
        counters["host_fetches"] += 1
    return out
