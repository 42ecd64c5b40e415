"""Reduce a JAX profiler trace to the benchmark's device numbers.

The trace of a TPU run (``<dir>/plugins/profile/<time>/*.xplane.pb``) holds,
on one time base in nanoseconds:

* plane ``/device:TPU:<i>``, line ``XLA Ops``: every HLO operation that ran
  on the chip; a Pallas kernel appears under its ``name`` (``%leaf_search.1
  = ... custom-call(...)``);
* the same plane's line ``XLA Modules``: one event per executed jitted
  program, named ``jit_<function>(<fingerprint>)``;
* plane ``/host:CPU``: host threads, among them the benchmark's own spans
  (``jax.profiler.TraceAnnotation``), all named ``bench.<what>``.

Busy time is the union of the ``XLA Ops`` intervals inside the window, so
nested operations (a while loop and its body) count once.  An idle gap is a
stretch of the window with no operation on the device; it is attributed to
the innermost benchmark span that covers its midpoint (``host.other`` where
none does).
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
_MODULE = re.compile(r"^jit_(?P<fn>.+?)(\(\d+\))?$")
_OP = re.compile(r"^%(?P<op>[^ =]+)")


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def read_events(path: str) -> list:
    """The events of the device planes' op and module lines and of the
    benchmark's host spans."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        dev = bool(DEVICE_PLANE.match(plane.name))
        if not dev and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            if dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                if dev or e.name.startswith(SPAN_PREFIX):
                    out.append(Event(plane.name, line.name, e.name,
                                     float(e.start_ns),
                                     float(e.duration_ns)))
    return out


def module_of(name: str) -> str:
    """``jit__jit_write_phase(123)`` -> ``_jit_write_phase``."""
    m = _MODULE.match(name)
    return m.group("fn") if m else name


def op_of(name: str) -> str:
    """``%leaf_search.1 = (...) custom-call(...)`` -> ``leaf_search.1``."""
    m = _OP.match(name)
    return m.group("op") if m else name


def _union(intervals, lo: float, hi: float) -> list:
    """Merged, clipped ``[start, end)`` intervals."""
    merged = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


@dataclasses.dataclass
class Summary:
    """What the per-layer metrics read from one trace window."""
    window_s: float
    busy_s: float                       # averaged over the device planes
    n_devices: int
    module_s: dict                      # jitted function -> device seconds
    op_s: dict                          # HLO op name -> device seconds
    span_s: dict                        # bench span -> host seconds
    idle_by_span: dict                  # bench span -> idle device seconds

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def modules(self, *fns) -> float:
        return sum(self.module_s.get(f, 0.0) for f in fns)

    def kernel_s(self, kernel: str) -> float:
        return sum(s for op, s in self.op_s.items()
                   if op.split(".")[0] == kernel)

    def breakdown(self, top: int = 10) -> dict:
        ops = collections.Counter(self.op_s).most_common(top)
        gaps = collections.Counter(self.idle_by_span).most_common(top)
        return dict(device_ops=[[k, v] for k, v in ops],
                    idle_gaps=[[k, v] for k, v in gaps])


def summarize(events, lo_ns: float, hi_ns: float) -> Summary:
    """Reduce the events inside ``[lo_ns, hi_ns)`` (the traced window)."""
    window = (hi_ns - lo_ns) * 1e-9
    planes = sorted({e.plane for e in events if e.line == OPS_LINE})
    busy_ns, merged_by_plane = 0.0, {}
    op_s = collections.Counter()
    for p in planes:
        ops = [e for e in events if e.plane == p and e.line == OPS_LINE]
        merged = _union(((e.start_ns, e.end_ns) for e in ops), lo_ns, hi_ns)
        merged_by_plane[p] = merged
        busy_ns += sum(e - s for s, e in merged)
        for e in ops:
            if lo_ns <= e.start_ns < hi_ns:
                op_s[op_of(e.name)] += e.dur_ns * 1e-9 / len(planes)
    module_s = collections.Counter()
    for e in events:
        if e.line == MODULES_LINE and lo_ns <= e.start_ns < hi_ns:
            module_s[module_of(e.name)] += e.dur_ns * 1e-9 / max(len(planes),
                                                                  1)
    spans = [e for e in events if e.plane == HOST_PLANE
             and e.name.startswith(SPAN_PREFIX)
             and e.end_ns > lo_ns and e.start_ns < hi_ns]
    span_s = collections.Counter()
    for e in spans:
        span_s[e.name] += (min(e.end_ns, hi_ns) - max(e.start_ns, lo_ns)
                           ) * 1e-9
    idle = collections.Counter()
    for p in planes:
        cursor, gaps = lo_ns, []
        for s, e in merged_by_plane[p] + [[hi_ns, hi_ns]]:
            if s > cursor:
                gaps.append((cursor, s))
            cursor = max(cursor, e)
        for name, sec in _attribute(gaps, spans).items():
            idle[name] += sec / len(planes)
    return Summary(window_s=window,
                   busy_s=busy_ns * 1e-9 / max(len(planes), 1),
                   n_devices=len(planes), module_s=dict(module_s),
                   op_s=dict(op_s), span_s=dict(span_s),
                   idle_by_span=dict(idle))


def _attribute(gaps, spans) -> dict:
    """Seconds of each gap, summed by the innermost span covering its
    midpoint (spans of one thread nest, so the shortest cover is it)."""
    if not gaps:
        return {}
    gaps = sorted(gaps, key=lambda g: g[0] + g[1])
    mids = np.array([0.5 * (s + e) for s, e in gaps])
    lens = np.array([(e - s) * 1e-9 for s, e in gaps])
    best = np.full(mids.size, np.inf)
    owner = np.full(mids.size, -1)
    for k, sp in enumerate(spans):
        a, b = np.searchsorted(mids, [sp.start_ns, sp.end_ns], side="left")
        sl = slice(int(a), int(b))
        inner = best[sl] > sp.dur_ns
        best[sl] = np.where(inner, sp.dur_ns, best[sl])
        owner[sl] = np.where(inner, k, owner[sl])
    out = collections.Counter()
    for k, sec in zip(owner.tolist(), lens.tolist()):
        out[spans[k].name if k >= 0 else "host.other"] += sec
    return dict(out)


def window_of(events, span: str) -> tuple:
    """The ``[start, end)`` of the one host span named ``span``."""
    hits = [e for e in events if e.plane == HOST_PLANE and e.name == span]
    if len(hits) != 1:
        raise ValueError(f"expected one {span!r} span, found {len(hits)}")
    return hits[0].start_ns, hits[0].end_ns
