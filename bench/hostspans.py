"""The program's own stages in a profiler trace: where device idle time and
device time go, by the spans and named scopes of the served path.

The program marks its host work with ``sherman.*`` spans
(``repro.obs.host``), on the profiler's clock like the device's ``XLA Ops``,
and its jitted stages with ``jax.named_scope``.  This module reduces the
events of a traced window, as :func:`xtrace.read_events` reads them, to

* **idle by program span**, interval-exact: each idle nanosecond of the
  device goes to the innermost ``sherman.*`` span covering it, else to
  ``host.other``.  The full nesting path is kept, so a metric can ask for
  the idle inside one wave span but outside another;
* **device time by stage**: each ``XLA Ops`` event is charged to
  ``<module>/<stage>``, as the reader names them.

The per-layer metric readers call :func:`of_run` with their ``ctx``, which
holds the traced window's events (``trace_events``) and bounds
(``trace_window``); the harness puts the top of the reduction's device
stages and idle spans in a traced run's result line.
"""
from __future__ import annotations

import collections
import dataclasses
import sys
from typing import Optional

import numpy as np

import xtrace

PROGRAM_PREFIX = xtrace.PROGRAM_PREFIX
FETCH = xtrace.FETCH
#: The innermost span of idle time no program span covers.
OTHER = "host.other"
#: The program's counters of host syncs, cache upkeep, stale cache reads
#: and splits, logged beside the breakdown.
UPKEEP = ("host_fetches", "rounds", "cache_fills", "cache_sweeps",
          "maint_fill_reads", "maint_sync_reads", "cache_stale",
          "leaf_splits", "internal_splits")


# --------------------------------------------------------------------------
# reducing
# --------------------------------------------------------------------------

def _segments(spans, lo: float, hi: float) -> list:
    """Cut ``[lo, hi)`` at every program span boundary: ``(start, end,
    path, what)`` with ``path`` the covering spans' names, outermost first
    (empty where none covers), and ``what`` the innermost one's site.
    Spans of one thread nest; a child is clipped to its parent."""
    out, stack, cursor = [], [], lo
    for sp in sorted(spans, key=lambda s: (s.start_ns, -s.end_ns)):
        s, e = max(sp.start_ns, lo), min(sp.end_ns, hi)
        if e <= s:
            continue
        while stack and stack[-1][0] <= s:
            end, path, what = stack.pop()
            out.append((cursor, end, path, what))
            cursor = end
        out.append((cursor, s) + (stack[-1][1:] if stack else ((), "")))
        cursor = s
        if stack:
            e = min(e, stack[-1][0])
        stack.append((e, (stack[-1][1] if stack else ()) + (sp.name,),
                      sp.what))
    while stack:
        end, path, what = stack.pop()
        out.append((cursor, end, path, what))
        cursor = end
    out.append((cursor, hi, (), ""))
    return [seg for seg in out if seg[1] > seg[0]]


def _idle_in(gaps, a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Idle nanoseconds inside each ``[a_i, b_i)``, from sorted disjoint
    ``gaps``."""
    if not gaps:
        return np.zeros(a.size)
    gs = np.array([g[0] for g in gaps])
    ge = np.array([g[1] for g in gaps])
    before = np.concatenate([[0.0], np.cumsum(ge - gs)])

    def upto(t):        # idle nanoseconds in [-inf, t)
        i = np.searchsorted(ge, t, side="right")
        part = np.where(i < gs.size,
                        np.clip(t - gs[np.minimum(i, gs.size - 1)], 0, None),
                        0.0)
        return before[i] + part

    return upto(b) - upto(a)


@dataclasses.dataclass
class Reduction:
    """What the program-span metrics read from one traced window."""
    idle_by_path: dict          # span path (outermost first) -> idle s
    idle_by_fetch: dict         # fetch site -> idle s inside its span
    span_s: dict                # program span name -> host seconds
    stage_s: dict               # "<module>/<stage>" -> device seconds

    def idle_within(self, outer: str, outside: str) -> float:
        """Idle seconds inside span ``outer`` and outside ``outside``."""
        return sum(s for p, s in self.idle_by_path.items()
                   if outer in p and outside not in p)

    def idle_by_span(self) -> dict:
        """Idle seconds by innermost program span (``host.other``)."""
        out = collections.Counter()
        for p, s in self.idle_by_path.items():
            out[p[-1] if p else OTHER] += s
        return dict(out)

    def breakdown(self, top: int = 10) -> dict:
        """The top entries of each attribution, in seconds."""
        paths = {"/".join(p) or OTHER: s
                 for p, s in self.idle_by_path.items()}
        return {key: [[k, float(v)] for k, v in
                      collections.Counter(d).most_common(top)]
                for key, d in (("idle_gaps_program", self.idle_by_span()),
                               ("device_ops_by_scope", self.stage_s),
                               ("idle_by_fetch", self.idle_by_fetch),
                               ("idle_by_span_path", paths))}


def reduce(events, lo: float, hi: float) -> Reduction:
    """Reduce the events inside ``[lo, hi)`` (the traced window): the
    device's ``XLA Ops`` and the program's host spans."""
    ops = [e for e in events if e.line == xtrace.OPS_LINE]
    planes = sorted({o.plane for o in ops})
    program = [e for e in events if e.plane == xtrace.HOST_PLANE
               and e.name.startswith(PROGRAM_PREFIX)]
    segs = _segments(program, lo, hi)
    a = np.array([s[0] for s in segs])
    b = np.array([s[1] for s in segs])
    idle, by_fetch = collections.Counter(), collections.Counter()
    stage = collections.Counter()
    for p in planes:
        mine = [o for o in ops if o.plane == p]
        busy = xtrace._union([o.start_ns for o in mine],
                             [o.end_ns for o in mine], lo, hi)
        gaps, cursor = [], lo
        for s, e in busy + [[hi, hi]]:
            if s > cursor:
                gaps.append((cursor, s))
            cursor = max(cursor, e)
        for (_, _, path, what), ns in zip(segs, _idle_in(gaps, a, b)):
            if ns > 0:
                idle[path] += ns * 1e-9 / len(planes)
                if path and path[-1] == FETCH:
                    by_fetch[what] += ns * 1e-9 / len(planes)
        for o in mine:
            if lo <= o.start_ns < hi:
                stage[o.module, o.stage] += o.dur_ns
    span_s = collections.Counter()
    for s in program:
        span_s[s.name] += max(0.0, min(s.end_ns, hi)
                              - max(s.start_ns, lo)) * 1e-9
    return Reduction(idle_by_path=dict(idle),
                     idle_by_fetch=dict(by_fetch), span_s=dict(span_s),
                     stage_s={f"{m}/{s}": ns * 1e-9 / len(planes)
                              for (m, s), ns in stage.items()})


# --------------------------------------------------------------------------
# for the metric readers
# --------------------------------------------------------------------------

def of_run(ctx) -> Optional[Reduction]:
    """The reduction of the traced window that ``ctx`` holds, made once a
    run and kept in ``ctx``; ``None`` without a trace, without device ops,
    or without program spans (a program that has none)."""
    if ctx.get("trace") is None or "trace_events" not in ctx:
        return None
    if "program_trace" not in ctx:
        red = reduce(ctx["trace_events"], *ctx["trace_window"])
        if not (red.stage_s and red.span_s):
            red = None
        else:
            for k, v in red.breakdown().items():
                print(f"bench: {k} = {v}", file=sys.stderr, flush=True)
            upkeep = {k: ctx["counters"].get(k) for k in UPKEEP}
            print(f"bench: program counters = {upkeep}", file=sys.stderr,
                  flush=True)
        ctx["program_trace"] = red
    return ctx["program_trace"]
