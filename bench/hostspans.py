"""The program's own stages in a profiler trace: where device idle time and
device time go, by the spans and named scopes of the served path.

The program marks its host work with ``sherman.*`` spans
(``repro.obs.host``), on the profiler's clock like the device's ``XLA Ops``,
and its jitted stages with ``jax.named_scope``.  This module reduces a
traced window to

* **idle by program span**, interval-exact: each idle nanosecond of the
  device goes to the innermost ``sherman.*`` span covering it, else to
  ``host.other``.  The full nesting path is kept, so a metric can ask for
  the idle inside one wave span but outside another;
* **device time by stage**: each ``XLA Ops`` event is charged to
  ``<module>/<stage>``.  The module is the ``XLA Modules`` event the op
  runs in (so same-named ops of two programs stay apart); the stage is the
  first component of the op's ``tf_op`` metadata (its named scope, or for
  a copy of an argument the argument's name), else the basename and line
  of its ``source``, else the op's own name.

The op metadata (``tf_op``, ``source``, ``program_id``) lives on the
trace's event metadata, which ``jax.profiler.ProfileData`` does not expose,
so it is read from the ``.xplane.pb`` with a minimal schema of the XSpace
proto (names, event metadata and stat metadata; the event lines are left
unparsed).  Stat names were read by hand from a TPU v5 lite trace.

The per-layer metric readers call :func:`of_run`.  The harness gives a
reader only ``ctx``, whose trace summary keeps the benchmark's own spans;
the trace's directory is found as the ``trace_dir`` of the ``run_cell``
call that is reading its metrics.
"""
from __future__ import annotations

import collections
import dataclasses
import os
import re
import sys
import warnings
from typing import NamedTuple, Optional

import numpy as np

import harness
import xtrace

PROGRAM_PREFIX = "sherman."
#: The span of one counted device-to-host read; its ``what`` names the site.
FETCH = "sherman.fetch"
#: The innermost span of idle time no program span covers.
OTHER = "host.other"
#: The program's counters of host syncs and cache upkeep, logged beside
#: the breakdown.
UPKEEP = ("host_fetches", "rounds", "cache_fills", "cache_sweeps",
          "maint_fill_reads", "maint_sync_reads")
_PROGRAM_ID = re.compile(r"\((\d+)\)$")


class Op(NamedTuple):
    """One ``XLA Ops`` event, charged to its module and stage."""
    plane: str
    module: str
    stage: str
    start_ns: float
    dur_ns: float


class Span(NamedTuple):
    name: str
    start_ns: float
    end_ns: float
    what: str = ""          # a fetch span's site


# --------------------------------------------------------------------------
# reading
# --------------------------------------------------------------------------

def _xspace_class():
    """A message class for the fields of ``XSpace`` read here.  Maps are
    declared as their repeated entry messages (the same wire format)."""
    from google.protobuf import descriptor_pb2, message_factory
    F = descriptor_pb2.FieldDescriptorProto
    f = descriptor_pb2.FileDescriptorProto(name="bench_xspace.proto",
                                           package="bench_xspace")

    def msg(name, *fields):
        m = f.message_type.add(name=name)
        for num, fname, ftype, rep, tname in fields:
            m.field.add(name=fname, number=num, type=ftype,
                        label=F.LABEL_REPEATED if rep else F.LABEL_OPTIONAL,
                        type_name=tname and ".bench_xspace." + tname)

    msg("Stat", (1, "metadata_id", F.TYPE_INT64, False, None),
        (3, "uint64_value", F.TYPE_UINT64, False, None),
        (4, "int64_value", F.TYPE_INT64, False, None),
        (5, "str_value", F.TYPE_BYTES, False, None),
        (7, "ref_value", F.TYPE_UINT64, False, None))
    msg("EventMetadata", (1, "id", F.TYPE_INT64, False, None),
        (2, "name", F.TYPE_BYTES, False, None),
        (5, "stats", F.TYPE_MESSAGE, True, "Stat"))
    msg("StatMetadata", (1, "id", F.TYPE_INT64, False, None),
        (2, "name", F.TYPE_BYTES, False, None))
    msg("EventMetadataEntry", (1, "key", F.TYPE_INT64, False, None),
        (2, "value", F.TYPE_MESSAGE, False, "EventMetadata"))
    msg("StatMetadataEntry", (1, "key", F.TYPE_INT64, False, None),
        (2, "value", F.TYPE_MESSAGE, False, "StatMetadata"))
    msg("Plane", (2, "name", F.TYPE_BYTES, False, None),
        (4, "event_metadata", F.TYPE_MESSAGE, True, "EventMetadataEntry"),
        (5, "stat_metadata", F.TYPE_MESSAGE, True, "StatMetadataEntry"))
    msg("Space", (1, "planes", F.TYPE_MESSAGE, True, "Plane"))
    return message_factory.GetMessages([f])["bench_xspace.Space"]


def op_metadata(path: str) -> dict:
    """``{(plane, program_id, op name): (tf_op, source)}`` of every device
    op in the trace, ``None`` where a stat is absent."""
    space = _xspace_class()()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    out = {}
    for plane in space.planes:
        pname = plane.name.decode()
        if not xtrace.DEVICE_PLANE.match(pname):
            continue
        stat_names = {e.key: e.value.name.decode()
                      for e in plane.stat_metadata}
        for entry in plane.event_metadata:
            em = entry.value
            got = {}
            for s in em.stats:
                what = stat_names.get(s.metadata_id)
                if what in ("tf_op", "source"):
                    got[what] = (stat_names.get(s.ref_value, "")
                                 if s.ref_value else
                                 s.str_value.decode(errors="replace"))
                elif what == "program_id":
                    got[what] = s.uint64_value or s.int64_value
            out[(pname, got.get("program_id"),
                 em.name.decode(errors="replace"))] = (got.get("tf_op"),
                                                        got.get("source"))
    return out


def stage_of(tf_op: Optional[str], source: Optional[str], op: str) -> str:
    """``jit(f)/descend/jit(searchsorted)/while:`` -> ``descend``;
    ``st.keys:`` -> ``st.keys``; else ``cache.py:232`` from the source;
    else the op's name."""
    if tf_op:
        parts = [p for p in tf_op.rstrip(":").split("/") if p]
        if parts and parts[0].startswith("jit("):
            parts = parts[1:]
        if parts:
            return parts[0].rstrip(":")
    if source:
        return os.path.basename(source)
    return op


def read(path: str) -> tuple:
    """``(ops, spans)``: every device op with its module and stage, and the
    host spans of the program and the benchmark."""
    import jax
    meta = op_metadata(path)
    pd = jax.profiler.ProfileData.from_file(path)
    ops, spans = [], []
    for plane in pd.planes:
        if xtrace.DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            if xtrace.OPS_LINE not in lines:
                continue
            mods = sorted((float(e.start_ns), float(e.end_ns), e.name)
                          for e in lines[xtrace.MODULES_LINE].events) \
                if xtrace.MODULES_LINE in lines else []
            evs = [(e.name, float(e.start_ns), float(e.duration_ns))
                   for e in lines[xtrace.OPS_LINE].events]
            # the module an op runs in is the module event covering it
            at = np.searchsorted(np.array([m[0] for m in mods]),
                                 np.array([t for _, t, _ in evs]),
                                 side="right") - 1
            stages = {}
            for (name, t, dur), k in zip(evs, at.tolist()):
                mod = mods[k][2] if k >= 0 and t < mods[k][1] else None
                key = (mod, name)
                if key not in stages:
                    m = _PROGRAM_ID.search(mod or "")
                    tf_op, source = meta.get(
                        (plane.name, m and int(m.group(1)), name),
                        (None, None))
                    stages[key] = (xtrace.module_of(mod) if mod
                                   else "unknown",
                                   stage_of(tf_op, source,
                                            xtrace.op_of(name)))
                ops.append(Op(plane.name, *stages[key], t, dur))
        elif plane.name == xtrace.HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith((PROGRAM_PREFIX,
                                          xtrace.SPAN_PREFIX)):
                        what = ""
                        if e.name == FETCH:
                            with warnings.catch_warnings():
                                warnings.simplefilter("ignore")
                                what = str(dict(e.stats).get("what", ""))
                        spans.append(Span(e.name, float(e.start_ns),
                                          float(e.end_ns), what))
    return ops, spans


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


def reduce(ops, spans, lo: float, hi: float) -> Reduction:
    """Reduce the ops and spans inside ``[lo, hi)`` (the traced window)."""
    planes = sorted({o.plane for o in ops})
    program = [s for s in spans if s.name.startswith(PROGRAM_PREFIX)]
    segs = _segments(program, lo, hi)
    a = np.array([s[0] for s in segs])
    b = np.array([s[1] for s in segs])
    idle, by_fetch = collections.Counter(), collections.Counter()
    stage = collections.Counter()
    for p in planes:
        mine = [o for o in ops if o.plane == p]
        busy = xtrace._union(((o.start_ns, o.start_ns + o.dur_ns)
                              for o in mine), lo, hi)
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

_done: dict = {}


def _trace_dir() -> Optional[str]:
    """``trace_dir`` of the ``run_cell`` call on the stack, if any."""
    f = sys._getframe(1)
    while f is not None:
        if f.f_code.co_name == "run_cell" and "trace_dir" in f.f_locals:
            return f.f_locals["trace_dir"]
        f = f.f_back
    return None


def of_run(ctx) -> Optional[Reduction]:
    """The reduction of the traced run whose metrics are being read, once
    per trace; ``None`` without a trace, without device ops, or without
    program spans (a program that has none)."""
    if ctx.get("trace") is None:
        return None
    d = _trace_dir()
    if not d:
        return None
    path = xtrace.find_xplane(d)
    if path not in _done:
        ops, spans = read(path)
        lo, hi = xtrace.window_of(
            [xtrace.Event(xtrace.HOST_PLANE, "", s.name, s.start_ns,
                          s.end_ns - s.start_ns) for s in spans],
            harness.TRACE_SPAN)
        red = reduce(ops, spans, lo, hi)
        if not (ops and red.span_s):
            red = None
        else:
            for k, v in red.breakdown().items():
                print(f"bench: {k} = {v}", file=sys.stderr, flush=True)
            upkeep = {k: ctx["counters"].get(k) for k in UPKEEP}
            print(f"bench: program counters = {upkeep}", file=sys.stderr,
                  flush=True)
        _done[path] = red
    return _done[path]
