"""Read a JAX profiler trace once, and reduce it to the benchmark's device
numbers.

The trace of a TPU run (``<dir>/plugins/profile/<time>/*.xplane.pb``) holds,
on one time base in nanoseconds:

* plane ``/device:TPU:<i>``, line ``XLA Ops``: every HLO operation that ran
  on the chip; a Pallas kernel appears under its ``name`` (``%leaf_search.1
  = ... custom-call(...)``);
* the same plane's line ``XLA Modules``: one event per executed jitted
  program, named ``jit_<function>(<fingerprint>)``;
* plane ``/host:CPU``: host threads, among them the benchmark's own spans
  (``jax.profiler.TraceAnnotation``), all named ``bench.<what>``, and the
  program's, named ``sherman.<what>`` (``repro.obs.host``).

:func:`read_events` is the one reader of the file: every event the
benchmark uses, in one schema.  An ``XLA Ops`` event carries its module
(the ``XLA Modules`` event covering it, so same-named ops of two programs
stay apart) and its stage: the first component of the op's ``tf_op``
metadata (its named scope, or for a copy of an argument the argument's
name), else the basename and line of its ``source``, else the op's own
name.  A ``sherman.fetch`` span carries its site (``what``).  The op
metadata (``tf_op``, ``source``, ``program_id``) lives on the trace's event
metadata, which ``jax.profiler.ProfileData`` does not expose, so it is
decoded from the same bytes with a minimal schema of the XSpace proto
(names, event metadata and stat metadata; the event lines are left
unparsed).  Stat names were read by hand from a TPU v5 lite trace.

Busy time is the union of the ``XLA Ops`` intervals inside the window, so
nested operations (a while loop and its body) count once.  Idle time and
device time by the program's own spans and stages are ``hostspans.py``'s,
from the same events.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
import warnings
from typing import NamedTuple, Optional

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
PROGRAM_PREFIX = "sherman."
#: The span of one counted device-to-host read; its ``what`` names the site.
FETCH = "sherman.fetch"
_MODULE = re.compile(r"^jit_(?P<fn>.+?)(\(\d+\))?$")
_OP = re.compile(r"^%(?P<op>[^ =]+)")
_PROGRAM_ID = re.compile(r"\((\d+)\)$")


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    module: str = ""        # an op's jitted program
    stage: str = ""         # an op's named scope, source line or name
    what: str = ""          # a fetch span's site

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


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


def op_metadata(data: bytes) -> dict:
    """``{(plane, program_id, op name): (tf_op, source)}`` of every device
    op in a serialized XSpace, ``None`` where a stat is absent."""
    space = _xspace_class()()
    space.ParseFromString(data)
    out = {}
    for plane in space.planes:
        pname = plane.name.decode()
        if not DEVICE_PLANE.match(pname):
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


def _device_events(plane, meta: dict) -> list:
    """A device plane's module events, and its op events, each with the
    module it runs in and its stage."""
    lines = {ln.name: ln for ln in plane.lines}
    mods = sorted((float(e.start_ns), float(e.end_ns), e.name,
                   float(e.duration_ns))
                  for e in lines[MODULES_LINE].events) \
        if MODULES_LINE in lines else []
    out = [Event(plane.name, MODULES_LINE, n, s, d) for s, _, n, d in mods]
    if OPS_LINE not in lines:
        return out
    evs = [(e.name, float(e.start_ns), float(e.duration_ns))
           for e in lines[OPS_LINE].events]
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
                (plane.name, m and int(m.group(1)), name), (None, None))
            stages[key] = (module_of(mod) if mod else "unknown",
                           stage_of(tf_op, source, op_of(name)))
        out.append(Event(plane.name, OPS_LINE, name, t, dur,
                         *stages[key]))
    return out


def read_events(path: str) -> list:
    """Every event of the device planes' op and module lines, and the
    benchmark's and the program's host spans, read from the file once."""
    import jax
    with open(path, "rb") as fh:
        data = fh.read()
    meta = op_metadata(data)
    pd = jax.profiler.ProfileData.from_serialized_xspace(data)
    out = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            out.extend(_device_events(plane, meta))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if not e.name.startswith((SPAN_PREFIX, PROGRAM_PREFIX)):
                        continue
                    what = ""
                    if e.name == FETCH:
                        with warnings.catch_warnings():
                            warnings.simplefilter("ignore")
                            what = str(dict(e.stats).get("what", ""))
                    out.append(Event(plane.name, line.name, e.name,
                                     float(e.start_ns),
                                     float(e.duration_ns), what=what))
    return out


def module_of(name: str) -> str:
    """``jit__jit_write_phase(123)`` -> ``_jit_write_phase``."""
    m = _MODULE.match(name)
    return m.group("fn") if m else name


def op_of(name: str) -> str:
    """``%leaf_search.1 = (...) custom-call(...)`` -> ``leaf_search.1``."""
    m = _OP.match(name)
    return m.group("op") if m else name


def _union(starts, ends, lo: float, hi: float) -> list:
    """The merged ``[start, end)`` intervals of ``starts[i], ends[i]``,
    clipped to ``[lo, hi)``; intervals that touch merge."""
    s = np.clip(np.asarray(starts, float), lo, hi)
    e = np.clip(np.asarray(ends, float), lo, hi)
    keep = e > s
    order = np.argsort(s[keep], kind="stable")
    s, e = s[keep][order], e[keep][order]
    if not s.size:
        return []
    reach = np.maximum.accumulate(e)
    first = np.flatnonzero(np.r_[True, s[1:] > reach[:-1]])
    last = np.r_[first[1:] - 1, s.size - 1]
    return [[a, b] for a, b in zip(s[first].tolist(), reach[last].tolist())]


@dataclasses.dataclass
class Summary:
    """What the per-layer metrics read from one trace window."""
    window_s: float
    busy_s: float                       # averaged over the device planes
    n_devices: int
    module_s: dict                      # jitted function -> device seconds
    op_s: dict                          # HLO op name -> device seconds
    span_s: dict                        # bench span -> host seconds

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def modules(self, *fns) -> float:
        return sum(self.module_s.get(f, 0.0) for f in fns)

    def kernel_s(self, kernel: str) -> float:
        return sum(s for op, s in self.op_s.items()
                   if op.split(".")[0] == kernel)


def summarize(events, lo_ns: float, hi_ns: float) -> Summary:
    """Reduce the events inside ``[lo_ns, hi_ns)`` (the traced window)."""
    window = (hi_ns - lo_ns) * 1e-9
    planes = sorted({e.plane for e in events if e.line == OPS_LINE})
    busy_ns = 0.0
    op_s = collections.Counter()
    for p in planes:
        ops = [e for e in events if e.plane == p and e.line == OPS_LINE]
        merged = _union([e.start_ns for e in ops], [e.end_ns for e in ops],
                        lo_ns, hi_ns)
        busy_ns += sum(e - s for s, e in merged)
        by_name = collections.Counter()
        for e in ops:
            if lo_ns <= e.start_ns < hi_ns:
                by_name[e.name] += e.dur_ns
        for name, ns in by_name.items():
            op_s[op_of(name)] += ns * 1e-9 / len(planes)
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
    return Summary(window_s=window,
                   busy_s=busy_ns * 1e-9 / max(len(planes), 1),
                   n_devices=len(planes), module_s=dict(module_s),
                   op_s=dict(op_s), span_s=dict(span_s))


def window_of(events, span: str) -> tuple:
    """The ``[start, end)`` of the one host span named ``span``."""
    hits = [e for e in events if e.plane == HOST_PLANE and e.name == span]
    if len(hits) != 1:
        raise ValueError(f"expected one {span!r} span, found {len(hits)}")
    return hits[0].start_ns, hits[0].end_ns
