"""The program-span reduction (``hostspans.py``) and its metric readers: a
synthetic trace with known answers, a trace file written from a text proto,
and a recorded excerpt of a chip trace (one round of the read-only cell at
fanout 58 on a TPU v5 lite, the round with a version sweep)."""
import gzip
import json
import os

import pytest

import harness
import hostspans
import xtrace

DEV, HOST = "/device:TPU:0", "/host:CPU"
DATA = os.path.join(os.path.dirname(__file__), "data",
                    "ro_round_program_trace.json.gz")


def Op(plane, module, stage, start_ns, dur_ns):
    """A device op as ``xtrace.read_events`` gives it."""
    return xtrace.Event(plane, xtrace.OPS_LINE, stage, start_ns, dur_ns,
                        module=module, stage=stage)


def Span(name, start_ns, end_ns, what=""):
    """A host span as ``xtrace.read_events`` gives it."""
    return xtrace.Event(HOST, "python3", name, start_ns, end_ns - start_ns,
                        what=what)


def synthetic():
    """Window [0, 100) ns.  Ops [10, 40), [50, 60), [90, 95).  Program
    spans: lookup_wave [0, 80) holding two sibling cs_lookups [0, 45) (with
    a fetch [5, 12)) and [45, 70), then price [70, 78); end_round [85, 100)
    holding maintenance [88, 92).  A benchmark span covers everything."""
    ops = [Op(DEV, "_jit_cached_lookup", "descend", 10, 30),
           Op(DEV, "_jit_cached_lookup", "st.keys", 50, 10),
           Op(DEV, "_take_rows", "gather", 90, 5)]
    spans = [Span("bench.window", 0, 100),
             Span("sherman.lookup_wave", 0, 80),
             Span("sherman.cs_lookup", 0, 45),
             Span("sherman.fetch", 5, 12, "lookup.hit"),
             Span("sherman.cs_lookup", 45, 70),
             Span("sherman.price", 70, 78),
             Span("sherman.end_round", 85, 100),
             Span("sherman.maintenance", 88, 92)]
    return ops, spans


def test_idle_is_attributed_interval_exactly():
    ops, spans = synthetic()
    red = hostspans.reduce(ops + spans, 0, 100)
    ns = {k: v * 1e9 for k, v in red.idle_by_span().items()}
    # gaps [0,10) [40,50) [60,90) [95,100); the gap [40,50) spans the two
    # sibling cs_lookups and is split between them at 45 (its midpoint
    # would give all of it to the second)
    assert ns == pytest.approx({
        "sherman.cs_lookup": 5 + 5 + 5 + 10, "sherman.fetch": 5,
        "sherman.price": 8, "sherman.lookup_wave": 2, "host.other": 5,
        "sherman.end_round": 3 + 5, "sherman.maintenance": 2})
    assert sum(ns.values()) == pytest.approx(100 - 45)
    assert red.idle_within("sherman.lookup_wave", "sherman.price") * 1e9 \
        == pytest.approx(32)
    assert red.idle_within("sherman.end_round", "sherman.price") * 1e9 \
        == pytest.approx(10)
    assert {k: v * 1e9 for k, v in red.idle_by_fetch.items()} == \
        pytest.approx({"lookup.hit": 5})
    assert red.span_s["sherman.price"] == pytest.approx(8e-9)
    assert "bench.window" not in red.span_s


def test_device_time_by_stage_keeps_modules_apart():
    ops = [Op(DEV, "_jit_cached_lookup", "copy.3", 0, 10),
           Op(DEV, "_jit_write_phase", "copy.3", 20, 30),
           Op(DEV, "_jit_write_phase", "copy.3", 60, 5)]
    red = hostspans.reduce(ops + [Span("sherman.write_wave", 0, 100)],
                           0, 100)
    assert red.stage_s == pytest.approx({
        "_jit_cached_lookup/copy.3": 10e-9, "_jit_write_phase/copy.3": 35e-9})
    bd = red.breakdown()
    assert bd["device_ops_by_scope"][0] == ["_jit_write_phase/copy.3",
                                           pytest.approx(35e-9)]


def test_stage_names():
    assert xtrace.stage_of(
        "jit(_jit_cached_lookup)/descend/jit(searchsorted)/while:", None,
        "while.52") == "descend"
    assert xtrace.stage_of("st.keys:", None, "copy.430") == "st.keys"
    assert xtrace.stage_of(None, "/x/src/repro/core/cache.py:232",
                              "cond.3.clone") == "cache.py:232"
    assert xtrace.stage_of(None, None, "while.213") == "while.213"


XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 40000 }
    events { metadata_id: 2 offset_ps: 50000 duration_ps: 30000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 5000 duration_ps: 20000 }
    events { metadata_id: 4 offset_ps: 55000 duration_ps: 10000 }
    events { metadata_id: 5 offset_ps: 66000 duration_ps: 4000 } }
  event_metadata { key: 1 value { id: 1 name: "jit__jit_cached_lookup(11)" } }
  event_metadata { key: 2 value { id: 2 name: "jit__jit_write_phase(22)" } }
  event_metadata { key: 3 value { id: 3
    name: "%copy.3 = s32[8,4]{1,0} copy(s32[8,4]{0,1} %st_keys.1)"
    stats { metadata_id: 1 str_value: "st.keys:" }
    stats { metadata_id: 2 uint64_value: 11 } } }
  event_metadata { key: 4 value { id: 4
    name: "%copy.3 = s32[8,4]{1,0} copy(s32[8,4]{0,1} %st_keys.1)"
    stats { metadata_id: 2 uint64_value: 22 } } }
  event_metadata { key: 5 value { id: 5 name: "%fusion.1 = s32[8] fusion()"
    stats { metadata_id: 3 str_value: "/src/repro/core/write.py:470" }
    stats { metadata_id: 2 uint64_value: 22 } } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 2 value { id: 2 name: "program_id" } }
  stat_metadata { key: 3 value { id: 3 name: "source" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 90000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 45000 }
    events { metadata_id: 3 offset_ps: 25000 duration_ps: 10000
      stats { metadata_id: 1 str_value: "lookup.hit" } }
    events { metadata_id: 4 offset_ps: 50000 duration_ps: 35000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "sherman.lookup_wave" } }
  event_metadata { key: 3 value { id: 3 name: "sherman.fetch" } }
  event_metadata { key: 4 value { id: 4 name: "sherman.write_wave" } }
  stat_metadata { key: 1 value { id: 1 name: "what" } }
}
"""


def _trace_dir(tmp_path, text=XSPACE):
    import jax
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        jax.profiler.ProfileData.text_proto_to_serialized_xspace(text))
    return str(tmp_path)


def test_read_takes_module_stage_and_site_from_the_file(tmp_path):
    events = xtrace.read_events(xtrace.find_xplane(_trace_dir(tmp_path)))
    assert [(e.module, e.stage, e.start_ns, e.dur_ns) for e in events
            if e.line == xtrace.OPS_LINE] == [
        ("_jit_cached_lookup", "st.keys", 1005, 20),
        ("_jit_write_phase", "copy.3", 1055, 10),
        ("_jit_write_phase", "write.py:470", 1066, 4)]
    assert [(e.name, e.start_ns, e.end_ns, e.what) for e in events
            if e.plane == HOST] == [
        ("bench.window", 1000, 1090, ""),
        ("sherman.lookup_wave", 1000, 1045, ""),
        ("sherman.fetch", 1025, 1035, "lookup.hit"),
        ("sherman.write_wave", 1050, 1085, "")]
    assert [(e.name, e.start_ns) for e in events
            if e.line == xtrace.MODULES_LINE] == [
        ("jit__jit_cached_lookup(11)", 1000),
        ("jit__jit_write_phase(22)", 1050)]


def run_cell(ctx, trace_dir, reader):
    """Stands in for ``harness.run_cell``: the trace is read once, and a
    reader finds its events and window in its context."""
    events = xtrace.read_events(xtrace.find_xplane(trace_dir))
    return reader(dict(ctx, trace_events=events, trace_window=xtrace.
                       window_of(events, harness.TRACE_SPAN)))


def _ctx(**kw):
    ctx = dict(reads=4, updates=2, trace=object(),
               counters=dict(host_fetches=300, rounds=3, verbs=20))
    ctx.update(kw)
    return ctx


def test_metric_readers(tmp_path):
    d = _trace_dir(tmp_path)
    read = {n: harness.metric_reader(n) for n in (
        "lookup_host_idle_us_per_op", "write_host_idle_us_per_op",
        "host_fetches_per_round", "replay_ns_per_verb")}
    got = {n: run_cell(_ctx(), d, r) for n, r in read.items()}
    # window [1000, 1090): busy [1005,1025) [1055,1065) [1066,1070); the
    # lookup wave [1000,1045) idles 5 + 20 ns, the write wave [1050,1085)
    # 5 + 1 + 15 ns; no price span
    assert got["lookup_host_idle_us_per_op"] == pytest.approx(25e-9 / 4 * 1e6)
    assert got["write_host_idle_us_per_op"] == pytest.approx(21e-9 / 2 * 1e6)
    assert got["host_fetches_per_round"] == 100.0
    assert got["replay_ns_per_verb"] is None
    priced = XSPACE.replace(
        'events { metadata_id: 4 offset_ps: 50000 duration_ps: 35000 } }',
        'events { metadata_id: 4 offset_ps: 50000 duration_ps: 35000 }\n'
        '    events { metadata_id: 5 offset_ps: 70000 duration_ps: 10000 } }'
    ).replace('  stat_metadata { key: 1 value { id: 1 name: "what" } }',
              '  event_metadata { key: 5 value { id: 5 name: '
              '"sherman.price" } }\n'
              '  stat_metadata { key: 1 value { id: 1 name: "what" } }')
    d2 = _trace_dir(tmp_path / "priced", priced)
    assert run_cell(_ctx(), d2, read["replay_ns_per_verb"]) == \
        pytest.approx(10 / 20)
    # idle in the priced stretch [1070, 1080) no longer counts for writes
    assert run_cell(_ctx(), d2, read["write_host_idle_us_per_op"]) == \
        pytest.approx(11e-9 / 2 * 1e6)


def test_readers_find_nothing_without_program_spans(tmp_path):
    """A program without spans or counters (the parent of this reader)
    gives no number, and no reader raises."""
    bare = XSPACE.replace('"sherman.', '"other.')
    d = _trace_dir(tmp_path, bare)
    ctx = _ctx(counters=dict(rounds=3, verbs=20))
    for n in ("lookup_host_idle_us_per_op", "write_host_idle_us_per_op",
              "host_fetches_per_round", "replay_ns_per_verb"):
        r = harness.metric_reader(n)
        assert run_cell(ctx, d, r) is None
        assert r(_ctx()) in (None, 100.0)      # no trace in ctx
        assert run_cell(_ctx(trace=None), d, r) in (None, 100.0)


def test_recorded_chip_round():
    with gzip.open(DATA, "rt") as f:
        rec = json.load(f)
    ops = [Op(rec["plane"], m, s, t, d) for m, s, t, d in rec["ops"]]
    spans = [Span(*s) for s in rec["spans"]]
    lo, hi = rec["lo"], rec["hi"]
    red = hostspans.reduce(ops + spans, lo, hi)
    # the same busy time as the benchmark's own reduction of those events
    summ = xtrace.summarize(ops + spans, lo, hi)
    assert sum(red.idle_by_path.values()) == \
        pytest.approx(summ.window_s - summ.busy_s)
    assert sum(red.stage_s.values()) == pytest.approx(sum(
        o.dur_ns for o in ops if lo <= o.start_ns < hi) * 1e-9)
    # eight cached lookups copy the pool's key, value and version columns
    top = [k for k, _ in red.breakdown()["device_ops_by_scope"][:4]]
    assert set(top) == {"_jit_cached_lookup/st.keys",
                        "_jit_cached_lookup/st.vals",
                        "_jit_cached_lookup/st.fev",
                        "_jit_cached_lookup/st.rev"}
    # the round's idle lies in named children, not in the wave bodies
    by_span = red.idle_by_span()
    bodies = sum(by_span.get(k, 0.0) for k in (
        "sherman.lookup_wave", "sherman.end_round"))
    named = sum(v for k, v in by_span.items() if k != "host.other")
    assert bodies < 0.2 * named
    assert red.idle_by_fetch["lookup.hit"] > 0
    assert sum(s.name == "sherman.cs_lookup" for s in spans) == 8
