"""The trace reduction, on a synthetic trace with known answers and on a
recorded excerpt of a chip trace (one lookup wave of a 10^8-record,
fanout-16 write cell on a TPU v5 lite: 8 ``_jit_cached_lookup`` modules,
each with one ``leaf_search`` kernel event, then the replay span)."""
import gzip
import json
import os

import pytest

import xtrace
from xtrace import Event

DEV, HOST = "/device:TPU:0", "/host:CPU"
DATA = os.path.join(os.path.dirname(__file__), "data",
                    "lookup_wave_trace.json.gz")


def synthetic():
    """Window [0, 100) ns.  Ops: a while [10, 40) holding a fusion
    [15, 20), a kernel [50, 60), an op [95, 110) that the window clips.
    Spans: write_wave [0, 70) holding price_merged_phase [42, 48);
    end_round [80, 100)."""
    return [
        Event(DEV, "XLA Ops", "%while.1 = (u32[]) while(...)", 10, 30),
        Event(DEV, "XLA Ops", "%fusion.2 = s32[4] fusion(...)", 15, 5),
        Event(DEV, "XLA Ops", "%leaf_search.1 = (s32[256,1]) custom-call",
              50, 10),
        Event(DEV, "XLA Ops", "%copy.3 = s32[] copy(...)", 95, 15),
        Event(DEV, "XLA Modules", "jit__jit_write_phase(123)", 10, 30),
        Event(DEV, "XLA Modules", "jit__jit_cached_lookup(77)", 50, 10),
        Event(HOST, "python3", "bench.write_wave", 0, 70),
        Event(HOST, "python3", "bench.price_merged_phase", 42, 6),
        Event(HOST, "python3", "bench.end_round", 80, 20),
    ]


def test_synthetic_busy_idle_and_attribution():
    s = xtrace.summarize(synthetic(), 0, 100)
    assert s.window_s == pytest.approx(100e-9)
    # busy: [10, 40) + [50, 60) + [95, 100) = 45 ns
    assert s.busy_s == pytest.approx(45e-9)
    assert s.idle_pct == pytest.approx(55.0)
    assert s.modules("_jit_write_phase") == pytest.approx(30e-9)
    assert s.modules("_jit_cached_lookup", "_jit_route") == \
        pytest.approx(10e-9)
    assert s.kernel_s("leaf_search") == pytest.approx(10e-9)
    assert s.span_s["bench.price_merged_phase"] == pytest.approx(6e-9)
    assert s.op_s["while.1"] == pytest.approx(30e-9)


def test_names():
    assert xtrace.module_of("jit__jit_write_phase(618772350377822586)") \
        == "_jit_write_phase"
    assert xtrace.module_of("jit_less(9115816297794883134)") == "less"
    assert xtrace.op_of("%leaf_search.1 = (s32[256,1]{1,0}) custom-call("
                        "s32[256,1] %p)") == "leaf_search.1"


def _union_brute(ivs, lo, hi):
    ivs = sorted((max(s, lo), min(e, hi)) for s, e in ivs if e > lo
                 and s < hi)
    total, end = 0.0, lo
    for s, e in ivs:
        if e > end:
            total += e - max(s, end)
            end = e
    return total


def test_recorded_chip_excerpt():
    with gzip.open(DATA, "rt") as f:
        events = [Event(*e) for e in json.load(f)]
    lo, hi = xtrace.window_of(events, "bench.lookup_wave")
    s = xtrace.summarize(events, lo, hi)
    ops = [e for e in events if e.line == "XLA Ops"]
    busy = _union_brute([(e.start_ns, e.end_ns) for e in ops], lo, hi)
    assert s.busy_s == pytest.approx(busy * 1e-9)
    assert 0 < s.busy_s < s.window_s
    # 8 CSs: 8 cached-lookup programs, one 256-lane kernel call each
    kernel = [e for e in ops if xtrace.op_of(e.name).split(".")[0]
              == "leaf_search" and lo <= e.start_ns < hi]
    assert len(kernel) == 8
    assert s.kernel_s("leaf_search") == \
        pytest.approx(sum(e.dur_ns for e in kernel) * 1e-9)
    mods = [e for e in events if e.line == "XLA Modules"
            and lo <= e.start_ns < hi]
    assert sum(xtrace.module_of(e.name) == "_jit_cached_lookup"
               for e in mods) == 8
    assert s.modules("_jit_cached_lookup") == pytest.approx(sum(
        e.dur_ns for e in mods
        if xtrace.module_of(e.name) == "_jit_cached_lookup") * 1e-9)
