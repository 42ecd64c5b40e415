"""The served path's wall-clock spans and counted device reads
(``repro.obs.host``): one tiny-Cluster round of lookup, write and
end_round (with a version sweep) under ``jax.profiler.trace``, read back
with ``jax.profiler.ProfileData``."""
import glob
import os
import warnings

import jax
import numpy as np
import pytest

from repro.cluster import build_cluster
from repro.core.netsim import SHERMAN
from repro.core.tree import TreeConfig
from repro.workloads.keygen import scramble

CFG = TreeConfig(n_ms=2, nodes_per_ms=1024, fanout=8, n_locks_per_ms=512,
                 max_height=6, n_cs=2)
RECORDS, KEYSPACE, LANES = 2_000, 1 << 12, 16

#: The innermost enclosing ``sherman.*`` span each span may have (None:
#: a top-level wave).
PARENTS = {
    "sherman.lookup_wave": {None},
    "sherman.write_wave": {None},
    "sherman.end_round": {None},
    "sherman.cs_lookup": {"sherman.lookup_wave"},
    "sherman.write.route": {"sherman.write_wave"},
    "sherman.write.phase": {"sherman.write_wave"},
    "sherman.write.drain": {"sherman.write_wave"},
    "sherman.cache.refill": {"sherman.cs_lookup", "sherman.write.route"},
    "sherman.cache.invalidate": {"sherman.cs_lookup"},
    "sherman.cache.sweep": {"sherman.end_round", "sherman.write.phase"},
    "sherman.maintenance": {"sherman.lookup_wave", "sherman.write_wave",
                            "sherman.end_round"},
    "sherman.trace_build": {"sherman.lookup_wave", "sherman.write_wave",
                            "sherman.maintenance"},
    "sherman.price": {"sherman.lookup_wave", "sherman.write_wave",
                      "sherman.maintenance"},
    "sherman.fetch": {"sherman.cs_lookup", "sherman.write.route",
                      "sherman.write.phase", "sherman.write.drain",
                      "sherman.cache.refill", "sherman.cache.invalidate",
                      "sherman.cache.sweep"},
}

#: Blocking device-to-host reads of each wave of the tiny round: per CS
#: lookup the root check, three cache stats, the leaf, the height and the
#: answers; a write wave's routing (root check and hit mask per CS) and one
#: stacked phase (its mask twice, the height, eleven stats, the split and
#: repair counts); one version sweep per CS at the end of the round.  A
#: change that adds or merges a sync changes these numbers, and should.
FETCHES = {"lookup": 16, "write": 21, "end_round": 2}


def _cluster():
    return build_cluster(SHERMAN, CFG, n_clients=CFG.n_cs * LANES,
                         records=RECORDS, keyspace=KEYSPACE, sync_rounds=1,
                         cache_bytes=1 << 20)


def _batches(seed):
    rng = np.random.default_rng(seed)
    keys = scramble(np.arange(RECORDS, dtype=np.int64), KEYSPACE)
    ks = [keys[rng.integers(0, RECORDS, LANES)].astype(np.int32)
          for _ in range(CFG.n_cs)]
    return ks, [(k * 7 + 1) & 0xFFFF for k in ks]


def _round(cl, seed, waves=("lookup", "write", "end_round")):
    """One round; returns the answers and each wave's fetch count."""
    keys, vals = _batches(seed)
    fetched, answers = {}, None
    for wave in waves:
        before = cl.counters["host_fetches"]
        if wave == "lookup":
            answers = cl.lookup_wave(keys)
        elif wave == "write":
            cl.write_wave(keys, vals)
        else:
            cl.end_round()
        fetched[wave] = cl.counters["host_fetches"] - before
    return answers, fetched


def _spans(trace_dir):
    """``(name, start, end, args)`` of every ``sherman.*`` host span."""
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    with warnings.catch_warnings():     # the stats type has no __module__
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("sherman."):
                        out.append((e.name, e.start_ns,
                                    e.start_ns + e.duration_ns,
                                    dict(e.stats)))
    return out


def _parent(spans, k):
    """Name of the innermost span strictly enclosing span ``k``."""
    name, s, e, _ = spans[k]
    best = None
    for j, (n2, s2, e2, _) in enumerate(spans):
        if j != k and s2 <= s and e <= e2 and (s2, e2) != (s, e) and \
                (best is None or e2 - s2 < best[1]):
            best = (n2, e2 - s2)
    return best and best[0]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The same round on two fresh clusters, one under the profiler."""
    runs = {}
    for profiled in (False, True):
        cl = _cluster()
        cl.record_traces()
        _round(cl, seed=1)                   # compiles every shape
        before = cl.combined_counters()
        d = str(tmp_path_factory.mktemp("trace"))
        if profiled:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            with jax.profiler.trace(d, profiler_options=opts):
                answers, fetched = _round(cl, seed=2)
        else:
            answers, fetched = _round(cl, seed=2)
        after = cl.combined_counters()
        runs[profiled] = dict(
            cluster=cl, answers=answers, fetched=fetched,
            delta={k: after[k] - before[k] for k in after},
            spans=_spans(d) if profiled else None)
    return runs


def test_spans_nest_as_documented(traced):
    spans = traced[True]["spans"]
    names = {n for n, *_ in spans}
    for want in ("sherman.lookup_wave", "sherman.write_wave",
                 "sherman.end_round", "sherman.cs_lookup",
                 "sherman.write.route", "sherman.write.phase",
                 "sherman.cache.sweep", "sherman.maintenance",
                 "sherman.trace_build", "sherman.price", "sherman.fetch"):
        assert want in names, want
    for k, (name, *_rest) in enumerate(spans):
        assert _parent(spans, k) in PARENTS[name], (name, _parent(spans, k))
    # cheap scalar arguments
    args = {n: a for n, _, _, a in spans}
    assert set(args["sherman.cs_lookup"]) == {"cs"}
    assert set(args["sherman.price"]) == {"kind", "verbs"}
    assert set(args["sherman.fetch"]) == {"what"}
    kinds = {a["kind"] for n, _, _, a in spans if n == "sherman.trace_build"}
    assert {"read", "write", "maint"} <= kinds


def test_one_price_span_per_priced_wave(traced):
    run = traced[True]
    prices = [a for n, _, _, a in run["spans"] if n == "sherman.price"]
    assert len(prices) == run["delta"]["merged_waves"] > 0
    assert sum(a["verbs"] for a in prices) == run["delta"]["verbs"]


def test_every_fetch_is_one_span(traced):
    run = traced[True]
    n = sum(name == "sherman.fetch" for name, *_ in run["spans"])
    assert n == run["delta"]["host_fetches"] == sum(run["fetched"].values())


def test_profiler_changes_nothing(traced):
    off, on = traced[False], traced[True]
    for (v0, f0), (v1, f1) in zip(off["answers"], on["answers"]):
        np.testing.assert_array_equal(v0, v1)
        np.testing.assert_array_equal(f0, f1)
    assert off["delta"] == on["delta"]
    assert off["fetched"] == on["fetched"]
    assert off["cluster"].trace_log == on["cluster"].trace_log
    assert off["cluster"].conservation_ok() and \
        on["cluster"].conservation_ok()


def test_cache_upkeep_counters(traced):
    d = traced[False]["delta"]
    assert d["cache_sweeps"] == CFG.n_cs         # sync_rounds=1
    assert d["maint_sync_reads"] > 0
    cl = traced[False]["cluster"]
    assert cl.combined_counters()["cache_fills"] == sum(
        n.cache.counters.fills for n in cl.nodes) >= CFG.n_cs


@pytest.mark.parametrize("wave", sorted(FETCHES))
def test_fetches_per_wave_are_pinned(traced, wave):
    assert traced[False]["fetched"][wave] == FETCHES[wave]
