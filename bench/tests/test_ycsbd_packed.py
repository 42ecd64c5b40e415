"""The YCSB D cell on the packed tree (``ycsbd-packed-c64m``) at a tiny
pool: its run reaches the structure changes it exists for, and ``correct``
catches a stale cached read served without the B-link chase.  Also the
cell's per-layer readers."""
import functools

import jax
import jax.numpy as jnp
import pytest

import harness
import xtrace
from tiny import run, tiny_cell

CELL = "ycsbd-packed-c64m"


def _numbers(out):
    return {k: v for k, (v, _) in out["checks"].items()}


def test_packed_cell_splits_and_answers_right():
    """Every answer agrees with the reference ``Store``, and the run split
    leaves and internal nodes, read stale cached entries, chased siblings
    and refilled images, without dropping a separator."""
    seen = {}
    out = run(tiny_cell(CELL), tamper=lambda cl: seen.setdefault("cl", cl),
              seconds=1.0, kernel_mode="ref")
    assert out["correct"] and out["failed"] == 0, out["checks"]
    assert out["compiles"] == 0
    c = seen["cl"].combined_counters()
    assert c["leaf_splits"] > 0 and c["internal_splits"] > 0
    assert c["cache_stale"] > 0 and c["image_refills"] > 0
    assert c["blink_hops"] > 0 and c["repair_dropped"] == 0


def _unchased(cfg, st, image, qkeys, chase_hops, kernel_mode):
    """A cached lookup that serves a hit from its cached parent's child:
    no B-link chase, and no retraversal of a leaf that split."""
    from repro.core import cache
    from repro.core.ops import traverse
    leaf0, hit, _ = cache.descend_image(image, qkeys, cfg.max_height)
    leaf = jnp.where(hit, leaf0, traverse(cfg, st, qkeys).leaf)
    res = cache._leaf_probe(st, leaf, qkeys, kernel_mode)
    reads = jnp.ones_like(leaf)
    return res._replace(hops=reads), cache.CacheStats(
        hit=hit, stale=jnp.zeros_like(hit), remote_reads=reads)


def test_unchased_stale_read_is_caught(monkeypatch):
    from repro.core import cache
    monkeypatch.setattr(cache, "_jit_cached_lookup", functools.partial(
        jax.jit, static_argnums=(0, 4, 5))(_unchased))
    out = run(tiny_cell(CELL), kernel_mode="ref")
    assert not out["correct"]
    assert _numbers(out)["lookup_wrong"] > 0


def _ctx(**kw):
    return dict(dict(cell=tiny_cell(CELL), ops=2_000, reads=1_900,
                     updates=100, counters={}, trace=None), **kw)


def test_structure_readers():
    c = dict(leaf_splits=90, internal_splits=30, blink_hops=95)
    read = harness.metric_reader
    assert read("splits_per_kop")(_ctx(counters=c)) == pytest.approx(60.0)
    assert read("blink_hops_per_lookup")(_ctx(counters=c)) == \
        pytest.approx(0.05)
    # a program without the hop counter gives the reader nothing to read
    assert read("blink_hops_per_lookup")(_ctx(counters={})) is None
    assert read("splits_per_kop")(_ctx(counters={})) is None


def test_image_upkeep_reader():
    """Idle inside the refill, invalidate and sweep spans, per op: the
    window [0, 100) ns has one device op [10, 40); the refill [0, 8) and
    the invalidation [40, 48) of a CS lookup, and a sweep [86, 96) of the
    round's end, hold 8 + 8 + 10 ns of idle."""
    host = "/host:CPU"

    def span(name, a, b):
        return xtrace.Event(host, "python3", name, a, b - a)

    events = [xtrace.Event("/device:TPU:0", xtrace.OPS_LINE, "descend", 10,
                           30, module="_jit_cached_lookup", stage="descend"),
              span("sherman.lookup_wave", 0, 80),
              span("sherman.cs_lookup", 0, 50),
              span("sherman.cache.refill", 0, 8),
              span("sherman.cache.invalidate", 40, 48),
              span("sherman.end_round", 85, 100),
              span("sherman.cache.sweep", 86, 96)]
    summ = xtrace.summarize(events, 0, 100)
    ctx = _ctx(ops=2, trace=summ, trace_events=events,
               trace_window=(0, 100))
    got = harness.metric_reader("image_upkeep_us_per_op")(ctx)
    assert got == pytest.approx(26e-9 / 2 * 1e6)
    assert harness.metric_reader("image_upkeep_us_per_op")(_ctx()) is None
