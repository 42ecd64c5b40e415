"""A CPU rehearsal of a cell: the harness's round loop, reference, checks
and metric readers at a tiny pool, with the Pallas leaf search in
interpret mode.  The command itself (``bench/run.py``) has no CPU path."""
import json
import os

import numpy as np
import pytest

import harness
import reference
import traffic
import xtrace
from tiny import CELLS, TINY, run, tiny_cell

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_correct(name):
    lines = []
    out = run(tiny_cell(name), log=lines.append)
    # the reference replayed a version sweep among the window's waves
    assert any("'sweep': " in m for m in lines if "replayed" in m), lines
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["compiles"] == 0
    assert set(out["metrics"]) == {"ops_per_s", "op_p95_ms", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert {k: v for k, (v, _) in out["checks"].items()} == \
        {"lookup_wrong": 0, "readback_wrong": 0, "replay_wrong": 0}


def test_ycsb_d_rehearsal():
    """The YCSB D mix (95% read, 5% insert, latest) through a tiny cell:
    every answer right, every insert read back, inserts counted as write
    ops."""
    lines = []
    cell = tiny_cell("ro-zipf-c64m", traffic="ycsb-d")
    out = run(cell, log=lines.append)
    assert {k: v for k, (v, _) in out["checks"].items()} == \
        {"lookup_wrong": 0, "readback_wrong": 0, "replay_wrong": 0}
    assert out["correct"] and out["failed"] == 0 and out["compiles"] == 0
    read_back = next(m for m in lines if "keys read back" in m)
    assert int(read_back.split(" window lookups, ")[1].split()[0]) > 0


def test_per_layer_readers_on_a_summary():
    """Every per-layer reader of BENCHMARK.json returns a number from a
    run's context, and nothing where the run gave it nothing to read."""
    cell = tiny_cell("wi-zipf-c24m")
    summ = xtrace.summarize([
        xtrace.Event("/device:TPU:0", "XLA Ops", "%leaf_search.1 = x",
                     0, 100),
        xtrace.Event("/device:TPU:0", "XLA Modules",
                     "jit__jit_write_phase(1)", 100, 400),
        xtrace.Event("/device:TPU:0", "XLA Ops", "%while.1 = x", 100, 400),
        xtrace.Event("/device:TPU:0", "XLA Modules",
                     "jit__jit_cached_lookup(2)", 0, 100),
        xtrace.Event("/host:CPU", "python3", "bench.price_merged_phase",
                     600, 300)], 0, 1000)
    ctx = dict(cell=cell, ops=10, reads=5, updates=5, write_waves=1,
               counters=dict(stacked_phases=1, cache_hits=3,
                             cache_misses=1, cache_stale=0),
               device_kind="TPU v5 lite", trace=summ)
    got = {m["name"]: harness.metric_reader(m["name"])(ctx)
           for m in cell.per_layer}
    assert got["device_idle_pct"] == pytest.approx(50.0)
    assert got["write_dev_us_per_op"] == pytest.approx(400e-9 / 10 * 1e6)
    assert got["lookup_dev_us_per_op"] == pytest.approx(100e-9 / 10 * 1e6)
    assert got["write_phases_per_wave"] == 1.0
    assert got["cache_hit_pct"] == pytest.approx(75.0)
    assert got["replay_host_us_per_op"] == pytest.approx(300e-9 / 10 * 1e6)
    assert 0 < got["leaf_search_roofline_pct"] < 100
    idle = dict(ctx, updates=0, write_waves=0, trace=None)
    for name in ("write_dev_us_per_op", "write_phases_per_wave",
                 "device_idle_pct", "leaf_search_roofline_pct"):
        assert harness.metric_reader(name)(idle) is None


def test_a_cell_is_added_as_data(tmp_path):
    """A new cell made of an existing configuration and traffic file loads
    and runs with no change to any code."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["workloads"].append(dict(
        name="wi-zipf-c64m", config="sherman-f58-c64m", traffic="wi-zipf",
        chips=1, why="the write-intensive mix with every internal node"))
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    cell = harness.load_cell("wi-zipf-c64m", bench_json=str(path))
    assert cell.config["cache_bytes_per_cs"] == 64 << 20
    assert cell.traffic["ops"] == {"read": 0.5, "update": 0.5}
    assert [m["name"] for m in cell.end_to_end] == \
        ["ops_per_s", "op_p95_ms", "peak_hbm_gib", "setup_s"]
    assert not cell.per_layer        # no metric lists the new cell yet
    cell.config = dict(cell.config, **TINY)
    cell.traffic = dict(cell.traffic, lanes_per_cs=64)
    assert run(cell, kernel_mode="ref")["correct"]


def test_traffic_is_fixed_by_the_seed():
    cell = tiny_cell("wi-zipf-c24m")
    a, b = (harness.generator(cell, 2**33 + 5) for _ in range(2))
    ra, rb = a.round("window", 3), b.round("window", 3)
    for x, y in zip(ra.read_ranks + ra.update_vals,
                    rb.read_ranks + rb.update_vals):
        np.testing.assert_array_equal(x, y)
    other = harness.generator(cell, 2**33 + 6).round("window", 3)
    assert not np.array_equal(other.read_ranks[0], ra.read_ranks[0])
    # every seed gets the same sizes
    assert [r.size for r in other.read_ranks] == \
        [r.size for r in ra.read_ranks]


def test_zipf_head_share():
    """YCSB's Zipfian gives rank 0 a 1/zeta(n) share of the draws."""
    z = traffic.Zipf(10**6, 0.99)
    ranks = z.ranks(np.random.default_rng(1), 200_000)
    assert ranks.min() == 0 and ranks.max() < 10**6
    share = np.mean(ranks == 0)
    assert share == pytest.approx(1 / z.zetan, rel=0.05)


def test_last_lane_wins():
    ranks = np.array([5, 3, 5, 9, 3])
    vals = np.array([1, 2, 3, 4, 5], np.int32)
    uniq, v = reference.last_writes(ranks, vals)
    assert dict(zip(uniq.tolist(), v.tolist())) == {3: 5, 5: 3, 9: 4}
