"""A cell of ``BENCHMARK.json`` cut to a pool the CPU runs in seconds."""
import json
import os
import time

import harness

with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as _f:
    #: Every cell of ``BENCHMARK.json``, for tests that run each one.
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]

#: 40,000 records at fanout 58 make 870 leaves and 17 internal nodes; an
#: 8-node cache (1,013 B a node) leaves lookups partly uncached.
TINY = dict(n_ms=2, nodes_per_ms=1024, records=40_000, keyspace=1 << 16,
            n_cs=2, cache_bytes_per_cs=1013 * 8)


def tiny_cell(name: str = "wi-zipf-c24m",
              traffic: str = None) -> harness.Cell:
    """Cell ``name`` at the tiny size; ``traffic`` names a mix of
    ``bench/traffic`` to run in place of the cell's own."""
    cell = harness.load_cell(name)
    if traffic is not None:
        with open(os.path.join(harness.BENCH, "traffic",
                               traffic + ".json")) as f:
            cell.traffic = json.load(f)
    cell.config = dict(cell.config, **TINY)
    cell.traffic = dict(cell.traffic, lanes_per_cs=64)
    return cell


def run(cell, seed=2**31 + 7, seconds=0.5, tamper=None,
        kernel_mode="interpret", log=lambda m: None):
    return harness.run_cell(cell, seed, seconds, False,
                            t_start=time.perf_counter(), log=log,
                            kernel_mode=kernel_mode, tamper=tamper)
