#!/usr/bin/env python3
"""One-chip smoke run of the Sherman index at deployment size.

    python chip_smoke.py [--records N] [--seed S]

Needs one TPU.  It bulk-loads 10^8 records (``--records`` may cut that, to
no fewer than 10^7) scrambled over a 2^27 keyspace into a pool of 4 memory
servers x 2^22 rows (fanout 16, about 3 GB on the device), then drives the
served path through the entry points a user calls:

1. **Served phase.** ``build_cluster`` + ``run_cluster`` on the Table-3
   write-intensive mix (50% reads, 50% updates, Zipf 0.99) with 8 compute
   servers, each taking 512-lane batches through its own 64 MiB cache and
   the Pallas leaf-search kernel.  The merged verb timeline must conserve
   the per-CS totals, and the caches must hit.
2. **Exact phase.** The same pool behind ``ShermanIndex``: fresh inserts
   above the keyspace, overwrites of loaded keys, deletes, then lookups
   (hits, misses, deleted keys) and range scans, all in 4096-lane batches.
   Every answer is compared with a NumPy reference built from the load
   arrays and the smoke's own writes; loaded keys that the served phase
   may have updated are checked for presence only.

The lines before the last describe this one smoke run (device, sizes,
wall time per phase, compiles, peak device memory); they are not benchmark
numbers.  The last line is ``{"ok": true, "device": {...}}``, printed only
when every check passed.  Without a TPU the script exits non-zero before it
loads anything: there is no CPU, interpret-mode or reference fallback.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDS = 10**8
MIN_RECORDS = 10**7
KEYSPACE = 1 << 27            # power of two: the rank scramble is a bijection
N_CLIENTS = 8 * 512           # 8 CSs x 512-lane batches per round
WARM_ROUNDS = 4               # compiles, cache fills, the first sync sweep
SERVED_ROUNDS = 8
LANES = 4096                  # exact-phase batch: 16 kernel tiles
SCAN_LEN = 10
KERNEL_MODE = "pallas"        # the only leaf search this script accepts


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def smoke_config():
    from repro.core import TreeConfig
    return TreeConfig(n_ms=4, nodes_per_ms=1 << 22, fanout=16,
                      n_locks_per_ms=131072, max_height=8, n_cs=8)


class Reference:
    """What the index owes: the load arrays, overlaid with the smoke's own
    writes in the order it made them (``None`` = deleted)."""

    def __init__(self, keys: np.ndarray, vals: np.ndarray):
        order = np.argsort(keys)
        self.keys, self.vals = keys[order], vals[order]
        self.own: dict = {}

    def write(self, keys, vals=None) -> None:
        vals = [None] * len(keys) if vals is None else vals.tolist()
        self.own.update(zip(keys.tolist(), vals))

    def loaded(self, q: np.ndarray) -> np.ndarray:
        pos = np.minimum(np.searchsorted(self.keys, q), self.keys.size - 1)
        return self.keys[pos] == q

    def check_lookup(self, q, got, found) -> int:
        """Compare one lookup batch; returns the answers compared."""
        loaded = self.loaded(q)
        for k, g, f, ld in zip(q.tolist(), got.tolist(), found.tolist(),
                               loaded.tolist()):
            if k in self.own:
                want = self.own[k]
                check((want is None and not f) or (f and g == want),
                      f"lookup {k}: got ({f}, {g}), want {want}")
            else:
                check(f == ld, f"lookup {k}: found={f}, loaded={ld}")
        return q.size

    def check_scan(self, lo, keys, vals, n, count) -> int:
        """Scans from ``lo`` above every loaded key see only own keys."""
        live = np.asarray(sorted(k for k, v in self.own.items()
                                 if v is not None and k > self.keys[-1]),
                          np.int64)
        for i, a in enumerate(lo.tolist()):
            start = int(np.searchsorted(live, a))
            want = live[start:start + count].tolist()
            got = keys[i][:n[i]].tolist()
            check(got == want, f"scan from {a}: {got[:4]}.. != {want[:4]}..")
            check(vals[i][:n[i]].tolist() == [self.own[k] for k in want],
                  f"scan from {a}: values differ")
        return int(n.sum())


def run(cfg, records: int, keyspace: int, seed: int, *,
        n_clients: int = N_CLIENTS, warm_rounds: int = WARM_ROUNDS,
        served_rounds: int = SERVED_ROUNDS, lanes: int = LANES,
        log=print) -> dict:
    """Load, serve, check.  Raises :class:`SmokeFailure` on a wrong answer
    or a broken invariant; returns the run's report."""
    import jax

    from repro.cluster import build_cluster, run_cluster
    from repro.cluster.sched import VAL_MASK
    from repro.core import SHERMAN, ShermanIndex
    from repro.workloads import get_preset, scramble
    from repro.workloads.jitstats import count_compiles

    rep: dict = dict(records=records, keyspace=keyspace)
    timed = {}

    def clock(name, t0, state):
        jax.block_until_ready(state)
        timed[name] = time.perf_counter() - t0
        log(f"smoke: phase {name}: {timed[name]:.3f} s wall")

    # -- load: the arrays build_cluster loads, and the reference's copy --
    t0 = time.perf_counter()
    cluster = build_cluster(SHERMAN, cfg, n_clients=n_clients,
                            records=records, keyspace=keyspace, seed=seed)
    clock("bulk_load", t0, cluster.state)
    load_keys = scramble(np.arange(records, dtype=np.int64), keyspace)
    load_vals = np.random.default_rng(seed).integers(0, VAL_MASK,
                                                     size=records)
    ref = Reference(load_keys, load_vals)
    rep["pool_bytes"] = sum(int(a.nbytes) for a in cluster.state)
    rep["tree_height"] = int(cluster.state.height)
    modes = sorted({n.cache.kernel_mode for n in cluster.nodes})
    check(modes == [KERNEL_MODE], f"cluster caches search with {modes}")
    log(f"smoke: pool {rep['pool_bytes']} bytes, height "
        f"{rep['tree_height']}, {cluster.n_cs} CS x {cluster.per_cs} lanes")

    # -- served phase: warm rounds first, then the counted window --
    spec = get_preset("write-intensive", theta=0.99, load_records=records)
    per_round = cluster.n_clients
    t0 = time.perf_counter()
    run_cluster(cluster, spec.replace(ops=warm_rounds * per_round),
                seed=seed + 1, keyspace=keyspace)
    clock("first_compile", t0, cluster.state)
    hits0 = cluster.combined_counters()["cache_hits"]
    with count_compiles() as compiles:
        t0 = time.perf_counter()
        done, _ = run_cluster(cluster,
                              spec.replace(ops=served_rounds * per_round),
                              seed=seed + 2, keyspace=keyspace)
        clock("served", t0, cluster.state)
    counters = cluster.combined_counters()
    rep.update(served_ops=done, served_compiles=compiles.count,
               served_cache_hits=counters["cache_hits"] - hits0)
    log(f"smoke: served {done} ops, {compiles.count} compiles, "
        f"{rep['served_cache_hits']} cache hits")
    check(done == served_rounds * per_round, f"served {done} ops")
    check(cluster.conservation_ok(), "merged timeline lost verbs")
    check(rep["served_cache_hits"] > 0, "served phase never hit the cache")

    # -- exact phase: the same pool behind the single-frontend API --
    idx = ShermanIndex(cfg, cluster.state, features=SHERMAN)
    check(idx.cache.kernel_mode == KERNEL_MODE,
          f"index cache searches with {idx.cache.kernel_mode}")
    rng = np.random.default_rng(seed + 3)
    fresh = (keyspace + rng.choice(keyspace, 2 * lanes, replace=False)
             ).astype(np.int32)
    fresh_vals = rng.integers(0, VAL_MASK, fresh.size).astype(np.int32)
    over = scramble(rng.choice(records, lanes, replace=False), keyspace
                    ).astype(np.int32)
    over_vals = rng.integers(0, VAL_MASK, lanes).astype(np.int32)
    gone = fresh[::2]
    t0 = time.perf_counter()
    for part in range(0, fresh.size, lanes):
        sl = slice(part, part + lanes)
        idx.insert(fresh[sl], fresh_vals[sl])
        ref.write(fresh[sl], fresh_vals[sl])
    idx.insert(over, over_vals)
    ref.write(over, over_vals)
    idx.delete(gone)
    ref.write(gone)
    miss = np.concatenate([
        scramble(records + rng.choice(keyspace - records, lanes // 2,
                                      replace=False), keyspace),
        2 * keyspace + rng.choice(keyspace, lanes - lanes // 2,
                                  replace=False)]).astype(np.int32)
    untouched = scramble(rng.choice(records, lanes, replace=False),
                         keyspace).astype(np.int32)
    compared = 0
    for q in (fresh[:lanes], fresh[lanes:], over, untouched, miss):
        got, found = idx.lookup(q)
        compared += ref.check_lookup(q, got, found)
    lo = rng.choice(fresh, lanes, replace=False).astype(np.int32)
    sk, sv, sn = idx.range(lo, count=SCAN_LEN, max_leaves=2 * SCAN_LEN)
    compared += ref.check_scan(lo, sk, sv, sn, SCAN_LEN)
    clock("exact", t0, idx.state)
    rep.update(exact_answers=compared,
               exact_cache_hits=idx.counters["cache_hits"])
    log(f"smoke: exact phase matched {compared} answers, "
        f"{idx.counters['cache_hits']} cache hits")
    check(idx.counters["cache_hits"] > 0, "exact phase never hit the cache")
    rep["wall_s"] = timed
    return rep


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[1])
    ap.add_argument("--records", type=int, default=RECORDS,
                    help=f"records to load (default {RECORDS}, "
                         f"at least {MIN_RECORDS})")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not MIN_RECORDS <= args.records <= KEYSPACE:
        ap.error(f"--records must lie in [{MIN_RECORDS}, {KEYSPACE}]")

    import jax
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 2
    dev = jax.devices()[0]
    device = dict(platform=dev.platform, kind=dev.device_kind,
                  count=len(jax.devices()))
    print(f"smoke: device {device}")

    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.compile_cache import enable_compile_cache
    print(f"smoke: compile cache {enable_compile_cache()}")
    try:
        rep = run(smoke_config(), args.records, KEYSPACE, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    stats = dev.memory_stats() or {}
    rep["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    print("smoke: report of this one run (not a benchmark) "
          + json.dumps(dict(rep, device=device)))
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
