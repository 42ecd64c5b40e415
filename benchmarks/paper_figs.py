"""One function per paper table/figure (Sherman, SIGMOD'22).

Each returns a list of CSV rows "name,us_per_call,derived" and prints a
small human table.  All workload mixes come from the unified engine in
:mod:`repro.workloads` (Table 3 presets: write-only, write-intensive,
read-intensive, range-only, range-write) — this module holds no private
workload logic, only figure orchestration.
"""
from __future__ import annotations

import numpy as np

from repro.core.netsim import ABLATION_LADDER, FG_PLUS, SHERMAN, NetConfig
from repro.workloads import (DEFAULT_CFG, build_index, get_preset,
                             run_workload)


def csv_row(name: str, us_per_call: float, derived: str) -> str:
    return f"{name},{us_per_call:.3f},{derived}"


def _run(features, skew, wl="write-intensive", n_ops=6_144, *, cfg=None,
         records=60_000, cache_bytes=64 << 20, **spec_kw):
    spec = get_preset(wl, theta=skew, ops=n_ops, load_records=records,
                      **spec_kw)
    idx = build_index(features, cfg or DEFAULT_CFG, records=records,
                      cache_bytes=cache_bytes)
    return idx, run_workload(idx, spec)


def table1_one_sided(n_ops=6_144):
    """§3.1 Table 1: the one-sided approach (FG+) across workloads."""
    rows = []
    print("\n== Table 1: one-sided approach (FG+) ==")
    print(f"{'workload':18s} {'dist':8s} {'Mops':>8s} {'p50us':>8s} "
          f"{'p99us':>10s}")
    for wl in ("read-intensive", "write-intensive"):
        for dist, skew in (("uniform", 0.0), ("skew", 0.99)):
            _, r = _run(FG_PLUS, skew, wl, n_ops)
            print(f"{wl:18s} {dist:8s} {r.mops:8.2f} {r.p50_us:8.1f} "
                  f"{r.p99_us:10.1f}")
            rows.append(csv_row(f"table1/{wl}/{dist}", r.p50_us,
                                f"mops={r.mops:.3f};p99us={r.p99_us:.1f}"))
    return rows


def fig10_11_breakdown(skew: float, label: str, n_ops=6_144):
    """Fig 10 (skew=0.99) / Fig 11 (uniform): technique ladder."""
    rows = []
    print(f"\n== Fig {label}: technique breakdown (skew={skew}) ==")
    print(f"{'config':14s}{'workload':18s} {'Mops':>8s} {'p50us':>8s} "
          f"{'p99us':>10s}")
    for wl in ("write-only", "write-intensive", "read-intensive"):
        base = None
        for name, feat in ABLATION_LADDER:
            _, r = _run(feat, skew, wl, n_ops)
            base = base or r.mops
            print(f"{name:14s}{wl:18s} {r.mops:8.2f} {r.p50_us:8.1f} "
                  f"{r.p99_us:10.1f}")
            rows.append(csv_row(
                f"fig{label}/{wl}/{name}", r.p50_us,
                f"mops={r.mops:.3f};p99us={r.p99_us:.1f};"
                f"speedup={r.mops / base:.2f}"))
    return rows


def fig12_range(n_ops=2_048):
    """Fig 12: range query (range-only + range-write)."""
    rows = []
    print("\n== Fig 12: range query ==")
    for size in (10, 50):
        for wl in ("range-only", "range-write"):
            for feat, nm in ((FG_PLUS, "FG+"), (SHERMAN, "Sherman")):
                _, r = _run(feat, 0.99, wl, n_ops, scan_len=size)
                print(f"{wl} size={size:4d} {nm:8s} mops={r.mops:.2f}")
                rows.append(csv_row(f"fig12/{wl}/{size}/{nm}", r.p50_us,
                                    f"mops={r.mops:.3f}"))
    return rows


def fig13_scalability(n_threads=(128, 256, 512, 1024, 2048)):
    """Fig 13: client threads scaling, uniform + skew (0.99)."""
    rows = []
    print("\n== Fig 13: scalability (write-intensive) ==")
    for skew, nm in ((0.0, "uniform"), (0.9, "skew0.9"), (0.99, "skew0.99")):
        for feat, sysn in ((FG_PLUS, "FG+"), (SHERMAN, "Sherman")):
            for nt in n_threads:
                _, r = _run(feat, skew, "write-intensive", 2 * nt, batch=nt)
                print(f"{nm:9s} {sysn:8s} threads={nt:5d} "
                      f"mops={r.mops:8.2f}")
                rows.append(csv_row(f"fig13/{nm}/{sysn}/{nt}", r.p50_us,
                                    f"mops={r.mops:.3f}"))
    return rows


def fig14_internal(n_ops=6_144):
    """Fig 14: retries, round-trip CDF, write sizes."""
    rows = []
    print("\n== Fig 14: internal metrics (write-intensive, skew 0.99) ==")
    for feat, nm in ((FG_PLUS, "FG+"), (SHERMAN, "Sherman")):
        idx, r = _run(feat, 0.99, "write-intensive", n_ops)
        print(f"{nm:8s} doorbells p50={r.doorbells_p50:.0f} "
              f"p99={r.doorbells_p99:.0f}  "
              f"write-bytes median={r.write_bytes_median:.0f}  "
              f"cas_msgs={idx.counters['cas_msgs']}")
        rows.append(csv_row(
            f"fig14/{nm}", r.p50_us,
            f"doorbells_p50={r.doorbells_p50:.0f};"
            f"doorbells_p99={r.doorbells_p99:.0f};"
            f"write_bytes={r.write_bytes_median:.0f};"
            f"cas={idx.counters['cas_msgs']}"))
    return rows


def fig15_sensitivity():
    """Fig 15: key size and index-cache size sensitivity."""
    import dataclasses
    rows = []
    print("\n== Fig 15a: key size (write-intensive, uniform) ==")
    for kb in (16, 64, 256, 1024):
        for feat, nm in ((FG_PLUS, "FG+"), (SHERMAN, "Sherman")):
            cfg = dataclasses.replace(DEFAULT_CFG, key_bytes=kb, fanout=16)
            _, r = _run(feat, 0.0, "write-intensive", 2_048, cfg=cfg,
                        records=20_000)
            print(f"key={kb:5d}B {nm:8s} mops={r.mops:8.2f}")
            rows.append(csv_row(f"fig15a/key{kb}/{nm}", r.p50_us,
                                f"mops={r.mops:.3f}"))
    print("\n== Fig 15c: index cache size (uniform write-intensive) ==")
    # budgets chosen around the tree's internal-level footprint so the
    # functional cache actually evicts level-1 nodes at the small end
    # (the paper scales cache vs a 1B-key tree; we scale cache vs leaves)
    for cache_kb in (2, 4, 8, 64):
        idx, r = _run(SHERMAN, 0.0, "write-intensive", 12_288,
                      records=8_000, cache_bytes=cache_kb << 10)
        hr = idx.cache.hit_ratio
        print(f"cache={cache_kb:5d}KB mops={r.mops:8.2f} "
              f"hit_ratio={hr:.3f}")
        rows.append(csv_row(f"fig15c/cache{cache_kb}KB", r.p50_us,
                            f"mops={r.mops:.3f};hit={hr:.3f}"))
    return rows


def fig_cache_sweep(n_ops=4_096, records=20_000):
    """Cache-size sweep over the *functional* CS cache (§4.2.3): hit/stale
    rates, remote reads per lookup, and throughput vs cache budget, on a
    read-heavy mix and on a mixed insert workload that goes stale."""
    rows = []
    print("\n== Cache sweep: CS index cache (read-intensive vs ycsb-d) ==")
    print(f"{'workload':16s} {'cacheKB':>8s} {'Mops':>8s} {'hit%':>7s} "
          f"{'stale':>6s} {'rd/lookup':>10s}")
    for wl in ("read-intensive", "ycsb-d"):
        for cache_kb in (0, 16, 64, 256, 4096):
            idx, r = _run(SHERMAN, 0.99, wl, n_ops, records=records,
                          cache_bytes=cache_kb << 10)
            print(f"{wl:16s} {cache_kb:8d} {r.mops:8.2f} "
                  f"{100 * r.cache_hit_rate:7.1f} {r.cache_stale:6d} "
                  f"{r.reads_per_lookup:10.2f}")
            rows.append(csv_row(
                f"figcache/{wl}/{cache_kb}KB", r.p50_us,
                f"mops={r.mops:.3f};hit={r.cache_hit_rate:.3f};"
                f"stale={r.cache_stale};rdl={r.reads_per_lookup:.2f}"))
    return rows


def ablation_sweep(n_ops=4_096, records=20_000,
                   json_path="BENCH_ablation.json"):
    """Fig. 10/11 technique ladder on a write-heavy YCSB-A batch, replayed
    through the verb-trace plane, plus the single-feature negations of
    full Sherman (`sherman-nocombine`, `sherman-flat`).

    Writes ``BENCH_ablation.json`` — the seed of the repo's perf
    trajectory: the ladder order, per-system Mops/p99, and the verb/
    doorbell totals that make the combine/hierarchy wins auditable.
    """
    import dataclasses as _dc

    from repro.workloads import get_preset, run_systems, write_json
    rows = []
    ladder = [nm.lower() for nm, _ in ABLATION_LADDER]
    systems = ladder + ["sherman-nocombine", "sherman-flat"]
    spec = get_preset("ycsb-a", ops=n_ops, load_records=records)
    results = run_systems(spec, systems)
    # the ladder's last rung *is* full Sherman — alias it instead of
    # paying a second identical build + run
    sherman = _dc.replace(results[len(ladder) - 1], system="sherman")
    results.insert(len(ladder), sherman)
    print("\n== Ablation sweep (YCSB-A, verb plane) ==")
    print(f"{'system':18s} {'Mops':>8s} {'p99us':>10s} {'verbs':>9s} "
          f"{'dbells':>9s} {'saved':>7s}")
    for r in results:
        print(f"{r.system:18s} {r.mops:8.2f} {r.p99_us:10.1f} "
              f"{r.verbs:9d} {r.doorbells:9d} {r.doorbells_saved:7d}")
        rows.append(csv_row(
            f"ablation/{r.system}", r.p50_us,
            f"mops={r.mops:.3f};p99us={r.p99_us:.1f};"
            f"doorbells={r.doorbells};saved={r.doorbells_saved}"))
    write_json(json_path, spec, results, extra={"ladder": ladder})
    print(f"wrote {json_path}")
    return rows


def scaling_sweep(client_counts=(8, 16, 32, 64), n_ops=512,
                  records=8_000, json_path="BENCH_scaling.json",
                  partitioned=False):
    """Client-scaling sweep through the multi-CS cluster plane (§5 /
    Fig. 13, now *simulated* rather than lane-labelled): for each client
    count, a fleet of compute servers with private caches and lock
    tables hammers the shared memory pool, and per-CS verb traces merge
    into one contended timeline (DESIGN.md §11).

    Writes ``BENCH_scaling.json`` — the cluster acceptance artifact: one
    RunResult per (system, n_clients) with the per-CS breakdown and the
    merged-trace conservation flag.  The headline curve is SHERMAN's
    write-heavy advantage *growing* with client count while FG+'s atomic
    unit saturates.
    """
    from repro.workloads import get_preset, run_cluster_systems, write_json
    rows = []
    systems = ("sherman", "fg+")
    spec = get_preset("write-intensive", theta=0.99, ops=n_ops,
                      load_records=records)
    results = []
    print("\n== Client scaling (cluster plane, write-intensive 0.99) ==")
    print(f"{'clients':>8s} {'system':10s} {'Mops':>8s} {'p99us':>9s} "
          f"{'stale':>6s} {'xCS':>5s} {'cons':>5s}")
    for nc in client_counts:
        for r in run_cluster_systems(spec, systems, n_clients=nc,
                                     partitioned=partitioned):
            stale = sum(p["cache_stale"] for p in r.per_cs)
            print(f"{r.n_clients:8d} {r.system:10s} {r.mops:8.2f} "
                  f"{r.p99_us:9.1f} {stale:6d} "
                  f"{r.counters['cross_cs_conflicts']:5d} "
                  f"{'OK' if r.conservation_ok else 'BAD':>5s}")
            rows.append(csv_row(
                f"scaling/{r.system}/{r.n_clients}", r.p50_us,
                f"mops={r.mops:.3f};p99us={r.p99_us:.1f};"
                f"conservation={r.conservation_ok}"))
            results.append(r)
    write_json(json_path, spec, results,
               extra={"client_counts": [r.n_clients for r in
                                        results[::len(systems)]],
                      "systems": list(systems),
                      "partitioned": partitioned})
    print(f"wrote {json_path}")
    return rows


def load_sweep_bench(n_ops=2_048, records=8_000, n_clients=16,
                     preset="write-intensive", arrival="poisson",
                     json_path="BENCH_load.json"):
    """Open-loop load sweep through the serving plane (DESIGN.md §12):
    latency vs offered load, queueing delay separated from service time,
    SLO attainment, and max-sustainable-load per system.

    Writes ``BENCH_load.json`` — the serving-plane acceptance artifact:
    per (system, offered rate) one RunResult whose sojourn p99 bends up
    and whose ``sustained_frac`` collapses past each system's knee, plus
    the self-calibrated ``capacity_mops`` / ``max_sustainable_mops``
    summary.  The headline is SHERMAN sustaining a higher offered load
    than FG+ on the write-heavy mix.
    """
    from repro.serve import load_sweep
    payload = load_sweep(preset, arrival=arrival, n_clients=n_clients,
                         load_records=records, ops=n_ops, out=json_path)
    rows = []
    print(f"\n== Load sweep ({preset}, {arrival}, "
          f"{n_clients} clients) ==")
    print(f"{'system':10s} {'offered':>8s} {'p50us':>8s} {'p99us':>9s} "
          f"{'queue':>7s} {'svc':>6s} {'slo%':>6s} {'sust%':>6s}")
    for r in payload["results"]:
        print(f"{r['system']:10s} {r['offered_mops']:8.3f} "
              f"{r['p50_us']:8.2f} {r['p99_us']:9.2f} "
              f"{r['queue_mean_us']:7.2f} {r['service_mean_us']:6.2f} "
              f"{100 * r['slo_attainment']:6.1f} "
              f"{100 * r['sustained_frac']:6.1f}")
        rows.append(csv_row(
            f"load/{r['system']}/{r['offered_mops']:.3f}", r["p50_us"],
            f"p99us={r['p99_us']:.2f};queue_us={r['queue_mean_us']:.2f};"
            f"sustained={r['sustained_frac']:.3f}"))
    for name, cap in payload["capacity_mops"].items():
        print(f"  {name}: closed capacity {cap:.3f} Mops, max sustainable "
              f"{payload['max_sustainable_mops'][name]:.3f} Mops")
    print(f"wrote {json_path}")
    return rows


def throughput_sweep(op_counts=(4_096, 16_384, 65_536), records=60_000,
                     systems=("sherman", "fg+"), warmup_ops=2_048,
                     json_path="BENCH_throughput.json"):
    """Harness-performance sweep: wall-clock sim-ops/s and XLA compile
    counts vs. op count on YCSB-A (the PR 5 shape-stability acceptance).

    Each system warms its jit caches with a ``warmup_ops`` pass on a
    fresh index, then runs the measured op counts on the same index —
    bucketed dispatch means the measured passes must trigger (almost) no
    fresh compilations.  Writes ``BENCH_throughput.json``: per (system,
    n_ops) wall time, sim-ops/s (wall-clock harness throughput — the
    ~372 ops/s pre-PR-5 baseline is recorded for trend), compiles during
    warmup and measurement, plus the simulated Mops/p99 so perf changes
    in either plane are auditable.
    """
    import json as _json
    import time as _time

    from repro.workloads import SYSTEMS, get_preset, run_workload
    from repro.workloads.jitstats import count_compiles

    rows, results = [], []
    spec = get_preset("ycsb-a", load_records=records)
    print("\n== Throughput sweep (harness wall-clock, YCSB-A) ==")
    print(f"{'system':10s} {'ops':>7s} {'wall_s':>8s} {'ops/s':>9s} "
          f"{'warm.c':>7s} {'meas.c':>7s} {'simMops':>8s}")
    for system in systems:
        idx = build_index(SYSTEMS[system.lower()], DEFAULT_CFG,
                          records=records)
        with count_compiles() as warm:
            run_workload(idx, spec.replace(ops=warmup_ops), seed=7,
                         system=system)
        for n_ops in op_counts:
            with count_compiles() as meas:
                t0 = _time.perf_counter()
                r = run_workload(idx, spec.replace(ops=n_ops), seed=1,
                                 system=system)
                wall = _time.perf_counter() - t0
            entry = dict(system=system, n_ops=n_ops, wall_s=wall,
                         sim_ops_per_s=n_ops / wall,
                         compiles_warmup=warm.count,
                         compiles_measured=meas.count,
                         mops_sim=r.mops, p99_us=r.p99_us)
            results.append(entry)
            print(f"{system:10s} {n_ops:7d} {wall:8.2f} "
                  f"{entry['sim_ops_per_s']:9.0f} {warm.count:7d} "
                  f"{meas.count:7d} {r.mops:8.2f}")
            rows.append(csv_row(
                f"throughput/{system}/{n_ops}", 1e6 * wall / n_ops,
                f"ops_per_s={entry['sim_ops_per_s']:.0f};"
                f"compiles={meas.count}"))
    total_ops = sum(e["n_ops"] for e in results)
    total_wall = sum(e["wall_s"] for e in results)
    payload = dict(workload=spec.name, records=records,
                   batch=spec.batch, warmup_ops=warmup_ops,
                   baseline_ops_per_s=372,        # pre-PR-5 harness speed
                   aggregate_ops_per_s=total_ops / total_wall,
                   results=results)
    with open(json_path, "w") as f:
        _json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"wrote {json_path} "
          f"(aggregate {payload['aggregate_ops_per_s']:.0f} ops/s)")
    return rows


def fig16_hocl(n_locks=1_024, n_threads=1_024):
    """Fig 16: HOCL microbenchmark — lock/unlock on a skewed pattern.

    Modeled through the lock plane only (hocl group stats + netsim CAS
    pricing), matching the paper's lock-table microbenchmark."""
    import jax.numpy as jnp

    from repro.core import hocl
    from repro.core.tree import TreeConfig
    from repro.workloads import zipf_keys
    rows = []
    net = NetConfig()
    cfg = TreeConfig(n_ms=1, nodes_per_ms=n_locks, fanout=4,
                     n_locks_per_ms=n_locks, n_cs=8)
    rng = np.random.default_rng(5)
    locks = (zipf_keys(rng, n_threads, n_locks, 0.99) % n_locks
             ).astype(np.int32)
    cs = (np.arange(n_threads) * 8 // n_threads).astype(np.int32)
    g = hocl.group_by_node(cfg, jnp.asarray(locks), jnp.asarray(cs),
                           jnp.ones(n_threads, bool))
    node_rank = np.asarray(g.node_rank)
    node_size = np.asarray(g.node_size)
    local_rank = np.asarray(g.local_rank)
    print("\n== Fig 16: HOCL microbenchmark ==")
    configs = [
        ("baseline", False, False),     # host-memory CAS, no hierarchy
        ("+on-chip", True, False),
        ("+hierarchical", True, True),
    ]
    base = None
    for nm, onchip, hier in configs:
        cas = net.cas_onchip_s if onchip else net.cas_pcie_s
        if hier:
            attempts = (local_rank % (net.handover_max + 1) == 0)
            wait = node_rank * cas
            lat = attempts * net.rtt_s + wait
        else:
            attempts = 1 + node_rank
            wait = node_rank * (cas + net.rtt_s * 0.5)
            lat = net.rtt_s + wait
        hot = float(node_size.max()) * cas * \
            (1 if hier else float(node_size.max()) * 0.1 + 1)
        makespan = max(float(attempts.sum()) / (110e6 if onchip else 2e6),
                       hot, float(np.median(lat)))
        mops = n_threads / makespan / 1e6
        base = base or mops
        print(f"{nm:14s} mops={mops:9.2f} p50={np.percentile(lat, 50) * 1e6:7.2f}us "
              f"p99={np.percentile(lat, 99) * 1e6:8.2f}us "
              f"({mops / base:.2f}x)")
        rows.append(csv_row(f"fig16/{nm}",
                            float(np.percentile(lat, 50)) * 1e6,
                            f"mops={mops:.2f};x={mops / base:.2f}"))
    return rows


def chaos_sweep_bench(records=6_000, n_ops=4_096, n_clients=16,
                      json_path="BENCH_chaos.json"):
    """Chaos sweep through the fault-injection plane (DESIGN.md §13):
    per system, a calibrated fault-free run then the standard five-event
    schedule (MS crash with memory loss, CS leave/join, hot-key storm
    in/out), reporting degraded throughput, SLO violations in the fault
    window and time-to-recover, with the differential-oracle and
    conservation audits inline.

    Writes ``BENCH_chaos.json`` — the recovery acceptance artifact
    scripts/ci.sh gates on (finite TTR and positive degraded throughput
    for every fault, both systems, oracle + conservation green).
    """
    from repro.chaos import chaos_sweep
    payload = chaos_sweep(records=records, ops=n_ops, n_clients=n_clients,
                          out=json_path)
    rows = []
    print(f"\n== Chaos sweep ({payload['preset']}, {n_clients} clients, "
          f"{len(payload['schedules'][payload['results'][0]['system']])} "
          f"faults) ==")
    print(f"{'system':8s} {'fault':10s} {'t_fault':>9s} {'ttr_ms':>8s} "
          f"{'degMops':>8s} {'slo%':>6s}")
    for r in payload["results"]:
        flags = (f"oracle={'OK' if r['oracle_ok'] else 'FAIL'} "
                 f"conserv={'OK' if r['conservation_ok'] else 'FAIL'} "
                 f"glt={'clean' if r['glt_clean'] else 'DIRTY'}")
        for f in r["faults"]:
            ttr = f["ttr_s"]
            print(f"{r['system']:8s} {f['kind']:10s} "
                  f"{f['t_fault_s'] * 1e3:9.3f} "
                  f"{(ttr or 0) * 1e3:8.3f} "
                  f"{f['degraded_mops'] or 0:8.3f} "
                  f"{100 * (f['slo_violation_frac'] or 0):6.1f}")
            rows.append(csv_row(
                f"chaos/{r['system']}/{f['kind']}",
                (ttr or 0) * 1e6,
                f"degraded_mops={f['degraded_mops'] or 0:.4f};"
                f"baseline_mops={r['baseline_mops']:.4f}"))
        print(f"  {r['system']}: baseline {r['baseline_mops']:.3f} Mops, "
              f"{flags}")
    print(f"wrote {json_path}")
    return rows


def obs_sweep(n_ops=1_024, records=8_000, tail_k=64,
              json_path="BENCH_obs.json"):
    """Observability sweep through the recording plane (DESIGN.md §14):
    the full ablation ladder replayed with a :class:`repro.obs.Recorder`
    attached, on a deliberately contended write-heavy batch (zipfian
    0.99, two memory servers) where the lock chains are deep enough for
    tail forensics to have something to say.

    Per rung it reports the p99 tail's exact latency attribution
    (nic_queue / atomic_ser / lock_wait / service, from the
    critical-path walk), the all-ops attribution, the span-conservation
    verdict and the maximum integer residual.

    Writes ``BENCH_obs.json`` — the tail-forensics acceptance artifact
    scripts/ci.sh gates on: zero residual and green span accounting on
    every rung, and the HOCL story made quantitative — enabling the
    hierarchical lock shifts the tail's attribution out of
    lock-protocol wait and into NIC/data time (Fig. 10/11, per op).
    """
    import dataclasses as _dc

    from repro.core.tree import TreeConfig
    from repro.workloads import get_preset, run_systems, write_json

    cfg = TreeConfig(n_ms=2, nodes_per_ms=8_192, fanout=16,
                     n_locks_per_ms=4_096, max_height=7, n_cs=8)
    ladder = [nm.lower() for nm, _ in ABLATION_LADDER]
    spec = get_preset("write-intensive", theta=0.99, ops=n_ops,
                      batch=max(128, n_ops // 2), load_records=records)
    recorders = {}
    results = run_systems(spec, ladder, cfg, recorders=recorders,
                          tail_k=tail_k)
    # the ladder's last rung *is* full Sherman — alias it
    results.append(_dc.replace(results[-1], system="sherman"))
    rows = []
    print(f"\n== Observability sweep (write-intensive 0.99, "
          f"{cfg.n_ms} MS, tail_k={tail_k}) ==")
    print(f"{'system':14s} {'p99us':>9s} {'nic%':>6s} {'atom%':>6s} "
          f"{'lock%':>6s} {'svc%':>6s} {'resid':>6s} {'spans':>6s}")
    for r in results:
        t = r.obs["tail_attribution"]
        print(f"{r.system:14s} {r.p99_us:9.1f} "
              f"{100 * t['nic_queue_frac']:6.1f} "
              f"{100 * t['atomic_ser_frac']:6.1f} "
              f"{100 * t['lock_wait_frac']:6.1f} "
              f"{100 * t['service_frac']:6.1f} "
              f"{r.obs['attr_residual_ps']:6d} "
              f"{'OK' if r.obs['spans_ok'] else 'BAD':>6s}")
        rows.append(csv_row(
            f"obs/{r.system}", r.p99_us,
            f"lock={t['lock_wait_frac']:.3f};"
            f"nic={t['nic_queue_frac']:.3f};"
            f"atomic={t['atomic_ser_frac']:.3f};"
            f"service={t['service_frac']:.3f};"
            f"residual_ps={r.obs['attr_residual_ps']};"
            f"spans_ok={r.obs['spans_ok']}"))
    write_json(json_path, spec, results,
               extra={"kind": "obs", "ladder": ladder, "tail_k": tail_k,
                      "n_ms": cfg.n_ms})
    print(f"wrote {json_path}")
    return rows
