"""Benchmark driver: one function per paper table/figure.

All figures run on the unified workload engine (:mod:`repro.workloads`).
Prints ``name,us_per_call,derived`` CSV rows (stdout) plus human tables;
``--quick`` shrinks op counts for CI-speed runs and ``--json`` writes the
rows to a ``BENCH_*.json`` file.
"""
from __future__ import annotations

import argparse
import json


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller op counts (CI)")
    ap.add_argument("--only", default="",
                    help="comma list: table1,fig10,fig11,fig12,fig13,"
                         "fig14,fig15,fig16,cache,ablation,scaling,"
                         "throughput,load,chaos,obs")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows to PATH (default "
                         "BENCH_paper_figs.json with --json '')")
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import paper_figs as F

    n = 2_048 if args.quick else 6_144
    sel = set(args.only.split(",")) if args.only else None
    rows = []

    def want(name):
        return sel is None or name in sel

    if want("table1"):
        rows += F.table1_one_sided(n_ops=n)
    if want("fig10"):
        rows += F.fig10_11_breakdown(0.99, "10", n_ops=n)
    if want("fig11"):
        rows += F.fig10_11_breakdown(0.0, "11", n_ops=n)
    if want("fig12"):
        rows += F.fig12_range(n_ops=max(512, n // 4))
    if want("fig13"):
        threads = (128, 512, 2048) if args.quick else \
            (128, 256, 512, 1024, 2048)
        rows += F.fig13_scalability(threads)
    if want("fig14"):
        rows += F.fig14_internal(n_ops=n)
    if want("fig15"):
        rows += F.fig15_sensitivity()
    if want("fig16"):
        rows += F.fig16_hocl()
    if want("cache"):
        rows += F.fig_cache_sweep(n_ops=max(1_024, n // 2))
    if want("ablation"):
        # verb-plane ladder; always writes BENCH_ablation.json (the perf
        # trajectory seed), independent of --json.  Since the PR 5
        # shape-stable hot path the full sweep runs at paper-ish scale.
        rows += F.ablation_sweep(n_ops=4_096 if args.quick else 65_536,
                                 records=8_000 if args.quick else 20_000)
    if want("scaling"):
        # multi-CS cluster plane; always writes BENCH_scaling.json (the
        # client-scaling acceptance curve), independent of --json
        rows += F.scaling_sweep(
            client_counts=(8, 16, 32, 64),
            n_ops=2_048 if args.quick else 32_768,
            records=8_000 if args.quick else 20_000)
    if want("load"):
        # open-loop serving plane; always writes BENCH_load.json (the
        # latency-vs-offered-load acceptance curve), independent of --json
        rows += F.load_sweep_bench(
            n_ops=1_024 if args.quick else 8_192,
            records=4_000 if args.quick else 20_000,
            n_clients=16)
    if want("chaos"):
        # fault-injection plane; always writes BENCH_chaos.json (the
        # recovery acceptance artifact), independent of --json
        rows += F.chaos_sweep_bench(
            records=4_000 if args.quick else 8_000,
            n_ops=2_048 if args.quick else 8_192,
            n_clients=8 if args.quick else 16)
    if want("obs"):
        # observability plane; always writes BENCH_obs.json (the
        # tail-forensics acceptance artifact: exact attribution +
        # span conservation per ladder rung)
        rows += F.obs_sweep(n_ops=1_024 if args.quick else 4_096,
                            records=8_000 if args.quick else 20_000)
    if want("throughput"):
        # harness-performance sweep; always writes BENCH_throughput.json
        # (wall-clock sim-ops/s + XLA compile counts — the PR 5 gate)
        rows += F.throughput_sweep(
            op_counts=(65_536,) if args.quick else (4_096, 16_384, 65_536),
            records=8_000 if args.quick else 60_000)

    print("\n# CSV")
    for r in rows:
        print(r)

    if args.json is not None:
        path = args.json or "BENCH_paper_figs.json"
        payload = []
        for r in rows:
            name, us, derived = r.split(",", 2)
            payload.append({"name": name, "us_per_call": float(us),
                            "derived": derived})
        with open(path, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
        print(f"wrote {path}")


if __name__ == "__main__":
    main()
