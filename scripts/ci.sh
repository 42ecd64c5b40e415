#!/usr/bin/env bash
# CI smoke: tier-1 tests + a quick paper-figure run + the workload CLI.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src:.${PYTHONPATH:+:$PYTHONPATH}"
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

# every gated BENCH artifact must exist before its gate reads it — a
# sweep that silently failed to write its file is a CI bug, not a pass
require_bench() {
    for f in "$@"; do
        if [ ! -s "$f" ]; then
            echo "FATAL: gated benchmark artifact $f is missing or empty" >&2
            exit 1
        fi
    done
}

echo "== tier-1 tests =="
python -m pytest -x -q

echo "== benchmark smoke (Table 1, quick) =="
python benchmarks/run.py --quick --only table1

echo "== verb-trace conservation check =="
python -m pytest -q tests/test_netsim_trace.py -k \
    "conservation or cycle_masks or doorbell"

echo "== vectorized-replay equivalence + compile stability =="
python -m pytest -q tests/test_throughput.py -k \
    "simulate_matches or property_simulate or compiles or bucketing"

echo "== throughput smoke gate (writes BENCH_throughput.json) =="
python benchmarks/run.py --quick --only throughput
require_bench BENCH_throughput.json
python - <<'EOF'
import json, math

d = json.load(open("BENCH_throughput.json"))
assert d["workload"] == "ycsb-a"
# the PR 5 acceptance floor: >= 20x the ~372 ops/s pre-PR-5 harness
assert d["aggregate_ops_per_s"] >= 7_500, d["aggregate_ops_per_s"]
by_sys = {}
for r in d["results"]:
    for k in ("wall_s", "sim_ops_per_s", "mops_sim", "p99_us"):
        assert math.isfinite(r[k]) and r[k] > 0, (r["system"], k, r[k])
    # bucketed dispatch: (almost) nothing compiles after warmup
    assert r["compiles_measured"] <= 8, r
    by_sys.setdefault(r["system"], []).append(r)
assert {"sherman", "fg+"} <= set(by_sys), sorted(by_sys)
big = [r for r in d["results"] if r["n_ops"] >= 65_536]
assert big, "sweep must include the 65536-op acceptance point"
for r in big:
    assert r["sim_ops_per_s"] >= 7_500, (r["system"], r["sim_ops_per_s"])
print("throughput OK:",
      " ".join(f"{r['system']}@{r['n_ops']}={r['sim_ops_per_s']:.0f}ops/s"
               f"(c={r['compiles_measured']})" for r in d["results"]),
      f"| aggregate {d['aggregate_ops_per_s']:.0f} ops/s")
EOF

echo "== ablation sweep (verb plane, writes BENCH_ablation.json) =="
python benchmarks/run.py --quick --only ablation
require_bench BENCH_ablation.json
python - <<'EOF'
import json, math

d = json.load(open("BENCH_ablation.json"))
res = {r["system"]: r for r in d["results"]}
ladder = d["ladder"]
mops = [res[s]["mops"] for s in ladder]
assert all(math.isfinite(m) and m > 0 for m in mops), mops
assert all(b >= 0.98 * a for a, b in zip(mops, mops[1:])), \
    ("ablation ladder regressed", list(zip(ladder, mops)))
sh = res["sherman"]
assert sh["doorbells"] < res["sherman-nocombine"]["doorbells"], \
    (sh["doorbells"], res["sherman-nocombine"]["doorbells"])
assert math.isfinite(sh["p99_us"]) and 0 < sh["p99_us"] < \
    res["sherman-flat"]["p99_us"], \
    (sh["p99_us"], res["sherman-flat"]["p99_us"])
print("ablation OK:", " -> ".join(f"{s}={m:.2f}" for s, m in
                                  zip(ladder, mops)),
      f"| doorbells {sh['doorbells']} < "
      f"{res['sherman-nocombine']['doorbells']}",
      f"| p99 {sh['p99_us']:.1f}us < "
      f"{res['sherman-flat']['p99_us']:.1f}us")
EOF

echo "== cluster scaling sweep (writes BENCH_scaling.json) =="
python benchmarks/run.py --quick --only scaling
require_bench BENCH_scaling.json
python - <<'EOF'
import json, math

d = json.load(open("BENCH_scaling.json"))
systems = d["systems"]
counts = d["client_counts"]
assert len(counts) >= 4, ("need >= 4 client counts", counts)
assert set(systems) == {"sherman", "fg+"}, systems
by = {(r["system"], r["n_clients"]): r for r in d["results"]}
assert len(by) == len(counts) * len(systems), "missing sweep points"
for r in d["results"]:
    # merged-trace verb conservation must hold at every sweep point
    assert r["conservation_ok"], (r["system"], r["n_clients"])
    assert math.isfinite(r["mops"]) and r["mops"] > 0
    assert len(r["per_cs"]) >= 2, "cluster runs must report >= 2 CSs"
    assert sum(p["ops"] for p in r["per_cs"]) >= r["n_ops"]

ratios = [by[("sherman", n)]["mops"] / by[("fg+", n)]["mops"]
          for n in counts]
# SHERMAN >= FG+ write-heavy throughput at max clients, and the
# advantage grows with client count
assert ratios[-1] >= 1.0, ratios
assert ratios[-1] > ratios[0] * 1.02, ("advantage must grow", ratios)
# p99 tail is monotone in client count (queue depth) until saturation
for s in systems:
    p99 = [by[(s, n)]["p99_us"] for n in counts]
    assert all(math.isfinite(p) and p > 0 for p in p99), (s, p99)
    assert all(b >= 0.95 * a for a, b in zip(p99, p99[1:])), (s, p99)
print("scaling OK:",
      " ".join(f"{n}cl={r:.2f}x" for n, r in zip(counts, ratios)),
      "| p99(sherman)",
      "->".join(f"{by[('sherman', n)]['p99_us']:.1f}" for n in counts))
EOF

echo "== open-loop load sweep (serving plane, writes BENCH_load.json) =="
python benchmarks/run.py --quick --only load
require_bench BENCH_load.json
python - <<'EOF'
import json, math

d = json.load(open("BENCH_load.json"))
assert d["kind"] == "load_sweep"
systems = set(d["capacity_mops"])
assert systems == {"sherman", "fg+"}, systems
rates = d["rates_mops"]
assert len(rates) >= 4, ("need >= 4 offered-load points", rates)
by = {}
for r in d["results"]:
    assert r["arrival"] == d["arrival"], r["arrival"]
    # queueing delay must be reported separately from service time
    assert math.isfinite(r["queue_mean_us"]) and r["queue_mean_us"] >= 0
    assert math.isfinite(r["service_mean_us"]) and r["service_mean_us"] > 0
    assert math.isfinite(r["p99_us"]) and r["p99_us"] > 0
    assert 0 <= r["slo_attainment"] <= 1, r["slo_attainment"]
    assert 0 < r["sustained_frac"] <= 1, r["sustained_frac"]
    assert r["conservation_ok"], (r["system"], r["offered_mops"])
    by.setdefault(r["system"], []).append(r)
for s in systems:
    assert len(by[s]) == len(rates), (s, len(by[s]), len(rates))
    # max sustainable load: finite, positive, one of the swept rates
    ms = d["max_sustainable_mops"][s]
    assert math.isfinite(ms) and ms > 0, (s, ms)
    assert any(abs(ms - r) < 1e-9 for r in rates), (s, ms, rates)
# the write-optimized system sustains >= the baseline's offered load
# on the write-heavy preset
assert d["max_sustainable_mops"]["sherman"] >= \
    d["max_sustainable_mops"]["fg+"], d["max_sustainable_mops"]
print("load OK:",
      " ".join(f"{s}: cap={d['capacity_mops'][s]:.2f} "
               f"sustained<={d['max_sustainable_mops'][s]:.2f}Mops"
               for s in sorted(systems)),
      f"| {len(rates)} rates, slo={d['slo_us']:.1f}us")
EOF

echo "== chaos sweep (fault injection, writes BENCH_chaos.json) =="
python benchmarks/run.py --quick --only chaos
require_bench BENCH_chaos.json
python - <<'EOF'
import json, math

d = json.load(open("BENCH_chaos.json"))
assert d["kind"] == "chaos"
systems = {r["system"] for r in d["results"]}
assert systems == {"sherman", "fg+"}, systems
for r in d["results"]:
    # the differential harness must hold under the full schedule
    assert r["oracle_ok"], (r["system"], "differential oracle broken")
    assert r["conservation_ok"], (r["system"], "conservation across crash")
    assert r["glt_clean"], (r["system"], "locks leaked after recovery")
    assert r["unfired_faults"] == 0, (r["system"], r["unfired_faults"])
    assert math.isfinite(r["baseline_mops"]) and r["baseline_mops"] > 0
    assert math.isfinite(r["slo_us"]) and r["slo_us"] > 0
    kinds = {f["kind"] for f in r["faults"]}
    assert {"ms_crash", "cs_leave", "cs_join", "skew_shift"} <= kinds, kinds
    for f in r["faults"]:
        # recovery gate: every fault recovers in finite time with
        # positive throughput inside the degraded window
        assert f["ttr_s"] is not None and math.isfinite(f["ttr_s"]) \
            and f["ttr_s"] >= 0, (r["system"], f["kind"], f["ttr_s"])
        assert f["degraded_mops"] is not None \
            and math.isfinite(f["degraded_mops"]) \
            and f["degraded_mops"] > 0, (r["system"], f["kind"])
        assert 0 <= f["slo_violation_frac"] <= 1, (r["system"], f["kind"])
crash = {r["system"]: [f for f in r["faults"] if f["kind"] == "ms_crash"][0]
         for r in d["results"]}
print("chaos OK:",
      " ".join(f"{s}: crash ttr={c['ttr_s'] * 1e3:.2f}ms "
               f"deg={c['degraded_mops']:.3f}Mops"
               for s, c in sorted(crash.items())))
EOF

echo "== observability sweep (tail forensics, writes BENCH_obs.json) =="
python benchmarks/run.py --quick --only obs
require_bench BENCH_obs.json
python - <<'PYEOF'
import json, math

d = json.load(open("BENCH_obs.json"))
assert d["kind"] == "obs"
ladder = d["ladder"]
res = {r["system"]: r for r in d["results"]}
assert set(ladder) <= set(res) and "sherman" in res, sorted(res)
FRACS = ("nic_queue_frac", "atomic_ser_frac", "lock_wait_frac",
         "service_frac")
for name, r in res.items():
    obs = r["obs"]
    # conservation: exact integer attribution + green span accounting
    # on every rung
    assert obs["attr_residual_ps"] == 0, (name, obs["attr_residual_ps"])
    assert obs["spans_ok"], (name, "span accounting broken")
    assert obs["verbs"] > 0 and obs["ops"] > 0, name
    assert len(obs["tail"]) == d["tail_k"], (name, len(obs["tail"]))
    for a in (obs["attribution"], obs["tail_attribution"]):
        assert all(0 <= a[k] <= 1 for k in FRACS), (name, a)
        assert abs(sum(a[k] for k in FRACS) - 1) < 1e-9, (name, a)
# the HOCL story, quantitative: enabling the hierarchical lock moves
# the p99 tail's attribution out of lock-protocol wait and into
# NIC/data time (queue + service), and NIC queueing itself rises
pre = res["+on-chip"]["obs"]["tail_attribution"]
post = res["+hierarchical"]["obs"]["tail_attribution"]
sherman = res["sherman"]["obs"]["tail_attribution"]
for name, t in (("+hierarchical", post), ("sherman", sherman)):
    assert t["lock_wait_frac"] < 0.8 * pre["lock_wait_frac"], \
        ("HOCL must cut the tail lock share", name, t, pre)
    assert t["nic_queue_frac"] > pre["nic_queue_frac"], \
        ("HOCL must raise the tail NIC-queue share", name, t, pre)
    assert (t["nic_queue_frac"] + t["service_frac"]
            > pre["nic_queue_frac"] + pre["service_frac"]), (name, t, pre)
locks = " ".join(
    "{}: lock={:.2f}".format(
        s, res[s]["obs"]["tail_attribution"]["lock_wait_frac"])
    for s in ladder)
print(f"obs OK: {locks} | sherman tail: "
      f"nic={sherman['nic_queue_frac']:.3f} "
      f"svc={sherman['service_frac']:.3f}")
PYEOF

echo "== open-loop CLI smoke (poisson arrivals) =="
python -m repro.workloads --preset write-intensive --quick \
    --records 4000 --ops 256 --batch 128 --systems sherman \
    --n-clients 8 --arrival poisson --rate 0.5 \
    --json BENCH_ci_open.json

echo "== cluster CLI smoke (2 CS, write-intensive) =="
python -m repro.workloads --preset write-intensive --quick \
    --records 4000 --ops 256 --batch 128 --systems sherman \
    --n-clients 2 --json BENCH_ci_cluster.json

echo "== docstring cross-references =="
python scripts/check_xrefs.py

echo "== workload CLI smoke (YCSB-A, tiny) =="
python -m repro.workloads --preset ycsb-a --quick \
    --records 4000 --ops 512 --batch 256 --json BENCH_ci_smoke.json

echo "== cache-enabled workload smoke (YCSB-C, explicit --cache-bytes) =="
python -m repro.workloads --preset ycsb-c --quick \
    --records 4000 --ops 512 --batch 256 --systems sherman \
    --cache-bytes $((64 << 20)) --json BENCH_ci_cache.json

echo "== BENCH json schema validation (docs/BENCHMARKS.md) =="
python - <<'EOF'
import json, math

SPEC_FIELDS = {"name", "read", "insert", "update", "delete", "scan", "rmw",
               "distribution", "theta", "scan_len", "load_records", "ops",
               "batch", "arrival", "offered_mops", "burst_factor",
               "burst_frac", "diurnal_period_s", "diurnal_peak"}
RESULT_FIELDS = {"mops", "p50_us", "p90_us", "p99_us", "counters", "system",
                 "workload", "n_ops", "read_p50_us", "read_p99_us",
                 "write_p50_us", "write_p99_us", "doorbells_p50",
                 "doorbells_p99",
                 "write_bytes_median", "op_counts", "cache_hits",
                 "cache_misses", "cache_stale", "cache_hit_rate",
                 "reads_per_lookup", "verbs", "doorbells",
                 "doorbells_saved", "retried_ops", "n_clients", "rounds",
                 "per_cs", "conservation_ok", "arrival", "offered_mops",
                 "queue_mean_us", "queue_p50_us", "queue_p99_us",
                 "service_mean_us", "slo_us", "slo_attainment",
                 "sustained_frac", "obs"}
COUNTER_KEYS = {"phases", "write_ops", "retried_ops", "read_ops",
                "leaf_splits",
                "internal_splits", "root_splits", "split_same_ms",
                "cas_msgs", "handovers", "msgs", "bytes", "sim_time_s",
                "cache_hits", "cache_misses", "cache_stale", "lookup_ops",
                "lookup_reads", "verbs", "doorbells", "hocl_cas",
                "flat_cas"}
FINITE = ("mops", "p50_us", "p90_us", "p99_us", "doorbells_p50",
          "doorbells_p99", "write_bytes_median")

for path in ("BENCH_ci_smoke.json", "BENCH_ci_cache.json",
             "BENCH_ci_cluster.json", "BENCH_scaling.json",
             "BENCH_ci_open.json", "BENCH_load.json"):
    d = json.load(open(path))
    missing = SPEC_FIELDS - set(d["spec"])
    assert not missing, (path, "spec missing", missing)
    for r in d["results"]:
        assert RESULT_FIELDS <= set(r), (path, RESULT_FIELDS - set(r))
        assert COUNTER_KEYS <= set(r["counters"]), \
            (path, COUNTER_KEYS - set(r["counters"]))
        assert r["mops"] > 0 and r["p99_us"] > 0
        # json floats must be finite: a zero-time run reports 0.0, never
        # the non-standard Infinity token
        for k in FINITE:
            assert math.isfinite(r[k]), (path, k, r[k])

d = json.load(open("BENCH_ci_smoke.json"))
systems = {r["system"] for r in d["results"]}
assert systems == {"sherman", "fg+"}, systems

c = json.load(open("BENCH_ci_cache.json"))["results"][0]
assert c["cache_hit_rate"] >= 0.9, c["cache_hit_rate"]
assert 0 < c["reads_per_lookup"] <= 1.5, c["reads_per_lookup"]

cl = json.load(open("BENCH_ci_cluster.json"))["results"][0]
assert cl["n_clients"] == 2 and len(cl["per_cs"]) == 2, \
    (cl["n_clients"], len(cl["per_cs"]))
assert cl["conservation_ok"] and cl["rounds"] > 0

op = json.load(open("BENCH_ci_open.json"))["results"][0]
assert op["arrival"] == "poisson" and op["offered_mops"] > 0
assert op["queue_mean_us"] >= 0 and op["service_mean_us"] > 0, \
    (op["queue_mean_us"], op["service_mean_us"])
assert 0 < op["sustained_frac"] <= 1
print("BENCH schema OK; cache smoke:",
      f"hit_rate={c['cache_hit_rate']:.3f}",
      f"reads/lookup={c['reads_per_lookup']:.2f};",
      f"cluster smoke: {len(cl['per_cs'])} CS, {cl['rounds']} rounds")
EOF

echo "== bench regression vs committed baselines =="
python scripts/check_bench_regression.py
