"""The benchmark's run of one cell: build, warm, window, check, metrics.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by the name ``BENCHMARK.json``
gives it:

* ``bench/configs/<config>.json``: the deployment (sizes, cache budget,
  guarantees, the network model the priced timeline follows);
* ``bench/traffic/<traffic>.json``: the mix this module's generator reads;
* ``bench/metrics/<metric>.py``: a ``read(ctx)`` that returns the metric or
  ``None`` when the run gave it nothing to read.

The window drives the program's served entry, ``repro.cluster.Cluster``'s
wave API, in closed-loop scheduler rounds in ``run_cluster``'s order,
with one write wave a round: ``lookup_wave`` for the round's reads,
``write_wave`` for its updates and inserts (each CS's updates, then its
inserts), ``end_round``.  The traffic is generated before the clock
starts, so the benchmark knows every op and every written value for the
reference.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import os
import sys
import time
from typing import Callable, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import reference as R          # noqa: E402  (bench-local modules)
import traffic as T            # noqa: E402

#: Priced waves kept per kind (lookup, write, cache fill, version sweep)
#: for the reference replay.
REPLAY_SAMPLE = 3
#: Window rounds generated per warm round time that fits in the window.
ROUND_MARGIN = 3.0
#: The host span that bounds a traced run's window.
TRACE_SPAN = "bench.window"
#: Every number ``correct`` compares is a count of wrong answers: exact.
EXACT = 0


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with its configuration and mix loaded."""
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: list
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_json: Optional[str] = None) -> Cell:
    """Find cell ``name`` in ``BENCHMARK.json`` and load its files."""
    with open(bench_json or os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    with open(os.path.join(BENCH, "configs", w["config"] + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
        mix = json.load(f)
    T.check_mix(mix)
    return Cell(name=name, config=config, traffic=mix, chips=w["chips"],
                end_to_end=[m for m in spec["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in spec["per_layer"]
                           if _applies(m, name)])


def metric_reader(name: str) -> Callable:
    """``bench/metrics/<name>.py``'s ``read``."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# --------------------------------------------------------------------------
# the deployment
# --------------------------------------------------------------------------

def build(cell: Cell, seed: int):
    """Bulk-load the configuration's pool and stand up its CS fleet through
    the program's ``build_cluster``; load values come from ``seed``."""
    from repro.cluster import build_cluster
    from repro.core import SHERMAN, TreeConfig
    from repro.core.netsim import NetConfig
    c = cell.config
    if c["features"] != "sherman":
        raise ValueError(f"config {c['name']}: features {c['features']!r}")
    cfg = TreeConfig(n_ms=c["n_ms"], nodes_per_ms=c["nodes_per_ms"],
                     fanout=c["fanout"], n_locks_per_ms=c["n_locks_per_ms"],
                     max_height=c["max_height"], n_cs=c["n_cs"],
                     key_bytes=c["key_bytes"], value_bytes=c["value_bytes"])
    net = NetConfig(**c["net"])
    return build_cluster(SHERMAN, cfg, n_clients=c["n_cs"]
                         * cell.traffic["lanes_per_cs"],
                         records=c["records"], keyspace=c["keyspace"],
                         cache_bytes=c["cache_bytes_per_cs"],
                         sync_rounds=c["sync_rounds"],
                         seed=seed % (1 << 64), fill=c["fill"], net=net)


def generator(cell: Cell, seed: int) -> T.Generator:
    c = cell.config
    return T.Generator(cell.traffic, n_cs=c["n_cs"], records=c["records"],
                       keyspace=c["keyspace"], value_mask=c["value_mask"],
                       seed=seed)


# --------------------------------------------------------------------------
# the priced-timeline tap
# --------------------------------------------------------------------------

class PricedTap:
    """Wraps ``repro.core.netsim.price_merged_phase`` (the module attribute
    the scheduler calls) while active: keeps a seeded sample of priced
    waves per kind for the reference replay, and, in a traced run, puts a
    ``bench.price_merged_phase`` span around each call."""

    def __init__(self, seed: int, annotate: bool):
        self.rng = np.random.default_rng((seed % (1 << 64), 7))
        self.annotate = annotate
        self.seen: dict = {}
        self.kept: dict = {}
        self.waves = 0

    @contextlib.contextmanager
    def active(self):
        from repro.core import netsim
        orig = netsim.price_merged_phase

        def priced(traces, *a, **kw):
            span = (_span("price_merged_phase") if self.annotate
                    else contextlib.nullcontext())
            with span:
                sim, merged = orig(traces, *a, **kw)
            self._keep(merged, sim)
            return sim, merged

        netsim.price_merged_phase = priced
        try:
            yield self
        finally:
            netsim.price_merged_phase = orig

    def _keep(self, merged, sim) -> None:
        """Reservoir sample of ``REPLAY_SAMPLE`` waves per kind."""
        self.waves += 1
        kind = wave_kind(merged)
        n = self.seen[kind] = self.seen.get(kind, 0) + 1
        keep = self.kept.setdefault(kind, [])
        if len(keep) < REPLAY_SAMPLE:
            keep.append((merged, sim))
        else:
            j = int(self.rng.integers(0, n))
            if j < REPLAY_SAMPLE:
                keep[j] = (merged, sim)

    def samples(self) -> list:
        return [w for k in sorted(self.kept) for w in self.kept[k]]


def wave_kind(merged) -> str:
    """``sweep`` (version-sweep reads), ``fill`` (other background reads,
    no client lanes), ``cas`` (a write wave's locks) or ``plain``."""
    if not merged.n_lanes:
        return ("sweep" if (np.asarray(merged.role) == R.SYNC).any()
                else "fill")
    return "cas" if (np.asarray(merged.kind) == R.CAS).any() else "plain"


def _span(what: str):
    import jax
    return jax.profiler.TraceAnnotation("bench." + what)


# --------------------------------------------------------------------------
# rounds
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Served:
    """What one round sent and what the program answered."""
    rnd: T.Round
    answers: list               # per CS (values, found) of its reads
    t_read: float               # perf_counter when lookup_wave returned
    t_write: float              # ... when write_wave (and the pool) did
    t_end: float                # ... when end_round did


def drive_round(cluster, gen: T.Generator, rnd: T.Round,
                annotate: bool = False) -> Served:
    """One scheduler round through the served wave API."""
    import jax
    span = _span if annotate else (lambda _: contextlib.nullcontext())
    answers = [(np.zeros(0, np.int32), np.zeros(0, bool))] * gen.n_cs
    if rnd.n_reads:
        with span("lookup_wave"):
            answers = cluster.lookup_wave([gen.keys(r)
                                           for r in rnd.read_ranks])
    t_read = time.perf_counter()
    if rnd.n_updates or rnd.n_inserts:
        with span("write_wave"):
            cluster.write_wave(
                [gen.keys(np.concatenate([u, i])) for u, i in
                 zip(rnd.update_ranks, rnd.insert_ranks)],
                [np.concatenate([u, i]) for u, i in
                 zip(rnd.update_vals, rnd.insert_vals)])
            jax.block_until_ready(cluster.state)
    t_write = time.perf_counter()
    with span("end_round"):
        cluster.end_round()
        jax.block_until_ready(cluster.state)
    return Served(rnd, answers, t_read, t_write, time.perf_counter())


def op_latencies(served: list, t_prev: np.ndarray, lanes: int):
    """Closed-loop latency of every op: a client issues its next op when
    its previous one returned, and the op ends when the wave that carries
    it returns.  Lane ``i`` of CS ``cs`` is one client; a round's reads
    take its first lanes, its updates and then its inserts the next.
    ``t_prev[cs, i]`` is when that client's last op returned (updated in
    place)."""
    out = []
    for s in served:
        for cs, reads in enumerate(s.rnd.read_ranks):
            n_r = reads.size
            n_u = (s.rnd.update_ranks[cs].size
                   + s.rnd.insert_ranks[cs].size)
            done = np.empty(n_r + n_u)
            done[:n_r], done[n_r:] = s.t_read, s.t_write
            out.append(done - t_prev[cs, :n_r + n_u])
            t_prev[cs, :n_r + n_u] = done
    return np.concatenate(out) if out else np.zeros(0)


# --------------------------------------------------------------------------
# correctness
# --------------------------------------------------------------------------

def check_answers(cell: Cell, seed: int, served: list, window: set) -> dict:
    """Replay every round against the rank-keyed reference: every lookup
    answer (all rounds; those of the window are counted apart) and every
    acknowledged update and insert, in wave and lane order.  A round's
    updates never name its own inserts (the generator draws them over the
    records before the round), so its updates and then its inserts is that
    order.  Returns the store."""
    c = cell.config
    top = max((int(r[-1]) + 1 for s in served for r in s.rnd.insert_ranks
               if r.size), default=0)
    store = R.Store(R.load_values(seed, c["records"], c["value_mask"]),
                    inserts=max(top - c["records"], 0))
    wrong = wrong_window = looked = 0
    for k, s in enumerate(served):
        for cs, ranks in enumerate(s.rnd.read_ranks):
            if not ranks.size:
                continue
            got, found = s.answers[cs]
            bad = (store.check_reads(ranks, got, found)
                   if len(got) == ranks.size else ranks.size)
            wrong += bad
            if k in window:
                wrong_window += bad
                looked += ranks.size
        store.apply_updates(
            np.concatenate(s.rnd.update_ranks),
            np.concatenate(s.rnd.update_vals))
        store.apply_inserts(
            np.concatenate(s.rnd.insert_ranks),
            np.concatenate(s.rnd.insert_vals))
    return dict(store=store, lookup_wrong=wrong,
                lookup_wrong_window=wrong_window, looked=looked)


def read_back(cluster, gen: T.Generator, store: R.Store, served: list,
              per_cs: int) -> tuple:
    """Look up every key any round updated or inserted, through
    ``lookup_wave`` at the window's own batch shape, and compare with its
    last written value.  Returns ``(wrong, compared)``."""
    ranks = np.unique(np.concatenate(
        [np.concatenate(s.rnd.update_ranks + s.rnd.insert_ranks)
         for s in served]))
    if not ranks.size:
        return 0, 0
    wave = per_cs * gen.n_cs
    pad = (-ranks.size) % wave
    todo = np.concatenate([ranks, np.full(pad, ranks[0])])
    wrong = 0
    for w in range(0, todo.size, wave):
        chunk = todo[w:w + wave].reshape(gen.n_cs, per_cs)
        got = cluster.lookup_wave([gen.keys(r) for r in chunk])
        for cs in range(gen.n_cs):
            vals, found = got[cs]
            wrong += (store.check_reads(chunk[cs], vals, found)
                      if len(vals) == per_cs else per_cs)
    return wrong, int(ranks.size)


def check_replay(cell: Cell, tap: PricedTap, cluster) -> tuple:
    """Replay the sampled priced waves through the reference; returns
    ``(waves that differ, waves compared, conservation held)``."""
    c = cell.config
    bad = 0
    samples = tap.samples()
    for merged, sim in samples:
        ref = R.replay({k: getattr(merged, k) for k in
                        ("kind", "ms", "nbytes", "lane", "doorbell", "dep",
                         "dep2", "at", "n_lanes")},
                       c["net"], c["n_ms"], onchip=True)
        bad += bool(R.replay_differs(sim, ref))
    return bad, len(samples), bool(cluster.conservation_ok())


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------

def device_info() -> dict:
    import jax
    devs = jax.devices()
    return dict(platform=devs[0].platform, kind=devs[0].device_kind,
                count=len(devs))


def peak_bytes() -> Optional[int]:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, log=print, kernel_mode: Optional[str] = "pallas",
             tamper: Optional[Callable] = None,
             trace_dir: Optional[str] = None) -> dict:
    """Run one cell once; returns the result line's fields.

    ``kernel_mode`` is the leaf search every CS must use (``pallas`` on the
    chip); a test passes ``interpret`` to run the Pallas kernel on the CPU.
    ``tamper(cluster)``, for tests and the control, breaks the served path
    underneath the benchmark before the warm rounds."""
    import jax
    from jitstats import count_compiles
    gen = generator(cell, seed)
    lanes = int(cell.traffic["lanes_per_cs"])

    t0 = time.perf_counter()
    cluster = build(cell, seed)
    jax.block_until_ready(cluster.state)
    log(f"bench: bulk load {time.perf_counter() - t0:.3f} s, pool "
        f"{sum(int(a.nbytes) for a in cluster.state)} bytes, height "
        f"{int(cluster.state.height)}, {cluster.n_cs} CS x "
        f"{cluster.per_cs} lanes")
    if kernel_mode is not None:
        for node in cluster.nodes:
            if kernel_mode == "interpret":
                node.cache.kernel_mode = kernel_mode
            if node.cache.kernel_mode != kernel_mode:
                raise RuntimeError(f"CS {node.cs_id} searches leaves with "
                                   f"{node.cache.kernel_mode!r}, not "
                                   f"{kernel_mode!r}")
    if tamper is not None:
        tamper(cluster)

    # -- warm-up: the cell's own shapes, including a sync sweep --
    served: list = []
    t0 = time.perf_counter()
    t_round = []
    for r in range(int(cell.traffic["warm_rounds"])):
        a = time.perf_counter()
        served.append(drive_round(cluster, gen, gen.round("warm", r)))
        t_round.append(time.perf_counter() - a)
    warm_s = time.perf_counter() - t0
    steady = float(np.mean(t_round[1:])) if len(t_round) > 1 else warm_s
    n_rounds = int(math.ceil(ROUND_MARGIN * seconds / max(steady, 1e-3))) + 8
    t0 = time.perf_counter()
    rounds = gen.rounds("window", 0, n_rounds)
    gen_s = time.perf_counter() - t0
    log(f"bench: warm-up {len(t_round)} rounds {warm_s:.3f} s (steady "
        f"{steady:.4f} s/round); generated {n_rounds} window rounds in "
        f"{gen_s:.3f} s")

    # -- the window --
    tap = PricedTap(seed, annotate=trace)
    t_prev = np.zeros((cluster.n_cs, lanes))
    op_latencies(served[-1:], t_prev, lanes)     # when each client issues
    before = cluster.combined_counters()
    first = len(served)
    profile = contextlib.nullcontext()
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        profile = jax.profiler.trace(trace_dir, profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    with profile, tap.active(), count_compiles() as compiles:
        with (_span("window") if trace else contextlib.nullcontext()):
            t_w = time.perf_counter()
            for rnd in rounds:
                served.append(drive_round(cluster, gen, rnd, trace))
                if time.perf_counter() - t_w >= seconds:
                    break
            window_s = time.perf_counter() - t_w
    if trace:
        log(f"bench: trace written in "
            f"{time.perf_counter() - t_w - window_s:.3f} s")
    after = cluster.combined_counters()
    peak = peak_bytes()
    win = served[first:]
    ops = sum(s.rnd.n_reads + s.rnd.n_updates + s.rnd.n_inserts
              for s in win)
    if len(win) == len(rounds):
        log(f"bench: WARNING the window used all {len(rounds)} generated "
            f"rounds before {seconds} s")
    lat = op_latencies(win, t_prev, lanes)
    ends = np.array([t_w] + [s.t_end for s in win])
    log(f"bench: window {window_s:.3f} s, {len(win)} rounds, {ops} ops, "
        f"{compiles.count} compiles in the window, {tap.waves} priced "
        f"waves; round seconds {np.round(np.diff(ends), 3).tolist()}")

    # -- correctness, once the window has closed --
    t0 = time.perf_counter()
    window_idx = set(range(first, len(served)))
    ans = check_answers(cell, seed, served, window_idx)
    per_cs = max((r.size for s in served for r in s.rnd.read_ranks),
                 default=0) or lanes
    rb_wrong, rb_n = read_back(cluster, gen, ans["store"], served, per_cs)
    rp_bad, rp_n, conserved = check_replay(cell, tap, cluster)
    log(f"bench: check {time.perf_counter() - t0:.3f} s: "
        f"{ans['looked']} window lookups, {rb_n} keys read back, "
        f"{rp_n} priced waves replayed ("
        f"{ {k: len(v) for k, v in sorted(tap.kept.items())} }), "
        f"conservation {'held' if conserved else 'BROKEN'}")
    # a window of a whole sweep period has a version sweep to replay
    swept = ("sweep" in tap.kept
             or len(served) - first < int(cell.config["sync_rounds"]))
    checks = {
        "lookup_wrong": (ans["lookup_wrong"], EXACT),
        "readback_wrong": (rb_wrong, EXACT),
        "replay_wrong": (rp_bad + (not conserved), EXACT),
    }
    # sound only if every number is within its limit and something was
    # compared: the window's lookups or read-backs, and priced waves,
    # a version sweep among them
    correct = (all(v <= lim for v, lim in checks.values())
               and ans["looked"] + rb_n > 0 and rp_n > 0 and swept)

    # ``updates`` counts every write op, inserts among them: the write
    # metrics are per op that the write wave carried
    inserts = sum(s.rnd.n_inserts for s in win)
    ctx = dict(cell=cell, ops=ops, window_s=window_s, latencies_s=lat,
               peak_bytes=peak, setup_s=setup_s,
               reads=sum(s.rnd.n_reads for s in win),
               updates=sum(s.rnd.n_updates for s in win) + inserts,
               inserts=inserts,
               write_waves=sum(bool(s.rnd.n_updates + s.rnd.n_inserts)
                               for s in win),
               counters={k: after[k] - before[k] for k in after},
               device_kind=device_info()["kind"], trace=None)
    device = dict(device_info(), memory_peak_bytes=peak)
    out = dict(correct=bool(correct), attempted=ops,
               failed=int(ans["lookup_wrong_window"] + rb_wrong),
               device=device, checks=checks, compiles=compiles.count)
    if trace:
        import xtrace
        t0 = time.perf_counter()
        events = xtrace.read_events(xtrace.find_xplane(trace_dir))
        lo, hi = xtrace.window_of(events, TRACE_SPAN)
        summ = xtrace.summarize(events, lo, hi)
        ctx.update(trace=summ, trace_events=events, trace_window=(lo, hi))
        device.update(busy_s=summ.busy_s, window_s=summ.window_s)
        log(f"bench: trace reduced in {time.perf_counter() - t0:.3f} s "
            f"({len(events)} events)")
        metrics = cell.per_layer
    else:
        metrics = cell.end_to_end
    out["metrics"] = {}
    t0 = time.perf_counter()
    for m in metrics:
        v = metric_reader(m["name"])(ctx)
        if v is not None:
            out["metrics"][m["name"]] = dict(value=v, unit=m["unit"])
    if trace:
        import hostspans
        red = hostspans.of_run(ctx)
        if red is not None:
            bd = red.breakdown()
            out["breakdown"] = dict(device_ops=bd["device_ops_by_scope"],
                                    idle_gaps=bd["idle_gaps_program"])
        log(f"bench: per-layer metrics read in "
            f"{time.perf_counter() - t0:.3f} s")
    return out
