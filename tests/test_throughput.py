"""PR 5 shape-stability + replay-equivalence regression tests.

Two planes are pinned here:

* **Compile stability** — the bucketed dispatch discipline
  (:func:`repro.core.api.bucket_size`, fixed ``REPAIR_CAP``) means a
  mixed YCSB workload compiles each jitted entry point once per bucket:
  a bounded count on the first pass, *zero* fresh XLA compilations on a
  repeat pass over the same spec.
* **Replay equivalence** — the vectorized wavefront
  :func:`repro.core.netsim.simulate` must reproduce the reference heapq
  loop :func:`repro.core.netsim.simulate_ref` tick-for-tick (both run on
  the shared integer ps grid) on real write/read/merged-cluster traces
  across the whole ablation ladder, seeded and under hypothesis.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import netsim, verbs as V, write
from repro.core.api import write_stats_dict
from repro.core.netsim import (ABLATION_LADDER, FG_PLUS, SHERMAN,
                               NetConfig)
from repro.core.tree import TreeConfig, bulkload
from repro.workloads import get_preset, run_workload, build_index, SYSTEMS
from repro.workloads.jitstats import count_compiles

CFG = TreeConfig(n_ms=2, nodes_per_ms=1024, fanout=8, n_locks_per_ms=512,
                 max_height=6, n_cs=4)
NET = NetConfig()


# --------------------------------------------------------------------------
# compile stability
# --------------------------------------------------------------------------

def test_mixed_workload_compiles_once_per_bucket():
    """A mixed YCSB run compiles a bounded set of shapes; running the
    same spec again — fresh index, same bucketed shapes — compiles
    nothing new.  This is the regression guard for the shape churn that
    used to recompile every op-mix batch size and repair-queue resize."""
    spec = get_preset("ycsb-d", load_records=2_000, ops=512, batch=128)
    idx = build_index(SYSTEMS["sherman"], CFG, records=spec.load_records)
    with count_compiles() as first:
        run_workload(idx, spec, seed=1)
    # one compile per (entry point, bucket); a mixed 4-kind workload
    # stays far below the old one-compile-per-batch churn
    assert 0 < first.count <= 16, first.count
    idx2 = build_index(SYSTEMS["sherman"], CFG, records=spec.load_records)
    with count_compiles() as second:
        run_workload(idx2, spec, seed=2)
    assert second.count == 0, second.count


def test_bucketing_pads_and_slices_correctly():
    """Odd batch sizes round-trip through the padded dispatch: results
    are sliced back to the caller's length and padding lanes never leak
    into counters."""
    from repro.core import ShermanIndex
    rng = np.random.default_rng(0)
    base = rng.choice(50_000, size=1_000, replace=False)
    idx = ShermanIndex.build(CFG, base, base)
    for n in (1, 3, 17, 100):
        got, found = idx.lookup(base[:n].astype(np.int32))
        assert got.shape == (n,) and found.shape == (n,)
        assert found.all() and (got == base[:n]).all()
    c0 = dict(idx.counters)
    keys = base[:37].astype(np.int32)
    idx.insert(keys, keys + 1)
    assert idx.counters["write_ops"] - c0["write_ops"] == 37
    got, found = idx.lookup(keys)
    assert found.all() and (got == keys + 1).all()
    k, v, cnt = idx.range(base[:5].astype(np.int32), count=4)
    assert k.shape == (5, 4) and cnt.shape == (5,)


def test_repair_queue_capacity_is_batch_independent():
    """The driver-owned repair queue keeps its fixed capacity across
    batch sizes (no shape churn), and dense split-heavy inserts still
    drain to a correct tree."""
    from repro.core import ShermanIndex
    from repro.core.api import REPAIR_CAP
    idx = ShermanIndex.build(CFG, np.arange(0, 640, 10), np.arange(64))
    assert idx._repair.valid.shape == (REPAIR_CAP,)
    keys = np.arange(0, 512, 2).astype(np.int32)
    idx.insert(keys, keys)
    assert idx._repair.valid.shape == (REPAIR_CAP,)
    assert idx._repair_backlog == 0
    assert idx.counters["leaf_splits"] > 0
    got, found = idx.lookup(keys)
    assert found.all() and (got == keys).all()


# --------------------------------------------------------------------------
# replay equivalence: simulate == simulate_ref, tick for tick
# --------------------------------------------------------------------------

def _phase_sd(n, seed, cs_spread=True, hot=40):
    """One real write phase over a seeded tree (hot + fresh keys =>
    contention, handover chains, splits)."""
    rng = np.random.default_rng(seed)
    base = rng.choice(20_000, size=600, replace=False)
    st = bulkload(CFG, base, base)
    hotk = rng.integers(0, hot, size=n // 2)
    new = rng.choice(np.setdiff1d(np.arange(20_000), base), size=n - n // 2,
                     replace=False)
    keys = jnp.asarray(np.concatenate([hotk, new]), jnp.int32)
    cs = jnp.asarray(np.arange(n) % (CFG.n_cs if cs_spread else 1),
                     jnp.int32)
    _, _, stats, _ = write.write_phase(CFG, st, keys, jnp.ones_like(keys),
                                       jnp.zeros((n,), bool),
                                       jnp.ones((n,), bool), cs)
    return write_stats_dict(stats, np.ones(n, bool), np.zeros(n, bool),
                            int(st.height))


def _assert_sim_equal(tr, onchip):
    ref = netsim.simulate_ref(tr, NET, CFG.n_ms, onchip)
    vec = netsim.simulate(tr, NET, CFG.n_ms, onchip)
    np.testing.assert_allclose(vec["latency_s"], ref["latency_s"],
                               rtol=1e-9, atol=0)
    assert vec["makespan_s"] == pytest.approx(ref["makespan_s"],
                                              rel=1e-9)
    for k in ("msgs", "verbs", "cas_msgs", "doorbells"):
        assert vec[k] == ref[k]
    assert vec["bytes"] == pytest.approx(ref["bytes"])
    np.testing.assert_array_equal(vec["lane_doorbells"],
                                  ref["lane_doorbells"])


@pytest.mark.parametrize("name,feat", ABLATION_LADDER)
def test_simulate_matches_ref_across_ablation_ladder(name, feat):
    """Every ablation rung's transformed write trace replays identically
    through the wavefront and the reference heap (spin storms, handover
    chains, combined doorbells — all of it)."""
    sd = _phase_sd(96, seed=11)
    tr = netsim.transformed_write_trace(sd, feat, NET, CFG)
    _assert_sim_equal(tr, feat.onchip)


def test_simulate_matches_ref_on_read_and_maintenance_traces():
    rng = np.random.default_rng(5)
    reads = rng.integers(1, 5, size=200).astype(np.int64)
    tr = V.read_phase_trace(reads, rng.integers(0, CFG.n_ms, 200),
                            CFG.n_ms, CFG.node_bytes)
    _assert_sim_equal(tr, True)
    tr = V.maintenance_trace(37, 91, CFG.n_ms, CFG.node_bytes, 128)
    _assert_sim_equal(tr, False)


@pytest.mark.parametrize("feat", [SHERMAN, FG_PLUS],
                         ids=["sherman", "fg+"])
def test_simulate_matches_ref_on_merged_cluster_traces(feat):
    """Merged multi-CS traces — including the cross-CS GLT lock chains
    `merge_traces` injects — replay identically."""
    traces = []
    for cs in range(3):
        sd = _phase_sd(24, seed=100 + cs)
        traces.append(netsim.transformed_write_trace(sd, feat, NET, CFG))
    merged = V.merge_traces(traces, glt_chain=True)
    locks = np.nonzero(merged.role == V.LOCK)[0]
    assert (merged.dep2[locks] >= 0).any()       # chains actually present
    _assert_sim_equal(merged, feat.onchip)


def test_property_simulate_equivalence():
    """Hypothesis: arbitrary phase sizes / skews / ladder rungs replay
    identically through both engines."""
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=20, deadline=None)
    @given(st.integers(min_value=2, max_value=64),
           st.integers(min_value=0, max_value=2 ** 31 - 1),
           st.integers(min_value=2, max_value=200),
           st.sampled_from([feat for _, feat in ABLATION_LADDER]))
    def inner(n, seed, hot, feat):
        sd = _phase_sd(n, seed=seed, hot=hot)
        tr = netsim.transformed_write_trace(sd, feat, NET, CFG)
        _assert_sim_equal(tr, feat.onchip)

    inner()


def test_drain_repairs_syncs_in_batches():
    """Satellite: the repair drain reads the backlog from the write
    phase's stats (no device sync when the queue is empty) and the
    jitted step exposes the pending count for k-batched host checks."""
    from repro.core.api import _jit_repair
    from repro.core.write import RepairQueue
    from repro.core.api import REPAIR_CAP
    st = bulkload(CFG, np.arange(0, 4_000, 7), np.arange(572))
    out = _jit_repair(CFG, st, RepairQueue.empty(REPAIR_CAP))
    assert len(out) == 6                 # ..., ni, nr, pending, dropped
    assert int(out[4]) == 0 and int(out[5]) == 0
