"""A packed tree (leaves and internal nodes bulk-loaded 100% full) under
YCSB D through the ``Cluster`` path: every hashed insert splits its leaf,
the full parents above split in turn, the CS images go stale and refill,
and reads chase siblings.  Every answer is checked against
``OracleIndex``."""
import numpy as np
import pytest

from repro.cluster import build_cluster
from repro.core import OracleIndex
from repro.core.api import REPAIR_CAP
from repro.core.netsim import SHERMAN
from repro.core.tree import EMPTY_KEY, NULL_PTR, TreeConfig
from repro.workloads.keygen import latest_ranks, scramble

#: Fanout 58 (1 KB nodes) on a pool of 2 MS x 1,024 rows: 40,000 records
#: packed take 690 leaves, 12 level-1 nodes and a root; an 8-node cache.
CFG = TreeConfig(n_ms=2, nodes_per_ms=1024, fanout=58, n_locks_per_ms=512,
                 max_height=8, n_cs=2)
RECORDS, KEYSPACE, LANES, SEED = 40_000, 1 << 16, 64, 2**31 + 5
VAL_MASK = (1 << 30) - 1


def _packed(cache_rows=8, lanes=LANES, fill=1.0):
    cl = build_cluster(SHERMAN, CFG, n_clients=CFG.n_cs * lanes,
                       records=RECORDS, keyspace=KEYSPACE,
                       cache_bytes=CFG.node_bytes * cache_rows,
                       sync_rounds=4, seed=SEED, fill=fill)
    # the load, as build_cluster draws it
    keys = scramble(np.arange(RECORDS, dtype=np.int64), KEYSPACE)
    vals = np.random.default_rng(SEED).integers(0, VAL_MASK, size=RECORDS)
    oracle = OracleIndex()
    oracle.insert_batch(keys, vals)
    return cl, oracle


def _check(cl, oracle, keys_by_cs) -> int:
    """Look ``keys_by_cs`` up through the cluster; the wrong answers."""
    wrong = 0
    for keys, (vals, found) in zip(keys_by_cs, cl.lookup_wave(keys_by_cs)):
        for k, v, f in zip(keys.tolist(), vals.tolist(), found.tolist()):
            want = oracle.lookup(k)
            wrong += f != (want is not None) or (f and v != want)
    return wrong


def _tree_complete(cl) -> bool:
    """Every allocated node but the root is the child of exactly one
    internal node: no separator was lost, so no node is reachable only
    through its left sibling."""
    st = cl.state
    level, keys, vals = (np.asarray(a) for a in (st.level, st.keys,
                                                 st.vals))
    internal = level > 0
    children = vals[internal][keys[internal] != EMPTY_KEY]
    alloc = np.flatnonzero(level >= 0)
    alloc = alloc[alloc != int(st.root)]
    counts = np.bincount(children[children != NULL_PTR],
                         minlength=level.size)
    return bool((counts[alloc] == 1).all())


def test_packed_ycsb_d_agrees_with_oracle():
    """YCSB D (95% read latest, 5% hashed insert) on the packed tree: every
    lookup of every round and every key read back afterwards agrees with
    the oracle, and the run reaches leaf and internal splits, stale cached
    reads, image refills and sibling chases, with no separator dropped."""
    cl, oracle = _packed()
    rng = np.random.default_rng(7)
    live, wrong = RECORDS, 0
    inserted = []
    for _ in range(24):
        reads, ins, ins_vals = [], [], []
        for _cs in range(CFG.n_cs):
            n_ins = int(rng.binomial(LANES, 0.05)) + 1
            ranks = latest_ranks(rng, LANES - n_ins, live, 0.99)
            reads.append(scramble(ranks, KEYSPACE).astype(np.int32))
            new = np.arange(live, live + n_ins)
            live += n_ins
            ins.append(scramble(new, KEYSPACE).astype(np.int32))
            ins_vals.append(rng.integers(0, VAL_MASK, n_ins))
        wrong += _check(cl, oracle, reads)
        cl.write_wave(ins, ins_vals)
        for k, v in zip(ins, ins_vals):
            oracle.insert_batch(k, v)
            inserted.append(k)
        cl.end_round()
    assert wrong == 0
    back = np.concatenate(inserted)
    half = back.size // 2
    assert _check(cl, oracle, [back[:half], back[half:]]) == 0
    c = cl.combined_counters()
    assert c["leaf_splits"] > 0 and c["internal_splits"] > 0
    assert c["cache_stale"] > 0 and c["image_refills"] > 0
    assert c["blink_hops"] > 0
    assert c["image_refills"] == sum(c["image_refills_" + w] for w in
                                     ("root", "upper_level", "threshold"))
    assert c["repair_dropped"] == 0 and cl._repair_backlog == 0
    assert _tree_complete(cl)


def test_wave_past_the_repair_queue():
    """One write wave of 1,024 hashed inserts into the packed tree asks for
    more leaf splits than the repair queue holds: the splits wait for
    slots, the wave retries its deferred inserts, nothing is dropped, every
    parent learns of every child, and every key answers right."""
    lanes = 512
    cl, oracle = _packed(cache_rows=64, lanes=lanes)
    new = np.arange(RECORDS, RECORDS + CFG.n_cs * lanes)
    keys = scramble(new, KEYSPACE).astype(np.int32).reshape(CFG.n_cs, lanes)
    vals = (keys.astype(np.int64) * 3 + 1) & VAL_MASK
    cl.write_wave(list(keys), list(vals))
    for k, v in zip(keys, vals):
        oracle.insert_batch(k, v)
    c = cl.combined_counters()
    assert c["leaf_splits"] > REPAIR_CAP
    assert c["stacked_phases"] > 1          # inserts waited for a slot
    assert c["repair_dropped"] == 0 and cl._repair_backlog == 0
    assert _tree_complete(cl)
    every = np.concatenate([scramble(np.arange(RECORDS), KEYSPACE),
                            keys.ravel()]).astype(np.int32)
    for w in range(0, every.size, CFG.n_cs * lanes):
        chunk = every[w:w + CFG.n_cs * lanes]
        assert _check(cl, oracle, np.array_split(chunk, CFG.n_cs)) == 0


@pytest.mark.parametrize("fill", [0.8, 1.0])
def test_bulkload_packs_internal_nodes(fill):
    """Internal levels are packed whatever the leaf fill: the first
    separator that reaches a level-1 parent splits it."""
    cl, _ = _packed(fill=fill)
    st = cl.state
    level, keys = np.asarray(st.level), np.asarray(st.keys)
    per = (keys != EMPTY_KEY).sum(axis=1)
    leaves = per[level == 0]
    assert leaves.max() == round(CFG.fanout * fill)
    lvl1 = per[level == 1]
    assert (lvl1 == CFG.fanout).sum() >= lvl1.size - 1   # all but the last
    assert _tree_complete(cl)
