"""The traffic generator: the existing mixes' rounds pinned bit for bit,
and the insert op and ``latest`` distribution that YCSB D needs."""
import dataclasses
import hashlib
import json
import os

import numpy as np
import pytest

import harness
import reference as R
import traffic as T

#: sha256 (first 16 hex digits) of warm round 0 and window rounds 0-2,
#: computed on the generator before it served inserts.
PINNED = {
    "wi-zipf-c24m": {
        2147483749: ["9f3852f80b3dae6a", "c120c2426854b303",
                     "642a4f80dcabe2cc", "bd3c023827093633"],
        12345: ["dac0c0e6033f0fd9", "f9aecf4c99defc2e",
                "50743c3deb46085d", "b679db1448d143e7"]},
    "ro-zipf-c64m": {
        2147483749: ["c90b606472312228", "5d260e3b2dd66b86",
                     "aa677b6b92bcc698", "f5aa8bc3a0b22cb8"],
        12345: ["ad227cd58f7a3e76", "7fdbd19a677c6889",
                "3cefd230e8373426", "399690b25dd78eeb"]},
    "wi-unif-c24m": {
        2147483749: ["79f47aa5e5a50d29", "68046201863f208f",
                     "9f4a5d259f80d0ba", "99561ad7cab401fe"],
        12345: ["ede742008de1f8ec", "99726ab8bc3189c3",
                "88a0cf74b2a1653f", "186f5514303abde4"]},
}
ROUNDS = [("warm", 0), ("window", 0), ("window", 1), ("window", 2)]


def digest(rnd: T.Round) -> str:
    h = hashlib.sha256()
    for arrs in (rnd.read_ranks, rnd.update_ranks, rnd.update_vals):
        for a in arrs:
            h.update(str(a.dtype).encode())
            h.update(a.tobytes())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("cell,seed", [(c, s) for c in PINNED
                                       for s in PINNED[c]])
def test_existing_mixes_are_pinned(cell, seed):
    c = harness.load_cell(cell)
    gen = harness.generator(c, seed)
    rounds = [gen.round(p, r) for p, r in ROUNDS]
    assert [digest(r) for r in rounds] == PINNED[cell][seed]
    assert all(r.n_inserts == 0 and r.live == c.config["records"]
               for r in rounds)


def _ycsb_d(seed=2**31 + 11, records=None, keyspace=None):
    with open(os.path.join(harness.BENCH, "traffic", "ycsb-d.json")) as f:
        mix = json.load(f)
    cfg = harness.load_cell("ro-zipf-c64m").config
    return T.Generator(mix, n_cs=cfg["n_cs"],
                       records=records or cfg["records"],
                       keyspace=keyspace or cfg["keyspace"],
                       value_mask=cfg["value_mask"], seed=seed)


def _phases(gen, window):
    warm = gen.rounds("warm", 0, int(gen.mix["warm_rounds"]))
    return warm + gen.rounds("window", 0, window)


def test_insert_ranks_are_fresh_and_consecutive():
    gen = _ycsb_d()
    rounds = _phases(gen, 20)
    ranks = np.concatenate([np.concatenate(r.insert_ranks)
                            for r in rounds])
    assert ranks.size == sum(r.n_inserts for r in rounds) > 0
    np.testing.assert_array_equal(
        ranks, np.arange(gen.records, gen.records + ranks.size))
    # each round's first insert rank is its count of live records
    for r in rounds:
        first = next(x for x in r.insert_ranks if x.size)
        assert first[0] == r.live
    # floor(5% of 512) = 25 a CS, and the one remainder lane of a batch
    # now and then; each CS's values drawn for its inserts
    for r in rounds:
        assert 200 <= r.n_inserts <= 208 and r.n_updates == 0
        assert [v.size for v in r.insert_vals] == \
            [x.size for x in r.insert_ranks]
        assert all(v.dtype == np.int32 for v in r.insert_vals)


def test_a_round_alone_is_the_round_of_a_run():
    run = _ycsb_d()
    rounds = _phases(run, 12)
    for phase, r, k in (("warm", 4, 4), ("window", 0, 6),
                        ("window", 9, 15)):
        alone = _ycsb_d().round(phase, r)
        for a, b in zip(dataclasses.astuple(alone)[:5],
                        dataclasses.astuple(rounds[k])[:5]):
            for x, y in zip(a, b):
                np.testing.assert_array_equal(x, y)
        assert alone.live == rounds[k].live


def test_reads_name_only_live_records():
    rounds = _phases(_ycsb_d(), 20)
    for r in rounds:
        reads = np.concatenate(r.read_ranks)
        assert reads.min() >= 0 and reads.max() < r.live


def test_latest_draws_the_newest_ranks():
    """Zipf 0.99 over recency: the newest record is the hottest, and the
    newest 1% of 1.5 x 10^8 hold about ln(1.5e6) / ln(1.5e8) = 3/4 of the
    draws (YCSB's SkewedLatestGenerator)."""
    r = _ycsb_d().round("window", 3)
    reads = np.concatenate(r.read_ranks)
    age = (r.live - 1) - reads
    assert np.bincount(age[age < 100]).argmax() == 0
    assert 0.65 < np.mean(age < r.live // 100) < 0.85


def test_inserts_stop_short_of_the_keyspace():
    gen = _ycsb_d(records=40_000, keyspace=40_000 + 900)
    gen.round("warm", 3)                # 4 rounds of 200-208 inserts fit
    with pytest.raises(ValueError, match="keyspace"):
        gen.round("warm", 4)


def test_the_mixes_check():
    with open(os.path.join(harness.BENCH, "traffic", "ycsb-d.json")) as f:
        mix = json.load(f)
    T.check_mix(mix)
    with pytest.raises(ValueError, match="not generated"):
        T.check_mix(dict(mix, ops={"read": 0.95, "scan": 0.05}))
    with pytest.raises(ValueError, match="distribution"):
        T.check_mix(dict(mix, distribution="hotspot"))


def test_store_with_inserts():
    store = R.Store(np.array([10, 11, 12], np.int32), inserts=3)
    ranks = np.array([0, 3, 4])
    # ranks 3 and 4 are not inserted yet: found or not, they are wrong
    assert store.check_reads(ranks, np.array([10, 0, 0]),
                             np.array([True, True, False])) == 2
    store.apply_inserts(np.array([3, 4, 3]), np.array([7, 8, 9], np.int32))
    assert store.check_reads(ranks, np.array([10, 9, 8]),
                             np.ones(3, bool)) == 0
    assert store.check_reads(ranks, np.array([10, 7, 8]),
                             np.ones(3, bool)) == 1
    assert store.check_reads(np.array([5]), np.array([0]),
                             np.array([False])) == 1
    store.apply_updates(np.array([4]), np.array([1], np.int32))
    assert store.check_reads(np.array([4]), np.array([1]),
                             np.array([True])) == 0
    # a store with no inserts is the load alone, unchanged
    load = np.array([1, 2], np.int32)
    assert R.Store(load).values is load
