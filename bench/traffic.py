"""The benchmark's traffic generator: closed-loop rounds of per-CS batches.

A traffic mix is a JSON file under ``bench/traffic/``; this one generator
reads every mix.  Keys follow YCSB: a record is named by its load rank
(0 = first record loaded), ranks are drawn from the mix's distribution
over the live records, and the multiplicative scramble maps a rank to its
key, so hot ranks land far apart in the key space (YCSB's
ScrambledZipfian).  The scramble is a bijection on ``[0, keyspace)`` when
``keyspace`` is a power of two.

An insert names a fresh rank: ranks ``records, records + 1, ...`` are
handed out in phase order (warm, then window), then round, then CS, then
lane, and scrambled to their keys (YCSB's ``insertorder=hashed``).  The
``live`` records of a round are the load and every insert of the rounds
before it; ``zipfian`` and ``uniform`` draw over the load ranks, ``latest``
(YCSB's SkewedLatestGenerator) over the live ones, newest hottest.  A
round's reads and updates therefore never name a rank inserted in its own
round or later.

The Zipf and latest draws, the scramble and the per-batch op counts are
copies of the program's (``repro.workloads.keygen`` and
``WorkloadSpec.batch_counts``), kept here so that a change to the program
cannot move the traffic.

Each round ``r`` draws from generators seeded by ``(seed, phase, r, cs)``,
and its first insert rank is a sum of the op counts of the rounds before
it, which depend on nothing but the mix: a round's inputs do not depend on
how many rounds were generated before it, and the same seed gives the same
rounds, however long the window runs.
"""
from __future__ import annotations

import dataclasses

import numpy as np

SCRAMBLE = 2_654_435_761            # odd: a bijection modulo a power of two
GOLDEN = 0.6180339887498949         # low-discrepancy remainder sequence
OP_KINDS = ("read", "update", "insert")  # what the reference can check
DISTRIBUTIONS = ("zipfian", "uniform", "latest")
PHASES = {"warm": 1, "window": 2}


def zeta(n: int, theta: float) -> float:
    """zeta(n, theta), exact to 10^4 terms and an integral tail beyond."""
    head = np.sum(1.0 / np.arange(1, min(n, 10_000) + 1) ** theta)
    tail = ((n ** (1 - theta) - 10_000 ** (1 - theta)) / (1 - theta)
            if n > 10_000 else 0.0)
    return float(head + tail)


class Zipf:
    """YCSB's ZipfianGenerator (Gray et al.) over ``[0, n)``, vectorized."""

    def __init__(self, n: int, theta: float):
        if abs(theta - 1.0) < 1e-9:
            theta = 1.0 - 1e-6       # the generator is singular at theta=1
        self.n, self.theta = int(n), float(theta)
        self.zetan = zeta(self.n, self.theta)
        self.alpha = 1.0 / (1.0 - self.theta)
        self.eta = ((1 - (2.0 / self.n) ** (1 - self.theta))
                    / (1 - zeta(2, self.theta) / self.zetan))

    def ranks(self, rng, size: int) -> np.ndarray:
        u = rng.random(size)
        uz = u * self.zetan
        ranks = np.where(
            uz < 1.0, 0,
            np.where(uz < 1.0 + 0.5 ** self.theta, 1,
                     (self.n * (self.eta * u - self.eta + 1) ** self.alpha
                      ).astype(np.int64)))
        return np.clip(ranks, 0, self.n - 1).astype(np.int64)


def scramble(ranks, keyspace: int) -> np.ndarray:
    """Map load ranks to keys (the YCSB scramble), as int64."""
    return (np.asarray(ranks, np.int64) * SCRAMBLE) % keyspace


def batch_counts(mix: dict, b: int, salt: int) -> dict:
    """Op counts of one ``b``-lane batch: floor each fraction, then give
    each remainder slot by a fraction-weighted golden-ratio draw."""
    fracs = [(k, float(mix.get(k, 0.0))) for k in OP_KINDS]
    counts = {k: int(f * b) for k, f in fracs}
    eligible = [(k, f) for k, f in sorted(fracs, key=lambda kv: -kv[1])
                if f > 0]
    total = sum(f for _, f in eligible)
    for i in range(b - sum(counts.values())):
        u = ((salt + i + 1) * GOLDEN) % 1.0
        acc = 0.0
        for k, f in eligible:
            acc += f / total
            if u < acc or (k, f) == eligible[-1]:
                counts[k] += 1
                break
    return counts


def check_mix(mix: dict) -> None:
    """Refuse a mix this generator and the reference cannot serve."""
    extra = set(mix.get("ops", {})) - set(OP_KINDS)
    if extra:
        raise ValueError(f"traffic {mix['name']!r}: ops {sorted(extra)} "
                         f"are not generated (known: {OP_KINDS})")
    total = sum(mix["ops"].values())
    if abs(total - 1.0) > 1e-9:
        raise ValueError(f"traffic {mix['name']!r}: op fractions sum to "
                         f"{total}, not 1")
    if mix["distribution"] not in DISTRIBUTIONS:
        raise ValueError(f"traffic {mix['name']!r}: distribution "
                         f"{mix['distribution']!r} not in {DISTRIBUTIONS}")
    if mix.get("streams", "shared") != "shared" or \
            mix.get("loop", "closed") != "closed":
        raise ValueError(f"traffic {mix['name']!r}: only shared streams in "
                         f"a closed loop are generated")


@dataclasses.dataclass
class Round:
    """One scheduler round: every CS's reads, updates and inserts (in CS
    order).  ``live`` is the number of records before the round's inserts:
    its first insert rank."""
    read_ranks: list            # per CS, int64 ranks (maybe empty)
    update_ranks: list          # per CS, int64 ranks
    update_vals: list           # per CS, int32 values
    insert_ranks: list          # per CS, int64 fresh consecutive ranks
    insert_vals: list           # per CS, int32 values
    live: int

    @property
    def n_reads(self) -> int:
        return sum(r.size for r in self.read_ranks)

    @property
    def n_updates(self) -> int:
        return sum(r.size for r in self.update_ranks)

    @property
    def n_inserts(self) -> int:
        return sum(r.size for r in self.insert_ranks)


class Generator:
    """Rounds of a mix over a loaded record space."""

    def __init__(self, mix: dict, *, n_cs: int, records: int,
                 keyspace: int, value_mask: int, seed: int):
        check_mix(mix)
        self.mix, self.n_cs = mix, int(n_cs)
        self.lanes = int(mix["lanes_per_cs"])
        self.records, self.keyspace = int(records), int(keyspace)
        self.value_mask = int(value_mask)
        self.seed = int(seed) % (1 << 64)
        self.zipf = (Zipf(self.records, mix["theta"])
                     if mix["distribution"] == "zipfian" else None)
        self._inserted = [0]    # inserts of a phase's rounds [0, k)

    def counts(self, r: int) -> list:
        """Each CS's op counts in round ``r`` of any phase."""
        return [batch_counts(self.mix["ops"], self.lanes,
                             salt=r * self.n_cs + cs)
                for cs in range(self.n_cs)]

    def _inserts_before(self, r: int) -> int:
        """Inserts in rounds ``[0, r)`` of a phase: a sum of op counts,
        which depend only on the round's index."""
        if not self.mix["ops"].get("insert"):
            return 0
        while len(self._inserted) <= r:
            k = len(self._inserted) - 1
            self._inserted.append(self._inserted[-1] + sum(
                c["insert"] for c in self.counts(k)))
        return self._inserted[r]

    def live(self, phase: str, r: int) -> int:
        """Records before round ``r`` of ``phase``: the load, every warm
        round's inserts if ``phase`` is the window, and the phase's own
        earlier rounds'."""
        warm = (self._inserts_before(int(self.mix["warm_rounds"]))
                if phase == "window" else 0)
        return self.records + warm + self._inserts_before(r)

    def _draw(self, live: int):
        """The mix's rank draw for a round with ``live`` records."""
        dist = self.mix["distribution"]
        if dist == "zipfian":
            return self.zipf.ranks
        if dist == "latest":
            zipf = Zipf(live, self.mix["theta"])
            return lambda rng, n: (live - 1) - zipf.ranks(rng, n)
        return lambda rng, n: rng.integers(0, self.records,
                                           size=n).astype(np.int64)

    def round(self, phase: str, r: int) -> Round:
        live = self.live(phase, r)
        draw = self._draw(live)
        reads, ups, vals, ins, ins_vals = [], [], [], [], []
        nxt = live
        for cs, c in enumerate(self.counts(r)):
            rng = np.random.default_rng((self.seed, PHASES[phase], r, cs))
            reads.append(draw(rng, c["read"]))
            ups.append(draw(rng, c["update"]))
            vals.append(rng.integers(0, self.value_mask, c["update"]
                                     ).astype(np.int32))
            n = c["insert"]
            ins.append(np.arange(nxt, nxt + n, dtype=np.int64))
            ins_vals.append(rng.integers(0, self.value_mask, n
                                         ).astype(np.int32) if n
                            else np.zeros(0, np.int32))
            nxt += n
        if nxt > self.keyspace:
            raise ValueError(
                f"traffic {self.mix['name']!r}: {phase} round {r} inserts "
                f"rank {nxt - 1}, past the keyspace of {self.keyspace} "
                f"where the scramble is a bijection")
        return Round(reads, ups, vals, ins, ins_vals, live)

    def rounds(self, phase: str, first: int, count: int) -> list:
        return [self.round(phase, r) for r in range(first, first + count)]

    def keys(self, ranks) -> np.ndarray:
        return scramble(ranks, self.keyspace).astype(np.int32)
