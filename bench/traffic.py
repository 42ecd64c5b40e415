"""The benchmark's traffic generator: closed-loop rounds of per-CS batches.

A traffic mix is a JSON file under ``bench/traffic/``; this one generator
reads every mix.  Keys follow YCSB: a record is named by its load rank
(0 = first record loaded), ranks are drawn from the mix's distribution
over the live records, and the multiplicative scramble maps a rank to its
key, so hot ranks land far apart in the key space (YCSB's
ScrambledZipfian).  The scramble is a bijection on ``[0, keyspace)`` when
``keyspace`` is a power of two.

The Zipf draw, the scramble and the per-batch op counts are copies of the
program's (``repro.workloads.keygen`` and ``WorkloadSpec.batch_counts``),
kept here so that a change to the program cannot move the traffic.

Each round ``r`` draws from generators seeded by ``(seed, phase, r, cs)``,
so a round's inputs do not depend on how many rounds were generated before
it: the same seed gives the same rounds, however long the window runs.
"""
from __future__ import annotations

import dataclasses

import numpy as np

SCRAMBLE = 2_654_435_761            # odd: a bijection modulo a power of two
GOLDEN = 0.6180339887498949         # low-discrepancy remainder sequence
OP_KINDS = ("read", "update")       # what the rank-keyed reference can check
DISTRIBUTIONS = ("zipfian", "uniform")
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
    """One scheduler round: every CS's reads and updates (in CS order)."""
    read_ranks: list            # per CS, int64 ranks (maybe empty)
    update_ranks: list          # per CS, int64 ranks
    update_vals: list           # per CS, int32 values

    @property
    def n_reads(self) -> int:
        return sum(r.size for r in self.read_ranks)

    @property
    def n_updates(self) -> int:
        return sum(r.size for r in self.update_ranks)


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

    def _ranks(self, rng, n: int) -> np.ndarray:
        if self.zipf is not None:
            return self.zipf.ranks(rng, n)
        return rng.integers(0, self.records, size=n).astype(np.int64)

    def round(self, phase: str, r: int) -> Round:
        reads, ups, vals = [], [], []
        for cs in range(self.n_cs):
            rng = np.random.default_rng((self.seed, PHASES[phase], r, cs))
            c = batch_counts(self.mix["ops"], self.lanes,
                             salt=r * self.n_cs + cs)
            reads.append(self._ranks(rng, c["read"]))
            ups.append(self._ranks(rng, c["update"]))
            vals.append(rng.integers(0, self.value_mask, c["update"]
                                     ).astype(np.int32))
        return Round(reads, ups, vals)

    def rounds(self, phase: str, first: int, count: int) -> list:
        return [self.round(phase, r) for r in range(first, first + count)]

    def keys(self, ranks) -> np.ndarray:
        return scramble(ranks, self.keyspace).astype(np.int32)
