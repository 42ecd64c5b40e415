"""The plain references that decide ``correct``.

Both import nothing of the program.

* :class:`Store` is what the index owes, keyed by rank: the load's values
  drawn from the seed, and a slot for every rank the traffic inserts,
  overlaid with every acknowledged update and insert in wave and lane
  order.  A lookup of a live record must return that record's latest
  value; an update or insert, once its wave returned, must read back.
* :func:`replay` is the priced timeline's specification: a per-verb event
  loop over one merged verb trace.  Every verb is posted once its gates
  (``dep``/``dep2`` completions and its ``at`` floor) allow, is served in
  FIFO order by its memory server's NIC message unit for
  ``max(1/iops, bytes/bandwidth)``, a CAS then waits for the server's
  atomic unit, and the client sees completion one round trip later.  Ties
  go to the lower verb index.  All times are integer picoseconds, so two
  sound implementations agree exactly.  A trace with no gates (a cache
  fill or version sweep: millions of independent reads) is replayed in
  closed form instead, the same FIFO in the same order;
  ``bench/tests/test_reference.py`` holds the two to each other.
"""
from __future__ import annotations

import heapq

import numpy as np

PS_PER_S = 1e12
CAS = 2                              # verb kind of a compare-and-swap
SYNC = 8                             # verb role of a version-sweep read


def load_values(seed: int, records: int, value_mask: int) -> np.ndarray:
    """The value of every load rank: ``default_rng(seed)`` integers below
    ``value_mask``, the deployment's load definition."""
    rng = np.random.default_rng(int(seed) % (1 << 64))
    return rng.integers(0, value_mask, size=records).astype(np.int32)


def last_writes(ranks: np.ndarray, vals: np.ndarray):
    """The surviving write per rank of one wave: the last lane wins."""
    rev_ranks = ranks[::-1]
    uniq, idx = np.unique(rev_ranks, return_index=True)
    return uniq, vals[::-1][idx]


class Store:
    """Rank-keyed model of the index: no sort of the load is needed.

    Ranks below ``records`` are the load; the ``inserts`` ranks above it
    hold nothing until an insert names them."""

    def __init__(self, values: np.ndarray, inserts: int = 0):
        self.records = int(values.size)
        self.values = (np.concatenate([values, np.zeros(inserts, np.int32)])
                       if inserts else values)
        self.inserted = np.zeros(inserts, bool)

    def check_reads(self, ranks, got, found) -> int:
        """Wrong answers among one wave's lookups: a rank not yet inserted
        is never a right answer."""
        want = self.values[ranks]
        live = np.ones(ranks.size, bool)
        fresh = ranks >= self.records
        if fresh.any():
            live[fresh] = self.inserted[ranks[fresh] - self.records]
        return int(np.count_nonzero(~np.asarray(found, bool) | ~live
                                    | (np.asarray(got) != want)))

    def apply_updates(self, ranks, vals) -> None:
        if ranks.size:
            uniq, v = last_writes(ranks, vals)
            self.values[uniq] = v

    def apply_inserts(self, ranks, vals) -> None:
        """Acknowledged inserts: the last lane to write a rank wins."""
        if ranks.size:
            self.inserted[ranks - self.records] = True
            self.apply_updates(ranks, vals)


def replay(trace: dict, net: dict, n_ms: int, onchip: bool) -> dict:
    """Reference replay of one merged trace (a dict of its arrays).

    Returns per-lane completion (``latency_s``), the makespan and the
    trace's totals, as the priced timeline reports them."""
    kind = np.asarray(trace["kind"])
    n = int(kind.shape[0])
    n_lanes = int(trace["n_lanes"])
    lane = np.asarray(trace["lane"])
    nbytes = np.asarray(trace["nbytes"])
    doorbell = np.asarray(trace["doorbell"])
    out = dict(verbs=n, bytes=float(nbytes.sum()),
               cas_msgs=int((kind == CAS).sum()),
               doorbells=int((doorbell == np.arange(n)).sum()))
    if n == 0:
        return dict(out, latency_s=np.zeros(n_lanes), makespan_s=0.0)
    gated = (np.asarray(trace["dep"]) >= 0) | (np.asarray(trace["dep2"]) >= 0)
    comp = (event_loop if gated.any() else closed_form)(
        trace, net, n_ms, onchip)
    comp_s = comp * (1.0 / PS_PER_S)
    lat = np.zeros(n_lanes)
    own = lane >= 0
    np.maximum.at(lat, lane[own], comp_s[own])
    return dict(out, latency_s=lat, makespan_s=float(comp_s.max()))


def _ticks(trace: dict, net: dict, onchip: bool):
    """Service, CAS, round trip and post times in integer picoseconds."""
    nbytes = np.asarray(trace["nbytes"])
    svc = np.rint(np.maximum(1.0 / net["nic_iops_small"],
                             nbytes / net["nic_bw_Bps"]) * PS_PER_S
                  ).astype(np.int64)
    cas_s = int(round((net["cas_onchip_s"] if onchip else net["cas_pcie_s"])
                      * PS_PER_S))
    rtt = int(round(net["rtt_s"] * PS_PER_S))
    at = np.rint(np.asarray(trace["at"]) * PS_PER_S).astype(np.int64)
    return svc, cas_s, rtt, at


def event_loop(trace: dict, net: dict, n_ms: int, onchip: bool):
    """Every verb's completion (ps), one verb at a time off a heap."""
    svc, cas_s, rtt, at = _ticks(trace, net, onchip)
    svc, at = svc.tolist(), at.tolist()
    n = len(svc)
    ms = np.asarray(trace["ms"]).tolist()
    kinds = np.asarray(trace["kind"]).tolist()
    dep = np.asarray(trace["dep"]).tolist()
    dep2 = np.asarray(trace["dep2"]).tolist()
    waiting = [(d >= 0) + (e >= 0) for d, e in zip(dep, dep2)]
    children: list = [[] for _ in range(n)]
    for i in range(n):
        for g in (dep[i], dep2[i]):
            if g >= 0:
                children[g].append(i)
    heap = [(at[i], i) for i in range(n) if not waiting[i]]
    heapq.heapify(heap)
    nic = [0] * n_ms
    atomic = [0] * n_ms
    comp = [0] * n
    while heap:
        t, i = heapq.heappop(heap)
        m = ms[i]
        d = max(t, nic[m]) + svc[i]
        nic[m] = d
        if kinds[i] == CAS:
            d = max(d, atomic[m]) + cas_s
            atomic[m] = d
        comp[i] = d + rtt
        for c in children[i]:
            waiting[c] -= 1
            if not waiting[c]:
                r = at[c]
                for g in (dep[c], dep2[c]):
                    if g >= 0:
                        r = max(r, comp[g])
                heapq.heappush(heap, (r, c))
    return np.asarray(comp, np.int64)


def _fifo(ready, svc, group):
    """``d_j = max(ready_j, d_{j-1}) + svc_j`` within each run of equal
    ``group`` (a server starting idle at 0), in closed form: ``d_j`` is
    the running sum of service plus the running maximum of each verb's
    ready time less the service queued before it."""
    if not ready.size:
        return ready
    head = np.flatnonzero(np.r_[True, group[1:] != group[:-1]])
    size = np.diff(np.r_[head, ready.size])
    csum = np.cumsum(svc)
    before = np.repeat(csum[head] - svc[head], size)
    c = csum - before                          # service so far, this server
    slack = ready - (c - svc)
    for a, b in zip(head, head + size):        # one pass per server
        slack[a:b] = np.maximum.accumulate(slack[a:b])
    return c + slack


def closed_form(trace: dict, net: dict, n_ms: int, onchip: bool):
    """Every verb's completion (ps) for a trace with no gates: the heap
    would pop them by (post time, index), so each server's FIFO sees its
    verbs in that order."""
    svc, cas_s, rtt, at = _ticks(trace, net, onchip)
    ms = np.asarray(trace["ms"]).astype(np.int64)
    n = svc.size
    order = np.lexsort((np.arange(n), at, ms))     # by server, then pop order
    m_o = ms[order]
    d = _fifo(at[order], svc[order], m_o)
    cas = np.asarray(trace["kind"])[order] == CAS
    if cas.any():
        d[cas] = _fifo(d[cas], np.full(int(cas.sum()), cas_s, np.int64),
                       m_o[cas])
    comp = np.empty(n, np.int64)
    comp[order] = d + rtt
    return comp


def replay_differs(sim: dict, ref: dict) -> list:
    """The fields in which the priced wave departs from the reference."""
    bad = [k for k in ("verbs", "doorbells", "cas_msgs")
           if int(sim[k]) != ref[k]]
    if float(sim["bytes"]) != ref["bytes"]:
        bad.append("bytes")
    if float(sim["makespan_s"]) != ref["makespan_s"]:
        bad.append("makespan_s")
    lat = np.asarray(sim["latency_s"])
    if lat.shape != ref["latency_s"].shape or \
            not np.array_equal(lat, ref["latency_s"]):
        bad.append("latency_s")
    return bad
