"""``correct`` comes out false when the served path is broken underneath a
run.  The harness's look for a chip is skipped; the rest of a run is
driven at a tiny pool on the CPU.  (The cells run on one chip, so there is
no exchange between chips to leave out.)"""
import numpy as np
import pytest

import faults
from tiny import CELLS, run, tiny_cell


def _numbers(out):
    return {k: v for k, (v, _) in out["checks"].items()}


@pytest.mark.parametrize("name,traffic", [
    ("wi-zipf-c24m", None), ("wi-unif-c24m", None),
    # YCSB D: each CS's write batch is all inserts, so ``half_batch``
    # leaves out half of every CS's inserts
    ("ro-zipf-c64m", "ycsb-d")])
@pytest.mark.parametrize("fault,number", [
    (faults.state_unchanged, "readback_wrong"),
    (faults.half_batch, "readback_wrong"),
    (faults.answer_altered, "lookup_wrong"),
])
def test_fault_is_caught(fault, number, name, traffic):
    out = run(tiny_cell(name, traffic=traffic), tamper=fault,
              kernel_mode="ref")
    assert not out["correct"]
    assert _numbers(out)[number] > 0


@pytest.mark.parametrize("where", ["lanes", "sweep"])
def test_priced_timeline_altered_is_caught(monkeypatch, where):
    """A replay one tick late where it is produced no longer matches the
    reference: a client lane's completion, or the makespan of a wave with
    no client lanes (a version sweep)."""
    from repro.core import netsim
    simulate = netsim.simulate

    def late(trace, *a, **kw):
        sim = simulate(trace, *a, **kw)
        if where == "lanes" and sim["verbs"]:
            sim["latency_s"] = np.asarray(sim["latency_s"]) + 1e-12
        if where == "sweep" and sim["verbs"] and not trace.n_lanes:
            sim["makespan_s"] = sim["makespan_s"] + 1e-12
        return sim

    monkeypatch.setattr(netsim, "simulate", late)
    out = run(tiny_cell("wi-zipf-c24m"), kernel_mode="ref")
    assert not out["correct"]
    assert _numbers(out)["replay_wrong"] > 0


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_every_cell(name):
    """The control (values held in 16 bits) fails every cell."""
    out = run(tiny_cell(name), tamper=faults.values_in_16_bits,
              kernel_mode="ref")
    assert not out["correct"]
    assert _numbers(out)["lookup_wrong"] > 0
