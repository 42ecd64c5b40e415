"""The closed-form replay of a trace with no gates is the event loop's
FIFO, tick for tick: the two are the same specification."""
import numpy as np
import pytest

import reference as R

NET = dict(rtt_s=2e-06, nic_bw_Bps=12.5e9, nic_iops_small=50e6,
           small_io_bytes=128, cas_onchip_s=1 / 110e6, cas_pcie_s=9e-07)


def _free_trace(rng, n, n_ms, spread_at):
    return dict(
        kind=rng.choice([0, 1, R.CAS], size=n).astype(np.int8),
        ms=rng.integers(0, n_ms, size=n).astype(np.int32),
        nbytes=rng.choice([8, 17, 128, 1013, 4096], size=n),
        lane=np.where(rng.random(n) < 0.3, -1,
                      rng.integers(0, max(n // 3, 1), size=n)).astype(np.int32),
        doorbell=np.arange(n),
        dep=np.full(n, -1), dep2=np.full(n, -1),
        at=(rng.integers(0, 50, size=n) * 1e-7 if spread_at
            else np.zeros(n)),
        n_lanes=max(n // 3, 1))


@pytest.mark.parametrize("seed,n,n_ms,spread_at", [
    (1, 1, 1, False), (2, 50, 2, True), (3, 2000, 4, True),
    (4, 5000, 4, False), (5, 3000, 3, True)])
@pytest.mark.parametrize("onchip", [True, False])
def test_closed_form_is_the_event_loop(seed, n, n_ms, spread_at, onchip):
    tr = _free_trace(np.random.default_rng(seed), n, n_ms, spread_at)
    np.testing.assert_array_equal(R.closed_form(tr, NET, n_ms, onchip),
                                  R.event_loop(tr, NET, n_ms, onchip))


def test_a_sweep_replays_in_closed_form():
    """A version sweep's independent small reads over 4 servers: one
    server's last read completes after its queue of 128 B services."""
    n = 40_000
    tr = dict(kind=np.zeros(n, np.int8), ms=(np.arange(n) % 4).astype(
        np.int32), nbytes=np.full(n, 128), lane=np.full(n, -1, np.int32),
        doorbell=np.arange(n), dep=np.full(n, -1), dep2=np.full(n, -1),
        at=np.zeros(n), n_lanes=0)
    out = R.replay(tr, NET, 4, True)
    svc_ps = 20_000                   # 1 / 50 Mops beats 128 B at 12.5 GB/s
    assert out["makespan_s"] == (n // 4 * svc_ps + 2_000_000) / 1e12
    assert out["latency_s"].shape == (0,)
