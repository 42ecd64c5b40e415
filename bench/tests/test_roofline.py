"""The leaf probe's work, counted from the algorithm, and the peak table."""
import pytest

import roofline


def test_leaf_search_bytes_at_fanout_16():
    # 16 keys + 16 values (4 B), 16 front + 16 rear entry versions (1 B),
    # front/rear node version and free bit (1 B), the query (4 B); writes
    # value, found, consistent (4 B each)
    assert roofline.leaf_search_bytes_per_lane(16) == \
        16 * 4 + 16 * 4 + 16 + 16 + 3 + 4 + 12 == 179


def test_roofline_share():
    # 256 lanes in 972 ns on a TPU v5 lite: 45,824 B at 819 GB/s
    pct = roofline.leaf_search_roofline_pct(256, 972e-9, 16, "TPU v5 lite")
    assert pct == pytest.approx(100 * 256 * 179 / 819e9 / 972e-9)
    assert 0 < pct < 100


def test_nothing_to_read_and_unknown_device():
    assert roofline.leaf_search_roofline_pct(0, 1e-6, 16,
                                             "TPU v5 lite") is None
    assert roofline.leaf_search_roofline_pct(256, 0.0, 16,
                                             "TPU v5 lite") is None
    with pytest.raises(KeyError):
        roofline.peak("TPU v9 imaginary")
