"""Peaks of the chips the benchmark runs on, and the work of its kernels.

A kernel's roofline share is the least time the chip could take over the
kernel's work, divided by the device time its events took in the trace.
The work is counted from the algorithm, not from the operands a given
version of the program happens to pass, so that a later change that fuses
a gather into the kernel, or widens an operand, does not change it.
"""
from __future__ import annotations

#: Published peaks per ``device_kind``.  Source: Google Cloud documentation,
#: "TPU v5e" (16 GB HBM at 819 GB/s, 197 TFLOP/s bf16, 393 TOP/s int8).
PEAKS = {
    "TPU v5 lite": dict(hbm_bytes_per_s=819e9, bf16_flops_per_s=197e12,
                        int8_ops_per_s=393e12, hbm_bytes=16e9),
}


def peak(device_kind: str) -> dict:
    """The peaks of ``device_kind``; an unknown device is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def leaf_search_bytes_per_lane(fanout: int, key_bytes: int = 4,
                               value_bytes: int = 4,
                               version_bytes: int = 1) -> int:
    """HBM bytes one lane of the leaf probe must move.

    It reads its leaf's ``fanout`` keys and values, the front and rear
    entry versions of every slot, the front and rear node versions and the
    free bit (one byte each, as the pool stores them), and its query key;
    it writes three words: the value, found, consistent.
    """
    reads = (fanout * (key_bytes + value_bytes + 2 * version_bytes)
             + 3 * version_bytes + key_bytes)
    return reads + 3 * 4


def leaf_search_roofline_pct(lanes: int, kernel_s: float, fanout: int,
                             device_kind: str):
    """Roofline share (%) of the leaf probe: memory-bound, so the least
    time is bytes over the HBM bandwidth.  ``None`` when nothing ran."""
    if lanes <= 0 or kernel_s <= 0:
        return None
    least = lanes * leaf_search_bytes_per_lane(fanout) / \
        peak(device_kind)["hbm_bytes_per_s"]
    return 100.0 * least / kernel_s
