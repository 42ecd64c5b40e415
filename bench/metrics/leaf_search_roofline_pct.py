"""The leaf probe's least time at the chip's HBM bandwidth, over the device
time of its ``leaf_search`` kernel events (%).  One lane per client
lookup; its bytes are counted from the algorithm (``roofline.py``)."""
import roofline


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    return roofline.leaf_search_roofline_pct(
        ctx["reads"], tr.kernel_s("leaf_search"),
        ctx["cell"].config["fanout"], ctx["device_kind"])
