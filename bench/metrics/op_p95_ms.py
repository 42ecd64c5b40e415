"""95th percentile of every window op's closed-loop latency, in ms: from
the return of its client's previous op to the return of its own wave."""
import numpy as np


def read(ctx):
    lat = ctx["latencies_s"]
    return float(np.percentile(lat, 95)) * 1e3 if lat.size else None
