"""95th-percentile latency of every request due in the window (as
``serve_p50_ms``)."""
import numpy as np


def read(r):
    lat = r.run.latencies_ms
    return None if lat is None or not len(lat) else float(
        np.percentile(lat, 95))
