"""Median latency of every request due in the window, from its due time
to the completion its client saw; a failed request counts at the time it
was given up."""
import numpy as np


def read(r):
    lat = r.run.latencies_ms
    return None if lat is None or not len(lat) else float(
        np.percentile(lat, 50))
