"""95th percentile of how late the load generator sent a request after
its due time (a starved generator shows here, not as a fast server)."""
import numpy as np


def read(r):
    lag = r.run.lateness_ms
    return None if lag is None or not len(lag) else float(
        np.percentile(lag, 95))
