"""Share of the traced window the host sat in encoder calls while the
device ran nothing: the total time of the program's ``encoder.call``
spans (padding, dispatch, the wait, the copy back) less the trace's
device busy time, floored at 0, in per cent.  In a sequential grid run
the device is busy only inside encoder calls."""
from bench.spans import span_s


def read(r):
    secs = span_s(r, ("encoder.call",), "total_s")
    if secs is None:
        return None
    return 100.0 * max(0.0, secs - r.trace["busy_s"]) / r.trace["window_s"]
