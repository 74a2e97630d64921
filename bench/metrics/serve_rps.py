"""Requests completed inside the window, per second of the window."""


def read(r):
    c = r.run.counters
    if "completed_in_window" not in c:
        return None
    return c["completed_in_window"] / r.run.window_s
