"""Topics per second through the whole grid: topics of the iterations
that started inside the window, over the time until the last of them
ended."""


def read(r):
    c = r.run.counters
    if "topics" not in c or r.run.window_s <= 0:
        return None
    return c["topics"] / r.run.window_s
