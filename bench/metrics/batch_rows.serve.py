"""Rows per micro-batch of the streaming executor
(``StreamStats.rows_executed`` over ``StreamStats.batches``)."""


def read(r):
    c = r.run.counters
    if not c.get("batches"):
        return None
    return c["rows_executed"] / c["batches"]
