"""Share of the traced window the calling thread spent in the caches'
store reads and writes, waits on prefetches and write-behind flushes
included (self time of ``cache.lookup`` and ``cache.store``), in per
cent.  The I/O pool's own work (``cache.io``) is off the blocking path
and left out."""
from bench.spans import self_share


def read(r):
    return self_share(r, ("cache.lookup", "cache.store"))
