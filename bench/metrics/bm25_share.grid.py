"""Share of the traced window the host spent in BM25 retrieval (self
time of the program's ``bm25.search`` spans, one per query), in per
cent."""
from bench.spans import self_share


def read(r):
    return self_share(r, ("bm25.search",))
