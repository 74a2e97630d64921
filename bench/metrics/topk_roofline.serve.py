"""The dense top-k program's share of its roofline, in per cent: the
index read once per call over HBM bandwidth, or the scoring operations
of the real queries over the bf16 peak, whichever is larger, over the
device time of ``jit__xla_chunk_topk``."""
from bench.readers import roofline


def read(r):
    return roofline(r, ("jit__xla_chunk_topk",), "topk_flops",
                    "topk_index_bytes")
