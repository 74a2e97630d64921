"""The LLM scorer's programs' share of their roofline, in per cent:
operations counted from real tokens (active experts only), or the layer
weights read once per call, over the device time of the program
``jit_llm_score`` (``LLMScorer``'s jitted scorer, weights as
arguments)."""
from bench.readers import roofline


def read(r):
    return roofline(r, ("jit_llm_score",), "llm_flops", "llm_weight_bytes")
