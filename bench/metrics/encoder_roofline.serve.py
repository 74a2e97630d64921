"""The cross-encoder programs' share of their roofline, in per cent:
operations counted from real tokens, or the layer weights read once per
call, over the device time of the mono and duo programs (both are the
jitted lambdas of the program's compile cache, ``jit__lambda``)."""
from bench.readers import roofline


def read(r):
    return roofline(r, ("jit__lambda",), "encoder_flops",
                    "encoder_weight_bytes")
