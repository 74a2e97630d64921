"""Share of the traced window the host spent tokenizing encoder pairs
(self time of the program's ``encoder.tokenize`` spans), in per cent."""
from bench.spans import self_share


def read(r):
    return self_share(r, ("encoder.tokenize",))
