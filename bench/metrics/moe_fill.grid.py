"""Share of the expert rows the MoE dispatch computes that belong to
real tokens: the program's counters ``moe.rows`` ((token, expert) rows
of real tokens over the MoE layers) over ``moe.slots`` (every slot of
every bucket row, as the program sizes the dispatch), in per cent."""
from bench.spans import table


def read(r):
    s = table() if r.trace else None
    slots = s["counters"].get("moe.slots") if s else None
    if not slots:
        return None
    return 100.0 * s["counters"].get("moe.rows", 0) / slots
