"""Share of the encoder programs' input slots that hold a real token:
nonzero token ids of the real rows (``encoder.tokens``) over bucket
rows times sequence length (``encoder.slots``), in per cent."""
from bench.spans import table


def read(r):
    s = table() if r.trace else None
    slots = s["counters"].get("encoder.slots") if s else None
    if not slots:
        return None
    return 100.0 * s["counters"].get("encoder.tokens", 0) / slots
