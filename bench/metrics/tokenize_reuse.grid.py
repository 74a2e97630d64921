"""Share of the string sides of the encoders' rows that their batch call
did not tokenize again: 100 x (1 - ``tokenizer.strings`` (distinct
strings tokenized) / ``tokenizer.sides`` (strings the rows reference)),
in per cent."""
from bench.spans import table


def read(r):
    s = table() if r.trace else None
    sides = s["counters"].get("tokenizer.sides") if s else None
    if not sides:
        return None
    return 100.0 * (1.0 - s["counters"].get("tokenizer.strings", 0) / sides)
