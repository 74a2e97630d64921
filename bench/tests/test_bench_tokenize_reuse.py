"""CPU tests of ``tokenize_reuse.grid``: the share of the encoders' string
sides that their batch call did not tokenize again, from the program's
``tokenizer.sides`` and ``tokenizer.strings`` counters."""
import sys
from types import SimpleNamespace

import pytest

from bench.registry import Registry
from bench.run import Record, start_trace

METRIC = "tokenize_reuse.grid"
TRACE = {"window_s": 10.0, "busy_s": 5.0}


def _record(trace):
    return Record(SimpleNamespace(counters={}), 1.0, trace, {}, {})


def _table(monkeypatch, counters):
    from repro.core import trace
    monkeypatch.setattr(trace, "summary", lambda: {"spans": {},
                                                   "counters": counters})


def test_reader_reads_the_recorded_table(monkeypatch):
    _table(monkeypatch, {"tokenizer.sides": 400.0,
                         "tokenizer.strings": 90.0})
    read = Registry().reader(METRIC)
    assert read(_record(dict(TRACE))) == pytest.approx(77.5)
    assert read(_record(None)) is None


def test_reader_is_silent_where_the_program_recorded_nothing(monkeypatch):
    read = Registry().reader(METRIC)
    _table(monkeypatch, {"encoder.tokens": 250.0, "encoder.slots": 1000.0})
    assert read(_record(dict(TRACE))) is None
    # a program without the module (an older commit) reads the same
    monkeypatch.setitem(sys.modules, "repro.core.trace", None)
    assert read(_record(dict(TRACE))) is None


def test_reader_reads_what_the_tokenizer_counts_while_traced(tmp_path):
    """Mono's rows reference the query and a passage, duo's the query and
    two passages; each distinct string of a call is tokenized once."""
    import jax
    from repro.core import trace
    from repro.ir import HashTokenizer
    tok = HashTokenizer(1024)
    p = ["alpha beta", "gamma delta", "epsilon"]
    trace.reset()
    start_trace(str(tmp_path))
    try:
        tok.encode_pairs(["q"] * 3, p, 16)                   # 6 sides, 4
        tok.encode_pairs(["q"] * 6, [(a, b) for a in p for b in p
                                     if a != b], 16)         # 18 sides, 4
    finally:
        jax.profiler.stop_trace()
    read = Registry().reader(METRIC)
    try:
        assert read(_record(dict(TRACE))) == pytest.approx(
            100.0 * (1 - 8 / 24))
    finally:
        trace.reset()


def test_the_metric_is_in_the_benchmark():
    reg = Registry()
    m = {m["name"]: m for m in reg.spec["per_layer"]}[METRIC]
    assert (m["moves"], m["workloads"], m["source"], m["layer"]) == (
        "grid_qps", ["grid.bm25-minilm.table2"], "program_counter",
        "host preparation: tokenizer")
    assert METRIC in {x["name"] for x in reg.metrics(
        "grid.bm25-minilm.table2", trace=True)}
