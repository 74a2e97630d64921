"""CPU tests of the readers of the program's own spans and counters
(``bench/spans.py``, the ``.grid`` share and fill readers), and of how
the trace reduction labels idle time with those spans."""
import os
import sys
import time
from types import SimpleNamespace

import pytest

from bench.registry import Registry
from bench.run import Record, start_trace
from bench.trace_reduce import reduce_trace

TABLE = {"spans": {
    "encoder.tokenize": {"total_s": 3.0, "self_s": 3.0, "n": 180},
    "bm25.search": {"total_s": 0.5, "self_s": 0.5, "n": 43},
    "cache.lookup": {"total_s": 0.25, "self_s": 0.2, "n": 8},
    "cache.store": {"total_s": 0.1, "self_s": 0.1, "n": 8},
    "cache.io": {"total_s": 9.0, "self_s": 9.0, "n": 8},
    "plan.run": {"total_s": 9.1, "self_s": 0.1, "n": 1},
    "plan.node": {"total_s": 9.0, "self_s": 0.5, "n": 21},
    "plan.build": {"total_s": 0.3, "self_s": 0.25, "n": 1},
    "experiment.evaluate": {"total_s": 0.15, "self_s": 0.15, "n": 1},
    "encoder.call": {"total_s": 5.5, "self_s": 5.5, "n": 190}},
    "counters": {"encoder.tokens": 250.0, "encoder.slots": 1000.0}}
TRACE = {"window_s": 10.0, "busy_s": 5.0}
#: what each reader makes of TABLE over TRACE, in per cent
EXPECTED = {"tokenize_share.grid": 30.0, "bm25_share.grid": 5.0,
            "cache_io_share.grid": 3.0, "plan_host_share.grid": 10.0,
            "encoder_dispatch_share.grid": 5.0, "encoder_fill.grid": 25.0}


def _record(trace):
    return Record(SimpleNamespace(counters={}), 1.0, trace, {}, {})


@pytest.fixture
def table(monkeypatch):
    from repro.core import trace
    monkeypatch.setattr(trace, "summary", lambda: TABLE)


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_reads_the_table_over_the_window(table, metric):
    read = Registry().reader(metric)
    assert read(_record(dict(TRACE))) == pytest.approx(EXPECTED[metric])
    assert read(_record(None)) is None


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_is_silent_where_the_program_recorded_nothing(metric,
                                                             monkeypatch):
    read = Registry().reader(metric)
    from repro.core import trace
    trace.reset()
    assert read(_record(dict(TRACE))) is None
    # a program without the module (an older commit) reads the same
    monkeypatch.setitem(sys.modules, "repro.core.trace", None)
    assert read(_record(dict(TRACE))) is None


def test_dispatch_share_is_floored_at_zero(table):
    read = Registry().reader("encoder_dispatch_share.grid")
    assert read(_record({"window_s": 10.0, "busy_s": 6.0})) == 0.0


def test_the_program_spans_are_in_the_benchmark():
    reg = Registry()
    names = {m["name"]: m for m in reg.spec["per_layer"]}
    for metric in EXPECTED:
        m = names[metric]
        assert m["moves"] == "grid_qps"
        assert m["workloads"] == ["grid.bm25-minilm.table2"]
        assert m["source"] in ("program_span", "program_counter")
    assert {m["name"] for m in reg.metrics("grid.bm25-minilm.table2",
                                           trace=True)} >= set(EXPECTED)


def _plane(name, lines):
    return SimpleNamespace(name=name, lines=[
        SimpleNamespace(name=ln, events=[SimpleNamespace(
            name=n, start_ns=s, duration_ns=e - s) for s, e, n in evs])
        for ln, evs in lines.items()])


def test_a_program_span_labels_the_idle_gap_it_covers(tmp_path,
                                                      monkeypatch):
    """Host planes recorded on the CPU and a device plane laid over them,
    busy everywhere in the window but while the tokenizer ran."""
    import jax
    from jax.profiler import ProfileData
    from repro.core import trace
    start_trace(str(tmp_path))                # the harness's own options
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.experiment"):
            time.sleep(0.005)
            with trace.span("encoder.tokenize", role="duo", pairs=90):
                time.sleep(0.02)
            time.sleep(0.005)
    jax.profiler.stop_trace()
    trace.reset()
    path = next(os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
                for f in fs if f.endswith(".xplane.pb"))
    pd = ProfileData.from_file(path)
    host = [(p.name, {ln.name: [(e.start_ns, e.start_ns + e.duration_ns,
                                 e.name) for e in ln.events]
                      for ln in p.lines})
            for p in pd.planes if p.name.startswith("/host:")]
    ev = {n: (s, e) for _, lines in host for evs in lines.values()
          for s, e, n in evs}
    assert "repro.encoder.tokenize" in ev         # its bare name
    (w0, w1), (t0, t1) = ev["bench.window"], ev["repro.encoder.tokenize"]
    tpu = _plane("/device:TPU:0", {
        "XLA Ops": [(w0, t0, "%fusion.1 = f32[] fusion()"),
                    (t1, w1, "%fusion.2 = f32[] fusion()")],
        "XLA Modules": [(w0, t0, "jit__lambda(1)"),
                        (t1, w1, "jit__lambda(1)")]})
    fake = SimpleNamespace(planes=[_plane(n, lines) for n, lines in host]
                           + [tpu])
    monkeypatch.setattr(jax.profiler, "ProfileData",
                        SimpleNamespace(from_file=lambda p: fake))
    r = reduce_trace(path)
    assert r["idle_gaps"] == [["bench.experiment / repro.encoder.tokenize",
                               pytest.approx((t1 - t0) / 1e9)]]
