"""Program spans and counters (``repro.core.trace``): they record only
while a profiler session records, land in the profiler's own trace under
their bare names, and agree with the executor's own node times."""
import glob
import os
import threading
import time

import jax
import numpy as np
import pytest

from repro.core import (ColFrame, Experiment, ExecutionPlan,
                        GenericTransformer, add_ranks, trace)
from repro.ir import InvertedIndex, TextLoader, msmarco_like
from repro.models.cross_encoder import DuoScorer, EncoderConfig, MonoScorer

CORPUS = msmarco_like(1, scale=0.04)
INDEX = InvertedIndex.build(CORPUS.get_corpus_iter())
CE = EncoderConfig(n_layers=1, d_model=32, n_heads=2, d_ff=64,
                   vocab_size=4096, max_len=32)
CUTS = (5, 8)

SPANS = {"plan.build", "plan.run", "plan.node", "experiment.evaluate",
         "cache.lookup", "cache.store", "bm25.search", "encoder.tokenize",
         "encoder.call"}
COUNTERS = {"encoder.tokens", "encoder.slots", "tokenizer.sides",
            "tokenizer.strings"}


def _systems():
    bm25 = INDEX.bm25(num_results=20)
    loader = TextLoader(CORPUS.text_map())
    mono, duo = MonoScorer(CE), DuoScorer(CE, max_docs=3)
    return [bm25 % k >> loader >> mono % 3 >> duo for k in CUTS]


def _host_events(trace_dir):
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    pd = ProfileData.from_file(path)
    return [(e.name, dict(e.stats)) for p in pd.planes
            if p.name.startswith("/host:") for ln in p.lines
            for e in ln.events]


@pytest.fixture(autouse=True)
def _clean_table():
    trace.reset()
    yield
    trace.reset()


def test_nothing_records_without_a_profiler_session(monkeypatch):
    opened = []
    monkeypatch.setattr(trace, "TraceMe",
                        lambda *a, **k: opened.append(a) or None)
    assert not trace.recording()
    with trace.span("plan.node", node="x") as s:
        pass
    assert s is trace.span("encoder.call")       # one shared no-op
    with trace.timed("plan.node", node="x") as t:
        pass
    assert t.t1 >= t.t0
    trace.count("encoder.tokens", 5)
    assert opened == []
    assert trace.summary() == {"spans": {}, "counters": {}}


def test_counters_do_no_work_when_off():
    def boom():
        raise AssertionError("counted while not recording")
    trace.count("encoder.tokens", boom)
    assert trace.summary()["counters"] == {}


def test_self_time_leaves_out_nested_spans(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with trace.span("outer"):
            time.sleep(0.01)
            with trace.span("inner"):
                time.sleep(0.01)
                with trace.span("leaf"):
                    time.sleep(0.01)
            with trace.span("inner"):
                time.sleep(0.005)
        trace.count("c", lambda: 3)
        trace.count("c", 4)

        def worker():                       # another thread's own stack
            with trace.span("leaf"):
                time.sleep(0.005)
        th = threading.Thread(target=worker)
        th.start()
        th.join(10)
        assert not th.is_alive()
    s = trace.summary()
    sp = s["spans"]
    assert s["counters"] == {"c": 7}
    assert sp["outer"]["n"] == 1 and sp["inner"]["n"] == 2
    assert sp["leaf"]["n"] == 2
    for row in sp.values():
        assert 0 < row["self_s"] <= row["total_s"]
    # a parent's total is its self time plus its children's totals
    assert sp["outer"]["total_s"] == pytest.approx(
        sp["outer"]["self_s"] + sp["inner"]["total_s"], rel=1e-9)
    leaf_here = sp["leaf"]["total_s"] - sp["leaf"]["self_s"]
    assert leaf_here == pytest.approx(0.0, abs=1e-12)   # leaves: self=total
    assert sp["inner"]["total_s"] - sp["inner"]["self_s"] < \
        sp["leaf"]["total_s"]                   # the thread's leaf is apart
    assert sp["outer"]["self_s"] >= 0.01 and sp["outer"]["self_s"] < 0.02


def test_a_plan_records_every_span_and_counter(tmp_path):
    topics, qrels = CORPUS.get_topics(), CORPUS.get_qrels()
    Experiment(_systems(), topics, qrels, ["nDCG@10"],
               precompute_prefix=True, precompute_mode="plan",
               cache_dir=str(tmp_path / "warm"))        # compile outside
    trace.reset()
    with jax.profiler.trace(str(tmp_path / "trace")):
        res = Experiment(_systems(), topics, qrels, ["nDCG@10"],
                         precompute_prefix=True, precompute_mode="plan",
                         cache_dir=str(tmp_path / "cache"))
    s = trace.summary()
    assert SPANS <= set(s["spans"]), sorted(s["spans"])
    assert COUNTERS == set(s["counters"])
    for name, row in s["spans"].items():
        assert row["n"] > 0 and 0 <= row["self_s"] <= row["total_s"], name
    node = s["spans"]["plan.node"]
    assert node["self_s"] < node["total_s"]     # BM25, encoders inside
    c = s["counters"]
    assert 0 < c["encoder.tokens"] < c["encoder.slots"]
    assert c["encoder.slots"] % CE.max_len == 0
    assert 0 < c["tokenizer.strings"] < c["tokenizer.sides"]
    # the planner's node times come from the spans' own readings
    st = res.precompute
    assert sum(st.node_exec_counts.values()) == node["n"]
    assert sum(st.node_times_s.values()) == pytest.approx(node["total_s"],
                                                          rel=1e-9)
    # the trace holds the spans by bare name, node labels as stats
    events = _host_events(str(tmp_path / "trace"))
    ours = [(n, st_) for n, st_ in events if n.startswith("repro.")]
    assert {n[len("repro."):] for n, _ in ours} >= SPANS
    assert not any("#" in n or n.startswith("bench.") for n, _ in ours)
    per_label = {}
    for n, stats in ours:
        if n == "repro.plan.node":
            per_label[stats["node"]] = per_label.get(stats["node"], 0) + 1
    assert per_label == {k.replace("#", "~"): v
                         for k, v in st.node_exec_counts.items()}
    tok = [stats for n, stats in ours if n == "repro.encoder.tokenize"]
    assert {t["role"] for t in tok} == {"mono", "duo"}


def test_node_times_match_spans_under_the_sharded_executor(tmp_path):
    plan = ExecutionPlan(_systems())
    plan.run(CORPUS.get_topics(), n_shards=2, max_workers=2)   # compile
    trace.reset()
    with jax.profiler.trace(str(tmp_path)):
        _, st = plan.run(CORPUS.get_topics(), n_shards=2, max_workers=2)
    node = trace.summary()["spans"]["plan.node"]
    assert st.n_shards == 2
    assert sum(st.node_exec_counts.values()) == node["n"]
    assert sum(st.node_times_s.values()) == pytest.approx(node["total_s"],
                                                          rel=1e-9)


def _pruned_pipes():
    """``annotate >> R % 2`` with a query-keyed R: on a warm cache the
    plan probes R's store and runs ``annotate`` only on a miss."""
    def retr_fn(inp):
        rows = [{"qid": q, "query": t, "docno": f"d{i}", "score": 9.0 - i}
                for q, t in zip(inp["qid"].tolist(), inp["query"].tolist())
                for i in range(3)]
        return add_ranks(ColFrame.from_dicts(rows))
    ann = GenericTransformer(lambda inp: inp.assign(prio=np.ones(len(inp))),
                             "annotate", augment_only=True)
    retr = GenericTransformer(retr_fn, "R", one_to_many=True,
                              key_columns=("qid", "query"))
    return [ann >> retr % 2]


@pytest.mark.parametrize("queries,pruned", [
    (ColFrame({"qid": ["q1", "q2"], "query": ["alpha", "beta"]}), 1),
    (ColFrame({"qid": ["q9"], "query": ["omega"]}), 0),
])
def test_node_times_match_spans_through_a_cache_probe(tmp_path, queries,
                                                      pruned):
    warm_up = ColFrame({"qid": ["q1", "q2"], "query": ["alpha", "beta"]})
    with ExecutionPlan(_pruned_pipes(), cache_dir=str(tmp_path)) as cold:
        cold.run(warm_up)
    with ExecutionPlan(_pruned_pipes(), cache_dir=str(tmp_path)) as warm:
        with jax.profiler.trace(str(tmp_path / "trace")):
            _, st = warm.run(queries)
    node = trace.summary()["spans"]["plan.node"]
    assert st.nodes_pruned == pruned
    # a probe that misses is the node's work too: one record, one more span
    assert sum(st.node_exec_counts.values()) == node["n"] - (1 - pruned)
    assert sum(st.node_times_s.values()) == pytest.approx(node["total_s"],
                                                          rel=1e-9)


@pytest.mark.parametrize("cls,role,other", [(MonoScorer, "mono", "duo"),
                                            (DuoScorer, "duo", "mono")])
def test_encoder_programs_keep_their_name_and_carry_their_role(
        monkeypatch, cls, role, other):
    from repro.caching.compile_cache import default_compile_cache
    fns = []
    call = default_compile_cache.call
    monkeypatch.setattr(default_compile_cache, "call",
                        lambda name, fn, *a, **k: fns.append(fn)
                        or call(name, fn, *a, **k))
    s = cls(CE, max_docs=3) if cls is DuoScorer else cls(CE)
    s._score_pairs(["a query"], ["a passage"])
    text = jax.jit(fns[0]).lower(np.ones((64, CE.max_len), np.int32)) \
        .compile().as_text()
    # the trace reads the program by its module name; its ops by scope
    assert text.startswith("HloModule jit__lambda,")
    assert f"/{role}/" in text and f"/{other}/" not in text
