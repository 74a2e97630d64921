"""Plan-compiler optimizer (core/ir.py + core/rewrite.py + core/plan.py).

Covers the pass pipeline — algebraic normalization of commutative
operators, cross-pipeline CSE beyond prefixes, RankCutoff pushdown into
retriever depth, cache-aware pruning behind warm manifests — the
``optimize=`` knob, ``explain()`` and its ``repro plan explain``
round-trip, and the hard invariant: ``optimize="all"`` and
``optimize="none"`` produce bit-identical per-qid results under both
the sequential and the sharded executor (property-tested).
"""
import json

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (ColFrame, ExecutionPlan, Experiment,
                        GenericTransformer, OPTIMIZER_PASSES, RankCutoff,
                        Transformer, add_ranks)

QUERIES = ColFrame({"qid": ["q1", "q2", "q3"],
                    "query": ["alpha", "beta", "gamma"]})

SORT = ["qid", "docno"]


class CutRetriever(Transformer):
    """Deterministic retriever with an absorbable depth knob: scores
    strictly decrease with the doc index, so the top-k is a prefix of
    the top-n for any n >= k (the contract ``with_cutoff`` needs)."""

    key_columns = ("qid", "query")
    one_to_many = True

    def __init__(self, name, n=6, base=100.0):
        self.name, self.n, self.base = name, int(n), float(base)

    def signature(self):
        return ("CutRetriever", self.name, self.n, self.base)

    def with_cutoff(self, k):
        return self if int(k) >= self.n \
            else CutRetriever(self.name, int(k), self.base)

    def transform(self, inp):
        rows = [{"qid": q, "query": t, "docno": f"{self.name}_d{i:02d}",
                 "score": self.base - i, "rank": i}
                for q, t in zip(inp["qid"].tolist(), inp["query"].tolist())
                for i in range(self.n)]
        return ColFrame.from_dicts(rows) if rows else inp.head(0)


class Counting(GenericTransformer):
    def __init__(self, name, fn=None, **kw):
        self.calls = 0

        def wrapped(inp, _fn=fn):
            self.calls += 1
            return _fn(inp) if _fn else inp
        super().__init__(wrapped, name, **kw)


def make_retriever(name, n=4, base=10.0):
    def fn(inp):
        rows = [{"qid": q, "query": t, "docno": f"{name}_d{i}",
                 "score": base - i}
                for q, t in zip(inp["qid"].tolist(), inp["query"].tolist())
                for i in range(n)]
        return add_ranks(ColFrame.from_dicts(rows))
    return Counting(name, fn, one_to_many=True, key_columns=("qid", "query"))


def docno_scorer(name, mult=1.0, rank_preserving=False):
    """Deterministic score from the docno (works on score-less frames,
    e.g. SetUnion output)."""
    def fn(inp, _m=mult):
        scores = np.array([float(ord(d[-1]) + len(d)) * _m
                           for d in inp["docno"].tolist()])
        return add_ranks(inp.assign(score=scores))
    return Counting(name, fn, rank_preserving=rank_preserving)


def boost(name="boost", factor=2.0):
    """Strictly monotone per-row score map — rank-preserving."""
    def fn(inp, _f=factor):
        return add_ranks(inp.assign(score=inp["score"] * _f))
    return Counting(name, fn, rank_preserving=True)


def assert_bit_identical(outs_a, outs_b):
    assert len(outs_a) == len(outs_b)
    for got, want in zip(outs_a, outs_b):
        cols = [c for c in ("qid", "docno", "score", "rank")
                if c in want.columns and c in got.columns]
        by = [c for c in SORT if c in want.columns]
        g = got.sort_values(by) if by else got
        w = want.sort_values(by) if by else want
        assert g.equals(w, cols=cols, rtol=0, atol=0), \
            "optimizer changed results"


def run_both(pipelines, queries=QUERIES, **run_kw):
    outs_opt, stats_opt = ExecutionPlan(pipelines, optimize="all").run(
        queries, **run_kw)
    outs_ref, stats_ref = ExecutionPlan(pipelines, optimize="none").run(
        queries, **run_kw)
    assert_bit_identical(outs_opt, outs_ref)
    assert stats_opt.nodes_executed <= stats_ref.nodes_executed
    return stats_opt, stats_ref


# ---------------------------------------------------------------------------
# normalization + CSE
# ---------------------------------------------------------------------------

def test_commutative_normalization_shares_nodes():
    a = make_retriever("A")
    b = make_retriever("B", base=8.0)
    stats_opt, stats_ref = run_both([a + b, b + a])
    # a, b and ONE combine node; unoptimized runs all six
    assert stats_opt.nodes_planned == 3
    assert stats_ref.nodes_planned == 6
    a.calls = b.calls = 0
    ExecutionPlan([a + b, b + a]).run(QUERIES)
    assert a.calls == 1 and b.calls == 1


def test_set_union_commutes_but_concat_does_not():
    a = make_retriever("A")
    b = make_retriever("B", base=8.0)
    assert ExecutionPlan([a | b, b | a]).n_nodes() == 3
    # ^ and & are order-sensitive: no merge
    assert ExecutionPlan([a ^ b, b ^ a]).n_nodes() == 4
    assert ExecutionPlan([a & b, b & a]).n_nodes() == 4
    run_both([a ^ b, b ^ a])
    run_both([a & b, b & a])


def test_cse_merges_non_prefix_subtrees():
    """The tentpole claim: an identical subtree *under* different
    operator contexts — not a stage-list prefix — executes once."""
    a = make_retriever("A")
    b = make_retriever("B", base=8.0)
    rr = docno_scorer("rr")
    pipelines = [(a | b) >> rr, (b | a) >> rr >> boost("post"),
                 ((a | b) >> rr) % 3]
    stats_opt, _ = run_both(pipelines)
    a.calls = b.calls = rr.calls = 0
    ExecutionPlan(pipelines).run(QUERIES)
    assert a.calls == 1 and b.calls == 1
    assert rr.calls == 1                 # shared through |, >> and %


def test_experiment_shares_non_prefix_subtree():
    """Acceptance criterion: an Experiment over >=3 pipelines sharing a
    non-prefix subtree (the same reranker over two differently-ordered
    unioned retrievers) executes that subtree once."""
    a = make_retriever("A")
    b = make_retriever("B", base=8.0)
    rr = docno_scorer("rr")
    systems = [(a | b) >> rr, (b | a) >> rr >> boost("post"),
               ((a | b) >> rr) % 3]
    qrels = ColFrame({"qid": ["q1", "q2", "q3"],
                      "docno": ["A_d0", "B_d1", "A_d2"],
                      "label": [1, 1, 1]})
    base = Experiment(systems, QUERIES, qrels, ["nDCG@10", "MAP"])
    a.calls = b.calls = rr.calls = 0
    planned = Experiment(systems, QUERIES, qrels, ["nDCG@10", "MAP"],
                         precompute_prefix=True, precompute_mode="plan")
    assert rr.calls == 1
    assert a.calls == 1 and b.calls == 1
    for n1, n2 in zip(base.names, planned.names):
        for m in ("nDCG@10", "MAP"):
            assert base.means[n1][m] == pytest.approx(planned.means[n2][m])


# ---------------------------------------------------------------------------
# RankCutoff pushdown
# ---------------------------------------------------------------------------

def _retriever_nodes(plan, cls=CutRetriever):
    return [n for n in plan.graph.nodes
            if n.kind == "stage" and isinstance(n.stage, cls)]


def test_pushdown_absorbs_cutoff_into_retriever():
    r = CutRetriever("R", n=8)
    plan = ExecutionPlan([r % 3 >> boost()])
    nodes = _retriever_nodes(plan)
    assert len(nodes) == 1
    assert nodes[0].stage.n == 3         # retriever-level depth assertion
    assert not any(isinstance(n.stage, RankCutoff) for n in plan.graph.nodes)
    stats_opt, _ = run_both([CutRetriever("R", n=8) % 3 >> boost("b2")])
    assert stats_opt.cutoffs_pushed == 1
    assert stats_opt.nodes_eliminated >= 1


def test_pushdown_through_rank_preserving_chain():
    r = CutRetriever("R", n=8)
    plan = ExecutionPlan([r >> boost("b1") >> boost("b2") % 4])
    nodes = _retriever_nodes(plan)
    assert nodes[0].stage.n == 4         # climbed through both boosts
    run_both([CutRetriever("R", n=8) >> boost("c1") >> boost("c2") % 4])


def test_pushdown_moves_cutoff_below_chain_without_absorber():
    """No absorber below the chain (the retriever lacks with_cutoff):
    the cutoff still moves below rank-preserving stages so they only
    process k rows."""
    a = make_retriever("A", n=8)         # GenericTransformer: no with_cutoff
    plan = ExecutionPlan([a >> boost("b") % 3])
    cut_nodes = [n for n in plan.graph.nodes
                 if isinstance(n.stage, RankCutoff)]
    assert len(cut_nodes) == 1
    # the cutoff's input is now the retriever, not the boost
    assert cut_nodes[0].inputs[0].stage is a
    assert sum(p.cutoffs_pushed for p in plan.pass_stats
               if p.name == "pushdown") == 1
    run_both([make_retriever("A2", n=8) >> boost("b2") % 3])


def test_pushdown_declined_on_shared_retriever():
    r = CutRetriever("R", n=8)
    plan = ExecutionPlan([r % 3, r])     # r itself is a terminal
    nodes = _retriever_nodes(plan)
    assert len(nodes) == 1 and nodes[0].stage.n == 8
    stats_opt, _ = run_both([CutRetriever("R", n=8) % 3,
                             CutRetriever("R", n=8)])
    assert stats_opt.cutoffs_pushed == 0


def test_stacked_cutoffs_fuse_to_min():
    r = CutRetriever("R", n=9)
    plan = ExecutionPlan([r % 5 % 3])
    nodes = _retriever_nodes(plan)
    assert nodes[0].stage.n == 3
    assert not any(isinstance(n.stage, RankCutoff) for n in plan.graph.nodes)
    run_both([CutRetriever("R", n=9) % 5 % 3])


def test_pushdown_bm25_num_results():
    """Retriever-level num_results assertion on the real BM25 stage."""
    from repro.ir import InvertedIndex, BM25Retriever
    docs = [{"docno": f"d{i}", "text": f"term{i % 7} shared tok{i}"}
            for i in range(40)]
    index = InvertedIndex.build(iter(docs))
    topics = ColFrame({"qid": ["q1", "q2"],
                       "query": ["shared term1", "shared term2"]})
    bm25 = index.bm25(num_results=25)
    pipes = [bm25 % 5 >> boost("bb")]
    plan = ExecutionPlan(pipes)
    nodes = _retriever_nodes(plan, BM25Retriever)
    assert len(nodes) == 1 and nodes[0].stage.num_results == 5
    outs_opt, _ = plan.run(topics)
    outs_ref, _ = ExecutionPlan(
        [index.bm25(num_results=25) % 5 >> boost("bb2")],
        optimize="none").run(topics)
    assert_bit_identical(outs_opt, outs_ref)


# ---------------------------------------------------------------------------
# cache-aware pruning
# ---------------------------------------------------------------------------

def _annotator(calls):
    def fn(inp):
        calls["ann"] += 1
        return inp.assign(prio=np.ones(len(inp)))
    return GenericTransformer(fn, "annotate", augment_only=True)


def _cached_retr_pipes(calls):
    def retr_fn(inp):
        rows = [{"qid": q, "query": t, "docno": f"d{i}", "score": 9.0 - i}
                for q, t in zip(inp["qid"].tolist(), inp["query"].tolist())
                for i in range(3)]
        return add_ranks(ColFrame.from_dicts(rows))
    retr = GenericTransformer(retr_fn, "R", one_to_many=True,
                              key_columns=("qid", "query"))
    return [_annotator(calls) >> retr % 2]


def test_cache_prune_skips_warm_upstream_chain(tmp_path):
    calls = {"ann": 0}
    pipes = _cached_retr_pipes(calls)
    with ExecutionPlan(pipes, cache_dir=str(tmp_path)) as cold:
        outs1, s1 = cold.run(QUERIES)
        assert s1.nodes_pruned == 0 and calls["ann"] == 1
    # a fresh plan consults the now-warm manifest and defers the chain
    with ExecutionPlan(pipes, cache_dir=str(tmp_path)) as warm:
        prune = next(p for p in warm.pass_stats if p.name == "cache-prune")
        assert prune.nodes_marked_prunable == 1
        outs2, s2 = warm.run(QUERIES)
        assert s2.nodes_pruned == 1
        assert calls["ann"] == 1         # annotate never ran warm
        assert s2.cache_hits == len(QUERIES)
        assert_bit_identical(outs2, outs1)
        # sharded execution prunes too
        outs3, s3 = warm.run(QUERIES, n_shards=2, max_workers=2)
        assert s3.nodes_pruned == 1 and calls["ann"] == 1
        assert_bit_identical(outs3, outs1)
        # unseen queries miss the probe: the chain runs, results correct
        fresh = ColFrame({"qid": ["q9"], "query": ["omega"]})
        outs4, s4 = warm.run(fresh)
        assert calls["ann"] == 2 and s4.nodes_pruned == 0
    naive = _cached_retr_pipes({"ann": 0})[0](fresh)
    assert_bit_identical(outs4, [naive])


def test_cache_prune_requires_augment_only(tmp_path):
    """A query-REWRITING upstream stage must never be deferred — its
    output changes the cache keys."""
    calls = {"rw": 0}

    def rw_fn(inp):
        calls["rw"] += 1
        return inp.assign(query=np.array(
            [q + "!" for q in inp["query"].tolist()], dtype=object))
    rewrite = GenericTransformer(rw_fn, "rewrite")   # not augment_only

    def retr_fn(inp):
        rows = [{"qid": q, "query": t, "docno": f"d{len(t)}", "score": 1.0}
                for q, t in zip(inp["qid"].tolist(), inp["query"].tolist())]
        return add_ranks(ColFrame.from_dicts(rows))
    retr = GenericTransformer(retr_fn, "R2", one_to_many=True,
                              key_columns=("qid", "query"))
    pipes = [rewrite >> retr]
    with ExecutionPlan(pipes, cache_dir=str(tmp_path)) as cold:
        cold.run(QUERIES)
    with ExecutionPlan(pipes, cache_dir=str(tmp_path)) as warm:
        marked = sum(p.nodes_marked_prunable for p in warm.pass_stats)
        assert marked == 0
        _, s = warm.run(QUERIES)
        assert s.nodes_pruned == 0 and calls["rw"] == 2


# ---------------------------------------------------------------------------
# the optimize= knob
# ---------------------------------------------------------------------------

def test_optimize_none_is_the_naive_forest():
    A = make_retriever("A")
    B = Counting("B", lambda inp: add_ranks(
        inp.assign(score=inp["score"] * 2.0)))
    pipelines = [A, A >> B]
    plan = ExecutionPlan(pipelines, optimize="none")
    assert plan.n_nodes() == 3           # A, A, B — no sharing at all
    A.calls = B.calls = 0
    _, stats = plan.run(QUERIES)
    assert stats.nodes_executed == 3 and A.calls == 2
    assert stats.optimizer_passes == [] and stats.pass_times_s == {}
    assert ExecutionPlan(pipelines).n_nodes() == 2


def test_optimize_accepts_pass_subset():
    a = make_retriever("A")
    b = make_retriever("B", base=8.0)
    # cse without normalize: structural twins merge, commuted ones don't
    plan = ExecutionPlan([a + b, b + a], optimize=["cse"])
    assert plan.n_nodes() == 4           # a, b, a+b, b+a
    assert [p.name for p in plan.pass_stats] == ["cse"]
    outs, _ = plan.run(QUERIES)
    ref, _ = ExecutionPlan([a + b, b + a], optimize="none").run(QUERIES)
    assert_bit_identical(outs, ref)


def test_optimize_rejects_unknown_passes():
    a = make_retriever("A")
    with pytest.raises(ValueError, match="optimize must be"):
        ExecutionPlan([a], optimize="fastest")
    with pytest.raises(ValueError, match="unknown optimizer pass"):
        ExecutionPlan([a], optimize=["cse", "bogus"])
    assert OPTIMIZER_PASSES == ("normalize", "cse", "pushdown",
                                "cache-prune")


def test_plan_stats_carry_optimizer_accounting():
    a = make_retriever("A")
    b = make_retriever("B", base=8.0)
    _, stats = ExecutionPlan([a + b, b + a, a % 3]).run(QUERIES)
    assert stats.optimizer_passes == ["normalize", "cse", "pushdown"]
    assert set(stats.pass_times_s) == {"normalize", "cse", "pushdown"}
    assert all(t >= 0 for t in stats.pass_times_s.values())
    assert stats.nodes_eliminated > 0
    assert "eliminated=" in str(stats)


# ---------------------------------------------------------------------------
# explain() and the CLI round-trip
# ---------------------------------------------------------------------------

def test_explain_lists_every_node_and_pass():
    a = make_retriever("A")
    b = make_retriever("B", base=8.0)
    plan = ExecutionPlan([a + b, b + a])
    text = plan.explain()
    assert "passes=['normalize', 'cse', 'pushdown']" in text
    assert "shared, see above" in text   # the merged combine
    for node in plan.graph.nodes:
        if node.kind != "source":
            assert f"#{node.id} " in text
    fps = plan.node_fingerprints()
    assert any(fps[n.id][:12] in text for n in plan.graph.nodes
               if n.kind != "source")


def test_explain_roundtrips_through_cli(tmp_path, capsys):
    from repro.cli import main
    a = make_retriever("A")
    pipes = [a % 3, a % 2]
    with ExecutionPlan(pipes, cache_dir=str(tmp_path)) as plan:
        plan.run(QUERIES)
        expected = plan.explain()
        plan_id = plan.to_record()["plan_id"]
    assert main(["plan", "explain", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert out.strip() == expected.strip()
    # id-prefix selection
    assert main(["plan", "explain", str(tmp_path),
                 "--plan", plan_id[:8]]) == 0
    assert capsys.readouterr().out.strip() == expected.strip()
    # --json is parseable and carries the same structure
    assert main(["plan", "explain", str(tmp_path), "--json"]) == 0
    docs = json.loads(capsys.readouterr().out)
    assert docs[0]["plan_id"] == plan_id
    assert {n["label"] for n in docs[0]["nodes"]} == \
        {n.label for n in plan.graph.nodes if n.kind != "source"}
    # cache dirs recorded in the manifest resolve via repro cache ls
    assert main(["cache", "ls", str(tmp_path), "--json"]) == 0
    ls = json.loads(capsys.readouterr().out)
    assert ls["plans"][0]["plan_id"] == plan_id


def test_explain_cli_errors_without_plans(tmp_path, capsys):
    from repro.cli import main
    assert main(["plan", "explain", str(tmp_path)]) == 1
    assert "no recorded plan manifests" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# the hard invariant, property-tested (hypothesis or the fallback shim)
# ---------------------------------------------------------------------------

def _build_pipes(seqs, ops, cutoffs):
    retr = {c: CutRetriever(c, n=5 + ord(c) % 3, base=40.0 + ord(c))
            for c in "ABCD"}
    rerank = {c: GenericTransformer(
        lambda inp, _c=c: add_ranks(
            inp.assign(score=inp["score"] * (1.0 + ord(_c) / 100.0))),
        f"re{c}", rank_preserving=True) for c in "ABCD"}
    pipes = []
    rtyped = []                          # score-bearing: valid under +/**/^/%
    for seq in seqs:
        p = retr[seq[0]]
        for c in seq[1:]:
            p = p >> rerank[c]
        pipes.append(p)
        rtyped.append(p)
    for i, op in enumerate(ops):
        left = rtyped[i % len(rtyped)]
        right = rtyped[(i + 1) % len(rtyped)]
        if op == "+":
            pipes.append(left + right)
            pipes.append(right + left)   # commuted twin for normalize+cse
            rtyped.extend(pipes[-2:])
        elif op == "|":                  # drops scores: terminal-only
            pipes.append(left | right)
            pipes.append(right | left)
        elif op == "**":
            pipes.append(left ** right)
            rtyped.append(pipes[-1])
        elif op == "^":
            pipes.append(left ^ right)
            rtyped.append(pipes[-1])
    for i, k in enumerate(cutoffs):
        pipes.append(rtyped[i % len(rtyped)] % k)
    return pipes


@given(st.lists(st.lists(st.sampled_from("ABCD"), min_size=1, max_size=3),
                min_size=1, max_size=4),
       st.lists(st.sampled_from(["+", "|", "**", "^"]),
                min_size=0, max_size=2),
       st.lists(st.integers(min_value=1, max_value=7),
                min_size=0, max_size=2))
@settings(max_examples=15, deadline=None)
def test_property_optimized_bit_identical_sequential(seqs, ops, cutoffs):
    """Random pipeline algebras: optimize='all' == optimize='none',
    bit-for-bit per qid, under the sequential executor."""
    run_both(_build_pipes(seqs, ops, cutoffs))


@given(st.lists(st.lists(st.sampled_from("ABCD"), min_size=1, max_size=3),
                min_size=1, max_size=3),
       st.lists(st.sampled_from(["+", "|", "**", "^"]),
                min_size=0, max_size=2),
       st.lists(st.integers(min_value=1, max_value=7),
                min_size=0, max_size=2),
       st.integers(min_value=2, max_value=3))
@settings(max_examples=10, deadline=None)
def test_property_optimized_bit_identical_sharded(seqs, ops, cutoffs,
                                                  n_shards):
    """Same invariant under the sharded wavefront executor."""
    run_both(_build_pipes(seqs, ops, cutoffs),
             n_shards=n_shards, max_workers=3)


def test_metadata_flags_lift_onto_ir_nodes():
    r = CutRetriever("R", n=4)
    chain = r >> GenericTransformer(lambda inp: inp, "aug",
                                    augment_only=True) \
        >> GenericTransformer(
            lambda inp: add_ranks(inp.assign(score=inp["score"])),
            "rp", rank_preserving=True)
    plan = ExecutionPlan([chain], optimize="none")
    aug = next(n for n in plan.graph.nodes
               if n.kind == "stage" and "aug" in n.label)
    rp = next(n for n in plan.graph.nodes
              if n.kind == "stage" and "rp" in n.label)
    assert aug.augment_only and not aug.rank_preserving
    assert rp.rank_preserving and not rp.augment_only
    retr_node = next(n for n in plan.graph.nodes
                     if isinstance(n.stage, CutRetriever))
    assert retr_node.relation == "R" and retr_node.shardable


def test_cache_prune_never_defers_key_column_producers(tmp_path):
    """Regression: an augment-only stage that *produces* one of the
    downstream cache's key columns (a query attacher) must not be
    deferred — the probe frame would lack the key — and even when it
    is undeclared, ``serve_from_store`` must treat the missing column
    as a miss instead of crashing."""
    calls = {"att": 0}

    def att_fn(inp):
        calls["att"] += 1
        return inp.assign(query=np.array(
            ["terms " + q for q in inp["qid"].tolist()], dtype=object))
    attach = GenericTransformer(att_fn, "attach", augment_only=True,
                                value_columns=("query",))

    def retr_fn(inp):
        rows = [{"qid": q, "query": t, "docno": f"d{i}", "score": 5.0 - i}
                for q, t in zip(inp["qid"].tolist(), inp["query"].tolist())
                for i in range(2)]
        return add_ranks(ColFrame.from_dicts(rows))
    retr = GenericTransformer(retr_fn, "R3", one_to_many=True,
                              key_columns=("qid", "query"))
    topics = ColFrame({"qid": ["q1", "q2"]})   # no query column yet
    pipes = [attach >> retr]
    with ExecutionPlan(pipes, cache_dir=str(tmp_path)) as cold:
        outs1, _ = cold.run(topics)
    with ExecutionPlan(pipes, cache_dir=str(tmp_path)) as warm:
        assert sum(p.nodes_marked_prunable for p in warm.pass_stats) == 0
        outs2, s2 = warm.run(topics)       # must not raise
        assert s2.nodes_pruned == 0 and s2.cache_hits == len(topics)
        assert calls["att"] == 2
        assert_bit_identical(outs2, outs1)
    # the dynamic guard alone: probing with a key-less frame is a miss
    from repro.caching import RetrieverCache
    cache = RetrieverCache(None, retr)
    try:
        assert cache.serve_from_store(topics) is None
    finally:
        cache.close()


def test_cse_reruns_after_pushdown_merges_fused_twins():
    """Regression: `r(n=8) % 3` fused by pushdown becomes structurally
    identical to a literal `r(n=3)` — a post-pushdown CSE round must
    merge them so the shared subtree still executes once."""
    pipes = [CutRetriever("R", n=8) % 3 >> boost("pb"),
             CutRetriever("R", n=3) >> boost("pb")]
    plan = ExecutionPlan(pipes)
    assert plan.n_nodes() == 2           # one fused retriever + one boost
    _, stats = plan.run(QUERIES)
    assert stats.nodes_executed == 2
    assert stats.optimizer_passes == ["normalize", "cse", "pushdown",
                                      "normalize", "cse"]
    assert set(stats.pass_times_s) == {"normalize", "cse", "pushdown"}
    run_both([CutRetriever("R", n=8) % 3 >> boost("pb2"),
              CutRetriever("R", n=3) >> boost("pb2")])
