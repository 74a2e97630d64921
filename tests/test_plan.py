"""ExecutionPlan: one shared DAG for a pipeline set (core/plan.py).

Covers the cache-transparency invariant (plan execution == naive
per-pipeline execution) across every operator of the §2.1 algebra,
sharing through binary operator nodes (the §6 limitation the stage-list
trie cannot resolve), planner-inserted memoization with hit accounting,
and the §6 ablation regression (A; A»B; A»B»C executes B exactly once).
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (ColFrame, ExecutionPlan, GenericTransformer,
                        add_ranks, plan_size, run_with_trie)


class CountingStage(GenericTransformer):
    def __init__(self, name, fn=None, **kw):
        self.calls = 0

        def wrapped(inp, _fn=fn):
            self.calls += 1
            return _fn(inp) if _fn else inp
        super().__init__(wrapped, name, **kw)


def make_retriever(name, n=6, base=10.0):
    def fn(inp):
        rows = []
        for qid in inp["qid"].tolist():
            for i in range(n):
                rows.append({"qid": qid, "docno": f"{name}_d{i}",
                             "score": base - i})
        return add_ranks(ColFrame.from_dicts(rows))
    return CountingStage(name, fn)


def boost_fn(inp):
    return add_ranks(inp.assign(score=inp["score"] * 2.0))


def shift_fn(inp):
    return add_ranks(inp.assign(score=inp["score"] + 1.0))


QUERIES = ColFrame({"qid": ["q1", "q2", "q3"],
                    "query": ["alpha", "beta", "gamma"]})

SORT = ["qid", "docno"]


def assert_equivalent(pipelines, queries=QUERIES, run_kw=None, **plan_kw):
    naive = [p(queries) for p in pipelines]
    with ExecutionPlan(pipelines, **plan_kw) as plan:
        outs, stats = plan.run(queries, **(run_kw or {}))
    assert len(outs) == len(naive)
    for got, want in zip(outs, naive):
        g = got.sort_values(SORT)
        w = want.sort_values(SORT)
        cols = [c for c in ("qid", "docno", "score", "rank")
                if c in want.columns]
        assert g.equals(w, cols=cols, rtol=0, atol=0), \
            f"plan diverged from naive for {pipelines}"
    return stats


def test_plan_equivalence_all_operator_types():
    a = make_retriever("A")
    b = make_retriever("B", base=8.0)
    boost = CountingStage("boost", boost_fn)
    shift = CountingStage("shift", shift_fn)
    pipelines = [
        a,                              # bare stage
        a >> boost,                     # compose
        a % 3,                          # rank cutoff
        a + b,                          # linear combine
        a ** b,                         # feature union
        a | b,                          # set union
        a & a,                          # set intersection
        a ^ b,                          # concatenate
        a * 0.5,                        # scalar product
        (a + b) % 4 >> shift,           # nested mix
        ((a * 2.0) + (b >> boost)) % 5,
    ]
    stats = assert_equivalent(pipelines)
    assert stats.nodes_executed == stats.nodes_planned
    assert stats.nodes_total == sum(plan_size(p) for p in pipelines)
    assert stats.stage_invocations_saved > 0


def test_shared_retriever_under_binary_operators_runs_once():
    """The tentpole claim: a retriever shared under ``a + b`` and
    ``a ** c`` executes once — stages_of-based sharing cannot see it."""
    a = make_retriever("A")
    b = make_retriever("B", base=8.0)
    c = make_retriever("C", base=6.0)
    pipelines = [a + b, a ** c, a % 3, a]
    assert_equivalent(pipelines)   # re-runs naive first
    a.calls = b.calls = c.calls = 0
    outs, stats = ExecutionPlan(pipelines).run(QUERIES)
    assert a.calls == 1
    assert b.calls == 1
    assert c.calls == 1
    # nodes: A, B, C, A+B, A**C, A%3  — naive would run 3+3+2+1=9
    assert stats.nodes_planned == 6
    assert stats.nodes_executed == 6
    assert stats.nodes_total == 9
    assert stats.stage_invocations_saved == 3


def test_section6_ablation_executes_B_once():
    """Regression for the paper-§6 case ``A; A»B; A»B»C``."""
    A = make_retriever("A")
    B = CountingStage("B", boost_fn)
    C = CountingStage("C", shift_fn)
    pipelines = [A, A >> B, A >> B >> C]
    assert_equivalent(pipelines)
    A.calls = B.calls = C.calls = 0
    _, stats = ExecutionPlan(pipelines).run(QUERIES)
    assert A.calls == 1
    assert B.calls == 1          # LCP-only precomputation runs B twice
    assert C.calls == 1
    assert stats.nodes_executed == 3
    assert stats.nodes_total == 6
    # the thin wrapper reports identical accounting
    _, trie_stats = run_with_trie(pipelines, QUERIES)
    assert trie_stats.nodes_executed == 3
    assert trie_stats.nodes_total == 6


def test_same_stage_under_different_prefixes_not_merged():
    """Correctness guard: node identity is (prefix, stage), not stage."""
    a = make_retriever("A")
    b = make_retriever("B", base=8.0)
    boost = CountingStage("boost", boost_fn)
    pipelines = [a >> boost, b >> boost]
    stats = assert_equivalent(pipelines)
    assert stats.nodes_planned == 4      # a, b, and TWO boost nodes


def test_planner_inserted_cache_hits_on_second_run(tmp_path):
    def retr_fn(inp):
        rows = []
        for qid, query in zip(inp["qid"].tolist(), inp["query"].tolist()):
            for i in range(4):
                rows.append({"qid": qid, "query": query,
                             "docno": f"d{i}", "score": 9.0 - i})
        return add_ranks(ColFrame.from_dicts(rows))
    retr = CountingStage("R", retr_fn,
                         one_to_many=True, key_columns=("qid", "query"))
    boost = CountingStage("boost", boost_fn)   # no metadata -> uncached
    pipelines = [retr % 3, retr >> boost]
    naive = [p(QUERIES) for p in pipelines]
    retr.calls = 0

    with ExecutionPlan(pipelines, cache_dir=str(tmp_path)) as plan:
        cached = [n for n in plan.nodes.values() if n.cache is not None]
        assert len(cached) == 1          # only the retriever is cacheable
        outs1, stats1 = plan.run(QUERIES)
        assert stats1.cache_hits == 0
        assert stats1.cache_misses == len(QUERIES)
        outs2, stats2 = plan.run(QUERIES)
        assert stats2.cache_hits == len(QUERIES)
        assert stats2.cache_misses == 0
    assert retr.calls == 1               # second run served from cache
    for got, want in zip(outs2, naive):
        assert got.sort_values(SORT).equals(
            want.sort_values(SORT), cols=["qid", "docno", "score", "rank"])

    # a fresh plan against the same cache_dir is hot from the start
    with ExecutionPlan(pipelines, cache_dir=str(tmp_path)) as plan2:
        _, stats3 = plan2.run(QUERIES)
        assert stats3.cache_hits == len(QUERIES)
    assert retr.calls == 1


def test_cache_paths_stable_across_processes(tmp_path):
    """Node cache directories must not depend on the per-process hash
    salt — a fresh interpreter pointed at the same cache_dir must hit."""
    import os
    import subprocess
    import sys
    script = (
        "import sys\n"
        "from repro.core import ColFrame, ExecutionPlan, "
        "GenericTransformer, add_ranks\n"
        "def retr(inp):\n"
        "    rows = [{'qid': q, 'query': t, 'docno': f'd{i}', "
        "'score': 5.0 - i}\n"
        "            for q, t in zip(inp['qid'].tolist(), "
        "inp['query'].tolist()) for i in range(3)]\n"
        "    return add_ranks(ColFrame.from_dicts(rows))\n"
        "a = GenericTransformer(retr, 'A', one_to_many=True, "
        "key_columns=('qid', 'query'))\n"
        "Q = ColFrame({'qid': ['q1'], 'query': ['x']})\n"
        "with ExecutionPlan([a % 2], cache_dir=sys.argv[1]) as plan:\n"
        "    _, stats = plan.run(Q)\n"
        "    print(stats.cache_hits, stats.cache_misses)\n")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "PYTHONPATH": os.path.join(root, "src")}
    outs = []
    for _ in range(2):
        p = subprocess.run([sys.executable, "-c", script, str(tmp_path)],
                           capture_output=True, text=True, env=env,
                           timeout=120)
        assert p.returncode == 0, p.stderr[-1000:]
        outs.append(p.stdout.split())
    assert outs[0] == ["0", "1"]         # cold
    assert outs[1] == ["1", "0"]         # second process hits


def test_pluggable_memo_factory():
    seen = []

    def factory(stage, path):
        seen.append(repr(stage))
        return None

    a = make_retriever("A")
    ExecutionPlan([a % 3], memo_factory=factory)
    assert len(seen) == 2                # a and the RankCutoff node


def test_plan_stats_carry_node_times():
    a = make_retriever("A")
    _, stats = ExecutionPlan([a % 3, a % 5]).run(QUERIES)
    assert set(stats.node_times_s) == {repr(a), repr(
        (a % 3).stages[1]), repr((a % 5).stages[1])}
    assert all(t >= 0 for t in stats.node_times_s.values())
    assert stats.wall_time_s > 0


def test_plan_batching_matches_unbatched():
    a = make_retriever("A", n=4)
    boost = CountingStage("boost", boost_fn)
    pipelines = [a >> boost, a % 2]
    big = ColFrame({"qid": [f"q{i}" for i in range(9)],
                    "query": [f"t{i}" for i in range(9)]})
    full, _ = ExecutionPlan(pipelines).run(big)
    batched, _ = ExecutionPlan(pipelines).run(big, batch_size=2)
    for f, b in zip(full, batched):
        assert f.sort_values(SORT).equals(b.sort_values(SORT),
                                          cols=["qid", "docno", "score"])


def test_experiment_plan_mode(tmp_path):
    from repro.core import Experiment, PlanStats
    qrels = ColFrame({"qid": ["q1", "q2", "q3"],
                      "docno": ["A_d0", "A_d1", "B_d0"],
                      "label": [1, 1, 1]})
    a = make_retriever("A")
    b = make_retriever("B", base=8.0)
    systems = [a % 3, a + b, a ** b]
    naive = Experiment(systems, QUERIES, qrels, ["nDCG@10", "MAP"])
    planned = Experiment(systems, QUERIES, qrels, ["nDCG@10", "MAP"],
                         precompute_prefix=True, precompute_mode="plan",
                         cache_dir=str(tmp_path))
    for n1, n2 in zip(naive.names, planned.names):
        for m in ("nDCG@10", "MAP"):
            assert naive.means[n1][m] == pytest.approx(planned.means[n2][m])
    assert isinstance(planned.precompute, PlanStats)
    assert planned.precompute.nodes_executed < planned.precompute.nodes_total


def _random_pipes(seqs, ops):
    retrievers = {c: make_retriever(c, base=ord(c) * 1.0) for c in "ABCD"}
    rerank = {c: GenericTransformer(
        lambda inp, _c=c: add_ranks(
            inp.assign(score=inp["score"] + ord(_c))), f"re{c}")
        for c in "ABCD"}
    pipes = []
    for seq in seqs:
        p = retrievers[seq[0]]
        for c in seq[1:]:
            p = p >> rerank[c]
        pipes.append(p)
    for i, op in enumerate(ops):
        l, r = pipes[i % len(pipes)], pipes[(i + 1) % len(pipes)]
        if op == "+":
            pipes.append(l + r)
        elif op == "**":
            pipes.append(l ** r)
        elif op == "^":
            pipes.append(l ^ r)
        else:
            pipes.append(l % 3)
    return pipes


@given(st.lists(st.lists(st.sampled_from("ABCD"), min_size=1, max_size=4),
                min_size=2, max_size=5),
       st.lists(st.sampled_from(["+", "**", "^", ">>"]),
                min_size=0, max_size=3))
@settings(max_examples=25, deadline=None)
def test_property_plan_equals_naive(seqs, ops):
    """Random pipeline sets: chains of rerankers over shared retrievers,
    optionally merged pairwise by binary operators."""
    assert_equivalent(_random_pipes(seqs, ops))


# ---------------------------------------------------------------------------
# concurrent sharded executor
# ---------------------------------------------------------------------------

def test_sharded_run_matches_sequential_all_operator_types():
    a = make_retriever("A")
    b = make_retriever("B", base=8.0)
    boost = CountingStage("boost", boost_fn)
    shift = CountingStage("shift", shift_fn)
    pipelines = [
        a, a >> boost, a % 3, a + b, a ** b, a | b, a & a, a ^ b,
        a * 0.5, (a + b) % 4 >> shift, ((a * 2.0) + (b >> boost)) % 5,
    ]
    stats = assert_equivalent(pipelines,
                              run_kw=dict(n_shards=2, max_workers=4))
    assert stats.n_shards == 2
    assert stats.n_workers == 4
    assert stats.nodes_executed == stats.nodes_planned


@given(st.lists(st.lists(st.sampled_from("ABCD"), min_size=1, max_size=4),
                min_size=2, max_size=4),
       st.lists(st.sampled_from(["+", "**", "^", ">>"]),
                min_size=0, max_size=3),
       st.integers(min_value=2, max_value=4))
@settings(max_examples=15, deadline=None)
def test_property_sharded_plan_equals_naive(seqs, ops, n_shards):
    """The acceptance-criteria property: ``run(..., n_shards>1)`` equals
    sequential/naive execution on every operator shape."""
    assert_equivalent(_random_pipes(seqs, ops),
                      run_kw=dict(n_shards=n_shards, max_workers=4))


def test_sharded_stats_carry_shard_times_and_occupancy():
    a = make_retriever("A")
    b = make_retriever("B", base=8.0)
    _, stats = ExecutionPlan([a + b, a % 3]).run(
        QUERIES, n_shards=3, max_workers=2)
    assert stats.n_shards == len(stats.shard_times_s) == 3
    assert all(t >= 0 for t in stats.shard_times_s)
    assert 0.0 < stats.occupancy <= 1.0
    assert stats.wall_time_s > 0
    assert "shards=3" in str(stats)


def test_max_workers_alone_enables_branch_parallelism():
    """Branch-level concurrency without sharding: n_shards defaults to
    max_workers, and a single-row frame degenerates to one shard."""
    a = make_retriever("A")
    b = make_retriever("B", base=8.0)
    one = ColFrame({"qid": ["q1"], "query": ["alpha"]})
    naive = (a + b)(one)
    outs, stats = ExecutionPlan([a + b]).run(one, max_workers=4)
    assert stats.n_shards == 1 and stats.n_workers == 4
    assert outs[0].sort_values(SORT).equals(
        naive.sort_values(SORT), cols=["qid", "docno", "score", "rank"])


def test_sharding_keeps_qid_groups_whole():
    """R-type inputs with several rows per qid: shard cuts only at qid
    boundaries, so per-qid operators see whole groups."""
    rows = [{"qid": f"q{i}", "query": f"t{i}", "docno": f"d{j}",
             "score": float(10 - j)}
            for i in range(5) for j in range(4)]
    results = add_ranks(ColFrame.from_dicts(rows))
    cut = GenericTransformer(
        lambda inp: inp.mask(inp["rank"] < 2), "cut2")
    from repro.core import Identity
    pipelines = [Identity() >> cut]
    naive = [p(results) for p in pipelines]
    outs, stats = ExecutionPlan(pipelines).run(
        results, n_shards=3, max_workers=3)
    assert stats.n_shards == 3
    assert outs[0].sort_values(SORT).equals(
        naive[0].sort_values(SORT), cols=["qid", "docno", "score", "rank"])


def test_unshardable_stage_falls_back_to_one_shard():
    """A stage declaring shardable=False (cross-query statistics) must
    not see a partitioned frame — results would silently change."""
    a = make_retriever("A")
    norm = GenericTransformer(
        lambda inp: add_ranks(inp.assign(
            score=inp["score"] - float(inp["score"].max()))),
        "global_norm", shardable=False)
    pipelines = [a >> norm]
    naive = [p(QUERIES) for p in pipelines]
    outs, stats = ExecutionPlan(pipelines).run(
        QUERIES, n_shards=3, max_workers=3)
    assert stats.n_shards == 1
    assert outs[0].sort_values(SORT).equals(
        naive[0].sort_values(SORT), cols=["qid", "docno", "score", "rank"],
        rtol=0, atol=0)
    # batch_size partitions the frame exactly like sharding would;
    # an unshardable stage must see it whole there too
    outs_b, _ = ExecutionPlan(pipelines).run(QUERIES, batch_size=1)
    assert outs_b[0].sort_values(SORT).equals(
        naive[0].sort_values(SORT), cols=["qid", "docno", "score", "rank"],
        rtol=0, atol=0)


def test_hand_wrapped_cache_preserves_unshardable(tmp_path):
    """A CacheTransformer wrapping a shardable=False stage must delegate
    the declaration — otherwise sharding silently changes results."""
    from repro.caching import KeyValueCache
    norm = GenericTransformer(
        lambda inp: add_ranks(inp.assign(
            score=inp["score"] - float(inp["score"].max()))),
        "global_norm", shardable=False,
        key_columns=("qid", "docno"), value_columns=("score",))
    cached = KeyValueCache(str(tmp_path), norm,
                           key=("qid", "docno"), value=("score",))
    assert cached.shardable is False
    a = make_retriever("A")
    pipelines = [a >> cached]
    naive = [(a >> norm)(QUERIES)]
    outs, stats = ExecutionPlan(pipelines).run(
        QUERIES, n_shards=3, max_workers=3)
    assert stats.n_shards == 1
    assert outs[0].sort_values(SORT).equals(
        naive[0].sort_values(SORT), cols=["qid", "docno", "score"],
        rtol=0, atol=0)
    cached.close()


def test_experiment_forwards_shards_in_lcp_and_trie_modes():
    a = make_retriever("A")
    b = make_retriever("B", base=8.0)
    from repro.core import Experiment
    qrels = ColFrame({"qid": ["q1"], "docno": ["A_d0"], "label": [1]})
    base = Experiment([a % 3, a + b], QUERIES, qrels, ["MAP"])
    for mode in ("lcp", "trie"):
        res = Experiment([a % 3, a + b], QUERIES, qrels, ["MAP"],
                         precompute_prefix=True, precompute_mode=mode,
                         n_shards=3, max_workers=3)
        if mode == "trie":               # trie returns PlanStats directly
            assert res.precompute.n_shards == 3
        for n1, n2 in zip(base.names, res.names):
            assert base.means[n1]["MAP"] == pytest.approx(
                res.means[n2]["MAP"])


def test_non_contiguous_qids_fall_back_to_one_shard():
    frame = add_ranks(ColFrame({
        "qid": ["q1", "q2", "q1"], "query": ["a", "b", "a"],
        "docno": ["d1", "d1", "d2"], "score": [3.0, 2.0, 1.0]}))
    boost = CountingStage("boost", boost_fn)
    from repro.core import Identity
    outs, stats = ExecutionPlan([Identity() >> boost]).run(
        frame, n_shards=4, max_workers=2)
    assert stats.n_shards == 1          # cannot cut without splitting q1
    naive = boost(frame)
    assert outs[0].sort_values(SORT).equals(
        naive.sort_values(SORT), cols=["qid", "docno", "score", "rank"])


def test_sharded_run_with_cache_dir_hits_on_second_run(tmp_path):
    def retr_fn(inp):
        rows = []
        for qid, query in zip(inp["qid"].tolist(), inp["query"].tolist()):
            for i in range(4):
                rows.append({"qid": qid, "query": query,
                             "docno": f"d{i}", "score": 9.0 - i})
        return add_ranks(ColFrame.from_dicts(rows))
    retr = CountingStage("R", retr_fn,
                         one_to_many=True, key_columns=("qid", "query"))
    pipelines = [retr % 3, retr % 2]
    with ExecutionPlan(pipelines, cache_dir=str(tmp_path),
                       cache_backend="pickle") as plan:
        _, s1 = plan.run(QUERIES, n_shards=3, max_workers=3)
        assert s1.cache_misses == len(QUERIES)
        outs, s2 = plan.run(QUERIES, n_shards=3, max_workers=3)
        assert s2.cache_hits == len(QUERIES)
        assert s2.cache_misses == 0
    naive = [p(QUERIES) for p in pipelines]
    for got, want in zip(outs, naive):
        assert got.sort_values(SORT).equals(
            want.sort_values(SORT), cols=["qid", "docno", "score", "rank"])


def test_plan_cache_backend_memory_without_cache_dir():
    """cache_backend="memory" alone enables in-process memoization."""
    def retr_fn(inp):
        rows = [{"qid": q, "query": t, "docno": "d0", "score": 1.0}
                for q, t in zip(inp["qid"].tolist(), inp["query"].tolist())]
        return add_ranks(ColFrame.from_dicts(rows))
    retr = CountingStage("R", retr_fn,
                         one_to_many=True, key_columns=("qid", "query"))
    with ExecutionPlan([retr % 1], cache_backend="memory") as plan:
        cached = [n for n in plan.nodes.values() if n.cache is not None]
        assert len(cached) == 1
        assert cached[0].cache.backend.name == "memory"
        plan.run(QUERIES)
        plan.run(QUERIES)
    assert retr.calls == 1              # second run served from memory


_CONCURRENT_PLAN_SCRIPT = """
import sys
from repro.core import ColFrame, ExecutionPlan, GenericTransformer, add_ranks

cache_dir, backend, log_path = sys.argv[1:4]

def retr(inp):
    with open(log_path, "a") as f:            # O_APPEND: atomic small writes
        for q in inp["qid"].tolist():
            f.write(q + "\\n")
    rows = [{"qid": q, "query": t, "docno": f"d{i}", "score": 5.0 - i}
            for q, t in zip(inp["qid"].tolist(), inp["query"].tolist())
            for i in range(3)]
    return add_ranks(ColFrame.from_dicts(rows))

a = GenericTransformer(retr, "A", one_to_many=True,
                       key_columns=("qid", "query"))
Q = ColFrame({"qid": [f"q{i}" for i in range(6)],
              "query": [f"t{i}" for i in range(6)]})
with ExecutionPlan([a % 2], cache_dir=cache_dir,
                   cache_backend=backend) as plan:
    outs, stats = plan.run(Q, n_shards=3, max_workers=3)
assert len(outs[0]) == 12, len(outs[0])
"""


@pytest.mark.slow
@pytest.mark.parametrize("backend", ["pickle", "dbm", "sqlite"])
def test_concurrent_processes_share_plan_cache_dir(tmp_path, backend):
    """Two concurrent interpreters run the same sharded plan against one
    cache_dir through each backend: the file-locked miss path computes
    every entry exactly once across both processes *and* all shards."""
    import os
    import subprocess
    import sys
    log = tmp_path / "computed.log"
    log.touch()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "PYTHONPATH": os.path.join(root, "src")}
    procs = [subprocess.Popen(
        [sys.executable, "-c", _CONCURRENT_PLAN_SCRIPT,
         str(tmp_path / "cache"), backend, str(log)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env)
        for _ in range(2)]
    for p in procs:
        _, err = p.communicate(timeout=120)
        assert p.returncode == 0, err.decode()[-2000:]
    computed = log.read_text().split()
    assert sorted(computed) == sorted(f"q{i}" for i in range(6)), \
        f"{backend}: entries computed more than once: {computed}"



# ---------------------------------------------------------------------------
# shared stores: one cache per row-level stage across a grid's depths
# ---------------------------------------------------------------------------

def _assert_bitwise(got_frames, want_frames):
    cols = ["qid", "query", "docno", "text", "score", "rank"]
    for got, want in zip(got_frames, want_frames):
        assert got.sort_values(SORT).equals(want.sort_values(SORT),
                                            cols=cols, rtol=0, atol=0)


def _stage_caches(plan, stage):
    return [n.cache for n in plan.graph.nodes
            if n.kind == "stage" and n.stage is stage]


@pytest.mark.parametrize("run_kw", [{}, {"n_shards": 3, "max_workers": 8}],
                         ids=["sequential", "sharded"])
def test_pointwise_scorer_shares_one_store_across_depths(tmp_path, run_kw):
    """``retr % k >> loader >> scorer`` for k in 2, 5, 10: the scorer's
    and the loader's nodes share one cache each, so every (query,
    docno) is scored once, and results are those of an uncached plan —
    also with the depths' nodes racing on the shared stores."""
    import sys
    from _depth_grid import DEPTHS, QUERIES, PairScorer, grid
    want, _ = ExecutionPlan(grid(PairScorer())).run(QUERIES)
    scorer = PairScorer()
    pipelines = grid(scorer)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)          # interleave the racing threads
    try:
        with ExecutionPlan(pipelines, cache_dir=str(tmp_path)) as plan:
            caches = _stage_caches(plan, scorer)
            assert len(caches) == len(DEPTHS)
            assert all(c is caches[0] for c in caches)
            loader = pipelines[0].stages[1]
            assert len({id(c) for c in _stage_caches(plan, loader)}) == 1
            outs, stats = plan.run(QUERIES, **run_kw)
            computed = list(scorer.calls)
            _, again = plan.run(QUERIES, **run_kw)
    finally:
        sys.setswitchinterval(interval)
    n_q, top = len(QUERIES), max(DEPTHS)
    assert len(scorer.pairs) == len(set(scorer.pairs)) == n_q * top
    _assert_bitwise(outs, want)
    # rows looked up by the loader and by the scorer: sum of the depths
    # per query, of which each distinct passage misses once; the one
    # retriever node the depths share misses once per query
    rows = n_q * sum(DEPTHS)
    assert stats.cache_misses == 2 * n_q * top + n_q
    assert stats.cache_hits == 2 * (rows - n_q * top)
    # in order, each depth scores only the passages the shallower depths
    # had not; racing, the calls split the same pairs among them
    if not run_kw:
        assert computed == [n_q * (k - j) for j, k in
                            zip((0,) + DEPTHS[:-1], DEPTHS)]
    assert sum(computed) == n_q * top
    assert again.cache_misses == 0
    assert again.cache_hits == 2 * rows + n_q
    assert len(scorer.pairs) == n_q * top


_SHARED_STORE_SCRIPT = """
import sys
sys.path.insert(0, sys.argv[2])
from _depth_grid import QUERIES, PairScorer, grid
from repro.core import ExecutionPlan
scorer = PairScorer()
with ExecutionPlan(grid(scorer), cache_dir=sys.argv[1],
                   on_stale="error") as plan:
    outs, stats = plan.run(QUERIES)
print(stats.cache_hits, stats.cache_misses, len(scorer.pairs))
for out in outs:
    print(out.sort_values(["qid", "docno"])["score"].tobytes().hex())
"""


def test_shared_store_is_warm_in_a_fresh_process(tmp_path):
    """A second interpreter planning the same grid over the same
    directory opens every shared store cleanly (``on_stale="error"``)
    and hits everything, with the first process's scores."""
    import os
    import subprocess
    import sys
    from _depth_grid import DEPTHS, QUERIES
    from repro.cli import main
    tests = os.path.dirname(os.path.abspath(__file__))
    env = {**os.environ,
           "PYTHONPATH": os.path.join(os.path.dirname(tests), "src")}
    runs = []
    for _ in range(2):
        p = subprocess.run([sys.executable, "-c", _SHARED_STORE_SCRIPT,
                            str(tmp_path), tests],
                           capture_output=True, text=True, env=env,
                           timeout=120)
        assert p.returncode == 0, p.stderr[-2000:]
        runs.append(p.stdout.split())
    n_q, rows = len(QUERIES), len(QUERIES) * sum(DEPTHS)
    assert runs[0][2] == str(n_q * max(DEPTHS))  # pairs scored, cold
    assert runs[1][:3] == [str(2 * rows + n_q), "0", "0"]
    assert runs[1][3:] == runs[0][3:]
    assert main(["cache", "verify", str(tmp_path)]) == 0


def test_scorer_after_another_text_loader_keeps_its_own_store(tmp_path):
    """The same scorer after two text loaders that give one docno other
    texts must not share a store: the text is part of its input."""
    from _depth_grid import QUERIES, PairScorer, Retriever, loader
    retr = Retriever()
    pipelines = [retr >> loader("v1") >> PairScorer(),
                 retr >> loader("v2") >> PairScorer()]
    want = [p(QUERIES) for p in pipelines]
    scorer = PairScorer()
    pipelines = [retr >> loader("v1") >> scorer,
                 retr >> loader("v2") >> scorer]
    with ExecutionPlan(pipelines, cache_dir=str(tmp_path)) as plan:
        a, b = _stage_caches(plan, scorer)
        assert a is not b and a.path != b.path
        outs, _ = plan.run(QUERIES)
    _assert_bitwise(outs, want)
    assert not outs[0].equals(outs[1], cols=["score"])
    assert len(scorer.pairs) == 2 * len(set(scorer.pairs))


def _retriever_after_rewriters():
    """One-to-many: a retriever under two query rewriters."""
    from _depth_grid import Retriever
    from repro.ir import QueryExpander
    retr = Retriever()
    return retr, [QueryExpander(2) >> retr, QueryExpander(3) >> retr]


def _one_to_many_by_docno_at_depths():
    """One-to-many though keyed by docno: each passage in two parts."""
    from _depth_grid import Retriever

    def fn(inp):
        out = inp.take(np.repeat(np.arange(len(inp)), 2))
        return out.assign(part=np.tile([0.0, 1.0], len(inp)))
    stage = GenericTransformer(fn, "split", key_columns=("docno",),
                               value_columns=("part",), one_to_many=True)
    stage.input_columns = frozenset({"qid", "docno"})
    retr = Retriever()
    return stage, [retr % 2 >> stage, retr % 5 >> stage]


def _qid_keyed_at_depths():
    """Row-wise, but keyed by qid: each row gets its query's length."""
    from _depth_grid import Retriever

    def fn(inp):
        return inp.assign(qlen=np.asarray(
            [len(q) for q in inp["query"].tolist()], dtype=np.float64))
    stage = GenericTransformer(fn, "qlen", key_columns=("qid",),
                               value_columns=("qlen",))
    stage.input_columns = frozenset({"qid", "query"})
    retr = Retriever()
    return stage, [retr % 2 >> stage, retr % 5 >> stage]


def _undeclared_inputs_at_depths():
    """Keyed by docno, but declares no input columns."""
    from _depth_grid import Retriever

    def fn(inp):
        return inp.assign(tag=np.asarray(
            [d.upper() for d in inp["docno"].tolist()], dtype=object))
    stage = GenericTransformer(fn, "tag", key_columns=("docno",),
                               value_columns=("tag",))
    retr = Retriever()
    return stage, [retr % 2 >> stage, retr % 5 >> stage]


@pytest.mark.parametrize("make", [
    _retriever_after_rewriters, _one_to_many_by_docno_at_depths,
    _qid_keyed_at_depths, _undeclared_inputs_at_depths,
], ids=["retriever", "one_to_many_by_docno", "qid_keyed",
        "undeclared_inputs"])
def test_non_row_level_caches_keep_per_position_stores(tmp_path, make):
    """Only row-level stages share: a retriever, any one-to-many stage,
    a qid-keyed stage and a stage that does not declare its inputs each
    keep one store per plan position, and give the results of an
    uncached run."""
    from _depth_grid import QUERIES
    stage, pipelines = make()
    want = [p(QUERIES) for p in pipelines]
    with ExecutionPlan(pipelines, cache_dir=str(tmp_path)) as plan:
        a, b = _stage_caches(plan, stage)
        assert a is not None and b is not None
        assert a is not b and a.path != b.path
        outs, _ = plan.run(QUERIES)
    for got, w in zip(outs, want):
        assert got.sort_values(SORT).equals(
            w.sort_values(SORT), cols=list(w.columns), rtol=0, atol=0)


# ---------------------------------------------------------------------------
# plans depend on structure alone: no run history, no timing
# ---------------------------------------------------------------------------

def test_fingerprints_invariant_under_operand_swap():
    """``a + b`` and ``b + a`` get one fingerprint (and so one cache
    provenance), with no optimizer pass involved."""
    p1 = ExecutionPlan([make_retriever("A") + make_retriever("B", base=8.0)],
                       optimize="none")
    p2 = ExecutionPlan([make_retriever("B", base=8.0) + make_retriever("A")],
                       optimize="none")
    fp1 = p1.node_fingerprints()[p1.graph.terminals[0].id]
    fp2 = p2.node_fingerprints()[p2.graph.terminals[0].id]
    assert fp1 == fp2


def _timed_grid(delay):
    """The depth grid with an uncached last stage that sleeps
    ``delay["s"]`` seconds per call, so runs differ in wall time."""
    import time
    from _depth_grid import PairScorer, grid

    def fn(inp):
        time.sleep(delay["s"])
        return inp
    pause = GenericTransformer(fn, "pause")
    return [p >> pause for p in grid(PairScorer())]


def _plan_view(plan):
    return (plan.explain(), plan.to_record()["nodes"],
            [(n.label, n.prefetch) for n in plan.graph.nodes])


@pytest.mark.parametrize("n_runs", [0, 1, 5])
def test_explain_is_the_same_whatever_ran_before(tmp_path, n_runs):
    """Planning the same pipelines over one cache directory gives the
    same plan with no history, after one run, and after five runs of
    differing wall times; every run is still logged.  No stage here can
    be deferred behind a warm store, so only history could change it."""
    import json
    from _depth_grid import QUERIES
    delay = {"s": 0.0}
    with ExecutionPlan(_timed_grid(delay), cache_dir=str(tmp_path)) as plan:
        first = _plan_view(plan)
        manifest = plan._plan_manifest_path
        for i in range(n_runs):
            delay["s"] = 0.004 * i
            plan.run(QUERIES)
    with ExecutionPlan(_timed_grid(delay), cache_dir=str(tmp_path)) as plan:
        assert _plan_view(plan) == first
        assert plan._plan_manifest_path == manifest
    with open(manifest) as f:
        record = json.load(f)
    assert len(record["runs"]) == n_runs
    assert "costs" not in record and "tuning" not in record


@pytest.mark.parametrize("backend,prefetch,stamped", [
    ("memory", True, False), ("pickle", True, True), ("dbm", True, True),
    ("sqlite", True, True), ("tiered:sqlite", True, True),
    ("mmap:sqlite", True, False), ("sqlite", False, False)])
def test_prefetch_stamps_follow_the_cache(tmp_path, monkeypatch, backend,
                                          prefetch, stamped):
    """A planner cache is prefetched iff the plan asks for it and the
    cache declares itself ``prefetchable``: memory-speed stores (the
    memory LRU, the mmap snapshot) decline, every other store accepts,
    whatever its measured speed on this host."""
    from _depth_grid import PairScorer, grid
    monkeypatch.delenv("REPRO_PREFETCH", raising=False)
    with ExecutionPlan(grid(PairScorer()), cache_dir=str(tmp_path),
                       cache_backend=backend, prefetch=prefetch) as plan:
        cached = [n for n in plan.graph.nodes if n.cache is not None]
        assert cached
        for n in cached:
            assert n.cache.prefetchable is (backend.split(":")[0]
                                            not in ("memory", "mmap"))
            assert n.prefetch is (prefetch and n.cache.prefetchable)
            assert n.prefetch is stamped


@pytest.mark.parametrize("backend", ["memory", "pickle", "dbm", "sqlite"])
def test_depth_grid_caches_on_every_store(tmp_path, backend):
    """On each store the depth grid caches its retriever, loader and
    scorer and never a cutoff, the loader and the scorer keep one store
    each across the depths, and results equal an uncached plan's bit for
    bit, cold and warm."""
    from _depth_grid import DEPTHS, QUERIES, PairScorer, Retriever, grid
    from repro.core import RankCutoff
    from repro.ir import TextLoader
    want, _ = ExecutionPlan(grid(PairScorer())).run(QUERIES)
    scorer = PairScorer()
    with ExecutionPlan(grid(scorer), cache_dir=str(tmp_path),
                       cache_backend=backend) as plan:
        nodes = [n for n in plan.graph.nodes if n.kind == "stage"]
        by_kind = {}
        for n in nodes:
            by_kind.setdefault(type(n.stage), []).append(n)
        assert len(by_kind[Retriever]) == 1
        assert by_kind[Retriever][0].cache is not None
        assert len(by_kind[RankCutoff]) == len(DEPTHS)
        assert all(n.cache is None for n in by_kind[RankCutoff])
        for kind in (TextLoader, PairScorer):
            assert len(by_kind[kind]) == len(DEPTHS)
            assert len({id(n.cache) for n in by_kind[kind]}) == 1
            assert by_kind[kind][0].cache is not None
        cold, _ = plan.run(QUERIES)
        warm, stats = plan.run(QUERIES)
    _assert_bitwise(cold, want)
    _assert_bitwise(warm, want)
    assert stats.cache_misses == 0
    assert len(scorer.pairs) == len(QUERIES) * max(DEPTHS)


def test_building_a_plan_opens_only_its_own_stores(tmp_path, monkeypatch):
    """Planning with a ``cache_dir`` opens one store per planned cache,
    each under that directory, and writes nowhere else: no throwaway
    store is opened to time the backend."""
    import os
    import tempfile
    from _depth_grid import PairScorer, grid
    from repro.caching import backends, kv, retriever
    opened = []

    def open_backend(spec, path, *a, **kw):
        opened.append(os.path.abspath(path))
        return real(spec, path, *a, **kw)
    real = backends.open_backend
    for mod in (backends, kv, retriever):
        monkeypatch.setattr(mod, "open_backend", open_backend)
    made = []
    real_mkdtemp = tempfile.mkdtemp
    monkeypatch.setattr(tempfile, "mkdtemp",
                        lambda *a, **kw: made.append(a or kw)
                        or real_mkdtemp(*a, **kw))
    root = tmp_path / "cache"
    with ExecutionPlan(grid(PairScorer()), cache_dir=str(root)) as plan:
        dirs = {n["dir"] for n in plan.to_record()["nodes"] if n["dir"]}
        assert len(dirs) == 3          # retriever, loader, scorer
        assert set(os.listdir(root)) == dirs | {"plans"}
    assert sorted(opened) == sorted(str(root / d) for d in dirs)
    assert made == []
