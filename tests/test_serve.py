"""Serve-layer tests: PipelineService, the streaming executor, the
bounded latency reservoir, per-call cache accounting, and the
single-key read-through fast path.

The acceptance invariants of the online mode:

* scores served through ``PipelineService`` are bit-identical per qid
  to an offline ``ExecutionPlan.run`` of the same pipeline, including
  under >=4 concurrent client threads;
* N in-flight requests sharing a query execute the retrieval stage
  once per unique query (coalescing), verified via node-execution
  counts;
* micro-batches flush on ``max_batch`` (size) and on ``max_wait_ms``
  (timeout);
* a warm cache directory serves a repeat stream without a single miss.
"""
import threading
import time

import numpy as np
import pytest

from repro.caching.kv import KeyValueCache
from repro.caching.retriever import RetrieverCache
from repro.core import ColFrame, ExecutionPlan, GenericTransformer
from repro.core.executor import Reservoir
from repro.core.pipeline import add_ranks
from repro.ir import InvertedIndex, TextLoader, msmarco_like
from repro.serve import PipelineService, build_scenario, run_closed_loop

CORPUS = msmarco_like(1, scale=0.02)
INDEX = InvertedIndex.build(CORPUS.get_corpus_iter())
TOPICS = CORPUS.get_topics()


def np_reranker():
    """Deterministic numpy pointwise reranker: row-local, bit-exact
    under any batching — lets equivalence tests assert exact equality
    (MonoScorer-based serving is covered by benchmarks/system tests)."""
    def fn(frame):
        if len(frame) == 0:
            return frame
        scores = np.array(
            [((hash((q, d)) % 100003) / 1000.0)
             for q, d in zip(frame["query"].tolist(),
                             frame["docno"].tolist())], dtype=np.float64)
        return add_ranks(frame.assign(score=scores))
    return GenericTransformer(
        fn, "np_rerank", key_columns=("query", "docno"),
        value_columns=("score",))


def two_stage():
    return (INDEX.bm25(num_results=50) % 10
            >> TextLoader(CORPUS.text_map()) >> np_reranker())


def per_qid(frame):
    return {str(k[0]): frame.take(idx)
            for k, idx in frame.group_indices(["qid"]).items()}


# ---------------------------------------------------------------------------
# equivalence: served == offline, concurrent clients
# ---------------------------------------------------------------------------

def test_served_scores_bit_identical_to_offline_concurrent():
    pipeline = two_stage()
    offline, _ = ExecutionPlan([pipeline]).run(TOPICS)
    ref = per_qid(offline[0])

    svc = PipelineService(pipeline, max_batch=8, max_wait_ms=20,
                          max_workers=4)
    results = {}
    lock = threading.Lock()
    qids = TOPICS["qid"].tolist()
    queries = TOPICS["query"].tolist()

    def client(cid):
        # overlapping slices: several clients serve the same queries
        for i in range(cid, len(qids), 2):
            out = svc.submit(qids[i], queries[i]).result(60)
            with lock:
                results[str(qids[i])] = out

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    svc.close()

    assert set(results) == set(ref)
    for qid, out in results.items():
        exp = ref[qid].sort_values(["docno"])
        got = out.sort_values(["docno"])
        assert got["docno"].tolist() == exp["docno"].tolist()
        assert np.array_equal(
            np.asarray(got["score"], dtype=np.float64),
            np.asarray(exp["score"], dtype=np.float64))      # bit-identical
        assert np.array_equal(got["rank"], exp["rank"])


def test_search_matches_offline_whole_frame():
    pipeline = two_stage()
    offline, _ = ExecutionPlan([pipeline]).run(TOPICS)
    with PipelineService(pipeline, max_wait_ms=0) as svc:
        served = svc.search(TOPICS)
    exp, got = per_qid(offline[0]), per_qid(served)
    assert set(exp) == set(got)
    for qid in exp:
        a = exp[qid].sort_values(["docno"])
        b = got[qid].sort_values(["docno"])
        assert np.array_equal(
            np.asarray(a["score"], dtype=np.float64),
            np.asarray(b["score"], dtype=np.float64))


# ---------------------------------------------------------------------------
# coalescing: a shared query retrieves once
# ---------------------------------------------------------------------------

def test_shared_query_executes_retrieval_once():
    calls = {"n": 0}
    inner = INDEX.bm25(num_results=20)

    def counted(frame):
        calls["n"] += len(frame)
        return inner(frame)

    retriever = GenericTransformer(counted, "counted_bm25",
                                   key_columns=("qid", "query"),
                                   one_to_many=True)
    svc = PipelineService(retriever, max_batch=6, max_wait_ms=2000,
                          max_workers=2)
    # 6 concurrent submissions of the SAME query fill one batch window
    futs = [svc.submit("q0", "shared query text") for _ in range(6)]
    outs = [f.result(60) for f in futs]
    stats = svc.plan_stats()
    svc.close()

    assert all(len(o) == len(outs[0]) for o in outs)
    assert calls["n"] == 1               # one unique row executed
    # node-execution counts agree: one micro-batch, one execution
    assert stats.node_exec_counts == \
        {"GenericTransformer('counted_bm25',)": 1}
    assert stats.online["rows_in"] == 6
    assert stats.online["rows_executed"] == 1


def test_conflicting_qid_rows_do_not_coalesce():
    scorer = np_reranker()
    svc = PipelineService(scorer, max_batch=4, max_wait_ms=500,
                          max_workers=2)
    rowa = {"qid": "q0", "query": "qq", "docno": "d1", "text": "ta",
            "score": 0.0, "rank": 0}
    rowb = {"qid": "q0", "query": "qq", "docno": "d2", "text": "tb",
            "score": 0.0, "rank": 0}
    fa = svc._exec.submit([rowa])
    fb = svc._exec.submit([rowb])
    a, b = fa.result(60), fb.result(60)
    svc.close()
    # same qid, different rows: each request keeps ITS row's result
    assert a["docno"].tolist() == ["d1"]
    assert b["docno"].tolist() == ["d2"]


# ---------------------------------------------------------------------------
# micro-batch flush triggers
# ---------------------------------------------------------------------------

def test_flush_trigger_size():
    svc = PipelineService(two_stage(), max_batch=4, max_wait_ms=30_000,
                          max_workers=2)
    qids = TOPICS["qid"].tolist()[:4]
    queries = TOPICS["query"].tolist()[:4]
    t0 = time.perf_counter()
    futs = [svc.submit(q, t) for q, t in zip(qids, queries)]
    for f in futs:
        f.result(60)                     # resolves long before the 30s window
    dt = time.perf_counter() - t0
    s = svc.online_stats
    assert s.flush_size >= 1 and s.flush_timeout == 0
    assert dt < 10
    svc.close()


def test_flush_trigger_timeout():
    svc = PipelineService(two_stage(), max_batch=100, max_wait_ms=50,
                          max_workers=2)
    futs = [svc.submit(TOPICS["qid"][i], TOPICS["query"][i])
            for i in range(2)]
    for f in futs:
        f.result(60)
    s = svc.online_stats
    assert s.flush_timeout >= 1 and s.flush_size == 0
    svc.close()


def test_explicit_flush_dispatches_immediately():
    svc = PipelineService(two_stage(), max_batch=100, max_wait_ms=30_000,
                          max_workers=2)
    fut = svc.submit(TOPICS["qid"][0], TOPICS["query"][0])
    svc.flush()
    fut.result(60)
    assert svc.online_stats.flush_forced >= 1
    svc.close()


# ---------------------------------------------------------------------------
# cold vs warm cache
# ---------------------------------------------------------------------------

def test_cold_then_warm_hit_rates(tmp_path):
    pipeline = two_stage()
    qids = TOPICS["qid"].tolist()[:8]
    queries = TOPICS["query"].tolist()[:8]

    svc1 = PipelineService(pipeline, cache_dir=str(tmp_path),
                           max_batch=4, max_wait_ms=5)
    r1 = [svc1.submit(q, t).result(60) for q, t in zip(qids, queries)]
    cold = svc1.stats
    assert cold.cache_misses > 0
    svc1.close()

    # a NEW service over the same directory: manifests re-validated at
    # start, stores adopted warm — the repeat stream never misses
    svc2 = PipelineService(pipeline, cache_dir=str(tmp_path),
                           max_batch=4, max_wait_ms=5)
    r2 = [svc2.submit(q, t).result(60) for q, t in zip(qids, queries)]
    warm = svc2.stats
    assert warm.cache_hits > 0 and warm.cache_misses == 0
    assert warm.summary()["hit_rate"] == 1.0
    svc2.close()

    for a, b in zip(r1, r2):
        sa = a.sort_values(["docno"])
        sb = b.sort_values(["docno"])
        assert np.array_equal(np.asarray(sa["score"], dtype=np.float64),
                              np.asarray(sb["score"], dtype=np.float64))


# ---------------------------------------------------------------------------
# satellite: bounded latency reservoir + thread-safe stats
# ---------------------------------------------------------------------------

def test_reservoir_bounded_and_stable():
    r = Reservoir(capacity=128, seed=0)
    for i in range(10_000):
        r.add(float(i % 100))
    assert len(r) == 128                 # memory bounded
    assert r.count == 10_000
    # percentiles of a uniform 0..99 stream stay near truth
    assert 30 <= r.percentile(50) <= 70
    assert r.percentile(99) >= 80


def test_service_stats_thread_safe():
    from repro.serve import ServiceStats
    stats = ServiceStats(reservoir_capacity=64)

    def hammer():
        for _ in range(500):
            stats.record_batch(n_requests=1, latencies_ms=[1.0])
            stats.add_cache_counts(2, 1)

    threads = [threading.Thread(target=hammer) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert stats.requests == 4000
    assert stats.batches == 4000
    assert stats.cache_hits == 8000 and stats.cache_misses == 4000
    assert len(stats.latencies) == 64    # bounded despite 4000 samples


# ---------------------------------------------------------------------------
# satellite: per-call hit/miss counts (not shared-counter deltas)
# ---------------------------------------------------------------------------

def test_per_call_counts_under_concurrency():
    seen = []

    def echo(frame):
        return frame.assign(out=np.asarray(
            [s.upper() for s in frame["text"].tolist()], dtype=object))

    t = GenericTransformer(echo, "upper", key_columns=("text",),
                           value_columns=("out",))
    cache = KeyValueCache(None, t, key="text", value="out")
    frames = [ColFrame({"text": [f"w{i}-{j}" for j in range(5)]})
              for i in range(4)]
    # warm one frame so hits and misses interleave across threads
    cache(frames[0])
    lock = threading.Lock()

    def call(i):
        out, hits, misses = cache.call_with_counts(frames[i])
        with lock:
            seen.append((i, hits, misses))

    threads = [threading.Thread(target=call, args=(i,)) for i in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    by_frame = dict((i, (h, m)) for i, h, m in seen)
    assert by_frame[0] == (5, 0)         # fully warm frame: all hits
    for i in (1, 2, 3):
        h, m = by_frame[i]
        assert h + m == 5 and m == 5     # cold frames: all misses
    cache.close()


# ---------------------------------------------------------------------------
# single-key read-through fast path
# ---------------------------------------------------------------------------

def test_kv_single_key_fast_path():
    def shout(frame):
        return frame.assign(out=np.asarray(
            [s + "!" for s in frame["text"].tolist()], dtype=object))

    t = GenericTransformer(shout, "shout", key_columns=("text",),
                           value_columns=("out",))
    cache = KeyValueCache(None, t, key="text", value="out")
    one = ColFrame({"text": ["hello"]})
    first = cache(one)
    assert first["out"].tolist() == ["hello!"]
    assert (cache.stats.hits, cache.stats.misses) == (0, 1)
    second = cache(one)                  # exercises _transform_single
    assert second["out"].tolist() == ["hello!"]
    assert (cache.stats.hits, cache.stats.misses) == (1, 1)
    # counts accumulate per thread until popped, then reset
    assert cache.pop_call_counts() == (1, 1)
    assert cache.pop_call_counts() == (0, 0)
    _, h, m = cache.call_with_counts(one)
    assert (h, m) == (1, 0)
    cache.close()


def test_retriever_single_key_fast_path():
    bm25 = INDEX.bm25(num_results=10)
    cache = RetrieverCache(None, bm25)
    one = ColFrame({"qid": ["q1"], "query": [TOPICS["query"][0]]})
    cold = cache(one)
    warm = cache(one)                    # exercises _transform_single
    assert cache.stats.hits == 1 and cache.stats.misses == 1
    a = cold.sort_values(["docno"])
    b = warm.sort_values(["docno"])
    assert a["docno"].tolist() == b["docno"].tolist()
    assert np.array_equal(np.asarray(a["score"], dtype=np.float64),
                          np.asarray(b["score"], dtype=np.float64))
    cache.close()


# ---------------------------------------------------------------------------
# explain / registry / closed loop
# ---------------------------------------------------------------------------

def test_explain_carries_online_latency():
    svc = PipelineService(two_stage(), max_batch=4, max_wait_ms=5)
    for i in range(4):
        svc.submit(TOPICS["qid"][i], TOPICS["query"][i]).result(60)
    text = svc.explain()
    svc.close()
    assert "online[p50=" in text
    assert "online: requests=4" in text
    stats = svc.plan_stats()
    assert stats.online["requests"] == 4
    assert set(stats.node_exec_counts) == set(stats.online["nodes"])


def test_registry_and_closed_loop():
    scenario = build_scenario("bm25", scale=0.02, cutoff=5)
    svc = PipelineService(scenario.pipeline, cache_backend="memory",
                          max_batch=8, max_wait_ms=2)
    loop = run_closed_loop(svc, scenario, n_requests=40, n_clients=4)
    assert loop["requests"] == 40
    assert svc.stats.requests == 40
    svc.close()
    with pytest.raises(KeyError):
        build_scenario("no-such-pipeline")


def test_streaming_executor_propagates_errors():
    def boom(frame):
        raise RuntimeError("stage exploded")

    svc = PipelineService(GenericTransformer(boom, "boom"),
                          max_batch=2, max_wait_ms=5)
    fut = svc.submit("q1", "a query")
    with pytest.raises(RuntimeError, match="stage exploded"):
        fut.result(60)
    # the service survives a failed batch and serves the next request
    ok = GenericTransformer(lambda f: f, "id2")
    svc.close()
    svc2 = PipelineService(ok, max_batch=2, max_wait_ms=5)
    out = svc2.submit("q1", "a query").result(60)
    assert out["qid"].tolist() == ["q1"]
    svc2.close()


@pytest.mark.parametrize("where,knob", [
    ("config", "max_batch"), ("config", "max_wait_ms"),
    ("cli", "--max-batch"), ("cli", "--max-wait-ms")])
def test_micro_batch_knobs_are_numbers(where, knob):
    """The micro-batch knobs take numbers only: ``"auto"`` is refused by
    ``ServeConfig`` and by the ``repro serve`` parser."""
    if where == "config":
        from repro.serve import ServeConfig
        with pytest.raises(ValueError, match=f"{knob} must be a number"):
            ServeConfig(**{knob: "auto"})
    else:
        from repro.cli import build_parser
        with pytest.raises(SystemExit):
            build_parser().parse_args(["serve", knob, "auto"])
        args = build_parser().parse_args(["serve", knob, "8"])
        assert getattr(args, knob[2:].replace("-", "_")) == 8
