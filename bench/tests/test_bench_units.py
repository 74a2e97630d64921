"""CPU tests of the benchmark's yardstick: generators, work counters,
the trace reduction and the registry."""
import os

import numpy as np
import pytest

from bench import flops, gen
from bench.reference.bm25 import BM25, _bf16
from bench.reference.tokens import Tokens
from bench.registry import Registry
from bench.trace_reduce import _Cover, _merge, program_name, reduce_trace
from bench.tests import toy

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CORPUS = {"words_mean": 20, "words_sigma": 0.45,
          "words_min": 4, "words_max": 60, "vocab": 3000, "zipf_s": 1.05}


# -- generators ---------------------------------------------------------------

def test_corpus_is_a_function_of_the_seed():
    a, b = gen.make_corpus(CORPUS, 500, 3), gen.make_corpus(CORPUS, 500, 3)
    c = gen.make_corpus(CORPUS, 500, 4)
    assert np.array_equal(a.doc_words, b.doc_words)
    assert np.array_equal(a.doc_ptr, b.doc_ptr)
    assert not np.array_equal(a.doc_ptr, c.doc_ptr)
    lens = a.lengths()
    assert lens.min() >= 4 and lens.max() <= 60
    assert abs(lens.mean() - 20) < 2


def test_vocabulary_words_are_distinct_and_never_stopwords():
    w = gen.vocabulary(20000)
    assert len(set(w)) == len(w)
    assert not set(w) & gen._STOPWORDS
    assert all(x.isalpha() and x.islower() for x in w[:100])


@pytest.mark.parametrize("kind", ["planted", "zipf_words"])
def test_queries_are_deterministic_distinct_and_differ_across_seeds(kind):
    corpus = gen.make_corpus(CORPUS, 500, 1)
    spec = {"vocab": 3000, "zipf_s": 1.05, "lengths": toy.LENGTHS}

    def make(seed):
        if kind == "planted":
            return gen.planted_queries(corpus, 40, toy.LENGTHS, seed, 0, "q")
        return gen.zipf_queries(40, spec, corpus.words, seed, 0, "q")

    a, b, c = make(5), make(5), make(6)
    assert a.texts == b.texts and a.texts != c.texts
    assert len(set(a.texts)) == 40
    # the same multiset of lengths for every seed, in another order
    assert sorted(map(len, a.word_ids)) == sorted(map(len, c.word_ids))
    if kind == "planted":
        for words, t in zip(a.word_ids, a.targets):
            assert set(words) <= set(corpus.doc(int(t)))


def test_arrivals_offer_the_same_load_for_every_seed():
    a, b, c = (gen.arrivals(40.0, 10.0, s) for s in (1, 1, 2))
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert len(a) == len(c) == 400
    assert a[0] == 0.0 and a[-1] < 10.0 and np.all(np.diff(a) > 0)
    assert np.allclose(np.sort(np.diff(a)), np.sort(np.diff(c)), rtol=1e-9,
                       atol=1e-12) or abs(a[-1] - c[-1]) < 1.0


def test_fnv_matches_the_program_tokenizer():
    from repro.ir.tokenizer import HashTokenizer, fnv1a32
    words = ["a", "bcd", "zzzz", "vs", "w123"]
    assert list(gen.fnv1a32_words(words)) == [fnv1a32(w.encode())
                                              for w in words]
    tok = Tokens(gen.fnv1a32_words(words), 1024, gen.fnv1a32_words(["vs"])[0])
    ht = HashTokenizer(1024)
    q, p = np.array([0, 1]), np.array([2, 3, 4])
    text = lambda ids: " ".join(words[i] for i in ids)  # noqa: E731
    assert np.array_equal(tok.pair(q, p, 8), ht.encode_pair(text(q),
                                                            text(p), 8))
    assert np.array_equal(tok.duo(q, p, q, 16), ht.encode_pair(
        text(q), text(p) + " [VS] " + text(q), 16))
    assert np.array_equal(tok.single(p, 2), ht.encode(text(p), 2))


# -- counters -----------------------------------------------------------------

def test_encoder_flops_against_a_hand_count():
    # L=2, d=4, F=8; one input of 3 real tokens:
    # per layer 2*3*(4*16) + 2*3*(2*4*8) + 4*9*4 = 384 + 384 + 144 = 912
    # two layers 1824, plus the score head 2*4 = 8
    assert flops.encoder_flops([3], 2, 4, 8) == 1832.0
    assert flops.encoder_flops([3], 2, 4, 8, score_head=False) == 1824.0
    assert flops.encoder_flops([3, 3], 2, 4, 8) == 2 * 1832.0


def test_pair_tokens_and_topk_counts():
    # [CLS] q [SEP] p, the query cut to max_len // 4, the pair to max_len
    assert list(flops.pair_tokens(5, np.array([10, 300]), 256)) == [17, 256]
    assert list(flops.pair_tokens(100, np.array([1]), 256)) == [67]
    assert flops.topk_bytes(1000, 8, 4, 2) == 1000 * 8 * 4 + 2 * 8 * 4
    assert flops.topk_flops(1000, 8, 2) == 2 * 1000 * 8 * 2


def test_reference_bm25_matches_the_program_index():
    from repro.ir import InvertedIndex
    corpus = gen.make_corpus(CORPUS, 500, 2)
    texts = corpus.texts()
    ix = InvertedIndex.build({"docno": d, "text": t}
                             for d, t in zip(corpus.docnos, texts))
    ret = ix.bm25(k1=1.2, b=0.75, num_results=50)
    ref = BM25(corpus.doc_ptr, corpus.doc_words, len(corpus.words), 1.2, 0.75)
    q = gen.planted_queries(corpus, 5, toy.LENGTHS, 0, 0, "q")
    for text, words in zip(q.texts, q.word_ids):
        ids, scores = ret.score_query(text)
        acc = ref.scores(words)
        assert list(ids) == list(BM25.top(acc, 50))
        assert np.allclose(scores, acc[ids], rtol=1e-5)


def test_bfloat16_rounding():
    x = np.array([1.0, 1.0 + 2 ** -9, 1.0 + 3 * 2 ** -9, 3.14159])
    assert list(_bf16(x)[:3]) == [1.0, 1.0, 1.0 + 2 ** -7]
    assert abs(_bf16(x)[3] - 3.14159) < 2 ** -7 * 4


# -- trace reduction ----------------------------------------------------------

def test_merge_and_cover():
    assert _merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    c = _Cover([(0, 100, "outer"), (10, 20, "inner"), (50, 60, "other")])
    assert c.at(15) == "inner" and c.at(30) == "outer"
    assert c.at(55) == "other" and c.at(200) is None
    assert program_name("jit__lambda(1234)") == "jit__lambda"


def test_reduce_recorded_trace():
    """A trace recorded on a TPU v5e: three rounds of two small programs
    inside ``bench.window``, the first of each round inside
    ``bench.call``, 10 ms of host sleep between them."""
    from jax.profiler import ProfileData
    path = os.path.join(DATA, "small.xplane.pb")
    r = reduce_trace(path)
    # busy is the union of the XLA Ops intervals inside the window
    pd = ProfileData.from_file(path)
    win = [(e.start_ns, e.start_ns + e.duration_ns) for p in pd.planes
           if p.name.startswith("/host:") for ln in p.lines for e in ln.events
           if e.name == "bench.window"][0]
    ops = [(max(e.start_ns, win[0]), min(e.start_ns + e.duration_ns, win[1]))
           for p in pd.planes if p.name.startswith("/device:TPU:")
           for ln in p.lines if ln.name == "XLA Ops" for e in ln.events]
    grid = np.zeros(int(win[1] - win[0]) + 1, bool)
    for s, e in ops:
        if e > s:
            grid[int(s - win[0]):int(e - win[0])] = True
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx((win[1] - win[0]) / 1e9)
    assert r["busy_s"] == pytest.approx(grid.sum() / 1e9, rel=1e-3)
    assert 0 < r["busy_s"] < r["window_s"]
    # three sleeps of 10 ms leave the device idle at least 30 ms
    idle = sum(s for _, s in r["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
    assert idle > 0.03
    # a program counts where it overlaps the window; on this trace the
    # device's clock reads about 1 ms ahead of the host's, so the first
    # program ends before the host opened the window
    mods = [(e.start_ns, e.start_ns + e.duration_ns) for p in pd.planes
            if p.name.startswith("/device:TPU:") for ln in p.lines
            if ln.name == "XLA Modules" for e in ln.events]
    inside = sum(1 for s, e in mods if e > win[0] and s < win[1])
    assert len(mods) == 6 and inside >= 5
    progs = r["programs"]
    assert set(progs) == {"jit__lambda"}
    assert progs["jit__lambda"]["n"] == inside
    assert r["device_ops"] and all(s > 0 for _, s in r["device_ops"])


# -- registry -----------------------------------------------------------------

def test_registry_finds_parts_by_name(tmp_path):
    root = toy.make(str(tmp_path))
    reg = Registry(root, os.path.join(root, "bench"))
    assert reg.workload("toy.grid")["traffic"] == "toy-grid"
    assert reg.config("toy-rerank")["max_len"] == 32
    assert reg.traffic("toy-dense")["pipeline"] == "dense % 20"
    assert reg.limits("toy.rerank")["missing"] == 0.0
    e2e = {m["name"] for m in reg.metrics("toy.grid", trace=False)}
    assert e2e == {"setup_s", "grid_qps"}
    layer = {m["name"] for m in reg.metrics("toy.grid", trace=True)}
    assert "toy_topics_per_iteration.grid" in layer
    assert "batch_rows.serve" not in layer
    serve = {m["name"] for m in reg.metrics("toy.dense", trace=True)}
    assert "topk_roofline.serve" in serve and "mfu.serve" in serve
    run = type("R", (), {"counters": {"topics": 8, "iterations": 2}})()
    rec = type("Rec", (), {"run": run})()
    assert reg.reader("toy_topics_per_iteration.grid")(rec) == 4.0
    with pytest.raises(KeyError):
        reg.peaks("TPU v0")
    mono = reg.kind("mono")
    assert mono.ROLE == "pointwise" and mono.STREAM == 1
    assert reg.kind("mono") is mono
    assert reg.kind("pw").ROLE == "pointwise"
    with pytest.raises(KeyError, match=r"no stage kind 'colbert'; known: "
                       r"\['bm25', 'dense', 'duo', 'mono', 'pw', "
                       r"'text_loader'\]"):
        reg.kind("colbert")


def test_the_benchmark_names_a_reader_for_every_metric():
    reg = Registry()
    for m in reg.spec["end_to_end"] + reg.spec["per_layer"]:
        assert callable(reg.reader(m["name"]))
    for w in reg.spec["workloads"]:
        assert reg.traffic(w["traffic"])["driver"] in ("grid", "open_loop")
        assert reg.limits(w["name"])
        for st in reg.config(w["config"])["stages"].values():
            assert reg.kind(st["kind"]).ROLE
    assert reg.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
