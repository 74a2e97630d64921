"""The batch encoders of ``HashTokenizer`` (word table, each distinct
string of a call tokenized once, rows laid out from id lists) give the
token rows of the per-pair loop bitwise, and the scorers that use them
score as they did over that loop's rows."""
import re
import sys
import threading

import numpy as np
import pytest

from repro.core import ColFrame, trace
from repro.ir import HashTokenizer, fnv1a32
from repro.models.cross_encoder import DuoScorer, EncoderConfig, MonoScorer

V, L = 1024, 16
CE = EncoderConfig(n_layers=1, d_model=32, n_heads=2, d_ff=64,
                   vocab_size=V, max_len=L)


def loop_pair(a: str, b: str, max_len: int, vocab: int = V) -> np.ndarray:
    """The per-pair layout as the tokenizer built it word by word, with no
    word table: ``[CLS] a[:max_len // 4] [SEP] b``, cut and 0-padded."""
    def ids(text):
        return [3 + fnv1a32(w.encode()) % (vocab - 3)
                for w in re.findall(r"[a-z0-9]+", text.lower())]
    seq = ([1] + ids(a)[:max_len // 4] + [2] + ids(b))[:max_len]
    return np.array(seq + [0] * (max_len - len(seq)), np.int32)


def joined(doc):
    """A doc side as one string: duo's passages around ``[VS]``."""
    return " [VS] ".join(doc) if isinstance(doc, tuple) else doc


P = ["alpha beta gamma", "delta, epsilon! zeta-eta theta",
     "iota kappa lambda mu nu xi omicron pi rho sigma tau upsilon"]
CASES = {
    "mono": (["a query"] * 3, P),
    "duo": (["a query"] * 6, [(P[i], P[j]) for i in range(3)
                              for j in range(3) if i != j]),
    "long_query": (["one two three four five six seven"], [P[0]]),
    "duo_past_max_len": (["q"], [(P[2], P[2] + " " + P[1])]),
    "empty_passage": (["q", "q"], ["", ("", P[0])]),
    # Kelvin sign and dotted capital I lower to ASCII; a final sigma
    # lowers by context
    "punctuation_non_ascii": (["Quéry: 'café'?"],
                              [("naïve Kelvin İstanbul",
                                "ΟΔΟΣ [x]--y__z")]),
    "repeated_strings": (["a", "b", "a", "a"], [P[0], P[0], P[1], P[0]]),
    "mixed_sides": (["q"] * 3, [P[0], (P[1], P[2]), (P[0], P[1], P[2])]),
}


@pytest.mark.parametrize("full_table", [False, True],
                         ids=["table", "full_table"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_batch_rows_equal_the_per_pair_loop(case, full_table):
    queries, docs = CASES[case]
    tok = HashTokenizer(V)
    if full_table:
        tok.WORD_TABLE_MAX = len(tok._ids) + 2
    want = np.stack([loop_pair(q, joined(d), L)
                     for q, d in zip(queries, docs)])
    got = tok.encode_pairs(queries, docs, L)
    assert got.dtype == np.int32 and got.shape == (len(queries), L)
    assert np.array_equal(got, want)
    # the single-pair form and a second call (table warm) agree
    assert np.array_equal(np.stack([tok.encode_pair(q, joined(d), L)
                                    for q, d in zip(queries, docs)]), want)
    assert np.array_equal(tok.encode_pairs(queries, docs, L), want)
    if full_table:
        assert len(tok._ids) == tok.WORD_TABLE_MAX


def test_single_texts_equal_the_per_text_loop():
    tok = HashTokenizer(V)
    texts = P + ["", P[0]]
    want = np.stack([loop_pair("", t, L + 2)[2:] for t in texts])
    assert np.array_equal(tok.encode_batch(texts, L), want)
    assert np.array_equal(tok.encode(P[2], L), want[2])
    assert tok.encode_batch([], L).shape == (0, L)
    assert tok.encode_pairs([], [], L).shape == (0, L)
    with pytest.raises(ValueError):
        tok.encode_pairs(["q"], [], L)


def test_threads_share_the_word_table_within_its_bound():
    tok = HashTokenizer(V)
    tok.WORD_TABLE_MAX = 300
    docs = [[" ".join(f"w{t}x{i}y{j}" for j in range(8)) for i in range(40)]
            for t in range(8)]
    want = [np.stack([loop_pair("q", d, L) for d in ds]) for ds in docs]
    got, old = {}, sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        ths = [threading.Thread(target=lambda t=t: got.__setitem__(
            t, tok.encode_pairs(["q"] * 40, docs[t], L))) for t in range(8)]
        for th in ths:
            th.start()
        for th in ths:
            th.join(30)
    finally:
        sys.setswitchinterval(old)
    assert not any(th.is_alive() for th in ths)
    assert all(np.array_equal(got[t], want[t]) for t in range(8))
    assert len(tok._ids) == tok.WORD_TABLE_MAX


def test_counters_read_sides_and_distinct_strings(monkeypatch):
    got = {}
    monkeypatch.setattr(trace, "count", lambda name, n: got.__setitem__(
        name, got.get(name, 0) + (n() if callable(n) else n)))
    tok = HashTokenizer(V)
    tok.encode_pairs(["q", "q"], [P[0], P[1]], L)            # mono
    tok.encode_pairs(["q"] * 2, [(P[0], P[1]), (P[1], P[0])], L)   # duo
    assert got == {"tokenizer.sides": 4 + 6, "tokenizer.strings": 3 + 3}


def _frame(n, texts):
    return ColFrame({"qid": ["q1"] * n, "query": ["a query here"] * n,
                     "docno": [f"d{i}" for i in range(n)],
                     "text": texts[:n], "rank": np.arange(n),
                     "score": -np.arange(n, dtype=np.float64)})


TEXTS = [f"passage {i} " + " ".join(P[i % 3].split()[:1 + i % 5])
         for i in range(6)]


@pytest.mark.parametrize("cls", [MonoScorer, DuoScorer])
def test_scores_equal_scores_of_the_per_pair_rows(cls):
    s = cls(CE, max_docs=4) if cls is DuoScorer else cls(CE)
    inp = _frame(5, TEXTS)
    q = inp["query"][0]
    texts = inp["text"].tolist()
    if cls is MonoScorer:
        rows = [loop_pair(q, t, L) for t in texts]
        want = np.asarray(s._runner(np.stack(rows)), np.float64)
    else:
        pairs = [(i, j) for i in range(4) for j in range(4) if i != j]
        v = s._runner(np.stack([loop_pair(q, texts[i] + " [VS] " + texts[j],
                                          L) for i, j in pairs]))
        want = np.zeros(4)
        for (i, j), x in zip(pairs, np.asarray(v, np.float64)):
            want[i] += x
            want[j] -= x
    out = s.transform(inp)
    got = dict(zip(out["docno"].tolist(), out["score"].tolist()))
    assert got == {f"d{i}": float(w) for i, w in enumerate(want)}
