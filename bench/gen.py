"""Seeded generators for the benchmark's inputs: corpora, queries, arrivals.

Everything here is numpy only, vectorised, and a pure function of the
parameters and the seed.  Traffic files (``bench/traffic/*.json``) and
configuration files (``bench/configs/*.json``) hold the parameters.

Steadiness: where a run's amount of work would otherwise depend on the
seed (how many requests fall into the window, how long each query is),
the generator draws a fixed multiset of sizes from quantiles of the
distribution and lets the seed choose only their order.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Sequence

import numpy as np

#: stopwords of the program's word tokenizer; generated words avoid them so
#: that BM25 sees every generated word
_STOPWORDS = frozenset("""
a an and are as at be by for from has he in is it its of on that the to was
were will with
""".split())


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream...); any seed >= 0."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *stream]))


def sub_seed(seed: int, stream: int) -> int:
    """A 31-bit seed for ``jax.random.key`` derived from (seed, stream)."""
    return int(np.random.SeedSequence([int(seed), 1000 + stream])
               .generate_state(1)[0] >> 1)


def vocabulary(n: int) -> np.ndarray:
    """``n`` distinct lowercase words; rank 0 is the most frequent.

    Word ``r`` spells ``r + 26**2`` in base 26, so frequent words have
    three letters and rare ones four or five, as in running text.
    """
    digits = []
    x = np.arange(n, dtype=np.int64) + 26 ** 2
    while True:
        digits.append(x % 26)
        x = x // 26
        if not x.any():
            break
    letters = np.stack(digits[::-1], axis=1)          # [n, width], leading 0s
    chars = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz", np.uint8)[letters]
    out = np.empty(n, dtype=object)
    for i, row in enumerate(chars):
        w = row.tobytes().decode().lstrip("a") or "a"
        out[i] = w + "zzz" if w in _STOPWORDS else w
    return out


@dataclass
class Corpus:
    """Passages as word ids (CSR) and as text, with their docnos."""
    words: np.ndarray          # [vocab] object: the word of each id
    doc_ptr: np.ndarray        # [n_docs + 1] int64
    doc_words: np.ndarray      # [total] int32 word ids
    docnos: np.ndarray         # [n_docs] object

    @property
    def n_docs(self) -> int:
        return len(self.doc_ptr) - 1

    def doc(self, i: int) -> np.ndarray:
        return self.doc_words[self.doc_ptr[i]:self.doc_ptr[i + 1]]

    def lengths(self) -> np.ndarray:
        return np.diff(self.doc_ptr)

    def texts(self) -> List[str]:
        w = self.words
        return [" ".join(w[self.doc_words[a:b]])
                for a, b in zip(self.doc_ptr[:-1], self.doc_ptr[1:])]


def zipf_cdf(vocab: int, s: float) -> np.ndarray:
    p = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** s
    c = np.cumsum(p)
    return c / c[-1]


def make_corpus(spec: Dict, n: int, seed: int) -> Corpus:
    """``n`` passages in the shape ``spec`` states: lognormal lengths
    (``words_mean``, ``words_sigma``, clipped to ``words_min``/``max``)
    over a Zipf(``zipf_s``) vocabulary of ``vocab`` words."""
    rng = rng_for(seed, 1)
    mu = np.log(spec["words_mean"]) - spec["words_sigma"] ** 2 / 2
    lens = np.clip(np.rint(rng.lognormal(mu, spec["words_sigma"], n)),
                   spec["words_min"], spec["words_max"]).astype(np.int64)
    ptr = np.zeros(n + 1, np.int64)
    np.cumsum(lens, out=ptr[1:])
    cdf = zipf_cdf(int(spec["vocab"]), float(spec["zipf_s"]))
    ids = np.searchsorted(cdf, rng.random(int(ptr[-1]))).astype(np.int32)
    docnos = np.array([f"p{i}" for i in range(n)], dtype=object)
    return Corpus(vocabulary(int(spec["vocab"])), ptr, ids, docnos)


def quantile_sizes(n: int, table: Dict[str, float], rng) -> np.ndarray:
    """``n`` sizes whose multiset follows the probability ``table``
    ({size: weight}) by quantiles, in an order the generator picks."""
    sizes = np.array(sorted(int(k) for k in table))
    w = np.array([table[str(s)] for s in sizes], np.float64)
    cdf = np.cumsum(w) / w.sum()
    q = (np.arange(n) + 0.5) / n
    out = sizes[np.searchsorted(cdf, q)]
    return out[rng.permutation(n)]


@dataclass
class Queries:
    qids: List[str]
    texts: List[str]
    word_ids: List[np.ndarray]     # the words of each query, in order
    targets: np.ndarray            # [n] planted doc index, -1 if none


def planted_queries(corpus: Corpus, n: int, lengths: Dict[str, float],
                    seed: int, stream: int, prefix: str) -> Queries:
    """``n`` distinct queries, each made of distinct words of one target
    passage (the passage that its qrel marks relevant)."""
    rng = rng_for(seed, 2, stream)
    sizes = quantile_sizes(n, lengths, rng)
    targets = rng.integers(0, corpus.n_docs, n)
    seen, texts, wids = set(), [], []
    for i in range(n):
        while True:
            uniq = np.unique(corpus.doc(int(targets[i])))
            take = rng.permutation(uniq)[:sizes[i]]
            text = " ".join(corpus.words[take])
            if text not in seen:
                break
            targets[i] = rng.integers(0, corpus.n_docs)
        seen.add(text)
        texts.append(text)
        wids.append(take.astype(np.int32))
    return Queries([f"{prefix}{i}" for i in range(n)], texts, wids, targets)


def arrivals(rate: float, seconds: float, seed: int,
             stream: int = 0) -> np.ndarray:
    """Poisson due times in [0, seconds): ``round(rate * seconds)``
    exponential gaps taken at their quantiles, ordered by (seed, stream),
    so every seed offers the same number of requests with the same gaps."""
    n = max(1, int(round(rate * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    gaps = gaps[rng_for(seed, 3, stream).permutation(n)]
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return due * (seconds * n / (n + 1)) / max(due[-1], 1e-9) if n > 1 \
        else due


def fnv1a32_words(words: Sequence[str]) -> np.ndarray:
    """32-bit FNV-1a of each word's UTF-8 bytes, vectorised over words."""
    raw = [w.encode() for w in words]
    width = max((len(b) for b in raw), default=0)
    buf = np.zeros((len(raw), width), np.uint32)
    lens = np.array([len(b) for b in raw], np.int64)
    for i, b in enumerate(raw):
        buf[i, :len(b)] = np.frombuffer(b, np.uint8)
    h = np.full(len(raw), 0x811C9DC5, np.uint64)
    for j in range(width):
        live = lens > j
        nh = ((h ^ buf[:, j]) * np.uint64(0x01000193)) & np.uint64(0xFFFFFFFF)
        h = np.where(live, nh, h)
    return h.astype(np.uint32)


def zipf_queries(n: int, spec: Dict, words: np.ndarray, seed: int,
                 stream: int, prefix: str) -> Queries:
    """``n`` distinct queries of distinct Zipf words (no planted passage);
    ``words`` is ``vocabulary(spec["vocab"])``."""
    rng = rng_for(seed, 4, stream)
    sizes = quantile_sizes(n, spec["lengths"], rng)
    cdf = zipf_cdf(int(spec["vocab"]), float(spec["zipf_s"]))
    seen, texts, wids = set(), [], []
    for i in range(n):
        while True:
            ids = np.unique(np.searchsorted(cdf, rng.random(4 * sizes[i])))
            take = rng.permutation(ids)[:sizes[i]].astype(np.int32)
            text = " ".join(words[take])
            if len(take) == sizes[i] and text not in seen:
                break
        seen.add(text)
        texts.append(text)
        wids.append(take)
    return Queries([f"{prefix}{i}" for i in range(n)], texts, wids,
                   np.full(n, -1))
