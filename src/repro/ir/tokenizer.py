"""Tokenization for the IR substrate.

Two tokenizers:

* ``WordTokenizer`` — whitespace/punctuation split + lowercase + optional
  stopword removal; produces string terms for the inverted index.
* ``HashTokenizer`` — maps terms to integer ids in a fixed vocabulary via
  a stable FNV-1a hash (no vocab file needed).  Used by the neural
  scorers: deterministic, dependency-free, and identical across hosts —
  a requirement for the caching layer's determinism assumptions.
"""
from __future__ import annotations

import itertools
import re
import threading
from typing import Dict, List, Sequence, Tuple, Union

import numpy as np

from ..core import trace

__all__ = ["WordTokenizer", "HashTokenizer", "fnv1a32"]

_TOKEN_RE = re.compile(r"[a-z0-9]+")

_STOPWORDS = frozenset("""
a an and are as at be by for from has he in is it its of on that the to was
were will with
""".split())


def fnv1a32(data: bytes) -> int:
    """32-bit FNV-1a (stable across runs/hosts, unlike hash())."""
    h = 0x811C9DC5
    for b in data:
        h ^= b
        h = (h * 0x01000193) & 0xFFFFFFFF
    return h


class WordTokenizer:
    def __init__(self, remove_stopwords: bool = True):
        self.remove_stopwords = remove_stopwords

    def tokenize(self, text: str) -> List[str]:
        toks = _TOKEN_RE.findall(text.lower())
        if self.remove_stopwords:
            toks = [t for t in toks if t not in _STOPWORDS]
        return toks


class HashTokenizer:
    """term -> stable id in [n_special, vocab); 0 = PAD, 1 = CLS, 2 = SEP.

    A word table sits in front of the hash: each word is hashed once and
    its id kept, for at most ``WORD_TABLE_MAX`` words; past that a new
    word is hashed each time it is seen.  The table caches a pure
    function, so it never changes an id.  The batch encoders tokenize
    each distinct string of a call once and lay the rows out from those
    id lists.
    """

    PAD, CLS, SEP = 0, 1, 2
    N_SPECIAL = 3
    #: most words the word table keeps, which bounds its memory
    WORD_TABLE_MAX = 1 << 20

    def __init__(self, vocab_size: int, remove_stopwords: bool = False):
        if vocab_size <= self.N_SPECIAL:
            raise ValueError("vocab too small")
        self.vocab_size = int(vocab_size)
        self._word = WordTokenizer(remove_stopwords)
        # hits read the dict without a lock (one atomic ``get`` under the
        # GIL); only an insert takes the lock, so the bound holds
        self._ids: Dict[str, int] = {}
        self._insert = threading.Lock()
        #: id of the word ``vs``, which joins the passages of a duo pair
        self.vs = self.term_id("vs")

    def _hash_id(self, term: str) -> int:
        tid = self.N_SPECIAL + fnv1a32(term.encode()) % (
            self.vocab_size - self.N_SPECIAL)
        with self._insert:
            if len(self._ids) < self.WORD_TABLE_MAX:
                self._ids[term] = tid
        return tid

    def term_id(self, term: str) -> int:
        # ids are >= N_SPECIAL, so a hit is never falsy
        return self._ids.get(term) or self._hash_id(term)

    def ids(self, text: str) -> List[int]:
        """The ids of the words of ``text``."""
        get, miss = self._ids.get, self._hash_id
        return [get(t) or miss(t) for t in self._word.tokenize(text)]

    def encode(self, text: str, max_len: int) -> np.ndarray:
        return self.encode_batch([text], max_len)[0]

    def encode_pair(self, a: str, b: str, max_len: int) -> np.ndarray:
        """[CLS] a [SEP] b — the cross-encoder input layout."""
        return self.encode_pairs([a], [b], max_len)[0]

    def encode_batch(self, texts: Sequence[str], max_len: int) -> np.ndarray:
        """One row per text: its word ids, cut to ``max_len``, 0-padded."""
        rows = _Rows(self)
        return rows.lay_out([(rows.piece(t),) for t in texts], (max_len,),
                            max_len)

    def encode_pairs(self, queries: Sequence[str],
                     docs: Sequence[Union[str, Tuple[str, ...]]],
                     max_len: int) -> np.ndarray:
        """Rows ``[CLS] query[:max_len // 4] [SEP] doc``, cut to
        ``max_len`` and 0-padded: ``encode_pair`` row by row.

        A doc is a string, or a tuple of passages joined by the id of the
        word ``vs``, which is what ``" [VS] ".join(doc)`` tokenizes to
        (the spaces and brackets keep words from merging).
        """
        if len(queries) != len(docs):
            raise ValueError(f"{len(queries)} queries for {len(docs)} docs")
        rows = _Rows(self)
        segs = []
        for q, doc in zip(queries, docs):
            parts = doc if isinstance(doc, tuple) else (doc,)
            seg = [_CLS, rows.piece(q), _SEP, rows.piece(parts[0])]
            for p in parts[1:]:
                seg += (_VS, rows.piece(p))
            segs.append(seg)
        width = max(map(len, segs), default=4)
        for seg in segs:
            seg += [_EMPTY] * (width - len(seg))
        caps = (max_len, max_len // 4) + (max_len,) * (width - 2)
        return rows.lay_out(segs, caps, max_len)


# the pieces every call's rows may hold besides its strings, which follow
_CLS, _SEP, _VS, _EMPTY = range(4)


class _Rows:
    """One batch call's distinct strings, each tokenized once, and the
    layout of rows that are sequences of them."""

    def __init__(self, tok: HashTokenizer):
        self.tok = tok
        self.index: Dict[str, int] = {}
        self.pieces: List[List[int]] = [[tok.CLS], [tok.SEP], [tok.vs], []]

    def piece(self, text: str) -> int:
        i = self.index.get(text)
        if i is None:
            i = self.index[text] = len(self.pieces)
            self.pieces.append(self.tok.ids(text))
        return i

    def lay_out(self, segs: Sequence[Sequence[int]], caps: Sequence[int],
                max_len: int) -> np.ndarray:
        """Row r is its pieces ``segs[r]``, the k-th cut to ``caps[k]``
        ids, one after another, cut to ``max_len`` and 0-padded."""
        n = len(segs)
        out = np.zeros((n, max_len), np.int32)
        if n == 0:
            return out
        segs = np.asarray(segs, np.int64)
        trace.count("tokenizer.sides", lambda: int((segs > _EMPTY).sum()))
        trace.count("tokenizer.strings", len(self.index))
        lens = np.fromiter(map(len, self.pieces), np.int64, len(self.pieces))
        flat = np.fromiter(itertools.chain.from_iterable(self.pieces),
                           np.int32, int(lens.sum()))
        first = np.cumsum(lens) - lens               # piece -> flat offset
        take = np.minimum(lens[segs], np.asarray(caps, np.int64))
        end = np.cumsum(take, axis=1)
        begin = np.minimum(end - take, max_len)      # where it lands in a row
        take = (np.minimum(end, max_len) - begin).ravel()
        within = np.arange(int(take.sum())) - np.repeat(
            np.cumsum(take) - take, take)
        row0 = (np.arange(n)[:, None] * max_len + begin).ravel()
        out.ravel()[np.repeat(row0, take) + within] = \
            flat[np.repeat(first[segs].ravel(), take) + within]
        return out
