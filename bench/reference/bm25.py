"""Plain reference of BM25 retrieval over the generated corpus.

Built from the corpus's word ids, not from the program's index: term
frequencies per (term, passage), document frequencies, and the scoring
rule the program states — Robertson-Sparck Jones idf with +1 inside the
log (``log(1 + (N - df + 0.5) / (df + 0.5))``), saturation ``k1`` and
length normalisation ``b`` against the mean passage length — in float64.
Ranking keeps passages with a positive score, ordered by score
descending, then by passage index ascending.

``precision="bfloat16"`` is the control: every per-term contribution is
rounded to bfloat16 and the sum is kept in bfloat16, one step below the
float32 the program accumulates in.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np


def _bf16(x: np.ndarray) -> np.ndarray:
    """Round float values to the nearest bfloat16 (ties to even)."""
    b = np.asarray(x, np.float32).view(np.uint32)
    b = (b + 0x7FFF + ((b >> 16) & 1)) & 0xFFFF0000
    return b.astype(np.uint32).view(np.float32).astype(np.float64)


class BM25:
    def __init__(self, doc_ptr: np.ndarray, doc_words: np.ndarray,
                 vocab: int, k1: float, b: float):
        n = len(doc_ptr) - 1
        lens = np.diff(doc_ptr)
        doc_of = np.repeat(np.arange(n, dtype=np.int64), lens)
        keys, tf = np.unique(doc_words.astype(np.int64) * n + doc_of,
                             return_counts=True)
        term = keys // n
        self.post_doc = keys % n
        self.post_tf = tf.astype(np.float64)
        self.term_ptr = np.searchsorted(term, np.arange(vocab + 1))
        df = np.diff(self.term_ptr).astype(np.float64)
        self.idf = np.log(1.0 + (n - df + 0.5) / (df + 0.5))
        self.norm = k1 * (1.0 - b + b * lens / lens.mean())
        self.k1 = k1
        self.n = n

    def scores(self, query_words: Sequence[int],
               precision: str = "float64") -> np.ndarray:
        acc = np.zeros(self.n, np.float64)
        for t in query_words:
            lo, hi = self.term_ptr[t], self.term_ptr[t + 1]
            d, tf = self.post_doc[lo:hi], self.post_tf[lo:hi]
            w = self.idf[t] * tf * (self.k1 + 1.0) / (tf + self.norm[d])
            if precision == "bfloat16":
                acc[d] = _bf16(acc[d] + _bf16(w))
            else:
                acc[d] += w
        return acc

    @staticmethod
    def top(acc: np.ndarray, k: int) -> np.ndarray:
        """Indices of the top ``k`` positive scores (score desc, index
        asc)."""
        nz = np.nonzero(acc > 0)[0]
        order = np.lexsort((nz, -acc[nz]))
        return nz[order[:k]]
