"""Plain reference of the encoders' token layout, from word ids.

The program hashes each word to an id in ``[3, vocab)`` with 32-bit
FNV-1a (0 pads, 1 opens, 2 separates) and lays a pair out as
``[CLS] query[:max_len // 4] [SEP] passage``, cut to ``max_len``; a
single text is its word ids, cut to ``max_len``.  A pairwise (duo) input
joins two passages around the word ``vs``.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np

CLS, SEP = 1, 2


class Tokens:
    def __init__(self, word_hash: np.ndarray, vocab_size: int,
                 vs_hash: int):
        self.ids = (3 + word_hash.astype(np.int64) % (vocab_size - 3)
                    ).astype(np.int32)
        self.vs = np.int32(3 + int(vs_hash) % (vocab_size - 3))

    def pair(self, query: np.ndarray, passage: np.ndarray,
             max_len: int) -> np.ndarray:
        seq = np.concatenate([[CLS], self.ids[query][:max_len // 4], [SEP],
                              self.ids[passage]])[:max_len]
        out = np.zeros(max_len, np.int32)
        out[:len(seq)] = seq
        return out

    def duo(self, query: np.ndarray, a: np.ndarray, b: np.ndarray,
            max_len: int) -> np.ndarray:
        seq = np.concatenate([[CLS], self.ids[query][:max_len // 4], [SEP],
                              self.ids[a], [self.vs], self.ids[b]])[:max_len]
        out = np.zeros(max_len, np.int32)
        out[:len(seq)] = seq
        return out

    def single(self, words: np.ndarray, max_len: int) -> np.ndarray:
        seq = self.ids[words][:max_len]
        out = np.zeros(max_len, np.int32)
        out[:len(seq)] = seq
        return out


def stack(rows: Sequence[np.ndarray], max_len: int) -> np.ndarray:
    return np.stack(rows) if len(rows) else np.zeros((0, max_len), np.int32)
