"""Operations and bytes that the benchmark's work needs, counted from its
inputs' real sizes (never from the padded shapes a program runs).

An encoder pass over ``n`` real tokens at widths (L layers, d, F) needs,
per layer, ``2 n (4 d^2)`` for the query, key, value and output
projections, ``2 n (2 d F)`` for the feed-forward, and ``2 (2 n^2 d)``
for the attention scores and their weighted sum over real keys; plus
``2 d`` for a linear score head.  Norms, softmax and the embedding
lookup are not counted (they are not matrix work and are a fraction of a
per cent at these widths).
"""
from __future__ import annotations

import numpy as np


def encoder_flops(real_tokens: np.ndarray, L: int, d: int, F: int,
                  score_head: bool = True) -> float:
    """Operations of encoder passes with the given real token counts."""
    n = np.asarray(real_tokens, np.float64)
    per_layer = 2.0 * n * (4 * d * d) + 2.0 * n * (2 * d * F) \
        + 4.0 * n * n * d
    total = L * per_layer.sum()
    if score_head:
        total += 2.0 * d * len(n)
    return float(total)


def encoder_weight_bytes(L: int, d: int, F: int) -> float:
    """float32 bytes of one encoder pass's layer weights (read per call)."""
    return 4.0 * L * (4 * d * d + 2 * d * F + 2 * d)


def pair_tokens(query_words: int, passage_words: np.ndarray,
                max_len: int) -> np.ndarray:
    """Real tokens of ``[CLS] query [SEP] passage`` inputs cut to
    ``max_len`` (the query itself cut to ``max_len // 4``)."""
    q = min(int(query_words), max_len // 4)
    return np.minimum(2 + q + np.asarray(passage_words, np.int64), max_len)


def topk_bytes(rows: int, dim: int, itemsize: int, queries: int) -> float:
    """Bytes one scoring pass over a ``rows x dim`` index must read: the
    index once, plus the queries."""
    return float(rows) * dim * itemsize + float(queries) * dim * itemsize


def topk_flops(rows: int, dim: int, queries: int) -> float:
    return 2.0 * rows * dim * queries
