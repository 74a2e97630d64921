"""Pairwise cross-encoder: the program's ``DuoScorer`` at the
configuration's BERT widths, which scores every ordered pair of a
query's passages and gives each passage its wins less its losses; its
reference does the same with the plain encoder."""
from typing import Dict, List

import numpy as np

from bench import bert, flops, weights
from bench.drivers import frame_rows
from bench.reference.encoder import run_blocks
from bench.reference.tokens import stack

ROLE = "pairwise"
CORPUS = True
STREAM = 2


def params(cfg, spec):
    return bert.params(cfg, spec.get("stream", STREAM))


def build(world, name, spec):
    from repro.models.cross_encoder import DuoScorer
    s = DuoScorer(bert.encoder_config(world.cfg, name),
                  max_docs=spec["max_docs"])
    weights.install(s, params(world.cfg, spec))
    return s


def warm(world, stage, spec, queries):
    """A group of n docs makes n (n - 1) pairs: 10 -> bucket 128, 8 -> 64."""
    c, texts = world.corpus, world.texts
    m = stage.max_docs
    for n in sorted({m, min(m, 8)}):
        rows = [{"qid": "w", "query": queries.texts[0],
                 "docno": c.docnos[i], "text": texts[i], "rank": i,
                 "score": float(-i)} for i in range(n)]
        stage.transform(frame_rows(rows))


def work(cfg, spec, real_tokens):
    return bert.work(cfg, real_tokens)


class Reference:
    def __init__(self, cfg, spec, inputs):
        self.S = cfg["max_len"]
        self.corpus = inputs.corpus
        self.lens = inputs.corpus.lengths()
        self.tok = bert.tokens(cfg, inputs)
        self.params = params(cfg, spec)

    def score(self, q: np.ndarray, docs: List[int], precision: str
              ) -> Dict[int, float]:
        S = self.S
        pairs = [(i, j) for i in range(len(docs)) for j in range(len(docs))
                 if i != j]
        toks = stack([self.tok.duo(q, self.corpus.doc(docs[i]),
                                   self.corpus.doc(docs[j]), S)
                      for i, j in pairs], S)
        s = run_blocks(self.params, toks, head="score", precision=precision)
        agg = np.zeros(len(docs))
        for (i, j), v in zip(pairs, s.astype(np.float64)):
            agg[i] += v
            agg[j] -= v
        return dict(zip(docs, agg))

    def real_tokens(self, q: np.ndarray, groups: List) -> np.ndarray:
        """One input per distinct ordered pair of passages of a group
        (``a vs b``)."""
        pairs = set()
        for g in groups:
            pairs |= {(a, b) for a in g for b in g if a != b}
        if not pairs:
            return np.zeros(0, np.int64)
        a, b = np.array(sorted(pairs)).T
        return flops.pair_tokens(len(q), self.lens[a] + self.lens[b] + 1,
                                 self.S)
