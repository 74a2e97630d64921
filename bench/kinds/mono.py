"""Pointwise cross-encoder: the program's ``MonoScorer`` at the
configuration's BERT widths; its reference is the plain encoder
(``bench/reference/encoder.py``) over the same pair layout."""
from typing import Dict, List

import numpy as np

from bench import bert, flops, weights
from bench.drivers import frame_rows
from bench.reference.encoder import run_blocks
from bench.reference.tokens import stack

ROLE = "pointwise"
CORPUS = True
STREAM = 1
#: the buckets the program's encoders pad a batch to (64 rows up to 1024)
BUCKETS = (64, 128, 256, 512, 1024)


def params(cfg, spec):
    return bert.params(cfg, spec.get("stream", STREAM))


def build(world, name, spec):
    from repro.models.cross_encoder import MonoScorer
    s = MonoScorer(bert.encoder_config(world.cfg, name))
    weights.install(s, params(world.cfg, spec))
    return s


def warm(world, stage, spec, queries):
    """One batch at each bucket."""
    c, texts = world.corpus, world.texts
    for b in BUCKETS:
        rows = [{"qid": "w", "query": queries.texts[i % len(queries.texts)],
                 "docno": c.docnos[i], "text": texts[i]} for i in range(b)]
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

    def score(self, q: np.ndarray, docs, precision: str) -> Dict[int, float]:
        docs = list(dict.fromkeys(int(d) for d in docs))
        S = self.S
        toks = stack([self.tok.pair(q, self.corpus.doc(d), S)
                      for d in docs], S)
        s = run_blocks(self.params, toks, head="score", precision=precision)
        return dict(zip(docs, s.astype(np.float64)))

    def real_tokens(self, q: np.ndarray, groups: List) -> np.ndarray:
        """One pair per distinct passage of the groups."""
        docs = list(dict.fromkeys(int(d) for g in groups for d in g))
        return flops.pair_tokens(len(q), self.lens[docs], self.S)
