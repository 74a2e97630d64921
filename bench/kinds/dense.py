"""Dense retrieval: the program's ``DenseEncoder`` at the configuration's
BERT widths embeds each query, and its ``DenseIndex`` holds the index
rows that the benchmark makes from the run's seed on the device; the
reference embeds with the plain encoder and scores the same rows block
by block."""
from typing import Dict

import numpy as np

from bench import bert, flops, gen, weights
from bench.reference.encoder import run_blocks
from bench.reference.tokens import stack

ROLE = "embedding"
CORPUS = False
STREAM = 3


def params(cfg, spec):
    return bert.params(cfg, spec.get("stream", STREAM))


def index_block(cfg: Dict, seed: int, block: int):
    """Block ``block`` of the index rows for ``seed``, on the device."""
    import jax
    n = cfg["num_passages"]
    dim, blocks = cfg["index"]["dim"], cfg["index"]["blocks"]
    if n % blocks:
        raise ValueError(f"num_passages {n} must divide into {blocks} blocks")
    key = jax.random.fold_in(jax.random.key(gen.sub_seed(seed, 10)), block)
    return weights.index_rows(key, n // blocks, dim)


def dense_rows(cfg: Dict, seed: int) -> np.ndarray:
    """The index rows for ``seed``, made on the device ``blocks`` blocks at
    a time (so that the device holds one block) and gathered into the one
    host float32 array the program's ``DenseIndex`` holds."""
    n, blocks = cfg["num_passages"], cfg["index"]["blocks"]
    per = n // blocks
    out = np.empty((n, cfg["index"]["dim"]), np.float32)
    for b in range(blocks):
        out[b * per:(b + 1) * per] = np.asarray(index_block(cfg, seed, b))
    return out


def build(world, name, spec):
    from repro.ir.dense import DenseEncoder, DenseIndex
    cfg = world.cfg
    enc = DenseEncoder(bert.encoder_config(cfg, name))
    weights.install(enc, params(cfg, spec))
    index = DenseIndex(enc)
    index.docnos = [f"p{i}" for i in range(cfg["num_passages"])]
    index.matrix = dense_rows(cfg, world.seed)
    return index.retriever(num_results=1000)


def warm(world, stage, spec, queries):
    """The index's device copy; the serving warm-up drives the query
    encoder's buckets."""
    stage.index.device_chunks()


def work(cfg, spec, real_tokens):
    """The query encoder's passes, and one scoring pass over the index
    per query."""
    n_rows, dim = cfg["num_passages"], cfg["index"]["dim"]
    enc = bert.work(cfg, real_tokens, score_head=False)
    return {"encoder_flops": enc["encoder_flops"],
            "topk_flops": flops.topk_flops(n_rows, dim, len(real_tokens)),
            "topk_index_bytes": flops.topk_bytes(n_rows, dim, 4, 0),
            "encoder_weight_bytes": enc["encoder_weight_bytes"]}


class Reference:
    def __init__(self, cfg, spec, inputs):
        self.cfg, self.seed = cfg, inputs.seed
        self.S = cfg["max_len"]
        self.n_rows, self.blocks = cfg["num_passages"], cfg["index"]["blocks"]
        self.tok = bert.tokens(cfg, inputs)
        self.params = params(cfg, spec)

    def embed(self, queries, precision: str) -> np.ndarray:
        toks = stack([self.tok.single(q, self.S) for q in queries], self.S)
        return run_blocks(self.params, toks, head="embed",
                          precision=precision)

    def rows(self, block: int):
        return index_block(self.cfg, self.seed, block)

    def real_tokens(self, q: np.ndarray) -> np.ndarray:
        return np.array([min(len(q), self.S)])
