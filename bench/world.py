"""Build what a configuration names, through the library's public API.

A configuration file lists its ``stages`` by name and kind; a traffic
file writes pipelines over those names (``bm25 % 100 >> text_loader >>
mono``).  ``World`` makes the inputs from the run's seed (corpus, index
rows) and the weights from the configuration's ``weight_seed``, and
builds each stage once per process (one scorer instance per role).
"""
from __future__ import annotations

import re
from typing import Dict, Optional

import numpy as np

from . import gen, weights

ROLE_STREAM = {"mono": 1, "duo": 2, "dense": 3}


def encoder_widths(cfg: Dict) -> Dict:
    return {"L": int(cfg["num_hidden_layers"]), "d": int(cfg["hidden_size"]),
            "H": int(cfg["num_attention_heads"]),
            "F": int(cfg["intermediate_size"]), "V": int(cfg["vocab_size"]),
            "S": int(cfg["max_len"])}


def install(target, params) -> None:
    """Give a program encoder the benchmark's weights (through its public
    ``params``), after checking that the layouts agree leaf by leaf."""
    import jax
    have = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)),
                        target.params)
    want = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), params)
    if have != want:
        raise ValueError(f"{type(target).__name__}: the program's encoder "
                         f"layout {have} differs from the benchmark's {want}")
    target.params = params


class World:
    def __init__(self, cfg: Dict, seed: int):
        self.cfg = cfg
        self.seed = int(seed)
        self.stages: Dict[str, object] = {}
        self.corpus: Optional[gen.Corpus] = None
        self.texts = None
        self.index = None                  # InvertedIndex
        self.dense = None                  # DenseIndex
        kinds = {s["kind"] for s in cfg["stages"].values()}
        if kinds & {"bm25", "text_loader", "mono", "duo"}:
            self._build_corpus()
        for name, spec in cfg["stages"].items():
            self.stages[name] = getattr(self, f"_stage_{spec['kind']}")(spec)

    # -- inputs ---------------------------------------------------------------
    def _build_corpus(self) -> None:
        self.corpus = gen.make_corpus(self.cfg["corpus"],
                                      self.cfg["num_passages"], self.seed)
        self.texts = self.corpus.texts()

    def encoder_config(self, role: str):
        from repro.models.cross_encoder import EncoderConfig
        w = encoder_widths(self.cfg)
        return EncoderConfig(name=f"{self.cfg['name']}.{role}", n_layers=w["L"],
                             d_model=w["d"], n_heads=w["H"], d_ff=w["F"],
                             vocab_size=w["V"], max_len=w["S"])

    def role_params(self, role: str):
        return weights.encoder_params(
            self.cfg, self.cfg["max_len"],
            gen.sub_seed(self.cfg["weight_seed"], ROLE_STREAM[role]))

    # -- stages ---------------------------------------------------------------
    def _stage_bm25(self, spec):
        from repro.ir import InvertedIndex
        if self.index is None:
            self.index = InvertedIndex.build(
                {"docno": d, "text": t}
                for d, t in zip(self.corpus.docnos, self.texts))
        return self.index.bm25(k1=spec["k1"], b=spec["b"])

    def _stage_text_loader(self, spec):
        from repro.ir import TextLoader
        return TextLoader(dict(zip(self.corpus.docnos, self.texts)))

    def _stage_mono(self, spec):
        from repro.models.cross_encoder import MonoScorer
        s = MonoScorer(self.encoder_config("mono"))
        install(s, self.role_params("mono"))
        return s

    def _stage_duo(self, spec):
        from repro.models.cross_encoder import DuoScorer
        s = DuoScorer(self.encoder_config("duo"), max_docs=spec["max_docs"])
        install(s, self.role_params("duo"))
        return s

    def _stage_dense(self, spec):
        from repro.ir.dense import DenseEncoder, DenseIndex
        enc = DenseEncoder(self.encoder_config("dense"))
        install(enc, self.role_params("dense"))
        self.dense = DenseIndex(enc)
        n = self.cfg["num_passages"]
        self.dense.docnos = [f"p{i}" for i in range(n)]
        self.dense.matrix = dense_rows(self.cfg, self.seed)
        return self.dense.retriever(num_results=1000)

    # -- pipelines ------------------------------------------------------------
    def pipeline(self, expr: str):
        """``a % 10 >> b >> c`` over this world's stage names."""
        out = None
        for part in expr.split(">>"):
            m = re.fullmatch(r"\s*([A-Za-z0-9_]+)\s*(?:%\s*(\d+))?\s*", part)
            if m is None or m.group(1) not in self.stages:
                raise ValueError(f"bad pipeline term {part!r} in {expr!r}; "
                                 f"stages: {sorted(self.stages)}")
            t = self.stages[m.group(1)]
            if m.group(2):
                t = t % int(m.group(2))
            out = t if out is None else out >> t
        return out


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
