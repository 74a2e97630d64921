"""Neural cross-encoder scorers as pipeline stages (MonoT5/DuoT5 roles).

``MonoScorer`` is a *pointwise* reranker: each (query, document) pair is
scored independently — the probability-ranking-principle pattern that
makes ScorerCache sound (paper §4.2).

``DuoScorer`` is a *pairwise* reranker: the score of a document depends
on the other retrieved documents for that query.  Exactly as the paper
notes for DuoT5 (§5), it is **not amenable to caching**; it declares
``cacheable=False`` and ``auto_cache`` refuses it.

Both wrap a small bidirectional JAX encoder over hash-tokenized text.
``LLMScorer`` is a pointwise reranker over a DeepSeek-V2 decoder
(``models/deepseek.py``), read at each pair's last real token.
Execution details that matter on TPU/XLA:

* miss batches run through ``BucketedRunner`` so the jitted scorer sees
  O(log n) distinct shapes (see caching/bucketing.py);
* compiled executables are shared across pipeline stages via the
  process-wide ``CompileCache`` — two experiments instantiating the same
  scorer shape pay XLA compilation once.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..caching.bucketing import DEVICE_BATCH_FLOOR, BucketedRunner
from ..caching.compile_cache import default_compile_cache
from ..core import trace
from ..core.frame import ColFrame
from ..core.pipeline import Transformer, add_ranks
from ..ir.tokenizer import HashTokenizer
from . import deepseek
from .common import ParamSpec, init_params, rms_norm

__all__ = ["EncoderConfig", "encoder_param_specs", "encoder_score",
           "MonoScorer", "DuoScorer", "LLMScorer"]


@dataclass(frozen=True)
class EncoderConfig:
    name: str = "mono-ce"
    n_layers: int = 2
    d_model: int = 128
    n_heads: int = 4
    d_ff: int = 256
    vocab_size: int = 32768
    max_len: int = 64
    dtype: Any = jnp.float32

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


def encoder_param_specs(cfg: EncoderConfig) -> Dict:
    L, D, H, hd, F, V = (cfg.n_layers, cfg.d_model, cfg.n_heads,
                         cfg.head_dim, cfg.d_ff, cfg.vocab_size)
    dt = cfg.dtype
    return {
        "embed": ParamSpec((V, D), ("vocab", "d_model"), dt, init="embed",
                           init_scale=0.02),
        "pos": ParamSpec((cfg.max_len, D), ("seq", "d_model"), dt,
                         init="embed", init_scale=0.02),
        "layers": {
            "ln1": ParamSpec((L, D), ("layers", "norm"), dt, init="ones"),
            "ln2": ParamSpec((L, D), ("layers", "norm"), dt, init="ones"),
            "wq": ParamSpec((L, D, H, hd),
                            ("layers", "d_model", "heads", "head_dim"), dt),
            "wk": ParamSpec((L, D, H, hd),
                            ("layers", "d_model", "heads", "head_dim"), dt),
            "wv": ParamSpec((L, D, H, hd),
                            ("layers", "d_model", "heads", "head_dim"), dt),
            "wo": ParamSpec((L, H, hd, D),
                            ("layers", "heads", "head_dim", "d_model_out"),
                            dt),
            "w1": ParamSpec((L, D, F), ("layers", "d_model", "d_ff"), dt),
            "w2": ParamSpec((L, F, D), ("layers", "d_ff", "d_model_out"), dt),
        },
        "ln_f": ParamSpec((D,), ("norm",), dt, init="ones"),
        "w_score": ParamSpec((D, 1), ("d_model", None), dt),
    }


def encoder_score(params: Dict, tokens: jnp.ndarray,
                  cfg: EncoderConfig) -> jnp.ndarray:
    """tokens [B, max_len] int32 -> scores [B] (bidirectional encoder)."""
    B, S = tokens.shape
    mask = (tokens != 0)
    x = jnp.take(params["embed"], tokens, axis=0, mode="clip")
    x = x + params["pos"][None, :S]
    scale = 1.0 / math.sqrt(cfg.head_dim)
    bias = jnp.where(mask, 0.0, -1e30).astype(jnp.float32)[:, None, None, :]

    def layer_body(x, layer):
        h = rms_norm(x, layer["ln1"])
        q = jnp.einsum("bsd,dnh->bsnh", h, layer["wq"])
        k = jnp.einsum("bsd,dnh->bsnh", h, layer["wk"])
        v = jnp.einsum("bsd,dnh->bsnh", h, layer["wv"])
        scores = jnp.einsum("bqnh,bsnh->bnqs", q, k).astype(jnp.float32)
        probs = jax.nn.softmax(scores * scale + bias, axis=-1).astype(x.dtype)
        attn = jnp.einsum("bnqs,bsnh->bqnh", probs, v)
        x = x + jnp.einsum("bqnh,nhd->bqd", attn, layer["wo"])
        h2 = rms_norm(x, layer["ln2"])
        ff = jnp.einsum("bsf,fd->bsd",
                        jax.nn.gelu(jnp.einsum("bsd,df->bsf", h2,
                                               layer["w1"])),
                        layer["w2"])
        return x + ff, None

    x, _ = jax.lax.scan(layer_body, x, params["layers"])
    x = rms_norm(x, params["ln_f"])
    # masked mean pool -> linear score
    m = mask[..., None].astype(x.dtype)
    pooled = (x * m).sum(1) / jnp.maximum(m.sum(1), 1.0)
    return jnp.einsum("bd,do->bo", pooled, params["w_score"])[:, 0]


def _scoped_score(role: str, params: Dict, tokens: jnp.ndarray,
                  cfg: EncoderConfig) -> jnp.ndarray:
    with jax.named_scope(role):
        return encoder_score(params, tokens, cfg)


class _PairScorer(Transformer):
    """What the (query, passage) scorers share: the tokenizing of a
    call's pairs under the ``encoder.tokenize`` span."""

    #: the stage's role (``mono``, ``duo``, ``llm``), naming its
    #: tokenizing spans and its programs' ops
    role: str

    def _tokenize(self, queries, texts) -> np.ndarray:
        with trace.span("encoder.tokenize", role=self.role,
                        pairs=len(queries)):
            toks = self.tokenizer.encode_pairs(queries, texts,
                                               self.cfg.max_len)
        self.invocations += len(queries)
        return toks

    def _score_rows(self, inp: ColFrame) -> ColFrame:
        """Each row's (query, text) pair scored on its own, then ranked."""
        if len(inp) == 0:
            return inp
        scores = self._score_pairs(inp["query"].tolist(),
                                   inp["text"].tolist())
        return add_ranks(inp.assign(score=scores))


class _EncoderBase(_PairScorer):
    def __init__(self, cfg: EncoderConfig, seed: int = 0):
        self.cfg = cfg
        self.seed = seed
        self.params = init_params(encoder_param_specs(cfg),
                                  jax.random.key(seed))
        self.tokenizer = HashTokenizer(cfg.vocab_size)
        self.invocations = 0     # pairs actually scored (cache accounting)

        def _score(tokens):
            # the jitted lambda keeps its name (``jit__lambda``) and its
            # persistent-cache key; the scope names the ops by role
            return default_compile_cache.call(
                f"{type(self).__name__}:{cfg.name}",
                lambda t: _scoped_score(self.role, self.params, t, self.cfg),
                tokens)

        self._runner = BucketedRunner(_score, floor=DEVICE_BATCH_FLOOR,
                                      max_bucket=1024)

    def _score_pairs(self, queries, texts) -> np.ndarray:
        return np.asarray(self._runner(self._tokenize(queries, texts)),
                          dtype=np.float64)


class MonoScorer(_EncoderBase):
    """Pointwise neural reranker (R→R).  Cache-safe (paper §4.2)."""

    role = "mono"

    input_columns = frozenset({"qid", "query", "docno", "text"})
    key_columns = ("query", "docno")
    value_columns = ("score",)
    cacheable = True

    def transform(self, inp: ColFrame) -> ColFrame:
        return self._score_rows(inp)

    def signature(self):
        return ("MonoScorer", self.cfg.name, self.cfg.n_layers,
                self.cfg.d_model, self.seed)


class DuoScorer(_EncoderBase):
    """Pairwise reranker (R→R): score of d_i depends on the other
    candidates (sum over j of s(d_i ≻ d_j)).  NOT cacheable — §5."""

    role = "duo"

    input_columns = frozenset({"qid", "query", "docno", "text"})
    value_columns = ("score",)
    cacheable = False

    def __init__(self, cfg: EncoderConfig, seed: int = 1, max_docs: int = 10):
        super().__init__(cfg, seed)
        self.max_docs = int(max_docs)

    def transform(self, inp: ColFrame) -> ColFrame:
        if len(inp) == 0:
            return inp
        out_parts = []
        for (qid,), idx in inp.group_indices(["qid"]).items():
            grp = inp.take(idx)
            if "rank" in grp:
                grp = grp.sort_values(["rank"])
            grp = grp.head(self.max_docs)
            n = len(grp)
            texts = grp["text"].tolist()
            query = grp["query"][0]
            if n <= 1:
                out_parts.append(grp.assign(
                    score=np.zeros(n, dtype=np.float64)))
                continue
            pairs = [(i, j) for i in range(n) for j in range(n) if i != j]
            # each pair's document side is its two passages, so each
            # passage is tokenized once per topic
            s = self._score_pairs([query] * len(pairs),
                                  [(texts[i], texts[j]) for i, j in pairs])
            agg = np.zeros(n, dtype=np.float64)
            for (i, j), v in zip(pairs, s):
                agg[i] += v          # wins of i over j
                agg[j] -= v
            out_parts.append(grp.assign(score=agg))
        return add_ranks(ColFrame.concat(out_parts))

    def signature(self):
        return ("DuoScorer", self.cfg.name, self.cfg.n_layers,
                self.cfg.d_model, self.seed, self.max_docs)


class LLMScorer(_PairScorer):
    """Pointwise decoder-LM reranker (R→R): a pair's score is the ``yes``
    logit less the ``no`` logit at its last real token
    (``models/deepseek.py``).  Cache-safe like ``MonoScorer``.

Its weights (``deepseek.param_specs``' layout) are given at
    construction and are arguments of the jitted program
    ``jit_llm_score``: scorers of one config share their compiled
    buckets whatever their weights, nothing of the weights is compiled
    in, and ``signature()`` names them by a digest of their bits.
    Buckets hold
    64 or 128 rows: one bucket's expert rows and attention scores must
    fit beside a pipeline stage's weights on one chip.

    Counters, while the profiler records: ``moe.rows``, the (token,
    expert) rows of real tokens over the MoE layers, and ``moe.slots``,
    the rows the dispatch computes (every slot of every bucket row).
    """

    role = "llm"

    input_columns = frozenset({"qid", "query", "docno", "text"})
    key_columns = ("query", "docno")
    value_columns = ("score",)
    cacheable = True

    #: most rows of one call of the program
    MAX_BUCKET = 128

    def __init__(self, cfg: deepseek.DeepSeekConfig, params: Dict):
        self.cfg = cfg
        self.params = params
        self._digest: Optional[str] = None
        self.tokenizer = HashTokenizer(cfg.vocab_size)
        self.invocations = 0
        # (token, expert) rows per token over the MoE layers
        self._routed = cfg.n_experts_per_tok * cfg.n_moe_layers
        interpret = jax.default_backend() != "tpu"

        def llm_score(params, tokens):
            return deepseek.score(params, tokens, cfg, interpret=interpret)

        def run(tokens):
            trace.count("moe.slots", tokens.size * self._routed)
            return default_compile_cache.call(
                f"LLMScorer:{cfg.name}", llm_score, self.params, tokens)

        self._runner = BucketedRunner(run, floor=DEVICE_BATCH_FLOOR,
                                      max_bucket=self.MAX_BUCKET)

    def _score_pairs(self, queries, texts) -> np.ndarray:
        toks = self._tokenize(queries, texts)
        trace.count("moe.rows",
                    lambda: int(np.count_nonzero(toks)) * self._routed)
        return np.asarray(self._runner(toks), dtype=np.float64)

    def transform(self, inp: ColFrame) -> ColFrame:
        return self._score_rows(inp)

    def signature(self):
        if self._digest is None:
            self._digest = deepseek.digest(self.params)
        c = self.cfg
        return ("LLMScorer", c.name, c.n_layers, c.d_model,
                c.n_routed_experts, c.max_len, str(jnp.dtype(c.dtype)),
                self._digest)
