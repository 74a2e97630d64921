"""Pointwise LLM reranker: the program's ``LLMScorer`` running the
configuration's DeepSeek-V2 layers (``repro.models.deepseek``), scored by
the ``yes`` less the ``no`` logit at each pair's last real token; its
reference is the plain ``bench/reference/deepseek.py`` over the same
pair layout, and its control that reference with int8 operands.

Weights are bfloat16, N(0, ``initializer_range``) per matrix and 1 per
norm scale, from the stream of ``weight_seed`` that the stage entry
names; they are made on the device one leaf at a time, a stacked leaf
one layer at a time, so that no float32 copy of a whole stack is live.
The head's two rows are rows ``yes`` and ``no`` (the hash tokenizer's
ids of those words) of a vocabulary-wide head drawn row by row.
"""
from functools import partial
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops, gen
from bench.drivers import frame_rows
from bench.reference import deepseek as ref
from bench.reference.tokens import Tokens, stack
from repro.models import deepseek
from repro.models.common import ParamSpec

ROLE = "pointwise"
CORPUS = True
#: the rows of the program's buckets
BUCKETS = (64, 128)


def model_config(cfg: Dict, stage: str) -> "deepseek.DeepSeekConfig":
    return deepseek.DeepSeekConfig.from_hf(
        cfg, name=f"{cfg['name']}.{stage}", max_len=cfg["max_len"],
        dtype=jnp.dtype(cfg["dtype"]))


def word_ids(cfg: Dict, words) -> np.ndarray:
    """The hash tokenizer's ids of ``words`` (``bench/reference/tokens``)."""
    V = int(cfg["vocab_size"])
    return 3 + gen.fnv1a32_words(list(words)).astype(np.int64) % (V - 3)


@partial(jax.jit, static_argnames=("shape", "std", "dtype"))
def _normal(key, shape, std, dtype):
    if len(shape) < 3:
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    return jax.lax.map(lambda k: (std * jax.random.normal(
        k, shape[1:], jnp.float32)).astype(dtype),
        jax.random.split(key, shape[0]))


@partial(jax.jit, static_argnames=("d", "std", "dtype"))
def _head_rows(key, ids, d, std, dtype):
    return jax.vmap(lambda i: (std * jax.random.normal(
        jax.random.fold_in(key, i), (d,), jnp.float32)).astype(dtype))(ids)


def params(cfg: Dict, spec: Dict) -> Dict:
    """The stage's weights from stream ``spec["stream"]`` of
    ``weight_seed``, in the program's layout."""
    m = model_config(cfg, "weights")
    specs = deepseek.param_specs(m)
    flat, tdef = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    key = jax.random.key(gen.sub_seed(cfg["weight_seed"], spec["stream"]))
    std = float(cfg["initializer_range"])
    leaves = []
    for i, (path, s) in enumerate(flat):
        name = jax.tree_util.keystr(path)
        k = jax.random.fold_in(key, i)
        if s.init == "ones":
            leaves.append(jnp.ones(s.shape, m.dtype))
        elif name == "['head']":
            leaves.append(_head_rows(k, jnp.asarray(word_ids(
                cfg, ["yes", "no"]), jnp.int32), m.d_model, std, m.dtype))
        else:
            leaves.append(_normal(k, s.shape, std, m.dtype))
    return jax.tree.unflatten(tdef, leaves)


def build(world, name, spec):
    from repro.models.cross_encoder import LLMScorer
    return LLMScorer(model_config(world.cfg, name),
                     params=params(world.cfg, spec))


def warm(world, stage, spec, queries):
    """One batch at each bucket."""
    c, texts = world.corpus, world.texts
    for b in BUCKETS:
        rows = [{"qid": "w", "query": queries.texts[i % len(queries.texts)],
                 "docno": c.docnos[i], "text": texts[i]} for i in range(b)]
        stage.transform(frame_rows(rows))


def _widths(cfg: Dict) -> Dict:
    d, H = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    nope, R = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    C, v = int(cfg["kv_lora_rank"]), int(cfg["v_head_dim"])
    E, f = int(cfg["n_routed_experts"]), int(cfg["moe_intermediate_size"])
    fs = f * int(cfg["n_shared_experts"])
    L, Ld = int(cfg["num_hidden_layers"]), int(cfg["first_k_dense_replace"])
    return {"d": d, "H": H, "qk": nope + R, "v": v, "L": L, "Ld": Ld,
            "Lm": L - Ld, "k": int(cfg["num_experts_per_tok"]), "E": E,
            "mla": d * H * (nope + R) + d * (C + R) + C * H * (nope + v)
            + H * v * d,
            "norms": 2 * d + C,
            "dense": 3 * d * int(cfg["intermediate_size"]),
            "expert": 3 * d * f, "shared": 3 * d * fs}


def work(cfg, spec, real_tokens):
    """``llm_flops``: per pair of ``n`` real tokens, per layer the MLA
    projections (2 n x their weights) and causal attention (scores over
    ``nope + rope`` and the weighted sum over ``v`` dims per head, at
    n (n + 1) / 2 positions), the dense SwiGLU or the router, the
    ``num_experts_per_tok`` active experts and the shared ones; and the
    head's two rows.  ``llm_weight_bytes``: the layer weights one call
    reads (every expert of every layer)."""
    w = _widths(cfg)
    n = np.asarray(real_tokens, np.float64)
    att = 2.0 * w["H"] * (w["qk"] + w["v"])
    per_tok = (w["L"] * w["mla"] + w["Ld"] * w["dense"]
               + w["Lm"] * (w["d"] * w["E"] + w["k"] * w["expert"]
                            + w["shared"]))
    total = (2.0 * per_tok * n.sum() + w["L"] * att * (n * (n + 1) / 2).sum()
             + 2.0 * 2 * w["d"] * len(n))
    layer_params = (w["L"] * (w["mla"] + w["norms"]) + w["Ld"] * w["dense"]
                    + w["Lm"] * (w["d"] * w["E"] + w["E"] * w["expert"]
                                 + w["shared"]))
    return {"llm_flops": float(total),
            "llm_weight_bytes": float(
                layer_params * jnp.dtype(cfg["dtype"]).itemsize)}


class Reference:
    def __init__(self, cfg, spec, inputs):
        self.cfg, self.S = cfg, cfg["max_len"]
        self.corpus = inputs.corpus
        self.lens = inputs.corpus.lengths()
        self.tok = Tokens(inputs.word_hash, cfg["vocab_size"],
                          gen.fnv1a32_words(["vs"])[0])
        self.params = params(cfg, spec)
        self._memo: Dict = {}

    def score(self, q: np.ndarray, docs, precision: str) -> Dict[int, float]:
        """Each (query, passage) pair is computed once per precision, so
        the grid's k's share their pairs."""
        docs = list(dict.fromkeys(int(d) for d in docs))
        key = (q.tobytes(), precision)
        new = [d for d in docs if (key, d) not in self._memo]
        if new:
            toks = stack([self.tok.pair(q, self.corpus.doc(d), self.S)
                          for d in new], self.S)
            s = ref.scores(self.params, toks, self.cfg, precision)
            self._memo.update({(key, d): float(v) for d, v in zip(new, s)})
        return {d: self._memo[(key, d)] for d in docs}

    def real_tokens(self, q: np.ndarray, groups: List) -> np.ndarray:
        """One pair per distinct passage of the groups."""
        docs = list(dict.fromkeys(int(d) for g in groups for d in g))
        return flops.pair_tokens(len(q), self.lens[docs], self.S)
