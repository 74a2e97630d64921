"""End-to-end driver: train a neural reranker, then evaluate it inside
a cached pipeline against the BM25 baseline.

    PYTHONPATH=src python examples/train_reranker.py [--steps 300]

The training substrate is the same stack the big configs use
(make_train_step -> AdamW + schedules; checkpointing via
repro.distrib) — dimensioned down to CPU.
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Experiment
from repro.ir import InvertedIndex, TextLoader, msmarco_like
from repro.models.common import init_params
from repro.models.cross_encoder import (EncoderConfig, MonoScorer,
                                        encoder_param_specs, encoder_score)
from repro.train import AdamWConfig, linear_warmup_cosine, make_train_step

ap = argparse.ArgumentParser()
ap.add_argument("--steps", type=int, default=300)
args = ap.parse_args()

dataset = msmarco_like(1, scale=0.1)
index = InvertedIndex.build(dataset.get_corpus_iter())
bm25 = index.bm25(num_results=50)
loader = TextLoader(dataset.text_map())
cfg = EncoderConfig(n_layers=2, d_model=64, n_heads=4, d_ff=128,
                    vocab_size=8192, max_len=32)

# ---- build (query, doc, label) training pairs from qrels + BM25 negatives
scorer = MonoScorer(cfg)
qrels = dataset.get_qrels()
text = dataset.text_map()
topics = dataset.get_topics()
q_text = dict(zip(topics["qid"].tolist(), topics["query"].tolist()))
pos = [(q_text[q], text[d]) for q, d in
       zip(qrels["qid"].tolist(), qrels["docno"].tolist())]
rng = np.random.default_rng(0)
docnos = dataset.docs["docno"].tolist()
neg = [(q_text[q], text[docnos[rng.integers(len(docnos))]])
       for q in qrels["qid"].tolist()]
pairs = pos + neg
labels = np.array([1.0] * len(pos) + [0.0] * len(neg), np.float32)
toks = scorer.tokenizer.encode_pairs([q for q, _ in pairs],
                                    [t for _, t in pairs], cfg.max_len)

# ---- train with the shared substrate
params = init_params(encoder_param_specs(cfg), jax.random.key(0))


def loss_fn(p, batch):
    logits = encoder_score(p, batch["toks"], cfg)
    y = batch["y"]
    z = logits.astype(jnp.float32)
    return jnp.mean(jnp.maximum(z, 0) - z * y
                    + jnp.log1p(jnp.exp(-jnp.abs(z))))


step_fn, init_opt = make_train_step(
    loss_fn, AdamWConfig(lr=3e-3, weight_decay=0.01),
    lr_schedule=lambda s: linear_warmup_cosine(s, warmup=20,
                                               total=args.steps))
jitted = jax.jit(step_fn, donate_argnums=(0, 1))
opt = init_opt(params)
B = 64
for step in range(args.steps):
    idx = rng.integers(0, len(pairs), B)
    batch = {"toks": jnp.asarray(toks[idx]), "y": jnp.asarray(labels[idx])}
    params, opt, m = jitted(params, opt, batch)
    if step % 50 == 0 or step == args.steps - 1:
        print(f"step {step:4d} loss {float(m['loss']):.4f}")

# ---- drop the trained weights into the pipeline stage and evaluate
scorer.params = params
res = Experiment(
    [bm25 % 10, bm25 % 50 >> loader >> scorer % 10],
    topics, qrels, ["nDCG@10", "MAP"],
    names=["bm25", "bm25 >> trained-mono"], baseline=0,
    precompute_prefix=True)
print(res)
