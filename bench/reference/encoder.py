"""Plain reference of the encoder block the program serves.

The equations of the program's encoder, written out in ``jax.numpy``
with no kernels, caches or batching tricks: token plus position
embedding; per layer RMSNorm, multi-head self-attention over the
non-padding keys, residual, RMSNorm, a tanh-GELU feed-forward, residual;
a final RMSNorm; a mean pool over the non-padding tokens; then a linear
head (cross-encoder score) or an L2 normalisation (dense embedding).
Departures from the published BERT block are the program's and are
listed in each configuration's ``assumed``.

``precision`` picks how every matrix product is taken:

- ``"highest"``: float32 inputs at ``Precision.HIGHEST`` — the reference;
- ``"int8"``: each operand rounded to a symmetric per-tensor int8 grid
  (scale = absmax / 127), products accumulated in float32 — the control,
  one step below the bfloat16 inputs the program's matrix units take.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def _q8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.round(x / s) * s


def _mm(spec, a, b, precision):
    if precision == "int8":
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms(x, scale, eps=1e-6):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


@partial(jax.jit, static_argnames=("head", "precision"))
def forward(params, tokens, *, head: str, precision: str):
    """tokens [B, S] int32 (0 = padding) -> scores [B] (``head="score"``)
    or unit embeddings [B, d] (``head="embed"``)."""
    mask = tokens != 0
    x = params["embed"][tokens] + params["pos"][None, :tokens.shape[1]]
    hd = params["layers"]["wq"].shape[-1]
    bias = jnp.where(mask, 0.0, -1e30)[:, None, None, :]
    n_layers = params["layers"]["wq"].shape[0]
    for i in range(n_layers):
        p = jax.tree.map(lambda a: a[i], params["layers"])
        h = _rms(x, p["ln1"])
        q = _mm("bsd,dnh->bsnh", h, p["wq"], precision)
        k = _mm("bsd,dnh->bsnh", h, p["wk"], precision)
        v = _mm("bsd,dnh->bsnh", h, p["wv"], precision)
        s = _mm("bqnh,bsnh->bnqs", q, k, precision) / math.sqrt(hd)
        a = jax.nn.softmax(s + bias, axis=-1)
        o = _mm("bnqs,bsnh->bqnh", a, v, precision)
        x = x + _mm("bqnh,nhd->bqd", o, p["wo"], precision)
        h = _rms(x, p["ln2"])
        f = jax.nn.gelu(_mm("bsd,df->bsf", h, p["w1"], precision))
        x = x + _mm("bsf,fd->bsd", f, p["w2"], precision)
    x = _rms(x, params["ln_f"])
    m = mask[..., None].astype(jnp.float32)
    pooled = (x * m).sum(1) / jnp.maximum(m.sum(1), 1.0)
    if head == "score":
        return _mm("bd,do->bo", pooled, params["w_score"], precision)[:, 0]
    return pooled / jnp.maximum(
        jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-6)


def run_blocks(params, tokens: np.ndarray, *, head: str, precision: str,
               block: int = 64) -> np.ndarray:
    """``forward`` over ``tokens`` in blocks of ``block`` rows (the last
    block padded with empty rows), so that it fits beside nothing else."""
    outs = []
    for lo in range(0, len(tokens), block):
        chunk = tokens[lo:lo + block]
        pad = np.zeros((block - len(chunk), tokens.shape[1]), np.int32)
        out = forward(params, jnp.asarray(np.concatenate([chunk, pad])),
                      head=head, precision=precision)
        outs.append(np.asarray(out)[:len(chunk)])
    return np.concatenate(outs) if outs else np.zeros((0,), np.float32)
