"""Plain reference of the DeepSeek-V2 layers the program's ``LLMScorer``
serves, written from the published equations and the configuration's
keys (its DeepSeek names), independent of ``repro.models.deepseek``:

- per row, the token embedding, then per layer, pre-norm: MLA attention
  (``q = h W_q``; ``[c, k_pe] = h W_kva``; ``[k_nope, v] = RMSNorm(c)
  W_kvb``; RoPE with YaRN frequencies on ``q_pe`` and the shared
  ``k_pe``; ``softmax(q_nope k_nope + q_pe k_pe)`` over earlier positions
  at ``192^-0.5 m^2``; ``o W_o``), then a SwiGLU (the first
  ``first_k_dense_replace`` layers) or the experts: ``p = softmax(h
  W_g)``, the greedy top ``num_experts_per_tok`` with no renormalising,
  ``sum p_e E_e(h)`` plus the shared experts' SwiGLU;
- the score: the final RMSNorm at the last real token, times the head's
  ``yes`` row less its ``no`` row.

It holds the weights in bfloat16 as given and casts one layer at a time
to float32.  Rows go in blocks of ``block``; each routed expert is
computed eagerly over the real tokens routed to it (padding, which no
real token attends to, is not routed).  ``precision`` as in
``bench/reference/encoder.py``: ``"highest"`` (float32 at
``Precision.HIGHEST``, under ``jax.default_matmul_precision``) or
``"int8"`` (every operand rounded to a per-tensor int8 grid: the
control).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from .encoder import _mm, _rms

F32 = jnp.float32


def yarn_tables(cfg: Dict, S: int):
    """cos and sin [S, rope / 2] of YaRN (DeepSeek-V2's
    ``DeepseekV2YarnRotaryEmbedding``), in float64 then float32."""
    rs, dim = cfg["rope_scaling"], cfg["qk_rope_head_dim"]
    base, factor = cfg["rope_theta"], rs["factor"]
    orig = rs["original_max_position_embeddings"]

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (
            2 * math.log(base))

    lo = max(math.floor(corr(rs["beta_fast"])), 0)
    hi = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    extra = base ** -(np.arange(0, dim, 2) / dim)
    mask = 1.0 - ramp
    inv = extra / factor * (1 - mask) + extra * mask
    ang = np.arange(S)[:, None] * inv[None, :]
    f = mscale(factor, rs["mscale"]) / mscale(factor, rs["mscale_all_dim"])
    return (jnp.asarray(np.cos(ang) * f, F32),
            jnp.asarray(np.sin(ang) * f, F32))


def mscale(scale: float, m: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def _rope(x, cos, sin):
    """x [..., S, (heads,) rope]; dimension i turns with i + rope / 2."""
    half = x.shape[-1] // 2
    if x.ndim == 4:
        cos, sin = cos[:, None], sin[:, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


@partial(jax.jit, static_argnames=("eps", "nope", "lora", "scale",
                                   "precision"))
def _attention(p, x, cos, sin, *, eps, nope, lora, scale, precision):
    """x [B, S, d] float32 -> x plus the layer's attention."""
    S = x.shape[1]
    h = _rms(x, p["ln1"], eps)
    q = _mm("bsd,dhk->bshk", h, p["wq"], precision)
    kva = _mm("bsd,dk->bsk", h, p["wkva"], precision)
    kv = _mm("bsc,chk->bshk", _rms(kva[..., :lora], p["kv_norm"], eps),
             p["wkvb"], precision)
    q_pe = _rope(q[..., nope:], cos, sin)
    k_pe = _rope(kva[..., lora:], cos, sin)
    s = (_mm("bqhd,bkhd->bhqk", q[..., :nope], kv[..., :nope], precision)
         + _mm("bqhd,bkd->bhqk", q_pe, k_pe, precision)) * scale
    causal = np.tril(np.ones((S, S), bool))
    a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = _mm("bhqk,bkhd->bqhd", a, kv[..., nope:], precision)
    return x + _mm("bqhd,hdD->bqD", o, p["wo"], precision)


def _swiglu(h, g, u, d, precision):
    a = jax.nn.silu(_mm("...d,df->...f", h, g, precision)) \
        * _mm("...d,df->...f", h, u, precision)
    return _mm("...f,fd->...d", a, d, precision)


@partial(jax.jit, static_argnames=("eps", "precision"))
def _dense_ffn(p, x, *, eps, precision):
    h = _rms(x, p["ln2"], eps)
    return x + _swiglu(h, p["w_gate"], p["w_up"], p["w_down"], precision)


@partial(jax.jit, static_argnames=("eps", "k", "precision"))
def _route(p, x, *, eps, k, precision):
    """The normed input, its top ``k`` expert weights and ids, and x plus
    the shared experts' output."""
    h = _rms(x, p["ln2"], eps)
    probs = jax.nn.softmax(_mm("bsd,de->bse", h, p["router"], precision),
                           axis=-1)
    w, idx = jax.lax.top_k(probs, k)
    return h, w, idx, x + _swiglu(h, p["s_gate"], p["s_up"], p["s_down"],
                                  precision)


@partial(jax.jit, static_argnames=("precision",), donate_argnums=(0,))
def _expert_add(acc, h, rows, wts, g, u, d, e, *, precision):
    """acc [T, d] plus expert ``e``'s output on the rows ``rows`` of h,
    times ``wts`` (0 on the rows that only pad ``rows``)."""
    y = _swiglu(h[rows], g[e], u[e], d[e], precision)
    return acc.at[rows].add(wts[:, None] * y)


def _moe(p, x, real: np.ndarray, *, eps, k, precision):
    """x [B, S, d] plus the experts' output at the real positions
    ``real`` (a [B, S] mask); each expert runs on its tokens alone,
    their count padded to a power of two so that few shapes compile."""
    h, w, idx, out = _route(p, x, eps=eps, k=k, precision=precision)
    B, S, d = x.shape
    pos = np.flatnonzero(real.reshape(-1))
    ids = np.asarray(idx).reshape(B * S, k)[pos]
    wts = np.asarray(w).reshape(B * S, k)[pos]
    h = h.reshape(B * S, d)
    acc = jnp.zeros((B * S, d), F32)
    for e in range(p["w_gate"].shape[0]):
        t, slot = np.nonzero(ids == e)
        if not len(t):
            continue
        n = max(64, 1 << (len(t) - 1).bit_length())
        rows, wt = np.zeros(n, np.int32), np.zeros(n, np.float32)
        rows[:len(t)], wt[:len(t)] = pos[t], wts[t, slot]
        acc = _expert_add(acc, h, rows, wt, p["w_gate"], p["w_up"],
                          p["w_down"], e, precision=precision)
    return out + acc.reshape(B, S, d)


def scores(params: Dict, tokens: np.ndarray, cfg: Dict, precision: str,
           block: int = 64) -> np.ndarray:
    """tokens [N, S] int32 (0 = padding, right-padded) -> scores [N]."""
    N, S = tokens.shape
    if N == 0:
        return np.zeros(0, np.float32)
    eps = float(cfg["rms_norm_eps"])
    nope, lora = cfg["qk_nope_head_dim"], cfg["kv_lora_rank"]
    m = mscale(cfg["rope_scaling"]["factor"],
               cfg["rope_scaling"]["mscale_all_dim"])
    scale = (nope + cfg["qk_rope_head_dim"]) ** -0.5 * m * m
    k = int(cfg["num_experts_per_tok"])
    cos, sin = yarn_tables(cfg, S)
    pad = (-N) % block
    toks = np.concatenate([tokens, np.zeros((pad, S), np.int32)])
    blocks = [toks[i:i + block] for i in range(0, len(toks), block)]
    with jax.default_matmul_precision("highest"):
        xs = [params["embed"][jnp.asarray(b)].astype(F32) for b in blocks]
        for group in ("dense", "moe"):
            for i in range(params[group]["ln1"].shape[0]):
                p = {n: v[i].astype(F32) for n, v in params[group].items()}
                for j, b in enumerate(blocks):
                    x = _attention(p, xs[j], cos, sin, eps=eps, nope=nope,
                                   lora=lora, scale=scale,
                                   precision=precision)
                    xs[j] = (_dense_ffn(p, x, eps=eps, precision=precision)
                             if group == "dense" else
                             _moe(p, x, b != 0, eps=eps, k=k,
                                  precision=precision))
                del p
        out = []
        head = params["head"].astype(F32)
        for b, x in zip(blocks, xs):
            last = np.maximum((b != 0).sum(1) - 1, 0)
            h = _rms(x[np.arange(len(b)), last],
                     params["ln_f"].astype(F32), eps)
            logits = _mm("bd,vd->bv", h, head, precision)
            out.append(np.asarray(logits[:, 0] - logits[:, 1]))
    return np.concatenate(out)[:N]
