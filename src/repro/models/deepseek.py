"""DeepSeek-V2 decoder layers as a pointwise reranker's scorer.

The score of a tokenized (query, passage) row is the ``yes`` logit less
the ``no`` logit at the row's last real token (the monoT5 convention on
a decoder).  Rows are right-padded with id 0, so under the causal mask
no real token sees a pad and the last nonzero id is the read position.

Each layer is pre-norm (RMSNorm, eps from the config): MLA attention,
then a feed-forward that is a SwiGLU in the first ``first_k_dense``
layers and a mixture of experts in the rest.

- **MLA** (no query LoRA): ``q = h W_q`` [S, H, nope + rope];
  ``[c, k_pe] = h W_kva``; ``c = RMSNorm(c)``; ``[k_nope, v] = c W_kvb``;
  RoPE with YaRN frequencies on ``q_pe`` and on ``k_pe``, which all heads
  share; causal softmax in float32 at ``(nope + rope)^-0.5 · m²``, with
  ``m`` YaRN's attention factor of ``mscale_all_dim``.  The rotation
  pairs dimension ``i`` with ``i + rope / 2`` (HF's interleaved layout is
  a fixed permutation of ``W_q`` and ``W_kva`` columns).
- **MoE**: ``p = softmax(h W_g)`` in float32 over the routed experts,
  greedy top-k with no renormalising, ``y = Σ p_e E_e(h) + S(h)`` with
  SwiGLU experts ``E`` and the shared experts ``S`` as one SwiGLU.  The
  dispatch is dropless: the (token, expert) rows are sorted by expert
  and run through a grouped matmul (megablox ``gmm``), so a row's result
  never depends on what else is in its batch.

Matrix products take ``cfg.dtype`` operands (bfloat16 as published)
with float32 accumulation; the residual stream, norms, router and
softmaxes are float32.  Weights are arguments of the jitted programs,
never constants in them.
"""
from __future__ import annotations

import hashlib
import math
from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas.ops.tpu.megablox import gmm

from .common import ParamSpec, rms_norm

__all__ = ["DeepSeekConfig", "param_specs", "yarn_inv_freq", "yarn_mscale", "softmax_scale", "score",
           "digest"]

F32 = jnp.float32


@dataclass(frozen=True)
class DeepSeekConfig:
    """Widths under the program's names; ``from_hf`` reads a published
    ``config.json``."""
    name: str = "dsv2-toy"
    n_layers: int = 3
    first_k_dense: int = 1
    d_model: int = 64
    n_heads: int = 4
    kv_lora_rank: int = 32
    qk_nope_dim: int = 16
    qk_rope_dim: int = 16
    v_head_dim: int = 16
    n_routed_experts: int = 8
    n_experts_per_tok: int = 2
    moe_d_ff: int = 32
    n_shared_experts: int = 1
    d_ff: int = 128
    vocab_size: int = 1024
    max_len: int = 32
    rms_eps: float = 1e-6
    rope_theta: float = 1e4
    rope_factor: float = 40.0
    rope_original_max: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 0.707
    mscale_all_dim: float = 0.707
    dtype: Any = jnp.bfloat16

    @classmethod
    def from_hf(cls, c: Dict, *, name: str, max_len: int,
                dtype: Any = jnp.bfloat16) -> "DeepSeekConfig":
        """The config of a DeepSeek-V2 ``config.json`` (``c``), checking
        the settings this block implements."""
        want = {"q_lora_rank": None, "scoring_func": "softmax",
                "topk_method": "greedy", "norm_topk_prob": False,
                "routed_scaling_factor": 1, "n_group": 1, "topk_group": 1,
                "moe_layer_freq": 1, "hidden_act": "silu",
                "attention_bias": False}
        bad = {k: c.get(k) for k, v in want.items() if c.get(k) != v}
        rs = c["rope_scaling"]
        if bad or rs.get("type") != "yarn":
            raise ValueError(f"unsupported DeepSeek-V2 settings: {bad}, "
                             f"rope_scaling {rs}")
        return cls(
            name=name, n_layers=int(c["num_hidden_layers"]),
            first_k_dense=int(c["first_k_dense_replace"]),
            d_model=int(c["hidden_size"]),
            n_heads=int(c["num_attention_heads"]),
            kv_lora_rank=int(c["kv_lora_rank"]),
            qk_nope_dim=int(c["qk_nope_head_dim"]),
            qk_rope_dim=int(c["qk_rope_head_dim"]),
            v_head_dim=int(c["v_head_dim"]),
            n_routed_experts=int(c["n_routed_experts"]),
            n_experts_per_tok=int(c["num_experts_per_tok"]),
            moe_d_ff=int(c["moe_intermediate_size"]),
            n_shared_experts=int(c["n_shared_experts"]),
            d_ff=int(c["intermediate_size"]),
            vocab_size=int(c["vocab_size"]), max_len=int(max_len),
            rms_eps=float(c["rms_norm_eps"]),
            rope_theta=float(c["rope_theta"]),
            rope_factor=float(rs["factor"]),
            rope_original_max=int(rs["original_max_position_embeddings"]),
            beta_fast=float(rs["beta_fast"]),
            beta_slow=float(rs["beta_slow"]),
            mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"]), dtype=dtype)

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.first_k_dense


def _layer_specs(cfg: DeepSeekConfig, L: int, moe: bool) -> Dict:
    D, H, C, R = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank, cfg.qk_rope_dim
    qk, vd, dt = cfg.qk_nope_dim + R, cfg.v_head_dim, cfg.dtype

    def w(*shape):
        return ParamSpec((L,) + shape, ("layers",) + (None,) * len(shape),
                         dt, init="normal", init_scale=0.02)

    def ones(n):
        return ParamSpec((L, n), ("layers", "norm"), dt, init="ones")

    p = {"ln1": ones(D), "wq": w(D, H, qk), "wkva": w(D, C + R),
         "kv_norm": ones(C), "wkvb": w(C, H, cfg.qk_nope_dim + vd),
         "wo": w(H, vd, D), "ln2": ones(D)}
    if not moe:
        return dict(p, w_gate=w(D, cfg.d_ff), w_up=w(D, cfg.d_ff),
                    w_down=w(cfg.d_ff, D))
    E, f = cfg.n_routed_experts, cfg.moe_d_ff
    fs = f * cfg.n_shared_experts
    return dict(p, router=w(D, E), w_gate=w(E, D, f), w_up=w(E, D, f),
                w_down=w(E, f, D), s_gate=w(D, fs), s_up=w(D, fs),
                s_down=w(fs, D))


def param_specs(cfg: DeepSeekConfig) -> Dict:
    """Embedding, the dense and the MoE layer stacks, the final norm and
    the LM head's ``yes`` and ``no`` rows (``head`` [2, d]); matrices
    N(0, 0.02) and norm scales 1 under ``common.init_params``."""
    D = cfg.d_model
    return {
        "embed": ParamSpec((cfg.vocab_size, D), ("vocab", "d_model"),
                           cfg.dtype, init="normal", init_scale=0.02),
        "dense": _layer_specs(cfg, cfg.first_k_dense, moe=False),
        "moe": _layer_specs(cfg, cfg.n_moe_layers, moe=True),
        "ln_f": ParamSpec((D,), ("norm",), cfg.dtype, init="ones"),
        "head": ParamSpec((2, D), (None, "d_model"), cfg.dtype,
                          init="normal", init_scale=0.02),
    }


# -- YaRN ----------------------------------------------------------------------

def yarn_mscale(scale: float, m: float) -> float:
    """YaRN's attention factor ``0.1 m ln(scale) + 1`` (1 at scale <= 1)."""
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def softmax_scale(cfg: DeepSeekConfig) -> float:
    m = yarn_mscale(cfg.rope_factor, cfg.mscale_all_dim)
    return (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5 * m * m


def yarn_inv_freq(cfg: DeepSeekConfig) -> np.ndarray:
    """Per rotated pair, ``f_inter (1 - r) + f_extra r``: the published
    frequency ``f_extra = θ^(-2i/dim)`` where ``r`` is 1, the frequency
    divided by the factor where it is 0, and ``r`` falls linearly between
    the correction dimensions of ``beta_fast`` and ``beta_slow`` at
    ``original_max`` positions."""
    dim = cfg.qk_rope_dim

    def corr(rotations: float) -> float:
        return (dim * math.log(cfg.rope_original_max
                               / (rotations * 2 * math.pi))
                / (2 * math.log(cfg.rope_theta)))

    lo = max(math.floor(corr(cfg.beta_fast)), 0)
    hi = min(math.ceil(corr(cfg.beta_slow)), dim - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0.0, 1.0)
    extra = 1.0 / cfg.rope_theta ** (np.arange(0, dim, 2) / dim)
    r = 1.0 - ramp
    return (extra / cfg.rope_factor * (1.0 - r) + extra * r).astype(
        np.float32)


def _rope_tables(cfg: DeepSeekConfig, S: int):
    """cos and sin [S, rope / 2]; YaRN's cos/sin factor is
    ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``."""
    ang = jnp.arange(S, dtype=F32)[:, None] * jnp.asarray(yarn_inv_freq(cfg))
    f = (yarn_mscale(cfg.rope_factor, cfg.mscale)
         / yarn_mscale(cfg.rope_factor, cfg.mscale_all_dim))
    return jnp.cos(ang) * f, jnp.sin(ang) * f


def _rotate(x, cos, sin):
    """x [B, S, heads, rope] float32; pairs (i, i + rope / 2)."""
    half = x.shape[-1] // 2
    c, s = cos[:, None, :], sin[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


# -- layers --------------------------------------------------------------------

def _mm(spec: str, a, b, cfg: DeepSeekConfig):
    """A product of ``cfg.dtype`` operands, accumulated in float32."""
    return jnp.einsum(spec, a.astype(cfg.dtype), b.astype(cfg.dtype),
                      preferred_element_type=F32)


def _mla(p, x, cos, sin, cfg: DeepSeekConfig):
    B, S, _ = x.shape
    nope, R = cfg.qk_nope_dim, cfg.qk_rope_dim
    h = rms_norm(x, p["ln1"], cfg.rms_eps)
    q = _mm("bsd,dhk->bshk", h, p["wq"], cfg)
    kva = _mm("bsd,dk->bsk", h, p["wkva"], cfg)
    c = rms_norm(kva[..., :cfg.kv_lora_rank], p["kv_norm"], cfg.rms_eps)
    kv = _mm("bsc,chk->bshk", c, p["wkvb"], cfg)
    k_pe = _rotate(kva[..., None, cfg.kv_lora_rank:], cos, sin)
    q = jnp.concatenate([q[..., :nope], _rotate(q[..., nope:], cos, sin)],
                        axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_pe, (B, S, cfg.n_heads, R))], axis=-1)
    s = _mm("bqhd,bkhd->bhqk", q, k, cfg) * softmax_scale(cfg)
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1)
    o = _mm("bhqk,bkhd->bqhd", probs, kv[..., nope:], cfg)
    return _mm("bqhd,hdD->bqD", o, p["wo"], cfg)


def _swiglu(h, w_gate, w_up, w_down, cfg: DeepSeekConfig):
    a = jax.nn.silu(_mm("...d,df->...f", h, w_gate, cfg)) \
        * _mm("...d,df->...f", h, w_up, cfg)
    return _mm("...f,fd->...d", a, w_down, cfg)


def _tile(n: int, t: int = 512) -> int:
    """``t`` where it divides ``n``, else all of ``n`` (a whole dimension
    is always a legal block)."""
    return t if n % t == 0 else n


def _row_tile(m: int) -> int:
    """The largest power of two up to 512 that divides ``m``."""
    t = 512
    while m % t:
        t //= 2
    return t


def _grouped(x, w, sizes, cfg: DeepSeekConfig, interpret: bool):
    """``x`` rows grouped by expert (``sizes`` rows each) times their
    expert's ``w`` [E, k, n]."""
    m, k = x.shape
    tiling = (_row_tile(m), _tile(k), _tile(w.shape[-1]))
    return gmm(x.astype(cfg.dtype), w, sizes, preferred_element_type=cfg.dtype,
               tiling=tiling, interpret=interpret)


def _moe(p, h, cfg: DeepSeekConfig, interpret: bool):
    """h [T, d] normed -> routed plus shared experts' output [T, d]."""
    T, d = h.shape
    k, E = cfg.n_experts_per_tok, cfg.n_routed_experts
    probs = jax.nn.softmax(_mm("td,de->te", h, p["router"], cfg), axis=-1)
    w, idx = jax.lax.top_k(probs, k)                  # greedy, [T, k]
    flat = idx.reshape(-1)
    order = jnp.argsort(flat, stable=True)            # (token, slot) by expert
    sizes = jnp.bincount(flat, length=E).astype(jnp.int32)
    xs = h.astype(cfg.dtype)[order // k]
    a = jax.nn.silu(_grouped(xs, p["w_gate"], sizes, cfg, interpret)
                    .astype(F32)) \
        * _grouped(xs, p["w_up"], sizes, cfg, interpret).astype(F32)
    ys = _grouped(a, p["w_down"], sizes, cfg, interpret)
    y = ys[jnp.argsort(order)].reshape(T, k, d)       # back to (token, slot)
    routed = (y.astype(F32) * w[..., None]).sum(axis=1)
    return routed + _swiglu(h, p["s_gate"], p["s_up"], p["s_down"], cfg)


def score(params: Dict, tokens: jnp.ndarray, cfg: DeepSeekConfig, *,
          interpret: bool = False) -> jnp.ndarray:
    """tokens [B, S] int32, right-padded with 0 -> scores [B] float32.

    ``interpret`` runs the grouped matmul's kernel in Pallas's interpret
    mode (off a TPU)."""
    B, S = tokens.shape
    cos, sin = _rope_tables(cfg, S)
    x = jnp.take(params["embed"], tokens, axis=0, mode="clip").astype(F32)

    def layer(scope, ffn):
        def body(x, p):
            with jax.named_scope("llm/mla"):
                x = x + _mla(p, x, cos, sin, cfg)
            with jax.named_scope(scope):
                x = x + ffn(p, rms_norm(x, p["ln2"], cfg.rms_eps))
            return x, None
        return body

    def dense(p, h):
        return _swiglu(h, p["w_gate"], p["w_up"], p["w_down"], cfg)

    def moe(p, h):
        return _moe(p, h.reshape(B * S, -1), cfg, interpret).reshape(h.shape)

    x, _ = jax.lax.scan(layer("llm/ffn", dense), x, params["dense"])
    x, _ = jax.lax.scan(layer("llm/moe", moe), x, params["moe"])
    last = jnp.maximum(jnp.sum(tokens != 0, axis=1) - 1, 0)
    h = rms_norm(x[jnp.arange(B), last], params["ln_f"], cfg.rms_eps)
    logits = _mm("bd,vd->bv", h, params["head"], cfg)
    return logits[:, 0] - logits[:, 1]


@jax.jit
def _digest(params):
    def one(a):
        bits = jax.lax.bitcast_convert_type(
            a, jnp.uint16 if a.dtype.itemsize == 2 else jnp.uint32
        ).astype(jnp.uint32).reshape(-1)
        pos = jnp.arange(bits.size, dtype=jnp.uint32) % 65521 + 1
        return jnp.stack([jnp.sum(bits), jnp.sum(bits * pos)])
    return jnp.stack([one(a) for a in jax.tree.leaves(params)])


def digest(params: Dict) -> str:
    """A short hex digest of the weights' bits: SHA-256 of two wrapping
    sums per leaf (plain and position-weighted), computed on the
    weights' device."""
    sums = np.asarray(_digest(params)).astype("<u4").tobytes()
    return hashlib.sha256(sums).hexdigest()[:16]
