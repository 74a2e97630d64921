"""Blocked query×corpus matmul with a fused streaming top-k.

The dense-retrieval inner loop (PLAID's lesson, arXiv:2205.09707):
latency is won by pruning candidates *inside* the scoring kernel
instead of materializing the full [Q, N] score matrix and sorting it
on the host.  TPU-native formulation, combining the bm25_block layout
with flash_attention's streaming-state schedule:

* grid ``(Q/bq, N/bd)`` with the doc axis innermost: the per-query
  running top-k state ``(vals [bq,k], idxs [bq,k])`` lives in VMEM
  scratch across doc tiles and the output block is written once on the
  last step — the corpus streams through VMEM exactly once;
* each step: a ``[bq,d]×[d,bd]`` contraction on the MXU, then a k-pass
  selection merge of the fresh tile into the running state on the VPU
  (max + masked-min index per pass — no sort primitive needed);
* tie-break is total and deterministic: descending score, then
  ascending global doc index — the same rule ``ref.dense_topk_ref``
  (``lax.top_k``) and the host merge in ``ir/dense.py`` apply, which
  is what makes ``RankCutoff`` fusion sound (top-k is a prefix of
  top-n);
* padded doc rows are masked by block-level iota comparison against
  ``nd_valid`` (score → −∞, index → sentinel), so ops.py's tile
  padding never surfaces in results.

Validated in interpret mode against ``ref.dense_topk_ref`` (the
container is CPU-only; TPU is the compile target).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["dense_topk", "NEG_INF", "IDX_PAD"]

NEG_INF = -1e30
IDX_PAD = 2 ** 30          # > any real doc index; sorts last on ties


def _kernel(q_ref, c_ref, v_ref, i_ref, vals_scr, idxs_scr, *,
            k: int, bd: int, n_d: int, nd_valid: int):
    di = pl.program_id(1)

    @pl.when(di == 0)
    def _init():
        vals_scr[...] = jnp.full_like(vals_scr, NEG_INF)
        idxs_scr[...] = jnp.full_like(idxs_scr, IDX_PAD)

    q = q_ref[...].astype(jnp.float32)               # [bq, d]
    c = c_ref[...].astype(jnp.float32)               # [bd, d]
    # full f32 contraction: ranks must match the f32 reference, which a
    # single bf16 MXU pass would not guarantee on near-ties
    s = jax.lax.dot_general(q, c, (((1,), (1,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)  # [bq, bd]
    bq = s.shape[0]
    dpos = di * bd + jax.lax.broadcasted_iota(jnp.int32, (bq, bd), 1)
    valid = dpos < nd_valid                   # mask padded doc rows
    s = jnp.where(valid, s, NEG_INF)
    dpos = jnp.where(valid, dpos, IDX_PAD)

    # merge the fresh tile into the running state: top-k of the k+bd
    # candidates by k selection passes (each: row max, then min index
    # among the maxima — indices are unique per row, so exactly one
    # real candidate is retired per pass)
    cv = jnp.concatenate([vals_scr[...], s], axis=1)       # [bq, k+bd]
    ci = jnp.concatenate([idxs_scr[...], dpos], axis=1)
    col = jax.lax.broadcasted_iota(jnp.int32, vals_scr.shape, 1)

    def select(j, carry):
        cv, ci, ov, oi = carry
        m = jnp.max(cv, axis=1, keepdims=True)             # [bq, 1]
        hit = cv >= m
        pick = jnp.min(jnp.where(hit, ci, IDX_PAD), axis=1,
                       keepdims=True)
        chosen = hit & (ci == pick)
        cv = jnp.where(chosen, NEG_INF, cv)
        ci = jnp.where(chosen, IDX_PAD, ci)
        ov = jnp.where(col == j, m, ov)
        oi = jnp.where(col == j, pick, oi)
        return cv, ci, ov, oi

    _, _, ov, oi = jax.lax.fori_loop(
        0, k, select,
        (cv, ci, jnp.full_like(vals_scr, NEG_INF),
         jnp.full_like(idxs_scr, IDX_PAD)))
    vals_scr[...] = ov
    idxs_scr[...] = oi

    @pl.when(di == n_d - 1)
    def _finalize():
        v_ref[...] = vals_scr[...]
        i_ref[...] = idxs_scr[...]


def dense_topk(q: jnp.ndarray, c: jnp.ndarray, *, k: int,
               nd_valid: int | None = None, block_q: int = 8,
               block_d: int = 128, interpret: bool = True):
    """q [Q, d] query embeddings; c [N, d] corpus matrix.

    Returns ``(vals [Q, k] f32, idxs [Q, k] i32)`` — the top-k inner
    products per query with global doc indices, ordered by descending
    score then ascending index.  Q/N must be multiples of
    block_q/block_d (ops.py pads; ``nd_valid`` marks the unpadded doc
    count).  On hardware the output lane dim wants ``k % 128 == 0``
    (ops.py rounds up when compiling); interpret mode takes any k.
    """
    Q, d = q.shape
    N = c.shape[0]
    assert Q % block_q == 0 and N % block_d == 0
    assert 1 <= k
    nd_valid = N if nd_valid is None else nd_valid
    n_d = N // block_d
    kernel = functools.partial(_kernel, k=k, bd=block_d, n_d=n_d,
                               nd_valid=nd_valid)
    return pl.pallas_call(
        kernel,
        grid=(Q // block_q, n_d),
        in_specs=[
            pl.BlockSpec((block_q, d), lambda qi, di: (qi, 0)),
            pl.BlockSpec((block_d, d), lambda qi, di: (di, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, k), lambda qi, di: (qi, 0)),
            pl.BlockSpec((block_q, k), lambda qi, di: (qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Q, k), jnp.float32),
            jax.ShapeDtypeStruct((Q, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, k), jnp.float32),
            pltpu.VMEM((block_q, k), jnp.int32),
        ],
        interpret=interpret,
    )(q, c)
