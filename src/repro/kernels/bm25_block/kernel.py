"""Blocked BM25 scoring as a Pallas TPU kernel.

The first-stage retrieval inner loop, restructured for the TPU memory
hierarchy: CPU BM25 walks per-term postings lists (pointer-chasing —
hostile to the VPU).  The TPU-native formulation processes a dense
(terms × docs) term-frequency tile per grid step:

* grid ``(docs/bd, terms/bt)`` with terms innermost: the per-doc score
  accumulator block stays in VMEM across term tiles;
* each step: load ``tf [bt, bd]``, apply the BM25 saturation
  elementwise on the VPU, weight each term row by its idf and reduce
  over the term (sublane) axis; accumulate into ``scores [1, bd]``;
* tiles are (8×128)-aligned — idf travels as a ``[T, 1]`` column so its
  ``(bt, 1)`` block spans the full lane dimension, which Mosaic
  requires of a block narrower than 128 lanes; zero tf contributes
  exactly 0, so the sparse→dense padding does not change scores.

The postings→tile densification is done host-side per query-term batch
(the tile is the *unit of transfer*, matching how one would stream
posting blocks through VMEM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["bm25_block"]


def _kernel(tf_ref, idf_ref, dl_ref, o_ref, *, k1: float, b: float,
            avg_dl: float, n_t: int):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    tf = tf_ref[...].astype(jnp.float32)          # [bt, bd]
    dl = dl_ref[...].astype(jnp.float32)          # [1, bd]
    idf = idf_ref[...].astype(jnp.float32)        # [bt, 1]
    dl_norm = k1 * (1.0 - b + b * dl / avg_dl)    # [1, bd]
    sat = tf * (k1 + 1.0) / (tf + dl_norm)        # [bt, bd]
    sat = jnp.where(tf > 0, sat, 0.0)
    o_ref[...] += jnp.sum(idf * sat, axis=0, keepdims=True)   # [1, bd]


def bm25_block(tf: jnp.ndarray, idf: jnp.ndarray, doc_len: jnp.ndarray, *,
               k1: float = 1.2, b: float = 0.75, avg_dl: float = 1.0,
               block_t: int = 8, block_d: int = 128,
               interpret: bool = True) -> jnp.ndarray:
    """tf [T,D]; idf [T]; doc_len [D] -> scores [D]."""
    T, D = tf.shape
    assert T % block_t == 0 and D % block_d == 0
    idf2 = idf[:, None]                            # [T, 1]
    dl2 = doc_len[None, :]                         # [1, D]
    out = pl.pallas_call(
        functools.partial(_kernel, k1=k1, b=b, avg_dl=avg_dl,
                          n_t=T // block_t),
        grid=(D // block_d, T // block_t),
        in_specs=[
            pl.BlockSpec((block_t, block_d), lambda di, ti: (ti, di)),
            pl.BlockSpec((block_t, 1), lambda di, ti: (ti, 0)),
            pl.BlockSpec((1, block_d), lambda di, ti: (0, di)),
        ],
        out_specs=pl.BlockSpec((1, block_d), lambda di, ti: (0, di)),
        out_shape=jax.ShapeDtypeStruct((1, D), jnp.float32),
        interpret=interpret,
    )(tf, idf2, dl2)
    return out[0]
