"""Weights and index rows made by the benchmark, on the device, from a seed.

The encoder's weights follow the layout of the program's encoder block
(token and position tables, a stack of pre-norm layers, a final norm and
a linear head) and the published models' own initialisation: every
matrix ~ N(0, ``initializer_range``), every norm scale 1.  Each tree is
made by one jitted call, in float32, the type the program serves in.
The plain reference makes the same trees again from the same seed.
"""
from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp

#: leaf name -> shape, from the widths (L layers, d width, H heads,
#: F feed-forward width, V vocabulary, S positions)
def encoder_shapes(L: int, d: int, H: int, F: int, V: int, S: int) -> Dict:
    hd = d // H
    return {
        "embed": (V, d), "pos": (S, d), "ln_f": (d,), "w_score": (d, 1),
        "layers": {"ln1": (L, d), "ln2": (L, d),
                   "wq": (L, d, H, hd), "wk": (L, d, H, hd),
                   "wv": (L, d, H, hd), "wo": (L, H, hd, d),
                   "w1": (L, d, F), "w2": (L, F, d)},
    }


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


@partial(jax.jit, static_argnames=("widths", "std"))
def _make(key, widths, std):
    shapes = encoder_shapes(*widths)
    leaves, tdef = jax.tree.flatten(shapes, is_leaf=_is_shape)
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes, is_leaf=_is_shape)[0]]
    keys = jax.random.split(key, len(leaves))
    vals = [jnp.ones(s, jnp.float32) if "ln" in p else
            std * jax.random.normal(k, s, jnp.float32)
            for p, s, k in zip(paths, leaves, keys)]
    return jax.tree.unflatten(tdef, vals)


def widths_of(enc: Dict, max_len: int) -> tuple:
    return (int(enc["num_hidden_layers"]), int(enc["hidden_size"]),
            int(enc["num_attention_heads"]), int(enc["intermediate_size"]),
            int(enc["vocab_size"]), int(max_len))


def encoder_params(enc: Dict, max_len: int, seed: int) -> Dict:
    """The encoder's weights for config section ``enc`` at ``max_len``
    positions, from ``seed`` (31-bit)."""
    return _make(jax.random.key(int(seed)), widths_of(enc, max_len),
                 float(enc["initializer_range"]))


def install(target, params) -> None:
    """Give a program stage the benchmark's weights (through its public
    ``params``), after checking that the layouts agree leaf by leaf."""
    have = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)),
                        target.params)
    want = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), params)
    if have != want:
        raise ValueError(f"{type(target).__name__}: the program's weight "
                         f"layout {have} differs from the benchmark's {want}")
    target.params = params


@partial(jax.jit, static_argnames=("rows", "dim"))
def index_rows(key, rows: int, dim: int):
    """``rows`` unit vectors of ``dim`` float32 entries (the corpus side
    of a dense index: embeddings are normalised like the program's)."""
    x = jax.random.normal(key, (rows, dim), jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=1, keepdims=True))
