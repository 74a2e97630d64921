"""Compile every cell's device programs at the cells' own shapes for a
described TPU v5e chip (no chip attached), and check that each fits the
chip's 16 GB: the mono and duo encoders at MiniLM-L-6 widths and 256
tokens, in the largest (1024) and smallest (64) row buckets; the query
encoder at TAS-B widths and 32 tokens; and the dense top-k over one
chip's share of MS MARCO v1 (2,210,456 x 768 float32).

The topology is described inside a fixture, never while a module is
imported, so every pytest-xdist worker collects the same tests.
"""
import json
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.ir.dense import DenseEncoder, _xla_chunk_topk
from repro.models.cross_encoder import EncoderConfig, encoder_score

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")
HBM_BYTES = 16e9


def _config(name: str):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        c = json.load(f)
    return c, EncoderConfig(name=c["name"], n_layers=c["num_hidden_layers"],
                            d_model=c["hidden_size"],
                            n_heads=c["num_attention_heads"],
                            d_ff=c["intermediate_size"],
                            vocab_size=c["vocab_size"], max_len=c["max_len"])


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile is written to the persistent cache but
    # cannot be read back without a chip: keep the cache out of it
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(desc.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


def _params(cfg, sharding):
    from bench.weights import encoder_shapes
    shapes = encoder_shapes(cfg.n_layers, cfg.d_model, cfg.n_heads,
                            cfg.d_ff, cfg.vocab_size, cfg.max_len)
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding),
        shapes, is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(i, int) for i in x))


def _fits(compiled) -> int:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes + m.generated_code_size_in_bytes)
    print(f"memory_analysis: arguments={m.argument_size_in_bytes} "
          f"outputs={m.output_size_in_bytes} temp={m.temp_size_in_bytes} "
          f"code={m.generated_code_size_in_bytes} total={total}")
    assert total < HBM_BYTES
    return total


@pytest.mark.parametrize("rows", [1024, 64])
def test_cross_encoder_compiles_at_minilm_widths(one_chip, rows):
    _, cfg = _config("msmarco-bm25-minilm")
    tokens = jax.ShapeDtypeStruct((rows, cfg.max_len), jnp.int32,
                                  sharding=one_chip)
    compiled = jax.jit(lambda p, t: encoder_score(p, t, cfg)).lower(
        _params(cfg, one_chip), tokens).compile()
    _fits(compiled)


def test_query_encoder_compiles_at_tasb_widths(one_chip):
    _, cfg = _config("msmarco-dense-tasb")
    tokens = jax.ShapeDtypeStruct((64, cfg.max_len), jnp.int32,
                                  sharding=one_chip)
    compiled = jax.jit(lambda p, t: DenseEncoder._embed_fn(
        SimpleNamespace(params=p, cfg=cfg), t)).lower(
        _params(cfg, one_chip), tokens).compile()
    _fits(compiled)


def test_dense_topk_compiles_at_one_chips_share(one_chip):
    c, _ = _config("msmarco-dense-tasb")
    n, dim = c["num_passages"], c["index"]["dim"]
    q = jax.ShapeDtypeStruct((64, dim), jnp.float32, sharding=one_chip)
    m = jax.ShapeDtypeStruct((n, dim), jnp.float32, sharding=one_chip)
    compiled = _xla_chunk_topk.lower(q, m, k=100).compile()
    assert _fits(compiled) > n * dim * 4
