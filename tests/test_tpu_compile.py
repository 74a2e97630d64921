"""Compile the main path's kernels and jitted stages for a described
TPU v5e chip — no chip attached (the TPU compiler is installed with
libtpu and compiles for a topology it is only told about).

What interpret mode cannot show, this does: Mosaic refuses blocks that
are not (8, 128)-tiled, VMEM overuse and unsupported primitives at
compile time.  Nothing runs, so these say nothing about results or
times.  The topology is described inside a fixture — never while a
module is imported — so every pytest-xdist worker collects the same
tests and only the worker given this file loads the TPU library.
"""
import functools
import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.ir.dense import _xla_chunk_topk
from repro.kernels.bm25_block import bm25_block_op
from repro.kernels.dense_topk import dense_topk_op
from repro.kernels.flash_attention import flash_attention_op
from repro.models import deepseek
from repro.models.common import abstract_params, init_params
from repro.models.cross_encoder import (EncoderConfig, encoder_param_specs,
                                        encoder_score)
from repro.serve.registry import _mono_config


@pytest.fixture(scope="module")
def topo():
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
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("k", [128, 1024])
def test_dense_topk_compiles(one_chip, k, dtype):
    q = _sds((8, 768), dtype, one_chip)
    c = _sds((65536, 768), dtype, one_chip)
    compiled = _compile(functools.partial(dense_topk_op, k=k,
                                          interpret=False), q, c)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("widths", ["registry", "defaults"])
def test_encoder_score_compiles(one_chip, widths):
    cfg = _mono_config() if widths == "registry" else EncoderConfig()
    params = jax.eval_shape(
        lambda: init_params(encoder_param_specs(cfg), jax.random.key(0)))
    params = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), params)
    tokens = _sds((1024, cfg.max_len), jnp.int32, one_chip)
    compiled = _compile(lambda p, t: encoder_score(p, t, cfg),
                        params, tokens)
    assert compiled.memory_analysis() is not None


def test_xla_chunk_topk_compiles(one_chip):
    q = _sds((16, 32), jnp.float32, one_chip)
    chunk = _sds((9000, 32), jnp.float32, one_chip)
    _compile(functools.partial(_xla_chunk_topk, k=100), q, chunk)


def test_flash_attention_compiles(one_chip):
    q, k, v = (_sds((8, 12, 512, 64), jnp.bfloat16, one_chip)
               for _ in range(3))
    compiled = _compile(functools.partial(flash_attention_op,
                                          interpret=False), q, k, v)
    assert "tpu_custom_call" in compiled.as_text()


def test_bm25_block_compiles(one_chip):
    tf = _sds((256, 16384), jnp.float32, one_chip)
    idf = _sds((256,), jnp.float32, one_chip)
    doc_len = _sds((16384,), jnp.float32, one_chip)
    compiled = _compile(functools.partial(bm25_block_op, avg_dl=55.0,
                                          interpret=False), tf, idf, doc_len)
    assert "tpu_custom_call" in compiled.as_text()


def test_llm_scorer_compiles_at_dsv2_lite_widths(one_chip):
    """DeepSeek-V2-Lite's first pipeline stage (7 of 27 layers, every
    expert held) at the scorer's largest bucket, 128 rows x 256 tokens:
    7.6 GB of bf16 weights as arguments, the grouped expert matmuls as
    Mosaic kernels, and the whole program within the chip's 16 GB."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench", "configs",
        "msmarco-bm25-dsv2lite.json")
    with open(path) as f:
        c = json.load(f)
    cfg = deepseek.DeepSeekConfig.from_hf(c, name=c["name"],
                                          max_len=c["max_len"])
    params = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip),
                          abstract_params(deepseek.param_specs(cfg)))
    tokens = _sds((128, cfg.max_len), jnp.int32, one_chip)
    compiled = _compile(lambda p, t: deepseek.score(p, t, cfg),
                        params, tokens)
    assert "tpu_custom_call" in compiled.as_text()
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes > 7.5e9
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 14e9
