"""The DeepSeek-V2 scorer (``models/deepseek.py``) and its pipeline stage
``LLMScorer`` against the plain reference (``bench/reference/
deepseek.py``) on seeded random weights, at toy widths on the CPU: d 64,
4 heads, kv_lora 32, rope 16, 8 routed experts top-2, 1 shared expert,
one dense and two MoE layers, the published YaRN settings."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import deepseek as ref
from repro.caching.compile_cache import default_compile_cache
from repro.core import trace
from repro.core.frame import ColFrame
from repro.models import deepseek as ds
from repro.models.common import init_params
from repro.models.cross_encoder import LLMScorer

#: the toy widths under the published config's keys
HF = {"num_hidden_layers": 3, "first_k_dense_replace": 1,
      "hidden_size": 64, "num_attention_heads": 4, "kv_lora_rank": 32,
      "qk_nope_head_dim": 16, "qk_rope_head_dim": 16, "v_head_dim": 16,
      "n_routed_experts": 8, "num_experts_per_tok": 2,
      "moe_intermediate_size": 32, "n_shared_experts": 1,
      "intermediate_size": 128, "vocab_size": 1024, "rms_norm_eps": 1e-6,
      "rope_theta": 10000, "q_lora_rank": None, "scoring_func": "softmax",
      "topk_method": "greedy", "norm_topk_prob": False,
      "routed_scaling_factor": 1, "n_group": 1, "topk_group": 1,
      "moe_layer_freq": 1, "hidden_act": "silu", "attention_bias": False,
      "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                       "mscale": 0.707, "mscale_all_dim": 0.707,
                       "original_max_position_embeddings": 4096,
                       "type": "yarn"}}
#: the published settings of DeepSeek-V2-Lite that YaRN reads
PUBLISHED = dict(HF, qk_nope_head_dim=128, qk_rope_head_dim=64)
S = 32
#: bf16 program against the float32 reference, over the spread of the
#: reference's scores: each product rounds its operands to 8 mantissa
#: bits (relative 2^-9) through three layers, and a near-tie in the
#: router can send a token to another expert.  Two weight seeds read
#: 0.005 and 0.008; the int8 control reads 0.067 and 0.115
BF16_TOL = 0.03


def _cfg(dtype):
    return ds.DeepSeekConfig.from_hf(HF, name="toy", max_len=S, dtype=dtype)


def _params(dtype, seed=0):
    """The published initialisation: N(0, 0.02), norm scales 1."""
    return init_params(ds.param_specs(_cfg(dtype)), jax.random.key(seed))


@pytest.fixture(scope="module")
def tokens():
    rng = np.random.default_rng(7)
    lens = rng.integers(3, S + 1, 96)
    t = rng.integers(3, HF["vocab_size"], (96, S)).astype(np.int32)
    t[np.arange(S)[None, :] >= lens[:, None]] = 0
    t[:, 0] = 1
    return t


def _score(params, toks, dtype):
    return np.asarray(ds.score(params, jnp.asarray(toks),
                               _cfg(dtype), interpret=True), np.float64)


def _ref(params, toks, precision):
    return np.asarray(ref.scores(params, toks, HF, precision),
                      np.float64)


def _err(got, want):
    return float(np.abs(got - want).max() / want.std())


def test_float32_matches_the_reference(tokens):
    p = _params(jnp.float32)
    want = _ref(p, tokens[:64], "highest")
    assert want.std() > 0.1
    assert _err(_score(p, tokens[:64], jnp.float32), want) < 1e-4


@pytest.mark.parametrize("seed", [0, 1])
def test_bfloat16_matches_the_reference_and_int8_does_not(tokens, seed):
    p = _params(jnp.bfloat16, seed)
    want = _ref(p, tokens[:64], "highest")
    assert _err(_score(p, tokens[:64], jnp.bfloat16), want) < BF16_TOL
    # the control (int8 operands) is caught by the bf16 tolerance
    assert _err(_ref(p, tokens[:64], "int8"), want) > BF16_TOL


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_rows_score_does_not_depend_on_its_bucket(tokens, dtype):
    """Dropless: the same rows alone, in another order, beside other rows
    and in a bucket twice as large all score bit for bit alike."""
    p = _params(dtype)
    alone = _score(p, tokens[:64], dtype)
    order = np.random.default_rng(1).permutation(64)
    shuffled = _score(p, tokens[:64][order], dtype)
    np.testing.assert_array_equal(shuffled, alone[order])
    mixed = np.concatenate([tokens[64:], tokens[:32]])
    np.testing.assert_array_equal(_score(p, mixed, dtype)[32:], alone[:32])
    big = np.concatenate([tokens[:64], tokens[32:96]])
    np.testing.assert_array_equal(_score(p, big, dtype)[:64], alone)


def test_right_padding_does_not_move_a_score(tokens):
    """16 more pad positions: causal attention never reads them, and a
    longer masked softmax row sums its terms in another order, so the
    scores agree to float32 rounding."""
    p = _params(jnp.float32)
    short = _score(p, tokens[:64], jnp.float32)
    long = _score(p, np.pad(tokens[:64], ((0, 0), (0, 16))), jnp.float32)
    np.testing.assert_allclose(long, short, rtol=0, atol=1e-6 * short.std())


def test_yarn_at_the_published_settings():
    cfg = ds.DeepSeekConfig.from_hf(PUBLISHED, name="lite", max_len=256)
    # the correction dimensions at 4096 positions: 64 ln(4096 / (32 2pi))
    # / (2 ln 1e4) = 10.47 -> 10 and 64 ln(4096 / 2pi) / (2 ln 1e4)
    # = 22.50 -> 23
    i = np.arange(32)
    extra = 10000.0 ** (-2 * i / 64)
    r = 1 - np.clip((i - 10) / 13, 0, 1)
    want = extra / 40 * (1 - r) + extra * r
    np.testing.assert_allclose(ds.yarn_inv_freq(cfg), want, rtol=1e-6)
    assert ds.yarn_inv_freq(cfg)[:10] == pytest.approx(extra[:10])
    assert ds.yarn_inv_freq(cfg)[23:] == pytest.approx(extra[23:] / 40)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert m == pytest.approx(1.2608, abs=1e-4)
    assert ds.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m)
    cos, sin = ref.yarn_tables(PUBLISHED, 4)
    np.testing.assert_allclose(np.asarray(cos)[3], np.cos(3 * want),
                               rtol=1e-6)


def test_unsupported_settings_are_refused():
    with pytest.raises(ValueError, match="norm_topk_prob"):
        ds.DeepSeekConfig.from_hf(dict(HF, norm_topk_prob=True),
                                  name="x", max_len=S)


def _frame(n, tag=""):
    return ColFrame.from_dicts([
        {"qid": "q", "query": "what eats cats", "docno": f"d{tag}{i}",
         "text": "small cats " * (1 + i % 7) + f"word{i}"}
        for i in range(n)])


def test_the_stage_scores_like_the_model_and_keys_its_weights():
    cfg = _cfg(jnp.bfloat16)
    a = LLMScorer(cfg, params=_params(jnp.bfloat16, seed=1))
    b = LLMScorer(cfg, params=_params(jnp.bfloat16, seed=2))
    misses = default_compile_cache.stats.compile_misses
    out_a, out_b = a.transform(_frame(70)), b.transform(_frame(70))
    # one compiled program for both: the weights are its arguments
    assert default_compile_cache.stats.compile_misses - misses <= 1
    assert not np.array_equal(out_a["score"], out_b["score"])
    toks = a.tokenizer.encode_pairs(["what eats cats"] * 70,
                                    _frame(70)["text"].tolist(), S)
    want = np.asarray(ds.score(a.params, jnp.asarray(np.concatenate(
        [toks, toks[:58]])), cfg, interpret=True))[:70]
    got = dict(zip(out_a["docno"].tolist(), out_a["score"].tolist()))
    np.testing.assert_array_equal(
        [got[f"d{i}"] for i in range(70)], want.astype(np.float64))
    assert a.signature() != b.signature()
    assert a.signature() == LLMScorer(cfg, params=a.params).signature()
    assert a.cacheable and a.key_columns == ("query", "docno")


def test_the_program_is_named_and_counts_its_expert_rows(tmp_path):
    s = LLMScorer(_cfg(jnp.bfloat16), _params(jnp.bfloat16, seed=4))
    fns = []
    call = default_compile_cache.call
    trace.reset()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(default_compile_cache, "call",
                       lambda name, fn, *a, **k: fns.append(fn)
                       or call(name, fn, *a, **k))
            s.transform(_frame(70))
    finally:
        jax.profiler.stop_trace()
    c = trace.summary()["counters"]
    trace.reset()
    toks = s.tokenizer.encode_pairs(["what eats cats"] * 70,
                                    _frame(70)["text"].tolist(), S)
    routed = 2 * 2            # top-2 over two MoE layers
    assert c["moe.rows"] == np.count_nonzero(toks) * routed
    assert c["moe.slots"] == 128 * S * routed
    assert fns[0].__name__ == "llm_score"
    text = jax.jit(fns[0]).lower(s.params, toks[:64]).compile().as_text()
    # the trace reads the program by its module name; its ops by scope
    assert text.startswith("HloModule jit_llm_score,")
    assert "llm/moe" in text and "llm/mla" in text and "llm/ffn" in text
