"""The ``llm/dsv2`` stage kind (DeepSeek-V2 as a pointwise reranker)
through the harness on the CPU, at toy widths: added to the toy tree as
a configuration would be (files and appended entries), a sound run is
correct and the int8 control is not; its work is the hand count; the new
metric readers read a recorded span table and trace; and the published
configuration keeps the catalog's numbers."""
import json
import os
from types import SimpleNamespace

import pytest

from bench import run as bench_run
from bench.registry import Registry
from bench.run import Record
from bench.tests import toy

CELL = "toy.llm"
#: DeepSeek-V2 keys at toy widths: d 64, 4 heads, kv_lora 32, rope 16,
#: 8 routed experts top-2, 1 shared, one dense and two MoE layers
TOY_WIDTHS = {"num_hidden_layers": 3, "hidden_size": 64,
              "num_attention_heads": 4, "num_key_value_heads": 4,
              "kv_lora_rank": 32, "qk_nope_head_dim": 16,
              "qk_rope_head_dim": 16, "v_head_dim": 16,
              "n_routed_experts": 8, "num_experts_per_tok": 2,
              "moe_intermediate_size": 32, "n_shared_experts": 1,
              "intermediate_size": 128, "vocab_size": 1024, "max_len": 32}
#: the toy runs bf16 against the float32 reference on the CPU: sound
#: runs read 0.004-0.008 here, the int8 control 0.06-0.12
LIMITS = {"missing": 0.0, "bm25_gap": 1e-6, "llm_err": 0.03,
          "rank_gap": 0.03}


def _published():
    with open(os.path.join(toy.BENCH, "configs",
                           "msmarco-bm25-dsv2lite.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def reg(tmp_path_factory):
    root = toy.make(str(tmp_path_factory.mktemp("bench")))
    bench = os.path.join(root, "bench")
    cfg = dict(_published(), name="toy-dsv2", weight_seed=9,
               num_passages=3000, corpus=toy.CONFIGS["toy-rerank"]["corpus"],
               **TOY_WIDTHS)
    files = {("configs", "toy-dsv2.json"): cfg,
             ("traffic", "toy-llm-grid.json"): dict(
                 toy.TRAFFIC["toy-grid"],
                 systems="bm25 % {k} >> text_loader >> llm"),
             ("limits", f"{CELL}.json"): LIMITS}
    for (sub, name), obj in files.items():
        with open(os.path.join(bench, sub, name), "w") as f:
            json.dump(obj, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "toy-dsv2", "source": "toy",
                            "file": "bench/configs/toy-dsv2.json",
                            "reduced": [], "why": "toy"})
    spec["workloads"].append({"name": CELL, "config": "toy-dsv2",
                              "traffic": "toy-llm-grid", "chips": 1,
                              "why": "toy grid over the llm/dsv2 kind"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m.get("workloads") == toy.GRID + ["toy.pw"]:
            m["workloads"] = m["workloads"] + [CELL]
    with open(path, "w") as f:
        json.dump(spec, f)
    return Registry(root, bench)


@pytest.mark.parametrize("seed", [2 ** 31 + 11, 5])
def test_a_sound_run_is_correct(reg, seed):
    res = bench_run.run_cell(reg, toy.args(CELL, seed=seed), toy.PEAKS)
    assert res["correct"], res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["check"]) == {"missing", "bm25_gap", "llm_err",
                                 "rank_gap", "failed", "compiles_in_window"}
    assert set(res["metrics"]) == {"setup_s", "grid_qps"}


def test_the_control_is_not_correct(reg):
    res = bench_run.run_cell(reg, toy.args(CELL, seed=3, control=True),
                             toy.PEAKS)
    assert not res["correct"], res["check"]
    assert res["check"]["llm_err"]["value"] > LIMITS["llm_err"]


def test_work_is_the_hand_count_for_two_pairs(reg):
    kind = reg.kind("llm/dsv2")
    cfg = reg.config("toy-dsv2")
    w = kind.work(cfg, cfg["stages"]["llm"], [5, 9])
    # per token: MLA 64*4*32 + 64*48 + 32*4*32 + 4*16*64 = 19456 over 3
    # layers; the dense SwiGLU 3*64*128 = 24576; per MoE layer the router
    # 64*8 = 512, two experts 2*3*64*32 = 12288 and the shared one 6144
    per_tok = 3 * 19456 + 24576 + 2 * (512 + 12288 + 6144)
    # attention: 2 * 4 heads * (32 + 16) per causal position per layer
    att = 3 * 2 * 4 * 48 * (5 * 6 / 2 + 9 * 10 / 2)
    head = 2 * 2 * 64 * 2
    assert w["llm_flops"] == 2 * per_tok * 14 + att + head
    # bf16 layer weights: MLA and norms (2*64 + 32) in 3 layers, the
    # dense SwiGLU, and per MoE layer the router, 8 experts, the shared
    layers = 3 * (19456 + 160) + 24576 + 2 * (512 + 8 * 6144 + 6144)
    assert w["llm_weight_bytes"] == 2 * layers


def test_the_readers_read_the_span_table_and_the_trace(reg, monkeypatch):
    from repro.core import trace
    table = {"spans": {}, "counters": {"moe.rows": 300.0,
                                       "moe.slots": 1200.0}}
    monkeypatch.setattr(trace, "summary", lambda: table)
    t = {"window_s": 10.0, "busy_s": 8.0,
         "programs": {"jit_llm_score": {"s": 4.0, "n": 2},
                      "jit__lambda": {"s": 1.0, "n": 1}}}
    work = {"llm_flops": 2e12, "llm_weight_bytes": 1e10}
    rec = Record(SimpleNamespace(counters={}), 1.0, t, work, toy.PEAKS)
    assert reg.reader("moe_fill.grid")(rec) == pytest.approx(25.0)
    # 2e12 / 1e12 FLOP/s = 2 s, against 2 calls x 1e10 B / 1e11 B/s
    assert reg.reader("llm_roofline.grid")(rec) == pytest.approx(50.0)
    bare = Record(SimpleNamespace(counters={}), 1.0, None, {}, toy.PEAKS)
    assert reg.reader("moe_fill.grid")(bare) is None
    assert reg.reader("llm_roofline.grid")(bare) is None
    table["counters"] = {}
    assert reg.reader("moe_fill.grid")(rec) is None


def test_the_published_configuration_keeps_the_catalogs_numbers():
    c = _published()
    catalog = {"first_k_dense_replace": 1, "hidden_size": 2048,
               "intermediate_size": 10944, "kv_lora_rank": 512,
               "max_position_embeddings": 163840,
               "moe_intermediate_size": 1408, "moe_layer_freq": 1,
               "n_group": 1, "n_routed_experts": 64, "n_shared_experts": 2,
               "num_attention_heads": 16, "num_experts_per_tok": 6,
               "num_key_value_heads": 16, "qk_nope_head_dim": 128,
               "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
               "rope_theta": 10000, "routed_scaling_factor": 1,
               "topk_group": 1, "v_head_dim": 128, "vocab_size": 102400,
               "rope_scaling": {"beta_fast": 32, "beta_slow": 1,
                                "factor": 40, "mscale": 0.707,
                                "mscale_all_dim": 0.707,
                                "original_max_position_embeddings": 4096,
                                "type": "yarn"}}
    assert {k: c[k] for k in catalog} == catalog
    assert c["num_hidden_layers"] == 7
    assert c["published_num_hidden_layers"] == 27
    spec = Registry().spec
    entry = {e["name"]: e for e in spec["configs"]}["msmarco-bm25-dsv2lite"]
    assert entry["reduced"] == ["num_hidden_layers", "num_passages"]
    cell = Registry().workload("grid.bm25-dsv2lite.rerank100")
    assert cell["chips"] == 1
    names = {m["name"] for m in Registry().metrics(cell["name"], trace=True)}
    assert {"llm_roofline.grid", "moe_fill.grid", "mfu.grid",
            "device_idle_share.grid"} <= names
    assert "encoder_roofline.grid" not in names
