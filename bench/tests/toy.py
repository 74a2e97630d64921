"""A toy benchmark in a temporary directory: the real harness, the real
metric readers, and toy-sized configurations, mixes and limits, so that a
whole run fits a CPU test."""
from __future__ import annotations

import json
import os
import shutil
from types import SimpleNamespace

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LENGTHS = {"2": 1, "3": 2, "4": 2, "6": 1}

ENCODER = {"num_hidden_layers": 1, "hidden_size": 32,
           "num_attention_heads": 2, "intermediate_size": 64,
           "vocab_size": 1024, "max_position_embeddings": 64,
           "initializer_range": 0.02, "dtype": "float32"}

CONFIGS = {
    "toy-rerank": dict(ENCODER, name="toy-rerank", max_len=32, weight_seed=5,
                       num_passages=3000,
                       corpus={"words_mean": 20,
                               "words_sigma": 0.45, "words_min": 4,
                               "words_max": 60, "vocab": 4000,
                               "zipf_s": 1.05},
                       stages={"bm25": {"kind": "bm25", "k1": 1.2, "b": 0.75},
                               "text_loader": {"kind": "text_loader"},
                               "mono": {"kind": "mono"},
                               "duo": {"kind": "duo", "max_docs": 10}}),
    "toy-dense": dict(ENCODER, name="toy-dense", max_len=16, weight_seed=6,
                      num_passages=4096,
                      index={"dim": 32,
                             "dtype": "float32", "blocks": 2},
                      stages={"dense": {"kind": "dense"}}),
}

TRAFFIC = {
    "toy-grid": {"driver": "grid",
                 "systems": "bm25 % {k} >> text_loader >> mono % 10 >> duo",
                 "k": [20, 50], "topics_per_iteration": 4,
                 "max_iterations": 40, "measures": ["nDCG@10"],
                 "queries": {"kind": "planted", "lengths": LENGTHS},
                 "check": {"topics": 2}},
    "toy-rerank": {"driver": "open_loop",
                   "pipeline": "bm25 % 30 >> text_loader >> mono",
                   "rate_per_s": 20, "arrival": "poisson",
                   "queries": {"kind": "planted", "lengths": LENGTHS},
                   "service": {"max_batch": 4, "max_wait_ms": 2.0,
                               "exec_workers": 2, "cache": True},
                   "warmup_s": 0.3, "grace_s": 30, "check": {"requests": 3}},
    "toy-dense": {"driver": "open_loop", "pipeline": "dense % 20",
                  "rate_per_s": 20, "arrival": "poisson",
                  "queries": {"kind": "zipf_words", "vocab": 4000,
                              "zipf_s": 1.05, "lengths": LENGTHS},
                  "service": {"max_batch": 4, "max_wait_ms": 2.0,
                              "exec_workers": 2, "cache": False},
                  "warmup_s": 0.3, "grace_s": 30, "check": {"requests": 3}},
}

#: the toy runs on the CPU in float32, where the program and the reference
#: agree to rounding (they read 0 here); the int8 control reads 0.03-0.06
#: and an altered score far more
LIMITS = {"missing": 0.0, "bm25_gap": 1e-6, "mono_gap": 0.003,
          "duo_err": 0.003, "mono_err": 0.003, "dense_err": 0.003,
          "rank_gap": 0.003}

WORKLOADS = [
    {"name": "toy.grid", "config": "toy-rerank", "traffic": "toy-grid",
     "chips": 1, "why": "toy grid"},
    {"name": "toy.rerank", "config": "toy-rerank", "traffic": "toy-rerank",
     "chips": 1, "why": "toy rerank serving"},
    {"name": "toy.dense", "config": "toy-dense", "traffic": "toy-dense",
     "chips": 1, "why": "toy dense serving"},
]

TOY_METRIC = '''"""Toy per-layer metric: topics per iteration of a grid run."""


def read(r):
    c = r.run.counters
    return c["topics"] / c["iterations"] if c.get("iterations") else None
'''


def _metric(name, unit, better, source, cells, layer=None, moves=None):
    m = {"name": name, "unit": unit, "better": better, "source": source,
         "workloads": cells}
    if layer:
        m.update(layer=layer, moves=moves)
    return m


GRID, SERVE = ["toy.grid"], ["toy.rerank", "toy.dense"]
END_TO_END = [
    {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
     "source": "host_clock"},
    dict(_metric("grid_qps", "queries/s", "higher", "host_clock", GRID),
         bound=0.05),
    dict(_metric("serve_p50_ms", "ms", "lower", "host_clock", SERVE),
         bound=0.05),
    dict(_metric("serve_p95_ms", "ms", "lower", "host_clock", SERVE),
         bound=0.1),
    dict(_metric("serve_rps", "requests/s", "higher", "host_clock", SERVE),
         bound=0.01),
]
PER_LAYER = [
    _metric("nodes_per_query.grid", "nodes/query", "lower",
            "program_counter", GRID, "planner", "grid_qps"),
    _metric("cache_hit_share.grid", "%", "higher", "program_counter", GRID,
            "cache data plane", "grid_qps"),
    _metric("device_idle_share.grid", "%", "lower", "device_trace", GRID,
            "device", "grid_qps"),
    _metric("encoder_roofline.grid", "%", "higher", "device_trace", GRID,
            "kernels: encoder programs", "grid_qps"),
    _metric("mfu.grid", "%", "higher", "device_trace", GRID, "whole step",
            "grid_qps"),
    _metric("batch_rows.serve", "rows/batch", "higher", "program_counter",
            SERVE, "executor", "serve_p95_ms"),
    _metric("gen_lag_p95_ms.serve", "ms", "lower", "host_clock", SERVE,
            "load generator", "serve_p95_ms"),
    _metric("device_idle_share.serve", "%", "lower", "device_trace", SERVE,
            "device", "serve_p95_ms"),
    _metric("encoder_roofline.serve", "%", "higher", "device_trace",
            ["toy.rerank"], "kernels: encoder programs", "serve_p95_ms"),
    _metric("topk_roofline.serve", "%", "higher", "device_trace",
            ["toy.dense"], "kernels: dense top-k", "serve_p95_ms"),
    _metric("mfu.serve", "%", "higher", "device_trace", SERVE, "whole step",
            "serve_p95_ms"),
]


def make(tmp: str, *, extra_metric: bool = True) -> str:
    """Lay the toy benchmark out under ``tmp``; returns its root.  It uses
    the harness's own metric readers, whatever cells ``BENCHMARK.json``
    names."""
    root = os.path.join(tmp, "toyroot")
    bench = os.path.join(root, "bench")
    for sub in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(bench, sub), exist_ok=True)
    shutil.copytree(os.path.join(BENCH, "metrics"),
                    os.path.join(bench, "metrics"), dirs_exist_ok=True)
    shutil.copy(os.path.join(BENCH, "peaks.json"), bench)
    spec = {"command": ["python3", "bench/run.py"], "paths": ["bench"],
            "run_seconds": 1,
            "configs": [{"name": n, "source": "toy",
                         "file": f"bench/configs/{n}.json", "reduced": [],
                         "why": "toy"} for n in CONFIGS],
            "workloads": WORKLOADS, "end_to_end": END_TO_END,
            "per_layer": list(PER_LAYER)}
    if extra_metric:
        spec["per_layer"].append(
            {"name": "toy_topics_per_iteration.grid", "unit": "topics",
             "better": "higher", "source": "program_counter", "layer": "toy",
             "moves": "grid_qps", "workloads": ["toy.grid"]})
        with open(os.path.join(bench, "metrics",
                               "toy_topics_per_iteration.grid.py"), "w") as f:
            f.write(TOY_METRIC)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f, indent=1)
    for n, c in CONFIGS.items():
        with open(os.path.join(bench, "configs", f"{n}.json"), "w") as f:
            json.dump(c, f)
    for n, t in TRAFFIC.items():
        with open(os.path.join(bench, "traffic", f"{n}.json"), "w") as f:
            json.dump(t, f)
    for w in WORKLOADS:
        with open(os.path.join(bench, "limits", f"{w['name']}.json"),
                  "w") as f:
            json.dump(LIMITS, f)
    return root


def args(workload: str, seed: int = 7, seconds: float = 1.0, trace: int = 0,
         control: bool = False):
    return SimpleNamespace(workload=workload, seed=seed, seconds=seconds,
                           trace=trace, control=control, sweep=None)


PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
