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


#: A pointwise scorer kind that the harness does not have: the program's
#: MonoScorer at the stream its stage entry names, with its own reference.
#: ``make`` adds it as a later configuration would, by files and entries.
PW_KIND = '''"""Toy pointwise kind: the program's MonoScorer at the
configuration's BERT widths, weighted from its stage entry's stream."""
import numpy as np

from bench import bert, flops, weights
from bench.drivers import frame_rows
from bench.reference.encoder import run_blocks
from bench.reference.tokens import stack

ROLE = "pointwise"
CORPUS = True


def params(cfg, spec):
    return bert.params(cfg, spec["stream"])


def build(world, name, spec):
    from repro.models.cross_encoder import MonoScorer
    s = MonoScorer(bert.encoder_config(world.cfg, name))
    weights.install(s, params(world.cfg, spec))
    return s


def warm(world, stage, spec, queries):
    c = world.corpus
    for b in (64, 128, 256, 512, 1024):
        stage.transform(frame_rows([
            {"qid": "w", "query": queries.texts[0], "docno": c.docnos[i],
             "text": world.texts[i]} for i in range(b)]))


def work(cfg, spec, real_tokens):
    return bert.work(cfg, real_tokens)


class Reference:
    def __init__(self, cfg, spec, inputs):
        self.S, self.corpus = cfg["max_len"], inputs.corpus
        self.tok = bert.tokens(cfg, inputs)
        self.params = params(cfg, spec)

    def score(self, q, docs, precision):
        docs = list(dict.fromkeys(int(d) for d in docs))
        toks = stack([self.tok.pair(q, self.corpus.doc(d), self.S)
                      for d in docs], self.S)
        s = run_blocks(self.params, toks, head="score", precision=precision)
        return dict(zip(docs, s.astype(np.float64)))

    def real_tokens(self, q, groups):
        docs = list(dict.fromkeys(int(d) for g in groups for d in g))
        return flops.pair_tokens(len(q), self.corpus.lengths()[docs], self.S)
'''
PW_CONFIG = dict(CONFIGS["toy-rerank"], name="toy-pw", weight_seed=8,
                 stages={"bm25": {"kind": "bm25", "k1": 1.2, "b": 0.75},
                         "text_loader": {"kind": "text_loader"},
                         "pw": {"kind": "pw", "stream": 4}})
PW_TRAFFIC = dict(TRAFFIC["toy-grid"],
                  systems="bm25 % {k} >> text_loader >> pw")
PW_CELL = {"name": "toy.pw", "config": "toy-pw", "traffic": "toy-pw-grid",
           "chips": 1, "why": "toy grid over a scorer kind added as files"}


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
    for sub in ("kinds", "metrics"):
        shutil.copytree(os.path.join(BENCH, sub), os.path.join(bench, sub),
                        dirs_exist_ok=True,
                        ignore=shutil.ignore_patterns("__pycache__"))
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
    _add_pw(root, bench)
    return root


def _add_pw(root: str, bench: str) -> None:
    """Add the ``pw`` kind and its cell as a configuration would: new files
    (kind, configuration, mix, limits) and appended entries (the
    configuration, the cell, and the cell in the grid metrics' lists)."""
    files = {("kinds", "pw.py"): PW_KIND,
             ("configs", "toy-pw.json"): json.dumps(PW_CONFIG),
             ("traffic", "toy-pw-grid.json"): json.dumps(PW_TRAFFIC),
             ("limits", "toy.pw.json"): json.dumps(
                 dict(LIMITS, pw_err=LIMITS["mono_err"]))}
    for (sub, name), text in files.items():
        with open(os.path.join(bench, sub, name), "w") as f:
            f.write(text)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "toy-pw", "source": "toy",
                            "file": "bench/configs/toy-pw.json",
                            "reduced": [], "why": "toy"})
    spec["workloads"].append(PW_CELL)
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m.get("workloads") == GRID:
            m["workloads"] = GRID + [PW_CELL["name"]]
    with open(path, "w") as f:
        json.dump(spec, f, indent=1)


def args(workload: str, seed: int = 7, seconds: float = 1.0, trace: int = 0,
         control: bool = False):
    return SimpleNamespace(workload=workload, seed=seed, seconds=seconds,
                           trace=trace, control=control, sweep=None)


PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
