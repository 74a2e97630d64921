"""Roofline-term derivation from compiled dry-run artifacts.

Three terms per (arch × shape × mesh), in seconds:

    compute    = HLO_FLOPs_per_device / peak_FLOPs_per_chip
    memory     = HLO_bytes_per_device / HBM_bw_per_chip
    collective = collective_bytes_per_device / ICI_link_bw

``compiled.cost_analysis()`` runs *after* SPMD partitioning, so its
flops/bytes are already per-device (global/chips).  Collective bytes are
not in cost_analysis: we parse the post-partitioning HLO text and sum
the result-shape bytes of every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute (async ``-start`` forms
counted once, ``-done`` skipped).

Hardware constants: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI (assignment-specified).

`roofline_fraction` = ideal_model_time / estimated_step_time, where
ideal_model_time assumes the model's *useful* FLOPs (6·N·D style) run at
peak and estimated_step_time = max of the three terms.  This is the
score reported in EXPERIMENTS.md §Perf.
"""
from __future__ import annotations

import math
import re
from dataclasses import asdict, dataclass, field
from typing import Any, Dict, Optional, Tuple

PEAK_FLOPS = 197e12         # bf16 / chip
HBM_BW = 819e9              # bytes/s / chip
ICI_BW = 50e9               # bytes/s / link

# host-side roofline priors for the plan compiler's cost model
# (core/cost.py): sustained throughput of the *Python/numpy host path*
# IR stages actually run on, far below chip peak.  Deliberately rough —
# these only seed cost estimates until real measurements replace them.
HOST_PEAK_FLOPS = 2e10      # sustained host FLOP/s (BLAS-ish)
HOST_MEM_BW = 5e9           # bytes/s effective host streaming
#: per-query Python dispatch floor added to every host estimate: frame
#: plumbing and interpreter overhead dominate tiny workloads, and an
#: optimistic prior must never claim a stage is cheaper than a cache
#: round-trip (only *measurements* may justify dropping a cache)
HOST_DISPATCH_OVERHEAD_S = 5e-5

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLLECTIVE_RE = re.compile(
    r"=\s*(\([^)]*\)|[\w\[\],{}\s]*?)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")

__all__ = ["PEAK_FLOPS", "HBM_BW", "ICI_BW", "HOST_PEAK_FLOPS",
           "HOST_MEM_BW", "HOST_DISPATCH_OVERHEAD_S",
           "parse_collective_bytes", "RooflineReport",
           "analyze_compiled", "estimate_stage_cost", "lm_model_flops",
           "gnn_model_flops", "recsys_model_flops", "model_flops_for"]


def estimate_stage_cost(stage) -> Optional[float]:
    """Analytic per-query cost prior (seconds) for kernel-backed
    pipeline stages — the plan compiler's cold-start estimate before
    any run has been measured (``core/cost.py``).

    Duck-typed on the stage class name so this module never imports the
    IR layer: a ``DenseRetriever`` costs one row of the blocked matmul
    + top-k against its corpus matrix, a ``BM25Retriever`` one TAAT
    postings traversal.  The figure is
    ``HOST_DISPATCH_OVERHEAD_S + max(flops / HOST_PEAK_FLOPS,
    bytes / HOST_MEM_BW)`` — the host roofline plus the per-query
    Python dispatch floor.  Returns ``None`` for stages with no
    analytic model (generic transformers fall back to the cost model's
    defaults).
    """
    name = type(stage).__name__
    if name == "DenseRetriever":
        matrix = getattr(getattr(stage, "index", None), "matrix", None)
        shape = getattr(matrix, "shape", None)
        if not shape or len(shape) != 2:
            return None
        n_docs, dim = int(shape[0]), int(shape[1])
        itemsize = int(getattr(matrix, "itemsize", 4) or 4)
        k = int(getattr(stage, "num_results", 100))
        flops = 2.0 * n_docs * dim            # one query row × corpus
        byts = float(n_docs * dim * itemsize) # stream the matrix
        topk = float(n_docs) * max(1.0, math.log2(max(2, k)))
        return HOST_DISPATCH_OVERHEAD_S + max(
            (flops + topk) / HOST_PEAK_FLOPS, byts / HOST_MEM_BW)
    if name == "BM25Retriever":
        index = getattr(stage, "index", None)
        n_docs = getattr(index, "n_docs", None)
        if n_docs is None:
            docnos = getattr(index, "docnos", None)
            n_docs = len(docnos) if docnos is not None else None
        if not n_docs:
            return None
        # TAAT: ~q_terms postings lists, each a fraction of the corpus;
        # model ≈ 4 query terms × 10% selectivity × (ids+tfs+score work)
        postings = 4 * 0.1 * float(n_docs)
        flops = 8.0 * postings                # idf/tf saturation per hit
        byts = 12.0 * postings                # int32 id + f32 tf + accum
        k = int(getattr(stage, "num_results", 1000))
        sort = float(n_docs) * max(1.0, math.log2(max(2, min(k, n_docs))))
        return HOST_DISPATCH_OVERHEAD_S + max(
            (flops + sort) / HOST_PEAK_FLOPS, byts / HOST_MEM_BW)
    return None


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def parse_collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum result-shape bytes per collective op type (per device)."""
    out: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        if "-done" in line:
            continue
        m = _COLLECTIVE_RE.search(line)
        if not m:
            continue
        shape_str, op = m.group(1), m.group(2)
        b = _shape_bytes(shape_str)
        out[op] = out.get(op, 0) + b
    out["total"] = sum(v for k, v in out.items() if k != "total")
    return out


@dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    n_devices: int
    kind: str
    # raw per-device quantities
    hlo_flops: float = 0.0
    hlo_bytes: float = 0.0
    collective_bytes: float = 0.0
    collective_breakdown: Dict[str, int] = field(default_factory=dict)
    # memory analysis (bytes per device)
    argument_bytes: int = 0
    output_bytes: int = 0
    temp_bytes: int = 0
    peak_bytes: int = 0
    # derived terms (seconds)
    compute_s: float = 0.0
    memory_s: float = 0.0
    collective_s: float = 0.0
    dominant: str = ""
    # useful-work accounting
    model_flops_global: float = 0.0
    useful_ratio: float = 0.0           # model_flops / (hlo_flops × chips)
    roofline_fraction: float = 0.0      # ideal model time / est step time
    est_step_s: float = 0.0
    compile_s: float = 0.0
    notes: str = ""

    def to_dict(self) -> Dict[str, Any]:
        return asdict(self)

    def summary(self) -> str:
        return (f"{self.arch:24s} {self.shape:14s} {self.mesh:10s} "
                f"compute={self.compute_s:.3e}s memory={self.memory_s:.3e}s "
                f"coll={self.collective_s:.3e}s dom={self.dominant:10s} "
                f"useful={self.useful_ratio:.2f} "
                f"roofline={self.roofline_fraction:.2%}")


def analyze_compiled(compiled, *, arch: str, shape: str, mesh_name: str,
                     n_devices: int, kind: str,
                     model_flops_global: float,
                     compile_s: float = 0.0,
                     notes: str = "") -> RooflineReport:
    cost = compiled.cost_analysis() or {}
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    hlo = compiled.as_text()
    coll = parse_collective_bytes(hlo)

    rep = RooflineReport(arch=arch, shape=shape, mesh=mesh_name,
                         n_devices=n_devices, kind=kind,
                         hlo_flops=flops, hlo_bytes=byts,
                         collective_bytes=float(coll.get("total", 0)),
                         collective_breakdown=coll,
                         model_flops_global=model_flops_global,
                         compile_s=compile_s, notes=notes)
    try:
        ma = compiled.memory_analysis()
        rep.argument_bytes = int(getattr(ma, "argument_size_in_bytes", 0))
        rep.output_bytes = int(getattr(ma, "output_size_in_bytes", 0))
        rep.temp_bytes = int(getattr(ma, "temp_size_in_bytes", 0))
        rep.peak_bytes = rep.argument_bytes + rep.temp_bytes
    except Exception:
        pass

    derive_terms(rep)
    return rep


def derive_terms(rep: "RooflineReport") -> "RooflineReport":
    """(Re-)derive the three terms + fractions from the raw quantities."""
    rep.compute_s = rep.hlo_flops / PEAK_FLOPS
    rep.memory_s = rep.hlo_bytes / HBM_BW
    rep.collective_s = rep.collective_bytes / ICI_BW
    terms = {"compute": rep.compute_s, "memory": rep.memory_s,
             "collective": rep.collective_s}
    rep.dominant = max(terms, key=terms.get)
    rep.est_step_s = max(terms.values())
    total_flops = rep.hlo_flops * rep.n_devices
    rep.useful_ratio = (rep.model_flops_global / total_flops
                        if total_flops else 0.0)
    ideal = rep.model_flops_global / (rep.n_devices * PEAK_FLOPS)
    rep.roofline_fraction = ideal / rep.est_step_s if rep.est_step_s else 0.0
    return rep


def apply_layer_correction(rep: "RooflineReport", probe: "RooflineReport",
                           n_layers: int) -> "RooflineReport":
    """total ≈ scanned_module + (L-1) × single-layer probe.

    XLA cost_analysis counts while bodies once; the scanned module holds
    one layer's worth of FLOPs/bytes/collectives, the probe supplies the
    remaining L-1.  Memory figures stay those of the scanned module
    (while-loop buffer liveness is the honest one).
    """
    rep.hlo_flops += (n_layers - 1) * probe.hlo_flops
    rep.hlo_bytes += (n_layers - 1) * probe.hlo_bytes
    rep.collective_bytes += (n_layers - 1) * probe.collective_bytes
    for k, v in probe.collective_breakdown.items():
        rep.collective_breakdown[k] = rep.collective_breakdown.get(k, 0) \
            + (n_layers - 1) * v
    rep.notes = (rep.notes + " " if rep.notes else "") + \
        f"[layer-corrected: +{n_layers - 1}x probe]"
    return derive_terms(rep)


# ---------------------------------------------------------------------------
# useful-FLOPs models (the 6·N·D convention + family-specific variants)
# ---------------------------------------------------------------------------

def lm_model_flops(cfg, seq_len: int, global_batch: int, kind: str) -> float:
    from ..models.lm import active_params
    n_active = active_params(cfg)
    tokens = global_batch * seq_len
    if kind == "train":
        return 6.0 * n_active * tokens
    if kind == "prefill":
        return 2.0 * n_active * tokens
    # decode: one token per sequence + attention over the cache
    attn = (2.0 * 2.0 * cfg.n_layers * global_batch * seq_len
            * cfg.n_heads * cfg.head_dim)
    return 2.0 * n_active * global_batch + attn


def gnn_model_flops(cfg, sh: Dict) -> float:
    """2·(matmul flops) ×3 for training (fwd+bwd)."""
    mult = 3.0 if sh["kind"].startswith("train") else 1.0
    F, H, C = sh["d_feat"], cfg.d_hidden, sh["n_classes"]
    if "batch_nodes" in sh:         # sampled: count gathered node compute
        f1, f2 = sh["fanouts"]
        n_eff = sh["batch_nodes"] * (1 + f1 + f1 * f2)
        dense = 2.0 * n_eff * F * H + 2.0 * sh["batch_nodes"] * H * C
        return mult * dense
    if "batch" in sh:               # molecules
        n = sh["batch"] * sh["n_nodes"]
        e = sh["batch"] * sh["n_edges"]
    else:
        n, e = sh["n_nodes"], sh["n_edges"]
    dense = 2.0 * n * F * H + 2.0 * n * H * C
    agg = 2.0 * e * (H + C)
    return mult * (dense + agg)


def recsys_model_flops(cfg, sh: Dict) -> float:
    mult = 6.0 if sh["kind"] == "train" else 2.0
    B = sh.get("batch", 1)
    if sh["kind"] == "retrieval":
        B = sh["n_candidates"]

    def mlp_flops(dims, d0):
        f, prev = 0.0, d0
        for d in dims:
            f += prev * d
            prev = d
        return f

    if cfg.kind == "dlrm":
        per_row = (mlp_flops(cfg.bot_mlp, cfg.n_dense)
                   + mlp_flops(cfg.top_mlp,
                               (cfg.n_sparse + 1) * cfg.n_sparse // 2
                               + cfg.bot_mlp[-1])
                   + (cfg.n_sparse + 1) ** 2 * cfg.embed_dim)
    elif cfg.kind == "dcn":
        d0 = cfg.n_dense + cfg.n_sparse * cfg.embed_dim
        per_row = (cfg.n_cross_layers * d0 * d0
                   + mlp_flops(cfg.deep_mlp, d0) + d0 + cfg.deep_mlp[-1])
    elif cfg.kind == "mind":
        d = cfg.embed_dim
        per_row = (cfg.hist_len * d * d                       # bilinear S
                   + cfg.capsule_iters * 2 * cfg.n_interests
                   * cfg.hist_len * d
                   + cfg.n_interests * (2 * d * d + d * d))   # interest MLP
        if sh["kind"] == "retrieval":
            return mult * (per_row + B * cfg.n_interests * d)
    else:  # two_tower
        d = cfg.embed_dim
        per_row = 2 * mlp_flops(cfg.tower_mlp, d)             # both towers
        if sh["kind"] == "retrieval":
            return mult * (mlp_flops(cfg.tower_mlp, d)
                           + B * (mlp_flops(cfg.tower_mlp, d)
                                  + cfg.tower_mlp[-1]))
    return mult * B * per_row


def model_flops_for(arch_def, shape_name: str) -> float:
    from ..configs.base import GNN_SHAPES, LM_SHAPES, RECSYS_SHAPES
    if arch_def.family == "lm":
        sh = LM_SHAPES[shape_name]
        return lm_model_flops(arch_def.config, sh["seq_len"],
                              sh["global_batch"], sh["kind"])
    if arch_def.family == "gnn":
        return gnn_model_flops(arch_def.config, GNN_SHAPES[shape_name])
    return recsys_model_flops(arch_def.config, RECSYS_SHAPES[shape_name])
