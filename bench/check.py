"""Whether what the timed path produced is correct, and the work it needed.

After the window has closed and the program's state is freed, a sample
drawn from the seed of what the window produced is recomputed by the
plain references in ``bench/reference`` and compared.  Every compared
number is a widest gap, scaled by the spread of the reference's own
values over the sample, so that one limit holds at any score scale:

- ``missing``: sampled results absent or with the wrong number of rows;
- ``bm25_gap``: how far a returned passage's reference BM25 score lies
  below the reference's k-th best, over the reference's top score;
- ``mono_gap`` (grid): how far below the reference's 10th best mono
  score a passage that the program passed on to duo lies;
- ``mono_err``, ``duo_err``, ``dense_err``: the widest difference between
  a returned score and the reference's score of the same input;
- ``rank_gap``: how far the reference's score of the passage at each
  returned rank lies below the reference's score at that rank.

``control=True`` puts the reference itself, one precision step lower
(BM25 in bfloat16, matrix products on int8 operands), in the program's
place: the same comparison has to fail it.

The same pass counts the work the window needed from the inputs' real
sizes: ``bench/flops.py``.
"""
from __future__ import annotations

import re
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import flops, gen, weights
from .reference.bm25 import BM25
from .reference.encoder import _q8, run_blocks
from .reference.tokens import Tokens, stack
from .world import ROLE_STREAM, encoder_widths, index_block


def _sample(keys: List, n: int, seed: int, must: List = ()) -> List:
    keys = sorted(keys)
    rng = gen.rng_for(seed, 5)
    pick = list(dict.fromkeys(list(must) + [keys[i] for i in
                                             rng.permutation(len(keys))]))
    return pick[:n]


def _scale(values) -> float:
    v = np.asarray(list(values), np.float64)
    return float(v.std()) if len(v) > 1 and v.std() > 0 else 1.0


def _rank_gap(rows, ref: Dict[int, float]) -> float:
    got = [ref[d] for d, _, _ in sorted(rows, key=lambda r: r[2])]
    want = sorted(got, reverse=True)
    return max((w - g for w, g in zip(want, got)), default=0.0)


class Reference:
    """The plain references of a BM25 and cross-encoder configuration over
    one corpus: BM25, the token layout, and the mono and duo encoders."""

    def __init__(self, cfg: Dict, corpus: gen.Corpus):
        self.cfg = cfg
        self.w = encoder_widths(cfg)
        self.corpus = corpus
        self.params = {}
        self.tok = Tokens(gen.fnv1a32_words(list(corpus.words)), self.w["V"],
                          gen.fnv1a32_words(["vs"])[0])
        st = cfg["stages"]["bm25"]
        self.bm25 = BM25(corpus.doc_ptr, corpus.doc_words, len(corpus.words),
                         st["k1"], st["b"])

    def role(self, role: str):
        if role not in self.params:
            self.params[role] = weights.encoder_params(
                self.cfg, self.cfg["max_len"],
                gen.sub_seed(self.cfg["weight_seed"], ROLE_STREAM[role]))
        return self.params[role]

    def mono(self, q: np.ndarray, docs, precision: str) -> Dict[int, float]:
        docs = list(dict.fromkeys(int(d) for d in docs))
        S = self.w["S"]
        toks = stack([self.tok.pair(q, self.corpus.doc(d), S) for d in docs], S)
        s = run_blocks(self.role("mono"), toks, head="score",
                       precision=precision)
        return dict(zip(docs, s.astype(np.float64)))

    def duo(self, q: np.ndarray, docs: List[int], precision: str
            ) -> Dict[int, float]:
        S = self.w["S"]
        pairs = [(i, j) for i in range(len(docs)) for j in range(len(docs))
                 if i != j]
        toks = stack([self.tok.duo(q, self.corpus.doc(docs[i]),
                                   self.corpus.doc(docs[j]), S)
                      for i, j in pairs], S)
        s = run_blocks(self.role("duo"), toks, head="score",
                       precision=precision)
        agg = np.zeros(len(docs))
        for (i, j), v in zip(pairs, s.astype(np.float64)):
            agg[i] += v
            agg[j] -= v
        return dict(zip(docs, agg))


def _ranked(scores: Dict[int, float], docs: List[int]) -> List[Tuple]:
    """Rows (doc, score, rank) by score descending, then input order."""
    order = sorted(range(len(docs)), key=lambda i: (-scores[docs[i]], i))
    return [(docs[i], scores[docs[i]], r) for r, i in enumerate(order)]


def pipeline_cutoffs(expr: str) -> List[int]:
    return [int(x) for x in re.findall(r"%\s*(\d+)", expr)]


# -- grid ---------------------------------------------------------------------

def check_grid(cfg, traffic, corpus, run, seed, control) -> Tuple[Dict, Dict]:
    ref = Reference(cfg, corpus)
    ks = list(traffic["k"])
    cut = pipeline_cutoffs(traffic["systems"].replace("{k}", "0"))[-1]
    qids = _sample(list(run.queries), int(traffic["check"]["topics"]), seed)
    nums = {"missing": 0.0, "bm25_gap": 0.0, "mono_gap": 0.0,
            "duo_err": 0.0, "rank_gap": 0.0}
    mono_all, duo_all, per = [], [], []
    for qid in qids:
        q = run.queries[qid]
        acc = ref.bm25.scores(q)
        top = BM25.top(acc, max(ks))
        outs = {k: run.outputs.get((qid, k)) for k in ks}
        if control:
            top_c = BM25.top(ref.bm25.scores(q, "bfloat16"), max(ks))
            mono_c = ref.mono(q, top_c, "int8")
            for k in ks:
                chosen = [d for d, _, _ in _ranked(mono_c, list(top_c[:k]))
                          ][:cut]
                outs[k] = _ranked(ref.duo(q, chosen, "int8"), chosen)
        docs = set(top.tolist())
        for rows in outs.values():
            docs |= {d for d, _, _ in rows or []}
        mono = ref.mono(q, sorted(docs), "highest")
        mono_all += list(mono.values())
        for k in ks:
            rows = outs[k]
            want = min(cut, len(top[:k]))
            if rows is None or len(rows) != want:
                nums["missing"] += 1
                continue
            kth = acc[top[k - 1]] if len(top) >= k else 0.0
            nums["bm25_gap"] = max(nums["bm25_gap"], max(
                (kth - acc[d]) / acc[top[0]] for d, _, _ in rows))
            t10 = sorted((mono[d] for d in top[:k]), reverse=True)[want - 1]
            chosen = [d for d, _, _ in rows]
            duo = ref.duo(q, chosen, "highest")
            duo_all += list(duo.values())
            per.append((rows, duo, t10 - min(mono[d] for d in chosen)))
    ms, ds = _scale(mono_all), _scale(duo_all)
    for rows, duo, mgap in per:
        nums["mono_gap"] = max(nums["mono_gap"], mgap / ms)
        nums["duo_err"] = max(nums["duo_err"], max(
            abs(s - duo[d]) for d, s, _ in rows) / ds)
        nums["rank_gap"] = max(nums["rank_gap"], _rank_gap(rows, duo) / ds)
    return nums, grid_work(cfg, traffic, corpus, run, ref)


def grid_work(cfg, traffic, corpus, run, ref) -> Dict:
    """Encoder operations of the window: every topic's mono pairs over the
    reference BM25 top max(k), and its distinct duo pairs over the
    passages the program passed to duo in any of the grid's systems."""
    S, w = cfg["max_len"], encoder_widths(cfg)
    lens = corpus.lengths()
    kmax = max(traffic["k"])
    real = []
    for qid, q in run.queries.items():
        top = BM25.top(ref.bm25.scores(q), kmax)
        real.append(flops.pair_tokens(len(q), lens[top], S))
        pairs = set()
        for k in traffic["k"]:
            docs = [d for d, _, _ in run.outputs.get((qid, k), [])]
            pairs |= {(a, b) for a in docs for b in docs if a != b}
        if pairs:
            a, b = np.array(sorted(pairs)).T
            real.append(flops.pair_tokens(len(q), lens[a] + lens[b] + 1, S))
    n = np.concatenate(real) if real else np.zeros(0)
    f = flops.encoder_flops(n, w["L"], w["d"], w["F"])
    return {"encoder_flops": f, "model_flops": f,
            "encoder_weight_bytes": _layer_bytes(w)}


def _layer_bytes(w: Dict) -> float:
    """float32 bytes of one encoder pass's layer weights (read per call)."""
    L, d, F = w["L"], w["d"], w["F"]
    return 4.0 * L * (4 * d * d + 2 * d * F + 2 * d)


# -- serving: BM25 then mono ----------------------------------------------------

def check_rerank(cfg, traffic, corpus, run, seed, control) -> Tuple[Dict, Dict]:
    ref = Reference(cfg, corpus)
    K = pipeline_cutoffs(traffic["pipeline"])[0]
    longest = max(run.outputs, key=lambda k: (len(run.queries[k]), k),
                  default=None)
    qids = _sample(list(run.outputs), int(traffic["check"]["requests"]),
                   seed, [longest] if longest else [])
    nums = {"missing": 0.0, "bm25_gap": 0.0, "mono_err": 0.0,
            "rank_gap": 0.0}
    if len(qids) < int(traffic["check"]["requests"]):
        nums["missing"] += int(traffic["check"]["requests"]) - len(qids)
    per, mono_all = [], []
    for qid in qids:
        q = run.queries[qid]
        acc = ref.bm25.scores(q)
        top = BM25.top(acc, K)
        rows = run.outputs[qid]
        if control:
            top_c = list(BM25.top(ref.bm25.scores(q, "bfloat16"), K))
            rows = _ranked(ref.mono(q, top_c, "int8"), top_c)
        if len(rows) != len(top):
            nums["missing"] += 1
            continue
        kth = acc[top[-1]]
        nums["bm25_gap"] = max(nums["bm25_gap"], max(
            (kth - acc[d]) / acc[top[0]] for d, _, _ in rows))
        mono = ref.mono(q, [d for d, _, _ in rows], "highest")
        mono_all += list(mono.values())
        per.append((rows, mono))
    ms = _scale(mono_all)
    for rows, mono in per:
        nums["mono_err"] = max(nums["mono_err"], max(
            abs(s - mono[d]) for d, s, _ in rows) / ms)
        nums["rank_gap"] = max(nums["rank_gap"], _rank_gap(rows, mono) / ms)
    S, w = cfg["max_len"], encoder_widths(cfg)
    lens = corpus.lengths()
    real = [flops.pair_tokens(len(q), lens[BM25.top(ref.bm25.scores(q), K)], S)
            for q in run.queries.values()]
    n = np.concatenate(real) if real else np.zeros(0)
    f = flops.encoder_flops(n, w["L"], w["d"], w["F"])
    return nums, {"encoder_flops": f, "model_flops": f,
                  "encoder_weight_bytes": _layer_bytes(w)}


# -- serving: dense retrieval ---------------------------------------------------

@jax.jit
def _scores(q, rows):
    """Reference scores of queries against one block of index rows, at
    float32 (``HIGHEST``), on the device."""
    return jnp.dot(q, rows.T, precision=jax.lax.Precision.HIGHEST)


def _top(s: np.ndarray, k: int) -> np.ndarray:
    """Per row, the column indices of the ``k`` largest values (value
    descending, then index ascending)."""
    part = np.argpartition(-s, k - 1, axis=1)[:, :k]
    out = np.empty_like(part)
    for i in range(len(s)):
        out[i] = part[i][np.lexsort((part[i], -s[i, part[i]]))]
    return out


def check_dense(cfg, traffic, corpus, run, seed, control) -> Tuple[Dict, Dict]:
    K = pipeline_cutoffs(traffic["pipeline"])[0]
    ix, S, w = cfg["index"], cfg["max_len"], encoder_widths(cfg)
    n_rows = cfg["num_passages"]
    words = gen.vocabulary(int(traffic["queries"]["vocab"]))
    tok = Tokens(gen.fnv1a32_words(list(words)), w["V"],
                 gen.fnv1a32_words(["vs"])[0])
    longest = max(run.outputs, key=lambda k: (len(run.queries[k]), k),
                  default=None)
    n_check = int(traffic["check"]["requests"])
    qids = _sample(list(run.outputs), n_check, seed,
                   [longest] if longest else [])
    params = weights.encoder_params(
        cfg, S, gen.sub_seed(cfg["weight_seed"], ROLE_STREAM["dense"]))
    toks = stack([tok.single(run.queries[q], S) for q in qids], S)
    q_ref = run_blocks(params, toks, head="embed", precision="highest")
    q_low = (run_blocks(params, toks, head="embed", precision="int8")
             if control else None)
    per = n_rows // ix["blocks"]
    want = {i: [d for d, _, _ in run.outputs[q]] for i, q in enumerate(qids)}
    got_ref: Dict[Tuple[int, int], float] = {}
    tops, tops_c = [], []
    s1 = np.zeros(len(qids))
    s2 = np.zeros(len(qids))
    q_dev = jnp.asarray(q_ref)
    q_low_dev = _q8(jnp.asarray(q_low)) if control else None
    for b in range(ix["blocks"]):
        rows = index_block(cfg, seed, b)
        s = np.asarray(_scores(q_dev, rows), np.float64)
        s1 += s.sum(1)
        s2 += (s * s).sum(1)
        part = _top(s, K)
        tops.append((np.take_along_axis(s, part, 1), part + b * per))
        for i, docs in want.items():
            for d in docs:
                if b * per <= d < (b + 1) * per:
                    got_ref[(i, d)] = s[i, d - b * per]
        if control:
            sc = np.asarray(_scores(q_low_dev, _q8(rows)))
            part = _top(sc, K)
            tops_c.append((np.take_along_axis(sc, part, 1), part + b * per))
            for i in range(len(qids)):
                for d in part[i]:
                    got_ref[(i, int(d) + b * per)] = s[i, d]
        del rows
    std = np.sqrt(np.maximum(s2 / n_rows - (s1 / n_rows) ** 2, 0))
    scale = float(std.mean()) if len(std) else 1.0

    def merged(parts, i):
        v = np.concatenate([p[0][i] for p in parts])
        d = np.concatenate([p[1][i] for p in parts])
        o = np.lexsort((d, -v))[:K]
        return v[o], d[o]

    nums = {"missing": float(max(0, n_check - len(qids))), "dense_err": 0.0,
            "rank_gap": 0.0}
    for i, qid in enumerate(qids):
        rv, rd = merged(tops, i)
        ref_of = dict(zip(rd.tolist(), rv.tolist()))
        if control:
            cv, cd = merged(tops_c, i)
            rows = [(int(d), float(v), r) for r, (d, v) in
                    enumerate(zip(cd, cv))]
        else:
            rows = run.outputs[qid]
        if len(rows) != K:
            nums["missing"] += 1
            continue
        val = {d: ref_of.get(d, got_ref.get((i, d))) for d, _, _ in rows}
        nums["dense_err"] = max(nums["dense_err"], max(
            abs(s - val[d]) for d, s, _ in rows) / scale)
        got = [val[d] for d, _, _ in sorted(rows, key=lambda r: r[2])]
        nums["rank_gap"] = max(nums["rank_gap"], max(
            (rv[r] - g for r, g in enumerate(got)), default=0.0) / scale)
    n_q = len(run.queries)
    real = np.array([min(len(q), S) for q in run.queries.values()])
    f_enc = flops.encoder_flops(real, w["L"], w["d"], w["F"],
                                score_head=False)
    f_topk = flops.topk_flops(n_rows, ix["dim"], n_q)
    return nums, {"encoder_flops": f_enc, "topk_flops": f_topk,
                  "model_flops": f_enc + f_topk,
                  "topk_index_bytes": flops.topk_bytes(n_rows, ix["dim"],
                                                       4, 0),
                  "encoder_weight_bytes": _layer_bytes(w)}


def checker(traffic: Dict, cfg: Dict):
    """The comparison for a traffic mix over a configuration."""
    if traffic["driver"] == "grid":
        return check_grid
    kinds = [cfg["stages"][re.match(r"\s*(\w+)", p).group(1)]["kind"]
             for p in traffic["pipeline"].split(">>")]
    if kinds[0] == "dense":
        return check_dense
    if kinds[0] == "bm25" and kinds[-1] == "mono":
        return check_rerank
    raise ValueError(f"no comparison for pipeline {traffic['pipeline']!r}")
