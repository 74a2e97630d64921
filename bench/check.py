"""Whether what the timed path produced is correct, and the work it needed.

After the window has closed and the program's state is freed, a sample
drawn from the seed of what the window produced is recomputed by the
plain references of the stages' kinds (``bench/kinds/<kind>.py``, built
on ``bench/reference``) and compared.  The comparison goes by the roles
of the pipeline's stages, and names each number after its stage.  Every
compared number is a widest gap, scaled by the spread of the reference's
own values over the sample, so that one limit holds at any score scale:

- ``missing``: sampled results absent or with the wrong number of rows;
- ``<retriever>_gap``: how far a returned passage's reference score lies
  below the reference's k-th best, over the reference's top score;
- ``<scorer>_gap`` (grid), for a pointwise scorer followed by a cutoff
  and another scorer: how far below the reference's cutoff-th best score
  a passage that the program passed on lies;
- ``<scorer>_err`` for the last scorer, ``<retriever>_err`` for an
  embedding retriever: the widest difference between a returned score
  and the reference's score of the same input;
- ``rank_gap``: how far the reference's score of the passage at each
  returned rank lies below the reference's score at that rank.

``control=True`` puts the references themselves, one precision step
lower (each kind's ``CONTROL``; scorers and embeddings take int8
operands), in the program's place: the same comparison has to fail them.

The same pass counts the work the window needed from the inputs' real
sizes: each model stage's kind counts its own (``work``), and the sums
are reported (``model_flops`` over every stage).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import cached_property, partial
from types import ModuleType
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import gen
from .reference.encoder import _q8
from .world import terms

SCORERS = ("pointwise", "pairwise")


@dataclass
class Stage:
    name: str
    kind: ModuleType
    spec: Dict
    cut: Optional[int]


class Inputs:
    """What the references read besides their configuration: the corpus
    (``None`` where no stage reads one), the run's seed, and the words
    that the queries' word ids index, hashed once."""

    def __init__(self, traffic: Dict, corpus: Optional[gen.Corpus],
                 seed: int):
        self.queries, self.corpus, self.seed = traffic["queries"], corpus, seed

    @cached_property
    def words(self) -> np.ndarray:
        if self.queries["kind"] == "planted":
            return self.corpus.words
        return gen.vocabulary(int(self.queries["vocab"]))

    @cached_property
    def word_hash(self) -> np.ndarray:
        return gen.fnv1a32_words(list(self.words))


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


def _ranked(scores: Dict[int, float], docs: List[int]) -> List[Tuple]:
    """Rows (doc, score, rank) by score descending, then input order."""
    order = sorted(range(len(docs)), key=lambda i: (-scores[docs[i]], i))
    return [(docs[i], scores[docs[i]], r) for r, i in enumerate(order)]


def _chain(stages: List[Stage]) -> Tuple[Stage, Optional[Stage], Stage]:
    """(retriever, middle scorer or None, last scorer) of a pipeline
    ``retriever % k >> loader ... >> [pointwise % c >>] scorer``."""
    body = [s for s in stages[1:] if s.kind.ROLE != "loader"]
    mid = body[0] if len(body) == 2 else None
    if (stages[0].kind.ROLE != "retriever" or stages[0].cut is None
            or len(body) not in (1, 2)
            or any(s.kind.ROLE not in SCORERS for s in body)
            or body[-1].cut is not None
            or mid is not None and (mid.kind.ROLE != "pointwise"
                                    or mid.cut is None)):
        raise ValueError("no comparison for the pipeline "
                         f"{[(s.name, s.kind.ROLE, s.cut) for s in stages]}")
    return stages[0], mid, body[-1]


def _work(cfg: Dict, parts: List[Tuple[Stage, List[np.ndarray]]]) -> Dict:
    """The stages' work from the real tokens of their inputs: ``*_flops``
    keys summed over the stages and into ``model_flops``; a key read per
    call taken once."""
    counts: Dict[str, float] = {}
    per_call: Dict[str, float] = {}
    for st, real in parts:
        n = np.concatenate(real) if real else np.zeros(0)
        for k, v in st.kind.work(cfg, st.spec, n).items():
            if k.endswith("_flops"):
                counts[k] = counts.get(k, 0.0) + v
            elif per_call.setdefault(k, v) != v:
                raise ValueError(f"stages read different {k}: "
                                 f"{per_call[k]} and {v}")
    return {**counts, "model_flops": sum(counts.values()), **per_call}


def _chain_work(cfg, run, R, scorers, ks) -> Dict:
    """The scorers' work over the window (``scorers`` are (stage,
    reference) pairs in pipeline order): the first one's inputs over the
    reference's top k of each system, a second one's over the passages
    the program passed to it in any system."""
    real: List[List[np.ndarray]] = [[] for _ in scorers]
    for qid, q in run.queries.items():
        top = R.top(R.scores(q), max(ks))
        real[0].append(scorers[0][1].real_tokens(q, [top[:k] for k in ks]))
        if len(scorers) == 2:
            real[1].append(scorers[1][1].real_tokens(q, [
                [d for d, _, _ in run.outputs.get((qid, k), [])]
                for k in ks]))
    return _work(cfg, [(st, r) for (st, _), r in zip(scorers, real)])


# -- grid ---------------------------------------------------------------------

def check_grid(stages, cfg, traffic, corpus, run, seed, control
               ) -> Tuple[Dict, Dict]:
    ret, mid, last = _chain(stages)
    inputs = Inputs(traffic, corpus, seed)
    R = ret.kind.Reference(cfg, ret.spec, inputs)
    M = mid.kind.Reference(cfg, mid.spec, inputs) if mid else None
    F = last.kind.Reference(cfg, last.spec, inputs)
    ks = list(traffic["k"])
    qids = _sample(list(run.queries), int(traffic["check"]["topics"]), seed)
    ret_gap, last_err = f"{ret.name}_gap", f"{last.name}_err"
    mid_gap = f"{mid.name}_gap" if mid else None
    nums = {"missing": 0.0, ret_gap: 0.0,
            **({mid_gap: 0.0} if mid else {}), last_err: 0.0,
            "rank_gap": 0.0}
    mid_all, last_all, per = [], [], []
    for qid in qids:
        q = run.queries[qid]
        acc = R.scores(q)
        top = R.top(acc, max(ks))
        outs = {k: run.outputs.get((qid, k)) for k in ks}
        if control:
            top_c = R.top(R.scores(q, ret.kind.CONTROL), max(ks))
            mid_c = M.score(q, top_c, "int8") if mid else None
            for k in ks:
                chosen = list(top_c[:k])
                if mid:
                    chosen = [d for d, _, _ in _ranked(mid_c, chosen)
                              ][:mid.cut]
                outs[k] = _ranked(F.score(q, chosen, "int8"), chosen)
        if mid:
            docs = set(top.tolist())
            for rows in outs.values():
                docs |= {d for d, _, _ in rows or []}
            pre = M.score(q, sorted(docs), "highest")
            mid_all += list(pre.values())
        for k in ks:
            rows = outs[k]
            want = min(mid.cut, len(top[:k])) if mid else len(top[:k])
            if rows is None or len(rows) != want:
                nums["missing"] += 1
                continue
            kth = acc[top[k - 1]] if len(top) >= k else 0.0
            nums[ret_gap] = max(nums[ret_gap], max(
                (kth - acc[d]) / acc[top[0]] for d, _, _ in rows))
            chosen = [d for d, _, _ in rows]
            fin = F.score(q, chosen, "highest")
            last_all += list(fin.values())
            gap = None
            if mid:
                cth = sorted((pre[d] for d in top[:k]), reverse=True)[want - 1]
                gap = cth - min(pre[d] for d in chosen)
            per.append((rows, fin, gap))
    ms, ls = _scale(mid_all), _scale(last_all)
    for rows, fin, gap in per:
        if mid:
            nums[mid_gap] = max(nums[mid_gap], gap / ms)
        nums[last_err] = max(nums[last_err], max(
            abs(s - fin[d]) for d, s, _ in rows) / ls)
        nums["rank_gap"] = max(nums["rank_gap"], _rank_gap(rows, fin) / ls)
    scorers = [(mid, M), (last, F)] if mid else [(last, F)]
    return nums, _chain_work(cfg, run, R, scorers, ks)


# -- serving: a retriever then a scorer ---------------------------------------

def check_serve(stages, cfg, traffic, corpus, run, seed, control
                ) -> Tuple[Dict, Dict]:
    ret, _, last = _chain(stages)
    inputs = Inputs(traffic, corpus, seed)
    R = ret.kind.Reference(cfg, ret.spec, inputs)
    F = last.kind.Reference(cfg, last.spec, inputs)
    K = ret.cut
    longest = max(run.outputs, key=lambda k: (len(run.queries[k]), k),
                  default=None)
    qids = _sample(list(run.outputs), int(traffic["check"]["requests"]),
                   seed, [longest] if longest else [])
    ret_gap, last_err = f"{ret.name}_gap", f"{last.name}_err"
    nums = {"missing": 0.0, ret_gap: 0.0, last_err: 0.0, "rank_gap": 0.0}
    if len(qids) < int(traffic["check"]["requests"]):
        nums["missing"] += int(traffic["check"]["requests"]) - len(qids)
    per, last_all = [], []
    for qid in qids:
        q = run.queries[qid]
        acc = R.scores(q)
        top = R.top(acc, K)
        rows = run.outputs[qid]
        if control:
            top_c = list(R.top(R.scores(q, ret.kind.CONTROL), K))
            rows = _ranked(F.score(q, top_c, "int8"), top_c)
        if len(rows) != len(top):
            nums["missing"] += 1
            continue
        kth = acc[top[-1]]
        nums[ret_gap] = max(nums[ret_gap], max(
            (kth - acc[d]) / acc[top[0]] for d, _, _ in rows))
        fin = F.score(q, [d for d, _, _ in rows], "highest")
        last_all += list(fin.values())
        per.append((rows, fin))
    ls = _scale(last_all)
    for rows, fin in per:
        nums[last_err] = max(nums[last_err], max(
            abs(s - fin[d]) for d, s, _ in rows) / ls)
        nums["rank_gap"] = max(nums["rank_gap"], _rank_gap(rows, fin) / ls)
    return nums, _chain_work(cfg, run, R, [(last, F)], [K])


# -- serving: an embedding retriever ------------------------------------------

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


def check_dense(stages, cfg, traffic, corpus, run, seed, control
                ) -> Tuple[Dict, Dict]:
    (st,) = stages
    ref = st.kind.Reference(cfg, st.spec, Inputs(traffic, corpus, seed))
    K, n_rows = st.cut, ref.n_rows
    longest = max(run.outputs, key=lambda k: (len(run.queries[k]), k),
                  default=None)
    n_check = int(traffic["check"]["requests"])
    qids = _sample(list(run.outputs), n_check, seed,
                   [longest] if longest else [])
    queries = [run.queries[q] for q in qids]
    q_ref = ref.embed(queries, "highest")
    q_low = ref.embed(queries, "int8") if control else None
    per = n_rows // ref.blocks
    want = {i: [d for d, _, _ in run.outputs[q]] for i, q in enumerate(qids)}
    got_ref: Dict[Tuple[int, int], float] = {}
    tops, tops_c = [], []
    s1 = np.zeros(len(qids))
    s2 = np.zeros(len(qids))
    q_dev = jnp.asarray(q_ref)
    q_low_dev = _q8(jnp.asarray(q_low)) if control else None
    for b in range(ref.blocks):
        rows = ref.rows(b)
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

    err = f"{st.name}_err"
    nums = {"missing": float(max(0, n_check - len(qids))), err: 0.0,
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
        nums[err] = max(nums[err], max(
            abs(s - val[d]) for d, s, _ in rows) / scale)
        got = [val[d] for d, _, _ in sorted(rows, key=lambda r: r[2])]
        nums["rank_gap"] = max(nums["rank_gap"], max(
            (rv[r] - g for r, g in enumerate(got)), default=0.0) / scale)
    return nums, _work(cfg, [(st, [ref.real_tokens(q) for q in
                                   run.queries.values()])])


def checker(traffic: Dict, cfg: Dict, kind: Callable) -> Callable:
    """The comparison for a traffic mix over a configuration, chosen by
    the driver and the roles of the pipeline's stages (``kind(name)`` is
    the module of stage kind ``name``): ``compare(corpus, run, seed,
    control) -> (numbers, work)``."""
    grid = traffic["driver"] == "grid"
    expr = (traffic["systems"].format(k=traffic["k"][0]) if grid
            else traffic["pipeline"])
    stages = [Stage(n, kind(cfg["stages"][n]["kind"]), cfg["stages"][n], c)
              for n, c in terms(expr, cfg["stages"])]
    if grid:
        _chain(stages)
        return partial(check_grid, stages, cfg, traffic)
    if stages[0].kind.ROLE == "embedding" and len(stages) == 1 \
            and stages[0].cut is not None:
        return partial(check_dense, stages, cfg, traffic)
    if _chain(stages)[1] is not None:
        raise ValueError(f"no serving comparison for {expr!r}: a middle "
                         f"scorer is compared in grids only")
    return partial(check_serve, stages, cfg, traffic)
