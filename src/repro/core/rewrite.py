"""Optimizer passes over the logical plan IR (``core/ir.py``).

The optimizer is an ordered pass pipeline; each pass is a
``(PlanGraph) -> PassStats`` rewrite with its own accounting, selected
through ``ExecutionPlan(optimize=...)``:

* ``"normalize"`` — algebraic normalization: annotates every node with
  a *canonical* structural key in which the operands of commutative
  operators (``a + b`` / ``b + a``, ``a | b`` / ``b | a``) compare
  equal, so CSE can share them.  Annotation-only: no node is rewritten,
  and a lone ``b + a`` keeps its own evaluation order (row order of the
  output frame is only ever affected for expressions that actually get
  merged with a commuted twin).
* ``"cse"`` — cross-pipeline common-subexpression elimination:
  hash-conses the forest bottom-up on (canonical) structural keys, so
  *any* identical subtree — prefix or not, through binary operators —
  executes once.  Strictly generalizes the prefix trie of
  ``precompute.py`` and subsumes the §3 LCP.
* ``"pushdown"`` — ``RankCutoff`` (``% k``) pushdown: a cutoff climbs
  through ``rank_preserving`` single-consumer stages and, when it
  reaches a stage that can absorb it (``Transformer.with_cutoff``,
  e.g. a retriever's ``num_results`` or the dense stage's per-block
  kernel k), is fused away entirely.  Invariant: **pushdown only
  climbs rank-preserving sole-consumer edges** — a shared (multi-
  consumer) node is never rewritten, so pushdown cannot duplicate
  work that CSE shares or deepen another pipeline's view of the node;
  and absorption is sound only because ``with_cutoff`` implementations
  guarantee a deterministic total order (score desc, then doc index),
  making every top-k a prefix of the top-n.
* ``"cache-prune"`` — cache-aware pruning (runs after planner memo
  insertion): consults the provenance manifests (``caching/provenance``)
  of planner-inserted caches and, for memo nodes whose store is warm
  and whose output is assembled purely from the store
  (``serve_from_store``), marks exclusive ``augment_only`` upstream
  stages as *deferred*: the executor probes the cache with the
  upstream chain's input first and only executes the chain on a miss.
  Invariant: only **exclusive, augment-only** upstream chains are
  deferred — augment-only stages cannot alter the keys the memo is
  probed with, and exclusivity guarantees no other consumer observes
  the skipped intermediate.

Invariant (property-tested): for any pipeline algebra, results with
``optimize="all"`` and ``optimize="none"`` are bit-identical per qid —
same (qid, docno, score, rank) values under canonical row order — in
both the sequential and the sharded executor.

No pass reads a timing or the plan's run history: a plan depends on its
pipelines and, through ``cache-prune``, on which of its stores are warm.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple, Union

from .ir import IRNode, PlanGraph, make_stage_node, node_key
from .pipeline import RankCutoff

__all__ = ["PassStats", "OPTIMIZER_PASSES", "PRE_MEMO_PASSES",
           "POST_MEMO_PASSES", "resolve_passes", "run_pass"]

#: canonical pass order; ``optimize="all"`` runs exactly these.  The
#: structural passes run before memo insertion; ``cache-prune`` runs
#: after it, since it consults the freshly opened cache manifests.
PRE_MEMO_PASSES: Tuple[str, ...] = ("normalize", "cse", "pushdown")
POST_MEMO_PASSES: Tuple[str, ...] = ("cache-prune",)
OPTIMIZER_PASSES: Tuple[str, ...] = PRE_MEMO_PASSES + POST_MEMO_PASSES


@dataclass
class PassStats:
    """Per-pass accounting (surfaced via ``PlanStats`` and ``explain()``)."""
    name: str
    nodes_before: int = 0
    nodes_after: int = 0
    nodes_eliminated: int = 0            # removed from the DAG
    cutoffs_pushed: int = 0              # RankCutoffs moved/absorbed
    nodes_marked_prunable: int = 0       # deferred behind a warm cache
    nodes_annotated: int = 0             # normalize: commuted canonical keys
    time_s: float = 0.0

    def as_dict(self) -> Dict:
        return {"name": self.name, "nodes_before": self.nodes_before,
                "nodes_after": self.nodes_after,
                "nodes_eliminated": self.nodes_eliminated,
                "cutoffs_pushed": self.cutoffs_pushed,
                "nodes_marked_prunable": self.nodes_marked_prunable,
                "nodes_annotated": self.nodes_annotated,
                "time_s": round(self.time_s, 6)}


def resolve_passes(optimize: Union[str, Sequence[str], None]) -> List[str]:
    """Validate the ``optimize=`` knob into an ordered pass-name list."""
    if optimize in ("all", True):
        return list(OPTIMIZER_PASSES)
    if optimize in ("none", None, False):
        return []
    if isinstance(optimize, str):
        raise ValueError(
            f"optimize must be 'all', 'none' or a list of pass names "
            f"drawn from {list(OPTIMIZER_PASSES)}; got {optimize!r}")
    names = list(optimize)
    for n in names:
        if n not in OPTIMIZER_PASSES:
            raise ValueError(f"unknown optimizer pass {n!r}; "
                             f"known passes: {list(OPTIMIZER_PASSES)}")
    return names


def run_pass(graph: PlanGraph, name: str) -> PassStats:
    """Run one pass by name, returning its stats."""
    fn = {"normalize": _pass_normalize, "cse": _pass_cse,
          "pushdown": _pass_pushdown, "cache-prune": _pass_cache_prune}[name]
    stats = PassStats(name=name, nodes_before=graph.n_nodes())
    t0 = time.perf_counter()
    fn(graph, stats)
    stats.time_s = time.perf_counter() - t0
    stats.nodes_after = graph.n_nodes()
    return stats


def _touch(node: IRNode, name: str) -> None:
    if name not in node.touched_by:
        node.touched_by.append(name)


# ---------------------------------------------------------------------------
# normalize — commutative-canonical keys
# ---------------------------------------------------------------------------

def _pass_normalize(graph: PlanGraph, stats: PassStats) -> None:
    for node in graph.nodes:
        if node.kind == "source":
            node.canon_key = node.key
            continue
        in_keys = [i.canon_key if i.canon_key is not None else i.key
                   for i in node.inputs]
        if node.kind == "combine":
            ordered = in_keys
            if getattr(node.stage, "commutative", False):
                ordered = sorted(in_keys, key=repr)
                if ordered != in_keys:
                    stats.nodes_annotated += 1
                    _touch(node, "normalize")
            node.canon_key = ("combine", type(node.stage).__name__,
                              *ordered)
        elif node.kind == "scale":
            node.canon_key = ("scale", node.stage.scalar, in_keys[0])
        else:
            node.canon_key = ("stage", node.stage.signature(), in_keys[0])


# ---------------------------------------------------------------------------
# cse — hash-consing on (canonical) keys
# ---------------------------------------------------------------------------

def _pass_cse(graph: PlanGraph, stats: PassStats) -> None:
    seen: Dict[Tuple, IRNode] = {}
    replace: Dict[int, IRNode] = {}
    kept: List[IRNode] = []
    for node in graph.nodes:
        new_inputs = [replace.get(i.id, i) for i in node.inputs]
        if any(n is not o for n, o in zip(new_inputs, node.inputs)):
            node.inputs = new_inputs
            # keep the structural key consistent with the rewired inputs
            node.key = node_key(node.kind, node.stage, node.inputs)
        k = node.canon_key if node.canon_key is not None else node.key
        rep = seen.get(k)
        if rep is None:
            seen[k] = node
            kept.append(node)
        else:
            replace[node.id] = rep
            _touch(rep, "cse")
            stats.nodes_eliminated += 1
    graph.nodes = kept
    graph.terminals = [replace.get(t.id, t) for t in graph.terminals]


# ---------------------------------------------------------------------------
# pushdown — RankCutoff through rank-preserving stages into absorbers
# ---------------------------------------------------------------------------

def _pass_pushdown(graph: PlanGraph, stats: PassStats) -> None:
    # iterate to a fixpoint: absorbing `% 50 % 10` takes two rounds
    while _pushdown_round(graph, stats):
        pass


def _pushdown_round(graph: PlanGraph, stats: PassStats) -> bool:
    consumers = graph.consumers()
    terminal_ids = {t.id for t in graph.terminals}

    def sole_inner(node: IRNode) -> bool:
        """True when ``node`` feeds exactly one consumer and is not a
        pipeline output itself — the only place a rewrite cannot
        duplicate or change shared work."""
        return len(consumers.get(node.id, ())) == 1 \
            and node.id not in terminal_ids

    for node in graph.nodes:
        if node.kind != "stage" or not isinstance(node.stage, RankCutoff):
            continue
        k = node.stage.k
        chain: List[IRNode] = []         # rank-preserving stages, cutoff-down
        cur = node.inputs[0]
        absorber: Optional[IRNode] = None
        absorbed = None
        while cur.kind == "stage" and sole_inner(cur):
            absorbed = cur.stage.with_cutoff(k)
            if absorbed is not None:
                absorber = cur
                break
            if not cur.rank_preserving:
                break
            chain.append(cur)
            cur = cur.inputs[0]
        if absorber is None and not chain:
            continue

        if absorber is not None:
            # fuse the cutoff into the absorber; rebuild the chain on top
            if absorbed is absorber.stage:
                top = absorber           # already <= k results: cutoff no-op
            else:
                top = make_stage_node(graph, absorbed, absorber.inputs[0])
                _touch(top, "pushdown")
            for st in reversed(chain):
                top = make_stage_node(graph, st.stage, top)
                _touch(top, "pushdown")
            replacement = top
            stats.nodes_eliminated += 1  # the cutoff node itself
        else:
            # no absorber: move the cutoff below the rank-preserving
            # chain so downstream stages only see k rows per query
            top = make_stage_node(graph, node.stage, chain[-1].inputs[0])
            _touch(top, "pushdown")
            for st in reversed(chain):
                top = make_stage_node(graph, st.stage, top)
                _touch(top, "pushdown")
            replacement = top
        stats.cutoffs_pushed += 1

        # rewire every consumer of the cutoff (and the terminals) onto
        # the rebuilt chain, then drop unreachable originals
        for consumer in consumers.get(node.id, ()):
            consumer.inputs = [replacement if i is node else i
                               for i in consumer.inputs]
            consumer.key = node_key(consumer.kind, consumer.stage,
                                    consumer.inputs)
        graph.terminals = [replacement if t is node else t
                           for t in graph.terminals]
        graph.retopo()
        return True
    return False


# ---------------------------------------------------------------------------
# cache-prune — defer exclusive augment-only chains behind warm caches
# ---------------------------------------------------------------------------

def _pass_cache_prune(graph: PlanGraph, stats: PassStats) -> None:
    consumers = graph.consumers()
    terminal_ids = {t.id for t in graph.terminals}
    for node in graph.nodes:
        cache = node.cache
        if cache is None or not hasattr(cache, "serve_from_store"):
            continue                     # only store-complete families
        manifest = getattr(cache, "manifest", None)
        if manifest is None or not getattr(manifest, "entry_count", 0):
            continue                     # cold store: nothing to defer to
        key_cols = set(getattr(cache, "key_cols", ()) or ())
        chain: List[IRNode] = []
        cur = node.inputs[0]
        while cur.kind == "stage" and cur.augment_only \
                and cur.cache is None and cur.id not in terminal_ids \
                and len(consumers.get(cur.id, ())) == 1 \
                and not (key_cols & set(
                    getattr(cur.stage, "value_columns", ()) or ())):
            # the last guard: an augment-only stage that *produces* one
            # of the cache's key columns (a query/text attacher) cannot
            # be deferred — the probe frame would lack (or mis-value)
            # that key.  serve_from_store additionally treats a missing
            # key column as a miss, so undeclared producers stay safe.
            chain.append(cur)
            cur = cur.inputs[0]
        if not chain:
            continue
        node.probe_input = cur
        node.inline_chain = list(reversed(chain))   # execution order
        for ch in chain:
            ch.inlined = True
            _touch(ch, "cache-prune")
        _touch(node, "cache-prune")
        stats.nodes_marked_prunable += len(chain)
