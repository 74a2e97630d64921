"""Provenance layer (caching/provenance.py): fingerprints, manifests,
stale-cache policies, and planner-level invalidation.

Acceptance coverage:

* mutating a cached transformer's config invalidates exactly that node
  (second run recomputes the mutated node + its downstream, still hits
  unaffected nodes);
* ``repro cache verify``-style manifest loading detects hand-corrupted
  manifests via the content checksum;
* fingerprinting is deterministic across processes (subprocess test)
  and never initialises a JAX backend;
* the host digest and the cachekey_hash kernel agree bit-for-bit.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import repro.caching.provenance as prov
from repro.caching import (CacheManifest, KeyValueCache, ManifestError,
                           StaleCacheError, auto_cache)
from repro.caching.provenance import (canonical_bytes, combine_fingerprints,
                                      transformer_fingerprint)
from repro.core import (ColFrame, ExecutionPlan, GenericTransformer,
                        add_ranks)
from repro.ir import QueryExpander

QUERIES = ColFrame({"qid": ["q1", "q2", "q3"],
                    "query": ["alpha beta", "gamma delta", "epsilon zeta"]})


def make_retriever(name, n=4, base=10.0):
    def fn(inp):
        rows = [{"qid": q, "query": t, "docno": f"{name}_d{i}",
                 "score": base - i}
                for q, t in zip(inp["qid"].tolist(), inp["query"].tolist())
                for i in range(n)]
        return add_ranks(ColFrame.from_dicts(rows))
    return GenericTransformer(fn, name, one_to_many=True,
                              key_columns=("qid", "query"))


# -- fingerprints -------------------------------------------------------------

def test_fingerprint_stable_and_config_sensitive():
    assert QueryExpander(2).fingerprint() == QueryExpander(2).fingerprint()
    assert QueryExpander(2).fingerprint() != QueryExpander(3).fingerprint()
    # 16 lowercase hex chars (two FNV-1a lanes)
    fp = QueryExpander(2).fingerprint()
    assert len(fp) == 16 and int(fp, 16) >= 0


def test_fingerprint_extras_fold_in():
    class Versioned(QueryExpander):
        corpus_version = "v1"

        def fingerprint_extras(self):
            return (self.corpus_version,)

    a = Versioned(2)
    b = Versioned(2)
    b.corpus_version = "v2"
    assert a.fingerprint() != b.fingerprint()


def test_fingerprint_covers_composite_subtrees():
    qe = QueryExpander(2)
    r = make_retriever("A")
    assert (qe >> r).fingerprint() != (QueryExpander(3) >> r).fingerprint()
    assert (qe >> r).fingerprint() == \
        (QueryExpander(2) >> make_retriever("A")).fingerprint()


def test_combine_fingerprints_order_sensitive():
    assert combine_fingerprints("a", "b") != combine_fingerprints("b", "a")
    assert combine_fingerprints("a", "b") == combine_fingerprints("a", "b")


def test_canonical_bytes_distinguishes_types():
    # "1" vs 1 vs 1.0 vs True must not collide
    vals = ["1", 1, 1.0, True, (1,), b"1"]
    encs = [canonical_bytes(v) for v in vals]
    assert len(set(encs)) == len(vals)


def test_host_and_kernel_digests_agree():
    """The host digest must stay bit-identical to the cachekey_hash
    kernel's, so cache directories keyed by either stay valid."""
    from repro.kernels.cachekey_hash import cachekey_hash_op
    data = canonical_bytes(("shared", 7, 2.5, ("nested", None)))
    buf = len(data).to_bytes(8, "little") + data
    buf += b"\x00" * ((-len(buf)) % 4)
    words = np.frombuffer(buf, dtype="<u4")
    words = np.concatenate([words, np.zeros(
        (-len(words)) % prov._WORD_BUCKET, dtype="<u4")])
    out = np.asarray(cachekey_hash_op(words.view(np.int32).reshape(1, -1)))
    kernel = ((int(out[0, 0]) & 0xFFFFFFFF).to_bytes(4, "little")
              + (int(out[0, 1]) & 0xFFFFFFFF).to_bytes(4, "little"))
    assert prov.digest_bytes(data) == kernel.hex()


@pytest.mark.slow
def test_fingerprint_deterministic_across_processes():
    script = ("from repro.ir import QueryExpander\n"
              "from repro.core import GenericTransformer\n"
              "print(QueryExpander(2).fingerprint())\n"
              "print(GenericTransformer(lambda x: x, 'named',"
              " params=(1, 2.5)).fingerprint())\n")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "PYTHONPATH": os.path.join(root, "src")}
    outs = []
    for _ in range(2):
        p = subprocess.run([sys.executable, "-c", script],
                           capture_output=True, text=True, env=env,
                           timeout=120)
        assert p.returncode == 0, p.stderr[-2000:]
        outs.append(p.stdout.split())
    assert outs[0] == outs[1]
    # ... and identical to this process's value
    assert outs[0][0] == QueryExpander(2).fingerprint()


def test_fingerprint_initialises_no_backend():
    """Fingerprinting digests on the host: a fresh process that
    fingerprints transformers has not initialised any JAX backend, so
    it never takes a device another process needs."""
    script = ("from repro.ir import QueryExpander\n"
              "from repro.caching.provenance import combine_fingerprints\n"
              "fp = QueryExpander(2).fingerprint()\n"
              "combine_fingerprints(fp, 'node')\n"
              "from jax._src import xla_bridge\n"
              "print(xla_bridge.backends_are_initialized())\n")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "PYTHONPATH": os.path.join(root, "src")}
    p = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.split()[-1] == "False"


# -- manifests ----------------------------------------------------------------

def test_manifest_roundtrip(tmp_path):
    m = CacheManifest.new(family="KeyValueCache", backend="sqlite",
                          fingerprint="aa" * 8, key_columns=["qid"],
                          value_columns=["query"])
    m.entry_count = 7
    m.save(str(tmp_path))
    loaded = CacheManifest.load(str(tmp_path))
    assert loaded == m


def test_manifest_checksum_detects_hand_edit(tmp_path):
    m = CacheManifest.new(family="KeyValueCache", backend="sqlite",
                          fingerprint="deadbeefdeadbeef")
    m.save(str(tmp_path))
    p = tmp_path / "manifest.json"
    p.write_text(p.read_text().replace("deadbeefdeadbeef",
                                       "deadbeefdeadbee0"))
    with pytest.raises(ManifestError, match="checksum"):
        CacheManifest.load(str(tmp_path))


def test_manifest_rejects_future_format_version(tmp_path):
    m = CacheManifest.new(family="X")
    m.format_version = prov.MANIFEST_VERSION + 1
    m.save(str(tmp_path))
    with pytest.raises(ManifestError, match="format_version"):
        CacheManifest.load(str(tmp_path))


def test_manifest_absent_returns_none(tmp_path):
    assert CacheManifest.load(str(tmp_path)) is None


# -- stale-cache policies -----------------------------------------------------

def _kv(path, t, **kw):
    return KeyValueCache(path, t, key=("qid", "query"), value=("query",),
                         **kw)


def test_stale_fingerprint_raises_by_default(tmp_path):
    t2, t3 = QueryExpander(2), QueryExpander(3)
    with _kv(str(tmp_path), t2, fingerprint=t2.fingerprint()) as kv:
        kv(QUERIES)
    with pytest.raises(StaleCacheError, match="fingerprint"):
        _kv(str(tmp_path), t3, fingerprint=t3.fingerprint())


def test_on_stale_recompute_discards_entries(tmp_path):
    t2, t3 = QueryExpander(2), QueryExpander(3)
    with _kv(str(tmp_path), t2, fingerprint=t2.fingerprint()) as kv:
        kv(QUERIES)
        assert len(kv) == len(QUERIES)
    with _kv(str(tmp_path), t3, fingerprint=t3.fingerprint(),
             on_stale="recompute") as kv:
        assert len(kv) == 0              # stale entries were wiped
        out = kv(QUERIES)
        assert kv.stats.misses == len(QUERIES)
        assert out["query"][0] == "alpha beta alpha alpha"   # repeat=3
    m = CacheManifest.load(str(tmp_path))
    assert m.fingerprint == t3.fingerprint()


def test_on_stale_readonly_serves_but_never_writes(tmp_path):
    t2, t3 = QueryExpander(2), QueryExpander(3)
    with _kv(str(tmp_path), t2, fingerprint=t2.fingerprint()) as kv:
        kv(QUERIES)
    extra = ColFrame({"qid": ["q9"], "query": ["eta theta"]})
    with _kv(str(tmp_path), t3, fingerprint=t3.fingerprint(),
             on_stale="readonly") as kv:
        assert kv.readonly
        kv(QUERIES)                      # stale hits, served as-is
        assert kv.stats.hits == len(QUERIES)
        kv(extra)                        # miss: computed, NOT inserted
        assert kv.stats.inserts == 0
        assert len(kv) == len(QUERIES)
    # the stale manifest was not overwritten either
    m = CacheManifest.load(str(tmp_path))
    assert m.fingerprint == t2.fingerprint()


def test_backend_mismatch_is_stale(tmp_path):
    t = QueryExpander(2)
    with _kv(str(tmp_path), t, backend="sqlite") as kv:
        kv(QUERIES)
    with pytest.raises(StaleCacheError, match="backend"):
        _kv(str(tmp_path), t, backend="dbm")


def test_invalid_on_stale_rejected(tmp_path):
    with pytest.raises(ValueError, match="on_stale"):
        _kv(str(tmp_path), QueryExpander(2), on_stale="panic")


def test_legacy_dir_without_manifest_is_adopted(tmp_path):
    """Directories written before the provenance layer (no manifest)
    stay warm: the first provenance-aware open adopts them and records
    the fingerprint."""
    t = QueryExpander(2)
    with _kv(str(tmp_path), t) as kv:    # no fingerprint recorded
        kv(QUERIES)
    os.remove(tmp_path / "manifest.json")        # simulate pre-PR3 dir
    fp = t.fingerprint()
    with _kv(str(tmp_path), t, fingerprint=fp) as kv:
        kv(QUERIES)
        assert kv.stats.hits == len(QUERIES)     # entries survived
    assert CacheManifest.load(str(tmp_path)).fingerprint == fp


def test_auto_cache_derives_fingerprint_and_detects_stale(tmp_path):
    c = auto_cache(QueryExpander(2), str(tmp_path))
    c(QUERIES)
    c.close()
    assert CacheManifest.load(str(tmp_path)).fingerprint == \
        QueryExpander(2).fingerprint()
    with pytest.raises(StaleCacheError):
        auto_cache(QueryExpander(3), str(tmp_path))
    c2 = auto_cache(QueryExpander(3), str(tmp_path), on_stale="recompute")
    assert len(c2) == 0
    c2.close()


# -- planner integration ------------------------------------------------------

def test_node_fingerprints_fold_upstream(tmp_path):
    a = make_retriever("A")
    plan2 = ExecutionPlan([QueryExpander(2) >> a])
    plan3 = ExecutionPlan([QueryExpander(3) >> a])
    fps2 = {n.label: plan2.node_fingerprints()[n.id]
            for n in plan2.nodes.values()}
    fps3 = {n.label: plan3.node_fingerprints()[n.id]
            for n in plan3.nodes.values()}
    assert fps2["<source>"] == fps3["<source>"]
    # the expander differs AND the downstream retriever node differs
    # (its provenance folds the upstream fingerprint in)
    assert fps2["QueryExpander(2,)"] != fps3["QueryExpander(3,)"]
    label_a = "GenericTransformer('A',)"
    assert fps2[label_a] != fps3[label_a]
    # replanning is deterministic
    replan = ExecutionPlan([QueryExpander(2) >> a])
    assert {n.label: replan.node_fingerprints()[n.id]
            for n in replan.nodes.values()} == fps2


def test_config_mutation_invalidates_exactly_that_node(tmp_path):
    """THE acceptance scenario: mutate one cached transformer's config;
    the second run recomputes the mutated node (and its downstream) but
    still hits every unaffected node."""
    def systems(repeat, a, b):
        return [QueryExpander(repeat) >> a, b]

    a, b = make_retriever("A"), make_retriever("B", base=8.0)
    with ExecutionPlan(systems(2, a, b), cache_dir=str(tmp_path)) as plan:
        plan.run(QUERIES)
    # same config, fresh plan: everything hits
    with ExecutionPlan(systems(2, a, b), cache_dir=str(tmp_path)) as plan:
        _, stats = plan.run(QUERIES)
        assert stats.cache_misses == 0 and stats.cache_hits > 0

    # mutate the expander's config (2 -> 3)
    with ExecutionPlan(systems(3, a, b), cache_dir=str(tmp_path)) as plan:
        node_cache = {n.stage: n.cache for n in plan.nodes.values()
                      if n.cache is not None}
        _, stats = plan.run(QUERIES)
    n = len(QUERIES)
    by_label = {type(s).__name__ if not hasattr(s, "name") else s.name: c
                for s, c in node_cache.items()}
    assert by_label["B"].stats.hits == n          # unaffected: pure hits
    assert by_label["B"].stats.misses == 0
    assert by_label["A"].stats.misses == n        # downstream of mutation
    expander = [c for s, c in node_cache.items()
                if isinstance(s, QueryExpander)][0]
    assert expander.stats.misses == n             # the mutated node
    assert stats.cache_hits == n                  # only B hit


def test_plan_manifest_written_and_updated(tmp_path):
    import json
    a, b = make_retriever("A"), make_retriever("B", base=8.0)
    with ExecutionPlan([a, b], cache_dir=str(tmp_path)) as plan:
        plan.run(QUERIES)
        path = plan._plan_manifest_path
    assert path is not None and os.path.exists(path)
    with open(path) as f:
        doc = json.load(f)
    assert doc["format_version"] == prov.PLAN_MANIFEST_VERSION
    assert len(doc["nodes"]) == 2
    assert all(nd["fingerprint"] for nd in doc["nodes"])
    assert len(doc["runs"]) == 1
    # a second plan over the same pipelines appends to the history
    with ExecutionPlan([a, b], cache_dir=str(tmp_path)) as plan:
        plan.run(QUERIES)
    with open(path) as f:
        assert len(json.load(f)["runs"]) == 2


def test_planner_on_stale_recompute_after_tamper(tmp_path):
    """Re-stamping a node dir with a foreign fingerprint trips the
    planner's default policy; on_stale='recompute' recovers."""
    a = make_retriever("A")
    with ExecutionPlan([a], cache_dir=str(tmp_path)) as plan:
        plan.run(QUERIES)
        node_dir = [n.cache.path for n in plan.nodes.values()
                    if n.cache is not None][0]
    m = CacheManifest.load(node_dir)
    m.fingerprint = "0" * 16                 # a different (valid) manifest
    m.save(node_dir)
    with pytest.raises(StaleCacheError):
        ExecutionPlan([a], cache_dir=str(tmp_path))
    with ExecutionPlan([a], cache_dir=str(tmp_path),
                       on_stale="recompute") as plan:
        _, stats = plan.run(QUERIES)
        assert stats.cache_misses == len(QUERIES)   # wiped, recomputed


def test_memo_factory_without_provenance_params_still_works(tmp_path):
    """Custom factories keep their minimal (stage, path) signature."""
    seen = []

    def factory(stage, path):
        seen.append((repr(stage), path))
        return None

    ExecutionPlan([make_retriever("A") % 3], cache_dir=str(tmp_path),
                  memo_factory=factory)
    assert len(seen) == 2 and all(p is not None for _, p in seen)


def test_experiment_forwards_on_stale(tmp_path):
    from repro.core import Experiment
    qrels = ColFrame({"qid": ["q1"], "docno": ["A_d0"], "label": [1]})
    a = make_retriever("A")
    systems = [a % 2, a % 3]
    Experiment(systems, QUERIES, qrels, ["nDCG@10"],
               precompute_prefix=True, precompute_mode="plan",
               cache_dir=str(tmp_path))
    node_dirs = [d for d in os.listdir(tmp_path) if d != "plans"]
    m = CacheManifest.load(os.path.join(str(tmp_path), node_dirs[0]))
    m.fingerprint = "1" * 16
    m.save(os.path.join(str(tmp_path), node_dirs[0]))
    with pytest.raises(StaleCacheError):
        Experiment(systems, QUERIES, qrels, ["nDCG@10"],
                   precompute_prefix=True, precompute_mode="plan",
                   cache_dir=str(tmp_path))
    Experiment(systems, QUERIES, qrels, ["nDCG@10"],
               precompute_prefix=True, precompute_mode="plan",
               cache_dir=str(tmp_path), on_stale="recompute")


def test_memo_factory_wrapper_without_path_attr(tmp_path):
    """A custom wrapper need not expose .path — the plan manifest
    records dir=None for it instead of crashing."""
    import json

    class BareMemo:
        def __init__(self, stage):
            self.stage = stage

        def __call__(self, inp):
            return self.stage(inp)

    plan = ExecutionPlan([make_retriever("A")],
                         cache_dir=str(tmp_path),
                         memo_factory=lambda stage, path: BareMemo(stage))
    outs, _ = plan.run(QUERIES)
    assert len(outs[0]) == len(QUERIES) * 4
    with open(plan._plan_manifest_path) as f:
        doc = json.load(f)
    assert doc["nodes"][0]["dir"] is None
    assert doc["nodes"][0]["family"] == "BareMemo"


def test_dense_cache_recompute_keeps_docno_enumeration(tmp_path):
    """on_stale='recompute' wipes the stale entries but must not strand
    the cache: the docno enumeration (key space) is re-used so the
    usual reopen-without-docnos path recomputes instead of raising."""
    from repro.caching import DenseScorerCache

    def scorer(shift):
        def fn(inp):
            return inp.assign(score=[float(len(d)) + shift
                                     for d in inp["docno"].tolist()])
        return GenericTransformer(fn, f"scorer{shift}",
                                  key_columns=("query", "docno"),
                                  value_columns=("score",))

    rows = ColFrame({"qid": ["q1", "q1"], "query": ["alpha", "alpha"],
                     "docno": ["d0", "d1"], "score": [0.0, 0.0]})
    s1, s2 = scorer(0.0), scorer(5.0)
    with DenseScorerCache(str(tmp_path), s1, docnos=["d0", "d1"],
                          fingerprint=s1.fingerprint()) as dc:
        dc(rows)
    with pytest.raises(StaleCacheError):
        DenseScorerCache(str(tmp_path), s2, fingerprint=s2.fingerprint())
    with DenseScorerCache(str(tmp_path), s2, fingerprint=s2.fingerprint(),
                          on_stale="recompute") as dc:
        out = dc(rows)
        assert dc.stats.misses == len(rows)       # wiped -> recomputed
        assert float(out["score"][0]) == 7.0      # len("d0") + 5.0
