"""`repro cache` CLI (cli/cache.py): ls / verify / gc / export / import.

Most tests drive `repro.cli.main` in-process for speed; one slow test
exercises the real `python -m repro.cli` entry point.
"""
import json
import os
import subprocess
import sys

import pytest

from repro.caching import CacheManifest, RetrieverCache
from repro.cli import main
from repro.core import ColFrame, ExecutionPlan, GenericTransformer, add_ranks
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


@pytest.fixture
def cache_root(tmp_path):
    """A planner-populated cache root: a KeyValueCache node (sqlite), a
    RetrieverCache node (dbm), and a plan manifest."""
    root = tmp_path / "cache"
    a = make_retriever("A")
    with ExecutionPlan([QueryExpander(2) >> a, a],
                       cache_dir=str(root)) as plan:
        plan.run(QUERIES)
    return root


def _node_dirs(root):
    return sorted(d for d in os.listdir(root) if d != "plans")


# -- ls -----------------------------------------------------------------------

def test_ls_reports_dirs_and_plans(cache_root, capsys):
    assert main(["cache", "ls", str(cache_root), "--json"]) == 0
    info = json.loads(capsys.readouterr().out)
    assert len(info["dirs"]) == 3            # expander + A-under-qe + A
    families = {d["family"] for d in info["dirs"]}
    assert families == {"KeyValueCache", "RetrieverCache"}
    assert all(d["entry_count"] == len(QUERIES) for d in info["dirs"])
    assert all(d["fingerprint"] for d in info["dirs"])
    assert len(info["plans"]) == 1
    assert info["plans"][0]["n_nodes"] == 3
    assert info["plans"][0]["n_runs"] == 1


def test_ls_single_dir(cache_root, capsys):
    node = os.path.join(str(cache_root), _node_dirs(cache_root)[0])
    assert main(["cache", "ls", node, "--json"]) == 0
    info = json.loads(capsys.readouterr().out)
    assert len(info["dirs"]) == 1 and info["dirs"][0]["dir"] == "."


# -- verify -------------------------------------------------------------------

def test_verify_clean_root_exits_zero(cache_root, capsys):
    assert main(["cache", "verify", str(cache_root)]) == 0
    out = capsys.readouterr().out
    assert "0 failure(s)" in out


def test_verify_detects_hand_corrupted_manifest(cache_root, capsys):
    """Acceptance: `repro cache verify` detects a hand-corrupted
    manifest (the checksum no longer matches the edited body)."""
    node = _node_dirs(cache_root)[0]
    mpath = os.path.join(str(cache_root), node, "manifest.json")
    with open(mpath) as f:
        text = f.read()
    with open(mpath, "w") as f:
        f.write(text.replace('"entry_count": 3', '"entry_count": 999'))
    assert main(["cache", "verify", str(cache_root)]) == 1
    out = capsys.readouterr().out
    assert "checksum mismatch" in out and f"FAIL {node}" in out


def test_verify_detects_missing_store(cache_root, capsys):
    """A manifest whose recorded entries have no backing store fails."""
    info_rc = None
    for node in _node_dirs(cache_root):
        d = os.path.join(str(cache_root), node)
        m = CacheManifest.load(d)
        if m.backend == "sqlite":
            os.remove(os.path.join(d, "cache.sqlite3"))
            info_rc = node
    assert info_rc is not None
    assert main(["cache", "verify", str(cache_root)]) == 1
    assert "entry count mismatch" in capsys.readouterr().out


def test_verify_detects_plan_dir_fingerprint_divergence(cache_root, capsys):
    node = _node_dirs(cache_root)[0]
    d = os.path.join(str(cache_root), node)
    m = CacheManifest.load(d)
    m.fingerprint = "f" * 16
    m.save(d)                                # valid checksum, wrong fp
    assert main(["cache", "verify", str(cache_root)]) == 1
    assert "plan fingerprint" in capsys.readouterr().out


# -- gc -----------------------------------------------------------------------

def test_gc_dry_run_then_delete_old_dirs(cache_root, capsys):
    n_before = len(_node_dirs(cache_root))
    assert main(["cache", "gc", str(cache_root), "--older-than", "0s"]) == 0
    assert "would remove" in capsys.readouterr().out
    assert len(_node_dirs(cache_root)) == n_before       # dry run
    assert main(["cache", "gc", str(cache_root), "--older-than", "0s",
                 "--yes"]) == 0
    assert _node_dirs(cache_root) == []
    # fresh dirs survive a 1-week threshold
    assert main(["cache", "gc", str(cache_root), "--older-than", "7d",
                 "--yes"]) == 0


def test_gc_orphaned_removes_unreferenced_only(cache_root, capsys):
    stray = cache_root / "stray-dir"
    stray.mkdir()
    CacheManifest.new(family="KeyValueCache", backend="sqlite").save(
        str(stray))
    referenced = _node_dirs(cache_root)
    assert main(["cache", "gc", str(cache_root), "--orphaned",
                 "--yes"]) == 0
    left = _node_dirs(cache_root)
    assert "stray-dir" not in left
    assert left == [d for d in referenced if d != "stray-dir"]


def test_gc_requires_a_selector(cache_root):
    with pytest.raises(SystemExit):
        main(["cache", "gc", str(cache_root)])


# -- export / import ----------------------------------------------------------

def _retriever_node(cache_root):
    for node in _node_dirs(cache_root):
        d = os.path.join(str(cache_root), node)
        if CacheManifest.load(d).family == "RetrieverCache":
            return d
    raise AssertionError("no RetrieverCache node found")


def test_export_import_roundtrip_cross_backend(cache_root, tmp_path,
                                               capsys):
    """Entries export backend-agnostically: a dbm RetrieverCache node
    re-imports into a sqlite store and serves the same hits."""
    src = _retriever_node(cache_root)
    art = str(tmp_path / "node.tar")
    dest = str(tmp_path / "imported")
    assert main(["cache", "export", src, art]) == 0
    assert "entries mode" in capsys.readouterr().out
    assert main(["cache", "import", art, dest, "--backend", "sqlite"]) == 0
    m = CacheManifest.load(dest)
    assert m.backend == "sqlite" and m.entry_count == len(QUERIES)
    assert m.fingerprint == CacheManifest.load(src).fingerprint
    # the imported dir serves the cached queries with no transformer
    with RetrieverCache(dest, None, backend="sqlite") as rc:
        out = rc(QUERIES)
        assert rc.stats.hits == len(QUERIES) and rc.stats.misses == 0
        assert len(out) == len(QUERIES) * 4
    assert main(["cache", "verify", dest]) == 0


def test_import_refuses_fingerprint_mismatch(cache_root, tmp_path, capsys):
    dirs = [os.path.join(str(cache_root), d) for d in
            _node_dirs(cache_root)]
    art_a, art_b = str(tmp_path / "a.tar"), str(tmp_path / "b.tar")
    dest = str(tmp_path / "imported")
    assert main(["cache", "export", dirs[0], art_a]) == 0
    assert main(["cache", "export", dirs[1], art_b]) == 0
    assert main(["cache", "import", art_a, dest]) == 0
    with pytest.raises(SystemExit, match="fingerprint mismatch"):
        main(["cache", "import", art_b, dest])
    capsys.readouterr()
    assert main(["cache", "import", art_b, dest, "--force"]) == 0


def test_export_raw_mode_for_pickle_backend(tmp_path, capsys):
    """Backends that cannot enumerate keys export raw store files and
    re-import them verbatim."""
    from repro.caching import KeyValueCache
    src, dest = str(tmp_path / "src"), str(tmp_path / "dest")
    t = QueryExpander(2)
    with KeyValueCache(src, t, key=("qid", "query"), value=("query",),
                       backend="pickle",
                       fingerprint=t.fingerprint()) as kv:
        kv(QUERIES)
    art = str(tmp_path / "raw.tar")
    assert main(["cache", "export", src, art]) == 0
    assert "raw mode" in capsys.readouterr().out
    assert main(["cache", "import", art, dest]) == 0
    with KeyValueCache(dest, t, key=("qid", "query"), value=("query",),
                       backend="pickle",
                       fingerprint=t.fingerprint()) as kv:
        kv(QUERIES)
        assert kv.stats.hits == len(QUERIES)


def test_export_requires_manifest(tmp_path):
    plain = tmp_path / "plain"
    plain.mkdir()
    with pytest.raises(SystemExit, match="manifest"):
        main(["cache", "export", str(plain), str(tmp_path / "x.tar")])


# -- the real entry point -----------------------------------------------------

@pytest.mark.slow
def test_python_m_repro_cli_verify(cache_root):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "PYTHONPATH": os.path.join(root, "src")}
    p = subprocess.run([sys.executable, "-m", "repro.cli", "cache",
                        "verify", str(cache_root)],
                       capture_output=True, text=True, env=env, timeout=180)
    assert p.returncode == 0, p.stderr[-2000:]
    assert "0 failure(s)" in p.stdout


# -- --json scripting contract (stable key order, unchanged exit codes) ------

def _assert_stable_json(raw: str):
    """Output must be pure JSON with recursively sorted keys, so shell
    pipelines can diff two invocations without canonicalizing first."""
    doc = json.loads(raw)
    assert raw.strip() == json.dumps(doc, indent=2, sort_keys=True)
    return doc


def test_ls_json_is_stable_and_pure(cache_root, capsys):
    assert main(["cache", "ls", str(cache_root), "--json"]) == 0
    doc = _assert_stable_json(capsys.readouterr().out)
    assert set(doc) == {"root", "dirs", "plans"}
    # repeated invocations are byte-identical (modulo nothing)
    assert main(["cache", "ls", str(cache_root), "--json"]) == 0
    assert json.loads(capsys.readouterr().out)["dirs"] == doc["dirs"]


def test_verify_json_keeps_exit_codes(cache_root, capsys):
    assert main(["cache", "verify", str(cache_root), "--json"]) == 0
    doc = _assert_stable_json(capsys.readouterr().out)
    assert doc["failed"] == 0 and doc["checked"] >= 4
    assert all(r["problems"] == [] for r in doc["report"])
    # corrupt one manifest: exit code flips to 1, report names the dir
    node = _node_dirs(cache_root)[0]
    mpath = os.path.join(str(cache_root), node, "manifest.json")
    with open(mpath) as f:
        text = f.read()
    with open(mpath, "w") as f:
        f.write(text.replace('"entry_count": 3', '"entry_count": 999'))
    assert main(["cache", "verify", str(cache_root), "--json"]) == 1
    doc = _assert_stable_json(capsys.readouterr().out)
    assert doc["failed"] == 1
    bad = [r for r in doc["report"] if r["problems"]]
    assert bad[0]["dir"] == node


def test_plan_explain_json_is_stable(cache_root, capsys):
    assert main(["plan", "explain", str(cache_root), "--json"]) == 0
    docs = _assert_stable_json(capsys.readouterr().out)
    assert len(docs) == 1 and docs[0]["nodes"]
