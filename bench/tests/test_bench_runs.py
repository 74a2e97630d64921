"""Whole runs of toy cells on the CPU: the harness past its look for a
chip, with the real drivers, comparison and metric readers.  A sound run
is correct; the lower-precision control and an answer altered where the
program produces it are not.  ``toy.pw`` runs a scorer kind that the
harness does not have, added to the toy tree as files and entries only."""
import filecmp
import os

import numpy as np
import pytest

from bench import run as bench_run
from bench.registry import Registry
from bench.tests import toy

CELLS = ["toy.grid", "toy.rerank", "toy.dense", "toy.pw"]


@pytest.fixture(scope="module")
def reg(tmp_path_factory):
    root = toy.make(str(tmp_path_factory.mktemp("bench")))
    return Registry(root, os.path.join(root, "bench"))


@pytest.mark.parametrize("cell", CELLS)
def test_toy_cell_runs_correct(reg, cell):
    res = bench_run.run_cell(reg, toy.args(cell, seed=2 ** 31 + 11), toy.PEAKS)
    assert res["correct"], res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in
                                   reg.metrics(cell, trace=False)}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "check"
    assert res["device"]["platform"] == "cpu"


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(reg, cell):
    res = bench_run.run_cell(reg, toy.args(cell, seed=3, control=True),
                             toy.PEAKS)
    assert not res["correct"], res["check"]


def _alter_scores(frame):
    """Move each query's first score far from what the scorer computed."""
    if len(frame) == 0:
        return frame
    s = np.asarray(frame["score"], np.float64).copy()
    first = [idx[0] for idx in frame.group_indices(["qid"]).values()]
    s[first] += 10.0 * (np.abs(s).max() + 1.0)
    return frame.assign(score=s)


@pytest.mark.parametrize("cell,target", [
    ("toy.grid", "repro.models.cross_encoder.DuoScorer"),
    ("toy.rerank", "repro.models.cross_encoder.MonoScorer"),
    ("toy.dense", "repro.ir.dense.DenseRetriever"),
    ("toy.pw", "repro.models.cross_encoder.MonoScorer"),
])
def test_an_altered_answer_is_not_correct(reg, cell, target, monkeypatch):
    import importlib
    mod, cls = target.rsplit(".", 1)
    klass = getattr(importlib.import_module(mod), cls)
    orig = klass.transform
    monkeypatch.setattr(klass, "transform",
                        lambda self, inp: _alter_scores(orig(self, inp)))
    res = bench_run.run_cell(reg, toy.args(cell, seed=4), toy.PEAKS)
    assert not res["correct"], res["check"]


def test_the_toy_tree_adds_files_and_edits_none_of_the_harness(reg):
    """The new kind is one new file beside byte-identical copies of the
    repository's kinds, metric readers and peaks."""
    for sub in ("kinds", "metrics"):
        ours = {f for f in os.listdir(os.path.join(toy.BENCH, sub))
                if f.endswith(".py")}
        theirs = {f for f in os.listdir(os.path.join(reg.bench, sub))
                  if f.endswith(".py")}
        assert ours <= theirs
        assert theirs - ours == ({"pw.py"} if sub == "kinds" else
                                 {"toy_topics_per_iteration.grid.py"})
        for f in ours:
            assert filecmp.cmp(os.path.join(toy.BENCH, sub, f),
                               os.path.join(reg.bench, sub, f),
                               shallow=False), f
    assert filecmp.cmp(os.path.join(toy.BENCH, "peaks.json"),
                       os.path.join(reg.bench, "peaks.json"), shallow=False)
    assert reg.config("toy-pw")["stages"]["pw"] == {"kind": "pw",
                                                    "stream": 4}
