"""The harness reads what it read before its stage kinds became modules
found by name: the same weights bit for bit, the same work and the same
compared numbers on the same seed.

The constants were computed at commit 49de1b4, where ``World`` built each
kind by a method of its own and ``check.py`` compared ``mono`` and
``duo`` by name, by the calls these tests make: SHA-256 over each
weights tree's leaves (path, shape, dtype, bytes) and whole toy runs of
seed 2**31 + 99 on the CPU, sound and control.  Counts must match
exactly; compared numbers to 1e-6 relative, since XLA on another host's
CPU may differ in the last bits."""
import hashlib
import os

import jax
import numpy as np
import pytest

from bench import run as bench_run
from bench.registry import Registry
from bench.tests import toy

WEIGHTS = {
    ("toy-rerank", "mono"):
        "cd73569548b5e4a75989bbf1e28169cc434200a29366c8c40b7b8fc4b48e32ff",
    ("toy-rerank", "duo"):
        "eb9ca2fdc2e646728bac4e0ee9683d96bd6da8bd3a0dfdc599f0e9d71b3cf2fc",
    ("toy-dense", "dense"):
        "7ebbec9230023fbcd0b4be270e70e55f33626f6e3ee03f2677a68b155aaa1e49",
}

SEED = 2 ** 31 + 99
#: a grid window this short runs exactly one iteration on any host; a
#: serving window's requests are fixed by its length
SECONDS = {"toy.grid": 1e-3, "toy.rerank": 1.0, "toy.dense": 1.0}
COUNTS = ("missing", "failed", "compiles_in_window")
_GRID_WORK = {"encoder_flops": 512123776.0, "model_flops": 512123776.0,
              "encoder_weight_bytes": 33024.0}
_RERANK_WORK = {"encoder_flops": 268823232.0, "model_flops": 268823232.0,
                "encoder_weight_bytes": 33024.0}
_DENSE_WORK = {"encoder_flops": 1233792.0, "topk_flops": 5242880.0,
               "model_flops": 6476672.0, "topk_index_bytes": 524288.0,
               "encoder_weight_bytes": 33024.0}
PINNED = {
    ("toy.grid", False): (4, _GRID_WORK, {
        "missing": 0.0, "bm25_gap": 0.0, "mono_gap": 0.0, "duo_err": 0.0,
        "rank_gap": 0.0, "failed": 0.0, "compiles_in_window": 0.0}),
    ("toy.grid", True): (4, _GRID_WORK, {
        "missing": 0.0, "bm25_gap": 0.0, "mono_gap": 0.018212399566012515,
        "duo_err": 0.06866164925018209, "rank_gap": 0.06871161048745786,
        "failed": 0.0, "compiles_in_window": 0.0}),
    ("toy.rerank", False): (20, _RERANK_WORK, {
        "missing": 0.0, "bm25_gap": 0.0, "mono_err": 0.0, "rank_gap": 0.0,
        "failed": 0.0, "compiles_in_window": 0.0}),
    ("toy.rerank", True): (20, _RERANK_WORK, {
        "missing": 0.0, "bm25_gap": 0.0, "mono_err": 0.0466769460412235,
        "rank_gap": 0.02055610875027666, "failed": 0.0,
        "compiles_in_window": 0.0}),
    ("toy.dense", False): (20, _DENSE_WORK, {
        "missing": 0.0, "dense_err": 0.0, "rank_gap": 0.0, "failed": 0.0,
        "compiles_in_window": 0.0}),
    ("toy.dense", True): (20, _DENSE_WORK, {
        "missing": 0.0, "dense_err": 0.03480258736196418,
        "rank_gap": 0.0248395659613607, "failed": 0.0,
        "compiles_in_window": 0.0}),
}


def _digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(f"{a.shape}{a.dtype}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.fixture(scope="module")
def reg(tmp_path_factory):
    root = toy.make(str(tmp_path_factory.mktemp("bench")))
    return Registry(root, os.path.join(root, "bench"))


@pytest.mark.parametrize("config,stage", sorted(WEIGHTS))
def test_each_stages_weights_are_the_pinned_ones(config, stage):
    cfg = toy.CONFIGS[config]
    spec = cfg["stages"][stage]
    params = Registry().kind(spec["kind"]).params(cfg, spec)
    assert _digest(params) == WEIGHTS[(config, stage)]


@pytest.mark.parametrize("cell,control", sorted(PINNED))
def test_work_and_compared_numbers_are_the_pinned_ones(reg, cell, control,
                                                       capsys):
    res = bench_run.run_cell(reg, toy.args(cell, seed=SEED,
                                           seconds=SECONDS[cell],
                                           control=control), toy.PEAKS)
    attempted, work, check = PINNED[(cell, control)]
    line = next(ln for ln in capsys.readouterr().out.splitlines()
                if ln.startswith("[work] "))
    got = {k: float(v) for k, v in (kv.split("=", 1)
                                    for kv in line.split()[1:])}
    assert res["attempted"] == attempted
    assert list(got.items()) == list(work.items())
    nums = {k: e["value"] for k, e in res["check"].items()}
    assert list(nums) == list(check)
    for k, want in check.items():
        if k in COUNTS:
            assert nums[k] == want, k
        else:
            assert nums[k] == pytest.approx(want, rel=1e-6), k
