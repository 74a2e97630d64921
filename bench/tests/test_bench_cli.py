"""The command refuses to measure where it cannot: with no TPU, and in a
directory that holds only the benchmark."""
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ARGS = ["--workload", "grid.bm25-minilm.table2", "--seed", "1",
        "--seconds", "1", "--trace", "0"]


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, os.path.join(root, "bench",
                                                        "run.py"), *ARGS],
                          cwd=root, env=env, capture_output=True, text=True,
                          timeout=600)


def _has_result(stdout: str) -> bool:
    return any(line.startswith("{") for line in stdout.splitlines())


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(REPO)
    assert p.returncode != 0
    assert not _has_result(p.stdout)
    assert "TPU" in p.stderr


@pytest.mark.parametrize("layout", ["benchmark_only"])
def test_benchmark_alone_exits_nonzero_without_a_result(tmp_path, layout):
    root = str(tmp_path / layout)
    os.makedirs(root)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "_work"))
    p = _run(root)
    assert p.returncode != 0
    assert not _has_result(p.stdout)
