"""TPU adaptations: bucketed miss execution + CompileCache, and where
JAX's persistent compilation cache is placed."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.caching import BucketedRunner, CompileCache, bucket_size, \
    pad_batch
from repro.caching.compile_cache import DEFAULT_JAX_CACHE_DIR


def test_bucket_size_powers_of_two():
    assert bucket_size(1) == 8
    assert bucket_size(8) == 8
    assert bucket_size(9) == 16
    assert bucket_size(1000) == 1024


@given(st.integers(1, 5000))
@settings(max_examples=100, deadline=None)
def test_property_bucket_bounds(n):
    b = bucket_size(n)
    assert b >= min(n, 8)
    assert b & (b - 1) == 0          # power of two
    assert b < 2 * max(n, 8)


def test_pad_batch_repeats_row0():
    a = np.arange(6).reshape(3, 2)
    p = pad_batch(a, 5)
    assert p.shape == (5, 2)
    assert (p[3:] == a[0]).all()


def test_bucketed_runner_bounded_shapes_and_exact_results():
    compiled_shapes = []
    @jax.jit
    def fn(x):
        compiled_shapes.append(x.shape)
        return x.sum(axis=1)
    runner = BucketedRunner(lambda x: fn(jnp.asarray(x)), floor=8,
                            max_bucket=64)
    rng = np.random.default_rng(0)
    sizes = [3, 7, 9, 17, 33, 63, 64, 65, 129, 5, 31]
    for n in sizes:
        x = rng.normal(size=(n, 4)).astype(np.float32)
        out = runner(x)
        assert out.shape == (n,)
        np.testing.assert_allclose(out, x.sum(1), rtol=1e-5, atol=1e-6)
    # O(log max_bucket) distinct compiled shapes
    assert len(set(runner.shapes_issued)) <= 5


def test_compile_cache_reuses_executables():
    cc = CompileCache()
    def f(x):
        return x * 2 + 1
    x = jnp.ones((16, 8))
    y1 = cc.call("f", f, x)
    y2 = cc.call("f", f, x)
    assert cc.stats.compile_misses == 1
    assert cc.stats.compile_hits == 1
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2))
    # different shape -> new compile
    cc.call("f", f, jnp.ones((32, 8)))
    assert cc.stats.compile_misses == 2
    # same shapes under a different name -> separate entry
    cc.call("g", f, x)
    assert cc.stats.compile_misses == 3


_PLACE_AND_COMPILE = (
    "import os, jax, jax.numpy as jnp\n"
    "from repro.caching import use_persistent_compile_cache\n"
    "print(use_persistent_compile_cache())\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
    "jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()\n")


@pytest.mark.parametrize("env_set", [True, False], ids=["env-set", "unset"])
def test_persistent_compile_cache_placement(tmp_path, env_set):
    """With JAX_COMPILATION_CACHE_DIR set, JAX's own reading stands and
    compiled programs land there; unset, the helper picks the one fixed
    path in the checkout (never a temp name, pid or time)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = os.path.join(root, "src")
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    want = DEFAULT_JAX_CACHE_DIR
    if env_set:
        want = str(tmp_path / "outside")
        env["JAX_COMPILATION_CACHE_DIR"] = want
    p = subprocess.run([sys.executable, "-c", _PLACE_AND_COMPILE],
                       capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.split() == [want, want]
    assert want == DEFAULT_JAX_CACHE_DIR or os.listdir(want)
    assert DEFAULT_JAX_CACHE_DIR == os.path.join(root, ".jax_cache")
