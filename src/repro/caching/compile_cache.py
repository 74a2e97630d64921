"""CompileCache — "precomputation of compilation" (beyond-paper family).

On TPU the first invocation of a pipeline component is dominated not by
model compute but by XLA *compilation* (minutes for large models).  Two
experiment pipelines sharing the same scorer at the same shapes should
pay that cost once — the exact analogue, one level down, of the paper's
prefix precomputation.  ``CompileCache`` memoizes lowered+compiled
executables in-process, keyed by (function identity, abstract input
signature, jit options).

Across processes the compiled programs persist through JAX's own
persistent compilation cache, which :func:`use_persistent_compile_cache`
places: where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it, and
otherwise the cache lives at one fixed path inside the checkout
(``<repo>/.jax_cache``).  The path is part of the cache's key, so it is
never derived from a temp name, a pid or the time.
"""
from __future__ import annotations

import os
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax

__all__ = ["CompileCache", "signature_of_args", "default_compile_cache",
           "use_persistent_compile_cache", "DEFAULT_JAX_CACHE_DIR"]

#: where compiled programs persist when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset: ``.jax_cache`` at the root of the checkout (gitignored)
DEFAULT_JAX_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_persistent_compile_cache() -> str:
    """Place JAX's persistent compilation cache; call before the first
    compile.  Returns the directory in use.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX has already read it into
    ``jax_compilation_cache_dir`` and nothing is changed; otherwise the
    cache goes to :data:`DEFAULT_JAX_CACHE_DIR`.  Touches no backend.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", DEFAULT_JAX_CACHE_DIR)
    return DEFAULT_JAX_CACHE_DIR


def _abstractify(x: Any):
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return ("arr", tuple(x.shape), str(x.dtype))
    return ("lit", repr(x))


def signature_of_args(args, kwargs) -> Tuple:
    leaves, treedef = jax.tree.flatten((args, kwargs))
    return (tuple(_abstractify(l) for l in leaves), str(treedef))


@dataclass
class CompileCacheStats:
    compile_hits: int = 0
    compile_misses: int = 0
    compile_time_s: float = 0.0

    def __str__(self):
        return (f"compiles={self.compile_misses} reuses={self.compile_hits} "
                f"compile_time={self.compile_time_s:.2f}s")


class CompileCache:
    """Process-wide memo of compiled executables."""

    def __init__(self):
        self._mem: Dict[Tuple, Any] = {}
        self.stats = CompileCacheStats()

    def get_compiled(self, name: str, fn: Callable, *args,
                     jit_kwargs: Optional[dict] = None, **kwargs):
        """Return a compiled executable for fn at these (abstract) args."""
        jit_kwargs = jit_kwargs or {}
        key = (name, signature_of_args(args, kwargs),
               tuple(sorted((k, repr(v)) for k, v in jit_kwargs.items())))
        hit = self._mem.get(key)
        if hit is not None:
            self.stats.compile_hits += 1
            return hit
        t0 = time.perf_counter()
        compiled = jax.jit(fn, **jit_kwargs).lower(*args, **kwargs).compile()
        self.stats.compile_misses += 1
        self.stats.compile_time_s += time.perf_counter() - t0
        self._mem[key] = compiled
        return compiled

    def call(self, name: str, fn: Callable, *args,
             jit_kwargs: Optional[dict] = None, **kwargs):
        compiled = self.get_compiled(name, fn, *args,
                                     jit_kwargs=jit_kwargs, **kwargs)
        return compiled(*args, **kwargs)


#: module-level default instance (shared across pipeline stages)
default_compile_cache = CompileCache()
