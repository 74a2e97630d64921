"""``repro`` command line (also invocable as ``python -m repro.cli``).

Subcommands register themselves on the top-level parser:

* ``repro cache`` (``cli/cache.py``) — inspection, verification,
  garbage collection and export/import of cache directories built on
  the provenance manifests of ``caching/provenance.py``;
* ``repro plan`` (``cli/plan.py``) — render recorded execution plans
  with the same ASCII tree as ``ExecutionPlan.explain()``;
* ``repro serve`` (``cli/serve.py``) — stand up a ``PipelineService``
  over a registry pipeline and drive it with a closed-loop request
  stream (micro-batching, planner caches, online latency stats).
"""
from __future__ import annotations

import argparse
from typing import List, Optional

__all__ = ["main", "build_parser"]


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="repro",
        description="Precomputation & caching in IR experiments — tooling")
    sub = ap.add_subparsers(dest="command", required=True)
    from . import cache as _cache
    from . import plan as _plan
    from . import serve as _serve
    _cache.register(sub)
    _plan.register(sub)
    _serve.register(sub)
    return ap


def main(argv: Optional[List[str]] = None) -> int:
    from ..caching import use_persistent_compile_cache
    args = build_parser().parse_args(argv)
    use_persistent_compile_cache()
    return int(args.func(args) or 0)
