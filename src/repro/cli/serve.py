"""``repro serve`` — stand up a serving service (or fleet) and drive it.

Builds a named pipeline from the serving registry
(``repro.serve.registry``), compiles it once through the plan compiler,
and runs a closed-loop synthetic request stream against it with N
concurrent client threads — the online analogue of the offline
benchmarks:

* ``repro serve --pipeline bm25-mono --requests 400 --clients 4``
* ``repro serve --pipeline bm25 --cache-dir .cache --explain``
* ``repro serve --pipeline bm25-sim --workers 3 --drain --json stats.json``

Everything routes through the unified serving surface
(``repro.serve.ServeConfig`` + ``build_service`` — see
``docs/serving.md``): ``--workers 1`` (default) serves in-process,
``--workers N`` launches a multi-process fleet over the same cache
directory, and ``--drain`` finishes in-flight work, refreshes the cache
manifests on disk and asserts every worker exited 0.

With ``--cache-dir`` the planner inserts the §4 cache families per node
(provenance manifests are validated once, at service start) so a second
invocation against the same directory starts warm; ``--backend``
accepts any ``caching.select_backend`` selector — ``memory`` alone
enables in-process memoization, ``mmap:sqlite`` gives fleet workers
lock-free shared hits.
"""
from __future__ import annotations

import argparse
import json
from typing import Any, Dict, Optional

__all__ = ["register", "cmd_serve", "serve_and_drive"]


def register(subparsers) -> None:
    p = subparsers.add_parser(
        "serve", help="serve a registry pipeline with micro-batching",
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--pipeline", default="bm25-mono",
                   help="serving pipeline name (see repro.serve.registry; "
                        "default: bm25-mono)")
    p.add_argument("--scale", type=float, default=0.05,
                   help="synthetic corpus scale (default 0.05)")
    p.add_argument("--cutoff", type=int, default=10,
                   help="rank cutoff of the retrieval stage")
    p.add_argument("--num-results", type=int, default=100,
                   help="retriever depth before the cutoff (pushdown "
                        "fuses the two)")
    p.add_argument("--requests", type=int, default=200)
    p.add_argument("--clients", type=int, default=4,
                   help="closed-loop client threads")
    p.add_argument("--max-batch", type=int, default=16,
                   help="micro-batch flush threshold")
    p.add_argument("--max-wait-ms", type=float, default=2.0,
                   help="micro-batch flush timeout (ms)")
    p.add_argument("--workers", type=int, default=1,
                   help="worker PROCESSES (1 = in-process service, N>1 = "
                        "multi-process fleet over the shared cache dir)")
    p.add_argument("--exec-workers", type=int, default=4,
                   help="executor thread-pool size per service")
    p.add_argument("--cache-dir", default=None,
                   help="planner cache root (persists across runs; "
                        "shared by all fleet workers)")
    p.add_argument("--backend", default=None,
                   help="cache backend selector (caching.select_backend: "
                        "memory/pickle/dbm/sqlite, tiered:<disk>, "
                        "mmap:<disk>)")
    p.add_argument("--no-optimize", action="store_true",
                   help="serve the naive lowered plan (baseline)")
    p.add_argument("--no-warm-start", action="store_true",
                   help="fleet workers skip replaying expected traffic "
                        "through their plan on start")
    p.add_argument("--drain", action="store_true",
                   help="gracefully drain on shutdown: finish in-flight "
                        "work, refresh manifests, assert workers exit 0")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--explain", action="store_true",
                   help="print the compiled plan with online latency "
                        "annotations after the run (workers=1 only)")
    p.add_argument("--json", default=None, metavar="PATH", dest="json_out",
                   help="write run statistics as JSON")
    p.set_defaults(func=cmd_serve)


def serve_and_drive(*, pipeline: str, scale: float, cutoff: int,
                    num_results: int, requests: int, clients: int,
                    max_batch: int,
                    max_wait_ms: float, workers: int = 1,
                    exec_workers: int = 4,
                    cache_dir: Optional[str] = None,
                    backend: Optional[str] = None,
                    optimize: str = "all", seed: int = 0,
                    explain: bool = False, drain: bool = False,
                    warm_start: bool = True) -> Dict[str, Any]:
    """Build the scenario, stand the service (or fleet) up, run the
    closed loop, return a JSON-able stats record.  Thin kwargs shim
    over :func:`repro.serve.drive_closed_loop` kept for callers of the
    historical flat signature; ``workers`` now counts worker
    *processes* (``exec_workers`` is the per-service thread pool)."""
    from ..serve import ServeConfig, drive_closed_loop

    cfg = ServeConfig(pipeline=pipeline, scale=scale, cutoff=cutoff,
                      num_results=num_results, seed=seed,
                      cache_dir=cache_dir, backend=backend,
                      optimize=optimize, max_batch=max_batch,
                      max_wait_ms=max_wait_ms, exec_workers=exec_workers,
                      workers=workers, warm_start=warm_start)
    return drive_closed_loop(cfg, requests=requests, clients=clients,
                             explain=explain, drain=drain)


def cmd_serve(args) -> int:
    from ..caching import select_backend

    if args.backend is not None:
        select_backend(args.backend)     # fail fast on a bad selector
    record = serve_and_drive(
        pipeline=args.pipeline, scale=args.scale, cutoff=args.cutoff,
        num_results=args.num_results, requests=args.requests,
        clients=args.clients, max_batch=args.max_batch,
        max_wait_ms=args.max_wait_ms, workers=args.workers,
        exec_workers=args.exec_workers,
        cache_dir=args.cache_dir, backend=args.backend,
        optimize="none" if args.no_optimize else "all",
        seed=args.seed, explain=args.explain, drain=args.drain,
        warm_start=not args.no_warm_start)
    explained = record.pop("_explain", None)
    print(f"served {record['requests']} requests from "
          f"{record['clients']} clients in {record['wall_s']}s "
          f"({record['throughput_rps']} req/s, "
          f"workers={record['workers']})")
    print(f"p50={record['p50_ms']:.2f}ms p99={record['p99_ms']:.2f}ms "
          f"hit_rate={record['hit_rate']:.3f} "
          f"occupancy={record['online']['batch_occupancy']:.2f}")
    if "fleet" in record:
        fl = record["fleet"]
        codes = fl["exit_codes"]
        print(f"fleet: respawns={fl['respawns']} "
              f"requeued={fl['requeued']} exit_codes="
              f"{[codes[k] for k in sorted(codes)]}")
        if args.drain and any(c != 0 for c in codes.values()):
            print("drain FAILED: nonzero worker exit code")
            return 1
    if explained is not None:
        print()
        print(explained)
    if args.json_out:
        with open(args.json_out, "w", encoding="utf-8") as f:
            json.dump(record, f, indent=2, sort_keys=True)
        print(f"wrote {args.json_out}")
    return 0
