"""Online-serving benchmark: closed-loop request stream, cold vs warm.

Stands up a :class:`~repro.serve.PipelineService` over the two-stage
``bm25 % k >> text_loader >> mono_scorer`` pipeline and drives it with
N closed-loop client threads (each submits one query at a time and
waits — concurrency equals the client count, the service's
micro-batching does the coalescing).  Two epochs over one cache
directory:

* **cold** — a fresh cache directory: every request pays retrieval and
  the jitted reranker;
* **warm** — a *new service instance* over the same directory
  (provenance manifests re-validated once at its start).  Both epochs
  run in one process, so JAX's compile cache stays warm across them —
  the latency comparison shows the caching win on top of compilation;
  the *correctness* gate is the miss count: a warm epoch whose reads
  actually come from the store misses **zero** times (zipf traffic
  only repeats topic-pool queries the cold epoch already cached);
* **warmed** — speculative precomputation instead of organic traffic:
  ``repro.caching.warm_scenario`` precomputes a *fresh* directory
  offline over the scenario's expected traffic distribution, then a
  first-ever service runs over it.  Its very first epoch should look
  like steady state — the cold-start tail collapses without any prior
  serve epoch having touched the directory.

Reported per epoch: request p50/p99 latency, throughput, cache
hits/misses + hit rate, micro-batch occupancy and per-node online
latency — the request-level view of the paper's Table-2 mechanism.
The CI ``serve-smoke`` job asserts ``warm p50 < cold p50`` AND
``warm cache_misses == 0`` from the ``--json`` artifact (the second
catches a broken warm-restart path that latency alone cannot); the
``cache-lifecycle`` job additionally asserts the warmed-start epoch
misses zero times with first-epoch p50 within 1.3x of the organic
warm epoch's.

With ``--fleet`` two additional epochs measure the multi-process serve
fleet (``repro.serve.FleetService``) on the ``bm25-sim`` scenario —
bm25 served from a warmed shared cache (``mmap:sqlite`` read-mostly
tier) followed by an *uncacheable* simulated per-row device latency,
so throughput measures serving capacity rather than cache lookups:
one worker process vs ``--fleet-workers`` processes over the same
cache directory, same request stream.  The row set gains
``fleet_scaling`` (N-worker / 1-worker throughput; ≥3x on a warm
4-worker fleet since the simulated device waits overlap across
processes) and a per-qid ``bit_identical`` gate: every topic served
through the fleet must equal the offline ``pipeline(topics)`` frame
bit-for-bit.

``--quick`` shrinks the workload for CI; ``--json PATH`` writes
``{"rows": [...]}`` with one row per epoch.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import tempfile
from typing import Dict, List, Optional

import numpy as np

from repro.caching import use_persistent_compile_cache, warm_scenario
from repro.serve import (PipelineService, ServeConfig, build_scenario,
                         build_service, run_closed_loop)


def run_epoch(name: str, scenario, cache_dir: str, *, requests: int,
              clients: int, max_batch: int, max_wait_ms: float,
              workers: int, seed: int, prefetch: bool = True) -> Dict:
    svc = PipelineService(scenario.pipeline, cache_dir=cache_dir,
                          max_batch=max_batch, max_wait_ms=max_wait_ms,
                          max_workers=workers, prefetch=prefetch)
    try:
        loop = run_closed_loop(svc, scenario, n_requests=requests,
                               n_clients=clients, seed=seed)
        summary = svc.stats.summary()
        online = svc.online_stats.as_dict(svc.max_batch)
    finally:
        svc.close()
    row = {"name": name, "prefetch": prefetch, **loop,
           "p50_ms": round(summary["p50_ms"], 4),
           "p99_ms": round(summary["p99_ms"], 4),
           "hit_rate": round(summary["hit_rate"], 4),
           "cache_hits": online["cache_hits"],
           "cache_misses": online["cache_misses"],
           "batches": summary["batches"],
           "batch_occupancy": online["batch_occupancy"],
           "flush_size": online["flush_size"],
           "flush_timeout": online["flush_timeout"],
           "nodes": online["nodes"]}
    print(f"[{name}] p50={row['p50_ms']}ms p99={row['p99_ms']}ms "
          f"hit_rate={row['hit_rate']} "
          f"throughput={row['throughput_rps']} req/s "
          f"occupancy={row['batch_occupancy']}")
    return row


def run_fleet_epoch(name: str, cfg: ServeConfig, *, requests: int,
                    clients: int, seed: int,
                    check_identity: bool = False) -> Dict:
    # with workers > 1 the parent generates traffic from the corpus
    # alone and builds the pipeline only after the fleet has drained:
    # the devices belong to the worker processes while they run
    scenario = cfg.build_scenario() if cfg.workers == 1 \
        else cfg.build_traffic()
    served = {}
    svc = build_service(cfg, scenario=scenario if cfg.workers == 1 else None)
    try:
        loop = run_closed_loop(svc, scenario, n_requests=requests,
                               n_clients=clients, seed=seed)
        if check_identity:
            # serve every topic once more, kept for the offline check
            qids = [str(q) for q in scenario.topics["qid"].tolist()]
            futs = [(qid, svc.submit(qid, query,
                                     **scenario.request_extra.get(qid, {})))
                    for qid, query in zip(qids,
                                          scenario.topics["query"].tolist())]
            served = {qid: fut.result(120) for qid, fut in futs}
        if cfg.workers > 1:
            report = svc.drain()
            online = report["online"]
            exit_codes = report["exit_codes"]
        else:
            online = svc.online_stats.as_dict(svc.max_batch)
            exit_codes = None
        summary = svc.stats.summary()
    finally:
        svc.close()
    identical = None
    if check_identity:
        # every served per-qid frame equals the offline pipeline run,
        # bit for bit
        full = scenario if scenario.pipeline is not None \
            else cfg.build_scenario()
        offline = full.pipeline(full.topics)
        identical = all(
            frame.equals(offline.take(np.nonzero(offline["qid"] == qid)[0]))
            for qid, frame in served.items())
    row = {"name": name, "workers": cfg.workers, **loop,
           "p50_ms": round(summary["p50_ms"], 4),
           "p99_ms": round(summary["p99_ms"], 4),
           "hit_rate": round(summary["hit_rate"], 4),
           "cache_hits": online["cache_hits"],
           "cache_misses": online["cache_misses"]}
    if identical is not None:
        row["bit_identical"] = identical
    if exit_codes is not None:
        row["exit_codes"] = {str(k): v for k, v in exit_codes.items()}
    print(f"[{name}] workers={cfg.workers} "
          f"throughput={row['throughput_rps']} req/s "
          f"p50={row['p50_ms']}ms misses={row['cache_misses']}"
          + (f" bit_identical={identical}" if identical is not None else ""))
    return row


def main(argv: Optional[List[str]] = None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="small workload for the CI smoke job")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write rows as a JSON artifact")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--scale", type=float, default=None)
    ap.add_argument("--cutoff", type=int, default=10)
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--cache-dir", default=None,
                    help="cache root (default: a temp dir per run)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-prefetch", action="store_true",
                    help="ablation: serve every epoch with the async "
                         "data plane's query-keyed prefetch disabled "
                         "(PipelineService(prefetch=False)); without "
                         "this flag a serve_warm_noprefetch epoch is "
                         "added so the artifact carries the paired "
                         "comparison either way")
    ap.add_argument("--fleet", action="store_true",
                    help="add the multi-process fleet scaling epochs")
    ap.add_argument("--fleet-workers", type=int, default=4,
                    help="fleet size of the scaled epoch (vs 1 worker)")
    ap.add_argument("--fleet-clients", type=int, default=16,
                    help="closed-loop clients of the fleet epochs")
    args = ap.parse_args(argv)

    requests = args.requests or (120 if args.quick else 600)
    scale = args.scale or (0.02 if args.quick else 0.05)

    use_persistent_compile_cache()
    tmp = None
    cache_dir = args.cache_dir
    if cache_dir is None:
        tmp = tempfile.TemporaryDirectory(prefix="serve-bench-")
        cache_dir = tmp.name
    rows = []

    fleet_scaling = None
    if args.fleet:
        # fleet epochs first, while this process is still off JAX (a
        # device belongs to one process): warmed shared cache (mmap read-mostly tier) +
        # uncacheable simulated device latency; max_batch=1 /
        # exec_workers=1 model one synchronous replica per process, so
        # the only parallelism measured is the fleet's
        fleet_dir = os.path.join(cache_dir, "fleet")
        base = ServeConfig(pipeline="bm25-sim", scale=scale,
                           cutoff=args.cutoff, num_results=100,
                           seed=args.seed, cache_dir=fleet_dir,
                           backend="mmap:sqlite", max_batch=1,
                           max_wait_ms=0.0, exec_workers=1)
        fleet_offline = warm_scenario(None, fleet_dir, config=base)
        print(f"[fleet_offline] precomputed "
              f"{fleet_offline['queries_warmed']} query(s) into the "
              f"shared {base.backend} store")
        fleet_requests = args.requests or (160 if args.quick else 400)
        w1 = run_fleet_epoch("fleet_w1", base,
                             requests=fleet_requests,
                             clients=args.fleet_clients, seed=args.seed)
        wn = run_fleet_epoch(f"fleet_w{args.fleet_workers}",
                             dataclasses.replace(
                                 base, workers=args.fleet_workers),
                             requests=fleet_requests,
                             clients=args.fleet_clients, seed=args.seed,
                             check_identity=True)
        rows.extend([w1, wn])
        fleet_scaling = round(
            wn["throughput_rps"] / max(w1["throughput_rps"], 1e-9), 2)
        print(f"fleet scaling 1->{args.fleet_workers}: {fleet_scaling}x "
              f"(bit_identical={wn['bit_identical']})")

    scenario = build_scenario("bm25-mono", scale=scale, cutoff=args.cutoff,
                              num_results=100, seed=args.seed)

    prefetch = not args.no_prefetch
    for epoch in ("serve_cold", "serve_warm"):
        rows.append(run_epoch(epoch, scenario, cache_dir,
                              requests=requests, clients=args.clients,
                              max_batch=args.max_batch,
                              max_wait_ms=args.max_wait_ms,
                              workers=args.workers, seed=args.seed,
                              prefetch=prefetch))
    cold, warm = rows[-2:]
    print(f"warm/cold p50: {warm['p50_ms']}/{cold['p50_ms']}ms "
          f"({cold['p50_ms'] / max(warm['p50_ms'], 1e-9):.1f}x)")

    if prefetch:
        # ablation epoch: same warm directory, prefetch off — the JSON
        # artifact then carries the paired data-plane comparison
        noprefetch = run_epoch("serve_warm_noprefetch", scenario, cache_dir,
                               requests=requests, clients=args.clients,
                               max_batch=args.max_batch,
                               max_wait_ms=args.max_wait_ms,
                               workers=args.workers, seed=args.seed,
                               prefetch=False)
        rows.append(noprefetch)
        print(f"warm p50 prefetch on/off: {warm['p50_ms']}/"
              f"{noprefetch['p50_ms']}ms (misses="
              f"{noprefetch['cache_misses']})")

    # warmed-start epoch: precompute a FRESH directory offline, then
    # measure the first-ever service over it (same process, so the JIT
    # compile cache is equally warm — the comparison isolates the cache
    # effect from compilation)
    warmed_dir = os.path.join(cache_dir, "warmed-start")
    offline = warm_scenario(scenario, warmed_dir,
                            clients=args.clients, seed=args.seed)
    print(f"[warm_offline] precomputed {offline['queries_warmed']} "
          f"query(s), {offline['cache_misses']} entries computed, "
          f"{offline['wall_s']}s")
    warmed = run_epoch("serve_warmed", scenario, warmed_dir,
                       requests=requests, clients=args.clients,
                       max_batch=args.max_batch,
                       max_wait_ms=args.max_wait_ms,
                       workers=args.workers, seed=args.seed)
    rows.append(warmed)
    print(f"warmed/warm p50: {warmed['p50_ms']}/{warm['p50_ms']}ms "
          f"({warmed['p50_ms'] / max(warm['p50_ms'], 1e-9):.2f}x, "
          f"misses={warmed['cache_misses']})")

    if args.json:
        payload = {"rows": rows, "requests": requests, "scale": scale,
                   "clients": args.clients, "max_batch": args.max_batch,
                   "max_wait_ms": args.max_wait_ms,
                   "warm_offline": offline}
        if fleet_scaling is not None:
            payload["fleet_scaling"] = fleet_scaling
            payload["fleet_workers"] = args.fleet_workers
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"[wrote {args.json}]")
    if tmp is not None:
        tmp.cleanup()
    return rows


if __name__ == "__main__":
    main()
