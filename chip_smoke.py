#!/usr/bin/env python3
"""Smoke test of the serving path on a TPU chip.

    python chip_smoke.py                 # one chip
    python chip_smoke.py --four-chips    # the fleet across four chips

One chip, one process, which holds the chip:

1. platform check — exits nonzero, without the result line, unless JAX's
   first device is a TPU;
2. the ``hybrid`` scenario (BM25 on the host; the dense encoder, the
   dense top-k and the mono cross-encoder on the device) over
   ``msmarco_like(scale=1.0)`` — 9,000 docs, 43 topics — served through
   ``ServeConfig`` / ``build_service`` / ``run_closed_loop``, the code
   behind ``repro serve``: a cold epoch, then a warm epoch on a new
   service over the same cache directory (which must miss zero times);
   after each epoch's closed loop every topic is served once more;
3. every topic's served frame against the offline ``ExecutionPlan.run``
   of a freshly built copy of the same pipeline: identical docnos in
   identical rank order and scores within ``rtol=SCORE_RTOL,
   atol=SCORE_ATOL``; whether they are also bitwise identical is
   printed;
4. kernel parity: ``dense_topk_op`` compiled for the chip against
   ``dense_topk_ref`` (matmuls at ``highest`` precision), at the
   scenario's own corpus matrix and at a seeded random bf16 matrix of
   2**20 x 768 (1.5 GiB, made on the device).  Its entries are small
   integers, so every score is exact in float32 whatever the
   accumulation order: indices must be identical and scores equal,
   which also exercises the tie-break (descending score, then ascending
   index) on many real ties.

``--four-chips`` runs only the fleet path and what it is compared with:
a ``hybrid`` fleet of four workers, each pinned to one chip, and a
one-worker fleet over the same traffic, with this process off JAX
while they run; then, after both have drained, the offline plan in this
process, whose dense index is row-sharded over all four chips.  All
three must agree per qid under the rule of step 3.

Earlier lines carry phase timings, compile counts and seconds, cache
hits and misses and the device kind.  The last line is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
printed only when every phase passed; any failure exits nonzero.
Outputs (the cache directory, per-qid offline results) go to ``--out``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SCALE = 1.0
REQUESTS = 200
CLIENTS = 4
SEED = 0
#: served and offline scores must agree to this (relative, absolute)
SCORE_RTOL = 1e-5
SCORE_ATOL = 1e-6
#: the large kernel-parity matrix: 2**20 docs x 768 dims in bf16
BIG_N, BIG_D, BIG_K = 1 << 20, 768, 100


class Smoke:
    """Phase runner: times each phase, records failures, never hides
    one (``main`` exits nonzero if any phase failed)."""

    def __init__(self):
        self.failures = []
        self.timings = {}

    @contextlib.contextmanager
    def phase(self, name):
        log(f"[phase] {name} ...")
        t0 = time.perf_counter()
        try:
            yield
        except Exception as e:           # noqa: BLE001 - recorded, fails run
            import traceback
            traceback.print_exc()
            self.fail(f"{name}: {type(e).__name__}: {e}")
        finally:
            self.timings[name] = time.perf_counter() - t0
            log(f"[phase] {name} took {self.timings[name]:.3f}s")

    def check(self, ok, what):
        log(f"[check] {'PASS' if ok else 'FAIL'} {what}")
        if not ok:
            self.fail(what)

    def fail(self, what):
        self.failures.append(what)


def log(msg):
    print(msg, flush=True)


def frames_by_qid(frame):
    import numpy as np
    return {str(q): frame.take(np.nonzero(frame["qid"] == q)[0])
            for q in np.unique(frame["qid"])}


def compare(label, served, offline, smoke):
    """Per-qid rule: identical docnos in identical rank order, scores
    within tolerance; also reports how many qids are bitwise equal."""
    import numpy as np
    ref = frames_by_qid(offline)
    mismatched, bitwise, worst = [], 0, 0.0
    for qid, got in sorted(served.items()):
        want = ref.get(qid)
        if want is None:
            mismatched.append(qid)
            continue
        got = got.sort_values(["rank"])
        want = want.sort_values(["rank"])
        same_order = (got["docno"].tolist() == want["docno"].tolist()
                      and np.array_equal(got["rank"], want["rank"]))
        gs = np.asarray(got["score"], np.float64)
        ws = np.asarray(want["score"], np.float64)
        if same_order:
            worst = max(worst, float(np.max(np.abs(gs - ws), initial=0.0)))
        if not (same_order and np.allclose(gs, ws, rtol=SCORE_RTOL,
                                           atol=SCORE_ATOL)):
            mismatched.append(qid)
        elif same_order and gs.tobytes() == ws.tobytes():
            bitwise += 1
    log(f"[{label}] qids={len(served)} agree={len(served) - len(mismatched)} "
        f"bitwise_identical={bitwise}/{len(served)} "
        f"max_abs_score_diff={worst!r}")
    if mismatched:
        log(f"[{label}] mismatched qids: {mismatched[:10]}")
    smoke.check(not mismatched and len(served) == len(ref),
                f"{label}: every qid has the offline docnos in rank order, "
                f"scores within rtol={SCORE_RTOL} atol={SCORE_ATOL}")
    return bitwise == len(served)


def serve_all_topics(svc, scenario):
    qids = [str(q) for q in scenario.topics["qid"].tolist()]
    futs = [(qid, svc.submit(qid, query,
                             **scenario.request_extra.get(qid, {})))
            for qid, query in zip(qids, scenario.topics["query"].tolist())]
    return {qid: fut.result(300) for qid, fut in futs}


def find_dense_index(t):
    """The DenseIndex inside a pipeline expression (or None)."""
    from repro.ir.dense import DenseRetriever
    if isinstance(t, DenseRetriever):
        return t.index
    for child in (list(getattr(t, "stages", ()))
                  + [getattr(t, a, None) for a in ("left", "right")]):
        if child is not None:
            found = find_dense_index(child)
            if found is not None:
                return found
    return None


def kernel_parity(label, q, c, k, smoke, *, exact):
    import jax
    import numpy as np
    from repro.kernels.dense_topk import dense_topk_op, dense_topk_ref
    t0 = time.perf_counter()
    v, i = jax.block_until_ready(dense_topk_op(q, c, k=k))
    t_kernel = time.perf_counter() - t0
    with jax.default_matmul_precision("highest"):
        rv, ri = jax.block_until_ready(dense_topk_ref(q, c, k=k))
    v, i, rv, ri = (np.asarray(a) for a in (v, i, rv, ri))
    diff = float(np.max(np.abs(v - rv)))
    same_idx = np.array_equal(i, ri)
    log(f"[{label}] q={tuple(q.shape)} c={tuple(c.shape)} {c.dtype} k={k} "
        f"indices_identical={same_idx} "
        f"mismatched_positions={int(np.sum(i != ri))} "
        f"max_abs_score_diff={diff!r} first_call_s={t_kernel:.3f}")
    smoke.check(same_idx, f"{label}: dense_topk indices == lax.top_k")
    if exact:
        smoke.check(diff == 0.0, f"{label}: scores exactly equal")
    else:
        smoke.check(bool(np.allclose(v, rv, rtol=1e-5, atol=1e-5)),
                    f"{label}: scores within rtol=1e-5 atol=1e-5")


class CompileMeter:
    """JAX's own compile events, over every jitted program: backend
    compiles and their seconds (a persistent-cache hit counts as one,
    with its load time), and persistent-cache hits and misses."""

    def __init__(self):
        import jax
        self.compiles, self.compile_s = 0, 0.0
        self.cache_hits, self.cache_misses = 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1


def print_compile_stats(meter):
    from repro.caching import default_compile_cache
    s = default_compile_cache.stats
    log(f"[compile] repro CompileCache: compiles={s.compile_misses} "
        f"reuses={s.compile_hits} compile_s={s.compile_time_s:.3f}; "
        f"JAX backend compiles={meter.compiles} "
        f"compile_s={meter.compile_s:.3f} persistent_cache "
        f"hits={meter.cache_hits} misses={meter.cache_misses}")


def one_chip(args, smoke, meter):
    import jax
    import numpy as np
    from repro.core import ExecutionPlan
    from repro.serve import ServeConfig, build_service, run_closed_loop

    cfg = ServeConfig(pipeline="hybrid", scale=SCALE, seed=SEED,
                      cache_dir=os.path.join(args.out, "cache"))
    with smoke.phase("build hybrid scenario"):
        scenario = cfg.build_scenario()
    log(f"[scenario] {scenario.description}; "
        f"docs={len(find_dense_index(scenario.pipeline).docnos)} "
        f"topics={len(scenario.topics)}")

    served = {}
    for epoch in ("cold", "warm"):
        with smoke.phase(f"serve {epoch} epoch"):
            svc = build_service(cfg, scenario=scenario)
            try:
                loop = run_closed_loop(svc, scenario, n_requests=REQUESTS,
                                       n_clients=CLIENTS, seed=SEED)
                online = svc.online_stats.as_dict(svc.max_batch)
                summary = svc.stats.summary()
                served[epoch] = serve_all_topics(svc, scenario)
            finally:
                svc.close()
            log(f"[{epoch}] requests={loop['requests']} "
                f"clients={loop['clients']} wall_s={loop['wall_s']} "
                f"throughput_rps={loop['throughput_rps']} "
                f"p50_ms={summary['p50_ms']!r} p99_ms={summary['p99_ms']!r} "
                f"cache_hits={online['cache_hits']} "
                f"cache_misses={online['cache_misses']}")
            smoke.check(loop["requests"] == REQUESTS,
                        f"{epoch} epoch served all {REQUESTS} requests")
            if epoch == "warm":
                smoke.check(online["cache_misses"] == 0,
                            "warm epoch has zero cache misses")
        print_compile_stats(meter)

    with smoke.phase("offline plan (fresh pipeline)"):
        fresh = cfg.build_scenario()
        outs, _ = ExecutionPlan([fresh.pipeline]).run(fresh.topics)
        offline = outs[0]
        np.savez(os.path.join(args.out, "hybrid_offline.npz"),
                 **{c: np.asarray(offline[c]).astype(str)
                    if offline[c].dtype == object else offline[c]
                    for c in ("qid", "docno", "score", "rank")})
    with smoke.phase("served vs offline"):
        for epoch in ("cold", "warm"):
            identical = compare(f"served-{epoch} vs offline",
                                served.get(epoch, {}), offline, smoke)
            log(f"[contract] served-{epoch} bitwise identical to offline: "
                f"{identical}")

    with smoke.phase("kernel parity: hybrid corpus matrix"):
        index = find_dense_index(scenario.pipeline)
        q = index.encoder.encode(scenario.topics["query"].tolist())
        kernel_parity("dense_topk hybrid", jax.numpy.asarray(q),
                      jax.numpy.asarray(index.matrix), 100, smoke,
                      exact=False)
    with smoke.phase("kernel parity: 2**20 x 768 bf16"):
        kq, kc = jax.random.split(jax.random.key(SEED))
        make = jax.jit(lambda key, shape: jax.random.randint(
            key, shape, -4, 5, jax.numpy.int32).astype(jax.numpy.bfloat16),
            static_argnums=1)
        c = make(kc, (BIG_N, BIG_D))
        q = make(kq, (8, BIG_D))
        kernel_parity("dense_topk 1M", q, c, BIG_K, smoke, exact=True)
        del c
    print_compile_stats(meter)


def four_chips(args, smoke, meter):
    import dataclasses
    from repro.serve import FleetService, ServeConfig, run_closed_loop
    from repro.serve.fleet import host_tpu_chips

    chips = host_tpu_chips()
    smoke.check(chips == 4, f"host has 4 TPU chips (device files: {chips})")
    if chips != 4:
        return
    base = ServeConfig(pipeline="hybrid", scale=SCALE, seed=SEED,
                       warm_start=False)
    traffic = base.build_traffic()       # numpy only: no JAX here yet
    served = {}
    for n in (4, 1):
        cfg = dataclasses.replace(
            base, workers=n, cache_dir=os.path.join(args.out, f"fleet{n}"))
        with smoke.phase(f"fleet of {n} worker(s)"):
            # a one-worker fleet still runs in its own process
            svc = FleetService(cfg)
            try:
                log(f"[fleet{n}] workers ready: {svc.worker_ids}")
                loop = run_closed_loop(svc, traffic, n_requests=REQUESTS,
                                       n_clients=CLIENTS, seed=SEED)
                served[n] = serve_all_topics(svc, traffic)
                report = svc.drain()
            finally:
                svc.close()
            log(f"[fleet{n}] requests={loop['requests']} "
                f"wall_s={loop['wall_s']} "
                f"throughput_rps={loop['throughput_rps']} "
                f"cache_hits={report['online']['cache_hits']} "
                f"cache_misses={report['online']['cache_misses']} "
                f"exit_codes={report['exit_codes']}")
            smoke.check(set(report["exit_codes"].values()) == {0},
                        f"fleet of {n} drained with every worker exiting 0")
    from jax._src import xla_bridge
    smoke.check(not xla_bridge.backends_are_initialized(),
                "the parent stayed off JAX while the fleets ran")

    import jax
    from repro.core import ExecutionPlan
    with smoke.phase("offline plan over four chips"):
        scenario = base.build_scenario()
        index = find_dense_index(scenario.pipeline)
        outs, _ = ExecutionPlan([scenario.pipeline]).run(scenario.topics)
        offline = outs[0]
        chunks = [(lo, int(c.shape[0]), str(c.devices()))
                  for lo, c in index.device_chunks()]
        log(f"[offline] devices={len(jax.devices())} dense chunks={chunks}")
        smoke.check(len(index.device_chunks()) == 4,
                    "the offline dense index is row-sharded over 4 chips")
    with smoke.phase("fleets vs offline"):
        for n in (4, 1):
            identical = compare(f"fleet{n} vs offline", served.get(n, {}),
                                offline, smoke)
            log(f"[contract] fleet{n} bitwise identical to offline: "
                f"{identical}")
        if 4 in served and 1 in served:
            from repro.core.frame import ColFrame
            compare("fleet4 vs fleet1", served[4],
                    ColFrame.concat(list(served[1].values())), smoke)
    print_compile_stats(meter)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip fleet path and the "
                         "offline plan it is compared with")
    ap.add_argument("--out", default=os.path.join(ROOT, "chip_smoke_out"),
                    help="output directory (emptied first)")
    args = ap.parse_args(argv)
    try:
        from repro.caching import use_persistent_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not next to this script "
              f"({e})", file=sys.stderr)
        return 2
    shutil.rmtree(args.out, ignore_errors=True)
    os.makedirs(args.out)
    log(f"[compile] persistent compile cache: "
        f"{use_persistent_compile_cache()}")
    smoke = Smoke()
    meter = CompileMeter()
    t0 = time.perf_counter()
    if args.four_chips:
        # the fleet workers take the chips first: no JAX in this
        # process until they have drained
        four_chips(args, smoke, meter)
    import jax
    devices = jax.devices()
    dev = devices[0]
    log(f"[device] platform={dev.platform} device_kind={dev.device_kind} "
        f"count={len(devices)}")
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX's first device is {dev.platform}); "
              f"this smoke test has no CPU path", file=sys.stderr)
        return 1
    if not args.four_chips:
        one_chip(args, smoke, meter)
    log(f"[timing] " + " ".join(f"{k!r}={v:.3f}s"
                                for k, v in smoke.timings.items())
        + f" total={time.perf_counter() - t0:.3f}s")
    if smoke.failures:
        for f in smoke.failures:
            print(f"chip_smoke: FAILED {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
