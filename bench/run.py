#!/usr/bin/env python3
"""Run one benchmark cell once on the chip(s) of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name
(``bench/registry.py``).  The run builds the cell (inputs from ``--seed``,
weights from the configuration), warms up every shape its window uses,
measures for ``--seconds``, then checks a sample of what the window
produced against the plain references and prints:

- on standard output, progress lines, then as the last line one JSON
  object: ``correct``, ``attempted``, ``failed``, ``metrics`` (with
  ``--trace 0`` the cell's end-to-end metrics, with ``--trace 1`` its
  per-layer metrics, from a profiler trace of the window), ``device``,
  with ``--trace 1`` ``breakdown``, and last ``check``: each compared
  number with its limit;
- on standard error, last, the same compared numbers with their limits.

It exits nonzero, with no result line, where JAX finds no TPU or fewer
chips than the cell asks for, or where the program is not next to it.

Tools for whoever sets the cell up, never used by a check:
``--control`` puts the lower-precision reference in the program's place
in the comparison (it must come out not correct); ``--sweep r1,r2,...``
(open-loop mixes) runs one window per offered rate after one set-up and
prints each rate's latency and throughput, with no result line.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import shutil  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileMeter:
    """JAX's own compile events: backend compiles (a persistent-cache hit
    counts as one, with its load time) and persistent-cache hits and
    misses."""

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


class Record:
    """What a metric reader reads."""

    def __init__(self, run, setup_s, trace, work, peaks):
        self.run, self.setup_s, self.trace = run, setup_s, trace
        self.work, self.peaks = work, peaks


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--sweep", default=None)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    return args


def start_trace(trace_dir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0       # Python calls would swamp the trace
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def main(argv=None) -> int:
    args = parse(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"bench: the program (src/repro) is not next to {BENCH}",
              file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench.registry import Registry
    reg = Registry()
    cell = reg.workload(args.workload)

    import jax
    from repro.caching import use_persistent_compile_cache
    cache_dir = use_persistent_compile_cache()
    # every program, however quick to compile or large, goes to the cache:
    # the program embeds its weights in its compiled encoders
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or len(devices) < int(cell["chips"]):
        print(f"bench: needs {cell['chips']} TPU chip(s); JAX found "
              f"{len(devices)} {dev.platform} device(s)", file=sys.stderr)
        return 3
    peaks = reg.peaks(dev.device_kind)
    log(f"[device] platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} compile_cache={cache_dir}")
    result = run_cell(reg, args, peaks)
    if result is not None:
        for k, e in result["check"].items():
            print(f"[check] {k} = {e['value']!r} limit {e['limit']!r}",
                  file=sys.stderr, flush=True)
        print(json.dumps(result), flush=True)
    return 0


def run_cell(reg, args, peaks):
    """Build, warm up, measure and check one cell on JAX's devices; the
    result line's object (None for a sweep)."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    cell = reg.workload(args.workload)
    cfg, traffic = reg.config(cell["config"]), reg.traffic(cell["traffic"])
    limits = reg.limits(args.workload)
    metrics = reg.metrics(args.workload, bool(args.trace))
    meter = CompileMeter()

    from bench.check import checker
    from bench.drivers import DRIVERS
    from bench.world import World
    compare = checker(traffic, cfg, reg.kind)
    world = World(cfg, args.seed, reg.kind)
    log(f"[setup] world built at {time.perf_counter() - T0:.3f}s")
    driver = DRIVERS[traffic["driver"]](world, traffic, args.seed)
    driver.warm_up()
    setup_s = time.perf_counter() - T0
    compiles0 = meter.compiles
    log(f"[setup] setup_s={setup_s!r} compiles={meter.compiles} "
        f"compile_s={meter.compile_s:.3f} cache_hits={meter.cache_hits} "
        f"cache_misses={meter.cache_misses}")

    if args.sweep:
        import numpy as np
        for i, rate in enumerate(float(x) for x in args.sweep.split(",")):
            run = driver.window(args.seconds, rate=rate, stream=i + 1)
            lat = run.latencies_ms
            log(f"[sweep] rate={rate} due={run.attempted} failed={run.failed}"
                f" completed_in_window={run.counters['completed_in_window']}"
                f" rps={run.counters['completed_in_window'] / args.seconds:.3f}"
                f" p50_ms={np.percentile(lat, 50):.3f}"
                f" p95_ms={np.percentile(lat, 95):.3f}"
                f" p99_ms={np.percentile(lat, 99):.3f}"
                f" lag_p95_ms={np.percentile(run.lateness_ms, 95):.3f}"
                f" rows_per_batch={run.counters['rows_executed'] / max(1, run.counters['batches']):.2f}")
        driver.close()
        return None

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    if trace_dir:
        start_trace(trace_dir)
    with jax.profiler.TraceAnnotation("bench.window"):
        run = driver.window(args.seconds)
    if trace_dir:
        jax.profiler.stop_trace()
    in_window = meter.compiles - compiles0
    stats = dev.memory_stats() or {}
    run.memory_peak_bytes = int(stats.get("peak_bytes_in_use", 0))
    c = run.counters
    log(f"[window] window_s={run.window_s!r} attempted={run.attempted} "
        f"failed={run.failed} compiles_in_window={in_window} "
        f"memory_peak_bytes={run.memory_peak_bytes}")
    log("[counters] " + " ".join(f"{k}={v}" for k, v in c.items()))
    if run.lateness_ms is not None:
        import numpy as np
        log(f"[generator] lateness_ms p50={np.percentile(run.lateness_ms, 50):.3f}"
            f" p95={np.percentile(run.lateness_ms, 95):.3f}"
            f" max={np.max(run.lateness_ms):.3f}")
    driver.close()
    corpus = world.corpus
    del driver, world
    gc.collect()

    t = time.perf_counter()
    nums, work = compare(corpus, run, args.seed, args.control)
    log(f"[check] took {time.perf_counter() - t:.3f}s control={args.control}")
    log("[work] " + " ".join(f"{k}={v!r}" for k, v in work.items()))
    trace = None
    if trace_dir:
        from bench.trace_reduce import find_xplane, reduce_trace
        t = time.perf_counter()
        trace = reduce_trace(find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"[trace] reduced in {time.perf_counter() - t:.3f}s "
            f"window_s={trace['window_s']!r} busy_s={trace['busy_s']!r} "
            f"programs={json.dumps(trace['programs'])}")

    rec = Record(run, setup_s, trace, work, peaks)
    out_metrics = {}
    for m in metrics:
        v = reg.reader(m["name"])(rec)
        if v is not None:
            out_metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    check = {k: {"value": float(v), "limit": limits.get(k)}
             for k, v in nums.items()}
    result = {"correct": False, "attempted": int(run.attempted),
              "failed": int(run.failed), "metrics": out_metrics,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devices),
                         "memory_peak_bytes": run.memory_peak_bytes}}
    if trace:
        result["device"]["busy_s"] = trace["busy_s"]
        result["device"]["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    # a request that never completed, or raised, is an answer that never
    # came (a late one is late, not wrong: it waited the grace period)
    check["failed"] = {"value": float(run.failed), "limit": 0.0}
    check["compiles_in_window"] = {"value": float(in_window), "limit": 0.0}
    result["correct"] = all(e["limit"] is not None and e["value"] <= e["limit"]
                            for e in check.values())
    result["check"] = check
    return result


if __name__ == "__main__":
    sys.exit(main())
