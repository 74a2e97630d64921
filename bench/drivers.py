"""The two ways a traffic mix drives the program: a grid of experiments
(``driver: grid``) and open-loop serving (``driver: open_loop``).

Each driver warms up every shape its window will use during set-up,
then runs the window, and returns a ``Run``: what happened, the program's
counters, and the outputs that the correctness check samples.  Host
spans of the harness's own calls go into the profiler's trace as
``bench.*`` annotations (they cost nothing when no trace is taken).
"""
from __future__ import annotations

import shutil
import tempfile
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from . import gen

@dataclass
class Run:
    window_s: float = 0.0
    attempted: int = 0
    failed: int = 0
    counters: Dict[str, float] = field(default_factory=dict)
    #: grid: per (qid, k) the program's final rows; serve: per qid
    outputs: Dict = field(default_factory=dict)
    #: the queries of the window by qid (word ids), for the check and work
    queries: Dict[str, np.ndarray] = field(default_factory=dict)
    latencies_ms: Optional[np.ndarray] = None      # open loop, per due request
    lateness_ms: Optional[np.ndarray] = None
    memory_peak_bytes: int = 0


def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def frame_rows(rows):
    from repro.core.frame import ColFrame
    return ColFrame.from_dicts(rows)


def rows_of(frame) -> List:
    """(docno index, score, rank) rows of a result frame."""
    return [(int(str(d)[1:]), float(s), int(r)) for d, s, r in
            zip(frame["docno"].tolist(), frame["score"].tolist(),
                frame["rank"].tolist())]


def warm_stages(world, queries: gen.Queries) -> None:
    """Run each stage at every shape the window can reach, as its kind's
    module says."""
    for name, stage in world.stages.items():
        world.kinds[name].warm(world, stage, world.cfg["stages"][name],
                               queries)


def queries_for(world, spec: Dict, n: int, seed: int, stream: int,
                prefix: str) -> gen.Queries:
    if spec["kind"] == "planted":
        return gen.planted_queries(world.corpus, n, spec["lengths"], seed,
                                   stream, prefix)
    if spec["kind"] == "zipf_words":
        words = getattr(world, "_query_words", None)
        if words is None:
            words = world._query_words = gen.vocabulary(int(spec["vocab"]))
        return gen.zipf_queries(n, spec, words, seed, stream, prefix)
    raise ValueError(f"unknown query kind {spec['kind']!r}")


# -- grid ---------------------------------------------------------------------

class GridDriver:
    """Iterations of ``Experiment`` over the traffic's grid of systems,
    each on fresh topics, with the plan's prefix sharing and caches."""

    def __init__(self, world, traffic: Dict, seed: int):
        self.world, self.t, self.seed = world, traffic, seed
        self.ks = list(traffic["k"])
        self.systems = [world.pipeline(traffic["systems"].format(k=k))
                        for k in self.ks]
        self.names = [f"k={k}" for k in self.ks]
        n = int(traffic["topics_per_iteration"])
        self.iters = [queries_for(world, traffic["queries"], n, seed,
                                  100 + i, f"t{i}.")
                      for i in range(int(traffic["max_iterations"]))]
        self.cache_dir = tempfile.mkdtemp(prefix="bench-grid-")

    def _experiment(self, q: gen.Queries):
        """One ``Experiment`` over the grid on the topics ``q``, each with
        its planted passage as the one relevant qrel."""
        from repro.core import Experiment
        from repro.core.frame import ColFrame
        topics = ColFrame({"qid": q.qids, "query": q.texts})
        qrels = ColFrame({"qid": q.qids,
                          "docno": [self.world.corpus.docnos[t]
                                    for t in q.targets],
                          "label": [1] * len(q.qids)})
        return Experiment(self.systems, topics, qrels, self.t["measures"],
                          names=self.names, precompute_prefix=True,
                          precompute_mode="plan", cache_dir=self.cache_dir,
                          keep_results=True)

    def warm_up(self) -> None:
        # each stage's shapes are warmed one by one; a small grid run
        # warms the rest of the plan's path
        q = queries_for(self.world, self.t["queries"],
                        min(8, int(self.t["topics_per_iteration"])),
                        self.seed, 99, "warm.")
        warm_stages(self.world, q)
        self._experiment(q)

    def window(self, seconds: float) -> Run:
        run = Run()
        c = {"iterations": 0, "topics": 0, "nodes_executed": 0,
             "plan_queries": 0, "cache_hits": 0, "cache_misses": 0}
        t0 = time.perf_counter()
        end = t0
        for q in self.iters:
            if time.perf_counter() - t0 >= seconds:
                break
            with _annotate("bench.experiment"):
                res = self._experiment(q)
            end = time.perf_counter()
            st = res.precompute
            c["iterations"] += 1
            c["topics"] += len(q.qids)
            c["nodes_executed"] += st.nodes_executed
            c["plan_queries"] += st.n_queries
            c["cache_hits"] += st.cache_hits
            c["cache_misses"] += st.cache_misses
            for k, frame in zip(self.ks, res.results_frames):
                for (qid,), idx in frame.group_indices(["qid"]).items():
                    run.outputs[(str(qid), k)] = rows_of(frame.take(idx))
            run.queries.update(zip(q.qids, q.word_ids))
        if c["iterations"] == len(self.iters) and end - t0 < seconds:
            raise RuntimeError(f"max_iterations {len(self.iters)} ran out "
                               f"before the {seconds}s window closed")
        run.window_s = end - t0
        run.attempted = c["topics"]
        run.counters = c
        return run

    def close(self) -> None:
        shutil.rmtree(self.cache_dir, ignore_errors=True)


# -- open loop ----------------------------------------------------------------

class OpenLoopDriver:
    """Requests at fixed Poisson due times, each a distinct query, to one
    ``PipelineService`` built by ``build_service``; latency runs from the
    due time to the completion the client sees."""

    def __init__(self, world, traffic: Dict, seed: int):
        from repro.serve import ServeConfig, build_service
        self.world, self.t, self.seed = world, traffic, seed
        svc = traffic["service"]
        self.cache_dir = (tempfile.mkdtemp(prefix="bench-serve-")
                          if svc["cache"] else None)
        cfg = ServeConfig(max_batch=svc["max_batch"],
                          max_wait_ms=svc["max_wait_ms"],
                          exec_workers=svc["exec_workers"],
                          cache_dir=self.cache_dir)
        self.svc = build_service(cfg, pipeline=world.pipeline(
            traffic["pipeline"]))

    def _drive(self, q: gen.Queries, due: np.ndarray, grace_s: float,
               seconds: float) -> Dict:
        n = len(due)
        done = np.full(n, np.nan)
        sent = np.full(n, np.nan)
        errors = [None] * n
        futs = [None] * n
        lock = threading.Lock()
        left = [n]
        all_done = threading.Event()

        def finish(i, fut):
            t = time.perf_counter()
            with lock:
                done[i] = t
                errors[i] = fut.exception()
                left[0] -= 1
                if left[0] == 0:
                    all_done.set()

        t0 = time.perf_counter()
        for i in range(n):
            wait = t0 + due[i] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            sent[i] = time.perf_counter()
            futs[i] = self.svc.submit(q.qids[i], q.texts[i])
            futs[i].add_done_callback(lambda f, i=i: finish(i, f))
        all_done.wait(max(0.0, t0 + seconds + grace_s - time.perf_counter()))
        gave_up = time.perf_counter()
        with lock:
            ok = ~np.isnan(done) & np.array([e is None for e in errors])
            # a request given up on counts at the time it was given up
            lat = (np.where(ok, done, gave_up) - t0 - due) * 1e3
        return {"t0": t0, "ok": ok, "done": done, "futs": futs,
                "latencies_ms": lat, "lateness_ms": (sent - t0 - due) * 1e3}

    def warm_up(self) -> None:
        q = queries_for(self.world, self.t["queries"], 64, self.seed, 99,
                        "warm.")
        warm_stages(self.world, q)
        rate = float(self.t["rate_per_s"])
        secs = float(self.t["warmup_s"])
        n = max(1, int(round(rate * secs)))
        q = queries_for(self.world, self.t["queries"], n, self.seed, 98,
                        "warm2.")
        self._drive(q, gen.arrivals(rate, secs, self.seed, 1),
                    float(self.t["grace_s"]), secs)

    def window(self, seconds: float, rate: Optional[float] = None,
               stream: int = 0) -> Run:
        """The measured window; ``rate`` and ``stream`` (fresh queries and
        another order of the same gaps) are for a set-up sweep only."""
        rate = float(rate if rate is not None else self.t["rate_per_s"])
        due = gen.arrivals(rate, seconds, self.seed, 2 + stream)
        q = queries_for(self.world, self.t["queries"], len(due), self.seed,
                        100 + stream, f"r{stream}." if stream else "r")
        stats0 = self._stream_counts()
        with _annotate("bench.requests"):
            d = self._drive(q, due, float(self.t["grace_s"]), seconds)
        stats1 = self._stream_counts()
        run = Run(window_s=seconds, attempted=len(due))
        run.failed = int((~d["ok"]).sum())
        in_window = d["ok"] & (d["done"] - d["t0"] <= seconds)
        run.latencies_ms = d["latencies_ms"]
        run.lateness_ms = d["lateness_ms"]
        run.counters = {k: stats1[k] - stats0[k] for k in stats1}
        run.counters.update(requests_due=len(due),
                            completed=int(d["ok"].sum()),
                            completed_in_window=int(in_window.sum()))
        for i, fut in enumerate(d["futs"]):
            if d["ok"][i]:
                run.outputs[q.qids[i]] = rows_of(fut.result())
        run.queries = dict(zip(q.qids, q.word_ids))
        return run

    def _stream_counts(self) -> Dict[str, int]:
        s = self.svc.online_stats
        return {"batches": s.batches, "rows_in": s.rows_in,
                "rows_executed": s.rows_executed,
                "cache_hits": s.cache_hits, "cache_misses": s.cache_misses}

    def close(self) -> None:
        self.svc.close()
        if self.cache_dir:
            shutil.rmtree(self.cache_dir, ignore_errors=True)


DRIVERS = {"grid": GridDriver, "open_loop": OpenLoopDriver}
