"""Multi-process serve fleet: N PipelineService workers, one cache.

``FleetService`` scales the serving layer past one process while
keeping the single-process API: ``submit(qid, query, **extra)`` returns
a future exactly like :class:`~repro.serve.service.PipelineService`,
so the closed-loop generator, the benchmarks and the CLI drive either
interchangeably (``build_service`` picks by ``workers=``).

Topology
--------
The front-end **demux** (this process) owns the client-facing futures
and a duplex ``multiprocessing.Pipe`` per worker.  Each **worker
process** (spawned — never forked: the parent runs jax and executor
threads) rebuilds the scenario from the shared
:class:`~repro.serve.config.ServeConfig`, compiles its own
``PipelineService`` over the *same* cache directory, optionally replays
the expected traffic through the plan (``warm_start`` — all hits over
a warmed dir, so a respawned worker rejoins warm from the PR-6
manifests), then serves requests from its pipe.  Routing follows
``config.routing``: ``"rr"`` (default) round-robins requests over the
live workers so a zipf-hot qid cannot bottleneck one process, while
``"qid"`` hashes the qid stably so repeat traffic for a hot query
keeps hitting the same worker's micro-batcher; either way results
(per-qid frames) are reassembled into the original futures, and
deterministic pipelines make the answers routing-independent.

Sharing the cache is what makes N processes one *fleet* rather than N
cold services: with the ``mmap:<disk>`` read-mostly tier
(``caching/mmap_tier.py``) every worker maps the same packed snapshot,
so cross-process hits take no lock, while misses still compute exactly
once through the disk backend's locked compute-once path.

Fault handling
--------------
A worker death is detected as EOF on its pipe.  The demux then (a)
requeues every accepted request that was in flight on the dead worker
onto survivors — accepted requests are never lost, they are recomputed
(bit-identically: deterministic pipelines) elsewhere; (b) respawns a
replacement, paced by :class:`~repro.distrib.fault.RetryPolicy`
backoff, which warms itself from the manifests before taking traffic.
Per-request requeues are bounded by the same policy; exhausting it
fails that request's future with the underlying error.

``drain()`` is the graceful shutdown: each worker finishes its
in-flight work, flushes, closes its service — which refreshes the
cache manifests (entry counts, access stats) on disk — reports its
stats and exits 0.  ``repro serve --drain`` surfaces the exit codes.

Devices
-------
A TPU chip belongs to one process.  The demux therefore never touches
JAX (it generates traffic from the corpus alone and only relays
frames), and on a TPU host each worker is pinned to one chip of its
own: libtpu's visibility variables (:func:`chip_env`) go into the
child's environment before it starts, so it never sees — or locks —
the other chips.  A fleet larger than the host's chip count is refused
up front, and a worker that cannot open its chip reports why and the
fleet start fails at once instead of waiting out the start timeout.
"""
from __future__ import annotations

import dataclasses
import glob
import itertools
import os
import threading
import time
import zlib
from concurrent.futures import Future
from typing import Any, Dict, List, Optional

from ..distrib.fault import RetryPolicy
from .config import ServeConfig
from .service import ServiceStats

__all__ = ["FleetService", "fleet_worker_main", "chip_env",
           "host_tpu_chips"]

#: serializes the parent-environment swap around a worker's start
_SPAWN_ENV_LOCK = threading.Lock()


def host_tpu_chips() -> int:
    """TPU chips attached to this host, counted from their device files
    without loading JAX; 0 when ``JAX_PLATFORMS`` excludes the TPU."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return 0
    return len(glob.glob("/dev/accel[0-9]*")) or \
        len(glob.glob("/dev/vfio/[0-9]*"))


def chip_env(chip: int) -> Dict[str, str]:
    """libtpu environment giving one process exactly TPU chip ``chip``
    as a single-chip slice of its own (process and chip bounds 1x1x1).
    The bounds are what let each worker load libtpu next to the others:
    on a v5e host with ``TPU_VISIBLE_CHIPS`` alone, one of four
    processes started and the rest failed on libtpu's host-wide lock
    file.  The per-chip port keeps their runtime ports apart."""
    return {"TPU_VISIBLE_CHIPS": str(chip),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_PORT": str(8476 + chip)}


def _qid_slot(qid: str, n: int) -> int:
    """Stable (cross-process, cross-run) qid → worker slot hash."""
    return zlib.crc32(str(qid).encode("utf-8")) % max(1, n)


# ---------------------------------------------------------------------------
# worker process
# ---------------------------------------------------------------------------

def fleet_worker_main(conn, cfg: ServeConfig, worker_id: int,
                      chip: Optional[int] = None) -> None:
    """Entry point of one worker process (module-level: spawn pickles
    it by reference).  Protocol, parent → worker::

        ("req", rid, row)   serve one row; reply ("res", rid, frame)
                            or ("err", rid, repr)
        ("drain",)          finish in-flight work, close the service
                            (refreshing manifests), reply
                            ("drained", wid, stats), exit 0
        ("stop",)           close immediately, exit 0

    and worker → parent additionally ``("ready", wid, warm_info)`` once
    the local service is built (and warmed), or ``("failed", wid,
    reason)`` when it cannot start (no respawn: the cause persists).

    ``chip`` is the TPU chip the parent pinned this worker to through
    :func:`chip_env`; the worker checks that JAX sees exactly it."""
    try:
        svc, warm_info = _start_worker(cfg, worker_id, chip)
    except BaseException as e:           # noqa: BLE001 - relay, then die
        conn.send(("failed", worker_id, f"{type(e).__name__}: {e}"))
        conn.close()
        raise
    send_lock = threading.Lock()
    outstanding = [0]
    done_cv = threading.Condition()
    conn.send(("ready", worker_id, warm_info))

    def _reply(payload) -> None:
        try:
            with send_lock:
                conn.send(payload)
        except (BrokenPipeError, OSError):
            pass                         # parent gone; nothing to tell

    def _on_done(fut: Future, rid: int) -> None:
        try:
            _reply(("res", rid, fut.result()))
        except BaseException as e:       # noqa: BLE001 - relay verbatim
            _reply(("err", rid, repr(e)))
        with done_cv:
            outstanding[0] -= 1
            done_cv.notify_all()

    while True:
        try:
            msg = conn.recv()
        except (EOFError, OSError):      # parent died: nothing to serve
            svc.close()
            return
        kind = msg[0]
        if kind == "req":
            rid, row = msg[1], dict(msg[2])
            qid = row.pop("qid")
            query = row.pop("query")
            with done_cv:
                outstanding[0] += 1
            try:
                fut = svc.submit(qid, query, **row)
            except BaseException as e:   # noqa: BLE001 - relay verbatim
                with done_cv:
                    outstanding[0] -= 1
                    done_cv.notify_all()
                _reply(("err", rid, repr(e)))
                continue
            fut.add_done_callback(lambda f, rid=rid: _on_done(f, rid))
        elif kind == "drain":
            svc.flush()
            with done_cv:
                done_cv.wait_for(lambda: outstanding[0] == 0, timeout=60.0)
            stats = {"worker": worker_id,
                     **svc.stats.summary(),
                     "online": svc.online_stats.as_dict(svc.max_batch),
                     **warm_info}
            svc.close()                  # refreshes manifests on disk
            _reply(("drained", worker_id, stats))
            conn.close()
            return                       # process exit code 0
        elif kind == "stop":
            svc.close()
            conn.close()
            return


def _start_worker(cfg: ServeConfig, worker_id: int, chip: Optional[int]):
    from ..caching import use_persistent_compile_cache
    from .config import build_service
    from .registry import warming_frame

    use_persistent_compile_cache()
    if chip is not None:
        import jax
        devs = jax.devices()
        if len(devs) != 1 or devs[0].platform != "tpu":
            raise RuntimeError(
                f"fleet worker {worker_id} was pinned to TPU chip {chip} "
                f"(TPU_VISIBLE_CHIPS={os.environ.get('TPU_VISIBLE_CHIPS')})"
                f" but JAX sees {devs}")
    cfg = cfg.single()
    scenario = cfg.build_scenario()
    svc = build_service(cfg, scenario=scenario)
    warm_info: Dict[str, Any] = {}
    if cfg.warm_start and cfg.cache_dir:
        t0 = time.perf_counter()
        frame = warming_frame(scenario, budget=cfg.warm_budget,
                              seed=cfg.seed)
        stats = svc.plan.warm(frame)
        warm_info = {"queries_warmed": int(len(frame)),
                     "warm_hits": int(stats.cache_hits),
                     "warm_misses": int(stats.cache_misses),
                     "warm_wall_s": round(time.perf_counter() - t0, 4)}
    return svc, warm_info


# ---------------------------------------------------------------------------
# demux (parent) side
# ---------------------------------------------------------------------------

class _Worker:
    """Parent-side handle of one worker process."""

    __slots__ = ("id", "proc", "conn", "chip", "send_lock", "ready",
                 "drained", "reaped", "alive", "drain_stats", "warm_info",
                 "exit_code", "start_error")

    def __init__(self, wid: int, proc, conn, chip: Optional[int]):
        self.id = wid
        self.proc = proc
        self.conn = conn
        self.chip = chip
        self.send_lock = threading.Lock()
        self.ready = threading.Event()
        self.drained = threading.Event()
        #: set by the reader thread once it has joined the process and
        #: recorded ``exit_code`` — the one thread that reaps it
        self.reaped = threading.Event()
        self.alive = True
        self.drain_stats: Optional[Dict[str, Any]] = None
        self.warm_info: Dict[str, Any] = {}
        self.exit_code: Optional[int] = None
        self.start_error: Optional[str] = None

    def send(self, payload) -> None:
        with self.send_lock:
            self.conn.send(payload)


class FleetService:
    """Demux over N spawned ``PipelineService`` worker processes.

    Implements the service surface the closed-loop generator relies on
    (``submit`` → future, ``stats``, ``flush``, ``close``) plus the
    fleet lifecycle: ``drain()`` for graceful shutdown with refreshed
    manifests, ``kill_worker()`` as the chaos hook the fault tests and
    the CI fleet-smoke job use.
    """

    def __init__(self, config: Any = None, *,
                 retry: Optional[RetryPolicy] = None,
                 start_timeout: float = 300.0,
                 reservoir_capacity: int = 4096,
                 **overrides: Any):
        self.config = ServeConfig.coerce(config)
        if overrides:
            self.config = dataclasses.replace(self.config, **overrides)
        self.retry = retry or RetryPolicy(max_retries=3, base_delay_s=0.05)
        self.stats = ServiceStats(reservoir_capacity)
        self._lock = threading.RLock()
        self._rids = itertools.count()
        self._wids = itertools.count()
        self._rr = itertools.count()
        #: rid -> {"row", "future", "worker", "attempts", "t0"}
        self._inflight: Dict[int, Dict[str, Any]] = {}
        self._workers: Dict[int, _Worker] = {}
        self._readers: List[threading.Thread] = []
        self.respawns = 0
        self.requeued = 0
        self._max_respawns = self.config.workers * (self.retry.max_retries + 1)
        self._draining = False
        self._closed = False
        self._drain_report: Optional[Dict[str, Any]] = None
        self._start_failures: List[str] = []
        self._chips = host_tpu_chips()
        self._held_chips: set = set()
        if self._chips and self.config.workers > self._chips:
            raise ValueError(
                f"a fleet on a TPU host runs one worker per chip: "
                f"{self.config.workers} workers > {self._chips} chips")
        import multiprocessing as mp
        self._ctx = mp.get_context("spawn")
        for _ in range(self.config.workers):
            self._spawn()
        self._wait_ready(start_timeout)

    # -- worker lifecycle ----------------------------------------------------
    def _take_chip(self) -> Optional[int]:
        """Reserve the lowest chip no live worker holds (None off-TPU);
        released once the holder's process has exited."""
        if not self._chips:
            return None
        with self._lock:
            chip = min(set(range(self._chips)) - self._held_chips)
            self._held_chips.add(chip)
        return chip

    def _spawn(self) -> "_Worker":
        wid = next(self._wids)
        chip = self._take_chip()
        parent_conn, child_conn = self._ctx.Pipe(duplex=True)
        proc = self._ctx.Process(
            target=fleet_worker_main,
            args=(child_conn, self.config, wid, chip),
            name=f"fleet-worker-{wid}", daemon=True)
        env = chip_env(chip) if chip is not None else {}
        with _SPAWN_ENV_LOCK:
            # the child inherits the environment at exec, before it
            # imports JAX; the parent's own is restored right after
            saved = {k: os.environ.get(k) for k in env}
            os.environ.update(env)
            try:
                proc.start()
            finally:
                for k, v in saved.items():
                    if v is None:
                        os.environ.pop(k, None)
                    else:
                        os.environ[k] = v
        child_conn.close()               # parent keeps its end only
        w = _Worker(wid, proc, parent_conn, chip)
        with self._lock:
            self._workers[wid] = w
        t = threading.Thread(target=self._reader, args=(w,),
                             name=f"fleet-reader-{wid}", daemon=True)
        self._readers.append(t)
        t.start()
        return w

    def _wait_ready(self, timeout: float) -> None:
        deadline = time.monotonic() + timeout
        while True:
            with self._lock:
                pending = [w for w in self._workers.values()
                           if w.alive and not w.ready.is_set()]
                n_alive = sum(w.alive for w in self._workers.values())
                failed = self._start_failures[:1]
            if failed:
                self.close(drain=False)
                raise RuntimeError(f"fleet startup failed: {failed[0]}")
            if n_alive == 0:
                raise RuntimeError(
                    "fleet startup failed: every worker process exited "
                    "before becoming ready (respawn budget exhausted)")
            if not pending:
                return
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"fleet startup timed out after {timeout}s waiting for "
                    f"workers {[w.id for w in pending]}")
            pending[0].ready.wait(0.2)

    def _reader(self, w: _Worker) -> None:
        while True:
            try:
                msg = w.conn.recv()
            except (EOFError, OSError):
                break
            kind = msg[0]
            if kind == "ready":
                w.warm_info = msg[2]
                w.ready.set()
            elif kind == "failed":
                w.start_error = msg[2]
            elif kind == "res":
                self._resolve(msg[1], msg[2], None)
            elif kind == "err":
                self._resolve(msg[1], None, RuntimeError(msg[2]))
            elif kind == "drained":
                w.drain_stats = msg[2]
                w.drained.set()
        self._on_worker_exit(w)

    def _on_worker_exit(self, w: _Worker) -> None:
        with self._lock:
            if w.start_error is not None:
                self._start_failures.append(
                    f"worker {w.id} (chip {w.chip}): {w.start_error}")
            w.alive = False
            self._workers.pop(w.id, None)
            orphaned = [rid for rid, e in self._inflight.items()
                        if e["worker"] == w.id]
        w.proc.join(timeout=10.0)
        w.exit_code = w.proc.exitcode
        w.reaped.set()
        with self._lock:
            self._held_chips.discard(w.chip)
        if self._draining or self._closed or w.drained.is_set():
            return
        # unexpected death: respawn warm (bounded; not after a start
        # failure, which would repeat), requeue the orphaned accepted
        # requests onto survivors
        with self._lock:
            may_respawn = (w.start_error is None
                           and self.respawns < self._max_respawns)
            if may_respawn:
                self.respawns += 1
                attempt = self.respawns
        if may_respawn:
            time.sleep(self.retry.delay(attempt))
            if not (self._draining or self._closed):
                self._spawn()
        for rid in orphaned:
            self.requeued += 1
            self._dispatch(rid)

    # -- request path --------------------------------------------------------
    def submit(self, qid: Any, query: str, **extra: Any) -> Future:
        """Asynchronously serve one query through the fleet; resolves
        to the per-qid result frame, exactly like
        ``PipelineService.submit``.  Once accepted (this method
        returned), the request survives worker deaths — it is requeued
        to a surviving worker and recomputed bit-identically."""
        if self._closed or self._draining:
            raise RuntimeError("FleetService is closed")
        row = {"qid": str(qid), "query": query, **extra}
        fut: Future = Future()
        rid = next(self._rids)
        with self._lock:
            self._inflight[rid] = {"row": row, "future": fut,
                                   "worker": None, "attempts": 0,
                                   "t0": time.perf_counter()}
        self._dispatch(rid)
        return fut

    def _dispatch(self, rid: int) -> None:
        while True:
            with self._lock:
                entry = self._inflight.get(rid)
                if entry is None:        # already resolved (late requeue)
                    return
                entry["attempts"] += 1
                if entry["attempts"] > self.retry.max_retries + 1:
                    self._inflight.pop(rid, None)
                    entry["future"].set_exception(RuntimeError(
                        f"request {entry['row'].get('qid')!r} failed after "
                        f"{entry['attempts'] - 1} dispatch attempts "
                        f"(workers kept dying)"))
                    return
                live = [w for w in self._workers.values() if w.alive]
                if not live:
                    self._inflight.pop(rid, None)
                    entry["future"].set_exception(RuntimeError(
                        "no live fleet workers to dispatch to"))
                    return
                if self.config.routing == "qid":
                    slot = _qid_slot(entry["row"]["qid"], len(live))
                else:
                    slot = next(self._rr) % len(live)
                w = live[slot]
                entry["worker"] = w.id
            try:
                w.send(("req", rid, entry["row"]))
                return
            except (BrokenPipeError, OSError):
                # raced a death the reader has not processed yet; the
                # loop re-picks among the remaining workers
                with self._lock:
                    w.alive = False

    def _resolve(self, rid: int, frame, error) -> None:
        with self._lock:
            entry = self._inflight.pop(rid, None)
        if entry is None:                # duplicate/late reply
            return
        dt_ms = (time.perf_counter() - entry["t0"]) * 1000.0
        self.stats.record_batch(n_requests=1, latencies_ms=[dt_ms])
        if error is not None:
            entry["future"].set_exception(error)
        else:
            entry["future"].set_result(frame)

    def flush(self) -> None:
        """No-op at the demux: each worker's streaming executor flushes
        on its own ``max_batch``/``max_wait_ms`` window."""

    # -- introspection -------------------------------------------------------
    @property
    def worker_ids(self) -> List[int]:
        with self._lock:
            return sorted(w.id for w in self._workers.values() if w.alive)

    @property
    def warm_info(self) -> Dict[int, Dict[str, Any]]:
        with self._lock:
            return {w.id: dict(w.warm_info)
                    for w in self._workers.values()}

    def kill_worker(self, worker_id: Optional[int] = None) -> int:
        """Chaos hook: SIGKILL one live worker (the lowest id by
        default) and return its id.  The demux requeues its in-flight
        requests and respawns a warm replacement — the fault-tolerance
        path the fleet tests and the CI fleet-smoke job exercise."""
        with self._lock:
            live = sorted((w.id, w) for w in self._workers.values()
                          if w.alive)
            if not live:
                raise RuntimeError("no live workers to kill")
            wid, w = live[0] if worker_id is None else \
                (worker_id, self._workers[worker_id])
        w.proc.kill()
        return wid

    # -- lifecycle -----------------------------------------------------------
    def drain(self, timeout: float = 120.0) -> Dict[str, Any]:
        """Graceful shutdown: every worker finishes in-flight work,
        closes its service — refreshing the cache manifests on disk —
        reports stats and exits 0.  Returns the fleet report
        (per-worker stats, exit codes, respawn/requeue counters,
        aggregated cache totals); idempotent."""
        if self._drain_report is not None:
            return self._drain_report
        with self._lock:
            self._draining = True
            workers = [w for w in self._workers.values() if w.alive]
        for w in workers:
            try:
                w.send(("drain",))
            except (BrokenPipeError, OSError):
                pass
        deadline = time.monotonic() + timeout
        for w in workers:
            w.drained.wait(max(0.0, deadline - time.monotonic()))
            # the reader thread reaps the process (two threads waiting
            # on one pid can lose its exit status)
            if not w.reaped.wait(max(0.1, deadline - time.monotonic())):
                w.proc.terminate()       # refuse to hang: escalate
                w.reaped.wait(15.0)
        per_worker = [w.drain_stats for w in workers
                      if w.drain_stats is not None]
        hits = sum(int(s["online"]["cache_hits"]) for s in per_worker)
        misses = sum(int(s["online"]["cache_misses"]) for s in per_worker)
        self.stats.add_cache_counts(hits, misses)
        batches = sum(int(s.get("batches", 0)) for s in per_worker)
        occ = (sum(float(s["online"]["batch_occupancy"])
                   * int(s.get("batches", 0)) for s in per_worker)
               / batches) if batches else 0.0
        self._drain_report = {
            "workers": [dict(s) for s in per_worker],
            "exit_codes": {w.id: w.exit_code for w in workers},
            "respawns": self.respawns,
            "requeued": self.requeued,
            "online": {"cache_hits": hits, "cache_misses": misses,
                       "batches": batches,
                       "batch_occupancy": round(occ, 4)},
        }
        return self._drain_report

    def close(self, drain: bool = True) -> None:
        if self._closed:
            return
        if drain and not self._draining:
            try:
                self.drain()
            except Exception:
                pass
        self._closed = True
        with self._lock:
            workers = list(self._workers.values())
            pending = list(self._inflight.values())
            self._inflight.clear()
        for e in pending:
            if not e["future"].done():
                e["future"].set_exception(
                    RuntimeError("FleetService closed"))
        for w in workers:
            try:
                w.send(("stop",))
            except (BrokenPipeError, OSError):
                pass
        for w in workers:
            w.proc.join(timeout=5.0)
            if w.proc.is_alive():
                w.proc.terminate()
                w.proc.join(timeout=5.0)
            try:
                w.conn.close()
            except OSError:
                pass

    def __enter__(self) -> "FleetService":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False
