"""FleetService (serve/fleet.py) + the unified ServeConfig surface:
multi-process serving over one cache directory — per-qid bit-identity
with the offline pipeline run, kill-a-worker robustness (no accepted
request lost), graceful drain with clean worker exits, and warm starts
with zero cold misses over a precomputed store."""
import glob
import os

import numpy as np
import pytest

from repro.serve import (FleetService, PipelineService, ServeConfig,
                         build_service, run_closed_loop)
from repro.caching import warm_scenario

pytestmark = pytest.mark.slow     # spawns worker processes

#: small, fast scenario shared by every fleet test
def _cfg(**kw):
    base = dict(pipeline="bm25", scale=0.02, cutoff=5, num_results=20,
                seed=0, max_batch=4, max_wait_ms=0.0, exec_workers=1,
                warm_start=False)
    base.update(kw)
    return ServeConfig(**base)


# -- ServeConfig surface ------------------------------------------------------

def test_serve_config_validates_eagerly():
    with pytest.raises(ValueError, match="workers"):
        ServeConfig(workers=0)
    with pytest.raises(ValueError, match="routing"):
        ServeConfig(routing="sticky")
    with pytest.raises(ValueError, match="selector"):
        ServeConfig(backend="bogus")
    # selectors are normalized at config time (what manifests record)
    assert ServeConfig(backend="mmap").backend == "mmap:sqlite"
    assert ServeConfig(backend=None).backend is None


def test_serve_config_coerce_and_single():
    cfg = ServeConfig.coerce({"pipeline": "bm25", "workers": 3})
    assert cfg.pipeline == "bm25" and cfg.workers == 3
    assert ServeConfig.coerce(cfg) is cfg
    assert ServeConfig.coerce(None) == ServeConfig()
    assert cfg.single().workers == 1
    assert cfg.single().pipeline == "bm25"
    with pytest.raises(TypeError, match="ServeConfig"):
        ServeConfig.coerce(42)


def test_build_service_dispatches_on_workers():
    svc = build_service(_cfg())
    try:
        assert isinstance(svc, PipelineService)
    finally:
        svc.close()
    with pytest.raises(ValueError, match="workers=1"):
        build_service(_cfg(workers=2), pipeline=object())


# -- fleet behaviour ----------------------------------------------------------

def test_fleet_bit_identity_and_clean_drain(tmp_path):
    """Every topic served through a 2-worker fleet equals the offline
    ``pipeline(topics)`` frame bit-for-bit; drain finishes in-flight
    work, refreshes the cache manifests and exits every worker 0."""
    cache_dir = str(tmp_path)
    cfg = _cfg(workers=2, cache_dir=cache_dir, warm_start=False)
    scenario = cfg.build_scenario()
    offline = scenario.pipeline(scenario.topics)
    with build_service(cfg) as svc:
        assert isinstance(svc, FleetService)
        assert sorted(svc.worker_ids) == [0, 1]
        futs = [(str(q), svc.submit(str(q), query))
                for q, query in zip(scenario.topics["qid"].tolist(),
                                    scenario.topics["query"].tolist())]
        for qid, fut in futs:
            served = fut.result(120)
            ref = offline.take(np.nonzero(offline["qid"] == qid)[0])
            assert served.equals(ref), f"fleet diverged from offline: {qid}"
        report = svc.drain()
        assert set(report["exit_codes"].values()) == {0}
        assert report["requeued"] == 0 and report["respawns"] == 0
        assert len(report["workers"]) == 2
        assert report["online"]["batches"] >= 1
        assert svc.drain() is report                     # idempotent
        with pytest.raises(RuntimeError):
            svc.submit("q1", "after drain")
    # worker close() wrote provenance manifests for the shared caches
    assert glob.glob(os.path.join(cache_dir, "**", "manifest.json"),
                     recursive=True)


def test_fleet_closed_loop_matches_single_process(tmp_path):
    """The demux resolves the same request stream a single process
    would: every request completes, none error."""
    cfg = _cfg(workers=2, cache_dir=str(tmp_path))
    with build_service(cfg) as svc:
        # run_closed_loop raises on any client error, so returning at
        # all means every request resolved
        loop = run_closed_loop(svc, cfg.build_scenario(),
                               n_requests=40, n_clients=4, seed=0)
        assert loop["requests"] == 40


def test_kill_worker_loses_no_accepted_request():
    """SIGKILL one worker with requests in flight: the demux requeues
    its accepted work to survivors and respawns the slot — every
    submitted future still resolves.  Uses the bm25-sim scenario so
    requests take long enough to be genuinely in flight."""
    cfg = _cfg(pipeline="bm25-sim", workers=3, max_batch=1)
    scenario = cfg.build_scenario()
    qids = [str(q) for q in scenario.topics["qid"].tolist()]
    queries = scenario.topics["query"].tolist()
    with FleetService(cfg) as svc:
        futs = []
        for i in range(60):                              # open loop
            j = i % len(qids)
            futs.append(svc.submit(qids[j], queries[j]))
        killed = svc.kill_worker()                       # chaos, mid-stream
        frames = [f.result(120) for f in futs]           # nothing lost
        assert len(frames) == 60
        assert all(frame is not None for frame in frames)
        assert svc.respawns >= 1
        report = svc.drain()
        # the killed worker's nonzero exit is recorded; survivors and
        # the respawned slot all drain cleanly
        live_codes = [c for wid, c in report["exit_codes"].items()
                      if wid != killed]
        assert live_codes and all(c == 0 for c in live_codes)


def test_fleet_warm_start_zero_misses(tmp_path):
    """Precompute the store offline, then serve with a fleet over the
    mmap read-mostly tier: every worker warms from the manifests on
    start and the serve epoch never misses."""
    cache_dir = str(tmp_path)
    cfg = _cfg(workers=2, cache_dir=cache_dir, backend="mmap:sqlite",
               warm_start=True)
    offline = warm_scenario(None, cache_dir, config=cfg)
    assert offline["queries_warmed"] > 0
    with FleetService(cfg) as svc:
        for wid, info in svc.warm_info.items():
            assert info["warm_misses"] == 0              # store was complete
            assert info["warm_hits"] > 0
        loop = run_closed_loop(svc, cfg.build_scenario(),
                               n_requests=40, n_clients=4, seed=0)
        assert loop["requests"] == 40
        report = svc.drain()
        assert report["online"]["cache_misses"] == 0     # no cold misses
        assert report["online"]["cache_hits"] > 0
        assert set(report["exit_codes"].values()) == {0}


# -- devices: one chip per worker, the parent off JAX ------------------------

def test_chip_env_pins_one_chip():
    """Worker i's libtpu environment shows it chip i alone, as a
    single-chip slice with a runtime port of its own."""
    from repro.serve.fleet import chip_env
    envs = [chip_env(i) for i in range(4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    for e in envs:
        assert e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert e["TPU_PROCESS_BOUNDS"] == "1,1,1"
    assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 4


def test_fleet_refuses_more_workers_than_chips(monkeypatch):
    import repro.serve.fleet as fleet
    monkeypatch.setattr(fleet, "host_tpu_chips", lambda: 2)
    with pytest.raises(ValueError, match="one worker per chip"):
        FleetService(_cfg(workers=3))


def test_pinned_worker_without_its_chip_fails_fast(monkeypatch):
    """A worker pinned to a chip JAX cannot give it fails at start with
    the reason, well before the start timeout; the chip variables
    reached the child and the parent's environment is restored."""
    import time

    import repro.serve.fleet as fleet
    monkeypatch.setattr(fleet, "host_tpu_chips", lambda: 1)
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="pinned to TPU chip 0") as err:
        FleetService(_cfg(workers=1), start_timeout=300.0)
    assert time.monotonic() - t0 < 120
    assert "TPU_VISIBLE_CHIPS=0" in str(err.value)
    assert "TPU_VISIBLE_CHIPS" not in os.environ


def test_fleet_parent_initialises_no_backend(tmp_path):
    """drive_closed_loop with workers > 1 generates traffic from the
    corpus alone: the parent never initialises a JAX backend, even for
    a scenario whose pipeline runs on the device."""
    import subprocess
    import sys
    script = (
        "from repro.serve import ServeConfig, drive_closed_loop\n"
        "cfg = ServeConfig(pipeline='dense', scale=0.02, cutoff=5,\n"
        "                  num_results=20, max_batch=4, max_wait_ms=0.0,\n"
        "                  exec_workers=1, warm_start=False, workers=2,\n"
        f"                  cache_dir={str(tmp_path)!r})\n"
        "rec = drive_closed_loop(cfg, requests=20, clients=2, drain=True)\n"
        "from jax._src import xla_bridge\n"
        "print(rec['requests'], rec['drained'],\n"
        "      xla_bridge.backends_are_initialized())\n")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "PYTHONPATH": os.path.join(root, "src")}
    p = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.split()[-3:] == ["20", "True", "False"]
