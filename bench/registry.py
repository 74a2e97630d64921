"""Find the benchmark's parts by name: cells, configurations, traffic
mixes, limits, metric readers and device peaks.

- ``BENCHMARK.json`` at the root names the cells and metrics;
- a configuration is the file its ``configs`` entry names;
- a traffic mix ``<mix>`` is ``bench/traffic/<mix>.json``;
- a cell's correctness limits are ``bench/limits/<cell>.json``;
- a metric ``<name>`` is read by ``read(record)`` in
  ``bench/metrics/<name>.py``, which returns a number, or ``None`` where
  the run has nothing for it to read;
- peaks are ``bench/peaks.json``, keyed by JAX's ``device_kind``.

A new cell, configuration, mix or metric is new files and new entries;
nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Callable, Dict, List

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def _json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


class Registry:
    def __init__(self, root: str = ROOT, bench: str = BENCH):
        self.root, self.bench = root, bench
        self.spec = _json(os.path.join(root, "BENCHMARK.json"))

    def workload(self, name: str) -> Dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r}; known: "
                       f"{[w['name'] for w in self.spec['workloads']]}")

    def config(self, name: str) -> Dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return _json(os.path.join(self.root, c["file"]))
        raise KeyError(f"no config {name!r}")

    def traffic(self, name: str) -> Dict:
        return _json(os.path.join(self.bench, "traffic", f"{name}.json"))

    def limits(self, workload: str) -> Dict[str, float]:
        return _json(os.path.join(self.bench, "limits", f"{workload}.json"))

    def peaks(self, device_kind: str) -> Dict:
        table = _json(os.path.join(self.bench, "peaks.json"))
        if device_kind not in table:
            raise KeyError(f"device kind {device_kind!r} is not in "
                           f"peaks.json ({sorted(table)})")
        return table[device_kind]

    def metrics(self, workload: str, trace: bool) -> List[Dict]:
        """The metrics a run of ``workload`` reports: with ``trace`` the
        per-layer ones, else the end-to-end ones."""
        e2e = [m for m in self.spec["end_to_end"]
               if workload in m.get("workloads", [workload])]
        if not trace:
            return e2e
        moved = {m["name"] for m in e2e}
        return [m for m in self.spec["per_layer"]
                if workload in m.get("workloads", [workload] if
                                     m["moves"] in moved else [])]

    def reader(self, metric: str) -> Callable:
        path = os.path.join(self.bench, "metrics", f"{metric}.py")
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{metric.replace('.', '_').replace('-', '_')}",
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
