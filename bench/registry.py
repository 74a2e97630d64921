"""Find the benchmark's parts by name: cells, configurations, stage
kinds, traffic mixes, limits, metric readers and device peaks.

- ``BENCHMARK.json`` at the root names the cells and metrics;
- a configuration is the file its ``configs`` entry names;
- a stage kind ``<kind>`` (a configuration's ``stages`` give each stage
  its kind) is the module ``bench/kinds/<kind>.py``; what it provides is
  set out below;
- a traffic mix ``<mix>`` is ``bench/traffic/<mix>.json``;
- a cell's correctness limits are ``bench/limits/<cell>.json``;
- a metric ``<name>`` is read by ``read(record)`` in
  ``bench/metrics/<name>.py``, which returns a number, or ``None`` where
  the run has nothing for it to read;
- peaks are ``bench/peaks.json``, keyed by JAX's ``device_kind``.

A new cell, configuration, stage kind, mix or metric is new files and new
entries; nothing here changes.

A stage kind's module gives the stage's ``ROLE`` in a pipeline,
``CORPUS`` (whether it reads the generated corpus), ``build(world, name,
spec)`` (the program's stage, made through the library's public API with
the benchmark's weights installed by ``weights.install``) and
``warm(world, stage, spec, queries)`` (the stage run at every shape the
window can reach, as set-up).  By role it also gives:

- ``"loader"``: nothing more;
- ``"retriever"`` (an exact ranking over the corpus): ``Reference(cfg,
  spec, inputs)`` with ``scores(query, precision)`` over every passage
  and ``top(scores, k)``, and ``CONTROL``, the precision of its control;
- ``"pointwise"`` and ``"pairwise"`` scorers, and ``"embedding"``
  retrievers (query embeddings against an index made on the device):
  ``params(cfg, spec)``, the weights tree from the configuration's
  ``weight_seed`` and the stage's stream (its ``stream`` entry, else the
  kind's ``STREAM``); ``work(cfg, spec, real_tokens)``, a dict of
  ``*_flops`` keys (summed over the stages, and into ``model_flops``)
  and bytes read per call of a program (equal wherever two stages give
  one key); and ``Reference(cfg, spec, inputs)``.  A scorer's reference
  has ``score(query, docs, precision) -> {doc: score}`` at ``"highest"``
  (the reference) or ``"int8"`` (the control) and ``real_tokens(query,
  groups)``, the real tokens of each distinct model input that the groups
  of passages scored for one query make; an embedding retriever's has
  ``embed(queries, precision)``, ``blocks``, ``n_rows``, ``rows(block)``
  and ``real_tokens(query)``.

``inputs`` (``bench.check.Inputs``) holds the corpus, the run's seed and
the queries' vocabulary.  A kind module imports what it shares with other
kinds from ``bench`` (``bench.bert`` for the BERT encoders).
"""
from __future__ import annotations

import importlib.util
import json
import os
from types import ModuleType
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
        self._kinds: Dict[str, ModuleType] = {}

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
        return _load(os.path.join(self.bench, "metrics", f"{metric}.py"),
                     "bench_metric_", metric).read

    def kind(self, name: str) -> ModuleType:
        """The module of stage kind ``name``, loaded once per registry."""
        if name not in self._kinds:
            path = os.path.join(self.bench, "kinds", f"{name}.py")
            if not os.path.isfile(path):
                d = os.path.dirname(path)
                known = sorted(f[:-3] for f in (
                    os.listdir(d) if os.path.isdir(d) else [])
                    if f.endswith(".py") and not f.startswith("_"))
                raise KeyError(f"no stage kind {name!r}; known: {known}")
            self._kinds[name] = _load(path, "bench_kind_", name)
        return self._kinds[name]


def _load(path: str, prefix: str, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        prefix + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
