"""Build what a configuration names, through the library's public API.

A configuration file lists its ``stages`` by name and kind; a traffic
file writes pipelines over those names (``bm25 % 100 >> text_loader >>
mono``).  ``World`` makes the inputs from the run's seed (the corpus,
where a stage's kind reads one) and builds each stage once per process
through its kind's module (``bench/kinds/<kind>.py``), which makes its
weights from the configuration's ``weight_seed``.
"""
from __future__ import annotations

import re
from typing import Callable, Dict, List, Optional, Tuple

from . import gen


def terms(expr: str, stages) -> List[Tuple[str, Optional[int]]]:
    """``a % 10 >> b >> c`` as (stage name, cutoff or None) terms over the
    stage names ``stages``."""
    out = []
    for part in expr.split(">>"):
        m = re.fullmatch(r"\s*([A-Za-z0-9_]+)\s*(?:%\s*(\d+))?\s*", part)
        if m is None or m.group(1) not in stages:
            raise ValueError(f"bad pipeline term {part!r} in {expr!r}; "
                             f"stages: {sorted(stages)}")
        out.append((m.group(1), int(m.group(2)) if m.group(2) else None))
    return out


class World:
    def __init__(self, cfg: Dict, seed: int, kind: Callable):
        """``kind(name)`` is the module of stage kind ``name``."""
        self.cfg = cfg
        self.seed = int(seed)
        self.kinds = {name: kind(spec["kind"])
                      for name, spec in cfg["stages"].items()}
        self.corpus: Optional[gen.Corpus] = None
        self.texts = None
        if any(k.CORPUS for k in self.kinds.values()):
            self.corpus = gen.make_corpus(cfg["corpus"], cfg["num_passages"],
                                          self.seed)
            self.texts = self.corpus.texts()
        self.stages: Dict[str, object] = {
            name: self.kinds[name].build(self, name, spec)
            for name, spec in cfg["stages"].items()}

    def pipeline(self, expr: str):
        """``a % 10 >> b >> c`` over this world's stage names."""
        out = None
        for name, cut in terms(expr, self.stages):
            t = self.stages[name]
            if cut is not None:
                t = t % cut
            out = t if out is None else out >> t
        return out
