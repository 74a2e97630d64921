"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

The window is the host annotation that the harness wraps around its
measured window (``bench.window``).  Within it, per TPU device plane:

- busy: the union of the intervals of the ``XLA Ops`` line (an operation
  running on the device); idle share = 1 - busy / window;
- per program: the ``XLA Modules`` line's durations, by program name
  with its ``(fingerprint)`` suffix removed (``jit__lambda``,
  ``jit__xla_chunk_topk``, ...), with the number of executions;
- device ops: the ``XLA Ops`` durations by HLO instruction name, without
  the loops and conditionals that contain other ops;
- idle gaps: the gaps between busy intervals, each labelled by the
  innermost harness annotation (``bench.*``) that covers its midpoint and
  by the innermost other host event there, summed per label.

Event times of the device and host planes are on the profiler's one
clock, in nanoseconds.
"""
from __future__ import annotations

import glob
import os
import re
from bisect import bisect_right
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
_SUFFIX = re.compile(r"\(\d+\)$")
#: control-flow ops whose interval holds their body's ops: counted in the
#: busy union, left out of the per-op list so no time is listed twice
_CONTAINERS = ("%while", "%conditional", "%call")


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _merge(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(s: float, e: float, lo: float, hi: float) -> float:
    return max(0.0, min(e, hi) - max(s, lo))


def op_name(event_name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``%fusion.3``."""
    return event_name.split(" = ", 1)[0].strip()


def program_name(event_name: str) -> str:
    """``jit__lambda(1352...)`` -> ``jit__lambda``."""
    return _SUFFIX.sub("", event_name.strip())


class _Cover:
    """Innermost covering span lookup over (start, end, name), for spans
    no longer than ``cap`` ns."""

    def __init__(self, spans: List[Tuple[float, float, str]],
                 cap: float = float("inf")):
        self.spans = sorted(sp for sp in spans if sp[1] - sp[0] <= cap)
        self.starts = [s for s, _, _ in self.spans]
        self.longest = max((e - s for s, e, _ in self.spans), default=0.0)

    def at(self, t: float) -> Optional[str]:
        best = None
        for i in range(bisect_right(self.starts, t) - 1, -1, -1):
            s, e, name = self.spans[i]
            if t - s > self.longest:
                break
            if e >= t and (best is None or e - s < best[0]):
                best = (e - s, name)
        return None if best is None else best[1]


def reduce_trace(path: str, top: int = 10) -> Dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host, bench = [], []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: [(e.start_ns, e.start_ns + e.duration_ns,
                                e.name) for e in ln.events]
                     for ln in plane.lines
                     if ln.name in ("XLA Ops", "XLA Modules")}
            devices.append(lines)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    span = (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    (bench if e.name.startswith("bench.") else host).append(
                        span)
    windows = [(s, e) for s, e, n in bench if n == WINDOW]
    if not devices:
        raise ValueError(f"{path}: no TPU device plane")
    if windows:
        lo, hi = windows[0]
    else:
        evs = [ev for d in devices for ev in d.get("XLA Ops", [])]
        lo, hi = min(s for s, _, _ in evs), max(e for _, e, _ in evs)
    window_s = (hi - lo) / 1e9

    busy_per_chip, programs, ops = [], {}, {}
    gaps: List[Tuple[float, float]] = []
    for i, d in enumerate(devices):
        iv = [(max(s, lo), min(e, hi)) for s, e, _ in d.get("XLA Ops", [])
              if e > lo and s < hi]
        merged = _merge(iv)
        busy_per_chip.append(sum(e - s for s, e in merged) / 1e9)
        for s, e, name in d.get("XLA Ops", []):
            t = _clip(s, e, lo, hi)
            k = op_name(name)
            if t > 0 and not k.startswith(_CONTAINERS):
                ops[k] = ops.get(k, 0.0) + t / 1e9
        for s, e, name in d.get("XLA Modules", []):
            t = _clip(s, e, lo, hi)
            if t > 0:
                p = programs.setdefault(program_name(name), {"s": 0.0, "n": 0})
                p["s"] += t / 1e9
                p["n"] += 1
        if i == 0:
            edges = [lo] + [x for se in merged for x in se] + [hi]
            gaps = [(edges[j], edges[j + 1])
                    for j in range(0, len(edges), 2)
                    if edges[j + 1] > edges[j]]

    harness = _Cover([b for b in bench if b[2] != WINDOW])
    other = _Cover(host, cap=50e6)
    idle: Dict[str, float] = {}
    for s, e in gaps:
        mid = (s + e) / 2
        label = harness.at(mid) or "outside harness calls"
        inner = other.at(mid)
        if inner:
            label = f"{label} / {inner}"
        idle[label] = idle.get(label, 0.0) + (e - s) / 1e9

    def _top(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                [:top]]

    return {
        "window_s": window_s,
        "busy_s": sum(busy_per_chip) / len(busy_per_chip),
        "chips": len(devices),
        "programs": programs,
        "device_ops": _top(ops),
        "idle_gaps": _top(idle),
    }
