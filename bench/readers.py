"""What several per-layer metric readers share: the device's idle share,
a program group's share of its roofline, and the whole window's share of
the bf16 peak.  Each reader in ``bench/metrics/`` picks one of these for
its cells, and returns ``None`` where the run has nothing for it to read
(no trace, no counted work, no device time)."""
from __future__ import annotations

from typing import Optional, Sequence


def idle_share(r) -> Optional[float]:
    """Per cent of the traced window in which no operation ran on the
    device (1 - union of the ``XLA Ops`` intervals / window)."""
    t = r.trace
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def mfu(r) -> Optional[float]:
    """Model operations of the window, counted from real tokens, over the
    chip's bf16 peak times the traced window, in per cent."""
    f = r.work.get("model_flops")
    if not f or not r.trace:
        return None
    return 100.0 * f / (r.peaks["bf16_flops_per_s"] * r.trace["window_s"])


def roofline(r, programs: Sequence[str], flops_key: str,
             bytes_key: str) -> Optional[float]:
    """Per cent of their roofline that the ``programs`` reached: the least
    time their work needs (``work[flops_key]`` over the bf16 peak, or
    ``work[bytes_key]`` read once per call over HBM bandwidth, whichever
    is larger) over their device time in the trace."""
    t, f = r.trace, r.work.get(flops_key)
    if not t or not f:
        return None
    progs = [p for n, p in t["programs"].items() if n in programs]
    secs = sum(p["s"] for p in progs)
    calls = sum(p["n"] for p in progs)
    if secs <= 0:
        return None
    need = max(f / r.peaks["bf16_flops_per_s"],
               calls * r.work[bytes_key] / r.peaks["hbm_bytes_per_s"])
    return 100.0 * need / secs
