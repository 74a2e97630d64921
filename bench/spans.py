"""What the readers of the program's own spans and counters share.

The program records them (``repro.core.trace``) only while the profiler
records, so in the bench process after a ``--trace 1`` run its table
holds the traced window alone.  A reader divides by the trace's window
(the ``bench.window`` interval), and returns ``None`` where the run was
not traced or the program keeps no such table (a program without
``repro.core.trace``)."""
from __future__ import annotations

from typing import Dict, Optional, Sequence


def table() -> Optional[Dict]:
    """``repro.core.trace.summary()``, or ``None`` where nothing was
    recorded."""
    try:
        from repro.core import trace
    except ImportError:
        return None
    s = trace.summary()
    return s if s["spans"] or s["counters"] else None


def span_s(r, names: Sequence[str], kind: str = "self_s"
           ) -> Optional[float]:
    """Seconds of ``kind`` (``self_s`` or ``total_s``) summed over the
    spans ``names``, where a trace and a table exist."""
    t, s = r.trace, table()
    if not t or t["window_s"] <= 0 or s is None:
        return None
    return sum(s["spans"].get(n, {}).get(kind, 0.0) for n in names)


def self_share(r, names: Sequence[str]) -> Optional[float]:
    """The self time of the spans ``names``, in per cent of the traced
    window."""
    secs = span_s(r, names)
    return None if secs is None else 100.0 * secs / r.trace["window_s"]
