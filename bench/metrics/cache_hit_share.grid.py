"""Share of the planner-inserted caches' lookups that hit
(``PlanStats.cache_hits`` over hits plus misses), in per cent."""


def read(r):
    c = r.run.counters
    n = c.get("cache_hits", 0) + c.get("cache_misses", 0)
    if "plan_queries" not in c or not n:
        return None
    return 100.0 * c["cache_hits"] / n
