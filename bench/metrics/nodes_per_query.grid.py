"""Plan nodes executed per query (``PlanStats.nodes_executed`` over
``PlanStats.n_queries``, summed over the window's iterations)."""


def read(r):
    c = r.run.counters
    if not c.get("plan_queries"):
        return None
    return c["nodes_executed"] / c["plan_queries"]
