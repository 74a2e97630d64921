"""Share of the traced window the host spent in the planner's own work:
building plans, the run's own bookkeeping (stats, the plan manifest's
run record), running nodes outside the stages' spans (frames,
cut-offs, text loading, merges) and evaluating measures (self time of
``plan.build``, ``plan.run``, ``plan.node`` and
``experiment.evaluate``), in per cent."""
from bench.spans import self_share


def read(r):
    return self_share(r, ("plan.build", "plan.run", "plan.node",
                          "experiment.evaluate"))
