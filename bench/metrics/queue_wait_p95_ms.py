"""95th percentile of scheduled arrival to the start of the ``step()``
whose wave carried the request: the wait in the engine's queue."""
from bench.stats import pct


def read(run):
    return pct([(r.t_step - r.t_sched) * 1e3 for r in run.window.requests
                if r.status == "served"], 95)
