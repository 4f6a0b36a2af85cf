"""Median over the window's waves of the device's idle time inside
``engine.step()`` (the ``bench.step`` span), from the trace: packing,
host transfers, dispatch, unpacking and bookkeeping, everything of a
wave that the device waits for."""
from bench.stats import pct


def read(run):
    if run.trace is None:
        return None
    return pct([s * 1e3 for s in run.trace["step_idle_s"]], 50)
