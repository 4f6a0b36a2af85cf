"""What the metric readers in ``bench/metrics`` share.

A reader is ``read(run) -> float | None``; ``run`` holds the window
(``bench.client.Window``), ``setup_s``, the engine's ``wave_seconds``
of the window's waves, the configuration, its ``model`` module, the
engine's ``buckets``, ``chips``, the chip's ``peaks`` and, in a traced
run, ``trace`` (``bench.trace.reduce``'s output).  A reader that finds
nothing to read returns None, and the metric is left out.
"""
from __future__ import annotations

import math

import numpy as np


def pct(values, q: float) -> float | None:
    """The ``q``-th percentile (linear), or None for no values; an
    infinite value (a request that failed) counts as the largest."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def latencies_ms(run) -> list:
    """Response received minus scheduled arrival, every request of the
    window; one that was not served counts as infinite."""
    return [(r.t_done - r.t_sched) * 1e3 if r.status == "served"
            else math.inf for r in run.window.requests]


def images(run) -> int:
    return sum(w.images for w in run.window.waves)


def images_per_s(run) -> float | None:
    n = images(run)
    return n / run.window.seconds if n and run.window.seconds > 0 else None


def wave_ms(run) -> float | None:
    return pct(np.asarray(run.wave_seconds) * 1e3, 50)


def per_image_ms(run, seconds: float) -> float | None:
    n = images(run)
    return seconds * 1e3 / n if n else None


def bucket(run, n: int) -> int:
    return min(b for b in run.buckets if b >= n)
