"""Arrival times of a Poisson process of rate ``rate_per_s``, one fixed
schedule per mix.

``round(rate * seconds)`` requests arrive; the gaps between them are
the exponential distribution's quantiles at ``(i + 0.5) / n``, in the
order drawn from the mix's ``order_seed``.  Every run of a mix offers
the same schedule, whatever its ``--seed``: over a window of some
hundreds of requests the order of the gaps sets the bursts, and with
them the tail, so an order drawn from the run's seed would make the
tail a property of the seed.
"""
from __future__ import annotations

import numpy as np


def times(mix: dict, seconds: float) -> np.ndarray:
    """Seconds from the window's start, the first at 0."""
    rate = mix["rate_per_s"]
    n = max(1, round(rate * seconds))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    np.random.default_rng([mix["order_seed"], 2]).shuffle(gaps)
    return np.cumsum(gaps) - gaps[0]
