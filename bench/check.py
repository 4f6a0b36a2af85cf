"""The comparison that decides ``correct``.

After the window, a sample of the served requests, drawn from the seed,
is run through the configuration's float32 reference
(``bench.reference``) one image at a time, and each served output is
compared with it.  The number compared is the widest relative gap,
``max |served - reference| / max |reference|`` over the elements of
each image, the worst image counting.  A non-finite element that the
reference does not share (the same infinity, or NaN for NaN) makes the
gap infinite.
"""
from __future__ import annotations

import numpy as np

from bench.reference import FORMATS, Reference


def rel_gap(out, ref) -> float:
    out = np.asarray(out, np.float64)
    ref = np.asarray(ref, np.float64)
    fin = np.isfinite(ref)
    same = (out == ref) | (np.isnan(out) & np.isnan(ref))
    if (np.isfinite(out) != fin).any() or not same[~fin].all():
        return float("inf")
    scale = np.abs(ref[fin]).max(initial=0.0)
    diff = np.abs(out[fin] - ref[fin]).max(initial=0.0)
    if scale == 0.0:
        return 0.0 if diff == 0.0 else float("inf")
    return float(diff / scale)


def reference_outputs(model, cfg, kernels, images) -> np.ndarray:
    ref = Reference(images, FORMATS[cfg["format"]])
    return ref.output(model.build(ref, cfg, kernels))


def sample(requests, seed: int, k: int) -> list:
    served = [r for r in requests if r.status == "served"]
    rng = np.random.default_rng([seed, 3])
    idx = rng.choice(len(served), size=min(k, len(served)), replace=False)
    return [served[i] for i in sorted(idx)]


def max_rel_gap(model, cfg, kernels, pool, reqs) -> float:
    """Widest gap over ``reqs`` (served, with ``out`` and ``image``)."""
    return max((rel_gap(r.out, reference_outputs(
        model, cfg, kernels, pool[r.image % len(pool)][None])[0])
        for r in reqs), default=float("inf"))
