"""Summed device time of every operation that is not the MAC kernel
(encode, patch masks, pool, add, ReLU and cast netlists, decode) in the
traced window, per image served."""
from bench.stats import per_image_ms


def read(run):
    if run.trace is None:
        return None
    return per_image_ms(run, run.trace["other_s"])
