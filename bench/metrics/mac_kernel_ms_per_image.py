"""Summed device time of the fused MAC kernel's operations in the traced
window, per image served."""
from bench.stats import per_image_ms


def read(run):
    if run.trace is None or run.trace["kernel_s"] <= 0:
        return None
    return per_image_ms(run, run.trace["kernel_s"])
