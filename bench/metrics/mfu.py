"""Whole-step share of the chip's peak: 2 x conv MACs per image x
images/s over chips x the chip's published bfloat16 peak, in percent.
The MACs come from the configuration's shapes, so the same work counts
whatever computes it."""
from bench import counts
from bench.stats import images_per_s


def read(run):
    rate = images_per_s(run)
    if rate is None or run.peaks is None:
        return None
    flops = 2 * counts.macs_per_image(run.model, run.cfg) * rate
    return 100 * flops / (run.chips * run.peaks["bf16_flops_per_s"]["value"])
