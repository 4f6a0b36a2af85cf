"""Share of the fused MAC kernel's output tile words that carry a real
(pixel, channel word) pair, in percent, weighted by multiply-accumulates
over the window's kernel calls: each conv's ``[P, ceil(M/32)]`` output
plane is laid onto (8, 128) int32 vregs as the program's ``tile_plan``
lays it.  A program without ``tile_plan``, or a configuration on
another backend, gives nothing."""
from bench import counts
from bench.stats import bucket


def read(run):
    try:
        from repro.core.pallas_backend import tile_plan
    except ImportError:
        return None
    if run.cfg["backend"] != "pallas_fused":
        return None
    macs = useful = 0
    for w in run.window.waves:
        for c in counts.convs(run.model, run.cfg, bucket(run, w.images)):
            fill = tile_plan(c.p, -(-c.cout // counts.LANE)).fill
            macs += c.macs
            useful += c.macs * fill
    return 100 * useful / macs if macs else None
