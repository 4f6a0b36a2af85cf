"""Share of its roofline that the fused MAC kernel reaches, in percent:
the chip's least time for every kernel call of the traced window over
the kernel's device time.  A call's least time is the larger of its
bitwise operations (``ops_per_mac`` of the configuration per 32 MACs)
over the VPU's int32 peak and its least bytes over HBM's bandwidth
(``bench/counts.py``).  The bound that applies most is noted as
``mac_roofline_bound``."""
from bench import counts
from bench.reference import FORMATS, acc_format
from bench.stats import bucket


def read(run):
    t = run.trace
    if t is None or t["kernel_s"] <= 0 or run.peaks is None:
        return None
    we, wf = FORMATS[run.cfg["format"]]
    nin, nout = we + wf + 3, sum(acc_format((we, wf))) + 3
    vpu = run.peaks["vpu_int32_ops_per_s"]["value"]
    hbm = run.peaks["hbm_bytes_per_s"]["value"]
    least, by_bound = 0.0, {"vpu": 0.0, "hbm": 0.0}
    for w in run.window.waves:
        for c in counts.convs(run.model, run.cfg, bucket(run, w.images)):
            s, b = counts.least_seconds(c, nin, nout,
                                        run.cfg["ops_per_mac"], vpu, hbm)
            least += s
            by_bound[b] += s
    run.notes["mac_roofline_bound"] = max(by_bound, key=by_bound.get)
    return 100 * least / t["kernel_s"]
