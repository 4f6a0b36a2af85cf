"""From the profiler's trace to per-layer numbers.

``capture`` records a device trace around the measured window, with
the host's Python tracer off, and keeps only what the reduction reads:

* ``window``: start and end of the ``bench.window`` host span;
* ``devices``: for each device plane, its operations on the ``XLA Ops``
  line as ``[name, start_ns, duration_ns]``;
* ``host``: the benchmark's own host spans (``bench.*``).

All times are on the trace's one clock.  ``reduce`` turns that into the
device's busy time (the union of its operations' intervals inside the
window), the time of the MAC kernel and of every other operation, the
device's idle time inside each ``bench.step`` span, the operations that
took most time, and the longest idle gaps named by the host span that
covers most of each.
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import re
import shutil
import tempfile

# The TPU trace names each device operation by its HLO text.  The only
# Pallas kernels of the served wave program are the fused MAC kernels
# (one per conv), and a Pallas kernel is a ``tpu_custom_call``: read by
# hand from a trace of the main path.
MAC_KERNEL = re.compile(r'custom_call_target="tpu_custom_call"')
LAYOUT = re.compile(r"\{[^{}]*\}")
ATTRS = re.compile(r", (?:custom_call_target|metadata|backend_config|"
                   r"frontend_attributes)=")

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"


def extract(profile) -> dict:
    """The reduction's input from a ``jax.profiler.ProfileData``."""
    devices, host = [], []
    window = None
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [[e.name, int(e.start_ns), int(e.duration_ns)]
                            for e in line.events]
            devices.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == "bench.window":
                        window = [int(e.start_ns), int(e.end_ns)]
                    elif e.name.startswith("bench."):
                        host.append([e.name, int(e.start_ns),
                                     int(e.duration_ns)])
    return {"window": window, "devices": devices, "host": host}


class Capture:
    events: dict | None = None


@contextlib.contextmanager
def capture():
    """Trace the block; ``.events`` holds ``extract``'s output after it."""
    import jax
    from jax.profiler import ProfileData, TraceAnnotation
    out = Capture()
    d = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    try:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            with TraceAnnotation("bench.window"):
                yield out
        finally:
            jax.profiler.stop_trace()
        path = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)
        out.events = extract(ProfileData.from_file(path[0]))
    finally:
        shutil.rmtree(d, ignore_errors=True)


def short(name: str, width: int = 160) -> str:
    """An operation's HLO text without layouts or attributes."""
    head = LAYOUT.sub("", ATTRS.split(name)[0])
    return ("fused_mac " if MAC_KERNEL.search(name) else "") + \
        head[:width]


def _union(intervals):
    """Sorted disjoint (start, end) pairs covering ``intervals``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap(a0, a1, b0, b1):
    return max(0, min(a1, b1) - max(a0, b0))


def reduce(ev: dict, top: int = 10) -> dict:
    """Seconds: ``window_s``, ``busy_s`` (averaged over the devices),
    ``kernel_s`` and ``other_s`` (summed over the devices),
    ``step_idle_s`` (per ``bench.step`` span in the window, averaged
    over the devices), and ``device_ops`` / ``idle_gaps`` as
    ``[[name, seconds], ...]``."""
    w0, w1 = ev["window"]
    steps = sorted((max(s, w0), min(s + d, w1)) for n, s, d in ev["host"]
                   if n == "bench.step" and s < w1 and s + d > w0)
    step_idle = [0] * len(steps)
    busy, kernel, other = [], 0, 0
    by_name: dict[str, int] = {}
    gaps = []
    for ops in ev["devices"]:
        inside = [(s, s + d, n) for n, s, d in ops
                  if _overlap(s, s + d, w0, w1) > 0]
        for s, e, n in inside:
            d = _overlap(s, e, w0, w1)
            if MAC_KERNEL.search(n):
                kernel += d
            else:
                other += d
            by_name[n] = by_name.get(n, 0) + d
        u = _union((max(s, w0), min(e, w1)) for s, e, _ in inside)
        busy.append(sum(e - s for s, e in u))
        starts = [s for s, _ in u]
        for k, (a, b) in enumerate(steps):
            i = max(0, bisect.bisect_right(starts, a) - 1)
            step_idle[k] += b - a
            while i < len(u) and u[i][0] < b:
                step_idle[k] -= _overlap(a, b, *u[i])
                i += 1
        edges = [w0] + [x for iv in u for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n_dev = max(1, len(ev["devices"]))

    def named(g0, g1):
        best, name = 0, "none"
        for n, s, d in ev["host"]:
            o = _overlap(g0, g1, s, s + d)
            if o > best:
                best, name = o, n
        return name

    gaps.sort(key=lambda g: g[0] - g[1])
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy) / n_dev * 1e-9,
        "kernel_s": kernel * 1e-9,
        "other_s": other * 1e-9,
        "step_idle_s": [x / n_dev * 1e-9 for x in step_idle],
        "device_ops": [[short(n), d * 1e-9] for n, d in ops],
        "idle_gaps": [[named(g0, g1), (g1 - g0) * 1e-9]
                      for g0, g1 in gaps[:top]],
    }
