#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chip it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Its
configuration is ``bench/configs/<config>.json`` (laid out by
``bench/models/<family>.py``), its traffic mix
``bench/traffic/<traffic>.json`` (an open loop's arrival times come from
``bench/arrivals/<kind>.py``), and each metric it reports is read by
``bench/metrics/<metric>.py``.  With ``--trace 0`` it reports the cell's
end-to-end metrics; with ``--trace 1`` it traces the window and reports
its per-layer metrics.

Set-up builds the weights on the device from the seed, compiles (or
loads from the persistent cache in ``.jax_cache`` at the root of the
checkout) the wave program of each batch bucket the traffic uses, and
runs one wave per bucket.  Then it measures for ``--seconds``, and
after the window compares a sample of the served outputs, drawn from
the seed, with the float32 reference (``bench/check.py``).

The last line of standard output is one JSON object.  It exits non-zero
and prints no result when JAX finds no TPU or fewer chips than the cell
asks for, when the chip is missing from ``bench/peaks.json``, when
anything compiles inside the window, and when a wave is retried,
degraded or fails.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "bench")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


class Refused(RuntimeError):
    """A run that must not report a result; ``code`` is its exit code."""

    def __init__(self, code: int, why: str):
        super().__init__(why)
        self.code = code


def process_age() -> float:
    """Seconds since this process started (0 where /proc cannot say)."""
    try:
        with open("/proc/self/stat") as f:
            start = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return max(0.0, up - start / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


SETUP_T0 = T_START - process_age()


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def metric_names(spec, cell: str, kind: str) -> list[str]:
    return [m["name"] for m in spec[kind]
            if cell in m.get("workloads", [cell])]


def reader(name: str):
    path = os.path.join(HERE, "metrics", f"{name}.py")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench.metrics.{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def chips_for(cell: dict):
    """The cell's devices; refuses without a TPU or with too few."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(3, f"no TPU: JAX found platform {devs[0].platform!r}")
    if len(devs) < cell["chips"]:
        raise Refused(3, f"the cell asks for {cell['chips']} chips, JAX "
                      f"sees {len(devs)}")
    return devs[:cell["chips"]]


def peaks_for(kind: str) -> dict:
    peaks = load_json(HERE, "peaks.json")
    if kind not in peaks:
        raise Refused(4, f"device kind {kind!r} is not in bench/peaks.json")
    return peaks[kind]


def measure(eng, mix, pool, seconds):
    """The measured window, and the full garbage collections inside it."""
    from bench import client, system
    pauses = system.GcPauses()
    try:
        return client.run(eng, mix, pool, seconds), pauses
    finally:
        pauses.stop()


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             spec=None, cfg=None, mix=None, need_chip: bool = True):
    """One run of one cell; returns the result object.  ``spec``,
    ``cfg`` and ``mix`` replace what the files hold, and
    ``need_chip=False`` runs wherever JAX runs (both for tests)."""
    import jax

    from bench import check, client, system

    spec = spec or load_json(ROOT, "BENCHMARK.json")
    cell = next(w for w in spec["workloads"] if w["name"] == workload)
    cfg = cfg or load_json(HERE, "configs", f"{cell['config']}.json")
    mix = mix or client.load(cell["traffic"])
    model = importlib.import_module(f"bench.models.{cfg['family']}")
    kind = "per_layer" if trace else "end_to_end"
    names = metric_names(spec, workload, kind)
    readers = {n: reader(n) for n in names}

    devs = chips_for(cell) if need_chip else jax.devices()[:cell["chips"]]
    peaks = peaks_for(devs[0].device_kind) if need_chip else None
    clock = system.CompileClock()

    laps = [("jax up", time.perf_counter() - SETUP_T0)]
    graph, kernels = system.build(model, cfg, seed)
    laps.append(("weights and graph", time.perf_counter() - SETUP_T0))
    eng = system.engine(graph, cfg)
    pool = system.images(cfg, seed, cfg["image_pool"])
    warm = eng.buckets if mix["loop"] == "open" else (eng.max_batch,)
    system.warm(eng, pool, warm)
    laps.append(("warm waves", time.perf_counter() - SETUP_T0))
    n_warm = len(eng.wave_seconds)
    counters = system.health(eng)
    compiles = clock.snapshot()
    setup_s = time.perf_counter() - SETUP_T0
    log(f"set-up {setup_s:.3f}s: compile {clock.seconds:.1f}s over "
        f"{clock.programs} programs, {clock.cache_hits} from the cache; "
        f"max_batch {eng.max_batch}, buckets {eng.buckets}; "
        + ", ".join(f"{n} by {t:.2f}s" for n, t in laps))

    cap = None
    if trace:
        from bench.trace import capture
        with capture() as cap:
            window, pauses = measure(eng, mix, pool, seconds)
    else:
        window, pauses = measure(eng, mix, pool, seconds)

    if clock.snapshot() != compiles:
        raise Refused(5, f"JAX compiled inside the window: (programs, "
                      f"cache hits, traces) {compiles} -> "
                      f"{clock.snapshot()}")
    after = system.health(eng)
    if after != counters:
        raise Refused(6, f"a wave was retried, failed or degraded, or a "
                      f"request refused: (retries, failures, failed "
                      f"waves, expired, rejected, shed, degradations) "
                      f"{counters} -> {after}")

    stats = [d.memory_stats() or {} for d in devs]
    peak_bytes = max(s.get("peak_bytes_in_use", 0) for s in stats)
    run = types.SimpleNamespace(
        cfg=cfg, model=model, mix=mix, window=window, setup_s=setup_s,
        wave_seconds=list(eng.wave_seconds[n_warm:]),
        buckets=tuple(eng.buckets), chips=len(devs), peaks=peaks,
        trace=None, notes={"gc_full": pauses.count,
                           "gc_full_ms_max": pauses.longest_ms})
    del eng, graph
    gc.collect()

    if cap is not None:
        from bench.trace import reduce
        run.trace = reduce(cap.events)
    metrics = {}
    for name in names:
        val = readers[name](run)
        if val is not None:
            unit = next(m["unit"] for m in spec[kind] if m["name"] == name)
            metrics[name] = {"value": val, "unit": unit}

    t0 = time.perf_counter()
    reqs = check.sample(window.requests, seed, cfg["check"]["sample"])
    gap = check.max_rel_gap(model, cfg, kernels, pool, reqs)
    limit = cfg["check"]["max_rel_gap"]
    log(f"reference: {len(reqs)} requests in "
        f"{time.perf_counter() - t0:.1f}s")

    served = sum(r.status == "served" for r in window.requests)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak_bytes)}
    result = {"correct": bool(gap <= limit and reqs),
              "attempted": len(window.requests),
              "failed": len(window.requests) - served,
              "metrics": metrics, "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    waves = window.waves
    run.notes["wave_ms_max"] = max(
        (w.t_end - w.t_start) * 1e3 for w in waves) if waves else None
    run.notes["host_gap_ms_max"] = max(
        ((b.t_start - a.t_end) * 1e3 for a, b in zip(waves, waves[1:])),
        default=None)
    log(f"window: {len(waves)} waves in {window.seconds:.3f}s; "
        f"longest step {run.notes['wave_ms_max']} ms, longest gap "
        f"between steps {run.notes['host_gap_ms_max']} ms; "
        f"{pauses.count} full garbage collections, the longest "
        f"{pauses.longest_ms:.1f} ms")
    result["notes"] = run.notes
    result["check"] = {"max_rel_gap": {
        "value": gap if math.isfinite(gap) else str(gap),
        "limit": limit}}
    log(f"check: max_rel_gap {gap} limit {limit} over {len(reqs)} "
        f"served requests")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except Refused as e:
        log(f"refused: {e}")
        return e.code
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
