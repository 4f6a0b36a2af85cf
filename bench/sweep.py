#!/usr/bin/env python3
"""Find the highest Poisson rate a configuration sustains, in one process.

Builds the configuration's engine once, warms every bucket, measures
the closed loop's images/s (the offline rate), then offers open-loop
Poisson traffic (``bench/arrivals/poisson.py``) at each fraction of it
for ``--seconds`` and prints, per rate, the served rate, the latency median
and 95th percentile from the scheduled arrival, and the mean queue wait
in the first and the last third of the arrivals.  A rate is sustained
when the last third waits no longer than the first, give or take a
wave: past the knee the queue grows all through the window.  The
open-loop cell's traffic file fixes its rate at four fifths of the
knee found here.

    python bench/sweep.py --config resnet20-cifar-h16 \
        --fractions 0.5,0.6,0.7,0.8,0.9,1.0 --seconds 15
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import run  # noqa: E402  (bench/run.py: sets the import paths)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--fractions", required=True,
                    type=lambda s: [float(x) for x in s.split(",")])
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    import jax
    jax.config.update("jax_compilation_cache_dir", run.CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    import importlib

    from bench import client, system
    cfg = run.load_json(run.HERE, "configs", f"{args.config}.json")
    model = importlib.import_module(f"bench.models.{cfg['family']}")
    graph, _ = system.build(model, cfg, args.seed)
    eng = system.engine(graph, cfg)
    pool = system.images(cfg, args.seed, cfg["image_pool"])
    system.warm(eng, pool, eng.buckets)
    w = client.run(eng, {"loop": "closed"}, pool, args.seconds)
    base = len(w.requests) / w.seconds
    print(json.dumps({"closed_loop_images_per_s": base}), flush=True)
    for frac in args.fractions:
        rate = frac * base
        mix = {"loop": "open", "arrivals": "poisson", "rate_per_s": rate,
               "order_seed": args.seed}
        n0 = len(eng.wave_seconds)
        w = client.run(eng, mix, pool, args.seconds)
        lat = np.array([(r.t_done - r.t_sched) * 1e3 for r in w.requests])
        wait = np.array([(r.t_step - r.t_sched) * 1e3 for r in w.requests])
        third = max(1, len(wait) // 3)
        waves = eng.wave_seconds[n0:]
        print(json.dumps({
            "fraction": frac, "rate_per_s": rate,
            "requests": len(w.requests),
            "served_per_s": len(w.requests) / w.seconds,
            "latency_p50_ms": float(np.percentile(lat, 50)),
            "latency_p95_ms": float(np.percentile(lat, 95)),
            "wait_first_third_ms": float(wait[:third].mean()),
            "wait_last_third_ms": float(wait[-third:].mean()),
            "waves": len(waves),
            "mean_wave_images": len(w.requests) / max(1, len(waves)),
            "wave_ms_median": float(np.median(waves) * 1e3)}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
