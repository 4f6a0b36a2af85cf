#!/usr/bin/env python3
"""Readings that the limit of ``correct`` is set from, in one process.

For one cell, serves the cell's own traffic for a short window once per
seed and prints the number ``correct`` compares (``bench/check.py``):
first for the program as the configuration states it, then for the
control, the program's own path one format below (``--control``, e.g.
hobflops8 for hobflops9).  Each side builds its graph and compiles its
wave programs once; a seed brings its own weights, images and sample,
served through a fresh engine: the seed's encoded weights replace the
graph's (they are arguments of its compiled wave program, not part of
it), so no seed traces or compiles again.

    python bench/readings.py --workload <cell> --seeds 1-12 \\
        --control hobflops8 --control-seeds 101-103 --seconds 5
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import run  # noqa: E402  (bench/run.py: sets the import paths)


def seeds(text: str) -> list[int]:
    out = []
    for part in text.split(","):
        a, _, b = part.partition("-")
        out += list(range(int(a), int(b or a) + 1))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, required=True)
    ap.add_argument("--control", required=True)
    ap.add_argument("--control-seeds", type=seeds, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args()
    import jax
    jax.config.update("jax_compilation_cache_dir", run.CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench import check, client, system
    from repro.core.fpformat import HOBFLOPS_FORMATS
    spec = run.load_json(run.ROOT, "BENCHMARK.json")
    cell = next(w for w in spec["workloads"] if w["name"] == args.workload)
    cfg = run.load_json(run.HERE, "configs", f"{cell['config']}.json")
    mix = client.load(cell["traffic"])
    model = importlib.import_module(f"bench.models.{cfg['family']}")
    shapes = tuple(model.kernel_shapes(cfg).items())
    out = {"program": {}, "control": {}}
    for side, fmt, ss in (("program", cfg["format"], args.seeds),
                          ("control", args.control, args.control_seeds)):
        graph, _ = system.build(model, cfg, ss[0], fmt_name=fmt)
        for s in ss:
            t = time.perf_counter()
            kernels, enc = system.weights(
                system.weight_key(s), shapes=shapes,
                fmt=HOBFLOPS_FORMATS[fmt])
            kernels = jax.device_get(kernels)
            graph._live_weights = enc
            eng = system.engine(graph, cfg)
            pool = system.images(cfg, s, cfg["image_pool"])
            system.warm(eng, pool, eng.buckets if mix["loop"] == "open"
                        else (eng.max_batch,))
            w = client.run(eng, mix, pool, args.seconds)
            reqs = check.sample(w.requests, s, cfg["check"]["sample"])
            gap = check.max_rel_gap(model, cfg, kernels, pool, reqs)
            out[side][s] = gap
            print(f"{side} {fmt} seed {s}: max_rel_gap {gap} over "
                  f"{len(reqs)} of {len(w.requests)} requests "
                  f"({time.perf_counter() - t:.1f}s)", file=sys.stderr,
                  flush=True)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
