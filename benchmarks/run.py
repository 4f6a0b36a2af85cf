"""Benchmark runner: one section per paper table/figure.

  gates      — MAC gate counts per cell library (paper Figs 7, 8b, 9b)
  macs       — MACs/s bitslice vs SoftFP word emulation (Figs 6, 8a, 9a)
  conv       — CNN convolution layer in HOBFLOPS (paper §3.4/§4)
  network    — multi-layer stack: bitslice-resident pipeline vs
               per-layer decode/re-encode (paper §3.4, DESIGN.md §8)
  serve      — lane-batched serving engine: wave throughput vs batch
               bucket vs the one-request-at-a-time loop (DESIGN.md §10)
  roofline   — assembled dry-run roofline table (§Roofline), if
               experiments/dryrun has been populated

Prints ``name,us_per_call,derived`` CSV blocks per section.  The
``gates`` and ``macs`` sections additionally write machine-readable
``BENCH_gates.json`` / ``BENCH_macs.json`` (gates/MAC per format +
library; MACs/s per format) so successive PRs have a perf trajectory:

    python benchmarks/run.py --quick --only macs,gates
"""
from __future__ import annotations

import argparse
import json
import os
import time

_JSON_SECTIONS = ("gates", "macs", "network", "serve")


def _write_json(out_dir: str, section: str, results) -> str:
    path = os.path.join(out_dir, f"BENCH_{section}.json")
    with open(path, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
        f.write("\n")
    return path


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small format subset (CI-speed)")
    ap.add_argument("--only", default=None,
                    help="comma list: gates,macs,conv,network,serve,roofline")
    ap.add_argument("--out-dir", default=".",
                    help="directory for BENCH_<section>.json files")
    args = ap.parse_args(argv)
    from repro.compile_cache import (enable_compile_cache,
                                      use_classic_cpu_emitters)
    use_classic_cpu_emitters()
    enable_compile_cache()
    only = set(args.only.split(",")) if args.only else None
    sections = [s for s in ("gates", "macs", "conv", "network", "serve",
                            "roofline")
                if only is None or s in only]

    for sec in sections:
        t0 = time.time()
        print(f"== {sec} ==", flush=True)
        try:
            if sec == "gates":
                from benchmarks import gates
                text, results = gates.run(quick=args.quick)
            elif sec == "macs":
                from benchmarks import macs
                text, results = macs.run(quick=args.quick)
            elif sec == "conv":
                from benchmarks import conv_layer
                text, results = conv_layer.run(quick=args.quick)
            elif sec == "network":
                from benchmarks import network
                text, results = network.run(quick=args.quick)
            elif sec == "serve":
                from benchmarks import serve
                text, results = serve.run(quick=args.quick)
            else:
                from benchmarks import roofline
                text, results = roofline.run(quick=args.quick)
            print(text, flush=True)
            if sec in _JSON_SECTIONS:
                path = _write_json(args.out_dir, sec, results)
                print(f"wrote {path}", flush=True)
        except Exception as e:  # keep the harness going
            print(f"SECTION-ERROR {sec}: {type(e).__name__}: {e}",
                  flush=True)
        print(f"== {sec} done in {time.time()-t0:.1f}s ==\n", flush=True)


if __name__ == "__main__":
    main()
