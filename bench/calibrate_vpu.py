#!/usr/bin/env python3
"""Measure the chip's int32 bitwise rate on the vector unit.

A Pallas kernel runs independent loop-carried chains of bitwise
operations on full (8, 128) int32 tiles held in registers: each step of
chain ``c`` is ``s[c] = (s[c] ^ s[c+1]) | (s[c] & s[c+2])``, three
operations on 1,024 words.  The sweep varies the number of chains and
the unrolling (steps per loop iteration); the best rate is the peak that ``bench/peaks.json``
records as ``vpu_int32_ops_per_s`` and ``mac_roofline`` divides by.
Each timing spans at least a quarter of a second of device work, ends
in ``block_until_ready``, and is the median of five.

    python bench/calibrate_vpu.py        # on the chip; prints one JSON line
"""
from __future__ import annotations

import functools
import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

OPS_PER_STEP = 3


def _kernel(x_ref, o_ref, *, chains, iters, unroll):
    s0 = tuple(x_ref[0, c] for c in range(chains))

    def body(_, s):
        for _ in range(unroll):
            s = tuple((s[c] ^ s[(c + 1) % chains])
                      | (s[c] & s[(c + 2) % chains])
                      for c in range(chains))
        return s

    s = jax.lax.fori_loop(0, iters // unroll, body, s0)
    for c in range(chains):
        o_ref[0, c] = s[c]


@functools.partial(jax.jit, static_argnames=("chains", "iters", "unroll"))
def chains_call(x, *, chains, iters, unroll):
    grid = x.shape[0]
    spec = pl.BlockSpec((1, chains, 8, 128), lambda i: (i, 0, 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, chains=chains, iters=iters,
                          unroll=unroll),
        grid=(grid,), in_specs=[spec], out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.int32))(x)


def rate(chains: int, unroll: int, iters: int = 4096,
         grid: int = 64) -> float:
    x = jax.random.randint(jax.random.key(chains), (grid, chains, 8, 128),
                           -2 ** 31, 2 ** 31 - 1, jnp.int32)
    f = functools.partial(chains_call, chains=chains, iters=iters,
                          unroll=unroll)
    jax.block_until_ready(f(x))
    reps = 1
    while True:
        t = time.perf_counter()
        for _ in range(reps):
            y = f(x)
        jax.block_until_ready(y)
        if time.perf_counter() - t >= 0.25:
            break
        reps *= 2
    times = []
    for _ in range(5):
        t = time.perf_counter()
        for _ in range(reps):
            y = f(x)
        jax.block_until_ready(y)
        times.append((time.perf_counter() - t) / reps)
    ops = grid * iters * chains * OPS_PER_STEP * 8 * 128
    return ops / statistics.median(times)


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX found {dev.platform!r}", file=sys.stderr)
        return 1
    sweep = {}
    for chains in (1, 2, 4, 8, 16, 32):
        for unroll in (1, 4, 16):
            sweep[f"chains{chains}_unroll{unroll}"] = r = rate(chains, unroll)
            print(f"chains {chains} unroll {unroll}: {r:.4e} ops/s",
                  file=sys.stderr, flush=True)
    best = max(sweep, key=sweep.get)
    print(json.dumps({"device_kind": dev.device_kind, "best": best,
                      "vpu_int32_ops_per_s": sweep[best], "sweep": sweep}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
