"""The system under test, as the benchmark drives it.

Builds one configuration's graph through the program's normal path
(``NetworkGraph`` -> ``ConvServeEngine``) with weights made on the
device from the seed, and counts JAX's compilations so that the harness
can refuse a run that compiles inside its measured window.
"""
from __future__ import annotations

import functools
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.fpformat import HOBFLOPS_FORMATS
from repro.kernels.conv2d_bitslice.network import NetworkGraph
from repro.kernels.conv2d_bitslice.ops import encode_conv_weights
from repro.serve_conv import ConvRequest, ConvServeEngine


def weight_key(seed: int):
    """A PRNG key for any non-negative whole number, 64-bit ones too."""
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


@functools.partial(jax.jit, static_argnames=("shapes", "fmt"))
def weights(key, *, shapes, fmt):
    """He-normal float32 kernels and their served bit planes, in one
    call: ``shapes`` is a tuple of (name, (kh, kw, cin, cout))."""
    f32, enc = {}, {}
    for (name, shp), k in zip(shapes, jax.random.split(key, len(shapes))):
        kh, kw, cin, _ = shp
        w = jax.random.normal(k, shp, jnp.float32) \
            * jnp.sqrt(2.0 / (kh * kw * cin))
        f32[name] = w
        enc[name] = encode_conv_weights(w, fmt)
    return f32, enc


def build(model, cfg, seed: int, fmt_name: str | None = None):
    """(frozen graph, float32 kernels on the host) of ``cfg`` for
    ``seed``.  ``fmt_name`` overrides the configuration's format."""
    fmt = HOBFLOPS_FORMATS[fmt_name or cfg["format"]]
    shapes = tuple(model.kernel_shapes(cfg).items())
    f32, enc = weights(weight_key(seed), shapes=shapes, fmt=fmt)
    g = NetworkGraph(fmt, backend=cfg["backend"])
    return g.output(model.build(g, cfg, enc)), jax.device_get(f32)


def engine(graph, cfg) -> ConvServeEngine:
    """The engine as the configuration serves: its derived batch and
    buckets, the default policy, no degraded variant."""
    return ConvServeEngine(graph, tuple(cfg["input_hwc"]))


def warm(eng, pool, buckets):
    """One full wave of each bucket, through the engine's own path."""
    for b in buckets:
        for i in range(b):
            eng.submit(ConvRequest(-1 - i, pool[i % len(pool)]))
        eng.step()


def images(cfg, seed: int, n: int) -> np.ndarray:
    """``n`` distinct standard-normal request images for ``seed``."""
    rng = np.random.default_rng([seed, 1])
    return rng.standard_normal((n,) + tuple(cfg["input_hwc"]),
                               np.float32)


def health(eng) -> tuple:
    """Counters that a sound window leaves unchanged: retries, failed
    executions, failed waves, expired, rejected and shed requests, and
    steps down the precision ladder."""
    return (eng.executor.retries, eng.executor.failures, eng.waves_failed,
            eng.requests_expired, eng.requests_rejected,
            eng.requests_shed, eng.controller.activations)


class CompileClock:
    """Seconds JAX spends compiling (or fetching from the persistent
    cache), how many programs it compiled, how many the persistent
    cache answered, and how many traces it made."""

    def __init__(self):
        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        self.traces = 0
        jax.monitoring.register_event_duration_secs_listener(self._took)
        jax.monitoring.register_event_listener(self._event)

    def _took(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds
            self.programs += 1
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple:
        return self.programs, self.cache_hits, self.traces


class GcPauses:
    """The interpreter's full (generation 2) garbage collections while
    it is on: how many, and the longest in ms.  The traced wave
    programs keep millions of objects alive, so one full collection can
    hold the host for a second."""

    def __init__(self):
        self.count, self.longest_ms, self._t = 0, 0.0, None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.count += 1
            self.longest_ms = max(self.longest_ms,
                                  (time.perf_counter() - self._t) * 1e3)
            self._t = None

    def stop(self):
        gc.callbacks.remove(self._on)
