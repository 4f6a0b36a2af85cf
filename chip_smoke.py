#!/usr/bin/env python3
"""On-chip smoke test: serve a ResNet-18 conv trunk on a TPU.

The main path, once, at the published widths of one model:

    NetworkGraph(hobflops9, backend="pallas_fused")
      -> ConvServeEngine
      -> Mosaic-compiled fused_mac_pallas, one kernel per conv

The model is the convolutional trunk of ResNet-18 (He et al. 2016,
arXiv:1512.03385, Table 1, 18-layer column) on a 224x224x3 input: the
7x7/2 stem, a 3x3/2 max-pool and four stages of two basic blocks at
64/128/256/512 channels (20 convolutions, 1,813,561,344 MACs per
image).  Its 7x7 global average pool and 1000-way FC are left out.
Weights are seeded He-normal; nothing is downloaded.

Checks, each of which raises (exit code 1) when it fails:

* 8 single-image requests are all served, with no retry, failure,
  quarantine or expiry in the engine;
* the compiled wave program holds one ``tpu_custom_call`` per conv;
* every served output equals, bit for bit, the same image through the
  same graph on the XLA-only ``jnp`` backend, and one image also
  through the gate-interpreter Pallas backend (``pallas``);
* one image is within ``F32_REL_BOUND`` of a plain float32 reference.

``--chips 4`` runs only the wave-sharded path instead: one engine over
a 4-device ``wave`` mesh, checked bit for bit against the one-device
runner.  The script exits non-zero without printing a result when JAX
finds no TPU.  The last line of its output is one JSON object naming
the device.

Run from the repo root:  python chip_smoke.py [--chips 4]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core.fpformat import HOBFLOPS_FORMATS  # noqa: E402
from repro.kernels.conv2d_bitslice.network import NetworkGraph  # noqa: E402
from repro.kernels.conv2d_bitslice.ref import conv2d_f32  # noqa: E402
from repro.serve_conv import (ConvRequest, ConvServeEngine,  # noqa: E402
                              wave_mesh)

HWC = (224, 224, 3)
N_REQUESTS = 8
STAGES = (64, 128, 256, 512)
SEED = 0

# Bound on the max relative error max|y - y_f32| / max|y_f32| of one
# image through the whole trunk.  hobflops9 keeps 3 fraction bits
# (unit roundoff 2^-4) and accumulates each conv's K = kh*kw*cin
# products in a 4-fraction-bit accumulator, so one 3x3 conv at K=576 is
# already off by 0.147 in L2 norm (float32 reference on CPU).  The main
# path is 17 convs deep, and independent per-layer errors compound to
# about sqrt(17) * 0.147 = 0.61.  Measured on CPU with backend="jnp"
# (bit-identical to the chip path), seeds 0-2: 0.29-0.39 at 32x32,
# 0.40-0.54 at 64x64 and 0.62-0.69 at 224x224; the error grows with
# resolution because the deep 3x3 convs then see fewer padding taps.
# The bound sits 25% above the largest of those.  An output that is
# all zero, or of the wrong sign, is off by 1 or more.
F32_REL_BOUND = 0.85


def check(ok: bool, what: str):
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def he_normal(rng, kh, kw, cin, cout):
    std = np.sqrt(2.0 / (kh * kw * cin))
    return (rng.standard_normal((kh, kw, cin, cout)) * std).astype(
        np.float32)


def trunk_kernels(seed: int = SEED) -> dict:
    """Seeded He-normal kernels of the ResNet-18 trunk, by conv name."""
    rng = np.random.default_rng(seed)
    ks = {"stem": he_normal(rng, 7, 7, 3, STAGES[0])}
    cin = STAGES[0]
    for s, c in enumerate(STAGES):
        for b in range(2):
            pre = f"s{s + 1}b{b + 1}"
            ks[f"{pre}c1"] = he_normal(rng, 3, 3, cin, c)
            ks[f"{pre}c2"] = he_normal(rng, 3, 3, c, c)
            if cin != c:
                ks[f"{pre}proj"] = he_normal(rng, 1, 1, cin, c)
            cin = c
    return ks


def build_trunk(g, ks: dict) -> str:
    """Lay the trunk onto ``g``: a :class:`NetworkGraph` or the float32
    :class:`F32Trunk`, which share the builder methods used here.
    Returns the name of the output node."""
    x = g.conv("stem", g.input_name, ks["stem"], stride=2, relu=True)
    x = g.maxpool2d("pool", x, 3, stride=2, padding="SAME")
    cin = STAGES[0]
    for s, c in enumerate(STAGES):
        for b in range(2):
            pre = f"s{s + 1}b{b + 1}"
            stride = 2 if (s > 0 and b == 0) else 1
            y = g.conv(f"{pre}c1", x, ks[f"{pre}c1"], stride=stride,
                       relu=True)
            y = g.conv(f"{pre}c2", y, ks[f"{pre}c2"])
            short = x
            if cin != c:
                short = g.conv(f"{pre}proj", x, ks[f"{pre}proj"],
                               stride=stride)
            x = g.relu(f"{pre}out", g.add(f"{pre}add", y, short))
            cin = c
    return x


class F32Trunk:
    """Plain float32 NHWC ``jax.numpy`` execution of the builder calls
    in :func:`build_trunk`: ``conv2d_f32`` at full f32 precision, and
    max-pool, add and ReLU in f32."""

    input_name = "input"

    def __init__(self, images):
        self.v = {self.input_name: jnp.asarray(images, jnp.float32)}

    def _put(self, name, val):
        self.v[name] = val
        return name

    def conv(self, name, src, kernels, *, stride=1, relu=False):
        y = jnp.asarray(conv2d_f32(self.v[src], kernels, stride))
        return self._put(name, jnp.maximum(y, 0.0) if relu else y)

    def maxpool2d(self, name, src, window, *, stride, padding):
        y = jax.lax.reduce_window(self.v[src], -jnp.inf, jax.lax.max,
                                  (1, window, window, 1),
                                  (1, stride, stride, 1), padding)
        return self._put(name, y)

    def add(self, name, a, b):
        return self._put(name, self.v[a] + self.v[b])

    def relu(self, name, src):
        return self._put(name, jnp.maximum(self.v[src], 0.0))


def trunk_graph(ks: dict, backend: str):
    g = NetworkGraph(HOBFLOPS_FORMATS["hobflops9"], backend=backend)
    return g.output(build_trunk(g, ks))


class CompileClock:
    """Seconds JAX spends compiling (or fetching from the persistent
    cache) and how many programs the persistent cache answered."""

    def __init__(self):
        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._took)
        jax.monitoring.register_event_listener(self._event)

    def _took(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds
            self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def request_images():
    rng = np.random.default_rng(SEED + 1)
    return rng.standard_normal((N_REQUESTS,) + HWC).astype(np.float32)


def count_kernels(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def one_chip() -> None:
    ks = trunk_kernels()
    n_convs = len(ks)
    images = request_images()
    g = trunk_graph(ks, "pallas_fused")
    print(f"trunk: {n_convs} convs, {g.macs((1,) + HWC):,} MACs/image",
          flush=True)
    eng = ConvServeEngine(g, HWC)
    check(eng.buckets == (1,), f"buckets {eng.buckets}")

    # Compile the wave program once, before serving, so the engine's
    # wave times exclude compilation (its first call reuses this).
    t0 = time.perf_counter()
    compiled = g._resident_fn.lower(images[:1], g._live_weights).compile()
    n_kern = count_kernels(compiled)
    print(f"compile: wave program in {time.perf_counter() - t0:.1f}s, "
          f"{n_kern} tpu_custom_call", flush=True)
    check(n_kern == n_convs,
          f"{n_kern} tpu_custom_call in the wave program, want {n_convs}")

    for i in range(N_REQUESTS):
        eng.submit(ConvRequest(i, images[i]))
    done = eng.run()
    if eng.quarantined:
        raise RuntimeError("a wave failed on the chip") \
            from eng.quarantined[0].error
    st = eng.stats()
    print("smoke figure, not a benchmark: "
          f"waves={st['waves']} images/s={st['images_per_s']} "
          f"mean_wave_s={st['mean_wave_s']} "
          f"p50_ms={st['p50_latency_ms']} p99_ms={st['p99_latency_ms']}",
          flush=True)
    check(len(done) == N_REQUESTS
          and all(r.status == "served" for r in done),
          f"{len(done)} of {N_REQUESTS} served")
    check(not eng.quarantined and not eng.expired,
          f"{len(eng.quarantined)} quarantined, {len(eng.expired)} expired")
    check(eng.executor.failures == 0 and eng.executor.retries == 0,
          f"{eng.executor.failures} failures, {eng.executor.retries} "
          f"retries")

    t0 = time.perf_counter()
    g_jnp = trunk_graph(ks, "jnp")
    for r in done:
        want = np.asarray(g_jnp.run(images[r.rid:r.rid + 1]))[0]
        check(np.array_equal(np.asarray(r.out), want),
              f"request {r.rid}: pallas_fused != jnp backend")
    print(f"bit-exact vs backend=jnp: {len(done)} of {len(done)} "
          f"({time.perf_counter() - t0:.1f}s incl. compile)", flush=True)

    t0 = time.perf_counter()
    first = done[0]
    g_pl = trunk_graph(ks, "pallas")
    want = np.asarray(g_pl.run(images[first.rid:first.rid + 1]))[0]
    check(np.array_equal(np.asarray(first.out), want),
          f"request {first.rid}: pallas_fused != pallas backend")
    print(f"bit-exact vs backend=pallas: request {first.rid} "
          f"({time.perf_counter() - t0:.1f}s incl. compile)", flush=True)

    ref = F32Trunk(images[first.rid:first.rid + 1])
    y32 = np.asarray(ref.v[build_trunk(ref, ks)])[0]
    err = float(np.abs(np.asarray(first.out) - y32).max()
                / np.abs(y32).max())
    print(f"f32 reference: max relative error {err} "
          f"(bound {F32_REL_BOUND})", flush=True)
    check(np.isfinite(err) and err < F32_REL_BOUND,
          f"relative error {err} >= {F32_REL_BOUND}")


def four_chips(devs) -> None:
    check(len(devs) == 4, f"--chips 4 needs 4 devices, JAX sees "
          f"{len(devs)}")
    images = request_images()
    g = trunk_graph(trunk_kernels(), "pallas_fused")
    eng = ConvServeEngine(g, HWC, mesh=wave_mesh(4))
    print(f"wave mesh: max_batch {eng.max_batch}, buckets {eng.buckets}",
          flush=True)
    for i in range(N_REQUESTS):
        eng.submit(ConvRequest(i, images[i]))
    t0 = time.perf_counter()
    done = eng.run()
    if eng.quarantined:
        raise RuntimeError("a sharded wave failed on the chips") \
            from eng.quarantined[0].error
    st = eng.stats()
    print(f"served {len(done)} in {time.perf_counter() - t0:.1f}s incl. "
          f"compile; smoke figure, not a benchmark: waves={st['waves']} "
          f"images/s={st['images_per_s']} "
          f"mean_wave_s={st['mean_wave_s']}", flush=True)
    check(len(done) == N_REQUESTS
          and all(r.status == "served" for r in done),
          f"{len(done)} of {N_REQUESTS} served")
    check(not eng.expired and eng.executor.failures == 0
          and eng.executor.retries == 0,
          f"{len(eng.expired)} expired, {eng.executor.failures} "
          f"failures, {eng.executor.retries} retries")
    check(st["waves"] == -(-N_REQUESTS // eng.max_batch),
          f"{st['waves']} waves for {N_REQUESTS} requests")

    for r in done:
        want = np.asarray(g.run(images[r.rid:r.rid + 1]))[0]
        check(np.array_equal(np.asarray(r.out), want),
              f"request {r.rid}: 4-device wave != one-device runner")
    print(f"bit-exact vs the one-device runner: {len(done)} of "
          f"{len(done)}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 4-device wave-sharded path")
    args = ap.parse_args(argv)

    devs = jax.devices()
    print(f"devices: {devs} kind={devs[0].device_kind!r}", flush=True)
    if devs[0].platform != "tpu":
        print(f"no TPU: JAX found platform {devs[0].platform!r}",
              file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    clock, t0 = CompileClock(), time.perf_counter()
    if args.chips == 4:
        four_chips(devs)
    else:
        one_chip()
    print(f"wall {time.perf_counter() - t0:.1f}s; compile {clock.seconds:.1f}s "
          f"over {clock.programs} programs, {clock.cache_hits} from the "
          f"persistent cache", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
