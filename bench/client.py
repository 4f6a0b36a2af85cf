"""The client: it reads a traffic mix from ``bench/traffic/<name>.json``
and drives the engine through a measured window on the host's clock.

Mixes (every request is one image of the configuration's size):

* ``{"loop": "closed"}``: a closed loop.  The client keeps one full
  wave (the engine's ``max_batch``) of images queued and sends a new
  one as each response returns, so every wave is full.  The window ends
  with the first wave that ends ``seconds`` or more after it began, so
  it holds whole waves.
* ``{"loop": "open", "arrivals": <kind>, ...}``: an open loop.
  Requests arrive at the times that ``times(mix, seconds)`` of
  ``bench/arrivals/<kind>.py`` gives; the rest of the mix is that
  file's parameters.  Latency runs from the scheduled arrival, so a
  late generator or a long wave counts.  After the last arrival the
  loop serves what is queued.

Each request's record holds its scheduled arrival (closed loop: its
submission), the start of the ``step()`` that carried it, and its
response time.  Host spans ``bench.submit``, ``bench.step`` and
``bench.wait_arrival`` go into the profiler's trace when one is taken.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import math
import os
import time

import numpy as np
from jax.profiler import TraceAnnotation

from repro.serve_conv import ConvRequest

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def arrival_times(mix: dict, seconds: float) -> np.ndarray:
    """The open-loop mix's arrival times, from the generator its
    ``arrivals`` names."""
    gen = importlib.import_module(f"bench.arrivals.{mix['arrivals']}")
    return gen.times(mix, seconds)


@dataclasses.dataclass
class Request:
    rid: int
    image: int              # index into the image pool
    t_sched: float          # scheduled arrival (closed loop: submission)
    t_step: float = math.nan   # start of the step() that carried it
    t_done: float = math.nan   # response received
    wave: int = -1
    out: np.ndarray | None = None
    status: str = "queued"


@dataclasses.dataclass
class Wave:
    t_start: float
    t_end: float
    images: int


@dataclasses.dataclass
class Window:
    t0: float
    t_end: float
    requests: list
    waves: list

    @property
    def seconds(self) -> float:
        return self.t_end - self.t0


def _submit(eng, req, pool):
    eng.submit(ConvRequest(req.rid, pool[req.image % len(pool)]))


def _step(eng, reqs_by_id, waves, clock):
    t = clock()
    with TraceAnnotation("bench.step"):
        served = eng.step()
    t_end = clock()
    for r in served:
        req = reqs_by_id[r.rid]
        req.t_step, req.t_done = t, t_end
        req.wave, req.out, req.status = len(waves), r.out, r.status
    waves.append(Wave(t, t_end, len(served)))
    return served


def run(eng, mix: dict, pool, seconds: float,
        clock=time.perf_counter) -> Window:
    """Drive ``eng`` with ``mix`` for ``seconds``; returns the window."""
    if mix["loop"] == "closed":
        return _closed(eng, pool, seconds, clock)
    if mix["loop"] == "open":
        return _open(eng, arrival_times(mix, seconds), pool, clock)
    raise ValueError(f"unknown traffic mix {mix}")


def _closed(eng, pool, seconds, clock) -> Window:
    reqs, waves, by_id = [], [], {}

    def send():
        req = Request(len(reqs), len(reqs), clock())
        reqs.append(req)
        by_id[req.rid] = req
        _submit(eng, req, pool)

    t0 = clock()
    with TraceAnnotation("bench.submit"):
        for _ in range(eng.max_batch):
            send()
    while True:
        served = _step(eng, by_id, waves, clock)
        if waves[-1].t_end - t0 >= seconds:
            break
        with TraceAnnotation("bench.submit"):
            for _ in served:
                send()
    return Window(t0, waves[-1].t_end, reqs, waves)


def _open(eng, arrivals, pool, clock) -> Window:
    reqs, waves, by_id = [], [], {}
    t0 = clock()
    i = 0
    while True:
        now = clock() - t0
        if i < len(arrivals) and arrivals[i] <= now:
            with TraceAnnotation("bench.submit"):
                while i < len(arrivals) and arrivals[i] <= now:
                    req = Request(i, i, t0 + arrivals[i])
                    reqs.append(req)
                    by_id[i] = req
                    _submit(eng, req, pool)
                    i += 1
        if eng.pending_images():
            _step(eng, by_id, waves, clock)
        elif i < len(arrivals):
            with TraceAnnotation("bench.wait_arrival"):
                time.sleep(max(0.0, t0 + arrivals[i] - clock()))
        else:
            break
    return Window(t0, waves[-1].t_end if waves else clock(), reqs, waves)
