"""The ``mac_tile_fill`` reader on the configurations' own conv shapes,
and on a program that has no tile plan."""
from __future__ import annotations

import json
import os
import types

import pytest

from bench import counts
from bench.models import resnet
from bench.run import reader

HERE = os.path.dirname(os.path.abspath(__file__))


def _run(name, waves):
    with open(os.path.join(HERE, "..", "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    window = types.SimpleNamespace(
        waves=[types.SimpleNamespace(images=n) for n in waves])
    return types.SimpleNamespace(cfg=cfg, model=resnet, window=window,
                                 buckets=(1, 2, 4))


@pytest.mark.parametrize("name,waves,lo,hi", [
    ("resnet18-imagenet-h9", [1, 1, 1], 60, 100),
    ("resnet20-cifar-h16", [4, 4, 3], 70, 100),
])
def test_fill_is_the_mac_weighted_tile_plan(name, waves, lo, hi):
    from repro.core.pallas_backend import tile_plan
    run = _run(name, waves)
    got = reader("mac_tile_fill")(run)
    macs = useful = 0
    for n in waves:
        for c in counts.convs(resnet, run.cfg, 4 if n > 2 else n):
            macs += c.macs
            useful += c.macs * tile_plan(c.p, -(-c.cout // 32)).fill
    assert got == pytest.approx(100 * useful / macs)
    assert lo <= got <= hi


def test_fill_is_left_out_without_a_tile_plan(monkeypatch):
    import repro.core.pallas_backend as pb
    monkeypatch.delattr(pb, "tile_plan")
    assert reader("mac_tile_fill")(_run("resnet20-cifar-h16", [4])) is None
