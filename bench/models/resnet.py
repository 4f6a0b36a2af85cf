"""ResNet conv trunks (He et al. 2016, arXiv:1512.03385), laid out from
a configuration file.

``build`` lays the nodes onto any object with the builder methods
``conv``, ``maxpool2d``, ``avgpool2d``, ``add`` and ``relu`` and an
``input_name``: the program's ``NetworkGraph``, the float32
``bench.reference.Reference`` and ``bench.counts.Shapes`` all take the
same calls, so the served graph, its reference and its counts cannot
drift apart.

Keys read from the configuration:

* ``stem``: ``kernel``, ``stride``, ``channels`` of the first conv
  (with ReLU), and ``pool``: a max-pool ``{"window", "stride"}`` with
  SAME padding, or null;
* ``stages``: output channels of each stage; ``blocks_per_stage`` basic
  blocks (two 3x3 convs) each; a stage after the first opens with a
  stride-2 block;
* ``shortcut``: ``"projection"``, a 1x1 conv where the channel count
  changes, which in these trunks is where the stride does (option B of
  the paper), and identity elsewhere;
* ``head``: ``{"avgpool": k}`` for a k x k global average pool, or an
  empty object for no pool.
"""
from __future__ import annotations


def kernel_shapes(cfg) -> dict:
    """(kh, kw, cin, cout) of every conv, by node name, in build order."""
    st = cfg["stem"]
    shapes = {"stem": (st["kernel"], st["kernel"], cfg["input_hwc"][2],
                       st["channels"])}
    cin = st["channels"]
    for s, c in enumerate(cfg["stages"]):
        for b in range(cfg["blocks_per_stage"]):
            pre = f"s{s + 1}b{b + 1}"
            shapes[f"{pre}c1"] = (3, 3, cin, c)
            shapes[f"{pre}c2"] = (3, 3, c, c)
            if cin != c:
                shapes[f"{pre}proj"] = (1, 1, cin, c)
            cin = c
    return shapes


def build(g, cfg, kernels) -> str:
    """Lay the trunk onto ``g``; returns the name of the output node."""
    if cfg["shortcut"] != "projection":
        raise ValueError(f"unknown shortcut {cfg['shortcut']!r}")
    st = cfg["stem"]
    x = g.conv("stem", g.input_name, kernels["stem"], stride=st["stride"],
               relu=True)
    if st.get("pool"):
        x = g.maxpool2d("pool", x, st["pool"]["window"],
                        stride=st["pool"]["stride"], padding="SAME")
    cin = st["channels"]
    for s, c in enumerate(cfg["stages"]):
        for b in range(cfg["blocks_per_stage"]):
            pre = f"s{s + 1}b{b + 1}"
            stride = 2 if (s > 0 and b == 0) else 1
            y = g.conv(f"{pre}c1", x, kernels[f"{pre}c1"], stride=stride,
                       relu=True)
            y = g.conv(f"{pre}c2", y, kernels[f"{pre}c2"])
            short = x
            if cin != c:
                short = g.conv(f"{pre}proj", x, kernels[f"{pre}proj"],
                               stride=stride)
            x = g.relu(f"{pre}out", g.add(f"{pre}add", y, short))
            cin = c
    k = cfg["head"].get("avgpool")
    if k:
        x = g.avgpool2d("avgpool", x, k)
    return x
