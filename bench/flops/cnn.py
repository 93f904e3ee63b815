"""Operations a NeuLite stage of the paper's CNNs requires per sample.

Counted from shapes: every convolution and matrix product as two
operations per multiply-add.  Element-wise work (GroupNorm, ReLU, pooling,
the Gram's exponent) is not counted.  The frozen prefix runs forward once;
every trainable layer runs forward, a weight gradient and an input gradient,
except that the first trainable unit's input is a constant, so its
input-side convolutions need no input gradient.  The nHSIC terms count
their Gram products, forward, and backward where the features train.
"""
from __future__ import annotations

import math

from bench.reference.cnn import stage_ranges, units


def _conv(h: int, k: int, cin: int, cout: int, stride: int):
    """(operations, output side) of a SAME convolution on an h x h map."""
    ho = math.ceil(h / stride)
    return 2 * ho * ho * k * k * cin * cout, ho


def _unit(spec, h: int):
    """(list of (operations, input-side conv?), output side) of one unit."""
    kind, cin, cout, stride = spec
    f1, ho = _conv(h, 3, cin, cout, stride)
    if kind == "stem":
        return [(f1, True)], ho
    f2, _ = _conv(ho, 3, cout, cout, 1)
    convs = [(f1, True), (f2, False)]
    if stride != 1 or cin != cout:
        convs.append((_conv(h, 1, cin, cout, stride)[0], True))
    return convs, ho


def flops_per_sample(cfg: dict, t: int, batch_size: int) -> dict:
    specs = units(cfg)
    (f0, f1), (b0, _), (_, a1) = stage_ranges(cfg, t)
    h = cfg["image_size"]
    prefix = 0
    for spec in specs[f0:f1]:
        convs, h = _unit(spec, h)
        prefix += sum(f for f, _ in convs)
    c_embed = specs[f1 - 1][2] if f1 > 0 else cfg["in_channels"]
    train = 0
    for i, spec in enumerate(specs[b0:a1]):
        convs, h = _unit(spec, h)
        for f, input_side in convs:
            train += f * (2 if (i == 0 and input_side) else 3)
    c_z = specs[a1 - 1][2]
    sur = 0
    last = cfg["num_stages"] - 1
    block_out = [specs[e - 1][2] for _, e in cfg["stage_units"]]
    c_out = c_z
    if t < last:
        for cin, cout in zip(block_out[t:-1], block_out[t + 1:]):
            f, h = _conv(h, 3, cin, cout, 2)
            sur += 3 * f
            c_out = cout
    head = 3 * 2 * c_out * cfg["num_classes"]
    hid, out = cfg["projector"]["hidden"], cfg["projector"]["out"]
    proj = 3 * 2 * (c_z * hid + hid * hid + hid * out)
    b = batch_size
    gram = lambda d, trains: (3 if trains else 1) * 2 * b * b * d / b
    nhsic = (gram(c_embed, False) + gram(c_z, True)
             + gram(cfg["num_classes"], False) + gram(out, True))
    parts = {"prefix_forward": prefix, "trainable": train,
             "surrogates": sur, "head": head, "projector": proj,
             "nhsic": nhsic}
    parts["total"] = sum(parts.values())
    return parts
