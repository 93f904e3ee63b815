"""Operations a NeuLite stage of the paper's ViT requires per sample.

Counted from shapes: every matrix product as two operations per
multiply-add, including attention's scores and its weighted sum.
Element-wise work (norms, softmax, GELU, rotary angles) is not counted.
The frozen prefix runs forward once.  Every trainable product runs forward,
a weight gradient and an input gradient, except where its input is a
constant: the patch embedding at stage 0, and the query, key and value
projections of the first trainable encoder otherwise.  The program
recomputes trainable encoders in the backward pass; recomputation is not
counted.  The nHSIC terms count their Gram products; the input features
train at stage 0, where the patch embedding does.
"""
from __future__ import annotations

from bench.reference.transformer import stage_ranges


def flops_per_sample(cfg: dict, t: int, batch_size: int) -> dict:
    d, ff, c = cfg["d_model"], cfg["d_ff"], cfg["num_classes"]
    n = (cfg["image_size"] // cfg["patch_size"]) ** 2
    p_dim = cfg["patch_size"] ** 2 * cfg["in_channels"]
    qkv = 3 * 2 * d * d * n
    rest = (2 * d * d + 2 * 2 * n * d + 2 * 2 * d * ff) * n
    (f0, f1), (b0, _), (_, a1) = stage_ranges(cfg, t)
    embed_f = 2 * p_dim * d * n
    embed = 2 * embed_f if t == 0 else embed_f
    prefix = (f1 - f0) * (qkv + rest)
    layers = a1 - b0
    train = 3 * layers * (qkv + rest) - (0 if t == 0 else qkv)
    last = cfg["num_stages"] - 1
    sur = 3 * (last - t) * 2 * 2 * d * d * n if t < last else 0
    head = 3 * 2 * d * c
    hid, out = cfg["projector"]["hidden"], cfg["projector"]["out"]
    proj = 3 * 2 * (d * hid + hid * hid + hid * out) * n
    b = batch_size
    gram = lambda dim, trains: (3 if trains else 1) * 2 * b * b * dim / b
    nhsic = (gram(d, t == 0) + gram(d, True) + gram(c, False)
             + gram(out, True))
    parts = {"embed": embed, "prefix_forward": prefix, "trainable": train,
             "surrogates": sur, "head": head, "projector": proj,
             "nhsic": nhsic}
    parts["total"] = sum(parts.values())
    return parts
