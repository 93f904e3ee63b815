"""Plain float32 reference of a NeuLite stage of the paper's CNNs.

ResNet18 as He et al. (2016) build it for 32-pixel inputs: a 3x3 stem and
four stages of two basic residual units, with GroupNorm in place of
BatchNorm (the program's stated departure, which the configuration file
records).  NeuLite (arXiv 2408.10826, Fig. 4) splits the unit list into
blocks; stage t runs the units before its block frozen, trains the last unit
of the previous block with its own block, replaces each later block by one
3x3 stride-2 convolution with GroupNorm and ReLU (the surrogate), and adds a
three-layer projector on the block's pooled output for nHSIC(Y; Z).

The weight tree has the program's layout, so the benchmark can hand one set
of weights to both: ``{"model": {"units": [...], "head": ...},
"surrogates": [...], "projector": [...]}``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.common import gelu


def _scaled(cfg: dict, c: int) -> int:
    g = cfg["groups"]
    return max(g, int(c * cfg["width_mult"]) // g * g)


def units(cfg: dict):
    """[(kind, cin, cout, stride)] of the network's unit list."""
    if cfg["arch"] != "resnet18":
        raise ValueError(f"no reference for arch {cfg['arch']!r}")
    widths = [_scaled(cfg, c) for c in cfg["stage_widths"]]
    out = [("stem", cfg["in_channels"], widths[0], 1)]
    cin = widths[0]
    for s, (reps, cout) in enumerate(zip(cfg["blocks_per_stage"], widths)):
        for i in range(reps):
            out.append(("basic", cin, cout, 2 if (i == 0 and s > 0) else 1))
            cin = cout
    return out


def stage_ranges(cfg: dict, t: int):
    """(frozen, boundary, active) unit ranges of stage t."""
    start, end = cfg["stage_units"][t]
    nb = min(cfg["boundary_units"], start) if t > 0 else 0
    return (0, start - nb), (start - nb, start), (start, end)


# --------------------------------------------------------------------------- #
# weights
# --------------------------------------------------------------------------- #
def _conv(key, k, cin, cout):
    return {"w": jax.random.normal(key, (k, k, cin, cout))
            * np.sqrt(2.0 / (k * k * cin))}


def _gn(c):
    return {"scale": jnp.ones((c,)), "bias": jnp.zeros((c,))}


def _linear(key, cin, cout):
    return {"w": jax.random.normal(key, (cin, cout)) / np.sqrt(cin),
            "b": jnp.zeros((cout,))}


def init(cfg: dict, key):
    """He-normal convolutions, 1/sqrt(fan-in) linear layers, unit norms."""
    keys = iter(jax.random.split(key, 64))
    us = units(cfg)
    unit_params = []
    for kind, cin, cout, stride in us:
        if kind == "stem":
            unit_params.append({"conv": _conv(next(keys), 3, cin, cout),
                                "gn": _gn(cout)})
            continue
        p = {"conv1": _conv(next(keys), 3, cin, cout), "gn1": _gn(cout),
             "conv2": _conv(next(keys), 3, cout, cout), "gn2": _gn(cout)}
        if stride != 1 or cin != cout:
            p["proj"] = _conv(next(keys), 1, cin, cout)
        unit_params.append(p)
    bounds = cfg["stage_units"]
    block_out = [us[e - 1][2] for _, e in bounds]
    surrogates = [{"conv": _conv(next(keys), 3, cin, cout), "gn": _gn(cout)}
                  for cin, cout in zip(block_out[:-1], block_out[1:])]
    hid, out = cfg["projector"]["hidden"], cfg["projector"]["out"]
    projector = [{"w1": _linear(next(keys), c, hid),
                  "w2": _linear(next(keys), hid, hid),
                  "w3": _linear(next(keys), hid, out)} for c in block_out]
    return {"model": {"units": unit_params,
                      "head": _linear(next(keys), us[-1][2],
                                      cfg["num_classes"])},
            "surrogates": surrogates, "projector": projector}


def split(cfg: dict, params, t: int):
    (f0, f1), (b0, b1), (a0, a1) = stage_ranges(cfg, t)
    last = t == cfg["num_stages"] - 1
    us = params["model"]["units"]
    frozen = {"units": us[f0:f1]}
    trainable = {"units": us[b0:a1],
                 "surrogates": None if last else params["surrogates"][t:],
                 "projector": params["projector"][t],
                 "head": params["model"]["head"]}
    return frozen, trainable


def merge(cfg: dict, params, trainable, t: int):
    (_, _), (b0, _), (_, a1) = stage_ranges(cfg, t)
    us = list(params["model"]["units"])
    us[b0:a1] = trainable["units"]
    sur = list(params["surrogates"])
    if trainable["surrogates"] is not None:
        sur[t:] = trainable["surrogates"]
    proj = list(params["projector"])
    proj[t] = trainable["projector"]
    return {"model": {"units": us, "head": trainable["head"]},
            "surrogates": sur, "projector": proj}


# --------------------------------------------------------------------------- #
# forward
# --------------------------------------------------------------------------- #
def conv(p, x, stride):
    w = p["w"].astype(x.dtype)
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def group_norm(p, x, groups: int, eps: float = 1e-5):
    b, h, w, c = x.shape
    g = min(groups, c)
    while c % g:
        g -= 1
    xg = x.reshape(b, h, w, g, c // g)
    mean = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(xg - mean), axis=(1, 2, 4), keepdims=True)
    xg = (xg - mean) / jnp.sqrt(var + eps)
    return xg.reshape(b, h, w, c) * p["scale"] + p["bias"]


def unit(cfg, spec, p, x):
    kind, _, _, stride = spec
    g = cfg["groups"]
    if kind == "stem":
        return jax.nn.relu(group_norm(p["gn"], conv(p["conv"], x, stride), g))
    h = jax.nn.relu(group_norm(p["gn1"], conv(p["conv1"], x, stride), g))
    h = group_norm(p["gn2"], conv(p["conv2"], h, 1), g)
    short = conv(p["proj"], x, stride) if "proj" in p else x
    return jax.nn.relu(h + short)


def linear(p, x):
    return jnp.matmul(x, p["w"]) + p["b"]


def pool(x):
    return x if x.ndim == 2 else x.mean(axis=tuple(range(1, x.ndim - 1)))


def stage_forward(cfg: dict, t: int, frozen, trainable, images):
    """(logits, x_embed, z_active, z_proj) of stage t on one batch."""
    specs = units(cfg)
    (f0, f1), (b0, _), (_, a1) = stage_ranges(cfg, t)
    x = images
    for spec, p in zip(specs[f0:f1], frozen["units"]):
        x = unit(cfg, spec, jax.lax.stop_gradient(p), x)
    x = jax.lax.stop_gradient(x)
    x_embed = x
    for spec, p in zip(specs[b0:a1], trainable["units"]):
        x = unit(cfg, spec, p, x)
    z_active = x
    for p in trainable["surrogates"] or []:
        x = jax.nn.relu(group_norm(p["gn"], conv(p["conv"], x, 2),
                                   cfg["groups"]))
    logits = linear(trainable["head"], x.mean(axis=(1, 2)))
    pr = trainable["projector"]
    h = gelu(linear(pr["w1"], z_active.mean(axis=(1, 2))))
    h = gelu(linear(pr["w2"], h))
    return logits, x_embed, z_active, linear(pr["w3"], h)
