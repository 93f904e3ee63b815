"""Plain float32 reference of a NeuLite stage of the paper's ViT.

A Vision Transformer (Dosovitskiy et al., 2021) for classification:
non-overlapping patches, a linear patch embedding with a learned position
table, pre-norm encoder layers of multi-head self-attention and a GELU MLP,
a final norm, mean pooling over patches and a linear head.  The program
departs from the published ViT in ways the configuration file records:
RMSNorm for LayerNorm, rotary position angles on queries and keys on top of
the learned table, no biases in attention or MLP, and no class token.
NeuLite (arXiv 2408.10826, section on transformers) splits the 12 encoders
into blocks of 4; each later block is replaced by one residual
``x + W_o gelu(W rmsnorm(x))`` layer, and a three-layer projector maps the
block's tokens to 64 features for nHSIC(Y; Z).

The weight tree has the program's layout: encoder weights are stacked on a
leading layer axis (``model.layers.sub0``), surrogates on a leading block
axis.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.common import gelu


def stage_ranges(cfg: dict, t: int):
    start, end = cfg["stage_units"][t]
    nb = min(cfg["boundary_units"], start) if t > 0 else 0
    return (0, start - nb), (start - nb, start), (start, end)


def _normal(key, shape, fan_in):
    return jax.random.normal(key, shape) / np.sqrt(fan_in)


def init(cfg: dict, key):
    """1/sqrt(fan-in) projections (the attention projections' fan-in is
    d_model, and the output projection's heads x head_dim), unit norms,
    a 0.02-scaled position table, zero biases."""
    d, h, L = cfg["d_model"], cfg["num_heads"], cfg["num_layers"]
    dh, ff, c = d // h, cfg["d_ff"], cfg["num_classes"]
    p_dim = cfg["patch_size"] ** 2 * cfg["in_channels"]
    n_patch = (cfg["image_size"] // cfg["patch_size"]) ** 2
    n_sur = cfg["num_stages"] - 1
    hid, out = cfg["projector"]["hidden"], cfg["projector"]["out"]
    k = iter(jax.random.split(key, 32))
    ones = lambda *s: jnp.ones(s)
    layers = {"sub0": {
        "norm1": {"scale": ones(L, d)},
        "mixer": {"wq": _normal(next(k), (L, d, h, dh), d),
                  "wk": _normal(next(k), (L, d, h, dh), d),
                  "wv": _normal(next(k), (L, d, h, dh), d),
                  "wo": _normal(next(k), (L, h, dh, d), h * dh)},
        "norm2": {"scale": ones(L, d)},
        "ffn": {"w_up": _normal(next(k), (L, d, ff), d),
                "w_down": _normal(next(k), (L, ff, d), ff)}}}
    return {
        "model": {
            "embed": {"w": _normal(next(k), (p_dim, d), p_dim),
                      "b": jnp.zeros((d,)),
                      "pos": 0.02 * jax.random.normal(next(k),
                                                      (n_patch, d))},
            "layers": layers,
            "final_norm": {"scale": ones(d)},
            "head": {"w_out": _normal(next(k), (d, c), d)}},
        "surrogates": {"norm": {"scale": ones(n_sur, d)},
                       "w": _normal(next(k), (n_sur, d, d), d),
                       "wo": _normal(next(k), (n_sur, d, d), d)},
        "projector": {"w1": _normal(next(k), (d, hid), d),
                      "w2": _normal(next(k), (hid, hid), hid),
                      "w3": _normal(next(k), (hid, out), hid)}}


def _rows(tree, s, e):
    return jax.tree.map(lambda a: a[s:e], tree)


def split(cfg: dict, params, t: int):
    (f0, f1), (b0, _), (_, a1) = stage_ranges(cfg, t)
    m = params["model"]
    last = cfg["num_stages"] - 1
    frozen = {"prefix": _rows(m["layers"], f0, f1)}
    trainable = {"layers": _rows(m["layers"], b0, a1),
                 "surrogates": (None if t == last
                                else _rows(params["surrogates"], t, last)),
                 "projector": params["projector"],
                 "final_norm": m["final_norm"], "head": m["head"]}
    (trainable if t == 0 else frozen)["embed"] = m["embed"]
    return frozen, trainable


def merge(cfg: dict, params, trainable, t: int):
    (_, _), (b0, _), (_, a1) = stage_ranges(cfg, t)
    m = params["model"]
    put = lambda s0, s1: lambda full, part: jnp.asarray(full).at[s0:s1].set(
        part)
    layers = jax.tree.map(put(b0, a1), m["layers"], trainable["layers"])
    sur = params["surrogates"]
    if trainable["surrogates"] is not None:
        sur = jax.tree.map(put(t, None), sur, trainable["surrogates"])
    return {"model": {"embed": trainable.get("embed", m["embed"]),
                      "layers": layers,
                      "final_norm": trainable["final_norm"],
                      "head": trainable["head"]},
            "surrogates": sur, "projector": trainable["projector"]}


# --------------------------------------------------------------------------- #
# forward
# --------------------------------------------------------------------------- #
def rms_norm(scale, x, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    y = x32 / jnp.sqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
                       + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def rotate(x, theta: float):
    """Rotary angles (Su et al., 2021) on (B, S, H, D), position = patch
    index, the two halves of D rotated as pairs."""
    s, d = x.shape[1], x.shape[-1]
    freq = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float32) / d)
    ang = np.arange(s, dtype=np.float32)[:, None] * freq      # (S, D/2)
    cos = jnp.asarray(np.cos(ang))[None, :, None, :]
    sin = jnp.asarray(np.sin(ang))[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


def encoder(cfg: dict, p, x):
    h = rms_norm(p["norm1"]["scale"], x)
    mx = p["mixer"]
    proj = lambda w: jnp.einsum("bsd,dhe->bshe", h, w)
    q = rotate(proj(mx["wq"]), cfg["rope_theta"])
    k = rotate(proj(mx["wk"]), cfg["rope_theta"])
    v = proj(mx["wv"])
    scores = jnp.einsum("bqhe,bkhe->bhqk", q, k).astype(jnp.float32)
    probs = jax.nn.softmax(scores / np.sqrt(q.shape[-1]), axis=-1)
    att = jnp.einsum("bhqk,bkhe->bqhe", probs.astype(v.dtype), v)
    x = x + jnp.einsum("bshe,hed->bsd", att, mx["wo"])
    h = rms_norm(p["norm2"]["scale"], x)
    up = gelu(jnp.matmul(h, p["ffn"]["w_up"]))
    return x + jnp.matmul(up, p["ffn"]["w_down"])


def encoders(cfg: dict, stacked, x):
    n = jax.tree.leaves(stacked)[0].shape[0]
    for i in range(n):
        x = encoder(cfg, jax.tree.map(lambda a: a[i], stacked["sub0"]), x)
    return x


def patches(cfg: dict, images):
    b, hh, ww, c = images.shape
    p = cfg["patch_size"]
    x = images.reshape(b, hh // p, p, ww // p, p, c).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, (hh // p) * (ww // p), p * p * c)


def pool(x):
    return x if x.ndim == 2 else x.mean(axis=tuple(range(1, x.ndim - 1)))


def stage_forward(cfg: dict, t: int, frozen, trainable, images):
    """(logits, x_embed, z_active, z_proj) of stage t on one batch."""
    emb = trainable.get("embed", frozen.get("embed"))
    x = jnp.matmul(patches(cfg, images), emb["w"]) + emb["b"] + emb["pos"]
    if "embed" in frozen:
        x = jax.lax.stop_gradient(x)
    x_embed = x
    if jax.tree.leaves(frozen["prefix"])[0].shape[0]:
        x = encoders(cfg, jax.lax.stop_gradient(frozen["prefix"]), x)
        x = jax.lax.stop_gradient(x)
    x = encoders(cfg, trainable["layers"], x)
    z_active = x
    sur = trainable["surrogates"]
    if sur is not None:
        for i in range(sur["w"].shape[0]):
            h = rms_norm(sur["norm"]["scale"][i], x)
            x = x + jnp.matmul(gelu(jnp.matmul(h, sur["w"][i])),
                               sur["wo"][i])
    x = rms_norm(trainable["final_norm"]["scale"], x).mean(axis=1)
    logits = jnp.matmul(x, trainable["head"]["w_out"])
    pr = trainable["projector"]
    h = gelu(jnp.matmul(gelu(jnp.matmul(z_active, pr["w1"])), pr["w2"]))
    z_proj = jnp.matmul(h, pr["w3"])
    return logits, x_embed, z_active, z_proj
