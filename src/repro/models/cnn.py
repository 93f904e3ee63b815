"""Paper-faithful CNN zoo: ResNet18/34, VGG11, SqueezeNet (CIFAR-scale).

These are the models the paper evaluates (Tables 1-2, Figs 5-8).  Each model
is a flat list of *units* (stem / residual blocks / fire modules); NeuLite
partitions the unit list into T blocks and trains them progressively.

Normalization is GroupNorm rather than BatchNorm: running-statistic BN is
known to interact badly with FedAvg under non-IID data, and GN is the
standard substitution in FL systems work (documented deviation, DESIGN.md).

Interface mirrors ``repro.models.model``:
  ``cnn_defs(cfg)``                     -> {"units": [unit ParamDef trees],
                                            "head": ..., "surrogates": ...,
                                            "projector": ...}
  ``cnn_forward(params, cfg, images)``  -> (B, num_classes) logits
  ``cnn_stage_apply(frozen, trainable, cfg, inputs)`` -> (logits, feats)
"""
from __future__ import annotations

import dataclasses
from typing import List, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.common.paramdef import ParamDef


@dataclasses.dataclass(frozen=True)
class CNNConfig:
    name: str
    arch: str                      # resnet18 | resnet34 | vgg11 | squeezenet
    num_classes: int = 10
    width_mult: float = 1.0        # AllSmall / HeteroFL width scaling
    in_channels: int = 3
    image_size: int = 32
    groups: int = 8                # GroupNorm groups
    conv_impl: str = "auto"        # lax | im2col | auto (see resolve_conv_impl)

    def scaled(self, c: int) -> int:
        return max(self.groups, int(c * self.width_mult) // self.groups
                   * self.groups)


# --------------------------------------------------------------------------- #
# primitive layers
# --------------------------------------------------------------------------- #
def conv_defs(cin: int, cout: int, k: int = 3) -> dict:
    return {"w": ParamDef((k, k, cin, cout), jnp.float32,
                          P(None, None, None, "model"),
                          scale=(2.0 / (k * k * cin)) ** 0.5)}


def resolve_conv_impl(impl: str) -> str:
    """Map ``conv_impl="auto"`` to a concrete implementation per backend.

    ``fl_round_throughput`` measures the crossover: under ``vmap`` over
    per-cohort weights ``lax.conv`` lowers to a grouped convolution (feature
    group per cohort) that CPU XLA executes serially, while the im2col/einsum
    form lowers to one batched matmul; on TPU/GPU the native conv is the
    fast path.  Hence: im2col on CPU, lax elsewhere."""
    if impl == "auto":
        return "im2col" if jax.default_backend() == "cpu" else "lax"
    return impl


def _conv_im2col(params, x, stride: int = 1):
    """SAME conv as patch-extraction + einsum (matmul-shaped, vmap-friendly).

    Identical math to ``lax.conv_general_dilated`` — k² strided slices of the
    SAME-padded input concatenated to (B, OH, OW, k²·Cin), contracted with
    the (k²·Cin, Cout) reshaped weight.  Patches are built with plain slices
    (not ``conv_general_dilated_patches``, which lowers back to a conv)."""
    w = params["w"]
    k, _, cin, cout = w.shape
    B, H, W, _ = x.shape
    if k == 1 and stride == 1:
        return jnp.einsum("bhwc,co->bhwo", x, w[0, 0])
    oh = -(-H // stride)
    ow = -(-W // stride)
    ph = max((oh - 1) * stride + k - H, 0)
    pw = max((ow - 1) * stride + k - W, 0)
    xp = jnp.pad(x, ((0, 0), (ph // 2, ph - ph // 2),
                     (pw // 2, pw - pw // 2), (0, 0)))
    patches = [xp[:, di:di + (oh - 1) * stride + 1:stride,
                  dj:dj + (ow - 1) * stride + 1:stride, :]
               for di in range(k) for dj in range(k)]
    cols = jnp.concatenate(patches, axis=-1)        # (B, OH, OW, k²·Cin)
    return jnp.einsum("bhwp,po->bhwo", cols, w.reshape(k * k * cin, cout))


def conv(params, x, stride: int = 1, impl: str = "lax"):
    if impl == "im2col":
        return _conv_im2col(params, x, stride)
    return jax.lax.conv_general_dilated(
        x, params["w"], (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def gn_defs(c: int) -> dict:
    return {"scale": ParamDef((c,), jnp.float32, P(None), init="ones"),
            "bias": ParamDef((c,), jnp.float32, P(None), init="zeros")}


def groupnorm(params, x, groups: int = 8, eps: float = 1e-5):
    B, H, W, C = x.shape
    g = min(groups, C)
    while C % g:
        g -= 1
    xg = x.reshape(B, H, W, g, C // g)
    mean = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = xg.var(axis=(1, 2, 4), keepdims=True)
    xg = (xg - mean) * jax.lax.rsqrt(var + eps)
    return xg.reshape(B, H, W, C) * params["scale"] + params["bias"]


def linear_defs(cin: int, cout: int) -> dict:
    return {"w": ParamDef((cin, cout), jnp.float32, P(None, "model")),
            "b": ParamDef((cout,), jnp.float32, P("model"), init="zeros")}


def linear(params, x):
    return x @ params["w"] + params["b"]


# --------------------------------------------------------------------------- #
# units
# --------------------------------------------------------------------------- #
# A unit is (kind, meta, defs).  meta carries static info (stride, cin, cout).
def _stem_unit(cfg, cout):
    return ("stem", {"cin": cfg.in_channels, "cout": cout, "stride": 1},
            {"conv": conv_defs(cfg.in_channels, cout), "gn": gn_defs(cout)})


def _basic_unit(cfg, cin, cout, stride):
    d = {"conv1": conv_defs(cin, cout), "gn1": gn_defs(cout),
         "conv2": conv_defs(cout, cout), "gn2": gn_defs(cout)}
    if stride != 1 or cin != cout:
        d["proj"] = conv_defs(cin, cout, k=1)
    return ("basic", {"cin": cin, "cout": cout, "stride": stride}, d)


def _vgg_unit(cfg, cin, cout, pool):
    return ("vgg", {"cin": cin, "cout": cout, "stride": 2 if pool else 1},
            {"conv": conv_defs(cin, cout), "gn": gn_defs(cout)})


def _fire_unit(cfg, cin, squeeze, expand, pool):
    d = {"squeeze": conv_defs(cin, squeeze, k=1), "gn": gn_defs(squeeze),
         "e1": conv_defs(squeeze, expand, k=1),
         "e3": conv_defs(squeeze, expand, k=3)}
    return ("fire", {"cin": cin, "cout": 2 * expand,
                     "stride": 2 if pool else 1}, d)


def _unit_apply(kind, meta, params, x, groups, impl: str = "lax"):
    s = meta["stride"]
    if kind == "stem":
        return jax.nn.relu(groupnorm(params["gn"],
                                     conv(params["conv"], x, s, impl),
                                     groups))
    if kind == "basic":
        h = jax.nn.relu(groupnorm(params["gn1"],
                                  conv(params["conv1"], x, s, impl), groups))
        h = groupnorm(params["gn2"], conv(params["conv2"], h, 1, impl), groups)
        sc = conv(params["proj"], x, s, impl) if "proj" in params else x
        return jax.nn.relu(h + sc)
    if kind == "vgg":
        h = jax.nn.relu(groupnorm(params["gn"],
                                  conv(params["conv"], x, 1, impl), groups))
        if s == 2 and h.shape[1] >= 2:       # skip pool once spatially flat
            h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max,
                                      (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
        return h
    if kind == "fire":
        sq = jax.nn.relu(groupnorm(params["gn"],
                                   conv(params["squeeze"], x, 1, impl),
                                   groups))
        h = jnp.concatenate([jax.nn.relu(conv(params["e1"], sq, 1, impl)),
                             jax.nn.relu(conv(params["e3"], sq, 1, impl))], -1)
        if s == 2 and h.shape[1] >= 2:
            h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max,
                                      (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
        return h
    raise ValueError(kind)


# --------------------------------------------------------------------------- #
# architectures as unit lists
# --------------------------------------------------------------------------- #
def build_units(cfg: CNNConfig) -> List[Tuple[str, dict, dict]]:
    s = cfg.scaled
    if cfg.arch in ("resnet18", "resnet34"):
        n = [2, 2, 2, 2] if cfg.arch == "resnet18" else [3, 4, 6, 3]
        widths = [s(64), s(128), s(256), s(512)]
        units = [_stem_unit(cfg, widths[0])]
        cin = widths[0]
        for stage, (reps, cout) in enumerate(zip(n, widths)):
            for i in range(reps):
                stride = 2 if (i == 0 and stage > 0) else 1
                units.append(_basic_unit(cfg, cin, cout, stride))
                cin = cout
        return units
    if cfg.arch == "vgg11":
        plan = [(s(64), True), (s(128), True), (s(256), False), (s(256), True),
                (s(512), False), (s(512), True), (s(512), False), (s(512), True)]
        units, cin = [], cfg.in_channels
        for cout, pool in plan:
            units.append(_vgg_unit(cfg, cin, cout, pool))
            cin = cout
        return units
    if cfg.arch == "squeezenet":
        units = [_stem_unit(cfg, s(64))]
        plan = [(s(16), s(64), False), (s(16), s(64), True),
                (s(32), s(128), False), (s(32), s(128), True),
                (s(48), s(192), False), (s(48), s(192), False),
                (s(64), s(256), True), (s(64), s(256), False)]
        cin = s(64)
        for sq, ex, pool in plan:
            units.append(_fire_unit(cfg, cin, sq, ex, pool))
            cin = 2 * ex
        return units
    raise ValueError(cfg.arch)


def cnn_defs(cfg: CNNConfig) -> dict:
    units = build_units(cfg)
    cout = units[-1][1]["cout"]
    return {
        "units": [d for _, _, d in units],
        "head": linear_defs(cout, cfg.num_classes),
    }


def unit_meta(cfg: CNNConfig) -> List[Tuple[str, dict]]:
    return [(k, m) for k, m, _ in build_units(cfg)]


def cnn_apply_units(cfg: CNNConfig, metas, params_list, x):
    impl = resolve_conv_impl(cfg.conv_impl)
    for (kind, meta), p in zip(metas, params_list):
        x = _unit_apply(kind, meta, p, x, cfg.groups, impl)
    return x


def cnn_forward(params, cfg: CNNConfig, images):
    metas = unit_meta(cfg)
    x = cnn_apply_units(cfg, metas, params["units"], images)
    x = jnp.mean(x, axis=(1, 2))
    return linear(params["head"], x)


def cnn_loss(params, cfg: CNNConfig, batch):
    from repro.models.layers import cross_entropy
    logits = cnn_forward(params, cfg, batch["inputs"]["images"])
    return cross_entropy(logits, batch["labels"])


# --------------------------------------------------------------------------- #
# NeuLite surrogate output module for CNNs
# --------------------------------------------------------------------------- #
def cnn_surrogate_defs(cfg: CNNConfig, block_bounds: List[Tuple[int, int]]):
    """One conv 'basic layer' per replaceable block (paper Fig. 4): a 3x3
    stride-2 conv mapping the previous block's output channels to this
    block's output channels."""
    metas = unit_meta(cfg)
    sur = []
    for (_s0, e0), (_s1, e1) in zip(block_bounds[:-1], block_bounds[1:]):
        cin = metas[e0 - 1][1]["cout"]
        cout = metas[e1 - 1][1]["cout"]
        sur.append({"conv": conv_defs(cin, cout), "gn": gn_defs(cout)})
    return sur


def cnn_apply_surrogates(cfg: CNNConfig, sur_params, x):
    impl = resolve_conv_impl(cfg.conv_impl)
    for p in sur_params:
        x = jax.nn.relu(groupnorm(p["gn"], conv(p["conv"], x, 2, impl),
                                  cfg.groups))
    return x


def cnn_projector_defs(cfg: CNNConfig, cin: int, out_dim: int = 64) -> dict:
    hid = 128
    return {"w1": linear_defs(cin, hid), "w2": linear_defs(hid, hid),
            "w3": linear_defs(hid, out_dim)}


def cnn_apply_projector(p, x_pooled):
    h = jax.nn.gelu(linear(p["w1"], x_pooled))
    h = jax.nn.gelu(linear(p["w2"], h))
    return linear(p["w3"], h)


def cnn_stage_apply(frozen, trainable, cfg: CNNConfig, metas_split, inputs):
    """NeuLite stage forward for CNNs.

    ``metas_split``: dict with "prefix", "boundary", "active" meta lists.
    Frozen/trainable trees carry matching "units" lists plus surrogates/head.
    Returns (logits, feats) in the same format as model.stage_apply."""
    x = inputs["images"]
    if frozen.get("units"):
        with jax.named_scope("neulite.prefix"):
            xf = cnn_apply_units(cfg, metas_split["prefix"],
                                 jax.lax.stop_gradient(frozen["units"]), x)
            x = jax.lax.stop_gradient(xf)
    x_embed = x
    with jax.named_scope("neulite.block"):
        if trainable.get("boundary_units"):
            x = cnn_apply_units(cfg, metas_split["boundary"],
                                trainable["boundary_units"], x)
        x = cnn_apply_units(cfg, metas_split["active"], trainable["units"],
                            x)
    z_active = x
    if trainable.get("surrogates"):
        with jax.named_scope("neulite.mentor"):
            x = cnn_apply_surrogates(cfg, trainable["surrogates"], x)
    with jax.named_scope("neulite.block"):
        pooled = jnp.mean(x, axis=(1, 2))
        logits = linear(trainable["head"], pooled)
    z_proj = None
    if trainable.get("projector") is not None:
        with jax.named_scope("neulite.mentor"):
            z_pooled = jnp.mean(z_active, axis=(1, 2))
            z_proj = cnn_apply_projector(trainable["projector"], z_pooled)
    feats = {"x_embed": x_embed, "z_active": z_active, "z_proj": z_proj,
             "aux": None, "loss_mask": None}
    return logits, feats
