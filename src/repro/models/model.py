"""Model assembly: embeddings -> scanned layer-stack -> norm -> head.

The layer stack is ``num_periods`` repetitions of the config's sub-layer
pattern, scanned with ``jax.lax.scan`` over stacked parameters, so HLO size
is O(period), not O(num_layers).

Three entry points:
  ``forward``        full-sequence logits (train / eval / prefill)
  ``decode_step``    one token against per-layer caches (serve)
  ``stage_apply``    NeuLite progressive stage: frozen prefix (stop-gradient),
                     boundary + active periods (trainable), surrogate output
                     module, head.  Takes (frozen, trainable) param subtrees
                     produced by ``repro.core.blocks.split_stage_params`` so
                     gradients/optimizer state exist *only* for the active
                     subtree — the paper's memory saving, visible to XLA.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.common.paramdef import ParamDef, stack_defs
from repro.common.sharding import shard
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm
from repro.models.config import ModelConfig
from repro.models.layers import (MODEL_AXIS, cross_entropy, embed,
                                 embedding_defs, head_defs, lm_head, mlp,
                                 mlp_defs, rmsnorm, rmsnorm_defs)


# --------------------------------------------------------------------------- #
# sub-layer defs / apply
# --------------------------------------------------------------------------- #
def _mixer_defs(cfg: ModelConfig, kind: str) -> dict:
    if kind == "attn":
        return attn.attn_defs(cfg)
    if kind == "mamba":
        return ssm.mamba_defs(cfg)
    if kind == "mlstm":
        return ssm.mlstm_defs(cfg)
    if kind == "slstm":
        return ssm.slstm_defs(cfg)
    raise ValueError(kind)


def _ffn_defs(cfg: ModelConfig, ffn: str, layer_in_pattern: int) -> Optional[dict]:
    if ffn == "none":
        return None
    if ffn == "moe":
        return moe_defs_cached(cfg)
    # dense mlp; MoE archs use d_ff_dense for their leading dense layers
    d_ff = cfg.d_ff
    if cfg.moe is not None and cfg.moe.d_ff_dense:
        d_ff = cfg.moe.d_ff_dense
    return mlp_defs(cfg.d_model, d_ff, cfg.param_dtype, cfg.act)


def moe_defs_cached(cfg):
    return moe_mod.moe_defs(cfg)


def sublayer_defs(cfg: ModelConfig, kind: str, ffn: str, idx: int) -> dict:
    d = {
        "norm1": rmsnorm_defs(cfg.d_model, cfg.param_dtype),
        "mixer": _mixer_defs(cfg, kind),
    }
    f = _ffn_defs(cfg, ffn, idx)
    if f is not None:
        d["norm2"] = rmsnorm_defs(cfg.d_model, cfg.param_dtype)
        d["ffn"] = f
    return d


def _mixer_forward(params, cfg, kind, x, positions, with_cache):
    fn = {"attn": attn.attn_forward, "mamba": ssm.mamba_forward,
          "mlstm": ssm.mlstm_forward, "slstm": ssm.slstm_forward}[kind]
    return fn(params, cfg, x, positions, with_cache=with_cache)


def _mixer_decode(params, cfg, kind, x, cache, pos):
    fn = {"attn": attn.attn_decode, "mamba": ssm.mamba_decode,
          "mlstm": ssm.mlstm_decode, "slstm": ssm.slstm_decode}[kind]
    return fn(params, cfg, x, cache, pos)


def sublayer_apply(params, cfg: ModelConfig, kind: str, ffn: str, x,
                   positions, *, with_cache=False):
    """Pre-norm residual sub-layer. Returns (x, cache, aux)."""
    h = rmsnorm(params["norm1"], x, cfg.norm_eps)
    mix, cache = _mixer_forward(params["mixer"], cfg, kind, h, positions,
                                with_cache)
    x = x + mix
    aux = None
    if ffn != "none":
        h = rmsnorm(params["norm2"], x, cfg.norm_eps)
        if ffn == "moe":
            y, aux = moe_mod.moe_apply(params["ffn"], cfg, h)
        else:
            y = mlp(params["ffn"], h, cfg.act)
        x = x + y
    return x, cache, aux


def sublayer_decode(params, cfg: ModelConfig, kind: str, ffn: str, x,
                    cache, pos):
    h = rmsnorm(params["norm1"], x, cfg.norm_eps)
    mix, cache = _mixer_decode(params["mixer"], cfg, kind, h, cache, pos)
    x = x + mix
    if ffn != "none":
        h = rmsnorm(params["norm2"], x, cfg.norm_eps)
        if ffn == "moe":
            y, _ = moe_mod.moe_apply(params["ffn"], cfg, h)
        else:
            y = mlp(params["ffn"], h, cfg.act)
        x = x + y
    return x, cache


# --------------------------------------------------------------------------- #
# whole-model defs
# --------------------------------------------------------------------------- #
def period_defs(cfg: ModelConfig) -> dict:
    return {f"sub{i}": sublayer_defs(cfg, kind, ffn, i)
            for i, (kind, ffn) in enumerate(cfg.pattern)}


def patch_embed_defs(cfg: ModelConfig) -> dict:
    pdim = cfg.patch_size * cfg.patch_size * cfg.in_channels
    return {
        "w": ParamDef((pdim, cfg.d_model), cfg.param_dtype, P(None, None)),
        "b": ParamDef((cfg.d_model,), cfg.param_dtype, P(None), init="zeros"),
        "pos": ParamDef(((cfg.image_size // cfg.patch_size) ** 2, cfg.d_model),
                        cfg.param_dtype, P(None, None), init="embed"),
    }


def patchify(cfg: ModelConfig, images):
    """(B, H, W, C) -> (B, n_patches, P*P*C)."""
    B, H, W, C = images.shape
    p = cfg.patch_size
    x = images.reshape(B, H // p, p, W // p, p, C)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(B, (H // p) * (W // p), p * p * C)


def model_defs(cfg: ModelConfig) -> dict:
    defs: dict = {}
    if cfg.modality in ("text", "vlm"):
        defs["embed"] = embedding_defs(cfg.vocab_size, cfg.d_model,
                                       cfg.param_dtype)
    elif cfg.modality == "image":
        defs["embed"] = patch_embed_defs(cfg)
    # audio: frontend stub feeds embeddings directly (no token embedding)
    defs["layers"] = stack_defs(period_defs(cfg), cfg.num_periods)
    defs["final_norm"] = rmsnorm_defs(cfg.d_model, cfg.param_dtype)
    defs["head"] = head_defs(cfg.d_model, cfg.vocab_size, cfg.param_dtype,
                             cfg.num_output_heads)
    return defs


def cache_defs(cfg: ModelConfig, batch: int, seq: int) -> dict:
    """Per-pattern-position caches stacked over periods."""
    out = {}
    for i, (kind, _) in enumerate(cfg.pattern):
        if kind == "attn":
            c = attn.attn_cache_defs(cfg, batch, seq)
        elif kind == "mamba":
            c = ssm.mamba_cache_defs(cfg, batch)
        elif kind == "mlstm":
            c = ssm.mlstm_cache_defs(cfg, batch)
        elif kind == "slstm":
            c = ssm.slstm_cache_defs(cfg, batch)
        out[f"sub{i}"] = stack_defs(c, cfg.num_periods)
    return out


# --------------------------------------------------------------------------- #
# input embedding per modality
# --------------------------------------------------------------------------- #
def embed_inputs(params, cfg: ModelConfig, inputs: dict):
    """Returns (x, positions, loss_mask)."""
    if cfg.modality == "text":
        tokens = inputs["tokens"]
        x = embed(params["embed"], tokens)
        B, S = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        return x, positions, None
    if cfg.modality == "audio":
        x = inputs["embeds"].astype(cfg.param_dtype)
        B, S = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        return x, positions, None
    if cfg.modality == "image":
        x = patchify(cfg, inputs["images"].astype(cfg.param_dtype))
        x = x @ params["embed"]["w"] + params["embed"]["b"]
        x = x + params["embed"]["pos"]
        B, S = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        return x, positions, None
    if cfg.modality == "vlm":
        patches = inputs["patches"].astype(cfg.param_dtype)   # (B, Pv, d)
        tokens = inputs["tokens"]                             # (B, St)
        xt = embed(params["embed"], tokens)
        x = jnp.concatenate([patches, xt], axis=1)
        B, S = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        mask = jnp.concatenate(
            [jnp.zeros((B, patches.shape[1]), bool),
             jnp.ones((B, tokens.shape[1]), bool)], axis=1)
        return x, positions, mask
    raise ValueError(cfg.modality)


# --------------------------------------------------------------------------- #
# layer-stack runners
# --------------------------------------------------------------------------- #
def _run_periods(layer_params, cfg: ModelConfig, x, positions, *,
                 with_cache=False, remat=True, collect_aux=True):
    """Scan the pattern over stacked period params.

    Returns (x, caches, aux_sum) where aux_sum accumulates MoE aux losses.
    """
    def body(carry, period_p):
        x, aux = carry
        caches = {}
        for i, (kind, ffn) in enumerate(cfg.pattern):
            x, c, a = sublayer_apply(period_p[f"sub{i}"], cfg, kind, ffn, x,
                                     positions, with_cache=with_cache)
            caches[f"sub{i}"] = c
            if a is not None and collect_aux:
                aux = {k: aux[k] + v for k, v in a.items()}
        x = shard(x, ("pod", "data"), None, None)
        return (x, aux), caches

    aux0 = {"load_balance": jnp.zeros((), jnp.float32),
            "router_z": jnp.zeros((), jnp.float32)}
    fn = jax.remat(body, prevent_cse=False) if remat else body
    (x, aux), caches = jax.lax.scan(fn, (x, aux0), layer_params)
    return x, caches, aux


def _decode_periods(layer_params, cfg: ModelConfig, x, caches, pos):
    def body(x, inp):
        period_p, period_c = inp
        new_c = {}
        for i, (kind, ffn) in enumerate(cfg.pattern):
            x, c = sublayer_decode(period_p[f"sub{i}"], cfg, kind, ffn, x,
                                   period_c[f"sub{i}"], pos)
            new_c[f"sub{i}"] = c
        return x, new_c

    x, new_caches = jax.lax.scan(body, x, (layer_params, caches))
    return x, new_caches


# --------------------------------------------------------------------------- #
# public entry points
# --------------------------------------------------------------------------- #
def forward(params, cfg: ModelConfig, inputs: dict, *, with_cache=False,
            remat=True):
    """Full model. Returns (logits, caches, aux)."""
    x, positions, _ = embed_inputs(params, cfg, inputs)
    x = shard(x, ("pod", "data"), None, None)
    x, caches, aux = _run_periods(params["layers"], cfg, x, positions,
                                  with_cache=with_cache, remat=remat)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if cfg.task == "classify":
        x = jnp.mean(x, axis=1)                       # global pool
    logits = lm_head(params["head"], x, cfg.num_output_heads)
    return logits, caches, aux


def loss_fn(params, cfg: ModelConfig, batch: dict, *, remat=True):
    """Standard (non-progressive) training loss: CE + MoE aux."""
    logits, _, aux = forward(params, cfg, batch["inputs"], remat=remat)
    _, _, mask = embed_inputs(params, cfg, batch["inputs"])
    labels = batch["labels"]
    if cfg.task == "classify":
        loss = cross_entropy(logits, labels)          # (B, V) vs (B,)
        if cfg.moe is not None:
            loss = loss + moe_mod.moe_aux_loss(aux, cfg.moe)
        return loss
    if cfg.num_output_heads > 1:
        # labels (B, S, heads); logits (B, S, heads, V)
        loss = cross_entropy(logits, labels,
                             None if mask is None else mask[..., None])
    else:
        if cfg.modality == "vlm":
            # logits cover [patches + text]; labels cover text only
            logits = logits[:, -labels.shape[1]:]
            mask = None
        loss = cross_entropy(logits, labels, mask)
    if cfg.moe is not None:
        loss = loss + moe_mod.moe_aux_loss(aux, cfg.moe)
    return loss


def decode_step(params, cfg: ModelConfig, inputs: dict, caches, pos):
    """One-token decode. inputs: {"tokens": (B,1)} or {"embeds": (B,1,d)}.

    Returns (logits (B, 1, V[, heads]), new_caches)."""
    if cfg.modality == "audio":
        x = inputs["embeds"].astype(cfg.param_dtype)
    else:
        x = embed(params["embed"], inputs["tokens"])
    x = shard(x, ("pod", "data"), None, None)
    x, new_caches = _decode_periods(params["layers"], cfg, x, caches, pos)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = lm_head(params["head"], x, cfg.num_output_heads)
    return logits, new_caches


def prefill(params, cfg: ModelConfig, inputs: dict):
    """Prefill: forward with caches. Returns (last-token logits, caches)."""
    logits, caches, _ = forward(params, cfg, inputs, with_cache=True,
                                remat=False)
    return logits[:, -1:], caches


# --------------------------------------------------------------------------- #
# NeuLite progressive stage forward
# --------------------------------------------------------------------------- #
def surrogate_defs(cfg: ModelConfig, num_blocks: int) -> dict:
    """Output-module 'basic layers': one residual projection per *replaced*
    block (paper: a conv layer per remaining block + FC head).  Stacked over
    the T-1 replaceable blocks; stage t uses suffix [t:]."""
    d = cfg.d_model
    dt = cfg.param_dtype
    base = {
        "norm": rmsnorm_defs(d, dt),
        "w": ParamDef((d, d), dt, P(None, MODEL_AXIS)),
        "wo": ParamDef((d, d), dt, P(MODEL_AXIS, None)),
    }
    return stack_defs(base, max(num_blocks - 1, 1))


def apply_surrogates(sur_params, cfg: ModelConfig, x):
    """Apply the surrogate basic layers sequentially (suffix already sliced)."""
    def body(x, p):
        h = rmsnorm(p["norm"], x, cfg.norm_eps)
        h = jax.nn.gelu(h @ p["w"]) @ p["wo"]
        return x + h, None

    x, _ = jax.lax.scan(body, x, sur_params)
    return x


def projector_defs(cfg: ModelConfig, out_dim: int = 64) -> dict:
    """3-layer MLP projecting block activations to a low-dim space for the
    nHSIC(Y;Z) estimate (paper, Curriculum Mentor)."""
    d, dt = cfg.d_model, cfg.param_dtype
    hid = max(out_dim * 2, 128)
    return {
        "w1": ParamDef((d, hid), dt, P(None, MODEL_AXIS)),
        "w2": ParamDef((hid, hid), dt, P(MODEL_AXIS, None)),
        "w3": ParamDef((hid, out_dim), dt, P(None, None)),
    }


def apply_projector(p, x):
    h = jax.nn.gelu(x @ p["w1"])
    h = jax.nn.gelu(h @ p["w2"])
    return h @ p["w3"]


def stage_apply(frozen, trainable, cfg: ModelConfig, inputs: dict, *,
                remat=True):
    """Progressive stage forward.

    ``frozen``:    {"embed"?: ..., "prefix": stacked periods (may be empty)}
    ``trainable``: {"embed"?: ..., "boundary": stacked periods (may be empty),
                    "active": stacked periods, "surrogates": suffix,
                    "projector": ..., "final_norm": ..., "head": ...}

    Returns (logits, feats) where feats carries the tensors the Curriculum
    Mentor needs: x_embed (input repr), z_active (active-block output),
    z_proj (projected low-dim z), aux (MoE aux losses from trainable periods).
    """
    embed_params = trainable.get("embed", frozen.get("embed"))
    holder = {"embed": embed_params}
    with jax.named_scope("neulite.prefix" if "embed" in frozen
                         else "neulite.block"):
        x, positions, loss_mask = embed_inputs(holder, cfg, inputs)
        if "embed" in frozen:
            x = jax.lax.stop_gradient(x)
    x_embed = x

    aux = {"load_balance": jnp.zeros((), jnp.float32),
           "router_z": jnp.zeros((), jnp.float32)}

    def has_periods(p):
        if p is None:
            return False
        leaves = jax.tree.leaves(p)
        return bool(leaves) and leaves[0].shape[0] > 0

    if has_periods(frozen.get("prefix")):
        with jax.named_scope("neulite.prefix"):
            fro = jax.lax.stop_gradient(frozen["prefix"])
            x, _, _ = _run_periods(fro, cfg, x, positions, remat=False,
                                   collect_aux=False)
            x = jax.lax.stop_gradient(x)
    with jax.named_scope("neulite.block"):
        if has_periods(trainable.get("boundary")):
            x, _, a = _run_periods(trainable["boundary"], cfg, x, positions,
                                   remat=remat)
            aux = {k: aux[k] + v for k, v in a.items()}
        x, _, a = _run_periods(trainable["active"], cfg, x, positions,
                               remat=remat)
        aux = {k: aux[k] + v for k, v in a.items()}
    z_active = x

    if has_periods(trainable.get("surrogates")):
        with jax.named_scope("neulite.mentor"):
            x = apply_surrogates(trainable["surrogates"], cfg, x)
    with jax.named_scope("neulite.block"):
        x = rmsnorm(trainable["final_norm"], x, cfg.norm_eps)
        if cfg.task == "classify":
            x = jnp.mean(x, axis=1)
        logits = lm_head(trainable["head"], x, cfg.num_output_heads)

    z_proj = None
    if trainable.get("projector") is not None:
        with jax.named_scope("neulite.mentor"):
            z_proj = apply_projector(trainable["projector"], z_active)

    feats = {"x_embed": x_embed, "z_active": z_active, "z_proj": z_proj,
             "aux": aux, "loss_mask": loss_mask}
    return logits, feats


# --------------------------------------------------------------------------- #
# accounting
# --------------------------------------------------------------------------- #
def model_flops(cfg: ModelConfig, num_tokens: int) -> float:
    """MODEL_FLOPS = 6 * N_active * D (MoE counts active experts only)."""
    n = active_param_count(cfg)
    return 6.0 * n * num_tokens


def total_param_count(cfg: ModelConfig) -> int:
    from repro.common.paramdef import nparams
    return nparams(model_defs(cfg))


def active_param_count(cfg: ModelConfig) -> int:
    """Params touched per token (MoE: top_k + shared experts only)."""
    from repro.common.paramdef import nparams
    defs = model_defs(cfg)
    if cfg.moe is None:
        return nparams(defs)
    total = nparams(defs)
    # subtract inactive routed experts
    moe_layers = sum(1 for _, f in cfg.pattern if f == "moe") * cfg.num_periods
    per_expert = 3 * cfg.d_model * cfg.moe.d_ff_expert
    inactive = moe_layers * (cfg.moe.num_experts - cfg.moe.top_k) * per_expert
    return total - inactive
