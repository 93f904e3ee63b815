"""Progressive-training engine: adapters + stage train-step factory.

An ``Adapter`` binds a model family (scanned transformer stack or CNN unit
list) to the NeuLite engine.  It owns

  * the combined ParamDef tree (model + output-module surrogates + nHSIC
    projector — the Training Harmonizer's extra parameters),
  * ``split_stage(params, t)``  -> (frozen, trainable) subtrees,
  * ``merge_stage(params, trainable, t)`` -> full params with the trained
    subtree written back,
  * ``stage_apply(frozen, trainable, inputs)`` -> (logits, feats).

``make_stage_step`` builds the jit-able per-stage train step: curriculum
loss (Eq. 4) + proximal term (Eq. 5), gradients and optimizer state over the
*trainable subtree only* — frozen parameters enter as plain forward inputs,
so XLA never allocates their gradients, activations (post stop-gradient) or
optimizer state.  That is the paper's memory claim, stated in a form the
dry-run's ``memory_analysis()`` can verify.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.common import paramdef as PD
from repro.core import curriculum as cur
from repro.core.blocks import BlockPlan, make_plan
from repro.models import cnn as cnn_mod
from repro.models import model as tx
from repro.models.config import ModelConfig


@dataclasses.dataclass
class Adapter:
    kind: str                       # "transformer" | "cnn"
    cfg: Any
    plan: BlockPlan
    defs: dict
    num_classes: int
    split_stage: Callable[[Any, int], tuple]
    merge_stage: Callable[[Any, Any, int], Any]
    stage_apply: Callable[[Any, Any, dict], tuple]
    full_loss: Callable[[Any, dict], jnp.ndarray]
    forward_eval: Callable[[Any, dict], jnp.ndarray]

    def init_params(self, rng):
        return PD.init_params(rng, self.defs)


# =========================================================================== #
# transformer adapter (scanned period stacks)
# =========================================================================== #
def neulite_defs(cfg: ModelConfig, plan: BlockPlan) -> dict:
    return {
        "model": tx.model_defs(cfg),
        "surrogates": tx.surrogate_defs(cfg, plan.num_stages),
        "projector": tx.projector_defs(cfg),
    }


def _slice_tree(tree, s: int, e: int):
    return jax.tree.map(lambda x: x[s:e], tree)


def _colocate(f, p):
    """Move ``f`` onto ``p``'s sharding before an eager update-slice.

    The 2-D ``ShardedRuntime`` returns trainable slices committed to a
    (data, model) mesh with model-sharded leaves; the full param stack may
    still live on one device (or a previous stage's sharding).  Mixing the
    two in one eager op either fails ("incompatible devices") or silently
    gathers — resharding the *stack* to the slice's sharding instead keeps
    the merged params model-sharded across stages, so the next stage's
    split hands the runtime already-placed leaves.  The stacked layer axis
    (dim 0) is never sharded in the logical specs, so the slice's sharding
    applies to the full stack as-is.
    """
    sharding = getattr(p, "sharding", None)
    if sharding is None or getattr(f, "sharding", None) == sharding:
        return f
    if getattr(sharding, "num_devices", 1) > 1:
        return jax.device_put(f, sharding)
    return f


def _setslice_tree(full, part, s: int):
    return jax.tree.map(
        lambda f, p: f if p.shape[0] == 0 else
        jax.lax.dynamic_update_slice_in_dim(
            _colocate(f, p), p.astype(f.dtype), s, 0),
        full, part)


def make_transformer_adapter(cfg: ModelConfig, num_stages: int,
                             boundary_units: int = 1) -> Adapter:
    plan = make_plan(cfg.num_periods, num_stages, boundary_units)
    defs = neulite_defs(cfg, plan)
    T = plan.num_stages

    def split_stage(params, t):
        (f0, f1), (b0, b1), (a0, a1) = plan.stage_ranges(t)
        layers = params["model"]["layers"]
        frozen, trainable = {}, {}
        if "embed" in params["model"]:
            (trainable if t == 0 else frozen)["embed"] = \
                params["model"]["embed"]
        frozen["prefix"] = _slice_tree(layers, f0, f1)
        trainable["boundary"] = _slice_tree(layers, b0, b1)
        trainable["active"] = _slice_tree(layers, a0, a1)
        trainable["surrogates"] = (
            _slice_tree(params["surrogates"], t, T - 1) if t < T - 1 else None)
        trainable["projector"] = params["projector"]
        trainable["final_norm"] = params["model"]["final_norm"]
        trainable["head"] = params["model"]["head"]
        return frozen, trainable

    def merge_stage(params, trainable, t):
        (_, _), (b0, b1), (a0, a1) = plan.stage_ranges(t)
        params = dict(params)
        model = dict(params["model"])
        layers = model["layers"]
        layers = _setslice_tree(layers, trainable["boundary"], b0)
        layers = _setslice_tree(layers, trainable["active"], a0)
        model["layers"] = layers
        if "embed" in trainable and trainable.get("embed") is not None:
            model["embed"] = trainable["embed"]
        model["final_norm"] = trainable["final_norm"]
        model["head"] = trainable["head"]
        params["model"] = model
        if trainable.get("surrogates") is not None:
            params["surrogates"] = _setslice_tree(
                params["surrogates"], trainable["surrogates"], t)
        params["projector"] = trainable["projector"]
        return params

    def stage_apply(frozen, trainable, inputs):
        return tx.stage_apply(frozen, trainable, cfg, inputs)

    def full_loss(params, batch):
        return tx.loss_fn(params["model"], cfg, batch)

    def forward_eval(params, inputs):
        logits, _, _ = tx.forward(params["model"], cfg, inputs, remat=False)
        return logits

    return Adapter(kind="transformer", cfg=cfg, plan=plan, defs=defs,
                   num_classes=cfg.vocab_size, split_stage=split_stage,
                   merge_stage=merge_stage, stage_apply=stage_apply,
                   full_loss=full_loss, forward_eval=forward_eval)


# =========================================================================== #
# CNN adapter (unit lists)
# =========================================================================== #
def make_cnn_adapter(ccfg: cnn_mod.CNNConfig, num_stages: int,
                     boundary_units: int = 1) -> Adapter:
    metas = cnn_mod.unit_meta(ccfg)
    plan = make_plan(len(metas), num_stages, boundary_units)
    base = cnn_mod.cnn_defs(ccfg)
    sur = cnn_mod.cnn_surrogate_defs(ccfg, list(plan.bounds))
    # per-stage projector input dim = active block's output channels
    proj = [cnn_mod.cnn_projector_defs(ccfg, metas[e - 1][1]["cout"])
            for s, e in plan.bounds]
    defs = {"model": base, "surrogates": sur, "projector": proj}

    def split_stage(params, t):
        (f0, f1), (b0, b1), (a0, a1) = plan.stage_ranges(t)
        units = params["model"]["units"]
        frozen = {"units": units[f0:f1]}
        trainable = {
            "boundary_units": units[b0:b1],
            "units": units[a0:a1],
            "surrogates": params["surrogates"][t:] if t < plan.num_stages - 1
            else None,
            "projector": params["projector"][t],
            "head": params["model"]["head"],
        }
        return frozen, trainable

    def merge_stage(params, trainable, t):
        (_, _), (b0, b1), (a0, a1) = plan.stage_ranges(t)
        params = dict(params)
        model = dict(params["model"])
        units = list(model["units"])
        units[b0:b1] = trainable["boundary_units"]
        units[a0:a1] = trainable["units"]
        model["units"] = units
        model["head"] = trainable["head"]
        params["model"] = model
        if trainable.get("surrogates") is not None:
            sur = list(params["surrogates"])
            sur[t:] = trainable["surrogates"]
            params["surrogates"] = sur
        proj = list(params["projector"])
        proj[t] = trainable["projector"]
        params["projector"] = proj
        return params

    def stage_apply(frozen, trainable, inputs):
        # reconstruct the static meta split for this stage from shapes
        t = _infer_stage(trainable)
        (f0, f1), (b0, b1), (a0, a1) = plan.stage_ranges(t)
        msplit = {"prefix": metas[f0:f1], "boundary": metas[b0:b1],
                  "active": metas[a0:a1]}
        return cnn_mod.cnn_stage_apply(frozen, trainable, ccfg, msplit,
                                       inputs)

    def _infer_stage(trainable):
        n_sur = (len(trainable["surrogates"])
                 if trainable.get("surrogates") else 0)
        return plan.num_stages - 1 - n_sur

    def full_loss(params, batch):
        return cnn_mod.cnn_loss(params["model"], ccfg, batch)

    def forward_eval(params, inputs):
        return cnn_mod.cnn_forward(params["model"], ccfg, inputs["images"])

    return Adapter(kind="cnn", cfg=ccfg, plan=plan, defs=defs,
                   num_classes=ccfg.num_classes, split_stage=split_stage,
                   merge_stage=merge_stage, stage_apply=stage_apply,
                   full_loss=full_loss, forward_eval=forward_eval)


def make_adapter(cfg, num_stages: int, boundary_units: int = 1) -> Adapter:
    if isinstance(cfg, cnn_mod.CNNConfig):
        return make_cnn_adapter(cfg, num_stages, boundary_units)
    return make_transformer_adapter(cfg, num_stages, boundary_units)


# =========================================================================== #
# stage train step
# =========================================================================== #
def make_stage_loss(adapter: Adapter, hp: cur.CurriculumHP, t: int):
    """loss(trainable, frozen, batch, global_ref) -> (loss, metrics)."""
    T = adapter.plan.num_stages

    def loss_fn(trainable, frozen, batch, global_ref):
        logits, feats = adapter.stage_apply(frozen, trainable,
                                            batch["inputs"])
        # the cross-entropy of the logits comes with the nHSIC terms
        with jax.named_scope("neulite.mentor"):
            loss, metrics = cur.curriculum_loss(
                logits, feats, batch, adapter.cfg, hp, t, T,
                adapter.num_classes)
            prox = cur.proximal_term(trainable, global_ref, hp.mu)
        metrics["prox"] = prox
        return loss + prox, metrics

    return loss_fn


def make_stage_step(adapter: Adapter, optimizer, hp: cur.CurriculumHP,
                    t: int, *, pmean_axis: Optional[str] = None):
    """Returns train_step(opt_state, trainable, frozen, batch, global_ref)
    -> (opt_state, trainable, metrics).  If ``pmean_axis`` is given the
    gradients are averaged over that mesh axis (used under shard_map).

    The signature is donation-friendly: the carried state (opt_state,
    trainable) leads and maps positionally onto the first two outputs, so
    ``jax.jit(step, donate_argnums=(0, 1))`` lets XLA update both in place.
    See ``jit_stage_step`` for the safe default."""
    loss_fn = make_stage_loss(adapter, hp, t)
    from repro.optim import apply_updates

    def train_step(opt_state, trainable, frozen, batch, global_ref):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(trainable, frozen, batch, global_ref)
        if pmean_axis is not None:
            grads = jax.lax.pmean(grads, pmean_axis)
            loss = jax.lax.pmean(loss, pmean_axis)
        updates, opt_state = optimizer.update(grads, opt_state, trainable)
        trainable = apply_updates(trainable, updates)
        metrics["loss"] = loss
        return opt_state, trainable, metrics

    return train_step


def jit_stage_step(adapter: Adapter, optimizer, hp: cur.CurriculumHP, t: int,
                   *, donate: bool = True, donate_trainable: bool = False,
                   pmean_axis: Optional[str] = None):
    """``make_stage_step`` jitted with buffer donation.

    ``opt_state`` (argnum 0) is donated by default — it is threaded through
    the local-training loop and never read again, so XLA reuses its buffers
    (the optimizer-state share of the paper's client memory budget).
    ``trainable`` (argnum 1) is only donated on request: FL callers routinely
    alias it with ``global_ref`` / the server's full param tree on the first
    local step, and donating an aliased buffer invalidates the other view.
    """
    step = make_stage_step(adapter, optimizer, hp, t, pmean_axis=pmean_axis)
    donate = donate and donation_supported()
    donate_argnums = ((0, 1) if donate_trainable else (0,)) if donate else ()
    return jax.jit(step, donate_argnums=donate_argnums)


def donation_supported() -> bool:
    """CPU XLA ignores donation and warns per compile — skip it there."""
    return jax.default_backend() != "cpu"


def make_full_step(adapter: Adapter, optimizer, *,
                   pmean_axis: Optional[str] = None):
    """End-to-end (vanilla FL / FedAvg) train step over the full model.

    Donation-friendly like ``make_stage_step``: (opt_state, params) lead and
    map onto the first two outputs (see ``jit_full_step``)."""
    from repro.optim import apply_updates

    def train_step(opt_state, params, batch):
        loss, grads = jax.value_and_grad(adapter.full_loss)(params, batch)
        if pmean_axis is not None:
            grads = jax.lax.pmean(grads, pmean_axis)
            loss = jax.lax.pmean(loss, pmean_axis)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = apply_updates(params, updates)
        return opt_state, params, {"loss": loss}

    return train_step


def jit_full_step(adapter: Adapter, optimizer, *, donate: bool = True,
                  donate_params: bool = False,
                  pmean_axis: Optional[str] = None):
    """``make_full_step`` jitted with opt-state (and optionally param)
    donation — same aliasing caveats as ``jit_stage_step``."""
    step = make_full_step(adapter, optimizer, pmean_axis=pmean_axis)
    donate = donate and donation_supported()
    donate_argnums = ((0, 1) if donate_params else (0,)) if donate else ()
    return jax.jit(step, donate_argnums=donate_argnums)
