"""ClientRuntime: pluggable execution engines for one NeuLite FL round.

One round (paper Alg. 1, lines 4-10) = split stage-t params, run E local
steps on each selected client with **no cross-cohort communication**, then
weighted-FedAvg (Eq. 1) the trainable subtree.  The three backends execute
those identical semantics at different points on the throughput curve:

  SequentialRuntime    — reference Python loop; one jitted stage step per
                         batch, clients simulated one-by-one (CPU testbeds,
                         debugging).
  VectorizedRuntime    — ONE jitted program per stage: cohort-vmapped
                         ``lax.scan`` local training fused with the Eq. 1
                         aggregation einsum (the round's single collective).
  ShardedRuntime       — the same program over a 2-D (data, model) launch
                         mesh: the cohort axis shards over "data" and the
                         aggregation lowers to one all-reduce over "data" —
                         the collective the roofline dry-run measures.  With
                         ``model_parallel > 1`` stage params, optimizer
                         state, and per-cohort local weights additionally
                         shard over "model" via the adapter's logical
                         ParamDef specs (``launch.sharding``), so clients
                         whose trainable block does not fit one device
                         still train.
  AsyncBufferedRuntime — a stateful FedBuff-style buffered-async server on
                         a virtual clock: clients deliver deltas at their
                         own simulated pace, the server flushes every K
                         arrivals with per-entry staleness-discounted Eq. 1
                         weights, and stragglers stay in a persistent
                         ``AsyncServerState`` buffer that carries them into
                         later ``run_round`` calls — no delivered delta is
                         ever dropped (see the class docstring).  With
                         ``model_parallel > 1`` its local training and
                         flush aggregation run on the same 2-D mesh
                         placements as ``ShardedRuntime``.

All backends consume a ``RoundStack`` (``data.loader.stack_round``): a
(C, E, ...) batch stack plus a (C, E) step mask.  The mask preserves the
sequential semantics exactly — cohorts with smaller datasets run fewer true
steps; padded steps are no-ops for params *and* optimizer state — so the
vectorized paths are numerically equivalent to the reference loop (same
post-round params up to dtype tolerance), not a fork of the semantics.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import spans
from repro.core.curriculum import CurriculumHP
from repro.core.progressive import (Adapter, jit_stage_step, make_full_step,
                                    make_stage_loss, make_stage_step)
from repro.data.loader import (Batcher, RoundStack, StackBuffer,
                               stack_round, truncate_step_mask)
from repro.federated import aggregation as agg
from repro.federated.client import run_local_training
from repro.optim import apply_updates


# =========================================================================== #
# the round program (one jit-able function per stage)
# =========================================================================== #
def make_local_program(adapter: Adapter, optimizer, hp: CurriculumHP,
                       t: int):
    """local_fn(trainable, frozen, batches, step_mask) -> (locals_, losses)

    The cohort-vmapped local-training half of a round, without the Eq. 1
    aggregation: ``locals_`` stacks each cohort's post-training trainable
    subtree on a leading (C,) axis, ``losses`` is the (C,) masked mean local
    loss.  ``make_round_program`` fuses this with the aggregation einsum;
    ``AsyncBufferedRuntime`` aggregates the resulting deltas itself, flush
    by flush, on the host-side virtual clock.
    """
    loss_fn = make_stage_loss(adapter, hp, t)

    def local_training(trainable0, frozen, cohort_batches, cohort_mask):
        """E masked local steps on one cohort — no cross-cohort comms."""
        opt_state0 = optimizer.init(trainable0)

        def step(carry, xs):
            batch, keep = xs
            opt_state, trainable = carry
            (loss, _), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(trainable, frozen, batch, trainable0)
            with jax.named_scope("neulite.optimizer"):
                updates, new_opt = optimizer.update(grads, opt_state,
                                                    trainable)
                new_tr = apply_updates(trainable, updates)
                sel = lambda new, old: jnp.where(keep, new, old)
                carry = (jax.tree.map(sel, new_opt, opt_state),
                         jax.tree.map(sel, new_tr, trainable))
            return carry, jnp.where(keep, loss, 0.0)

        (_, trainable), losses = jax.lax.scan(
            step, (opt_state0, trainable0), (cohort_batches, cohort_mask))
        n = jnp.maximum(cohort_mask.sum(), 1)
        return trainable, losses.sum() / n

    def local_fn(trainable, frozen, batches, step_mask):
        return jax.vmap(local_training, in_axes=(None, None, 0, 0))(
            trainable, frozen, batches, step_mask)

    return local_fn


def _psum_if(x, ax):
    return x if ax is None else jax.lax.psum(x, ax)


def eq1_aggregate(locals_, weights, losses, *, axis: Optional[str] = None,
                  locals_shardings: Any = None):
    """The Eq. 1 aggregation seam: one weighted einsum over the cohort axis.

    ``locals_`` leaves carry a leading (C,) cohort axis; ``weights`` is the
    (C,) sample-count vector and ``losses`` the (C,) per-cohort mean local
    loss.  Returns ``(new_trainable, mean_loss)``.  Every synchronous
    backend funnels its round through this function — it is the single
    point the collective auditor (``repro.analysis``) traces to prove the
    "one all-reduce over 'data' per aggregated leaf" contract, and the
    instrumentation point for secure-agg / DP hooks.

    With ``axis`` set the reductions are explicit ``psum`` collectives
    (the ``shard_map`` path); with ``locals_shardings`` set the cohort
    contraction lowers under GSPMD to one all-reduce over the data axis
    per leaf while model shards keep owning their slice (no gather).
    """
    with jax.named_scope("neulite.eq1"):
        if locals_shardings is not None:
            locals_ = jax.lax.with_sharding_constraint(locals_,
                                                       locals_shardings)
        total = weights.sum().astype(jnp.float32)
        if axis is not None:
            total = jax.lax.psum(total, axis)
        w = weights.astype(jnp.float32) / jnp.maximum(total, 1e-12)
        # Eq. 1: weighted FedAvg over the trainable subtree only — this
        # einsum over the cohort axis is the round's one all-reduce
        new_trainable = jax.tree.map(
            lambda leaf: _psum_if(jnp.einsum(
                "c...,c->...", leaf.astype(jnp.float32), w), axis).astype(
                    leaf.dtype), locals_)
        mean_loss = _psum_if(jnp.sum(losses * w), axis)
    return new_trainable, mean_loss


def make_round_program(adapter: Adapter, optimizer, hp: CurriculumHP, t: int,
                       *, axis: Optional[str] = None,
                       locals_shardings: Any = None):
    """round_fn(trainable, frozen, batches, weights, step_mask)
         -> (new_trainable, metrics)

    trainable : stage-t global trainable subtree (replicated across cohorts)
    batches   : pytree with leading (C, E, ...) axes
    weights   : (C,) Eq. 1 aggregation weights (true |D_c|)
    step_mask : (C, E) bool — False steps are exact no-ops

    With ``axis`` set the program is written for ``shard_map``: the cohort
    axis is device-local and the aggregation / loss reductions become
    ``psum`` collectives over that mesh axis.

    With ``locals_shardings`` set (a NamedSharding tree matching the
    trainable subtree with a leading cohort axis) the program instead
    targets GSPMD on a 2-D (data, model) mesh: the per-cohort local weights
    are constrained to shard (cohort → "data", params → "model"), so the
    Eq. 1 contraction lowers to one all-reduce over "data" only while each
    model shard keeps owning its slice of the result — no gather.
    """
    local_fn = make_local_program(adapter, optimizer, hp, t)

    def round_fn(trainable, frozen, batches, weights, step_mask):
        locals_, losses = local_fn(trainable, frozen, batches, step_mask)
        new_trainable, mean_loss = eq1_aggregate(
            locals_, weights, losses, axis=axis,
            locals_shardings=locals_shardings)
        return new_trainable, {"mean_local_loss": mean_loss,
                               "cohort_losses": losses}

    return round_fn


def make_full_round_program(adapter: Adapter, optimizer,
                            *, axis: Optional[str] = None,
                            locals_shardings: Any = None):
    """Full-model FL round (vanilla FedAvg): the memory-audit reference.

    Same structure as ``make_round_program`` — cohort-vmapped masked
    ``lax.scan`` local training fused with the Eq. 1 einsum — but every
    parameter trains (no frozen subtree), so gradients and optimizer state
    cover the whole model.  ``repro.analysis`` compiles this next to the
    per-stage programs to machine-check the paper's block-wise-memory
    claim: every stage's peak bytes must undercut this program's.
    """

    def local_training(params0, cohort_batches, cohort_mask):
        opt_state0 = optimizer.init(params0)

        def step(carry, xs):
            batch, keep = xs
            opt_state, params = carry

            def sel(new, old):
                return jnp.where(keep, new, old)

            loss, grads = jax.value_and_grad(adapter.full_loss)(params,
                                                                batch)
            updates, new_opt = optimizer.update(grads, opt_state, params)
            new_p = apply_updates(params, updates)
            carry = (jax.tree.map(sel, new_opt, opt_state),
                     jax.tree.map(sel, new_p, params))
            return carry, jnp.where(keep, loss, 0.0)

        (_, params), losses = jax.lax.scan(
            step, (opt_state0, params0), (cohort_batches, cohort_mask))
        n = jnp.maximum(cohort_mask.sum(), 1)
        return params, losses.sum() / n

    def round_fn(params, batches, weights, step_mask):
        locals_, losses = jax.vmap(local_training, in_axes=(None, 0, 0))(
            params, batches, step_mask)
        new_params, mean_loss = eq1_aggregate(
            locals_, weights, losses, axis=axis,
            locals_shardings=locals_shardings)
        return new_params, {"mean_local_loss": mean_loss,
                            "cohort_losses": losses}

    return round_fn


def make_fl_round_step(adapter: Adapter, optimizer, hp: CurriculumHP, t: int):
    """Legacy entry point (was federated.distributed.make_fl_round_step).

    round_fn(trainable, frozen, batches, weights) with an all-true step
    mask — every cohort runs all E steps of its (C, E, ...) stack.
    """
    program = make_round_program(adapter, optimizer, hp, t)

    def round_fn(trainable, frozen, batches, weights):
        C, E = jax.tree.leaves(batches)[0].shape[:2]
        new_trainable, metrics = program(
            trainable, frozen, batches, weights, jnp.ones((C, E), bool))
        return new_trainable, {"mean_local_loss": metrics["mean_local_loss"]}

    return round_fn


def cohort_batches_specs(cfg, num_cohorts: int, local_steps: int,
                         per_cohort_batch: int, seq: int):
    """ShapeDtypeStruct tree for the (C, E, ...) batch stack (dry-run)."""
    from repro.configs import label_specs, token_inputs

    def stack(sds):
        return jax.ShapeDtypeStruct(
            (num_cohorts, local_steps, *sds.shape), sds.dtype)

    inputs = jax.tree.map(stack, token_inputs(cfg, per_cohort_batch, seq))
    labels = jax.tree.map(stack, label_specs(cfg, per_cohort_batch, seq))
    return {"inputs": inputs, "labels": labels}


# =========================================================================== #
# static-analysis registry: traceable round programs (see repro.analysis)
# =========================================================================== #
def abstract_like(tree):
    """``ShapeDtypeStruct`` tree matching what ``jnp.asarray`` would make of
    ``tree``'s leaves (canonicalized dtypes: f64 -> f32 off-x64) WITHOUT
    materializing any device array — the auditor traces programs on these,
    it never runs them."""
    from jax import dtypes as _dtypes

    def conv(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return jax.ShapeDtypeStruct(
                tuple(x.shape), _dtypes.canonicalize_dtype(x.dtype))
        a = np.asarray(x)
        return jax.ShapeDtypeStruct(
            a.shape, _dtypes.canonicalize_dtype(a.dtype))

    return jax.tree.map(conv, tree)


def shard_abstract(sds_tree, shardings):
    """Attach a NamedSharding tree to a ShapeDtypeStruct tree so ``lower``
    sees the same placements ``device_put`` would commit at run time."""
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        sds_tree, shardings)


@dataclasses.dataclass
class RoundProgramSpec:
    """One traceable round program plus the contracts it must satisfy.

    The backends contribute these via ``trace_specs`` /
    ``full_reference_spec``; ``repro.analysis`` lowers and compiles them
    (``.lower()`` — pure tracing, no execution) to machine-check the
    collective / memory / donation / purity invariants the docs claim.

    kind            : "round" (local training fused with Eq. 1),
                      "local" (no aggregation — zero data-axis collectives
                      allowed), "aggregation" (the bare Eq. 1 seam),
                      "step" (one client step), "reference" (full-model
                      program the per-stage memory peaks must undercut).
    donate_argnums  : donation the runtime *intends* (applied only where
                      ``donation_supported()``) — the donation audit
                      re-lowers with it forced on.
    alias_argnums   : subset of ``donate_argnums`` that MUST alias an
                      output (threaded state); the rest are opportunistic
                      scratch donations (e.g. the batch stack) whose
                      "not usable" is informational.
    n_agg_leaves    : leaf count of the Eq. 1 contraction — bounds the
                      legal number of data-axis all-reduces.
    """

    name: str
    backend: str
    kind: str
    fn: Any
    abstract_args: tuple
    jit_kwargs: Dict[str, Any] = dataclasses.field(default_factory=dict)
    donate_argnums: tuple = ()
    alias_argnums: tuple = ()
    mesh: Any = None
    data_axis: Optional[str] = None
    model_axis: Optional[str] = None
    stage: Optional[int] = None
    n_agg_leaves: int = 0

    def jit(self, *, donate: bool = False, keep_unused: bool = False):
        kw = dict(self.jit_kwargs)
        if donate and self.donate_argnums:
            kw["donate_argnums"] = self.donate_argnums
        if keep_unused:
            kw["keep_unused"] = True
        return jax.jit(self.fn, **kw)

    def lower(self, **kw):
        """Trace the program on its abstract args (never executes)."""
        return self.jit(**kw).lower(*self.abstract_args)


# =========================================================================== #
# runtimes
# =========================================================================== #
@dataclasses.dataclass
class RoundOutcome:
    """What the server needs back from one executed round."""
    params: Any                  # full param tree with stage t merged back
    trainable: Any               # aggregated trainable subtree (upload bytes)
    mean_loss: Any               # |D_c|-weighted mean local loss (device ok)
    cohort_losses: Any           # (C,) per-cohort mean local loss
    num_batches: List[int]       # true local steps per cohort (sim time)
    num_samples: List[float]     # effective per-cohort sample counts
    n_uploads: Optional[int] = None           # cohorts that actually
                                              # delivered a counted update
                                              # (drops step-0 crashes and
                                              # async pending stragglers)
    sim_times: Optional[List[float]] = None   # per-cohort simulated seconds
    round_sim_time: Optional[float] = None    # simulated round wall-clock;
                                              # async: last flush, not the
                                              # slowest straggler


class ClientRuntime:
    """Base: owns the adapter/optimizer/hp triple and per-stage programs.

    ``run_round`` is the server-facing entry (builds the round's data from
    client batchers); ``run_stacked`` executes a pre-materialized
    ``RoundStack`` — the seam the equivalence tests and the throughput
    benchmark drive directly.
    """

    name = "base"

    def __init__(self, adapter: Adapter, optimizer, hp: CurriculumHP):
        self.adapter = adapter
        self.optimizer = optimizer
        self.hp = hp
        self._programs: Dict[int, Any] = {}

    # -- backend hooks ----------------------------------------------------- #
    def _run_stack(self, t: int, trainable, frozen, stack: RoundStack):
        raise NotImplementedError

    def _round_buffer(self) -> Optional[StackBuffer]:
        """Host memory ``run_round`` stacks into, reused round to round;
        ``None`` (fresh arrays) unless the backend consumes the stack
        within the round."""
        return None

    def _lost_round_extras(self, stack: RoundStack,
                           cohorts: Sequence[int]) -> dict:
        """``RoundOutcome`` extras for an all-dropped (lost) round.

        The async backend overrides this to report its virtual clock — a
        lost round it never waited on must not fall back to the server's
        synchronous straggler wall-clock."""
        return {}

    # -- shared driver ----------------------------------------------------- #
    def run_stacked(self, params, t: int, stack: RoundStack):
        """One round on a prepared stack -> (new_trainable, metrics)."""
        if float(np.sum(stack.weights)) <= 0:
            raise ValueError("round has zero total aggregation weight")
        with spans.span("fl.split"):
            frozen, trainable = self.adapter.split_stage(params, t)
        return self._run_stack(t, trainable, frozen, stack)

    def _round_from_stack(self, params, t: int, stack: RoundStack,
                          cohorts: Sequence[int]):
        """Execute one prepared stack -> (new_trainable, metrics, extras).

        ``extras`` merges into the ``RoundOutcome`` (the async backend adds
        its virtual-clock fields here).
        """
        new_trainable, metrics = self.run_stacked(params, t, stack)
        return new_trainable, metrics, {}

    def run_round(self, params, t: int, batchers: Sequence[Batcher],
                  cohorts: Sequence[int], local_epochs: int,
                  faults: Optional[Sequence[Optional[int]]] = None
                  ) -> RoundOutcome:
        """One FL round.  ``faults`` (one entry per cohort, ``None`` = no
        fault) injects mid-round dropout: cohort i's mask row is truncated
        to its first ``faults[i]`` completed steps and its Eq. 1 weight
        scales by the completed fraction (``loader.truncate_step_mask``).
        A round where every cohort crashed before step 0 is a lost round:
        params come back unchanged with a NaN loss.
        """
        buffer = self._round_buffer()
        with spans.span("fl.stack") as s:
            stack = stack_round(batchers, cohorts, local_epochs=local_epochs,
                                buffer=buffer)
            if faults is not None:
                stack = truncate_step_mask(stack, faults)
            if s is not None:
                s.attrs.update(C=stack.num_cohorts, E=stack.max_steps)
            spans.count("stack_bytes", stack.nbytes)
        if float(np.sum(stack.weights)) <= 0:        # all cohorts dropped
            _, trainable = self.adapter.split_stage(params, t)
            return RoundOutcome(
                params=params, trainable=trainable,
                mean_loss=jnp.asarray(float("nan")),
                cohort_losses=jnp.zeros(stack.num_cohorts),
                num_batches=list(stack.num_batches),
                num_samples=[float(w) for w in stack.weights],
                n_uploads=0,
                **self._lost_round_extras(stack, cohorts))
        new_trainable, metrics, extras = self._round_from_stack(
            params, t, stack, cohorts)
        if buffer is not None:
            buffer.hold(new_trainable, metrics)
        extras.setdefault(
            "n_uploads", int(np.count_nonzero(
                np.asarray(stack.weights) > 0)))
        with spans.span("fl.merge"):
            params = self.adapter.merge_stage(params, new_trainable, t)
        return RoundOutcome(
            params=params,
            trainable=new_trainable,
            mean_loss=metrics["mean_local_loss"],
            cohort_losses=metrics["cohort_losses"],
            num_batches=list(stack.num_batches),
            num_samples=[float(w) for w in stack.weights],
            **extras)

    # -- static-analysis registry hooks (repro.analysis) ------------------- #
    def _abstract_stack(self, stack: RoundStack):
        return (abstract_like(stack.batches),
                abstract_like(np.asarray(stack.weights)),
                abstract_like(np.asarray(stack.step_mask)))

    def trace_specs(self, params, t: int,
                    stack: RoundStack) -> List[RoundProgramSpec]:
        """This backend's stage-``t`` programs as traceable specs shaped
        like ``stack`` — the auditor's registry entry point."""
        raise NotImplementedError

    def full_reference_spec(self, params,
                            stack: RoundStack) -> RoundProgramSpec:
        """Full-model (vanilla FedAvg) round on the same stack: the memory
        reference every per-stage peak must undercut."""
        batches, weights, mask = self._abstract_stack(stack)
        model = {"model": params["model"]}
        return RoundProgramSpec(
            name=f"{self.name}/full-model-round", backend=self.name,
            kind="reference",
            fn=make_full_round_program(self.adapter, self.optimizer),
            abstract_args=(abstract_like(model), batches, weights, mask),
            n_agg_leaves=len(jax.tree.leaves(model)))


class SequentialRuntime(ClientRuntime):
    """Reference backend: clients one-by-one, one jitted step per batch.

    Kept as the semantic baseline the array backends must match; per-step
    losses stay on device (no host sync until the server reads the round's
    aggregate).
    """

    name = "sequential"

    def _step(self, t: int):
        if t not in self._programs:
            self._programs[t] = jit_stage_step(
                self.adapter, self.optimizer, self.hp, t)
        return self._programs[t]

    def _run_stack(self, t, trainable, frozen, stack: RoundStack):
        step = self._step(t)
        results, losses = [], []
        for c in range(stack.num_cohorts):
            tr_c = trainable
            opt_state = self.optimizer.init(tr_c)
            cohort_losses = []
            for e in range(stack.max_steps):
                # honor arbitrary masks (e.g. mid-round dropout), not just
                # the True-prefix padding stack_round emits
                if not stack.step_mask[c, e]:
                    continue
                batch = jax.tree.map(lambda x: jnp.asarray(x[c, e]),
                                     stack.batches)
                opt_state, tr_c, metrics = step(opt_state, tr_c, frozen,
                                                batch, trainable)
                cohort_losses.append(metrics["loss"])
            results.append(tr_c)
            losses.append(jnp.stack(cohort_losses).mean() if cohort_losses
                          else jnp.zeros(()))
        new_trainable = agg.weighted_average(results, stack.weights)
        cohort_losses = jnp.stack(losses)
        w = jnp.asarray(stack.weights / stack.weights.sum(), jnp.float32)
        return new_trainable, {"mean_local_loss": (cohort_losses * w).sum(),
                               "cohort_losses": cohort_losses}

    def run_round(self, params, t, batchers, cohorts, local_epochs,
                  faults=None):
        """Current server semantics: iterate each client's own Batcher.

        With ``faults`` the round routes through the base stacked path —
        the sequential ``_run_stack`` honors arbitrary (truncated) masks,
        so dropout semantics stay identical across backends.
        """
        if faults is not None:
            return ClientRuntime.run_round(self, params, t, batchers,
                                           cohorts, local_epochs, faults)
        with spans.span("fl.split"):
            frozen, trainable = self.adapter.split_stage(params, t)
        step = self._step(t)
        results, losses, num_batches, num_samples = [], [], [], []
        for cid in cohorts:
            res = run_local_training(step, self.optimizer, trainable, frozen,
                                     batchers[cid], local_epochs,
                                     global_ref=trainable)
            results.append(res.trainable)
            losses.append(res.mean_loss)
            num_batches.append(res.num_batches)
            num_samples.append(res.num_samples)
        if float(np.sum(num_samples)) <= 0:
            # zero total aggregation weight = the documented lost round
            # (params unchanged, NaN loss) — the same outcome the base-class
            # stacked path produces, instead of a ValueError from
            # stacked_weighted_average / a 0/0 in the loss weights
            return RoundOutcome(
                params=params, trainable=trainable,
                mean_loss=jnp.asarray(float("nan")),
                cohort_losses=jnp.zeros(len(cohorts)),
                num_batches=num_batches,
                num_samples=[float(n) for n in num_samples],
                n_uploads=0)
        new_trainable = agg.weighted_average(results, num_samples)
        cohort_losses = jnp.stack([jnp.asarray(l) for l in losses])
        w = np.asarray(num_samples, np.float32)
        w = jnp.asarray(w / w.sum())
        with spans.span("fl.merge"):
            params = self.adapter.merge_stage(params, new_trainable, t)
        return RoundOutcome(
            params=params,
            trainable=new_trainable,
            mean_loss=(cohort_losses * w).sum(),
            cohort_losses=cohort_losses,
            num_batches=num_batches,
            num_samples=num_samples)

    # -- static-analysis registry ------------------------------------------ #
    def trace_specs(self, params, t, stack):
        frozen, trainable = self.adapter.split_stage(params, t)
        tr, fr = abstract_like(trainable), abstract_like(frozen)
        batch = abstract_like(
            jax.tree.map(lambda x: x[0, 0], stack.batches))
        opt = jax.eval_shape(self.optimizer.init, tr)
        return [RoundProgramSpec(
            name=f"sequential/stage{t}/step", backend=self.name,
            kind="step",
            fn=make_stage_step(self.adapter, self.optimizer, self.hp, t),
            abstract_args=(opt, tr, fr, batch, tr),
            donate_argnums=(0,), alias_argnums=(0,), stage=t)]

    def full_reference_spec(self, params, stack):
        model = {"model": params["model"]}
        p = abstract_like(model)
        opt = jax.eval_shape(self.optimizer.init, p)
        batch = abstract_like(
            jax.tree.map(lambda x: x[0, 0], stack.batches))
        return RoundProgramSpec(
            name="sequential/full-model-step", backend=self.name,
            kind="reference",
            fn=make_full_step(self.adapter, self.optimizer),
            abstract_args=(opt, p, batch),
            donate_argnums=(0,), alias_argnums=(0,))


class VectorizedRuntime(ClientRuntime):
    """One jitted program per stage: vmapped scan + fused Eq. 1 einsum.

    The (C, E, ...) batch stack is donated to the program — it is rebuilt
    from host data every round, so XLA may reuse its buffers in place.
    ``run_round`` builds that stack in one ``StackBuffer`` the runtime
    keeps: the next round waits for this round's program before it writes
    the buffer again, which costs the server nothing, since it syncs on
    every round's loss.
    """

    name = "vectorized"

    def __init__(self, adapter: Adapter, optimizer, hp: CurriculumHP):
        super().__init__(adapter, optimizer, hp)
        self._buffer = StackBuffer()

    def _round_buffer(self) -> StackBuffer:
        return self._buffer

    def _program(self, t: int):
        if t not in self._programs:
            from repro.core.progressive import donation_supported
            self._programs[t] = jax.jit(
                make_round_program(self.adapter, self.optimizer, self.hp, t),
                donate_argnums=(2,) if donation_supported() else ())
        return self._programs[t]

    def _device_stack(self, stack: RoundStack):
        with spans.span("fl.to_device"):
            spans.count("h2d_bytes", stack.nbytes)
            return (jax.tree.map(jnp.asarray, stack.batches),
                    jnp.asarray(stack.weights),
                    jnp.asarray(stack.step_mask))

    def _run_stack(self, t, trainable, frozen, stack: RoundStack):
        batches, weights, mask = self._device_stack(stack)
        program = self._program(t)
        with spans.span("fl.dispatch"):
            return program(trainable, frozen, batches, weights, mask)

    # -- static-analysis registry ------------------------------------------ #
    def trace_specs(self, params, t, stack):
        frozen, trainable = self.adapter.split_stage(params, t)
        batches, weights, mask = self._abstract_stack(stack)
        return [RoundProgramSpec(
            name=f"{self.name}/stage{t}/round", backend=self.name,
            kind="round",
            fn=make_round_program(self.adapter, self.optimizer, self.hp, t),
            abstract_args=(abstract_like(trainable), abstract_like(frozen),
                           batches, weights, mask),
            donate_argnums=(2,), stage=t,
            n_agg_leaves=len(jax.tree.leaves(trainable)))]


# =========================================================================== #
# shared 2-D (data, model) mesh plumbing — used by the sharded and async
# backends so both place round inputs/outputs identically
# =========================================================================== #
def resolve_round_mesh(mesh, model_parallel: int, model_axis: str = "model"):
    """Build (``make_host_mesh``) or validate an explicit round mesh.

    An explicit mesh whose ``model_axis`` size contradicts ``model_parallel``
    is rejected — it would silently run with the mesh's sharding, not the
    request."""
    if mesh is None:
        from repro.launch.mesh import make_host_mesh
        return make_host_mesh(model_parallel)
    if (model_parallel != 1
            and dict(mesh.shape).get(model_axis, 1) != model_parallel):
        raise ValueError(
            f"model_parallel={model_parallel} contradicts the explicit "
            f"mesh (shape {dict(mesh.shape)}): pass one or the other "
            f"— a mesh whose '{model_axis}' axis disagrees would "
            f"silently run with the mesh's sharding, not the request")
    return mesh


class StagePlacements:
    """Cached per-stage NamedSharding placements on a (data, model) mesh.

    One instance per runtime: ``placements(t)`` returns the
    ``(trainable, frozen, cohort-axis)`` shardings for stage ``t`` (fitted
    from the adapter's logical ParamDef specs), and ``place_inputs``
    commits a round's inputs to them — params/optimizer seeds onto the
    model axis, the cohort stack onto the data axis (batch leaves via
    ``batch_spec``)."""

    def __init__(self, adapter: Adapter, mesh, axis: str = "data"):
        self.adapter = adapter
        self.mesh = mesh
        self.axis = axis
        self._cache: Dict[int, Any] = {}

    def placements(self, t: int):
        if t not in self._cache:
            from repro.launch.sharding import cohort_sharding, tree_shardings
            frozen_defs, trainable_defs = self.adapter.split_stage(
                self.adapter.defs, t)
            self._cache[t] = (tree_shardings(trainable_defs, self.mesh),
                              tree_shardings(frozen_defs, self.mesh),
                              cohort_sharding(self.mesh, self.axis))
        return self._cache[t]

    def stacked_locals(self, t: int):
        """Shardings for per-cohort local weights: (C, *param) leaves place
        as P(data, *model_spec)."""
        from repro.launch.sharding import stacked_tree_shardings
        return stacked_tree_shardings(
            self.adapter.split_stage(self.adapter.defs, t)[1],
            self.mesh, self.axis)

    def place_inputs(self, t, trainable, frozen, batches, weights, mask):
        from jax.sharding import NamedSharding
        from repro.launch.sharding import batch_spec
        tr_sh, fr_sh, cohort_sh = self.placements(t)
        batches = jax.tree.map(
            lambda x: jax.device_put(x, NamedSharding(
                self.mesh, batch_spec(x.shape, self.mesh))), batches)
        weights = (None if weights is None
                   else jax.device_put(weights, cohort_sh))
        return (jax.device_put(trainable, tr_sh),
                jax.device_put(frozen, fr_sh), batches, weights,
                jax.device_put(mask, cohort_sh))


def pad_cohorts(batches, weights, mask, shards: int):
    """Pad the cohort axis to a multiple of the data-axis size with
    zero-weight, fully-masked cohorts (exact no-ops on every path)."""
    C = weights.shape[0]
    pad = (-C) % shards
    if pad:
        batches = jax.tree.map(
            lambda x: jnp.concatenate(
                [x, jnp.zeros((pad, *x.shape[1:]), x.dtype)]), batches)
        weights = jnp.concatenate([weights, jnp.zeros(pad, weights.dtype)])
        mask = jnp.concatenate(
            [mask, jnp.zeros((pad, mask.shape[1]), bool)])
    return batches, weights, mask


class ShardedRuntime(VectorizedRuntime):
    """The vectorized program over a 2-D ``(data, model)`` launch mesh.

    The cohort axis always shards over ``axis`` (the mesh's "data" axis).
    What happens along the model axis depends on the mesh:

    * ``model`` axis of size 1 (the default host mesh) — stage params stay
      replicated and the program runs under ``shard_map`` with the Eq. 1
      aggregation as one explicit ``psum`` over "data": FL's single
      per-round collective, the one the roofline dry-run measures.
    * ``model`` axis > 1 (``model_parallel=k`` or an explicit 2-D mesh) —
      stage params, optimizer state, and the per-cohort local weights
      additionally shard over "model" using the adapter's logical ParamDef
      specs (``launch.sharding.fit_spec`` / ``tree_shardings`` — the same
      specs the production mesh uses), so the per-device trainable block
      shrinks by ~1/k and paper-scale clients fit where replication does
      not.  The program runs under GSPMD (``jax.jit`` with NamedSharding
      placements, as ``launch.steps`` does): the Eq. 1 contraction still
      lowers to a single all-reduce over "data" only — each model shard
      owns its slice of the aggregate, no gather — and batch leaves pick up
      ``batch_spec`` placement on the cohort axis.

    Cohort counts that don't divide the data-axis size are padded with
    zero-weight, fully-masked cohorts.
    """

    name = "sharded"

    def __init__(self, adapter, optimizer, hp, *, mesh=None,
                 axis: str = "data", model_axis: str = "model",
                 model_parallel: int = 1):
        super().__init__(adapter, optimizer, hp)
        self.mesh = resolve_round_mesh(mesh, model_parallel, model_axis)
        self.axis = axis
        self.model_axis = model_axis
        self._place = StagePlacements(adapter, self.mesh, axis)

    @property
    def _shards(self) -> int:
        return self.mesh.shape[self.axis]

    @property
    def model_shards(self) -> int:
        return dict(self.mesh.shape).get(self.model_axis, 1)

    def _program(self, t: int):
        if t not in self._programs:
            from repro.core.progressive import donation_supported
            donate = (2,) if donation_supported() else ()
            if self.model_shards > 1:
                self._programs[t] = jax.jit(self._build_2d(t),
                                            out_shardings=self._out_sh(t),
                                            donate_argnums=donate)
            else:
                self._programs[t] = jax.jit(self._build_1d(t),
                                            donate_argnums=donate)
        return self._programs[t]

    def _build_1d(self, t: int):
        """Replicated-params path: explicit psum under ``shard_map``."""
        from jax.sharding import PartitionSpec as P
        program = make_round_program(self.adapter, self.optimizer,
                                     self.hp, t, axis=self.axis)
        return jax.shard_map(
            program, mesh=self.mesh,
            in_specs=(P(), P(), P(self.axis), P(self.axis),
                      P(self.axis)),
            out_specs=(P(), {"mean_local_loss": P(),
                             "cohort_losses": P(self.axis)}),
            check_vma=False)

    def _build_2d(self, t: int):
        """Model-sharded path: GSPMD over the (data, model) mesh."""
        return make_round_program(self.adapter, self.optimizer, self.hp, t,
                                  locals_shardings=self._place.stacked_locals(t))

    def _out_sh(self, t: int):
        from repro.launch.sharding import replicated
        tr_sh, _, cohort_sh = self._place.placements(t)
        return (tr_sh, {"mean_local_loss": replicated(self.mesh),
                        "cohort_losses": cohort_sh})

    def _device_stack(self, stack: RoundStack):
        batches, weights, mask = super()._device_stack(stack)
        return pad_cohorts(batches, weights, mask, self._shards)

    def _run_stack(self, t, trainable, frozen, stack: RoundStack):
        batches, weights, mask = self._device_stack(stack)
        program = self._program(t)
        if self.model_shards > 1:
            trainable, frozen, batches, weights, mask = \
                self._place.place_inputs(t, trainable, frozen, batches,
                                         weights, mask)
        with spans.span("fl.dispatch"):
            new_trainable, metrics = program(trainable, frozen, batches,
                                             weights, mask)
        C = stack.num_cohorts
        metrics = dict(metrics,
                       cohort_losses=metrics["cohort_losses"][:C])
        return new_trainable, metrics

    # -- static-analysis registry ------------------------------------------ #
    def _abstract_stack(self, stack: RoundStack):
        batches, weights, mask = super()._abstract_stack(stack)
        pad = (-stack.num_cohorts) % self._shards

        def grow(s):
            return jax.ShapeDtypeStruct((s.shape[0] + pad, *s.shape[1:]),
                                        s.dtype)

        if pad:
            batches = jax.tree.map(grow, batches)
            weights, mask = grow(weights), grow(mask)
        return batches, weights, mask

    def _abstract_stack_placed(self, t: int, stack: RoundStack):
        """Abstract stack carrying the shardings ``place_inputs`` commits."""
        from jax.sharding import NamedSharding

        from repro.launch.sharding import batch_spec
        batches, weights, mask = self._abstract_stack(stack)
        _, _, cohort_sh = self._place.placements(t)
        batches = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=NamedSharding(
                    self.mesh, batch_spec(s.shape, self.mesh))), batches)
        weights = jax.ShapeDtypeStruct(weights.shape, weights.dtype,
                                       sharding=cohort_sh)
        mask = jax.ShapeDtypeStruct(mask.shape, mask.dtype,
                                    sharding=cohort_sh)
        return batches, weights, mask

    def _seam_spec(self, t: int, trainable, n_cohorts: int):
        """The bare Eq. 1 aggregation over stacked per-cohort locals — the
        spec whose lowered module must contain ONLY data-axis all-reduces
        (one per aggregated leaf plus the scalar normalizer/loss)."""
        from jax.sharding import PartitionSpec as P
        tr_sds = abstract_like(trainable)
        stacked = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((n_cohorts, *s.shape), s.dtype),
            tr_sds)
        vec = jax.ShapeDtypeStruct((n_cohorts,), jnp.float32)
        n_leaves = len(jax.tree.leaves(trainable))
        if self.model_shards > 1:
            from repro.launch.sharding import replicated
            locals_sh = self._place.stacked_locals(t)
            tr_sh, _, cohort_sh = self._place.placements(t)
            stacked = shard_abstract(stacked, locals_sh)

            def seam(locals_, weights, losses):
                return eq1_aggregate(locals_, weights, losses,
                                     locals_shardings=locals_sh)

            return RoundProgramSpec(
                name=f"sharded2d/stage{t}/eq1-seam", backend=self.name,
                kind="aggregation", fn=seam,
                abstract_args=(
                    stacked,
                    jax.ShapeDtypeStruct(vec.shape, vec.dtype,
                                         sharding=cohort_sh),
                    jax.ShapeDtypeStruct(vec.shape, vec.dtype,
                                         sharding=cohort_sh)),
                jit_kwargs={"out_shardings": (tr_sh,
                                              replicated(self.mesh))},
                mesh=self.mesh, data_axis=self.axis,
                model_axis=self.model_axis, stage=t,
                n_agg_leaves=n_leaves)
        seam = jax.shard_map(
            lambda l, w, s: eq1_aggregate(l, w, s, axis=self.axis),
            mesh=self.mesh,
            in_specs=(P(self.axis), P(self.axis), P(self.axis)),
            out_specs=(P(), P()), check_vma=False)
        return RoundProgramSpec(
            name=f"sharded1d/stage{t}/eq1-seam", backend=self.name,
            kind="aggregation", fn=seam,
            abstract_args=(stacked, vec, vec),
            mesh=self.mesh, data_axis=self.axis, stage=t,
            n_agg_leaves=n_leaves)

    def trace_specs(self, params, t, stack):
        frozen, trainable = self.adapter.split_stage(params, t)
        n_leaves = len(jax.tree.leaves(trainable))
        if self.model_shards > 1:
            batches, weights, mask = self._abstract_stack_placed(t, stack)
            tr_sh, fr_sh, _ = self._place.placements(t)
            round_spec = RoundProgramSpec(
                name=f"sharded2d/stage{t}/round", backend=self.name,
                kind="round", fn=self._build_2d(t),
                abstract_args=(
                    shard_abstract(abstract_like(trainable), tr_sh),
                    shard_abstract(abstract_like(frozen), fr_sh),
                    batches, weights, mask),
                jit_kwargs={"out_shardings": self._out_sh(t)},
                donate_argnums=(2,), mesh=self.mesh, data_axis=self.axis,
                model_axis=self.model_axis, stage=t,
                n_agg_leaves=n_leaves)
        else:
            batches, weights, mask = self._abstract_stack(stack)
            round_spec = RoundProgramSpec(
                name=f"sharded1d/stage{t}/round", backend=self.name,
                kind="round", fn=self._build_1d(t),
                abstract_args=(abstract_like(trainable),
                               abstract_like(frozen), batches, weights,
                               mask),
                donate_argnums=(2,), mesh=self.mesh, data_axis=self.axis,
                stage=t, n_agg_leaves=n_leaves)
        return [round_spec,
                self._seam_spec(t, trainable, weights.shape[0])]

    def full_reference_spec(self, params, stack):
        spec = super().full_reference_spec(params, stack)
        if self.model_shards > 1:
            # place the full-model reference on the same mesh: params and
            # locals model-shard exactly as the per-stage programs do, so
            # the peak-bytes comparison is like for like
            from jax.sharding import NamedSharding

            from repro.launch.sharding import (batch_spec, replicated,
                                               stacked_tree_shardings,
                                               tree_shardings)
            model_defs = {"model": self.adapter.defs["model"]}
            p_sh = tree_shardings(model_defs, self.mesh)
            locals_sh = stacked_tree_shardings(model_defs, self.mesh,
                                               self.axis)
            fn = make_full_round_program(self.adapter, self.optimizer,
                                         locals_shardings=locals_sh)
            p, batches, weights, mask = spec.abstract_args
            _, _, cohort_sh = self._place.placements(0)
            batches = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(
                    s.shape, s.dtype, sharding=NamedSharding(
                        self.mesh, batch_spec(s.shape, self.mesh))),
                batches)
            weights = jax.ShapeDtypeStruct(weights.shape, weights.dtype,
                                           sharding=cohort_sh)
            mask = jax.ShapeDtypeStruct(mask.shape, mask.dtype,
                                        sharding=cohort_sh)
            spec = dataclasses.replace(
                spec, fn=fn,
                abstract_args=(shard_abstract(p, p_sh), batches, weights,
                               mask),
                jit_kwargs={"out_shardings": (
                    p_sh, {"mean_local_loss": replicated(self.mesh),
                           "cohort_losses": cohort_sh})},
                mesh=self.mesh, data_axis=self.axis,
                model_axis=self.model_axis)
        else:
            spec = dataclasses.replace(spec, mesh=self.mesh,
                                       data_axis=None)
        return spec


# =========================================================================== #
# buffered-async (FedBuff-style) backend
# =========================================================================== #
@dataclasses.dataclass
class FlushPlan:
    """Arrival-order schedule for buffered-async flushes.

    flushes    : delivery-index arrays, one per server flush, in arrival
                 order (staleness is NOT planned here — it is true
                 versions-behind, computed per entry at flush time by
                 ``AsyncServerState.schedule``)
    pending    : deliveries still in the buffer when the round closes; they
                 stay in the server's persistent buffer and flush in a
                 later round
    round_time : simulated wall-clock of the last flush (0.0 when nothing
                 flushed) — the async round ends there, not at the slowest
                 straggler
    """
    flushes: List[np.ndarray]
    pending: np.ndarray
    round_time: float


def plan_flushes(sim_times: Sequence[float], buffer_size: int) -> FlushPlan:
    """Schedule FedBuff flushes on a virtual clock.

    Deliveries arrive at ``sim_times``; the server flushes its buffer every
    ``buffer_size`` arrivals (0 means "everything delivered" — one
    synchronous flush).  Arrivals after the last full buffer stay pending —
    with fewer than ``buffer_size`` arrivals nothing flushes at all (the
    persistent buffer carries them into the next round; the old one-shot
    simulation clamped K to the arrival count and force-flushed).  Ties
    break by position (stable sort) so the plan is deterministic.
    """
    t = np.asarray(sim_times, np.float64)
    if t.ndim != 1 or t.size == 0:
        raise ValueError(f"sim_times must be a non-empty 1-D sequence; "
                         f"got shape {t.shape}")
    if t.min() < 0:
        raise ValueError(f"negative sim_time {t.min()}")
    order = np.argsort(t, kind="stable")
    C = t.size
    K = C if buffer_size <= 0 else int(buffer_size)
    n_full = C // K
    flushes = [order[j * K:(j + 1) * K] for j in range(n_full)]
    pending = order[n_full * K:]
    round_time = float(t[flushes[-1][-1]]) if flushes else 0.0
    return FlushPlan(flushes=flushes, pending=pending,
                     round_time=round_time)


@dataclasses.dataclass
class BufferEntry:
    """One delivered-but-unflushed client delta in the async server buffer.

    The delta survives round boundaries: it is aggregated (exactly once)
    when its flush comes, however many rounds later that is.
    """
    delta: Any            # f32 trainable-subtree delta vs pull-time params
    weight: float         # Eq. 1 sample weight (completed-step scaled)
    loss: Any             # client mean local loss (0-d device array)
    pulled_version: int   # server version when the client pulled params
    arrival_time: float   # ABSOLUTE virtual-clock delivery time
    stage: int            # progressive stage the delta trains
    cohort: int           # cohort index within its round (diagnostics)


@dataclasses.dataclass
class Flush:
    """One server flush: the entries it aggregates, their true staleness
    (server versions elapsed since each entry's pull — entries in the SAME
    flush can differ), the server version the flush updates, and its
    absolute virtual time."""
    entries: List[BufferEntry]
    staleness: np.ndarray
    version: int
    time: float


class AsyncServerState:
    """Host-side cross-round state of the buffered-async server.

    entries : deliveries waiting for a flush — they persist across
              ``run_round`` calls instead of being dropped at round close
    version : monotonically increasing server parameter version; one bump
              per flush.  True staleness of an entry at flush time is
              ``version - entry.pulled_version`` (versions-behind, not the
              old flush-index proxy).
    clock   : absolute virtual time of the last flush (rounds are open
              intervals on this clock; new pulls happen at ``clock``)
    """

    def __init__(self):
        self.entries: List[BufferEntry] = []
        self.version: int = 0
        self.clock: float = 0.0

    def __len__(self) -> int:
        return len(self.entries)

    def evict_stale(self, max_staleness: Optional[int]) -> List[BufferEntry]:
        """Drop (and return) entries more than ``max_staleness`` server
        versions behind — the only way a delivered delta ever leaves the
        buffer unaggregated, and only when the cap is explicitly set.

        The cap is enforced at ROUND OPEN, against the version at that
        moment: an entry that survives it can still aggregate a few
        versions past the cap if earlier flushes of its own round bump the
        version first (bounded by that round's flush count, and the
        staleness discount keeps shrinking it) — it just cannot linger into
        the next round."""
        if max_staleness is None:
            return []
        keep, evicted = [], []
        for e in self.entries:
            dest = (evicted if self.version - e.pulled_version
                    > max_staleness else keep)
            dest.append(e)
        self.entries = keep
        return evicted

    def drop_retired_stages(self, current_stage: int) -> List[BufferEntry]:
        """Drop (and return) pending entries of stages BEFORE
        ``current_stage``.

        Only valid under a monotone stage schedule (``revisits_stages``
        False — sequential / plateau): a stage the schedule moved past will
        never train again, so its pending deltas are permanently
        unusable — without this they would sit in the buffer (and hold
        their device arrays) for the rest of the run.  Round-robin
        schedules revisit stages and must NOT call this."""
        keep = [e for e in self.entries if e.stage >= current_stage]
        dropped = [e for e in self.entries if e.stage < current_stage]
        self.entries = keep
        return dropped

    def schedule(self, new_entries: Sequence[BufferEntry], buffer_size: int,
                 stage: int) -> List[Flush]:
        """Admit this round's deliveries and plan its flushes.

        Pending entries of the SAME stage merge with the new arrivals in
        delivery order; entries of other stages stay buffered untouched
        (their trainable subtree does not exist in this round — they flush
        when their stage next runs).  Every flush bumps ``version``; per-
        entry staleness is the version gap at that moment, so one flush can
        mix fresh deliveries with multi-round-old stragglers at different
        discounts.  Flushed entries leave the buffer; leftovers stay.
        """
        eligible = [e for e in self.entries if e.stage == stage]
        held = [e for e in self.entries if e.stage != stage]
        eligible.extend(new_entries)
        if not eligible:
            return []
        plan = plan_flushes([e.arrival_time for e in eligible], buffer_size)
        flushes = []
        for idx in plan.flushes:
            group = [eligible[i] for i in idx]
            staleness = np.asarray(
                [self.version - e.pulled_version for e in group], int)
            flushes.append(Flush(entries=group, staleness=staleness,
                                 version=self.version,
                                 time=float(group[-1].arrival_time)))
            self.version += 1
        self.entries = held + [eligible[i] for i in plan.pending]
        if flushes:
            self.clock = max(self.clock, flushes[-1].time)
        return flushes

    # -- checkpoint/resume seam: exact serialization of the buffer --------- #
    def state_dict(self):
        """``(arrays, meta)`` for the flat-path checkpoint store.

        The ragged cross-stage ``BufferEntry`` list serializes as one
        *stacked* delta pytree per stage (entries of the same stage share a
        trainable-subtree structure) plus per-entry
        weight/loss/pulled_version/arrival_time/cohort arrays; ``meta``
        carries the version counter, the absolute clock, and the exact
        buffer order as a per-entry stage list (order matters — flush
        planning breaks arrival-time ties by buffer position).  Every entry
        must hold a materialized delta, which is always true between
        ``run_round`` calls (only mid-round does a fresh entry briefly use
        the shared stacked-deltas array).
        """
        for e in self.entries:
            if e.delta is None:
                raise ValueError(
                    "cannot serialize AsyncServerState mid-round: a buffer "
                    "entry has no materialized delta")
        order = [int(e.stage) for e in self.entries]
        arrays = {}
        for t in sorted(set(order)):
            es = [e for e in self.entries if e.stage == t]
            arrays[f"stage_{t}"] = {
                "delta": jax.tree.map(
                    lambda *xs: np.stack([np.asarray(x) for x in xs]),
                    *[e.delta for e in es]),
                "weight": np.asarray([e.weight for e in es], np.float64),
                "loss": np.asarray([np.asarray(e.loss) for e in es],
                                   np.float32),
                "pulled_version": np.asarray([e.pulled_version for e in es],
                                             np.int64),
                "arrival_time": np.asarray([e.arrival_time for e in es],
                                           np.float64),
                "cohort": np.asarray([e.cohort for e in es], np.int64),
            }
        meta = {"version": int(self.version), "clock": float(self.clock),
                "stages": order}
        return arrays, meta

    @classmethod
    def arrays_like(cls, adapter, params, meta):
        """Structure template (``ShapeDtypeStruct`` leaves) matching
        ``state_dict``'s arrays for ``checkpoint.load_checkpoint`` — built
        from the adapter's per-stage trainable subtree shapes and the
        checkpointed per-entry stage list."""
        counts: Dict[int, int] = {}
        for t in meta["stages"]:
            counts[int(t)] = counts.get(int(t), 0) + 1
        like = {}
        for t, n in sorted(counts.items()):
            trainable = adapter.split_stage(params, t)[1]
            like[f"stage_{t}"] = {
                "delta": jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct((n,) + tuple(np.shape(x)),
                                                   jnp.float32), trainable),
                "weight": jax.ShapeDtypeStruct((n,), np.dtype(np.float64)),
                "loss": jax.ShapeDtypeStruct((n,), np.dtype(np.float32)),
                "pulled_version": jax.ShapeDtypeStruct((n,),
                                                       np.dtype(np.int64)),
                "arrival_time": jax.ShapeDtypeStruct((n,),
                                                     np.dtype(np.float64)),
                "cohort": jax.ShapeDtypeStruct((n,), np.dtype(np.int64)),
            }
        return like

    @classmethod
    def from_state_dict(cls, meta, arrays) -> "AsyncServerState":
        """Rebuild the exact buffer: same entries, same order, same version
        counter and absolute clock as at ``state_dict`` time."""
        state = cls()
        state.version = int(meta["version"])
        state.clock = float(meta["clock"])
        cursor: Dict[int, int] = {}
        for s in meta["stages"]:
            s = int(s)
            i = cursor.get(s, 0)
            cursor[s] = i + 1
            g = arrays[f"stage_{s}"]
            state.entries.append(BufferEntry(
                delta=jax.tree.map(lambda x: x[i], g["delta"]),
                weight=float(np.asarray(g["weight"])[i]),
                loss=g["loss"][i],
                pulled_version=int(np.asarray(g["pulled_version"])[i]),
                arrival_time=float(np.asarray(g["arrival_time"])[i]),
                stage=s,
                cohort=int(np.asarray(g["cohort"])[i])))
        return state


class AsyncBufferedRuntime(ClientRuntime):
    """Stateful FedBuff-style buffered-async server on a simulated clock.

    Each ``run_round`` call opens at the server's current virtual clock and
    version: selected cohorts pull the round's params (stamping
    ``pulled_version``) and deliver their deltas ``num_batches / speed``
    later on the absolute clock.  The server flushes every K deliveries
    (``buffer_size``; 0 = everything delivered this round): a flush
    aggregates the sample-weighted buffer deltas scaled by ``server_lr``
    and each entry's OWN staleness discount — staleness is true
    versions-behind (``server_version - pulled_version``), so a flush can
    mix a fresh delivery with a straggler pulled several rounds (and
    server versions) ago.  Every flush bumps the server version.

    Deliveries past the last full buffer stay **pending in the persistent
    ``AsyncServerState`` buffer and aggregate in a later round** — the
    one-shot simulation used to drop them, systematically biasing Eq. 1
    toward fast clients.  The round's simulated wall-clock is the span from
    round open to the last flush (0 when nothing flushed); the async
    speedup over the synchronous barrier comes from never waiting for the
    straggler tail.  Zero-weight cohorts (clients that crashed before
    completing a single step) never deliver: they take no buffer slot and
    consume no staleness level.  Pending entries whose progressive stage
    differs from the current round's stay buffered until their stage runs
    again (``max_staleness`` evicts entries more than that many versions
    behind — the only sanctioned drop, off by default).

    On a fresh server with K = cohort size, the single flush at staleness 0
    reproduces the synchronous ``VectorizedRuntime`` round (base + sum of
    weight-normalized deltas == the Eq. 1 average).  With
    ``model_parallel > 1`` local training runs under GSPMD on the same
    (data, model) mesh placements as ``ShardedRuntime`` — per-cohort local
    weights shard ``P(data, *model_spec)`` and buffered flush aggregation
    inherits the model sharding, so per-device trainable bytes shrink by
    ~1/k exactly as on the synchronous 2-D path.
    """

    name = "async"

    def __init__(self, adapter, optimizer, hp, *, buffer_size: int = 0,
                 staleness_schedule: str = "polynomial",
                 staleness_alpha: float = 0.5, server_lr: float = 1.0,
                 client_speeds: Optional[Dict[int, float]] = None,
                 max_staleness: Optional[int] = None,
                 mesh=None, model_parallel: int = 1, axis: str = "data",
                 model_axis: str = "model"):
        super().__init__(adapter, optimizer, hp)
        agg.staleness_discount(np.zeros(1), staleness_schedule,
                               staleness_alpha)    # validate eagerly
        self.buffer_size = int(buffer_size)
        self.staleness_schedule = staleness_schedule
        self.staleness_alpha = float(staleness_alpha)
        self.server_lr = float(server_lr)
        self.client_speeds = client_speeds
        self.max_staleness = (None if max_staleness is None
                              else int(max_staleness))
        self.axis = axis
        self.model_axis = model_axis
        if mesh is not None or model_parallel != 1:
            self.mesh = resolve_round_mesh(mesh, model_parallel, model_axis)
            self._place = StagePlacements(adapter, self.mesh, axis)
        else:
            self.mesh = None
            self._place = None
        self.state = AsyncServerState()

    @property
    def model_shards(self) -> int:
        return (1 if self.mesh is None
                else dict(self.mesh.shape).get(self.model_axis, 1))

    def reset_state(self):
        """Fresh server: empty buffer, version 0, clock 0."""
        self.state = AsyncServerState()

    def load_server_state(self, state: AsyncServerState):
        """Install a restored ``AsyncServerState``.  On a 2-D mesh the
        carried deltas are re-committed to the stage's model-sharded
        placements so a resumed run keeps the per-device-bytes contract
        (and the exact GSPMD program layout) of the original run."""
        if self.mesh is not None:
            for e in state.entries:
                e.delta = jax.device_put(
                    e.delta, self._place.placements(e.stage)[0])
        self.state = state

    def _program(self, t: int):
        if t not in self._programs:
            from repro.core.progressive import donation_supported
            donate = (2,) if donation_supported() else ()
            local_fn = make_local_program(self.adapter, self.optimizer,
                                          self.hp, t)
            if self.mesh is not None:
                # GSPMD: same placements as the sharded backend's 2-D round
                _, _, cohort_sh = self._place.placements(t)
                self._programs[t] = jax.jit(
                    local_fn,
                    out_shardings=(self._place.stacked_locals(t), cohort_sh),
                    donate_argnums=donate)
            else:
                self._programs[t] = jax.jit(local_fn, donate_argnums=donate)
        return self._programs[t]

    def cohort_sim_times(self, stack: RoundStack,
                         cohorts: Optional[Sequence[int]] = None
                         ) -> np.ndarray:
        """Simulated delivery durations: completed steps / client speed.

        ``client_speeds`` is either an explicit ``{client_id: speed}`` dict
        or a fleet-like object exposing vectorized ``speeds(ids)`` — the
        streaming path, so a 10^6-device population never materializes a
        speed table on the runtime."""
        steps = np.asarray(stack.num_batches, np.float64)
        if self.client_speeds is None or cohorts is None:
            return steps
        if hasattr(self.client_speeds, "speeds"):
            speeds = np.asarray(self.client_speeds.speeds(list(cohorts)),
                                np.float64)
        else:
            speeds = np.asarray([self.client_speeds.get(c, 1.0)
                                 for c in cohorts], np.float64)
        return steps / np.maximum(speeds, 1e-9)

    def run_stacked(self, params, t: int, stack: RoundStack, *,
                    sim_times: Optional[Sequence[float]] = None):
        """One buffered-async round on a prepared stack.

        Stateful: advances the server's persistent buffer/version/clock
        (``reset_state`` for a fresh server).  ``sim_times`` are per-cohort
        delivery DURATIONS from round open (default: true step counts, unit
        speed).  Metrics add the virtual-clock fields: ``staleness`` (per
        cohort of THIS round's stack, -1 = pending or crashed),
        ``n_pending``, ``n_carried``, ``n_evicted``, ``server_version``,
        and ``sim_round_time``.
        """
        if float(np.sum(stack.weights)) <= 0:
            raise ValueError("round has zero total aggregation weight")
        with spans.span("fl.split"):
            frozen, trainable = self.adapter.split_stage(params, t)
        return self._run_stack(t, trainable, frozen, stack,
                               sim_times=sim_times)

    def _local_training(self, t, trainable, frozen, stack: RoundStack):
        """Run the cohort-vmapped local program; returns (trainable as
        placed, (C,) locals stack, (C,) losses) with any mesh padding
        already stripped from the metrics axis."""
        with spans.span("fl.to_device"):
            spans.count("h2d_bytes", stack.nbytes)
            batches = jax.tree.map(jnp.asarray, stack.batches)
            mask = jnp.asarray(stack.step_mask)
        if self.mesh is not None:
            batches, _, mask = pad_cohorts(
                batches, jnp.asarray(stack.weights), mask,
                self.mesh.shape[self.axis])
            trainable, frozen, batches, _, mask = self._place.place_inputs(
                t, trainable, frozen, batches, None, mask)
        program = self._program(t)
        with spans.span("fl.dispatch"):
            locals_, losses = program(trainable, frozen, batches, mask)
        return trainable, locals_, losses

    def _run_stack(self, t, trainable, frozen, stack: RoundStack, *,
                   sim_times=None):
        C = stack.num_cohorts
        weights = np.asarray(stack.weights, np.float64)
        times = np.asarray(self.cohort_sim_times(stack)
                           if sim_times is None else sim_times, np.float64)
        trainable, locals_, losses = self._local_training(
            t, trainable, frozen, stack)

        # deltas against the pull-time params, accumulated in f32; on a
        # mesh they inherit the P(data, *model_spec) placement of locals_
        deltas = jax.tree.map(
            lambda loc, base: loc.astype(jnp.float32)
            - base.astype(jnp.float32), locals_, trainable)
        # cohorts that crashed before completing one step never deliver —
        # they must not occupy buffer slots, displace real updates, or
        # consume staleness levels (consistent with n_uploads accounting)
        active = np.flatnonzero(weights > 0)
        round_open = self.state.clock
        pulled = self.state.version
        evicted = self.state.evict_stale(self.max_staleness)
        # this round's deliveries enter the buffer WITHOUT a standalone
        # delta copy (delta=None): flushes below read the stacked ``deltas``
        # array directly (one gather per flush, not one slice per cohort);
        # only the pending tail that survives the round materializes its own
        # slice, since ``deltas`` dies with this call
        new_entries = [
            BufferEntry(
                delta=None, weight=float(weights[i]), loss=losses[i],
                pulled_version=pulled,
                arrival_time=round_open + float(times[i]),
                stage=t, cohort=int(i))
            for i in active]
        new_ids = {id(e) for e in new_entries}
        flushes = self.state.schedule(new_entries, self.buffer_size, t)
        for e in self.state.entries:
            if e.delta is None:               # this round's pending tail
                e.delta = jax.tree.map(lambda x, i=e.cohort: x[i], deltas)

        new_tr = jax.tree.map(lambda b: b.astype(jnp.float32), trainable)
        staleness = np.full(C, -1, int)
        eff_w, flushed_losses, n_flushed, n_carried = [], [], 0, 0
        for fl in flushes:
            # per-entry discounts over heterogeneous staleness: one flush
            # can mix fresh deliveries (read from the stacked deltas in one
            # gather) with multi-round carried stragglers (their own
            # copies); Eq. 1 commutes, so the fresh-then-carried order only
            # reassociates float sums
            pairs = list(zip(fl.entries, fl.staleness))
            fresh = [(e, s) for e, s in pairs if id(e) in new_ids]
            carried = [(e, s) for e, s in pairs if id(e) not in new_ids]
            parts = []
            if fresh:
                pos = np.asarray([e.cohort for e, _ in fresh])
                parts.append(jax.tree.map(lambda x: x[pos], deltas))
            if carried:
                parts.append(jax.tree.map(
                    lambda *xs: jnp.stack(xs),
                    *[e.delta for e, _ in carried]))
            stacked = parts[0] if len(parts) == 1 else jax.tree.map(
                lambda a, b: jnp.concatenate([a, b]), *parts)
            ordered = fresh + carried
            update, d = agg.buffered_flush_average(
                stacked, [e.weight for e, _ in ordered],
                [s for _, s in ordered],
                schedule=self.staleness_schedule,
                alpha=self.staleness_alpha)
            new_tr = jax.tree.map(
                lambda b, u: b + self.server_lr * u.astype(jnp.float32),
                new_tr, update)
            for (e, s), di in zip(ordered, d):
                n_flushed += 1
                n_carried += id(e) not in new_ids
                if id(e) in new_ids:
                    staleness[e.cohort] = int(s)
                eff_w.append(e.weight * float(di))
                flushed_losses.append(e.loss)
        new_trainable = jax.tree.map(lambda b, ref: b.astype(ref.dtype),
                                     new_tr, trainable)
        if self.mesh is not None:
            # the aggregate must keep the model-sharded placement the
            # synchronous 2-D path guarantees (per-device bytes ~1/k)
            new_trainable = jax.device_put(
                new_trainable, self._place.placements(t)[0])

        if n_flushed:
            w = jnp.asarray(np.asarray(eff_w) / np.sum(eff_w), jnp.float32)
            mean_loss = (jnp.stack(flushed_losses) * w).sum()
        else:
            # deliveries buffered but nothing flushed: no aggregation
            # happened this round (params unchanged, nothing to average)
            mean_loss = jnp.asarray(float("nan"))
        return new_trainable, {
            "mean_local_loss": mean_loss,
            "cohort_losses": losses[:C],
            "staleness": staleness,
            "n_pending": len(self.state),
            "n_uploads": n_flushed,
            "n_carried": n_carried,
            "n_evicted": len(evicted),
            "server_version": self.state.version,
            "sim_round_time": (max(0.0, flushes[-1].time - round_open)
                               if flushes else 0.0)}

    def _round_from_stack(self, params, t, stack, cohorts):
        sim_times = self.cohort_sim_times(stack, cohorts)
        new_trainable, metrics = self.run_stacked(params, t, stack,
                                                  sim_times=sim_times)
        return new_trainable, metrics, {
            "sim_times": [float(x) for x in sim_times],
            "round_sim_time": float(metrics["sim_round_time"]),
            "n_uploads": metrics["n_uploads"]}

    def _lost_round_extras(self, stack, cohorts):
        # a lost round delivers nothing: the buffered server flushes zero
        # times and never waits, so its virtual clock never advances —
        # report that instead of letting the server fall back to the
        # synchronous straggler wall-clock for a barrier it never had
        return {"round_sim_time": 0.0,
                "sim_times": [0.0] * stack.num_cohorts}

    # -- static-analysis registry ------------------------------------------ #
    def trace_specs(self, params, t, stack):
        frozen, trainable = self.adapter.split_stage(params, t)
        tr, fr = abstract_like(trainable), abstract_like(frozen)
        batches, _, mask = self._abstract_stack(stack)
        n_leaves = len(jax.tree.leaves(trainable))
        local_fn = make_local_program(self.adapter, self.optimizer,
                                      self.hp, t)
        jit_kwargs = {}
        mesh_kwargs = {}
        if self.mesh is not None:
            pad = (-stack.num_cohorts) % self.mesh.shape[self.axis]

            def grow(s):
                return jax.ShapeDtypeStruct(
                    (s.shape[0] + pad, *s.shape[1:]), s.dtype)

            from jax.sharding import NamedSharding

            from repro.launch.sharding import batch_spec
            tr_sh, fr_sh, cohort_sh = self._place.placements(t)
            if pad:
                batches = jax.tree.map(grow, batches)
                mask = grow(mask)
            batches = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(
                    s.shape, s.dtype, sharding=NamedSharding(
                        self.mesh, batch_spec(s.shape, self.mesh))),
                batches)
            mask = jax.ShapeDtypeStruct(mask.shape, mask.dtype,
                                        sharding=cohort_sh)
            tr = shard_abstract(tr, tr_sh)
            fr = shard_abstract(fr, fr_sh)
            jit_kwargs = {"out_shardings": (self._place.stacked_locals(t),
                                            cohort_sh)}
            mesh_kwargs = {"mesh": self.mesh, "data_axis": self.axis,
                           "model_axis": self.model_axis}
        specs = [RoundProgramSpec(
            name=f"async/stage{t}/local", backend=self.name, kind="local",
            fn=local_fn, abstract_args=(tr, fr, batches, mask),
            jit_kwargs=jit_kwargs, donate_argnums=(2,), stage=t,
            n_agg_leaves=0, **mesh_kwargs)]
        specs.append(self._flush_spec(t, trainable, mask.shape[0],
                                      mesh_kwargs))
        return specs

    def _flush_spec(self, t, trainable, n_entries, mesh_kwargs):
        """The buffered-flush aggregation seam: one ``stacked_weighted_
        average`` einsum over a (K,) f32 delta buffer.  Weights/staleness
        are host-side at flush time, so the traced program's only
        data-axis collectives are the per-leaf Eq. 1 all-reduces."""
        stacked = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(
                (n_entries, *np.shape(s)), jnp.float32),
            abstract_like(trainable))
        weights = [1.0] * n_entries
        staleness = [0] * n_entries
        schedule, alpha = self.staleness_schedule, self.staleness_alpha

        def flush(stacked_deltas):
            update, _ = agg.buffered_flush_average(
                stacked_deltas, weights, staleness,
                schedule=schedule, alpha=alpha)
            return update

        jit_kwargs = {}
        if self.mesh is not None:
            from repro.launch.sharding import stacked_tree_shardings
            frozen_defs, trainable_defs = self.adapter.split_stage(
                self.adapter.defs, t)
            del frozen_defs
            stacked = shard_abstract(
                stacked, stacked_tree_shardings(trainable_defs, self.mesh,
                                                self.axis))
            jit_kwargs = {"out_shardings": self._place.placements(t)[0]}
        return RoundProgramSpec(
            name=f"async/stage{t}/flush-seam", backend=self.name,
            kind="aggregation", fn=flush, abstract_args=(stacked,),
            jit_kwargs=jit_kwargs, stage=t,
            n_agg_leaves=len(jax.tree.leaves(trainable)), **mesh_kwargs)


RUNTIMES = {"sequential": SequentialRuntime,
            "vectorized": VectorizedRuntime,
            "sharded": ShardedRuntime,
            "async": AsyncBufferedRuntime}


def make_runtime(spec: Union[str, ClientRuntime], adapter: Adapter,
                 optimizer, hp: CurriculumHP, **kwargs) -> ClientRuntime:
    """Resolve a runtime name ("sequential" | "vectorized" | "sharded" |
    "async") or pass an already-constructed ClientRuntime through
    unchanged (constructor kwargs cannot apply to an instance — passing
    both is an error, not a silent drop)."""
    if isinstance(spec, ClientRuntime):
        if kwargs:
            raise ValueError(
                f"make_runtime got an already-constructed "
                f"{type(spec).__name__} AND constructor kwargs "
                f"{sorted(kwargs)} — those would be silently ignored; "
                f"configure the instance directly or pass the runtime "
                f"name instead")
        return spec
    try:
        cls = RUNTIMES[spec]
    except KeyError:
        raise ValueError(f"unknown runtime {spec!r}; "
                         f"choose from {sorted(RUNTIMES)}") from None
    return cls(adapter, optimizer, hp, **kwargs)
