"""The parts of a NeuLite round that every model family shares, written
plainly in float32 from the paper (arXiv 2408.10826) and nothing of the
program: the Curriculum Mentor's loss (Eq. 4) with its two nHSIC terms, the
proximal term (Eq. 5), SGD with momentum and weight decay, local training
client by client and step by step over exactly the client's real batches,
and the Eq. 1 aggregation weighted by each client's sample count.

A family module (``cnn.py``, ``transformer.py``) supplies ``init``,
``split``, ``merge`` and ``stage_forward``; ``Round`` drives them.

nHSIC follows Ma, Lewis & Kleijn (2020): centred Gram matrices, a Gaussian
kernel whose bandwidth is the mean pairwise squared distance (held constant
under differentiation), and a linear kernel over normalised one-hot labels.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-8


def gelu(x):
    """GELU in its tanh form (Hendrycks & Gimpel, 2016)."""
    return 0.5 * x * (1.0 + jnp.tanh(np.sqrt(2.0 / np.pi)
                                     * (x + 0.044715 * x ** 3)))


def rbf_gram(x):
    sq = jnp.sum(jnp.square(x), axis=-1)
    d2 = jnp.maximum(sq[:, None] + sq[None, :] - 2.0 * (x @ x.T), 0.0)
    sigma2 = jax.lax.stop_gradient(jnp.maximum(jnp.mean(d2), EPS))
    return jnp.exp(-d2 / (2.0 * sigma2))


def centre(k):
    """H K H with H = I - 11^T/m, by subtracting row and column means."""
    return (k - k.mean(axis=0, keepdims=True) - k.mean(axis=1, keepdims=True)
            + k.mean())


def nhsic(kx, kz):
    kx, kz = centre(kx), centre(kz)
    return jnp.sum(kx * kz) / (jnp.linalg.norm(kx) * jnp.linalg.norm(kz)
                               + EPS)


def label_features(labels, num_classes: int):
    onehot = jax.nn.one_hot(labels, num_classes, dtype=jnp.float32)
    return onehot / (jnp.linalg.norm(onehot, axis=-1, keepdims=True) + EPS)


def cross_entropy(logits, labels):
    logits = logits.astype(jnp.float32)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


def lambdas(t: int, num_stages: int, lambda1: float, lambda2: float):
    """lambda1 falls and lambda2 rises with the stage (Curriculum Mentor)."""
    frac = t / (num_stages - 1) if num_stages > 1 else 0.0
    return lambda1 * (1.0 - frac), lambda2 * (0.25 + 0.75 * frac)


@dataclasses.dataclass(frozen=True)
class Hyper:
    lr: float
    momentum: float
    weight_decay: float
    mu: float
    lambda1: float
    lambda2: float

    @classmethod
    def from_traffic(cls, d: dict) -> "Hyper":
        o, c = d["optimizer"], d["curriculum"]
        return cls(lr=o["lr"], momentum=o["momentum"],
                   weight_decay=o["weight_decay"], mu=c["mu"],
                   lambda1=c["lambda1"], lambda2=c["lambda2"])


def stage_loss(family, cfg: dict, hyper: Hyper, t: int, trainable, frozen,
               images, labels, global_ref):
    """Eq. 4 plus Eq. 5 on one batch."""
    logits, x_embed, z_active, z_proj = family.stage_forward(
        cfg, t, frozen, trainable, images)
    lam1, lam2 = lambdas(t, cfg["num_stages"], hyper.lambda1, hyper.lambda2)
    x_feat = family.pool(x_embed)
    z_feat = family.pool(z_active)
    zp_feat = family.pool(z_proj)
    y_feat = label_features(labels, cfg["num_classes"])
    h_xz = nhsic(rbf_gram(x_feat - x_feat.mean(0)),
                 rbf_gram(z_feat - z_feat.mean(0)))
    y_c = y_feat - y_feat.mean(0)
    h_yz = nhsic(y_c @ y_c.T, rbf_gram(zp_feat - zp_feat.mean(0)))
    prox = sum(jnp.sum(jnp.square(a.astype(jnp.float32)
                                  - b.astype(jnp.float32)))
               for a, b in zip(jax.tree.leaves(trainable),
                               jax.tree.leaves(global_ref)))
    return (cross_entropy(logits, labels) - lam1 * h_xz - lam2 * h_yz
            + 0.5 * hyper.mu * prox)


def make_step(family, cfg: dict, hyper: Hyper, t: int, dtype,
              batch_rows: int | None = None) -> Callable:
    """One SGD step of one client:
    (trainable, momentum, step, frozen, images, labels, global_ref)
        -> (loss, trainable, momentum).

    ``dtype`` is what the whole step computes in.  ``batch_rows`` trains on
    the first rows of the batch only; it exists to plant a fault."""

    def step(trainable, mom, frozen, images, labels, global_ref):
        cast = lambda tree: jax.tree.map(lambda a: a.astype(dtype), tree)
        trainable, mom, frozen, global_ref = (
            cast(trainable), cast(mom), cast(frozen), cast(global_ref))
        images = images.astype(dtype)
        if batch_rows is not None:
            images, labels = images[:batch_rows], labels[:batch_rows]
        loss, grads = jax.value_and_grad(
            lambda tr: stage_loss(family, cfg, hyper, t, tr, frozen, images,
                                  labels, global_ref))(trainable)
        g = jax.tree.map(lambda g_, p: g_ + hyper.weight_decay * p, grads,
                         trainable)
        mom = jax.tree.map(lambda m, g_: hyper.momentum * m + g_, mom, g)
        trainable = jax.tree.map(lambda p, m: p - hyper.lr * m, trainable,
                                 mom)
        return loss.astype(jnp.float32), trainable, mom

    return jax.jit(step)


class Round:
    """Replays the rounds of one fixed stage from the bank's log and the
    server's starting weights, client by client.

    ``run(params, cohorts)`` takes the full weight tree and one round's
    cohorts, each ``(sample_count, [(images, labels), ...])`` with the
    client's real batches in order, and returns (new full weights, the
    round's weighted mean loss, per-cohort mean losses)."""

    def __init__(self, family, cfg: dict, hyper: Hyper, t: int,
                 dtype=jnp.float32, batch_rows: int | None = None):
        self.family, self.cfg, self.t = family, cfg, t
        self.step = make_step(family, cfg, hyper, t, dtype, batch_rows)
        self.dtype = dtype

    def local(self, trainable0, frozen, batches):
        trainable = trainable0
        mom = jax.tree.map(lambda a: jnp.zeros(a.shape, self.dtype),
                           trainable0)
        losses = []
        for images, labels in batches:
            loss, trainable, mom = self.step(trainable, mom, frozen, images,
                                             labels, trainable0)
            losses.append(loss)
        return trainable, float(np.mean(jax.device_get(losses)))

    def run(self, params, cohorts):
        frozen, trainable0 = self.family.split(self.cfg, params, self.t)
        locals_, losses, weights = [], [], []
        for n_samples, batches in cohorts:
            tr, loss = self.local(trainable0, frozen, batches)
            locals_.append(tr)
            losses.append(loss)
            weights.append(float(n_samples))
        w = np.asarray(weights, np.float64)
        w = w / w.sum()
        new = jax.tree.map(
            lambda *xs: sum(wi * x.astype(jnp.float32)
                            for wi, x in zip(w, xs)), *locals_)
        mean_loss = float(np.dot(w, losses))
        return self.family.merge(self.cfg, params, new, self.t), \
            mean_loss, losses
