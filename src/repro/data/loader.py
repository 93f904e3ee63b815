"""Batching pipeline: per-client epochs + cohort batch stacks.

``Batcher`` yields fixed-shape batches (sub-batch remainders are dropped
per epoch; datasets smaller than one batch are filled by resampling).
``stack_round`` materializes the ``(C, E, ...)`` cohort batch stack that
the vectorized / mesh-sharded ``ClientRuntime`` backends consume as one
array program input, writing each batch once: into fresh arrays, or into
a ``StackBuffer`` that a runtime owns and refills every round.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Sequence

import numpy as np

from repro.common import spans


class Batcher:
    """Yields fixed-shape batches (jit-friendly).

    An epoch yields ``floor(n / batch_size)`` full batches; the remainder is
    dropped for that epoch (each epoch reshuffles, so coverage rotates).
    Only when ``len(ds) < batch_size`` does the epoch resample examples to
    fill the single batch it yields.  Resampled duplicates must NOT inflate
    FedAvg weights — ``num_samples`` always reports the *true*
    (deduplicated) dataset size, and aggregation weighting goes through it
    rather than counting batch rows.
    """

    def __init__(self, dataset, batch_size: int, seed: int = 0,
                 kind: str = "image"):
        self.ds = dataset
        self.bs = batch_size
        self.rng = np.random.default_rng(seed)
        self.kind = kind

    @property
    def num_samples(self) -> int:
        """True sample count — excludes wraparound resampling duplicates."""
        return len(self.ds)

    @property
    def steps_per_epoch(self) -> int:
        return max(1, len(self.ds) // self.bs)

    def epoch(self):
        n = len(self.ds)
        order = self.rng.permutation(n)
        if n < self.bs:
            order = np.concatenate(
                [order, self.rng.choice(n, self.bs - n, replace=True)])
            n = self.bs
        for i in range(0, n - self.bs + 1, self.bs):
            idx = order[i : i + self.bs]
            yield self.make_batch(idx)

    def batches(self, num_steps: int):
        """Exactly ``num_steps`` batches, cycling shuffled epochs as needed."""
        done = 0
        while done < num_steps:
            for batch in self.epoch():
                yield batch
                done += 1
                if done == num_steps:
                    return

    def make_batch(self, idx):
        if self.kind == "image":
            return {"inputs": {"images": self.ds.images[idx]},
                    "labels": self.ds.labels[idx]}
        toks = self.ds.tokens[idx]
        return {"inputs": {"tokens": toks[:, :-1]},
                "labels": toks[:, 1:]}

    def sample(self, batch_size=None):
        bs = batch_size or self.bs
        idx = self.rng.integers(0, len(self.ds), bs)
        return self.make_batch(idx)


@dataclasses.dataclass
class RoundStack:
    """One FL round's cohort data as a single array program input.

    batches   : pytree with leading (C, E, ...) axes — C cohorts × E local
                steps of per-cohort data (numpy; runtimes move it on device)
    step_mask : (C, E) bool — False marks padding steps (cohorts with fewer
                true local steps than the widest cohort); masked steps are
                exact no-ops for params and optimizer state
    weights   : (C,) float32 — true per-cohort sample counts (Eq. 1 weights)
    num_batches : true (unpadded) local step count per cohort

    A stack ``stack_round`` returns without a buffer is never written
    again; one built in a runtime's ``StackBuffer`` is overwritten by the
    runtime's next round.
    """
    batches: dict
    step_mask: np.ndarray
    weights: np.ndarray
    num_batches: List[int]

    @property
    def num_cohorts(self) -> int:
        return len(self.num_batches)

    @property
    def max_steps(self) -> int:
        return int(self.step_mask.shape[1])

    @property
    def nbytes(self) -> int:
        """Host bytes of the arrays a runtime copies to the device."""
        import jax
        return int(sum(np.asarray(x).nbytes for x in jax.tree.leaves(
            (self.batches, self.step_mask, self.weights))))


_ALIGN = 64       # bytes; the CPU backend's arrays may alias aligned memory


class StackBuffer:
    """Host memory a runtime reuses for its round stacks.

    One flat, grow-only byte array per batch leaf, 64-byte aligned; a
    round's leaf is a C-contiguous ``(C, E, ...)`` view of the array's
    first bytes, so its pages are mapped once, not every round.  Each round
    overwrites the last one's stack, so a runtime passes its buffer only
    where it consumes the stack within the round.

    ``hold`` takes device arrays computed from the last stack: the round
    program's outputs.  Before the next stack is written, those not deleted
    are waited for, so the host-to-device copy, and on the CPU, where
    ``jnp.asarray`` may alias host memory, the program that read the stack,
    have finished.  Under ``spans.recording()`` a round that grows
    the buffer adds the new bytes to ``stack_buffer_grown_bytes``; one that
    fits adds 1 to ``stack_buffer_reused``.
    """

    def __init__(self):
        self._flat: List[np.ndarray] = []
        self._held: list = []

    def hold(self, *trees) -> None:
        import jax
        self._held = jax.tree.leaves(trees)

    def arrays(self, specs) -> List[np.ndarray]:
        """Views of shape and dtype ``specs[i]`` for leaf ``i``, once the
        held arrays are ready."""
        import jax
        jax.block_until_ready([x for x in self._held if not x.is_deleted()])
        self._held = []
        out, grown = [], 0
        for i, (shape, dtype) in enumerate(specs):
            need = math.prod(shape) * np.dtype(dtype).itemsize
            if i == len(self._flat):
                self._flat.append(np.empty(0, np.uint8))
            have = self._flat[i].nbytes
            if have < need:
                raw = np.empty(need + _ALIGN, np.uint8)
                start = -raw.ctypes.data % _ALIGN
                self._flat[i] = raw[start:start + need]
                grown += need - have
            out.append(self._flat[i][:need].view(dtype).reshape(shape))
        if grown:
            spans.count("stack_buffer_grown_bytes", grown)
        else:
            spans.count("stack_buffer_reused", 1)
        return out


def _allocate(first_batch, lead, buffer: Optional[StackBuffer]):
    specs = [(lead + np.shape(x), np.asarray(x).dtype) for x in first_batch]
    if buffer is None:
        return [np.empty(shape, dtype) for shape, dtype in specs]
    return buffer.arrays(specs)


def stack_round(batchers: Sequence[Batcher],
                cohorts: Optional[Sequence[int]] = None,
                local_steps: Optional[int] = None, *,
                local_epochs: Optional[int] = None,
                buffer: Optional[StackBuffer] = None) -> RoundStack:
    """Materialize the (C, E, ...) batch stack for a vectorized FL round.

    cohorts selects which batchers participate (default: all).  Pass either
    ``local_steps`` (uniform step count per cohort) or ``local_epochs``
    (each cohort runs ``local_epochs * steps_per_epoch`` true steps — the
    sequential reference semantics).  Cohorts with fewer true steps than the
    widest cohort are padded with repeats of their last batch, masked out of
    training.

    The batchers are walked cohort by cohort, each batch written once into
    its ``(c, e)`` slot.  Without ``buffer`` the stack is fresh arrays that
    nothing writes again; with one, it is views into the buffer that the
    next call with the same buffer overwrites.
    """
    import jax
    if (local_steps is None) == (local_epochs is None):
        raise ValueError("pass exactly one of local_steps / local_epochs")
    if cohorts is None:
        cohorts = range(len(batchers))
    picked = [batchers[c] for c in cohorts]
    if not picked:
        raise ValueError("stack_round needs at least one cohort")

    targets = [local_steps if local_steps is not None
               else local_epochs * b.steps_per_epoch for b in picked]
    if min(targets) < 1:
        raise ValueError(f"every cohort needs a local step: {targets}")
    C, E = len(picked), max(targets)

    treedef, out = None, None
    for c, (b, tgt) in enumerate(zip(picked, targets)):
        for e, batch in enumerate(b.batches(tgt)):
            leaves, tdef = jax.tree.flatten(batch)
            if out is None:
                treedef, out = tdef, _allocate(leaves, (C, E), buffer)
            elif tdef != treedef:
                raise ValueError(f"batch structure {tdef} is not {treedef}")
            for o, x in zip(out, leaves):
                x = np.asarray(x)
                if x.shape != o.shape[2:] or x.dtype != o.dtype:
                    raise ValueError(
                        f"batch leaf {x.dtype}{list(x.shape)} does not "
                        f"match the stack's {o.dtype}{list(o.shape[2:])}")
                o[c, e] = x
        for o in out:
            o[c, tgt:] = o[c, tgt - 1]                   # masked padding

    return RoundStack(
        batches=jax.tree.unflatten(treedef, out),
        step_mask=np.arange(E) < np.asarray(targets)[:, None],
        weights=np.asarray([b.num_samples for b in picked], np.float32),
        num_batches=[int(t) for t in targets])


def truncate_step_mask(stack: RoundStack,
                       completed_steps: Sequence[Optional[int]]
                       ) -> RoundStack:
    """Mid-round dropout / fault injection on a prepared ``RoundStack``.

    ``completed_steps[i]`` is the number of true local steps cohort i
    finished before dropping out (``None`` = no fault).  The cohort's mask
    row is truncated to its first ``completed_steps[i]`` true steps — the
    remaining steps become exact no-ops on every backend — and its Eq. 1
    weight is scaled by the completed fraction (completed-step-weighted
    aggregation).  A cohort that crashes before step 0 keeps zero weight;
    weights never increase, so dropout can only *shrink* a cohort's share.

    Returns a new ``RoundStack`` sharing the (immutable here) batch arrays.
    """
    if len(completed_steps) != stack.num_cohorts:
        raise ValueError(
            f"completed_steps has {len(completed_steps)} entries for "
            f"{stack.num_cohorts} cohorts")
    mask = stack.step_mask.copy()
    weights = np.asarray(stack.weights, np.float32).copy()
    num_batches = list(stack.num_batches)
    for i, done in enumerate(completed_steps):
        if done is None:
            continue
        done = int(done)
        if done < 0:
            raise ValueError(f"negative completed_steps[{i}] = {done}")
        target = num_batches[i]
        if done >= target:
            continue                      # fault after finishing: no-op
        true_pos = np.flatnonzero(mask[i])
        mask[i, true_pos[done:]] = False
        weights[i] *= done / target
        num_batches[i] = done
    return RoundStack(batches=stack.batches, step_mask=mask,
                      weights=weights, num_batches=num_batches)
