"""How ``correct`` is decided: the numbers compared and their limits.

The timed path's first three rounds are replayed by the plain float32
reference (``bench/reference``) from the same starting weights, clients and
batches.  ``compare`` gives these numbers; a cell holds the ones its
``bench/limits/<cell>.json`` names, each to its limit:

``round_loss_gap``, ``round1_loss_gap``
    Worst gap, over the three rounds or over round 1 alone, between the
    program's and the reference's mean local loss of each cohort and of the
    round (Eq. 1 weighted), divided by the larger of the reference's loss
    and 1.
``first_update_gap``, ``first_update_median_gap``
    The trainable weights' change in round 1, per leaf: the gap between the
    program's norm and the reference's, divided by the larger of the
    reference's norm of that leaf and of the median leaf; the worst leaf, or
    the median one.
``three_round_change_gap``, ``three_round_change_median_gap``
    The same for the change over all three rounds.
``untouched_max_abs``
    Largest change, over the three rounds, of any weight that the stage does
    not train (the frozen prefix, other stages' surrogates and projectors).
    The limit is 0: the merge may only write the trainable subtree.

A leaf whose change in the reference is under a thousandth of the median
leaf's is left out of the norm gaps: its change is round-off (the
projector's output bias, which a centred Gram cannot see, stays at 0).
Where a cell's training amplifies rounding-sized differences over its later
steps, its limits hold only the round-1 numbers; ``PERF.md`` gives the
readings each limit was set from.
"""
from __future__ import annotations

import jax
import numpy as np

NUMBERS = ("round_loss_gap", "round1_loss_gap", "first_update_gap",
           "first_update_median_gap", "three_round_change_gap",
           "three_round_change_median_gap", "untouched_max_abs")
MOVED = 1e-3


def rel_err(got, want, floor: float = 1e-12) -> float:
    """max |got - want| / max(max |want|, floor) over one array."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    scale = max(float(np.max(np.abs(want), initial=0.0)), floor)
    return float(np.max(np.abs(got - want), initial=0.0)) / scale


def tree_rel_err(got, want):
    """(largest per-leaf ``rel_err``, path of that leaf); a leaf's scale is
    floored at 1e-3 of the tree's largest magnitude."""
    got, want = jax.device_get((got, want))
    g_leaves, g_def = jax.tree.flatten(got)
    w_paths, w_def = jax.tree_util.tree_flatten_with_path(want)
    if g_def != w_def:
        raise ValueError(f"tree structures differ: {g_def} vs {w_def}")
    top = max((float(np.max(np.abs(w), initial=0.0)) for _, w in w_paths),
              default=0.0)
    floor = max(1e-3 * top, 1e-12)
    return max(((rel_err(g, w, floor=floor), jax.tree_util.keystr(p))
                for g, (p, w) in zip(g_leaves, w_paths)),
               default=(0.0, ""))


def _norms(before, after):
    b = jax.tree.leaves(before)
    a = jax.tree.leaves(after)
    return np.asarray([np.linalg.norm(np.asarray(y, np.float64)
                                      - np.asarray(x, np.float64))
                       for x, y in zip(b, a)])


def norm_gaps(prog_before, prog_after, ref_before, ref_after):
    """Per leaf: |norm of the program's change - the reference's|, over the
    larger of the reference's norm of that leaf and the median leaf's;
    leaves the reference did not move are left out."""
    n_p = _norms(prog_before, prog_after)
    n_r = _norms(ref_before, ref_after)
    med = float(np.median(n_r))
    keep = n_r >= MOVED * med
    if not keep.any():
        return np.asarray([np.inf])
    return np.abs(n_p[keep] - n_r[keep]) / np.maximum(n_r[keep], med)


def loss_gap(prog, ref) -> float:
    prog = np.asarray(prog, np.float64)
    ref = np.asarray(ref, np.float64)
    if not np.all(np.isfinite(prog)):
        return float("inf")
    return float(np.max(np.abs(prog - ref) / np.maximum(np.abs(ref), 1.0)))


def untouched(family, cfg, t, before, after) -> float:
    """Largest change of any weight outside stage t's trainable subtree."""
    _, trainable0 = family.split(cfg, before, t)
    restored = family.merge(cfg, after, trainable0, t)
    return max(float(np.max(np.abs(np.asarray(x, np.float64)
                                   - np.asarray(y, np.float64)),
                            initial=0.0))
               for x, y in zip(jax.tree.leaves(restored),
                               jax.tree.leaves(before)))


def compare(family, cfg, t, snaps, prog_losses, ref_snaps, ref_losses):
    """Every number above.  ``snaps`` and ``ref_snaps`` are the full weight
    trees before round 1 and after rounds 1 and 3; the losses are, per
    round, the round's mean followed by the per-cohort means."""
    tr = lambda p: family.split(cfg, p, t)[1]
    p0, p1, p3 = (jax.device_get(tr(s)) for s in snaps)
    r0, r1, r3 = (jax.device_get(tr(s)) for s in ref_snaps)
    flat = lambda ls: np.concatenate([np.ravel(x) for x in ls])
    first = norm_gaps(p0, p1, r0, r1)
    three = norm_gaps(p0, p3, r0, r3)
    return {
        "round_loss_gap": loss_gap(flat(prog_losses), flat(ref_losses)),
        "round1_loss_gap": loss_gap(prog_losses[0], ref_losses[0]),
        "first_update_gap": float(np.max(first)),
        "first_update_median_gap": float(np.median(first)),
        "three_round_change_gap": float(np.max(three)),
        "three_round_change_median_gap": float(np.median(three)),
        "untouched_max_abs": max(untouched(family, cfg, t, snaps[0], s)
                                 for s in snaps[1:]),
    }


def verdict(numbers: dict, limits: dict) -> bool:
    """Every number the cell's limits name is finite and within its limit."""
    return all(np.isfinite(numbers[k]) and numbers[k] <= v
               for k, v in limits.items())
