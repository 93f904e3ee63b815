"""Property-based invariants of the cohort batch stack and dropout
truncation (hypothesis; conftest shims a seeded fallback when absent).

The contract under test: however ragged the per-cohort step counts and
however dropout truncates them, ``stack_round``/``truncate_step_mask`` must
(a) keep every cohort's Eq. 1 weight at or below its TRUE sample count —
wraparound resampling and fault injection can never inflate FedAvg weights —
and (b) keep the step mask consistent with the reported true step counts,
so completed-step-weighted aggregation falls out of the mask semantics.

The stack itself must be bit-identical to the construction it replaced
(each cohort's batches ``np.stack``ed, then the cohorts ``np.stack``ed),
with the batchers called in the same order, whether it is written into
fresh arrays or into a reused ``StackBuffer``.
"""
import dataclasses

import jax
import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

import pytest

from repro.common import spans
from repro.data import Batcher, make_image_dataset, make_lm_dataset
from repro.data.loader import StackBuffer, stack_round, truncate_step_mask


def _batchers(sizes, batch_size):
    return [Batcher(make_image_dataset(i, n, num_classes=4, image_size=4),
                    batch_size, seed=i, kind="image")
            for i, n in enumerate(sizes)]


@settings(max_examples=20, deadline=None)
@given(sizes=st.lists(st.integers(1, 50), min_size=1, max_size=4),
       batch_size=st.integers(2, 16),
       local_epochs=st.integers(1, 3))
def test_stack_round_mask_and_weight_invariants(sizes, batch_size,
                                                local_epochs):
    stack = stack_round(_batchers(sizes, batch_size),
                        local_epochs=local_epochs)
    # weights are the TRUE sample counts — wraparound resampling for
    # datasets smaller than one batch must never inflate them
    assert stack.weights.tolist() == [float(n) for n in sizes]
    # mask rows are True-prefixes matching the true step counts
    mask = stack.step_mask
    assert mask.shape[0] == len(sizes)
    for row, nb in zip(mask, stack.num_batches):
        assert int(row.sum()) == nb
        assert row[:nb].all() and not row[nb:].any()
    assert max(stack.num_batches) == stack.max_steps
    # every batch leaf carries the (C, E) leading axes
    import jax
    for leaf in jax.tree.leaves(stack.batches):
        assert leaf.shape[:2] == mask.shape


@settings(max_examples=20, deadline=None)
@given(sizes=st.lists(st.integers(1, 50), min_size=1, max_size=4),
       batch_size=st.integers(2, 16),
       local_epochs=st.integers(1, 3),
       draws=st.lists(st.integers(0, 10 ** 6), min_size=4, max_size=4),
       survive=st.lists(st.booleans(), min_size=4, max_size=4))
def test_truncation_never_inflates_weights(sizes, batch_size, local_epochs,
                                           draws, survive):
    stack = stack_round(_batchers(sizes, batch_size),
                        local_epochs=local_epochs)
    C = stack.num_cohorts
    faults = [None if survive[i] else draws[i] % (stack.num_batches[i] + 1)
              for i in range(C)]
    out = truncate_step_mask(stack, faults)

    for i in range(C):
        done = stack.num_batches[i] if faults[i] is None \
            else min(faults[i], stack.num_batches[i])
        # completed-step weighting: w' = w * done/target, never inflated
        assert out.num_batches[i] == done
        np.testing.assert_allclose(
            out.weights[i],
            stack.weights[i] * done / stack.num_batches[i], rtol=1e-6)
        assert out.weights[i] <= stack.weights[i] + 1e-6
        # the truncated mask row keeps exactly the first `done` true steps
        assert int(out.step_mask[i].sum()) == done
        assert (out.step_mask[i] <= stack.step_mask[i]).all()
    # total effective samples can only shrink; cohorts that completed keep
    # their exact weight (no cross-cohort renormalization at this seam)
    assert out.weights.sum() <= stack.weights.sum() + 1e-6
    for i in range(C):
        if faults[i] is None or faults[i] >= stack.num_batches[i]:
            assert out.weights[i] == stack.weights[i]


@settings(max_examples=10, deadline=None)
@given(sizes=st.lists(st.integers(1, 30), min_size=1, max_size=3),
       local_epochs=st.integers(1, 2))
def test_full_completion_truncation_is_identity(sizes, local_epochs):
    stack = stack_round(_batchers(sizes, 8), local_epochs=local_epochs)
    out = truncate_step_mask(stack, [None] * stack.num_cohorts)
    np.testing.assert_array_equal(out.step_mask, stack.step_mask)
    np.testing.assert_array_equal(out.weights, stack.weights)
    assert out.num_batches == stack.num_batches


def test_truncation_validates_inputs():
    stack = stack_round(_batchers([20, 20], 8), local_epochs=1)
    with pytest.raises(ValueError):
        truncate_step_mask(stack, [0])              # wrong arity
    with pytest.raises(ValueError):
        truncate_step_mask(stack, [-1, None])       # negative steps


# --------------------------------------------------------------------------- #
# the stack against the two-np.stack construction it replaced
# --------------------------------------------------------------------------- #
class _LoggingBatcher(Batcher):
    """Logs ``(cid, indices)`` of every batch it makes, in order."""

    def __init__(self, ds, bs, seed, kind, cid, log):
        super().__init__(ds, bs, seed=seed, kind=kind)
        self.cid, self.log = cid, log

    def make_batch(self, idx):
        self.log.append((self.cid, np.array(idx)))
        return super().make_batch(idx)


def _logging_batchers(kind, sizes, batch_size):
    log = []
    if kind == "image":
        data = [make_image_dataset(i, n, num_classes=4, image_size=4)
                for i, n in enumerate(sizes)]
    else:
        data = [make_lm_dataset(i, n, 6, 32) for i, n in enumerate(sizes)]
    return [_LoggingBatcher(ds, batch_size, i, kind, i, log)
            for i, ds in enumerate(data)], log


def _two_stack_reference(batchers, cohorts, local_steps=None,
                         local_epochs=None):
    """The stack as two rounds of ``np.stack`` built it."""
    picked = [batchers[c] for c in cohorts]
    targets = [local_steps if local_steps is not None
               else local_epochs * b.steps_per_epoch for b in picked]
    E = max(targets)
    per_cohort, mask_rows = [], []
    for b, tgt in zip(picked, targets):
        seq = list(b.batches(tgt))
        seq.extend(seq[-1] for _ in range(E - tgt))
        per_cohort.append(jax.tree.map(lambda *xs: np.stack(xs), *seq))
        mask_rows.append([True] * tgt + [False] * (E - tgt))
    return (jax.tree.map(lambda *xs: np.stack(xs), *per_cohort),
            np.asarray(mask_rows, bool),
            np.asarray([b.num_samples for b in picked], np.float32),
            [int(t) for t in targets])


def _assert_same_stack(stack, ref):
    batches, mask, weights, num_batches = ref
    assert (jax.tree.structure(stack.batches)
            == jax.tree.structure(batches))
    for got, want in zip(jax.tree.leaves((stack.batches, stack.step_mask,
                                          stack.weights)),
                         jax.tree.leaves((batches, mask, weights))):
        assert got.dtype == want.dtype and got.shape == want.shape
        assert got.flags.c_contiguous
        assert np.array_equal(got, want)
    assert stack.num_batches == num_batches


def _buffer(state, kind):
    """None, a new buffer, or one already grown past and refilled below
    this stack's size."""
    if state == "none":
        return None
    buf = StackBuffer()
    if state == "used":
        for sizes in ([64, 64, 64, 64, 64], [4]):
            warm, _ = _logging_batchers(kind, sizes, 8)
            stack_round(warm, local_epochs=2, buffer=buf)
    return buf


@pytest.mark.parametrize("buffer", ["none", "new", "used"])
@pytest.mark.parametrize("kind,sizes,steps", [
    ("image", [5, 40, 17, 26], {"local_epochs": 2}),     # uneven: padding
    ("image", [12, 30], {"local_steps": 3}),
    ("lm", [9, 30, 3], {"local_epochs": 1}),             # uneven: padding
    ("lm", [20, 7], {"local_steps": 2}),
])
def test_stack_round_is_bit_identical_to_two_np_stacks(kind, sizes, steps,
                                                       buffer):
    cohorts = list(range(len(sizes)))[::-1]          # not the bank's order
    mine, log = _logging_batchers(kind, sizes, 8)
    theirs, ref_log = _logging_batchers(kind, sizes, 8)
    stack = stack_round(mine, cohorts, buffer=_buffer(buffer, kind),
                        **steps)
    ref = _two_stack_reference(theirs, cohorts, **steps)
    _assert_same_stack(stack, ref)
    # the batchers were called in the same order with the same indices
    assert [c for c, _ in log] == [c for c, _ in ref_log]
    for (_, a), (_, b) in zip(log, ref_log):
        np.testing.assert_array_equal(a, b)
    # dropout on top: the same truncation of the same stack
    faults = [None, 1] + [0] * (len(sizes) - 2)
    cut = truncate_step_mask(stack, faults)
    ref_cut = truncate_step_mask(dataclasses.replace(stack, batches=ref[0]),
                                 faults)
    _assert_same_stack(cut, (ref_cut.batches, ref_cut.step_mask,
                             ref_cut.weights, ref_cut.num_batches))
    assert cut.batches is stack.batches


@settings(max_examples=10, deadline=None)
@given(sizes=st.lists(st.integers(1, 40), min_size=1, max_size=5),
       batch_size=st.integers(2, 9),
       local_epochs=st.integers(1, 3),
       reuse=st.booleans())
def test_stack_round_counts_and_log_match_two_np_stacks(sizes, batch_size,
                                                        local_epochs, reuse):
    """A counting batcher sees the same per-cohort steps and the same
    ``(cid, indices)`` log, entry for entry, as under the old stacker."""
    mine, log = _logging_batchers("image", sizes, batch_size)
    theirs, ref_log = _logging_batchers("image", sizes, batch_size)
    buf = StackBuffer() if reuse else None
    for _ in range(2):                       # a second round refills it
        stack = stack_round(mine, local_epochs=local_epochs, buffer=buf)
        ref = _two_stack_reference(theirs, range(len(sizes)),
                                   local_epochs=local_epochs)
        _assert_same_stack(stack, ref)
    steps = lambda lg: np.bincount([c for c, _ in lg], minlength=len(sizes))
    np.testing.assert_array_equal(steps(log), steps(ref_log))
    assert len(log) == len(ref_log)
    for (c, a), (d, b) in zip(log, ref_log):
        assert c == d
        np.testing.assert_array_equal(a, b)


def test_stack_buffer_grows_only_and_counts_under_recording():
    buf = StackBuffer()
    sizes_by_round = [[16, 16], [48, 48], [16], [48, 48]]
    with spans.recording() as rec:
        views = []
        for r, sizes in enumerate(sizes_by_round):
            with spans.span("fl.round", round=r):
                b, _ = _logging_batchers("image", sizes, 8)
                views.append(stack_round(b, local_epochs=1, buffer=buf))
    img = lambda st_: st_.batches["inputs"]["images"]
    per_sample = 4 * 4 * 3 * 4 + 4                   # image f32 + label i32
    assert rec.counters == {
        ("stack_buffer_grown_bytes", 0): 2 * 2 * 8 * per_sample,
        ("stack_buffer_grown_bytes", 1): (2 * 6 - 2 * 2) * 8 * per_sample,
        ("stack_buffer_reused", 2): 1,
        ("stack_buffer_reused", 3): 1}
    # rounds 1 and 3 share memory: the later round overwrote the earlier
    assert np.shares_memory(img(views[1]), img(views[3]))
    assert img(views[3]).flags.c_contiguous
    # outside recording nothing is counted
    b, _ = _logging_batchers("image", [16], 8)
    stack_round(b, local_epochs=1, buffer=buf)
    assert len(rec.counters) == 4


def test_stack_round_without_buffer_is_never_rewritten():
    b, _ = _logging_batchers("image", [20, 33], 8)
    kept = stack_round(b, local_epochs=1)
    before = jax.tree.map(np.copy, kept.batches)
    buf = StackBuffer()
    for _ in range(3):
        stack_round(b, local_epochs=1)
        stack_round(b, local_epochs=2, buffer=buf)
    for x, y in zip(jax.tree.leaves(kept.batches), jax.tree.leaves(before)):
        np.testing.assert_array_equal(x, y)


def test_stack_round_rejects_mismatched_batches_and_empty_cohorts():
    class Ragged(Batcher):
        def make_batch(self, idx):
            batch = super().make_batch(idx)
            if self.calls:
                batch["labels"] = batch["labels"][:-1]
            self.calls += 1
            return batch

    ds = make_image_dataset(0, 32, num_classes=4, image_size=4)
    ragged = Ragged(ds, 8)
    ragged.calls = 0
    with pytest.raises(ValueError, match="does not match"):
        stack_round([ragged], local_steps=2)
    with pytest.raises(ValueError, match="local step"):
        stack_round([Batcher(ds, 8)], local_steps=0)
