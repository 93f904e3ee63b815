"""Unified ClientRuntime: adapter round-trips, backend equivalence, and the
cohort batch-stack pipeline."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CurriculumHP, PlateauSchedule, make_adapter
from repro.data import Batcher, dirichlet_partition, make_image_dataset, \
    make_lm_dataset
from repro.data.loader import stack_round
from repro.federated import aggregation as agg
from repro.federated.runtime import (AsyncBufferedRuntime, SequentialRuntime,
                                     ShardedRuntime, VectorizedRuntime,
                                     make_runtime)
from repro.federated.server import FLConfig, NeuLiteServer
from repro.models.cnn import CNNConfig
from repro.optim import sgd

# cnn_setup / tx_setup fixtures are shared via tests/conftest.py


def _assert_trees_equal(a, b, **tol):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), **tol)


# --------------------------------------------------------------------------- #
# adapter round-trips: split_stage -> merge_stage is the identity
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("setup", ["cnn_setup", "tx_setup"])
def test_split_merge_roundtrip_identity(setup, request):
    adapter, params, _ = request.getfixturevalue(setup)
    for t in range(adapter.plan.num_stages):
        frozen, trainable = adapter.split_stage(params, t)
        merged = adapter.merge_stage(params, trainable, t)
        # identity on every subtree — touched slices get the same values
        # written back, untouched ones must come through bit-identical
        la = jax.tree.leaves(params)
        lm = jax.tree.leaves(merged)
        assert len(la) == len(lm)
        for x, y in zip(la, lm):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# --------------------------------------------------------------------------- #
# cohort batch stack
# --------------------------------------------------------------------------- #
def test_stack_round_shapes_mask_and_true_weights():
    small = make_image_dataset(0, 5, num_classes=4, image_size=8)   # n < bs
    big = make_image_dataset(1, 40, num_classes=4, image_size=8)
    batchers = [Batcher(small, 16, seed=0), Batcher(big, 16, seed=1)]
    stack = stack_round(batchers, [0, 1], local_epochs=2)
    assert stack.num_cohorts == 2
    # cohort 0 wraps around (resamples 11 of its 5 examples per batch) but
    # its aggregation weight must stay the TRUE sample count
    assert stack.weights.tolist() == [5.0, 40.0]
    assert stack.num_batches == [2, 4]
    assert stack.step_mask.tolist() == [[True, True, False, False],
                                        [True] * 4]
    C, E = stack.step_mask.shape
    for leaf in jax.tree.leaves(stack.batches):
        assert leaf.shape[:2] == (C, E)


def test_stack_round_argument_validation():
    b = [Batcher(make_image_dataset(0, 32, 4, 8), 16)]
    with pytest.raises(ValueError):
        stack_round(b, [0])                              # neither
    with pytest.raises(ValueError):
        stack_round(b, [0], local_steps=2, local_epochs=1)   # both


def test_batcher_reports_true_sample_count():
    ds = make_image_dataset(0, 5, num_classes=4, image_size=8)
    b = Batcher(ds, 8, seed=0)
    batches = list(b.epoch())
    assert len(batches) == 1
    assert batches[0]["labels"].shape == (8,)     # fixed shape via wraparound
    assert b.num_samples == 5                     # no double-counting
    assert b.steps_per_epoch == 1


# --------------------------------------------------------------------------- #
# backend equivalence on the same cohort data
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("setup", ["cnn_setup", "tx_setup"])
def test_sequential_vs_vectorized_equivalence(setup, request):
    adapter, params, batchers = request.getfixturevalue(setup)
    opt = sgd(0.05, momentum=0.9, weight_decay=5e-4)
    # Full curriculum runs on the CNN; the transformer's stage-0 nHSIC term
    # chaotically amplifies f32 reassociation noise across steps, so its
    # variant checks the architecture path with the prox term only.
    hp = CurriculumHP(mu=0.01) if setup == "cnn_setup" \
        else CurriculumHP(enabled=False, mu=0.01)
    stack = stack_round(batchers, range(len(batchers)), local_epochs=1)
    for t in range(adapter.plan.num_stages):
        seq = SequentialRuntime(adapter, opt, hp)
        vec = VectorizedRuntime(adapter, opt, hp)
        tr_s, m_s = seq.run_stacked(params, t, stack)
        tr_v, m_v = vec.run_stacked(params, t, stack)
        _assert_trees_equal(tr_s, tr_v, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(float(m_s["mean_local_loss"]),
                                   float(m_v["mean_local_loss"]), rtol=1e-4)


def test_non_prefix_mask_equivalence(cnn_setup):
    """Mid-round dropout masks (False inside the step sequence, not just
    trailing padding) must mean the same thing to every backend."""
    adapter, params, batchers = cnn_setup
    opt = sgd(0.05, momentum=0.9, weight_decay=5e-4)
    hp = CurriculumHP(mu=0.01)
    stack = stack_round(batchers[:2], [0, 1], local_steps=4)
    stack.step_mask = np.asarray([[True, False, True, True],
                                  [True, True, False, False]])
    seq = SequentialRuntime(adapter, opt, hp)
    vec = VectorizedRuntime(adapter, opt, hp)
    tr_s, _ = seq.run_stacked(params, 0, stack)
    tr_v, _ = vec.run_stacked(params, 0, stack)
    _assert_trees_equal(tr_s, tr_v, rtol=1e-4, atol=1e-5)


# --------------------------------------------------------------------------- #
# full backend-equivalence matrix: every array backend vs the sequential
# reference on the same cohort data (async runs with a full buffer, so its
# single flush at staleness 0 must reproduce the synchronous round; the 2-D
# sharded backend additionally shards params over the "model" axis and only
# runs on a multi-device host — CI forces 8 CPU devices via
# XLA_FLAGS=--xla_force_host_platform_device_count=8)
# --------------------------------------------------------------------------- #
needs_multidevice = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="2-D (data, model) mesh needs >= 4 devices "
           "(run with XLA_FLAGS=--xla_force_host_platform_device_count=8)")

_MATRIX_BACKENDS = {
    "vectorized": lambda a, o, h: VectorizedRuntime(a, o, h),
    "sharded": lambda a, o, h: ShardedRuntime(a, o, h),
    "sharded-2d": lambda a, o, h: ShardedRuntime(a, o, h, model_parallel=2),
    "async-zero-staleness": lambda a, o, h: AsyncBufferedRuntime(
        a, o, h, buffer_size=0, staleness_schedule="polynomial"),
    "async-2d": lambda a, o, h: AsyncBufferedRuntime(
        a, o, h, buffer_size=0, model_parallel=2),
}
_MATRIX_REF = {}


def _matrix_reference(setup, request):
    """Per-setup cache: one stack + the sequential reference result."""
    if setup not in _MATRIX_REF:
        adapter, params, batchers = request.getfixturevalue(setup)
        hp = CurriculumHP(mu=0.01) if setup == "cnn_setup" \
            else CurriculumHP(enabled=False, mu=0.01)
        opt = sgd(0.05, momentum=0.9, weight_decay=5e-4)
        stack = stack_round(batchers, range(len(batchers)), local_epochs=1)
        seq = SequentialRuntime(adapter, opt, hp)
        _MATRIX_REF[setup] = (adapter, params, opt, hp, stack,
                              seq.run_stacked(params, 1, stack))
    return _MATRIX_REF[setup]


@pytest.mark.parametrize("backend", [
    pytest.param(b, marks=(needs_multidevice,) if b.endswith("-2d") else ())
    for b in sorted(_MATRIX_BACKENDS)])
@pytest.mark.parametrize("setup", ["cnn_setup", "tx_setup"])
def test_backend_matrix_matches_sequential(setup, backend, request):
    adapter, params, opt, hp, stack, (tr_ref, m_ref) = \
        _matrix_reference(setup, request)
    rt = _MATRIX_BACKENDS[backend](adapter, opt, hp)
    tr, m = rt.run_stacked(params, 1, stack)
    _assert_trees_equal(tr_ref, tr, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(float(m_ref["mean_local_loss"]),
                               float(m["mean_local_loss"]), rtol=1e-4)


# --------------------------------------------------------------------------- #
# fused-path matrix (ISSUE 6): every backend running the Pallas nHSIC in the
# loss (use_hsic_kernel=True, interpret mode on CPU) and — for the CNN — the
# im2col conv path, against the sequential reference running the *naive*
# paths (jnp Grams + lax convs), at the same tolerance as the plain matrix.
# --------------------------------------------------------------------------- #
_FUSED_REF = {}


def _fused_reference(setup, request):
    """Per-setup cache: naive-path sequential reference + fused adapter."""
    if setup not in _FUSED_REF:
        import dataclasses

        adapter, params, batchers = request.getfixturevalue(setup)
        if adapter.kind == "cnn":
            ref_ad = make_adapter(
                dataclasses.replace(adapter.cfg, conv_impl="lax"),
                adapter.plan.num_stages)
            fused_ad = make_adapter(
                dataclasses.replace(adapter.cfg, conv_impl="im2col"),
                adapter.plan.num_stages)
        else:
            ref_ad = fused_ad = adapter          # transformer has no convs
        opt = sgd(0.05, momentum=0.9, weight_decay=5e-4)
        hp_fused = CurriculumHP(mu=0.01, use_hsic_kernel=True)
        stack = stack_round(batchers, range(len(batchers)), local_epochs=1)
        seq = SequentialRuntime(ref_ad, opt, CurriculumHP(mu=0.01))
        _FUSED_REF[setup] = (fused_ad, params, opt, hp_fused, stack,
                             seq.run_stacked(params, 1, stack))
    return _FUSED_REF[setup]


@pytest.mark.parametrize("backend", [
    pytest.param(b, marks=(needs_multidevice,) if b.endswith("-2d") else ())
    for b in sorted(_MATRIX_BACKENDS)])
@pytest.mark.parametrize("setup", ["cnn_setup", "tx_setup"])
def test_fused_backend_matrix_matches_reference(setup, backend, request):
    fused_ad, params, opt, hp, stack, (tr_ref, m_ref) = \
        _fused_reference(setup, request)
    rt = _MATRIX_BACKENDS[backend](fused_ad, opt, hp)
    tr, m = rt.run_stacked(params, 1, stack)
    _assert_trees_equal(tr_ref, tr, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(float(m_ref["mean_local_loss"]),
                               float(m["mean_local_loss"]), rtol=1e-4)


@pytest.mark.parametrize("setup", ["cnn_setup", "tx_setup"])
def test_fused_sequential_matches_reference(setup, request):
    """The fused paths must also agree *within* the sequential backend, so a
    matrix failure cleanly separates kernel-vs-reference drift from
    cross-backend drift."""
    fused_ad, params, opt, hp, stack, (tr_ref, m_ref) = \
        _fused_reference(setup, request)
    tr, m = SequentialRuntime(fused_ad, opt, hp).run_stacked(params, 1, stack)
    _assert_trees_equal(tr_ref, tr, rtol=1e-4, atol=1e-5)


def test_sharded_matches_vectorized(cnn_setup):
    adapter, params, batchers = cnn_setup
    opt = sgd(0.05, momentum=0.9, weight_decay=5e-4)
    hp = CurriculumHP(mu=0.01)
    stack = stack_round(batchers, range(len(batchers)), local_epochs=1)
    vec = VectorizedRuntime(adapter, opt, hp)
    sh = ShardedRuntime(adapter, opt, hp)
    tr_v, m_v = vec.run_stacked(params, 0, stack)
    tr_h, m_h = sh.run_stacked(params, 0, stack)
    _assert_trees_equal(tr_v, tr_h, rtol=1e-4, atol=1e-5)
    assert m_h["cohort_losses"].shape == m_v["cohort_losses"].shape


@needs_multidevice
def test_sharded_2d_matches_vectorized_all_stages(cnn_setup):
    """2-D (data, model) rounds must reproduce the replicated vectorized
    round stage by stage, including merge back into (sharded) full params."""
    adapter, params, batchers = cnn_setup
    opt = sgd(0.05, momentum=0.9, weight_decay=5e-4)
    hp = CurriculumHP(mu=0.01)
    vec = VectorizedRuntime(adapter, opt, hp)
    sh2 = ShardedRuntime(adapter, opt, hp, model_parallel=2)
    assert sh2.model_shards == 2
    stack = stack_round(batchers, range(len(batchers)), local_epochs=1)
    for t in range(adapter.plan.num_stages):
        tr_v, m_v = vec.run_stacked(params, t, stack)
        tr_s, m_s = sh2.run_stacked(params, t, stack)
        _assert_trees_equal(tr_v, tr_s, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(float(m_v["mean_local_loss"]),
                                   float(m_s["mean_local_loss"]), rtol=1e-4)
        # merging the model-sharded trainable back must keep the full
        # params usable (and sharded) for the next stage's split
        merged_v = adapter.merge_stage(params, tr_v, t)
        merged_s = adapter.merge_stage(params, tr_s, t)
        _assert_trees_equal(merged_v, merged_s, rtol=1e-4, atol=1e-5)


@needs_multidevice
def test_sharded_2d_halves_per_device_trainable_bytes(cnn_setup):
    """model_parallel=2 must place ~half the trainable bytes per device
    (small unsharded leaves — norms, biases — keep it from exactly 1/2)."""
    from repro.launch.sharding import per_device_nbytes
    adapter, params, batchers = cnn_setup
    opt = sgd(0.05, momentum=0.9, weight_decay=5e-4)
    hp = CurriculumHP(mu=0.01)
    stack = stack_round(batchers, range(len(batchers)), local_epochs=1)
    tr_v, _ = VectorizedRuntime(adapter, opt, hp).run_stacked(params, 1,
                                                              stack)
    tr_s, _ = ShardedRuntime(adapter, opt, hp,
                             model_parallel=2).run_stacked(params, 1, stack)
    replicated, sharded = per_device_nbytes(tr_v), per_device_nbytes(tr_s)
    assert sharded < 0.65 * replicated, (sharded, replicated)


def test_zero_weight_round_rejected(cnn_setup):
    adapter, params, batchers = cnn_setup
    vec = VectorizedRuntime(adapter, sgd(0.05), CurriculumHP())
    stack = stack_round(batchers, [0], local_epochs=1)
    stack.weights = np.zeros_like(stack.weights)
    with pytest.raises(ValueError):
        vec.run_stacked(params, 0, stack)


# --------------------------------------------------------------------------- #
# the vectorized runtime's reused stack buffer
# --------------------------------------------------------------------------- #
class _UnbufferedVectorized(VectorizedRuntime):
    def _round_buffer(self):
        return None


def test_round_buffer_only_where_the_round_consumes_the_stack(cnn_setup):
    adapter, _, _ = cnn_setup
    opt, hp = sgd(0.05), CurriculumHP()
    assert SequentialRuntime(adapter, opt, hp)._round_buffer() is None
    assert AsyncBufferedRuntime(adapter, opt, hp)._round_buffer() is None
    vec = VectorizedRuntime(adapter, opt, hp)
    assert vec._round_buffer() is vec._round_buffer() is not None
    sh = ShardedRuntime(adapter, opt, hp)
    assert sh._round_buffer() is not vec._round_buffer()


def test_reused_stack_buffer_rounds_bit_identical_to_fresh(cnn_setup):
    """Rounds whose widest cohort grows, shrinks and grows again give the
    same params and losses whether the stack is written into the runtime's
    buffer (grown twice, then reused) or into fresh arrays.  On the CPU
    the device arrays may alias the buffer, so this also shows the buffer
    is not refilled while the last round can still read it."""
    import copy

    from repro.common import spans
    adapter, params, batchers = cnn_setup
    opt, hp = sgd(0.05, momentum=0.9), CurriculumHP(mu=0.01)
    vec = VectorizedRuntime(adapter, opt, hp)
    plain = _UnbufferedVectorized(adapter, opt, hp)
    plain._programs = vec._programs                 # compile each E once
    mine, theirs = copy.deepcopy(batchers), copy.deepcopy(batchers)
    kept = stack_round(copy.deepcopy(batchers), local_epochs=1)
    before = jax.tree.map(np.copy, kept.batches)
    vec.run_stacked(params, 0, kept)                # fresh stack, on device

    cohorts, schedule = [0, 1, 2], [1, 2, 1, 2]     # E up, down, repeat
    per_sample = 8 * 8 * 3 * 4 + 4                  # image f32 + label i32
    outs, counted, p = [], [], params
    for epochs in schedule:                         # no host sync between
        with spans.recording() as rec:
            outs.append(vec.run_round(p, 0, mine, cohorts, epochs))
        p = outs[-1].params
        counted.append({k: v for (k, _), v in rec.counters.items()
                        if k.startswith("stack_buffer")})
    p, had = params, 0
    for r, (out, epochs) in enumerate(zip(outs, schedule)):
        ref = plain.run_round(p, 0, theirs, cohorts, epochs)
        p = ref.params
        for x, y in zip(jax.tree.leaves((out.params, out.mean_loss,
                                         out.cohort_losses)),
                        jax.tree.leaves((ref.params, ref.mean_loss,
                                         ref.cohort_losses))):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        assert out.num_batches == ref.num_batches
        need = len(cohorts) * max(out.num_batches) * 16 * per_sample
        if r in (0, 1):
            assert counted[r] == {"stack_buffer_grown_bytes": need - had}
            had = need
        else:
            assert counted[r] == {"stack_buffer_reused": 1}
    for x, y in zip(jax.tree.leaves(kept.batches), jax.tree.leaves(before)):
        np.testing.assert_array_equal(x, y)


# --------------------------------------------------------------------------- #
# aggregation einsum path
# --------------------------------------------------------------------------- #
def test_weighted_average_zero_sum_raises():
    tree = {"w": jnp.ones((3,))}
    with pytest.raises(ValueError):
        agg.weighted_average([tree, tree], [0.0, 0.0])
    with pytest.raises(ValueError):
        agg.weighted_average([tree], [float("nan")])


def test_weighted_average_zero_sum_guard_edge_cases():
    """The zero-sum guard, exercised directly: all-zero weights (every
    cohort fully dropped), a single client, and mixed dropped cohorts."""
    tree = {"w": jnp.asarray([1.0, 2.0, 3.0])}
    # all-dropped cohort: completed-step weighting zeroes every weight
    with pytest.raises(ValueError, match="positive finite"):
        agg.weighted_average([tree, tree, tree], [0.0, 0.0, 0.0])
    with pytest.raises(ValueError):
        agg.weighted_average([tree], [0.0])           # single client, zero
    with pytest.raises(ValueError):
        agg.weighted_average([tree], [float("inf")])
    # single client with positive weight: exactly its own params
    out = agg.weighted_average([tree], [7.0])
    np.testing.assert_allclose(np.asarray(out["w"]),
                               np.asarray(tree["w"]), rtol=1e-6)
    # partially-dropped cohort: zero-weight members contribute nothing
    other = {"w": jnp.asarray([100.0, 100.0, 100.0])}
    out = agg.weighted_average([tree, other], [5.0, 0.0])
    np.testing.assert_allclose(np.asarray(out["w"]),
                               np.asarray(tree["w"]), rtol=1e-6)


def test_weighted_average_matches_manual_einsum():
    rng = np.random.default_rng(0)
    trees = [{"w": jnp.asarray(rng.standard_normal((4, 3)), jnp.float32)}
             for _ in range(3)]
    weights = [1.0, 2.0, 5.0]
    out = agg.weighted_average(trees, weights)
    w = np.asarray(weights) / np.sum(weights)
    ref = sum(wi * np.asarray(t["w"], np.float64)
              for wi, t in zip(w, trees))
    np.testing.assert_allclose(np.asarray(out["w"]), ref,
                               rtol=1e-4, atol=1e-6)


# --------------------------------------------------------------------------- #
# runtime factory + server integration
# --------------------------------------------------------------------------- #
def test_make_runtime_resolution(cnn_setup):
    adapter, _, _ = cnn_setup
    opt, hp = sgd(0.05), CurriculumHP()
    rt = make_runtime("vectorized", adapter, opt, hp)
    assert isinstance(rt, VectorizedRuntime)
    assert make_runtime(rt, adapter, opt, hp) is rt       # passthrough
    with pytest.raises(ValueError):
        make_runtime("warp-drive", adapter, opt, hp)


def test_make_runtime_rejects_kwargs_on_instance(cnn_setup):
    """Constructor kwargs cannot apply to an already-built runtime — they
    used to be silently discarded (e.g. a buffer_size that never took
    effect); now that is a loud error naming the ignored kwargs."""
    adapter, _, _ = cnn_setup
    opt, hp = sgd(0.05), CurriculumHP()
    rt = make_runtime("vectorized", adapter, opt, hp)
    with pytest.raises(ValueError, match="buffer_size"):
        make_runtime(rt, adapter, opt, hp, buffer_size=4)
    with pytest.raises(ValueError, match="model_parallel"):
        make_runtime(rt, adapter, opt, hp, model_parallel=2)


def test_sequential_zero_sample_round_is_lost_not_crash(cnn_setup):
    """The sequential fast path with every cohort at zero samples must
    return the documented lost round (params unchanged, NaN loss) exactly
    like the base-class stacked path — not raise from the Eq. 1 zero-sum
    guard or divide by zero in the loss weights."""

    class _EmptyBatcher:
        ds = ()
        num_samples = 0
        steps_per_epoch = 0

        def epoch(self):
            return iter(())

    adapter, params, _ = cnn_setup
    seq = SequentialRuntime(adapter, sgd(0.05), CurriculumHP(mu=0.01))
    out = seq.run_round(params, 0, [_EmptyBatcher(), _EmptyBatcher()],
                        [0, 1], local_epochs=1)
    _assert_trees_equal(out.params, params, rtol=0, atol=0)
    assert np.isnan(float(out.mean_loss))
    assert out.n_uploads == 0
    assert out.num_samples == [0.0, 0.0]
    assert out.num_batches == [0, 0]


def test_evaluate_batched_matches_sequential_loop():
    """The vmapped one-program evaluate must count exactly like the
    per-batch reference loop on identical data (image and LM labels)."""
    ccfg = CNNConfig(name="r18", arch="resnet18", num_classes=4,
                     image_size=8, width_mult=0.125)
    ds = make_image_dataset(0, 160, num_classes=4, image_size=8)
    test = make_image_dataset(3, 96, num_classes=4, image_size=8)
    flc = FLConfig(n_devices=4, clients_per_round=2, local_epochs=1,
                   batch_size=16, num_stages=2, seed=0)
    parts = dirichlet_partition(0, ds.labels, 4, alpha=1.0)
    srv = NeuLiteServer(make_adapter(ccfg, flc.num_stages),
                        [ds.subset(p) for p in parts], flc)
    # identical data: same-seed batchers replay the same shuffles
    srv.test_batcher = Batcher(test, 32, seed=11, kind="image")
    loop = srv.evaluate(max_batches=3, batched=False)
    srv.test_batcher = Batcher(test, 32, seed=11, kind="image")
    batched = srv.evaluate(max_batches=3, batched=True)
    assert batched == loop


def test_evaluate_batched_handles_ragged_final_batch():
    """External batchers may yield a ragged final partial batch; the
    batched path must pad it with mask=False rows and count exactly like
    the per-batch loop (it used to crash in np.stack or miscount)."""
    ccfg = CNNConfig(name="r18", arch="resnet18", num_classes=4,
                     image_size=8, width_mult=0.125)
    ds = make_image_dataset(0, 160, num_classes=4, image_size=8)
    test = make_image_dataset(3, 80, num_classes=4, image_size=8)
    flc = FLConfig(n_devices=4, clients_per_round=2, local_epochs=1,
                   batch_size=16, num_stages=2, seed=0)
    parts = dirichlet_partition(0, ds.labels, 4, alpha=1.0)
    srv = NeuLiteServer(make_adapter(ccfg, flc.num_stages),
                        [ds.subset(p) for p in parts], flc)

    class _RaggedBatcher:
        """Yields 2 full 32-row batches + 1 partial 16-row batch."""

        def epoch(self):
            for lo, hi in ((0, 32), (32, 64), (64, 80)):
                yield {"inputs": {"images": test.images[lo:hi]},
                       "labels": test.labels[lo:hi]}

    srv.test_batcher = _RaggedBatcher()
    loop = srv.evaluate(max_batches=3, batched=False)
    batched = srv.evaluate(max_batches=3, batched=True)
    assert batched == loop


def test_evaluate_batched_matches_loop_lm_labels(tx_setup):
    adapter, params, _ = tx_setup
    test = make_lm_dataset(5, 48, 8, 64)
    flc = FLConfig(n_devices=2, clients_per_round=1, local_epochs=1,
                   batch_size=8, num_stages=2, seed=0)
    srv = NeuLiteServer(adapter, [test], flc, data_kind="lm")
    srv.params = params
    srv.test_batcher = Batcher(test, 16, seed=3, kind="lm")
    loop = srv.evaluate(max_batches=2, batched=False)
    srv.test_batcher = Batcher(test, 16, seed=3, kind="lm")
    batched = srv.evaluate(max_batches=2, batched=True)
    assert batched == loop


# --------------------------------------------------------------------------- #
# regression tests: lost-round / mesh correctness bugfixes
# --------------------------------------------------------------------------- #
def test_plateau_schedule_skips_nonfinite_observations():
    """A lost round observes NaN.  NaN must neither become ``_best`` (which
    would make every later improvement check False and force-advance the
    stage after ``patience`` rounds) nor count toward patience or the
    ``max_rounds_per_stage`` budget — but a run whose every round is
    non-finite (divergence, not dropout) must still hit the budget."""
    sch = PlateauSchedule(num_stages=3, patience=2, min_delta=1e-3,
                          max_rounds_per_stage=6)
    sch.observe(0, 1.0)
    for r in range(1, 5):                     # a burst of lost rounds
        sch.observe(r, float("nan"))
    assert sch.stage(5) == 0                  # no force-advance
    assert sch._best == 1.0                   # NaN never became best
    assert sch._bad == 0                      # nor counted toward patience
    assert sch._rounds_in_stage == 1          # nor the max-rounds budget
    sch.observe(5, 0.9)                       # still improving
    assert sch._best == 0.9 and sch.stage(6) == 0
    sch.observe(6, 0.9)                       # genuine plateau still works
    sch.observe(7, 0.9)
    assert sch.stage(8) == 1

    # divergence backstop: max_rounds_per_stage consecutive non-finite
    # rounds (no finite round ever resets the streak) still advance, so a
    # permanently-NaN run cannot pin its stage forever
    div = PlateauSchedule(num_stages=2, patience=2, max_rounds_per_stage=3)
    div.observe(0, 1.0)
    div.observe(1, float("nan"))
    div.observe(2, 0.8)                       # finite: streak resets
    for r in range(3, 6):
        div.observe(r, float("nan"))
    assert div.stage(6) == 1


def test_make_host_mesh_clamps_non_divisor_model_parallel():
    from repro.launch.mesh import make_host_mesh
    n = jax.device_count()
    with pytest.warns(UserWarning, match="clamping"):
        mesh = make_host_mesh(n + 1)          # over-ask: clamped + warned
    assert mesh.shape["data"] * mesh.shape["model"] == n
    bad = next((k for k in range(2, n) if n % k), None)
    if bad is not None:                       # e.g. 3 on an 8-device host
        with pytest.warns(UserWarning, match="clamping"):
            mesh = make_host_mesh(bad)
        assert mesh.shape["data"] * mesh.shape["model"] == n
        assert n % mesh.shape["model"] == 0 and mesh.shape["model"] < bad


def test_sharded_runtime_rejects_contradictory_mesh(cnn_setup):
    """An explicit mesh whose "model" axis disagrees with model_parallel
    must not silently run with the mesh's (e.g. replicated) sharding."""
    from repro.launch.mesh import make_host_mesh
    adapter, _, _ = cnn_setup
    with pytest.raises(ValueError, match="contradicts"):
        ShardedRuntime(adapter, sgd(0.05), CurriculumHP(),
                       mesh=make_host_mesh(1), model_parallel=4)


def test_async_lost_round_reports_zero_sim_time(cnn_setup):
    """An all-dropped async round flushes nothing and never waits: it must
    report its own (zero) virtual clock, not fall back to the server's
    synchronous straggler wall-clock."""
    adapter, params, batchers = cnn_setup
    rt = AsyncBufferedRuntime(adapter, sgd(0.05), CurriculumHP(),
                              buffer_size=2)
    out = rt.run_round(params, 0, batchers, [0, 1], local_epochs=1,
                       faults=[0, 0])         # every client crashes at step 0
    assert out.round_sim_time == 0.0
    assert out.n_uploads == 0
    assert not np.isfinite(float(out.mean_loss))
    _assert_trees_equal(out.params, params, rtol=0, atol=0)


@pytest.mark.slow
def test_server_backends_agree():
    """Same seeds + same per-round data => same post-round params whether
    the server runs the reference loop or the one-program cohort round."""
    ds = make_image_dataset(0, 240, num_classes=4, image_size=8)
    parts = dirichlet_partition(0, ds.labels, 6, alpha=1.0)
    clients = [ds.subset(p) for p in parts]
    ccfg = CNNConfig(name="r18", arch="resnet18", num_classes=4,
                     image_size=8, width_mult=0.125)
    flc = FLConfig(n_devices=6, clients_per_round=3, local_epochs=1,
                   batch_size=16, num_stages=2, seed=0)

    def run(runtime):
        srv = NeuLiteServer(make_adapter(ccfg, flc.num_stages), clients,
                            flc, runtime=runtime)
        hist = srv.run(2)
        assert all(np.isfinite(h.mean_loss) for h in hist if h.n_selected)
        return srv.params

    p_seq = run("sequential")
    p_vec = run("vectorized")
    _assert_trees_equal(p_seq, p_vec, rtol=1e-4, atol=1e-5)


# --------------------------------------------------------------------------- #
# crash safety: k rounds -> save_state -> restore in a fresh server -> N-k
# rounds must equal N straight rounds EXACTLY (params, history, versions) on
# every backend — the resume contract of NeuLiteServer.save_state/restore
# --------------------------------------------------------------------------- #
import dataclasses  # noqa: E402

_RESUME_DATA = {}


def _resume_data():
    if not _RESUME_DATA:
        ds = make_image_dataset(0, 160, num_classes=4, image_size=8)
        parts = dirichlet_partition(0, ds.labels, 4, alpha=1.0)
        _RESUME_DATA["clients"] = [ds.subset(p) for p in parts]
        _RESUME_DATA["test"] = make_image_dataset(3, 64, num_classes=4,
                                                  image_size=8)
        _RESUME_DATA["ccfg"] = CNNConfig(name="r18", arch="resnet18",
                                         num_classes=4, image_size=8,
                                         width_mult=0.125)
    return _RESUME_DATA


def _resume_server(kw):
    d = _resume_data()
    flc = FLConfig(n_devices=4, clients_per_round=3, local_epochs=1,
                   batch_size=16, num_stages=2, seed=0, **kw)
    adapter = make_adapter(d["ccfg"], flc.num_stages)
    srv = NeuLiteServer(adapter, d["clients"], flc,
                        test_batcher=Batcher(d["test"], 32, seed=7,
                                             kind="image"))
    return srv, adapter


def _assert_history_equal(ref, res):
    assert len(ref) == len(res)
    for ha, hb in zip(ref, res):
        da, db = dataclasses.asdict(ha), dataclasses.asdict(hb)
        for k, va in da.items():
            vb = db[k]
            if isinstance(va, float) and np.isnan(va):
                assert np.isnan(vb), (k, ha, hb)
            else:
                assert va == vb, (k, ha, hb)


# buffer_size=4 > cohort 3: round k's deliveries stay PENDING across the
# save point (the carried-straggler case — they must flush after restore
# exactly as they would have in the uninterrupted run)
_RESUME_BACKENDS = {
    "sequential": dict(runtime="sequential"),
    "vectorized": dict(runtime="vectorized"),
    "sharded": dict(runtime="sharded"),
    "async": dict(runtime="async", buffer_size=4,
                  dropout_schedule="constant", dropout_rate=0.15),
    "sharded-2d": dict(runtime="sharded", model_parallel=2),
    "async-2d": dict(runtime="async", buffer_size=4, model_parallel=2),
}


@pytest.mark.parametrize("backend", [
    pytest.param(b, marks=(needs_multidevice,) if b.endswith("-2d") else ())
    for b in sorted(_RESUME_BACKENDS)])
def test_resume_matches_straight_run_exactly(backend, tmp_path):
    kw = _RESUME_BACKENDS[backend]
    ref, _ = _resume_server(kw)
    ref.run(4)

    srv, adapter = _resume_server(kw)
    srv.run(2)
    if backend.startswith("async"):
        # the kill point must strand deliveries in the pending buffer
        assert len(srv.runtime.state) > 0
    srv.save_state(str(tmp_path))

    d = _resume_data()
    res = NeuLiteServer.restore(adapter, d["clients"], srv.flc,
                                str(tmp_path),
                                test_batcher=Batcher(d["test"], 32, seed=7,
                                                     kind="image"))
    assert res.next_round == 2
    res.run(2)

    for a, b in zip(jax.tree.leaves(ref.params),
                    jax.tree.leaves(res.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    _assert_history_equal(ref.history, res.history)
    if backend.startswith("async"):
        assert res.runtime.state.version == ref.runtime.state.version
        assert res.runtime.state.clock == ref.runtime.state.clock
        assert len(res.runtime.state) == len(ref.runtime.state)


@pytest.mark.parametrize("extra", [
    dict(schedule="plateau"),
    dict(selection="tifl"),
    dict(selection="oort"),
], ids=["plateau", "tifl", "oort"])
def test_resume_preserves_schedule_and_selector_state(extra, tmp_path):
    kw = dict(runtime="vectorized", **extra)
    ref, _ = _resume_server(kw)
    ref.run(4)

    srv, adapter = _resume_server(kw)
    srv.run(2)
    srv.save_state(str(tmp_path))
    d = _resume_data()
    res = NeuLiteServer.restore(adapter, d["clients"], srv.flc,
                                str(tmp_path),
                                test_batcher=Batcher(d["test"], 32, seed=7,
                                                     kind="image"))
    res.run(2)

    for a, b in zip(jax.tree.leaves(ref.params),
                    jax.tree.leaves(res.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    _assert_history_equal(ref.history, res.history)
    # the mutable policy/schedule state itself must have converged to the
    # straight run's, not just the params
    assert ref.schedule.state_dict() == res.schedule.state_dict()
    assert ref.selector.state_dict() == res.selector.state_dict()


def test_restore_rejects_config_mismatch(tmp_path):
    srv, adapter = _resume_server(dict(runtime="vectorized"))
    srv.run(1)
    srv.save_state(str(tmp_path))
    d = _resume_data()
    flc2 = dataclasses.replace(srv.flc, runtime="async", buffer_size=2)
    with pytest.raises(ValueError, match="mismatch on runtime"):
        NeuLiteServer.restore(adapter, d["clients"], flc2, str(tmp_path))
    flc3 = dataclasses.replace(srv.flc, selection="oort")
    with pytest.raises(ValueError, match="mismatch on selector_kind"):
        NeuLiteServer.restore(adapter, d["clients"], flc3, str(tmp_path))


def test_restore_rejects_plain_param_checkpoint(tmp_path):
    from repro.checkpoint import save_checkpoint
    srv, adapter = _resume_server(dict(runtime="vectorized"))
    save_checkpoint(str(tmp_path), 0, srv.params, meta={"arch": "r18"})
    d = _resume_data()
    with pytest.raises(ValueError, match="not a NeuLiteServer state"):
        NeuLiteServer.restore(adapter, d["clients"], srv.flc,
                              str(tmp_path))
