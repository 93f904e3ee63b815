"""The program's spans and named scopes on a trace's clock
(``bench/spans.py``), on hand-built traces, and a span-traced toy run on
the CPU (``bench/trace_spans.py``)."""
from __future__ import annotations

import pytest

from bench import harness, toy
from bench import spans as sp
from bench import trace as tr
from repro.common import spans

E = tr.Event
H = sp.HostSpan
PLANE = "/device:TPU:0"


def _trace(ops, modules, host, window=(0, 100)):
    return tr.Trace(ops={PLANE: ops}, modules={PLANE: modules}, host=host,
                    window=window)


def _round(r0, dispatch=(40, 45), sync=(45, 80)):
    """One round's program spans from ``r0``: open, stack, to_device,
    dispatch, merge, sync (times relative to r0)."""
    return [H(spans_name, r0 + a, r0 + b, depth)
            for spans_name, a, b, depth in [
                ("fl.round", 0, 90, 0), ("fl.open", 0, 10, 1),
                ("fl.stack", 10, 30, 1), ("fl.to_device", 30, 40, 1),
                ("fl.dispatch", *dispatch, 1), ("fl.merge", 44, 45, 1),
                ("fl.sync", *sync, 1)]]


# --------------------------------------------------------------------------- #
# placing recorded spans
# --------------------------------------------------------------------------- #
def test_place_shifts_recorded_spans_and_gives_depths():
    with spans.recording() as rec:
        with spans.span("fl.round", round=0):
            with spans.span("fl.stack"):
                with spans.span("inner"):
                    pass
            with spans.span("fl.sync"):
                pass
    placed = sp.place(rec.spans, rec.spans[0].start_ns - 5)
    assert [(s.name, s.depth) for s in placed] == [
        ("fl.round", 0), ("fl.stack", 1), ("inner", 2), ("fl.sync", 1)]
    assert placed[0].start_ns == 5
    assert placed[1].end_ns == rec.spans[1].end_ns - rec.spans[0].start_ns + 5


def test_self_ms_takes_the_children_out():
    rec = spans.Recorder(spans=[
        spans.Span("fl.round", 0, 10_000_000, None, 0),
        spans.Span("fl.stack", 1_000_000, 4_000_000, 0, 0),
        spans.Span("fl.sync", 5_000_000, 9_000_000, 0, 0),
        spans.Span("fl.round", 20_000_000, 24_000_000, None, 1),
        spans.Span("fl.stack", 20_000_000, 22_000_000, 3, 1)])
    assert sp.self_ms(rec.spans) == pytest.approx(
        {"fl.round": 2.5, "fl.stack": 2.5, "fl.sync": 2.0})


# --------------------------------------------------------------------------- #
# idle gaps
# --------------------------------------------------------------------------- #
def test_innermost_span_labels_each_piece_of_a_gap():
    # the round program executes [72, 95]; the gap [5, 50] ends at another
    # program's operation, so it is the host's, piece by piece
    ops = [E("%a.1 = f32[] add()", 0, 5), E("%b.1 = f32[] add()", 50, 70),
           E("%c.1 = f32[] add()", 72, 90), E("%d.1 = f32[] add()", 92, 95),
           E("%e.1 = f32[] add()", 98, 100)]
    modules = [E("jit_other", 0, 5), E("jit_other", 50, 70),
               E("jit_round_fn(1)", 72, 95), E("jit_other", 98, 100)]
    host = [E(tr.WINDOW, 0, 100), E(tr.ROUND, 0, 95),
            E(tr.BETWEEN, 95, 100)]
    t = _trace(ops, modules, host)
    got = sp.idle_labels(t, _round(2, dispatch=(40, 44)))
    # [5, 50]: open, stack, to_device, dispatch, merge, sync; [70, 72]
    # leads into the round program after its dispatch began; [90, 92]
    # inside it; [95, 98] between rounds
    assert got == pytest.approx({
        "fl.open": 7e-9, "fl.stack": 20e-9, "fl.to_device": 10e-9,
        "fl.dispatch": 4e-9, "fl.merge": 1e-9, "fl.sync": 3e-9,
        sp.INPUT_WAIT: 2e-9, tr.IN_PROGRAM: 2e-9, tr.BETWEEN: 3e-9})
    # without program spans the harness's round holds what the host did
    assert sp.idle_labels(t, []) == pytest.approx({
        tr.ROUND: 47e-9, tr.IN_PROGRAM: 2e-9, tr.BETWEEN: 3e-9})


def test_gap_ending_at_an_execution_after_its_dispatch_is_input_wait():
    # the program executes [60, 75] though it was dispatched at 40: the
    # device waited [40, 60] for its inputs, whatever the host was in
    ops = [E("%a.1 = f32[] add()", 0, 2), E("%b.1 = f32[] add()", 60, 75)]
    modules = [E("jit_round_fn(1)", 0, 2), E("jit_round_fn(1)", 60, 75)]
    host = [E(tr.WINDOW, 0, 100), E(tr.ROUND, 0, 92),
            E(tr.BETWEEN, 92, 100)]
    t = _trace(ops, modules, host)
    got = sp.idle_labels(t, _round(0))
    # after the execution: the sync's tail, the round's own code, the
    # harness after run_round returned, the stretch between rounds
    assert got == pytest.approx({
        "fl.open": 8e-9, "fl.stack": 20e-9, "fl.to_device": 10e-9,
        sp.INPUT_WAIT: 20e-9, "fl.sync": 5e-9, "fl.round": 10e-9,
        tr.ROUND: 2e-9, tr.BETWEEN: 8e-9})
    # a gap that ends at another program's start is not input wait
    t.modules[PLANE][1] = E("jit_other", 60, 75)
    assert sp.INPUT_WAIT not in sp.idle_labels(t, _round(0))


def test_clock_check():
    modules = [E("jit_round_fn(1)", 50, 70), E("jit_round_fn(2)", 150, 170)]
    t = _trace([], modules, [E(tr.WINDOW, 0, 200)], window=(0, 200))
    assert sp.clock_violation_ms(t, _round(0) + _round(100)) == 0.0
    # a dispatch noted 2 ns after its execution began, a sync that ended
    # 7 ns before its execution did
    late = (_round(0, dispatch=(52, 53), sync=(53, 80))
            + _round(100, sync=(45, 63)))
    assert sp.clock_violation_ms(t, late) == pytest.approx(7e-6)
    assert sp.clock_violation_ms(t, []) is None
    with pytest.raises(ValueError):
        sp.clock_violation_ms(t, _round(0))


# --------------------------------------------------------------------------- #
# named scopes
# --------------------------------------------------------------------------- #
HLO = """HloModule jit_round_fn, is_scheduled=true

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %mul.0 = f32[8]{0} multiply(%param_0, %param_0),\
 metadata={op_name="f/body/transpose(jvp(neulite.block))/mul"}
}

ENTRY %main.2 (w.1: f32[8], x.1: f32[4,8]) -> f32[8] {
  %w.1 = f32[8]{0} parameter(0), metadata={op_name="w"}
  %x.1 = f32[4,8]{1,0} parameter(1), metadata={op_name="x"}
  %copy-start.1 = (f32[8]{0:S(1)}, f32[8]{0}, u32[]{:S(2)}) copy-start(%w.1)
  %copy-done.1 = f32[8]{0:S(1)} copy-done(%copy-start.1)
  %dot.3 = f32[8]{0} dot(%copy-done.1, %x.1), metadata={op_name="f/jvp(neulite.prefix)/dot"}
  %fusion.4 = f32[8]{0} fusion(%dot.3), kind=kLoop, calls=%fused_computation.1,\
 metadata={op_name="f/jvp(neulite.prefix)/mul"}
  %slice.5 = f32[4]{0} slice(%fusion.4), slice={[0:4]}, metadata={op_name="f/body/dynamic_slice"}
  %select.6 = f32[8]{0} select(%fusion.4, %fusion.4, %w.1),\
 metadata={op_name="f/neulite.optimizer/jvp(neulite.mentor)/select_n"}
  ROOT %add.7 = f32[8]{0} add(%select.6, %w.1), metadata={op_name="f/neulite.eq1/add"}
}
"""


def test_scope_table_reads_metadata_fusion_roots_and_users():
    table = sp.scope_table(HLO)
    scopes = {name: scope for name, (_, scope) in table.items()}
    assert scopes["dot.3"] == "prefix"
    assert scopes["fusion.4"] == "block"              # its root's scope
    assert scopes["copy-done.1"] == "prefix"          # its user's
    assert scopes["copy-start.1"] == "prefix"
    assert scopes["slice.5"] == sp.UNSCOPED           # op_name, no scope
    assert scopes["select.6"] == "mentor"             # the innermost
    assert scopes["add.7"] == "eq1"
    assert table["copy-start.1"][0] == \
        "(f32[8]{0:S(1)}, f32[8]{0}, u32[]{:S(2)}) copy-start"
    assert sp.op_key("%fusion.4 = f32[8]{0} fusion(f32[8]{0} %dot.3), "
                     "kind=kLoop, calls=%fused_computation.1") == \
        ("fusion.4", "f32[8]{0} fusion")


def test_scope_seconds_counts_each_instant_once():
    table = sp.scope_table(HLO)
    ops = [E("%copy-start.1 = (f32[8]{0:S(1)}, f32[8]{0}, u32[]{:S(2)}) "
             "copy-start(f32[8]{0} %w.1)", 10, 11),
           E("%copy-done.1 = f32[8]{0:S(1)} copy-done((f32[8]{0:S(1)}, "
             "f32[8]{0}, u32[]{:S(2)}) %copy-start.1)", 11, 20),
           E("%dot.3 = f32[8]{0} dot(f32[8]{0} %copy-done.1, f32[4,8]{1,0} "
             "%x.1)", 15, 30),                        # overlaps 5 ns
           E("%while.9 = (f32[8]{0}) while((f32[8]{0}) %t)", 30, 50),
           E("%fusion.4 = f32[8]{0} fusion(f32[8]{0} %dot.3)", 30, 40),
           E("%slice.5 = f32[4]{0} slice(f32[8]{0} %fusion.4)", 40, 42),
           E("%mystery.1 = f32[8]{0} add(f32[8]{0} %a)", 42, 44),
           E("%add.7 = f32[4]{0} add(f32[8]{0} %select.6)", 44, 45),
           E("%add.7 = f32[8]{0} add(f32[8]{0} %select.6)", 45, 50),
           E("%dot.3 = f32[8]{0} dot(f32[8]{0} %x)", 65, 70),  # not round's
           E("%dot.3 = f32[8]{0} dot(f32[8]{0} %x)", 95, 105)]
    modules = [E("jit_round_fn(7)", 10, 50), E("jit_other", 60, 70),
               E("jit_round_fn(7)", 95, 110)]
    t = _trace(ops, modules, [E(tr.WINDOW, 0, 100)])
    got = sp.scope_seconds(t, [table])
    # copy-start 1 + copy-done 9 + dot 10 (its first 5 ns were the
    # copy's) + the second execution's dot inside the window 5; fusion 10;
    # slice 2 + an unknown instruction 2 + an add of another shape 1;
    # add 5; the while loop's own event is left out
    assert got == pytest.approx({"prefix": 25e-9, "block": 10e-9,
                                 sp.UNSCOPED: 5e-9, "eq1": 5e-9})
    assert sum(got.values()) == pytest.approx(45e-9)   # the busy time
    # an execution takes the module whose instructions its names match
    other = {name: ("f64" + head, "mentor")
             for name, (head, _) in table.items()}
    assert sp.scope_seconds(t, [other, table]) == pytest.approx(got)
    assert sp.scope_seconds(t, []) == {}


# --------------------------------------------------------------------------- #
# a span-traced toy run
# --------------------------------------------------------------------------- #
def test_span_traced_toy_run_on_the_cpu():
    from bench import trace_spans
    own = (harness.warm, harness.measure, tr.read_xspace)
    result = trace_spans.traced_run(
        toy.cell("resnet18-cifar10.stage3"), 2 ** 33 + 17, 0.3,
        require_tpu=False)
    assert (harness.warm, harness.measure, tr.read_xspace) == own
    assert spans._active is None
    assert result["correct"] is True and result["metrics"] == {}
    got = result["spans"]
    assert got["rounds"] == result["info"]["rounds_in_window"]
    assert got["bytes_per_round"]["stack_bytes"] == \
        got["bytes_per_round"]["h2d_bytes"] > 0
    assert set(got["metrics"]) == {
        "round_open_ms", "stack_ms", "h2d_ms", "input_wait_ms",
        "stage_slice_ms", "prefix_ms", "block_ms", "mentor_ms",
        "optimizer_ms"}
    assert got["metrics"]["stack_ms"] > 0
    # the CPU trace has no device plane: the spans name the whole window
    assert sum(got["idle_s"].values()) == pytest.approx(
        result["info"]["window_s"], rel=0.05)


def test_scope_table_of_a_compiled_round_program(cnn_setup):
    """Every scope of the round program is found in its compiled text, and
    few of its instructions have none."""
    import jax

    from repro.core import CurriculumHP
    from repro.data.loader import stack_round
    from repro.federated.runtime import abstract_like, make_round_program
    from repro.optim import sgd
    adapter, params, batchers = cnn_setup
    t = adapter.plan.num_stages - 1
    stack = stack_round(batchers, local_steps=2)
    frozen, trainable = adapter.split_stage(params, t)
    text = jax.jit(make_round_program(
        adapter, sgd(0.05, 0.9, 5e-4), CurriculumHP(mu=0.01), t)).lower(
        *abstract_like((trainable, frozen, stack.batches, stack.weights,
                        stack.step_mask))).compile().as_text()
    scopes = [s for _, s in sp.scope_table(text).values()]
    assert set(sp.SCOPES) <= set(scopes)
    assert scopes.count(sp.UNSCOPED) < 0.25 * len(scopes)


@pytest.fixture(scope="module")
def cnn_setup():
    import jax

    from repro.core import make_adapter
    from repro.data import Batcher, dirichlet_partition, make_image_dataset
    from repro.models.cnn import CNNConfig
    ccfg = CNNConfig(name="r18", arch="resnet18", num_classes=4,
                     image_size=8, width_mult=0.125)
    adapter = make_adapter(ccfg, 2)
    params = adapter.init_params(jax.random.PRNGKey(0))
    ds = make_image_dataset(0, 200, num_classes=4, image_size=8)
    parts = dirichlet_partition(0, ds.labels, 4, alpha=1.0)
    return adapter, params, [Batcher(ds.subset(p), 16, seed=i, kind="image")
                             for i, p in enumerate(parts)]

