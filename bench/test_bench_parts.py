"""The benchmark's parts on the CPU: files load by name, the plain
reference matches the program, the operation counters match XLA's, the
trace reduction and the per-layer readers."""
from __future__ import annotations

import dataclasses
import json

import jax
import numpy as np
import pytest

from bench import checks, harness, toy
from bench import trace as tr
from bench.cache import ROOT

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]


# --------------------------------------------------------------------------- #
# files
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("name", CELLS)
def test_cell_files_load_and_match_the_program(name):
    cell = harness.load_cell(name, SPEC)
    assert set(cell.limits) <= set(checks.NUMBERS)
    assert 0 <= cell.traffic["stage"] < cell.config["num_stages"]
    adapter = harness.build_adapter(cell.config)
    harness.check_layout(adapter, cell.family, cell.config,
                         jax.random.PRNGKey(0))
    parts = cell.flops.flops_per_sample(cell.config, cell.traffic["stage"],
                                        cell.traffic["batch_size"])
    assert parts["total"] > 0
    for m in cell.per_layer:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()


def test_every_config_has_a_cell_and_a_reference():
    used = {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert c["name"] in used
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert (ROOT / "bench" / "reference" / f"{cfg['family']}.py").exists()
        assert (ROOT / "bench" / "flops" / f"{cfg['family']}.py").exists()


# --------------------------------------------------------------------------- #
# the plain reference against the program
# --------------------------------------------------------------------------- #
def _stage_pieces(cell_name, t, config=None):
    from repro.core import CurriculumHP, make_stage_loss

    from bench import traffic as traffic_mod
    from bench.reference.common import Hyper, stage_loss
    cell = harness.overridden(toy.cell(cell_name), config)
    cfg = cell.config
    adapter = harness.build_adapter(cfg)
    params = jax.jit(lambda k: cell.family.init(cfg, k))(
        jax.random.PRNGKey(3))
    hyper = Hyper.from_traffic(cell.traffic)
    hp = CurriculumHP(lambda1_max=hyper.lambda1, lambda2_max=hyper.lambda2,
                      mu=hyper.mu)
    labels = np.arange(8, dtype=np.int32) % cfg["num_classes"]
    images = traffic_mod.images(5, labels, cfg["num_classes"],
                                cfg["image_size"], cfg["in_channels"])
    prog_loss = make_stage_loss(adapter, hp, t)
    fr_p, tr_p = adapter.split_stage(params, t)
    fr_r, tr_r = cell.family.split(cfg, params, t)
    ref = lambda trn: stage_loss(cell.family, cfg, hyper, t, trn, fr_r,
                                 images, labels, tr_r)
    prog = lambda trn: prog_loss(trn, fr_p, {"inputs": {"images": images},
                                             "labels": labels}, tr_p)[0]
    return adapter, cell, params, (prog, tr_p), (ref, tr_r), images, labels


@pytest.mark.parametrize("cell_name,t", [
    ("resnet18-cifar10.stage3", 0), ("resnet18-cifar10.stage3", 3),
    ("vit12-miniimagenet.stage2", 0), ("vit12-miniimagenet.stage2", 2)])
def test_reference_stage_loss_and_grads_match_the_program(cell_name, t):
    adapter, cell, params, (prog, tr_p), (ref, tr_r), *_ = _stage_pieces(
        cell_name, t)
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.jit(jax.value_and_grad(prog))(tr_p)
        lr, gr = jax.jit(jax.value_and_grad(ref))(tr_r)
        # compare through the full tree, where both layouts agree
        merged_p = adapter.merge_stage(params, gp, t)
        merged_r = cell.family.merge(cell.config, params, gr, t)
    assert checks.rel_err(lp, lr) < 1e-5
    err, leaf = checks.tree_rel_err(merged_p, merged_r)
    assert err < 1e-4, leaf


# --------------------------------------------------------------------------- #
# operation counters against XLA
# --------------------------------------------------------------------------- #
# the toy CNN at twice its width and side, where GroupNorm and the other
# element-wise work XLA counts are a smaller share than at toy size
CNN_QUARTER = {"width_mult": 0.25, "image_size": 16,
               "program": {"builder": "resnet18",
                           "kwargs": {"num_classes": 10, "image_size": 16,
                                      "width_mult": 0.25}}}


@pytest.mark.parametrize("cell_name,t,remat,config", [
    ("resnet18-cifar10.stage3", 0, 0.0, CNN_QUARTER),
    ("resnet18-cifar10.stage3", 3, 0.0, CNN_QUARTER),
    ("vit12-miniimagenet.stage2", 0, 1.0, None),
    ("vit12-miniimagenet.stage2", 2, 1.0, None)])
def test_flop_counter_matches_xla_cost_analysis(cell_name, t, remat, config):
    """One unbatched stage step of the program (loss, gradients, SGD): XLA
    counts every operation, the counter only products, and XLA also counts
    the transformer's recomputed trainable forward (``remat`` times it).
    So the counter, plus that recomputation, is at most XLA's count and
    within 20% of it."""
    from repro import optim
    from repro.core import CurriculumHP, make_stage_step
    adapter, cell, params, *_, images, labels = _stage_pieces(cell_name, t,
                                                              config)
    fr, trn = adapter.split_stage(params, t)
    step = make_stage_step(adapter, optim.sgd(0.05), CurriculumHP(), t)
    batch = {"inputs": {"images": images}, "labels": labels}
    cost = jax.jit(step).lower(optim.sgd(0.05).init(trn), trn, fr, batch,
                               trn).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    xla = float(cost["flops"])
    b = len(labels)
    parts = cell.flops.flops_per_sample(cell.config, t, b)
    mine = (parts["total"] + remat * parts["trainable"] / 3) * b
    assert 0.8 * xla <= mine <= xla, (mine, xla)


# --------------------------------------------------------------------------- #
# the trace reduction and the readers
# --------------------------------------------------------------------------- #
def _trace():
    E = tr.Event
    ops = [E("%conv.1 = f32[8] convolution(...)", 10, 30),
           E("%fusion.2 = f32[8] fusion(...)", 25, 40),
           E("%while.3 = (s32[], f32[8]) while(...)", 60, 70),
           E("%conv.4 = f32[8] convolution(...)", 60, 65),
           E("%conv.5 = f32[8] convolution(...)", 67, 70),
           E("%fusion.6 = f32[8] fusion(...)", 95, 130)]
    modules = [E("jit_round_fn(1)", 10, 40), E("jit_round_fn(2)", 55, 80),
               E("jit_other", 95, 130)]
    host = [E(tr.WINDOW, 0, 100), E(tr.ROUND, 0, 50), E(tr.ROUND, 50, 80),
            E(tr.BETWEEN, 80, 100), E("TransferToDevice", 42, 58),
            E("Linearize", 40, 49), E("Linearize", 50, 59)]
    return tr.Trace(ops={"/device:TPU:0": ops},
                    modules={"/device:TPU:0": modules}, host=host,
                    window=(0, 100))


def test_trace_reduction():
    t = _trace()
    # busy: [10, 40] + [60, 70] + [95, 100] inside the window
    assert tr.busy_ns(t) == 45
    assert tr.gaps(t) == [(0, 10), (40, 60), (70, 95)]
    gaps = dict(tr.idle_gaps(t))
    # (40, 60): Linearize overlaps it 18 ns over two spans, the transfer 16
    assert gaps == pytest.approx({tr.ROUND: 10e-9, "Linearize": 20e-9,
                                  tr.BETWEEN: 25e-9})
    t.ops["/device:TPU:0"] = [e for e in t.ops["/device:TPU:0"]
                              if not e.name.startswith("%while")]
    assert dict(tr.idle_gaps(t))[tr.IN_PROGRAM] == pytest.approx(2e-9)
    assert dict(tr.top_ops(t)) == pytest.approx({"conv": 28e-9,
                                                 "fusion": 20e-9})
    assert tr.op_kind("%add_select_fusion.54 = (f32[2]) fusion(x)") == \
        "add_select_fusion"
    assert tr.module_ns(t, "jit_round_fn") == 55


def test_readers():
    cell = harness.load_cell("resnet18-cifar10.stage3", SPEC)
    win = harness.Window(seconds=1.0, rounds=2, real_steps=30, slots=40,
                         real_samples=960, programs=0)
    ctx = harness.Context(cell=cell, trace=_trace(), window=win,
                          window_s=100e-9, device_kind="TPU v5 lite")
    got = {k: v["value"] for k, v in harness.read_per_layer(cell, ctx).items()}
    per = cell.flops.flops_per_sample(cell.config, 3, 32)["total"]
    assert got == pytest.approx({
        "device_idle_share": 55.0, "round_program_ms": 55e-6 / 2,
        "round_mfu": 100 * per * 960 / 100e-9 / 197e12,
        "step_useful_share": 75.0, "compiles_in_window": 0})
    with pytest.raises(KeyError):
        harness.read_per_layer(cell, dataclasses.replace(
            ctx, device_kind="TPU v9 imaginary"))
