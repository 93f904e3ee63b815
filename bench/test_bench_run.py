"""The harness end to end at toy size on the CPU: a sound run is correct
and prints no device metric; a run whose timed path is broken underneath
comes out not correct."""
from __future__ import annotations

import dataclasses
import json
import time

import jax
import pytest

from bench import harness, toy

CELL = "resnet18-cifar10.stage3"
SEED = 2 ** 33 + 17


def _run(capsys, patch=None, trace=False):
    result = harness.run(toy.cell(CELL), SEED, 0.3, trace,
                         t_start=time.perf_counter(), require_tpu=False,
                         patch_server=patch)
    harness.emit(result)
    out = capsys.readouterr()
    return result, out


@pytest.mark.parametrize("trace", [False, True], ids=["timed", "traced"])
def test_rehearsal_is_correct_and_prints_no_device_metric(capsys, trace):
    result, out = _run(capsys, trace=trace)
    line = json.loads(out.out.strip().splitlines()[-1])
    assert line["correct"] is True, line["checks"]
    assert line["metrics"] == {}
    assert "busy_s" not in line["device"] and "breakdown" not in line
    assert list(line)[-1] == "checks"
    assert line["attempted"] > 3 and line["failed"] == 0
    names = ["samples_per_s", "peak_hbm_gib", "setup_s", "round_mfu",
             "device_idle_share", "round_program_ms"]
    assert not any(n in out.out for n in names)
    assert out.err.strip().splitlines()[-1] == "correct True"


def _wrap(server, after=None, before=None):
    rt = server.runtime
    inner = rt._run_stack

    def run(t, trainable, frozen, stack):
        if before is not None:
            stack = before(stack)
        new, metrics = inner(t, trainable, frozen, stack)
        return (new if after is None else after(trainable, new)), metrics

    rt._run_stack = run


def _unchanged(server):
    _wrap(server, after=lambda old, new: old)


def _half_batch(server):
    def cut(stack):
        rows = stack.batches["labels"].shape[2] // 2
        return dataclasses.replace(stack, batches=jax.tree.map(
            lambda x: x[:, :, :rows], stack.batches))
    _wrap(server, before=cut)


def _altered(server):
    _wrap(server, after=lambda old, new: {**new, "head": old["head"]})


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _altered],
                         ids=["state_unchanged", "half_batch",
                              "answer_altered"])
def test_broken_timed_path_is_not_correct(capsys, fault):
    result, out = _run(capsys, patch=fault)
    assert result["correct"] is False, result["checks"]
    assert out.err.strip().splitlines()[-1] == "correct False"
