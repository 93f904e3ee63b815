"""The comparison that decides ``correct``, at toy size on the CPU: the
control (the reference computed in bfloat16 in the program's place) and the
planted faults come out not correct, and the sound program comes out
correct."""
from __future__ import annotations

import numpy as np
import pytest

from bench import checks, control, toy


@pytest.mark.parametrize("mode", ["control", "half_batch", "frozen_leaf"])
def test_control_and_faults_are_not_correct(mode):
    cell = toy.cell("resnet18-cifar10.stage3")
    (seed, numbers, _), = control.readings(cell, [7], mode,
                                           require_tpu=False)
    assert not checks.verdict(numbers, cell.limits), numbers


def test_sound_program_readings_are_correct():
    cell = toy.cell("vit12-miniimagenet.stage2")
    (seed, numbers, _), = control.readings(cell, [7], "program",
                                           require_tpu=False)
    assert checks.verdict(numbers, cell.limits), numbers
    assert numbers["untouched_max_abs"] == 0.0
    assert np.isfinite(numbers["round_loss_gap"])
