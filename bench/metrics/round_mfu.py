"""The whole round's share of the chip's peak: the operations the stage
requires per real sample (``bench/flops/<family>.py``), times the real
samples trained in the window, over the window and the peak of the chip
(``bench/peaks.json``).  Padding steps do no required work and are not
counted."""
import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"


def peak_flops(kind: str) -> float:
    peaks = json.loads(PEAKS.read_text())["chips"]
    if kind not in peaks:
        raise KeyError(f"no peak for device kind {kind!r} in {PEAKS}")
    return float(peaks[kind]["bf16_flops_per_s"])


def read(ctx):
    w = ctx.window
    if not w.real_samples or ctx.window_s <= 0:
        return None
    cell = ctx.cell
    per_sample = cell.flops.flops_per_sample(
        cell.config, cell.traffic["stage"],
        cell.traffic["batch_size"])["total"]
    return (100.0 * per_sample * w.real_samples / ctx.window_s
            / peak_flops(ctx.device_kind))
