"""Toy sizes of the benchmark's configurations and traffic, for the CPU
rehearsal and tests.  The benchmark's own runs never use them."""
from __future__ import annotations

CONFIG = {
    "resnet18-cifar10": {
        "width_mult": 0.125, "image_size": 8,
        "dataset": {"num_samples": 3000},
        "program": {"builder": "resnet18",
                    "kwargs": {"num_classes": 10, "image_size": 8,
                               "width_mult": 0.125}}},
    "vit12-miniimagenet": {
        "num_layers": 3, "d_model": 48, "d_ff": 192, "image_size": 16,
        "num_classes": 10, "stage_units": [[0, 1], [1, 2], [2, 3]],
        "dataset": {"num_samples": 3000},
        "program": {"builder": "vit",
                    "kwargs": {"num_classes": 10, "image_size": 16,
                               "num_layers": 3, "d_model": 48}}},
}
TRAFFIC = {"n_clients": 40, "clients_per_round": 3, "batch_size": 8}


def cell(name: str):
    from bench import harness
    full = harness.load_cell(name)
    return harness.overridden(full, CONFIG[full.config["name"]], TRAFFIC)
