"""Shared benchmark harness utilities."""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "results")


def timeit(fn, *args, warmup: int = 1, iters: int = 5):
    """Median wall time per call in seconds (blocks on jax outputs)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def csv_row(name: str, us_per_call: float, derived: str):
    print(f"{name},{us_per_call:.1f},{derived}")


def ensure_dir(*parts):
    p = os.path.join(RESULTS_DIR, *parts)
    os.makedirs(p, exist_ok=True)
    return p


def make_fl_setup(seed=0, n_clients=20, n_train=2000, n_test=512,
                  num_classes=10, image_size=16, alpha=1.0):
    from repro.data import Batcher, dirichlet_partition, make_image_dataset
    ds = make_image_dataset(seed, n_train, num_classes=num_classes,
                            image_size=image_size)
    test = make_image_dataset(seed + 1, n_test, num_classes=num_classes,
                              image_size=image_size)
    parts = dirichlet_partition(seed, ds.labels, n_clients, alpha=alpha)
    clients = [ds.subset(p) for p in parts]
    test_batcher = Batcher(test, 128, kind="image")
    return clients, test_batcher
