#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from.  Not run by the
benchmark's own runs.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --mode <mode>

For each seed it sets the cell up as a run does (data, weights, server),
drives the first three rounds, replays them with the plain float32
reference, and prints the numbers ``bench/checks.py`` compares, one JSON
line per seed.  ``--mode`` says what stands in the program's place:

``program``     the program itself: the sound readings (the lower end);
``control``     one precision step below the float32 the configuration
                states: weights and all arithmetic in bfloat16.  The
                program's own bfloat16 path where it has one (``dtype`` of
                the transformer), else the reference computed in bfloat16;
``half_batch``  the reference trained on half of each batch, the loss the
                mean over the rest;
``frozen_leaf`` the reference with one answer altered where it is made: the
                head's aggregate is dropped, so the head stays where it was;
``twin``        the reference itself, started from weights moved by 2^-20 of
                their size: what rounding-sized differences grow into over
                the three rounds, the floor under any sound reading.

A round that returns its state unchanged reads 1 on both norm gaps by
construction and needs no run.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

MODES = ("program", "control", "half_batch", "frozen_leaf", "twin")
TWIN = 2.0 ** -20


class FrozenLeafRound:
    """The reference round with the head's aggregate dropped."""

    def __init__(self, inner):
        self.inner = inner

    def run(self, params, cohorts):
        new, mean, per = self.inner.run(params, cohorts)
        model = dict(new["model"])
        model["head"] = params["model"]["head"]
        return {**new, "model": model}, mean, per


class TwinRound:
    """The reference round, its first starting weights scaled by
    (1 + 2^-20 z) with z standard normal."""

    def __init__(self, inner, seed: int = 0):
        self.inner = inner
        self.moved = False
        self.rng = seed

    def run(self, params, cohorts):
        import jax
        import numpy as np
        if not self.moved:
            rng = np.random.default_rng(self.rng)
            params = jax.tree.map(
                lambda a: a * (1 + TWIN * rng.standard_normal(a.shape)
                               ).astype(np.float32), params)
            self.moved = True
        return self.inner.run(params, cohorts)


def stand_in(mode: str, cell):
    """(make_round for the reference in the program's place, or None when
    the program itself stands there, program config change or None)."""
    import jax.numpy as jnp

    from bench.reference.common import Round
    batch = cell.traffic["batch_size"]
    if mode == "program":
        return None, None
    if mode == "control":
        if cell.config["family"] == "transformer":
            return None, {"dtype": "bfloat16"}
        return (lambda fam, cfg, hyper, t:
                Round(fam, cfg, hyper, t, dtype=jnp.bfloat16)), None
    if mode == "half_batch":
        return (lambda fam, cfg, hyper, t:
                Round(fam, cfg, hyper, t, batch_rows=batch // 2)), None
    if mode == "twin":
        return (lambda fam, cfg, hyper, t:
                TwinRound(Round(fam, cfg, hyper, t))), None
    if mode == "frozen_leaf":
        return (lambda fam, cfg, hyper, t:
                FrozenLeafRound(Round(fam, cfg, hyper, t))), None
    raise ValueError(mode)


def readings(cell, seeds, mode: str, require_tpu: bool = True):
    """Yields (seed, numbers, per-round loss gaps) for each seed."""
    import jax
    import numpy as np

    from bench import checks, harness
    from bench import traffic as traffic_mod
    from bench.bank import CountingBank
    harness.device_check(cell.chips, require_tpu)
    make_round, program_change = stand_in(mode, cell)
    cfg, t = cell.config, cell.traffic["stage"]
    tc = traffic_mod.Traffic.from_dict(cell.traffic)
    labels, parts = traffic_mod.population(
        tc, cfg["dataset"]["num_samples"], cfg["num_classes"])
    adapter = harness.build_adapter(cfg)
    if program_change:
        from repro.core import make_adapter
        adapter = make_adapter(
            dataclasses.replace(adapter.cfg, **program_change),
            cfg["num_stages"], cfg["boundary_units"])
    runtime = None
    for seed in seeds:
        images = traffic_mod.images(seed, labels, cfg["num_classes"],
                                    cfg["image_size"], cfg["in_channels"])
        bank = CountingBank(images, labels, parts, tc.batch_size, seed)
        key = jax.random.PRNGKey(int(np.random.default_rng(seed)
                                     .integers(2 ** 31)))
        server = harness.make_server(cell, adapter, bank)
        if runtime is not None:
            server.runtime = runtime
        runtime = server.runtime
        weights = jax.jit(lambda k: cell.family.init(cfg, k))(key)
        server.params = jax.tree.map(
            lambda w, p: w.astype(p.dtype), weights, server.params)
        rec = harness.check_rounds(server, bank, parts)
        rec.snaps = [jax.tree.map(lambda a: np.asarray(a, np.float32), s)
                     for s in rec.snaps]
        rec.snaps[0] = jax.device_get(weights)
        del server
        ref_snaps, ref_losses = harness.reference_rounds(cell, rec, images,
                                                         labels)
        if make_round is not None:
            rec.snaps, rec.losses = harness.reference_rounds(
                cell, rec, images, labels, make_round=make_round)
        numbers = checks.compare(cell.family, cfg, t, rec.snaps, rec.losses,
                                 ref_snaps, ref_losses)
        per_round = [checks.loss_gap(np.ravel(a), np.ravel(b))
                     for a, b in zip(rec.losses, ref_losses)]
        yield seed, numbers, per_round


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--mode", choices=MODES, required=True)
    args = ap.parse_args(argv)
    from bench.cache import enable_compile_cache
    enable_compile_cache()
    import jax

    from bench import harness
    cell = harness.load_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    for seed, numbers, per_round in readings(cell, seeds, args.mode):
        print(json.dumps({
            "workload": args.workload, "mode": args.mode, "seed": seed,
            "numbers": numbers, "round_loss_gaps": per_round,
            "correct": harness.checks.verdict(numbers, cell.limits),
            "device": jax.devices()[0].device_kind,
            "elapsed_s": time.perf_counter() - T_START}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
