"""One run of one cell: set-up, the measured window, the check, the result.

Everything that belongs to one configuration, traffic mix or metric is
found by name from ``BENCHMARK.json``:

- ``bench/configs/<config>.json``: the model, the program's builder for it,
  and the sizes the plain reference reads;
- ``bench/workloads/<traffic>.json``: the federation and the stage;
- ``bench/limits/<cell>.json``: the limit of each number ``correct`` holds;
- ``bench/reference/<family>.py`` and ``bench/flops/<family>.py``;
- ``bench/metrics/<metric>.py``: one reader per per-layer metric.

A run:

1. makes the data on the host and the weights on the device from
   ``--seed`` (``bench/traffic.py``, the reference's ``init`` in one jitted
   call), builds a ``NeuLiteServer`` over a counting batcher bank on the
   vectorized runtime, and holds its schedule at the traffic's stage;
2. warms the round program for every cohort length E the population allows,
   through the runtime's own ``run_stacked``;
3. runs the first three rounds through ``server.run_round`` with the bank
   recording, keeping the weights before and after;
4. measures: closed-loop ``server.run_round`` calls until ``--seconds``
   have passed, noting on the host's clock when each round and each gap
   between rounds began and ended; with ``--trace 1`` under the profiler,
   which records the device's operations only (host tracer off), for at
   most ``TRACE_SECONDS``;
5. reads the device's peak memory, frees the server, and replays the three
   rounds with the plain reference at the configuration's
   ``reference_precision`` (``bench/checks.py`` says what is compared).
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Optional

import numpy as np

from bench import checks, traffic as traffic_mod
from bench.cache import ROOT, CompileCounter

BENCH = ROOT / "bench"
# A traced run measures at most this long, so that it ends well within its
# 360 s: on a v5e, stopping the profiler after 30 s of ResNet rounds took up
# to 53 s, and whole traced runs with a 51 s window took up to 352 s.
TRACE_SECONDS = 30.0


class BenchError(RuntimeError):
    pass


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    limits: dict
    chips: int
    end_to_end: list
    per_layer: list

    @property
    def family(self):
        return importlib.import_module(f"bench.reference.{self.config['family']}")

    @property
    def flops(self):
        return importlib.import_module(f"bench.flops.{self.config['family']}")


def load_cell(name: str, spec: Optional[dict] = None) -> Cell:
    spec = spec or load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg = load_json(ROOT / configs[w["config"]]["file"])
    tr = load_json(BENCH / "workloads" / f"{w['traffic']}.json")
    limits = load_json(BENCH / "limits" / f"{name}.json")
    return Cell(name=name, config=cfg, traffic=tr, limits=limits,
                chips=w["chips"], end_to_end=spec["end_to_end"],
                per_layer=spec["per_layer"])


def overridden(cell: Cell, config: Optional[dict] = None,
               traffic: Optional[dict] = None) -> Cell:
    """The cell with some sizes replaced; the CPU rehearsal uses it."""
    merge = lambda base, new: {**base, **(new or {})}
    return dataclasses.replace(cell, config=merge(cell.config, config),
                               traffic=merge(cell.traffic, traffic))


class FixedStage:
    """A schedule that holds every round at one stage."""

    revisits_stages = True

    def __init__(self, stage: int):
        self.t = stage

    def stage(self, round_idx: int) -> int:
        return self.t

    def observe(self, round_idx: int, metric: float) -> None:
        pass


# --------------------------------------------------------------------------- #
# set-up
# --------------------------------------------------------------------------- #
def build_adapter(cfg: dict):
    from repro.configs import paper_models
    from repro.core import make_adapter
    prog = cfg["program"]
    model = getattr(paper_models, prog["builder"])(**prog["kwargs"])
    adapter = make_adapter(model, cfg["num_stages"], cfg["boundary_units"])
    bounds = [list(b) for b in adapter.plan.bounds]
    if bounds != cfg["stage_units"]:
        raise BenchError(f"the program splits the units as {bounds}, the "
                         f"configuration says {cfg['stage_units']}")
    return adapter


def check_layout(adapter, family, cfg: dict, key) -> None:
    """The reference's weight tree has the program's layout, leaf for leaf."""
    import jax
    want = jax.eval_shape(adapter.init_params, key)
    got = jax.eval_shape(lambda k: family.init(cfg, k), key)
    shape = lambda tree: jax.tree.map(lambda a: (a.shape, str(a.dtype)),
                                      tree)
    if shape(got) != shape(want):
        raise BenchError("the reference's weight tree does not match the "
                         "program's")


def make_server(cell: Cell, adapter, bank):
    from repro.federated.server import FLConfig, NeuLiteServer
    tr, cfg = cell.traffic, cell.config
    opt, cur = tr["optimizer"], tr["curriculum"]
    flc = FLConfig(
        n_devices=tr["n_clients"], clients_per_round=tr["clients_per_round"],
        local_epochs=tr["local_epochs"], batch_size=tr["batch_size"],
        lr=opt["lr"], momentum=opt["momentum"],
        weight_decay=opt["weight_decay"], num_stages=cfg["num_stages"],
        boundary_units=cfg["boundary_units"], mu=cur["mu"],
        lambda1=cur["lambda1"], lambda2=cur["lambda2"], curriculum=True,
        alpha=tr["alpha"], selection="random",
        seed=tr["population_seed"], runtime="vectorized")
    server = NeuLiteServer(adapter, bank, flc, data_kind="image")
    server.schedule = FixedStage(tr["stage"])
    return server


def warm_lengths(server, steps, cohort: int, t: int):
    """Cohort lengths E to compile for: over the clients the stage's memory
    admits, the distinct step counts at or above the cohort-th smallest."""
    fleet = server.fleet
    ids = np.arange(fleet.n_devices)
    ok = fleet.mem_bytes(ids) >= server.stage_mem_requirement(t)
    feasible = [s for s, y in zip(steps, ok) if y]
    if not feasible:
        raise BenchError(f"no client can hold stage {t}")
    return traffic_mod.warm_step_counts(feasible,
                                        min(cohort, len(feasible)))


def warm(server, t: int, lengths, cohort: int, batch: int, images, labels):
    """Run the round program once for each cohort length, on real images."""
    import jax
    from repro.data.loader import RoundStack
    for e in lengths:
        n = cohort * e * batch
        rows = np.arange(n) % len(labels)
        shape = (cohort, e, batch)
        stack = RoundStack(
            batches={"inputs": {"images": images[rows].reshape(
                         *shape, *images.shape[1:])},
                     "labels": labels[rows].reshape(shape)},
            step_mask=np.ones((cohort, e), bool),
            weights=np.ones((cohort,), np.float32),
            num_batches=[e] * cohort)
        jax.block_until_ready(server.runtime.run_stacked(server.params, t,
                                                         stack))


@dataclasses.dataclass
class CheckRounds:
    snaps: list            # host weight trees: before round 1, after 1, 3
    losses: list           # per round: [round mean, cohort means...]
    cohorts: list          # per round: [(sample_count, [row arrays])]
    results: list          # RoundResult of each


def check_rounds(server, bank, parts, n_rounds: int = 3) -> CheckRounds:
    """The first rounds through ``server.run_round``, recorded."""
    import jax
    rt = server.runtime
    seen = []
    own_run_round = rt.run_round

    def recording(params, t, batchers, cohorts, local_epochs, faults=None):
        out = own_run_round(params, t, batchers, cohorts, local_epochs,
                            faults=faults)
        seen.append(([int(c) for c in cohorts], out.cohort_losses))
        return out

    rt.run_round = recording
    bank.record = True
    snaps, results, cohorts = [jax.device_get(server.params)], [], []
    try:
        for r in range(n_rounds):
            results.append(server.run_round(r))
            log = bank.take_log()
            cids, _ = seen[-1]
            rows = {c: [] for c in cids}
            for cid, idx in log:
                rows[cid].append(idx)
            cohorts.append([(len(parts[c]), rows[c]) for c in cids])
            if r in (0, n_rounds - 1):
                snaps.append(jax.device_get(server.params))
    finally:
        del rt.run_round
        bank.record = False
        bank.take_round()
    losses = [[rr.mean_loss] + list(np.asarray(jax.device_get(cl))
                                    [:len(cids)])
              for rr, (cids, cl) in zip(results, seen)]
    return CheckRounds(snaps=snaps, losses=losses, cohorts=cohorts,
                       results=results)


# --------------------------------------------------------------------------- #
# the reference
# --------------------------------------------------------------------------- #
def reference_rounds(cell: Cell, rec: CheckRounds, images, labels,
                     make_round: Optional[Callable] = None):
    """Replay the recorded rounds with the plain reference, from the same
    starting weights, at the configuration's ``reference_precision``: the
    default (one bfloat16 pass per product on the TPU, as the program
    runs) or highest.  Returns
    (weight trees before round 1 and after rounds 1 and 3, losses per
    round)."""
    import jax
    import jax.numpy as jnp
    from bench.reference.common import Hyper, Round
    t = cell.traffic["stage"]
    if make_round is None:
        make_round = lambda fam, cfg, hyper, t: Round(fam, cfg, hyper, t)
    rnd = make_round(cell.family, cell.config,
                     Hyper.from_traffic(cell.traffic), t)
    with jax.default_matmul_precision(cell.config["reference_precision"]):
        params = jax.tree.map(jnp.asarray, rec.snaps[0])
        snaps, losses = [rec.snaps[0]], []
        for r, cohorts in enumerate(rec.cohorts):
            batches = [(n, [(images[idx], labels[idx]) for idx in rows])
                       for n, rows in cohorts]
            params, mean, per = rnd.run(params, batches)
            losses.append([mean] + list(per))
            if r in (0, len(rec.cohorts) - 1):
                snaps.append(jax.device_get(params))
    return snaps, losses


# --------------------------------------------------------------------------- #
# a run
# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class Window:
    seconds: float = 0.0
    rounds: int = 0
    failed: int = 0
    real_steps: int = 0
    slots: int = 0
    real_samples: int = 0
    programs: int = 0
    spans: list = dataclasses.field(default_factory=list)


def measure(server, bank, first_round: int, seconds: float,
            counter: CompileCounter) -> Window:
    """Closed loop: the next round starts when the last has returned; the
    window closes at the end of the first round that ends past
    ``seconds``, once the weights are on the device.  ``spans`` holds
    (name, start, end) on the wall clock in ns: the window, each round and
    each stretch between rounds."""
    import jax
    from bench.trace import BETWEEN, ROUND, WINDOW
    w = Window()
    programs0 = counter.programs
    r = first_round
    w0 = time.time_ns()
    t0 = time.perf_counter()
    while True:
        a = time.time_ns()
        rr = server.run_round(r)
        b = time.time_ns()
        steps, samples = bank.take_round()
        w.rounds += 1
        r += 1
        if rr.n_selected == 0 or not math.isfinite(rr.mean_loss):
            w.failed += 1
        if steps:
            w.real_steps += sum(steps)
            w.slots += len(steps) * max(steps)
        w.real_samples += samples
        w.spans += [(ROUND, a, b), (BETWEEN, b, time.time_ns())]
        if time.perf_counter() - t0 >= seconds:
            break
    jax.block_until_ready(server.params)
    w.seconds = time.perf_counter() - t0
    w.spans.append((WINDOW, w0, time.time_ns()))
    w.programs = counter.programs - programs0
    return w


def device_check(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise BenchError(f"needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return devs


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_tpu: bool = True,
        patch_server: Optional[Callable] = None) -> dict:
    """One run; returns the result object (``metrics`` holds only what this
    run measured on a TPU)."""
    import jax
    devs = device_check(cell.chips, require_tpu)
    counter = CompileCounter()
    cfg, tr = cell.config, cell.traffic
    t = tr["stage"]
    if not 0 <= t < cfg["num_stages"]:
        raise BenchError(f"stage {t} outside 0..{cfg['num_stages'] - 1}")
    family = cell.family
    tc = traffic_mod.Traffic.from_dict(tr)

    labels, parts = traffic_mod.population(
        tc, cfg["dataset"]["num_samples"], cfg["num_classes"])
    images = traffic_mod.images(seed, labels, cfg["num_classes"],
                                cfg["image_size"], cfg["in_channels"])
    from bench.bank import CountingBank
    bank = CountingBank(images, labels, parts, tc.batch_size, seed)

    adapter = build_adapter(cfg)
    key = jax.random.PRNGKey(int(np.random.default_rng(seed)
                                 .integers(2 ** 31)))
    check_layout(adapter, family, cfg, key)
    server = make_server(cell, adapter, bank)
    server.params = jax.jit(lambda k: family.init(cfg, k))(key)
    if patch_server is not None:
        patch_server(server)

    steps = traffic_mod.steps_per_client(parts, tc.batch_size,
                                         tc.local_epochs)
    lengths = warm_lengths(server, steps, tc.clients_per_round, t)
    cohort = min(tc.clients_per_round, server.fleet.feasible_count(
        server.stage_mem_requirement(t)))
    warm(server, t, lengths, cohort, tc.batch_size, images, labels)
    rec = check_rounds(server, bank, parts)

    setup_s = time.perf_counter() - t_start
    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        win = measure(server, bank, len(rec.results),
                      min(seconds, TRACE_SECONDS) if trace else seconds,
                      counter)
    finally:
        if trace:
            jax.profiler.stop_trace()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs[:cell.chips])

    del server, adapter
    gc.collect()
    t_ref = time.perf_counter()
    ref_snaps, ref_losses = reference_rounds(cell, rec, images, labels)
    reference_s = time.perf_counter() - t_ref
    numbers = checks.compare(family, cfg, t, rec.snaps, rec.losses,
                             ref_snaps, ref_losses)
    correct = checks.verdict(numbers, cell.limits)
    attempted = len(rec.results) + win.rounds
    failed = win.failed + sum(1 for rr in rec.results
                              if rr.n_selected == 0
                              or not math.isfinite(rr.mean_loss))

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct and failed == 0),
              "attempted": attempted, "failed": failed}
    metrics, breakdown = {}, None
    on_chip = devs[0].platform == "tpu"
    if not trace:
        values = {"samples_per_s": win.real_samples / win.seconds,
                  "peak_hbm_gib": peak / 2 ** 30, "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if on_chip}
    else:
        from bench import trace as trace_mod
        tr_data = trace_mod.read_xspace(log_dir, win.spans)
        shutil.rmtree(log_dir, ignore_errors=True)
        if on_chip:
            device["busy_s"] = trace_mod.busy_ns(tr_data) * 1e-9
            device["window_s"] = tr_data.window_ns * 1e-9
            ctx = Context(cell=cell, trace=tr_data, window=win,
                          window_s=tr_data.window_ns * 1e-9,
                          device_kind=devs[0].device_kind)
            metrics = read_per_layer(cell, ctx)
            breakdown = {"device_ops": [list(x) for x in
                                        trace_mod.top_ops(tr_data)],
                         "idle_gaps": [list(x) for x in
                                       trace_mod.idle_gaps(tr_data)]}
    result["metrics"] = metrics
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": numbers[k], "limit": v}
                        for k, v in cell.limits.items()}
    result["info"] = {"numbers": numbers, "reference_s": reference_s,
                      "rounds_in_window": win.rounds,
                      "window_s": win.seconds,
                      "programs_in_window": win.programs,
                      "warm_lengths": lengths}
    return result


@dataclasses.dataclass
class Context:
    """What a per-layer reader may read: the reduced trace of the window,
    the window's counts, and the cell."""
    cell: Cell
    trace: object
    window: Window
    window_s: float
    device_kind: str


def read_per_layer(cell: Cell, ctx: "Context") -> dict:
    """Each per-layer metric from its reader; one that finds nothing to
    read is left out."""
    out = {}
    for m in cell.per_layer:
        value = importlib.import_module(f"bench.metrics.{m['name']}").read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def emit(result: dict) -> None:
    """The checks as the last lines of standard error, then the result as
    the last line of standard output, with the checks as its last key."""
    for k, v in result["checks"].items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    print(f"correct {result['correct']}", file=sys.stderr, flush=True)
    ordered = {k: v for k, v in result.items() if k != "checks"}
    ordered["checks"] = result["checks"]
    print(json.dumps(ordered), flush=True)
