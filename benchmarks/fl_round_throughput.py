"""FL round throughput across ClientRuntime backends (paper §Efficiency).

One NeuLite round = C cohorts × E local steps + Eq. 1 aggregation.  The
sequential reference dispatches C·E jitted steps from Python with a host
round-trip per client; the vectorized runtime lowers the whole round to one
program; the sharded runtime runs that program under ``shard_map`` on the
host mesh.  Reported number = rounds/sec on the same pre-materialized
cohort batch stack (data pipeline excluded), for the paper's CNN
(ResNet18) and transformer (ViT) at CPU-benchmark scale.

  PYTHONPATH=src python -m benchmarks.fl_round_throughput [--cohorts 16]

``--population 1e2 .. 1e6`` instead sweeps the *population* axis on the
streaming fleet (``federated.devices.Fleet`` + procedural client bank):
full NeuLite rounds at a fixed cohort size, reporting rounds/sec, the
Python-heap peak of server construction + one round, and process maxrss —
the numbers that must stay flat when round opening is O(cohort).

``--runtime async`` instead reports the stateful buffered-async (FedBuff)
server over ``--rounds`` rounds on an absolute virtual clock: cohorts
deliver deltas at ``steps / speed`` under a heterogeneous device-tier
speed mix, the server flushes every K arrivals at true versions-behind
staleness, stragglers pending at a round's close carry into the next round
(the ``carried`` column), and the simulated wall-clock (round open to last
flush) is compared against the synchronous barrier (slowest straggler).
Combine with ``--model-parallel K`` to run the async local program and
buffered flushes on the 2-D (data, model) mesh (per-device trainable
bytes shrink ~1/K).

``--model-parallel K`` reports the 2-D (data, model) sharded round: stage
params / optimizer state / per-cohort local weights shard K-ways over the
"model" axis, and the report compares per-device trainable bytes (and
rounds/sec) against the replicated vectorized path.  Forces
``--xla_force_host_platform_device_count=8`` when the host has too few
devices.
"""
from __future__ import annotations

import argparse
import os

from benchmarks.common import csv_row, timeit


def _force_host_devices(n: int):
    """Fake ``n`` CPU devices.  XLA reads the flag at backend init (the
    first device query), so this works as long as it runs before any jax
    device use — merely importing jax is fine."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}".strip())


def _setup(kind: str, num_cohorts: int, batch_size: int, local_steps: int,
           seed: int = 0, conv_impl: str | None = None,
           use_hsic_kernel: bool = False):
    import dataclasses

    import jax
    import numpy as np
    from repro.configs.paper_models import resnet18, vit
    from repro.core import CurriculumHP, make_adapter
    from repro.data import Batcher, iid_partition, make_image_dataset
    from repro.data.loader import stack_round
    from repro.optim import sgd

    if kind == "cnn":
        cfg = resnet18(num_classes=10, image_size=8, width_mult=0.0625)
        if conv_impl is not None:
            cfg = dataclasses.replace(cfg, conv_impl=conv_impl)
        image_size = 8
    else:
        cfg = vit(num_classes=10, image_size=16, num_layers=4, d_model=64)
        image_size = 16
    adapter = make_adapter(cfg, num_stages=4)
    params = adapter.init_params(jax.random.PRNGKey(seed))

    n = num_cohorts * batch_size * local_steps
    ds = make_image_dataset(seed, n, num_classes=10, image_size=image_size)
    parts = iid_partition(seed, n, num_cohorts)
    batchers = [Batcher(ds.subset(p), batch_size, seed=seed + i,
                        kind="image")
                for i, p in enumerate(parts)]
    stack = stack_round(batchers, range(num_cohorts),
                        local_steps=local_steps)
    opt = sgd(0.05, momentum=0.9, weight_decay=5e-4)
    hp = CurriculumHP(mu=0.01, use_hsic_kernel=use_hsic_kernel)
    return adapter, params, opt, hp, stack


def bench(kind: str, num_cohorts: int = 16, batch_size: int = 4,
          local_steps: int = 2, stage: int = 1, iters: int = 3,
          conv_impl: str | None = None, use_hsic_kernel: bool = False):
    """rounds/sec per backend on one stage-t round; returns {name: r/s}."""
    import jax
    from repro.federated.runtime import RUNTIMES

    adapter, params, opt, hp, stack = _setup(
        kind, num_cohorts, batch_size, local_steps, conv_impl=conv_impl,
        use_hsic_kernel=use_hsic_kernel)
    out = {}
    for name, cls in RUNTIMES.items():
        runtime = cls(adapter, opt, hp)

        def one_round(rt=runtime):
            new_tr, metrics = rt.run_stacked(params, stage, stack)
            return jax.tree.leaves(new_tr)[0], metrics["mean_local_loss"]

        out[name] = 1.0 / timeit(one_round, warmup=1, iters=iters)
    return out


def bench_async(kind: str, num_cohorts: int = 16, batch_size: int = 4,
                local_steps: int = 2, stage: int = 1,
                buffer_size: int = 0, seed: int = 0, rounds: int = 2,
                model_parallel: int = 1):
    """Simulated-time speedup of buffered-async rounds vs the synchronous
    barrier over ``rounds`` stateful server rounds (stragglers pending at
    one round's close carry over and flush in a later one); returns a dict
    of the virtual-clock numbers.  ``model_parallel > 1`` runs the async
    local training + buffered flushes on the 2-D (data, model) mesh and
    reports per-device trainable bytes vs the replicated async path."""
    import numpy as np
    from repro.federated.devices import sample_devices
    from repro.federated.runtime import AsyncBufferedRuntime
    from repro.launch.sharding import per_device_nbytes

    if buffer_size <= 0:
        buffer_size = max(1, (3 * num_cohorts) // 4)
    rounds = max(1, int(rounds))
    adapter, params, opt, hp, stack = _setup(kind, num_cohorts, batch_size,
                                             local_steps)
    # heterogeneous fleet: device-tier speed mix (Jetson-class .. phones)
    speeds = np.asarray([d.speed for d in
                         sample_devices(seed, num_cohorts, 1)])
    sim_times = np.asarray(stack.num_batches, float) / speeds
    sync_time = float(sim_times.max()) * rounds

    runtime = AsyncBufferedRuntime(adapter, opt, hp,
                                   buffer_size=buffer_size,
                                   model_parallel=model_parallel)
    async_time, n_carried, n_uploads, new_tr = 0.0, 0, 0, None
    for _ in range(rounds):
        new_tr, metrics = runtime.run_stacked(params, stage, stack,
                                              sim_times=sim_times)
        async_time += metrics["sim_round_time"]
        n_carried += metrics["n_carried"]
        n_uploads += metrics["n_uploads"]
    return {"buffer_size": buffer_size, "rounds": rounds,
            "sync_time": sync_time, "async_time": async_time,
            "speedup": sync_time / max(async_time, 1e-12),
            "n_pending": metrics["n_pending"],
            "n_carried": n_carried, "n_uploads": n_uploads,
            "server_version": metrics["server_version"],
            "trainable_bytes_per_device": per_device_nbytes(new_tr),
            "model_shards": runtime.model_shards}


def bench_model_parallel(kind: str, model_parallel: int,
                         num_cohorts: int = 16, batch_size: int = 4,
                         local_steps: int = 2, stage: int = 1,
                         iters: int = 3):
    """2-D sharded round vs the replicated vectorized path: rounds/sec and
    per-device trainable bytes (the paper's client-memory axis)."""
    import jax
    from repro.federated.runtime import ShardedRuntime, VectorizedRuntime
    from repro.launch.sharding import per_device_nbytes

    adapter, params, opt, hp, stack = _setup(kind, num_cohorts, batch_size,
                                             local_steps)
    sharded = ShardedRuntime(adapter, opt, hp, model_parallel=model_parallel)
    runtimes = {
        "replicated": VectorizedRuntime(adapter, opt, hp),
        # label with the mesh actually built: make_host_mesh clamps a
        # non-divisor request, and the report must not attribute the
        # measured ratio to a shard count that never ran
        f"model-sharded x{sharded.model_shards}": sharded,
    }
    out = {}
    for name, rt in runtimes.items():
        new_tr, _ = rt.run_stacked(params, stage, stack)     # warmup + bytes

        def one_round(rt=rt):
            tr, metrics = rt.run_stacked(params, stage, stack)
            return jax.tree.leaves(tr)[0], metrics["mean_local_loss"]

        out[name] = {
            "rounds_per_s": 1.0 / timeit(one_round, warmup=0, iters=iters),
            "trainable_bytes_per_device": per_device_nbytes(new_tr),
        }
    return out


def bench_population(populations, clients_per_round: int = 8,
                     rounds: int = 8, seed: int = 0,
                     selection: str = "random"):
    """Server-side round cost vs *population* size on the streaming fleet.

    Each row opens a ``NeuLiteServer`` over a ``Fleet`` + procedural
    client bank of ``population`` devices and times full rounds (selection
    + local training + aggregation) at a FIXED cohort size — with O(cohort)
    round opening, rounds/sec and server memory must stay flat from 10^2
    to 10^6 clients.  Reports rounds/sec, the tracemalloc peak of server
    construction + one round (Python-heap allocations, which is where an
    O(population) scan would show), and the process ``ru_maxrss``.
    """
    import gc
    import resource
    import time
    import tracemalloc

    from repro.configs.paper_models import resnet18
    from repro.core import make_adapter
    from repro.core.memory import estimate_stage_memory
    from repro.data import ProceduralClients
    from repro.federated import FLConfig, Fleet, NeuLiteServer

    cfg = resnet18(num_classes=10, image_size=8, width_mult=0.0625)
    adapter = make_adapter(cfg, num_stages=4)
    # budget the fleet against the PEAK per-stage requirement (this tiny
    # config's stage footprints exceed full-model training, so the default
    # full-model budget would leave every stage infeasible): the top tier
    # (1.10x budget, jitter >= 0.9) then fits every stage by construction,
    # and stratified tiers guarantee those devices exist at any population
    max_req = max(estimate_stage_memory(adapter, t, 4, seq=0).total
                  for t in range(4))
    budget = int(max_req / 0.99) + 1
    rows = []
    for pop in populations:
        pop = int(pop)
        flc = FLConfig(n_devices=pop, clients_per_round=clients_per_round,
                       local_epochs=1, batch_size=4, num_stages=4,
                       seed=seed, runtime="vectorized", selection=selection)
        # fixed shard size -> stable cohort shapes, so jit compiles once
        # per stage and the timed window measures rounds, not tracing;
        # cache_size=1 -> every population pays the same per-cohort data
        # derivation (a warm LRU would hand small populations an edge that
        # has nothing to do with round-opening cost)
        bank = ProceduralClients(seed, pop, batch_size=flc.batch_size,
                                 samples_per_client=16, cache_size=1)
        srv = NeuLiteServer(adapter, bank, flc,
                            fleet=Fleet(seed, pop, budget))
        warm = flc.num_stages                  # one full stage cycle
        for r in range(warm):                  # jit warmup, outside timing
            srv.run_round(r)
        t0 = time.perf_counter()
        for r in range(warm, warm + rounds):
            srv.run_round(r)
        dt = (time.perf_counter() - t0) / rounds

        # memory probe separated from timing (tracemalloc taxes every
        # Python allocation): fresh server, one round, peak heap growth
        del srv
        gc.collect()
        tracemalloc.start()
        srv = NeuLiteServer(adapter, ProceduralClients(
            seed, pop, batch_size=flc.batch_size, samples_per_client=16,
            cache_size=1), flc, fleet=Fleet(seed, pop, budget))
        srv.run_round(0)
        _, peak = tracemalloc.get_traced_memory()
        tracemalloc.stop()
        del srv
        gc.collect()
        rows.append({
            "population": pop,
            "clients_per_round": clients_per_round,
            "selection": selection,
            "rounds_per_s": 1.0 / dt,
            "server_peak_mb": peak / 2 ** 20,
            "ru_maxrss_mb": resource.getrusage(
                resource.RUSAGE_SELF).ru_maxrss / 1024.0,
        })
    return rows


def bench_conv_impl(num_cohorts: int = 16, batch_size: int = 4,
                    local_steps: int = 2, stage: int = 1, iters: int = 3,
                    use_hsic_kernel: bool = False):
    """The measured lax-vs-im2col crossover on the *vectorized* CNN round
    (the shape that decides ``conv_impl="auto"``): per-cohort weights under
    ``vmap`` lower 3×3 convs to grouped convs whose CPU backward is the
    round bottleneck; im2col turns them into batched matmuls.  Returns
    {"lax": r/s, "im2col": r/s, "speedup": ...} at ``num_cohorts``."""
    import jax
    from repro.federated.runtime import VectorizedRuntime

    out = {}
    for impl in ("lax", "im2col"):
        adapter, params, opt, hp, stack = _setup(
            "cnn", num_cohorts, batch_size, local_steps, conv_impl=impl,
            use_hsic_kernel=use_hsic_kernel)
        rt = VectorizedRuntime(adapter, opt, hp)

        def one_round(rt=rt, params=params, stack=stack):
            tr, metrics = rt.run_stacked(params, stage, stack)
            return jax.tree.leaves(tr)[0], metrics["mean_local_loss"]

        out[impl] = 1.0 / timeit(one_round, warmup=1, iters=iters)
    out["speedup"] = out["im2col"] / out["lax"]
    out["num_cohorts"] = num_cohorts
    return out


def quick():
    for kind in ("cnn", "transformer"):
        # fused flags on: the im2col convs + Pallas-nHSIC loss are the
        # paths CI must actually execute (ISSUE 6 bench-smoke)
        rps = bench(kind, num_cohorts=16, batch_size=4, local_steps=2,
                    conv_impl="im2col" if kind == "cnn" else None,
                    use_hsic_kernel=True)
        base = rps["sequential"]
        for name, r in rps.items():
            csv_row(f"fl_round_{kind}_{name}", 1e6 / r,
                    f"{r:.2f}r/s x{r / base:.1f}")
    cross = bench_conv_impl(num_cohorts=16)
    csv_row("fl_round_conv_crossover", 1e6 / cross["im2col"],
            f"im2col {cross['im2col']:.2f}r/s vs lax {cross['lax']:.2f}r/s "
            f"x{cross['speedup']:.2f}")
    # streaming-fleet smoke: round cost must not grow with the population
    sweep = bench_population([1e2, 1e4], rounds=1)
    for row in sweep:
        csv_row(f"fl_round_pop_{row['population']}",
                1e6 / row["rounds_per_s"],
                f"{row['rounds_per_s']:.2f}r/s "
                f"{row['server_peak_mb']:.1f}MBpeak")


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--cohorts", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--steps", type=int, default=2)
    ap.add_argument("--stage", type=int, default=1)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--conv-impl", choices=["auto", "lax", "im2col"],
                    default="auto",
                    help="CNN conv lowering (auto: im2col on CPU, lax "
                         "elsewhere — see models.cnn.resolve_conv_impl)")
    ap.add_argument("--use-hsic-kernel", action="store_true",
                    help="route the curriculum's nHSIC terms through the "
                         "fused Pallas custom_vjp (interpret mode off-TPU)")
    ap.add_argument("--runtime", choices=["all", "async"], default="all",
                    help="'async': simulated-time FedBuff speedup report")
    ap.add_argument("--buffer", type=int, default=0,
                    help="async buffer size K (0 = 3/4 of the cohort)")
    ap.add_argument("--model-parallel", type=int, default=1,
                    help="report the 2-D (data, model) sharded round: "
                         "per-device trainable bytes + rounds/s vs the "
                         "replicated path")
    ap.add_argument("--rounds", type=int, default=2,
                    help="async: stateful server rounds (stragglers carry "
                         "across round boundaries)")
    ap.add_argument("--population", type=float, nargs="+", default=None,
                    metavar="N",
                    help="streaming-fleet sweep: time full rounds at these "
                         "population sizes (e.g. --population 1e2 1e3 1e4 "
                         "1e5 1e6) at a fixed cohort")
    ap.add_argument("--selection", choices=["random", "tifl", "oort"],
                    default="random",
                    help="cohort policy for the --population sweep")
    args = ap.parse_args()
    if args.population:
        print(f"{'population':>10s} {'rounds/s':>9s} {'peak MB':>8s} "
              f"{'maxrss MB':>9s}")
        sweep = bench_population(args.population,
                                 selection=args.selection)
        for row in sweep:
            print(f"{row['population']:10d} {row['rounds_per_s']:9.2f} "
                  f"{row['server_peak_mb']:8.1f} "
                  f"{row['ru_maxrss_mb']:9.1f}")
        base, last = sweep[0], sweep[-1]
        print(f"rounds/s at {last['population']} = "
              f"{last['rounds_per_s'] / base['rounds_per_s']:.2f}x of "
              f"{base['population']}")
        return
    if args.runtime == "async":
        # async x sharded composition: --model-parallel K runs the async
        # local program + buffered flushes on the 2-D (data, model) mesh
        if args.model_parallel > 1:
            _force_host_devices(max(8, 2 * args.model_parallel))
        print(f"{'model':12s} {'mesh':>8s} {'K':>4s} {'ver':>4s} "
              f"{'carried':>7s} {'pending':>7s} {'t_sync':>8s} "
              f"{'t_async':>8s} {'speedup':>8s} {'trainB/dev':>11s}")
        for kind in ("cnn", "transformer"):
            r = bench_async(kind, args.cohorts, args.batch, args.steps,
                            args.stage, args.buffer, rounds=args.rounds,
                            model_parallel=args.model_parallel)
            mesh = f"x{r['model_shards']}"
            print(f"{kind:12s} {mesh:>8s} {r['buffer_size']:4d} "
                  f"{r['server_version']:4d} {r['n_carried']:7d} "
                  f"{r['n_pending']:7d} {r['sync_time']:8.2f} "
                  f"{r['async_time']:8.2f} {r['speedup']:7.2f}x "
                  f"{r['trainable_bytes_per_device']:11d}")
        return
    if args.model_parallel > 1:
        _force_host_devices(max(8, 2 * args.model_parallel))
        print(f"{'model':12s} {'placement':>20s} {'rounds/s':>9s} "
              f"{'trainable B/dev':>15s} {'ratio':>6s}")
        for kind in ("cnn", "transformer"):
            r = bench_model_parallel(kind, args.model_parallel,
                                     args.cohorts, args.batch, args.steps,
                                     args.stage, args.iters)
            base = r["replicated"]["trainable_bytes_per_device"]
            for name, row in r.items():
                ratio = row["trainable_bytes_per_device"] / base
                print(f"{kind:12s} {name:>20s} "
                      f"{row['rounds_per_s']:9.2f} "
                      f"{row['trainable_bytes_per_device']:15d} "
                      f"{ratio:5.2f}x")
        return
    print(f"{'model':12s} {'backend':12s} {'rounds/s':>9s} {'speedup':>8s}")
    for kind in ("cnn", "transformer"):
        rps = bench(kind, args.cohorts, args.batch, args.steps, args.stage,
                    args.iters, conv_impl=args.conv_impl,
                    use_hsic_kernel=args.use_hsic_kernel)
        base = rps["sequential"]
        for name, r in rps.items():
            print(f"{kind:12s} {name:12s} {r:9.2f} {r / base:7.1f}x")
    cross = bench_conv_impl(args.cohorts, args.batch, args.steps, args.stage,
                            args.iters, use_hsic_kernel=args.use_hsic_kernel)
    print(f"{'cnn':12s} {'conv-impl':12s} im2col {cross['im2col']:.2f}r/s "
          f"vs lax {cross['lax']:.2f}r/s = {cross['speedup']:.2f}x "
          f"at {cross['num_cohorts']} cohorts")


if __name__ == "__main__":
    main()
