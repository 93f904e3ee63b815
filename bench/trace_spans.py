#!/usr/bin/env python3
"""A traced run of one cell with the program's spans recorded.

    python3 bench/trace_spans.py --workload <cell> --seed <n> [--seconds 30]
    python3 bench/trace_spans.py --span-cost

The first form is ``bench/run.py --trace 1`` with two additions: the window
runs inside ``repro.common.spans.recording()``, and set-up keeps the
compiled text of every round program it warms.  The last line of standard
output is the run's result object with a ``spans`` key: idle seconds of
the window per label (``bench/spans.py``), device seconds per named scope,
host self time per span, the byte counters, the clock check, and the
per-layer values read from them.  The second form prints what one span
costs the host with recording off and on.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from collections import defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def span_cost(n: int = 200_000) -> dict:
    """ns per ``with span(...)`` (recording off and on) and per ``count``
    (off), each the best of five loops of ``n``."""
    from repro.common import spans

    def best(fn):
        times = []
        for _ in range(5):
            t0 = time.perf_counter_ns()
            fn()
            times.append((time.perf_counter_ns() - t0) / n)
        return min(times)

    def spans_loop():
        for _ in range(n):
            with spans.span("fl.stack"):
                pass

    def count_loop():
        for _ in range(n):
            spans.count("stack_bytes", 1)

    def empty_loop():
        for _ in range(n):
            pass

    out = {"loop_ns": best(empty_loop), "span_off_ns": best(spans_loop),
           "count_off_ns": best(count_loop)}
    with spans.recording():
        with spans.span("fl.round", round=0):
            out["span_on_ns"] = best(spans_loop)
    return out


def traced_run(cell, seed: int, seconds: float, *,
               require_tpu: bool = True) -> dict:
    """``harness.run`` with ``trace`` on, the window recorded and the round
    programs' compiled text kept; the harness's functions are its own
    again when it returns."""
    from bench import harness
    from bench import spans as sp
    from bench import trace as trace_mod
    from repro.common import spans
    kept = {}

    own_warm = harness.warm

    def warm(server, t, lengths, cohort, batch, images, labels):
        """The harness's warm-up, then the compiled text of each round
        program, lowered on inputs like the round's."""
        import numpy as np
        from repro.data.loader import RoundStack
        own_warm(server, t, lengths, cohort, batch, images, labels)
        rt = server.runtime
        frozen, trainable = server.adapter.split_stage(server.params, t)
        texts = []
        for e in lengths:
            shape = (cohort, e, batch)
            stack = RoundStack(
                batches={"inputs": {"images": np.zeros(
                             (*shape, *images.shape[1:]), images.dtype)},
                         "labels": np.zeros(shape, labels.dtype)},
                step_mask=np.ones((cohort, e), bool),
                weights=np.ones((cohort,), np.float32),
                num_batches=[e] * cohort)
            texts.append(rt._program(t).lower(
                trainable, frozen, *rt._device_stack(stack)).compile()
                .as_text())
        kept["texts"] = texts

    own_measure = harness.measure

    def measure(server, bank, first_round, seconds, counter):
        with spans.recording() as rec:
            win = own_measure(server, bank, first_round, seconds, counter)
        kept["recorder"] = rec
        kept["window_wall"] = next(s for s in win.spans
                                   if s[0] == trace_mod.WINDOW)
        return win

    own_read = trace_mod.read_xspace

    def read_xspace(log_dir, spans_):
        kept["trace"] = own_read(log_dir, spans_)
        return kept["trace"]

    harness.warm, harness.measure = warm, measure
    trace_mod.read_xspace = read_xspace
    try:
        result = harness.run(cell, seed, seconds, True, t_start=T_START,
                             require_tpu=require_tpu)
    finally:
        harness.warm, harness.measure = own_warm, own_measure
        trace_mod.read_xspace = own_read
    trace, rec = kept["trace"], kept["recorder"]
    t0 = time.perf_counter()
    offset = kept["window_wall"][1] - trace.window[0]
    placed = sp.place(rec.spans, offset)
    rounds = max(1, sum(1 for s in rec.spans if s.name == sp.ROUND))
    idle = sp.idle_labels(trace, placed)
    scopes = sp.scope_seconds(trace, [sp.scope_table(t)
                                      for t in kept["texts"]])
    own = sp.self_ms(rec.spans)
    counters = defaultdict(int)
    for (name, _), n in rec.counters.items():
        counters[name] += n
    per_round = lambda x: x * 1e3 / rounds
    result["spans"] = {
        "rounds": rounds,
        "idle_s": dict(sorted(idle.items(), key=lambda kv: -kv[1])),
        "scope_s": scopes,
        "self_ms": own,
        "bytes_per_round": {k: v / rounds for k, v in counters.items()},
        "clock_violation_ms": sp.clock_violation_ms(trace, placed),
        "metrics": {
            "round_open_ms": own.get("fl.open"),
            "stack_ms": own.get("fl.stack"),
            "h2d_ms": own.get("fl.to_device"),
            "input_wait_ms": per_round(idle.get(sp.INPUT_WAIT, 0.0)),
            "stage_slice_ms": own.get("fl.split", 0.0)
            + own.get("fl.merge", 0.0),
            **{f"{s}_ms": per_round(scopes.get(s, 0.0))
               for s in ("prefix", "block", "mentor", "optimizer")}},
        "eq1_ms": per_round(scopes.get("eq1", 0.0)),
        "unscoped_ms": per_round(scopes.get(sp.UNSCOPED, 0.0)),
        "reduce_s": time.perf_counter() - t0,
        "run_s": time.perf_counter() - T_START}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--span-cost", action="store_true")
    args = ap.parse_args(argv)
    if args.span_cost:
        print(json.dumps(span_cost()), flush=True)
        return 0
    if args.workload is None or args.seed is None:
        ap.error("--workload and --seed are needed")
    from bench.cache import enable_compile_cache
    enable_compile_cache()
    from bench import harness
    try:
        result = traced_run(harness.load_cell(args.workload), args.seed,
                            args.seconds)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
