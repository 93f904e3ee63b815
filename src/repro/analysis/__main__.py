"""CLI: ``python -m repro.analysis --backend sharded --model-parallel 2``
or ``python -m repro.analysis --kernels [--fuzz 50]``.

Default mode traces (never runs) the chosen backend's round programs and
checks the repo's structural contracts — collectives, per-stage memory,
host syncs, donation.  ``--kernels`` instead audits every registered
``pallas_call`` site (grid/BlockSpec races, block bounds & padding masks,
VMEM budget, accumulation dtype) and optionally fuzzes each kernel
against its reference oracle.  Exits non-zero on any un-waived error.
See docs/analysis.md.
"""
from __future__ import annotations

import argparse
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="Static auditors: round programs (jaxpr/HLO "
                    "invariants) and Pallas kernels (grid/BlockSpec "
                    "contracts + differential fuzzing).")
    ap.add_argument("--kernels", action="store_true",
                    help="audit the registered pallas_call sites instead "
                         "of the round programs")
    ap.add_argument("--backend", default="sharded",
                    choices=["seq", "vec", "sharded", "async",
                             "sequential", "vectorized"],
                    help="runtime backend whose round programs to audit")
    ap.add_argument("--model-parallel", type=int, default=1, metavar="K",
                    help="model-axis size for sharded/async (default 1)")
    ap.add_argument("--arch", default="tx", choices=["tx", "cnn"],
                    help="tiny audit model (dense transformer or ResNet18)")
    ap.add_argument("--no-probe", action="store_true",
                    help="skip the dynamic host-sync probe (pure tracing; "
                         "use where running even a tiny round is too slow)")
    ap.add_argument("--family", action="append", default=[], metavar="FAM",
                    help="with --kernels: restrict to a kernel family "
                         "(flash_attention / hsic_gram / slstm_scan); "
                         "repeatable")
    ap.add_argument("--fuzz", type=int, default=0, metavar="N",
                    help="with --kernels: also differential-fuzz each "
                         "family with N generated shape cases vs its "
                         "ref.py oracle (fwd + grad, interpret mode)")
    ap.add_argument("--fuzz-seed", type=int, default=0, metavar="S",
                    help="base RNG seed for --fuzz draws (default 0)")
    ap.add_argument("--vmem-budget-mib", type=float, default=None,
                    metavar="MIB",
                    help="with --kernels: per-grid-step VMEM budget "
                         "(default 16 MiB, the per-core TPU budget)")
    ap.add_argument("--waive", action="append", default=[], metavar="CHECK",
                    help="downgrade a check (e.g. memory.trainable-ratio "
                         "or a whole family like 'pallas') to a warning; "
                         "repeatable")
    ap.add_argument("--json", metavar="PATH",
                    help="write the full report (findings + artifact "
                         "tables) as JSON")
    ap.add_argument("--verbose", action="store_true",
                    help="also print info-level findings")
    args = ap.parse_args(argv)

    if args.kernels:
        from repro.analysis import pallas_audit
        budget = (args.vmem_budget_mib
                  if args.vmem_budget_mib is not None
                  else pallas_audit.DEFAULT_VMEM_BUDGET_MIB)
        report = pallas_audit.run_kernel_audits(
            waive=args.waive, families=args.family or None,
            fuzz=args.fuzz, seed=args.fuzz_seed, vmem_budget_mib=budget)
    else:
        from repro.analysis.harness import run_audits
        report = run_audits(args.backend,
                            model_parallel=args.model_parallel,
                            arch=args.arch, waive=args.waive,
                            probe=not args.no_probe)
    print(report.render(verbose=args.verbose))
    if args.json:
        report.dump_json(args.json)
        print(f"report written to {args.json}")
    return 0 if report.ok() else 1


if __name__ == "__main__":
    sys.exit(main())
