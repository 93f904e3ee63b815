"""Static Pallas kernel auditor + differential shape fuzzer.

The kernel-level mirror of the round-program auditor: every registered
``pallas_call`` site (each ``kernels/<family>/ops.py`` exposes an
``AUDIT_CASES`` registry of ``KernelAuditCase``s built from the same
``*_call_spec()`` builders the production calls execute) is checked
WITHOUT running a kernel:

* ``pallas.write-race`` — every ``out_specs`` index map is evaluated over
  the full grid product; distinct grid points mapping to the same output
  block are only legal when the revisited axes form the innermost
  (TPU-sequential) suffix of the grid AND the kernel declares them via
  ``sequential_axes``.  Silent revisits are correct in interpret mode but
  racy (or revisit-order-dependent) when compiled.
* ``pallas.oob-block`` / ``pallas.unmasked-padding`` — ``block_shape ×
  index_map`` extents vs the operand array shape: out-of-bounds block
  starts are errors; partial (padding) tiles require the case to declare
  in-kernel masking (``masked=True``), cross-checked against the kernel
  source for a ``pl.when`` / iota-mask construct.
* ``pallas.vmem-budget`` — per-grid-step bytes (all in/out blocks +
  scratch, VMEM and SMEM accounted separately) against a configurable
  per-platform budget (16 MiB TPU default); the per-kernel table is
  exported as the ``kernel_vmem`` report artifact.
* ``pallas.low-precision-accum`` — bf16/f16 operand blocks must
  accumulate in f32: an f32 scratch accumulator, an f32 output, or an
  explicit in-kernel upcast / ``preferred_element_type``.

Alongside the static passes, ``fuzz_families`` cross-checks each kernel
against its ``ref.py`` oracle (forward AND gradients where the public op
is differentiable) on adversarial generated shapes — non-dividing
blocks, batches smaller than one block, degenerate D=1, bf16 inputs —
in interpret mode, so CPU CI exercises the exact kernel code path.

CLI: ``python -m repro.analysis --kernels [--fuzz N] [--json PATH]
[--waive CHECK]``; see docs/analysis.md.
"""
from __future__ import annotations

import itertools
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from repro.analysis.report import Report
from repro.kernels import KernelAuditCase

FAMILIES = ("flash_attention", "hsic_gram", "slstm_scan")

# enumerate at most this many grid points per case; representative audit
# shapes keep grids tiny, so hitting the cap means the case is misdeclared
MAX_GRID_POINTS = 65536

DEFAULT_VMEM_BUDGET_MIB = 16.0        # per-core VMEM on current TPUs

_LOW_PRECISION = ("bfloat16", "float16")
# textual evidence of an in-kernel f32 upcast (cheap but effective: the
# kernels are short, and the declaration is cross-checked by the fuzzer)
_F32_CAST_MARKERS = ("astype(jnp.float32)", "preferred_element_type")
_MASK_MARKERS = ("pl.when", "iota", "jnp.where")


def iter_cases(families: Optional[Iterable[str]] = None) \
        -> List[KernelAuditCase]:
    """All registered audit cases (optionally restricted to families)."""
    import importlib
    cases: List[KernelAuditCase] = []
    for fam in (families or FAMILIES):
        ops = importlib.import_module(f"repro.kernels.{fam}.ops")
        cases.extend(ops.AUDIT_CASES())
    return cases


# --------------------------------------------------------------------------- #
# static checks
# --------------------------------------------------------------------------- #
def _grid_points(grid: Tuple[int, ...]):
    return itertools.product(*(range(n) for n in grid))


def _map_index(spec, point) -> Optional[Tuple[int, ...]]:
    idx = spec.index_map(*point)
    if not isinstance(idx, (tuple, list)):
        idx = (idx,)
    return tuple(int(i) for i in idx)


def _fmt_axes(axes) -> str:
    return "{" + ", ".join(str(a) for a in sorted(axes)) + "}"


def check_write_races(case: KernelAuditCase, report: Report) -> None:
    """(a) distinct grid points writing one output block must be the
    declared, innermost-sequential accumulation axes — anything else is a
    race under compiled (parallelized / reordered) execution."""
    grid = case.grid
    n_axes = len(grid)
    for o, (spec, aval) in enumerate(zip(case.out_specs, case.out_avals)):
        if spec.index_map is None:
            # memory_space-only spec: every grid point addresses the whole
            # operand — revisited by every axis with extent > 1
            varying = {a for a in range(n_axes) if grid[a] > 1}
            groups = {(): varying} if varying else {}
        else:
            seen: Dict[Tuple[int, ...], list] = {}
            try:
                for p in _grid_points(grid):
                    seen.setdefault(_map_index(spec, p), []).append(p)
            except Exception as e:  # index map not statically evaluable
                report.add("pallas.index-map",
                           f"out[{o}] index map failed at a grid point: "
                           f"{type(e).__name__}: {e}",
                           program=f"{case.family}/{case.name}",
                           location=case.location())
                continue
            groups = {}
            for block, pts in seen.items():
                if len(pts) > 1:
                    groups[block] = {a for a in range(n_axes)
                                     if len({p[a] for p in pts}) > 1}
        for block, varying in groups.items():
            where = f"{case.family}/{case.name}"
            k = min(varying)
            holes = [a for a in range(k, n_axes)
                     if grid[a] > 1 and a not in varying]
            if holes:
                report.add(
                    "pallas.write-race",
                    f"out[{o}] block {block} is revisited by grid axes "
                    f"{_fmt_axes(varying)}, but axes {_fmt_axes(holes)} "
                    f"iterate between the revisits — the writes are not "
                    f"consecutive in the sequential TPU grid order, so "
                    f"compiled execution clobbers the accumulator.  Make "
                    f"the revisited axes the innermost grid axes.",
                    program=where, location=case.location())
                break
            undeclared = varying - set(case.sequential_axes)
            if undeclared:
                report.add(
                    "pallas.write-race",
                    f"out[{o}] block {block} is revisited across grid "
                    f"axes {_fmt_axes(varying)} without a matching "
                    f"sequential_axes declaration (declared "
                    f"{_fmt_axes(case.sequential_axes) or '{}'}).  "
                    f"Innermost revisits are sequential accumulation on "
                    f"TPU but a race on parallel backends — declare them "
                    f"so the contract is explicit and audited.",
                    program=where, location=case.location())
                break


def check_bounds_and_padding(case: KernelAuditCase, report: Report) -> None:
    """(b) block starts must land inside the operand; partial (padding)
    tiles must be masked in-kernel and declared."""
    where = f"{case.family}/{case.name}"
    operands = [("in", i, s, a) for i, (s, a)
                in enumerate(zip(case.in_specs, case.in_avals))] + \
               [("out", i, s, a) for i, (s, a)
                in enumerate(zip(case.out_specs, case.out_avals))]
    padded = []
    for kind, i, spec, aval in operands:
        bs = spec.block_shape
        if bs is None or spec.index_map is None:
            continue
        name = f"{kind}[{i}]"
        if len(bs) != len(aval.shape):
            report.add("pallas.index-map",
                       f"{name} block_shape {tuple(bs)} rank != operand "
                       f"rank {aval.shape}", program=where,
                       location=case.location())
            continue
        try:
            for p in _grid_points(case.grid):
                idx = _map_index(spec, p)
                if len(idx) != len(bs):
                    report.add("pallas.index-map",
                               f"{name} index map returns {len(idx)} "
                               f"indices for a rank-{len(bs)} block",
                               program=where, location=case.location())
                    break
                for d, (b, blk, dim) in enumerate(zip(idx, bs, aval.shape)):
                    start = b * blk
                    if start < 0 or start >= dim:
                        report.add(
                            "pallas.oob-block",
                            f"{name} grid point {p} maps to block "
                            f"{idx}: dim {d} start {start} is outside "
                            f"the operand extent {dim} (block_shape "
                            f"{tuple(bs)}) — the kernel would read/write "
                            f"out of bounds when compiled.",
                            program=where, location=case.location())
                        raise StopIteration
                    if start + blk > dim:
                        padded.append((name, p, d, start, blk, dim))
        except StopIteration:
            break
        except Exception as e:
            report.add("pallas.index-map",
                       f"{name} index map failed: {type(e).__name__}: {e}",
                       program=where, location=case.location())
    if padded:
        name, p, d, start, blk, dim = padded[0]
        if not case.masked:
            report.add(
                "pallas.unmasked-padding",
                f"{name} grid point {p} covers [{start}, {start + blk}) "
                f"of a {dim}-long dim {d} — a partial (padding) tile, "
                f"and the case does not declare in-kernel masking.  Mask "
                f"the tail with pl.when / an iota mask (and declare "
                f"masked=True), or pad the operand to a dividing shape "
                f"in the wrapper.  ({len(padded)} padded tile(s) total.)",
                program=where, location=case.location())
        elif not any(m in case.kernel_source() for m in _MASK_MARKERS):
            report.add(
                "pallas.unmasked-padding",
                f"{name} has partial (padding) tiles and the case "
                f"declares masked=True, but the kernel source shows no "
                f"masking construct ({' / '.join(_MASK_MARKERS)}) — the "
                f"declaration looks stale.",
                program=where, location=case.location())


def _nbytes(shape, dtype) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n * np.dtype(dtype).itemsize


def _is_smem(memory_space) -> bool:
    return memory_space is not None and "smem" in str(memory_space).lower()


def check_vmem_budget(case: KernelAuditCase, report: Report, *,
                      budget_mib: float = DEFAULT_VMEM_BUDGET_MIB) -> dict:
    """(c) per-grid-step working set vs the VMEM budget; returns the
    per-kernel table row (also without violations)."""
    where = f"{case.family}/{case.name}"
    vmem = smem = 0
    breakdown = {}
    operands = [("in", i, s, a) for i, (s, a)
                in enumerate(zip(case.in_specs, case.in_avals))] + \
               [("out", i, s, a) for i, (s, a)
                in enumerate(zip(case.out_specs, case.out_avals))]
    for kind, i, spec, aval in operands:
        shape = spec.block_shape if spec.block_shape is not None \
            else aval.shape
        nb = _nbytes(shape, aval.dtype)
        if _is_smem(spec.memory_space):
            smem += nb
        else:
            vmem += nb
        breakdown[f"{kind}[{i}]"] = nb
    for i, sc in enumerate(case.scratch_shapes):
        nb = _nbytes(sc.shape, sc.dtype)
        if _is_smem(getattr(sc, "memory_space", None)):
            smem += nb
        else:
            vmem += nb
        breakdown[f"scratch[{i}]"] = nb
    budget = int(budget_mib * 2 ** 20)
    if vmem > budget:
        report.add(
            "pallas.vmem-budget",
            f"per-grid-step working set is {vmem / 2**20:.2f} MiB "
            f"(blocks + scratch) > the {budget_mib:g} MiB VMEM budget — "
            f"shrink the block sizes or split the kernel.",
            program=where, location=case.location())
    return {"family": case.family, "name": case.name,
            "grid": list(case.grid), "vmem_bytes": vmem,
            "smem_bytes": smem, "vmem_mib": round(vmem / 2 ** 20, 4),
            "budget_mib": budget_mib, "breakdown": breakdown}


def check_accum_dtype(case: KernelAuditCase, report: Report) -> None:
    """(d) bf16/f16 operand blocks must accumulate via f32."""
    low = [str(a.dtype) for a in case.in_avals
           if str(a.dtype) in _LOW_PRECISION]
    if not low:
        return
    f32_scratch = any(np.dtype(sc.dtype).itemsize >= 4
                      and np.dtype(sc.dtype).kind == "f"
                      for sc in case.scratch_shapes)
    f32_out = any(np.dtype(a.dtype) == np.dtype(np.float32)
                  for a in case.out_avals)
    src = case.kernel_source()
    casts = any(m in src for m in _F32_CAST_MARKERS)
    if not (f32_scratch or f32_out or casts):
        report.add(
            "pallas.low-precision-accum",
            f"operand blocks are {'/'.join(sorted(set(low)))} but the "
            f"kernel shows no f32 accumulation path — no f32 scratch, no "
            f"f32 output, and no in-kernel upcast "
            f"({' / '.join(_F32_CAST_MARKERS)}).  Low-precision "
            f"accumulation loses ~3 decimal digits per 2x reduction "
            f"depth; accumulate in f32 and cast once on the final write.",
            program=f"{case.family}/{case.name}", location=case.location())


def audit_case(case: KernelAuditCase, report: Report, *,
               vmem_budget_mib: float = DEFAULT_VMEM_BUDGET_MIB) -> dict:
    """Run all four static check families over one case; returns the VMEM
    table row."""
    n_points = 1
    for n in case.grid:
        n_points *= int(n)
    if n_points > MAX_GRID_POINTS:
        report.add("pallas.grid-too-large",
                   f"grid product {n_points} > {MAX_GRID_POINTS}; "
                   f"race/bounds enumeration skipped — use a smaller "
                   f"representative shape in AUDIT_CASES",
                   severity="warning",
                   program=f"{case.family}/{case.name}",
                   location=case.location())
    else:
        check_write_races(case, report)
        check_bounds_and_padding(case, report)
    check_accum_dtype(case, report)
    return check_vmem_budget(case, report, budget_mib=vmem_budget_mib)


def audit_kernels(report: Report, *,
                  families: Optional[Sequence[str]] = None,
                  vmem_budget_mib: float = DEFAULT_VMEM_BUDGET_MIB) -> None:
    """Static audit over every registered case; fills the ``kernel_vmem``
    artifact table."""
    table = [audit_case(c, report, vmem_budget_mib=vmem_budget_mib)
             for c in iter_cases(families)]
    report.artifacts["kernel_vmem"] = table


# --------------------------------------------------------------------------- #
# differential shape fuzzing: kernel (interpret mode) vs ref.py oracle
# --------------------------------------------------------------------------- #
def _rel_err(a, b) -> float:
    """max |a-b| / max(|b|), floored so near-zero oracles don't explode."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    if a.shape != b.shape:
        return float("inf")
    if a.size == 0:
        return 0.0
    denom = max(float(np.max(np.abs(b))), 1e-6)
    return float(np.max(np.abs(a - b)) / denom)


def _tol(dtype) -> float:
    return 2e-2 if str(np.dtype(dtype)) in _LOW_PRECISION else 1e-3


def _fuzz_flash_once(rng: np.random.Generator):
    """One adversarial flash-attention draw: non-dividing blocks, Sq != Skv,
    GQA groups, degenerate D, bf16 operands; fwd + grads vs attention_ref."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref

    B = int(rng.integers(1, 3))
    KV = int(rng.integers(1, 3))
    G = int(rng.integers(1, 3))
    H = KV * G
    Sq = int(rng.integers(1, 161))
    Skv = int(rng.integers(1, 161))
    D = int(rng.choice([1, 4, 8, 32, 64]))
    bq = int(rng.choice([8, 16, 128]))
    bkv = int(rng.choice([8, 16, 128]))
    causal = bool(rng.integers(0, 2))
    window = int(rng.choice([0, 0, 1, int(rng.integers(1, max(Sq, 2)))]))
    dtype = jnp.bfloat16 if rng.random() < 0.25 else jnp.float32
    params = dict(B=B, H=H, KV=KV, Sq=Sq, Skv=Skv, D=D, block_q=bq,
                  block_kv=bkv, causal=causal, window=window,
                  dtype=str(np.dtype(dtype)))

    q = rng.standard_normal((B, Sq, H, D), np.float32)
    k = rng.standard_normal((B, Skv, KV, D), np.float32)
    v = rng.standard_normal((B, Skv, KV, D), np.float32)
    q, k, v = (jnp.asarray(t, dtype) for t in (q, k, v))
    kw = dict(causal=causal, window=window)

    out = flash_attention(q, k, v, block_q=bq, block_kv=bkv,
                          interpret=True, **kw)
    ref = attention_ref(q, k, v, **kw)
    results = [("flash fwd", _rel_err(out, ref), _tol(dtype), params)]

    w = jnp.asarray(rng.standard_normal(ref.shape, np.float32))
    gk_fn = jax.grad(lambda q_, k_, v_: jnp.sum(
        flash_attention(q_, k_, v_, block_q=bq, block_kv=bkv,
                        interpret=True, **kw).astype(jnp.float32) * w),
        argnums=(0, 1, 2))
    gr_fn = jax.grad(lambda q_, k_, v_: jnp.sum(
        attention_ref(q_, k_, v_, **kw).astype(jnp.float32) * w),
        argnums=(0, 1, 2))
    for name, gk, gr in zip(("dq", "dk", "dv"), gk_fn(q, k, v),
                            gr_fn(q, k, v)):
        results.append((f"flash grad {name}", _rel_err(gk, gr),
                        _tol(dtype), params))
    return results


def _fuzz_slstm_once(rng: np.random.Generator):
    """One sLSTM-scan draw: tail seq blocks (S % block_s != 0), S smaller
    than one block, degenerate Dh=1; fwd states + grads vs slstm_scan_ref."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.slstm_scan.ops import slstm_scan
    from repro.kernels.slstm_scan.ref import slstm_scan_ref

    B = int(rng.integers(1, 3))
    S = int(rng.integers(1, 97))
    H = int(rng.integers(1, 3))
    Dh = int(rng.choice([1, 3, 8, 16]))
    block_s = int(rng.choice([4, 8, 32, 128]))
    params = dict(B=B, S=S, H=H, Dh=Dh, block_s=block_s)

    f32 = np.float32
    g_in = jnp.asarray(rng.standard_normal((B, S, 4, H, Dh), f32))
    r = jnp.asarray(rng.standard_normal((4, H, Dh, Dh), f32)
                    / np.sqrt(max(Dh, 1)))
    b = jnp.asarray(0.5 * rng.standard_normal((4, H, Dh), f32))
    state0 = {"c": jnp.asarray(rng.standard_normal((B, H, Dh), f32)),
              "n": jnp.asarray(rng.uniform(0.5, 2.0, (B, H, Dh))
                               .astype(f32)),
              "m": jnp.asarray(0.5 * rng.standard_normal((B, H, Dh), f32)),
              "h": jnp.asarray(rng.standard_normal((B, H, Dh), f32))}

    hs, fin = slstm_scan(g_in, r, b, state0, block_s=block_s,
                         interpret=True)
    hs_r, fin_r = slstm_scan_ref(g_in, r, b, state0)
    results = [("slstm fwd hs", _rel_err(hs, hs_r), 1e-3, params)]
    for kname in ("c", "n", "m", "h"):
        results.append((f"slstm fwd fin[{kname}]",
                        _rel_err(fin[kname], fin_r[kname]), 1e-3, params))

    w = jnp.asarray(rng.standard_normal(hs_r.shape, f32))
    wf = jnp.asarray(rng.standard_normal(fin_r["h"].shape, f32))

    def loss_k(g_, r_, b_):
        hs_, fin_ = slstm_scan(g_, r_, b_, state0, block_s=block_s,
                               interpret=True)
        return jnp.sum(hs_ * w) + jnp.sum(fin_["h"] * wf)

    def loss_r(g_, r_, b_):
        hs_, fin_ = slstm_scan_ref(g_, r_, b_, state0)
        return jnp.sum(hs_ * w) + jnp.sum(fin_["h"] * wf)

    for name, gk, gr in zip(("dg", "dr", "db"),
                            jax.grad(loss_k, argnums=(0, 1, 2))(g_in, r, b),
                            jax.grad(loss_r, argnums=(0, 1, 2))(g_in, r, b)):
        results.append((f"slstm grad {name}", _rel_err(gk, gr), 1e-3,
                        params))
    return results


def _fuzz_nhsic_once(rng: np.random.Generator):
    """One streaming-nHSIC draw: B far from a block multiple (or smaller
    than one block), degenerate D=1, rbf/linear kernel mixes; fwd + the
    closed-form Pallas backward vs core.hsic.nhsic autodiff."""
    import jax
    import jax.numpy as jnp

    from repro.core.hsic import nhsic as nhsic_ref
    from repro.kernels.hsic_gram.ops import nhsic as nhsic_kernel

    B = int(rng.integers(2, 49))
    Dx = int(rng.choice([1, 2, 7, 32]))
    Dz = int(rng.choice([1, 2, 7, 32]))
    kx = str(rng.choice(["rbf", "linear"]))
    kz = str(rng.choice(["rbf", "linear"]))
    block = int(rng.choice([2, 3, 5, 128]))
    params = dict(B=B, Dx=Dx, Dz=Dz, kernel_x=kx, kernel_z=kz, block=block)

    x = jnp.asarray(rng.standard_normal((B, Dx), np.float32))
    z = jnp.asarray(rng.standard_normal((B, Dz), np.float32))

    def f_k(x_, z_):
        return nhsic_kernel(x_, z_, kernel_x=kx, kernel_z=kz, block=block,
                            interpret=True)

    def f_r(x_, z_):
        return nhsic_ref(x_, z_, kernel_x=kx, kernel_z=kz)

    results = [("nhsic fwd", _rel_err(f_k(x, z), f_r(x, z)), 1e-3, params)]
    for name, gk, gr in zip(("dx", "dz"),
                            jax.grad(f_k, argnums=(0, 1))(x, z),
                            jax.grad(f_r, argnums=(0, 1))(x, z)):
        results.append((f"nhsic grad {name}", _rel_err(gk, gr), 1e-3,
                        params))
    return results


_FUZZERS = {
    "flash_attention": _fuzz_flash_once,
    "hsic_gram": _fuzz_nhsic_once,
    "slstm_scan": _fuzz_slstm_once,
}

MAX_FUZZ_FINDINGS = 10    # per family: stop reporting after this many


def fuzz_families(report: Report, *, n_cases: int = 50, seed: int = 0,
                  families: Optional[Sequence[str]] = None) -> None:
    """Differential kernel-vs-reference fuzzing (interpret mode).

    Every case draws an adversarial shape from a seeded
    ``np.random.default_rng`` stream and compares forward AND gradient
    outputs of the public op against the ``ref.py`` oracle at
    scale-relative tolerance (1e-3 f32 / 2e-2 bf16).  Mismatches become
    ``pallas.fuzz-mismatch`` findings carrying the exact draw parameters,
    so any failure is a one-line pinned regression test."""
    summary = {}
    for i_fam, fam in enumerate(families or FAMILIES):
        rng = np.random.default_rng(1_000_003 * (seed + 1) + i_fam)
        checks = failures = errors = 0
        for i in range(n_cases):
            try:
                results = _FUZZERS[fam](rng)
            except Exception as e:
                errors += 1
                if errors + failures <= MAX_FUZZ_FINDINGS:
                    report.add("pallas.fuzz-error",
                               f"case {i}: {type(e).__name__}: {e}",
                               program=fam)
                continue
            for label, err, tol, params in results:
                checks += 1
                if not (err <= tol):
                    failures += 1
                    if errors + failures <= MAX_FUZZ_FINDINGS:
                        report.add(
                            "pallas.fuzz-mismatch",
                            f"{label}: rel err {err:.3e} > tol {tol:.0e} "
                            f"at {params}",
                            program=fam)
        if errors + failures > MAX_FUZZ_FINDINGS:
            report.add("pallas.fuzz-mismatch",
                       f"...{errors + failures - MAX_FUZZ_FINDINGS} further "
                       f"failure(s) suppressed", severity="warning",
                       program=fam)
        summary[fam] = {"cases": n_cases, "checks": checks,
                        "failures": failures, "errors": errors,
                        "seed": seed}
    report.artifacts["kernel_fuzz"] = summary


# --------------------------------------------------------------------------- #
# entry point (python -m repro.analysis --kernels)
# --------------------------------------------------------------------------- #
def run_kernel_audits(*, waive: Iterable[str] = (),
                      families: Optional[Sequence[str]] = None,
                      fuzz: int = 0, seed: int = 0,
                      vmem_budget_mib: float = DEFAULT_VMEM_BUDGET_MIB) \
        -> Report:
    """Static audit of every registered kernel case, plus (``fuzz > 0``)
    differential shape fuzzing against the reference oracles."""
    report = Report(waive=waive)
    audit_kernels(report, families=families,
                  vmem_budget_mib=vmem_budget_mib)
    if fuzz > 0:
        fuzz_families(report, n_cases=fuzz, seed=seed, families=families)
    return report
