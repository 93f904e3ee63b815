"""Kernel microbenchmarks: Pallas flash attention + HSIC Gram vs jnp refs.

On this CPU container the Pallas kernels run in interpret mode, so wall
times compare the *reference* path against the streaming path's lowered-HLO
form (interpret mode lowers ``pallas_call`` to plain lax ops); MXU-tiled
wall-clock wins need a TPU.  What IS meaningful on CPU — and asserted here —
is the memory shape of the differentiable path: the fused nHSIC custom_vjp
saves O(B·D) residuals (no B×B Gram), measured against the 4·B² floats the
naive autodiff path keeps live for the two centered Grams.

Also times the lax-conv vs im2col unit conv (forward and backward) under
``vmap`` over per-cohort weights — the shape the vectorized FL round
actually runs (see ``fl_round_throughput`` for the full-round crossover).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks.common import csv_row, timeit
from repro.core import hsic
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.hsic_gram import ops as kops


def _nhsic_rows(key, out, quiet):
    """Reference vs fused-Pallas nHSIC, forward and jax.grad."""
    for B, Dx in [(64, 128), (256, 256)]:
        x = jax.random.normal(key, (B, Dx))
        z = jax.random.normal(jax.random.PRNGKey(1), (B, 64))
        ref_f = jax.jit(lambda a, b: hsic.nhsic(a, b))
        ker_f = jax.jit(lambda a, b: kops.nhsic(a, b))
        ref_g = jax.jit(jax.grad(lambda a, b: hsic.nhsic(a, b),
                                 argnums=(0, 1)))
        ker_g = jax.jit(jax.grad(lambda a, b: kops.nhsic(a, b),
                                 argnums=(0, 1)))
        row = {"fwd_ref_s": timeit(ref_f, x, z),
               "fwd_pallas_s": timeit(ker_f, x, z),
               "grad_ref_s": timeit(ref_g, x, z),
               "grad_pallas_s": timeit(ker_g, x, z)}
        # residual memory of the differentiable path: the custom_vjp keeps
        # O(B·D) activations + row means; naive autodiff keeps the two
        # centered B×B Grams (and their raw forms) live for the backward
        _, res = kops.nhsic_residuals(x, z)
        res_bytes = sum(leaf.size * leaf.dtype.itemsize
                        for leaf in jax.tree.leaves(res))
        res_elems = sum(leaf.size for leaf in jax.tree.leaves(res))
        # exactly the activations + two row-mean vectors + scalars
        assert res_elems <= x.size + z.size + 2 * B + 16, \
            "B×B residual leaked"
        row["bwd_residual_bytes"] = res_bytes
        row["naive_gram_bytes"] = 4 * B * B * 4      # 4 × B² float32 Grams
        row["residual_ratio"] = res_bytes / row["naive_gram_bytes"]
        out[f"nhsic_B{B}_D{Dx}"] = row
        if not quiet:
            print(f"nhsic B{B} D{Dx}: fwd ref {row['fwd_ref_s']*1e3:.2f}ms "
                  f"pallas {row['fwd_pallas_s']*1e3:.2f}ms | grad ref "
                  f"{row['grad_ref_s']*1e3:.2f}ms pallas "
                  f"{row['grad_pallas_s']*1e3:.2f}ms | bwd residuals "
                  f"{res_bytes/1024:.0f}KiB vs {4*B*B*4/1024:.0f}KiB Grams")


def _conv_rows(key, out, quiet):
    """lax vs im2col unit conv under vmap over per-cohort weights."""
    from repro.models.cnn import conv

    C, B, H, cin, cout, k = 16, 16, 8, 8, 8, 3
    wv = jax.random.normal(key, (C, k, k, cin, cout)) * 0.1
    xv = jax.random.normal(jax.random.PRNGKey(2), (C, B, H, H, cin))
    for impl in ("lax", "im2col"):
        fwd = jax.jit(jax.vmap(lambda w, x, i=impl: conv({"w": w}, x, 1, i)))
        bwd = jax.jit(jax.grad(
            lambda w, x, i=impl: jnp.sum(
                jax.vmap(lambda wi, xi: conv({"w": wi}, xi, 1, i))(w, x))))
        row = {"fwd_s": timeit(fwd, wv, xv), "bwd_s": timeit(bwd, wv, xv)}
        out[f"conv_{impl}_C{C}"] = row
        if not quiet:
            print(f"conv[{impl}] vmap C{C} {H}x{H}x{cin}: "
                  f"fwd {row['fwd_s']*1e3:.2f}ms bwd {row['bwd_s']*1e3:.2f}ms")


def run(quiet: bool = False):
    key = jax.random.PRNGKey(0)
    out = {}
    # attention reference throughput (per-shape)
    for (B, S, H, KV, D) in [(2, 256, 8, 2, 64), (1, 1024, 8, 8, 64)]:
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, KV, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, KV, D), jnp.float32)
        f = jax.jit(lambda q, k, v: attention_ref(q, k, v, causal=True))
        t = timeit(f, q, k, v)
        flops = 4 * B * S * S / 2 * H * D
        out[f"attn_ref_S{S}"] = {"s": t, "gflops": flops / t / 1e9}
        if not quiet:
            print(f"attn_ref B{B} S{S}: {t*1e3:.1f}ms "
                  f"({flops/t/1e9:.1f} GFLOP/s)")
    _nhsic_rows(key, out, quiet)
    _conv_rows(key, out, quiet)
    return out


def quick():
    t0 = time.time()
    out = run(quiet=True)
    dt = (time.time() - t0) * 1e6
    r64 = out["nhsic_B64_D128"]
    csv_row("kernels_bench", dt / max(len(out), 1),
            f"attn_S1024_gflops={out['attn_ref_S1024']['gflops']:.1f} "
            f"nhsic_grad_residual_ratio={r64['residual_ratio']:.2f}")


if __name__ == "__main__":
    run()
