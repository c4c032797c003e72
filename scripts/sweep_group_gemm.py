"""Real-chip MFU sweep of the grouped (expert-blocked) GEMM vs XLA.

Reference analog: the GroupGEMM perf focus of ``moe_reduce_rs.py`` /
``allgather_group_gemm.py`` — the MoE backbone matmul.  Baselines:
``jax.lax.ragged_dot`` (XLA's native grouped matmul) and our
``group_gemm_xla`` dense-einsum fallback.

Serving shape defaults: DeepSeek-style per-rank expert compute — E_loc=8
expert slabs, K=hidden=7168, N=moe-intermediate=2048, M_pad=4096 sorted
rows; bf16 and int8 (W8A8 path).

Protocol: scripts/bench_decode.py's — value-feedback dependent chains
inside one jit (each iteration's input is the previous output through a
dense [N, K] projection whose FLOPs are counted), rotated config order
per trial, paired long/short diffs, fresh time-seeded inputs per trial,
float() materialization, pooled median.  Reported rates are the combined
grouped+projection rate (the realistic chained-expert-matmul pattern).
"""

import argparse
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from scripts.benchlib import RUN_SEED, rotated_paired_bench
from triton_dist_tpu.kernels.group_gemm import group_gemm

E, K, N, M = 8, 7168, 2048, 4096


def make_chain(n_iters, fn, dtype):
    """fn: (x [M, K], w [E, K, N], tile_expert) -> y [M, N].  The chain
    feeds y back through a fixed [N, K] projection, so every iteration's
    input VALUES depend on the previous output — the only dependence the
    measurement can trust.  (Zero-add "dependence" tricks — adding a
    never-true comparison of y — produced >100%-of-peak readings for both
    XLA and opaque pallas ops on this backend; values must actually
    change.)  The projection's FLOPs are counted: reported numbers are
    the COMBINED grouped-GEMM + dense-projection rate, which is also the
    realistic MoE FFN pattern (chained expert matmuls)."""

    @jax.jit
    def chain(x, w, te, back):
        def body(_, xx):
            y = fn(xx, w, te)
            z = jnp.dot(y.astype(jnp.bfloat16), back,
                        preferred_element_type=jnp.float32)
            if dtype == jnp.int8:
                return jnp.clip(z / 16.0, -127, 127).astype(jnp.int8)
            return z.astype(dtype)

        return jnp.sum(jax.lax.fori_loop(0, n_iters, body, x)
                       .astype(jnp.float32))

    return chain


def bench(configs, dtype, n_short=8, n_long=72, trials=9):
    ks = jax.random.split(jax.random.key(0), 3)
    if dtype == jnp.int8:
        w = jax.random.randint(ks[1], (E, K, N), -127, 127, jnp.int8)
        x0 = jax.random.randint(ks[0], (M, K), -127, 127, jnp.int8)
    else:
        w = jax.random.normal(ks[1], (E, K, N), dtype)
        x0 = jax.random.normal(ks[0], (M, K), dtype)
    back = jax.random.normal(ks[2], (N, K), jnp.bfloat16) * 0.02
    n_tiles_of = lambda bm: M // bm

    chains = {}
    for label, fn, bm in configs:
        # SORTED tile→expert map (what moe_utils.sort_align produces):
        # consecutive tiles share an expert slab, the realistic layout.
        # A round-robin map is the pessimal slab-churn case and measures
        # ~10% lower — worth knowing, but not the serving distribution.
        n_tiles = n_tiles_of(bm)
        te = jnp.sort(jnp.arange(n_tiles, dtype=jnp.int32)
                      % min(E, n_tiles))
        short = make_chain(n_short, fn, dtype)
        long = make_chain(n_long, fn, dtype)
        float(short(x0, w, te, back))
        float(long(x0, w, te, back))
        chains[label] = (short, long, te)

    def fresh_x(t):
        if dtype == jnp.int8:
            return jax.random.randint(jax.random.key(RUN_SEED + t), (M, K),
                                      -127, 127, jnp.int8)
        return jax.random.normal(jax.random.key(RUN_SEED + t), (M, K),
                                 dtype)

    res = rotated_paired_bench(
        {label: (short, long, (w, te, back))
         for label, (short, long, te) in chains.items()},
        fresh_x, n_long - n_short, trials=trials)
    flops = 2 * M * K * N * 2  # grouped GEMM + the equal-FLOPs projection
    return {label: (med * 1e6, flops / med / 1e12)
            for label, (med, _iqr) in res.items()}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dtypes", nargs="+", default=["bf16", "int8"])
    ap.add_argument("--blocks", type=int, nargs="+", default=[256, 512])
    ap.add_argument("--trials", type=int, default=9)
    args = ap.parse_args()
    from triton_dist_tpu.runtime import configure_compile_cache, require_tpu

    configure_compile_cache()
    # prints device metrics: the CPU backend cannot stand in
    require_tpu("scripts/sweep_group_gemm.py")

    for dname in args.dtypes:
        dtype = {"bf16": jnp.bfloat16, "int8": jnp.int8}[dname]
        peak = 197.0 * (2.0 if dtype == jnp.int8 else 1.0)

        def ragged(x, w, te, bm=None):
            gs = jnp.bincount(te, length=E) * (M // te.shape[0])
            return jax.lax.ragged_dot(
                x, w, gs.astype(jnp.int32),
                preferred_element_type=(jnp.int32 if dtype == jnp.int8
                                        else jnp.float32))

        configs = [("xla ragged_dot", ragged, 256)]
        for bm in args.blocks:
            for bn, bk in [(512, 512), (512, 1024), (1024, 512),
                           (1024, 1024)]:
                label = f"pallas bm={bm} bn={bn} bk={bk}"
                fn = (lambda x, w, te, bm=bm, bn=bn, bk=bk:
                      group_gemm(x, w, te, block_m=bm, bn=bn, bk=bk,
                                 impl="pallas"))
                configs.append((label, fn, bm))
        res = bench(configs, dtype, trials=args.trials)
        print(f"\n{dname}: E={E} K={K} N={N} M_pad={M} "
              f"(chip peak ~{peak:.0f} T{'OPS' if dtype==jnp.int8 else 'FLOPS'}):")
        for label, (us, tf) in res.items():
            print(f"  {label:<28}: {us:8.1f} µs  {tf:7.1f} "
                  f"T{'OPS' if dtype==jnp.int8 else 'FLOPS'} "
                  f"({tf/peak:.0%} MFU)")


if __name__ == "__main__":
    main()
