"""Single-chip GQA decode step benchmark: pallas split-KV vs XLA fused.

Protocol (docs/perf.md): dependent-iteration chains inside ONE jit (the
decode output feeds the next step's query, so XLA cannot hoist work),
timed as (t_long - t_short) / extra to cancel the per-call dispatch
constant; trials of ALL configs are interleaved round-robin so slow drift
(thermal / host contention) hits every config equally; pooled median over
>= 9 trials.  Completion barrier is a float() materialization of the
chain's scalar result.

Usage: python scripts/bench_decode.py [--batch 8 32]
       [--block-s 1024 2048 4096] [--trials 9]
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
from triton_dist_tpu.kernels.flash_decode import gqa_decode_shard

HQ, HKV, D, S = 32, 8, 128, 8192


def make_chain(n_iters, impl, block_s):
    @jax.jit
    def chain(q, k, v, lens):
        def body(_, qq):
            out, _lse = gqa_decode_shard(qq, k, v, lens, block_s=block_s,
                                         impl=impl)
            return out.astype(qq.dtype)

        return jnp.sum(jax.lax.fori_loop(0, n_iters, body, q)
                       .astype(jnp.float32))

    return chain


def bench_batch(B, configs, n_short=32, n_long=288, trials=9):
    """configs: list of (label, impl, block_s).
    Returns {label: (median µs/step, IQR µs)}."""
    ks = jax.random.split(jax.random.key(0), 3)
    k = jax.random.normal(ks[1], (B, HKV, S, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, HKV, S, D), jnp.bfloat16)
    lens = jnp.full((B,), S, jnp.int32)
    q0 = jax.random.normal(ks[0], (B, HQ, D), jnp.bfloat16)

    chains = {}
    for label, impl, bs in configs:
        short = make_chain(n_short, impl, bs)
        long = make_chain(n_long, impl, bs)
        float(short(q0, k, v, lens))  # warmup/compile
        float(long(q0, k, v, lens))
        chains[label] = (short, long, (k, v, lens))

    def fresh_q(t):
        return jax.random.normal(jax.random.key(RUN_SEED + t),
                                 (B, HQ, D), jnp.bfloat16)

    res = rotated_paired_bench(chains, fresh_q, n_long - n_short,
                               trials=trials)
    return {label: (med * 1e6, iqr * 1e6) for label, (med, iqr) in
            res.items()}


def make_chain_i8(n_iters, impl, block_s):
    @jax.jit
    def chain(q, k, v, ks_, vs_, lens):
        def body(_, qq):
            out, _lse = gqa_decode_shard(qq, k, v, lens, block_s=block_s,
                                         impl=impl, k_scale=ks_, v_scale=vs_)
            return out.astype(qq.dtype)

        return jnp.sum(jax.lax.fori_loop(0, n_iters, body, q)
                       .astype(jnp.float32))

    return chain


def bench_batch_i8(B, configs, n_short=32, n_long=288, trials=9):
    """int8-KV variant (VERDICT r3 #5): the cache streams as int8 + f32
    scale planes; configs: (label, impl, block_s) where impl='pallas'
    runs the fused dequant split-KV kernel and impl='xla' the fused XLA
    program (the r3 serving path to beat: 206 µs at B=8 S=8192)."""
    from triton_dist_tpu.kernels.flash_decode import quantize_kv

    ks = jax.random.split(jax.random.key(0), 3)
    k = jax.random.normal(ks[1], (B, HKV, S, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, HKV, S, D), jnp.bfloat16)
    kq, ksc = quantize_kv(k.astype(jnp.float32))
    vq, vsc = quantize_kv(v.astype(jnp.float32))
    lens = jnp.full((B,), S, jnp.int32)
    q0 = jax.random.normal(ks[0], (B, HQ, D), jnp.bfloat16)

    chains = {}
    for label, impl, bs in configs:
        short = make_chain_i8(n_short, impl, bs)
        long = make_chain_i8(n_long, impl, bs)
        float(short(q0, kq, vq, ksc, vsc, lens))
        float(long(q0, kq, vq, ksc, vsc, lens))
        chains[label] = (short, long, (kq, vq, ksc, vsc, lens))

    def fresh_q(t):
        return jax.random.normal(jax.random.key(RUN_SEED + t),
                                 (B, HQ, D), jnp.bfloat16)

    res = rotated_paired_bench(chains, fresh_q, n_long - n_short,
                               trials=trials)
    return {label: (med * 1e6, iqr * 1e6) for label, (med, iqr) in
            res.items()}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, nargs="+", default=[8, 32])
    ap.add_argument("--block-s", type=int, nargs="+",
                    default=[1024, 2048, 4096])
    ap.add_argument("--trials", type=int, default=9)
    ap.add_argument("--int8", action="store_true",
                    help="bench the int8-KV cache path instead of bf16")
    args = ap.parse_args()
    from triton_dist_tpu.runtime import configure_compile_cache, require_tpu

    configure_compile_cache()
    # prints device metrics: the CPU backend cannot stand in
    require_tpu("scripts/bench_decode.py")

    for B in args.batch:
        if args.int8:
            floor = (B * HKV * S * D * 2 * 1 + B * HKV * S * 2 * 4) \
                / 819e9 * 1e6
            configs = [("i8 xla fused", "xla", 1024)]
            configs += [(f"i8 pallas block_s={bs}", "pallas", bs)
                        for bs in args.block_s]
            res = bench_batch_i8(B, configs, trials=args.trials)
            print(f"\nB={B} Hq={HQ} Hkv={HKV} S={S} int8-KV "
                  f"(HBM floor ~{floor:.0f} µs):")
        else:
            floor = 2 * B * HKV * S * D * 2 / 819e9 * 1e6
            configs = [("xla fused", "xla", 1024)]
            configs += [(f"pallas block_s={bs}", "pallas", bs)
                        for bs in args.block_s]
            res = bench_batch(B, configs, trials=args.trials)
            print(f"\nB={B} Hq={HQ} Hkv={HKV} S={S} bf16 "
                  f"(HBM floor ~{floor:.0f} µs):")
        for label, (t, iqr) in res.items():
            print(f"  {label:<22}: {t:8.1f} µs/step  (IQR {iqr:.0f})")


if __name__ == "__main__":
    main()
