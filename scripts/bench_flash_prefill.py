"""Single-chip causal-prefill attention benchmark: flash kernel vs XLA dense.

Protocol (docs/perf.md / bench_decode.py): dependent-iteration chains in
ONE jit (each step's output is the next step's query — nothing can be
hoisted or elided), (t_long - t_short)/extra cancels the per-call
dispatch constant, config order rotates per trial so drift hits every
config equally,
pooled median over trials.

The dense XLA path materializes [B, Hq, S, S] f32 logits — 8.6 GB/step
at S = 8192, Hq = 32, B = 1.  That still fits this chip's HBM (the bench
measures it at ~38 ms), but it is the scaling wall: one more doubling of
S or B OOMs, while flash stays O(S) — configs that exceed memory are
reported as SKIP rather than crashing the sweep.

Usage: python scripts/bench_flash_prefill.py [--seq 2048 4096] [--trials 9]
"""

import argparse
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from scripts.benchlib import RUN_SEED, rotated_paired_bench
from triton_dist_tpu.kernels.flash_attention import flash_attention

B, HQ, HKV, D = 1, 32, 8, 128


def make_chain(n_iters, impl, bq, bk, grad=False):
    def step(qq, k, v):
        return flash_attention(qq, k, v, causal=True, impl=impl,
                               block_q=bq, block_k=bk)

    @jax.jit
    def chain(q, k, v):
        def body(_, qq):
            if grad:
                # fwd + flash bwd per step; dq feeds the next step.
                out = jax.grad(lambda q_: jnp.sum(
                    step(q_, k, v).astype(jnp.float32) ** 2))(qq)
            else:
                out = step(qq, k, v)
            return out.astype(qq.dtype)

        return jnp.sum(jax.lax.fori_loop(0, n_iters, body, q)
                       .astype(jnp.float32))

    return chain


def bench_seq(S, configs, n_short=4, n_long=20, trials=9, grad=False):
    ks = jax.random.split(jax.random.key(0), 3)
    k = jax.random.normal(ks[1], (B, HKV, S, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, HKV, S, D), jnp.bfloat16)
    q0 = jax.random.normal(ks[0], (B, HQ, S, D), jnp.bfloat16)

    chains = {}
    for label, impl, bq, bk in configs:
        short = make_chain(n_short, impl, bq, bk, grad=grad)
        long = make_chain(n_long, impl, bq, bk, grad=grad)
        try:
            float(short(q0, k, v))  # warmup/compile
            float(long(q0, k, v))
        except Exception as e:  # noqa: BLE001 — OOM/compile: report, skip
            print(f"  {label:28s} SKIP ({type(e).__name__})", flush=True)
            continue
        chains[label] = (short, long, (k, v))

    if not chains:  # every config SKIPped (e.g. absurd S): no sweep
        return {}

    def fresh_q(t):
        return jax.random.normal(jax.random.key(RUN_SEED + t),
                                 (B, HQ, S, D), jnp.bfloat16)

    res = rotated_paired_bench(chains, fresh_q, n_long - n_short,
                               trials=trials)
    # Causal FLOPs: 2 matmuls x 2 flops x Hq x S^2 x D, half masked.
    flops = 2 * 2 * HQ * S * S * D * B / 2
    out = {}
    for label, (med, iqr) in res.items():
        out[label] = (med * 1e3, iqr * 1e3, flops / med / 1e12)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", nargs="*", type=int, default=[2048, 4096, 8192])
    ap.add_argument("--trials", type=int, default=9)
    ap.add_argument("--grad", action="store_true",
                    help="bench fwd+bwd per step (the flash VJP kernels)")
    args = ap.parse_args()
    from triton_dist_tpu.runtime import configure_compile_cache, require_tpu

    configure_compile_cache()
    # prints device metrics: the CPU backend cannot stand in
    require_tpu("scripts/bench_flash_prefill.py")

    configs = [
        ("xla dense", "xla", None, None),
        ("flash defaults", "pallas", None, None),
        ("flash bq=512 bk=512", "pallas", 512, 512),
        ("flash bq=512 bk=1024", "pallas", 512, 1024),
    ]
    mode = "fwd+bwd" if args.grad else "fwd"
    for S in args.seq:
        print(f"\nS={S} (B={B} Hq={HQ} Hkv={HKV} D={D}, causal, {mode}):")
        for label, (ms, iqr, tf) in bench_seq(S, configs, grad=args.grad,
                                              trials=args.trials).items():
            # --grad TFLOPS uses the fwd flop count: interpret as a
            # relative number only (bwd is ~2.5x the fwd flops).
            print(f"  {label:28s} {ms:8.2f} ms/step (IQR {iqr:.2f})  "
                  f"{tf:6.1f} TFLOPS(fwd-equiv)", flush=True)


if __name__ == "__main__":
    main()
