"""End-to-end serving-prefill benchmark: the model forward with flash vs
dense attention (everything else — projections, FFN, cache writes —
identical).

Measures `models/generate._prompt_forward` on a 1-layer Llama-8B-dims
slice (dim 4096, 32/8 heads, head_dim 128, FFN 14336, bf16) at B=1.

Protocol note: unlike the kernel benches this times SINGLE jitted
forwards — whole-model dependent chains compile slowly, and the dense
S^2 variant fails outright inside a loop.  Fresh random tokens per call;
dispatch rides on a 10s-of-ms forward, so medians over rotated calls are
meaningful at the 10%+ effect sizes this measures.

Usage: python scripts/bench_prefill_e2e.py [--seq 4096] [--calls 15]
"""

import argparse
import functools
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from scripts.benchlib import RUN_SEED
from triton_dist_tpu.models.llama import LlamaConfig, init_params
from triton_dist_tpu.models.generate import _prompt_forward


def _cfg():
    return LlamaConfig(vocab=8192, dim=4096, n_layers=1, n_heads=32,
                       n_kv_heads=8, ffn_dim=14336, max_seq=16384,
                       dtype=jnp.bfloat16)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", nargs="*", type=int, default=[4096])
    ap.add_argument("--calls", type=int, default=15)
    args = ap.parse_args()
    from triton_dist_tpu.runtime import configure_compile_cache, require_tpu

    configure_compile_cache()
    # prints device metrics: the CPU backend cannot stand in
    require_tpu("scripts/bench_prefill_e2e.py")

    cfg = _cfg()
    params = init_params(cfg, jax.random.key(0))

    for S in args.seq:
        fns = {}
        for label, impl in [("dense (impl=xla)", "xla"),
                            ("flash (impl=auto)", "auto")]:
            fwd = functools.partial(_prompt_forward, cfg=cfg, impl=impl)

            # The reduction lives INSIDE the jit: returning the full
            # [1, S, V] logits would copy ~100 MB back to the host
            # per call and swamp the measurement.
            @jax.jit
            def jitted(params, tokens, fwd=fwd):
                _, logits = fwd(params, tokens)
                return jnp.sum(logits[:, -1])

            def call(tokens, jitted=jitted):
                return float(jitted(params, tokens))

            try:
                call(jnp.zeros((1, S), jnp.int32))  # compile + warm
            except Exception as e:  # noqa: BLE001
                print(f"  {label:20s} SKIP ({type(e).__name__})",
                      flush=True)
                continue
            fns[label] = call

        labels = list(fns)
        times = {label: [] for label in labels}
        for t in range(args.calls):
            toks = jax.random.randint(jax.random.key(RUN_SEED + t),
                                      (1, S), 0, cfg.vocab, jnp.int32)
            jax.block_until_ready(toks)
            rot = t % max(len(labels), 1)
            for label in labels[rot:] + labels[:rot]:
                t0 = time.perf_counter()
                fns[label](toks)
                times[label].append(time.perf_counter() - t0)

        print(f"\nS={S} (1-layer 8B-dims slice, B=1, bf16, single "
              f"forwards incl. ~ms dispatch):")
        for label in labels:
            d = sorted(times[label])
            med = statistics.median(d) * 1e3
            iqr = (d[(3 * len(d)) // 4] - d[len(d) // 4]) * 1e3
            print(f"  {label:20s} {med:8.2f} ms/forward (IQR {iqr:.2f})",
                  flush=True)


if __name__ == "__main__":
    main()
