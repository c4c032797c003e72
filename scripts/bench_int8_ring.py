"""int8 through the flagship ring AG-GEMM kernel (VERDICT r2 #6).

Round 2 conceded the int8 ring slope was "too noisy to quote".
Round-3 protocol: TWO structurally identical chains — the ring
AG-GEMM in int8 vs bf16, everything else shared — measured in ONE
rotated trial loop (benchlib), so drift cancels out of their
difference and the paired delta isolates the ring GEMM's dtype swap.

Chain body (both variants):
    c   = ag_gemm(xq[, astype], b1)      # ring kernel, int8 OR bf16
    cb  = (c.astype(f32) * 1e-4).astype(bf16)
    nxt = matmul(cb, b2)                 # counted bf16 return projection
    f   = _feedback(nxt, i)              # bench.py serializing feedback
    xq  = requantize_int8(f)             # probe-scaled, same in both

Known one-sided bias, CORRECTED analytically: the bf16 variant pays one
extra [M, K] int8→bf16 astype pass (64 MB read + 128 MB write ≈ 235 µs
at 819 GB/s) that the int8 variant does not — left uncorrected it
INFLATES both the paired delta and the derived TOPS, so the script
subtracts the analytic estimate from t_bf before deriving anything.

Derived TOPS uses the documented bf16 ring-kernel rate (~146 TFLOPS,
docs/perf.md) as the prior for the shared remainder:
    t_rest    = (t_bf_pair - eps_astype) - 2MNK/146e12
    t_ring_i8 = t_i8_pair - t_rest
    TOPS_i8   = 2MNK / t_ring_i8

Run: python scripts/bench_int8_ring.py [--trials 15]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

import bench  # repo-root: _feedback + chain protocol
from scripts.benchlib import RUN_SEED, rotated_paired_bench
from triton_dist_tpu.kernels.allgather_gemm import ag_gemm_shard
from triton_dist_tpu.kernels.gemm import MatmulConfig, matmul

M, K, N = 8192, 8192, 3584
# r4: the aliased/persistent ring kernel measures at parity with the
# dense kernel at world-1 (docs/perf.md "Ring-kernel schedule overhead
# decomposed"); the old 146 figure was protocol bias + the staging DMA.
BF16_RING_TFLOPS = 190.0
HBM_GBPS = 819.0
# The bf16 chain's extra [M,K] int8->bf16 astype: read M*K + write 2*M*K
EPS_ASTYPE_S = (M * K * 3) / (HBM_GBPS * 1e9)


def _requant(f, i):
    """Probe-scaled int8 requantization — identical pass in both chains
    (fused scale+round+clip+cast; values keep changing via _feedback)."""
    s = jnp.max(jnp.abs(f[::128, ::128]).astype(jnp.float32)) + 1e-6
    return jnp.clip(jnp.round(f.astype(jnp.float32) / s * 63.0),
                    -127, 127).astype(jnp.int8)


def make_chain(mesh, n, ring_dtype):
    def body_fn(xq, b1i, b1f, b2):
        def body(i, xq):
            if ring_dtype == jnp.int8:
                _, c = ag_gemm_shard(xq, b1i, axis="tp", impl="pallas",
                                     interpret=False)
            else:
                _, c = ag_gemm_shard(xq.astype(jnp.bfloat16), b1f,
                                     axis="tp", impl="pallas",
                                     interpret=False)
            cb = (c.astype(jnp.float32) * 1e-4).astype(jnp.bfloat16)
            nxt = matmul(cb, b2, config=MatmulConfig(2048, 512, 512))
            f = bench._feedback(nxt, i)
            return _requant(f, i)
        out = jax.lax.fori_loop(0, n, body, xq)
        return out[0, 0].astype(jnp.int32)

    return jax.jit(jax.shard_map(
        body_fn, mesh=mesh,
        in_specs=(P("tp", None), P(None, "tp"), P(None, "tp"), P(None, None)),
        out_specs=P(), check_vma=False))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trials", type=int, default=15)
    args = ap.parse_args()
    from triton_dist_tpu.runtime import configure_compile_cache, require_tpu

    configure_compile_cache()
    # prints device metrics: the CPU backend cannot stand in
    require_tpu("scripts/bench_int8_ring.py")

    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    kw = jax.random.split(jax.random.key(RUN_SEED), 3)
    b1i = jnp.clip(jnp.round(jax.random.normal(kw[0], (K, N)) * 32), -127,
                   127).astype(jnp.int8)
    b1f = b1i.astype(jnp.bfloat16) * 0.02
    b2 = jax.random.normal(kw[1], (N, K), jnp.bfloat16) * 0.02

    n_long = 9
    chains = {}
    for name, dt in (("i8", jnp.int8), ("bf", jnp.bfloat16)):
        c1 = make_chain(mesh, 1, dt)
        cn = make_chain(mesh, n_long, dt)
        chains[name] = (c1, cn, (b1i, b1f, b2))

    def fresh(t):
        f = jax.random.normal(jax.random.key(RUN_SEED + t), (M, K))
        return jnp.clip(jnp.round(f * 32), -127, 127).astype(jnp.int8)

    x0 = fresh(-1)
    for c1, cn, extra in chains.values():
        int(c1(x0, *extra))
        int(cn(x0, *extra))

    res = rotated_paired_bench(chains, fresh, n_extra=n_long - 1,
                               trials=args.trials)
    (t_i8, iqr_i8), (t_bf, iqr_bf) = res["i8"], res["bf"]
    flops = 2.0 * M * N * K
    t_bf_c = t_bf - EPS_ASTYPE_S  # remove the one-sided astype pass
    t_ring_bf = flops / (BF16_RING_TFLOPS * 1e12)
    t_rest = t_bf_c - t_ring_bf  # shared remainder, bias-corrected
    t_ring_i8 = max(t_i8 - t_rest, 1e-9)
    print(f"pair times: int8 {t_i8 * 1e3:.2f} ms (IQR {iqr_i8 * 1e3:.2f}), "
          f"bf16 {t_bf * 1e3:.2f} ms (IQR {iqr_bf * 1e3:.2f})")
    print(f"paired delta (bf16 - int8), astype-corrected: "
          f"{(t_bf_c - t_i8) * 1e3:.2f} ms per chain pair "
          f"(raw {(t_bf - t_i8) * 1e3:.2f} ms includes the bf16 "
          f"variant's extra astype, eps={EPS_ASTYPE_S * 1e3:.2f} ms)")
    tops = flops / t_ring_i8 / 1e12
    # Self-consistency ceiling (bench.py's rule): the ring cannot beat
    # the measured dense int8 kernel (358 TOPS, docs/perf.md) at the
    # same shape; a reading above it means drift leaked into the
    # small t_ring_i8 denominator — cap and flag rather than quote.
    I8_DENSE_CEILING = 358.0
    capped = " (CAPPED at dense-int8 ceiling; reading suspect)" \
        if tops > I8_DENSE_CEILING else ""
    print(f"implied int8 ring AG-GEMM: {min(tops, I8_DENSE_CEILING):.0f} "
          f"TOPS{capped} "
          f"(prior: bf16 ring at {BF16_RING_TFLOPS:.0f} TFLOPS; "
          f"astype bias corrected)")


if __name__ == "__main__":
    main()
