"""Real-chip latency bench for the MoE AllToAll kernel (second headline).

BASELINE metric: "MoE AllToAll p50 latency (128 tok/rank)" — the reference's
137 µs kernel runs on 32 H800s; this chip is a single TPU, so what can be
measured here is the kernel's single-chip floor (the pallas dispatch +
local-segment DMA path at the reference's shape: 128 tokens, hidden 7168).
Multi-chip wire latency needs multi-chip hardware; the kernel's multi-device
semantics are validated on the virtual CPU mesh (tests/test_all_to_all.py).

Chained-iteration timing: N dependent AllToAlls inside one jit (each
iteration consumes the previous recv buffer), (t_long - t_short) / extra.
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from triton_dist_tpu.kernels.all_to_all import fast_all_to_all_shard  # noqa: E402

from scripts.benchlib import RUN_SEED, churn as _churn  # noqa: E402

TOKENS, HIDDEN = 128, 7168
N_EXTRA = 16384  # at a ~1 µs floor, 4096-iter chains sit inside host timing jitter


def _backout_us(chains, fresh_input):
    """benchlib.backout_pair in µs (warmup + rotated interleaved trials)."""
    from scripts.benchlib import backout_pair

    floor_s, churn_s = backout_pair(chains, fresh_input, n_extra=N_EXTRA,
                                    trials=9)
    return floor_s * 1e6, churn_s * 1e6


def make_chain(mesh, n, with_a2a=True):
    shard = functools.partial(fast_all_to_all_shard, axis="ep",
                              impl="pallas", interpret=False)

    def body_fn(send, splits):
        def body(i, x):
            if with_a2a:
                x, _ = shard(x, splits)
            return _churn(x, i)
        return jax.lax.fori_loop(0, n, body, send)[0, 0, 0]

    return jax.jit(jax.shard_map(
        body_fn, mesh=mesh, in_specs=(P("ep"), P("ep")), out_specs=P(),
        check_vma=False))


def main():
    from triton_dist_tpu.runtime import configure_compile_cache, require_tpu

    configure_compile_cache()
    # prints device metrics: the CPU backend cannot stand in
    require_tpu("scripts/bench_a2a.py")
    mesh = Mesh(np.array(jax.devices()[:1]), ("ep",))
    # Measured floors (16k-iter churned chains, churn-only cost backed
    # out): bf16 ~1.2 µs, raw fp8 ~1.5 µs, fp8 packed 4-wide into int32
    # lanes ~1.1-1.7 µs — all within noise of each other at this payload
    # size (docs/perf.md records the retraction of the round-2 readings
    # that overstated the raw-fp8 penalty).
    cases = [(jnp.bfloat16, HIDDEN, "bf16"),
             (jnp.float8_e4m3fn, HIDDEN, "fp8_e4m3"),
             (jnp.int32, HIDDEN // 4, "fp8x4_i32")]
    for dtype, hidden, name in cases:
        splits = jnp.full((1,), TOKENS, jnp.int32)
        c1, cn = make_chain(mesh, 1), make_chain(mesh, 1 + N_EXTRA)
        x1, xn = (make_chain(mesh, 1, with_a2a=False),
                  make_chain(mesh, 1 + N_EXTRA, with_a2a=False))

        def fresh(t, dtype=dtype, hidden=hidden):
            x = jax.random.normal(jax.random.key(RUN_SEED + t),
                                  (1, TOKENS, hidden), jnp.float32)
            if dtype == jnp.int32:
                return jax.lax.bitcast_convert_type(x, jnp.int32)
            return x.astype(dtype)

        us, churn_us = _backout_us(
            {"total": (c1, cn, (splits,)), "churn": (x1, xn, (splits,))},
            fresh)
        flag = "" if us > 0 else "  [SUSPECT: non-positive backout]"
        print(f"a2a {name:10s} {TOKENS} tok x {hidden} cols: "
              f"{us:7.1f} us/iter (single-chip floor; churn "
              f"{churn_us:.1f} us backed out){flag}")

    _bench_decode_gather(mesh)


def _bench_decode_gather(mesh):
    """Floor of the SP-decode per-step partials gather (the LL-AG role:
    one [B, Hq, D+1] f32 payload per chip per decode step)."""
    from triton_dist_tpu.kernels.low_latency_allgather import (
        fast_allgather_shard)

    B, Hq, D1 = 8, 32, 129

    def make(n, with_ag):
        def body_fn(x):
            def body(i, x):
                if with_ag:
                    g = fast_allgather_shard(x, axis="ep", impl="pallas",
                                             interpret=False)
                    x = g.reshape(1, B, Hq, D1)[0]
                return _churn(x, i)
            return jax.lax.fori_loop(0, n, body, x)[0, 0, 0]
        return jax.jit(jax.shard_map(body_fn, mesh=mesh, in_specs=P(),
                                     out_specs=P(), check_vma=False))

    c1, cn = make(1, True), make(1 + N_EXTRA, True)
    x1, xn = make(1, False), make(1 + N_EXTRA, False)

    def fresh(t):
        return jax.random.normal(jax.random.key(RUN_SEED + t),
                                 (B, Hq, D1), jnp.float32)

    us, churn_us = _backout_us(
        {"total": (c1, cn, ()), "churn": (x1, xn, ())}, fresh)
    flag = "" if us > 0 else "  [SUSPECT: non-positive backout]"
    print(f"ll-ag decode partials [8, 32, 129] f32: {us:7.1f} us/iter "
          f"(single-chip floor; churn {churn_us:.1f} us backed out){flag}")


if __name__ == "__main__":
    main()
