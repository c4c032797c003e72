"""Real-chip GEMM block sweep (the tuning recipe behind MatmulConfig).

Paired-diff timing: a 1-iteration and a 17-iteration chain of dependent
matmuls inside one jit; (t17 - t1) / 16 cancels the per-call dispatch
overhead.  Short chains (1 against 9) showed ±10% IQR; 1v17 with 9
trials was stable to ~2% (docs/perf.md).

Run on the chip through the chip tool: `python scripts/sweep_gemm.py`
(.claude/skills/verify/SKILL.md).
"""

import functools
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from triton_dist_tpu.kernels.gemm import MatmulConfig, matmul  # noqa: E402
from triton_dist_tpu.runtime import (  # noqa: E402
    configure_compile_cache,
    require_tpu,
)

configure_compile_cache()
# prints device metrics: the CPU backend cannot stand in
require_tpu("scripts/sweep_gemm.py")

M, K, N = 8192, 8192, 3584
N_EXTRA = 16

a = jnp.zeros((M, K), jnp.bfloat16)
b1 = jnp.zeros((K, N), jnp.bfloat16)
b2 = jnp.zeros((N, K), jnp.bfloat16)
flops_per_iter = 2 * M * N * K * 2  # forward + return matmul


def chain(fn, n):
    def body_fn(a, b1, b2):
        def body(i, x):
            return fn(fn(x, b1), b2)
        return jax.lax.fori_loop(0, n, body, a)[0, 0]
    return jax.jit(body_fn)


def run(name, fn):
    c1, cn = chain(fn, 1), chain(fn, 1 + N_EXTRA)
    try:
        float(c1(a, b1, b2)); float(cn(a, b1, b2))
    except Exception as e:
        print(f"{name:28s} FAIL {str(e)[:80]}")
        return
    diffs = []
    for _ in range(9):
        t0 = time.perf_counter(); float(c1(a, b1, b2)); t1 = time.perf_counter() - t0
        t0 = time.perf_counter(); float(cn(a, b1, b2)); tn = time.perf_counter() - t0
        diffs.append((tn - t1) / N_EXTRA)
    med = float(np.median(diffs))
    lo, hi = np.percentile(diffs, [25, 75])
    print(f"{name:28s} {flops_per_iter / med / 1e12:7.1f} TFLOPS  "
          f"(iqr {flops_per_iter / hi / 1e12:.1f}-{flops_per_iter / lo / 1e12:.1f})")


if __name__ == "__main__":
    run("xla_dot",
        lambda x, w: jnp.dot(x, w, preferred_element_type=jnp.float32)
        .astype(jnp.bfloat16))
    for (bm, bn, bk) in [(2048, 512, 512), (1024, 1024, 512),
                         (2048, 512, 256), (1024, 512, 512),
                         (512, 1024, 1024), (512, 512, 512)]:
        run(f"pallas {bm}x{bn}x{bk}",
            functools.partial(matmul, config=MatmulConfig(bm, bn, bk)))
