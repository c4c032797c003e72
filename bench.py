"""Driver benchmark: AG-GEMM effective TFLOPS/chip at the reference's shape.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Metric (BASELINE.json): "AG-GEMM TFLOPS/chip (overlap eff.)" at the
reference's LLaMA-3.1-70B FFN shard shape (test_ag_gemm.py --shape_id):
M=8192, K=8192, N=28672/8=3584 per chip, bfloat16.

Hardware note: every device leg builds its mesh on `jax.devices()[:1]`,
so `ag_gemm_shard` under auto dispatch takes its world-1 fast path (no
gather exists at world 1; the ring-kernel machinery itself is compiled+run
on hardware by scripts/smoke_tpu.py).  This script refuses to start
without a TPU (`bootstrap.require_tpu`): it prints device metrics, and a
"TFLOPS" from the CPU backend is not one.  The legs that start child
processes (`_bench_serve_mesh`, `_bench_serve_mesh2d`,
`_bench_kernel_report`) force those children onto the virtual CPU mesh —
this process holds the chip, and a chip belongs to one process — so their
fields are exactness checks and counts, listed under
`"cpu_mesh_fields"` in the output, never device measurements.

vs_baseline: the reference's README charts claim AG-GEMM parity with
hand-tuned libraries (FLUX/cuBLAS) on H800, i.e. ~65% of the H800's 989
bf16 TFLOPS peak at these shapes.  We normalize both sides by their chip
peaks:  vs_baseline = (ours/peak_tpu) / 0.65.  >1 means better MXU/SM
utilization than the reference achieves on its own hardware.

Timing note: timings use chained dependent iterations inside one jit
and subtract the 1-iteration chain, churn/work chains interleaved in
one rotated trial loop (scripts/benchlib.py: rotated_paired_bench /
backout_pair); block sizes are the real-chip sweep winners (MatmulConfig
defaults, gemm.py).
"""

import functools
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from triton_dist_tpu.kernels.allgather_gemm import ag_gemm_shard
from triton_dist_tpu.kernels.gemm import matmul
from triton_dist_tpu.runtime.topology import peak_bf16_tflops

M, K, N_PER_CHIP = 8192, 8192, 28672 // 8
# Per-process time-based seed (scripts/benchlib.py has the rationale).
import os as _os
import sys as _sys
_sys.path.insert(0, _os.path.dirname(_os.path.abspath(__file__)))
from scripts.benchlib import RUN_SEED  # noqa: E402
REF_UTILIZATION = 0.65  # reference AG-GEMM ~= hand-tuned library on H800


def _feedback(x, i):
    """Serializing value feedback between chain iterations
    (benchlib.churn_barrier): an int32-grouped mantissa churn whose lane
    relayout is a deliberate compute barrier, keyed by a sampled sum (one
    element per 128x128 tile) so no element of the next input exists
    before every tile of this output does.

    Why this exact construction (round-3 protocol sweep, docs/perf.md): a bare matmul chain reads 200-220 "TFLOPS"
    (above the 197 peak — the TPU pipelines consecutive kernels' tiles),
    a cheap same-width churn still trips the ceiling guard, and a full
    f32 RMS rescale reads 141-148 with ±5% spread; the relayout barrier
    is the only variant both below the measured XLA-dot ceiling and
    stable (±3% across processes once the median-of-three seed banks is
    applied; honest range 143-153).  The mantissa-only mask keeps
    sign/exponent intact (no inf/NaN into the matmuls; value growth is
    bounded by the 0.02-scaled weights, ~2.2x/iter, inside bf16 range
    over 17 iterations), and the mixed key guarantees every iteration's
    values differ (the values-must-change rule of scripts/benchlib.py).  The barrier's large
    bandwidth cost is measured by a feedback-only twin chain and
    subtracted (backout_pair)."""
    from scripts.benchlib import churn_barrier

    probe = jnp.sum(x[::128, ::128].astype(jnp.float32))
    s = jax.lax.bitcast_convert_type(probe, jnp.int32)
    return churn_barrier(x, i, extra_key=s & 1)


def _make_chain(mesh, n_iters, impl="auto", bm=None, bn=None, bk=None,
                chunks=1):
    """n_iters of (AG-GEMM -> matmul-back -> _feedback) with real value
    dependence, returning a scalar so fetching it forces execution.

    ``impl``/``bm``/``bn``/``bk``/``chunks`` parameterize the AG-GEMM so
    the on-chip autotune session (scripts/autotune_onchip.py) reuses this
    exact protocol with impl="pallas" and swept blocks — one chain
    implementation, not two drifting copies."""
    shard_ag = functools.partial(ag_gemm_shard, axis="tp", impl=impl,
                                 bm=bm, bn=bn, bk=bk, chunks=chunks,
                                 interpret=False)

    def body_fn(a, b1, b2):
        def body(i, x):
            _, c = shard_ag(x, b1)     # [M, N_loc]
            nxt = matmul(c, b2)        # [M, K]
            return _feedback(nxt, i)
        return jax.lax.fori_loop(0, n_iters, body, a)[0, 0]

    return jax.jit(jax.shard_map(
        body_fn, mesh=mesh,
        in_specs=(P("tp", None), P(None, "tp"), P(None, None)),
        out_specs=P(), check_vma=False))


def _make_xform_chain(mesh, n_iters):
    """Feedback-only chain at the same [M, K] shape: measures the feedback
    transform's own per-iteration cost so the AG-GEMM number can subtract
    it (the grouped-GEMM sweep's counted-projection protocol,
    docs/perf.md).  Identical _feedback call as the work chain, so the
    backout is exact; the mantissa churn inside it keeps the iterates
    value-changing without the work chain's matmuls."""

    def body_fn(a, b1, b2):
        def body(i, x):
            return _feedback(x, i)
        return jax.lax.fori_loop(0, n_iters, body, a)[0, 0]

    return jax.jit(jax.shard_map(
        body_fn, mesh=mesh,
        in_specs=(P("tp", None), P(None, "tp"), P(None, None)),
        out_specs=P(), check_vma=False))


def _bench_moe_a2a_us(n_extra=16384):
    """MoE AllToAll single-chip floor at the BASELINE serving point
    (128 tok/rank, hidden 7168, fp8 packed 4-wide into int32 lanes — the
    recommended fp8 wire layout, scripts/bench_a2a.py).  The reference's
    137 µs headline is a 32-chip wire number; one chip exposes only the
    kernel's dispatch + local-segment floor.  16k-iteration chains: at a
    ~1 µs floor, 4k iterations sit inside host timing jitter.

    At world=1 the AllToAll itself is the identity, so a bare
    recv-feedback chain's values never change between iterations and the
    whole chain is elided (an impossible 0.00 µs was once recorded).  Fix: every iteration XORs the loop index into the payload
    (values change, one cheap elementwise pass), and a second chain with
    the XOR alone measures that pass's cost, which is subtracted.

    Returns (floor_us, suspect: bool) — suspect when even the doubled-chain
    retry stays below the 0.2 µs physical floor (the measured LL-AG
    [8, 32, 129] gather floor; a 918 KB segment copy cannot beat it).
    """
    mesh = Mesh(np.array(jax.devices()[:1]), ("ep",))
    send = jnp.zeros((1, 128, 7168 // 4), jnp.int32)
    splits = jnp.full((1,), 128, jnp.int32)

    from scripts.bench_a2a import make_chain

    def make(n, with_a2a):
        return make_chain(mesh, n, with_a2a=with_a2a)

    def measure(n, seed_off=0):
        # backout_pair interleaves the total and churn-only chains in one
        # rotated trial loop (drift cancels out of the difference;
        # separate loops were producing negative floors).  ``seed_off``
        # gives the retry fresh trial inputs — replaying the first
        # measurement's keys would hand the retry cached (executable,
        # args) pairs, the very contamination it is probing for.
        from scripts.benchlib import backout_pair

        ca1, can = make(1, True), make(1 + n, True)
        cx1, cxn = make(1, False), make(1 + n, False)
        floor_s, _ = backout_pair(
            {"total": (ca1, can, (splits,)), "churn": (cx1, cxn, (splits,))},
            fresh_input=lambda t: jax.random.randint(
                jax.random.key(RUN_SEED + seed_off + t), send.shape,
                0, 1 << 20, jnp.int32),
            n_extra=n, trials=9)
        return floor_s * 1e6

    us = measure(n_extra)
    if us < 0.2:  # impossible reading: retry once with doubled chains
        us = measure(2 * n_extra, seed_off=100_000)
        if us < 0.2:
            return max(us, 0.0), True
    return us, False


def _bench_decode_us(trials=9):
    """GQA decode at the serving shape (B=8, Hq=32, Hkv=8, S=8192 bf16):
    the pallas split-KV kernel AND the XLA fused program interleaved in
    ONE rotated trial loop (VERDICT r4 next#1b: `decode_step_us` alone is
    dispatch-sensitive — 353-361 across sessions — so the PAIRED ratio is
    the field that can resolve a kernel change; both legs see identical
    drift and it cancels in the quotient).

    Returns (auto_us, decode_vs_xla_ratio) — ratio > 1 means the repo's
    kernel beats XLA's fused decode at the same shape."""
    import os as _os
    import sys as _sys

    _sys.path.insert(0, _os.path.dirname(_os.path.abspath(__file__)))
    from scripts.bench_decode import bench_batch

    # block_s=None → the dtype-uniform full-shard default (r4: reads at
    # the HBM floor; the pinned 2048 measured the retired r3 default).
    # At this shape ``auto`` resolves to the pallas kernel, so the pallas
    # leg IS the served path — benching a separate auto leg would time
    # the identical kernel a third time.
    res = bench_batch(8, [("pallas", "pallas", None),
                          ("xla", "xla", None)], trials=trials)
    ratio = (res["xla"][0] / res["pallas"][0]
             if res["pallas"][0] > 0 else 0.0)
    return res["pallas"][0], ratio


def _bench_ring_vs_dense(trials=12):
    """Ring-kernel quality ratio (VERDICT r4 next#1a): the dense
    pallas_call GEMM and the FULL world-1 ring AG-GEMM kernel (producer
    loop, semaphores, input_output_aliases — zero actual communication)
    in ONE rotated trial loop — the r4 decomposition protocol
    (scripts/exp_ring_schedule.py) promoted into the driver artifact.

    ratio = dense_pair_time / ring_pair_time.  >= 0.97 means the ring
    schedule costs <= ~3% over the bare kernel; a drop below is a real
    schedule regression (both legs share the back-matmul + feedback and
    the drift, which cancel in the quotient)."""
    from scripts.benchlib import rotated_paired_bench
    from scripts.exp_ring_schedule import make_chain as exp_chain

    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    kw = jax.random.split(jax.random.key(RUN_SEED + 1234), 3)
    b1 = jax.random.normal(kw[1], (K, N_PER_CHIP), jnp.bfloat16) * 0.02
    b2 = jax.random.normal(kw[2], (N_PER_CHIP, K), jnp.bfloat16) * 0.02
    n_long = 9
    chains = {
        v: (exp_chain(mesh, 1, v), exp_chain(mesh, n_long, v), (b1, b2))
        for v in ("dense", "ring")
    }

    def fresh(t):
        return jax.random.normal(jax.random.key(RUN_SEED + 30_000 + t),
                                 (M, K), jnp.bfloat16)

    x0 = fresh(-1)
    for c1, cn, extra in chains.values():
        float(c1(x0, *extra))
        float(cn(x0, *extra))
    res = rotated_paired_bench(chains, fresh, n_extra=n_long - 1,
                               trials=trials)
    if res["ring"][0] <= 0:
        return 0.0
    return res["dense"][0] / res["ring"][0]


def _make_dot_chain(mesh, n_iters):
    """Bare XLA-dot pair chain at the bench shape — the contention
    sentinel's known-cost reference op (no repo kernels involved)."""

    def body_fn(a, b1, b2):
        def body(i, x):
            c = jnp.dot(x, b1,
                        preferred_element_type=jnp.float32).astype(jnp.bfloat16)
            nxt = jnp.dot(c, b2,
                          preferred_element_type=jnp.float32).astype(jnp.bfloat16)
            return _feedback(nxt, i)
        return jax.lax.fori_loop(0, n_iters, body, a)[0, 0]

    return jax.jit(jax.shard_map(
        body_fn, mesh=mesh,
        in_specs=(P("tp", None), P(None, "tp"), P(None, None)),
        out_specs=P(), check_vma=False))


def _bench_contention_sentinel():
    """Time a known-cost reference op (the bare XLA dot whose measured
    ceiling `topology.measured_dot_ceiling_tflops` is already the elision
    guard's bound) under the exact chain protocol (VERDICT r3 #6).

    The AG-GEMM chain is host-dispatch sensitive: a run concurrent with a
    heavy CPU job read 138 TFLOPS vs the 143-153 quiet-machine range
    (docs/perf.md), and the driver artifact is whatever number survives
    the round.  A depressed *sentinel* reading separates "the machine was
    contended" from "the kernel regressed": XLA's dot has no repo code in
    it, so it can only read low for environmental reasons.

    Returns (sentinel_tflops, suspect: bool) — suspect when even a
    fresh-seeded retry stays below 85% of the measured ceiling.

    Reading the value: only a LOW sentinel is meaningful (contention).
    The absolute number routinely OVERSTATES the dot rate (meas. up to
    ~250 "TFLOPS" > the 197 peak): XLA fuses part of the feedback churn
    into the dots' prologue/epilogue, so the churn-only twin chain
    over-measures the backout.  The same fusion is why the world-1 auto
    path uses jnp.dot (allgather_gemm.py) — it is a real wall-clock win
    for users' chains even though the per-op TFLOPS attribution blurs.
    """
    from scripts.benchlib import backout_pair
    from triton_dist_tpu.runtime.topology import measured_dot_ceiling_tflops

    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    kw = jax.random.split(jax.random.key(RUN_SEED + 777), 3)
    b1 = jax.random.normal(kw[1], (K, N_PER_CHIP), jnp.bfloat16) * 0.02
    b2 = jax.random.normal(kw[2], (N_PER_CHIP, K), jnp.bfloat16) * 0.02
    flops_per_pair = 2 * M * N_PER_CHIP * K * 2
    n_long = 9
    chains = (_make_dot_chain(mesh, 1), _make_dot_chain(mesh, n_long),
              _make_xform_chain(mesh, 1), _make_xform_chain(mesh, n_long))

    def measure(seed_off):
        c1, cn, x1, xn = chains
        per_pair, _ = backout_pair(
            {"total": (c1, cn, (b1, b2)), "churn": (x1, xn, (b1, b2))},
            fresh_input=lambda t: jax.random.normal(
                jax.random.key(RUN_SEED + seed_off + t), (M, K),
                jnp.bfloat16),
            n_extra=n_long - 1, trials=9)
        return (flops_per_pair / per_pair / 1e12) if per_pair > 0 else 0.0

    ceiling = measured_dot_ceiling_tflops()
    tflops = measure(seed_off=50_000)
    if tflops < 0.85 * ceiling:
        tflops = max(tflops, measure(seed_off=60_000))
    return tflops, tflops < 0.85 * ceiling


def _bench_ag_gemm_tflops():
    """Headline AG-GEMM chain with the rescale-cost backout and the
    ceiling self-consistency guard (a reading above the measured XLA-dot
    ceiling is elision, not performance — docs/perf.md).

    Returns (tflops, suspect: bool)."""
    from triton_dist_tpu.runtime.topology import measured_dot_ceiling_tflops

    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    # NONZERO weights: with zero weights every iteration's values are
    # identically zero and the chain is elided (the "values must
    # actually change" rule — scripts/benchlib.py).  The 0.02 scale keeps
    # 17 chained matmul pairs inside bf16 range (~2.2x growth/iter).
    kw = jax.random.split(jax.random.key(RUN_SEED), 3)
    b1 = jax.random.normal(kw[1], (K, N_PER_CHIP), jnp.bfloat16) * 0.02
    b2 = jax.random.normal(kw[2], (N_PER_CHIP, K), jnp.bfloat16) * 0.02
    flops_per_pair = 2 * M * N_PER_CHIP * K * 2  # ag_gemm + return matmul

    chain_cache = {}

    def chains_for(n_long):
        # chains depend only on n_long; reuse across the three seed banks
        # (the closures otherwise miss jax.jit's identity cache and every
        # measure() call would re-trace + re-compile)
        if n_long not in chain_cache:
            chain_cache[n_long] = (
                _make_chain(mesh, 1), _make_chain(mesh, n_long),
                _make_xform_chain(mesh, 1), _make_xform_chain(mesh, n_long))
        return chain_cache[n_long]

    def measure(n_long, seed_off=0):
        # backout_pair: the AG-GEMM chain and the feedback-only chain share
        # one rotated trial loop so drift cancels out of the
        # difference.  ``seed_off`` gives the ceiling-guard retry fresh
        # trial inputs.
        from scripts.benchlib import backout_pair

        c1, cn, x1, xn = chains_for(n_long)
        per_pair, _ = backout_pair(
            {"total": (c1, cn, (b1, b2)), "churn": (x1, xn, (b1, b2))},
            fresh_input=lambda t: jax.random.normal(
                jax.random.key(RUN_SEED + seed_off + t), (M, K),
                jnp.bfloat16),
            n_extra=n_long - 1, trials=14)
        return per_pair

    def to_tflops(per_pair):
        # A non-positive backout means churn out-measured the whole chain:
        # a failed measurement (elision or extreme drift), not a speed.
        return (flops_per_pair / per_pair / 1e12) if per_pair > 0 else None

    # Median of three independent measurements (distinct seed banks):
    # single measure() calls still swung ±10% across minutes
    # (docs/perf.md) even though each is internally rotated/paired.
    import statistics

    samples = [measure(9, seed_off=k * 10_000) for k in range(3)]
    positive = sorted(s for s in samples if s > 0)
    tflops = to_tflops(statistics.median(positive) if positive else -1.0)
    ceiling = measured_dot_ceiling_tflops()
    if tflops is None or tflops > ceiling:
        # Impossible: the chain pays AG dispatch on top of two dense
        # matmuls, so it cannot beat XLA's bare dot at the same shape.
        # Longer chains dilute whatever was elided; if the reading
        # stays impossible, report the bound (ceiling, or 0.0 for a
        # failed backout) with the suspect flag rather than a fiction.
        tflops = to_tflops(measure(17, seed_off=100_000))
        if tflops is None:
            return 0.0, True
        if tflops > ceiling:
            return ceiling, True
    return tflops, False


def _bench_serve_engine():
    """Serving-engine decode throughput at decode horizon H=8 vs H=1
    (scripts/bench_serve.py — the PAIRED-quotient protocol again: both
    configurations drive the identical warmed workload, so host
    drift cancels in the speedup ratio while `serve_toks_per_s` carries
    the absolute H=8 number).  A tiny world-1 model: the field measures
    the ENGINE's dispatch economics (per-token host round trips vs fused
    horizons + async pipelining), not model FLOPS — the kernel-side
    decode cost is already `decode_step_us`.

    Returns (h8_decode_toks_per_s, h8_vs_h1_speedup)."""
    from scripts.bench_serve import bench_engine

    r1 = bench_engine(1, batch=4, prompt_len=16, new_tokens=48, dim=32)
    r8 = bench_engine(8, batch=4, prompt_len=16, new_tokens=48, dim=32)
    speedup = (r8["decode_toks_per_s"] / r1["decode_toks_per_s"]
               if r1["decode_toks_per_s"] > 0 else 0.0)
    return r8["decode_toks_per_s"], speedup


def _bench_serve_spec():
    """Fused speculative rounds vs plain fused decode at H=8
    (scripts/bench_serve.py bench_spec): the tokens-per-dispatch ratio
    on the identical warmed workload, with a SELF-draft (acceptance ~1)
    so the quotient isolates the one-dispatch round's economics from
    draft quality.  >= 1.0 is the ISSUE-7 acceptance bar — a fused
    round commits ~k+1 tokens per row per dispatch vs the horizon's H —
    and, as a paired quotient on one host, it is dispatch-drift-immune
    like ring_vs_dense/decode_vs_xla (docs/perf.md 'Bench
    trajectory')."""
    from scripts.bench_serve import bench_spec

    r = bench_spec(k=12, batch=4, prompt_len=16, new_tokens=48, dim=32)
    return r["spec_vs_plain_tokens_per_dispatch"]


def _bench_serve_trace():
    """Flight-recorder overhead (scripts/bench_serve.py
    bench_trace_overhead): the identical warmed decode workload with
    tracing OFF vs FULL detail, paired tokens/s quotient — dispatch
    drift cancels like the other paired ratios.  The recorder's
    hot-path contract (bounded-ring append only: no sync, no I/O, no
    formatting) is only real if it is measured; the PERF_FLOORS.json
    ``serve_trace_overhead`` floor (0.95) is the acceptance bar."""
    from scripts.bench_serve import bench_trace_overhead

    r = bench_trace_overhead(batch=4, prompt_len=16, new_tokens=48,
                             dim=32)
    return r["serve_trace_overhead"]


def _bench_serve_fleet():
    """Fleet chaos guardrail (scripts/bench_serve.py bench_fleet): N=2
    replicas behind the router, one killed mid-decode — the fraction of
    streams finishing bit-identical to the single-engine oracle with an
    exactly-once delivery record across the kill + migration + restart.
    A correctness guardrail wearing a bench harness (like
    serve_spec_speedup's >= 1.0): the PERF_FLOORS.json
    ``serve_fleet_zero_loss`` floor is 1.0 — anything below it means
    the fleet lost or duplicated tokens.  Returns (zero_loss,
    fleet_toks_per_s)."""
    from scripts.bench_serve import bench_fleet

    r = bench_fleet(n_replicas=2, batch=4, prompt_len=16,
                    new_tokens=32, dim=32)
    return r["serve_fleet_zero_loss"], r["fleet_toks_per_s"]


def _bench_serve_fleet_net():
    """NETWORK fleet chaos guardrail (scripts/bench_serve.py
    bench_fleet_net): replicas reachable only over the serve/net.py
    wire behind RemoteReplica clients, one process killed mid-decode
    plus a client-side partition of the other (healed once the breaker
    opens to SUSPECT) — the fraction of streams bit-identical to the
    single-engine oracle with exactly-once delivery across retries +
    backoff + journal crash migration.  The cross-process twin of
    serve_fleet_zero_loss, same 1.0 floor, same contract: below it the
    network plane lost or duplicated tokens."""
    from scripts.bench_serve import bench_fleet_net

    r = bench_fleet_net(n_replicas=2, batch=4, prompt_len=16,
                        new_tokens=32, dim=32)
    return r["serve_fleet_net_zero_loss"]


def _bench_serve_disagg():
    """Disaggregated-serving chaos guardrail (scripts/bench_serve.py
    bench_disagg): a 1:2 prefill→decode tier where every request
    prefills on the prefill replica, PUSHes its KV pages at prefill
    completion, and decodes in place on a decode replica — the chaos
    leg kills the prefill tier mid-push AND a decode replica post-adopt
    and reports the fraction of streams still bit-identical to the
    single-engine oracle with exactly-once delivery.  The ISSUE-16 twin
    of serve_fleet_zero_loss, same 1.0 floor, same contract: below it
    the push protocol lost or duplicated tokens.  Also returns the
    decode p99 ITL isolation ratio (co-located / disagg under a
    long-prompt burst) — informational on CPU, where the compute/memory
    split the ratio measures has no hardware to show on."""
    from scripts.bench_serve import bench_disagg

    r = bench_disagg(prefill=1, decode=2, batch=2, prompt_len=16,
                     new_tokens=32, dim=32)
    return r["serve_disagg_zero_loss"], r["serve_disagg_itl_isolation"]


def _bench_serve_corrupt():
    """State-integrity chaos guardrail (scripts/bench_serve.py
    bench_corrupt, docs/serving.md 'Durability & integrity'): the
    network fleet under injected CORRUPTION of every artifact class —
    a bitflipped journal line on disk, a bitflipped drain-response KV
    blob (client-side detect → same-key retry), a bitflipped
    migrate_in manifest (server-side counted 400 → placer fallback) —
    with a SIGKILL on the bit-rotted replica so the crash path must
    quarantine + salvage its journal and reconcile against the
    delivery record.  The fraction of streams bit-identical to the
    single-engine oracle with exactly-once delivery; 1.0 floor, same
    contract as the other zero-loss bars: below it, corruption was
    adopted as state or committed tokens were lost."""
    from scripts.bench_serve import bench_corrupt

    r = bench_corrupt(n_replicas=2, batch=4, prompt_len=16,
                      new_tokens=32, dim=32)
    return r["serve_corrupt_recovery_zero_loss"]


def _bench_serve_kv_int8():
    """Quantized-serving capacity + fidelity (scripts/bench_serve.py
    bench_kv_int8, docs/serving.md 'Quantized serving'): the identical
    warmed greedy workload through a float32 and an int8 engine at
    head_dim 64.  serve_kv_int8_capacity is the resident-token capacity
    at EQUAL pool bytes (float bytes/token over int8 bytes/token, read
    from the allocated pools — the model says 4D/(D+4) ~ 3.76x; the
    1.9 floor catches a quantized pool that silently fell back to
    float without false-alarming on layout changes).
    serve_kv_int8_token_match is the mean greedy prefix match vs the
    float oracle — quantization error is real and the floor pins how
    much is acceptable.  Determinism (int8 leg bit-identical to
    itself) is a hard assert inside the harness, not a scored field.
    Returns (capacity, token_match)."""
    from scripts.bench_serve import bench_kv_int8

    r = bench_kv_int8(batch=4, prompt_len=16, new_tokens=32)
    return r["serve_kv_int8_capacity"], r["serve_kv_int8_token_match"]


def _bench_serve_overload():
    """Bursty overload goodput guardrail (scripts/bench_serve.py
    bench_overload, docs/serving.md 'Overload, SLO classes &
    autoscaling'): measure fleet capacity closed-loop on a virtual
    clock, then replay a trace-shaped workload (benchlib.trace_workload
    — bursty arrivals, lognormal lengths, 50/30/20 class mix) at 2x
    that rate through token-bucket ingress + the brownout ladder + the
    autoscaler.  serve_slo_interactive_goodput is the fraction of
    ADMITTED interactive requests finishing bit-exactly (refusals are
    counted SHED terminals; exactly-once terminals are hard-asserted
    inside the harness) — the ISSUE-18 bar, floor 1.0.  Returns
    (goodput, brownout_rung_max, scale_ups)."""
    from scripts.bench_serve import bench_overload

    r = bench_overload()
    return (r["serve_slo_interactive_goodput"],
            r["brownout_rung_max"], r["scale_ups"])


def _bench_serve_fleet_trace():
    """Fleet tracing overhead (scripts/bench_serve.py
    bench_fleet_trace_overhead): the identical warmed fleet workload
    with the WHOLE observability stack off (engine rings, controller
    ring, router decision audit) vs full detail, paired fleet tokens/s
    quotient — the fleet twin of serve_trace_overhead, same hot-path
    contract (ring/audit appends only), same 0.95 floor."""
    from scripts.bench_serve import bench_fleet_trace_overhead

    r = bench_fleet_trace_overhead(n_replicas=2, batch=4,
                                   prompt_len=16, new_tokens=32,
                                   dim=32, repeats=2)
    return r["serve_fleet_trace_overhead"]


def _bench_serve_mesh():
    """Sharded-engine exactness guardrail (scripts/bench_serve.py
    bench_mesh): a 2-device kv_shard='heads' engine on the FORCED
    host-platform mesh serves the identical mixed greedy + seeded-
    sampled workload; serve_mesh_zero_loss is the fraction of streams
    bit-identical to the world-1 oracle (floor 1.0 — a correctness
    bar, not throughput: forced host 'chips' share the bench host's
    cores, so tokens/s is informational).  Runs as a SUBPROCESS: the
    device count is fixed at backend init, and this process may be
    pinned to one real chip."""
    import os
    import subprocess
    import sys as _sys

    from triton_dist_tpu.runtime.testenv import virtual_mesh_env

    here = os.path.dirname(os.path.abspath(__file__))
    out = subprocess.run(
        [_sys.executable, os.path.join(here, "scripts", "bench_serve.py"),
         "--mesh", "2", "--new-tokens", "48"],
        capture_output=True, text=True, timeout=1200, cwd=here,
        env=virtual_mesh_env(n_devices=2))
    assert out.returncode == 0, out.stderr[-2000:]
    r = json.loads([ln for ln in out.stdout.splitlines()
                    if ln.startswith("{")][-1])
    assert r["mesh_fresh_compiles"] == 0, r
    return r["serve_mesh_zero_loss"], r["mesh_toks_per_s"]


def _bench_serve_mesh2d():
    """2D sharded-engine exactness guardrail (ISSUE 19): the same
    paired-oracle leg on a 4-device heads+seq engine — bench_serve
    factors the mesh 2x2 (tp x sp), TP weights + heads shard over tp
    while the paged KV shards by block over sp — and the fraction of
    mixed greedy + seeded-sampled streams bit-identical to the world-1
    oracle must be 1.0 with zero post-warmup compiles (the 2-axis
    ladder is fully enumerable, like the 1D one)."""
    import os
    import subprocess
    import sys as _sys

    from triton_dist_tpu.runtime.testenv import virtual_mesh_env

    here = os.path.dirname(os.path.abspath(__file__))
    out = subprocess.run(
        [_sys.executable, os.path.join(here, "scripts", "bench_serve.py"),
         "--mesh", "4", "--kv-shard", "heads+seq", "--new-tokens", "48"],
        capture_output=True, text=True, timeout=1200, cwd=here,
        env=virtual_mesh_env(n_devices=4))
    assert out.returncode == 0, out.stderr[-2000:]
    r = json.loads([ln for ln in out.stdout.splitlines()
                    if ln.startswith("{")][-1])
    assert r["mesh_fresh_compiles"] == 0, r
    return r["serve_mesh2d_zero_loss"]


def _bench_kernel_report():
    """Kernel overlap scoreboard (scripts/kernel_report.py, ISSUE 14):
    the ag_gemm fused/compute-only/comm-only legs + phase-sliced
    per-ring-step replay on a FORCED 2-device host mesh, reporting
    overlap efficiency ``(T_compute + T_comm) / T_fused`` and the
    perf_model model-vs-measured ratio.  INFORMATIONAL on CPU (the
    fused kernel takes its XLA fallback and the model's rate tables
    describe a TPU) — the artifact records the schedule decomposition
    so a hardware session reads the same fields against real rates.
    Runs as a subprocess like the mesh leg: the device count is fixed
    at backend init.  Returns (overlap_efficiency,
    model_vs_measured)."""
    import os
    import subprocess
    import sys as _sys

    from triton_dist_tpu.runtime.testenv import virtual_mesh_env

    here = os.path.dirname(os.path.abspath(__file__))
    out = subprocess.run(
        [_sys.executable,
         os.path.join(here, "scripts", "kernel_report.py"),
         "--cpu", "2", "--kernel", "ag_gemm", "-M", "512", "-K", "256",
         "--n-loc", "128"],
        capture_output=True, text=True, timeout=900, cwd=here,
        env=virtual_mesh_env(n_devices=2))
    assert out.returncode == 0, out.stderr[-2000:]
    r = json.loads([ln for ln in out.stdout.splitlines()
                    if ln.startswith("{")][-1])
    k = r["kernels"]["ag_gemm"]
    return k["overlap_efficiency"], k["model_vs_measured"]


def _bench_lint() -> dict:
    """dist-lint verdict for the artifact (ISSUE 15, docs/analysis.md):
    run the full static-analysis rule registry — annotation coverage,
    trace-taxonomy closure, unseeded randomness, unique collective
    ids, and the CommSchedule race/deadlock checker over every ring
    kernel at worlds 2-32 — and stamp {rules run, violations, waived,
    stale waivers} so a trajectory audit reads the lint state that
    shipped with each bench round.  A lint crash fails the bench: an
    artifact whose lint verdict is an error string looks like a
    result and is not one."""
    from triton_dist_tpu.analysis import run_rules

    rep = run_rules()
    return {
        "rules_run": len(rep["rules_run"]),
        "violations": len(rep["violations"]),
        "waived": len(rep["waived"]),
        "stale_waivers": len(rep["stale_waivers"]),
        "ok": rep["ok"] and not rep["stale_waivers"],
    }


def _environment_provenance(contended: bool) -> dict:
    """Environment stamp for the bench artifact (ROADMAP #5b
    follow-through, docs/perf.md 'Bench trajectory'): the absolute
    chain numbers are dispatch-sensitive, so every artifact must carry
    the evidence needed to audit a swing — jax version, host load, CPU
    count, and whether the contention sentinel flagged this session.
    Without this, a future 'did ag_gemm regress?' reading has to guess
    what machine state produced the number."""
    import os
    import platform

    try:
        load = [round(x, 2) for x in os.getloadavg()]
    except OSError:
        load = None
    import jax

    dev = jax.devices()[0]
    return {
        # the device every in-process leg ran on, as jax reports it
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "jax_version": jax.__version__,
        "python_version": platform.python_version(),
        "platform": platform.platform(),
        "cpu_count": os.cpu_count(),
        "loadavg_1m_5m_15m": load,
        # the dispatch-sensitivity flag: True means the known-cost
        # sentinel read low this session, so absolute fields are lower
        # bounds, not regressions (paired ratios stay trustworthy)
        "dispatch_sensitive": bool(contended),
    }


def check_floors(out: dict, floors: dict) -> tuple[dict, list]:
    """Per-metric guardrail (PERF_FLOORS.json, ROADMAP #5b): for each
    floor whose metric is present in ``out``, a ``vs_floor`` ratio
    normalized so >= 1.0 always means "at or above the floor" —
    ``value/min`` for higher-is-better metrics, ``max/value`` for
    latency-style ceilings.  Returns (ratios, names below floor).  Pure
    (unit-tested in tests/test_serve_prefix.py); the floors themselves
    are set below the honest session ranges because the absolute chain
    numbers are dispatch-sensitive — docs/perf.md 'Bench trajectory'."""
    ratios, below = {}, []
    for name, spec in floors.items():
        v = out.get(name)
        if v is None:
            continue
        if "min" in spec:
            r = v / spec["min"] if spec["min"] > 0 else 0.0
        else:
            r = spec["max"] / v if v > 0 else 0.0
        ratios[name] = round(r, 3)
        if r < 1.0:
            below.append(name)
    return ratios, below


def _load_floors() -> dict:
    import os

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "PERF_FLOORS.json")
    # committed beside this script: missing or torn is an error, not "no
    # floors" — an artifact with no vs_floor reads as "nothing below"
    with open(path) as f:
        return json.load(f)["floors"]


#: Output fields that come from child processes forced onto the virtual
#: CPU mesh (this process holds the chip): exactness checks and counts,
#: never device measurements.
CPU_MESH_FIELDS = (
    "serve_mesh_zero_loss", "serve_mesh_toks_per_s",
    "serve_mesh2d_zero_loss", "ag_gemm_overlap_efficiency",
    "ag_gemm_model_vs_measured",
)


def main():
    from triton_dist_tpu.runtime import configure_compile_cache, require_tpu

    configure_compile_cache()
    # "TFLOPS" from the CPU backend is not a slower device number
    require_tpu("bench.py")
    sentinel_tflops, contended = _bench_contention_sentinel()
    tflops, ag_suspect = _bench_ag_gemm_tflops()
    moe_a2a_us, a2a_suspect = _bench_moe_a2a_us()
    decode_us, decode_ratio = _bench_decode_us()
    ring_ratio = _bench_ring_vs_dense()
    serve_tps, serve_speedup = _bench_serve_engine()
    spec_speedup = _bench_serve_spec()
    trace_overhead = _bench_serve_trace()
    fleet_zero_loss, fleet_tps = _bench_serve_fleet()
    fleet_net_zero_loss = _bench_serve_fleet_net()
    disagg_zero_loss, disagg_itl_isolation = _bench_serve_disagg()
    corrupt_zero_loss = _bench_serve_corrupt()
    fleet_trace_overhead = _bench_serve_fleet_trace()
    mesh_zero_loss, mesh_tps = _bench_serve_mesh()
    mesh2d_zero_loss = _bench_serve_mesh2d()
    kv_int8_capacity, kv_int8_token_match = _bench_serve_kv_int8()
    slo_goodput, slo_rung_max, slo_scale_ups = _bench_serve_overload()
    overlap_eff, model_vs_meas = _bench_kernel_report()
    lint = _bench_lint()

    peak = peak_bf16_tflops()
    vs = (tflops / peak) / REF_UTILIZATION if peak else 0.0
    out = {
        "metric": "ag_gemm_tflops_per_chip",
        "value": round(tflops, 1),
        "unit": "TFLOPS",
        "vs_baseline": round(vs, 3),
        # BASELINE.json co-headline: MoE AllToAll p50 (single-chip floor at
        # 128 tok/rank, hidden 7168, fp8x4-packed) + the decode step time
        # (B=8 Hq=32 Hkv=8 S=8192 bf16, pallas under auto).
        "moe_a2a_floor_us": round(moe_a2a_us, 2),
        "decode_step_us": round(decode_us, 1),
        # PAIRED-DELTA kernel-quality ratios (r5, VERDICT r4 next#1):
        # drift cancels in each quotient, so these resolve kernel
        # changes that the absolute fields cannot.  ring_vs_dense_ratio:
        # dense pallas GEMM pair-time / world-1 ring AG-GEMM pair-time,
        # target >= 0.97 (ring schedule overhead <= ~3%).
        # decode_vs_xla_ratio: XLA fused decode / pallas split-KV decode
        # at B=8 S=8192, > 1 = the repo's kernel wins.  Variance: each
        # leg's IQR runs 5-15% of its median across sessions (perf.md);
        # the paired quotient's session spread measured ~±0.05.
        "ring_vs_dense_ratio": round(ring_ratio, 3),
        "decode_vs_xla_ratio": round(decode_ratio, 3),
        # Serving-engine decode throughput (tiny world-1 model, warmed):
        # tokens/s at decode horizon H=8 with async pipelining, and the
        # paired H=8 / H=1 speedup — the dispatch-economics field the
        # decode horizon exists to move (scripts/bench_serve.py).
        "serve_toks_per_s": round(serve_tps, 1),
        "serve_horizon_speedup": round(serve_speedup, 2),
        # Fused speculative rounds vs plain fused decode (H=8), paired
        # tokens-per-dispatch quotient with a self-draft — the PR 7
        # one-dispatch spec path's guardrail (>= 1.0 means a spec round
        # commits at least as many tokens per dispatch as the horizon).
        "serve_spec_speedup": round(spec_speedup, 2),
        # Flight-recorder overhead: tokens/s with full tracing over
        # tokens/s with tracing off on the identical workload — the
        # PR 8 hot-path discipline bar (>= 0.95 means the recorder's
        # ring appends cost under 5% of serving throughput).
        "serve_trace_overhead": round(trace_overhead, 3),
        # Fleet chaos zero-loss: exact streams / total after killing one
        # of two replicas mid-decode (live migration + restart).  1.0 or
        # the fleet broke exactly-once — the PR 9 robustness bar.
        "serve_fleet_zero_loss": round(fleet_zero_loss, 4),
        "serve_fleet_toks_per_s": round(fleet_tps, 1),
        # Network-fleet chaos zero-loss: the same bar with replicas
        # reachable ONLY over the wire (kill + partition + retries +
        # journal crash migration) — the ISSUE-12 robustness bar.
        "serve_fleet_net_zero_loss": round(fleet_net_zero_loss, 4),
        # Disaggregated-serving chaos zero-loss: exact streams / total
        # after SIGKILLing the prefill tier mid-push AND a decode
        # replica post-adopt in a 1:2 role tier (per-request KV-page
        # PUSH + in-place adoption) — the ISSUE-16 robustness bar.
        # The isolation ratio (decode p99 ITL, co-located / disagg
        # under a prefill burst) is INFORMATIONAL on CPU.
        "serve_disagg_zero_loss": round(disagg_zero_loss, 4),
        "serve_disagg_itl_isolation": round(disagg_itl_isolation, 4),
        # State-integrity chaos zero-loss: exact streams / total with
        # injected corruption of every artifact class (journal line on
        # disk, drain-response wire blob, migrate_in manifest) plus a
        # SIGKILL forcing journal quarantine + salvage — the ISSUE-20
        # robustness bar: corruption degrades to re-queue + recompute,
        # never adopted rot or lost tokens.
        "serve_corrupt_recovery_zero_loss": round(corrupt_zero_loss, 4),
        # Fleet tracing overhead: fleet tokens/s with the full
        # observability stack (engine rings + controller ring + router
        # decision audit) over tokens/s with it all off — the
        # fleet-wide hot-path bar (>= 0.95, like serve_trace_overhead).
        "serve_fleet_trace_overhead": round(fleet_trace_overhead, 3),
        # Sharded-engine exactness: fraction of mixed greedy + seeded-
        # sampled streams a 2-device mesh engine (TP weights +
        # head-sharded paged KV under shard_map) serves bit-identical
        # to the world-1 oracle on the forced host-platform mesh —
        # the ISSUE-13 correctness bar (tokens/s informational: forced
        # host "chips" share this host's cores).
        "serve_mesh_zero_loss": round(mesh_zero_loss, 4),
        "serve_mesh_toks_per_s": round(mesh_tps, 1),
        # 2D sharded-engine exactness (ISSUE 19): the same bar on a
        # 4-device heads+seq engine — a 2x2 (tp x sp) mesh with TP
        # weights + heads over tp and block-sharded paged KV over sp —
        # with zero post-warmup compiles (the 2-axis bucket ladder is
        # enumerable exactly like the 1D one).
        "serve_mesh2d_zero_loss": round(mesh2d_zero_loss, 4),
        # Quantized serving (ISSUE 17): resident-token capacity at
        # equal pool bytes — float bytes/token over int8 bytes/token on
        # the engines' allocated pools at head_dim 64 (~3.76x; floor
        # 1.9 guards against a silent float fallback) — and the mean
        # greedy prefix match vs the float oracle (the acceptance
        # metric for quantization error; determinism is a hard assert
        # inside the harness).
        "serve_kv_int8_capacity": round(kv_int8_capacity, 3),
        "serve_kv_int8_token_match": round(kv_int8_token_match, 4),
        # Overload robustness (ISSUE 18): fraction of ADMITTED
        # interactive requests finishing bit-exactly under a bursty
        # trace-shaped workload at 2x measured capacity through
        # ingress + brownout + autoscaling (floor 1.0 — below it the
        # fleet lost an interactive request it accepted).  The peak
        # brownout rung and autoscaler spawns are the evidence the
        # leg actually stressed the ladder, not scored fields.
        "serve_slo_interactive_goodput": round(slo_goodput, 4),
        "serve_slo_brownout_rung_max": slo_rung_max,
        "serve_slo_scale_ups": slo_scale_ups,
        # Kernel overlap scoreboard (scripts/kernel_report.py): the
        # ag_gemm (T_compute + T_comm) / T_fused ratio and the
        # perf_model predicted-fused / measured-fused ratio from the
        # phase-sliced replay.  INFORMATIONAL on CPU (XLA fallback +
        # TPU rate tables — no floor); a hardware session reads them
        # as the overlap-quality and speed-of-light-distance fields.
        "ag_gemm_overlap_efficiency": round(overlap_eff, 4),
        "ag_gemm_model_vs_measured": round(model_vs_meas, 4),
        # Known-cost reference op (bare XLA dot, measured ceiling 189.7):
        # a depressed sentinel means the HOST was contended during this
        # session and `value` is a lower bound, not a regression.
        "sentinel_dot_tflops": round(sentinel_tflops, 1),
        # dist-lint verdict (scripts/lint_dist.py, docs/analysis.md):
        # rule registry size + violation/waiver counts at bench time —
        # the trajectory-audit field that says whether THIS round's
        # numbers came from a tree with unexplained static-analysis
        # violations.
        "lint": lint,
    }
    # Guardrail floors (PERF_FLOORS.json, ROADMAP #5b): vs_floor >= 1.0
    # per metric means at-or-above its floor; below_floor lists the
    # violations.  Read together with suspect_contention — a depressed
    # sentinel says the HOST was busy, and an ag_gemm floor miss in the
    # same session is environment, not regression (the paired ratios
    # are the kernel-regression fields either way).
    out["cpu_mesh_fields"] = list(CPU_MESH_FIELDS)
    vs_floor, below = check_floors(out, _load_floors())
    if vs_floor:
        out["vs_floor"] = vs_floor
    if below:
        out["below_floor"] = below
    # Environment provenance (ROADMAP #5b): the audit trail that lets a
    # future session read this artifact's absolute numbers against the
    # host state that produced them (docs/perf.md 'Bench trajectory').
    out["env"] = _environment_provenance(contended)
    if contended:
        out["suspect_contention"] = True
    if ag_suspect or a2a_suspect:
        # Self-consistency guard tripped even after the retry: the value
        # is reported at its physical bound, not as measured.
        out["suspect_elision"] = (
            (["ag_gemm"] if ag_suspect else []) +
            (["moe_a2a"] if a2a_suspect else []))
    print(json.dumps(out))
    print(f"# chip peak {peak} TFLOPS, utilization "
          f"{tflops / peak:.1%}, shape M={M} K={K} N/chip={N_PER_CHIP}; "
          f"moe_a2a floor {moe_a2a_us:.2f} us; decode {decode_us:.1f} us; "
          f"ring/dense {ring_ratio:.3f}; decode/xla {decode_ratio:.3f}; "
          f"serve {serve_tps:.0f} tok/s (H8/H1 {serve_speedup:.2f}x, "
          f"spec/plain {spec_speedup:.2f}x t/dispatch, "
          f"trace {trace_overhead:.3f}x, "
          f"fleet zero-loss {fleet_zero_loss:.3f}, "
          f"fleet trace {fleet_trace_overhead:.3f}x, "
          f"kv int8 {kv_int8_capacity:.2f}x capacity / "
          f"{kv_int8_token_match:.3f} match, "
          f"slo goodput {slo_goodput:.3f} "
          f"at rung {slo_rung_max} +{slo_scale_ups} replicas); "
          f"ag overlap eff {overlap_eff:.3f} "
          f"(model/meas {model_vs_meas:.3f}); "
          f"sentinel dot {sentinel_tflops:.1f} TFLOPS"
          + (" (CONTENDED)" if contended else ""),
          file=sys.stderr)
    if below:
        print(f"# BELOW FLOOR: {below} (PERF_FLOORS.json; see "
              f"docs/perf.md 'Bench trajectory' before reading this as "
              f"a kernel regression"
              + (" — sentinel says this session was contended)"
                 if contended else ")"),
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
