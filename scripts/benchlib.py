"""Shared pieces of the kernel micro-benchmark protocol.

One home for the rules the kernel bench scripts follow (docs/perf.md
"Grouped GEMM MFU" has the postmortem that produced them):

- RUN_SEED: per-process time-based seed for trial inputs, so no trial
  re-runs an (executable, args) pair an earlier trial or process ran.
- rotated_paired_bench: per-trial fresh inputs, config order rotated per
  trial (position-in-trial effects average out), paired long/short chain
  diffs (the per-call dispatch constant cancels), pooled median with a
  positive floor (a noisy trial can go negative), IQR reported for
  stability.
- Chains must have VALUE dependence between iterations (feed real outputs
  forward).  Zero-add "dependence" tricks and all-zero weights produce
  >100%-of-peak readings: work whose values do not change gets elided.
- Completion barrier is a float()/device-get materialization of the
  chain's scalar result.
"""

import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

RUN_SEED = time.time_ns() % (1 << 31)

# Default SLO-class mix for trace_workload: the interactive-heavy blend
# the overload bench and tests drive (docs/serving.md "Overload, SLO
# classes & autoscaling").
TRACE_CLASS_MIX = (("interactive", 0.5), ("batch", 0.3),
                   ("best_effort", 0.2))


def trace_workload(seed, n, *, mean_interarrival_s=0.05,
                   burst_factor=8.0, mean_burst=8, mean_lull=4,
                   prompt_median=24, prompt_sigma=0.6,
                   output_median=24, output_sigma=0.8,
                   prompt_min=1, prompt_max=None,
                   output_min=1, output_max=None,
                   class_mix=TRACE_CLASS_MIX):
    """Trace-shaped open-loop workload: ``n`` arrival records with bursty
    Poisson timing, heavy-tailed lognormal prompt/output lengths and a
    per-SLO-class mix — fully determined by ``seed`` (ROADMAP #5b's
    "trace-shaped" bench half; docs/serving.md "Overload, SLO classes &
    autoscaling").

    Timing is a two-state modulated Poisson process: episodes alternate
    between BURST (exponential interarrivals at ``mean_interarrival_s /
    burst_factor``) and LULL (at ``mean_interarrival_s``), with
    geometric episode lengths of ``mean_burst`` / ``mean_lull`` requests
    — the on/off shape real serving traces show, not a flat rate.
    Absolute rate rarely matters to callers (the overload bench rescales
    arrival times to pin offered/capacity); the burst SHAPE is the
    point.

    Lengths are lognormal around the medians (sigma in log-space), so
    the tail is heavy but the median is the knob you set.  Clipped to
    ``[min, max]`` when bounds are given.

    Returns a list of dicts sorted by arrival time::

        {"rid": "w0003", "t": 0.173, "prompt_len": 31,
         "max_new": 12, "slo": "interactive"}

    Same seed + same kwargs => identical list (np.random.default_rng;
    no wall-clock reads), so bench legs and tests replay it exactly.
    """
    if n < 1:
        raise ValueError(f"need n >= 1, got {n}")
    if mean_interarrival_s <= 0 or burst_factor < 1:
        raise ValueError(
            f"need mean_interarrival_s > 0 and burst_factor >= 1, got "
            f"{mean_interarrival_s}, {burst_factor}")
    classes = [c for c, _ in class_mix]
    weights = np.array([w for _, w in class_mix], dtype=np.float64)
    if (weights <= 0).any():
        raise ValueError(f"class weights must be > 0: {class_mix}")
    weights = weights / weights.sum()
    rng = np.random.default_rng(seed)

    # alternating burst/lull episodes (geometric lengths, >= 1 request)
    gaps = np.empty(n)
    i, in_burst = 0, bool(rng.integers(0, 2))
    while i < n:
        mean_len = mean_burst if in_burst else mean_lull
        ep = int(rng.geometric(1.0 / max(mean_len, 1)))
        ep = min(max(ep, 1), n - i)
        scale = (mean_interarrival_s / burst_factor if in_burst
                 else mean_interarrival_s)
        gaps[i:i + ep] = rng.exponential(scale, size=ep)
        i += ep
        in_burst = not in_burst
    times = np.cumsum(gaps)

    def _lengths(median, sigma, lo, hi):
        raw = median * np.exp(sigma * rng.standard_normal(n))
        out = np.maximum(np.rint(raw).astype(np.int64), lo)
        return np.minimum(out, hi) if hi is not None else out

    prompts = _lengths(prompt_median, prompt_sigma, prompt_min,
                       prompt_max)
    outputs = _lengths(output_median, output_sigma, output_min,
                       output_max)
    slos = rng.choice(len(classes), size=n, p=weights)
    return [{"rid": f"w{i:04d}", "t": float(times[i]),
             "prompt_len": int(prompts[i]), "max_new": int(outputs[i]),
             "slo": classes[int(slos[i])]} for i in range(n)]


_CHURN_UINT = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}
_CHURN_MANTISSA = {1: 0x07, 2: 0x007F, 4: 0x007FFFFF}  # fp8e4m3/bf16/f32


def churn(x, i, mantissa_only=False):
    """XOR a well-mixed function of the loop index into the payload's raw
    bits (a SAME-WIDTH unsigned bitcast view for float dtypes — a wider
    grouped view needs a lane relayout on TPU that costs ~10x the copy).

    The value-change rule made cheap: one elementwise pass that changes
    every element every iteration with no arithmetic hazards (bit garbage
    is fine for DMA-only chains).  The index is multiplied by the odd
    Fibonacci-hash constant before the XOR — XOR-ing the bare index
    self-cancels (x^0^1^2^3 = x: the payload returns to its exact
    starting bits every 4 iterations), while the mixed sequence's running
    XOR never short-cycles.  The key is forced odd, so the low bit always flips.

    ``mantissa_only`` restricts the flips to the dtype's mantissa bits,
    for chains whose values feed real arithmetic and must stay finite
    (sign/exponent intact — no inf/NaN, bounded relative perturbation).
    Churn's bandwidth cost is real: measure a churn-only chain alongside
    and subtract (:func:`backout_pair`)."""
    key = (i * jnp.int32(-1640531527)) | 1  # 0x9E3779B9, forced odd
    if mantissa_only:
        key = (key & _CHURN_MANTISSA[x.dtype.itemsize]) | 1
    if jnp.issubdtype(x.dtype, jnp.integer):
        return x ^ key.astype(x.dtype)
    u = _CHURN_UINT[x.dtype.itemsize]
    bits = jax.lax.bitcast_convert_type(x, u) ^ key.astype(u)
    return jax.lax.bitcast_convert_type(bits, x.dtype)


def churn_barrier(x, i, extra_key=0):
    """Mantissa churn through an int32-GROUPED bitcast view: pairs of bf16
    lanes pack into 32-bit lanes, which forces a full lane relayout on TPU
    — deliberately expensive (~10x a copy pass), because the relayout is
    the strongest compute-serializing barrier found so far.

    Chains of MXU work need it: TPU pipelines consecutive kernels'
    tiles enough that a bare matmul chain reads 200-220 "TFLOPS" (above
    the 197 peak — physically impossible) and a same-width churn chain
    still trips the XLA-dot ceiling guard; with this barrier between
    iterations the AG-GEMM chain reads 143-153 TFLOPS (median-of-three
    seed banks, ±3% across processes), the only protocol variant that is
    both stable and below the measured ceiling (docs/perf.md protocol
    history).  Only the
    mantissa bits of each half flip (mask 0x007F007F) so values stay
    finite for downstream matmuls.  Its large bandwidth cost makes the
    backout twin chain (:func:`backout_pair`) mandatory.

    ``extra_key`` folds a data-dependent scalar (e.g. a sampled-tile
    probe sum) into the key for full-tensor serialization."""
    key = ((i ^ extra_key) * jnp.int32(-1640531527)) & 0x007F007F | 1
    assert x.dtype.itemsize == 2, "barrier churn packs 2-byte lanes"
    v = x.reshape(*x.shape[:-1], x.shape[-1] // 2, 2)
    bits = jax.lax.bitcast_convert_type(v, jnp.int32) ^ key
    return jax.lax.bitcast_convert_type(bits, x.dtype).reshape(x.shape)


def backout_pair(chains, fresh_input, n_extra, trials=9):
    """Measure a work chain against its churn-only twin in ONE rotated
    trial loop and return ``(total - churn, churn)`` median seconds/step.

    chains: {"total": (short, long, extra), "churn": (short, long, extra)}.
    Interleaving is required: readings drift across minutes, and
    separately-looped churn/total measurements produce negative floors
    after subtraction.  Warms every chain with ``fresh_input(-1)`` — an
    input no trial reuses."""
    x_warm = fresh_input(-1)
    jax.block_until_ready(x_warm)
    for short, long, extra in chains.values():
        float(short(x_warm, *extra))
        float(long(x_warm, *extra))
    res = rotated_paired_bench(chains, fresh_input, n_extra=n_extra,
                               trials=trials)
    return res["total"][0] - res["churn"][0], res["churn"][0]


def rotated_paired_bench(chains, fresh_input, n_extra, trials=9):
    """chains: {label: (short_fn, long_fn, extra_args tuple)} — called as
    fn(x, *extra_args) where x = fresh_input(trial).  Returns
    {label: (median seconds/step, iqr seconds/step)}."""
    labels = list(chains)
    diffs = {label: [] for label in labels}
    for t in range(trials):
        x = fresh_input(t)
        jax.block_until_ready(x)
        for label in labels[t % len(labels):] + labels[:t % len(labels)]:
            short, long, extra = chains[label]
            t0 = time.perf_counter()
            float(short(x, *extra))
            t1 = time.perf_counter()
            float(long(x, *extra))
            t2 = time.perf_counter()
            diffs[label].append(((t2 - t1) - (t1 - t0)) / n_extra)
    out = {}
    for label, d in diffs.items():
        d = sorted(d)
        med = max(statistics.median(d), 1e-12)
        iqr = d[(3 * len(d)) // 4] - d[len(d) // 4]
        out[label] = (med, iqr)
    return out
