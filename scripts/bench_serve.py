"""Engine-level serving throughput: decode tokens/s and dispatches/token
at decode horizons H in {1, 8} (or ``--horizons``).

The decode horizon (docs/serving.md) removes the per-token dispatch +
sync + host-sample tax from the serving engine's decode loop; this
benchmark measures exactly that tax.  Each configuration drives the SAME
steady decode-only workload — ``--batch`` greedy requests submitted up
front, all slots busy, no admission churn — through a warmed engine, so
the wall-clock difference between H=1 and H=8 is dispatch economics, not
compilation or scheduling noise.  ``dispatches/token`` comes from the
``ServeMetrics.summary()["decode"]`` counters: ~1/batch at H=1 (one
dispatch per step, a token per active row) and ~1/(batch·H) fused — the
batch amortizes rows either way; the horizon's contribution is the
/H.

Emitted streams are bit-identical across horizons (the engine's oracle
tests pin this), so the configurations are directly comparable.

Runs anywhere (TPU or CPU):

    env JAX_PLATFORMS=cpu \
      python scripts/bench_serve.py --batch 4 --new-tokens 64

Prints one JSON line per horizon plus a summary; ``bench.py`` embeds the
H=8 decode tokens/s as ``serve_toks_per_s`` in the driver artifact.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh


def bench_engine(horizon: int, *, batch: int = 4, prompt_len: int = 16,
                 new_tokens: int = 64, pipeline: int = 2, dim: int = 64,
                 n_layers: int = 2, vocab: int = 256, page_size: int = 16,
                 seed: int = 0, warmup: bool = True,
                 trace_level: int = 1) -> dict:
    """One configuration: a warmed engine drains a steady decode-only
    batch; returns wall time, decode tokens/s, and the dispatch counters.
    A fresh engine per call — the trace caches must not leak between
    horizon configurations."""
    from triton_dist_tpu.models import llama
    from triton_dist_tpu.models.generate import Generator
    from triton_dist_tpu.serve import Request, SamplingParams, ServeEngine

    max_seq = prompt_len + new_tokens
    max_seq += (-max_seq) % page_size
    cfg = llama.LlamaConfig(vocab=vocab, dim=dim, n_layers=n_layers,
                            n_heads=2, n_kv_heads=2, ffn_dim=2 * dim,
                            max_seq=max_seq, dtype=jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    params = llama.init_params(cfg, jax.random.key(seed))
    gen = Generator(cfg, mesh, axis="sp", max_seq=max_seq)
    per_req = -(-max_seq // page_size)
    eng = ServeEngine(gen, params, num_blocks=1 + per_req * batch,
                      page_size=page_size, max_batch=batch,
                      prefill_chunk=max(8, page_size), horizon=horizon,
                      pipeline=pipeline, trace_level=trace_level)
    if warmup:
        eng.warmup()
    rng = np.random.default_rng(seed)
    for i in range(batch):
        eng.submit(Request(
            f"b{i}", rng.integers(0, vocab, size=prompt_len)
            .astype(np.int32), SamplingParams(max_new_tokens=new_tokens)))
    t0 = time.perf_counter()
    outs = eng.run()
    dt = time.perf_counter() - t0
    assert all(len(o.token_ids) == new_tokens for o in outs.values())
    # Snapshot latency on the drained engine (pool size dominates the
    # Orbax write, and the pool is identical drained or mid-flight) —
    # the serving-side cost of each incremental crash-recovery capture.
    import shutil
    import tempfile
    snap_dir = tempfile.mkdtemp(prefix="bench_snap_")
    try:
        snapshot_ms = eng.snapshot(snap_dir)["ms"]
    finally:
        shutil.rmtree(snap_dir, ignore_errors=True)
    d = eng.metrics.summary()["decode"]
    return {
        "horizon": horizon,
        "pipeline": pipeline if horizon > 1 else 1,
        "batch": batch,
        "new_tokens": new_tokens,
        "wall_s": round(dt, 4),
        "decode_tokens": d["decode_tokens"],
        "decode_toks_per_s": round(d["decode_tokens"] / dt, 1),
        "dispatches": d["dispatches"],
        "host_syncs": d["host_syncs"],
        "tokens_per_dispatch": round(d["tokens_per_dispatch"], 3),
        "dispatches_per_token": round(d["dispatches_per_token"], 4),
        "snapshot_ms": round(snapshot_ms, 2),
    }


def bench_kv_int8(*, batch: int = 4, prompt_len: int = 16,
                  new_tokens: int = 32, dim: int = 128,
                  n_layers: int = 2, vocab: int = 256,
                  page_size: int = 16, seed: int = 0,
                  warmup: bool = True) -> dict:
    """Quantized-serving capacity + fidelity (docs/serving.md
    'Quantized serving'): the SAME warmed greedy workload through a
    float32 engine and an int8 engine of identical geometry.

    Two headline fields:

    - ``serve_kv_int8_capacity``: resident-token capacity at EQUAL pool
      bytes — float bytes/token over int8 bytes/token, read from the
      engines' own ``kv_stats()`` (the pool arrays as allocated, not a
      formula).  With per-(block, head, slot) f32 scales the model is
      4D/(D+4): ~3.76x at head_dim 64.  The PERF_FLOORS.json floor is
      1.9 — well below the model so page-size/layout changes don't
      false-alarm, well above 1 so the field still catches a quantized
      pool that silently fell back to float.
    - ``serve_kv_int8_token_match``: mean per-stream greedy prefix
      match vs the float oracle (first divergence ends the credit —
      positions after it match only by accident).  Quantization error
      is real; the floor pins how much is acceptable, not zero.

    The int8 leg runs TWICE and must be bit-identical to itself:
    determinism is a hard assert here, not a scored metric."""
    from triton_dist_tpu.models import llama
    from triton_dist_tpu.models.generate import Generator
    from triton_dist_tpu.serve import Request, SamplingParams, ServeEngine

    max_seq = prompt_len + new_tokens
    max_seq += (-max_seq) % page_size
    # head_dim 64 (dim 128 / 2 heads): the capacity model only clears
    # the floor when D dwarfs the 4-byte scale tax — at D=8 the ratio
    # is 2.67 and a layout tweak could graze the floor.
    cfg = llama.LlamaConfig(vocab=vocab, dim=dim, n_layers=n_layers,
                            n_heads=2, n_kv_heads=2, ffn_dim=2 * dim,
                            max_seq=max_seq, dtype=jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    params = llama.init_params(cfg, jax.random.key(seed))
    per_req = -(-max_seq // page_size)
    num_blocks = 1 + per_req * batch
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, vocab, size=prompt_len).astype(np.int32)
               for _ in range(batch)]

    def drive(kv_dtype):
        gen = Generator(cfg, mesh, axis="sp", max_seq=max_seq,
                        kv_dtype=kv_dtype)
        eng = ServeEngine(gen, params, num_blocks=num_blocks,
                          page_size=page_size, max_batch=batch,
                          prefill_chunk=max(8, page_size),
                          trace_level=0)
        if warmup:
            eng.warmup()
        for i, tok in enumerate(prompts):
            eng.submit(Request(f"q{i}", tok, SamplingParams(
                max_new_tokens=new_tokens)))
        t0 = time.perf_counter()
        outs = eng.run()
        dt = time.perf_counter() - t0
        streams = {rid: list(o.token_ids) for rid, o in outs.items()}
        return streams, eng.metrics.kv_stats(), dt

    fp_streams, fp_kv, fp_dt = drive(None)
    q_streams, q_kv, q_dt = drive(jnp.int8)
    q2_streams, _, _ = drive(jnp.int8)
    assert q_streams == q2_streams, (
        "int8 engine is not bit-reproducible across runs")
    assert q_kv["quantized"] and not fp_kv["quantized"]

    def prefix_match(a, b):
        n = 0
        for x, y in zip(a, b):
            if x != y:
                break
            n += 1
        return n / max(len(a), len(b), 1)

    matches = [prefix_match(fp_streams[r], q_streams[r])
               for r in sorted(fp_streams)]
    capacity = fp_kv["bytes_per_token"] / q_kv["bytes_per_token"]
    total = sum(len(s) for s in fp_streams.values())
    return {
        "batch": batch,
        "new_tokens": new_tokens,
        "head_dim": cfg.head_dim,
        "fp_bytes_per_token": round(fp_kv["bytes_per_token"], 2),
        "int8_bytes_per_token": round(q_kv["bytes_per_token"], 2),
        "fp_pool_bytes": fp_kv["pool_bytes"],
        "int8_pool_bytes": q_kv["pool_bytes"],
        "serve_kv_int8_capacity": round(capacity, 3),
        "serve_kv_int8_token_match": round(
            sum(matches) / max(len(matches), 1), 4),
        "token_match_per_stream": [round(m, 3) for m in matches],
        "fp_toks_per_s": round(total / fp_dt, 1),
        "int8_toks_per_s": round(total / q_dt, 1),
    }


def bench_mesh(*, n_devices: int = 2, kv_shard: str = "heads",
               batch: int = 4, prompt_len: int = 16,
               new_tokens: int = 48, n_layers: int = 2, vocab: int = 256,
               page_size: int = 8, horizon: int = 8, pipeline: int = 2,
               seed: int = 0, warmup: bool = True) -> dict:
    """Sharded-engine serving: a PAIRED world-N vs world-1 run of the
    identical mixed greedy + seeded-sampled workload (docs/serving.md
    "Sharded serving").

    The guardrail is ``serve_mesh_zero_loss`` — the fraction of streams
    the mesh engine serves BIT-IDENTICAL to the world-1 oracle (1.0 or
    the sharded forwards broke the correctness contract).  Decode
    tokens/s for both legs is reported informationally only: on the
    forced host-platform mesh every "chip" shares the same CPU cores,
    so the mesh leg pays real shard_map orchestration against fake
    parallel hardware.  ``mesh_fresh_compiles`` must be 0 — the
    executable-cache fork warmup cannot enumerate is exactly the PR-7
    failure mode this path closes."""
    from triton_dist_tpu.models import llama
    from triton_dist_tpu.models.generate import Generator
    from triton_dist_tpu.serve import Request, SamplingParams, ServeEngine

    if jax.device_count() < n_devices:
        raise SystemExit(
            f"bench_mesh: --mesh {n_devices} needs {n_devices} devices, "
            f"runtime exposes {jax.device_count()} — re-run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n_devices}")
    n_heads = max(2, n_devices)
    max_seq = prompt_len + new_tokens
    max_seq += (-max_seq) % (page_size * n_devices)
    cfg = llama.LlamaConfig(vocab=vocab, dim=16 * n_heads,
                            n_layers=n_layers, n_heads=n_heads,
                            n_kv_heads=n_heads,
                            ffn_dim=-(-32 * n_heads // n_devices)
                            * n_devices,
                            max_seq=max_seq, dtype=jnp.float32)
    mesh1 = Mesh(np.array(jax.devices()[:1]), ("sp",))
    params = llama.init_params(cfg, jax.random.key(seed))
    gen = Generator(cfg, mesh1, axis="sp", max_seq=max_seq)
    if kv_shard == "heads+seq":
        # Factor N = tp x sp with sp = the smallest prime factor
        # (4 -> 2x2, 8 -> 4x2); n_heads/ffn/blocks above are rounded
        # to N, which both factors divide, so the geometry stays legal.
        sp_w = next((p for p in range(2, n_devices + 1)
                     if n_devices % p == 0), 1)
        engine_mesh = Mesh(np.array(jax.devices()[:n_devices])
                           .reshape(n_devices // sp_w, sp_w),
                           ("tp", "sp"))
    else:
        engine_mesh = Mesh(np.array(jax.devices()[:n_devices]), ("tp",))
    per_req = -(-max_seq // page_size)
    num_blocks = -(-(1 + per_req * batch + n_devices)
                   // n_devices) * n_devices

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, vocab, size=prompt_len).astype(np.int32)
               for _ in range(batch)]

    def requests():
        out = []
        for i, p in enumerate(prompts):
            sp = (SamplingParams(max_new_tokens=new_tokens)
                  if i % 2 == 0 else
                  SamplingParams(max_new_tokens=new_tokens,
                                 temperature=0.8, top_k=32,
                                 seed=seed + 17 * i))
            out.append(Request(f"m{i}", p, sp))
        return out

    def leg(mesh):
        eng = ServeEngine(gen, params, num_blocks=num_blocks,
                          page_size=page_size, max_batch=batch,
                          prefill_chunk=max(8, page_size),
                          horizon=horizon, pipeline=pipeline,
                          mesh=mesh, kv_shard=kv_shard)
        if warmup:
            eng.warmup()
        flat = eng.metrics.compile_misses
        for r in requests():
            eng.submit(r)
        t0 = time.perf_counter()
        outs = eng.run()
        dt = time.perf_counter() - t0
        d = eng.metrics.summary()["decode"]
        return ({k: v.token_ids for k, v in outs.items()},
                d["decode_tokens"] / dt,
                eng.metrics.compile_misses - flat)

    oracle, w1_tps, _ = leg(None)
    got, mesh_tps, fresh = leg(engine_mesh)
    exact = sum(1 for rid in oracle if got.get(rid) == oracle[rid])
    # the 2D layout reports under its own guardrail name so the two
    # PERF_FLOORS entries (serve_mesh_zero_loss / serve_mesh2d_zero_loss)
    # can never shadow each other in a merged artifact
    loss_key = ("serve_mesh2d_zero_loss" if kv_shard == "heads+seq"
                else "serve_mesh_zero_loss")
    return {
        "mode": "mesh",
        "devices": n_devices,
        "kv_shard": kv_shard,
        "batch": batch,
        "horizon": horizon,
        "new_tokens": new_tokens,
        loss_key: round(exact / len(oracle), 4),
        "world1_toks_per_s": round(w1_tps, 1),
        "mesh_toks_per_s": round(mesh_tps, 1),
        "mesh_vs_world1": round(mesh_tps / w1_tps, 3) if w1_tps else 0.0,
        "mesh_fresh_compiles": fresh,
    }


def bench_spec(*, k: int = 12, batch: int = 4, prompt_len: int = 16,
               new_tokens: int = 64, pipeline: int = 2, dim: int = 64,
               n_layers: int = 2, vocab: int = 256, page_size: int = 16,
               seed: int = 0, warmup: bool = True,
               horizon: int = 8) -> dict:
    """Fused speculative rounds vs plain fused decode (docs/serving.md
    "Speculative decoding"): the SAME steady decode-only workload runs
    through a spec engine (one dispatch per whole round) and through
    ``bench_engine`` at ``horizon`` (the plain fused-decode champion),
    and the headline is the tokens-per-dispatch ratio — the ISSUE-7
    guardrail (spec >= plain at H=8, carried by ``bench.py`` as
    ``serve_spec_speedup`` with a ``PERF_FLOORS.json`` floor).

    The draft SHARES the target's weights (a self-draft): acceptance is
    ~1, so the field isolates the fused round's dispatch economics —
    what the one-dispatch path exists to buy — from draft quality,
    which this tiny random-weights model could not represent anyway.
    With acceptance ~1 a round commits ~k+1 tokens per row per
    dispatch vs the horizon's H."""
    from triton_dist_tpu.models import llama
    from triton_dist_tpu.models.generate import Generator
    from triton_dist_tpu.serve import Request, SamplingParams, ServeEngine

    max_seq = prompt_len + new_tokens
    max_seq += (-max_seq) % page_size
    cfg = llama.LlamaConfig(vocab=vocab, dim=dim, n_layers=n_layers,
                            n_heads=2, n_kv_heads=2, ffn_dim=2 * dim,
                            max_seq=max_seq, dtype=jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    params = llama.init_params(cfg, jax.random.key(seed))
    gen = Generator(cfg, mesh, axis="sp", max_seq=max_seq)
    draft = Generator(cfg, mesh, axis="sp", max_seq=max_seq)
    per_req = -(-max_seq // page_size)
    eng = ServeEngine(gen, params, num_blocks=1 + per_req * batch,
                      page_size=page_size, max_batch=batch,
                      prefill_chunk=max(8, page_size), draft=draft,
                      draft_params=params, spec_k=k, pipeline=pipeline)
    if warmup:
        eng.warmup()
    rng = np.random.default_rng(seed)
    for i in range(batch):
        eng.submit(Request(
            f"s{i}", rng.integers(0, vocab, size=prompt_len)
            .astype(np.int32), SamplingParams(max_new_tokens=new_tokens)))
    t0 = time.perf_counter()
    outs = eng.run()
    dt = time.perf_counter() - t0
    assert all(len(o.token_ids) == new_tokens for o in outs.values())
    s = eng.metrics.summary()
    d, sp = s["decode"], s["spec"]
    plain = bench_engine(horizon, batch=batch, prompt_len=prompt_len,
                         new_tokens=new_tokens, pipeline=pipeline,
                         dim=dim, n_layers=n_layers, vocab=vocab,
                         page_size=page_size, seed=seed, warmup=warmup)
    ratio = (d["tokens_per_dispatch"] / plain["tokens_per_dispatch"]
             if plain["tokens_per_dispatch"] > 0 else 0.0)
    return {
        "mode": "spec",
        "spec_k": k,
        "pipeline": pipeline,
        "batch": batch,
        "new_tokens": new_tokens,
        "wall_s": round(dt, 4),
        "spec_toks_per_s": round(d["decode_tokens"] / dt, 1),
        "plain_toks_per_s": plain["decode_toks_per_s"],
        "accept_rate": round(sp["accept_rate"], 3),
        "chosen_k": sp["chosen_k"],
        "spec_tokens_per_dispatch": round(d["tokens_per_dispatch"], 3),
        "plain_tokens_per_dispatch": plain["tokens_per_dispatch"],
        "dispatches_per_token": round(d["dispatches_per_token"], 4),
        "spec_vs_plain_tokens_per_dispatch": round(ratio, 3),
    }


def bench_trace_overhead(*, batch: int = 4, prompt_len: int = 16,
                         new_tokens: int = 64, pipeline: int = 2,
                         dim: int = 64, n_layers: int = 2,
                         vocab: int = 256, page_size: int = 16,
                         seed: int = 0, warmup: bool = True,
                         horizon: int = 8, repeats: int = 3) -> dict:
    """Flight-recorder overhead (docs/observability.md): the SAME
    steady decode-only workload runs with tracing OFF (trace_level=0 —
    ``emit`` returns before touching the ring) and at FULL detail
    (trace_level=2, per-chunk events included), and the headline is the
    paired tokens/s quotient — tracing on over tracing off.  The
    hot-path contract (append to a bounded ring, no sync/IO/formatting)
    says this must stay ~1.0; ``bench.py`` carries it as
    ``serve_trace_overhead`` with a ``PERF_FLOORS.json`` floor of 0.95.
    The full leg also pays the ISSUE-14 per-program wall-time timers
    (``serve_program_ms`` — one perf_counter pair + histogram observe
    per device dispatch, armed by the same trace_level knob), so the
    floor covers the whole observability hot path, not just the ring.
    Each leg takes the best of ``repeats`` runs so a host scheduling
    blip can't read as recorder overhead."""
    def best(level):
        tps = 0.0
        last = None
        for i in range(max(repeats, 1)):
            last = bench_engine(horizon, batch=batch,
                                prompt_len=prompt_len,
                                new_tokens=new_tokens,
                                pipeline=pipeline, dim=dim,
                                n_layers=n_layers, vocab=vocab,
                                page_size=page_size, seed=seed + i,
                                warmup=warmup, trace_level=level)
            tps = max(tps, last["decode_toks_per_s"])
        return tps, last

    off_tps, _ = best(0)
    on_tps, on = best(2)
    return {
        "mode": "trace",
        "horizon": horizon,
        "batch": batch,
        "new_tokens": new_tokens,
        "toks_per_s_trace_off": off_tps,
        "toks_per_s_trace_on": on_tps,
        "serve_trace_overhead": round(
            on_tps / off_tps if off_tps > 0 else 0.0, 3),
    }


def _prefix_engine(*, batch, max_seq, page_size, prefill_chunk, dim,
                   n_layers, vocab, seed, num_blocks, horizon=1):
    from triton_dist_tpu.models import llama
    from triton_dist_tpu.models.generate import Generator
    from triton_dist_tpu.serve import ServeEngine

    cfg = llama.LlamaConfig(vocab=vocab, dim=dim, n_layers=n_layers,
                            n_heads=2, n_kv_heads=2, ffn_dim=2 * dim,
                            max_seq=max_seq, dtype=jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    params = llama.init_params(cfg, jax.random.key(seed))
    gen = Generator(cfg, mesh, axis="sp", max_seq=max_seq)
    eng = ServeEngine(gen, params, num_blocks=num_blocks,
                      page_size=page_size, max_batch=batch,
                      prefill_chunk=prefill_chunk, horizon=horizon)
    return eng, cfg


def bench_prefix(*, batch: int = 4, prompt_len: int = 256,
                 suffix_len: int = 16, new_tokens: int = 8,
                 n_cold: int = 4, n_warm: int = 4, dim: int = 64,
                 n_layers: int = 2, vocab: int = 256, page_size: int = 16,
                 prefill_chunk: int = 32, seed: int = 0,
                 warmup: bool = True, horizon: int = 1) -> dict:
    """Shared-prompt traffic (docs/serving.md "Prefix caching"): a cold
    phase of distinct prompts, one seeder that commits the shared
    prompt's pages, then warm requests = shared prompt + a distinct
    per-request suffix.  Warm TTFT pays only the residual chunks past
    the cached block-aligned prefix — the number this mode exists to
    collapse (the acceptance gate holds warm/cold <= 0.35)."""
    from triton_dist_tpu.serve import Request, SamplingParams

    total = prompt_len + suffix_len + new_tokens
    max_seq = total + (-total) % page_size
    per_req = -(-max_seq // page_size)
    eng, cfg = _prefix_engine(
        batch=batch, max_seq=max_seq, page_size=page_size,
        prefill_chunk=prefill_chunk, dim=dim, n_layers=n_layers,
        vocab=vocab, seed=seed,
        num_blocks=1 + per_req * (max(n_cold, n_warm) + 1),
        horizon=horizon)
    if warmup:
        eng.warmup()
    rng = np.random.default_rng(seed)
    sp = SamplingParams(max_new_tokens=new_tokens)
    L = prompt_len + suffix_len

    def drain(reqs):
        for r in reqs:
            eng.submit(r)
        outs = eng.run()
        assert all(len(outs[r.request_id].token_ids) == new_tokens
                   for r in reqs)

    t0 = time.perf_counter()
    drain([Request(f"cold{i}",
                   rng.integers(0, vocab, size=L).astype(np.int32), sp)
           for i in range(n_cold)])
    shared = rng.integers(0, vocab, size=prompt_len).astype(np.int32)
    drain([Request("seed0", np.concatenate(
        [shared, rng.integers(0, vocab, size=suffix_len)
         .astype(np.int32)]), sp)])
    drain([Request(f"warm{i}", np.concatenate(
        [shared, rng.integers(0, vocab, size=suffix_len)
         .astype(np.int32)]), sp) for i in range(n_warm)])
    dt = time.perf_counter() - t0

    s = eng.metrics.summary()["prefix_cache"]
    return {
        "mode": "prefix",
        "batch": batch, "prompt_len": prompt_len,
        "suffix_len": suffix_len,
        "wall_s": round(dt, 4),
        "warm_requests": s["warm_requests"],
        "cold_requests": s["cold_requests"],
        "ttft_cold_ms": round(s["mean_ttft_cold"] * 1e3, 2),
        "ttft_warm_ms": round(s["mean_ttft_warm"] * 1e3, 2),
        "ttft_warm_over_cold": round(s["ttft_warm_over_cold"], 3),
        "hit_rate": round(s["hit_rate"], 3),
        "hit_tokens": s["hit_tokens"],
        "prefix_skipped_tokens": s["prefix_skipped_tokens"],
        "cached_blocks": s["cached_blocks"],
        "evictions": s["evictions"],
        "cow_copies": s["cow_copies"],
    }


def bench_sessions(*, n_sessions: int = 3, n_turns: int = 4,
                   turn_user: int = 32, new_tokens: int = 8,
                   dim: int = 64, n_layers: int = 2, vocab: int = 256,
                   page_size: int = 16, prefill_chunk: int = 32,
                   seed: int = 0, warmup: bool = True) -> dict:
    """Multi-turn session traffic: turn t's prompt is the FULL previous
    conversation (prompt + assistant tokens) plus a fresh user message —
    the dominant production shape prefix reuse exists for.  Every turn
    past the first should hit the cache for the whole history (generated
    tokens commit too, as their pages fill), so per-turn TTFT stays
    ~flat while the prompt grows linearly."""
    from triton_dist_tpu.serve import Request, SamplingParams

    if n_sessions < 1 or n_turns < 1:
        raise ValueError(f"need n_sessions >= 1 and n_turns >= 1, got "
                         f"{n_sessions}/{n_turns}")

    total = n_turns * (turn_user + new_tokens)
    max_seq = total + (-total) % page_size
    per_req = -(-max_seq // page_size)
    eng, cfg = _prefix_engine(
        batch=n_sessions, max_seq=max_seq, page_size=page_size,
        prefill_chunk=prefill_chunk, dim=dim, n_layers=n_layers,
        vocab=vocab, seed=seed,
        num_blocks=1 + per_req * (n_sessions + 1))
    if warmup:
        eng.warmup()
    rng = np.random.default_rng(seed)
    sp = SamplingParams(max_new_tokens=new_tokens)
    history = {s: rng.integers(0, vocab, size=turn_user)
               .astype(np.int32) for s in range(n_sessions)}
    turn_ttft, turn_hit = [], []
    t0 = time.perf_counter()
    for turn in range(n_turns):
        rids = []
        for s in range(n_sessions):
            rid = f"s{s}t{turn}"
            eng.submit(Request(rid, history[s], sp))
            rids.append((s, rid))
        outs = eng.run()
        ttfts, hits = [], 0
        for s, rid in rids:
            o = outs[rid]
            ttfts.append(o.metrics.ttft)
            hits += o.metrics.cached_prefix_tokens > 0
            history[s] = np.concatenate(
                [history[s], np.asarray(o.token_ids, np.int32),
                 rng.integers(0, vocab, size=turn_user)
                 .astype(np.int32)])
        turn_ttft.append(round(sum(ttfts) / len(ttfts) * 1e3, 2))
        turn_hit.append(hits / n_sessions)
    dt = time.perf_counter() - t0
    s = eng.metrics.summary()["prefix_cache"]
    return {
        "mode": "sessions",
        "sessions": n_sessions, "turns": n_turns,
        "wall_s": round(dt, 4),
        "ttft_by_turn_ms": turn_ttft,
        "hit_rate_by_turn": turn_hit,
        "hit_rate": round(s["hit_rate"], 3),
        "prefix_skipped_tokens": s["prefix_skipped_tokens"],
        "cached_blocks": s["cached_blocks"],
        "evictions": s["evictions"],
    }


def bench_fleet(*, n_replicas: int = 2, batch: int = 4,
                prompt_len: int = 16, new_tokens: int = 48,
                dim: int = 64, n_layers: int = 2, vocab: int = 256,
                page_size: int = 16, seed: int = 0,
                warmup: bool = True, kill_at_call: int = 20) -> dict:
    """Fleet serving (docs/serving.md "Fleet serving"): aggregate
    decode tokens/s at N replicas behind the router, then the chaos
    leg — the SAME workload with one replica killed mid-decode — with
    zero-loss verification against the single-engine oracle.

    ``serve_fleet_zero_loss`` is the headline: the fraction of streams
    that finish BIT-IDENTICAL to the oracle with an exactly-once
    delivery record across the kill + migration + restart.  1.0 is the
    only acceptable reading (PERF_FLOORS.json floors it there — this is
    a correctness guardrail wearing a bench harness, like
    serve_spec_speedup's >= 1.0).  ``chaos_recovery_s`` is the
    wall-clock from the replica death to the fleet fully drained
    (migration + backoff restart + remaining decode)."""
    import shutil
    import tempfile

    from triton_dist_tpu.models import llama
    from triton_dist_tpu.models.generate import Generator
    from triton_dist_tpu.runtime.faults import FaultInjector
    from triton_dist_tpu.serve import Request, SamplingParams, ServeEngine
    from triton_dist_tpu.serve.fleet import FleetController

    max_seq = prompt_len + new_tokens
    max_seq += (-max_seq) % page_size
    cfg = llama.LlamaConfig(vocab=vocab, dim=dim, n_layers=n_layers,
                            n_heads=2, n_kv_heads=2, ffn_dim=2 * dim,
                            max_seq=max_seq, dtype=jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    params = llama.init_params(cfg, jax.random.key(seed))
    gen = Generator(cfg, mesh, axis="sp", max_seq=max_seq)
    per_req = -(-max_seq // page_size)
    n_reqs = n_replicas * batch
    rng = np.random.default_rng(seed)
    reqs = [(f"f{i}", rng.integers(0, vocab, size=prompt_len)
             .astype(np.int32)) for i in range(n_reqs)]
    sp = SamplingParams(max_new_tokens=new_tokens)

    def make_factory(injector):
        def factory(d):
            faults = (injector if injector is not None
                      and (os.sep + "r0" + os.sep) in d
                      and d.endswith("life1") else None)
            eng = ServeEngine(
                gen, params, num_blocks=1 + per_req * batch,
                page_size=page_size, max_batch=batch,
                prefill_chunk=max(8, page_size), snapshot_dir=d,
                faults=faults)
            if warmup and faults is None:
                eng.warmup()
            return eng
        return factory

    def drive(injector):
        root = tempfile.mkdtemp(prefix="bench_fleet_")
        fc = FleetController(make_factory(injector), n_replicas,
                             root=root, backoff_base_s=0.01,
                             backoff_cap_s=0.1,
                             suspect_after_s=1e6, dead_after_s=2e6,
                             seed=seed)
        t0 = time.perf_counter()
        t_death = None
        for rid, prompt in reqs:
            fc.submit(Request(rid, prompt, sp))
        while fc.has_work():
            fc.step()
            if t_death is None and fc.deaths:
                t_death = time.perf_counter()
        dt = time.perf_counter() - t0
        toks = sum(len(o.token_ids) for o in fc.outputs.values())
        recovery = (time.perf_counter() - t_death
                    if t_death is not None else None)
        streams = {rid: list(fc.streams[rid]) for rid, _ in reqs}
        outs = {rid: list(fc.outputs[rid].token_ids)
                for rid, _ in reqs}
        shutil.rmtree(root, ignore_errors=True)
        return dt, toks, fc.deaths, recovery, streams, outs

    # oracle: every stream is per-request deterministic
    oracle = {}
    for rid, prompt in reqs:
        eng = ServeEngine(gen, params, num_blocks=1 + per_req * batch,
                          page_size=page_size, max_batch=batch,
                          prefill_chunk=max(8, page_size))
        eng.submit(Request(rid, prompt, sp))
        oracle[rid] = list(eng.run()[rid].token_ids)

    dt, toks, deaths, _, streams, outs = drive(None)
    assert deaths == 0
    inj = FaultInjector(seed=seed).inject("forward", kill=True,
                                          at_call=kill_at_call)
    cdt, ctoks, cdeaths, recovery, cstreams, couts = drive(inj)
    # the floor is only meaningful if the kill actually landed — a
    # workload that drains before at_call would read 1.0 vacuously
    assert cdeaths >= 1, (
        f"chaos leg never killed a replica (kill_at_call="
        f"{kill_at_call} not reached); lower it or grow the workload")
    exact = sum(1 for rid in oracle
                if couts[rid] == oracle[rid]
                and cstreams[rid] == oracle[rid])
    return {
        "mode": "fleet",
        "replicas": n_replicas,
        "requests": n_reqs,
        "new_tokens": new_tokens,
        "wall_s": round(dt, 4),
        "fleet_toks_per_s": round(toks / dt, 1),
        "chaos_wall_s": round(cdt, 4),
        "chaos_deaths": cdeaths,
        "chaos_recovery_s": (round(recovery, 4)
                             if recovery is not None else None),
        "serve_fleet_zero_loss": round(exact / len(oracle), 4),
    }


def bench_fleet_net(*, n_replicas: int = 2, batch: int = 4,
                    prompt_len: int = 16, new_tokens: int = 48,
                    dim: int = 64, n_layers: int = 2, vocab: int = 256,
                    page_size: int = 16, seed: int = 0,
                    warmup: bool = True,
                    step_sleep_s: float = 0.004) -> dict:
    """NETWORK fleet chaos guardrail (docs/serving.md "Network fleet
    serving"): N replicas reachable ONLY over the wire
    (``InProcessReplica``: each engine free-runs its ``serve_loop`` on
    its own thread, the controller drives ``RemoteReplica`` HTTP
    clients), then the chaos leg — one replica's process killed
    mid-decode AND the other cut off by an injected client-side
    partition that heals once the controller circuit-breaks it to
    SUSPECT.  ``serve_fleet_net_zero_loss`` is the fraction of streams
    finishing BIT-IDENTICAL to the single-engine oracle with an
    exactly-once delivery record across the kill + retries + partition
    + journal crash migration.  1.0 is the only acceptable reading
    (PERF_FLOORS.json floors it there — the cross-process twin of
    ``serve_fleet_zero_loss``)."""
    import shutil
    import tempfile

    from triton_dist_tpu.models import llama
    from triton_dist_tpu.models.generate import Generator
    from triton_dist_tpu.runtime.faults import FaultInjector
    from triton_dist_tpu.serve import Request, SamplingParams, ServeEngine
    from triton_dist_tpu.serve.fleet import (
        FleetController,
        RemoteReplica,
        ReplicaState,
    )
    from triton_dist_tpu.serve.net import InProcessReplica

    max_seq = prompt_len + new_tokens
    max_seq += (-max_seq) % page_size
    cfg = llama.LlamaConfig(vocab=vocab, dim=dim, n_layers=n_layers,
                            n_heads=2, n_kv_heads=2, ffn_dim=2 * dim,
                            max_seq=max_seq, dtype=jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    params = llama.init_params(cfg, jax.random.key(seed))
    gen = Generator(cfg, mesh, axis="sp", max_seq=max_seq)
    per_req = -(-max_seq // page_size)
    n_reqs = n_replicas * batch
    rng = np.random.default_rng(seed)
    reqs = [(f"n{i}", rng.integers(0, vocab, size=prompt_len)
             .astype(np.int32)) for i in range(n_reqs)]
    sp = SamplingParams(max_new_tokens=new_tokens)

    oracle = {}
    for rid, prompt in reqs:
        eng = ServeEngine(gen, params, num_blocks=1 + per_req * n_reqs,
                          page_size=page_size, max_batch=batch,
                          prefill_chunk=max(8, page_size))
        eng.submit(Request(rid, prompt, sp))
        oracle[rid] = list(eng.run()[rid].token_ids)

    client_inj = FaultInjector(seed=seed)
    root = tempfile.mkdtemp(prefix="bench_fleet_net_")
    procs: dict = {}

    def factory(life_dir):
        name = os.path.basename(os.path.dirname(life_dir))
        eng = ServeEngine(gen, params,
                          num_blocks=1 + per_req * n_reqs,
                          page_size=page_size, max_batch=batch,
                          prefill_chunk=max(8, page_size),
                          snapshot_dir=life_dir)
        if warmup:
            eng.warmup()
        rep = InProcessReplica(eng, stall_after_s=5.0,
                               step_sleep_s=step_sleep_s)
        procs[name] = rep
        rr = RemoteReplica(name, rep.url, kill=rep.kill, retries=2,
                           retry_base_s=0.01, retry_cap_s=0.05,
                           timeout_s=5.0, faults=client_inj, seed=seed)
        return rr.wait_ready(60)

    try:
        fc = FleetController(factory, n_replicas, root=root,
                             suspect_after_s=0.5, dead_after_s=1.5,
                             backoff_base_s=0.05, backoff_cap_s=0.1,
                             max_restarts=0, seed=seed)
        t0 = time.perf_counter()
        for rid, prompt in reqs:
            fc.submit(Request(rid, prompt, sp))
        kill_name = fc.placement.get(reqs[0][0],
                                     next(iter(fc.replicas)))
        part_name = next(n for n in fc.replicas if n != kill_name)
        killed = partitioned = healed = False
        t_death = None
        deadline = time.monotonic() + 300.0
        while fc.has_work():
            if time.monotonic() > deadline:
                raise RuntimeError("bench_fleet_net: fleet not drained "
                                   "inside the 300s chaos deadline")
            fc.step()
            toks = sum(len(s) for s in fc.streams.values())
            if not killed and toks >= 1:
                procs[kill_name].kill()
                client_inj.inject("net", partition=True,
                                  target=part_name)
                killed = partitioned = True
            if (partitioned and not healed
                    and fc.replicas[part_name].state
                    is ReplicaState.SUSPECT):
                # the breaker opened on the partition: heal the link —
                # the replica must recover to HEALTHY on its next
                # proven progress, not die (the SIGKILLed one
                # exercises DEAD)
                client_inj.heal(target=part_name)
                healed = True
            if t_death is None and fc.deaths:
                t_death = time.perf_counter()
        dt = time.perf_counter() - t0
        assert fc.deaths >= 1, "chaos leg never killed a replica"
        assert healed, "the partition never drove SUSPECT (widen the " \
                       "workload or shrink suspect_after_s)"
        retries = sum(1 for e in fc.audit.entries()
                      if e["kind"] == "net_retry")
        exact = sum(1 for rid in oracle
                    if rid in fc.outputs
                    and list(fc.outputs[rid].token_ids) == oracle[rid]
                    and fc.streams[rid] == oracle[rid])
        toks = sum(len(o.token_ids) for o in fc.outputs.values())
    finally:
        # a wedged/failed chaos leg must not leak free-running replica
        # threads into the later bench legs (they'd contend every
        # subsequent measurement) nor its temp tree onto disk
        for rep in procs.values():
            rep.kill()
        shutil.rmtree(root, ignore_errors=True)
    return {
        "mode": "fleet_net",
        "replicas": n_replicas,
        "requests": n_reqs,
        "new_tokens": new_tokens,
        "chaos_wall_s": round(dt, 4),
        "net_fleet_toks_per_s": round(toks / dt, 1),
        "chaos_deaths": fc.deaths,
        "chaos_recovery_s": (round(time.perf_counter() - t_death, 4)
                             if t_death is not None else None),
        "net_retries": retries,
        "serve_fleet_net_zero_loss": round(exact / len(oracle), 4),
    }


def bench_corrupt(*, n_replicas: int = 2, batch: int = 4,
                  prompt_len: int = 16, new_tokens: int = 48,
                  dim: int = 64, n_layers: int = 2, vocab: int = 256,
                  page_size: int = 16, seed: int = 0,
                  warmup: bool = True,
                  step_sleep_s: float = 0.004) -> dict:
    """State-integrity chaos guardrail (docs/serving.md "Durability &
    integrity"): the network fleet under injected CORRUPTION of each
    artifact class, mid-run, with a SIGKILL on top.

    Timeline: (a) replica r0's engine carries an ``integrity`` fault
    that bitflips one journal line mid-decode (interior corruption on
    disk); (b) once tokens flow, r1 is cooperatively drained with its
    drain-response manifest bitflipped in flight (wire KV blob — the
    client detects the digest mismatch and retries the SAME key, so
    the server's cached clean manifest replays), and the re-placement
    ``migrate_in`` manifest is bitflipped once too (the receiver
    REJECTS with the counted 400 and the placer walks on); (c) r0 is
    then SIGKILLed, so the crash path must SALVAGE its bit-rotted
    journal — quarantine, longest-valid prefix, controller
    reconciliation against the delivery record, recompute for the
    lost tail.

    ``serve_corrupt_recovery_zero_loss`` is the fraction of streams
    bit-identical to the single-engine oracle with an exactly-once
    delivery record across all of that.  1.0 is the only acceptable
    reading (PERF_FLOORS.json floors it there): corruption must
    degrade to re-queue + recompute, never to adopted rot or lost
    tokens."""
    import shutil
    import tempfile

    from triton_dist_tpu.models import llama
    from triton_dist_tpu.models.generate import Generator
    from triton_dist_tpu.runtime.faults import FaultInjector
    from triton_dist_tpu.serve import Request, SamplingParams, ServeEngine
    from triton_dist_tpu.serve.fleet import FleetController, RemoteReplica
    from triton_dist_tpu.serve.net import InProcessReplica

    max_seq = prompt_len + new_tokens
    max_seq += (-max_seq) % page_size
    cfg = llama.LlamaConfig(vocab=vocab, dim=dim, n_layers=n_layers,
                            n_heads=2, n_kv_heads=2, ffn_dim=2 * dim,
                            max_seq=max_seq, dtype=jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    params = llama.init_params(cfg, jax.random.key(seed))
    gen = Generator(cfg, mesh, axis="sp", max_seq=max_seq)
    per_req = -(-max_seq // page_size)
    n_reqs = n_replicas * batch
    rng = np.random.default_rng(seed)
    reqs = [(f"c{i}", rng.integers(0, vocab, size=prompt_len)
             .astype(np.int32)) for i in range(n_reqs)]
    sp = SamplingParams(max_new_tokens=new_tokens)

    oracle = {}
    for rid, prompt in reqs:
        eng = ServeEngine(gen, params, num_blocks=1 + per_req * n_reqs,
                          page_size=page_size, max_batch=batch,
                          prefill_chunk=max(8, page_size))
        eng.submit(Request(rid, prompt, sp))
        oracle[rid] = list(eng.run()[rid].token_ids)

    client_inj = FaultInjector(seed=seed)
    # r0's engine carries this injector; the journal-rot spec is armed
    # mid-timeline (after the drain re-placements land), so the damage
    # falls on a tok line — the realistic class (tok lines are ~all of
    # the file).  A rotted SUBMIT line is a different, honest failure:
    # the prompt exists nowhere else and salvage reports the rid lost.
    journal_inj = FaultInjector(seed=seed)
    root = tempfile.mkdtemp(prefix="bench_corrupt_")
    procs: dict = {}

    def factory(life_dir):
        name = os.path.basename(os.path.dirname(life_dir))
        eng = ServeEngine(gen, params,
                          num_blocks=1 + per_req * n_reqs,
                          page_size=page_size, max_batch=batch,
                          prefill_chunk=max(8, page_size),
                          snapshot_dir=life_dir,
                          faults=(journal_inj if name == "r0"
                                  and life_dir.endswith("life1")
                                  else None))
        if warmup:
            eng.warmup()
        rep = InProcessReplica(eng, stall_after_s=5.0,
                               step_sleep_s=step_sleep_s)
        procs[name] = rep
        rr = RemoteReplica(name, rep.url, kill=rep.kill, retries=2,
                           retry_base_s=0.01, retry_cap_s=0.05,
                           timeout_s=5.0, faults=client_inj, seed=seed)
        return rr.wait_ready(60)

    try:
        fc = FleetController(factory, n_replicas, root=root,
                             suspect_after_s=0.5, dead_after_s=1.5,
                             backoff_base_s=0.05, backoff_cap_s=0.1,
                             max_restarts=0, seed=seed)
        t0 = time.perf_counter()
        for rid, prompt in reqs:
            fc.submit(Request(rid, prompt, sp))
        drained = killed = False
        t_death = None
        deadline = time.monotonic() + 300.0
        while fc.has_work():
            if time.monotonic() > deadline:
                raise RuntimeError("bench_corrupt: fleet not drained "
                                   "inside the 300s chaos deadline")
            fc.step()
            toks = sum(len(s) for s in fc.streams.values())
            if not drained and toks >= 1:
                # wire-blob corruption, both directions: the drain
                # RESPONSE (client-side detect -> same-key retry) and
                # the re-placement migrate_in (server-side reject ->
                # placer fallback).  max_fires=1 without at_call: each
                # spec takes its op's FIRST arrival, whatever the
                # shared per-point call index has reached by then.
                client_inj.inject("integrity", corrupt="bitflip",
                                  op="drain", max_fires=1)
                client_inj.inject("integrity", corrupt="bitflip",
                                  op="migrate_in", max_fires=1)
                fc.drain_replica("r1")
                drained = True
                # every submit (originals + the re-placements the drain
                # just adopted) is now journaled on r0 — the next
                # append is a tok/fin line: rot it
                journal_inj.inject("integrity", corrupt="bitflip",
                                   op="journal", max_fires=1)
            elif (drained and not killed and toks >= n_reqs
                  and journal_inj.fire_count("integrity") >= 1):
                procs["r0"].kill()
                killed = True
            if t_death is None and fc.deaths:
                t_death = time.perf_counter()
        dt = time.perf_counter() - t0
        assert killed and fc.deaths >= 1, \
            "chaos leg never killed the bit-rotted replica"
        fired = [k for p, _, k, _, _ in journal_inj.fired
                 if p == "integrity"]
        assert "bitflip" in fired, "the journal bitflip never fired"
        wire_fired = [k for p, _, k, _, _ in client_inj.fired
                      if p == "integrity"]
        # each wire spec is max_fires=1, so >= 2 bitflips means BOTH
        # the drain-response and the migrate_in corruption fired
        assert wire_fired.count("bitflip") >= 2, (
            f"wire corruption incomplete: {wire_fired}")
        salvages = sum(1 for e in fc.audit.entries()
                       if e["kind"] == "journal_corrupt")
        assert salvages >= 1, (
            "the crash path never salvaged the corrupt journal — the "
            "bitflipped line was not exercised")
        exact = sum(1 for rid in oracle
                    if rid in fc.outputs
                    and list(fc.outputs[rid].token_ids) == oracle[rid]
                    and fc.streams[rid] == oracle[rid])
        toks = sum(len(o.token_ids) for o in fc.outputs.values())
    finally:
        for rep in procs.values():
            rep.kill()
        shutil.rmtree(root, ignore_errors=True)
    return {
        "mode": "corrupt",
        "replicas": n_replicas,
        "requests": n_reqs,
        "new_tokens": new_tokens,
        "chaos_wall_s": round(dt, 4),
        "corrupt_toks_per_s": round(toks / dt, 1),
        "chaos_deaths": fc.deaths,
        "chaos_recovery_s": (round(time.perf_counter() - t_death, 4)
                             if t_death is not None else None),
        "journal_salvages": salvages,
        "serve_corrupt_recovery_zero_loss": round(exact / len(oracle), 4),
    }


def bench_disagg(*, prefill: int = 1, decode: int = 2, batch: int = 4,
                 prompt_len: int = 16, new_tokens: int = 48,
                 burst_len: int = 128, burst_n: int = 2,
                 dim: int = 64, n_layers: int = 2, vocab: int = 256,
                 page_size: int = 16, seed: int = 0,
                 warmup: bool = True) -> dict:
    """Disaggregated prefill→decode serving (docs/serving.md
    "Disaggregated serving"): the P:D tier vs a co-located fleet of the
    same size, under a long-prompt burst landing mid-decode.

    ``serve_disagg_zero_loss`` is the headline: the chaos leg SIGKILLs
    the prefill tier mid-push and a decode replica post-adopt, and
    reports the fraction of streams that still finish BIT-IDENTICAL to
    the single-engine oracle with exactly-once delivery.  1.0 is the
    only acceptable reading (PERF_FLOORS.json floors it there — a
    correctness guardrail wearing a bench harness, like
    serve_fleet_zero_loss).  ``serve_disagg_itl_isolation`` is the
    interference story: decode p99 inter-token latency under the burst,
    co-located over disagg — > 1 means the split shielded decode from
    the prefill burst.  Informational on CPU hosts (the compute/memory
    split the ratio measures needs a real accelerator to show its
    shape)."""
    import shutil
    import tempfile

    from triton_dist_tpu.models import llama
    from triton_dist_tpu.models.generate import Generator
    from triton_dist_tpu.serve import Request, SamplingParams, ServeEngine
    from triton_dist_tpu.serve.disagg import DisaggController
    from triton_dist_tpu.serve.fleet import FleetController, ReplicaState

    n_replicas = prefill + decode
    max_seq = max(prompt_len, burst_len) + new_tokens
    max_seq += (-max_seq) % page_size
    cfg = llama.LlamaConfig(vocab=vocab, dim=dim, n_layers=n_layers,
                            n_heads=2, n_kv_heads=2, ffn_dim=2 * dim,
                            max_seq=max_seq, dtype=jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    params = llama.init_params(cfg, jax.random.key(seed))
    gen = Generator(cfg, mesh, axis="sp", max_seq=max_seq)
    per_req = -(-max_seq // page_size)
    rng = np.random.default_rng(seed)
    n_reqs = max(decode, 1) * batch
    reqs = [(f"d{i}", rng.integers(0, vocab, size=prompt_len)
             .astype(np.int32)) for i in range(n_reqs)]
    burst = [(f"b{i}", rng.integers(0, vocab, size=burst_len)
              .astype(np.int32)) for i in range(burst_n)]
    sp = SamplingParams(max_new_tokens=new_tokens)
    bsp = SamplingParams(max_new_tokens=8)

    def factory(d):
        eng = ServeEngine(gen, params,
                          num_blocks=1 + per_req * (batch + burst_n),
                          page_size=page_size, max_batch=batch,
                          prefill_chunk=max(8, page_size),
                          snapshot_dir=d)
        if warmup:
            eng.warmup()
        return eng

    def make_fc(root, disagg):
        if disagg:
            return DisaggController(factory, prefill, decode, root=root,
                                    backoff_base_s=0.01,
                                    backoff_cap_s=0.1,
                                    suspect_after_s=1e6,
                                    dead_after_s=2e6, seed=seed)
        return FleetController(factory, n_replicas, root=root,
                               backoff_base_s=0.01, backoff_cap_s=0.1,
                               suspect_after_s=1e6, dead_after_s=2e6,
                               seed=seed)

    def drive(disagg, chaos=False):
        root = tempfile.mkdtemp(prefix="bench_disagg_")
        fc = make_fc(root, disagg)
        stamps: dict = {rid: [] for rid, _ in reqs}

        def on_tok(rid, _tok):
            stamps[rid].append(time.perf_counter())

        for rid, prompt in reqs:
            fc.submit(Request(rid, prompt, sp, on_token=on_tok))
        burst_sent = killed_decode = killed_prefill = False
        t0 = time.perf_counter()
        while fc.has_work() or not burst_sent:
            # the burst lands once decode is underway everywhere
            if (not burst_sent
                    and all(len(s) >= 4 for s in stamps.values())):
                for rid, prompt in burst:
                    fc.submit(Request(rid, prompt, bsp))
                burst_sent = True
            if chaos and disagg:
                if not killed_decode and fc.pushes >= 1:
                    vs = {fc.placement.get(rid) for rid in fc.streams
                          if rid not in fc.outputs} - {None, "r0"}
                    if vs:
                        fc.kill_replica(sorted(vs)[0],
                                        "bench chaos: post-adopt")
                        killed_decode = True
                elif (killed_decode and not killed_prefill
                      and (fc.replicas["r0"].state
                           is ReplicaState.HEALTHY)
                      and any(p == "r0"
                              for p in fc.placement.values())):
                    fc.kill_replica("r0", "bench chaos: mid-push")
                    killed_prefill = True
            fc.step()
        dt = time.perf_counter() - t0
        gaps = [b - a for ts in stamps.values()
                for a, b in zip(ts, ts[1:])]
        streams = {rid: list(fc.streams[rid]) for rid, _ in reqs}
        outs = {rid: list(fc.outputs[rid].token_ids)
                for rid, _ in reqs}
        pushes = fc.pushes if disagg else 0
        deaths = fc.deaths
        kills_landed = (killed_decode and killed_prefill)
        shutil.rmtree(root, ignore_errors=True)
        return dt, gaps, streams, outs, pushes, deaths, kills_landed

    # oracle: every stream is per-request deterministic
    oracle = {}
    for rid, prompt in reqs:
        eng = ServeEngine(gen, params,
                          num_blocks=1 + per_req * (batch + burst_n),
                          page_size=page_size, max_batch=batch,
                          prefill_chunk=max(8, page_size))
        eng.submit(Request(rid, prompt, sp))
        oracle[rid] = list(eng.run()[rid].token_ids)

    _, colo_gaps, _, couts, _, _, _ = drive(disagg=False)
    dt, dis_gaps, _, douts, pushes, _, _ = drive(disagg=True)
    for rid in oracle:
        assert douts[rid] == oracle[rid], f"disagg diverged on {rid}"
        assert couts[rid] == oracle[rid], f"co-located diverged on {rid}"
    colo_p99 = float(np.percentile(colo_gaps, 99)) * 1e3
    dis_p99 = float(np.percentile(dis_gaps, 99)) * 1e3

    cdt, _, cstreams, chouts, cpushes, cdeaths, kills = drive(
        disagg=True, chaos=True)
    # the floor is only meaningful if both kills actually landed — a
    # workload that drains first would read 1.0 vacuously
    assert kills, ("chaos leg drained before both kills landed; "
                   "grow the workload")
    exact = sum(1 for rid in oracle
                if chouts[rid] == oracle[rid]
                and cstreams[rid] == oracle[rid])
    return {
        "mode": "disagg",
        "prefill": prefill,
        "decode": decode,
        "requests": n_reqs,
        "burst": burst_n,
        "new_tokens": new_tokens,
        "wall_s": round(dt, 4),
        "pushes": pushes,
        "decode_itl_p99_ms_disagg": round(dis_p99, 3),
        "decode_itl_p99_ms_colocated": round(colo_p99, 3),
        "serve_disagg_itl_isolation": round(colo_p99 / max(dis_p99,
                                                           1e-9), 4),
        "chaos_wall_s": round(cdt, 4),
        "chaos_deaths": cdeaths,
        "chaos_pushes": cpushes,
        "serve_disagg_zero_loss": round(exact / len(oracle), 4),
    }


def bench_fleet_trace_overhead(*, n_replicas: int = 2, batch: int = 4,
                               prompt_len: int = 16,
                               new_tokens: int = 64, dim: int = 64,
                               n_layers: int = 2, vocab: int = 256,
                               page_size: int = 16, seed: int = 0,
                               warmup: bool = True,
                               repeats: int = 3) -> dict:
    """Fleet tracing overhead (docs/observability.md "Fleet
    observability"): the IDENTICAL warmed fleet workload (N replicas
    behind the router, no chaos) runs with the whole observability
    stack OFF (engine rings at trace_level=0, controller ring + router
    decision audit disabled) and at FULL detail (trace_level=2), and
    the headline is the paired fleet tokens/s quotient — the fleet twin
    of ``bench_trace_overhead``.  The hot-path contract is the same
    (ring/audit appends only), so this must stay ~1.0; ``bench.py``
    carries it as ``serve_fleet_trace_overhead`` with a
    ``PERF_FLOORS.json`` floor of 0.95.  Best-of-``repeats`` per leg."""
    import shutil
    import tempfile

    from triton_dist_tpu.models import llama
    from triton_dist_tpu.models.generate import Generator
    from triton_dist_tpu.serve import Request, SamplingParams, ServeEngine
    from triton_dist_tpu.serve.fleet import FleetController

    max_seq = prompt_len + new_tokens
    max_seq += (-max_seq) % page_size
    cfg = llama.LlamaConfig(vocab=vocab, dim=dim, n_layers=n_layers,
                            n_heads=2, n_kv_heads=2, ffn_dim=2 * dim,
                            max_seq=max_seq, dtype=jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    params = llama.init_params(cfg, jax.random.key(seed))
    gen = Generator(cfg, mesh, axis="sp", max_seq=max_seq)
    per_req = -(-max_seq // page_size)
    n_reqs = n_replicas * batch
    rng = np.random.default_rng(seed)
    reqs = [(f"t{i}", rng.integers(0, vocab, size=prompt_len)
             .astype(np.int32)) for i in range(n_reqs)]
    sp = SamplingParams(max_new_tokens=new_tokens)

    def run(level: int) -> float:
        root = tempfile.mkdtemp(prefix="bench_fleet_trace_")

        def factory(d):
            eng = ServeEngine(
                gen, params, num_blocks=1 + per_req * batch,
                page_size=page_size, max_batch=batch,
                prefill_chunk=max(8, page_size), snapshot_dir=d,
                trace_level=level)
            if warmup:
                eng.warmup()
            return eng

        fc = FleetController(factory, n_replicas, root=root,
                             suspect_after_s=1e6, dead_after_s=2e6,
                             trace_level=level, seed=seed)
        for rid, prompt in reqs:
            fc.submit(Request(rid, prompt, sp))
        t0 = time.perf_counter()
        while fc.has_work():
            fc.step()
        dt = time.perf_counter() - t0
        toks = sum(len(o.token_ids) for o in fc.outputs.values())
        assert toks == n_reqs * new_tokens
        shutil.rmtree(root, ignore_errors=True)
        return toks / dt

    def best(level: int) -> float:
        return max(run(level) for _ in range(max(repeats, 1)))

    off_tps = best(0)
    on_tps = best(2)
    return {
        "mode": "fleet_trace",
        "replicas": n_replicas,
        "batch": batch,
        "new_tokens": new_tokens,
        "fleet_toks_per_s_trace_off": round(off_tps, 1),
        "fleet_toks_per_s_trace_on": round(on_tps, 1),
        "serve_fleet_trace_overhead": round(
            on_tps / off_tps if off_tps > 0 else 0.0, 3),
    }


def bench_overload(*, n_replicas: int = 1, max_replicas: int = 3,
                   batch: int = 4, n_requests: int = 48,
                   prompt_len: int = 16, new_tokens: int = 12,
                   dim: int = 64, n_layers: int = 2, vocab: int = 256,
                   page_size: int = 16, seed: int = 0,
                   warmup: bool = True,
                   overload_factor: float = 2.0) -> dict:
    """Bursty overload leg (docs/serving.md "Overload, SLO classes &
    autoscaling"): a trace-shaped open-loop workload — bursty Poisson
    arrivals, lognormal lengths, a 50/30/20 interactive/batch/
    best_effort mix (``benchlib.trace_workload``) — offered at
    ``overload_factor``x the fleet's measured capacity on a VIRTUAL
    clock, through a class-aware fleet with token-bucket ingress, the
    brownout ladder armed and the autoscaler allowed to grow from
    ``n_replicas`` to ``max_replicas``.

    ``serve_slo_interactive_goodput`` is the headline: the fraction of
    ADMITTED interactive requests (not refused at ingress or the
    brownout door — refusals land a counted SHED terminal, never a
    silent drop) that finish healthy (EOS/LENGTH) with their delivered
    stream exactly matching the final output.  1.0 is the only
    acceptable reading (PERF_FLOORS.json floors it there): under 2x
    overload the fleet may shed best_effort and batch — counted, per
    class — but an interactive request it accepted must never be lost.
    The harness also hard-asserts exactly-once terminals for EVERY
    submitted request and that per-class shed counters match the
    observed SHED terminals (shedding is never silent)."""
    import shutil
    import tempfile

    from benchlib import trace_workload
    from triton_dist_tpu.models import llama
    from triton_dist_tpu.models.generate import Generator
    from triton_dist_tpu.serve import Request, SamplingParams, ServeEngine
    from triton_dist_tpu.serve.request import FinishReason
    from triton_dist_tpu.serve.fleet import FleetController

    max_seq = 2 * prompt_len + 2 * new_tokens
    max_seq += (-max_seq) % page_size
    cfg = llama.LlamaConfig(vocab=vocab, dim=dim, n_layers=n_layers,
                            n_heads=2, n_kv_heads=2, ffn_dim=2 * dim,
                            max_seq=max_seq, dtype=jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    params = llama.init_params(cfg, jax.random.key(seed))
    gen = Generator(cfg, mesh, axis="sp", max_seq=max_seq)
    per_req = -(-max_seq // page_size)
    dt = 0.05  # virtual seconds per fleet step

    class _Clock:
        now = 0.0

        def __call__(self):
            return self.now

    def make_fleet(clock, root, *, ingress, autoscale, brownout):
        def factory(d):
            eng = ServeEngine(
                gen, params, num_blocks=1 + per_req * batch,
                page_size=page_size, max_batch=batch,
                prefill_chunk=max(8, page_size), clock=clock,
                max_queue=4 * batch, class_aware=True,
                brownout=brownout, snapshot_dir=d)
            if warmup:
                eng.warmup()
            return eng
        return FleetController(factory, n_replicas, root=root,
                               clock=clock, suspect_after_s=1e6,
                               dead_after_s=2e6, seed=seed,
                               ingress=ingress, autoscale=autoscale)

    # --- calibration: closed-loop service rate on the virtual clock ----
    cal_clock = _Clock()
    cal_root = tempfile.mkdtemp(prefix="bench_overload_cal_")
    fc = make_fleet(cal_clock, cal_root, ingress=None, autoscale=None,
                    brownout=None)
    rng = np.random.default_rng(seed)
    sp = SamplingParams(max_new_tokens=new_tokens)
    n_cal = 2 * n_replicas * batch
    for i in range(n_cal):
        fc.submit(Request(f"c{i}", rng.integers(0, vocab, size=prompt_len)
                          .astype(np.int32), sp))
    cal_steps = 0
    while fc.has_work():
        fc.step()
        cal_clock.now += dt
        cal_steps += 1
    assert all(o.finish_reason in (FinishReason.EOS, FinishReason.LENGTH)
               for o in fc.outputs.values())
    shutil.rmtree(cal_root, ignore_errors=True)
    capacity_rps = n_cal / (cal_steps * dt)

    # --- trace-shaped workload, rescaled to overload_factor x capacity -
    wl = trace_workload(seed, n_requests, prompt_median=prompt_len,
                        prompt_sigma=0.5, output_median=new_tokens,
                        output_sigma=0.6, prompt_min=4,
                        prompt_max=2 * prompt_len, output_min=2,
                        output_max=2 * new_tokens)
    raw_rate = n_requests / max(wl[-1]["t"], 1e-9)
    target_rate = overload_factor * capacity_rps
    scale = raw_rate / target_rate
    for rec in wl:
        rec["t"] *= scale

    # ingress: per-class budget at ~60% of capacity each (1.8x total —
    # deliberately above capacity so the brownout ladder and door sheds
    # carry the rest; interactive borrows from the lower buckets)
    ingress = {"rate": 0.6 * capacity_rps,
               "burst": max(4.0, 0.6 * capacity_rps)}
    autoscale = {"min": n_replicas, "max": max_replicas,
                 "high": 0.75, "low": 0.2, "window_s": 10 * dt,
                 "dwell_steps": 2}
    brownout = {"high": 0.85, "low": 0.5, "window_s": 10 * dt,
                "dwell_steps": 2, "best_effort_cap": 2}

    clock = _Clock()
    root = tempfile.mkdtemp(prefix="bench_overload_")
    fc = make_fleet(clock, root, ingress=ingress, autoscale=autoscale,
                    brownout=brownout)
    finished: dict[str, list] = {}

    def on_finish(out):
        finished.setdefault(out.request_id, []).append(
            out.finish_reason)

    t0 = time.perf_counter()
    i = 0
    steps = 0
    rung_max = 0
    replicas_peak = n_replicas
    step_cap = 200 * (cal_steps + n_requests)
    while i < len(wl) or fc.has_work():
        while i < len(wl) and wl[i]["t"] <= clock.now:
            rec = wl[i]
            i += 1
            prompt = rng.integers(0, vocab, size=rec["prompt_len"]
                                  ).astype(np.int32)
            fc.submit(Request(
                rec["rid"], prompt,
                SamplingParams(max_new_tokens=rec["max_new"]),
                slo_class=rec["slo"], on_finish=on_finish))
        fc.step()
        clock.now += dt
        steps += 1
        live = [r for r in fc.replicas.values() if r.engine is not None]
        replicas_peak = max(replicas_peak, len(live))
        rung_max = max([rung_max] + [r.engine.brownout_rung
                                     for r in live])
        assert steps < step_cap, "overload leg failed to drain"
    wall = time.perf_counter() - t0

    # --- accounting: exactly-once terminals, no silent sheds ----------
    by_slo = {rec["rid"]: rec["slo"] for rec in wl}
    assert sorted(finished) == sorted(by_slo), (
        "missing/phantom terminal callbacks")
    assert all(len(v) == 1 for v in finished.values()), (
        "a request fired its terminal callback more than once")
    shed_by_class: dict[str, int] = {}
    healthy = (FinishReason.EOS, FinishReason.LENGTH)
    inter_total = inter_ok = inter_refused = 0
    for rec in wl:
        rid, slo = rec["rid"], rec["slo"]
        out = fc.outputs[rid]
        if out.finish_reason == FinishReason.SHED:
            shed_by_class[slo] = shed_by_class.get(slo, 0) + 1
        if slo != "interactive":
            continue
        inter_total += 1
        if out.finish_reason in healthy and (
                list(fc.streams[rid]) == list(out.token_ids)
                and len(out.token_ids) >= 1):
            inter_ok += 1
        elif out.finish_reason in (FinishReason.SHED,
                                   FinishReason.DEADLINE):
            inter_refused += 1
    counted_shed = dict(fc.aggregate_metrics().slo_stats()["shed"])
    for slo, n_shed in shed_by_class.items():
        assert counted_shed.get(slo, 0) >= n_shed, (
            f"silent shed: {slo} saw {n_shed} SHED terminals but the "
            f"per-class counter reads {counted_shed.get(slo, 0)}")
    admitted = inter_total - inter_refused
    goodput = inter_ok / admitted if admitted else 0.0
    shutil.rmtree(root, ignore_errors=True)
    return {
        "mode": "overload",
        "requests": n_requests,
        "offered_over_capacity": round(overload_factor, 2),
        "capacity_rps": round(capacity_rps, 2),
        "replicas_start": n_replicas,
        "replicas_peak": replicas_peak,
        "scale_ups": fc.scale_ups,
        "scale_downs": fc.scale_downs,
        "brownout_rung_max": rung_max,
        "shed_by_class": dict(sorted(shed_by_class.items())),
        "ingress_shed": dict(sorted(fc.ingress_shed_by_class.items())),
        "interactive_total": inter_total,
        "interactive_refused": inter_refused,
        "serve_slo_interactive_goodput": round(goodput, 4),
        "wall_s": round(wall, 4),
    }


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--horizons", default="1,8",
                   help="comma-separated decode horizons to compare")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=16)
    p.add_argument("--new-tokens", type=int, default=64)
    p.add_argument("--pipeline", type=int, default=2)
    p.add_argument("--dim", type=int, default=64)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--page-size", type=int, default=16)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--no-warmup", action="store_true")
    p.add_argument("--spec", action="store_true",
                   help="speculative mode: fused spec rounds (self-"
                        "draft, one dispatch per round) vs plain fused "
                        "decode at H=8 — reports tokens-per-dispatch "
                        "both ways and their ratio (docs/serving.md "
                        "'Speculative decoding')")
    p.add_argument("--spec-k", type=int, default=12,
                   help="--spec: speculation depth (pow2-ladder "
                        "bucketed)")
    p.add_argument("--trace", action="store_true",
                   help="flight-recorder overhead mode: the same "
                        "steady workload with tracing off vs full "
                        "detail — prints the paired tokens/s quotient "
                        "(bench.py's serve_trace_overhead; the "
                        "PERF_FLOORS.json floor holds it >= 0.95). "
                        "Combined with --fleet N: FLEET tracing "
                        "overhead (engine rings + controller ring + "
                        "router decision audit off vs full) — "
                        "bench.py's serve_fleet_trace_overhead, same "
                        "0.95 floor")
    p.add_argument("--shared-prompt", action="store_true",
                   help="prefix-cache mode: cold vs warm shared-prompt "
                        "TTFT + hit rate (docs/serving.md 'Prefix "
                        "caching') instead of the horizon sweep")
    p.add_argument("--sessions", type=int, default=None, metavar="N",
                   help="prefix-cache mode: N multi-turn sessions "
                        "(growing conversation prompts; per-turn TTFT "
                        "should stay flat while prompts grow)")
    p.add_argument("--turns", type=int, default=4,
                   help="--sessions: turns per session")
    p.add_argument("--fleet", type=int, default=None, metavar="N",
                   help="fleet mode: aggregate tokens/s at N replicas "
                        "behind the router, plus the chaos leg (one "
                        "replica killed mid-decode) with zero-loss "
                        "verification vs the single-engine oracle and "
                        "the recovery wall time (docs/serving.md "
                        "'Fleet serving'; PERF_FLOORS.json holds "
                        "serve_fleet_zero_loss at 1.0)")
    p.add_argument("--mesh", type=int, default=None, metavar="N",
                   help="sharded-engine mode: paired world-N vs "
                        "world-1 decode tokens/s on an N-device mesh "
                        "(force devices on CPU with XLA_FLAGS=--xla_"
                        "force_host_platform_device_count=N) plus the "
                        "serve_mesh_zero_loss exactness fraction — "
                        "1.0 or the sharded forwards broke "
                        "bit-exactness (PERF_FLOORS.json floor; "
                        "tokens/s informational on forced host "
                        "devices)")
    p.add_argument("--kv-shard", choices=("heads", "seq", "heads+seq"),
                   default="heads",
                   help="--mesh KV layout (docs/serving.md 'Sharded "
                        "serving'); 'heads+seq' factors N into a 2D "
                        "tp x sp mesh (docs/serving.md '2D sharded "
                        "serving')")
    p.add_argument("--kv-dtype", choices=("float32", "int8"),
                   default=None,
                   help="'int8': the quantized-serving leg — identical "
                        "warmed greedy traffic through a float32 and "
                        "an int8 engine at head_dim 64; reports the "
                        "equal-pool-bytes capacity ratio "
                        "(serve_kv_int8_capacity, floor 1.9) and the "
                        "greedy prefix match vs the float oracle "
                        "(serve_kv_int8_token_match; docs/serving.md "
                        "'Quantized serving')")
    p.add_argument("--net", action="store_true",
                   help="with --fleet N: the NETWORK chaos leg — "
                        "replicas reachable only over the serve/net.py "
                        "wire, one process killed mid-decode plus an "
                        "injected client-side partition of another "
                        "(healed at SUSPECT), zero-loss vs the oracle "
                        "(bench.py's serve_fleet_net_zero_loss, "
                        "floor 1.0)")
    p.add_argument("--corrupt", action="store_true",
                   help="state-integrity chaos mode: the network "
                        "fleet with a bitflipped journal line on one "
                        "replica, a bitflipped drain-response + "
                        "migrate_in wire manifest mid-run, and a "
                        "SIGKILL of the bit-rotted replica — salvage, "
                        "quarantine, digest rejection and recompute "
                        "must keep every stream bit-identical to the "
                        "oracle (bench.py's "
                        "serve_corrupt_recovery_zero_loss, floor 1.0; "
                        "docs/serving.md 'Durability & integrity')")
    p.add_argument("--overload", action="store_true",
                   help="bursty overload mode: a trace-shaped workload "
                        "(bursty Poisson arrivals, lognormal lengths, "
                        "50/30/20 interactive/batch/best_effort mix) "
                        "offered at --overload-factor x measured "
                        "capacity on a virtual clock through a "
                        "class-aware fleet with token-bucket ingress, "
                        "the brownout ladder and the autoscaler armed "
                        "(docs/serving.md 'Overload, SLO classes & "
                        "autoscaling'); reports "
                        "serve_slo_interactive_goodput "
                        "(PERF_FLOORS.json holds it at 1.0) plus "
                        "per-class shed counts and the peak brownout "
                        "rung")
    p.add_argument("--overload-factor", type=float, default=2.0,
                   help="--overload: offered load as a multiple of "
                        "measured fleet capacity (>= 2.0 is the "
                        "acceptance regime)")
    p.add_argument("--overload-requests", type=int, default=48,
                   help="--overload: workload size")
    p.add_argument("--disagg", default=None, metavar="P:D",
                   help="disaggregated prefill→decode tier: P prefill "
                        "+ D decode replicas vs a co-located fleet of "
                        "the same size under a long-prompt burst "
                        "(serve_disagg_itl_isolation, informational "
                        "on CPU), then the chaos leg — SIGKILL the "
                        "prefill tier mid-push and a decode replica "
                        "post-adopt — zero-loss vs the oracle "
                        "(bench.py's serve_disagg_zero_loss, floor "
                        "1.0)")
    args = p.parse_args()
    from triton_dist_tpu.runtime import configure_compile_cache

    configure_compile_cache()
    if args.sessions is not None and args.sessions < 1:
        p.error(f"--sessions must be >= 1, got {args.sessions}")
    if args.sessions is not None and args.turns < 1:
        p.error(f"--turns must be >= 1, got {args.turns}")
    if args.fleet is not None and args.fleet < 1:
        p.error(f"--fleet must be >= 1, got {args.fleet}")
    if args.net and args.fleet is None:
        p.error("--net needs --fleet N")
    if args.net and args.trace:
        p.error("--net and --trace are separate fleet legs")
    if args.mesh is not None and args.mesh < 1:
        p.error(f"--mesh must be >= 1, got {args.mesh}")
    if args.mesh is not None and (args.fleet is not None or args.net
                                  or args.trace or args.spec
                                  or args.shared_prompt
                                  or args.sessions is not None):
        p.error("--mesh is its own mode: it does not combine with "
                "--fleet/--net/--trace/--spec/--shared-prompt/"
                "--sessions")
    if args.kv_shard != "heads" and args.mesh is None:
        p.error("--kv-shard needs --mesh N")
    if args.kv_dtype is not None and (
            args.mesh is not None or args.fleet is not None or args.net
            or args.trace or args.spec or args.shared_prompt
            or args.sessions is not None or args.disagg is not None):
        p.error("--kv-dtype is its own paired leg: it does not combine "
                "with the other modes")
    if args.overload and (
            args.mesh is not None or args.fleet is not None or args.net
            or args.trace or args.spec or args.shared_prompt
            or args.sessions is not None or args.disagg is not None
            or args.kv_dtype is not None):
        p.error("--overload is its own mode: it does not combine with "
                "the other modes")
    if args.corrupt and (
            args.mesh is not None or args.fleet is not None or args.net
            or args.trace or args.spec or args.shared_prompt
            or args.sessions is not None or args.disagg is not None
            or args.kv_dtype is not None or args.overload):
        p.error("--corrupt is its own mode: it does not combine with "
                "the other modes")
    if args.corrupt:
        r = bench_corrupt(batch=args.batch, prompt_len=args.prompt_len,
                          new_tokens=args.new_tokens, dim=args.dim,
                          n_layers=args.layers,
                          page_size=args.page_size, seed=args.seed,
                          warmup=not args.no_warmup)
        print(json.dumps(r))
        print(f"# corrupt chaos: zero-loss "
              f"{r['serve_corrupt_recovery_zero_loss']:.3f} "
              f"(floor 1.0), {r['chaos_deaths']} death(s), "
              f"{r['journal_salvages']} journal salvage(s), recovery "
              f"{r['chaos_recovery_s']}s", file=sys.stderr)
        return
    if args.overload:
        if args.overload_factor < 1.0:
            p.error(f"--overload-factor must be >= 1.0, got "
                    f"{args.overload_factor}")
        if args.overload_requests < 1:
            p.error(f"--overload-requests must be >= 1, got "
                    f"{args.overload_requests}")
        r = bench_overload(batch=args.batch, prompt_len=args.prompt_len,
                           n_requests=args.overload_requests,
                           dim=args.dim, n_layers=args.layers,
                           page_size=args.page_size, seed=args.seed,
                           warmup=not args.no_warmup,
                           overload_factor=args.overload_factor)
        print(json.dumps(r))
        print(f"# overload {r['offered_over_capacity']:.1f}x capacity "
              f"({r['capacity_rps']:.1f} req/s): interactive goodput "
              f"{r['serve_slo_interactive_goodput']:.3f} (floor 1.0), "
              f"{r['interactive_refused']}/{r['interactive_total']} "
              f"interactive refused-with-receipt; shed "
              f"{r['shed_by_class']} (ingress {r['ingress_shed']}); "
              f"brownout peak rung {r['brownout_rung_max']}, replicas "
              f"{r['replicas_start']}->{r['replicas_peak']} "
              f"({r['scale_ups']} up / {r['scale_downs']} down)",
              file=sys.stderr)
        return
    if args.kv_dtype is not None:
        if args.kv_dtype == "float32":
            p.error("--kv-dtype float32 IS the baseline every other "
                    "mode runs; the paired leg wants --kv-dtype int8")
        r = bench_kv_int8(batch=args.batch, prompt_len=args.prompt_len,
                          new_tokens=args.new_tokens,
                          n_layers=args.layers,
                          page_size=args.page_size, seed=args.seed,
                          warmup=not args.no_warmup)
        print(json.dumps(r))
        print(f"# kv int8 (head_dim {r['head_dim']}): "
              f"{r['int8_bytes_per_token']:.0f} vs "
              f"{r['fp_bytes_per_token']:.0f} B/token -> capacity "
              f"{r['serve_kv_int8_capacity']:.2f}x at equal pool bytes "
              f"(floor 1.9); greedy prefix match vs float oracle "
              f"{r['serve_kv_int8_token_match']:.3f}",
              file=sys.stderr)
        return
    if args.disagg is not None:
        if (args.mesh is not None or args.fleet is not None or args.net
                or args.trace or args.spec or args.shared_prompt
                or args.sessions is not None):
            p.error("--disagg is its own mode: it does not combine "
                    "with --mesh/--fleet/--net/--trace/--spec/"
                    "--shared-prompt/--sessions")
        from triton_dist_tpu.serve.disagg import parse_disagg
        try:
            n_p, n_d = parse_disagg(args.disagg)
        except ValueError as e:
            p.error(str(e))
        r = bench_disagg(prefill=n_p, decode=n_d, batch=args.batch,
                         prompt_len=args.prompt_len,
                         new_tokens=args.new_tokens, dim=args.dim,
                         n_layers=args.layers,
                         page_size=args.page_size, seed=args.seed,
                         warmup=not args.no_warmup)
        print(json.dumps(r))
        print(f"# disagg {r['prefill']}:{r['decode']}: {r['pushes']} "
              f"pushes; chaos kill both tiers -> zero-loss "
              f"{r['serve_disagg_zero_loss']:.3f} (floor 1.0); decode "
              f"p99 ITL {r['decode_itl_p99_ms_disagg']:.2f} ms vs "
              f"co-located {r['decode_itl_p99_ms_colocated']:.2f} ms "
              f"({r['serve_disagg_itl_isolation']:.2f}x, informational "
              f"on CPU)", file=sys.stderr)
        return
    if args.mesh is not None:
        r = bench_mesh(n_devices=args.mesh, kv_shard=args.kv_shard,
                       batch=args.batch, prompt_len=args.prompt_len,
                       new_tokens=args.new_tokens,
                       n_layers=args.layers, page_size=args.page_size,
                       horizon=8, pipeline=args.pipeline,
                       seed=args.seed, warmup=not args.no_warmup)
        zl = r.get("serve_mesh_zero_loss",
                   r.get("serve_mesh2d_zero_loss"))
        print(json.dumps(r))
        print(f"# mesh N={r['devices']} ({r['kv_shard']}): zero-loss "
              f"{zl:.3f} (floor 1.0), "
              f"{r['mesh_toks_per_s']:.1f} vs world-1 "
              f"{r['world1_toks_per_s']:.1f} tokens/s "
              f"({r['mesh_vs_world1']:.2f}x, informational on forced "
              f"host devices), {r['mesh_fresh_compiles']} fresh "
              f"compiles after warmup", file=sys.stderr)
        return
    if args.net:
        r = bench_fleet_net(n_replicas=args.fleet, batch=args.batch,
                            prompt_len=args.prompt_len,
                            new_tokens=args.new_tokens, dim=args.dim,
                            n_layers=args.layers,
                            page_size=args.page_size, seed=args.seed,
                            warmup=not args.no_warmup)
        print(json.dumps(r))
        print(f"# net fleet N={r['replicas']}: chaos kill+partition -> "
              f"zero-loss {r['serve_fleet_net_zero_loss']:.3f} "
              f"(floor 1.0), {r['net_retries']} retries, recovery "
              f"{r['chaos_recovery_s']}s", file=sys.stderr)
        return
    if args.fleet is not None and args.trace:
        r = bench_fleet_trace_overhead(
            n_replicas=args.fleet, batch=args.batch,
            prompt_len=args.prompt_len, new_tokens=args.new_tokens,
            dim=args.dim, n_layers=args.layers,
            page_size=args.page_size, seed=args.seed,
            warmup=not args.no_warmup)
        print(json.dumps(r))
        print(f"# fleet tracing on {r['fleet_toks_per_s_trace_on']:.1f} "
              f"vs off {r['fleet_toks_per_s_trace_off']:.1f} tokens/s "
              f"({r['serve_fleet_trace_overhead']:.3f}x — floor 0.95)",
              file=sys.stderr)
        return
    if args.fleet is not None:
        r = bench_fleet(n_replicas=args.fleet, batch=args.batch,
                        prompt_len=args.prompt_len,
                        new_tokens=args.new_tokens, dim=args.dim,
                        n_layers=args.layers,
                        page_size=args.page_size, seed=args.seed,
                        warmup=not args.no_warmup)
        print(json.dumps(r))
        print(f"# fleet N={r['replicas']}: "
              f"{r['fleet_toks_per_s']:.1f} tokens/s; chaos kill -> "
              f"zero-loss {r['serve_fleet_zero_loss']:.3f} (floor 1.0), "
              f"recovery {r['chaos_recovery_s']}s", file=sys.stderr)
        return
    if args.trace:
        r = bench_trace_overhead(batch=args.batch,
                                 prompt_len=args.prompt_len,
                                 new_tokens=args.new_tokens,
                                 pipeline=args.pipeline, dim=args.dim,
                                 n_layers=args.layers,
                                 page_size=args.page_size,
                                 seed=args.seed,
                                 warmup=not args.no_warmup)
        print(json.dumps(r))
        print(f"# tracing on {r['toks_per_s_trace_on']:.1f} vs off "
              f"{r['toks_per_s_trace_off']:.1f} decode tokens/s "
              f"({r['serve_trace_overhead']:.3f}x — floor 0.95)",
              file=sys.stderr)
        return
    if args.spec:
        if args.spec_k < 1:
            p.error(f"--spec-k must be >= 1, got {args.spec_k}")
        r = bench_spec(k=args.spec_k, batch=args.batch,
                       prompt_len=args.prompt_len,
                       new_tokens=args.new_tokens,
                       pipeline=args.pipeline, dim=args.dim,
                       n_layers=args.layers, page_size=args.page_size,
                       seed=args.seed, warmup=not args.no_warmup)
        print(json.dumps(r))
        print(f"# spec {r['spec_tokens_per_dispatch']:.2f} vs plain "
              f"{r['plain_tokens_per_dispatch']:.2f} tokens/dispatch "
              f"({r['spec_vs_plain_tokens_per_dispatch']:.2f}x), accept "
              f"rate {r['accept_rate']:.2f}, "
              f"{r['dispatches_per_token']:.4f} dispatches/token",
              file=sys.stderr)
        return
    if args.shared_prompt:
        r = bench_prefix(batch=args.batch,
                         prompt_len=max(args.prompt_len, 128),
                         new_tokens=args.new_tokens, dim=args.dim,
                         n_layers=args.layers, page_size=args.page_size,
                         seed=args.seed, warmup=not args.no_warmup,
                         horizon=max(int(args.horizons.split(",")[0]), 1))
        print(json.dumps(r))
        print(f"# warm TTFT {r['ttft_warm_ms']:.2f} ms vs cold "
              f"{r['ttft_cold_ms']:.2f} ms "
              f"({r['ttft_warm_over_cold']:.3f}x), hit rate "
              f"{r['hit_rate']:.2f}", file=sys.stderr)
        return
    if args.sessions is not None:
        r = bench_sessions(n_sessions=args.sessions, n_turns=args.turns,
                           new_tokens=args.new_tokens, dim=args.dim,
                           n_layers=args.layers,
                           page_size=args.page_size, seed=args.seed,
                           warmup=not args.no_warmup)
        print(json.dumps(r))
        print(f"# per-turn TTFT {r['ttft_by_turn_ms']} ms, per-turn hit "
              f"rate {r['hit_rate_by_turn']}", file=sys.stderr)
        return
    results = {}
    for h in (int(x) for x in args.horizons.split(",")):
        r = bench_engine(h, batch=args.batch, prompt_len=args.prompt_len,
                         new_tokens=args.new_tokens,
                         pipeline=args.pipeline, dim=args.dim,
                         n_layers=args.layers, page_size=args.page_size,
                         seed=args.seed, warmup=not args.no_warmup)
        results[f"h{h}"] = r
        print(json.dumps(r))
    hs = sorted(results, key=lambda k: results[k]["horizon"])
    if len(hs) >= 2:
        lo, hi = results[hs[0]], results[hs[-1]]
        print(f"# H={hi['horizon']} vs H={lo['horizon']}: "
              f"{hi['decode_toks_per_s']:.1f} vs "
              f"{lo['decode_toks_per_s']:.1f} decode tokens/s "
              f"({hi['decode_toks_per_s'] / max(lo['decode_toks_per_s'], 1e-9):.2f}x), "
              f"dispatches/token {hi['dispatches_per_token']:.3f} vs "
              f"{lo['dispatches_per_token']:.3f}", file=sys.stderr)


if __name__ == "__main__":
    main()
