"""Chip smoke: the serving engine, end to end, on the TPU it was written for.

The quickest proof that the system still starts on the chip.  One process
(a chip belongs to one process at a time) builds the engine through the
constructors a user calls — ``LlamaConfig.llama3_8b()`` widths unchanged,
depth cut to what fits beside the KV pool, bf16, weights from ``--seed`` —
warms it up, serves a handful of requests to their full budgets, and checks
what came out by the repo's own means:

- every request retires ``LENGTH``/``EOS`` with exactly its token budget,
  every token inside the vocabulary, the block manager's free list whole;
- zero fresh compiles after ``engine.warmup()`` — by the engine's own
  count of its programs, and by jax's count of every executable the
  process asked XLA for while the requests were served;
- the programs the engine SERVED WITH, re-lowered from the signatures it
  was called with, hold one Mosaic custom call per layer for the decode
  horizon (every rung, the one-step link of a clamped step included) and
  chunked prefill — and the engine's own
  construction-time kernel-reach report is empty.  A run that "works" on
  XLA fallbacks has exercised no kernel of this repo and fails here;
- for one short prompt, the prefill-last-position and first-decode logits
  the served programs compute are finite and agree with the repo's XLA
  path (a twin engine over ``Generator(..., impl="xla")``, same weights,
  whose programs must hold no Mosaic call at all);
- the served KV pools are finite in every layer.

With four chips it goes on, in the same process, to the mesh legs:
``kv_shard="heads"`` at all 32 layers (weights and pools resident on four
devices, none holding the model alone), then one short run each of ``seq``
and ``heads+seq`` (2x2).  ``--chips 4`` makes their absence an error.

Off a TPU it refuses to start (exit 1, no result line).  The only other way
in is ``--cpu-dryrun``: the same legs at a toy size with the kernels in the
Pallas interpreter, for tests/test_chip_smoke.py — it proves the script, not
the chip, and says so in its result line.

Set-up figures (compile seconds — cold on an empty compile cache, warm on a
second run in the same checkout — warm-up program count, HBM peak, wall
time) are reported as set-up, never as a speed, on the ``report`` line.  The
last line of stdout is one JSON object: ``{"ok": true, "device": {...}}``.

    python chip_smoke.py [--seed N] [--chips 4]
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import faulthandler
import gc
import json
import logging
import os
import sys
import time

import numpy as np

# Where two logit vectors computed from the same bf16 weights may differ.
# Both paths multiply bf16 inputs with float32 accumulation; they differ in
# the attention inner loop (the Pallas kernels feed P to the MXU in bf16 and
# merge blocks by online softmax, the XLA path runs one float32 softmax), and
# every layer rounds the residual stream back to bf16 (8 mantissa bits, one
# step = 2**-8 of the value), so a one-step flip in a layer is carried
# through the rest.  Independent flips add in quadrature: the difference
# between the two paths grows like sqrt(n_layers) steps, and the logits are
# O(1) (a normalised residual through an lm_head scaled by 1/sqrt(dim)), so
# the largest of 128k logit differences is a few steps times sqrt(layers).
# Bound: LOGIT_STEPS bf16 steps per sqrt(layer).  The v5e measured 0.084 at
# 16 layers and 0.110 at 32 (prefill-last; chip runs, PR 21) — 5.4 and 5.0
# steps per sqrt(layer) — so 12 is a little over twice what one seed showed:
# 0.19 at 16 layers, 0.27 at 32.  A wrong mask, page or offset moves logits
# by O(1), several times the bound.
LOGIT_STEPS = 12


def logit_bound(n_layers: int) -> float:
    return LOGIT_STEPS * 2.0 ** -8 * n_layers ** 0.5


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class CompileTally:
    """Every executable XLA was asked for in this process, counted where
    jax itself reports it — the engine's ``compile_misses`` sees only its
    own registered programs, not the small eager ops around them.
    ``seconds`` is time spent compiling OR fetching from the persistent
    cache; ``cache_hits`` says how many were fetches."""

    def __init__(self):
        from jax import monitoring

        self.requests, self.seconds, self.cache_hits = 0, 0.0, 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += secs

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"requests": self.requests,
                "seconds": round(self.seconds, 1),
                "cache_hits": self.cache_hits}

    @contextlib.contextmanager
    def named(self):
        """While open, collect jax's own line for every executable it
        compiles or fetches (``jax_log_compiles``): a gate that counts
        must also name."""
        import jax

        names = []
        handler = logging.Handler()
        handler.emit = lambda rec: names.append(rec.getMessage())
        logger = logging.getLogger("jax._src.dispatch")
        logger.addHandler(handler)
        logger.propagate = False    # to ``names`` only, not to stderr
        jax.config.update("jax_log_compiles", True)
        try:
            yield names
        finally:
            jax.config.update("jax_log_compiles", False)
            logger.propagate = True
            logger.removeHandler(handler)


def fail(msg: str):
    raise SystemExit(f"[chip_smoke] FAIL: {msg}")


def parse_args():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0,
                   help="weights, prompts and sampling all derive from it")
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: the mesh legs are required, not opportunistic")
    p.add_argument("--legs", default=None, metavar="NAME[,NAME]",
                   help="run only these of the available legs (one_chip, "
                        "mesh_heads, mesh_seq, mesh_heads_seq): a four-chip "
                        "minute costs four, so a repaired leg is re-run "
                        "alone.  Default: every leg the devices allow")
    p.add_argument("--cpu-dryrun", action="store_true",
                   help="toy size, kernels in the Pallas interpreter, on "
                        "the CPU: exercises this script, not the chip")
    return p.parse_args()


# ---------------------------------------------------------------------------
# One leg: build, warm up, serve, check
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Leg:
    name: str
    n_layers: int
    max_seq: int
    num_blocks: int
    n_requests: int
    prompt_lens: tuple          # (lo, hi) tokens
    max_new: int
    mesh_shape: tuple = ()      # () = world 1; (4,) or (2, 2)
    kv_shard: str = "heads"
    ladder_base: int = 0        # 0 = the engine's own ladder
    # seconds the leg may take, cold compiles included, before it is a
    # hang (measured cold on the v5e, PR 21: one_chip 222 s, mesh_heads
    # 444 s)
    timeout_s: int = 600


def build_engine(leg: Leg, base_cfg, seed: int, *, interpret: bool,
                 impl: str = "auto", params=None, num_blocks=None):
    """The calls ``examples/serve.py --engine`` makes, at ``leg``'s size.
    Weights first, pools second: ``init_params`` holds float32
    transients of the two vocabulary matrices while it draws them."""
    import jax
    from jax.sharding import Mesh

    from triton_dist_tpu.models import llama
    from triton_dist_tpu.models.generate import Generator
    from triton_dist_tpu.serve import ServeEngine
    from triton_dist_tpu.serve.engine import build_bucket_ladder

    cfg = dataclasses.replace(base_cfg, n_layers=leg.n_layers,
                              max_seq=leg.max_seq)
    devs = jax.devices()
    mesh = None
    kw = {}
    if leg.mesh_shape:
        n = int(np.prod(leg.mesh_shape))
        axes = ("tp", "sp")[:len(leg.mesh_shape)]
        mesh = Mesh(np.array(devs[:n]).reshape(leg.mesh_shape), axes)
        kw = dict(mesh=mesh, kv_shard=leg.kv_shard)
    if params is None:
        shardings = None
        if mesh is not None and leg.kv_shard != "seq":
            # TP layouts: draw each leaf on its mesh layout — the whole
            # model on device 0 first is exactly what does not fit
            shardings = llama.param_shardings(cfg, mesh, "tp")
        params = llama.init_params(cfg, jax.random.key(seed), shardings)
        jax.block_until_ready(params)
    gen = Generator(cfg, Mesh(np.array(devs[:1]), ("sp",)), axis="sp",
                    max_seq=leg.max_seq, impl=impl, interpret=interpret)
    if leg.ladder_base:
        # seq layouts attend over a 1/sp row span of the prefill scratch:
        # start the extent ladder where that span still tiles the kernel
        kw["bucket_ladder"] = build_bucket_ladder(leg.ladder_base,
                                                  leg.max_seq, 128)
    engine = ServeEngine(gen, params, num_blocks=num_blocks or leg.num_blocks,
                         page_size=128, prefill_chunk=128, max_batch=8,
                         horizon=8, pipeline=2, **kw)
    return engine


def serve_traffic(engine, leg: Leg, cfg, seed: int,
                  tally: CompileTally) -> dict:
    """``leg.n_requests`` seeded requests, every third one sampled (so the
    mixed-sampler horizon program runs beside the greedy one), stepped to
    completion.  Returns the checks' inputs."""
    from triton_dist_tpu.serve import Request, SamplingParams
    from triton_dist_tpu.serve.request import FinishReason

    rng = np.random.default_rng(seed)
    lo, hi = leg.prompt_lens
    reqs = []
    for i in range(leg.n_requests):
        n = int(rng.integers(lo, hi + 1))
        sampled = i % 3 == 1
        reqs.append(Request(
            f"r{i}", rng.integers(0, cfg.vocab, size=n).astype(np.int32),
            SamplingParams(max_new_tokens=leg.max_new,
                           temperature=0.8 if sampled else 0.0,
                           top_k=64 if sampled else None,
                           top_p=0.95 if sampled else None,
                           seed=seed + i)))
    misses0 = engine.metrics.compile_misses
    xla0 = tally.snapshot()
    t0 = time.perf_counter()
    with tally.named() as compiled:
        for r in reqs:
            engine.submit(r)
        outs = engine.run()
    wall = time.perf_counter() - t0
    xla1 = tally.snapshot()
    for r in reqs:
        out = outs.get(r.request_id)
        if out is None:
            fail(f"{leg.name}: {r.request_id} never retired")
        if out.finish_reason not in (FinishReason.LENGTH, FinishReason.EOS):
            fail(f"{leg.name}: {r.request_id} finished "
                 f"{out.finish_reason} ({out.error})")
        toks = np.asarray(out.token_ids)
        if toks.shape[0] != leg.max_new:
            fail(f"{leg.name}: {r.request_id} emitted {toks.shape[0]} "
                 f"tokens, budget {leg.max_new}")
        if toks.min() < 0 or toks.max() >= cfg.vocab:
            fail(f"{leg.name}: {r.request_id} emitted a token outside "
                 f"[0, {cfg.vocab})")
    no_fresh_compiles(engine, misses0, leg, "serving after warmup()")
    # ... and what the engine's own counter cannot see: ANY executable
    # XLA was asked for under traffic, compiled or fetched from the
    # persistent cache — the eager ops around the registered programs.
    # The first sampled request once compiled its sampler here for 18 s
    # with compile_misses flat (v5e, PR 21).
    if xla1["requests"] != xla0["requests"]:
        fail(f"{leg.name}: {xla1['requests'] - xla0['requests']} "
             f"executables were compiled or fetched under traffic, after "
             f"warmup(): " + "; ".join(
                 m for m in compiled if "XLA compilation" in m))
    if engine.bm.num_free != engine.bm.num_allocatable:
        fail(f"{leg.name}: free list not whole after traffic: "
             f"{engine.bm.num_free} of {engine.bm.num_allocatable}")
    return {"requests": len(reqs),
            "prompt_tokens": int(sum(r.prompt.shape[0] for r in reqs)),
            "new_tokens": leg.max_new * len(reqs),
            "sampled_requests": sum(not r.params.greedy for r in reqs),
            "traffic_wall_s": round(wall, 2)}


def no_fresh_compiles(engine, misses0: int, leg: Leg, when: str) -> None:
    fresh = engine.metrics.compile_misses - misses0
    if fresh:
        fail(f"{leg.name}: {fresh} fresh compiles while {when} "
             f"({engine.metrics.compile_stats()['programs']})")


def engine_logits(engine, prompt, first_token=None):
    """(prefill-last-position logits, first-decode logits, first token) of
    ``prompt``, computed by calling — in the order the step loop does — the
    very programs ``engine`` prefills with (chunked prefill into a scratch,
    the page scatter) and then ``paged_decode`` on the next token: the one
    program that hands back a decode step's logits, over the same forward
    the horizon's links scan."""
    import jax.numpy as jnp

    page = engine.page
    n = int(prompt.shape[0])
    chunk = engine.prefill_width     # rows of one prefill call
    ext = engine._bucket_s_ext(n)
    scratch = engine._zero_fn(s_ext=ext)
    for pos in range(0, n, chunk):
        c = min(chunk, n - pos)
        buf = np.zeros((1, chunk), np.int32)
        buf[0, :c] = prompt[pos:pos + c]
        scratch, logits = engine._chunk_fn(
            engine.params, jnp.asarray(buf), scratch, np.int32(pos),
            quantized=False, extent=ext, n_valid=np.int32(c))
    prefill_last = np.asarray(logits[0, 0], np.float32)   # the kept row
    if first_token is None:
        first_token = int(prefill_last.argmax())
    rid = "__chip_smoke_ref"
    engine.bm.allocate(rid, n + 1)
    try:
        ids = np.zeros((ext // page,), np.int32)
        k = engine.bm.blocks_for(n)
        ids[:k] = engine.bm.table(rid)[:k]
        engine._pools = engine._fill_fn(engine._pools, scratch,
                                        jnp.asarray(ids))
        B = engine.max_batch
        tables = np.zeros((B, engine.n_pages_max), np.int32)
        tables[0] = engine.bm.padded_table(rid, engine.n_pages_max)
        lens = np.zeros((B,), np.int32)
        tokens = np.zeros((B,), np.int32)
        active = np.zeros((B,), bool)
        lens[0], tokens[0], active[0] = n, first_token, True
        engine._pools, dec = engine._decode_fn(
            engine.params, engine._pools, jnp.asarray(tables),
            jnp.asarray(lens), jnp.asarray(tokens), jnp.asarray(active))
    finally:
        engine.bm.free(rid)
    return prefill_last, np.asarray(dec[0], np.float32), first_token


# The programs this engine serves with that attend (docs/serving.md
# "Kernel reach"): an engine with a horizon decodes through its links
# alone, so ``paged_decode`` is not among them.
ATTENTION_PROGRAMS = ("decode_horizon", "prefill_chunk")


def check_mosaic(engine, leg: Leg, *, interpret: bool) -> dict:
    """One Mosaic custom call per layer in each attention program the
    engine served with (two under seq / heads+seq: the paged kernel and the
    SP combine), read from the lowered StableHLO — and none announced
    missing by the engine itself."""
    from triton_dist_tpu.analysis.jaxpr_audit import lowered_mosaic_calls

    if engine.kernel_gaps:
        fail(f"{leg.name}: the engine reports attention off the Pallas "
             f"kernels: {engine.kernel_gaps}")
    if interpret:
        return {}   # the interpreter lowers kernels to plain HLO
    calls = lowered_mosaic_calls(engine)
    per_layer = 2 if leg.kv_shard in ("seq", "heads+seq") and \
        leg.mesh_shape else 1
    want = per_layer * leg.n_layers
    for prog in ATTENTION_PROGRAMS:
        counts = calls.get(prog)
        if not counts:
            fail(f"{leg.name}: {prog} was never called — nothing to "
                 f"lower (programs seen: {sorted(calls)})")
        if min(counts) < want:
            fail(f"{leg.name}: {prog} lowered with {counts} Mosaic "
                 f"calls per signature, want >= {want} ({per_layer} per "
                 f"layer x {leg.n_layers}): an attention kernel was not "
                 f"traced")
    return {p: calls[p] for p in ATTENTION_PROGRAMS}


def check_reference(engine, leg: Leg, base_cfg, seed: int, *,
                    interpret: bool) -> dict:
    """Served-program logits vs the XLA path, and finiteness."""
    cfg = engine.cfg
    import jax
    import jax.numpy as jnp

    from triton_dist_tpu.analysis.jaxpr_audit import lowered_mosaic_calls

    rng = np.random.default_rng(seed + 1000)
    n = min(2 * 128 + 37, leg.max_seq - 2)      # 2 full chunks + a residual
    prompt = rng.integers(0, cfg.vocab, size=n).astype(np.int32)
    misses0 = engine.metrics.compile_misses
    decode0 = engine._decode_fn.misses
    got_p, got_d, tok = engine_logits(engine, prompt)
    # the prefill calls above are the step loop's own, so they hit its
    # programs; the single-step decode is not (the horizon's links hand
    # back tokens, never logits): the forward they scan compiles here
    no_fresh_compiles(engine, misses0 + engine._decode_fn.misses - decode0,
                      leg, "replaying the step loop's calls for the "
                      "reference check")
    # built on the engine's own (mesh-placed) weights: shared buffers,
    # not a second copy
    twin = build_engine(leg, base_cfg, seed, interpret=False, impl="xla",
                        params=engine.params,
                        num_blocks=max(8, engine.bm.shards * 4))
    ref_p, ref_d, _ = engine_logits(twin, prompt, first_token=tok)
    if not interpret:
        stray = {p: c for p, c in lowered_mosaic_calls(twin).items()
                 if max(c)}
        if stray:
            fail(f"{leg.name}: the XLA reference holds Mosaic calls "
                 f"{stray}: it is not a reference")
    out = {}
    for what, got, ref in (("prefill_last", got_p, ref_p),
                           ("first_decode", got_d, ref_d)):
        if got.shape != (cfg.vocab,):
            fail(f"{leg.name}: {what} logits have shape {got.shape}")
        if not np.isfinite(got).all():
            fail(f"{leg.name}: {what} logits are not finite")
        err = float(np.abs(got - ref).max())
        bound = logit_bound(leg.n_layers)
        if not err <= bound:
            fail(f"{leg.name}: {what} logits differ from the XLA path by "
                 f"{err:.4f} > {bound:.4f} (max |ref| "
                 f"{np.abs(ref).max():.3f})")
        out[f"{what}_max_abs_err"] = round(err, 5)
        out[f"{what}_max_abs_ref"] = round(float(np.abs(ref).max()), 3)
        out[f"{what}_argmax_agrees"] = bool(got.argmax() == ref.argmax())
    finite = jax.jit(lambda t: jnp.stack(
        [jnp.isfinite(x.astype(jnp.float32)).all()
         for x in jax.tree_util.tree_leaves(t)]).all())(engine._pools)
    if not bool(finite):
        fail(f"{leg.name}: the served KV pools hold non-finite values")
    return out


def memory_report(n_devices: int) -> list:
    import jax

    rep = []
    for d in jax.devices()[:n_devices]:
        st = d.memory_stats() or {}
        rep.append({"id": d.id,
                    "bytes_in_use": st.get("bytes_in_use"),
                    "peak_bytes_in_use": st.get("peak_bytes_in_use")})
    return rep


def run_leg(leg: Leg, base_cfg, seed: int, tally: CompileTally, *,
            interpret: bool) -> dict:
    log(f"--- leg {leg.name}: {leg.n_layers} layers, "
        f"mesh {leg.mesh_shape or 1} {leg.kv_shard if leg.mesh_shape else ''}")
    # A wrong credit or barrier on hardware is a hang, not an exception:
    # past the limit, dump every thread's stack and leave (exit code 1).
    faulthandler.dump_traceback_later(leg.timeout_s, exit=True)
    t_leg = time.perf_counter()
    engine = build_engine(leg, base_cfg, seed, interpret=interpret)
    cfg = engine.cfg
    t_built = time.perf_counter()
    n_dev = int(np.prod(leg.mesh_shape)) if leg.mesh_shape else 1
    mem = memory_report(n_dev)
    if n_dev > 1 and all(m["bytes_in_use"] for m in mem):
        held = [m["bytes_in_use"] for m in mem]
        if max(held) > 1.25 * min(held):
            fail(f"{leg.name}: weights and pools are not spread over the "
                 f"mesh: bytes_in_use per device {held}")
    w = engine.warmup()
    log(f"{leg.name}: warmup compiled {w['programs']} programs in "
        f"{w['seconds']:.1f} s")
    res = {"layers": leg.n_layers, "mesh": list(leg.mesh_shape) or [1],
           "kv_shard": leg.kv_shard if leg.mesh_shape else None,
           "kv_token_slots": engine.bm.num_allocatable * engine.page,
           "build_s": round(t_built - t_leg, 1),
           "warmup_programs": w["programs"],
           # warm-up wall = compile + the dummy traffic that drives it;
           # compile_s = wall time inside the calls that compiled
           "warmup_s": round(w["seconds"], 1),
           "compile_s": round(
               engine.metrics.compile_stats()["total_compile_time_s"], 1),
           "ladder": list(engine.ladder)}
    res.update(serve_traffic(engine, leg, cfg, seed, tally))
    res["mosaic_calls"] = check_mosaic(engine, leg, interpret=interpret)
    # how the paged decode call is blocked on this leg's ranks
    res["paged_attn_blocking"] = engine.paged_attn_blocking
    res.update(check_reference(engine, leg, base_cfg, seed,
                               interpret=interpret))
    res["memory_after_build"] = mem
    res["memory_at_end"] = memory_report(n_dev)
    res["leg_wall_s"] = round(time.perf_counter() - t_leg, 1)
    faulthandler.cancel_dump_traceback_later()
    log(f"{leg.name}: OK {json.dumps(res)}")
    return res


# ---------------------------------------------------------------------------


def main() -> int:
    args = parse_args()
    t_start = time.perf_counter()
    # First thing, before any backend exists: where compiled programs go.
    from triton_dist_tpu.runtime.bootstrap import (
        configure_compile_cache,
        require_tpu,
    )

    cache_dir = configure_compile_cache()
    import jax
    import jax.numpy as jnp

    from triton_dist_tpu.models.llama import LlamaConfig

    if args.cpu_dryrun:
        if jax.devices()[0].platform == "tpu":
            fail("--cpu-dryrun is for a host with no chip; on a TPU run "
                 "the real thing")
    else:
        require_tpu("chip_smoke.py", n_devices=args.chips)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    import jaxlib
    from importlib import metadata

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    log(f"device {device}; jax {jax.__version__}, jaxlib "
        f"{jaxlib.__version__}, libtpu {libtpu}; compile cache "
        f"{cache_dir} holds {cached} entries; seed {args.seed}")

    if args.cpu_dryrun:
        # Same kernels (head_dim 128, page 128, chunk 128), toy
        # everything else; interpret mode stands in for Mosaic.
        base = LlamaConfig(vocab=512, dim=256, n_layers=2, n_heads=2,
                           n_kv_heads=2, ffn_dim=512, dtype=jnp.bfloat16)
        legs = [Leg("one_chip", 2, 512, 17, 3, (130, 300), 10)]
        if len(jax.devices()) >= 4:
            legs += [
                Leg("mesh_heads", 2, 512, 17, 2, (130, 300), 10,
                    mesh_shape=(2,), kv_shard="heads"),
                Leg("mesh_heads_seq", 2, 512, 18, 2, (130, 300), 10,
                    mesh_shape=(2, 2), kv_shard="heads+seq",
                    ladder_base=256),
            ]
    else:
        base = LlamaConfig.llama3_8b()
        # 16 whole layers = 8.5 GiB of bf16 weights; 257 blocks = 256
        # allocatable x 128 = 32,768 token slots (64 KiB/token at 16
        # layers: 2 GiB) beside them on a 16 GB chip.
        legs = [Leg("one_chip", 16, 2048, 257, 8, (200, 1500), 64)]
        if len(jax.devices()) >= 4:
            legs += [
                # all 32 layers: 5.2 GiB of weights per chip TP-sharded
                # (embed and lm_head replicate), 128 KiB/token of KV
                # spread over four chips
                Leg("mesh_heads", 32, 2048, 257, 8, (200, 1500), 64,
                    mesh_shape=(4,), kv_shard="heads", timeout_s=900),
                # short runs: replicated (seq) or half-sharded weights,
                # so depth 4; max_seq 1024 puts 2 logical pages on each
                # of seq's 4 ranks, so these prompts span all of them
                Leg("mesh_seq", 4, 1024, 68, 4, (300, 900), 32,
                    mesh_shape=(4,), kv_shard="seq", ladder_base=512,
                    timeout_s=420),
                Leg("mesh_heads_seq", 4, 1024, 66, 4, (300, 900), 32,
                    mesh_shape=(2, 2), kv_shard="heads+seq",
                    ladder_base=256, timeout_s=420),
            ]
    if args.legs:
        want = args.legs.split(",")
        unknown = sorted(set(want) - {leg.name for leg in legs})
        if unknown:
            fail(f"--legs {unknown}: not among this host's legs "
                 f"{[leg.name for leg in legs]}")
        legs = [leg for leg in legs if leg.name in want]
    tally = CompileTally()
    results = {}
    for leg in legs:
        results[leg.name] = run_leg(leg, base, args.seed, tally,
                                    interpret=args.cpu_dryrun)
        gc.collect()    # the engine is a reference cycle holding HBM

    report = {
        "cpu_dryrun": bool(args.cpu_dryrun),
        "seed": args.seed,
        "legs_run": list(results),
        "legs": results,
        # set-up, not speed
        "compile_s_total": round(sum(r["compile_s"]
                                     for r in results.values()), 1),
        # every executable of the process, compiled or fetched
        "xla_requests": tally.snapshot(),
        "compile_cache_dir": cache_dir,
        "compile_cache_entries_at_start": cached,
        "wall_s": round(time.perf_counter() - t_start, 1),
    }
    log(f"report {json.dumps(report)}")
    result = {"ok": True, "device": device}
    if args.cpu_dryrun:
        result["cpu_dryrun"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
