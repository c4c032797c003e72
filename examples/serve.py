"""Serving CLI: prefill + autoregressive decode over the kernel stack.

The inference-side twin of examples/train.py, wiring the serving
subsystems end-to-end:

- dense Llama or MoE families (``--model``), weights replicated except
  the MoE expert stacks (EP-sharded) and the sequence-sharded KV cache;
- decode through the SP flash-decode layer each step;
- sampling knobs: ``--temperature`` / ``--top-k`` / ``--top-p``
  (temperature 0 = greedy), reproducible under ``--seed``;
- optional W8A8 quantized prompt scoring for the dense family
  (``--w8a8``: per-channel int8 weights, int8 over the AG-GEMM ring);
- quantized serving (``--kv-dtype int8`` with ``--engine``/``--fleet``/
  ``--disagg``): int8 paged KV pools with per-page-slot scales, same
  streams every run (docs/serving.md "Quantized serving").

Runs anywhere, TPU or the virtual CPU mesh:

    env JAX_PLATFORMS=cpu \
      XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/serve.py --model moe --batch 2 --prompt-len 8 \
      --new-tokens 16 --temperature 0.8 --top-p 0.95
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--model", choices=("llama", "moe"), default="llama")
    p.add_argument("--batch", type=int, default=2)
    p.add_argument("--prompt-len", type=int, default=8)
    p.add_argument("--new-tokens", type=int, default=16)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--top-k", type=int, default=None)
    p.add_argument("--top-p", type=float, default=None)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--w8a8", action="store_true",
                   help="also score the prompt with the W8A8 forward "
                        "(dense family only) and report logit agreement")
    p.add_argument("--kv-int8", action="store_true",
                   help="int8 KV cache (half the memory, ~1.55x decode)")
    p.add_argument("--kv-dtype", choices=("float32", "int8"),
                   default="float32",
                   help="serving modes (--engine/--fleet/--disagg): "
                        "paged KV pool dtype.  'int8' stores pages as "
                        "int8 with per-(block, head, page-slot) f32 "
                        "scales — ~4x the resident sessions per pool "
                        "byte at head_dim 64, same streams every run "
                        "(docs/serving.md 'Quantized serving').  The "
                        "bare generation demo uses --kv-int8 instead")
    p.add_argument("--chunk-prefill", type=int, default=None, metavar="C",
                   help="prefill in C-token chunks (bounded memory)")
    p.add_argument("--speculative", type=int, default=None, metavar="K",
                   help="speculative decoding with a K-token draft (a "
                        "small same-vocab draft model; greedy at "
                        "temperature 0, rejection sampling otherwise; "
                        "batch > 1 rides the q_lens multi-token verify "
                        "kernel and needs a world-1 mesh)")
    p.add_argument("--spec-adaptive", type=int, default=None,
                   metavar="W",
                   help="engine mode with --speculative: adaptive "
                        "per-row speculation depth from a W-round "
                        "acceptance window (docs/serving.md "
                        "'Speculative decoding'; 0 pins k fixed; "
                        "default: the engine's window of 8)")
    p.add_argument("--engine", action="store_true",
                   help="continuous-batching serving engine "
                        "(triton_dist_tpu/serve): staggered multi-"
                        "request traffic over a paged KV cache with "
                        "iteration-level scheduling; dense family, "
                        "world-1 (docs/serving.md)")
    p.add_argument("--requests", type=int, default=8,
                   help="engine mode: number of requests to drive")
    p.add_argument("--mixed", action="store_true",
                   help="engine mode: sweep prompt lengths across the "
                        "shape-bucket ladder (one short/one long per "
                        "rung) instead of sampling them — demos that "
                        "O(ladder) compiled programs cover every "
                        "length; prints trace-cache stats")
    p.add_argument("--warmup", action="store_true",
                   help="engine mode: engine.warmup() before traffic "
                        "(pre-compiles the bucket ladder; steady-state "
                        "serving then never compiles)")
    p.add_argument("--horizon", type=int, default=1, metavar="H",
                   help="engine mode: fuse up to H decode steps into one "
                        "device dispatch with on-device sampling (the "
                        "decode horizon, docs/serving.md — streams stay "
                        "bit-identical to H=1; watch dispatches/token "
                        "drop in the decode stats line)")
    p.add_argument("--pipeline", type=int, default=2, metavar="N",
                   help="engine mode: chain N horizon dispatches with a "
                        "device-resident carry so the host commits "
                        "horizon k's tokens while the device runs "
                        "horizon k+1 (only engages at --horizon > 1)")
    p.add_argument("--mesh", type=int, default=None, metavar="N",
                   help="engine mode: place the engine on an N-device "
                        "mesh — TP-sharded weights + sharded paged KV "
                        "under shard_map (docs/serving.md 'Sharded "
                        "serving'); streams stay bit-identical to the "
                        "world-1 engine.  An error when the runtime "
                        "exposes fewer than N devices (for the CPU "
                        "tests force them with XLA_FLAGS="
                        "--xla_force_host_platform_device_count=N)")
    p.add_argument("--kv-shard", choices=("heads", "seq", "heads+seq"),
                   default="heads",
                   help="--mesh KV layout: 'heads' shards the pools by "
                        "KV head (Megatron TP attention), 'seq' shards "
                        "by block — each rank owns a contiguous "
                        "sequence span and attention runs the SP "
                        "flash-decode combine (long-context scaling), "
                        "'heads+seq' factors the mesh 2D — weights and "
                        "heads TP-shard over the tp axis while the "
                        "paged KV shards by block over the sp axis "
                        "(pod-scale serving; docs/serving.md '2D "
                        "sharded serving')")
    p.add_argument("--stagger", type=int, default=2,
                   help="engine mode: submit a new request every "
                        "S engine steps")
    p.add_argument("--max-batch", type=int, default=4,
                   help="engine mode: decode batch slots")
    p.add_argument("--page-size", type=int, default=16,
                   help="engine mode: KV page size (tokens per block).  "
                        "The default 16 is a CPU-demo value: the paged "
                        "split-KV kernel needs page %% 128 == 0 (and "
                        "head_dim %% 128 == 0), so at 16 every decode "
                        "attend gathers its pages and runs as XLA — "
                        "docs/serving.md 'Kernel reach'; the engine says "
                        "so at construction on a TPU")
    p.add_argument("--num-blocks", type=int, default=None,
                   help="engine mode: KV pool blocks (default: sized "
                        "to ~half the offered load, exercising "
                        "queueing)")
    p.add_argument("--chaos", action="store_true",
                   help="engine mode: drive a seeded FaultInjector "
                        "(runtime/faults.py) through the traffic — "
                        "random forward/callback/block-alloc faults "
                        "plus a bounded queue — and print the failure-"
                        "containment accounting (every request still "
                        "retires: LENGTH, ERROR, SHED or DEADLINE)")
    p.add_argument("--deadline", type=float, default=None,
                   help="engine mode: per-request TTL in seconds "
                        "(WAITING/PREFILL requests past it retire "
                        "with finish reason 'deadline')")
    p.add_argument("--max-queue", type=int, default=None,
                   help="engine mode: waiting-queue bound; arrivals "
                        "beyond it are shed at submit() (chaos mode "
                        "defaults this to requests // 2)")
    p.add_argument("--snapshot-dir", default=None, metavar="DIR",
                   help="engine mode: crash recovery — append every "
                        "submit/token/retire to DIR's token journal and "
                        "snapshot the paged KV + engine state there "
                        "(docs/serving.md 'Crash recovery')")
    p.add_argument("--snapshot-every", type=int, default=8, metavar="N",
                   help="engine mode: KV snapshot cadence in engine "
                        "steps (the journal appends per token commit "
                        "regardless; only with --snapshot-dir)")
    p.add_argument("--resume", action="store_true",
                   help="engine mode: restore from --snapshot-dir "
                        "before serving (fresh start when the dir is "
                        "empty); already-journaled requests are not "
                        "re-submitted and resumed streams are bit-"
                        "identical to an uninterrupted run")
    p.add_argument("--heartbeat", default=None, metavar="PATH",
                   help="engine mode: beat a liveness file each step "
                        "(scripts/serve_supervisor.py polls it)")
    p.add_argument("--hb-interval", type=float, default=5.0,
                   help="engine mode: heartbeat cadence in seconds")
    p.add_argument("--kill-at-step", type=int, default=None, metavar="K",
                   help="engine mode, chaos/demo: os._exit mid-run at "
                        "engine step K — once (a marker in "
                        "--snapshot-dir gates re-kills), so a "
                        "supervisor restart runs to completion")
    p.add_argument("--metrics-port", type=int, default=None, metavar="P",
                   help="engine mode: serve the live Prometheus text "
                        "exposition (ServeMetrics.to_prometheus) at "
                        "http://127.0.0.1:P/metrics from a stdlib-HTTP "
                        "daemon thread while the engine runs (0 picks a "
                        "free port; docs/observability.md lists the "
                        "metric names)")
    p.add_argument("--stats-every", type=int, default=None, metavar="N",
                   help="engine mode: log one compact stats line "
                        "(metrics.format_statline — the same formatter "
                        "the supervisor's postmortem uses, incl. the "
                        "top device program by wall time when "
                        "--trace-level >= 1) every N engine steps")
    p.add_argument("--trace-level", type=int, default=None,
                   help="engine mode: flight-recorder detail (0 = off, "
                        "1 = lifecycle + failures [default], 2 = "
                        "+ per-dispatch events; docs/observability.md)")
    p.add_argument("--trace-perfetto", default=None, metavar="PATH",
                   help="engine mode: export the flight recorder's "
                        "per-request timeline as a Chrome/Perfetto "
                        "trace at PATH after the run (open in "
                        "ui.perfetto.dev; .gz suffix gzips)")
    p.add_argument("--shared-prompt", action="store_true",
                   help="engine mode: every request shares one system-"
                        "prompt prefix (plus a distinct suffix) — the "
                        "first commits its pages to the prefix cache, "
                        "the rest map them read-only and prefill only "
                        "the residual (docs/serving.md 'Prefix "
                        "caching'; watch the prefix-cache stats line)")
    p.add_argument("--fleet", type=int, default=None, metavar="N",
                   help="engine mode: serve through a FleetController "
                        "of N in-process engine replicas behind the "
                        "queue-pressure admission router "
                        "(docs/serving.md 'Fleet serving'); prints "
                        "per-request placement and the fleet summary")
    p.add_argument("--fleet-kill-step", type=int, default=None,
                   metavar="K",
                   help="fleet mode chaos: kill replica r0 at fleet "
                        "step K — its in-flight requests live-migrate "
                        "to the survivors (journal hand-off) and r0 "
                        "restarts under exponential backoff")
    p.add_argument("--disagg", default=None, metavar="P:D",
                   help="disaggregated serving: a two-role tier of P "
                        "prefill + D decode in-process replicas — every "
                        "request prefills on the prefill pool, PUSHes "
                        "its KV pages at prefill completion, and "
                        "decodes in place on its stamped decode "
                        "target; prints each request's journey "
                        "(docs/serving.md 'Disaggregated serving'). "
                        "Its own mode: no --engine/--mesh/--fleet")
    p.add_argument("--sessions", type=int, default=None, metavar="T",
                   help="engine mode: after the first drain, run T-1 "
                        "follow-up turns per request — each turn's "
                        "prompt is the full previous conversation plus "
                        "a fresh user message, so turns >= 1 hit the "
                        "prefix cache for their whole history")
    p.add_argument("--migrate-in", default=None, metavar="PATH",
                   help="engine mode: adopt a saved migration-manifest "
                        "JSON at startup (recovery.save_manifest / "
                        "manifest_from_journal) and print each "
                        "request's adopt/requeue placement before "
                        "serving it to completion")
    p.add_argument("--serve-port", type=int, default=None, metavar="P",
                   help="engine mode: NETWORK INGEST instead of local "
                        "traffic (docs/serving.md 'Network fleet "
                        "serving') — serve POST /submit, GET /stream, "
                        "POST /drain, POST /migrate_in, GET /health on "
                        "port P (0 picks free; published to "
                        "<snapshot-dir>/net_port)")
    p.add_argument("--serve-deadline", type=float, default=None,
                   metavar="S",
                   help="network mode: hard wall-clock lifetime bound "
                        "(a wedged replica exits on its own)")
    p.add_argument("--serve-idle-exit", type=float, default=None,
                   metavar="S",
                   help="network mode: exit after S seconds with no "
                        "work (demo/test hygiene; default: run until "
                        "POST /shutdown)")
    args = p.parse_args()
    if args.sessions is not None and args.sessions < 1:
        p.error(f"--sessions must be >= 1, got {args.sessions}")
    if args.fleet is not None and not args.engine:
        p.error("--fleet is an engine-mode flag: add --engine")
    if args.fleet is not None and args.fleet < 1:
        p.error(f"--fleet must be >= 1, got {args.fleet}")
    if args.fleet_kill_step is not None and args.fleet is None:
        p.error("--fleet-kill-step needs --fleet")
    if args.disagg is not None:
        if args.engine or args.mesh is not None:
            p.error("--disagg is its own serving mode: it does not "
                    "combine with --engine or --mesh (the tier builds "
                    "its own in-process replicas)")
        if args.fleet is not None:
            p.error("--disagg replaces --fleet: the P:D spec already "
                    "sizes the tier")
        from triton_dist_tpu.serve.disagg import parse_disagg
        try:
            parse_disagg(args.disagg)
        except ValueError as e:
            p.error(str(e))
    if args.fleet is not None and (args.mixed or args.sessions
                                   or args.shared_prompt
                                   or args.speculative or args.resume):
        p.error("--fleet drives plain engine traffic (no --mixed/"
                "--sessions/--shared-prompt/--speculative/--resume)")
    if args.speculative is not None and args.speculative < 1:
        p.error(f"--speculative must be >= 1, got {args.speculative}")
    if args.spec_adaptive is not None and args.spec_adaptive < 0:
        p.error(f"--spec-adaptive must be >= 0 (0 pins k fixed), got "
                f"{args.spec_adaptive}")
    if args.spec_adaptive is not None and not args.speculative:
        p.error("--spec-adaptive needs --speculative")
    if args.trace_level is not None and args.trace_level < 0:
        p.error(f"--trace-level must be >= 0, got {args.trace_level}")
    if args.stats_every is not None and args.stats_every < 1:
        p.error(f"--stats-every must be >= 1, got {args.stats_every}")
    for flag, name in ((args.metrics_port, "--metrics-port"),
                       (args.stats_every, "--stats-every"),
                       (args.trace_level, "--trace-level"),
                       (args.trace_perfetto, "--trace-perfetto"),
                       (args.migrate_in, "--migrate-in"),
                       (args.serve_port, "--serve-port")):
        if flag is not None and not args.engine:
            p.error(f"{name} is an engine-mode flag: add --engine")
    if args.serve_port is not None and (args.mixed or args.sessions
                                        or args.shared_prompt
                                        or args.fleet is not None):
        p.error("--serve-port serves network traffic only (no --mixed/"
                "--sessions/--shared-prompt/--fleet)")
    if ((args.serve_deadline is not None
         or args.serve_idle_exit is not None)
            and args.serve_port is None):
        p.error("--serve-deadline/--serve-idle-exit need --serve-port")
    if args.kv_dtype != "float32":
        # Validated BEFORE dispatch, like --kv-shard: every serving
        # mode either honours the dtype or refuses it loudly here —
        # never a silent float fallback.
        if not args.engine and args.disagg is None:
            p.error("--kv-dtype is a serving-mode flag: add --engine "
                    "(or --fleet/--disagg); the bare generation demo "
                    "quantizes with --kv-int8")
        if args.speculative:
            p.error("--kv-dtype int8 does not compose with "
                    "--speculative: the multi-token verify scatters "
                    "accepted spans through the float write path "
                    "(quantized verify is a recorded debt, ROADMAP)")
    if args.kv_int8 and (args.engine or args.disagg is not None):
        p.error("--kv-int8 is the bare-demo flag; serving modes take "
                "--kv-dtype int8")
    return args


def run_fleet(args, key):
    """--fleet N: staggered traffic through a FleetController of N
    in-process engine replicas — the router places by queue pressure,
    ``--fleet-kill-step K`` kills replica r0 mid-run and its in-flight
    requests live-migrate to the survivors (docs/serving.md "Fleet
    serving")."""
    import tempfile

    import numpy as np

    from triton_dist_tpu.models import llama
    from triton_dist_tpu.models.generate import Generator
    from triton_dist_tpu.runtime import dist_print
    from triton_dist_tpu.serve import (
        Request,
        SamplingParams,
        ServeEngine,
    )
    from triton_dist_tpu.serve.fleet import FleetController

    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    rng = np.random.default_rng(args.seed)
    lens = rng.integers(max(2, args.prompt_len // 2),
                        2 * args.prompt_len + 1, size=args.requests)
    max_seq = int(max(lens)) + args.new_tokens
    max_seq += (-max_seq) % args.page_size
    cfg = llama.LlamaConfig(vocab=256, dim=32, n_layers=2, n_heads=2,
                            n_kv_heads=2, ffn_dim=64, max_seq=max_seq,
                            dtype=jnp.float32)
    params = llama.init_params(cfg, key)
    gen = Generator(cfg, mesh, axis="sp", max_seq=max_seq,
                    kv_dtype=jnp.int8 if args.kv_dtype == "int8"
                    else None)
    page = args.page_size
    per_req = -(-max_seq // page)
    num_blocks = args.num_blocks or (1 + per_req * max(
        2, args.requests // max(args.fleet, 1)))

    def factory(d):
        return ServeEngine(gen, params, num_blocks=num_blocks,
                           page_size=page, max_batch=args.max_batch,
                           prefill_chunk=max(8, page),
                           horizon=args.horizon,
                           pipeline=args.pipeline,
                           max_queue=args.max_queue, snapshot_dir=d,
                           trace_level=(1 if args.trace_level is None
                                        else args.trace_level))

    root = args.snapshot_dir or tempfile.mkdtemp(prefix="fleet_")
    fc = FleetController(factory, args.fleet, root=root,
                         backoff_base_s=0.05, backoff_cap_s=2.0,
                         suspect_after_s=30.0, dead_after_s=120.0,
                         trace_level=(1 if args.trace_level is None
                                      else args.trace_level),
                         seed=args.seed)
    dist_print(f"fleet: {args.fleet} replicas x (pool {num_blocks} "
               f"blocks, batch {args.max_batch}), {args.requests} "
               f"requests under {root}")
    srv = None
    if args.metrics_port is not None:
        # the FLEET aggregate exposition: serve_* merged across
        # replicas + the fleet_* controller series
        from triton_dist_tpu.serve.trace import start_metrics_server

        srv = start_metrics_server(fc, port=args.metrics_port)
        dist_print(f"fleet /metrics on port {srv.server_address[1]} "
                   f"(aggregated across replicas)")
    params_s = SamplingParams(max_new_tokens=args.new_tokens,
                              temperature=args.temperature,
                              top_k=args.top_k, top_p=args.top_p,
                              seed=args.seed, deadline_s=args.deadline)
    reqs = [Request(f"req-{i}",
                    rng.integers(0, cfg.vocab, size=int(lens[i]))
                    .astype(np.int32), params_s)
            for i in range(args.requests)]
    t0 = time.perf_counter()
    submitted = step = 0
    killed = False
    while fc.has_work() or submitted < len(reqs):
        if step % max(args.stagger, 1) == 0 and submitted < len(reqs):
            fc.submit(reqs[submitted])
            submitted += 1
        if (args.fleet_kill_step is not None and not killed
                and step == args.fleet_kill_step):
            killed = True
            dist_print(f"chaos: killing replica r0 at fleet step "
                       f"{step} (in-flight requests live-migrate)")
            fc.kill_replica("r0", f"--fleet-kill-step {step}")
        fc.step()
        step += 1
    dt = time.perf_counter() - t0

    total = 0
    for rid in sorted(fc.outputs):
        o = fc.outputs[rid]
        total += len(o.token_ids)
        path = ">".join(fc.history.get(rid, []))
        dist_print(f"{rid}: prompt {len(o.prompt)} -> "
                   f"{len(o.token_ids)} tokens "
                   f"({o.finish_reason.value}) via {path}")
    s = fc.fleet_summary()
    dist_print(f"fleet: {total} tokens / {args.requests} requests in "
               f"{dt * 1e3:.1f} ms over {s['steps']} fleet steps — "
               f"{s['deaths']} deaths, {s['migrations']} migrations, "
               f"{s['pending']} pending")
    for name, r in s["replicas"].items():
        dist_print(f"  {name}: {r['state']}, life {r['life']} "
                   f"({r['restarts']} restarts), "
                   f"{r.get('completed', 0)} completed, "
                   f"{r.get('migrated_in', 0)} migrated in / "
                   f"{r.get('migrated_out', 0)} out")
    kv = [r.engine.metrics.kv_stats() for r in fc.replicas.values()
          if r.engine is not None]
    slots = sum(k["token_slots"] for k in kv)
    if slots:
        pool = sum(k["pool_bytes"] for k in kv)
        dist_print(f"fleet kv pool: {pool} bytes for {slots} token "
                   f"slots across {len(kv)} replicas "
                   f"({pool / slots:.1f} B/token, "
                   f"{'int8+scales' if any(k['quantized'] for k in kv) else 'float'})")
    lat = s["latency"]

    def _p(h, k):
        v = h.get(k)
        return f"{v * 1e3:.1f}" if v is not None else "-"

    dist_print(f"fleet latency slo (merged across replicas): ttft "
               f"p50/p95/p99 {_p(lat['ttft'], 'p50')}/"
               f"{_p(lat['ttft'], 'p95')}/{_p(lat['ttft'], 'p99')} ms, "
               f"itl p50/p95/p99 {_p(lat['itl'], 'p50')}/"
               f"{_p(lat['itl'], 'p95')}/{_p(lat['itl'], 'p99')} ms")
    slo = s["slo"]
    dist_print(f"fleet slo burn ({slo['window_s']:.0f}s window): "
               f"{slo['deadline_miss_window']} deadline misses, "
               f"{slo['shed_window']} sheds "
               f"({s['audit']['recorded']} routing decisions audited)")
    moved = [r for r, h in fc.history.items() if len(set(h)) > 1]
    if moved:
        dist_print(f"live-migrated requests: {sorted(moved)}")
        for rid in sorted(moved)[:1]:
            hops = [f"{e['kind']}->{e.get('chosen')}"
                    for e in fc.explain(rid)
                    if e["kind"] in ("route", "migrate")]
            dist_print(f"  {rid} journey: {' '.join(hops)}")
    if args.trace_perfetto:
        path = fc.export_perfetto(args.trace_perfetto)
        dist_print(f"fleet perfetto timeline: {path} (controller + "
                   f"{args.fleet} replica tracks, migration flow "
                   f"arrows; open in ui.perfetto.dev)")
    if srv is not None:
        import urllib.request
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.server_address[1]}/metrics",
                timeout=10) as r:
            body = r.read()
        series = sum(1 for ln in body.decode().splitlines()
                     if ln and not ln.startswith("#"))
        dist_print(f"fleet metrics self-scrape: {len(body)} bytes, "
                   f"{series} series")
        srv.shutdown()
    dist_print("done")


def run_disagg(args, key):
    """--disagg P:D: a two-role tier of P prefill + D decode in-process
    replicas — every request prefills on the prefill pool, PUSHes its
    single-request KV hand-off at prefill completion, and decodes IN
    PLACE on its stamped decode target; prints each request's journey
    and the push audit (docs/serving.md "Disaggregated serving")."""
    import tempfile

    import numpy as np

    from triton_dist_tpu.models import llama
    from triton_dist_tpu.models.generate import Generator
    from triton_dist_tpu.runtime import dist_print
    from triton_dist_tpu.serve import (
        DisaggController,
        Request,
        SamplingParams,
        ServeEngine,
        parse_disagg,
    )

    n_p, n_d = parse_disagg(args.disagg)
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    rng = np.random.default_rng(args.seed)
    lens = rng.integers(max(2, args.prompt_len // 2),
                        2 * args.prompt_len + 1, size=args.requests)
    max_seq = int(max(lens)) + args.new_tokens
    max_seq += (-max_seq) % args.page_size
    cfg = llama.LlamaConfig(vocab=256, dim=32, n_layers=2, n_heads=2,
                            n_kv_heads=2, ffn_dim=64, max_seq=max_seq,
                            dtype=jnp.float32)
    params = llama.init_params(cfg, key)
    gen = Generator(cfg, mesh, axis="sp", max_seq=max_seq,
                    kv_dtype=jnp.int8 if args.kv_dtype == "int8"
                    else None)
    page = args.page_size
    per_req = -(-max_seq // page)
    num_blocks = args.num_blocks or (1 + per_req * max(
        2, args.requests // max(n_d, 1)))

    def factory(d):
        return ServeEngine(gen, params, num_blocks=num_blocks,
                           page_size=page, max_batch=args.max_batch,
                           prefill_chunk=max(8, page),
                           horizon=args.horizon,
                           pipeline=args.pipeline,
                           max_queue=args.max_queue, snapshot_dir=d,
                           trace_level=(1 if args.trace_level is None
                                        else args.trace_level))

    root = args.snapshot_dir or tempfile.mkdtemp(prefix="disagg_")
    fc = DisaggController(factory, n_p, n_d, root=root,
                          backoff_base_s=0.05, backoff_cap_s=2.0,
                          suspect_after_s=30.0, dead_after_s=120.0,
                          trace_level=(1 if args.trace_level is None
                                       else args.trace_level),
                          seed=args.seed)
    roles = {name: rep.role for name, rep in fc.replicas.items()}
    dist_print(f"disagg tier: {n_p} prefill + {n_d} decode replicas x "
               f"(pool {num_blocks} blocks, batch {args.max_batch}), "
               f"{args.requests} requests under {root}")
    dist_print(f"roles: {roles}")
    params_s = SamplingParams(max_new_tokens=args.new_tokens,
                              temperature=args.temperature,
                              top_k=args.top_k, top_p=args.top_p,
                              seed=args.seed, deadline_s=args.deadline)
    reqs = [Request(f"req-{i}",
                    rng.integers(0, cfg.vocab, size=int(lens[i]))
                    .astype(np.int32), params_s)
            for i in range(args.requests)]
    t0 = time.perf_counter()
    submitted = step = 0
    while fc.has_work() or submitted < len(reqs):
        if step % max(args.stagger, 1) == 0 and submitted < len(reqs):
            fc.submit(reqs[submitted])
            submitted += 1
        fc.step()
        step += 1
    dt = time.perf_counter() - t0

    total = 0
    for rid in sorted(fc.outputs):
        o = fc.outputs[rid]
        total += len(o.token_ids)
        # the journey the tier exists for: prefill replica -> push ->
        # decode replica
        path = " -push-> ".join(fc.history.get(rid, []))
        dist_print(f"{rid}: prompt {len(o.prompt)} -> "
                   f"{len(o.token_ids)} tokens "
                   f"({o.finish_reason.value}) via {path}")
    s = fc.fleet_summary()
    d = s["disagg"]
    dist_print(f"disagg: {total} tokens / {args.requests} requests in "
               f"{dt * 1e3:.1f} ms over {s['steps']} fleet steps — "
               f"{d['pushes']} pushes, {d['push_fallbacks']} "
               f"fallbacks, {s['deaths']} deaths")
    for name, r in s["replicas"].items():
        dist_print(f"  {name} ({r['role']}): {r['state']}, "
                   f"{r.get('completed', 0)} completed, "
                   f"{r.get('pushed_out', 0)} pushed out / "
                   f"{r.get('pushed_in', 0)} pushed in")
    kv = [r.engine.metrics.kv_stats() for r in fc.replicas.values()
          if r.engine is not None]
    slots = sum(k["token_slots"] for k in kv)
    if slots:
        pool = sum(k["pool_bytes"] for k in kv)
        dist_print(f"disagg kv pool: {pool} bytes for {slots} token "
                   f"slots across {len(kv)} replicas "
                   f"({pool / slots:.1f} B/token, "
                   f"{'int8+scales' if any(k['quantized'] for k in kv) else 'float'})")
    if fc.outputs:
        rid = sorted(fc.outputs)[0]
        hops = [f"{e['kind']}->{e.get('chosen')}"
                for e in fc.explain(rid)
                if e["kind"] in ("route", "decode_target", "push")]
        dist_print(f"{rid} routing audit: {' '.join(hops)}")
    dist_print("done")


def run_engine(args, key):
    """--engine: staggered multi-request traffic through the
    continuous-batching engine (serve/engine.py)."""
    import numpy as np

    from triton_dist_tpu.models import llama
    from triton_dist_tpu.models.generate import Generator
    from triton_dist_tpu.runtime import dist_print
    from triton_dist_tpu.serve import Request, SamplingParams, ServeEngine

    if args.model != "llama":
        raise SystemExit("--engine serves the dense family only")
    # the Generator stays world-1 (it provides the model + chunked
    # prefill); --mesh places the ENGINE's forwards on a device mesh
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    engine_mesh = None
    tp_w = sp_w = 1
    if args.mesh:
        if args.mesh < 1:
            raise SystemExit("--mesh needs N >= 1")
        if jax.device_count() < args.mesh:
            # An error, never a skip that exits 0: a run that was asked
            # for N chips and served on fewer has shown nothing.
            raise SystemExit(
                f"[serve] --mesh {args.mesh} needs {args.mesh} devices, "
                f"this runtime exposes {jax.device_count()} "
                f"({jax.devices()[0].platform}).  Run it on a "
                f"{args.mesh}-chip host, or for the CPU tests under "
                f"XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{args.mesh} (virtual CPU mesh).")
        if args.kv_shard == "heads+seq":
            # Factor N = tp x sp: sp takes the smallest prime factor
            # (spare pages are easier to come by than whole KV heads),
            # tp the rest — 4 -> 2x2, 8 -> 4x2, 6 -> 3x2.  A prime N
            # degenerates to tp=1 (pure block sharding on a 2-axis
            # mesh), which the engine serves identically to 'seq'.
            sp_w = next((p for p in range(2, args.mesh + 1)
                         if args.mesh % p == 0), 1)
            tp_w = args.mesh // sp_w
            engine_mesh = Mesh(np.array(jax.devices()[:args.mesh])
                               .reshape(tp_w, sp_w), ("tp", "sp"))
        else:
            engine_mesh = Mesh(np.array(jax.devices()[:args.mesh]),
                               ("tp",))
    rng = np.random.default_rng(args.seed)
    if args.mixed:
        if args.shared_prompt or args.sessions:
            raise SystemExit("--mixed is exclusive with --shared-prompt/"
                             "--sessions (ladder sweep vs prefix demo)")
        # Lengths picked AFTER the engine exists, swept across its
        # bucket ladder (below); size the model for the longest.
        lens = None
        hi = max(4, 2 * args.prompt_len)
        max_seq = hi + args.new_tokens
    else:
        lens = rng.integers(max(2, args.prompt_len // 2),
                            2 * args.prompt_len + 1, size=args.requests)
        # --requests 0 (e.g. --migrate-in only, or --serve-port): size
        # the model for the lengths local traffic WOULD have used, so
        # carried/wire prompts built against the same knobs always fit
        max_seq = (int(max(lens)) if args.requests
                   else 2 * args.prompt_len) + args.new_tokens
    shared_base = None
    if args.shared_prompt:
        # The shared "system prompt": long enough to span several pages
        # so warm admissions map a real block-aligned prefix.
        shared_base = rng.integers(
            0, 256, size=max(2 * args.page_size, args.prompt_len)
        ).astype(np.int32)
        max_seq += int(shared_base.shape[0])
    if args.sessions:
        # Each follow-up turn appends (answer + fresh user message).
        max_seq += (args.sessions - 1) * (args.new_tokens
                                          + max(4, args.prompt_len))
    max_seq += (-max_seq) % args.page_size
    n_heads = 2
    ffn_dim = 64
    seq_w = {"heads": 1, "seq": args.mesh,
             "heads+seq": sp_w}.get(args.kv_shard, 1) or 1
    if engine_mesh is not None:
        # Geometry must divide the mesh (the engine rejects anything
        # else loudly): whole heads per rank of the HEAD-sharding
        # world (the full mesh for 'heads'/'seq', the tp axis for
        # 'heads+seq'), ffn columns per rank, and for the block-
        # sharded layouts a page count divisible by the sp world.
        heads_w = tp_w if args.kv_shard == "heads+seq" else args.mesh
        n_heads = max(2, heads_w)
        ffn_dim = -(-64 // heads_w) * heads_w
        if seq_w > 1:
            max_seq += (-max_seq) % (args.page_size * seq_w)

    cfg = llama.LlamaConfig(vocab=256, dim=16 * n_heads, n_layers=2,
                            n_heads=n_heads, n_kv_heads=n_heads,
                            ffn_dim=ffn_dim, max_seq=max_seq,
                            dtype=jnp.float32)
    params = llama.init_params(cfg, key)
    gen = Generator(cfg, mesh, axis="sp", max_seq=max_seq,
                    kv_dtype=jnp.int8 if args.kv_dtype == "int8"
                    else None)
    draft = d_params = None
    if args.speculative:
        dcfg = llama.LlamaConfig(vocab=cfg.vocab, dim=cfg.dim // 2,
                                 n_layers=1, n_heads=1, n_kv_heads=1,
                                 ffn_dim=cfg.ffn_dim // 2, max_seq=max_seq,
                                 dtype=cfg.dtype)
        d_params = llama.init_params(dcfg, jax.random.fold_in(key, 2))
        draft = Generator(dcfg, mesh, axis="sp", max_seq=max_seq)

    page = args.page_size
    per_req = -(-max_seq // page)
    num_blocks = args.num_blocks or (1 + per_req * max(2, args.requests
                                                       // 2))
    if engine_mesh is not None and seq_w > 1 and args.num_blocks is None:
        # block-sharded layouts: equal per-rank partitions (one null
        # each) sized so a full-length span still fits its partition
        num_blocks = -(-(num_blocks + seq_w) // seq_w) * seq_w
    faults = None
    max_queue = args.max_queue
    if args.chaos:
        from triton_dist_tpu.runtime.faults import FaultInjector
        faults = (FaultInjector(seed=args.seed)
                  .inject("forward", rate=0.04, error="chaos: forward")
                  .inject("callback", rate=0.1, error="chaos: callback")
                  .inject("block_alloc", rate=0.05,
                          error="chaos: alloc"))
        if max_queue is None:
            max_queue = max(2, args.requests // 2)
    kw = dict(num_blocks=num_blocks, page_size=page,
              max_batch=args.max_batch, prefill_chunk=max(8, page),
              mesh=engine_mesh, kv_shard=args.kv_shard,
              horizon=args.horizon, pipeline=args.pipeline,
              draft=draft, draft_params=d_params,
              spec_k=args.speculative or 0,
              faults=faults, max_queue=max_queue, fault_retries=1,
              heartbeat=args.heartbeat,
              heartbeat_interval_s=args.hb_interval,
              trace_level=(1 if args.trace_level is None
                           else args.trace_level))
    if args.spec_adaptive is not None:
        kw["spec_adaptive"] = args.spec_adaptive
    from triton_dist_tpu.serve.recovery import has_restorable_state

    # An empty journal the constructor touched before the process died
    # is NOT resumable — restore would find nothing and a supervisor
    # retrying --resume would never recover; start fresh instead.
    snap_dir = args.snapshot_dir
    resumable = snap_dir is not None and has_restorable_state(snap_dir)
    if args.resume and resumable:
        kw.pop("spec_k")  # restore keys speculation off the draft args
        engine = ServeEngine.restore(
            snap_dir, gen, params, snapshot_every=args.snapshot_every,
            **kw)
        r = engine.metrics.recovery_stats()
        dist_print(f"resumed from snapshot: "
                   f"{r['restored_in_place']} in place, "
                   f"{r['restored_requeued']} requeued "
                   f"({r['restored_tokens']} journal tokens carried), "
                   f"{engine.metrics.completed} already finished")
    else:
        engine = ServeEngine(
            gen, params, snapshot_dir=snap_dir,
            snapshot_every=args.snapshot_every if snap_dir else None,
            **kw)
    if engine_mesh is not None:
        layout = ("TP weights + head-sharded paged KV"
                  if args.kv_shard == "heads" else
                  "replicated weights + block-sharded paged KV "
                  "(SP flash-decode)"
                  if args.kv_shard == "seq" else
                  f"2D: TP weights + heads over tp={tp_w}, block-"
                  f"sharded paged KV over sp={sp_w} (SP flash-decode "
                  f"combine)")
        axes = (f"axes ('tp', 'sp') = {tp_w} x {sp_w}"
                if args.kv_shard == "heads+seq" else "axis 'tp'")
        dist_print(f"mesh serving: {args.mesh} devices over {axes}, "
                   f"kv_shard={args.kv_shard!r} — {layout} under "
                   f"shard_map; streams are bit-identical to the "
                   f"world-1 engine")
    dist_print(f"engine: {args.requests} requests, pool {num_blocks} "
               f"blocks x{page} tokens, batch {args.max_batch}"
               f"{f', mesh {args.mesh} ({args.kv_shard})' if engine_mesh is not None else ''}"
               f"{f', horizon {args.horizon} (pipeline {args.pipeline})' if args.horizon > 1 else ''}"
               f"{f', speculative k={args.speculative}' if args.speculative else ''}"
               f"{f', chaos seed {args.seed}' if args.chaos else ''}"
               f"{f', max_queue {max_queue}' if max_queue is not None else ''}")
    if args.mixed:
        # One just-under-a-rung and one just-over-half-a-rung length per
        # ladder rung: every bucket gets traffic, no length repeats a
        # shape the engine would have to retrace on.
        cand = sorted({min(hi, max(2, v)) for r in engine.ladder
                       for v in (r // 2 + 1, r - 1)})
        lens = np.array([cand[i % len(cand)]
                         for i in range(args.requests)])
        dist_print(f"mixed traffic: ladder {engine.ladder}, "
                   f"prompt lengths {sorted(set(int(x) for x in lens))}")
    resumed_engine = args.resume and resumable
    if args.warmup and resumed_engine:
        # warmup() requires an idle engine; a restored one already
        # holds re-queued work.  Programs compile on demand instead.
        dist_print("warmup skipped on --resume (restored work in "
                   "flight; programs compile on demand)")
    elif args.warmup:
        w = engine.warmup()
        caveat = (" (spec mode: the draft's padded chunked prefill + "
                  "join ride their own extent ladder — see the "
                  "draft_prefill/draft_join counters)"
                  if args.speculative else "")
        dist_print(f"warmup: {w['programs']} programs compiled in "
                   f"{w['seconds'] * 1e3:.0f} ms — steady-state serving "
                   f"is compile-free{caveat}")

    metrics_srv = None
    if args.metrics_port is not None:
        from triton_dist_tpu.serve.trace import start_metrics_server

        metrics_srv = start_metrics_server(engine.metrics,
                                           port=args.metrics_port)
        dist_print(f"metrics: Prometheus text at http://127.0.0.1:"
                   f"{metrics_srv.server_address[1]}/metrics")

    if args.migrate_in:
        # the subprocess hand-off: adopt a saved JSON manifest (KV-
        # stripped — recovery.save_manifest), print where each request
        # landed, then serve it to completion below
        from triton_dist_tpu.serve.recovery import load_manifest

        res = engine.migrate_in(load_manifest(args.migrate_in))
        for rid in res["adopted"]:
            dist_print(f"migrate-in {rid}: adopted in place (live KV)")
        for rid in res["requeued"]:
            dist_print(f"migrate-in {rid}: requeued (exact recompute)")
        for rid, why in sorted(res["rejected"].items()):
            dist_print(f"migrate-in {rid}: REJECTED ({why})")
        dist_print(f"migrate-in: {len(res['adopted'])} adopted, "
                   f"{len(res['requeued'])} requeued, "
                   f"{len(res['rejected'])} rejected "
                   f"from {args.migrate_in}")

    params_s = SamplingParams(max_new_tokens=args.new_tokens,
                              temperature=args.temperature,
                              top_k=args.top_k, top_p=args.top_p,
                              seed=args.seed, deadline_s=args.deadline)
    # chaos mode attaches a no-op streaming callback so the injector's
    # callback faults have a seam to fire at
    on_token = (lambda rid, tok: None) if args.chaos else None
    def _prompt(i):
        own = rng.integers(0, cfg.vocab, size=int(lens[i])).astype(np.int32)
        if shared_base is None:
            return own
        return np.concatenate([shared_base, own])

    reqs = [Request(f"req-{i}", _prompt(i), params_s, on_token=on_token)
            for i in range(args.requests)]

    kill_marker = (os.path.join(snap_dir, "killed.marker")
                   if snap_dir else None)
    t0 = time.perf_counter()
    if args.serve_port is not None:
        # network ingest mode: requests arrive over the wire
        # (docs/serving.md "Network fleet serving"); the local traffic
        # generator stands down
        from triton_dist_tpu.serve.net import (
            PORT_FILE,
            ReplicaServer,
            serve_loop,
            write_port_file,
        )

        server = ReplicaServer(engine)
        server.start(port=args.serve_port)
        if snap_dir:
            write_port_file(os.path.join(snap_dir, PORT_FILE),
                            server.port)
        dist_print(f"net: replica serving at http://127.0.0.1:"
                   f"{server.port} (POST /submit, GET /stream, "
                   f"POST /drain, POST /migrate_in, GET /health)")
        sys.stdout.flush()
        steps = serve_loop(engine, server,
                           deadline_s=args.serve_deadline,
                           exit_when_idle_s=args.serve_idle_exit)
        dist_print(f"net: serve loop exited after {steps} steps, "
                   f"{engine.metrics.completed} requests completed")
        reqs = []                        # the drain loop below no-ops
        args.requests = engine.metrics.completed  # honest stats label
    submitted = step = 0
    finished = [engine._outputs[rid] for rid in sorted(engine._outputs)]
    while engine.has_work() or submitted < len(reqs):
        if step % max(args.stagger, 1) == 0 and submitted < len(reqs):
            if engine.has_request(reqs[submitted].request_id):
                submitted += 1  # resumed: already in the journal
            else:
                shed = engine.submit(reqs[submitted])
                if shed is not None:    # bounded admission said no
                    finished.append(shed)
                submitted += 1
        if (args.kill_at_step is not None and step == args.kill_at_step
                and kill_marker is not None
                and not os.path.exists(kill_marker)):
            # Simulated process death (demo / supervisor test): durable
            # state is the journal + snapshots only — no cleanup, like
            # a real SIGKILL.  The marker keeps the restarted run alive.
            with open(kill_marker, "w") as f:
                f.write("killed once\n")
            # the flight recorder's postmortem trail is the ONE thing
            # worth a syscall on the way down (the supervisor surfaces
            # it on restart; a real SIGKILL gets the previous flush)
            engine.flight_flush(f"kill-at-step {step}", force=True)
            dist_print(f"killing engine process at step {step} "
                       f"(os._exit; restart with --resume)")
            sys.stdout.flush()
            os._exit(17)
        finished.extend(engine.step())
        step += 1
        if args.stats_every is not None and step % args.stats_every == 0:
            from triton_dist_tpu.serve.metrics import format_statline
            dist_print("stats: "
                       + format_statline(engine.metrics.light_summary()))

    if args.sessions:
        # Follow-up turns: each turn's prompt is the FULL previous
        # conversation (prompt + answer) plus a fresh user message —
        # the prefix cache serves the whole history from its pages, so
        # only the new message prefills (the stats line shows it).
        history = {o.request_id: np.concatenate(
            [np.asarray(o.prompt, np.int32),
             np.asarray(o.token_ids, np.int32)])
            for o in finished if not o.error}
        for turn in range(1, args.sessions):
            turn_reqs = []
            for rid in sorted(history):
                history[rid] = np.concatenate(
                    [history[rid],
                     rng.integers(0, cfg.vocab,
                                  size=max(4, args.prompt_len))
                     .astype(np.int32)])
                turn_reqs.append(Request(f"{rid}.t{turn}", history[rid],
                                         params_s, on_token=on_token))
            for r in turn_reqs:
                shed = engine.submit(r)
                if shed is not None:
                    finished.append(shed)
            outs = engine.run()
            for r in turn_reqs:
                o = outs.get(r.request_id)
                if o is None:
                    continue
                finished.append(o)
                base = r.request_id.rsplit(".t", 1)[0]
                if not o.error:
                    history[base] = np.concatenate(
                        [np.asarray(o.prompt, np.int32),
                         np.asarray(o.token_ids, np.int32)])
    dt = time.perf_counter() - t0

    total_tokens = sum(len(o.token_ids) for o in finished)
    for o in sorted(finished, key=lambda o: o.request_id):
        ttft = (f"ttft {o.metrics.ttft * 1e3:.1f} ms"
                if o.metrics.ttft is not None else "no token emitted")
        dist_print(f"{o.request_id}: prompt {len(o.prompt)} -> "
                   f"{len(o.token_ids)} tokens ({o.finish_reason.value}), "
                   f"{ttft}")
    s = engine.metrics.summary()
    dist_print(f"engine: {total_tokens} tokens / {args.requests} requests "
               f"in {dt * 1e3:.1f} ms over {s['steps']} iterations "
               f"({s['decode_steps']} decode, {s['verify_rounds']} verify)")
    # ONE formatter renders summary() everywhere (serve/metrics.py):
    # this end-of-run block, the --stats-every one-liner, and the
    # supervisor's postmortem line can never drift apart.
    from triton_dist_tpu.serve.metrics import format_stats

    for line in format_stats(
            s, spec=bool(args.speculative), prefix=engine.prefix_cache,
            failures=(args.chaos or args.deadline is not None
                      or max_queue is not None),
            recovery=snap_dir is not None):
        dist_print(line)
    if metrics_srv is not None:
        # Self-scrape: prove the live endpoint served parseable text
        # during the run (what a Prometheus agent would have seen).
        import urllib.request
        port = metrics_srv.server_address[1]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
            body = r.read().decode()
        series = sum(1 for ln in body.splitlines()
                     if ln and not ln.startswith("#"))
        dist_print(f"metrics endpoint: {len(body)} bytes, "
                   f"{series} series served")
        metrics_srv.shutdown()
    if args.trace_perfetto:
        path = engine.trace.export_perfetto(args.trace_perfetto)
        n = len(engine.trace.events())
        dist_print(f"perfetto trace: {n} events -> {path} "
                   f"(open in ui.perfetto.dev)")
    dumped = engine.metrics.maybe_dump()
    if dumped:
        dist_print(f"engine metrics dumped to {dumped}")
    dist_print("done")


def main():
    args = parse_args()
    from triton_dist_tpu.models.sampling import make_sampler
    from triton_dist_tpu.runtime import (
        configure_compile_cache,
        dist_print,
        initialize_distributed,
    )

    configure_compile_cache()
    initialize_distributed()
    if args.kv_shard != "heads" and args.mesh is None:
        # Validated for EVERY mode before dispatch: a non-default
        # layout without a mesh would serve plain world-1 while the
        # user believes they exercised sequence sharding.
        raise SystemExit("--kv-shard needs --mesh N (and --engine)")
    if args.disagg is not None:
        return run_disagg(args, jax.random.key(args.seed))
    if args.engine and args.fleet is not None:
        if args.mesh is not None:
            raise SystemExit("--mesh does not compose with --fleet yet "
                             "(each replica would need its own device "
                             "slice); run one sharded engine per "
                             "process instead")
        return run_fleet(args, jax.random.key(args.seed))
    if args.engine:
        return run_engine(args, jax.random.key(args.seed))
    if args.shared_prompt or args.sessions:
        raise SystemExit("--shared-prompt/--sessions are engine-mode "
                         "flags: add --engine")
    if args.mesh is not None:
        raise SystemExit("--mesh is an engine-mode flag: add --engine "
                         "(sharded ServeEngine serving; the bare "
                         "generation demo below shards its KV cache "
                         "over all devices already)")
    n = jax.device_count()
    mesh = Mesh(np.array(jax.devices()), ("sp",))
    key = jax.random.key(args.seed)
    dist_print(f"mesh sp={n}  model={args.model}")

    max_seq = max(64, args.prompt_len + args.new_tokens)
    max_seq += (-max_seq) % n  # cache shards over the mesh axis

    if args.model == "llama":
        from triton_dist_tpu.models import llama
        from triton_dist_tpu.models.generate import Generator
        cfg = llama.LlamaConfig(vocab=256, dim=32 * n, n_layers=2,
                                n_heads=n, n_kv_heads=n, ffn_dim=64 * n,
                                max_seq=max_seq, dtype=jnp.float32)
        params = llama.init_params(cfg, key)
        gen = Generator(cfg, mesh, axis="sp", max_seq=max_seq,
                        kv_dtype=jnp.int8 if args.kv_int8 else None)
    else:
        from triton_dist_tpu.models import moe
        from triton_dist_tpu.models.generate_moe import (
            MoEGenerator, place_params_serving)
        cfg = moe.MoEConfig(vocab=256, dim=32 * n, n_layers=2, n_heads=n,
                            n_kv_heads=n, n_experts=2 * n, topk=2,
                            expert_ffn_dim=32, max_seq=max_seq, block_m=8,
                            dtype=jnp.float32)
        params = place_params_serving(moe.init_params(cfg, key), cfg, mesh,
                                      axis="sp")
        gen = MoEGenerator(cfg, mesh, axis="sp", max_seq=max_seq,
                           kv_dtype=jnp.int8 if args.kv_int8 else None)

    prompt = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                cfg.vocab, jnp.int32)
    if args.chunk_prefill is not None and args.chunk_prefill <= 0:
        raise SystemExit(f"--chunk-prefill must be positive, got "
                         f"{args.chunk_prefill}")
    if not args.speculative:
        # Speculative runs its own prefill inside spec.generate — a
        # standalone one here would double the prompt work and hold a
        # dead cache set alive.
        t0 = time.perf_counter()
        if args.chunk_prefill:
            state = gen.prefill_chunked(params, prompt,
                                        chunk_size=args.chunk_prefill)
        else:
            state = gen.prefill(params, prompt)
        jax.block_until_ready(state.last_logits)
        dist_print(f"prefill {args.prompt_len} tokens x{args.batch}"
                   f"{f' (chunks of {args.chunk_prefill})' if args.chunk_prefill else ''}: "
                   f"{(time.perf_counter() - t0) * 1e3:.1f} ms")

    if args.speculative:
        if args.model != "llama":
            raise SystemExit("--speculative drafts the dense family only")
        if args.batch > 1 and n > 1:
            raise SystemExit("--speculative with batch > 1 needs a "
                             "world-1 mesh (the batched q_lens verify)")
        if args.batch > 1 and args.kv_int8:
            raise SystemExit("--speculative with batch > 1 needs a float "
                             "target cache (drop --kv-int8)")
        from triton_dist_tpu.models.speculative import (
            SpeculativeGenerator,
            SpeculativeSampler,
        )
        dcfg = llama.LlamaConfig(vocab=cfg.vocab, dim=cfg.dim // 2,
                                 n_layers=1, n_heads=max(cfg.n_heads // 2,
                                                         1),
                                 n_kv_heads=max(cfg.n_kv_heads // 2, 1),
                                 ffn_dim=cfg.ffn_dim // 2,
                                 max_seq=max_seq, dtype=cfg.dtype)
        d_params = llama.init_params(dcfg, jax.random.fold_in(key, 2))
        draft = Generator(dcfg, mesh, axis="sp", max_seq=max_seq)
        if args.temperature > 0:
            spec = SpeculativeSampler(gen, draft, k=args.speculative,
                                      temperature=args.temperature,
                                      top_k=args.top_k, top_p=args.top_p)
            skey = jax.random.fold_in(key, 1)
        else:
            spec = SpeculativeGenerator(gen, draft, k=args.speculative)
            skey = None
        t0 = time.perf_counter()
        tokens, stats = spec.generate(params, d_params, prompt,
                                      args.new_tokens, key=skey)
        jax.block_until_ready(tokens)
        dt = time.perf_counter() - t0
        dist_print(f"speculative decode k={args.speculative}: "
                   f"{dt * 1e3:.1f} ms, target passes "
                   f"{stats['target_passes']}, accept rate "
                   f"{stats['accept_rate']:.2f}")
        dist_print(f"tokens:\n{np.asarray(tokens)}")
        return

    sampler = None
    skey = None
    if args.temperature > 0:
        sampler = make_sampler(temperature=args.temperature,
                               top_k=args.top_k, top_p=args.top_p)
        skey = jax.random.fold_in(key, 1)
    t0 = time.perf_counter()
    tokens, state = gen.generate(params, state, args.new_tokens,
                                 sample=sampler, key=skey)
    jax.block_until_ready(tokens)
    dt = time.perf_counter() - t0
    dist_print(f"decode {args.new_tokens} steps: {dt * 1e3:.1f} ms "
               f"({dt / args.new_tokens * 1e3:.1f} ms/token)")
    dist_print(f"tokens:\n{np.asarray(tokens)}")

    if args.w8a8 and args.model == "llama":
        from triton_dist_tpu.models.llama_w8a8 import (
            make_w8a8_forward, place_w8a8_params, quantize_params_w8a8)
        from jax.sharding import NamedSharding, PartitionSpec as P
        qp = place_w8a8_params(
            quantize_params_w8a8(params, cfg, world=n), cfg, mesh,
            axis="sp")
        fwd = make_w8a8_forward(cfg, mesh, axis="sp")
        seq = jnp.concatenate([prompt, tokens], axis=1).T  # [S, B]
        pad = (-seq.shape[0]) % n
        seq = jnp.pad(seq, ((0, pad), (0, 0)))
        seq = jax.device_put(seq, NamedSharding(mesh, P("sp")))
        ql = np.asarray(fwd(qp, seq))
        fl = np.asarray(jax.jit(lambda s: gen._prefill_jit(params, s.T)[1]
                                )(seq))
        fl = np.transpose(fl, (1, 0, 2))  # [S, B, V]
        cos = (ql * fl).sum() / (np.linalg.norm(ql) * np.linalg.norm(fl))
        dist_print(f"w8a8 prompt scoring vs float: cosine {cos:.4f}")

    dist_print("done")


if __name__ == "__main__":
    main()
