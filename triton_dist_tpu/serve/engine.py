"""The continuous-batching step loop over a paged KV cache.

Each :meth:`ServeEngine.step` is one scheduler iteration (Orca's
iteration-level scheduling):

1. **Admit** waiting requests into free batch slots while the block
   manager can cover their prompt (+1 decode block of headroom).
2. **Prefill** admitted prompts in chunks against a per-request scratch
   cache (``Generator._chunk_jit`` — the chunked-prefill machinery),
   metered by the scheduler's token budget so long prompts interleave
   with in-flight decode; a completed prompt's K/V scatter into the
   request's pool pages and the request joins the decode batch.
3. **Decode** all running rows — with a decode ``horizon`` H > 1, up to
   H steps FUSE into one device dispatch (``_paged_decode_horizon`` —
   the program bodies are ``serve/programs.py``'s, pure functions of
   arrays under this module and ``serve/mesh.py`` alike: a
   traced scan with on-device sampling and KV commit, pipelined so the
   host commits horizon N's token burst while the device runs horizon
   N+1 — docs/serving.md "Decode horizon"; a step the scheduler clamps
   to one decode step is ONE link of that program at H = 1); on an
   engine without a horizon, one batched forward per step through
   ``kernels/flash_decode.gqa_decode_paged_shard`` — per-row lengths,
   per-row block tables, the r5 ``active`` mask semantics (retired/free
   rows freeze; their dummy K/V writes redirect to the reserved null
   block so freed pages can never be corrupted — the paged twin of the
   ``_write_rows`` overflow rule).  With a draft model attached, the
   decode step becomes a speculative round: the draft proposes ``k``
   tokens per row and ONE multi-token verify pass scores every row at
   its own length (the r5 ``q_lens`` batched-verify contract), accepts
   applying per row.  The WHOLE round — draft k-step scan, verify,
   seeded accept, closing decode for both models — is one traced
   program (``programs._spec_round_fused``) chained ``pipeline`` deep
   on a device-resident carry, with adaptive per-row ``k`` bucketed
   down a pow2 k-ladder; sampled requests ride the same seeded accept
   chain (docs/serving.md "Speculative decoding").

Requests retire individually (their blocks free immediately); when a
running request cannot extend its allocation, the scheduler preempts the
latest-admitted request (recompute-style: emitted tokens are kept and the
victim re-prefills ``prompt + generated``).

Compilation is BOUNDED and observable (PR 2): prefill always runs the
one fixed ``[1, prefill_width]`` shape (a request's share of a step's
budget in calls as wide as the weights pay for; a residual padded, its
K/V writes zero-masked via ``n_valid``), scratch extents and the page
scatter bucket to a powers-of-two ladder, :meth:`ServeEngine.warmup`
pre-compiles the lot, and every program's trace-cache hit/miss/stall
counters ride ``ServeMetrics`` (docs/serving.md "bucket ladder").

Failures are CONTAINED (PR 3, docs/serving.md "Failure containment"):
requests carry optional deadlines (expired WAITING/PREFILL requests are
swept each step), ``submit()`` enforces an optional queue bound with a
shed-or-raise policy, a poison request — a raising ``on_token``
callback, a failing forward, a failed mid-decode block grow — is
quarantined (retired ``FinishReason.ERROR``, blocks freed) while its
slot-mates keep decoding (batched-forward failures bisect over the
batch to isolate the poison row), every device dispatch runs under an
optional step watchdog, and the step loop drives a synchronous
:class:`runtime.watchdog.Heartbeat` so an external supervisor sees a
wedged engine as a stale file.  A ``runtime.faults.FaultInjector``
threads through the engine/block-manager seams so every containment
path is exercised by deterministic chaos tests.

The process itself is EXPENDABLE (PR 5, docs/serving.md "Crash
recovery"): with ``snapshot_dir=`` every submit/commit/retire appends
to a durable token journal and ``snapshot_every=N`` captures the paged
KV pools + a state manifest through the ``runtime/checkpoint`` Orbax
path; :meth:`ServeEngine.restore` rebuilds a fresh engine whose every
resumed stream is bit-identical to the uninterrupted run — tokens are
emitted exactly once across the crash (journal-matching rows resume in
place, journal-ahead rows replay through the exact-recompute
preemption path; serve/recovery.py holds the argument).

The engine is MESH-AWARE (PR 12, docs/serving.md "Sharded serving"):
``mesh=``/``tp_axis=``/``kv_shard=`` rebuild every device program above
as a ``shard_map`` body (serve/mesh.py) — TP weights + head-sharded
pools (``"heads"``: Megatron attention, per-rank paged decode, spec
rounds included) or replicated weights + block-sharded pools through
``sp_gqa_decode_paged_shard`` (``"seq"``: SP flash-decode with a
partitioned block allocator).  The scheduler, block tables, journal,
and step loop are unchanged host machinery; streams stay bit-identical
to the world-1 engine and snapshots restore across mesh shapes.

KV pools are float by default, or INT8 with per-page scale planes
(ISSUE 17, docs/serving.md "Quantized serving"): construct the
``Generator`` with ``kv_dtype=jnp.int8`` and every pool layer becomes a
``{"q": int8 [NB, Hkv, page, D], "s": f32 [NB, Hkv, page]}`` pair —
``_scatter_kv`` quantizes rows as they land (``flash_decode.quantize_kv``,
the contiguous cache's recipe) and writes every plane, float or int8,
through its ``[NB * Hkv, page, ...]`` view: the indexed dimensions of a
scatter must be adjacent, or the chip re-lays the whole plane out and
back around each write.  The scale plane moves WITH its page
through fill/gather/COW/snapshot/migration (never a dequant/requant
round trip — quantization is not idempotent, so bit-reproducibility
demands the bytes move as bytes), and attention dequantizes inside
``gqa_decode_paged_shard``'s fused int8 path.  The emitted stream is
bit-reproducible (same stream every run; snapshot/restore/migrate
bit-exact; mesh bit-identical to quantized world-1) and tracked against
the fp oracle by an explicit acceptance metric — the two-gate split
ROADMAP #3 prescribes.  Speculative decoding over int8 pools is a
recorded follow-up (rejected loudly at construction).

Scope: a ``Generator`` of the dense Llama family or a
``MlaMoeGenerator`` (latent attention + experts) — each hands the engine
its cache planes and block seams (``kv_planes``, ``serve_hooks()``,
``wrap_program``, ``kernel_gaps``); batch-1 SP serving keeps the
contiguous `Generator.generate` path.
"""

from __future__ import annotations

import contextlib
import functools
import math
import os
import sys
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from triton_dist_tpu.kernels.flash_decode import paged_kernel_blocking
from triton_dist_tpu.models.generate import (  # noqa: F401
    GenerationState,
    Generator,
    attention_kernel_gaps,
)
from triton_dist_tpu.models.sampling import takes_candidates
from triton_dist_tpu.runtime import dump as ir_dump
from triton_dist_tpu.runtime import topology
from triton_dist_tpu.runtime.faults import FaultInjector
from triton_dist_tpu.runtime.jit_cache import (
    CountingJit,
    bucket_down,
    named,
    pow2_ladder,
)
from triton_dist_tpu.runtime.watchdog import (
    Heartbeat,
    WatchdogTimeout,
    run_with_watchdog,
)
from triton_dist_tpu.serve import mesh as serve_mesh
from triton_dist_tpu.serve.block_manager import (
    BlockExhausted,
    BlockManager,
    KvGroups,
    KvGroupsUnsupported,
    StateCacheUnsupported,
    StateSlots,
)
from triton_dist_tpu.serve.metrics import RequestMetrics, ServeMetrics
from triton_dist_tpu.serve.programs import (
    _copy_pool_block,
    _draft_decode_forward,
    _fill_pool_pages,
    _gather_pool_pages,
    _key_batch,
    _paged_decode_forward,
    _paged_decode_horizon,
    _paged_decode_step,
    _paged_verify_forward,
    _sample_token,
    _spec_round_fused,
    _splice_draft_rows,
    _zero_scratch,
)
from triton_dist_tpu.serve.recovery import (
    JOURNAL_NAME,
    TokenJournal,
    has_restorable_state,
)
from triton_dist_tpu.serve.request import (
    SLO_CLASSES,
    FinishReason,
    Request,
    RequestOutput,
    SamplingParams,
    slo_rank,
)
from triton_dist_tpu.serve.scheduler import FCFSScheduler, ReqState, Status
from triton_dist_tpu.serve.trace import MIGRATE_EVENT_TAIL, FlightRecorder


class QueueFull(RuntimeError):
    """``submit()`` rejected a request: the waiting queue is at
    ``max_queue`` and the engine runs the ``"raise"`` overload policy
    (the ``"shed"`` policy retires the request ``FinishReason.SHED``
    instead of raising)."""


class ChainCommitted(RuntimeError):
    """A pipelined decode-horizon chain failed AFTER some of its token
    bursts were already committed: the retry/bisect machinery must NOT
    re-run it (a retry would double-emit the committed bursts), so it
    escalates out of ``step()`` like a consumed-pool failure."""


# Exceptions containment must NEVER swallow: a tripped step watchdog is
# an engine-level stall (the caller decides whether to checkpoint or
# abort), and interrupts/exits belong to the process.
_FATAL = (WatchdogTimeout, KeyboardInterrupt, SystemExit)


def _refuse_latent(latent: bool, what: str) -> None:
    """What has not been carried over to latent (MLA) pools — the latent
    rows' plane, and the index keys' beside it under learned sparse
    attention — refuses by name, where the engine is built or the entry
    point is called."""
    if latent:
        from triton_dist_tpu.models.mla_moe import LatentPoolUnsupported

        raise LatentPoolUnsupported(
            f"{what}: not served on latent (MLA) pools yet (neither the "
            f"latent rows' plane nor an indexer's key plane carries it)")


def _kv_groups(gen):
    """The cache groups a generator names (``kv_groups``) where it names
    several, else None: one group is the engine as it always was."""
    groups = list(getattr(gen, "kv_groups", ()) or ())
    return groups if len(groups) > 1 else None


def _state_group(groups):
    """The STATE group among a generator's cache groups (one fixed slot a
    running request: it names ``state_planes``), or None."""
    return next((g for g in groups or () if g.get("state_planes")), None)


def _refuse_groups(groups, what: str) -> None:
    """What has not been carried over to a cache of several layer GROUPS
    (window and global layers: one block table a group, pools of their own
    geometry — docs/serving.md) refuses by name, where the engine is built
    or the entry point is called."""
    if groups and _state_group(groups):
        raise StateCacheUnsupported(
            f"{what}: not served beside a state group yet — a request "
            f"holds a fixed slot of state-space state beside its pages "
            f"({', '.join(g['name'] for g in groups)}), and no prefix hit, "
            f"layout or format carries the state at a position")
    if groups:
        raise KvGroupsUnsupported(
            f"{what}: not served over cache groups "
            f"({', '.join(g['name'] for g in groups)}) yet — a request has "
            f"one block table a group and no format or layout carries them")


def build_bucket_ladder(base: int, cap: int, page: int) -> list[int]:
    """The powers-of-two scratch-extent ladder: rungs double from
    ``base`` (rounded up to a page multiple) until ``cap`` (the largest
    extent any admissible prompt needs), which always closes the ladder.
    Every rung is a multiple of ``page`` so a bucketed scratch reshapes
    cleanly into pool pages."""
    if base < 1 or cap < 1:
        raise ValueError(f"ladder needs base, cap >= 1; got {base}, {cap}")
    cap = -(-cap // page) * page
    rungs = []
    r = -(-base // page) * page
    while r < cap:
        rungs.append(r)
        r *= 2
    rungs.append(cap)
    return rungs


# Past this many rows a prefill call is bound by its products and a wider
# one buys nothing a row.  A call reads every weight once whatever its
# rows: at bf16 that is 2 FLOP a row for every 2 bytes of weight, so it
# turns from weight-bound to product-bound at the chip's FLOP a byte — the
# v5e's 197 TF/s over 819 GB/s = ~240 rows.  256 is that ridge in whole
# 128-row chunks.  Measured on the v5e (PERF.md §6 "PR 33": Mistral-7B at
# 16 layers, 7.5 GB of weights, extent 2,048): one call of 128 / 256 /
# 384 / 512 rows takes 13.6 / 16.0 / 22.3 / 30.8 ms = 106 / 62 / 58 /
# 60 us a row — 256 rows share the 9.2 ms read of the weights as well as
# 512 do, two calls of 256 cost what one of 512 costs, and a residual or a
# request's first tokens on a step's leftover budget pad to 256, not 512
# (in the engine: 1,113-1,129 tokens/s at 256 against 1,043-1,052 at 512).
_PREFILL_WIDTH_MAX = 256


def prefill_width(prefill_chunk: int, prefill_budget: int) -> int:
    """Rows of ONE ``prefill_chunk`` program call: what the weights pay
    for, not the scheduler's metering granule.  The step's budget up to
    ``_PREFILL_WIDTH_MAX``, in whole chunks, and never under one chunk —
    so an engine whose chunk is at or past the ridge, or whose budget is
    one chunk, runs ``[1, prefill_chunk]`` calls as it always did."""
    return max(prefill_chunk,
               min(prefill_budget, _PREFILL_WIDTH_MAX)
               // prefill_chunk * prefill_chunk)


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------


class ServeEngine:
    """Continuous-batching serving over one :class:`Generator`.

    Usage::

        engine = ServeEngine(gen, params, num_blocks=64, page_size=16,
                             max_batch=8)
        engine.warmup()                 # pre-compile the bucket ladder
        engine.submit(Request("r0", prompt_tokens,
                              SamplingParams(max_new_tokens=32)))
        outputs = engine.run()          # step() until drained

    ``draft``/``draft_params`` + ``spec_k`` turn every decode step into a
    speculative round: up to ``spec_k + 1`` tokens per row per round,
    same emitted stream as serving without the draft (greedy AND seeded
    sampled — the accept chain scores proposals against the target's own
    per-index stream).  The whole round is ONE device dispatch chained
    ``pipeline`` deep, and ``spec_adaptive=W`` picks each row's k from a
    W-round acceptance window (docs/serving.md "Speculative decoding").

    ``horizon=H`` fuses up to H decode steps into ONE device dispatch
    (on-device sampling, per-row EOS/max-token/page-boundary early exit)
    and ``pipeline=N`` chains N such dispatches with a device-resident
    carry — the host drains token bursts instead of paying a round trip
    per token.  Streams are bit-identical at every H (docs/serving.md
    "Decode horizon"); the scheduler clamps fused decode back to
    one step whenever prefill interleaving, waiting-queue deadlines,
    or speculative rounds need iteration-level scheduling, and the
    engine runs that step as one link at H = 1 (no option: it has the
    program and runs no speculative rounds, or it does not).

    **Shape bucketing** (docs/serving.md): prefill always runs the ONE
    fixed shape ``[1, prefill_width]`` — the step's budget up to 256
    rows, in whole ``prefill_chunk``s, which stay the scheduler's
    metering granule (a residual pads, its K/V writes zero-masked by
    ``n_valid``) — and each prompt's scratch extent
    rounds up a powers-of-two ``bucket_ladder`` — so O(len(ladder))
    compiled programs cover EVERY prompt length, and :meth:`warmup`
    pre-compiles them all so steady-state serving never compiles.
    Trace-cache hit/miss/compile-stall counters live in
    ``metrics.summary()["compilation"]``.
    """

    def __init__(self, gen: Generator, params, *, num_blocks: int,
                 page_size: int, max_batch: int = 8,
                 mesh=None, tp_axis: str = "tp",
                 sp_axis: str = "sp",
                 kv_shard: str = "heads",
                 w8a8: bool = False,
                 prefill_chunk: int = 64,
                 prefill_budget: Optional[int] = None,
                 bucket_ladder: Optional[list] = None,
                 horizon: int = 1, pipeline: int = 2,
                 draft: Optional[Generator] = None, draft_params=None,
                 spec_k: int = 0, spec_adaptive: int = 8,
                 clock=time.monotonic,
                 max_queue: Optional[int] = None, overload: str = "shed",
                 class_aware: bool = False,
                 brownout: Optional[dict] = None,
                 step_timeout_s: Optional[float] = None,
                 heartbeat: Optional[str] = None,
                 heartbeat_interval_s: float = 10.0,
                 faults: Optional[FaultInjector] = None,
                 fault_retries: int = 1,
                 snapshot_dir: Optional[str] = None,
                 snapshot_every: Optional[int] = None,
                 journal_fsync: bool = False,
                 journal_fsync_interval_s: Optional[float] = None,
                 journal_rotate_bytes: Optional[int] = None,
                 journal_retain_done: Optional[int] = 4096,
                 prefix_cache: bool = True,
                 trace_level: int = 1, trace_events: int = 4096):
        assert gen.attn.world == 1, (
            "ServeEngine owns its own mesh placement (pass mesh=/"
            "tp_axis=/kv_shard= — docs/serving.md 'Sharded serving'); "
            "the Generator itself must stay world-1 (it only provides "
            "the model cfg and, off-mesh, the chunked-prefill program)")
        # int8 paged KV (docs/serving.md "Quantized serving"): a
        # Generator built with kv_dtype=jnp.int8 switches every pool
        # layer to {"q", "s"} dicts; the stream is bit-reproducible but
        # NOT the fp stream, so speculative decode (whose accept chain
        # assumes the target's own fp logits) is a recorded follow-up.
        self.kv_quant = bool(gen.attn.quantized)
        # A model family says what it is on its generator (dense:
        # models/generate.py; latent attention + experts: models/
        # mla_moe.py): the planes of a layer's cache, the seams of its
        # block, and the counters its programs hand out.  What has not
        # been carried over to one-plane (latent) pools refuses HERE, by
        # name — never a quiet fallback.
        self.kv_planes = list(gen.kv_planes)
        self.latent = gen.latent
        for what, asked in (
                ("a mesh (sharded latent pools)", mesh is not None),
                ("int8 pools", self.kv_quant),
                ("w8a8 weights", w8a8),
                ("speculative rounds (spec_k / draft)",
                 bool(spec_k) or draft is not None),
                ("snapshot_dir (journal, snapshot / restore)",
                 snapshot_dir is not None)):
            _refuse_latent(self.latent and asked, what)
        # Layers of different reach (sliding-window beside full attention)
        # fall into cache GROUPS — the generator names them
        # (``kv_groups``); one group is the engine as it always was.
        self.kv_groups = _kv_groups(gen)
        # ... and one of them may be a STATE group: a fixed slot a request
        self._has_state = _state_group(self.kv_groups) is not None
        for what, asked in (
                ("a mesh", mesh is not None),
                ("int8 pools", self.kv_quant),
                ("w8a8 weights", w8a8),
                ("speculative rounds (spec_k / draft)",
                 bool(spec_k) or draft is not None),
                ("snapshot_dir (journal, snapshot / restore)",
                 snapshot_dir is not None),
                ("prefix_cache=True (a hit would have to hold in every "
                 "group: the whole prefix in the full group, the last "
                 "window before its end in the window group)",
                 bool(prefix_cache))):
            _refuse_groups(asked and self.kv_groups, what)
        if self.kv_quant and spec_k:
            raise ValueError(
                "int8 KV pools cannot drive speculative decoding yet "
                "(recorded follow-up, ROADMAP #3): the draft/verify "
                "round assumes fp target logits — serve with spec_k=0 "
                "or a float kv_dtype")
        if draft is not None and draft.attn.quantized:
            raise ValueError(
                "the draft Generator must keep float KV (its contiguous "
                "caches are served unquantized); only the target's "
                "paged pools quantize")
        # w8a8 weights (docs/serving.md "Quantized serving"): the two
        # hook seams (out_proj / ffn) run int8 GEMMs; QKV, norms and the
        # KV pools are orthogonal (w8a8 composes with either kv dtype).
        self.w8a8 = bool(w8a8)
        if self.w8a8 and spec_k:
            raise ValueError(
                "w8a8 weights cannot drive speculative decoding yet "
                "(recorded follow-up, ROADMAP #3): the draft/verify "
                "round's target forwards are unhooked — serve with "
                "spec_k=0 or float weights")
        if self.w8a8 and mesh is not None and kv_shard != "heads":
            raise ValueError(
                "w8a8 is a tensor-parallel weight layout: supported "
                "world-1 and kv_shard='heads' (the seq and heads+seq "
                "layouts keep float weights on their sp bodies; "
                "recorded follow-up)")
        cfg = gen.cfg
        # mesh serving (docs/serving.md "Sharded serving"): with mesh=,
        # every device program below is rebuilt as a shard_map over the
        # tp_axis — TP weights + head-sharded pools (kv_shard="heads"),
        # replicated weights + block-sharded pools with SP flash-decode
        # (kv_shard="seq"), or BOTH on a 2D mesh (kv_shard="heads+seq":
        # heads over tp_axis, blocks over sp_axis).  Geometry that
        # cannot divide the mesh is rejected HERE, loudly, instead of
        # as a shape error inside a traced forward.
        self.mesh = mesh
        self.tp_axis = tp_axis
        self.sp_axis = sp_axis
        self.kv_shard = kv_shard
        self.mesh_world = 1
        self.sp_world = 1
        self._pool_sharding = None
        if mesh is None and kv_shard not in ("heads", "seq",
                                             "heads+seq"):
            # validated even off-mesh: a typo'd layout must not ride
            # silently until a mesh= is added later
            raise ValueError(
                f"kv_shard must be 'heads', 'seq' or 'heads+seq', "
                f"got {kv_shard!r}")
        if mesh is not None:
            self.mesh_world = serve_mesh.validate_mesh_geometry(
                mesh=mesh, tp_axis=tp_axis, kv_shard=kv_shard, cfg=cfg,
                max_seq=gen.max_seq, num_blocks=num_blocks,
                page_size=page_size, spec_k=spec_k, sp_axis=sp_axis)
            if kv_shard == "seq":
                self.sp_world = self.mesh_world
            elif kv_shard == "heads+seq":
                self.sp_world = int(mesh.shape[sp_axis])
        if gen.max_seq % page_size:
            raise ValueError(
                f"max_seq {gen.max_seq} must divide by page_size "
                f"{page_size} (the block table is fixed-width)")
        if spec_k:
            assert draft is not None and draft_params is not None, (
                "spec_k needs draft + draft_params")
            assert draft.max_seq >= gen.max_seq, (
                "draft max_seq must cover the target's")
        if overload not in ("shed", "raise"):
            raise ValueError(
                f"overload must be 'shed' or 'raise', got {overload!r}")
        if max_queue is not None and max_queue < 0:
            raise ValueError(f"max_queue must be >= 0, got {max_queue}")
        if horizon < 1:
            raise ValueError(f"horizon must be >= 1, got {horizon}")
        if pipeline < 1:
            raise ValueError(f"pipeline must be >= 1, got {pipeline}")
        if spec_adaptive < 0:
            raise ValueError(
                f"spec_adaptive must be >= 0 (0 disables adaptive k), "
                f"got {spec_adaptive}")
        self.gen = gen
        self.cfg = cfg
        self.params = params
        self.page = page_size
        self.max_batch = max_batch
        self.n_pages_max = gen.max_seq // page_size
        # prefix cache (docs/serving.md "Prefix caching"): paged blocks
        # are content-addressed and ref-counted — admission maps a
        # prompt's longest cached block-aligned prefix in read-only and
        # chunked prefill starts at the first divergent chunk; freed
        # committed blocks linger in an LRU cache tier until allocation
        # pressure reclaims them.
        self.prefix_cache = bool(prefix_cache)
        # kv_shard="seq" partitions the block-id space per rank (rank r
        # owns pool rows [r*NB/W, (r+1)*NB/W) = the pages of its
        # sequence span); the allocator places every logical page in
        # its owner's partition and reserves one null block per
        # partition (serve/block_manager.py).  Under "heads+seq" the
        # partition count is the SP world — the tp axis splits heads
        # inside each block, never the block-id space.
        seq_shards = self.sp_world
        if self.kv_groups:
            # One allocator a group, each over its own block-id space:
            # ``num_blocks`` is the full group's; a window group's count is
            # DERIVED — what ``max_batch`` rows can hold at once.  A row's
            # live span is its window plus what one chain of links may
            # write ahead (``horizon * pipeline`` rows): ``(window +
            # ahead - 2) // page + 2`` pages however it lies on the page
            # grid, so the group can never be the one that runs out.
            # A STATE group (state-space layers) has one fixed slot a row,
            # and the null slot.
            ahead = horizon * pipeline if horizon > 1 else 1
            self.group_blocks = [
                1 + max_batch if g.get("state_planes") else
                num_blocks if not g["window"] else 1 + max_batch * (
                    (g["window"] + ahead - 2) // page_size + 2)
                for g in self.kv_groups]
            self.bm = KvGroups({
                g["name"]: StateSlots(max_batch, page_size)
                if g.get("state_planes") else
                BlockManager(nb, page_size, faults=faults,
                             window=g["window"])
                for g, nb in zip(self.kv_groups, self.group_blocks)})
        else:
            self.group_blocks = [num_blocks]
            self.bm = BlockManager(num_blocks, page_size, faults=faults,
                                   prefix_cache=self.prefix_cache,
                                   shards=seq_shards,
                                   pages_per_shard=self.n_pages_max
                                   // seq_shards)
        # the decode programs' table operand: [B, pages], or one a group
        self._tables_shape = (
            (len(self.kv_groups),) if self.kv_groups else ()) + (
            max_batch, self.n_pages_max)
        self.scheduler = FCFSScheduler(
            self.bm,
            prefill_budget=prefill_budget or 4 * prefill_chunk,
            prefill_chunk=prefill_chunk, class_aware=class_aware)
        self.class_aware = bool(class_aware)
        # Graceful-degradation ladder (docs/serving.md "Overload, SLO
        # classes & autoscaling"): brownout=dict(...) arms an ordered
        # response to SUSTAINED pressure — a smoothed (clock-driven EMA)
        # max of queue backlog and KV utilization climbs the rungs after
        # `dwell_steps` consecutive over-high steps and descends after
        # as many under-low steps:
        #   0 full service
        #   1 speculative k clamped to 1
        #   2 chunked-prefill token budget halved
        #   3 best_effort max_new_tokens capped (best_effort_cap)
        #   4 incoming best_effort shed
        #   5 incoming batch shed too
        #   6 incoming interactive refused (the old cliff, now last)
        # brownout=None (default) skips the evaluation entirely — the
        # ladder is provably inert (no state reads on the step path).
        self.brownout_cfg = None
        if brownout is not None:
            b = dict(brownout)
            high = float(b.pop("high", 0.85))
            low = float(b.pop("low", 0.55))
            window_s = float(b.pop("window_s", 1.0))
            dwell_steps = int(b.pop("dwell_steps", 4))
            best_effort_cap = int(b.pop("best_effort_cap", 4))
            if b:
                raise ValueError(
                    f"unknown brownout keys: {sorted(b)} (expected "
                    f"high/low/window_s/dwell_steps/best_effort_cap)")
            if not 0.0 < low < high:
                raise ValueError(
                    f"brownout needs 0 < low < high, got low={low} "
                    f"high={high}")
            if window_s < 0 or dwell_steps < 1 or best_effort_cap < 1:
                raise ValueError(
                    f"brownout needs window_s >= 0, dwell_steps >= 1, "
                    f"best_effort_cap >= 1; got {window_s}, "
                    f"{dwell_steps}, {best_effort_cap}")
            self.brownout_cfg = {
                "high": high, "low": low, "window_s": window_s,
                "dwell_steps": dwell_steps,
                "best_effort_cap": best_effort_cap,
            }
        self.brownout_rung = 0
        self._pressure_ema = 0.0
        self._pressure_t: Optional[float] = None
        self._brownout_dwell = 0
        self._base_prefill_budget = self.scheduler.prefill_budget
        # Rows of one ``prefill_chunk`` call, from the budget the engine
        # was BUILT with: the brownout ladder's halving fills calls less
        # and compiles nothing.
        self.prefill_width = prefill_width(prefill_chunk,
                                           self._base_prefill_budget)
        if self._has_state:
            # a call that starts off a multiple of its rows slides back
            # and feeds rows again (_call_window): rows a scan has been
            # through cannot be fed twice, so a call is ONE chunk and every
            # call starts where the last one ended
            self.prefill_width = prefill_chunk
        self.metrics = ServeMetrics()
        self.metrics.prefill_width = self.prefill_width
        # the query heads of each attention group's layers, where the
        # generator states them (they may differ by kind: swa_moe)
        self.metrics.swa_heads = {g["name"]: g["heads"]
                                  for g in self.kv_groups or ()
                                  if "heads" in g}
        if self._has_state:
            sg = _state_group(self.kv_groups)
            self.metrics.state_kind = sg.get("kind", "ssm")
            self.metrics.state_layers = len(sg["layers"])
            self.metrics.state_bytes_per_request = len(sg["layers"]) * sum(
                math.prod(sh) * jnp.dtype(dt).itemsize
                for sh, dt in sg["state_planes"])
        # flight recorder (docs/observability.md): a bounded ring of
        # typed engine events — submit/admit/prefill/decode drains, spec
        # rounds, preemptions, COW splits, faults, retirements — that
        # exports per-request Perfetto spans, flushes to
        # flight_<step>.json on any fault/crash path, and rides
        # snapshots so a restored engine carries its previous life's
        # trail.  trace_level=0 turns the hot-path appends off entirely
        # (every cell of the benchmark runs at the default, 1; what
        # that costs on the chip has no reading: PERF.md section 7).
        if trace_level < 0:
            raise ValueError(f"trace_level must be >= 0, got {trace_level}")
        self.trace = FlightRecorder(capacity=trace_events,
                                    level=trace_level)
        self.metrics.attach_recorder(self.trace)
        # per-program wall-time attribution (docs/observability.md
        # "Kernel observability"): behind the SAME trace_level knob as
        # the recorder, register_compiled below wires every program's
        # CountingJit timer into metrics.observe_program — step time
        # decomposes by device program (summary()["programs"],
        # serve_program_ms{program=}); one knob turns the timers and
        # the ring off together.
        self.metrics.program_timing = trace_level >= 1
        self._trace_fault_idx = 0   # audit entries already mirrored
        self._last_flight_step = -1  # flush throttle: one file per step
        self.draft = draft
        self.draft_params = draft_params
        self.spec_k = int(spec_k)
        # speculative rounds (docs/serving.md "Speculative decoding"):
        # the whole draft-propose / verify / accept / closing-decode
        # round runs as ONE traced program, chained `pipeline` deep on a
        # device-resident carry.  spec_adaptive is the acceptance-rate
        # window behind the scheduler's per-row k (0 = fixed k).
        self.spec_adaptive = int(spec_adaptive)
        # decode horizon (docs/serving.md "Decode horizon"): up to
        # `horizon` decode steps fuse into one device dispatch with
        # on-device sampling; `pipeline` chains that many dispatches
        # back-to-back with a device-resident carry, so the host commits
        # horizon N's burst while the device executes horizon N+1.
        self.horizon = int(horizon)
        self.pipeline = int(pipeline)
        self.h_ladder = pow2_ladder(self.horizon) if self.horizon > 1 else [1]
        # failure containment (docs/serving.md "Failure containment")
        self.max_queue = max_queue
        self.overload = overload
        self.step_timeout_s = step_timeout_s
        self.faults = faults
        self.fault_retries = int(fault_retries)
        self.heartbeat = (Heartbeat(heartbeat,
                                    interval_s=heartbeat_interval_s)
                          if heartbeat is not None else None)
        self._last_beat = float("-inf")
        self._spec_off = False  # latched by a failed speculative round
        if faults is not None:
            clock = faults.wrap_clock(clock)
        self._clock = clock
        # crash recovery (docs/serving.md "Crash recovery"): with a
        # snapshot_dir, every submit/commit/retire appends to the token
        # journal, and snapshot_every=N captures the KV pools + manifest
        # each N steps (the journal may run AHEAD of the KV snapshot;
        # restore replays the journal-ahead suffix through recompute).
        if snapshot_every is not None and snapshot_every < 1:
            raise ValueError(
                f"snapshot_every must be >= 1, got {snapshot_every}")
        self.snapshot_dir = snapshot_dir
        self.snapshot_every = snapshot_every
        # journal durability/size knobs (docs/serving.md "Crash
        # recovery"): fsync batching at a configurable interval, and
        # rotation/compaction at snapshot barriers once the file passes
        # the byte bound (:meth:`_rotate_journal`).
        if (journal_fsync_interval_s is not None
                and journal_fsync_interval_s < 0):
            raise ValueError(f"journal_fsync_interval_s must be >= 0, "
                             f"got {journal_fsync_interval_s}")
        if journal_rotate_bytes is not None and journal_rotate_bytes < 1:
            raise ValueError(f"journal_rotate_bytes must be >= 1, "
                             f"got {journal_rotate_bytes}")
        if journal_retain_done is not None and journal_retain_done < 0:
            raise ValueError(f"journal_retain_done must be >= 0, "
                             f"got {journal_retain_done}")
        self.journal_fsync_interval_s = journal_fsync_interval_s
        self.journal_rotate_bytes = journal_rotate_bytes
        # Rotation retention bound: without one, every finished request
        # ever served would be rewritten as a `done` record at every
        # rotation — the compacted file (and each rewrite's cost) would
        # still grow O(total requests), and a floor above
        # journal_rotate_bytes would re-trigger a full-history rewrite
        # at every snapshot barrier.  Keeping only the newest N finished
        # requests (and pruning the older ones from the engine's output
        # map with them) is what actually bounds a long-lived engine's
        # journal AND memory; None keeps the full history.
        self.journal_retain_done = journal_retain_done
        # file size right after the last rewrite: rotation re-triggers
        # only once the file at least doubles past it, so rewrite cost
        # stays amortized O(1) per appended byte even when the retained
        # floor sits above journal_rotate_bytes.
        self._journal_floor = 0
        self._snap_seq = 0
        self._last_snap_step = 0
        self._in_warmup = False
        self._journal: Optional[TokenJournal] = None
        self._snap_mgr = None  # CheckpointManager, cached per directory
        if snapshot_dir is not None:
            os.makedirs(snapshot_dir, exist_ok=True)
            jpath = os.path.join(snapshot_dir, JOURNAL_NAME)
            if has_restorable_state(snapshot_dir):
                # A FRESH engine appending a second life to an existing
                # journal would interleave reused request ids with the
                # previous run's records — replay keeps first
                # occurrences, so a later restore would resurrect OLD
                # prompts under new ids.  Only restore() may reopen a
                # populated directory.
                raise ValueError(
                    f"snapshot_dir {snapshot_dir!r} already holds "
                    f"serving state from a previous life; resume it "
                    f"with ServeEngine.restore(...) or point the fresh "
                    f"engine at a clean directory")
            self._journal = TokenJournal(
                jpath, fsync=journal_fsync,
                fsync_interval_s=journal_fsync_interval_s,
                faults=self.faults)

        # The scratch-extent bucket ladder: every prefill's s_ext (and
        # with it the _chunk_jit extent and the _fill_fn table width)
        # rounds up to a rung, so O(len(ladder)) traces cover every
        # prompt length instead of one per distinct shape.  The cap is
        # the largest extent an admissible prompt can need (submit()
        # holds prompt <= max_seq - 1).
        cap = self._scratch_need(gen.max_seq - 1)
        width = self.prefill_width
        if bucket_ladder is None:
            self.ladder = build_bucket_ladder(
                max(page_size, width), cap, page_size)
        else:
            rungs = sorted({int(r) for r in bucket_ladder})
            bad = [r for r in rungs
                   if r % page_size or r < prefill_chunk]
            if bad:
                raise ValueError(
                    f"bucket_ladder rungs must be multiples of page_size "
                    f"{page_size} and hold one prefill_chunk "
                    f"{prefill_chunk}; got {bad}")
            # a rung under one call's rows cannot take its write: such
            # rungs fold into the first that can (no program of their own)
            first = -(-width // page_size) * page_size
            rungs = sorted({max(r, first) for r in rungs})
            if rungs[-1] < cap:
                rungs.append(-(-cap // page_size) * page_size)
            self.ladder = rungs

        impl = gen.attn.ctx.impl
        interpret = gen.attn.ctx.interpret
        # Kernel reach (docs/serving.md "Kernel reach"): the attention
        # paths that geometry or dispatch will keep OFF the Pallas
        # kernels, visible in metrics.summary()["kernel_gaps"] and, on a
        # TPU — where a silent XLA reroute is a lost kernel, not a test
        # convenience — said once at construction.
        self.kernel_gaps = gen.kernel_gaps(
            page_size=page_size, prefill_chunk=width,
            ladder=self.ladder, sp_world=self.sp_world)
        self.metrics.kernel_gaps = self.kernel_gaps
        # Which form each program's expert combine takes: a family with
        # expert layers reads it off the rows the program carries.
        if hasattr(gen, "moe_combine_forms"):
            rows = {"prefill_chunk": width, "paged_decode": max_batch}
            if self.horizon > 1:
                rows["decode_horizon"] = max_batch
            self.metrics.moe_combine = gen.moe_combine_forms(rows)
            # ... and, where the residual is several streams, the rows
            # each program carries through the two mixes
            if hasattr(gen, "stream_rows"):
                self.metrics.hc = gen.stream_rows(rows)
                self.kernel_gaps.update(self.metrics.hc.get("gaps", {}))
        # How the paged decode call is blocked (static, decided where the
        # programs are built — kernels/flash_decode.py): the KV heads a
        # step carries of the heads THIS rank holds, the grid steps of a
        # call.  Empty when the call runs as XLA.
        self.paged_attn_blocking = (
            {} if "paged_decode" in self.kernel_gaps or self.latent
            else paged_kernel_blocking(
                self.kv_planes[0][0] // (self.mesh_world // self.sp_world),
                page_size, self.kv_planes[0][1],
                jnp.dtype(cfg.dtype).itemsize, batch=max_batch))
        self.metrics.paged_attn_blocking = self.paged_attn_blocking
        if topology.is_tpu():
            for prog, why in self.kernel_gaps.items():
                print(f"[serve] {prog} attention will run as XLA, not "
                      f"the Pallas kernel — {why}", file=sys.stderr)
            if self.paged_attn_blocking:
                print(f"[serve] paged decode attention: "
                      f"{self.paged_attn_blocking}", file=sys.stderr)
        # The family's seams, bound into every program below.  w8a8
        # swaps the weight tree ONCE, host-side, before any program
        # captures it; its hooks ride the same ffn / out_proj seams the
        # mesh TP bodies use, so every program stays one copy.
        hooks = gen.serve_hooks()
        # Where ONE cache is read by layers that own none (cross-attention
        # over an earlier layer's K and V), the layers that read each
        # group's table, for the reach counters; None where every reader
        # owns its cache.
        self._cache_readers = None
        if self.kv_groups:
            readers = {g["name"]: sum(k.group == gi for k in hooks["kinds"])
                       for gi, g in enumerate(self.kv_groups)}
            if any(readers[g["name"]] != len(g["layers"])
                   for g in self.kv_groups):
                self._cache_readers = readers
        # a family's programs may end in counters of their own (the MoE
        # tally): ``_note_aux`` takes them off every program's outputs
        wrap = gen.wrap_program
        self._aux_pending: list = []
        self._aux_folded = 0    # counter outputs folded so far
        w8a8_hooks = {}
        if self.w8a8:
            from triton_dist_tpu.models import llama_w8a8

            params = llama_w8a8.quantize_serve_params(
                params, cfg,
                world=self.mesh_world if mesh is not None else 1)
            self.params = params
            w8a8_hooks = {
                "ffn": functools.partial(
                    llama_w8a8.w8a8_serve_ffn, impl=impl,
                    interpret=interpret),
                "out_proj": functools.partial(
                    llama_w8a8.w8a8_serve_out_proj, impl=impl,
                    interpret=interpret),
            }
            hooks.update(w8a8_hooks)
        fwd_kw = dict(cfg=cfg, page=page_size, **hooks)
        # Every jitted program is wrapped for trace-cache accounting
        # (runtime/jit_cache.CountingJit): hit/miss/compile-stall
        # counters ride ServeMetrics onto the TDT_DUMP_IR dump path.
        # Each is built through ``named`` under the name its CountingJit
        # and _device_call use, so a device trace reads
        # ``jit_paged_decode``, not ``jit__unknown`` for all of them.
        if mesh is not None:
            # Mesh placement (docs/serving.md "Sharded serving"): every
            # program is the SAME traced math rebuilt as a shard_map
            # body, under the same names/ladders/donation — warmup, the
            # step loop, and the metrics plumbing below need no mesh
            # branches.  serve_mesh.ShardedProgram canonicalizes every
            # argument's sharding at the call seam, so host-built and
            # device-carried calls share one executable per program
            # (the PR-7 cache-fork problem, closed for good).
            from jax.sharding import NamedSharding

            progs = serve_mesh.build_programs(
                mesh=mesh, tp_axis=tp_axis, kv_shard=kv_shard, cfg=cfg,
                params=params, page_size=page_size,
                num_blocks=num_blocks, n_pages_max=self.n_pages_max,
                impl=impl, interpret=interpret, horizon=self.horizon,
                draft=draft if spec_k else None,
                draft_params=draft_params, prefix_cache=self.prefix_cache,
                kv_quant=self.kv_quant, w8a8=self.w8a8,
                sp_axis=sp_axis)
            self._mesh_progs = progs
            self._pool_sharding = NamedSharding(mesh, progs["pool_spec"])
            # Weights live TP-sharded (heads) / replicated (seq) on the
            # mesh for the engine's lifetime (a no-op for params built
            # on that layout already); the pools are born on theirs.
            self.params = progs["paged_decode"].place(0, params)
            self._decode_fn = CountingJit(progs["paged_decode"],
                                          "paged_decode")
            if self.horizon > 1:
                self._horizon_fn = CountingJit(progs["decode_horizon"],
                                               "decode_horizon",
                                               timed_statics=("H",))
            self._fill_fn = CountingJit(progs["fill_pages"],
                                        "fill_pages")
            self._load_fn = CountingJit(progs["load_pages"],
                                        "load_pages")
            self._cow_fn = CountingJit(progs["cow_copy"], "cow_copy")
            self._chunk_fn = CountingJit(progs["prefill_chunk"],
                                         "prefill_chunk")
        else:
            self._decode_fn = CountingJit(jax.jit(named(
                wrap(_paged_decode_step), "paged_decode", **fwd_kw),
                donate_argnums=(1,)), "paged_decode")
            decode_fwd = wrap(functools.partial(_paged_decode_forward,
                                                **fwd_kw))
            if self.horizon > 1:
                # One program per (horizon rung, greedy-or-mixed): the
                # scan length is static, so the ladder bounds the trace
                # count and warmup() sweeps every rung (the
                # prompt-extent ladder's twin for the decode side).
                self._horizon_fn = CountingJit(jax.jit(
                    named(_paged_decode_horizon, "decode_horizon",
                          decode_fwd=decode_fwd),
                    static_argnames=("H", "all_greedy"),
                    donate_argnums=(1,)), "decode_horizon",
                    timed_statics=("H",))
            # scratch is not donatable (the page reshape transposes it);
            # pools are — the scatter updates them in place.
            self._fill_fn = CountingJit(jax.jit(named(
                _fill_pool_pages, "fill_pages", page=page_size,
                **({"kinds": hooks["kinds"]} if self.kv_groups else {})),
                donate_argnums=(0,)), "fill_pages")
            # Prefix-cache device programs: the warm-prefill gather
            # (pools read back into scratch — NOT donated, the pools
            # live on) keyed by the s_ext rung like fill_pages, and the
            # one-page COW copy (traced src/dst: one program total).
            self._load_fn = CountingJit(jax.jit(named(
                _gather_pool_pages, "load_pages", page=page_size)),
                "load_pages")
            self._cow_fn = CountingJit(jax.jit(
                named(_copy_pool_block, "cow_copy"),
                donate_argnums=(0,)), "cow_copy")
            # The Generator's chunked-prefill program; the trace cache
            # lives on the Generator (shared with prefill_chunked/
            # speculative), the counters here see this engine's calls.
            # w8a8 needs its own jit: the Generator's program has the
            # float seams bound, and preemption recompute-exactness
            # requires the SAME hooked program for cold and re-prefill.
            self._chunk_fn = CountingJit(
                gen.chunk_program(**w8a8_hooks) if self.w8a8
                else gen._chunk_jit, "prefill_chunk")
        # Zeroed pools, born on their mesh layout (None off-mesh): a mesh
        # engine's pools never exist whole on one device — at real widths
        # they would not fit beside its weight shard.
        # A plane is [blocks, heads, page, width] at ITS heads and width
        # (K and V alike; a latent row beside a narrower index key).
        if self.kv_quant:
            # int8 pools: the quant plane plus its per-(head, row) scale
            # plane — one scale per (block, head, in-page row), the exact
            # shape _scatter_kv's quantize_kv emits, living in the SAME
            # pool tuple so pages and scales can never travel separately.
            def zpool(nb, h, d):
                return {"q": jnp.zeros((nb, h, page_size, d),
                                       jnp.int8, device=self._pool_sharding),
                        "s": jnp.zeros((nb, h, page_size),
                                       jnp.float32,
                                       device=self._pool_sharding)}
        else:
            def zpool(nb, h, d):
                return jnp.zeros((nb, h, page_size, d), cfg.dtype,
                                 device=self._pool_sharding)
        # A layer's planes hold its GROUP's block count (one group: all of
        # them ``num_blocks``) — of the group it OWNS a pool in
        # (``kv_groups[..]["layers"]``): a layer that reads another's cache,
        # or none, has no planes.  A state group's planes are one slot's
        # (shape, dtype) a block: ``_plane_specs[li]`` says which.
        if self.kv_groups:
            self._plane_specs = [()] * cfg.n_layers
            layer_blocks = [0] * cfg.n_layers
            for g, nb in zip(self.kv_groups, self.group_blocks):
                for li in g["layers"]:
                    self._plane_specs[li] = tuple(
                        g.get("state_planes") or self.kv_planes)
                    layer_blocks[li] = nb
        else:
            self._plane_specs = [tuple(self.kv_planes)] * cfg.n_layers
            layer_blocks = [num_blocks] * cfg.n_layers
        self._pools = [
            tuple(zpool(nb, *p) if isinstance(p[0], int) else
                  jnp.zeros((nb, *p[0]), p[1]) for p in planes)
            for nb, planes in zip(layer_blocks, self._plane_specs)]
        self._sample_fn = CountingJit(
            jax.jit(named(_sample_token, "sample_token")), "sample_token")
        # A cold request's scratch: ONE program of no arguments a rung
        # (its tree is a function of ``_plane_specs`` alone), where an
        # eager ``jnp.zeros`` a plane is two launches a plane with the
        # chip idle.  On a mesh it is born on the chunk program's spec.
        self._zero_fn = CountingJit(
            self._mesh_progs["zero_scratch"] if mesh is not None
            else jax.jit(named(
                _zero_scratch, "zero_scratch", specs=self._plane_specs,
                quantized=self.kv_quant, dtype=cfg.dtype),
                static_argnames=("s_ext",)), "zero_scratch")
        for c in (self._chunk_fn, self._fill_fn, self._decode_fn,
                  self._sample_fn, self._zero_fn):
            self.metrics.register_compiled(c)
        if self.horizon > 1:
            self.metrics.register_compiled(self._horizon_fn)
        if self.prefix_cache:
            self.metrics.register_compiled(self._load_fn)
            self.metrics.register_compiled(self._cow_fn)
        self.metrics.attach_block_manager(self.bm)
        # KV capacity observability (docs/observability.md "KV
        # capacity"): pool bytes are THE capacity currency — stamp the
        # real allocated footprint (quant + scale planes both) and the
        # token-slot count so bytes/token and fleet-wide sums fall out.
        index_width = sum(d for _, d in self.kv_planes[1:])   # latent pools
        self.metrics.set_kv_capacity(
            pool_bytes=sum(int(x.size) * x.dtype.itemsize
                           for x in jax.tree_util.tree_leaves(self._pools)),
            token_slots=num_blocks * page_size,
            quantized=self.kv_quant,
            row=({"latent_row_width": cfg.latent_width,
                  "stored_row_width": cfg.head_dim,
                  "index_key_width": index_width,
                  "latent_bytes_per_token":
                      (cfg.latent_width + index_width) * cfg.n_layers
                      * jnp.dtype(cfg.dtype).itemsize}
                 if self.latent else None))
        # cache-tier reclaims happen inside the allocator; the hook puts
        # them on the flight-recorder timeline (an eviction storm under
        # allocation pressure is a classic tail-latency culprit)
        self.bm.on_evict = (
            lambda b: self.trace.emit("evict", None, block=int(b)))

        self.slots: list[Optional[ReqState]] = [None] * max_batch
        self._states: dict[str, ReqState] = {}
        self._outputs: dict[str, RequestOutput] = {}
        # terminal outputs produced OUTSIDE a step (class-aware
        # displacement sheds inside submit()): already retired, they
        # ride the next step()'s finished batch so polling controllers
        # see them exactly once
        self._shed_pending: list[RequestOutput] = []
        # distributed-tracing context per live request (docs/
        # observability.md "Fleet observability"): {"trace_id", "hop"} —
        # stamped by the fleet controller (or defaulted at submit),
        # carried by migration manifests and the journal, bumped one hop
        # per adopting life, so one request's journey is ONE trace
        # however many replicas serve it.
        self._trace_ctx: dict[str, dict] = {}
        # speculative-mode device state ([B]-shaped, slot-indexed)
        if self.spec_k:
            # The draft joins through the SAME padded fixed-chunk
            # machinery as the target (its own _chunk_jit + an extent
            # ladder of chunk multiples), so spec-mode admission is
            # fully compile-free after warmup — the ROADMAP follow-up
            # that used to leave draft.prefill compiling per prompt
            # length.  _splice_draft_rows lands the prefilled row in
            # the slot-indexed batch caches (traced slot/length: one
            # program per rung).
            # Rungs are multiples of lcm(chunk, page): one chunked
            # prefill trace per rung as before, AND the scratch
            # reshapes cleanly into DRAFT pool pages (the draft-side
            # prefix cache below).
            self._draft_ladder = build_bucket_ladder(
                prefill_chunk, gen.max_seq - 1,
                prefill_chunk * page_size
                // math.gcd(prefill_chunk, page_size))
            if mesh is not None:
                # On a mesh the draft runs REPLICATED per rank (its
                # slot-indexed batch caches are whole-batch host-managed
                # state), but its programs must still be shard_map
                # bodies so every array stays in one NamedSharding
                # world — a single-device draft program fed mesh-placed
                # carries would fork executables and bounce buffers
                # across placements every round.
                self._draft_chunk_fn = CountingJit(
                    self._mesh_progs["draft_prefill"], "draft_prefill")
                self._draft_join_fn = CountingJit(
                    self._mesh_progs["draft_join"], "draft_join")
            else:
                if getattr(draft._chunk_jit, "__name__", "") \
                        != "draft_prefill":
                    # The draft Generator's own chunk program, under the
                    # name it has here (an outer jit: the inner one is
                    # inlined into it).  Once per draft: engines sharing
                    # a draft share it.
                    draft._chunk_jit = jax.jit(
                        named(draft._chunk_jit, "draft_prefill"),
                        static_argnames=("quantized", "extent"),
                        donate_argnums=(2,))
                self._draft_chunk_fn = CountingJit(draft._chunk_jit,
                                                   "draft_prefill")
                # temp caches (arg 3) are NOT donatable: the splice
                # reads a sliced view of them into the batch caches
                self._draft_join_fn = CountingJit(
                    jax.jit(named(_splice_draft_rows, "draft_join"),
                            donate_argnums=(0, 1, 2)),
                    "draft_join")
            dcfg = draft.cfg
            # the draft's temp caches, from the same body (K and V a layer)
            self._draft_zero_fn = CountingJit(
                self._mesh_progs["draft_zero_scratch"] if mesh is not None
                else jax.jit(named(
                    _zero_scratch, "draft_zero_scratch",
                    specs=[((dcfg.n_kv_heads, dcfg.head_dim),) * 2]
                    * dcfg.n_layers, quantized=False, dtype=dcfg.dtype),
                    static_argnames=("s_ext",)), "draft_zero_scratch")
            for c in (self._draft_chunk_fn, self._draft_join_fn,
                      self._draft_zero_fn):
                self.metrics.register_compiled(c)
            self._last_logits = jnp.zeros((max_batch, cfg.vocab),
                                          jnp.float32)
            self._draft_state = GenerationState(
                caches=[(jnp.zeros((max_batch, dcfg.n_kv_heads,
                                    draft.max_seq, dcfg.head_dim),
                                   dcfg.dtype),
                         jnp.zeros((max_batch, dcfg.n_kv_heads,
                                    draft.max_seq, dcfg.head_dim),
                                   dcfg.dtype))
                        for _ in range(dcfg.n_layers)],
                kv_lens=jnp.zeros((max_batch,), jnp.int32),
                last_logits=jnp.zeros((max_batch, dcfg.vocab),
                                      jnp.float32))
            # One-dispatch fused rounds (docs/serving.md "Speculative
            # decoding"): the k-ladder is the verify scan's static-K
            # bucket set (one trace per rung x {greedy, mixed}, swept
            # by warmup); pools (arg 2) and the draft batch caches
            # (arg 3) are donated like every decode-path program.
            self._k_ladder = pow2_ladder(self.spec_k)
            if mesh is not None:
                # The whole round as ONE shard_map body: target verify /
                # decode legs sharded like every other program of the
                # layout, draft replicated, seeded accept on replicated
                # logits (serve/mesh.build_programs).
                self._spec_fn = CountingJit(
                    self._mesh_progs["spec_round"], "spec_round",
                    timed_statics=("K",))
                self._draft_tail_fn = CountingJit(
                    self._mesh_progs["draft_tail_step"],
                    "draft_tail_step")
            else:
                # The draft steps inside the trace through the
                # MESH-FREE _draft_decode_forward (see its docstring:
                # shard_map-placed carries would fork the executable
                # cache into flavors warmup cannot enumerate).
                draft_fwd = functools.partial(
                    _draft_decode_forward, cfg=dcfg,
                    impl=draft.attn.ctx.impl,
                    interpret=draft.attn.ctx.interpret)
                self._spec_fn = CountingJit(jax.jit(
                    named(
                        _spec_round_fused, "spec_round",
                        draft_step=draft_fwd, decode_fwd=decode_fwd,
                        verify_fwd=functools.partial(
                            _paged_verify_forward, **fwd_kw)),
                    static_argnames=("K", "all_greedy"),
                    donate_argnums=(2, 3)), "spec_round",
                    timed_statics=("K",))
                # The k<=0 tail's closing draft step — the same
                # mesh-free forward, standalone (going through
                # draft.step would hand the next chain NamedSharding
                # draft caches and recompile every rung).
                self._draft_tail_fn = CountingJit(jax.jit(
                    named(draft_fwd, "draft_tail_step"),
                    donate_argnums=(1,)), "draft_tail_step")
            self.metrics.register_compiled(self._spec_fn)
            self.metrics.register_compiled(self._draft_tail_fn)
            # Draft-side prefix cache (the ISSUE-7 warm-admit fix): the
            # draft's prompt K/V pages live in draft-geometry pools
            # UNDER THE SAME BLOCK IDS as the target's, validated
            # against the content index key at read time — a warm
            # target admit then skips the draft's already-known prefix
            # too instead of re-prefilling the full prompt draft-side.
            self._draft_pools = None
            self._draft_page_key: dict[int, tuple] = {}
            if self.prefix_cache:
                self._draft_pools = [
                    (jnp.zeros((num_blocks, dcfg.n_kv_heads, page_size,
                                dcfg.head_dim), dcfg.dtype),
                     jnp.zeros((num_blocks, dcfg.n_kv_heads, page_size,
                                dcfg.head_dim), dcfg.dtype))
                    for _ in range(dcfg.n_layers)]
                if mesh is not None:
                    self._draft_fill_fn = CountingJit(
                        self._mesh_progs["draft_fill_pages"],
                        "draft_fill_pages")
                    self._draft_load_fn = CountingJit(
                        self._mesh_progs["draft_load_pages"],
                        "draft_load_pages")
                else:
                    self._draft_fill_fn = CountingJit(jax.jit(
                        named(_fill_pool_pages, "draft_fill_pages",
                              page=page_size),
                        donate_argnums=(0,)), "draft_fill_pages")
                    self._draft_load_fn = CountingJit(jax.jit(
                        named(_gather_pool_pages, "draft_load_pages",
                              page=page_size)),
                        "draft_load_pages")
                self.metrics.register_compiled(self._draft_fill_fn)
                self.metrics.register_compiled(self._draft_load_fn)

    # -- submission -------------------------------------------------------

    def submit(self, req: Request) -> Optional[RequestOutput]:
        """Queue a request.  Returns ``None`` on acceptance; under the
        ``"shed"`` overload policy a request arriving with the waiting
        queue at ``max_queue`` is retired immediately with
        ``FinishReason.SHED`` and its output returned (the ``"raise"``
        policy raises :class:`QueueFull` instead — backpressure the
        frontend can propagate)."""
        with self.trace.span("submit"):
            return self._submit(req, bounded=True)

    def _submit(self, req: Request,
                bounded: bool = True) -> Optional[RequestOutput]:
        if req.request_id in self._states:
            raise ValueError(f"duplicate request id {req.request_id!r}")
        total = int(req.prompt.shape[0]) + req.params.max_new_tokens
        if total > self.gen.max_seq:
            raise ValueError(
                f"{req.request_id}: prompt + max_new_tokens = {total} "
                f"exceeds max_seq {self.gen.max_seq}")
        fit = self.bm.fit_error(total)
        if fit is not None:
            raise ValueError(f"{req.request_id}: {fit}")
        if req.arrival_time is None:
            req.arrival_time = self._clock()
        # Brownout ingress rungs (4/5/6): under a deep enough rung the
        # request's class is refused at the door regardless of queue
        # headroom — rung 4 sheds best_effort, 5 adds batch, 6 finally
        # refuses interactive (the old single cliff, now the LAST rung).
        browned_out = (bounded and self.brownout_cfg is not None
                       and self.brownout_rung >= 4
                       and slo_rank(req.slo_class)
                       >= 6 - self.brownout_rung)
        overloaded = (bounded and self.max_queue is not None
                      and self.scheduler.queue_depth >= self.max_queue)
        displaced: Optional[ReqState] = None
        if browned_out:
            msg = (f"brownout rung {self.brownout_rung}: "
                   f"{req.slo_class} ingress shed")
            overloaded = True
        elif overloaded:
            # Bounded admission: shedding at submit() keeps an overload
            # from growing an unbounded queue of requests that would
            # only expire later — the caller learns immediately.
            msg = (f"queue at bound ({self.scheduler.queue_depth} >= "
                   f"max_queue {self.max_queue})")
            if self.overload == "raise":
                # Raised BEFORE any journal record exists: the frontend
                # was told this request never entered the engine, so a
                # restore must not resurrect and serve it.
                raise QueueFull(f"{req.request_id}: {msg}")
            if self.class_aware:
                # Class-aware displacement: a full queue never sheds a
                # request while a WORSE class holds a queue slot — the
                # latest, lowest-tier waiting request is shed instead
                # and the arrival takes its place (so interactive is
                # only refused once the queue is all-interactive).
                displaced = self.scheduler.pick_shed_victim(
                    slo_rank(req.slo_class))
                if displaced is not None:
                    overloaded = False
        if req.trace is None:
            # a bare engine starts the journey itself: the request id is
            # fleet-unique within any one controller (duplicates are
            # rejected), and the fleet stamps richer ids before submit
            req.trace = {"trace_id": req.request_id, "hop": 0}
        self._trace_ctx[req.request_id] = req.trace
        if self._journal_on(req.request_id):
            # Journaled before the shed retirement below: a shed writes
            # its finish record right after, so restore accounts it.
            self._journal.submit(req)
            self._note_journal()
        rs = ReqState(req=req,
                      metrics=RequestMetrics(arrival_time=req.arrival_time))
        self.trace.emit("submit", req.request_id,
                        prompt=int(req.prompt.shape[0]),
                        max_new=req.params.max_new_tokens)
        self.metrics.observe_class_submit(req.slo_class)
        if overloaded:
            self._states[req.request_id] = rs
            self.metrics.shed += 1
            return self._retire(rs, FinishReason.SHED, free=False,
                                error=msg)
        if displaced is not None:
            # The victim's terminal output cannot return from THIS call
            # (submit answers for the arrival only): it retires now —
            # journal finish, metrics, trace, on_finish all fire here —
            # and the output joins the next step()'s finished batch so
            # a polling controller finalizes its stream too.
            self.scheduler.waiting.remove(displaced)
            self.metrics.shed += 1
            self._shed_pending.append(self._retire(
                displaced, FinishReason.SHED, free=False,
                error=(f"displaced by {req.request_id} "
                       f"({req.slo_class} over "
                       f"{displaced.req.slo_class})")))
        if (self.brownout_cfg is not None and self.brownout_rung >= 3
                and req.slo_class == "best_effort"):
            # rung 3 caps best_effort output length at the door too —
            # a cap that only touched in-flight rows would leak full-
            # length best_effort admitted during the brownout
            rs.new_cap = self.brownout_cfg["best_effort_cap"]
        self._states[req.request_id] = rs
        self.scheduler.add(rs)
        return None

    def abort(self, request_id: str) -> Optional[RequestOutput]:
        """Cancel a request wherever it is; returns its (partial) output.
        Safe mid-step (e.g. from an ``on_token`` callback): the commit
        loops skip rows that retired under them."""
        rs = self._states.get(request_id)
        if rs is None or rs.status is Status.FINISHED:
            return self._outputs.get(request_id)
        if rs.status is Status.WAITING:
            self.scheduler.waiting.remove(rs)
            return self._retire(rs, FinishReason.ABORT, free=False)
        return self._retire(rs, FinishReason.ABORT)

    def has_work(self) -> bool:
        return bool(self.scheduler.waiting) or any(
            s is not None for s in self.slots)

    def has_request(self, request_id: str) -> bool:
        """True when the engine knows this id (queued, running, or
        finished) — a resuming frontend uses it to skip re-submitting
        requests the restored journal already carries."""
        return request_id in self._states

    def unfinished_rids(self) -> list[str]:
        """Ids still in flight (WAITING / PREFILL / RUNNING) — what a
        no-argument :meth:`drain` would hand off.  The network drain
        endpoint (serve/net.py) filters retried rids through this, so a
        drain whose first attempt already landed is a no-op, never an
        error."""
        return [rid for rid, rs in self._states.items()
                if rs.status is not Status.FINISHED
                and not rid.startswith("__warmup_")]

    # -- crash recovery ---------------------------------------------------

    def _journal_on(self, rid: str) -> bool:
        return self._journal is not None and not rid.startswith("__warmup_")

    def _note_journal(self) -> None:
        self.metrics.journal_records = self._journal.records
        self.metrics.journal_bytes = self._journal.bytes

    def _place_pools(self, pools: list) -> list:
        """Lay restored/imported pool arrays out on this engine's mesh
        (no-op off-mesh).  Snapshots hold GLOBAL arrays — orbax
        assembles them regardless of the writer's mesh — so restore
        onto a different mesh shape is one ``device_put`` per leaf
        (docs/serving.md "Sharded serving": recovery across meshes)."""
        if self._pool_sharding is None:
            return pools
        s = self._pool_sharding
        # tree_map covers both pool layouts: bare float arrays and the
        # quantized {"q", "s"} dicts (one sharding leaf fits every plane
        # — P(None, axis) shards the Hkv axis of 4D quant and 3D scale
        # arrays alike; P(axis) shards their block axis).
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, s), pools)

    def snapshot(self, directory: Optional[str] = None) -> dict:
        """Durably capture the FULL serving state — paged KV pools +
        block tables (via the ``runtime/checkpoint`` Orbax path) and
        per-request journal records (prompt, sampling params + PRNG
        stream position, emitted tokens, kv_lens, status, deadline
        timestamps) — such that :meth:`restore` rebuilds an engine whose
        every resumed stream is bit-identical to the uninterrupted run.

        Call between steps (the engine auto-snapshots there with
        ``snapshot_every=N``).  ``directory`` defaults to the engine's
        ``snapshot_dir``.  Returns ``{"step", "ms"}``; latency and
        journal overhead ride ``metrics.summary()["recovery"]``.
        See serve/recovery.py for the format and the exactly-once
        argument; docs/serving.md "Crash recovery" for the recipe."""
        from triton_dist_tpu.serve import recovery

        _refuse_latent(self.latent, "snapshot()")
        _refuse_groups(self.kv_groups, "snapshot()")
        d = directory or self.snapshot_dir
        if d is None:
            raise ValueError("snapshot() needs a directory: pass one or "
                             "construct the engine with snapshot_dir=")
        info = recovery.snapshot_engine(self, d)
        self.metrics.hist_snapshot.observe(info["ms"] / 1e3)
        self.trace.emit("snapshot", None, step=info["step"],
                        ms=round(info["ms"], 3))
        # A one-shot capture to a foreign directory must not delay the
        # next periodic home-directory snapshot.
        if (self.snapshot_dir is not None
                and os.path.abspath(d) == os.path.abspath(self.snapshot_dir)):
            self._last_snap_step = self.metrics.steps
            if (self.journal_rotate_bytes is not None
                    and self._journal is not None
                    and self._journal.file_bytes
                    > self.journal_rotate_bytes
                    and self._journal.file_bytes
                    >= 2 * self._journal_floor):
                self._rotate_journal()
        return info

    def _rotate_journal(self) -> None:
        """Compact the token journal at a snapshot barrier (docs/
        serving.md "Crash recovery"): each finished request's
        submit/tok/fin record train collapses into ONE ``done`` line
        (prompt, params, tokens, finish — everything a restore rebuilds
        from, so replay semantics are unchanged), and in-flight requests
        rewrite as fresh submit/tok records.  The rewrite is atomic
        (tmp + rename), runs only AFTER the barrier's KV snapshot
        published (a crash mid-rotation leaves a journal some snapshot
        fully covers), and bounds the file: without it a long-lived
        engine's journal grows with every token it ever served
        (ROADMAP #5a).  ``journal_retain_done=N`` caps the rewrite at
        the N most recently finished requests — the older ones leave
        the journal AND the engine's request/output maps (so
        ``get_output`` forgets them; a restore never resurrects a
        finished request either way)."""
        if self.journal_retain_done is not None:
            done = sorted(
                (rid for rid, rs in self._states.items()
                 if rs.status is Status.FINISHED
                 and not rid.startswith("__warmup_")),
                key=lambda rid: (
                    self._states[rid].metrics.finish_time or 0.0,
                    self._states[rid].seq))
            n_drop = len(done) - self.journal_retain_done
            for rid in done[:max(0, n_drop)]:
                del self._states[rid]
                self._outputs.pop(rid, None)
                # the per-request metrics map grows with every request
                # ever retired; pruned history leaves it too, or
                # summary()/prefix_stats() iteration cost (and RSS)
                # would still grow O(total requests forever)
                self.metrics.requests.pop(rid, None)
        recs = []
        for rid, rs in self._states.items():
            if rid.startswith("__warmup_"):
                continue
            if rs.status is Status.FINISHED:
                out = self._outputs.get(rid)
                if out is None:
                    continue
                recs.append({
                    "t": "done", "rid": rid,
                    "prompt": [int(x) for x in np.asarray(rs.req.prompt)],
                    "params": rs.req.params.to_dict(),
                    "slo": rs.req.slo_class,
                    "arrival": rs.req.arrival_time,
                    # carried explicitly: the windowed tts None-pads its
                    # head on long streams, so "first retained ts" would
                    # inflate a restored TTFT by the whole decode
                    "ftt": rs.metrics.first_token_time,
                    "toks": [int(t) for t in out.token_ids],
                    # time_at: the bounded window's base shifts on long
                    # streams — never index the raw list (None pads
                    # forgotten entries, keeping toks[i] <-> tts[i])
                    "tts": [rs.metrics.time_at(i)
                            for i in range(len(out.token_ids))],
                    "reason": out.finish_reason.value,
                    "err": out.error,
                    "fts": rs.metrics.finish_time,
                })
            else:
                recs.append({
                    "t": "submit", "rid": rid,
                    "prompt": [int(x) for x in np.asarray(rs.req.prompt)],
                    "params": rs.req.params.to_dict(),
                    "slo": rs.req.slo_class,
                    "ts": rs.req.arrival_time,
                    "ftt": rs.metrics.first_token_time,
                    # in-flight rows keep their trace context across
                    # rotation: a crash-path manifest rebuilt from the
                    # compacted journal must still carry the journey
                    "trace": self._trace_ctx.get(rid)})
                for i, t in enumerate(rs.generated):
                    recs.append({
                        "t": "tok", "rid": rid, "i": i, "tok": int(t),
                        "ts": rs.metrics.time_at(i)})
        self._journal.rewrite(recs)
        self._journal_floor = self._journal.file_bytes
        self.metrics.journal_rotations += 1
        self._note_journal()

    @classmethod
    def restore(cls, directory, gen, params, **kwargs) -> "ServeEngine":
        """Rebuild an engine from :meth:`snapshot` state (plus the token
        journal) under ``directory``.  Requests whose journal matches
        the KV snapshot resume IN PLACE (pools, block table, pending
        token); journal-ahead or non-fitting requests re-queue through
        admission and replay via the exact-recompute preemption path —
        either way every resumed stream is bit-identical to the
        uninterrupted run.  See :func:`serve.recovery.restore_engine`
        for the knobs (``on_token=`` re-attachment, ``replay_tokens=``,
        geometry overrides)."""
        from triton_dist_tpu.serve import recovery

        _refuse_latent(gen.latent, "restore()")
        _refuse_groups(_kv_groups(gen), "restore()")
        return recovery.restore_engine(directory, gen, params, **kwargs)

    # -- live migration ---------------------------------------------------

    def drain(self, rids: Optional[list] = None, *,
              include_kv: bool = True, push: bool = False) -> dict:
        """Migrate-out: remove ``rids`` (default: every unfinished
        request) from this engine and return a migration manifest a
        peer replica's :meth:`migrate_in` continues from — the
        cooperative half of fleet live migration (docs/serving.md
        "Fleet serving"; serve/fleet.py drives it).

        Call between steps (no dispatch in flight).  Each request's
        journal-segment view rides the manifest (prompt, params, the
        emitted token prefix + timestamps); a plain RUNNING row with a
        pending token additionally carries its live KV pages (gathered
        through the warm-prefix ``load_pages`` program) so the target
        adopts it MID-STREAM with zero recompute — the same invariant
        the restore path's in-place resume checks.  ``include_kv=False``
        drops the pages (every row then replays through exact recompute
        on the target — still bit-exact, just not free).

        The source journal gets one ``mig`` record per request — the
        ownership receipt: a later restore of THIS directory never
        resurrects a handed-off request, so the cross-replica token
        union stays exactly-once.  The drained requests leave the
        engine's maps entirely (they are not retirements — no output,
        no finish accounting).

        ``push=True`` keeps the identical receipt/release semantics but
        frames the hand-off as a disaggregated prefill→decode PUSH
        (docs/serving.md "Disaggregated serving"): the ring records
        ``push_out`` instead of ``migrate_out`` and the
        ``pushed_out`` counter advances instead of ``migrated_out`` —
        tier hand-offs and failure migrations stay separately
        observable."""
        from triton_dist_tpu.serve.recovery import MANIFEST_FORMAT

        _refuse_latent(self.latent, "push_out() of latent pages" if push
                            else "drain() / migrate-out")
        _refuse_groups(self.kv_groups, "push_out() (disaggregated push)"
                       if push else "drain() / migrate-out")

        if rids is None:
            rids = self.unfinished_rids()
        rids = list(dict.fromkeys(rids))  # a duplicate would double-free
        now = self._clock()
        spec_live = bool(self.spec_k) and not self._spec_off
        # Two phases: build EVERY record (validation + KV gather — no
        # engine mutation, the gather only reads the pools) first, then
        # journal the receipts and release the state.  A bad rid or a
        # failed gather must leave the engine exactly as it was — a
        # partially-drained engine whose receipted requests never made
        # it into a manifest would lose their streams irrecoverably
        # (restore skips migrated rids by design).
        staged = []
        # per-request ring tails, gathered ONCE (before any migrate_out
        # event lands in the ring): the manifest carries each request's
        # recent event trail so the adopting replica's ring continues
        # the journey — the merged fleet timeline then shows one
        # connected track across replicas (docs/observability.md
        # "Fleet observability")
        tails: dict[str, list] = {}
        rid_set = set(rids)
        for ts, step, etype, r, data in self.trace.events():
            if r in rid_set:
                tails.setdefault(r, []).append([ts, step, etype, data])
        for rid in rids:
            rs = self._states.get(rid)
            if rs is None or rs.status is Status.FINISHED:
                raise ValueError(f"drain: {rid!r} is not an in-flight "
                                 f"request of this engine")
            rec = {
                "rid": rid,
                "prompt": [int(x) for x in np.asarray(rs.req.prompt)],
                "params": rs.req.params.to_dict(),
                "slo": rs.req.slo_class,
                "arrival": rs.req.arrival_time,
                "tokens": [int(t) for t in rs.generated],
                "tok_ts": [rs.metrics.time_at(i)
                           for i in range(len(rs.generated))],
                "first_tok": rs.metrics.first_token_time,
                "first_sched": rs.metrics.first_scheduled_time,
                "n_preempt": rs.metrics.n_preemptions,
                "cb_off": rs.callback_disabled,
                "trace": dict(self._trace_ctx.get(rid)
                              or {"trace_id": rid, "hop": 0}),
                "events": tails.get(rid, [])[-MIGRATE_EVENT_TAIL:],
            }
            # In-place eligibility is the restore invariant: a plain
            # RUNNING row between steps holds kv_len committed cache
            # rows and ONE emitted-but-unconsumed pending token
            # (kv_len == S0 + len(generated) - 1).  Spec rows have no
            # pending token (their round state is slot-indexed draft
            # caches that cannot leave this engine) — they replay.
            if (include_kv and not spec_live
                    and rs.status is Status.RUNNING
                    and rs.pending_token is not None):
                n_used = self.bm.blocks_for(rs.kv_len)
                ext = self._bucket_s_ext(rs.kv_len)
                ids = np.zeros((ext // self.page,), np.int32)
                ids[:n_used] = self.bm.table(rid)[:n_used]
                scratch = self._device_call(
                    "load_pages", (rid,), self._load_fn, self._pools,
                    jnp.asarray(ids))
                def _host(x):
                    # quantized scratch travels as int8 bytes + scales —
                    # HALF the fp wire bytes, and never requantized
                    if isinstance(x, dict):
                        return {"q": np.asarray(x["q"]),
                                "s": np.asarray(x["s"])}
                    return np.asarray(x)
                rec["kv"] = [(_host(k), _host(v)) for k, v in scratch]
                rec["kv_len"] = rs.kv_len
                rec["pending"] = int(rs.pending_token)
                rec["s_ext"] = ext
            staged.append((rid, rs, rec))
        reqs = []
        for rid, rs, rec in staged:
            if self._journal_on(rid):
                self._journal.migrate(rid, len(rs.generated), now)
                self._note_journal()
            ctx = rec["trace"]
            self.trace.emit("push_out" if push else "migrate_out", rid,
                            tokens=len(rs.generated),
                            in_place="kv" in rec,
                            trace=ctx["trace_id"], hop=ctx["hop"],
                            # flow id of the hand-off this record opens:
                            # the adopting replica's migrate_in closes
                            # the SAME id (its hop is ours + 1), and the
                            # merged Perfetto export draws the arrow
                            flow=f"{ctx['trace_id']}#{ctx['hop'] + 1}")
            self._trace_ctx.pop(rid, None)
            if rs.slot is not None:
                self.slots[rs.slot] = None
            if rs.status is Status.WAITING:
                self.scheduler.waiting.remove(rs)
            if rid in self.bm._tables:
                self.bm.free(rid)
            rs.scratch = None
            rs.status = Status.FINISHED  # terminal for the old object
            del self._states[rid]
            if push:
                self.metrics.pushed_out += 1
            else:
                self.metrics.migrated_out += 1
            reqs.append(rec)
        cfg = self.cfg
        return {
            "format": MANIFEST_FORMAT,
            "clock": now,
            "page_size": self.page,
            "kv_geom": {
                "n_layers": cfg.n_layers,
                "n_kv_heads": cfg.n_kv_heads,
                "head_dim": cfg.head_dim,
                "dtype": str(np.dtype(cfg.dtype)),
                # pool quantization is part of the geometry: int8 pages
                # cannot adopt into fp pools (or vice versa) in place —
                # a mismatched target requeues the request for exact
                # recompute instead
                "kv_quant": self.kv_quant,
            },
            "requests": reqs,
            "finished": [],
        }

    def migrate_in(self, manifest: dict, *,
                   on_token=None, replay_tokens: bool = False,
                   push: bool = False) -> dict:
        """Adopt a migration manifest's requests mid-stream — the target
        half of fleet live migration (docs/serving.md "Fleet serving").

        CAPACITY ADMISSION first, per request: a request whose
        ``prompt + max_new_tokens`` cannot ever fit this engine's
        geometry, whose id this engine already knows, or that would land
        on a waiting queue at ``max_queue`` is REJECTED (left for the
        caller to place elsewhere — nothing about it is journaled
        here).  Accepted requests split two ways:

        - **adopted in place**: the manifest carries live KV + a pending
          token, the page geometry matches, a batch slot is free, and
          the blocks fit — the pages scatter into this engine's pools
          (``fill_pages``), the block table is allocated fresh, and the
          row resumes RUNNING at its exact stream position (zero
          recompute; the Llumnix hand-off).
        - **requeued**: everything else replays through the
          exact-recompute admission path (``work_prompt = prompt +
          generated``) — bit-identical by the PR 5 argument, just not
          free.

        Exactly-once: ``generated`` pre-populates from the manifest's
        journal segment and ``journal_base`` records the carry, so this
        engine never re-emits a carried token; the carried submit/token
        records backfill THIS journal (the single-writer hand-off — the
        source's journal holds the matching ``mig`` receipts).
        ``on_token`` re-attaches streaming callbacks (one callable or a
        ``{rid: callable}`` map); ``replay_tokens=True`` re-fires them
        for the carried prefix.  ``push=True`` is the disaggregated
        prefill→decode admission framing (:meth:`admit_pushed`): the
        identical capacity-admission + in-place-adoption machinery, but
        the ring records ``push_in`` and ``pushed_in`` advances instead
        of the ``migrated_*`` counters.  Returns ``{"adopted",
        "requeued", "rejected"}`` (rejected maps rid -> reason)."""
        from triton_dist_tpu.serve.recovery import (
            MANIFEST_FORMAT,
            _resolve_callback,
            _shift,
        )

        _refuse_latent(self.latent, "admit_pushed() of latent pages" if push
                            else "migrate_in()")
        _refuse_groups(self.kv_groups, "admit_pushed() (disaggregated push)"
                       if push else "migrate_in()")
        if manifest.get("format") != MANIFEST_FORMAT:
            raise ValueError(
                f"migration manifest format {manifest.get('format')}; "
                f"this build reads format {MANIFEST_FORMAT}")
        offset = self._clock() - (manifest.get("clock") or 0.0)
        spec_live = bool(self.spec_k) and not self._spec_off
        geom_ok = (manifest.get("page_size") == self.page
                   and manifest.get("kv_geom") == {
                       "n_layers": self.cfg.n_layers,
                       "n_kv_heads": self.cfg.n_kv_heads,
                       "head_dim": self.cfg.head_dim,
                       "dtype": str(np.dtype(self.cfg.dtype)),
                       "kv_quant": self.kv_quant,
                   })
        adopted, requeued, rejected = [], [], {}
        for rec in manifest.get("requests", ()):
            rid = rec["rid"]
            if rid in self._states:
                rejected[rid] = "duplicate request id"
                continue
            params = SamplingParams.from_dict(rec["params"])
            prompt = np.asarray(rec["prompt"], np.int32)
            total = int(prompt.shape[0]) + params.max_new_tokens
            if total > self.gen.max_seq:
                rejected[rid] = (f"prompt + max_new_tokens = {total} "
                                 f"exceeds max_seq {self.gen.max_seq}")
                continue
            fit = self.bm.fit_error(total)
            if fit is not None:
                rejected[rid] = fit
                continue
            if (self.max_queue is not None
                    and self.scheduler.queue_depth >= self.max_queue):
                rejected[rid] = (f"queue at bound "
                                 f"({self.scheduler.queue_depth} >= "
                                 f"max_queue {self.max_queue})")
                continue
            tokens = [int(t) for t in rec.get("tokens", [])]
            rm = RequestMetrics(
                arrival_time=_shift(rec.get("arrival"), offset)
                or self._clock())
            rm.first_scheduled_time = _shift(rec.get("first_sched"),
                                             offset)
            rm.first_token_time = _shift(rec.get("first_tok"), offset)
            rm.seed_token_times(
                [_shift(t, offset) for t in (rec.get("tok_ts") or [])],
                total=len(tokens))
            rm.n_preemptions = rec.get("n_preempt", 0)
            # the source already fed its queue-wait into ITS histogram;
            # observing it again here would double-count the fleet SLO
            rm.queue_observed = rm.first_scheduled_time is not None
            # trace continuity: same trace id, one hop deeper — this
            # life's span of the journey.  The hop also names the flow
            # id the source's migrate_out opened (crash-path manifests
            # carry the ctx from the journal instead).
            prev = rec.get("trace") or {"trace_id": rid, "hop": 0}
            ctx = {"trace_id": prev.get("trace_id", rid),
                   "hop": int(prev.get("hop", 0)) + 1}
            req = Request(rid, prompt, params, arrival_time=rm.arrival_time,
                          on_token=_resolve_callback(on_token, rid),
                          trace=ctx,
                          slo_class=rec.get("slo", "interactive"))
            rs = ReqState(req=req, metrics=rm)
            rs.generated = tokens
            rs.journal_base = len(tokens)
            rs.callback_disabled = bool(rec.get("cb_off", False))
            self._trace_ctx[rid] = ctx
            if self.trace.level > 0 and rec.get("events"):
                # the carried ring tail precedes this engine's own
                # events: the adopting ring CONTINUES the journey, so a
                # postmortem (or the merged fleet timeline) here shows
                # the source-side lifecycle too.  Timestamps stay on
                # the source's wall clock — one monotonic domain for
                # in-process fleets; subprocess domains may skew
                # (docs/observability.md).
                self.trace.seed([[ts, step, et, rid, data]
                                 for ts, step, et, data in rec["events"]])
            # journal the carried segment BEFORE serving resumes (the
            # restore-backfill rule: every life's journal is
            # self-contained on its own)
            if self._journal_on(rid):
                self._journal.submit(req)
                for i, t in enumerate(tokens):
                    ts = rm.time_at(i)
                    self._journal.token(
                        rid, i, t,
                        ts if ts is not None else self._clock())
                self._note_journal()
            in_place = (geom_ok and not spec_live
                        and rec.get("pending") is not None
                        and rec.get("kv") is not None
                        and None in self.slots
                        and rec["kv_len"] + 1 <= self.gen.max_seq
                        and self.bm.can_allocate(rec["kv_len"] + 1))
            self._states[rid] = rs
            if in_place:
                slot = self.slots.index(None)
                self.bm.allocate(rid, rec["kv_len"] + 1)
                n_used = self.bm.blocks_for(rec["kv_len"])
                ids = np.zeros((rec["s_ext"] // self.page,), np.int32)
                ids[:n_used] = self.bm.table(rid)[:n_used]
                def _dev(x):
                    if isinstance(x, dict):
                        return {"q": jnp.asarray(x["q"]),
                                "s": jnp.asarray(x["s"])}
                    return jnp.asarray(x)
                scratch = [(_dev(k), _dev(v)) for k, v in rec["kv"]]
                self._pools = self._device_call(
                    "fill_pages", (rid,), self._fill_fn, self._pools,
                    scratch, jnp.asarray(ids))
                rs.status = Status.RUNNING
                rs.slot = slot
                rs.kv_len = rec["kv_len"]
                rs.pending_token = rec["pending"]
                rs.seq = self.scheduler._seq
                self.scheduler._seq += 1
                self.slots[slot] = rs
                if not push:
                    self.metrics.migrated_in_place += 1
                adopted.append(rid)
            else:
                if tokens:
                    rs.work_prompt = np.concatenate(
                        [prompt, np.asarray(tokens, np.int32)])
                rs.status = Status.WAITING
                self.scheduler.add(rs)
                requeued.append(rid)
            if push:
                self.metrics.pushed_in += 1
            else:
                self.metrics.migrated_in += 1
                self.metrics.migrated_tokens += len(tokens)
            self.trace.emit("push_in" if push else "migrate_in", rid,
                            tokens=len(tokens), in_place=in_place,
                            trace=ctx["trace_id"], hop=ctx["hop"],
                            flow=f"{ctx['trace_id']}#{ctx['hop']}")
            if (replay_tokens and req.on_token is not None
                    and not rs.callback_disabled):
                for t in tokens:
                    req.on_token(rid, t)
        return {"adopted": adopted, "requeued": requeued,
                "rejected": rejected}

    # -- disaggregated prefill -> decode hand-off --------------------------

    def push_ready(self) -> list[str]:
        """Requests whose prefill is complete and whose KV can leave
        RIGHT NOW: plain RUNNING rows holding a pending token between
        steps — exactly :meth:`drain`'s in-place hand-off eligibility.
        The disagg controller (serve/disagg.py) polls this after each
        step to find what a prefill-role replica should push.  Empty
        while speculative rounds are live (spec rows carry slot-indexed
        draft state that cannot leave this engine)."""
        if bool(self.spec_k) and not self._spec_off:
            return []
        return [rid for rid, rs in self._states.items()
                if not rid.startswith("__warmup_")
                and rs.status is Status.RUNNING
                and rs.pending_token is not None]

    def push_out(self, rid: str, target=None) -> dict:
        """Per-request prefill→decode hand-off: build the single-request
        PUSH manifest (journal segment + live KV pages — the same
        records :meth:`drain` emits) and release the request, with the
        ``mig`` receipt journaled so crash recovery never resurrects it
        (docs/serving.md "Disaggregated serving").

        With ``target=None`` (the fleet-controller path) the manifest is
        returned for the caller to deliver — the controller walks the
        decode ranking on a capacity rejection.  With a ``target`` (an
        object exposing ``admit_pushed`` — a peer :class:`ServeEngine`,
        or a ``serve.fleet.RemoteReplica`` over the wire) the hand-off
        is delivered directly and the admission result rides back:
        ``{"manifest", "adopted", "requeued", "rejected"}``."""
        m = self.drain([rid], include_kv=True, push=True)
        if target is None:
            return m
        res = target.admit_pushed(m)
        return {"manifest": m,
                "adopted": res.get("adopted", []),
                "requeued": res.get("requeued", []),
                "rejected": res.get("rejected", {})}

    def admit_pushed(self, manifest: dict, *, on_token=None,
                     replay_tokens: bool = False) -> dict:
        """Admit a prefill replica's PUSH manifest — :meth:`migrate_in`'s
        cheap sibling (docs/serving.md "Disaggregated serving"):
        capacity admission first (a rejected request is left for the
        caller to place elsewhere — nothing journaled here), then
        in-place adoption via the ``fill_pages`` scatter so the row
        resumes RUNNING at its exact stream position with the
        pending-token invariant intact.  Emits ``push_in`` and advances
        ``pushed_in``; otherwise identical semantics and return shape."""
        return self.migrate_in(manifest, on_token=on_token,
                               replay_tokens=replay_tokens, push=True)

    # -- the iteration ----------------------------------------------------

    def step(self) -> list[RequestOutput]:
        """One scheduler iteration; returns requests that finished.

        Failure containment: a request whose prefill or commit fails is
        quarantined (``FinishReason.ERROR``, blocks freed) without
        unwinding the step; batched decode failures retry then bisect
        (:meth:`_forward_contained`); a failed speculative round latches
        speculation off and degrades to plain decode.  Only ``_FATAL``
        (watchdog trips, interrupts) escapes.

        Observability wrapper: the step's wall time feeds the SLO
        histogram, new fault-injector audit entries mirror into the
        flight recorder each iteration, and ANYTHING escaping the step —
        an :class:`runtime.faults.InjectedKill` standing in for process
        death, a watchdog trip, an escalated containment failure — first
        flushes the ring to ``flight_<step>.json`` so the supervisor and
        the chaos harness get a postmortem trail (docs/observability.md;
        the re-raise is unconditional — this is a flight recorder, not a
        containment path)."""
        self.trace.set_step(self.metrics.steps)
        # the root of the step's spans (docs/observability.md "Step
        # spans"); the one clock pair here feeds it and hist_step
        span = self.trace.span("step")
        t0 = time.perf_counter_ns()
        span.start(t0)
        try:
            out = self._step_inner()
        except BaseException as e:
            span.stop(time.perf_counter_ns())
            self._trace_faults()
            self.trace.emit("fault", None, point="crash",
                            kind=type(e).__name__)
            self.flight_flush(f"crash: {type(e).__name__}", force=True)
            raise
        t1 = time.perf_counter_ns()
        span.stop(t1)
        self.metrics.hist_step.observe((t1 - t0) * 1e-9)
        return out

    def _step_inner(self) -> list[RequestOutput]:
        span = self.trace.span
        with span("admit"):
            self._beat()
            if self._journal is not None:
                # Group-commit deadline sweep: an fsync interval is only
                # checked inside append(), so a traffic pause would leave
                # the burst's last record un-fsynced indefinitely without
                # this per-step nudge.
                self._journal.maybe_sync()
            if self.faults is not None:
                # The audit log stamps every firing with the engine step so
                # a chaos schedule replays deterministically post-mortem.
                self.faults.set_step(self.metrics.steps)
            now = self._clock()
            finished: list[RequestOutput] = []
            if self._shed_pending:
                # displacement sheds retired inside submit(): deliver their
                # terminal outputs through the normal finished batch
                finished.extend(self._shed_pending)
                self._shed_pending.clear()
            if self.brownout_cfg is not None:
                self._brownout_step(now)

            # Deadline sweep BEFORE admission: expired WAITING/PREFILL
            # requests retire (DEADLINE) and their slots/blocks free for
            # live traffic this same iteration.  Rows already decoding run
            # to completion — their prefill is paid for.
            for rs in self.scheduler.pop_expired(now):
                finished.append(self._expire(rs, now, free=False))
            for rs in list(self.slots):
                if (rs is not None and rs.status is Status.PREFILL
                        and rs.expired(now)):
                    finished.append(self._expire(rs, now, free=True))

            free = [i for i, s in enumerate(self.slots) if s is None]
            for rs in self.scheduler.admit(free, now):
                self.slots[rs.slot] = rs
                self.trace.emit("admit", rs.req.request_id, slot=rs.slot,
                                cached_prefix=rs.cached_prefix)
                # once per request: first_scheduled_time is first-write-wins,
                # so a preempted request's re-admissions would re-observe the
                # ORIGINAL wait and inflate the queue SLO exactly under the
                # overload it exists to diagnose
                qt = rs.metrics.queue_time
                if qt is not None and not rs.metrics.queue_observed:
                    rs.metrics.queue_observed = True
                    self.metrics.hist_queue.observe(qt)
                if rs.cached_prefix > 0:
                    self.metrics.prefix_hits += 1
                    self.metrics.prefix_hit_tokens += rs.cached_prefix
                    rs.metrics.cached_prefix_tokens = rs.cached_prefix
                try:
                    self._start_prefill(rs)
                except _FATAL:
                    raise
                except Exception as e:
                    if not self._state_intact():
                        raise  # pools consumed: engine-fatal
                    # two device calls can fail here, the warm-prefix
                    # gather (reads the pools) and the cold scratch's
                    # zero program (reads nothing): neither donates, so a
                    # failure is per-request by construction — quarantine
                    # and serve on
                    finished.append(self._quarantine(rs, f"prefill start: "
                                                         f"{e!r}"))

        prefilling = [s for s in self.slots
                      if s is not None and s.status is Status.PREFILL]
        for rs, n in self.scheduler.prefill_plan(prefilling):
            if rs.status is not Status.PREFILL:
                continue  # aborted mid-step (e.g. from an on_token
            try:          # callback fired earlier in this plan)
                with span("prefill"):
                    out = self._run_prefill(rs, n, now)
            except _FATAL:
                raise
            except Exception as e:
                if not self._state_intact():
                    raise  # fill_pages donated the pools: engine-fatal
                # Prefill is already per-request (own scratch, own
                # chunk stream) — the poison is isolated by
                # construction; no retry or bisection needed.
                finished.append(self._quarantine(rs, f"prefill: {e!r}"))
                continue
            if out is not None:
                finished.append(out)

        running = [s for s in self.slots
                   if s is not None and s.status is Status.RUNNING]
        if running:
            if self.spec_k and not self._spec_off:
                finished.extend(self._spec_chain(running))
            else:
                finished.extend(self._decode_once(running))

        with span("observe"):
            self.metrics.observe_step(
                queue_depth=self.scheduler.queue_depth,
                running=len([s for s in self.slots if s is not None]),
                kv_utilization=self.bm.utilization)
            if (self.snapshot_every is not None
                    and self.snapshot_dir is not None
                    and not self._in_warmup
                    and self.metrics.steps - self._last_snap_step
                    >= self.snapshot_every):
                # Incremental capture at the step boundary (no dispatch
                # in flight).  A snapshot failure ESCALATES — durability
                # is the contract, and serving on while silently not
                # snapshotting would turn the next crash into unbounded
                # recompute.
                self.snapshot()
            self._trace_faults()
        return finished

    def run(self, max_steps: int = 100_000) -> dict[str, RequestOutput]:
        """Step until drained; returns {request_id: output}.  Drives the
        heartbeat (one beat per iteration via :meth:`step`); raises
        ``RuntimeError`` when ``max_steps`` iterations don't drain the
        queue — the backstop against a scheduling livelock."""
        self._beat()
        steps = 0
        while self.has_work():
            self.step()
            steps += 1
            if steps > max_steps:
                raise RuntimeError(f"engine not drained after {max_steps} "
                                   "steps")
        return dict(self._outputs)

    # -- warmup -----------------------------------------------------------

    def warmup(self) -> dict:
        """Pre-compile every program steady-state serving can hit, so no
        request ever eats an XLA compile stall on the admission path.

        Warmup drives REAL dummy traffic — one max-length request per
        bucket-ladder rung — through the production step loop, so every
        program compiles against exactly the buffers steady state will
        hand it (the executable cache keys on more than shapes: layouts
        and donation lineage matter, so hand-built dummy calls can leave
        the first production step compiling anyway).  The sweep repeats
        until a full round compiles nothing new (a compile fixed point,
        reached on the second round at the latest in practice), then all
        dummy bookkeeping is scrubbed: outputs, request states, and the
        step/latency metrics the dummies generated (the compile counters
        keep accumulating — they are the point).  KV pool pages touched
        by dummies are freed and fully overwritten by the next scatter
        before any read, so no request-visible state leaks.

        Call BEFORE submitting traffic (asserted).  A rung is skipped
        only when no admissible request can reach it (shorter prompts
        and max_new=1 are tried before giving up) — then production
        cannot hit it either.  With a decode ``horizon`` the sweep also
        drains one dummy per HORIZON rung, rung 1 included (greedy and
        sampled variants — at rung 1 the sampled one serves both —
        serially: co-scheduled rung dummies would all bucket to the
        largest limit), so fused decode never compiles under traffic
        either; such an engine's decode never reaches ``paged_decode``,
        so nothing here compiles it.  Spec mode: the draft prefills
        through its own padded chunk + extent ladder (``draft_prefill`` /
        ``draft_join`` counters), and warmup sweeps THAT ladder too —
        spec-mode admission is fully compile-free after warmup.  An
        attached ``FaultInjector`` is disabled for the duration (dummy
        traffic must not eat injected faults) and the queue bound does
        not apply to warmup dummies.

        Returns ``{"programs": <fresh compiles>, "seconds": <wall>}``;
        the same numbers accumulate in ``metrics.warmup_compiles`` /
        ``metrics.warmup_time`` and ride the ``TDT_DUMP_IR`` dump.
        """
        assert not self.has_work(), "warmup() must run before traffic"
        t0 = time.perf_counter()
        misses0 = self.metrics.compile_misses
        width = self.prefill_width
        # dummy traffic must not pollute serving metrics; the CountingJit
        # wrappers are shared so compile accounting continues
        saved, self.metrics = self.metrics, ServeMetrics()
        self.metrics.compiled_fns = saved.compiled_fns
        guard = (self.faults.disabled() if self.faults is not None
                 else contextlib.nullcontext())
        self._in_warmup = True  # dummy traffic must not trigger snapshots
        # Dummy prompts must not seed or match the content index: their
        # zero-token chains would shadow real traffic's and park dummy
        # blocks in the cache tier past the scrub.  The load/cow device
        # programs are warmed by direct dispatch below instead.
        saved_pc = self.bm.prefix_cache
        self.bm.prefix_cache = False
        # dummy traffic must not pollute the flight recorder either —
        # a production ring starting with __warmup_ lifecycles would
        # waste its bounded capacity on events nobody can act on
        saved_lvl, self.trace.level = self.trace.level, 0
        # ... nor the per-program wall-time histograms: warmup calls ARE
        # compile stalls, and the timers are bound to ``saved`` (the
        # production metrics object), so pause at the master gate
        saved_pt, saved.program_timing = saved.program_timing, False
        failed: list = []   # dummies the containment path quarantined
        try:
            with guard:
                prev, round_ = -1, 0
                while self.metrics.compile_misses != prev and round_ < 4:
                    prev = self.metrics.compile_misses
                    for i, rung in enumerate(self.ladder):
                        # Longest prompt whose _scratch_need fits this
                        # rung: n <= rung keeps the pool pages in, and
                        # n <= (rung // width) * width keeps the padded
                        # final call in.  If even that n buckets LOWER,
                        # no admissible prompt can reach this rung —
                        # skip it (production can't hit it either).
                        n_max = min(rung, (rung // width) * width,
                                    self.gen.max_seq - 1)
                        if n_max < 1 or self._bucket_s_ext(n_max) != rung:
                            continue
                        # n_min is the shortest prompt reaching this
                        # rung (one past what the rung below can hold);
                        # blocks_for is monotone, so if n_min + 1
                        # doesn't fit, nothing reaching this rung does.
                        if i == 0:
                            n_min = 1
                        else:
                            below = self.ladder[i - 1]
                            n_min = 1 + max(0, min(below,
                                                   (below // width)
                                                   * width))
                        self._warmup_try(f"w{round_}_{i}", n_max, n_min)
                    if self.spec_k:
                        # Sweep the DRAFT extent ladder too: its rungs
                        # (chunk multiples) need not align with the
                        # engine's scratch rungs, and a cold draft rung
                        # would compile on the admission path.
                        for i, rung in enumerate(self._draft_ladder):
                            n_max = min(rung, self.gen.max_seq - 1)
                            if (n_max < 1
                                    or self._draft_bucket(n_max) != rung):
                                continue
                            n_min = (1 if i == 0
                                     else self._draft_ladder[i - 1] + 1)
                            self._warmup_try(f"wd{round_}_{i}", n_max,
                                             n_min)
                    self.run()
                    # The host sampler: one program for every sampler
                    # class (its knobs are traced), and no dummy above
                    # is sampled.
                    self._device_call(
                        "sample_token", (), self._sample_fn,
                        np.zeros((self.cfg.vocab,), np.float32),
                        jax.random.key(0), np.int32(0), np.float32(1.0),
                        np.int32(0), np.float32(1.0))
                    if self.horizon > 1 and not self.spec_k:
                        # Horizon rungs compile one program per (scan
                        # length, greedy-or-mixed sampler).  Each rung
                        # drains SERIALLY: co-scheduled rung dummies
                        # would all bucket to the largest limit in the
                        # batch and leave the smaller rungs cold for the
                        # tail of every request's generation.
                        # Rung 1 is the link of a clamped step (a slot
                        # mid-prefill) and of a one-step tail: ONE
                        # program, the mixed one, whatever the samplers.
                        for r in self.h_ladder:
                            for ti, temp in enumerate((0.0, 1.0)):
                                self._warmup_horizon_try(
                                    f"wh{round_}_{r}_{ti}", r, temp)
                                self.run()
                    if self.spec_k:
                        # Spec-round rungs: one program per
                        # (K rung, greedy-or-mixed).  The dummy traffic
                        # above only reaches the rung its adaptive k
                        # lands on, so the remaining rungs warm by
                        # direct dispatch over an ALL-INACTIVE batch —
                        # every write redirects to the null block /
                        # dead draft slots, and the donated pools +
                        # draft caches are reassigned exactly like a
                        # production call (same donation lineage).
                        for r in self._k_ladder:
                            for ag in (True, False):
                                self._warmup_spec_rung(r, ag)
                        if self._draft_pools is not None:
                            # Draft-side prefix programs: the draft
                            # pool gather + scatter per draft-ladder
                            # rung (all-null ids -> block 0 only).
                            for rung in self._draft_ladder:
                                ids = jnp.asarray(np.zeros(
                                    (rung // self.page,), np.int32))
                                self._device_call(
                                    "draft_load_pages", (),
                                    self._draft_load_fn,
                                    self._draft_pools, ids)
                                scratch = self._device_call(
                                    "draft_zero_scratch", (),
                                    self._draft_zero_fn, s_ext=rung)
                                self._draft_pools = self._device_call(
                                    "draft_fill_pages", (),
                                    self._draft_fill_fn,
                                    self._draft_pools, scratch, ids)
                    if self.prefix_cache:
                        # Warm-prefix programs: the pool->scratch gather
                        # (one trace per ladder rung, like fill_pages)
                        # and the one-page COW copy (traced src/dst: one
                        # trace total).  All-null ids / the null block
                        # make the dispatches harmless.
                        for rung in self.ladder:
                            self._device_call(
                                "load_pages", (), self._load_fn,
                                self._pools,
                                jnp.asarray(np.zeros(
                                    (rung // self.page,), np.int32)))
                        self._pools = self._device_call(
                            "cow_copy", (), self._cow_fn, self._pools,
                            np.int32(0), np.int32(0))
                    for rid in [r for r in self._outputs
                                if r.startswith("__warmup_")]:
                        if (self._outputs[rid].finish_reason
                                is FinishReason.ERROR):
                            failed.append(self._outputs[rid])
                        del self._outputs[rid]
                        del self._states[rid]
                        self._trace_ctx.pop(rid, None)
                    round_ += 1
        finally:
            self._in_warmup = False
            self.bm.prefix_cache = saved_pc
            self.trace.level = saved_lvl
            saved.program_timing = saved_pt
            self.metrics = saved
        if failed:
            # Containment quarantines a request whose forward raises and
            # serves on — right under traffic, wrong here: a program that
            # cannot compile fails EVERY dummy that needs it, and a
            # warm-up that returns normally after that has warmed nothing
            # (on the chip: 3 programs "compiled", then every request of
            # the first real batch retired ERROR — PR 21).
            raise RuntimeError(
                f"warmup(): {len(failed)} warm-up requests failed; first: "
                f"{failed[0].request_id}: {failed[0].error}")
        dt = time.perf_counter() - t0
        fresh = self.metrics.compile_misses - misses0
        self.metrics.warmup_time += dt
        self.metrics.warmup_compiles += fresh
        return {"programs": fresh, "seconds": dt}

    def _warmup_try(self, tag: str, n_max: int, n_min: int) -> None:
        """Queue ONE warmup dummy for a rung, falling back to smaller
        totals before giving up: the pool may reject n_max + 2 while a
        production request (shorter prompt or max_new=1) reaching the
        same rung is still admittable.  Candidate order: longest first
        (covers the rung's full extent), max_new=2 before 1 (a 2-token
        dummy runs a decode step; a 1-token dummy retires on its
        prefill logits and would leave the decode program cold)."""
        for j, (n, new) in enumerate(
                ((n_max, min(2, self.gen.max_seq - n_max)),
                 (n_max, 1),
                 (n_min, min(2, self.gen.max_seq - n_min)),
                 (n_min, 1))):
            req = Request(f"__warmup_{tag}_{j}", np.zeros((n,), np.int32),
                          SamplingParams(max_new_tokens=new))
            try:
                self._submit(req, bounded=False)
                return
            except ValueError:
                continue

    def _warmup_horizon_try(self, tag: str, rung: int,
                            temperature: float) -> None:
        """Queue ONE warmup dummy reaching horizon rung ``rung``: a
        1-token prompt with ``max_new = rung + 1`` — after the
        prefill-path first token its remaining budget is exactly
        ``rung``, so the planner's bucketed horizon lands on the rung.
        A pool that cannot hold ``2 + rung`` tokens cannot admit ANY
        request able to reach the rung (remaining >= rung needs
        ``max_new >= rung + 1`` on top of a >= 1-token prompt), so a
        rejected dummy means production cannot hit it either.
        ``temperature`` 0/1 sweeps the greedy and mixed-sampler variants
        of the trace."""
        req = Request(f"__warmup_{tag}", np.zeros((1,), np.int32),
                      SamplingParams(max_new_tokens=rung + 1,
                                     temperature=temperature))
        try:
            self._submit(req, bounded=False)
        except ValueError:
            pass

    def _warmup_spec_rung(self, rung: int, all_greedy: bool) -> None:
        """Compile one fused spec-round variant (static K=``rung``,
        ``all_greedy``) by direct dispatch over an all-inactive batch:
        no row is live, so every K/V write redirects to the null block
        (target) or a dead slot row (draft) and no engine state can
        change — but the call's shapes, dtypes, and donation lineage
        (pools + draft caches donated, reassigned) match production
        exactly, so the executable cache key does too."""
        B = self.max_batch
        z32 = jnp.zeros((B,), jnp.int32)
        zb = jnp.zeros((B,), bool)
        sd = self._draft_state
        out = self._device_call(
            "spec_round", (), self._spec_fn, self.params,
            self.draft_params, self._pools, sd.caches,
            jnp.zeros((B, self.n_pages_max), jnp.int32), z32, zb, zb,
            self._last_logits, sd.last_logits, z32, z32,
            jnp.ones((B,), jnp.int32),
            _key_batch([None] * B),
            jnp.ones((B,), jnp.float32), z32,
            jnp.ones((B,), jnp.float32), jnp.ones((B,), bool),
            jnp.full((B,), -1, jnp.int32), K=int(rung),
            all_greedy=all_greedy)
        self._pools = out[0]
        self._draft_state = GenerationState(
            caches=out[1], kv_lens=sd.kv_lens,
            last_logits=sd.last_logits)

    # -- prefill ----------------------------------------------------------

    def _scratch_need(self, n_prompt: int) -> int:
        """Unbucketed scratch extent an ``n_prompt``-token prefill needs:
        its pool pages, OR the padded final call's write rounded up to
        the call's rows (dynamic_update_slice must never clamp),
        whichever is larger.  THE sizing formula — the ladder cap, the
        bucket lookup, and warmup's per-rung prompt picker all derive
        from it.  It is sized for calls that start on multiples of the
        width; one that starts elsewhere (:meth:`_call_window`) slides
        back inside the same extent."""
        width = self.prefill_width
        return max(self.bm.blocks_for(n_prompt) * self.page,
                   -(-n_prompt // width) * width)

    def _call_window(self, pos: int, s_ext: int) -> int:
        """Where the ``prefill_width``-row call that prefills from ``pos``
        starts: at ``pos``, or — where the scratch has fewer rows than
        that left past it — as far back as makes its write end at the
        extent.  Budget is metered in ``prefill_chunk`` multiples, so a
        request that began on a step's leftover budget (or on a warm
        prefix) carries an offset, and its last call may begin within a
        width of the extent's end.  The rows between the window's start
        and ``pos`` are fed again and recompute to what they held (the
        same tokens over the same earlier rows — the warm start's
        argument), so the write never clamps and the scratch needs no
        rung of its own for the offset."""
        return min(pos, s_ext - self.prefill_width)

    def _bucket_s_ext(self, n_prompt: int) -> int:
        """Scratch extent for an ``n_prompt``-token prefill, bucketed up
        the ladder."""
        need = self._scratch_need(n_prompt)
        for r in self.ladder:
            if r >= need:
                return r
        raise AssertionError(
            f"bucket ladder {self.ladder} cannot cover scratch extent "
            f"{need} (prompt {n_prompt})")

    def _draft_bucket(self, n_prompt: int) -> int:
        """Draft-side prefill extent for an ``n_prompt``-token prompt,
        bucketed up the draft's chunk-multiple ladder."""
        chunk = self.scheduler.prefill_chunk
        need = -(-n_prompt // chunk) * chunk
        for r in self._draft_ladder:
            if r >= need:
                return r
        raise AssertionError(
            f"draft ladder {self._draft_ladder} cannot cover extent "
            f"{need} (prompt {n_prompt})")

    def _start_prefill(self, rs: ReqState) -> None:
        s_ext = self._bucket_s_ext(int(rs.prompt_tokens.shape[0]))
        rs.s_ext = s_ext
        cached = rs.cached_prefix if self.prefix_cache else 0
        chunk = self.scheduler.prefill_chunk
        # Warm prefix (docs/serving.md "Prefix caching"): admission
        # mapped `cached` block-aligned tokens of shared KV into the
        # table; chunked prefill starts at the chunk FLOOR of that
        # (``prefill_pos`` stays a chunk multiple, what the scheduler
        # meters in — the few tokens between floor and hit recompute
        # bit-identically over the gathered rows) and only the residual
        # pays compute.  Any such start is one the scratch covers: a
        # call that would pass the extent slides back (_call_window).
        start = (cached // chunk) * chunk
        if start > 0:
            rs.prefill_pos = start
            ids = np.zeros((s_ext // self.page,), np.int32)
            n_hit = cached // self.page
            ids[:n_hit] = self.bm.table(rs.req.request_id)[:n_hit]
            rs.scratch = self._device_call(
                "load_pages", (rs.req.request_id,), self._load_fn,
                self._pools, jnp.asarray(ids))
            self.metrics.prefix_skipped_tokens += start
            return
        # One scratch plane per pool plane (K and V; or the latent row; a
        # state-space layer's: the request's state, from zero), all from
        # ONE launch.  A quantized scratch is in the pool layout: chunked
        # prefill quantizes each chunk's rows as it writes them (the
        # generate._write_chunk convention), so fill_pages moves finished
        # bytes + scales into the pool verbatim.
        rs.scratch = self._device_call(
            "zero_scratch", (rs.req.request_id,), self._zero_fn,
            s_ext=s_ext)
        self.metrics.scratch_dispatches += 1
        if self._has_state:
            self.metrics.state_resets += 1
            if rs.generated:        # a preempted request's second life
                self.metrics.state_recomputed_tokens += int(
                    rs.prompt_tokens.shape[0])

    def _run_prefill(self, rs: ReqState, n_tokens: int,
                     now: float) -> Optional[RequestOutput]:
        prompt = rs.prompt_tokens
        S0 = int(prompt.shape[0])
        end = min(rs.prefill_pos + n_tokens, S0)
        width = self.prefill_width
        logits = None
        while rs.prefill_pos < end:
            c = min(width, end - rs.prefill_pos)
            # Every call is the ONE fixed shape [1, width] — as many rows
            # as it takes to share one read of the weights: a residual
            # pads with zeros and n_valid masks its K/V writes, so the
            # trace is keyed by (width, s_ext bucket) only — varied
            # prompt lengths never compile on the admission path.
            with self.trace.span("prefill.stage"):
                at = self._call_window(rs.prefill_pos, rs.s_ext)
                n_fed = rs.prefill_pos + c - at
                buf = np.zeros((1, width), np.int32)
                buf[0, :n_fed] = prompt[at:at + n_fed]
                buf_d = jnp.asarray(buf)
                # host numbers, transferred by the call itself: an eager
                # jnp.int32 is a launch of its own for four bytes.  Only
                # the prompt's last call has a row read (_finish_prefill):
                # the others say so by the count's sign (_chunk_forward)
                last = rs.prefill_pos + c == S0
                pos_d = np.int32(at)
                valid_d = np.int32(n_fed if last else -n_fed)
            rs.scratch, logits, *aux = self._device_call(
                "prefill_chunk", (rs.req.request_id,), self._chunk_fn,
                self.params, buf_d, rs.scratch, pos_d,
                quantized=self.kv_quant, extent=rs.s_ext, n_valid=valid_d)
            self._note_aux(aux)
            rs.prefill_pos += c
            self.metrics.prefill_tokens += c
            if self._has_state:
                self.metrics.ssm_scan_tokens += c
            self.metrics.prefill_dispatches += 1
            self.metrics.prefill_pad_tokens += width - c
            if last:
                self.metrics.prefill_tail_rows += logits.shape[1]
            if self.trace.level >= 2:
                self.trace.emit("prefill_chunk", rs.req.request_id,
                                n=c, pos=rs.prefill_pos)
        if rs.prefill_pos < S0:
            return None
        return self._finish_prefill(rs, logits, now)

    def _finish_prefill(self, rs: ReqState, logits,
                        now: float) -> Optional[RequestOutput]:
        rid = rs.req.request_id
        S0 = int(rs.prompt_tokens.shape[0])
        n_prompt_pages = self.bm.blocks_for(S0)
        # One table entry per SCRATCH page (trace keyed by the s_ext
        # bucket, not the prompt's page count); pages past the prompt's
        # allocation scatter their zero-masked padding into the null
        # block.  SHARED prefix pages (a warm hit) scatter there too —
        # their pool pages already hold the exact K/V and are read-only
        # to this request (never write a block with refcount > 1).
        span = self.trace.span
        with span("prefill.stage"):
            n_hit = (rs.cached_prefix // self.page if self.prefix_cache
                     else 0)
            # (one row a cache group where the model has them: a window
            # group's entries hold the null block wherever no decode
            # query can see the page, so only pages in sight are kept)
            ids_d = jnp.asarray(self.bm.page_ids(
                rid, n_hit, n_prompt_pages, rs.s_ext // self.page))
        self._pools = self._device_call(
            "fill_pages", (rid,), self._fill_fn, self._pools, rs.scratch,
            ids_d)
        rs.scratch = None
        rs.kv_len = S0
        rs.status = Status.RUNNING
        self.trace.emit("prefill_done", rid, kv_len=S0)
        spec = bool(self.spec_k and not self._spec_off)
        with span("prefill.commit"):
            self._commit_full_blocks(rs)
        with span("prefill.wait"):
            # the call kept its last valid row alone (_chunk_forward)
            last = logits[:, 0]                            # [1, V]
            if not spec:
                # the host blocks here until the chunk has run
                row = np.asarray(last[0], np.float32)
        with span("prefill.commit"):
            self._fold_aux(self._note_aux(()))
            if spec:
                self._last_logits = \
                    self._last_logits.at[rs.slot].set(last[0])
                self._join_draft(rs)
                return None  # first token: the next verify round emits it
            token = self._choose_token(rs, row)
            return self._commit_token(rs, token)

    def _join_draft(self, rs: ReqState) -> None:
        """Prefill the draft model for a joining row (spec mode) through
        the SAME padded fixed-chunk machinery as the target: every chunk
        call is the one ``prefill_chunk`` shape (final residual padded,
        K/V zero-masked by ``n_valid``) against a temp cache whose
        extent buckets up the draft ladder, then one traced-slot splice
        lands the row in the batch caches — O(len(draft ladder))
        programs cover every prompt length, so spec-mode admission
        never compiles after warmup (the old ``draft.prefill`` path
        compiled per distinct length).

        Warm prefix (docs/serving.md "Speculative decoding"): the
        draft's K/V for every FULL prompt page is also scattered into
        draft-geometry pools under the request's block ids, each page
        tagged with the block's content-index key.  A later warm admit
        whose target prefix hit covers blocks with matching tags skips
        the draft prefill for them too — the gathered draft pages feed
        the residual chunks exactly like the target's warm path — so a
        shared system prompt no longer re-prefills the full prompt on
        the DRAFT side.  Tag validation is reuse-safe by construction:
        a reused block id's content-index key changes or vanishes, and
        the tag compare fails."""
        rid = rs.req.request_id
        prompt = np.asarray(rs.prompt_tokens)
        S0 = int(prompt.shape[0])
        chunk = self.scheduler.prefill_chunk
        page = self.page
        ext = self._draft_bucket(S0)
        table = (self.bm.table(rid) if self._draft_pools is not None
                 else [])
        d_skip = 0
        if self._draft_pools is not None and rs.cached_prefix > 0:
            for logical in range(rs.cached_prefix // page):
                b = table[logical]
                key = self.bm.block_key(b)
                if key is None or self._draft_page_key.get(b) != key:
                    break
                d_skip += page
        start = (d_skip // chunk) * chunk
        if start > 0:
            # Gather the draft's cached prefix pages into the prefill
            # scratch; tokens between the chunk floor and the hit
            # recompute bit-identically over the gathered rows (the
            # target warm path's argument, draft-side).
            ids = np.zeros((ext // page,), np.int32)
            ids[:d_skip // page] = table[:d_skip // page]
            caches = self._device_call(
                "draft_load_pages", (rid,), self._draft_load_fn,
                self._draft_pools, jnp.asarray(ids))
            self.metrics.draft_prefix_skipped_tokens += start
        else:
            caches = self._device_call(
                "draft_zero_scratch", (rid,), self._draft_zero_fn,
                s_ext=ext)
        logits = None
        for off in range(start, S0, chunk):
            c = min(chunk, S0 - off)
            buf = np.zeros((1, chunk), np.int32)
            buf[0, :c] = prompt[off:off + c]
            caches, logits = self._device_call(
                "draft_prefill", (rid,), self._draft_chunk_fn,
                self.draft_params, jnp.asarray(buf), caches,
                np.int32(off), quantized=False, extent=ext,
                n_valid=np.int32(c if off + c == S0 else -c))
        if self._draft_pools is not None:
            # Commit the draft's prompt pages (before the splice — the
            # join donates nothing of ``caches``, this fill only reads
            # it).  Shared blocks rewrite too: their draft content is a
            # deterministic function of the certified chain, so the
            # overwrite is idempotent.  Only FULL pages get a reuse tag.
            n_prompt_pages = self.bm.blocks_for(S0)
            ids = np.zeros((ext // page,), np.int32)
            lo = d_skip // page
            ids[lo:n_prompt_pages] = table[lo:n_prompt_pages]
            self._draft_pools = self._device_call(
                "draft_fill_pages", (rid,), self._draft_fill_fn,
                self._draft_pools, caches, jnp.asarray(ids))
            for logical in range(min(S0 // page, len(table))):
                key = self.bm.block_key(table[logical])
                if key is not None:
                    self._draft_page_key[table[logical]] = key
        sd = self._draft_state
        new_caches, kv_lens, last_logits = self._device_call(
            "draft_join", (rid,), self._draft_join_fn, sd.caches,
            sd.kv_lens, sd.last_logits, caches, np.int32(rs.slot),
            np.int32(S0), logits[0, 0])
        self._draft_state = GenerationState(
            caches=new_caches, kv_lens=kv_lens, last_logits=last_logits)

    # -- token choice / emission -----------------------------------------

    def _choose_token(self, rs: ReqState, logits_row) -> int:
        """HOST-side token choice (counted: ``metrics.host_choices``) —
        the first token after a prefill on every engine, and each decode
        token of :meth:`_decode_rows` (``horizon=1`` engines, the
        speculative tail and bail-out); the decode horizon, its one-step
        link included, samples ON DEVICE.  Both draw through
        ``sampling.sample_logits_rowwise`` (same filter math, same
        ``fold_in(key(seed), emission_index)`` stream), so a stream may
        cross between the two mid-request (prefill to decode,
        preemption) without a token ever differing."""
        self.metrics.host_choices += 1
        p = rs.req.params
        row = np.asarray(logits_row, np.float32)
        if p.greedy:
            return int(np.argmax(row))
        # Per-token PRNG stream keyed by (seed, emission index): a
        # preempted-and-recomputed request keeps drawing the same stream.
        # Host operands only (a mesh-placed row would fork the
        # executable warmup() compiled).
        return int(self._sample_fn(
            row, jax.random.key(p.seed), np.int32(len(rs.generated)),
            np.float32(p.temperature), np.int32(p.top_k or 0),
            np.float32(1.0 if p.top_p is None else p.top_p)))

    def _commit_token(self, rs: ReqState, token: int,
                      now: Optional[float] = None
                      ) -> Optional[RequestOutput]:
        """Emit one token; retire the request when it finishes.  The
        token stays ``pending`` (not yet in the cache) until the next
        decode step consumes it.  Timestamps are taken HERE (not at the
        step boundary) so TTFT/ITL separate tokens emitted within one
        iteration (prefill completion + same-step decode); a horizon
        burst commit passes explicit ``now`` values paced by the DEVICE
        step cadence (``RequestMetrics.burst_times``), since its tokens
        were produced steps apart but drain together.

        The ``on_token`` callback is CONTAINED: a raising frontend
        callback used to propagate out of ``step()`` with the token
        already committed, corrupting mid-step state — now it is logged
        once, the request's callback is disabled, and serving
        continues.  A callback may also ``abort()`` requests (including
        this one): commit re-checks status afterwards so a retired
        request is never retired twice."""
        if rs.status is Status.FINISHED:  # aborted mid-step by a callback
            return self._outputs.get(rs.req.request_id)
        if now is None:
            now = self._clock()
        rs.generated.append(token)
        rs.pending_token = token
        first = rs.metrics.first_token_time is None
        itl = rs.metrics.on_token(now)
        if first:
            ttft = rs.metrics.ttft
            if ttft is not None:
                self.metrics.hist_ttft.observe(ttft)
                self.metrics.class_ttft_hist(
                    rs.req.slo_class).observe(ttft)
        elif itl is not None:
            self.metrics.hist_itl.observe(itl)
        if self._journal_on(rs.req.request_id):
            # The journal append PRECEDES the on_token callback: a crash
            # in between re-derives nothing (the token is durable) and
            # re-delivers nothing (restore resumes past it) — the stream
            # is exactly-once; callback delivery for this one token is
            # at-most-once (restore(replay_tokens=True) flips that).
            self._journal.token(rs.req.request_id,
                                len(rs.generated) - 1, token, now)
            self._note_journal()
        if rs.req.on_token is not None and not rs.callback_disabled:
            try:
                if self.faults is not None:
                    self.faults.fire("callback", rid=rs.req.request_id)
                rs.req.on_token(rs.req.request_id, token)
            except _FATAL:
                raise
            except Exception as e:
                rs.callback_disabled = True
                self.metrics.callback_errors += 1
                print(f"[serve] {rs.req.request_id}: on_token callback "
                      f"raised ({e!r}); callback disabled, request "
                      f"keeps serving", file=sys.stderr)
        if rs.status is Status.FINISHED:  # callback aborted this request
            return self._outputs.get(rs.req.request_id)
        p = rs.req.params
        if p.eos_id is not None and token == p.eos_id:
            return self._retire(rs, FinishReason.EOS)
        if len(rs.generated) >= rs.effective_max_new:
            return self._retire(rs, FinishReason.LENGTH)
        return None

    def _retire(self, rs: ReqState, reason: FinishReason, *,
                free: bool = True, error: Optional[str] = None
                ) -> RequestOutput:
        now = self._clock()
        if free:
            self.bm.free(rs.req.request_id)
            self.slots[rs.slot] = None
        rs.status = Status.FINISHED
        rs.slot = None
        rs.scratch = None
        rs.pending_token = None
        rs.metrics.finish_time = now
        if self._journal_on(rs.req.request_id):
            self._journal.finish(rs.req.request_id, reason.value, error,
                                 len(rs.generated), now)
            self._note_journal()
        out = RequestOutput(request_id=rs.req.request_id,
                            prompt=rs.req.prompt,
                            token_ids=list(rs.generated),
                            finish_reason=reason, metrics=rs.metrics,
                            error=error)
        self._outputs[rs.req.request_id] = out
        self.metrics.observe_finish(rs.req.request_id, rs.metrics, reason,
                                    slo_class=rs.req.slo_class)
        self.trace.emit("retire", rs.req.request_id,
                        reason=reason.value, n_tokens=len(rs.generated))
        # the journey ends here: the per-request trace context must not
        # outlive the request (the maps above are pruned; this one is too)
        self._trace_ctx.pop(rs.req.request_id, None)
        if rs.req.on_finish is not None:
            # The terminal notification, fired on EVERY retirement path
            # (shed at submit, deadline sweep, quarantine, healthy
            # finish) — a zero-token retirement never touches on_token,
            # so without this a shed request's consumer waits forever.
            # Contained like on_token: a raising frontend must not
            # corrupt the retirement that already happened.
            try:
                rs.req.on_finish(out)
            except _FATAL:
                raise
            except Exception as e:
                self.metrics.callback_errors += 1
                print(f"[serve] {rs.req.request_id}: on_finish callback "
                      f"raised ({e!r}); ignored", file=sys.stderr)
        return out

    # -- flight recorder plumbing ----------------------------------------

    def _trace_faults(self) -> None:
        """Mirror NEW fault-injector audit entries into the ring (one
        ``fault`` event per firing, same (point, call, kind, who, step)
        tuple) — by construction every audit entry has a matching event,
        which is exactly what the completeness test cross-checks."""
        if self.faults is None or self.trace.level <= 0:
            return
        fired = self.faults.fired
        for point, call, kind, who, step in fired[self._trace_fault_idx:]:
            self.trace.emit("fault", who, point=point, call=call,
                            kind=kind, at_step=step)
        self._trace_fault_idx = len(fired)

    def flight_flush(self, reason: str,
                     force: bool = False) -> Optional[str]:
        """Write the event ring to ``flight_<step>.json`` — the
        postmortem trail.  Directory preference: the snapshot dir FIRST
        (the supervisor's postmortem globs exactly there — a
        ``TDT_DUMP_IR``-first rule would silently divert the trail the
        moment the IR switch is armed), else ``TDT_DUMP_IR``; no-op
        without either or with tracing off.  Throttled to one file per engine step so a quarantine
        storm cannot turn the fault path into an I/O loop.  Best-effort:
        a failing flush must never mask the fault being recorded."""
        if self.trace.level <= 0:
            return None
        d = self.snapshot_dir or ir_dump.dump_dir()
        if d is None or (not force
                         and self.trace.step == self._last_flight_step):
            return None
        self._last_flight_step = self.trace.step
        try:
            from triton_dist_tpu.serve.metrics import format_statline

            statline = format_statline(self.metrics.light_summary())
        except Exception:  # noqa: BLE001 — crash-path best effort
            statline = None
        try:
            return self.trace.flush(d, reason=reason, statline=statline)
        except Exception:  # noqa: BLE001 — crash-path best effort
            return None

    # -- failure containment ---------------------------------------------

    def _beat(self) -> None:
        """Synchronous heartbeat — deliberately not Heartbeat's daemon
        thread: a wedged forward must STOP the beats so an external
        supervisor sees the stall as a stale file.  Throttled to a
        quarter of the supervisor cadence (wall clock, independent of
        the — possibly fake — engine clock) so fast step loops don't
        pay a file write per iteration."""
        if self.heartbeat is None:
            return
        t = time.monotonic()
        if t - self._last_beat >= self.heartbeat.interval_s / 4:
            self.heartbeat.beat()
            self._last_beat = t

    def _state_intact(self) -> bool:
        """Containment precondition: the shared KV pools survived the
        failure.  The batched forwards DONATE the pools — an exception
        raised after dispatch (a genuine device error, as opposed to a
        pre-dispatch injector/seam failure) may have consumed them, and
        a retry over deleted buffers would cascade the fault onto every
        request while the engine kept reporting healthy steps.  When
        the pools are gone, containment escalates to the caller
        instead — a lost pool is an engine-level failure, like a
        tripped watchdog."""
        return not any(getattr(x, "is_deleted", lambda: False)()
                       for x in jax.tree_util.tree_leaves(self._pools))

    def _expire(self, rs: ReqState, now: float,
                *, free: bool) -> RequestOutput:
        """Retire a deadline-expired WAITING/PREFILL request."""
        self.metrics.deadline_expired += 1
        waited = now - (rs.req.arrival_time or now)
        return self._retire(
            rs, FinishReason.DEADLINE, free=free,
            error=(f"deadline {rs.req.params.deadline_s}s exceeded "
                   f"({waited:.3f}s since arrival, status "
                   f"{rs.status.value})"))

    # -- graceful-degradation ladder --------------------------------------

    def _brownout_step(self, now: float) -> None:
        """One evaluation of the brownout ladder (docs/serving.md
        "Overload, SLO classes & autoscaling"), called at the top of
        every step while ``brownout=`` is armed.

        Pressure is the worse of queue backlog (normalized by
        ``max_queue``, or ``4 * max_batch`` unbounded) and KV-pool
        utilization, smoothed by a clock-driven EMA over ``window_s``
        (deterministic under a fake clock — no wall reads).  The rung
        climbs ONE level after ``dwell_steps`` consecutive steps above
        ``high`` and descends one after as many below ``low``; the
        dwell counter is the hysteresis that keeps a bursty boundary
        from flapping the ladder every step."""
        cfg = self.brownout_cfg
        qd = self.scheduler.queue_depth
        denom = (self.max_queue if self.max_queue
                 else 4 * self.max_batch)
        pressure = max(qd / denom if denom else 0.0,
                       self.bm.utilization)
        if self._pressure_t is None or cfg["window_s"] <= 0:
            self._pressure_ema = pressure
        else:
            dt = max(now - self._pressure_t, 0.0)
            alpha = 1.0 - math.exp(-dt / cfg["window_s"])
            self._pressure_ema += alpha * (pressure - self._pressure_ema)
        self._pressure_t = now
        if self._pressure_ema > cfg["high"] and self.brownout_rung < 6:
            self._brownout_dwell = max(self._brownout_dwell, 0) + 1
            if self._brownout_dwell >= cfg["dwell_steps"]:
                self._brownout_dwell = 0
                self._set_brownout(self.brownout_rung + 1)
        elif self._pressure_ema < cfg["low"] and self.brownout_rung > 0:
            self._brownout_dwell = min(self._brownout_dwell, 0) - 1
            if -self._brownout_dwell >= cfg["dwell_steps"]:
                self._brownout_dwell = 0
                self._set_brownout(self.brownout_rung - 1)
        else:
            self._brownout_dwell = 0

    def _set_brownout(self, rung: int) -> None:
        """Move the ladder to ``rung``, applying/releasing each rung's
        effect (entering and leaving both land a ``brownout`` trace
        event and move the ``serve_brownout_rung`` gauge — a degrade
        decision is never silent)."""
        prev, self.brownout_rung = self.brownout_rung, rung
        if rung == prev:
            return
        self.metrics.observe_brownout(rung)
        self.trace.emit("brownout", None, rung=rung, prev=prev,
                        pressure=round(self._pressure_ema, 4))
        # rung 2: chunked-prefill budget halves (floor: one chunk, the
        # scheduler's own livelock floor); released on descent
        sched = self.scheduler
        sched.prefill_budget = (
            max(sched.prefill_chunk, self._base_prefill_budget // 2)
            if rung >= 2 else self._base_prefill_budget)
        # rung 3: best_effort emission caps (>= 1 token of headroom on
        # live rows so every capped row retires through a normal LENGTH
        # commit); released on descent — a request that outlived the
        # brownout serves its full budget
        cap = self.brownout_cfg["best_effort_cap"]
        for rs in self._states.values():
            if (rs.status is Status.FINISHED
                    or rs.req.slo_class != "best_effort"):
                continue
            if rung >= 3:
                rs.new_cap = max(len(rs.generated) + 1, cap)
            elif rs.new_cap is not None:
                rs.new_cap = None

    def _quarantine(self, rs: ReqState, msg: str) -> RequestOutput:
        """Retire a poison request (``FinishReason.ERROR``): its blocks
        free immediately so the pool stays whole, its partial output is
        preserved, and the rest of the batch keeps serving."""
        self.metrics.quarantined += 1
        print(f"[serve] {rs.req.request_id}: quarantined — {msg}",
              file=sys.stderr)
        out = self._retire(rs, FinishReason.ERROR,
                           free=rs.slot is not None, error=msg)
        self._trace_faults()
        self.flight_flush(f"quarantine: {rs.req.request_id}")
        return out

    # Decode-loop device programs: their dispatches count toward
    # metrics.dispatches (summary()["decode"] — the denominator of
    # tokens_per_dispatch).  Admission-path programs (prefill, page
    # scatter, draft join) do not.
    _DECODE_OPS = frozenset({"paged_decode", "decode_horizon", "spec_round",
                             "draft_tail_step"})

    def program_registry(self) -> list:
        """Every compiled device program behind this engine, as audit
        records for ``analysis.jaxpr_audit`` (docs/analysis.md): the
        ``CountingJit`` wrappers ``metrics.register_compiled`` collected
        at construction, each with its declared static-kwarg ladders
        (the horizon's ``H`` rides ``h_ladder``, the spec round's ``K``
        the pow2 k-ladder — off-ladder statics are the cache-fork
        class) and its allowed collective seams (world-1 programs allow
        none; mesh programs declare ``serve.mesh.collective_seams``)."""
        ladders = {
            "zero_scratch": {"s_ext": tuple(self.ladder)},
            "draft_zero_scratch": {
                "s_ext": tuple(getattr(self, "_draft_ladder", ()))},
            "decode_horizon": {"H": tuple(self.h_ladder),
                               "all_greedy": (True, False)},
            "spec_round": {"K": tuple(getattr(self, "_k_ladder", ())),
                           "all_greedy": (True, False)},
            "draft_tail_step": {"K": tuple(getattr(self, "_k_ladder",
                                                   ()))},
        }
        if self.mesh is not None:
            seams = serve_mesh.collective_seams(
                self.cfg, kv_shard=self.kv_shard,
                draft_cfg=(self.draft.cfg if self.draft is not None
                           else None))
        else:
            seams = {}
        recs, seen = [], set()
        for fn in self.metrics.compiled_fns:
            name = getattr(fn, "name", None)
            if name is None or name in seen:
                continue
            seen.add(name)
            recs.append({"name": name, "fn": fn,
                         "ladders": ladders.get(name, {}),
                         "seams": seams.get(name, {})})
        return recs

    def _device_call(self, op: str, rids: tuple, fn, *args,
                     fire_injector: bool = True, **kwargs):
        """The ONE guarded device-dispatch seam: the ``forward`` fault
        point fires inside the watched thunk (an injected stall trips
        the watchdog exactly like a wedged device), and with
        ``step_timeout_s`` set the result is forced to ready under
        ``runtime.watchdog`` so a hung forward raises
        :class:`WatchdogTimeout` instead of wedging ``run()`` forever
        (the heartbeat file goes stale — the beats are synchronous).

        ``fire_injector=False`` skips the fault seam: links 2..N of a
        pipelined horizon chain dispatch through it — an injected fault
        AFTER link 1 donated the pools would otherwise leave a
        retry-looking state whose retry double-commits link 1's burst
        (the chain fires the injector exactly once, at its head)."""
        def call():
            if fire_injector and self.faults is not None:
                self.faults.fire("forward", op=op, rids=rids)
            # Counted AFTER the injector seam: an injector-aborted
            # attempt never reached the device and must not inflate
            # dispatches_per_token under chaos.
            if op in self._DECODE_OPS:
                self.metrics.dispatches += 1
            out = fn(*args, **kwargs)
            return (jax.block_until_ready(out)
                    if self.step_timeout_s is not None else out)
        if self.step_timeout_s is None:
            return call()
        try:
            return run_with_watchdog(call, self.step_timeout_s, name=op)
        except WatchdogTimeout:
            self.metrics.watchdog_trips += 1
            self.trace.emit("fault", None, point="watchdog", op=op)
            self.flight_flush(f"watchdog: {op}")
            raise

    def _forward_contained(self, rows: list[ReqState], runner, kind: str,
                           finished: list) -> None:
        """Run ``runner(rows)`` — ONE batched forward plus its per-row
        commits — containing failures: the whole set retries up to
        ``fault_retries`` times (transient faults), then bisects to
        isolate the poison row(s); a single row that still fails is
        quarantined and its slot-mates re-run clean.  ``runner`` must
        keep all engine-state mutation AFTER its device sync, so a
        failed attempt leaves nothing committed and the retry is safe
        (per-row commit errors are contained inside ``runner`` itself
        and never escape it).  Precondition for every retry: the
        donated pools survived (:meth:`_state_intact`) — a genuine
        post-dispatch device failure escalates instead of cascading
        over deleted buffers."""
        err = None
        for attempt in range(1 + max(self.fault_retries, 0)):
            try:
                runner(rows)
                return
            except _FATAL:
                raise
            except ChainCommitted:
                raise  # bursts already committed: a retry double-emits
            except Exception as e:
                if not self._state_intact():
                    raise  # donated pools consumed: engine-fatal
                err = e
                if attempt < self.fault_retries:
                    self.metrics.forward_retries += 1
        if len(rows) == 1:
            rs = rows[0]
            if rs.status is Status.RUNNING:
                finished.append(self._quarantine(
                    rs, f"{kind} forward failed after "
                        f"{1 + self.fault_retries} attempts: {err!r}"))
            return
        self.metrics.forward_bisections += 1
        mid = len(rows) // 2
        for half in (rows[:mid], rows[mid:]):
            live = [r for r in half if r.status is Status.RUNNING]
            if live:
                self._forward_contained(live, runner, kind, finished)

    # -- capacity / preemption -------------------------------------------

    def _ensure_capacity(self, rs: ReqState, n_tokens: int) -> None:
        """Grow ``rs``'s allocation to ``n_tokens`` rows, preempting
        later-admitted slot holders (running OR mid-prefill — both hold
        blocks) until it fits.  Victims never include ``rs`` itself;
        when none remain the pool is genuinely too small for this
        request and the engine raises.

        Capacity includes EXCLUSIVITY (docs/serving.md "Prefix
        caching"): every page the grown request may write must be owned
        by it alone, so shared pages in the write range copy-on-write
        split here — under the same preemption loop, since the split
        needs a fresh block too."""
        while True:
            try:
                self.bm.ensure(rs.req.request_id, n_tokens)
                self._cow_writable(rs)
                return
            except BlockExhausted:
                victim = self.scheduler.pick_victim(
                    [s for s in self.slots if s is not None
                     and s.status in (Status.RUNNING, Status.PREFILL)],
                    rs)
                if victim is None:
                    raise RuntimeError(
                        f"{rs.req.request_id}: cannot extend to "
                        f"{n_tokens} tokens and no preemption victim "
                        f"remains — the block pool ({self.bm.num_blocks}"
                        " blocks) is too small for this request")
                self._preempt(victim)

    def _note_aux(self, aux: list) -> int:
        """Keep a program's trailing counter outputs (device arrays, not
        waited on) until a commit point folds them.  Returns how many
        have been noted in all: the device runs ONE stream in order, so
        once a later result of the same program has reached the host,
        everything noted up to here is ready."""
        self._aux_pending.extend(aux)
        return self._aux_folded + len(self._aux_pending)

    def _fold_aux(self, upto: int) -> None:
        """Fold the counter arrays noted up to the ``upto``-th (all ready:
        see :meth:`_note_aux`) into the metrics — the tally of a family
        with expert layers, and an indexer's behind it
        (``ServeMetrics.observe_family``)."""
        n = upto - self._aux_folded
        if n > 0:
            done = self._aux_pending[:n]
            del self._aux_pending[:n]
            self._aux_folded = upto
            self.metrics.observe_family(
                np.sum(jax.device_get(done), axis=0))

    def _note_reach(self, kv_len: int, n: int) -> None:
        """Count the cached tokens the ``n`` decode queries of one row
        from length ``kv_len`` read, a layer, by layer group (cache
        groups only: ``summary()["swa"]``): the context on full layers,
        ``min(context, window)`` on window layers."""
        if not self.kv_groups:
            return
        ctx = np.arange(kv_len + 1, kv_len + n + 1)
        shared = self._cache_readers is not None
        for g in self.kv_groups:
            if g.get("state_planes"):
                continue
            # a cache read by layers that own none counts each reader
            readers = (self._cache_readers[g["name"]] if shared
                       else len(g["layers"]))
            seen = int((np.minimum(ctx, g["window"]) if g["window"]
                        else ctx).sum()) * readers
            if shared:
                if g["window"]:
                    self.metrics.yoco_window_tokens += seen
                else:
                    self.metrics.yoco_shared_tokens += seen
            elif g["window"]:
                self.metrics.swa_window_tokens += seen
            else:
                self.metrics.swa_full_tokens += seen

    def _preempt(self, victim: ReqState) -> None:
        self.trace.emit("preempt", victim.req.request_id,
                        kv_len=victim.kv_len,
                        generated=len(victim.generated))
        self.slots[victim.slot] = None
        victim.scratch = None
        self.scheduler.preempt(victim)
        self.metrics.preemptions += 1
        self.metrics.observe_class_preempt(victim.req.slo_class)

    # -- prefix sharing: copy-on-write + content commits ------------------

    def _cow_writable(self, rs: ReqState) -> None:
        """Copy-on-write guard (docs/serving.md "Prefix caching"): every
        logical page from ``rs``'s current length to the end of its
        allocation — the pages a decode/verify write may touch — must be
        exclusively owned.  A page still shared (refcount > 1: a
        partially-filled tail mapped into several tables by beam-style
        sharing or a restored overlapping snapshot) splits here: the
        block manager swaps in a fresh block and the device copies the
        page BEFORE any write can land.  Admission-shared prefix pages
        are full pages strictly below the write range, so steady-state
        traffic never pays a copy — the loop is a few dict lookups."""
        if self.kv_groups:
            return      # no sharing path runs through cache groups
        rid = rs.req.request_id
        table = self.bm.table(rid)
        for logical in range(rs.kv_len // self.page, len(table)):
            if self.bm.ref_of(table[logical]) <= 1:
                continue
            old, new = self.bm.cow(rid, logical)
            self.trace.emit("cow_split", rid, old=old, new=new,
                            logical=logical)
            self._pools = self._device_call(
                "cow_copy", (rid,), self._cow_fn, self._pools,
                np.int32(old), np.int32(new))

    def _commit_full_blocks(self, rs: ReqState) -> None:
        """Register every newly-FULL logical page of ``rs`` in the
        content index (``BlockManager.commit_block``) so later prompts —
        a multi-turn session's next turn, an identical system prompt, a
        preempted victim's recompute — map it read-only instead of
        re-prefilling.  Generated tokens commit too, the moment their
        page fills: cache row ``j`` holds the K/V of ``prompt[j]`` for
        ``j < S0`` and of ``generated[j - S0]`` past it (a recompute
        prompt is exactly that concatenation, so the indexing is
        invariant under preemption).  ``committed_pages`` is the
        watermark — each page commits once per admission."""
        if not self.bm.prefix_cache:
            return
        full = rs.kv_len // self.page
        if full <= rs.committed_pages:
            return
        rid = rs.req.request_id
        prompt = rs.req.prompt
        S0 = int(prompt.shape[0])
        for logical in range(rs.committed_pages, full):
            lo = logical * self.page
            toks = [int(prompt[j]) if j < S0 else rs.generated[j - S0]
                    for j in range(lo, lo + self.page)]
            self.bm.commit_block(rid, logical, toks)
        rs.committed_pages = full

    # -- plain decode -----------------------------------------------------

    def _decode_once(self,
                     running: list[ReqState]) -> list[RequestOutput]:
        """One decode pass for the running rows.  An engine that has a
        horizon program and runs no speculative rounds (``horizon > 1``,
        no ``spec_k``) dispatches horizon links whatever the scheduler
        plans: a fused multi-step chain (pipelined when ``pipeline > 1``)
        with its blessing, ONE link of ``H = 1`` when it clamps the step
        (a slot mid-prefill, a waiting deadline) — the token is chosen on
        the device either way and the host drains a ``[B, H]`` burst.
        Every other engine (``horizon=1``; a speculative engine's
        bail-out) takes the per-token step with the host sampler,
        :meth:`_decode_rows`.  Capacity for the WHOLE planned horizon is
        reserved up front — a row that cannot grow quarantines here, per
        row, on either path."""
        finished: list[RequestOutput] = []
        with self.trace.span("decode.plan"):
            h_plan = self.scheduler.plan_horizon(
                self.horizon,
                prefilling=any(s is not None and s.status is Status.PREFILL
                               for s in self.slots),
                spec=bool(self.spec_k),
                deadline_waiting=any(
                    w.req.params.deadline_s is not None
                    for w in self.scheduler.waiting))
            links = self.pipeline if h_plan > 1 else 1
            if self.kv_groups:
                # Nothing is in flight between chains, so the earliest
                # query still to come for a row sits at its committed
                # length: the window group's pages wholly behind that
                # query's window go back before anyone grows.
                with self.trace.span("decode.plan.release"):
                    self.metrics.kv_window_released += sum(
                        self.bm.release_unseen(rs.req.request_id, rs.kv_len)
                        for rs in running if rs.status is Status.RUNNING)
                if self._has_state:
                    # slots were taken at admission and given back at
                    # finish or preemption since the last chain: the peak
                    with self.trace.span("decode.plan.state"):
                        self.bm.note_peak()
            for rs in sorted(running, key=lambda r: r.seq):
                if rs.status is Status.RUNNING:  # may get preempted below
                    want = rs.kv_len + min(max(h_plan, 1) * links,
                                           rs.remaining_new)
                    want = min(want, rs.total_tokens)
                    try:
                        self._ensure_capacity(rs, want)
                    except _FATAL:
                        raise
                    except Exception as e:
                        # No-victim RuntimeError or an injected alloc fault:
                        # this request cannot grow — quarantine it (its
                        # blocks come back) instead of unwinding the step.
                        finished.append(self._quarantine(
                            rs, f"kv grow to {want} rows: {e!r}"))
            live = [r for r in running if r.status is Status.RUNNING]
            if not live:
                return finished
            h_eff = 1
            if h_plan > 1:
                # The scan length is a STATIC trace parameter: bucket the
                # planned horizon down the ladder so tail-of-generation
                # batches reuse compiled rungs instead of tracing one
                # program per residual length.
                h_eff = bucket_down(
                    self.h_ladder,
                    min(h_plan, max(r.remaining_new for r in live)))
        if self.horizon > 1 and not self.spec_k:
            self._forward_contained(
                live,
                lambda rows: self._decode_horizon_rows(rows, h_eff,
                                                       finished),
                "decode horizon", finished)
        else:
            self._forward_contained(
                live, lambda rows: self._decode_rows(rows, finished),
                "decode", finished)
        return finished

    def _decode_rows(self, rows: list[ReqState], finished: list) -> None:
        """The per-token step of an engine WITHOUT horizon links
        (``horizon=1``; a speculative engine's bail-out): ONE batched
        decode for ``rows`` (other slots inactive — their writes redirect
        to the null block), the logits to the host, a host-side token
        choice and commit per row.  All
        engine-state mutation happens after the logits sync, so a
        failed dispatch leaves nothing committed and
        :meth:`_forward_contained` can retry or bisect safely."""
        span = self.trace.span
        with span("decode.stage"):
            B = self.max_batch
            tokens = np.zeros((B,), np.int32)
            lens = np.zeros((B,), np.int32)
            active = np.zeros((B,), bool)
            tables = np.zeros(self._tables_shape, np.int32)
            for rs in rows:
                b = rs.slot
                tokens[b] = rs.pending_token
                lens[b] = rs.kv_len
                active[b] = True
                tables[..., b, :] = self.bm.padded_table(
                    rs.req.request_id, self.n_pages_max)
            operands = (jnp.asarray(tables), jnp.asarray(lens),
                        jnp.asarray(tokens), jnp.asarray(active))
        pools, logits, *aux = self._device_call(
            "paged_decode", tuple(r.req.request_id for r in rows),
            self._decode_fn, self.params, self._pools, *operands)
        with span("decode.wait"):
            logits_np = np.asarray(logits)  # sync BEFORE committing pools
        self._pools = pools
        self._fold_aux(self._note_aux(aux))
        self.metrics.decode_steps += 1
        self.metrics.host_syncs += 1
        toks0 = self.metrics.decode_tokens

        with span("decode.commit"):
            for rs in rows:
                if rs.status is not Status.RUNNING:
                    continue  # aborted mid-loop by a slot-mate's callback
                self._note_reach(rs.kv_len, 1)
                rs.kv_len += 1
                rs.pending_token = None
                self._commit_full_blocks(rs)  # the write just landed
                try:
                    token = self._choose_token(rs, logits_np[rs.slot])
                    out = self._commit_token(rs, token)
                except _FATAL:
                    raise
                except Exception as e:
                    finished.append(self._quarantine(rs,
                                                     f"commit: {e!r}"))
                    continue
                self.metrics.decode_tokens += 1
                if out is not None:
                    finished.append(out)
        self.trace.emit("decode_drain", None, h=1, rows=len(rows),
                        tokens=self.metrics.decode_tokens - toks0)

    def _decode_horizon_rows(self, rows: list[ReqState], h: int,
                             finished: list) -> None:
        """Fused decode for ``rows``: up to ``pipeline`` chained
        ``_paged_decode_horizon`` dispatches of ``h`` steps each — or, at
        ``h == 1`` (a step the scheduler clamped), one link of one step —
        then an in-order drain committing each link's token burst.

        The async pipeline is the point of the chaining: every link's
        carry (kv lengths, last token, EOS marks, PRNG counters) stays
        DEVICE-RESIDENT, so link N+1 dispatches before link N's results
        ever reach the host, and the host commits link N's burst (token
        bookkeeping, ``on_token`` callbacks) while the device executes
        link N+1 — ``block_until_ready`` is deferred to each link's drain
        point.  (With ``step_timeout_s`` set the watchdog forces every
        link ready at dispatch, so the links serialize and only the
        step-fusion win remains — stall detection and dispatch overlap
        are mutually exclusive by construction.)  A row that hits EOS
        mid-link is frozen by the device for the rest of the chain
        (``eos_done`` carry); its retire, block free, and the discard of
        any later-link output all happen at drain, guarded by the same
        status checks as the single-step path.

        Containment mirrors :meth:`_decode_rows`: nothing host-side
        mutates before the first drain, the injector seam fires once at
        the chain head (see ``_device_call(fire_injector=...)``), so
        :meth:`_forward_contained` can retry/bisect a failed chain whose
        pools survived; once any burst has committed, failures escalate
        as :class:`ChainCommitted` instead (a retry would double-emit)."""
        span = self.trace.span
        with span("decode.stage"):
            B = self.max_batch
            tokens = np.zeros((B,), np.int32)
            lens = np.zeros((B,), np.int32)
            active = np.zeros((B,), bool)
            tables = np.zeros(self._tables_shape, np.int32)
            counts = np.zeros((B,), np.int32)
            temps = np.ones((B,), np.float32)
            top_ks = np.zeros((B,), np.int32)
            top_ps = np.ones((B,), np.float32)
            greedy = np.ones((B,), bool)
            eos_ids = np.full((B,), -1, np.int32)
            rem = np.zeros((B,), np.int32)
            for rs in rows:
                b = rs.slot
                p = rs.req.params
                tokens[b] = rs.pending_token
                lens[b] = rs.kv_len
                active[b] = True
                tables[..., b, :] = self.bm.padded_table(
                    rs.req.request_id, self.n_pages_max)
                counts[b] = len(rs.generated)
                temps[b] = p.temperature if not p.greedy else 1.0
                top_ks[b] = p.top_k or 0
                top_ps[b] = p.top_p if p.top_p is not None else 1.0
                greedy[b] = p.greedy
                eos_ids[b] = p.eos_id if p.eos_id is not None else -1
                # Per-row step budget: remaining max-tokens AND the pages the
                # host actually reserved (the page-boundary early exit).
                rem[b] = min(rs.remaining_new,
                             self.bm.capacity_tokens(rs.req.request_id)
                             - rs.kv_len)
            all_greedy = bool(greedy[active].all())
            rids = tuple(r.req.request_id for r in rows)

            # Host link plan: link j runs min(h, what's left after j-1) steps
            # per row; the device masks enforce it, EOS exits ride the carry.
            # Each link's scan length buckets DOWN the ladder from its own
            # max budget — a tail link covering a 2-step residual runs the
            # warmed H=2 program, not h-2 dead full-batch forwards on the
            # H=h one (every rung is warmup-swept, so no new traces).
            # A step the scheduler clamps (h == 1) is ONE link of one step,
            # whatever the rows could still run: the mid-prefill row it was
            # clamped for is owed its chunk budget next engine step.  A
            # one-step residual behind longer links chains like any other.
            budgets = []
            left = rem.copy()
            for _ in range(self.pipeline if h > 1 else 1):
                need = int(left[active].max()) if active.any() else 0
                if need < 1:
                    break
                h_link = bucket_down(self.h_ladder, min(h, need))
                lim = np.minimum(left, h_link).astype(np.int32)
                budgets.append((h_link, jnp.asarray(lim)))
                left = left - lim

            # Dispatch every link before draining any (async pipelining);
            # the carry arrays never touch the host between links.
            kv_d = jnp.asarray(lens)
            tok_d = jnp.asarray(tokens)
            done_d = jnp.asarray(np.zeros((B,), bool))
            cnt_d = jnp.asarray(counts)
            tables_d = jnp.asarray(tables)
            active_d = jnp.asarray(active)
            # Host-built per-row base keys — the SAME jax.random.key(p.seed)
            # call `_choose_token` makes, so seeds the int32 array route
            # would overflow (>= 2**31) stream identically at every H.
            seeds = [None] * B
            if not all_greedy:
                for rs in rows:
                    if not rs.req.params.greedy:
                        seeds[rs.slot] = rs.req.params.seed
            samp = (_key_batch(seeds), jnp.asarray(temps),
                    jnp.asarray(top_ks), jnp.asarray(top_ps),
                    jnp.asarray(greedy), jnp.asarray(eos_ids))
        outs = []
        t_prev = self._clock()
        for j, (h_link, lim) in enumerate(budgets):
            (pools, toks, mask, kv_d, tok_d, done_d,
             cnt_d, *aux) = self._device_call(
                "decode_horizon", rids, self._horizon_fn, self.params,
                self._pools, tables_d, kv_d, tok_d, active_d, done_d,
                lim, cnt_d, *samp, H=int(h_link),
                # Rung 1 has the mixed-sampler variant alone: a greedy
                # row takes its argmax there as beside any sampled
                # slot-mate, at the sampler's ~0.3 ms a step, where a
                # second program would be 3.4 s of every start (16
                # layers, v5e: PERF.md §6, PR 31).
                all_greedy=all_greedy and h_link > 1,
                fire_injector=(j == 0))
            self._pools = pools
            # a wide vocabulary's sampler says LAST how many sampled
            # row-steps took their cut-offs from the whole rows
            whole = aux.pop() if takes_candidates(self.cfg.vocab) else None
            outs.append((toks, mask, whole, self._note_aux(aux)))

        # Drain in order: committing link j's burst overlaps the device
        # executing links > j (nothing here forces their results).
        committed = False
        try:
            for toks, mask, whole, n_aux in outs:
                with span("decode.wait"):
                    toks_np, mask_np, whole = jax.device_get(
                        (toks, mask, whole))
                self.metrics.host_syncs += 1
                self._fold_aux(n_aux)
                self.metrics.observe_sampled(int(mask_np[~greedy].sum()),
                                             whole)
                now = self._clock()
                steps = int(mask_np.any(axis=0).sum())
                self.metrics.decode_steps += steps
                step_s = (now - t_prev) / max(steps, 1)
                t_prev = now
                toks0 = self.metrics.decode_tokens
                with span("decode.commit"):
                    for rs in sorted(rows, key=lambda r: r.seq):
                        if rs.status is not Status.RUNNING:
                            continue  # retired mid-drain (EOS/abort/length)
                        b = rs.slot
                        n = int(mask_np[b].sum())
                        if n == 0:
                            continue
                        self._note_reach(rs.kv_len, n)
                        rs.kv_len += n  # the device already wrote these rows
                        times = rs.metrics.burst_times(now, n, step_s)
                        out = None
                        try:
                            for i in range(n):
                                out = self._commit_token(
                                    rs, int(toks_np[b, i]), now=times[i])
                                committed = True
                                self.metrics.decode_tokens += 1
                                if (out is not None
                                        or rs.status is not Status.RUNNING):
                                    break  # retired; rest of burst discarded
                        except _FATAL:
                            raise
                        except Exception as e:
                            finished.append(self._quarantine(
                                rs, f"commit: {e!r}"))
                            continue
                        if rs.status is Status.RUNNING:
                            # the burst's tokens are in `generated` now, so
                            # any page the device filled this link commits
                            self._commit_full_blocks(rs)
                        if out is not None:
                            finished.append(out)
                self.trace.emit(
                    "decode_drain", None, h=steps,
                    tokens=self.metrics.decode_tokens - toks0)
        except (*_FATAL, ChainCommitted):
            raise
        except Exception as e:
            if committed:
                raise ChainCommitted(
                    f"horizon chain failed after committing tokens: "
                    f"{e!r}") from e
            raise

    # -- fused speculative rounds (docs/serving.md "Speculative
    # decoding") ----------------------------------------------------------

    def _spec_chain(self,
                    running: list[ReqState]) -> list[RequestOutput]:
        """Up to ``pipeline`` chained ``_spec_round_fused`` dispatches —
        ONE device dispatch per whole speculative round (draft k-scan,
        verify, accept, closing decode) with the carry (kv lengths, both
        models' round-opening logits, emission counters, EOS/budget
        exits) staying device-resident between rounds, then an in-order
        drain committing each round's accepted burst.  The spec twin of
        :meth:`_decode_horizon_rows`: round j+1 dispatches before round
        j's results reach the host, and the host commits round j's
        tokens while the device runs j+1.

        Adaptive k: each row's depth comes from the scheduler's windowed
        acceptance estimate (``choose_spec_k``), the batch max buckets
        down the pow2 k-ladder (static scan length — one warmed trace
        per rung), and per-row depths ride the traced ``k_rows`` array.

        Containment keeps the PR-3 contract: capacity growth
        quarantines per request; a device failure latches speculation
        OFF via :meth:`_spec_bailout_fused` — already-drained tokens
        stand, undrained rows emit exactly what the round would have
        emitted first — and the engine degrades to plain decode
        bit-exactly."""
        finished: list[RequestOutput] = []
        live = [r for r in running if r.status is Status.RUNNING]
        top = max(r.kv_len for r in live)
        k_cap = min(self.spec_k, self.gen.max_seq - 1 - top,
                    self.draft.max_seq - 1 - top)
        if self.brownout_rung >= 1:
            # brownout rung 1: clamp speculation to k=1 — the cheapest
            # rung sheds DRAFT compute, not user tokens (the k=1 rung
            # is already on the warmed pow2 k-ladder, so no new traces)
            k_cap = min(k_cap, 1)
        if k_cap <= 0:
            return self._spec_tail(live)
        with self.trace.span("decode.plan"):
            links = self.scheduler.plan_spec(
                self.pipeline,
                prefilling=any(s is not None and s.status is Status.PREFILL
                               for s in self.slots),
                deadline_waiting=any(
                    w.req.params.deadline_s is not None
                    for w in self.scheduler.waiting))
            # Capacity for the WHOLE chain up front (capped at the admitted
            # total; writes past the allocation land in dead padded-table
            # entries -> the null block, never a live page).
            for rs in sorted(live, key=lambda r: r.seq):
                if rs.status is Status.RUNNING:
                    want = min(rs.kv_len + links * (k_cap + 1),
                               rs.total_tokens)
                    try:
                        self._ensure_capacity(rs, want)
                    except _FATAL:
                        raise
                    except Exception as e:
                        finished.append(self._quarantine(
                            rs, f"kv grow (spec chain): {e!r}"))
            live = [r for r in live if r.status is Status.RUNNING]
            if not live:
                return finished

        with self.trace.span("decode.stage"):
            B = self.max_batch
            lens = np.zeros((B,), np.int32)
            active = np.zeros((B,), bool)
            tables = np.zeros(self._tables_shape, np.int32)
            counts = np.zeros((B,), np.int32)
            limits = np.zeros((B,), np.int32)
            k_rows = np.ones((B,), np.int32)
            temps = np.ones((B,), np.float32)
            top_ks = np.zeros((B,), np.int32)
            top_ps = np.ones((B,), np.float32)
            greedy = np.ones((B,), bool)
            eos_ids = np.full((B,), -1, np.int32)
            seeds = [None] * B
            for rs in live:
                b = rs.slot
                p = rs.req.params
                lens[b] = rs.kv_len
                active[b] = True
                tables[..., b, :] = self.bm.padded_table(
                    rs.req.request_id, self.n_pages_max)
                counts[b] = len(rs.generated)
                # Per-row emission budget: remaining max-tokens AND the
                # reserved page capacity (never binds after a successful
                # _ensure_capacity — kept as the device-side safety net).
                limits[b] = min(rs.remaining_new,
                                self.bm.capacity_tokens(rs.req.request_id)
                                - rs.kv_len)
                k_rows[b] = (self.scheduler.choose_spec_k(
                                 rs, k_cap, window=self.spec_adaptive)
                             if self.spec_adaptive else k_cap)
                temps[b] = p.temperature if not p.greedy else 1.0
                top_ks[b] = p.top_k or 0
                top_ps[b] = p.top_p if p.top_p is not None else 1.0
                greedy[b] = p.greedy
                eos_ids[b] = p.eos_id if p.eos_id is not None else -1
                if not p.greedy:
                    # Host-built typed keys, like the horizon: any seed the
                    # host path accepts (>= 2**31 included) streams
                    # identically on device.
                    seeds[b] = p.seed
            all_greedy = bool(greedy[active].all())
            k_rung = bucket_down(self._k_ladder, int(k_rows[active].max()))
            chain_k = {rs.slot: min(int(k_rows[rs.slot]), k_rung)
                       for rs in live}
            rids = tuple(r.req.request_id for r in live)
            # A round emits >= 1 token per live row, so rounds beyond the
            # widest per-row budget would dispatch dead full-batch work.
            links = max(1, min(links, int(limits[active].max())))

            kv_d = jnp.asarray(lens)
            act_d = jnp.asarray(active)
            done_d = jnp.asarray(np.zeros((B,), bool))
            tables_d = jnp.asarray(tables)
            cnt_d = jnp.asarray(counts)
            lim_d = jnp.asarray(limits)
            k_rows_d = jnp.asarray(k_rows)
            samp = (_key_batch(seeds), jnp.asarray(temps),
                    jnp.asarray(top_ks), jnp.asarray(top_ps),
                    jnp.asarray(greedy), jnp.asarray(eos_ids))
        # The PRE-CHAIN round-opening logits: every live row's next
        # emission comes from these until its first burst commits, so
        # any bailout with uncommitted rows must sample HERE — never
        # from the chain's advanced carry (which already consumed
        # device-emitted tokens the host never saw).
        opening = self._last_logits
        last_d = opening
        dcaches = self._draft_state.caches
        dlast_d = self._draft_state.last_logits
        outs = []
        t_prev = self._clock()
        try:
            for j in range(links):
                (pools, dcaches, toks, n_emit, m_acc, kv_d, last_d,
                 dlast_d, cnt_d, lim_d, done_d) = self._device_call(
                    "spec_round", rids, self._spec_fn,
                    self.params, self.draft_params, self._pools,
                    dcaches, tables_d, kv_d, act_d, done_d, last_d,
                    dlast_d, cnt_d, lim_d, k_rows_d, *samp,
                    K=int(k_rung), all_greedy=all_greedy,
                    fire_injector=(j == 0))
                self._pools = pools
                # Re-anchor the draft state per link: a LATER link's
                # dispatch failure must not leave _draft_state pointing
                # at buffers this link's donation already consumed (the
                # spec_off snapshot guard in recovery covers the
                # failed-dispatch-itself case).
                self._draft_state = GenerationState(
                    caches=dcaches, kv_lens=kv_d, last_logits=dlast_d)
                self.metrics.spec_dispatches += 1
                outs.append((toks, n_emit, m_acc))
        except _FATAL:
            raise
        except Exception as e:
            if not self._state_intact():
                raise  # donated pools consumed: engine-fatal
            # Nothing drained: the pre-chain opening logits are what
            # every live row's accept would have emitted from.
            return finished + self._spec_bailout_fused(live, set(), e,
                                                       opening)
        # The chain's final carry opens the next step's round.
        self._last_logits = last_d

        # Drain in order; committing round j overlaps rounds > j on
        # device.  Status checks guard every commit (abort/EOS/quarantine
        # mid-drain), exactly like the horizon drain.
        committed: set[int] = set()
        try:
            for toks, n_emit, m_acc in outs:
                with self.trace.span("decode.wait"):
                    toks_np, n_np, m_np = jax.device_get(
                        (toks, n_emit, m_acc))
                self.metrics.host_syncs += 1
                now = self._clock()
                burst = int(n_np.max())
                step_s = (now - t_prev) / max(burst, 1)
                t_prev = now
                round_live = False
                toks0 = self.metrics.spec_tokens
                with self.trace.span("decode.commit"):
                    for rs in sorted(live, key=lambda r: r.seq):
                        if rs.status is not Status.RUNNING:
                            continue
                        b = rs.slot
                        n = int(n_np[b])
                        if n == 0:
                            continue
                        round_live = True
                        prop = chain_k[b]
                        acc = min(int(m_np[b]), prop)
                        rs.spec_window.append((prop, acc))
                        # keep at least the configured adaptive window
                        del rs.spec_window[:-max(32, self.spec_adaptive)]
                        self.metrics.observe_spec_row(prop, acc, prop)
                        rs.kv_len += n  # the device already wrote the rows
                        times = rs.metrics.burst_times(now, n, step_s)
                        out = None
                        try:
                            for i in range(n):
                                out = self._commit_token(
                                    rs, int(toks_np[b, i]), now=times[i])
                                committed.add(b)
                                self.metrics.decode_tokens += 1
                                self.metrics.spec_tokens += 1
                                if (out is not None
                                        or rs.status is not Status.RUNNING):
                                    break  # retired; rest of burst dropped
                        except _FATAL:
                            raise
                        except Exception as e:
                            finished.append(self._quarantine(
                                rs, f"commit: {e!r}"))
                            continue
                        if rs.status is Status.RUNNING:
                            # spec-mode invariant: the round's closing decode
                            # already consumed the burst's last token — there
                            # is no pending token (commit_token set one)
                            rs.pending_token = None
                            self._commit_full_blocks(rs)
                        if out is not None:
                            finished.append(out)
                if round_live:
                    self.metrics.verify_rounds += 1
                    self.metrics.spec_rounds += 1
                    self.trace.emit(
                        "spec_round", None, k=int(k_rung),
                        tokens=self.metrics.spec_tokens - toks0)
        except _FATAL:
            raise
        except Exception as e:
            if not self._state_intact():
                raise
            # Rows with a drained burst re-open their last token as
            # pending; rows without one (only possible when the FIRST
            # drain failed) sample from the pre-chain opening logits.
            return finished + self._spec_bailout_fused(live, committed,
                                                       e, opening)
        return finished

    def _spec_tail(self, live: list[ReqState]) -> list[RequestOutput]:
        """No headroom to speculate (the last cache slots): one plain
        target token per row via the host sampler, consumed by one paged
        decode (which also refreshes the round-opening logits) with the
        draft stepping along — the round's k<=0 fallback, greedy and
        sampled rows alike (:meth:`_choose_token` serves both).  This
        must never under-serve a draft-less engine."""
        finished: list[RequestOutput] = []
        with self.trace.span("decode.plan"):
            for rs in sorted(live, key=lambda r: r.seq):
                if rs.status is Status.RUNNING:
                    try:
                        self._ensure_capacity(
                            rs, min(rs.kv_len + 1, rs.total_tokens))
                    except _FATAL:
                        raise
                    except Exception as e:
                        finished.append(self._quarantine(
                            rs, f"kv grow (spec tail): {e!r}"))
            live = [r for r in live if r.status is Status.RUNNING]
            if not live:
                return finished
        B = self.max_batch
        lens = np.zeros((B,), np.int32)
        active = np.zeros((B,), bool)
        tables = np.zeros((B, self.n_pages_max), np.int32)
        toks_np = np.zeros((B,), np.int32)
        with self.trace.span("decode.wait"):
            last_np = np.asarray(self._last_logits)
        self.metrics.host_syncs += 1
        for rs in live:
            b = rs.slot
            lens[b] = rs.kv_len
            active[b] = True
            tables[b] = self.bm.padded_table(rs.req.request_id,
                                             self.n_pages_max)
            toks_np[b] = self._choose_token(rs, last_np[b])
        rids = tuple(r.req.request_id for r in live)
        closing = jnp.asarray(toks_np)
        lens_d = jnp.asarray(lens)
        active_d = jnp.asarray(active)
        opening = self._last_logits  # the logits the tokens came from
        try:
            self._pools, logits = self._device_call(
                "paged_decode", rids, self._decode_fn, self.params,
                self._pools, jnp.asarray(tables), lens_d, closing,
                active_d)
            self.metrics.decode_steps += 1
            sd = self._draft_state
            dcaches, dlens, dlogits = self._device_call(
                "draft_tail_step", rids, self._draft_tail_fn,
                self.draft_params, sd.caches, lens_d, closing, active_d)
            # Commit the carry only once BOTH dispatches succeeded: a
            # draft-step failure bails out below, and the bailout must
            # re-derive each row's token from the ROUND-OPENING logits
            # — overwriting _last_logits first would hand it the
            # post-consumption logits and fork the stream.
            self._last_logits = logits
            self._draft_state = GenerationState(
                caches=dcaches, kv_lens=dlens, last_logits=dlogits)
        except _FATAL:
            raise
        except Exception as e:
            if not self._state_intact():
                raise
            # Nothing committed: the bailout re-derives the SAME token
            # per row from the still-intact round-opening logits.
            return finished + self._spec_bailout_fused(live, set(), e,
                                                       opening)
        with self.trace.span("decode.commit"):
            for rs in sorted(live, key=lambda r: r.seq):
                if rs.status is not Status.RUNNING:
                    continue
                rs.kv_len += 1
                out = None
                try:
                    out = self._commit_token(rs, int(toks_np[rs.slot]))
                    self.metrics.decode_tokens += 1
                except _FATAL:
                    raise
                except Exception as e:
                    finished.append(self._quarantine(rs, f"commit: {e!r}"))
                    continue
                rs.pending_token = None  # the decode above consumed it
                if rs.status is Status.RUNNING:
                    self._commit_full_blocks(rs)
                if out is not None:
                    finished.append(out)
        return finished

    def _spec_bailout_fused(self, live: list[ReqState], committed: set,
                            err, opening) -> list[RequestOutput]:
        """A fused speculative chain failed mid-flight: latch
        speculation OFF (the device-resident carry and draft state can
        no longer be trusted) and convert every live row to plain-decode
        state, bit-exactly:

        - a row that already committed tokens from this chain keeps
          them and re-opens its LAST token as pending (``kv_len`` steps
          back one row): the next plain decode re-writes that token's
          K/V — an idempotent overwrite, the device already landed it —
          and re-derives the logits the chain was carrying on device;
        - a row that committed nothing emits one token from ``opening``
          — the caller's snapshot of the PRE-CHAIN round-opening logits
          (never the advanced device carry, which has already consumed
          tokens the host never saw) — via the host sampler: exactly
          what the round's accept chain would have emitted first
          (``expected[0]`` is the target's own choice at this emission
          index), so the stream cannot differ from the fault-free run.

        From here the engine serves through :meth:`_decode_once` (full
        retry/bisect containment) and joining prompts take the plain
        prefill path."""
        self._spec_off = True
        self.metrics.spec_bailouts += 1
        self.trace.emit("bailout", None, err=type(err).__name__,
                        fused=True)
        self.flight_flush("spec bailout (fused)")
        print(f"[serve] fused speculative chain failed ({err!r}); "
              f"speculation latched off, serving degrades to plain "
              f"decode", file=sys.stderr)
        finished: list[RequestOutput] = []
        last_np = np.asarray(opening)
        for rs in sorted(live, key=lambda r: r.seq):
            if rs.status is not Status.RUNNING:
                continue
            if rs.slot in committed:
                rs.pending_token = rs.generated[-1]
                rs.kv_len -= 1
                continue
            out = self._commit_token(
                rs, self._choose_token(rs, last_np[rs.slot]))
            if out is not None:
                finished.append(out)
        return finished
