"""Serving metrics: per-request latencies + engine-level gauges.

Per request: TTFT (arrival → first emitted token), inter-token latencies,
queue wait (arrival → first scheduled).  Per engine step: queue depth,
running batch occupancy, KV-block utilization; counters for preemptions,
prefill tokens, decode/verify passes.  Compilation observability: the
engine registers its ``jit_cache.CountingJit``-wrapped programs here, so
trace-cache hits/misses, cumulative compile-stall time, warmup coverage,
and the process-wide ``cached_shard_jit`` stats all land in
:meth:`ServeMetrics.summary` under ``"compilation"`` (docs/serving.md
"Reading the compile metrics").

Memory is BOUNDED for long-lived engines (docs/observability.md): the
per-step gauge series are streaming aggregates (last/peak/mean — never
per-step lists), per-request ``token_times`` keeps a fixed recent
window, latency distributions live in log-bucketed
:class:`serve.trace.LogHistogram` fields (TTFT / ITL / queue-time /
step-time / snapshot-time with p50/p95/p99 in ``summary()``), and the
retired-request map prunes past ``requests_retain`` — consistent with
the journal's ``journal_retain_done`` pruning, so neither RSS nor
``summary()`` cost grows with every request or token ever served.

Export rides three paths: ``TDT_DUMP_IR=<dir>`` +
:meth:`ServeMetrics.maybe_dump` writes ``<dir>/<name>.json`` next to the
kernel IR dumps (one switch arms both); :meth:`ServeMetrics.to_prometheus`
is the text exposition behind ``examples/serve.py --metrics-port``
(served by ``serve.trace.start_metrics_server``); and
:func:`format_stats` / :func:`format_statline` are THE human-readable
renderings — the CLI's end-of-run block, its periodic one-liner, and the
supervisor's postmortem line all come from here, so the stats can never
drift between surfaces.
"""

from __future__ import annotations

import json
import os
from collections import deque
from dataclasses import dataclass, field
from typing import Optional

from triton_dist_tpu.runtime import dump
from triton_dist_tpu.serve.trace import LogHistogram

#: Recent token timestamps one request retains (the bounded window
#: behind ``inter_token_latencies`` and horizon burst pacing; full
#: distributions live in the engine-level histograms).
TOKEN_TIMES_WINDOW = 256

#: Retired requests ``ServeMetrics.requests`` keeps before pruning the
#: oldest (per-request detail only; the aggregate counters and
#: histograms keep counting forever).  Matches the journal's
#: ``journal_retain_done`` default.
REQUESTS_RETAIN = 4096

#: Counter fields :meth:`ServeMetrics.merge` adds across engines — the
#: fleet aggregation contract (serve/fleet.py): every additive counter
#: in the exposition sums replica-wise, histograms merge bucket-exactly,
#: gauges take last-sum/peak-max.  A counter added to ServeMetrics
#: without joining this tuple silently vanishes from the fleet
#: aggregate, so keep them in lockstep.
MERGE_COUNTERS = (
    "steps", "decode_steps", "verify_rounds", "prefill_tokens",
    "prefill_dispatches", "prefill_pad_tokens", "prefill_tail_rows",
    "scratch_dispatches", "preemptions", "completed",
    "decode_tokens", "dispatches",
    "host_syncs", "host_choices", "shed", "deadline_expired", "quarantined",
    "callback_errors", "forward_retries", "forward_bisections",
    "watchdog_trips", "spec_bailouts", "spec_rounds", "spec_proposed",
    "spec_accepted", "spec_tokens", "spec_dispatches",
    "draft_prefix_skipped_tokens", "snapshots", "snapshot_ms_total",
    "journal_records", "journal_bytes", "journal_rotations", "restores",
    "restored_in_place", "restored_requeued", "restored_tokens",
    "migrated_out", "migrated_in", "migrated_in_place",
    "migrated_tokens", "pushed_out", "pushed_in",
    "prefix_hits", "prefix_hit_tokens",
    "prefix_skipped_tokens", "running_sum", "kv_util_sum",
    "moe_assignments", "moe_local_assignments", "moe_pad_rows",
    "moe_experts_hit",
    "dsa_indexed_tokens", "dsa_selected_rows", "dsa_rows_sparse",
    "dsa_rows_dense",
    "swa_window_tokens", "swa_full_tokens", "kv_window_released",
    "state_resets", "state_recomputed_tokens", "ssm_scan_tokens",
    "yoco_shared_tokens", "yoco_window_tokens",
    "sample_narrow_rows", "sample_full_rows",
    "net_requests", "net_dup_hits", "net_redelivered_tokens",
    "brownout_transitions",
    "journal_corrupt", "manifest_corrupt",
)


class WindowedRate:
    """Bounded sliding-window event counter — the SLO burn-rate
    primitive (docs/observability.md "Fleet observability").

    Cumulative counters answer "how many ever"; an SLO burn alert needs
    "how many in the last W seconds".  ``observe(ts)`` records one
    event; ``count(now)``/``rate(now)`` report the trailing window.
    Memory is bounded two ways: expired timestamps drop on every call,
    and the deque caps at ``max_events`` (saturation flags rather than
    grows — at that point the rate is "a lot", exactly what the alert
    needed to know)."""

    def __init__(self, window_s: float = 60.0, max_events: int = 65536):
        if window_s <= 0:
            raise ValueError(f"window_s must be > 0, got {window_s}")
        self.window_s = window_s
        self.max_events = max_events
        self._ts = deque(maxlen=max_events)
        self.total = 0

    def observe(self, ts: float, n: int = 1) -> None:
        self.total += n
        for _ in range(n):
            self._ts.append(ts)

    def _trim(self, now: float) -> None:
        lo = now - self.window_s
        while self._ts and self._ts[0] < lo:
            self._ts.popleft()

    def count(self, now: float) -> int:
        """Events inside ``[now - window_s, now]``."""
        self._trim(now)
        return len(self._ts)

    def rate(self, now: float) -> float:
        """Events per second over the trailing window."""
        return self.count(now) / self.window_s

    @property
    def saturated(self) -> bool:
        return len(self._ts) == self.max_events


@dataclass
class RequestMetrics:
    """Timestamps (engine clock) and derived latencies for one request."""

    arrival_time: float
    first_scheduled_time: Optional[float] = None
    first_token_time: Optional[float] = None
    finish_time: Optional[float] = None
    # recent token timestamps only (bounded: a long stream must not grow
    # host memory); times_dropped counts the forgotten prefix, so
    # n_tokens and index math stay exact
    token_times: list[float] = field(default_factory=list)
    times_dropped: int = 0
    # queue-time histogram guard: first_scheduled_time is first-write-
    # wins, so only the FIRST admission's wait may feed hist_queue (a
    # preempted request's re-admissions would re-observe the same value)
    queue_observed: bool = False
    n_preemptions: int = 0
    # prefix cache (docs/serving.md "Prefix caching"): prompt tokens
    # covered by shared cached blocks at this request's admission — a
    # warm request skips that much prefill compute, so its TTFT is the
    # number the cache exists to collapse
    cached_prefix_tokens: int = 0

    def on_scheduled(self, now: float) -> None:
        if self.first_scheduled_time is None:
            self.first_scheduled_time = now

    def on_token(self, now: float) -> Optional[float]:
        """Record one emission; returns the inter-token latency this
        token closes (``None`` for the first token) so the engine can
        feed the ITL histogram without re-deriving it."""
        itl = (now - self.token_times[-1]) if self.token_times else None
        if self.first_token_time is None:
            self.first_token_time = now
            itl = None
        self.token_times.append(now)
        extra = len(self.token_times) - TOKEN_TIMES_WINDOW
        if extra > 0:
            del self.token_times[:extra]
            self.times_dropped += extra
        return itl

    @property
    def n_tokens(self) -> int:
        return self.times_dropped + len(self.token_times)

    def seed_token_times(self, times: list, total: Optional[int] = None
                         ) -> None:
        """Restore-time seeding (serve/recovery.py): install journal/
        manifest timestamps under the same bounded-window invariants
        ``on_token`` maintains.  ``total`` is the true emission count
        when timestamps were lost (rotation/window pruning writes
        ``None`` pads) so ``n_tokens`` stays exact."""
        times = [t for t in times if t is not None]
        extra = len(times) - TOKEN_TIMES_WINDOW
        if extra > 0:
            del times[:extra]
        self.token_times = times
        n = total if total is not None else len(times)
        self.times_dropped = max(0, n - len(times))
        if times and self.first_token_time is None:
            self.first_token_time = times[0]

    def time_at(self, i: int) -> Optional[float]:
        """Timestamp of emission index ``i``, or ``None`` once the
        bounded window has dropped it (journal backfill/rotation use
        this instead of indexing the raw list — the window's base
        shifts)."""
        j = i - self.times_dropped
        if 0 <= j < len(self.token_times):
            return self.token_times[j]
        return None

    def burst_times(self, now: float, n: int, step_s: float) -> list[float]:
        """Timestamps for ``n`` tokens committed in ONE decode-horizon
        drain: spaced backwards from ``now`` by the DEVICE step cadence
        (``step_s`` = horizon wall time / device steps) instead of
        collapsing onto the drain instant.  Burst commits would otherwise
        read as ITL 0 inside a burst and a full horizon between bursts —
        the per-token latency a client streaming from the engine actually
        sees is the device's, and this reconstructs it."""
        return [now - i * step_s for i in range(n - 1, -1, -1)]

    @property
    def ttft(self) -> Optional[float]:
        """Time to first token (arrival → first emission)."""
        if self.first_token_time is None:
            return None
        return self.first_token_time - self.arrival_time

    @property
    def queue_time(self) -> Optional[float]:
        if self.first_scheduled_time is None:
            return None
        return self.first_scheduled_time - self.arrival_time

    @property
    def inter_token_latencies(self) -> list[float]:
        """Gaps within the RECENT window (full distributions live in the
        engine-level ITL histogram)."""
        t = self.token_times
        return [b - a for a, b in zip(t, t[1:])]

    @property
    def mean_itl(self) -> Optional[float]:
        itl = self.inter_token_latencies
        return sum(itl) / len(itl) if itl else None

    def to_dict(self) -> dict:
        return {
            "arrival_time": self.arrival_time,
            "ttft": self.ttft,
            "queue_time": self.queue_time,
            "mean_itl": self.mean_itl,
            "n_tokens": self.n_tokens,
            "n_preemptions": self.n_preemptions,
            "cached_prefix_tokens": self.cached_prefix_tokens,
            "finish_time": self.finish_time,
        }


@dataclass
class ServeMetrics:
    """Engine-level counters + streaming per-step gauges."""

    # counters
    steps: int = 0
    decode_steps: int = 0
    verify_rounds: int = 0
    prefill_tokens: int = 0
    # how often the wide prefill call engages (docs/serving.md "bucket
    # ladder"): ``prefill_chunk`` program calls, and the rows of those
    # calls that prefilled no new token (a residual's zero padding, rows
    # a call recomputed where its window slid back at the scratch's end)
    prefill_dispatches: int = 0
    prefill_pad_tokens: int = 0
    # rows those calls carried past the last layer that writes a cache or
    # a state, through the head: the rows of the logits that a prompt's
    # LAST call returned (the others skip what nobody reads) — ONE a
    # finished prefill, of a program that keeps the row the engine reads
    prefill_tail_rows: int = 0
    # launches a request costs on its way in: ``zero_scratch`` program
    # calls, ONE a cold admission (a warm one gathers its scratch instead)
    scratch_dispatches: int = 0
    prefill_width: int = 0        # rows of one call (stamped by the engine)
    preemptions: int = 0
    completed: int = 0
    # decode-loop dispatch accounting (docs/serving.md "Decode horizon"):
    # how many device dispatches and host sync points the decode path
    # paid per emitted token.  At horizon H=1 every token costs one
    # dispatch + one sync; the fused horizon amortizes both — the
    # dispatches_per_token quotient is THE metric the horizon exists to
    # shrink.
    # expert layers of a share of an expert-parallel deployment
    # (models/mla_moe.py): every (row, expert) choice the routers made
    # for the rows the programs computed, those whose expert is held
    # here, the pad rows of the grouped GEMM's live tiles, and the
    # (layer, step) x held-expert pairs that got any row.  Folded in at
    # commit from a small output of each program (observe_family).
    moe_assignments: int = 0
    moe_local_assignments: int = 0
    moe_pad_rows: int = 0
    moe_experts_hit: int = 0
    # learned sparse attention (models/mla_moe.py, a block with an
    # indexer): cached tokens the indexer scored and latent rows the
    # attention kept, over every query the programs computed (prefill
    # chunks included), and the decode queries whose context lay past /
    # at or under ``index_topk`` (the sparse read / every visible row).
    # Folded in with the expert counts (observe_family).
    dsa_indexed_tokens: int = 0
    dsa_selected_rows: int = 0
    dsa_rows_sparse: int = 0
    dsa_rows_dense: int = 0
    # window and global layers (docs/serving.md): cached tokens the decode
    # queries read, a layer, on window layers and on full ones (host
    # arithmetic at commit: min(context, window) and the context), and the
    # window-group pages given back while their requests ran
    swa_window_tokens: int = 0
    swa_full_tokens: int = 0
    kv_window_released: int = 0
    # the query heads of each attention group's layers, stamped by the
    # engine where the generator states them ({"full": 48, "window": 72}:
    # they may differ by kind over the same KV planes)
    swa_heads: dict = field(default_factory=dict)
    # a state beside pages (docs/serving.md "State beside pages"): slots
    # zeroed for a request's first chunk, tokens scanned again after a
    # preemption, prompt tokens through the chunk's scan; and, where ONE
    # cache is read by several layers, the cached tokens the decode queries
    # read through it (readers x context) and on window layers (host
    # arithmetic at commit, as the swa_* pair)
    state_resets: int = 0
    state_recomputed_tokens: int = 0
    ssm_scan_tokens: int = 0
    # what the state group holds, stamped by the engine: the kind of state
    # ("ssm": a selective scan's; "gdn": a gated delta rule's matrices),
    # the layers that hold one and the bytes of one request's slot
    state_kind: str = ""
    state_layers: int = 0
    state_bytes_per_request: int = 0
    yoco_shared_tokens: int = 0
    yoco_window_tokens: int = 0
    # the sampler inside the decode horizon (models/sampling.py): sampled
    # row-steps whose cut-offs were found among one pass's candidates,
    # and those that read the whole row (every one on a vocabulary under
    # the crossover; on a wide one a step's batch that fell back)
    sample_narrow_rows: int = 0
    sample_full_rows: int = 0
    decode_tokens: int = 0        # tokens committed by the decode loop
    dispatches: int = 0           # decode-path device dispatches
    host_syncs: int = 0           # decode-path host sync points
    # tokens chosen ON THE HOST (engine._choose_token: a logits row
    # brought over, argmax or one `sample_token` launch): one a completed
    # prefill on an engine whose decode runs horizon links, one a token
    # on a `horizon=1` engine
    host_choices: int = 0
    # failure-containment counters (docs/serving.md "Failure
    # containment"): every non-healthy retirement and every recovery
    # action is a counter, so overload and poison traffic are visible
    # in the same summary as latency.
    shed: int = 0                 # submit() rejections (queue at bound)
    deadline_expired: int = 0     # WAITING/PREFILL TTL sweeps
    quarantined: int = 0          # requests retired FinishReason.ERROR
    callback_errors: int = 0      # on_token raised; callback disabled
    forward_retries: int = 0      # batched-forward retry attempts
    forward_bisections: int = 0   # batch splits isolating a poison row
    watchdog_trips: int = 0       # step watchdog timeouts (re-raised)
    spec_bailouts: int = 0        # speculative rounds latched off
    # speculative-decoding counters (docs/serving.md "Speculative
    # decoding"): acceptance is the number that decides whether
    # speculation pays — proposed/accepted feed the overall and rolling
    # rates, chosen_k histograms the adaptive per-row depth, and
    # spec_tokens/spec_dispatches give tokens-per-dispatch for the fused
    # round alone (the ISSUE-7 guardrail: >= plain fused decode).
    spec_rounds: int = 0          # fused rounds that emitted something
    spec_proposed: int = 0        # draft tokens proposed (per-row budget)
    spec_accepted: int = 0        # proposals the target's stream matched
    spec_tokens: int = 0          # tokens committed by spec rounds
    spec_dispatches: int = 0      # fused spec-round dispatches
    spec_recent: list = field(default_factory=list, repr=False)
    spec_chosen_k: dict = field(default_factory=dict)
    draft_prefix_skipped_tokens: int = 0  # draft prefill skipped via the
    #                               draft-side page cache (warm admits)
    # retirements by FinishReason.value
    finish_reasons: dict = field(default_factory=dict)
    # per-SLO-class accounting (docs/serving.md "Overload, SLO classes
    # & autoscaling"): every counter keyed by slo_class so overload
    # response is auditable PER TIER — "best_effort shed, interactive
    # untouched" must be a number, not a claim.  Labeled dicts merge
    # by-key across the fleet (the finish_reasons pattern), per-class
    # TTFT histograms merge bucket-exactly by class (the program_hists
    # pattern).  All-default traffic lands every count under
    # "interactive", so the split costs nothing to read.
    class_submitted: dict = field(default_factory=dict)
    class_finished: dict = field(default_factory=dict)
    class_shed: dict = field(default_factory=dict)
    class_deadline: dict = field(default_factory=dict)
    class_preempted: dict = field(default_factory=dict)
    class_ttft: dict = field(default_factory=dict, repr=False)
    # graceful-degradation ladder (engine brownout): the rung the
    # engine currently sits on (0 = full service), its lifetime peak,
    # and how many rung transitions it has walked.  Rung gauges take
    # max across the fleet ("the worst brownout anywhere" is the
    # alertable fact); transitions is an additive MERGE_COUNTERS
    # member.
    brownout_rung_last: int = 0
    brownout_rung_peak: int = 0
    brownout_transitions: int = 0
    # crash-recovery counters (docs/serving.md "Crash recovery"):
    # snapshot latency + journal overhead on the serving side, restore
    # provenance on the resume side (how much state came back in place
    # vs through exact recompute).
    snapshots: int = 0            # engine.snapshot() captures
    snapshot_ms_last: float = 0.0
    snapshot_ms_total: float = 0.0
    journal_records: int = 0      # journal appends by this engine
    journal_bytes: int = 0
    journal_rotations: int = 0    # compactions at snapshot barriers
    restores: int = 0             # 1 on an engine built by restore()
    restored_in_place: int = 0    # requests resumed with live KV
    restored_requeued: int = 0    # requests re-queued for recompute
    restored_tokens: int = 0      # journal tokens carried across
    # live-migration counters (docs/serving.md "Fleet serving"): the
    # hand-off twins of the restore provenance fields — how many
    # requests left this engine mid-stream (drain) and how many arrived
    # (migrate_in, split by in-place KV adopt vs exact-recompute
    # requeue), plus the journal tokens that crossed with them.
    migrated_out: int = 0         # requests drained to a manifest
    migrated_in: int = 0          # manifest requests this engine adopted
    migrated_in_place: int = 0    # adopted WITH live KV (no recompute)
    migrated_tokens: int = 0      # journal tokens carried by migrations
    # disaggregated prefill->decode counters (serve/disagg.py,
    # docs/serving.md "Disaggregated serving"): per-request KV-page
    # PUSH hand-offs at prefill completion — distinct from the
    # migration counters above so tier hand-offs and failure-driven
    # moves stay separately alertable.
    pushed_out: int = 0           # requests pushed to a decode replica
    pushed_in: int = 0            # pushed requests this engine admitted
    # prefix-cache counters (docs/serving.md "Prefix caching"): engine-
    # side admission hits; the block-level gauges (refcounts, cache
    # tier, COW/eviction counts) live on the attached BlockManager and
    # merge into summary()["prefix_cache"] via attach_block_manager().
    prefix_hits: int = 0          # admissions mapping >= 1 shared block
    prefix_hit_tokens: int = 0    # prompt tokens covered by shared blocks
    prefix_skipped_tokens: int = 0  # prefill tokens actually skipped
    # network serving plane counters (serve/net.py, docs/serving.md
    # "Network fleet serving"): how often the wire asked, how often
    # idempotency made a retried call a no-op (duplicate submit, cached
    # drain/migrate replay), and how many tokens were SERVED again
    # because a stream poll re-read indices below the high-water mark
    # (an ack lost to the network re-delivers but never re-derives).
    net_requests: int = 0         # API calls the replica server answered
    net_dup_hits: int = 0         # idempotent no-op replays
    net_redelivered_tokens: int = 0  # tokens re-served below the watermark
    # state-integrity counters (serve/integrity.py, docs/serving.md
    # "Durability & integrity"): journal_corrupt counts salvage events
    # (interior damage quarantined, longest-valid prefix replayed);
    # manifest_corrupt counts wire manifests a RECEIVER rejected on a
    # digest mismatch (the sender re-queues through exact recompute —
    # corruption is never adopted, so either counter being nonzero is
    # an alert about the storage/transport substrate, not about
    # correctness).
    journal_corrupt: int = 0      # journal salvage (quarantine) events
    manifest_corrupt: int = 0     # wire manifests rejected on digest
    block_manager: object = field(default=None, repr=False)
    # compilation observability: CountingJit wrappers the engine
    # registers (runtime/jit_cache.py) + warmup accounting
    compiled_fns: list = field(default_factory=list, repr=False)
    warmup_time: float = 0.0
    warmup_compiles: int = 0
    # attention paths that will run as XLA instead of a Pallas kernel,
    # with the reason (engine.attention_kernel_gaps; stamped once at
    # engine construction)
    kernel_gaps: dict = field(default_factory=dict, repr=False)
    # blocking of the paged decode attention call (heads_per_step,
    # steps_per_call, ...: flash_decode.paged_kernel_blocking; stamped
    # once at engine construction, empty when the call runs as XLA)
    paged_attn_blocking: dict = field(default_factory=dict, repr=False)
    # how each program sums its held experts' products into its tokens
    # (program -> "walk" | "gather": mla_moe.combine_form; stamped once at
    # engine construction, empty for a family without expert layers)
    moe_combine: dict = field(default_factory=dict, repr=False)
    # a residual of several streams (``MlaMoeGenerator.stream_rows``:
    # streams, sub-layers, rows a program, the two mixes' blocking; stamped
    # once at engine construction, empty for one stream)
    hc: dict = field(default_factory=dict, repr=False)
    # per-step gauges as STREAMING aggregates (last / peak / running
    # sums) — never per-step lists, so a long-lived engine's metrics
    # stay O(1) regardless of how many steps it has served
    queue_depth_last: int = 0
    queue_depth_peak: int = 0
    running_last: int = 0
    running_sum: int = 0
    kv_util_last: float = 0.0
    kv_util_peak: float = 0.0
    kv_util_sum: float = 0.0
    # KV pool capacity gauges (docs/serving.md "Quantized serving"):
    # stamped once at construction by set_kv_capacity() — the resident
    # bytes the paged pools pin on device and the token slots they buy.
    # bytes/token is THE quotient int8 pools exist to shrink (scales
    # included: int8 pays Hkv*(D+4) per token-layer-plane vs fp32's
    # Hkv*D*4), and the capacity bench gates its ratio across dtypes.
    kv_pool_bytes: int = 0        # device bytes pinned by the KV pools
    kv_token_slots: int = 0       # num_blocks * page_size token capacity
    kv_quant: bool = False        # pools hold int8 pages + f32 scales
    kv_row: dict = field(default_factory=dict)  # latent pools: row widths
    # SLO latency histograms (serve/trace.LogHistogram): log-bucketed,
    # bounded, p50/p95/p99 in summary()["latency"] and the Prometheus
    # exposition.  TTFT/ITL/queue on the ENGINE clock; step/snapshot on
    # wall time (the engine clock may be fake under chaos tests).
    hist_ttft: LogHistogram = field(default_factory=LogHistogram,
                                    repr=False)
    hist_itl: LogHistogram = field(default_factory=LogHistogram,
                                   repr=False)
    hist_queue: LogHistogram = field(default_factory=LogHistogram,
                                     repr=False)
    hist_step: LogHistogram = field(default_factory=LogHistogram,
                                    repr=False)
    hist_snapshot: LogHistogram = field(default_factory=LogHistogram,
                                        repr=False)
    # per-program wall-time attribution (docs/observability.md "Kernel
    # observability"): one LogHistogram of per-call wall MILLISECONDS
    # per device program (paged_decode, decode_horizon[H=8], prefill
    # chunk, verify, spec rung, page scatter/gather/COW), fed by the
    # CountingJit/ShardedProgram ``timer`` hook the engine wires when
    # trace_level >= 1 — engine step time decomposes by program instead
    # of being one opaque hist_step.  ``program_timing`` is the master
    # gate (warmup pauses it so compile stalls never pollute p99).
    program_hists: dict = field(default_factory=dict, repr=False)
    program_timing: bool = False
    # step-span table (serve/trace.FlightRecorder.span): phase name ->
    # [calls, total ns, self ns].  An engine's metrics share the dict of
    # its recorder (attach_recorder); a fleet aggregate owns its own.
    phases: dict = field(default_factory=dict, repr=False)
    # flight recorder (serve/trace.FlightRecorder) the engine attaches
    # so the exposition can report ring occupancy
    recorder: object = field(default=None, repr=False)
    # retired requests' metrics, keyed by request id; pruned oldest-first
    # past requests_retain (None keeps everything — unit-test mode)
    requests: dict = field(default_factory=dict)
    requests_retain: Optional[int] = REQUESTS_RETAIN

    def observe_step(self, *, queue_depth: int, running: int,
                     kv_utilization: float) -> None:
        self.steps += 1
        self.queue_depth_last = queue_depth
        if queue_depth > self.queue_depth_peak:
            self.queue_depth_peak = queue_depth
        self.running_last = running
        self.running_sum += running
        self.kv_util_last = kv_utilization
        self.kv_util_sum += kv_utilization
        if kv_utilization > self.kv_util_peak:
            self.kv_util_peak = kv_utilization

    # -- KV pool capacity --------------------------------------------------

    def set_kv_capacity(self, *, pool_bytes: int, token_slots: int,
                        quantized: bool, row: dict | None = None) -> None:
        """Stamp the engine's KV pool geometry (the engine calls this at
        construction, right after allocating pools): resident device
        bytes across every pool leaf (int8 pages AND their f32 scales
        both count — the scales are real memory), the token slots those
        bytes buy (``num_blocks * page_size``), and whether the pools
        are quantized.  Feeds ``summary()["kv"]``, the
        ``serve_kv_pool_bytes`` / ``serve_kv_bytes_per_token`` gauges,
        and the CLI stats block."""
        self.kv_pool_bytes = int(pool_bytes)
        self.kv_token_slots = int(token_slots)
        self.kv_quant = bool(quantized)
        self.kv_row = dict(row or {})

    MOE_COUNTERS = ("moe_assignments", "moe_local_assignments",
                    "moe_pad_rows", "moe_experts_hit")

    DSA_COUNTERS = ("dsa_indexed_tokens", "dsa_selected_rows",
                    "dsa_rows_sparse", "dsa_rows_dense")
    FAMILY_COUNTERS = MOE_COUNTERS + DSA_COUNTERS   # a program's tally

    def observe_family(self, stats) -> None:
        """Add one or more programs' trailing counts: the expert layers'
        int[4] in the order of ``MOE_COUNTERS`` and, from a block with an
        indexer, ``DSA_COUNTERS``' four behind them."""
        for name, v in zip(self.FAMILY_COUNTERS, stats):
            setattr(self, name, getattr(self, name) + int(v))

    def observe_sampled(self, rows: int, whole) -> None:
        """One drained horizon link: ``rows`` sampled row-steps (host
        arithmetic over the link's live mask), ``whole`` of them served
        from the whole row — the program's count on a wide vocabulary,
        None on one under the crossover, where all are."""
        full = rows if whole is None else int(whole)
        self.sample_full_rows += full
        self.sample_narrow_rows += rows - full

    def sample_stats(self) -> dict:
        """summary()["sample"]: sampled row-steps of the decode horizon by
        where their cut-offs were found, and the candidates' share."""
        both = self.sample_narrow_rows + self.sample_full_rows
        return {"narrow_rows": self.sample_narrow_rows,
                "full_rows": self.sample_full_rows,
                "narrow_share": (self.sample_narrow_rows / both
                                 if both else 0.0)}

    def dsa_stats(self) -> dict:
        """summary()["dsa"]: the four counters and the share of the
        scored tokens that the attention read (``index_topk`` over the
        mean context, where contexts lie past it)."""
        out = {k[4:]: getattr(self, k) for k in self.DSA_COUNTERS}
        out["selected_share"] = (self.dsa_selected_rows
                                 / self.dsa_indexed_tokens
                                 if self.dsa_indexed_tokens else 0.0)
        return out

    def hc_stats(self) -> dict:
        """summary()["hc"]: the residual streams (0: the block has one),
        the sub-layers a row's mixes run in and how the two calls are
        blocked in each program."""
        return {"streams": self.hc.get("streams", 0),
                "sublayers": self.hc.get("sublayers", 0),
                "blocking": dict(self.hc.get("blocking", {}))}

    def swa_stats(self) -> dict:
        """summary()["swa"]: cached tokens the decode queries read on
        window layers and on full layers (each counted a layer), the
        window layers' share of both — what the window saves is the
        distance of that share from the layers' own — and the query heads
        of each kind's layers."""
        both = self.swa_window_tokens + self.swa_full_tokens
        return {"window_tokens": self.swa_window_tokens,
                "full_tokens": self.swa_full_tokens,
                "window_share": (self.swa_window_tokens / both
                                 if both else 0.0),
                "window_released_pages": self.kv_window_released,
                "heads": dict(self.swa_heads)}

    def state_group(self) -> dict:
        """The state group's entry of :meth:`kv_group_stats` ({} where the
        cache has none)."""
        return next((st for st in self.kv_group_stats().values()
                     if st.get("state")), {})

    def ssm_stats(self) -> dict:
        """summary()["ssm"]: the state slots in use and their peak, slots
        zeroed for a first chunk, tokens scanned again after a preemption,
        prompt tokens through the chunk's scan."""
        st = self.state_group()
        return {"state_slots_in_use": st.get("in_use", 0),
                "state_slots_peak": st.get("peak", 0),
                "state_resets": self.state_resets,
                "state_recomputed_tokens": self.state_recomputed_tokens,
                "scan_tokens": self.ssm_scan_tokens}

    def gdn_stats(self) -> dict:
        """summary()["gdn"]: a linear-attention state group — the bytes of
        one request's slot (every layer's matrix state and carried
        convolution inputs), the slots held and their peak, the
        ``gdn_chunk`` and ``gdn_step`` calls made (a call a linear layer a
        prefill chunk / a decode step) and the prompt tokens that went
        through the rule ({} where the state is of another kind)."""
        if self.state_kind != "gdn":
            return {}
        st = self.state_group()
        return {"state_bytes_per_request": self.state_bytes_per_request,
                "state_slots_in_use": st.get("in_use", 0),
                "state_slots_peak": st.get("peak", 0),
                "chunk_calls": self.prefill_dispatches * self.state_layers,
                "step_calls": self.decode_steps * self.state_layers,
                "rule_tokens": self.ssm_scan_tokens,
                "state_resets": self.state_resets,
                "state_recomputed_tokens": self.state_recomputed_tokens}

    def yoco_stats(self) -> dict:
        """summary()["yoco"]: cached tokens the decode queries read
        through the ONE shared cache (each reader layer counted) and on
        window layers, and the shared cache's share of both."""
        both = self.yoco_shared_tokens + self.yoco_window_tokens
        return {"shared_tokens": self.yoco_shared_tokens,
                "window_tokens": self.yoco_window_tokens,
                "shared_share": (self.yoco_shared_tokens / both
                                 if both else 0.0)}

    def moe_stats(self) -> dict:
        """summary()["moe"]: the four counters and the share of routed
        assignments that landed on the experts held here (1 / chips that
        share a layer, under even routing) — and, with expert layers,
        ``combine``: the form each program's routed sum took."""
        out = {k[4:]: getattr(self, k) for k in self.MOE_COUNTERS}
        out["local_share"] = (self.moe_local_assignments
                              / self.moe_assignments
                              if self.moe_assignments else 0.0)
        if self.moe_combine:
            out["combine"] = dict(self.moe_combine)
        return out

    def kv_stats(self) -> dict:
        """KV pool capacity (summary()["kv"]): pool bytes, token slots,
        and bytes/token — the memory-economics view the int8 pools
        exist to move (docs/serving.md "Quantized serving")."""
        groups = self.kv_group_stats()
        return {
            "pool_bytes": self.kv_pool_bytes,
            "token_slots": self.kv_token_slots,
            "bytes_per_token": (self.kv_pool_bytes / self.kv_token_slots
                                if self.kv_token_slots else 0.0),
            "quantized": self.kv_quant,
            # a latent (MLA) pool: the numbers a token's row holds a
            # layer, and the width it is stored at (whole lane tiles)
            **self.kv_row,
            # window and global layers: blocks in use and their peak, by
            # group (one allocator a group; absent with one group)
            **({"groups": groups} if groups else {}),
        }

    def kv_group_stats(self) -> dict:
        """Blocks in use by cache group ({} with one group, or with no
        block manager attached)."""
        bm = self.block_manager
        return bm.group_stats() if bm is not None else {}

    # -- per-program wall-time attribution --------------------------------

    def program_hist(self, name: str) -> LogHistogram:
        """Get-or-create the per-call wall-time histogram (milliseconds)
        for device program ``name`` — every engine shares one bucket
        scheme so :meth:`merge` and ``merge_scrapes`` stay bucket-exact
        across the fleet."""
        h = self.program_hists.get(name)
        if h is None:
            h = self.program_hists[name] = LogHistogram()
        return h

    def observe_program(self, name: str, ms: float) -> None:
        """One program call's wall time (the CountingJit/ShardedProgram
        ``timer`` hook target).  No-op while ``program_timing`` is off —
        the trace_level gate and warmup's pause both flip this flag, so
        the hot path stays one attribute check when attribution is
        disabled and compile stalls never land in the distributions."""
        if not self.program_timing:
            return
        self.program_hist(name).observe(ms)

    def program_stats(self) -> dict:
        """``summary()["programs"]``: per-program p50/p95/p99/mean/count
        of the call's wall milliseconds — on an accelerator the time to
        HAND THE PROGRAM OVER (``CountingJit``), the same reading as the
        ``dispatch.<program>`` step span."""
        return {name: self.program_hists[name].stats()
                for name in sorted(self.program_hists)}

    def phase_stats(self) -> dict:
        """``summary()["phases"]``: the step spans (``trace.STEP_PHASES``)
        as ``{phase: {"calls", "total_s", "self_s"}}``.  ``self_s`` is a
        span's time less its child spans', so the self times of every
        phase but ``submit`` (which runs outside a step) sum to
        ``step``'s total: where the host's share of a step goes."""
        return {k: {"calls": c, "total_s": t * 1e-9, "self_s": s * 1e-9}
                for k, (c, t, s) in sorted(self.phases.items())}

    def observe_finish(self, request_id: str, rm: RequestMetrics,
                       reason=None, slo_class: str = "interactive"
                       ) -> None:
        self.completed += 1
        self.requests[request_id] = rm
        if self.requests_retain is not None:
            # dict preserves insertion order: drop the oldest retirement
            # (O(overflow) per finish — never materialize the whole map)
            while len(self.requests) > self.requests_retain:
                del self.requests[next(iter(self.requests))]
        self._bump(self.class_finished, slo_class)
        if reason is not None:
            key = getattr(reason, "value", str(reason))
            self.finish_reasons[key] = self.finish_reasons.get(key, 0) + 1
            if key == "shed":
                self._bump(self.class_shed, slo_class)
            elif key == "deadline":
                self._bump(self.class_deadline, slo_class)

    # -- per-SLO-class accounting ------------------------------------------

    @staticmethod
    def _bump(d: dict, key: str, n: int = 1) -> None:
        d[key] = d.get(key, 0) + n

    def observe_class_submit(self, slo_class: str) -> None:
        """One request accepted into the engine queue, by class."""
        self._bump(self.class_submitted, slo_class)

    def observe_class_preempt(self, slo_class: str) -> None:
        """One preemption eviction, by the victim's class — with the
        class-aware scheduler on, this is the proof best-effort absorbs
        the pressure before interactive does."""
        self._bump(self.class_preempted, slo_class)

    def class_ttft_hist(self, slo_class: str) -> LogHistogram:
        """Get-or-create the per-class TTFT histogram — one bucket
        scheme across classes and engines, so fleet merge stays
        bucket-exact (the ``program_hists`` pattern)."""
        h = self.class_ttft.get(slo_class)
        if h is None:
            h = self.class_ttft[slo_class] = LogHistogram()
        return h

    def observe_brownout(self, rung: int) -> None:
        """One brownout-ladder transition (engine `_brownout_step`):
        the new rung becomes the gauge, every transition counts."""
        self.brownout_transitions += 1
        self.brownout_rung_last = rung
        if rung > self.brownout_rung_peak:
            self.brownout_rung_peak = rung

    def slo_stats(self) -> dict:
        """Per-class overload accounting (summary()["slo"]): submitted/
        finished/shed/deadline/preempted by class, per-class TTFT
        percentiles, and the brownout rung — the per-tier view the SLO
        classes exist to provide."""
        return {
            "submitted": dict(sorted(self.class_submitted.items())),
            "finished": dict(sorted(self.class_finished.items())),
            "shed": dict(sorted(self.class_shed.items())),
            "deadline_expired": dict(sorted(self.class_deadline.items())),
            "preempted": dict(sorted(self.class_preempted.items())),
            "ttft": {c: self.class_ttft[c].stats()
                     for c in sorted(self.class_ttft)},
            "brownout_rung": self.brownout_rung_last,
            "brownout_rung_peak": self.brownout_rung_peak,
            "brownout_transitions": self.brownout_transitions,
        }

    def failure_stats(self) -> dict:
        """The containment counters as one dict (summary()["failures"])."""
        return {
            "shed": self.shed,
            "deadline_expired": self.deadline_expired,
            "quarantined": self.quarantined,
            "callback_errors": self.callback_errors,
            "forward_retries": self.forward_retries,
            "forward_bisections": self.forward_bisections,
            "watchdog_trips": self.watchdog_trips,
            "spec_bailouts": self.spec_bailouts,
            "finish_reasons": dict(self.finish_reasons),
        }

    def observe_spec_row(self, proposed: int, accepted: int,
                         chosen_k: int) -> None:
        """One row's share of one fused speculative round (the engine
        calls this at each round's drain)."""
        self.spec_proposed += proposed
        self.spec_accepted += accepted
        self.spec_recent.append((proposed, accepted))
        del self.spec_recent[:-64]
        self.spec_chosen_k[chosen_k] = \
            self.spec_chosen_k.get(chosen_k, 0) + 1

    def spec_stats(self) -> dict:
        """Speculative-decoding observability (summary()["spec"]):
        per-round proposed/accepted counters, the overall and ROLLING
        (last 64 row-rounds) acceptance rates, the chosen-k histogram
        the adaptive policy produced, and spec tokens-per-dispatch —
        the economics field the fused round exists to move."""
        rp = sum(p for p, _ in self.spec_recent)
        ra = sum(a for _, a in self.spec_recent)
        return {
            "rounds": self.spec_rounds,
            "proposed": self.spec_proposed,
            "accepted": self.spec_accepted,
            "accept_rate": (self.spec_accepted / self.spec_proposed
                            if self.spec_proposed else 0.0),
            "rolling_accept_rate": (ra / rp if rp else 0.0),
            "chosen_k": dict(sorted(self.spec_chosen_k.items())),
            "spec_tokens": self.spec_tokens,
            "spec_dispatches": self.spec_dispatches,
            "spec_tokens_per_dispatch": (
                self.spec_tokens / self.spec_dispatches
                if self.spec_dispatches else 0.0),
            "bailouts": self.spec_bailouts,
            "draft_prefix_skipped_tokens": self.draft_prefix_skipped_tokens,
        }

    def recovery_stats(self) -> dict:
        """Snapshot/journal/restore counters (summary()["recovery"])."""
        return {
            "snapshots": self.snapshots,
            "snapshot_ms_last": self.snapshot_ms_last,
            "snapshot_ms_total": self.snapshot_ms_total,
            "journal_records": self.journal_records,
            "journal_bytes": self.journal_bytes,
            "journal_rotations": self.journal_rotations,
            "restores": self.restores,
            "restored_in_place": self.restored_in_place,
            "restored_requeued": self.restored_requeued,
            "restored_tokens": self.restored_tokens,
            "journal_corrupt": self.journal_corrupt,
        }

    def migration_stats(self) -> dict:
        """Live-migration provenance (summary()["migration"]) — the
        fleet hand-off counters (docs/serving.md "Fleet serving")."""
        return {
            "migrated_out": self.migrated_out,
            "migrated_in": self.migrated_in,
            "migrated_in_place": self.migrated_in_place,
            "migrated_tokens": self.migrated_tokens,
            "pushed_out": self.pushed_out,
            "pushed_in": self.pushed_in,
        }

    def net_stats(self) -> dict:
        """Network serving plane counters (summary()["net"]) — the wire
        side of docs/serving.md "Network fleet serving"."""
        return {
            "net_requests": self.net_requests,
            "net_dup_hits": self.net_dup_hits,
            "net_redelivered_tokens": self.net_redelivered_tokens,
            "manifest_corrupt": self.manifest_corrupt,
        }

    def merge(self, other: "ServeMetrics") -> "ServeMetrics":
        """Fold another engine's metrics into this one — the fleet
        aggregation primitive (serve/fleet.py,
        ``FleetController.aggregate_metrics``).  Counters add
        (:data:`MERGE_COUNTERS` — the exposition's additive series),
        the SLO histograms merge bucket-EXACTLY
        (:meth:`serve.trace.LogHistogram.merge`: identical schemes add
        count-wise, so fleet p50/p95/p99 equal percentiles over the
        pooled per-replica samples), finish-reason tallies add, and
        gauges take sum-of-last / max-of-peak.  Per-request detail
        (``requests``), compiled-program registries, and recorder
        attachments stay local — they name objects, not quantities."""
        for name in MERGE_COUNTERS:
            setattr(self, name, getattr(self, name)
                    + getattr(other, name))
        self.snapshot_ms_last = max(self.snapshot_ms_last,
                                    other.snapshot_ms_last)
        self.queue_depth_last += other.queue_depth_last
        self.queue_depth_peak = max(self.queue_depth_peak,
                                    other.queue_depth_peak)
        self.running_last += other.running_last
        self.kv_util_last = max(self.kv_util_last, other.kv_util_last)
        self.kv_util_peak = max(self.kv_util_peak, other.kv_util_peak)
        # KV capacity sums replica-wise (the fleet's pooled bytes and
        # slots; bytes/token re-derives from the sums, so a mixed
        # int8/fp fleet reports its true blended quotient); kv_quant
        # ORs — "any replica quantized" is the alertable fact
        self.kv_pool_bytes += other.kv_pool_bytes
        self.kv_token_slots += other.kv_token_slots
        self.kv_quant = self.kv_quant or other.kv_quant
        self.prefill_width = max(self.prefill_width, other.prefill_width)
        for reason, n in other.finish_reasons.items():
            self.finish_reasons[reason] = \
                self.finish_reasons.get(reason, 0) + n
        # per-class tallies merge by key (the finish_reasons pattern);
        # brownout rung gauges take max — "the worst rung anywhere"
        for mine, theirs in (
                (self.class_submitted, other.class_submitted),
                (self.class_finished, other.class_finished),
                (self.class_shed, other.class_shed),
                (self.class_deadline, other.class_deadline),
                (self.class_preempted, other.class_preempted)):
            for cls, n in theirs.items():
                mine[cls] = mine.get(cls, 0) + n
        for cls, theirs in other.class_ttft.items():
            self.class_ttft_hist(cls).merge(theirs)
        self.brownout_rung_last = max(self.brownout_rung_last,
                                      other.brownout_rung_last)
        self.brownout_rung_peak = max(self.brownout_rung_peak,
                                      other.brownout_rung_peak)
        for mine, theirs in ((self.hist_ttft, other.hist_ttft),
                             (self.hist_itl, other.hist_itl),
                             (self.hist_queue, other.hist_queue),
                             (self.hist_step, other.hist_step),
                             (self.hist_snapshot, other.hist_snapshot)):
            mine.merge(theirs)
        # per-program wall-time histograms merge bucket-exactly by name
        # (a program only one replica ran still joins the aggregate)
        for name, theirs in other.program_hists.items():
            self.program_hist(name).merge(theirs)
        for name, theirs in other.phases.items():
            mine = self.phases.setdefault(name, [0, 0, 0])
            for i, v in enumerate(theirs):
                mine[i] += v
        return self

    def attach_block_manager(self, bm) -> None:
        """Fold the block manager's prefix-cache gauges into
        :meth:`summary` (the engine calls this at construction)."""
        self.block_manager = bm

    def attach_recorder(self, recorder) -> None:
        """Track the engine's flight recorder so the exposition reports
        ring occupancy/drops alongside the counters, and its step-span
        table (one dict, shared)."""
        self.recorder = recorder
        self.phases = recorder.phases

    def prefix_stats(self) -> dict:
        """Admission-level hit counters + block-level cache gauges +
        the warm/cold TTFT split (summary()["prefix_cache"]).  A warm
        request is one whose admission mapped >= 1 shared block;
        ``ttft_warm_over_cold`` is the ratio the cache exists to
        collapse (the bench gate holds it <= 0.35 for a shared-prompt
        workload)."""
        warm = [m.ttft for m in self.requests.values()
                if m.cached_prefix_tokens > 0 and m.ttft is not None]
        cold = [m.ttft for m in self.requests.values()
                if m.cached_prefix_tokens == 0 and m.ttft is not None]
        out = {
            "prefix_hits": self.prefix_hits,
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "prefix_skipped_tokens": self.prefix_skipped_tokens,
            "warm_requests": len(warm),
            "cold_requests": len(cold),
            "mean_ttft_warm": sum(warm) / len(warm) if warm else None,
            "mean_ttft_cold": sum(cold) / len(cold) if cold else None,
            "ttft_warm_over_cold": (
                (sum(warm) / len(warm)) / (sum(cold) / len(cold))
                if warm and cold and sum(cold) > 0 else None),
        }
        if self.block_manager is not None:
            out.update(self.block_manager.prefix_stats())
        return out

    def decode_stats(self) -> dict:
        """The decode-loop dispatch economics (summary()["decode"]).
        ``dispatches_per_token`` is ~1/batch for per-token decode (one
        dispatch per STEP emits a token per active row) and ~1/(batch·H)
        on a steady fused-horizon batch — the horizon amortizes steps,
        the batch amortizes rows, and only the former is the decode
        horizon's doing; ``host_syncs`` counts the blocking device→host
        fetches the loop paid, ``host_choices`` the tokens the host chose
        from a logits row (first tokens after a prefill, and every token
        of the per-token ``_decode_rows`` path)."""
        return {
            "decode_steps": self.decode_steps,
            "decode_tokens": self.decode_tokens,
            "dispatches": self.dispatches,
            "host_syncs": self.host_syncs,
            "host_choices": self.host_choices,
            "tokens_per_dispatch": (self.decode_tokens / self.dispatches
                                    if self.dispatches else 0.0),
            "dispatches_per_token": (self.dispatches / self.decode_tokens
                                     if self.decode_tokens else 0.0),
        }

    def prefill_stats(self) -> dict:
        """How full the prefill program's calls run (summary()["prefill"]):
        every call is ``width`` rows whatever it was given, so
        ``tokens_per_dispatch`` against ``width`` is what the weights'
        read was shared over and ``pad_share`` the rows that bought
        nothing."""
        rows = self.prefill_tokens + self.prefill_pad_tokens
        return {
            "tokens": self.prefill_tokens,
            "dispatches": self.prefill_dispatches,
            "tokens_per_dispatch": (
                self.prefill_tokens / self.prefill_dispatches
                if self.prefill_dispatches else 0.0),
            "pad_share": self.prefill_pad_tokens / rows if rows else 0.0,
            "tail_rows": self.prefill_tail_rows,
            "width": self.prefill_width,
            "scratch_dispatches": self.scratch_dispatches,
        }

    def latency_stats(self) -> dict:
        """The SLO histograms' percentile view (summary()["latency"]):
        p50/p95/p99 + mean + count for TTFT, ITL, queue wait, step wall
        time, and snapshot capture time — the bounded replacement for
        per-request latency lists (docs/observability.md)."""
        return {
            "ttft": self.hist_ttft.stats(),
            "itl": self.hist_itl.stats(),
            "queue": self.hist_queue.stats(),
            "step": self.hist_step.stats(),
            "snapshot": self.hist_snapshot.stats(),
        }

    def light_summary(self) -> dict:
        """Just the fields :func:`format_statline` reads — O(1) scalars
        and histogram scans, never the per-request map that the full
        :meth:`summary` materializes (up to ``requests_retain`` dicts).
        The ``--stats-every`` periodic line and every ``flight_flush``
        use this, so per-step logging and the quarantine path stay
        cheap."""
        return {
            "steps": self.steps,
            "completed": self.completed,
            "max_queue_depth": self.queue_depth_peak,
            "peak_kv_utilization": self.kv_util_peak,
            "decode": self.decode_stats(),
            "latency": self.latency_stats(),
            "programs": self.program_stats(),
        }

    # -- compilation observability ---------------------------------------

    def register_compiled(self, counter) -> None:
        """Track a ``jit_cache.CountingJit``-wrapped program; its
        hit/miss/compile-time counters appear in :meth:`summary` under
        ``compilation`` (and on the ``TDT_DUMP_IR`` dump path).  With
        ``program_timing`` armed the wrapper's ``timer`` hook is wired
        here too, so every registered program feeds its per-call wall
        time into :meth:`observe_program`, and its ``tracer`` to the
        attached recorder, which books the same reading as the step span
        ``dispatch.<program>`` (docs/observability.md "Step spans")."""
        self.compiled_fns.append(counter)
        if (self.program_timing
                and getattr(counter, "timer", None) is None):
            counter.timer = self.observe_program
            counter.tracer = self.recorder

    @property
    def compile_misses(self) -> int:
        """Total trace-cache misses (compiles) across engine programs —
        the bounded-compilation tests watch this stay flat after
        ``engine.warmup()``."""
        return sum(c.misses for c in self.compiled_fns)

    def compile_stats(self) -> dict:
        """Per-program trace-cache counters + the process-wide
        ``cached_shard_jit`` memo stats (runtime/jit_cache.py)."""
        from triton_dist_tpu.runtime import jit_cache

        return {
            "programs": {c.name: c.stats() for c in self.compiled_fns},
            "total_misses": self.compile_misses,
            "total_hits": sum(c.hits for c in self.compiled_fns),
            "total_compile_time_s": sum(c.compile_time
                                        for c in self.compiled_fns),
            "warmup_time_s": self.warmup_time,
            "warmup_compiles": self.warmup_compiles,
            "cached_shard_jit": jit_cache.cache_stats(),
        }

    def summary(self) -> dict:
        """Aggregate view (what the CLI prints and maybe_dump writes)."""
        # TTFT/ITL means from the engine-level histograms (exact
        # sum/count over EVERY request ever served — the requests map
        # prunes past requests_retain, so deriving from it would
        # silently turn into a recent-window mean on long-lived
        # engines); the per-request fallbacks serve metrics objects fed
        # outside an engine (unit tests, hand-built summaries).
        if self.hist_ttft.count:
            mean_ttft = self.hist_ttft.mean
            max_ttft = self.hist_ttft.max
        else:
            ttfts = [m.ttft for m in self.requests.values()
                     if m.ttft is not None]
            mean_ttft = sum(ttfts) / len(ttfts) if ttfts else None
            max_ttft = max(ttfts, default=None) if ttfts else None
        if self.hist_itl.count:
            mean_itl = self.hist_itl.mean
        else:
            itls = [x for m in self.requests.values()
                    for x in m.inter_token_latencies]
            mean_itl = sum(itls) / len(itls) if itls else None
        return {
            "steps": self.steps,
            "decode_steps": self.decode_steps,
            "verify_rounds": self.verify_rounds,
            "prefill_tokens": self.prefill_tokens,
            "preemptions": self.preemptions,
            "completed": self.completed,
            "max_queue_depth": self.queue_depth_peak,
            "mean_running": (self.running_sum / self.steps
                             if self.steps else 0.0),
            "peak_kv_utilization": self.kv_util_peak,
            "mean_kv_utilization": (self.kv_util_sum / self.steps
                                    if self.steps else 0.0),
            "mean_ttft": mean_ttft,
            "max_ttft": max_ttft,
            "mean_itl": mean_itl,
            "latency": self.latency_stats(),
            "programs": self.program_stats(),
            "phases": self.phase_stats(),
            "decode": self.decode_stats(),
            "prefill": self.prefill_stats(),
            "kv": self.kv_stats(),
            "moe": self.moe_stats(),
            "dsa": self.dsa_stats(),
            "swa": self.swa_stats(),
            "ssm": self.ssm_stats(),
            "gdn": self.gdn_stats(),
            "yoco": self.yoco_stats(),
            "sample": self.sample_stats(),
            "spec": self.spec_stats(),
            "slo": self.slo_stats(),
            "failures": self.failure_stats(),
            "recovery": self.recovery_stats(),
            "migration": self.migration_stats(),
            "net": self.net_stats(),
            "prefix_cache": self.prefix_stats(),
            "compilation": self.compile_stats(),
            "kernel_gaps": dict(self.kernel_gaps),
            "paged_attn_blocking": dict(self.paged_attn_blocking),
            "hc": self.hc_stats(),
            "requests": {rid: m.to_dict()
                         for rid, m in self.requests.items()},
        }

    # -- Prometheus text exposition ---------------------------------------

    def to_prometheus(self) -> str:
        """The engine's live state in the Prometheus text format
        (version 0.0.4) — served by ``serve.trace.start_metrics_server``
        behind ``examples/serve.py --metrics-port``.  Metric names are
        documented in docs/observability.md; counters end ``_total``,
        histograms expose cumulative ``_bucket{le=}`` + ``_sum`` +
        ``_count``."""
        L: list[str] = []

        def counter(name, v, help_=None):
            if help_:
                L.append(f"# HELP {name} {help_}")
            L.append(f"# TYPE {name} counter")
            L.append(f"{name} {v}")

        def gauge(name, v, help_=None):
            if help_:
                L.append(f"# HELP {name} {help_}")
            L.append(f"# TYPE {name} gauge")
            L.append(f"{name} {v}")

        counter("serve_steps_total", self.steps,
                "engine scheduler iterations")
        counter("serve_decode_steps_total", self.decode_steps)
        counter("serve_decode_tokens_total", self.decode_tokens)
        counter("serve_prefill_tokens_total", self.prefill_tokens)
        counter("serve_prefill_dispatches_total", self.prefill_dispatches,
                "prefill_chunk program calls")
        counter("serve_prefill_pad_tokens_total", self.prefill_pad_tokens,
                "rows of those calls that prefilled no new token")
        counter("serve_prefill_tail_rows_total", self.prefill_tail_rows,
                "rows a prompt's last call carried through the head")
        counter("serve_prefill_scratch_dispatches_total",
                self.scratch_dispatches,
                "zero_scratch program calls: one a cold admission")
        counter("serve_dispatches_total", self.dispatches,
                "decode-path device dispatches")
        counter("serve_host_syncs_total", self.host_syncs)
        counter("serve_host_choices_total", self.host_choices,
                "tokens chosen on the host from a logits row")
        counter("serve_completed_total", self.completed,
                "requests retired (any reason)")
        counter("serve_preemptions_total", self.preemptions)
        for name in self.FAMILY_COUNTERS:
            counter(f"serve_{name}_total", getattr(self, name))
        counter("serve_swa_window_tokens_total", self.swa_window_tokens,
                "cached tokens decode queries read on window layers")
        counter("serve_swa_full_tokens_total", self.swa_full_tokens,
                "cached tokens decode queries read on full layers")
        counter("serve_kv_window_released_total", self.kv_window_released,
                "window-group pages given back while requests ran")
        counter("serve_state_resets_total", self.state_resets,
                "state slots zeroed for a request's first chunk")
        counter("serve_state_recomputed_tokens_total",
                self.state_recomputed_tokens,
                "tokens scanned again after a preemption")
        counter("serve_ssm_scan_tokens_total", self.ssm_scan_tokens,
                "prompt tokens through the chunk's selective scan")
        counter("serve_yoco_shared_tokens_total", self.yoco_shared_tokens,
                "cached tokens decode queries read through the shared cache")
        counter("serve_yoco_window_tokens_total", self.yoco_window_tokens,
                "cached tokens decode queries read on window layers "
                "beside a shared cache")
        L.append("# HELP serve_sample_rows_total sampled row-steps of the "
                 "decode horizon by where their cut-offs were found")
        L.append("# TYPE serve_sample_rows_total counter")
        for path, n in (("narrow", self.sample_narrow_rows),
                        ("full", self.sample_full_rows)):
            L.append(f'serve_sample_rows_total{{path="{path}"}} {n}')
        counter("serve_shed_total", self.shed)
        counter("serve_deadline_expired_total", self.deadline_expired)
        counter("serve_quarantined_total", self.quarantined)
        counter("serve_callback_errors_total", self.callback_errors)
        counter("serve_forward_retries_total", self.forward_retries)
        counter("serve_forward_bisections_total", self.forward_bisections)
        counter("serve_watchdog_trips_total", self.watchdog_trips)
        counter("serve_spec_bailouts_total", self.spec_bailouts)
        counter("serve_spec_proposed_total", self.spec_proposed)
        counter("serve_spec_accepted_total", self.spec_accepted)
        counter("serve_snapshots_total", self.snapshots)
        counter("serve_journal_records_total", self.journal_records)
        counter("serve_journal_rotations_total", self.journal_rotations)
        counter("serve_migrated_out_total", self.migrated_out,
                "requests drained to a migration manifest")
        counter("serve_migrated_in_total", self.migrated_in,
                "manifest requests adopted from another replica")
        counter("serve_pushed_out_total", self.pushed_out,
                "requests pushed to a decode replica at prefill end")
        counter("serve_pushed_in_total", self.pushed_in,
                "pushed requests admitted from a prefill replica")
        counter("serve_prefix_hits_total", self.prefix_hits)
        counter("serve_prefix_skipped_tokens_total",
                self.prefix_skipped_tokens)
        counter("serve_net_requests_total", self.net_requests,
                "network serving-plane API calls answered")
        counter("serve_net_dup_hits_total", self.net_dup_hits,
                "idempotent no-op replays (duplicate submit, cached "
                "drain/migrate response)")
        counter("serve_net_redelivered_tokens_total",
                self.net_redelivered_tokens,
                "tokens re-served below a stream's high-water mark")
        counter("serve_journal_corrupt_total", self.journal_corrupt,
                "journal salvage events (interior corruption "
                "quarantined, longest-valid prefix replayed)")
        counter("serve_manifest_corrupt_total", self.manifest_corrupt,
                "wire manifests rejected on a digest mismatch "
                "(sender re-queues through exact recompute)")
        L.append("# TYPE serve_finished_total counter")
        for reason, n in sorted(self.finish_reasons.items()):
            L.append(f'serve_finished_total{{reason="{reason}"}} {n}')
        # per-SLO-class series: labeled counter families (one TYPE
        # header each) + the per-class TTFT histogram family
        for name, d in (("serve_class_submitted_total",
                         self.class_submitted),
                        ("serve_class_finished_total",
                         self.class_finished),
                        ("serve_class_shed_total", self.class_shed),
                        ("serve_class_deadline_expired_total",
                         self.class_deadline),
                        ("serve_class_preempted_total",
                         self.class_preempted)):
            L.append(f"# TYPE {name} counter")
            for cls, n in sorted(d.items()):
                L.append(f'{name}{{slo_class="{cls}"}} {n}')
        for i, cls in enumerate(sorted(self.class_ttft)):
            L.extend(self.class_ttft[cls].prom_lines(
                "serve_class_ttft_seconds", labels=f'slo_class="{cls}"',
                typed=i == 0))
        counter("serve_brownout_transitions_total",
                self.brownout_transitions,
                "graceful-degradation ladder rung transitions")
        gauge("serve_brownout_rung", self.brownout_rung_last,
              "current brownout rung (0 = full service)")
        gauge("serve_queue_depth", self.queue_depth_last,
              "waiting requests at the last engine step")
        gauge("serve_running", self.running_last)
        gauge("serve_kv_utilization", round(self.kv_util_last, 6))
        stats = self.kv_group_stats()
        if stats:
            L.append("# TYPE serve_kv_group_blocks_in_use gauge")
            for g, st in stats.items():
                L.append(f'serve_kv_group_blocks_in_use{{group="{g}"}} '
                         f'{st["in_use"]}')
            L.append("# TYPE serve_kv_group_blocks_peak gauge")
            for g, st in stats.items():
                L.append(f'serve_kv_group_blocks_peak{{group="{g}"}} '
                         f'{st["peak"]}')
        slots = self.state_group()
        if slots:
            gauge("serve_state_slots_in_use", slots["in_use"],
                  "state slots held by running requests")
            gauge("serve_state_slots_peak", slots["peak"])
        gauge("serve_kv_pool_bytes", self.kv_pool_bytes,
              "device bytes pinned by the paged KV pools "
              "(int8 pages + f32 scales both count)")
        gauge("serve_kv_token_slots", self.kv_token_slots,
              "token capacity of the pools (num_blocks * page_size)")
        gauge("serve_kv_bytes_per_token",
              round(self.kv_pool_bytes / self.kv_token_slots, 6)
              if self.kv_token_slots else 0.0,
              "KV pool bytes per token slot — the quotient int8 "
              "pools shrink")
        gauge("serve_journal_bytes", self.journal_bytes)
        gauge("serve_compile_misses", self.compile_misses)
        gauge("serve_paged_attn_heads_per_step",
              self.paged_attn_blocking.get("heads_per_step", 0),
              "KV heads of a page one step of the paged decode "
              "attention kernel carries (0: the call runs as XLA)")
        gauge("serve_paged_attn_steps_per_call",
              self.paged_attn_blocking.get("steps_per_call", 0),
              "grid steps of one paged decode attention call; each "
              "walks only its row's live pages")
        gauge("serve_paged_attn_pages_in_flight",
              self.paged_attn_blocking.get("pages_in_flight", 0),
              "slots of the paged decode attention kernel's page ring: "
              "the page being multiplied and the copies ahead of it")
        gauge("serve_hc_streams", self.hc.get("streams", 0),
              "residual streams a token carries (0: the block has one)")
        if self.recorder is not None:
            counter("serve_trace_events_total", self.recorder.emitted,
                    "flight-recorder events emitted")
            gauge("serve_trace_dropped", self.recorder.dropped,
                  "events the bounded ring has forgotten")
        for name, hist in (("serve_ttft_seconds", self.hist_ttft),
                           ("serve_itl_seconds", self.hist_itl),
                           ("serve_queue_time_seconds", self.hist_queue),
                           ("serve_step_time_seconds", self.hist_step),
                           ("serve_snapshot_seconds",
                            self.hist_snapshot)):
            L.extend(hist.prom_lines(name))
        # per-program wall-time attribution: ONE labeled histogram
        # family (dense buckets like the SLO histograms, so fleet
        # scrape-and-merge stays bucket-exact per program); the TYPE
        # header rides the first member only
        for i, name in enumerate(sorted(self.program_hists)):
            L.extend(self.program_hists[name].prom_lines(
                "serve_program_ms", labels=f'program="{name}"',
                typed=i == 0))
        # step spans: two labeled counter families (seconds are the
        # span's SELF time: summed over the phases they give the step
        # total, and a fleet scrape-and-merge adds them like any counter)
        phases = sorted(self.phases.items())
        L.append("# TYPE serve_step_phase_seconds_total counter")
        for name, (_, _, self_ns) in phases:
            L.append(f'serve_step_phase_seconds_total{{phase="{name}"}} '
                     f'{self_ns * 1e-9:.9f}')
        L.append("# TYPE serve_step_phase_calls_total counter")
        for name, (calls, _, _) in phases:
            L.append(f'serve_step_phase_calls_total{{phase="{name}"}} '
                     f'{calls}')
        return "\n".join(L) + "\n"

    def maybe_dump(self, name: str = "serve_metrics") -> Optional[str]:
        """Write the summary as JSON under the IR-dump dir when
        ``TDT_DUMP_IR`` is set (runtime/dump.py — one observability
        switch for kernels AND serving); no-op otherwise."""
        directory = dump.dump_dir()
        if directory is None:
            return None
        path = os.path.join(directory, dump._safe(name) + ".json")
        dump._write(path, json.dumps(self.summary(), indent=2))
        return path


# ---------------------------------------------------------------------------
# THE stats renderings (CLI end-of-run block, periodic one-liner,
# supervisor postmortem) — one formatter, zero drift between surfaces
# ---------------------------------------------------------------------------


def _ms(x) -> str:
    return f"{x * 1e3:.2f} ms" if x is not None else "n/a"


def format_statline(s: dict) -> str:
    """ONE line of live engine state (the ``--stats-every`` periodic log
    and the flight-recorder postmortem header): progress, queue
    pressure, and the SLO percentiles that page an operator."""
    lat = s.get("latency", {})
    ttft = lat.get("ttft", {})
    itl = lat.get("itl", {})

    def p(h, k):
        v = h.get(k)
        return f"{v * 1e3:.1f}" if v is not None else "-"

    line = (f"step {s['steps']} | {s['completed']} done, "
            f"{s['decode']['decode_tokens']} decode toks | "
            f"queue {s.get('max_queue_depth', 0)} peak | "
            f"kv {s.get('peak_kv_utilization', 0.0):.2f} peak | "
            f"ttft p50/p95/p99 {p(ttft, 'p50')}/{p(ttft, 'p95')}/"
            f"{p(ttft, 'p99')} ms | itl p50/p95/p99 {p(itl, 'p50')}/"
            f"{p(itl, 'p95')}/{p(itl, 'p99')} ms")
    progs = s.get("programs") or {}
    if progs:
        # the program eating the most wall time this life (count * mean)
        top = max(progs, key=lambda n: (progs[n]["count"] or 0)
                  * (progs[n]["mean"] or 0.0))
        st = progs[top]
        line += (f" | top program {top} "
                 f"p50 {st['p50']:.2f} ms x{st['count']}")
    return line


def format_stats(s: dict, *, spec: bool = False, prefix: bool = False,
                 failures: bool = False, recovery: bool = False
                 ) -> list[str]:
    """The end-of-run stats block ``examples/serve.py`` prints — moved
    here so every surface (CLI, supervisor, tests) renders ``summary()``
    identically.  Sections beyond the engine/decode core are opt-in by
    flag, matching the CLI's feature gates."""
    lat = s["latency"]
    lines = [
        (f"engine metrics: mean ttft {_ms(s['mean_ttft'])}, "
         f"mean itl {_ms(s['mean_itl'])}, max queue depth "
         f"{s['max_queue_depth']}, peak kv util "
         f"{s['peak_kv_utilization']:.2f}, preemptions "
         f"{s['preemptions']}"),
        (f"latency slo: ttft p50/p95/p99 "
         f"{_ms(lat['ttft']['p50'])}/{_ms(lat['ttft']['p95'])}/"
         f"{_ms(lat['ttft']['p99'])}, itl p50/p95/p99 "
         f"{_ms(lat['itl']['p50'])}/{_ms(lat['itl']['p95'])}/"
         f"{_ms(lat['itl']['p99'])}, step p99 "
         f"{_ms(lat['step']['p99'])}"),
    ]
    kv = s.get("kv")
    if kv and kv.get("token_slots"):
        lines.append(
            f"kv pool: {kv['pool_bytes']} bytes for "
            f"{kv['token_slots']} token slots "
            f"({kv['bytes_per_token']:.1f} B/token, "
            f"{'int8+scales' if kv['quantized'] else 'float'})")
    d = s["decode"]
    lines.append(
        f"decode horizon: {d['dispatches']} dispatches / "
        f"{d['host_syncs']} host syncs for {d['decode_tokens']} "
        f"tokens ({d['decode_steps']} device steps) — "
        f"{d['tokens_per_dispatch']:.2f} tokens/dispatch, "
        f"{d['dispatches_per_token']:.3f} dispatches/token")
    progs = s.get("programs") or {}
    if progs:
        # per-program wall-time attribution (trace_level >= 1), worst
        # total-time first — the step-time decomposition that replaces
        # "which program ate the slow step" archaeology
        by_total = sorted(
            progs, key=lambda n: (progs[n]["count"] or 0)
            * (progs[n]["mean"] or 0.0), reverse=True)
        parts = ", ".join(
            f"{n} p50/p99 {progs[n]['p50']:.2f}/{progs[n]['p99']:.2f} "
            f"x{progs[n]['count']}" for n in by_total[:6])
        lines.append(f"program ms: {parts}")
    if spec:
        sp = s["spec"]
        lines.append(
            f"speculative: {sp['rounds']} fused rounds, accept "
            f"rate {sp['accept_rate']:.2f} (rolling "
            f"{sp['rolling_accept_rate']:.2f}), chosen k "
            f"{sp['chosen_k']}, "
            f"{sp['spec_tokens_per_dispatch']:.2f} spec tokens/"
            f"dispatch, {sp['bailouts']} bailouts"
            + (f", {sp['draft_prefix_skipped_tokens']} draft "
               f"prefill tokens skipped"
               if sp['draft_prefix_skipped_tokens'] else ""))
    if prefix:
        pc = s["prefix_cache"]
        ratio = (f", warm/cold ttft {pc['ttft_warm_over_cold']:.2f}x"
                 if pc.get("ttft_warm_over_cold") is not None else "")
        lines.append(
            f"prefix cache: {pc['lookup_hits']}/{pc['lookups']} "
            f"lookups hit, {pc['prefix_skipped_tokens']} prefill "
            f"tokens skipped, {pc['cached_blocks']} cached / "
            f"{pc['shared_blocks']} shared blocks, "
            f"{pc['cow_copies']} COW, {pc['evictions']} "
            f"evictions{ratio}")
    if failures:
        f = s["failures"]
        lines.append(
            f"failure containment: {f['shed']} shed, "
            f"{f['deadline_expired']} expired, "
            f"{f['quarantined']} quarantined, "
            f"{f['callback_errors']} callback errors, "
            f"{f['forward_retries']} retries / "
            f"{f['forward_bisections']} bisections, "
            f"finish reasons {f['finish_reasons']}")
    if recovery:
        r = s["recovery"]
        lines.append(
            f"crash recovery: {r['snapshots']} snapshots "
            f"(last {r['snapshot_ms_last']:.1f} ms), "
            f"{r['journal_records']} journal records "
            f"({r['journal_bytes']} bytes), "
            f"{r['restored_in_place']} resumed in place / "
            f"{r['restored_requeued']} requeued")
        mg = s.get("migration")
        if mg and (mg["migrated_out"] or mg["migrated_in"]):
            lines.append(
                f"migration: {mg['migrated_out']} drained out, "
                f"{mg['migrated_in']} adopted "
                f"({mg['migrated_in_place']} with live KV), "
                f"{mg['migrated_tokens']} journal tokens carried")
        if mg and (mg.get("pushed_out") or mg.get("pushed_in")):
            lines.append(
                f"disagg push: {mg['pushed_out']} pushed out, "
                f"{mg['pushed_in']} admitted")
    comp = s["compilation"]
    per = ", ".join(f"{n} {c['misses']}c/{c['hits']}h"
                    for n, c in comp["programs"].items())
    lines.append(f"trace cache (compiles/hits): {per}")
    lines.append(
        f"compile stalls: {comp['total_compile_time_s'] * 1e3:.0f} "
        f"ms total, {comp['warmup_compiles']} programs "
        f"({comp['warmup_time_s'] * 1e3:.0f} ms) during warmup")
    return lines
