"""Iteration-level FCFS scheduler with a chunked-prefill token budget.

Orca-style continuous batching: scheduling decisions happen every engine
iteration, not per request — new prompts are admitted the moment a batch
slot AND enough KV blocks exist, prompt prefill is metered in chunks so a
long prompt cannot starve in-flight decode (the budget), and decode rows
retire individually.

Preemption (vLLM-style recompute): when a running request cannot extend
its KV allocation, the LATEST-admitted running request is evicted — its
blocks free immediately, its emitted tokens are kept, and it re-queues at
the FRONT of the waiting line with ``prompt + generated`` as the new
prompt (greedy recompute is deterministic, and sampled requests keep
their per-token PRNG stream, so the emission is unchanged).
"""

from __future__ import annotations

import enum
import math
from collections import deque
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from triton_dist_tpu.serve.block_manager import BlockManager
from triton_dist_tpu.serve.metrics import RequestMetrics
from triton_dist_tpu.serve.request import Request, slo_rank


class Status(enum.Enum):
    WAITING = "waiting"    # queued, no slot/blocks yet
    PREFILL = "prefill"    # admitted, prompt streaming through chunks
    RUNNING = "running"    # in the decode batch
    FINISHED = "finished"


@dataclass
class ReqState:
    """Engine-side state of one request (the scheduler moves it between
    queues; the engine owns its device-facing fields)."""

    req: Request
    metrics: RequestMetrics
    status: Status = Status.WAITING
    slot: Optional[int] = None      # decode-batch row while admitted
    kv_len: int = 0                 # committed cache rows
    prefill_pos: int = 0            # prompt tokens already prefilled
    generated: list[int] = field(default_factory=list)
    pending_token: Optional[int] = None  # emitted, not yet consumed
    seq: int = 0                    # admission order (preemption victim)
    # recompute prompt: original prompt + tokens generated before a
    # preemption (rebuilt by the scheduler on eviction)
    work_prompt: Optional[np.ndarray] = None
    # chunked-prefill scratch (engine-owned): per-layer contiguous K/V
    # [1, Hkv, s_ext, D] the prompt streams into before the page scatter
    scratch: Optional[list] = None
    s_ext: int = 0
    # failure containment (engine-owned): a request whose on_token
    # callback raised keeps serving with the callback off (logged once)
    callback_disabled: bool = False
    # crash recovery (engine-owned): number of tokens restored from the
    # durable journal when this state was rebuilt (0 on a fresh
    # request).  Post-restore commits continue at len(generated), which
    # starts AT this index — the pre-populated `generated` list is what
    # keeps a restored stream from re-journaling or re-delivering a
    # pre-crash token; this field records that provenance and bounds
    # the restore(replay_tokens=True) redelivery
    journal_base: int = 0
    # prefix cache (docs/serving.md "Prefix caching"): tokens of this
    # admission's prompt covered by shared cached blocks (block-aligned;
    # set by admit(), reset on preemption — the re-admission re-matches).
    # The engine starts chunked prefill at the chunk floor of this, so a
    # warm prefix pays ~one residual chunk instead of the whole prompt.
    cached_prefix: int = 0
    # full logical pages whose token contents the engine has committed to
    # the content index (a watermark, monotone within one admission)
    committed_pages: int = 0
    # whether this admission attempt already counted toward the block
    # manager's lookups/lookup_hits gauges (a blocked head re-matches
    # every step; only the first walk per admission attempt counts, so
    # hit_rate stays per-request, not per-retry)
    lookup_counted: bool = False
    # memoized match_prefix result for THIS admission attempt, valid
    # while the index generation it was computed under is current — a
    # capacity-blocked head re-enters admission every engine step, and
    # without the memo each retry re-pays the O(prompt) chain walk
    match_cache: Optional[list] = None
    match_gen: int = -1
    # speculative decoding (docs/serving.md "Speculative decoding"):
    # recent (proposed, accepted) pairs, one per fused round this row
    # took part in — the windowed acceptance estimate behind the
    # scheduler's adaptive per-row k (choose_spec_k); trimmed by the
    # engine, survives preemption (acceptance is a property of the
    # request's text, not of its admission)
    spec_window: list = field(default_factory=list)
    # brownout ladder (engine-owned; docs/serving.md "Overload, SLO
    # classes & autoscaling"): a rung-3 emission cap for best-effort
    # rows — ``remaining_new`` and the LENGTH finish check both honor
    # it, while ``total_tokens`` (the admitted cache ceiling) does not,
    # so capping never re-plans allocations.  ``None`` = uncapped (the
    # default path is untouched).
    new_cap: Optional[int] = None

    def expired(self, now: float) -> bool:
        """Past its deadline TTL (``params.deadline_s`` from arrival)."""
        d = self.req.params.deadline_s
        return (d is not None and self.req.arrival_time is not None
                and now - self.req.arrival_time > d)

    @property
    def prompt_tokens(self) -> np.ndarray:
        return (self.work_prompt if self.work_prompt is not None
                else self.req.prompt)

    @property
    def effective_max_new(self) -> int:
        """``params.max_new_tokens``, clamped by a brownout ``new_cap``
        (the cap is applied with >= 1 token of headroom, so a live row
        always retires through a normal LENGTH commit)."""
        m = self.req.params.max_new_tokens
        return m if self.new_cap is None else min(m, self.new_cap)

    @property
    def remaining_new(self) -> int:
        return self.effective_max_new - len(self.generated)

    @property
    def total_tokens(self) -> int:
        """The request's admitted cache ceiling (prompt + max_new):
        invariant under preemption/recompute — the recompute prompt
        absorbs generated tokens 1:1 from the remaining budget."""
        return int(self.req.prompt.shape[0]) + self.req.params.max_new_tokens


class FCFSScheduler:
    """First-come-first-served admission + prefill metering + LIFO
    preemption, all against one :class:`BlockManager`."""

    def __init__(self, block_manager: BlockManager, *,
                 prefill_budget: int, prefill_chunk: int,
                 class_aware: bool = False):
        assert prefill_chunk >= 1 and prefill_budget >= 1
        self.bm = block_manager
        # Batch-slot capacity lives with the ENGINE (admit() is bounded
        # by the free_slots list it passes in) — one source of truth.
        # tokens of prompt prefill allowed per engine iteration; at least
        # one chunk always proceeds so prefill cannot livelock
        self.prefill_budget = prefill_budget
        self.prefill_chunk = prefill_chunk
        # SLO-class-aware policy (docs/serving.md "Overload, SLO classes
        # & autoscaling"): admission considers waiting requests in
        # (class rank, queue position) order and preemption spends the
        # worst class first.  Both orders are STABLE on arrival, so with
        # every request in one class (the default — slo_class defaults
        # to "interactive") they reduce bit-for-bit to FCFS / LIFO.
        self.class_aware = class_aware
        self.waiting: deque[ReqState] = deque()
        self._seq = 0

    # -- queue ------------------------------------------------------------

    def add(self, rs: ReqState, *, front: bool = False) -> None:
        (self.waiting.appendleft if front else self.waiting.append)(rs)

    @property
    def queue_depth(self) -> int:
        return len(self.waiting)

    def pop_expired(self, now: float) -> list[ReqState]:
        """Drop WAITING requests whose deadline TTL has passed (the
        engine retires them with ``FinishReason.DEADLINE``).  Swept
        every iteration BEFORE admission, so an expired head of line
        frees its queue position for live requests behind it."""
        expired = [rs for rs in self.waiting if rs.expired(now)]
        for rs in expired:
            self.waiting.remove(rs)
        return expired

    # -- admission --------------------------------------------------------

    def admit(self, free_slots: list[int], now: float) -> list[ReqState]:
        """Pop waiting requests while a slot and their prompt's blocks
        (plus one decode-headroom block) are available.  FCFS: the head
        blocking keeps everyone behind it queued — no starvation.

        With the block manager's prefix cache on, the prompt's longest
        cached block-aligned prefix maps in as SHARED blocks: only the
        remainder needs free blocks (so a warm prompt admits under
        pressure a cold one could not), and ``rs.cached_prefix`` tells
        the engine where chunked prefill may start.  A recompute prompt
        (``work_prompt`` after preemption) matches the same way — the
        victim's own committed blocks usually sit in the cache tier, so
        preemption recompute collapses too.

        With ``class_aware`` on, candidates are scanned in (class rank,
        queue position) order — a stable sort, so within one class it IS
        the FCFS order, and with every request in one class the two
        paths admit identically.  Head-of-line blocking applies within
        that order: the first blocked candidate stops the scan, so no
        class starves its own members and no lower class jumps a
        blocked higher-class head."""
        admitted = []
        if self.class_aware:
            queue = sorted(self.waiting,
                           key=lambda r: slo_rank(r.req.slo_class))
        else:
            queue = list(self.waiting)
        for rs in queue:
            if not free_slots:
                break
            # Every admission needs >= 1 fresh block (match_prefix caps
            # at n_prompt - 1 tokens, so shared pages never cover the
            # prompt + headroom) — with nothing allocatable, skip the
            # O(prompt) chain walk entirely.
            if self.bm.num_free == 0:
                break
            n_prompt = int(rs.prompt_tokens.shape[0])
            # match_prefix caps at n_prompt - 1: at least one prompt
            # token always prefills (the request needs its logits).
            if (rs.match_cache is not None
                    and rs.match_gen == self.bm.index_gen):
                shared = rs.match_cache
            else:
                shared = self.bm.match_prefix(
                    np.asarray(rs.prompt_tokens),
                    count=not rs.lookup_counted)
                rs.lookup_counted = True
                rs.match_cache = shared
                rs.match_gen = self.bm.index_gen
            # +1 token of headroom: admission must leave room to decode
            # at least one token past the prompt, or the request would
            # immediately preempt something.
            if not self.bm.can_allocate(n_prompt + 1, shared):
                break
            self.waiting.remove(rs)
            rs.slot = free_slots.pop(0)
            rs.status = Status.PREFILL
            rs.prefill_pos = 0
            rs.kv_len = 0
            rs.seq = self._seq
            self._seq += 1
            self.bm.allocate(rs.req.request_id, n_prompt + 1,
                             shared=shared)
            rs.match_cache = None  # consumed
            rs.cached_prefix = len(shared) * self.bm.page_size
            rs.committed_pages = len(shared)
            rs.metrics.on_scheduled(now)
            admitted.append(rs)
        return admitted

    # -- chunked-prefill metering ----------------------------------------

    def prefill_plan(self, prefilling: list[ReqState]) -> list[tuple]:
        """Assign this iteration's prompt-token budget to PREFILL-state
        requests (admission order).  Returns [(rs, n_tokens)]; the first
        assignment always gets at least one chunk (progress guarantee).

        Assignments are quantized to WHOLE ``prefill_chunk`` multiples
        (except a prompt's final residual): the chunk is the METERING
        granule.  The engine runs an assignment in calls of its one fixed
        shape ``[1, W]`` (``ServeEngine.prefill_width``: the step's
        budget up to 256 rows, in whole chunks — a residual pads up), so
        prefill never retraces on prompt length — the trace-cache
        contract of docs/serving.md's bucket ladder.  A padded final
        chunk is charged as a full chunk of budget."""
        plan = []
        budget = self.prefill_budget
        chunk = self.prefill_chunk
        for rs in sorted(prefilling, key=lambda r: r.seq):
            remaining = int(rs.prompt_tokens.shape[0]) - rs.prefill_pos
            if remaining <= 0:
                continue
            if not plan:
                # Head of line: at least one chunk even when budget <
                # chunk (otherwise a budget smaller than the chunk size
                # would stall prefill forever).
                n_chunks = max(1, budget // chunk)
            elif budget < chunk:
                break
            else:
                n_chunks = budget // chunk
            n_chunks = min(n_chunks, -(-remaining // chunk))
            plan.append((rs, min(remaining, n_chunks * chunk)))
            budget -= n_chunks * chunk
        return plan

    # -- decode-horizon planning -----------------------------------------

    def plan_horizon(self, horizon: int, *, prefilling: bool, spec: bool,
                     deadline_waiting: bool) -> int:
        """Decode steps ONE device dispatch may fuse this iteration (the
        engine buckets the result down its horizon ladder and enforces
        per-row budgets on device — docs/serving.md "Decode horizon").

        Fusing trades scheduling granularity for dispatch economy, so the
        plan clamps back to ITERATION-LEVEL decode (1) whenever a fused
        horizon would break a per-step contract.  How the one step is
        dispatched is the engine's: an engine with a horizon program
        runs it as ONE link of that program at ``H = 1`` (token choice
        stays on the device), every other engine as the per-token
        ``paged_decode`` step with the host sampler.

        - ``spec``: speculative rounds are already multi-token per
          dispatch and share device state across rows; they keep their
          own round machinery (a post-bailout engine serves single steps
          on ``paged_decode``, the program its warm-up compiled: it
          warms no horizon rung).
        - ``prefilling``: mid-prefill rows are owed chunk budget every
          iteration — a fused horizon would freeze their TTFT for its
          whole duration.
        - ``deadline_waiting``: WAITING deadlines are swept at step
          boundaries; fusing would delay the sweep (and the blocks it
          frees) by the horizon's wall time.

        A non-empty waiting queue WITHOUT deadlines does not clamp:
        admission runs before decode each step, so anything still queued
        at decode time could not be admitted now anyway, and retirements
        that unblock it only land at the horizon's drain regardless."""
        if horizon <= 1 or spec or prefilling or deadline_waiting:
            return 1
        return horizon

    # -- speculative planning --------------------------------------------

    def plan_spec(self, pipeline: int, *, prefilling: bool,
                  deadline_waiting: bool) -> int:
        """Fused speculative rounds ONE engine step may chain on a
        device-resident carry (the spec twin of :meth:`plan_horizon` —
        a chained round is a spec-shaped horizon link).  The same
        per-step contracts clamp chaining back to one round per step:
        mid-prefill rows are owed chunk budget every iteration, and
        WAITING deadlines are swept at step boundaries.  The
        ``plan_horizon`` spec clamp does NOT apply here — a spec round
        is already the multi-token dispatch it protects."""
        if pipeline <= 1 or prefilling or deadline_waiting:
            return 1
        return pipeline

    def choose_spec_k(self, rs: ReqState, k_max: int, *, window: int = 8,
                      floor: float = 0.25) -> int:
        """Per-row speculation depth from a windowed acceptance-rate
        estimate: under an i.i.d.-acceptance model with per-token rate
        ``alpha`` (the window's accepted/proposed), a k-token chain
        fully accepts with probability ``alpha ** k`` — pick the
        deepest k that still clears ``floor``, so a well-matched draft
        speculates the full ``k_max`` while a mismatched one collapses
        to 1 instead of burning k draft steps per emitted token.
        Optimistic while the window is still filling (a fresh request
        starts at full depth); the evidence floor is min(k_max, window)
        proposals so a COLLAPSED row — whose window holds `window`
        1-proposal rounds, fewer than k_max proposals — stays collapsed
        instead of periodically resetting to full depth (and dragging
        the whole batch's k-rung up with it).  The engine buckets the
        batch max down the pow2 k-ladder, so the chosen depths never
        cost fresh traces."""
        window = max(window, 1)
        hist = rs.spec_window[-window:]
        prop = sum(p for p, _ in hist)
        if k_max <= 1 or prop < min(k_max, window):
            return max(k_max, 1)
        alpha = sum(a for _, a in hist) / prop
        if alpha <= 0.0:
            return 1
        if alpha >= 1.0:
            return k_max
        return max(1, min(k_max, int(math.log(floor) / math.log(alpha))))

    # -- preemption -------------------------------------------------------

    def pick_victim(self, running: list[ReqState],
                    needy: ReqState) -> Optional[ReqState]:
        """LIFO eviction: the latest-admitted running request other than
        ``needy`` (evicting the one that still needs blocks would free
        nothing it can use — its own blocks come back to it).

        With ``class_aware`` on, the worst SLO class is spent first —
        best-effort before batch before interactive — LIFO within a
        class.  With every request in one class the (rank, seq) max is
        the seq max, so the default path is unchanged."""
        candidates = [r for r in running if r is not needy]
        if not candidates:
            return None
        if self.class_aware:
            return max(candidates,
                       key=lambda r: (slo_rank(r.req.slo_class), r.seq))
        return max(candidates, key=lambda r: r.seq)

    def pick_shed_victim(self, rank: int) -> Optional[ReqState]:
        """Class-aware overload displacement: the latest-queued WAITING
        request of the WORST class strictly below service rank ``rank``
        (higher ``slo_rank``), or ``None`` when no lower class holds a
        queue slot.  Used by the engine when the waiting queue is at
        ``max_queue``: an arriving higher-class request sheds this
        victim and takes its slot instead of being refused — interactive
        is never shed while best-effort or batch occupies the queue."""
        worst: Optional[ReqState] = None
        worst_key = (rank, -1)
        for i, rs in enumerate(self.waiting):
            key = (slo_rank(rs.req.slo_class), i)
            if key > worst_key:
                worst, worst_key = rs, key
        return worst

    def preempt(self, rs: ReqState) -> None:
        """Evict ``rs``: free its blocks and re-queue it (front) for
        recompute — the new prompt is everything already committed, so
        emitted tokens stay emitted."""
        self.bm.free(rs.req.request_id)
        rs.work_prompt = np.concatenate(
            [rs.req.prompt, np.asarray(rs.generated, np.int32)])
        rs.status = Status.WAITING
        rs.slot = None
        rs.kv_len = 0
        rs.prefill_pos = 0
        rs.pending_token = None
        rs.cached_prefix = 0
        rs.committed_pages = 0
        # The recompute admission re-matches (and may land cold): a
        # request whose TTFT is still pending must be re-classified by
        # what that admission finds, not by the one that was evicted.
        # An already-recorded TTFT keeps its warm/cold label.
        rs.lookup_counted = False
        rs.match_cache = None  # the recompute prompt is different
        rs.match_gen = -1
        if rs.metrics.first_token_time is None:
            rs.metrics.cached_prefix_tokens = 0
        rs.metrics.n_preemptions += 1
        self.add(rs, front=True)
