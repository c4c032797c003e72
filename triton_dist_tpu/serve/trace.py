"""Engine flight recorder: structured event tracing for the serving loop.

Every interesting engine decision — admission, prefill chunking, horizon
drains, spec rounds, COW splits, preemption, quarantine, bailout,
snapshot — used to happen invisibly inside the step loop; diagnosing a
tail-latency spike or a chaos-test failure meant re-running under a
debugger.  This module makes the engine's timeline a first-class
artifact, three ways:

- **Ring buffer** (:class:`FlightRecorder`): a bounded deque of typed,
  timestamped events, each carrying the PR 5 monotonic step index, the
  request id(s) involved, and a small payload (chunk size, chosen k,
  accept count, blocks touched).  Hot-path discipline: ``emit`` is an
  append to a bounded ring — no device sync, no I/O, no string
  formatting — and a single ``level`` knob gates it off entirely
  (what level 1 costs on the chip has no reading yet: PERF.md
  section 7).

- **Perfetto export** (:meth:`FlightRecorder.to_perfetto`): per-request
  lifecycle *spans* (queue → prefill → decode, re-opened across
  preemptions) reconstructed from the event stream as a Chrome trace,
  pid-namespaced so :func:`runtime.profiling.merge_rank_traces` merges
  the engine timeline with the device profiler's into ONE
  ui.perfetto.dev view (:meth:`export_profile` drops the file where the
  merge globs it).

- **Postmortem flush** (:meth:`FlightRecorder.flush`): on any
  fault/quarantine/watchdog/crash path the engine writes the ring to
  ``flight_<step>.json`` (under ``TDT_DUMP_IR`` or the snapshot dir) so
  the PR 5 supervisor and the chaos harness get a trail; the tail of
  the ring also rides snapshots (serve/recovery.py), so a restored
  engine carries its previous life's provenance.

- **Step spans** (:meth:`FlightRecorder.span`): the layer boundaries of
  one ``ServeEngine.step()`` — admit, prefill, decode plan / stage /
  wait / commit, every program hand-over — as nested spans.  Each is a
  ``jax.profiler.TraceAnnotation`` (so under a running profile it lands
  on the host plane, on the device trace's clock) and adds its wall and
  SELF time to a per-name table an operator reads without any profiler
  (``summary()["phases"]``, ``serve_step_phase_seconds_total``).  The
  names are the closed set :data:`STEP_PHASES`; the same ``level`` knob
  gates them.

The taxonomy is CLOSED over the engine's failure surface: every
:class:`serve.request.FinishReason` retires through a ``retire`` event
(:data:`RETIRE_REASONS`), and every ``runtime/faults.py`` injection
point lands in the ring as a ``fault`` event
(:data:`FAULT_POINT_EVENTS`) — a meta-test cross-checks both sets
against the source so a new failure path cannot silently skip the
recorder.  See docs/observability.md for the event reference and the
Perfetto recipe.
"""

from __future__ import annotations

import gzip
import json
import math
import os
import time
from collections import deque
from typing import Optional

from jax.profiler import TraceAnnotation

# ---------------------------------------------------------------------------
# Event taxonomy
# ---------------------------------------------------------------------------

#: Every event type the recorder may emit (docs/observability.md).
EVENT_TYPES = frozenset({
    "submit",         # request entered the engine (or was shed at the door)
    "admit",          # WAITING -> PREFILL: slot + blocks granted
    "prefill_chunk",  # one chunked-prefill dispatch (level >= 2 only)
    "prefill_done",   # prompt fully prefilled; row joins the decode batch
    "decode_drain",   # one decode drain (single-step batch or horizon link)
    "spec_round",     # one fused speculative round drained
    "preempt",        # LIFO eviction back to the waiting queue
    "cow_split",      # copy-on-write block split before a shared-page write
    "evict",          # prefix-cache tier block reclaimed under pressure
    "snapshot",       # durable engine capture published
    "restore",        # engine rebuilt from snapshot + journal
    "fault",          # an injected/contained/engine-level failure seam fired
    "bailout",        # speculative chain failed; spec latched off
    "retire",         # request finished (reason = any FinishReason value)
    # fleet serving (serve/fleet.py, docs/serving.md "Fleet serving"):
    # migration rides the engine ring on BOTH sides of a hand-off, and
    # the FleetController keeps its own recorder for routing + replica
    # lifecycle (one timeline per surface, same event vocabulary).
    "migrate_out",    # request handed off to another replica (drain)
    "migrate_in",     # request adopted from a migration manifest
    "route",          # fleet router placed a request on a replica
    "replica_state",  # replica HEALTHY -> SUSPECT -> DEAD transitions
    # disaggregated prefill->decode tier (serve/disagg.py,
    # docs/serving.md "Disaggregated serving"): the per-request
    # KV-page PUSH at prefill completion — the drain/migrate machinery
    # under a distinct name, so tier hand-offs and failure migrations
    # read apart on one timeline.
    "push_out",       # prefill replica pushed a request's KV hand-off
    "push_in",        # decode replica admitted a pushed request
    # network serving plane (serve/net.py, docs/serving.md "Network
    # fleet serving"): the RemoteReplica client's ring records every
    # retried call, so a postmortem shows the backoff ladder a
    # partition actually drove.
    "net_retry",      # a network call failed and will retry under backoff
    # overload robustness (docs/serving.md "Overload, SLO classes &
    # autoscaling"): the engine's graceful-degradation ladder and the
    # fleet's pressure-driven scaling — every degrade/scale decision
    # lands on a timeline next to the traffic it shaped.
    "brownout",       # engine ladder moved a rung (data: rung, prev)
    "scale",          # fleet autoscaler spawned/retired a replica
    "ingress_shed",   # fleet token-bucket refused a request at the door
    # state integrity (docs/serving.md "Durability & integrity"): a
    # durable or wire artifact FAILED verification — journal interior
    # corruption salvaged + quarantined at restore, a snapshot leaf
    # digest mismatch, or a wire manifest rejected by its receiver.
    # Data names the artifact class and what the salvage kept/lost.
    "corrupt",        # artifact integrity check failed (never adopted)
})

#: FinishReason values the ``retire`` event is specified over — the
#: meta-test asserts every ``serve.request.FinishReason`` member is here,
#: so a new retirement reason must be registered with the recorder.
RETIRE_REASONS = frozenset({
    "length", "eos", "abort", "deadline", "shed", "error",
})

#: Every ``FaultInjector`` point (plus the engine-level seams that fire
#: without the injector) mapped to the event type that records it.  The
#: meta-test greps the source tree for ``.fire("<point>"`` calls and
#: asserts each point appears here.
FAULT_POINT_EVENTS = {
    "forward": "fault",       # engine device-dispatch seam
    "block_alloc": "fault",   # BlockManager.ensure grow path
    "callback": "fault",      # the on_token invocation seam
    "clock": "fault",         # wrap_clock readings (skew)
    "snapshot": "fault",      # the two snapshot crash windows
    "watchdog": "fault",      # step watchdog trip (engine-level, no
                              # injector point — WatchdogTimeout)
    "crash": "fault",         # anything escaping step() (InjectedKill,
                              # escalations, interrupts)
    "net": "fault",           # network serving plane seams (serve/net.py:
                              # client send, server receive, server
                              # respond — drop/delay/duplicate/partition)
    "integrity": "fault",     # artifact corruption seams (journal-line
                              # append, snapshot tmp-dir leaf, wire
                              # manifest blob — bitflip/truncate/zero);
                              # the DETECTION lands as a "corrupt" event
                              # on whichever surface caught it
}

#: Every step-span name (:meth:`FlightRecorder.span`), without the
#: ``serve.`` prefix the profiler sees.  ``dispatch`` is a family: one
#: member per engine program, ``dispatch.<program>`` (and
#: ``dispatch.<program>.compile`` in the in-memory table for a call that
#: compiled).  A meta-test holds every ``span("...")`` literal under
#: ``serve/`` to this set, and every member to a use.
STEP_PHASES = frozenset({
    "submit",          # ServeEngine.submit: journal append, queue, shed
    "step",            # root of one ServeEngine.step()
    "admit",           # beat, journal sync, deadline sweep, admission,
                       # prefill start
    "prefill",         # one _run_prefill call (one request's chunk budget)
    "prefill.stage",   # host arrays for a chunk / the page scatter
    "prefill.wait",    # host blocked on the last chunk's logits
    "prefill.commit",  # first token choice + commit, content-index commits
    "decode.plan",     # plan_horizon, capacity growth, preemption
    "decode.plan.release",  # cache groups: window pages no query sees go
                       # back (child of decode.plan; absent with one group)
    "decode.plan.state",  # a state group: slots taken and given back since
                       # the last chain, counted (child of decode.plan)
    "decode.stage",    # numpy batch arrays -> device operands
    "decode.wait",     # host blocked on the device (logits / token burst)
    "decode.commit",   # token choice, _commit_token, callbacks, journal
    "dispatch",        # family: runtime.jit_cache.CountingJit.__call__
    "observe",         # observe_step, fault mirror, snapshot when due
})


#: pid the engine timeline claims in exported Chrome traces.  Below the
#: Linux pid cap (4194304) so :func:`runtime.profiling.merge_rank_traces`'s
#: per-rank re-namespacing (rank * 10_000_000 + pid) stays injective
#: against real process pids.
ENGINE_PID = 3_999_999

#: Newest ring events a migration manifest carries per request — both
#: producers share it: the live ``ServeEngine.drain`` gathers the tail
#: from its ring, the crash-path ``recovery.manifest_from_journal``
#: recovers it from the dead life's flight file.  Bounded so a manifest
#: cannot grow with ring capacity (docs/observability.md "Fleet
#: observability").
MIGRATE_EVENT_TAIL = 128

#: pid of the fleet controller's own timeline in a merged fleet export
#: (serve/fleet.py), and the base pid replica ``r<i>`` claims
#: (``FLEET_REPLICA_PID_BASE + i``).  All below the Linux pid cap for
#: the same merge-injectivity reason as :data:`ENGINE_PID`.
FLEET_PID = 3_999_998
FLEET_REPLICA_PID_BASE = 3_900_000


# ---------------------------------------------------------------------------
# Log-bucketed histograms (the bounded replacement for per-request
# latency lists)
# ---------------------------------------------------------------------------


class LogHistogram:
    """Log-bucketed scalar histogram: O(buckets) memory regardless of
    sample count, percentiles within one bucket's relative width.

    Buckets span ``[lo, hi)`` with ``per_decade`` buckets per decade
    (default 24 → ~10% wide, so p50/p95/p99 land within ~5% of numpy's
    on the same samples — pinned by tests/test_serve_trace.py).  Values
    below ``lo`` (including 0 and negatives — fake test clocks produce
    them) land in the underflow bucket; values past ``hi`` in the
    overflow bucket.  ``sum``/``count``/``min``/``max`` track exact
    values, so the mean is exact even though percentiles are bucketed.
    """

    def __init__(self, lo: float = 1e-6, hi: float = 4000.0,
                 per_decade: int = 24):
        if lo <= 0 or hi <= lo:
            raise ValueError(f"need 0 < lo < hi, got {lo}, {hi}")
        self.lo = lo
        self.per_decade = per_decade
        self._log_lo = math.log10(lo)
        n = int(math.ceil(math.log10(hi / lo) * per_decade))
        # counts[0] = underflow (< lo); counts[1 + i] covers
        # [edge(i), edge(i + 1)); counts[-1] = overflow (>= hi)
        self.counts = [0] * (n + 2)
        self.count = 0
        self.sum = 0.0
        self.min = float("inf")
        self.max = float("-inf")

    def edge(self, i: int) -> float:
        """Upper edge of bucket ``i`` (0-based over the log range)."""
        return self.lo * 10.0 ** ((i + 1) / self.per_decade)

    def observe(self, x: float) -> None:
        x = float(x)
        self.count += 1
        self.sum += x
        if x < self.min:
            self.min = x
        if x > self.max:
            self.max = x
        if x < self.lo:
            self.counts[0] += 1
            return
        i = 1 + int((math.log10(x) - self._log_lo) * self.per_decade)
        self.counts[min(i, len(self.counts) - 1)] += 1

    @property
    def mean(self) -> Optional[float]:
        return self.sum / self.count if self.count else None

    def percentile(self, p: float) -> Optional[float]:
        """Approximate p-th percentile: the geometric midpoint of the
        bucket holding the rank (underflow reports ``min``, overflow
        ``max`` — both exact)."""
        if not self.count:
            return None
        rank = max(1, int(-(-p / 100.0 * self.count // 1)))  # ceil
        acc = 0
        for i, c in enumerate(self.counts):
            acc += c
            if acc >= rank:
                if i == 0:
                    return self.min
                if i == len(self.counts) - 1:
                    return self.max
                hi = self.edge(i - 1)
                lo = hi / 10.0 ** (1.0 / self.per_decade)
                return (lo * hi) ** 0.5
        return self.max

    def stats(self) -> dict:
        """The summary() view: count/mean plus the SLO percentiles."""
        return {
            "count": self.count,
            "mean": self.mean,
            "p50": self.percentile(50),
            "p95": self.percentile(95),
            "p99": self.percentile(99),
            "max": self.max if self.count else None,
        }

    def merge(self, other: "LogHistogram") -> "LogHistogram":
        """Fold ``other`` into this histogram EXACTLY: identical bucket
        schemes add count-wise, so the merged percentiles equal those of
        a histogram fed the pooled samples bucket-exactly, and
        sum/count/min/max stay exact — the fleet aggregation primitive
        (serve/fleet.py; a mean-of-percentiles would be wrong, this is
        a percentile-of-merged-counts).  Raises on a bucket-scheme
        mismatch: adding misaligned buckets would silently corrupt the
        quantiles."""
        if (self.lo != other.lo or self.per_decade != other.per_decade
                or len(self.counts) != len(other.counts)):
            raise ValueError(
                f"histogram bucket schemes differ: "
                f"(lo={self.lo}, per_decade={self.per_decade}, "
                f"n={len(self.counts)}) vs (lo={other.lo}, "
                f"per_decade={other.per_decade}, n={len(other.counts)})")
        for i, c in enumerate(other.counts):
            self.counts[i] += c
        self.count += other.count
        self.sum += other.sum
        if other.min < self.min:
            self.min = other.min
        if other.max > self.max:
            self.max = other.max
        return self

    def bucket_index(self, le: float) -> int:
        """Index of the bucket whose upper edge is ``le`` (inverse of
        the exposition's edge math; tolerant of float round-trips —
        buckets are ~10% apart, a ``%.6g`` parse-back is ~1e-6 off)."""
        if le <= self.lo * 10.0 ** (0.5 / self.per_decade):
            return 0
        i = int(round((math.log10(le) - self._log_lo) * self.per_decade))
        return max(0, min(i, len(self.counts) - 2))

    @classmethod
    def from_prom(cls, series: dict, name: str, *,
                  labels: str = "",
                  lo: float = 1e-6, hi: float = 4000.0,
                  per_decade: int = 24) -> "LogHistogram":
        """Rebuild a histogram from its own text exposition (a
        ``parse_prometheus`` dict) — the subprocess half of fleet
        aggregation (scrape-and-merge).  The exposition's cumulative
        buckets de-accumulate back into per-bucket counts on the SAME
        scheme, so a scrape-reconstructed histogram merges bucket-
        exactly with a live one; ``_sum``/``_count`` and the
        ``_min``/``_max`` gauges restore the exact scalar fields.
        ``labels`` selects one series of a labeled histogram family
        (e.g. ``'program="paged_decode"'`` for ``serve_program_ms`` —
        the exact label text :meth:`prom_lines` emitted)."""
        h = cls(lo=lo, hi=hi, per_decade=per_decade)
        lab = f"{{{labels}}}" if labels else ""
        h.count = int(series.get(f"{name}_count{lab}", 0))
        h.sum = float(series.get(f"{name}_sum{lab}", 0.0))
        if h.count:
            h.min = float(series.get(f"{name}_min{lab}", float("inf")))
            h.max = float(series.get(f"{name}_max{lab}", float("-inf")))
        buckets = []
        inner = f"{labels}," if labels else ""
        prefix = f"{name}_bucket{{{inner}le=\""
        for key, v in series.items():
            if key.startswith(prefix) and not key.startswith(
                    f"{name}_bucket{{{inner}le=\"+Inf"):
                buckets.append((float(key[len(prefix):-2]), int(v)))
        buckets.sort()
        acc = 0
        for le, cum in buckets:
            h.counts[h.bucket_index(le)] = cum - acc
            acc = cum
        h.counts[-1] = h.count - acc   # overflow: past the last edge
        return h

    def prom_lines(self, name: str, *, labels: str = "",
                   typed: bool = True) -> list[str]:
        """Prometheus text-exposition lines for this histogram —
        DENSE cumulative ``_bucket{le=}`` (EVERY bucket edge in the
        scheme, zero-traffic ones included, plus ``+Inf``), then
        ``_sum``/``_count`` and exact ``_min``/``_max`` gauges.
        ``labels`` prepends extra label pairs to every bucket and
        suffixes the scalar series (the ``serve_program_ms{program=}``
        family); ``typed=False`` suppresses the ``# TYPE`` header so a
        labeled family emits it once, on its first member.

        Dense matters for aggregation: every engine shares one bucket
        scheme, so every replica's exposition carries the IDENTICAL
        full ``le`` label set — a recording rule's ``sum by (le)`` (and
        :meth:`from_prom` scrape-and-merge) stays monotone and complete
        even when the replicas reached different depths.  Sparse
        nonzero-only buckets broke exactly that: a replica missing an
        intermediate ``le`` made the cross-instance sum non-monotone,
        and stopping at each replica's own deepest reached bucket would
        still drop its total from the deeper sums
        (tests/test_serve_fleet.py pins the merged-vs-pooled bucket
        equality).  Cost: ~230 lines per histogram — a few tens of KB
        per scrape, the price of correct `histogram_quantile` over
        `sum by (le)`."""
        out = [f"# TYPE {name} histogram"] if typed else []
        inner = f"{labels}," if labels else ""
        lab = f"{{{labels}}}" if labels else ""
        acc = 0
        for i in range(len(self.counts) - 1):
            acc += self.counts[i]
            le = self.lo if i == 0 else self.edge(i - 1)
            out.append(f'{name}_bucket{{{inner}le="{le:.6g}"}} {acc}')
        out.append(f'{name}_bucket{{{inner}le="+Inf"}} {self.count}')
        # .17g: enough digits to round-trip a float64 exactly, so a
        # scrape reconstruction (from_prom) recovers sum/min/max EXACTLY
        out.append(f"{name}_sum{lab} {self.sum:.17g}")
        out.append(f"{name}_count{lab} {self.count}")
        if self.count:
            # exact extremes ride as gauges so a scrape reconstruction
            # (from_prom) merges with exact min/max, not bucket edges
            if typed:
                out.append(f"# TYPE {name}_min gauge")
            out.append(f"{name}_min{lab} {self.min:.17g}")
            if typed:
                out.append(f"# TYPE {name}_max gauge")
            out.append(f"{name}_max{lab} {self.max:.17g}")
        return out


# ---------------------------------------------------------------------------
# Event-stream views (module-level so the fleet controller can render
# ANY event list — a live ring, a flight-file postmortem, a carried
# migration tail — not just its own recorder's)
# ---------------------------------------------------------------------------


def spans_from_events(evs: list) -> dict:
    """Per-request lifecycle spans from a SORTED event stream:
    ``{rid: [(phase, t0, t1), ...]}`` with phases ``queue``
    (submit→admit, re-opened by preemption), ``prefill``
    (admit→prefill_done) and ``decode`` (prefill_done→retire).  A phase
    still open at the newest event closes there (an in-flight request's
    span is the stream's honest horizon)."""
    if not evs:
        return {}
    end = evs[-1][0]
    out: dict[str, list] = {}
    open_: dict[str, tuple] = {}   # rid -> (phase, t0)

    def close(rid, ts):
        ph = open_.pop(rid, None)
        if ph is not None:
            out.setdefault(rid, []).append((ph[0], ph[1], ts))

    for ts, step, etype, rid, data in evs:
        if rid is None:
            continue
        if etype == "submit":
            close(rid, ts)
            open_[rid] = ("queue", ts)
        elif etype == "admit":
            close(rid, ts)
            open_[rid] = ("prefill", ts)
        elif etype == "prefill_done":
            close(rid, ts)
            open_[rid] = ("decode", ts)
        elif etype == "preempt":
            close(rid, ts)
            open_[rid] = ("queue", ts)
        elif etype == "migrate_out":
            # the request LEFT this timeline: close without reopening,
            # or the source track would render it active until the
            # stream horizon — hours after it migrated away
            close(rid, ts)
        elif etype == "migrate_in":
            # the journey continues HERE: the carried tail seeded ahead
            # of this event holds the source-side phases, and the
            # adopted row is decoding (in place) or re-queued — either
            # way a fresh span opens at the adoption instant
            close(rid, ts)
            open_[rid] = ("decode" if (data or {}).get("in_place")
                          else "queue", ts)
        elif etype == "retire":
            close(rid, ts)
            out.setdefault(rid, [])
    for rid in list(open_):
        close(rid, end)
    return out


def events_to_perfetto(events: list, *, pid: int = ENGINE_PID,
                       process_name: str =
                       "serve engine (flight recorder)",
                       tids_out: Optional[dict] = None) -> list[dict]:
    """Render one event stream as Chrome-trace events under ``pid``:
    a process_name meta, one thread per request with its whole-request
    span enclosing the lifecycle phase spans, and instants for point
    events.  The fleet merge (serve/fleet.py) calls this once per
    replica with a distinct pid, so one file holds every replica's
    timeline side by side; :meth:`FlightRecorder.to_perfetto` is the
    single-engine wrapper.  ``tids_out`` (optional dict) is filled with
    the ``rid -> tid`` assignment so :func:`link_migration_flows` can
    anchor flow arrows on the request's own thread (a flow event on a
    slice-less tid would not bind in ui.perfetto.dev)."""
    evs = sorted(events, key=lambda e: (e[0], e[1]))
    trace: list[dict] = [{
        "ph": "M", "pid": pid, "tid": 0,
        "name": "process_name",
        "args": {"name": process_name},
    }]
    tids: dict[str, int] = {}

    def tid_of(rid):
        if rid not in tids:
            tids[rid] = len(tids) + 1
            trace.append({"ph": "M", "pid": pid,
                          "tid": tids[rid], "name": "thread_name",
                          "args": {"name": rid}})
        return tids[rid]

    def us(ts):
        return ts * 1e6

    # Whole-request spans enclose the phase spans (first event ->
    # retire / stream horizon).
    first: dict[str, float] = {}
    last: dict[str, float] = {}
    for ts, step, etype, rid, data in evs:
        if rid is None:
            continue
        first.setdefault(rid, ts)
        last[rid] = ts
    for rid, phases in spans_from_events(evs).items():
        t0, t1 = first[rid], last[rid]
        trace.append({"ph": "X", "pid": pid,
                      "tid": tid_of(rid), "cat": "request",
                      "name": f"request {rid}", "ts": us(t0),
                      "dur": max(us(t1) - us(t0), 1.0)})
        for name, p0, p1 in phases:
            trace.append({"ph": "X", "pid": pid,
                          "tid": tid_of(rid), "cat": "phase",
                          "name": name, "ts": us(p0),
                          "dur": max(us(p1) - us(p0), 1.0)})
    for ts, step, etype, rid, data in evs:
        if etype in ("submit", "admit", "prefill_done"):
            continue  # phase boundaries, already spans
        args = {"step": step}
        if data:
            args.update(data)
        trace.append({"ph": "i", "s": "t" if rid else "g",
                      "pid": pid,
                      "tid": tid_of(rid) if rid else 0,
                      "cat": "engine", "name": etype, "ts": us(ts),
                      "args": args})
    if tids_out is not None:
        tids_out.update(tids)
    return trace


def link_migration_flows(sources: list,
                         tids: Optional[dict] = None) -> list[dict]:
    """Perfetto flow arrows for cross-replica request journeys.

    ``sources`` is ``[(pid, events), ...]`` — one entry per replica
    timeline already rendered into a merged file; ``tids`` maps
    ``pid -> {rid: tid}`` (the ``tids_out`` of each
    :func:`events_to_perfetto` call) so the arrows anchor on the
    request's own thread, where its slices live — Perfetto binds a
    flow event to the slice enclosing its timestamp on the same
    pid/tid, so a slice-less tid would drop the arrow.  For every
    ``migrate_in`` (or disagg ``push_in``) event, emit a flow-start
    (``ph: "s"``) anchored at
    the hand-off point on the SOURCE replica and a flow-finish
    (``ph: "f"``) at the adoption instant on the target, sharing one
    flow id — ui.perfetto.dev draws the arrow, making a migrated
    request ONE connected journey across replica tracks.

    The source anchor prefers the exact ``migrate_out`` twin (the
    cooperative drain path emits one, carrying the same ``flow`` id);
    on the crash path the source process died before any
    ``migrate_out`` could be recorded, so the anchor falls back to the
    source's newest event for that rid preceding the adoption (the
    postmortem flight file is where those events survive)."""
    flows: list[dict] = []
    # index: flow id -> (pid, ts) of the matching migrate_out
    out_by_flow: dict = {}
    # rid -> [(ts, pid)] of every event, for the crash-path fallback
    rid_events: dict = {}
    for pid, events in sources:
        for ev in sorted(events, key=lambda e: (e[0], e[1])):
            ts, step, etype, rid, data = ev
            if rid is not None:
                rid_events.setdefault(rid, []).append((ts, pid))
            if (etype in ("migrate_out", "push_out")
                    and data and data.get("flow")):
                out_by_flow[data["flow"]] = (pid, ts)

    def emit(ph, pid, rid, ts, fid, **extra):
        flows.append({"ph": ph, "pid": pid,
                      "tid": (tids or {}).get(pid, {}).get(rid, 0),
                      "cat": "migration", "name": "migrate",
                      "id": fid, "args": {"rid": rid},
                      "ts": ts * 1e6, **extra})

    for pid, events in sources:
        for ts, step, etype, rid, data in events:
            if etype not in ("migrate_in", "push_in") or rid is None:
                continue
            fid = (data or {}).get("flow") or f"{rid}#?"
            src = out_by_flow.get(fid)
            if src is None:
                # crash path: anchor at the newest source-side event
                # before the adoption, on a DIFFERENT pid
                cands = sorted((t, p) for t, p in rid_events.get(rid, ())
                               if p != pid and t <= ts)
                src = (cands[-1][1], cands[-1][0]) if cands else None
            if src is None:
                continue
            emit("s", src[0], rid, src[1], fid)
            emit("f", pid, rid, ts, fid, bp="e")
    return flows


# ---------------------------------------------------------------------------
# Step spans
# ---------------------------------------------------------------------------


class _NoSpan:
    """What :meth:`FlightRecorder.span` hands back at ``level <= 0``: ONE
    shared object, no annotation built, no clock read."""

    __slots__ = ()

    def start(self, t_ns):
        pass

    def stop(self, t_ns, compiled=False):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NO_SPAN = _NoSpan()


class _Span:
    """One open step span.  ``with`` reads the clock itself; a caller that
    already holds a ``perf_counter_ns`` pair for its own accounting
    (``ServeEngine.step``, ``CountingJit.__call__``) hands its readings
    to :meth:`start` / :meth:`stop` instead, so one pair feeds both."""

    __slots__ = ("_rec", "name", "_ann", "_t0", "_child_ns")

    def __init__(self, rec, name, ann):
        self._rec, self.name, self._ann = rec, name, ann
        self._t0 = self._child_ns = 0

    def start(self, t_ns: int) -> None:
        self._ann.__enter__()
        self._rec._open.append(self)
        self._t0 = t_ns

    def stop(self, t_ns: int, compiled: bool = False) -> None:
        """Close the span at ``t_ns``.  Its time is its parent's child
        time — the parent is the span that was open when this one
        started (one stack, the engine's one thread).  ``compiled``
        books the call under ``<name>.compile``: a compile stall inside
        a step has to be charged somewhere, and not to the steady-state
        row of the program it stalled."""
        self._ann.__exit__(None, None, None)
        open_ = self._rec._open
        # an exception that skipped a child's stop left it above this one
        while open_ and open_.pop() is not self:
            pass
        ns = t_ns - self._t0
        if open_:
            open_[-1]._child_ns += ns
        key = self.name + ".compile" if compiled else self.name
        acc = self._rec.phases.get(key)
        if acc is None:
            acc = self._rec.phases[key] = [0, 0, 0]
        acc[0] += 1
        acc[1] += ns
        acc[2] += ns - self._child_ns

    def __enter__(self):
        self.start(time.perf_counter_ns())
        return self

    def __exit__(self, *exc):
        self.stop(time.perf_counter_ns())
        return False


# ---------------------------------------------------------------------------
# The flight recorder
# ---------------------------------------------------------------------------


class FlightRecorder:
    """Bounded ring of typed engine events (module docstring).

    ``level`` gates the hot path: 0 records nothing (``emit`` returns
    before touching the ring), 1 records lifecycle + failure events,
    2 adds per-dispatch detail (``prefill_chunk``).  ``capacity`` bounds
    memory — the ring drops its oldest events, ``dropped`` counts them.

    Events are plain tuples ``(ts, step, type, rid, data)``: ``ts`` is
    wall time (``time.monotonic`` — deliberately NOT the engine clock,
    which chaos tests fake and the injector's ``clock`` point meters),
    ``step`` the engine's monotonic iteration index, ``rid`` a request
    id or ``None`` for engine-scoped events, ``data`` a small dict or
    ``None``.
    """

    def __init__(self, capacity: int = 4096, level: int = 1,
                 clock=time.monotonic):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self.level = int(level)
        self._clock = clock
        self._ring: deque = deque(maxlen=capacity)
        self.step = 0
        self.emitted = 0
        #: step-span table: name -> [calls, total ns, self ns].  The
        #: engine's ``ServeMetrics`` shares this dict (``attach_recorder``)
        #: and reports it: ``summary()["phases"]``, ``to_prometheus()``
        self.phases: dict = {}
        self._open: list = []      # stack of open spans

    # -- hot path ---------------------------------------------------------

    def set_step(self, step: int) -> None:
        self.step = step

    def emit(self, etype: str, rid: Optional[str] = None,
             **data) -> None:
        """Append one event — ring append only (the hot-path contract)."""
        if self.level <= 0:
            return
        self.emitted += 1
        self._ring.append((self._clock(), self.step, etype, rid,
                           data or None))

    def span(self, name: str, **attrs):
        """A step span named ``name`` (a :data:`STEP_PHASES` member):
        ``with recorder.span("decode.stage"): ...``.  At ``level <= 0``
        the shared :data:`NO_SPAN`.  Otherwise a
        ``TraceAnnotation("serve." + name, step=<engine step>, **attrs)``
        — free while no profile runs; under one, an event on the host
        plane that joins the ring's point events by ``step`` — whose
        closing adds to :attr:`phases`.  Not for per-token or
        per-request work: a step opens about a dozen."""
        if self.level <= 0:
            return NO_SPAN
        return _Span(self, name, TraceAnnotation(
            "serve." + name, step=self.step, **attrs))

    @property
    def dropped(self) -> int:
        """Events the bounded ring has already forgotten."""
        return self.emitted - len(self._ring)

    # -- views ------------------------------------------------------------

    def events(self) -> list[tuple]:
        return list(self._ring)

    def tail(self, n: int = 256) -> list[list]:
        """The newest ``n`` events, JSON-safe (rides snapshots and the
        postmortem flush)."""
        evs = list(self._ring)[-n:]
        return [[float(ts), int(step), etype, rid, data]
                for ts, step, etype, rid, data in evs]

    def seed(self, events) -> None:
        """Re-append events carried across a restore (snapshot tail) —
        the restored engine's ring then holds its previous life's trail
        ahead of its own events."""
        for ev in events:
            try:
                ts, step, etype, rid, data = ev
            except (TypeError, ValueError):
                continue
            self.emitted += 1
            self._ring.append((float(ts), int(step), str(etype), rid,
                               data))

    # -- per-request lifecycle spans --------------------------------------

    def spans(self, evs: Optional[list] = None) -> dict:
        """Reconstruct per-request lifecycle spans from the event
        stream: ``{rid: [(phase, t0, t1), ...]}`` with phases ``queue``
        (submit→admit, re-opened by preemption), ``prefill``
        (admit→prefill_done) and ``decode`` (prefill_done→retire).  A
        phase still open at the newest event closes there (an in-flight
        request's span is the ring's honest horizon).  ``evs`` lets a
        caller pass ONE snapshot of the ring (``to_perfetto`` does — the
        engine may be emitting concurrently, and two reads of the live
        deque could disagree on which requests exist)."""
        if evs is None:
            evs = sorted(self._ring, key=lambda e: (e[0], e[1]))
        return spans_from_events(evs)

    # -- Perfetto / Chrome trace export -----------------------------------

    def to_perfetto(self) -> dict:
        """The ring as a Chrome trace (``{"traceEvents": [...]}``):
        one thread per request carrying its lifecycle phase spans
        (``ph: "X"``) under a whole-request span, instants (``ph: "i"``)
        for point events, all on :data:`ENGINE_PID` so
        ``runtime.profiling.merge_rank_traces`` folds the engine
        timeline into the device profiler's merged view."""
        return {"traceEvents": events_to_perfetto(list(self._ring))}

    def export_perfetto(self, path: str) -> str:
        """Write :meth:`to_perfetto` to ``path`` (gzipped when the name
        ends ``.gz`` — the profiler's own trace format)."""
        return write_trace(self.to_perfetto(), path)

    def export_profile(self, job_dir: str, rank: int = 0) -> str:
        """Drop the engine timeline where
        :func:`runtime.profiling.merge_rank_traces` globs per-rank
        traces (``{job_dir}/rank{rank}/engine.trace.json.gz``) — run a
        ``group_profile`` capture into the same ``job_dir``, call this,
        then merge: ONE ui.perfetto.dev file holds the device timeline
        and the engine's side by side (docs/observability.md has the
        recipe)."""
        out = os.path.join(job_dir, f"rank{rank}", "engine.trace.json.gz")
        return self.export_perfetto(out)

    # -- postmortem flush -------------------------------------------------

    def flush(self, directory: str, *, reason: str,
              statline: Optional[str] = None,
              extra: Optional[dict] = None) -> str:
        """Write the ring to ``{directory}/flight_<step>.json`` — the
        postmortem trail for the supervisor and the chaos harness.  Only
        called OFF the hot path (fault/quarantine/watchdog/crash seams);
        best-effort durable (flush + fsync) so the file survives the
        process dying right after.  ``extra`` merges additional JSON-safe
        sections into the document (the fleet controller rides its
        router decision audit along — serve/fleet.py)."""
        os.makedirs(directory, exist_ok=True)
        path = os.path.join(directory, f"flight_{self.step}.json")
        doc = {
            "reason": reason,
            "step": self.step,
            "wall": time.time(),
            "emitted": self.emitted,
            "dropped": self.dropped,
            "statline": statline,
            "events": self.tail(self.capacity),
        }
        if extra:
            doc.update(extra)
        from triton_dist_tpu.serve.integrity import atomic_write_json
        # JSON-safe normalization first (ring events may carry numpy
        # scalars etc. — the old ``default=str`` behavior), then the
        # shared digest-stamping atomic writer: the postmortem file is
        # read back on the crash path (manifest_from_journal's event
        # tails), so it gets the same integrity framing as every other
        # durable serving artifact.
        doc = json.loads(json.dumps(doc, default=str))
        try:
            return atomic_write_json(path, doc)
        except OSError:
            return path  # best-effort durable, as before


def write_trace(doc: dict, path: str) -> str:
    """Write a Chrome-trace document to ``path`` (gzipped when the name
    ends ``.gz`` — the device profiler's own format, so the file lands
    wherever ``merge_rank_traces`` globs)."""
    text = json.dumps(doc, default=str)
    parent = os.path.dirname(os.path.abspath(path))
    os.makedirs(parent, exist_ok=True)
    if path.endswith(".gz"):
        with gzip.open(path, "wt") as f:
            f.write(text)
    else:
        with open(path, "w") as f:
            f.write(text)
    return path


def load_flight(path: str) -> dict:
    """Read a :meth:`FlightRecorder.flush` postmortem file.  Raises
    :class:`ValueError` on a whole-document digest mismatch (readers on
    the crash path already treat an unreadable flight file as
    best-effort-absent); pre-integrity files carry no digest and load
    unverified."""
    from triton_dist_tpu.serve.integrity import DOC_CRC, verify_json_doc
    with open(path) as f:
        doc = json.load(f)
    if verify_json_doc(doc) is False:
        raise ValueError(f"flight file {path}: digest mismatch")
    doc.pop(DOC_CRC, None)
    return doc


def latest_flight(directory: str) -> Optional[str]:
    """Newest ``flight_*.json`` under ``directory`` (what the
    supervisor surfaces after a crash), or ``None``."""
    try:
        names = [n for n in os.listdir(directory)
                 if n.startswith("flight_") and n.endswith(".json")]
    except OSError:
        return None
    if not names:
        return None
    paths = [os.path.join(directory, n) for n in names]
    return max(paths, key=os.path.getmtime)


# ---------------------------------------------------------------------------
# Live metrics endpoint (Prometheus text exposition over stdlib HTTP)
# ---------------------------------------------------------------------------


def start_metrics_server(metrics, port: int = 0, host: str = "127.0.0.1"):
    """Serve ``metrics.to_prometheus()`` at ``/metrics`` from a daemon
    thread (``examples/serve.py --metrics-port``).  Returns the server;
    ``server.server_address[1]`` is the bound port (pass 0 to pick a
    free one).  Stdlib only — no new dependency rides the image."""
    import http.server
    import threading

    class Handler(http.server.BaseHTTPRequestHandler):
        def do_GET(self):  # noqa: N802 — stdlib handler contract
            if self.path.rstrip("/") in ("", "/metrics".rstrip("/"),
                                         "/metrics"):
                try:
                    body = metrics.to_prometheus().encode()
                except Exception as e:  # noqa: BLE001 — the endpoint
                    # must answer even when a gauge source is mid-update
                    self.send_response(500)
                    self.end_headers()
                    self.wfile.write(repr(e).encode())
                    return
                self.send_response(200)
                self.send_header("Content-Type",
                                 "text/plain; version=0.0.4")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            else:
                self.send_response(404)
                self.end_headers()

        def log_message(self, *args):  # quiet: the engine's stdout is
            pass                       # the serving log

    srv = http.server.ThreadingHTTPServer((host, port), Handler)
    t = threading.Thread(target=srv.serve_forever, daemon=True,
                         name="serve-metrics")
    t.start()
    return srv
