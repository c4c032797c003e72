"""Fleet serving: a multi-replica router with live request migration.

PRs 1-8 made ONE engine fast, observable, and crash-resilient — but the
stack still served from exactly one process, so one wedged replica was a
full outage.  This module runs N engine replicas behind an admission
router and makes the PR 5 journal + snapshot + ``BlockManager.adopt``
machinery do what it always was underneath: a *migration* primitive
(the Llumnix live-migration / MegaScale fast-hand-off insight — the TPU
analog of the reference's producer/consumer signal-and-put hand-off,
SURVEY.md §2.5).

Three cooperating layers:

- :class:`Router` — admission placement by queue-depth / deadline
  pressure read from each replica's ``ServeMetrics`` (direct engine
  state in-process; :func:`parse_prometheus` over a ``/metrics`` scrape
  for subprocess replicas — ``scripts/serve_supervisor.py --fleet``).
  SUSPECT and DEAD replicas are circuit-broken out of the candidate
  set, so the router can never place onto a replica that stopped
  making progress.

- **Health state machine** — per replica HEALTHY → SUSPECT → DEAD,
  layered on the existing liveness signals (heartbeat staleness,
  step-progress age, a ``WatchdogTimeout`` or process-death exception
  escaping ``step``).  A SUSPECT replica stops receiving admissions and
  recovers to HEALTHY the moment progress resumes; a DEAD one is killed
  and restarted under :class:`RestartBackoff` (exponential + jitter,
  healthy-uptime budget reset — shared with the supervisor).

- **Live migration** — a dying replica's in-flight requests move to
  healthy peers and finish there.  Cooperative path:
  ``ServeEngine.drain(rids)`` gathers live KV pages + the pending token
  and the target's ``migrate_in`` adopts the row MID-STREAM (zero
  recompute).  Crash path: the dead replica's durable token journal is
  the source of truth — :func:`serve.recovery.manifest_from_journal`
  rebuilds the journal segment and the target replays the remainder
  through the exact-recompute path, bit-identical by the PR 5
  argument.  Either way the source journal records a ``mig`` receipt
  per request, so the union of all replicas' journals holds every
  token of every stream EXACTLY ONCE (the fleet chaos harness in
  tests/test_serve_fleet.py pins this: kill a replica mid-decode under
  load — every stream finishes bit-identical to the single-engine
  oracle, zero lost, zero duplicated).

See docs/serving.md "Fleet serving" for the operator recipe.
"""

from __future__ import annotations

import enum
import glob
import math
import os
import random
import sys
import time
from collections import deque
from dataclasses import dataclass
from typing import Callable, Optional

import numpy as np

from triton_dist_tpu.runtime.faults import CORRUPT_ACTIONS
from triton_dist_tpu.runtime.watchdog import WatchdogTimeout
from triton_dist_tpu.serve.metrics import (
    RequestMetrics,
    ServeMetrics,
    WindowedRate,
)
from triton_dist_tpu.serve.net import (
    ManifestCorrupt,
    NetClient,
    NetError,
    NetHTTPError,
    NetOverloaded,
    NetUnreachable,
    corrupt_wire_doc,
    decode_manifest,
    encode_manifest,
)
from triton_dist_tpu.serve.request import (
    SLO_CLASSES,
    FinishReason,
    Request,
    RequestOutput,
    slo_rank,
)
from triton_dist_tpu.serve.trace import (
    FLEET_PID,
    FLEET_REPLICA_PID_BASE,
    FlightRecorder,
    LogHistogram,
    events_to_perfetto,
    latest_flight,
    link_migration_flows,
    load_flight,
    write_trace,
)


class ReplicaState(enum.Enum):
    HEALTHY = "healthy"   # serving; admissible by the router
    SUSPECT = "suspect"   # progress stalled past suspect_after_s:
    #                       circuit-broken (no admissions), not yet dead
    DEAD = "dead"         # killed or crashed; restarting under backoff


# ---------------------------------------------------------------------------
# Restart backoff (shared by the FleetController and serve_supervisor)
# ---------------------------------------------------------------------------


class RestartBackoff:
    """Exponential restart backoff with jitter and a healthy-uptime
    budget reset.

    A crash-looping child used to restart instantly and burn its whole
    ``max_restarts`` budget in seconds; this paces restarts at
    ``base_s * 2^(attempt-1)`` capped at ``cap_s``, jittered by up to
    ``jitter`` of the delay (deterministic under ``seed`` — restarts
    across a fleet must not synchronize), and FORGIVES the attempt
    count once a life stays up ``healthy_reset_s`` (a process that ran
    healthy for an hour and then died is a fresh incident, not attempt
    #4 of a crash loop).

    Protocol: :meth:`on_start` when the process launches,
    :meth:`on_death` when it dies — returns the delay to wait before
    the next restart, or ``None`` when ``max_restarts`` is exhausted.
    """

    def __init__(self, *, base_s: float = 0.5, cap_s: float = 30.0,
                 jitter: float = 0.5, healthy_reset_s: float = 60.0,
                 max_restarts: Optional[int] = None, seed: int = 0):
        if base_s <= 0 or cap_s < base_s:
            raise ValueError(f"need 0 < base_s <= cap_s, got "
                             f"{base_s}, {cap_s}")
        if not 0 <= jitter <= 1:
            raise ValueError(f"jitter must be in [0, 1], got {jitter}")
        self.base_s = base_s
        self.cap_s = cap_s
        self.jitter = jitter
        self.healthy_reset_s = healthy_reset_s
        self.max_restarts = max_restarts
        self.attempts = 0
        self._rng = random.Random(seed)
        self._started: Optional[float] = None

    def on_start(self, now: float) -> None:
        self._started = now

    def on_death(self, now: float) -> Optional[float]:
        """Delay before the next restart, or ``None`` (budget spent)."""
        if (self._started is not None
                and now - self._started >= self.healthy_reset_s):
            self.attempts = 0
        self.attempts += 1
        if (self.max_restarts is not None
                and self.attempts > self.max_restarts):
            return None
        d = min(self.cap_s, self.base_s * 2.0 ** (self.attempts - 1))
        return d * (1.0 + self.jitter * self._rng.random())


# ---------------------------------------------------------------------------
# Router decision audit: "why did this request land there / why did it
# move", answerable post-hoc
# ---------------------------------------------------------------------------


class DecisionAudit:
    """Bounded ring of fleet control decisions (docs/observability.md
    "Fleet observability").

    The flight recorder answers *what happened*; this ring answers *why
    the router did it*: every ``route``/``migrate`` placement records
    the candidate pressures it weighed and the replica it chose, every
    ``shed`` the reason, every ``replica_state``/``restart`` the health
    evidence.  Entries are small dicts ``{"ts", "step", "kind", "rid",
    ...}`` in a ``deque(maxlen=capacity)`` — same hot-path discipline as
    the recorder (append only, no I/O) and the same bounded-memory
    contract.  The ring rides the fleet's postmortem flight flush, so a
    supervisor reading the crash file sees the routing history that led
    up to it."""

    def __init__(self, capacity: int = 1024, enabled: bool = True):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self._ring: deque = deque(maxlen=capacity)
        self.enabled = enabled
        self.recorded = 0

    def record(self, ts: float, step: int, kind: str,
               rid: Optional[str] = None, **data) -> None:
        if not self.enabled:
            return
        self.recorded += 1
        self._ring.append({"ts": ts, "step": step, "kind": kind,
                           "rid": rid, **data})

    @property
    def dropped(self) -> int:
        return self.recorded - len(self._ring)

    def entries(self) -> list[dict]:
        return list(self._ring)

    def for_request(self, rid: str) -> list[dict]:
        """Every decision that touched ``rid`` still in the ring — the
        post-hoc "why is my request on r2" query
        (``FleetController.explain``)."""
        return [e for e in self._ring if e.get("rid") == rid]


#: Controller-level Prometheus series ``FleetController.to_prometheus``
#: emits ON TOP of the aggregated per-engine ``serve_*`` series.  Every
#: name here must appear in docs/observability.md — enforced by the
#: tier-1 fleet taxonomy meta-test (tests/test_serve_fleet.py), the
#: fleet twin of the PR-8 event/fault coverage test.
FLEET_SERIES = (
    "fleet_replicas",              # gauge, {state=...}: replica counts
    "fleet_replica_state",         # gauge, {replica=,state=}: one-hot
    #                                per-replica health (alerting sees
    #                                WHICH breaker is open, not just a
    #                                count)
    "fleet_replica_role",          # gauge, {replica=,role=}: one-hot
    #                                routing role (prefill/decode/both —
    #                                the disagg tier's shape, constant
    #                                "both" for homogeneous fleets)
    "fleet_lives_total",           # counter: replica lives ever started
    "fleet_deaths_total",          # counter: replica deaths
    "fleet_migrations_total",      # counter: requests moved between replicas
    "fleet_completed_total",       # counter: requests retired fleet-wide
    "fleet_steps_total",           # counter: fleet ticks
    "fleet_pending",               # gauge: unplaced work (fleet queue)
    "fleet_deadline_miss_window",  # gauge: deadline misses in the SLO window
    "fleet_shed_window",           # gauge: sheds in the SLO window
    "fleet_deadline_miss_per_s",   # gauge: deadline-miss burn rate
    "fleet_shed_per_s",            # gauge: shed burn rate
    "fleet_audit_records_total",   # counter: router decisions recorded
    "fleet_pressure_smoothed",     # gauge: the autoscaler's EMA pressure
    #                                signal (what the high/low water
    #                                marks compare against)
    "fleet_scale_ups_total",       # counter: replicas spawned by the
    #                                autoscaler
    "fleet_scale_downs_total",     # counter: replicas retired (drained)
    #                                by the autoscaler
    "fleet_ingress_shed_total",    # counter, {slo_class=}: requests the
    #                                token-bucket admission refused at
    #                                the door
)


# ---------------------------------------------------------------------------
# Router: queue-depth / deadline pressure placement
# ---------------------------------------------------------------------------


def parse_prometheus(text: str) -> dict:
    """Parse a Prometheus text exposition into ``{series: value}`` —
    the scrape half of the router's load signal for SUBPROCESS replicas
    (``ServeMetrics.to_prometheus`` is the other end; labeled series
    keep their full left-hand side as the key)."""
    out: dict[str, float] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        parts = line.rsplit(None, 1)
        if len(parts) != 2:
            continue
        try:
            out[parts[0]] = float(parts[1])
        except ValueError:
            continue
    return out


@dataclass
class ReplicaLoad:
    """One replica's admission-pressure signal, however it was read
    (direct engine state in-process, Prometheus scrape out-of-process)."""

    queue_depth: int = 0
    running: int = 0
    max_batch: int = 1
    kv_util: float = 0.0

    @classmethod
    def from_engine(cls, engine) -> "ReplicaLoad":
        return cls(queue_depth=engine.scheduler.queue_depth,
                   running=sum(1 for s in engine.slots if s is not None),
                   max_batch=engine.max_batch,
                   kv_util=engine.bm.utilization)

    @classmethod
    def from_prometheus(cls, text: str,
                        max_batch: int = 1) -> "ReplicaLoad":
        """Load from a ``/metrics`` scrape (the subprocess path —
        docs/observability.md lists the series names)."""
        g = parse_prometheus(text)
        return cls(queue_depth=int(g.get("serve_queue_depth", 0)),
                   running=int(g.get("serve_running", 0)),
                   max_batch=max_batch,
                   kv_util=float(g.get("serve_kv_utilization", 0.0)))


def replica_state_lines(named_states) -> list[str]:
    """The ``fleet_replica_state{replica=,state=}`` one-hot exposition
    (docs/observability.md "Fleet observability") from ``[(name,
    ReplicaState), ...]`` — ONE renderer shared by
    ``FleetController.to_prometheus`` and the supervisor's subprocess
    aggregate, so the two expositions cannot drift.  The full 0/1
    matrix (not just the current state) keeps a PromQL
    ``max by (replica)`` well-defined across flips."""
    L = ["# TYPE fleet_replica_state gauge"]
    for name, state in named_states:
        for st in ReplicaState:
            L.append(f'fleet_replica_state{{replica="{name}",'
                     f'state="{st.value}"}} '
                     f'{1 if state is st else 0}')
    return L


#: Routing roles a replica can hold in a disaggregated tier
#: (docs/serving.md "Disaggregated serving").  A role is routing
#: POLICY, not capability — every replica can compute anything, so
#: availability fallbacks may cross role lines.
REPLICA_ROLES = ("prefill", "decode", "both")


def replica_role_lines(named_roles) -> list[str]:
    """The ``fleet_replica_role{replica=,role=}`` one-hot exposition
    from ``[(name, role), ...]`` — same full-matrix rendering rule as
    :func:`replica_state_lines` (a PromQL ``max by (replica)`` stays
    well-defined if roles ever flip)."""
    L = ["# TYPE fleet_replica_role gauge"]
    for name, role in named_roles:
        for r in REPLICA_ROLES:
            L.append(f'fleet_replica_role{{replica="{name}",'
                     f'role="{r}"}} {1 if role == r else 0}')
    return L


class Router:
    """Least-pressure admission placement over HEALTHY replicas.

    Pressure is ``queue_weight * queue_depth + running / max_batch +
    kv_weight * kv_util`` — queued requests dominate (one queued
    request outweighs even a fully occupied batch: it is a whole
    request of delay ahead, where a running batch is already making
    progress), batch occupancy and KV pressure break the near-ties.  A
    deadline-carrying request weighs queue depth
    ``deadline_queue_weight``× harder: its TTL burns while it waits, so
    it must land on the emptiest queue even when occupancy says
    otherwise.  Exact pressure ties rotate round-robin so a cold fleet
    does not pile onto one replica."""

    def __init__(self, *, queue_weight: float = 2.0,
                 kv_weight: float = 0.5,
                 deadline_queue_weight: float = 4.0):
        self.queue_weight = queue_weight
        self.kv_weight = kv_weight
        self.deadline_queue_weight = deadline_queue_weight
        self._rr = 0

    def pressure(self, load: ReplicaLoad, *,
                 deadline: bool = False) -> float:
        qw = self.deadline_queue_weight if deadline else self.queue_weight
        return (qw * load.queue_depth
                + load.running / max(load.max_batch, 1)
                + self.kv_weight * load.kv_util)

    def rank(self, candidates: list, *, deadline: bool = False) -> list:
        """``[(name, load)]`` sorted best-first (the migration placer
        walks this to find capacity)."""
        n = max(len(candidates), 1)
        self._rr += 1
        scored = sorted(
            (self.pressure(load, deadline=deadline),
             (i + self._rr) % n, name)
            for i, (name, load) in enumerate(candidates))
        return [name for _, _, name in scored]

    def pick(self, candidates: list, *,
             deadline: bool = False) -> Optional[str]:
        """Best HEALTHY replica for one new request, or ``None``."""
        ranked = self.rank(candidates, deadline=deadline)
        return ranked[0] if ranked else None


# ---------------------------------------------------------------------------
# In-process replica
# ---------------------------------------------------------------------------


class EngineReplica:
    """One in-process engine replica under the :class:`FleetController`.

    Each LIFE gets its own snapshot directory (``root/life<N>``): the
    life's journal is its durable request ownership record, so a crash
    migrates from the dead life's journal and the restart opens a fresh
    one — nothing a previous life owned can leak into the next (the
    handed-off requests carry ``mig`` receipts besides; belt and
    suspenders)."""

    def __init__(self, name: str, factory: Callable, root: str):
        self.name = name
        self._factory = factory
        self.root = root
        # routing role (REPLICA_ROLES) — "both" keeps homogeneous
        # fleets exactly as before; DisaggController splits the tier
        self.role = "both"
        self.engine = None
        self.life = 0
        self.state = ReplicaState.DEAD
        self.last_progress: Optional[float] = None
        self.restart_at: Optional[float] = None
        self.restarts = 0          # lives after the first
        self.death_reason: Optional[str] = None

    @property
    def life_dir(self) -> str:
        return os.path.join(self.root, f"life{self.life}")

    def start(self, now: float) -> None:
        self.life += 1
        os.makedirs(self.life_dir, exist_ok=True)
        self.engine = self._factory(self.life_dir)
        if self.engine._journal is None:
            raise ValueError(
                f"replica {self.name}: the factory must build engines "
                f"with snapshot_dir=<life dir> — the journal is what "
                f"crash migration hands off")
        self.state = ReplicaState.HEALTHY
        self.last_progress = now
        self.restart_at = None
        self.death_reason = None

    def load(self) -> ReplicaLoad:
        if hasattr(self.engine, "load"):   # RemoteReplica carries its
            return self.engine.load()      # own scrape-fed snapshot
        return ReplicaLoad.from_engine(self.engine)


# ---------------------------------------------------------------------------
# Remote replica: the engine protocol over the wire (serve/net.py)
# ---------------------------------------------------------------------------


def _manifest_header(manifest: dict) -> dict:
    """The placement-relevant manifest envelope (everything but the
    per-request records) — ONE extraction for every site that re-parks
    or re-places a rec, so a new header key cannot be silently
    stripped at one of them."""
    return {k: manifest[k] for k in
            ("format", "clock", "page_size", "kv_geom")
            if k in manifest}


class _RemoteKill:
    """``RemoteReplica._journal``: for a remote replica, "closing the
    journal" means making sure the remote WRITER is gone — the
    controller closes it right before the crash-path
    ``manifest_from_journal(mark=True)``, which must be the single
    writer on the dead life's journal.  ``kill`` is the SIGKILL hook
    the spawning factory provides (a subprocess's ``proc.kill()``; an
    :class:`serve.net.InProcessReplica`'s ``kill()``)."""

    def __init__(self, kill: Optional[Callable]):
        self._kill = kill

    def close(self) -> None:
        if self._kill is not None:
            self._kill()


class RemoteReplica:
    """A replica process over the wire, speaking the SAME protocol the
    :class:`FleetController` speaks to in-process engines — submit /
    step / drain / migrate_in / has_work / load — so a fleet of
    subprocesses drives through the identical controller code path
    (docs/serving.md "Network fleet serving").

    Fault tolerance is the client's half of the contract:

    - every call has a per-call timeout and bounded retries under
      jittered exponential backoff (:class:`serve.net.NetClient` on
      :class:`RestartBackoff`); each retry lands a ``net_retry`` event
      in this replica's ring and a ``net_retry`` entry in the fleet's
      :class:`DecisionAudit` (``attach_fleet``);
    - retries are IDEMPOTENT by protocol: submits key on the rid,
      drains/migrations on a client-generated idempotency key the
      server replays from its response cache — a retry whose first
      attempt landed is a no-op, never a duplicate stream;
    - a call that fails EVERY retry is ambiguous — it may have landed.
      The request stays optimistically BOUND to this replica
      (``_maybe``): the next successful contact re-sends it
      (idempotent, so landing twice is impossible), and if the replica
      instead dies, :meth:`unplaced` hands back exactly the ones the
      dead journal does not cover — the journal is the ownership
      record, so nothing is ever served from two replicas;
    - :meth:`step` raising :class:`~serve.net.NetUnreachable` (or
      :meth:`ping` returning ``False`` while idle) is NOT a death: the
      controller records no progress and the probe age walks the
      HEALTHY→SUSPECT→DEAD ladder — a partition is handled by the same
      machinery as a SIGKILL, just ``dead_after_s`` later.
    """

    def __init__(self, name: str, url: str, *,
                 kill: Optional[Callable] = None,
                 timeout_s: float = 5.0, retries: int = 2,
                 retry_base_s: float = 0.05, retry_cap_s: float = 2.0,
                 ping_interval_s: float = 0.2,
                 faults=None, trace_events: int = 512,
                 trace_level: int = 1, seed: int = 0):
        self.name = name
        self.url = url
        self.timeout_s = timeout_s
        self.trace = FlightRecorder(capacity=trace_events,
                                    level=trace_level)
        self.audit: Optional[DecisionAudit] = None
        self.client = NetClient(url, name=name, timeout_s=timeout_s,
                                retries=retries,
                                retry_base_s=retry_base_s,
                                retry_cap_s=retry_cap_s, seed=seed,
                                faults=faults,
                                on_retry=self._on_retry)
        self.metrics = ServeMetrics()   # client-side stub: the fleet
        #                                 aggregate for subprocesses is
        #                                 the scrape path (merge_scrapes)
        self._journal = _RemoteKill(kill)
        self.max_queue: Optional[int] = None
        self.last_contact: Optional[float] = None
        self.ping_interval_s = ping_interval_s
        self._last_ping: Optional[tuple] = None   # (mono_ts, ok)
        self._load = ReplicaLoad()
        self._live: dict[str, dict] = {}
        self._maybe_reqs: dict[str, dict] = {}
        self._maybe_migs: list[dict] = []
        self._bounced: list[tuple] = []   # (header, rec) to re-place
        self._drains = 0
        self._migs = 0
        self._pushes = 0
        # prefill-complete rids the remote engine reported on its last
        # health answer — the disagg controller's PUSH trigger
        self._push_ready: list[str] = []

    def attach_fleet(self, audit: DecisionAudit) -> None:
        """Wire this client's retry reporting into the fleet's decision
        audit (the controller calls it after every ``start``)."""
        self.audit = audit

    def _on_retry(self, op: str, attempt: int, delay: float,
                  err: str) -> None:
        self.trace.emit("net_retry", None, replica=self.name, op=op,
                        attempt=attempt, delay_s=round(delay, 4),
                        err=err)
        if self.audit is not None:
            self.audit.record(time.monotonic(), -1, "net_retry",
                              replica=self.name, op=op, attempt=attempt,
                              delay_s=round(delay, 4))

    # -- liveness / load ---------------------------------------------------

    def _absorb_health(self, h: dict) -> bool:
        from triton_dist_tpu.serve.net import NET_PROTOCOL

        p = h.get("protocol", NET_PROTOCOL)
        if p != NET_PROTOCOL:
            # fail LOUD, not quietly-unhealthy: a wire-version mismatch
            # is an operator error (stale replica binary), and treating
            # it as a partition would just burn the restart budget.
            # Plain RuntimeError deliberately — NetError handlers must
            # not swallow it.
            raise RuntimeError(
                f"replica {self.name} speaks net protocol {p}; this "
                f"client speaks {NET_PROTOCOL} — mismatched builds")
        if not h.get("ok"):
            return False
        self.last_contact = time.monotonic()
        self.max_queue = h.get("max_queue")
        self._load = ReplicaLoad(
            queue_depth=int(h.get("queue_depth", 0)),
            running=int(h.get("running", 0)),
            max_batch=int(h.get("max_batch", 1)),
            kv_util=float(h.get("kv_util", 0.0)))
        self._push_ready = [str(r) for r in h.get("push_ready", ())]
        return True

    def ping(self, force: bool = False) -> bool:
        """One health probe — a SINGLE short-timeout attempt, no retry
        ladder, throttled to ``ping_interval_s`` (the health ladder's
        granularity is ``suspect_after_s``, so the controller's
        per-tick idle pings need no finer resolution and a blackholed
        replica must not cost the single-threaded loop a timeout on
        EVERY tick).  ``False`` means unreachable OR the remote serve
        loop stopped pumping — either way, no progress to prove."""
        now = time.monotonic()
        if (not force and self._last_ping is not None
                and now - self._last_ping[0] < self.ping_interval_s):
            return self._last_ping[1]
        try:
            h = self.client.call("health", "/health", retries=0,
                                 timeout_s=min(self.timeout_s, 1.0))
            ok = self._absorb_health(h)
        except NetError:
            ok = False
        self._last_ping = (time.monotonic(), ok)
        return ok

    def wait_ready(self, deadline_s: float = 60.0,
                   poll_s: float = 0.1) -> "RemoteReplica":
        """Block until the replica answers /health (spawning factories
        call this so the controller never adopts a half-started child);
        raises :class:`NetError` past the bounded deadline."""
        t0 = time.monotonic()
        while time.monotonic() - t0 < deadline_s:
            if self.ping():
                return self
            time.sleep(poll_s)
        raise NetError(f"replica {self.name} at {self.url} not ready "
                       f"within {deadline_s}s")

    def load(self) -> ReplicaLoad:
        return self._load

    # -- the engine protocol ----------------------------------------------

    def submit(self, req: Request):
        from triton_dist_tpu.serve.engine import QueueFull

        rid = req.request_id
        doc = {"rid": rid,
               "prompt": [int(x) for x in np.asarray(req.prompt)],
               "params": req.params.to_dict(), "slo": req.slo_class,
               "trace": req.trace}
        self._live[rid] = {"acked": 0, "tokens": [], "cb": req.on_token,
                           "done": False,
                           "prompt": np.asarray(req.prompt, np.int32),
                           "req": req}
        try:
            resp = self.client.call("submit", "/submit", method="POST",
                                    body=doc)
        except NetOverloaded as e:
            # the replica answered 429 on every paced retry: admission
            # pressure is a DEFINITIVE verdict (never ambiguous — the
            # rid-keyed replay cache would have answered dup had any
            # attempt landed), and the fleet word for a full queue is
            # QueueFull: the controller walks to the next candidate or
            # sheds under the bounded-admission contract
            del self._live[rid]
            raise QueueFull(f"{self.name}: {e}") from e
        except NetHTTPError as e:
            # the replica ANSWERED with an error: definitive, not
            # ambiguous — same behavior as an in-process engine
            # raising at submit()
            del self._live[rid]
            raise ValueError(
                f"replica {self.name} rejected submit: {e}") from e
        except NetError:
            # ambiguous: it may have landed.  Bind it here (optimistic)
            # — reconciliation re-sends idempotently on the next
            # successful contact, and death resolves through the
            # journal (unplaced()).  Placing it elsewhere NOW could
            # serve one stream from two replicas.
            self._maybe_reqs[rid] = doc
            return None
        if resp.get("queue_full"):
            del self._live[rid]
            raise QueueFull(resp.get("why",
                                     f"{self.name}: queue at bound"))
        if resp.get("rejected"):
            del self._live[rid]
            raise ValueError(f"replica {self.name} rejected submit: "
                             f"{resp.get('why')}")
        if resp.get("shed"):
            del self._live[rid]
            rm = RequestMetrics(arrival_time=time.monotonic())
            rm.finish_time = rm.arrival_time
            return RequestOutput(
                request_id=rid, prompt=req.prompt, token_ids=[],
                finish_reason=FinishReason(resp["reason"]), metrics=rm,
                error=resp.get("error"))
        return None

    def migrate_in(self, manifest: dict, *, on_token=None) -> dict:
        self._migs += 1
        return self._send_manifest(manifest, on_token, op="migrate_in",
                                   key=f"{self.name}-mig-{self._migs}")

    def admit_pushed(self, manifest: dict, *, on_token=None) -> dict:
        """Adopt a disagg PUSH hand-off over the wire (``POST /push``
        — the engine-side ``admit_pushed``).  Same retry / idempotency
        / ambiguity discipline as :meth:`migrate_in`, under its own key
        namespace and server cache kind."""
        self._pushes += 1
        return self._send_manifest(manifest, on_token, op="push",
                                   key=f"{self.name}-push-{self._pushes}")

    def _send_manifest(self, manifest: dict, on_token, *, op: str,
                       key: str) -> dict:
        from triton_dist_tpu.serve.recovery import _resolve_callback

        enc = encode_manifest(manifest)
        # The integrity fault point's wire-blob site: damage a COPY of
        # the encoded doc in flight (the clean ``enc`` is what a later
        # ambiguous-call reconcile re-sends — transport rot must not
        # become persistent sender state).  The receiver's digest check
        # rejects with 400 → the rejected-path fallback below.
        wire = enc
        faults = getattr(self.client, "faults", None)
        if faults is not None:
            rids_hint = [rec.get("rid")
                         for rec in manifest.get("requests", ())]
            act = faults.fire("integrity", op=op,
                              rid=rids_hint[0] if rids_hint else None)
            if act in CORRUPT_ACTIONS:
                wire = corrupt_wire_doc(enc, act)
        rids = [rec["rid"] for rec in manifest.get("requests", ())]
        for rec in manifest.get("requests", ()):
            rid = rec["rid"]
            toks = [int(t) for t in rec.get("tokens", [])]
            self._live[rid] = {
                "acked": len(toks), "tokens": toks,
                "cb": _resolve_callback(on_token, rid), "done": False,
                "prompt": np.asarray(rec.get("prompt", []), np.int32),
                "req": None}
        try:
            resp = self.client.call(
                op, f"/{op}", method="POST",
                body={"manifest": wire, "key": key},
                timeout_s=max(self.timeout_s, 30.0))
        except NetHTTPError as e:
            # answered-with-error is definitive: nothing was adopted —
            # report every rec rejected so the placer walks on
            for rid in rids:
                self._live.pop(rid, None)
            return {"adopted": [], "requeued": [],
                    "rejected": {rid: str(e) for rid in rids}}
        except NetError:
            # ambiguous — bound here until reconciled or resolved by
            # the journal at death (same argument as submit)
            self._maybe_migs.append({"enc": enc, "key": key,
                                     "manifest": manifest, "op": op})
            return {"adopted": [], "requeued": rids, "rejected": {}}
        for rid in resp.get("rejected", {}):
            self._live.pop(rid, None)
        return {"adopted": resp.get("adopted", []),
                "requeued": resp.get("requeued", []),
                "rejected": resp.get("rejected", {})}

    def drain(self, rids: Optional[list] = None, *,
              include_kv: bool = True, push: bool = False) -> dict:
        """Cooperative migrate-out over the wire.  The idempotency key
        makes a retried drain return the CACHED manifest — the engine
        drains once however flaky the ack path was.  Raises
        :class:`NetError` when the replica is unreachable (a
        cooperative drain needs a live peer; the crash path is the
        journal).

        The key advances only on SUCCESS: a drain that raised may have
        LANDED (receipts written, state released, manifest cached) —
        the next :meth:`drain` call re-uses the outstanding key, so it
        recovers exactly that manifest instead of asking a drained
        engine for its (now empty) in-flight set and stranding the
        handed-off streams.  (The server keeps the cached response for
        ``cache_ttl_s`` — retry within it; past that, a dead replica's
        journal still has the receipts but the cooperative manifest is
        gone.)"""
        key = f"{self.name}-drain-{self._drains + 1}"
        faults = getattr(self.client, "faults", None)
        # A drain-RESPONSE corrupted in flight is recoverable without
        # re-draining: the server cached the clean manifest under this
        # key (the engine drained once), so a bounded retry with the
        # SAME key replays it.  Corruption that survives the retries is
        # a dead transport for state-bearing purposes: raise NetError so
        # the controller walks the death ladder and recovers from the
        # journal instead of adopting rot.
        last: Optional[ManifestCorrupt] = None
        for _ in range(3):
            resp = self.client.call(
                "drain", "/drain", method="POST",
                body={"rids": rids, "key": key, "include_kv": include_kv,
                      "push": push},
                timeout_s=max(self.timeout_s, 30.0))
            doc = resp["manifest"]
            if faults is not None:   # wire-blob site, receive direction
                act = faults.fire("integrity", op="drain")
                if act in CORRUPT_ACTIONS:
                    doc = corrupt_wire_doc(doc, act)
            try:
                m = decode_manifest(doc)
            except ManifestCorrupt as e:
                last = e
                continue
            self._drains += 1
            for rec in m.get("requests", ()):
                self._live.pop(rec["rid"], None)
            return m
        raise NetError(
            f"drain manifest from {self.name} corrupt after retries "
            f"({last}) — treating the replica as unrecoverable over "
            f"the wire; the journal crash path has the receipts")

    def push_ready(self) -> list[str]:
        """Prefill-complete rids from the last health answer — the
        remote twin of ``ServeEngine.push_ready`` (stale by at most one
        poll interval; the push itself re-validates via the drain)."""
        return list(self._push_ready)

    def push_out(self, rid: str) -> dict:
        """Extract ``rid``'s PUSH hand-off manifest (a single-request
        drain framed as ``push_out`` — ``/drain`` with ``push=true``).
        Raises :class:`NetError` when the replica is unreachable; the
        drain key replays a landed-but-unacked attempt."""
        return self.drain([rid], push=True)

    def has_work(self) -> bool:
        return (any(not s["done"] for s in self._live.values())
                or bool(self._maybe_reqs) or bool(self._maybe_migs))

    def _reconcile(self) -> None:
        """Re-send every ambiguous call on a proven-reachable replica.
        Idempotent by protocol: a maybe that landed answers ``dup`` /
        the cached response; one that never arrived lands now."""
        for rid, doc in list(self._maybe_reqs.items()):
            try:
                resp = self.client.call("submit", "/submit",
                                        method="POST", body=doc)
            except NetHTTPError:
                # answered-with-error: definitively not here — hand it
                # back for re-placement (a genuinely invalid request
                # then fails at its next placement exactly like an
                # in-process submit would)
                s = self._live.pop(rid, None)
                del self._maybe_reqs[rid]
                if s is not None and s.get("req") is not None:
                    self._bounced.append(("req", s["req"]))
                continue
            except NetError:
                return
            if resp.get("rejected"):
                s = self._live.pop(rid, None)
                del self._maybe_reqs[rid]
                if s is not None and s.get("req") is not None:
                    self._bounced.append(("req", s["req"]))
                continue
            if resp.get("queue_full"):
                # the replica ANSWERED queue_full, so the ambiguity is
                # resolved: the request is definitively NOT here (a
                # landed first attempt would have answered dup).  Hand
                # it back for fleet re-placement — pinning it to a
                # persistently-full replica would starve it while
                # others sit idle.
                s = self._live.pop(rid, None)
                del self._maybe_reqs[rid]
                if s is not None and s.get("req") is not None:
                    self._bounced.append(("req", s["req"]))
                continue
            del self._maybe_reqs[rid]
        for m in list(self._maybe_migs):
            op = m.get("op", "migrate_in")
            try:
                resp = self.client.call(
                    op, f"/{op}", method="POST",
                    body={"manifest": m["enc"], "key": m["key"]},
                    timeout_s=max(self.timeout_s, 30.0))
            except NetHTTPError:
                # definitive: nothing adopted — bounce every rec back
                # to the controller for re-placement elsewhere
                self._maybe_migs.remove(m)
                hdr = _manifest_header(m["manifest"])
                for rec in m["manifest"].get("requests", ()):
                    self._live.pop(rec["rid"], None)
                    self._bounced.append(("rec", hdr, rec))
                continue
            except NetError:
                return
            self._maybe_migs.remove(m)
            header = _manifest_header(m["manifest"])
            for rid, why in resp.get("rejected", {}).items():
                if "duplicate" in str(why):
                    continue   # the first attempt landed: a no-op
                # genuine capacity rejection — hand the rec back to the
                # controller for re-placement elsewhere
                self._live.pop(rid, None)
                for rec in m["manifest"].get("requests", ()):
                    if rec["rid"] == rid:
                        self._bounced.append(("rec", header, rec))

    def take_bounced(self) -> list:
        """Work the replica definitively rejected after an ambiguous
        window (``("req", Request)`` fresh submits, ``("rec", header,
        rec)`` migration records) — the controller drains this each
        tick and re-places them."""
        out, self._bounced = self._bounced, []
        return out

    def step(self) -> list:
        """One controller tick against this replica: prove liveness,
        reconcile ambiguous calls, poll every live stream since its
        acknowledged index, deliver the new tokens, and return the
        retirements.  ONE round trip when there is work — /poll's
        response carries the health/load snapshot, so a separate ping
        is only paid when there is nothing to poll.  Raises
        :class:`~serve.net.NetUnreachable` when the replica answers
        nothing — the controller counts that as missing progress, not
        death."""
        polls = {rid: s["acked"] for rid, s in self._live.items()
                 if not s["done"] and rid not in self._maybe_reqs}
        outs: list[RequestOutput] = []
        if not polls:
            if not self.ping():
                raise NetUnreachable(
                    f"replica {self.name} at {self.url}: "
                    f"no health answer")
            self._reconcile()
            return outs
        try:
            resp = self.client.call("poll", "/poll", method="POST",
                                    body={"streams": polls})
        except NetError as e:
            raise NetUnreachable(str(e)) from e
        if not self._absorb_health(resp.get("health", {"ok": True})):
            # answered, but the serve loop behind it stopped pumping:
            # tokens (if any) are still real, progress is not proven
            raise NetUnreachable(
                f"replica {self.name} at {self.url}: serve loop "
                f"not pumping")
        self._reconcile()
        now = time.monotonic()
        for rid, st in resp.get("streams", {}).items():
            s = self._live.get(rid)
            if s is None or st.get("missing"):
                continue
            for t in st.get("tokens", ()):
                s["tokens"].append(int(t))
                if s["cb"] is not None:
                    try:
                        s["cb"](rid, int(t))
                    except Exception:  # noqa: BLE001 — the engine-side
                        s["cb"] = None  # callback-containment rule
                # the ack advances only once the token is DELIVERED: a
                # poll response lost mid-delivery re-serves from here
                s["acked"] += 1
            if st.get("done") and not s["done"]:
                s["done"] = True
                rm = RequestMetrics(arrival_time=now)
                rm.finish_time = now
                outs.append(RequestOutput(
                    request_id=rid, prompt=s["prompt"],
                    token_ids=list(s["tokens"]),
                    finish_reason=FinishReason(st["reason"]),
                    metrics=rm, error=st.get("error")))
        for rid in [r for r, s in self._live.items() if s["done"]]:
            del self._live[rid]
        return outs

    def unplaced(self) -> tuple[list, list]:
        """What this client could never confirm landed — called at
        replica death, AFTER the journal manifest: the controller
        re-places exactly the rids the dead journal does not cover
        (anything journaled is owned; anything else never arrived)."""
        reqs = [self._live[rid]["req"] for rid in self._maybe_reqs
                if rid in self._live
                and self._live[rid].get("req") is not None]
        recs: list[tuple] = []
        for m in self._maybe_migs:
            header = _manifest_header(m["manifest"])
            for rec in m["manifest"].get("requests", ()):
                recs.append((header, rec))
        for b in self._bounced:
            if b[0] == "req":
                reqs.append(b[1])
            else:
                recs.append((b[1], b[2]))
        return reqs, recs


# ---------------------------------------------------------------------------
# The fleet controller
# ---------------------------------------------------------------------------


class FleetController:
    """N in-process engine replicas behind a :class:`Router`, with
    health-checked circuit breaking, backoff restarts, and live request
    migration (module docstring; docs/serving.md "Fleet serving").

    ``factory(snapshot_dir) -> ServeEngine`` builds one replica life
    (it MUST pass ``snapshot_dir`` through — the journal is the
    migration substrate).  Drive it like an engine: :meth:`submit` then
    :meth:`step`/:meth:`run`; finished streams land in
    :attr:`outputs`, the exactly-once delivery record in
    :attr:`streams`, and per-request placement history (which replicas
    served it) in :attr:`history`.

    Exactly-once across the fleet: every token reaches the caller
    exactly once — live tokens through the wrapped ``on_token``, and on
    a migration the manifest's journal segment fills exactly the
    indices the dead replica journaled but never delivered (the
    commit→callback crash window).  The journal union argument lives in
    serve/recovery.py; the chaos harness asserts both.
    """

    def __init__(self, factory: Callable, n_replicas: int, *,
                 root: str, clock=time.monotonic,
                 router: Optional[Router] = None,
                 suspect_after_s: float = 5.0,
                 dead_after_s: float = 15.0,
                 probe: Optional[Callable] = None,
                 backoff_base_s: float = 0.25,
                 backoff_cap_s: float = 30.0,
                 backoff_jitter: float = 0.5,
                 healthy_reset_s: float = 60.0,
                 max_restarts: Optional[int] = None,
                 trace_events: int = 2048, trace_level: int = 1,
                 audit_events: int = 1024,
                 slo_window_s: float = 60.0,
                 fleet_id: Optional[str] = None, seed: int = 0,
                 roles: Optional[dict] = None,
                 ingress: Optional[dict] = None,
                 autoscale: Optional[dict] = None):
        if n_replicas < 1:
            raise ValueError(f"need >= 1 replica, got {n_replicas}")
        # -- token-bucket ingress admission (per-SLO-class budgets) ------
        # ``{"rate": req/s, "burst": bucket_cap, "per_class": {class:
        # {"rate", "burst"}}}`` — rate/burst are the per-class defaults;
        # per_class overrides one class's budget.  None (the default)
        # admits everything: existing fleets are untouched.
        self.ingress_cfg: Optional[dict] = None
        self._buckets: dict[str, dict] = {}
        if ingress is not None:
            cfg = dict(ingress)
            rate = float(cfg.pop("rate", 0.0))
            burst = float(cfg.pop("burst", max(rate, 1.0)))
            per_class = dict(cfg.pop("per_class", None) or {})
            if cfg:
                raise ValueError(f"unknown ingress keys: {sorted(cfg)}")
            if rate <= 0:
                raise ValueError(f"ingress rate must be > 0, got {rate}")
            for klass in per_class:
                if klass not in SLO_CLASSES:
                    raise ValueError(
                        f"unknown SLO class in ingress per_class: "
                        f"{klass!r} (expected one of {SLO_CLASSES})")
            for klass in SLO_CLASSES:
                o = dict(per_class.get(klass, None) or {})
                r = float(o.pop("rate", rate))
                b = float(o.pop("burst", burst))
                if o:
                    raise ValueError(
                        f"unknown ingress per_class[{klass!r}] keys: "
                        f"{sorted(o)}")
                if r <= 0 or b < 1:
                    raise ValueError(
                        f"ingress class {klass!r}: need rate > 0 and "
                        f"burst >= 1, got {r}, {b}")
                self._buckets[klass] = {"rate": r, "burst": b,
                                        "tokens": b, "t": None}
            self.ingress_cfg = {"rate": rate, "burst": burst}
        self.ingress_shed_by_class: dict[str, int] = {}
        # -- pressure-driven autoscaling ---------------------------------
        # ``{"min", "max", "high", "low", "window_s", "dwell_steps"}`` —
        # smoothed fleet pressure above ``high`` for ``dwell_steps``
        # consecutive ticks spawns a replica (up to ``max``); below
        # ``low`` retires the least-loaded one through the exactly-once
        # drain path (down to ``min``).  None disables scaling.
        self.autoscale_cfg: Optional[dict] = None
        if autoscale is not None:
            cfg = dict(autoscale)
            a = {
                "min": int(cfg.pop("min", 1)),
                "max": int(cfg.pop("max", n_replicas)),
                "high": float(cfg.pop("high", 0.8)),
                "low": float(cfg.pop("low", 0.3)),
                "window_s": float(cfg.pop("window_s", 5.0)),
                "dwell_steps": int(cfg.pop("dwell_steps", 3)),
            }
            if cfg:
                raise ValueError(f"unknown autoscale keys: {sorted(cfg)}")
            if not 1 <= a["min"] <= n_replicas <= a["max"]:
                raise ValueError(
                    f"need 1 <= min <= n_replicas <= max, got "
                    f"min={a['min']}, n_replicas={n_replicas}, "
                    f"max={a['max']}")
            if not 0.0 < a["low"] < a["high"]:
                raise ValueError(
                    f"need 0 < low < high, got {a['low']}, {a['high']}")
            if a["window_s"] < 0:
                raise ValueError(
                    f"window_s must be >= 0, got {a['window_s']}")
            if a["dwell_steps"] < 1:
                raise ValueError(
                    f"dwell_steps must be >= 1, got {a['dwell_steps']}")
            self.autoscale_cfg = a
        # routing roles ({name: "prefill"|"decode"|"both"}, default
        # "both" for every replica — a homogeneous fleet routes exactly
        # as before; docs/serving.md "Disaggregated serving")
        roles = dict(roles or {})
        for rname, role in roles.items():
            if role not in REPLICA_ROLES:
                raise ValueError(
                    f"replica {rname!r}: unknown role {role!r} "
                    f"(expected one of {REPLICA_ROLES})")
        if not suspect_after_s < dead_after_s:
            raise ValueError(
                f"need suspect_after_s < dead_after_s, got "
                f"{suspect_after_s}, {dead_after_s}")
        if trace_level < 0:
            raise ValueError(f"trace_level must be >= 0, got {trace_level}")
        self._clock = clock
        self.router = router or Router()
        self.suspect_after_s = suspect_after_s
        self.dead_after_s = dead_after_s
        # progress age in seconds; replaceable so tests (and subprocess
        # drivers) can layer heartbeat-file staleness in
        self._probe = probe or (
            lambda r, now: now - (r.last_progress
                                  if r.last_progress is not None
                                  else now))
        self.trace = FlightRecorder(capacity=trace_events,
                                    level=trace_level)
        # the router decision audit ring (docs/observability.md "Fleet
        # observability"); gated by the same level knob as the recorder
        # so trace_level=0 turns both off together
        self.audit = DecisionAudit(capacity=audit_events,
                                   enabled=trace_level > 0)
        os.makedirs(root, exist_ok=True)
        self.root = root
        # trace-id namespace: fleet-unique request journeys.  rids are
        # unique within one controller (duplicate submits raise), so the
        # fleet id only needs to distinguish controllers sharing a sink.
        self.fleet_id = fleet_id or (os.path.basename(
            os.path.abspath(root)) or "fleet")
        # fleet-level SLO burn windows: deadline misses and sheds over
        # the trailing slo_window_s, fed at finalization wherever the
        # retirement happened (an engine's sweep, the fleet queue's, or
        # an admission shed)
        self.slo_window_s = slo_window_s
        self._slo_deadline = WindowedRate(slo_window_s)
        self._slo_shed = WindowedRate(slo_window_s)
        # dead lives' metrics, folded in before each engine is
        # discarded (the in-process stand-in for a final scrape; a
        # subprocess SIGKILL loses whatever its last scrape missed);
        # their recorders ride along so trace-event totals survive too
        self._carry = ServeMetrics()
        self._carry_recorders: list = []
        now = self._clock()
        # kept for autoscale spawns — a scaled-up replica is built
        # exactly like the initial fleet (same factory, same backoff
        # shape, its own jitter seed)
        self._factory = factory
        self._seed = seed
        self._backoff_kw = dict(
            base_s=backoff_base_s, cap_s=backoff_cap_s,
            jitter=backoff_jitter, healthy_reset_s=healthy_reset_s,
            max_restarts=max_restarts)
        self.replicas: dict[str, EngineReplica] = {}
        self._backoff: dict[str, RestartBackoff] = {}
        for i in range(n_replicas):
            name = f"r{i}"
            rep = EngineReplica(name, factory, os.path.join(root, name))
            rep.role = roles.pop(name, "both")
            self.replicas[name] = rep
            self._backoff[name] = RestartBackoff(
                base_s=backoff_base_s, cap_s=backoff_cap_s,
                jitter=backoff_jitter, healthy_reset_s=healthy_reset_s,
                max_restarts=max_restarts, seed=seed + i)
            rep.start(now)
            if hasattr(rep.engine, "attach_fleet"):
                rep.engine.attach_fleet(self.audit)
            self._backoff[name].on_start(now)
        if roles:
            raise ValueError(
                f"roles for unknown replicas: {sorted(roles)} "
                f"(replicas are r0..r{n_replicas - 1})")
        self.steps = 0
        self.deaths = 0
        self.migrations = 0        # requests moved between replicas
        self.outputs: dict[str, RequestOutput] = {}
        self.streams: dict[str, list] = {}   # exactly-once delivery
        self.placement: dict[str, str] = {}  # rid -> current replica
        self.history: dict[str, list] = {}   # rid -> replicas that held it
        self._cbs: dict[str, Callable] = {}  # rid -> wrapped on_token
        # rid -> the user's terminal callback, stripped off the Request
        # at submit: the serving engine can change mid-stream
        # (migration) and a fleet-level shed never reaches ANY engine,
        # so the fleet is the only layer that can promise exactly-once
        # terminal delivery (_finalize pops it)
        self._finish_cbs: dict[str, Callable] = {}
        self._pending_reqs: deque = deque()  # unplaced fresh requests
        self._pending_recs: deque = deque()  # (header, rec) to re-place
        # autoscaler state: monotonic replica naming (a retired or dead
        # slot's name is NEVER reused — the double-adopt guard), the
        # smoothed-pressure tracker, and the retirement record
        self._next_index = n_replicas
        self._scale_state = {"ema": 0.0, "t": None, "dwell": 0}
        self.scale_ups = 0
        self.scale_downs = 0
        self.retired: set[str] = set()

    # -- submission -------------------------------------------------------

    def _make_cb(self, rid: str, orig) -> Callable:
        stream = self.streams[rid]

        def cb(_rid, tok):
            stream.append(int(tok))
            if orig is not None:
                orig(_rid, tok)
        return cb

    def submit(self, req: Request) -> None:
        """Route one request onto the least-pressure HEALTHY replica.
        Fleet-queued while no healthy replica exists (an outage window
        is transient — deadlines still sweep the fleet queue); SHED
        when every healthy replica's waiting queue is at its bound (the
        PR 3 bounded-admission contract holds fleet-wide: the fleet
        sheds only when EVERY replica is full)."""
        rid = req.request_id
        if rid in self.streams:
            raise ValueError(f"duplicate request id {rid!r}")
        if req.trace is None:
            # fleet-unique trace id, hop 0: one journey however many
            # replicas end up serving it (docs/observability.md
            # "Fleet observability")
            req.trace = {"trace_id": f"{self.fleet_id}/{rid}", "hop": 0}
        if req.arrival_time is None:
            req.arrival_time = self._clock()  # fleet-queue deadlines
        self.streams[rid] = []
        self.history[rid] = []
        self._cbs[rid] = self._make_cb(rid, req.on_token)
        req.on_token = self._cbs[rid]
        if req.on_finish is not None:
            self._finish_cbs[rid] = req.on_finish
            req.on_finish = None
        if self._buckets and not self._ingress_admit(req):
            self.ingress_shed_by_class[req.slo_class] = (
                self.ingress_shed_by_class.get(req.slo_class, 0) + 1)
            self.trace.emit("ingress_shed", rid, slo=req.slo_class)
            self.audit.record(self._clock(), self.steps, "ingress_shed",
                              rid, slo=req.slo_class)
            self._shed(req, f"ingress token bucket empty "
                            f"(class {req.slo_class!r})")
            return
        if not self._place_request(req):
            self._pending_reqs.append(req)

    def _ingress_admit(self, req: Request) -> bool:
        """Spend one ingress token for ``req``: its own class's bucket
        first, then BORROW downward — a class is never refused while a
        LOWER tier still holds budget (the interactive-never-shed-
        before-best-effort contract, generalized), and a lower class
        can never drain a higher one's budget."""
        now = self._clock()
        for klass in SLO_CLASSES[slo_rank(req.slo_class):]:
            b = self._buckets[klass]
            if b["t"] is not None:
                b["tokens"] = min(
                    b["burst"],
                    b["tokens"] + (now - b["t"]) * b["rate"])
            b["t"] = now
            if b["tokens"] >= 1.0:
                b["tokens"] -= 1.0
                return True
        return False

    def _healthy(self, role: Optional[str] = None) -> list:
        """HEALTHY ``(name, load)`` candidates, optionally filtered to
        replicas that can serve ``role`` (a ``"both"`` replica serves
        either role — role is routing preference, not capability)."""
        return [(name, r.load()) for name, r in self.replicas.items()
                if r.state is ReplicaState.HEALTHY
                and (role is None or r.role in (role, "both"))]

    def _place_request(self, req: Request) -> bool:
        from triton_dist_tpu.serve.engine import QueueFull

        healthy = self._healthy()
        # role-aware admission: fresh requests prefer the PREFILL pool
        # (least-pressure within it); with no prefill-capable replica
        # up, availability beats role policy and any healthy replica
        # serves.  All-"both" fleets: pool == healthy, routing exactly
        # as before (docs/serving.md "Disaggregated serving").
        pool = self._healthy("prefill") or healthy
        # capacity-aware: never place onto a queue already at its bound
        # (the engine would shed it; a fleet with room elsewhere must
        # not)
        def with_room(cs):
            return [(n, l) for n, l in cs
                    if (self.replicas[n].engine.max_queue is None
                        or l.queue_depth
                        < self.replicas[n].engine.max_queue)]
        cands = with_room(pool)
        if not cands and len(pool) < len(healthy):
            # the whole prefill tier is at its bound: spill to the rest
            # of the fleet rather than shed while decode queues idle
            cands = with_room(healthy)
        deadline = req.params.deadline_s is not None
        # candidate pressures, captured BEFORE the walk: the audit
        # entry answers "why did this request land there" with the
        # numbers the router actually weighed.  Gated on the audit knob
        # — a trace_level=0 controller must not pay the O(replicas)
        # capture either.
        pressures = ({n: round(self.router.pressure(l, deadline=deadline),
                               4) for n, l in cands}
                     if self.audit.enabled else None)
        skipped = []
        for name in self.router.rank(cands, deadline=deadline):
            rep = self.replicas[name]
            try:
                shed = rep.engine.submit(req)
            except QueueFull:
                skipped.append(name)
                continue
            self.trace.emit("route", req.request_id, replica=name,
                            state=rep.state.value, deadline=deadline)
            if self.audit.enabled:
                self.audit.record(self._clock(), self.steps, "route",
                                  req.request_id, chosen=name,
                                  deadline=deadline, pressures=pressures,
                                  skipped=skipped)
            self.placement[req.request_id] = name
            self.history[req.request_id].append(name)
            if shed is not None:   # raced to a full queue: final verdict
                self._finalize(shed, name)
            return True
        if healthy:
            # Healthy replicas exist and EVERY one is at its queue
            # bound: the fleet is genuinely full — shed now (the
            # bounded-admission contract, fleet-wide).  Nothing was
            # journaled anywhere for this request.  With NO healthy
            # replica the caller queues instead: that is a transient
            # outage window, not admission pressure.
            self._shed(req, f"every replica's queue at bound "
                            f"({len(healthy)} healthy)")
            return True
        return False

    def _shed(self, req: Request, msg: str) -> None:
        rm = RequestMetrics(arrival_time=req.arrival_time
                            or self._clock())
        rm.finish_time = self._clock()
        out = RequestOutput(request_id=req.request_id,
                            prompt=req.prompt, token_ids=[],
                            finish_reason=FinishReason.SHED,
                            metrics=rm, error=msg)
        self.trace.emit("retire", req.request_id, reason="shed")
        self.audit.record(self._clock(), self.steps, "shed",
                          req.request_id, why=msg)
        # a fleet-level shed reaches no engine, so no engine's metrics
        # ever see it — count it in the carry exactly as an engine-side
        # shed would (shed counter, finish reason, per-class split), or
        # the fleet aggregate under-reports precisely under overload
        self._carry.shed += 1
        self._carry.observe_finish(req.request_id, rm, FinishReason.SHED,
                                   slo_class=req.slo_class)
        self._finalize(out, "fleet")

    def _place_rec(self, header: dict, rec: dict,
                   exclude: frozenset = frozenset()) -> bool:
        """Place one migration-manifest record onto a healthy replica
        via ``migrate_in`` (capacity admission: a rejecting replica
        passes it to the next candidate)."""
        rid = rec["rid"]
        cands = [(n, l) for n, l in self._healthy() if n not in exclude]
        params_deadline = rec.get("params", {}).get("deadline_s")
        deadline = params_deadline is not None
        pressures = ({n: round(self.router.pressure(l, deadline=deadline),
                               4) for n, l in cands}
                     if self.audit.enabled else None)
        # decode-capable candidates first: a migrated/pushed record is
        # past (or resuming) its prefill, so it belongs on the decode
        # tier — prefill-role replicas stay as the availability
        # fallback.  All-"both" fleets: one rank() call, ordering (and
        # the round-robin tie state) exactly as before.
        dec = [(n, l) for n, l in cands
               if self.replicas[n].role != "prefill"]
        rest = [(n, l) for n, l in cands
                if self.replicas[n].role == "prefill"]
        order = self.router.rank(dec, deadline=deadline) if dec else []
        if rest:
            order += self.router.rank(rest, deadline=deadline)
        rejected = {}
        for name in order:
            rep = self.replicas[name]
            res = rep.engine.migrate_in(
                {**header, "requests": [rec]},
                on_token={rid: self._cbs.get(rid)})
            if rid in res["rejected"]:
                rejected[name] = res["rejected"][rid]
                continue
            self.migrations += 1
            self.trace.emit("migrate_in", rid, replica=name,
                            state=rep.state.value,
                            in_place=rid in res["adopted"])
            if self.audit.enabled:
                self.audit.record(self._clock(), self.steps, "migrate",
                                  rid, chosen=name,
                                  in_place=rid in res["adopted"],
                                  pressures=pressures,
                                  rejected=rejected)
            self.placement[rid] = name
            self.history[rid].append(name)
            return True
        return False

    def _drain_pending(self, exclude: frozenset = frozenset()) -> None:
        for _ in range(len(self._pending_recs)):
            header, rec, expires = self._pending_recs.popleft()
            if not self._place_rec(header, rec, exclude):
                self._pending_recs.append((header, rec, expires))
        for _ in range(len(self._pending_reqs)):
            req = self._pending_reqs.popleft()
            if not self._place_request(req):
                self._pending_reqs.append(req)

    # -- the fleet tick ---------------------------------------------------

    def step(self) -> list:
        """One fleet iteration: due restarts → place pending work →
        step every live replica (a step that raises is a replica death:
        migrate + schedule restart) → health sweep.  Returns the
        requests that finished this tick."""
        now = self._clock()
        self.trace.set_step(self.steps)
        finished: list[RequestOutput] = []
        # deadline sweep over the FLEET queue: a request parked here
        # (no healthy replica when it arrived) is visible to no
        # engine's sweep, so its TTL must expire here or never
        for _ in range(len(self._pending_reqs)):
            req = self._pending_reqs.popleft()
            d = req.params.deadline_s
            if (d is not None and req.arrival_time is not None
                    and now - req.arrival_time > d):
                rm = RequestMetrics(arrival_time=req.arrival_time)
                rm.finish_time = now
                out = RequestOutput(
                    request_id=req.request_id, prompt=req.prompt,
                    token_ids=[], finish_reason=FinishReason.DEADLINE,
                    metrics=rm,
                    error=f"deadline {d}s exceeded in the fleet queue")
                self.trace.emit("retire", req.request_id,
                                reason="deadline")
                # the fleet-queue sweep is this request's ONLY metrics
                # seam (no engine ever saw it) — count like an engine
                # deadline sweep would
                self._carry.deadline_expired += 1
                self._carry.observe_finish(
                    req.request_id, rm, FinishReason.DEADLINE,
                    slo_class=req.slo_class)
                self._finalize(out, "fleet")
                finished.append(out)
            else:
                self._pending_reqs.append(req)
        # ...and over the parked MIGRATION records: a deadline-carrying
        # rec stranded here during an outage is just as invisible to
        # every engine's sweep (engines expire WAITING rows whatever
        # their carried progress; the fleet queue must match)
        for _ in range(len(self._pending_recs)):
            header, rec, expires = self._pending_recs.popleft()
            if expires is not None and now > expires:
                rid = rec["rid"]
                ttl = rec["params"]["deadline_s"]
                # expires was arrival(rebased) + ttl: recover the
                # arrival so the retirement's latency is the >= ttl
                # wait it actually suffered, not zero
                rm = RequestMetrics(arrival_time=expires - ttl)
                rm.finish_time = now
                out = RequestOutput(
                    request_id=rid,
                    prompt=np.asarray(rec.get("prompt", []), np.int32),
                    token_ids=[int(t) for t in rec.get("tokens", [])],
                    finish_reason=FinishReason.DEADLINE, metrics=rm,
                    error=f"deadline "
                          f"{rec['params']['deadline_s']}s exceeded "
                          f"in the fleet queue (migrated)")
                self.trace.emit("retire", rid, reason="deadline")
                self._carry.deadline_expired += 1
                self._carry.observe_finish(
                    rid, rm, FinishReason.DEADLINE,
                    slo_class=rec.get("slo", "interactive"))
                self._finalize(out, "fleet")
                finished.append(out)
            else:
                self._pending_recs.append((header, rec, expires))
        for name, rep in self.replicas.items():
            if (rep.state is ReplicaState.DEAD
                    and rep.restart_at is not None
                    and now >= rep.restart_at):
                rep.start(now)
                if hasattr(rep.engine, "attach_fleet"):
                    rep.engine.attach_fleet(self.audit)
                rep.restarts += 1
                self._backoff[name].on_start(now)
                self.trace.emit("replica_state", None, replica=name,
                                state=rep.state.value,
                                life=rep.life)
                self.audit.record(now, self.steps, "restart",
                                  replica=name, life=rep.life)
        self._drain_pending()
        for name, rep in self.replicas.items():
            if rep.state is ReplicaState.DEAD or rep.engine is None:
                continue
            if not rep.engine.has_work():
                # idle is not a stall — but an idle REMOTE replica must
                # still answer a health probe, or a partition of an
                # idle process would never be noticed until the router
                # placed onto it
                ping = getattr(rep.engine, "ping", None)
                if ping is None or ping():
                    rep.last_progress = now
                continue
            try:
                outs = rep.engine.step()
            except (KeyboardInterrupt, SystemExit):
                raise
            except NetUnreachable:
                # the replica answered nothing this tick: NOT a death —
                # no progress is recorded, so the probe age walks the
                # SUSPECT→DEAD ladder (a partition is handled by the
                # same machinery as a SIGKILL, dead_after_s later)
                continue
            except WatchdogTimeout as e:
                # engine-level stall: the dispatch wedged past its
                # budget — the process is as good as gone
                self._on_replica_death(name, f"watchdog: {e}", now)
                continue
            except BaseException as e:  # noqa: BLE001 — InjectedKill /
                # engine-fatal escalations ARE the process-death seam
                self._on_replica_death(
                    name, f"{type(e).__name__}: {e}", now)
                continue
            rep.last_progress = now
            if rep.state is ReplicaState.SUSPECT:
                rep.state = ReplicaState.HEALTHY  # progress: recovered
                self.trace.emit("replica_state", None, replica=name,
                                state=rep.state.value)
                self.audit.record(now, self.steps, "replica_state",
                                  replica=name, state=rep.state.value,
                                  why="progress resumed")
            for out in outs:
                self._finalize(out, name)
                finished.append(out)
            # a remote replica's reconciliation can BOUNCE a migration
            # rec (genuine capacity rejection discovered late): re-place
            take = getattr(rep.engine, "take_bounced", None)
            if take is not None:
                for b in take():
                    if b[0] == "req":
                        req = b[1]
                        self.placement.pop(req.request_id, None)
                        if not self._place_request(req):
                            self._pending_reqs.append(req)
                    else:
                        _, header, rec = b
                        self.placement.pop(rec["rid"], None)
                        self._pending_recs.append(
                            (header, rec,
                             self._rec_expiry(header, rec)))
        # health sweep: probe-driven SUSPECT/DEAD (heartbeat staleness
        # for subprocess drivers; progress age in-process)
        for name, rep in self.replicas.items():
            if rep.state is ReplicaState.DEAD:
                continue
            age = self._probe(rep, now)
            if age > self.dead_after_s:
                self._on_replica_death(name, f"stalled {age:.1f}s", now)
            elif (age > self.suspect_after_s
                  and rep.state is ReplicaState.HEALTHY):
                rep.state = ReplicaState.SUSPECT
                self.trace.emit("replica_state", None, replica=name,
                                state=rep.state.value,
                                age=round(age, 3))
                self.audit.record(now, self.steps, "replica_state",
                                  replica=name, state=rep.state.value,
                                  age=round(age, 3))
            elif (age <= self.suspect_after_s
                  and rep.state is ReplicaState.SUSPECT):
                # the probe says healthy again (an IDLE suspect replica
                # never re-proves itself through a step, so the sweep
                # must heal too, or it would stay circuit-broken
                # forever)
                rep.state = ReplicaState.HEALTHY
                self.trace.emit("replica_state", None, replica=name,
                                state=rep.state.value)
                self.audit.record(now, self.steps, "replica_state",
                                  replica=name, state=rep.state.value,
                                  why="probe healthy")
        if self.autoscale_cfg is not None:
            self._autoscale_step(now)
        self.steps += 1
        return finished

    def has_work(self) -> bool:
        return (bool(self._pending_reqs) or bool(self._pending_recs)
                or any(r.engine is not None and r.engine.has_work()
                       for r in self.replicas.values()))

    def run(self, max_steps: int = 100_000) -> dict:
        """Step until the fleet drains; returns ``dict(outputs)``.
        Raises when no replica is live and none will restart (budget
        exhausted with work pending) — the fleet-level outage."""
        steps = 0
        while self.has_work():
            if not any(r.state is not ReplicaState.DEAD
                       or r.restart_at is not None
                       for r in self.replicas.values()):
                raise RuntimeError(
                    "fleet outage: every replica is dead with its "
                    "restart budget exhausted and work is pending")
            self.step()
            steps += 1
            if steps > max_steps:
                raise RuntimeError(
                    f"fleet not drained after {max_steps} steps")
        return dict(self.outputs)

    # -- pressure-driven autoscaling --------------------------------------

    def _tier_pressure(self, reps: list) -> float:
        """Mean per-replica saturation over the live members of one
        tier: queue depth against its admission bound (``max_queue``,
        else ``4 * max_batch`` — the same denominator the engine's
        brownout ladder uses) or KV-pool utilization, whichever is
        tighter.  A tier with NO live replica is fully saturated."""
        live = [r for r in reps if r.engine is not None
                and r.state is not ReplicaState.DEAD]
        if not live:
            return 1.0

        def sat(rep) -> float:
            load = rep.load()
            mq = rep.engine.max_queue
            denom = mq if mq else 4 * max(load.max_batch, 1)
            return max(load.queue_depth / max(denom, 1), load.kv_util)

        return sum(sat(r) for r in live) / len(live)

    def _autoscale_tier(self, now: float, st: dict, reps: list, *,
                        role: str, pending: bool) -> None:
        """One tier's autoscale evaluation: smooth the raw pressure
        into ``st["ema"]`` (clock-driven EMA, ``alpha = 1 -
        exp(-dt/window_s)``), walk the signed dwell counter, and act at
        the water marks — spawn at sustained-high (to ``max``), retire
        the least-loaded healthy replica through the exactly-once drain
        path at sustained-low (to ``min``).  ``reps`` is ``[(name,
        EngineReplica)]``; ``pending`` marks unplaced fleet-queue work
        waiting on this tier (saturation wherever the replicas sit).
        Returns ``(spawned_name, retired_name)`` (either may be
        ``None``)."""
        cfg = self.autoscale_cfg
        raw = self._tier_pressure([r for _, r in reps])
        if pending:
            raw = max(raw, 1.0)
        if st["t"] is None or cfg["window_s"] <= 0:
            st["ema"] = raw
        else:
            dt = max(now - st["t"], 0.0)
            alpha = 1.0 - math.exp(-dt / cfg["window_s"])
            st["ema"] += alpha * (raw - st["ema"])
        st["t"] = now
        if st["ema"] >= cfg["high"]:
            st["dwell"] = max(st["dwell"], 0) + 1
        elif st["ema"] <= cfg["low"]:
            st["dwell"] = min(st["dwell"], 0) - 1
        else:
            st["dwell"] = 0
        spawned = retired = None
        if st["dwell"] >= cfg["dwell_steps"]:
            # a DEAD replica with a scheduled restart is capacity in
            # flight — spawning past it would overshoot max
            capacity = sum(1 for _, r in reps
                           if r.state is not ReplicaState.DEAD
                           or r.restart_at is not None)
            if capacity < cfg["max"]:
                spawned = self._spawn_replica(now, role=role,
                                              pressure=st["ema"])
            st["dwell"] = 0
        elif st["dwell"] <= -cfg["dwell_steps"]:
            healthy = [(self.router.pressure(r.load()), n)
                       for n, r in reps
                       if r.state is ReplicaState.HEALTHY]
            if len(healthy) > cfg["min"]:
                retired = min(healthy)[1]
                self.retire_replica(retired)
            st["dwell"] = 0
        return spawned, retired

    def _autoscale_step(self, now: float) -> None:
        self._autoscale_tier(
            now, self._scale_state, list(self.replicas.items()),
            role="both",
            pending=bool(self._pending_reqs or self._pending_recs))

    def _spawn_replica(self, now: float, role: str = "both",
                       pressure: Optional[float] = None) -> str:
        """Scale-up: bring ONE new replica into the fleet from the
        stored factory.  Names are monotonic (``r{next_index}``, never
        reused) — a retired or dead replica's name can never be
        double-adopted by a new life racing its crash migration."""
        idx = self._next_index
        self._next_index += 1
        name = f"r{idx}"
        rep = EngineReplica(name, self._factory,
                            os.path.join(self.root, name))
        rep.role = role
        self.replicas[name] = rep
        self._backoff[name] = RestartBackoff(**self._backoff_kw,
                                             seed=self._seed + idx)
        rep.start(now)
        if hasattr(rep.engine, "attach_fleet"):
            rep.engine.attach_fleet(self.audit)
        self._backoff[name].on_start(now)
        self.scale_ups += 1
        p = round(self._scale_state["ema"] if pressure is None
                  else pressure, 4)
        self.trace.emit("scale", None, action="up", replica=name,
                        role=role, pressure=p)
        self.audit.record(now, self.steps, "scale", replica=name,
                          action="up", role=role, pressure=p)
        return name

    def retire_replica(self, name: str) -> int:
        """Scale-down: cooperatively drain every in-flight request off
        ``name`` through the exactly-once path (``mig`` receipts land
        in the journal before the manifest leaves — the same argument
        as :meth:`drain_replica`), fold the life's metrics into the
        fleet carry, and retire the replica FOR GOOD: no restart is
        scheduled and the name is never reused (:attr:`retired`).
        Returns the number of requests moved."""
        rep = self.replicas[name]
        if rep.engine is None:
            raise ValueError(f"replica {name} is not live")
        now = self._clock()
        # circuit-break admissions FIRST: the drain re-places parked
        # work through _drain_pending, and a still-HEALTHY leaver could
        # win that placement and strand the request when its engine
        # drops a moment later
        rep.state = ReplicaState.SUSPECT
        moved = self.drain_replica(name)
        # same carry fold as a death, minus the crash migration: the
        # drain already moved everything, so only the accounting rides
        m = rep.engine.metrics
        self._carry.merge(m)
        self._carry.queue_depth_last = 0
        self._carry.running_last = 0
        self._carry.kv_util_last = 0.0
        self._carry.compiled_fns.extend(m.compiled_fns)
        if m.recorder is not None:
            self._carry_recorders.append(m.recorder)
        if rep.engine._journal is not None:
            rep.engine._journal.close()
        rep.engine = None
        rep.state = ReplicaState.DEAD
        rep.restart_at = None
        rep.death_reason = "retired (scaled down)"
        self.retired.add(name)
        self.scale_downs += 1
        self.trace.emit("scale", None, action="down", replica=name,
                        moved=moved,
                        pressure=round(self._scale_state["ema"], 4))
        self.audit.record(now, self.steps, "scale", replica=name,
                          action="down", moved=moved,
                          pressure=round(self._scale_state["ema"], 4))
        return moved

    # -- failure handling + migration -------------------------------------

    def kill_replica(self, name: str, why: str = "killed") -> None:
        """Declare a replica dead NOW (the chaos / ops hook — the
        in-process stand-in for SIGKILL): its in-flight requests
        migrate from the durable journal and a restart is scheduled
        under backoff."""
        self._on_replica_death(name, why, self._clock())

    def drain_replica(self, name: str) -> int:
        """Cooperatively migrate every in-flight request OFF a live
        replica (maintenance drain / rebalance): ``ServeEngine.drain``
        hands off live KV + pending tokens, so RUNNING rows resume
        mid-stream on their new replica with zero recompute.  Returns
        the number of requests moved."""
        rep = self.replicas[name]
        if rep.engine is None:
            raise ValueError(f"replica {name} is dead; crash migration "
                             f"already ran")
        manifest = rep.engine.drain()
        n = len(manifest["requests"])
        self._absorb_manifest(manifest, source=name)
        self._drain_pending(exclude=frozenset((name,)))
        return n

    def _on_replica_death(self, name: str, why: str,
                          now: float) -> None:
        rep = self.replicas[name]
        if rep.state is ReplicaState.DEAD:
            return
        from triton_dist_tpu.serve.recovery import manifest_from_journal

        print(f"[fleet] replica {name} dead ({why}); migrating its "
              f"in-flight requests", file=sys.stderr)
        # remote replicas: calls whose ack was lost and never
        # reconciled — captured BEFORE the engine ref drops, resolved
        # against the journal below (anything journaled is owned by the
        # dead life; anything else never arrived and re-places)
        lost_reqs: list = []
        lost_recs: list = []
        if rep.engine is not None and hasattr(rep.engine, "unplaced"):
            lost_reqs, lost_recs = rep.engine.unplaced()
        if rep.engine is not None and rep.engine._journal is not None:
            rep.engine._journal.close()  # single writer for the mark
            #                              (for a RemoteReplica this
            #                              SIGKILLs the child process —
            #                              a partitioned zombie must
            #                              stop writing before the
            #                              crash path reads)
        if rep.engine is not None:
            # fold the dying life's metrics into the fleet carry so the
            # aggregate histograms keep its samples (the in-process
            # stand-in for a subprocess replica's final scrape — a
            # SIGKILL there loses whatever the last scrape missed)
            m = rep.engine.metrics
            self._carry.merge(m)
            # ...but NOT its point-in-time gauges: a dead replica's
            # current queue/batch/KV state is zero, and carrying its
            # last readings would hold a pressure alert firing forever
            # (peaks stay — they are history, not state)
            self._carry.queue_depth_last = 0
            self._carry.running_last = 0
            self._carry.kv_util_last = 0.0
            # compile/trace counters have no additive field to merge
            # (compile_misses is a property over the registered
            # CountingJit wrappers; the recorder is an object) — carry
            # the frozen objects themselves so the in-process aggregate
            # reports the same totals the scrape path would sum
            self._carry.compiled_fns.extend(m.compiled_fns)
            if m.recorder is not None:
                self._carry_recorders.append(m.recorder)
        life_dir = rep.life_dir
        rep.engine = None  # the process is gone; durable state remains
        rep.state = ReplicaState.DEAD
        rep.death_reason = why
        self.deaths += 1
        self.trace.emit("replica_state", None, replica=name,
                        state=rep.state.value, why=why)
        self.audit.record(now, self.steps, "replica_state",
                          replica=name, state=rep.state.value, why=why)
        manifest = manifest_from_journal(life_dir, mark=True)
        # Journal salvage escalation: the dead life's journal carried
        # interior corruption — the salvaged prefix may be missing
        # committed tokens.  Count + trace it here (the dead engine's
        # own metrics are gone), then let _absorb_manifest reconcile
        # each stream against OUR delivery record: what the controller
        # delivered is committed truth the salvage cannot un-commit.
        jdamage = manifest.get("damage")
        if jdamage is not None:
            self._carry.journal_corrupt += 1
            self.trace.emit("corrupt", None, artifact="journal",
                            replica=name, **jdamage)
            self.audit.record(now, self.steps, "journal_corrupt",
                              replica=name,
                              quarantine=jdamage.get("quarantine"),
                              affected=jdamage.get("affected_rids"))
        # retirements whose outputs the dying step swallowed: the
        # journal's fin records are the accounting of record
        for f in manifest["finished"]:
            if f["rid"] in self.streams and f["rid"] not in self.outputs:
                self._finalize_from_journal(f, name)
        self._absorb_manifest(manifest, source=name)
        covered = ({r["rid"] for r in manifest.get("requests", ())}
                   | {f["rid"] for f in manifest.get("finished", ())})
        for req in lost_reqs:
            rid = req.request_id
            if rid in covered or rid in self.outputs:
                continue   # the ambiguous call DID land: the journal
                #            (or a retirement) owns it
            self.placement.pop(rid, None)
            self._pending_reqs.append(req)
        for header, rec in lost_recs:
            if rec["rid"] in covered or rec["rid"] in self.outputs:
                continue
            self.placement.pop(rec["rid"], None)
            self._pending_recs.append(
                (header, rec, self._rec_expiry(header, rec)))
        self._drain_pending(exclude=frozenset((name,)))
        delay = self._backoff[name].on_death(now)
        if delay is None:
            rep.restart_at = None
            print(f"[fleet] replica {name}: restart budget exhausted; "
                  f"staying dead", file=sys.stderr)
        else:
            rep.restart_at = now + delay
        # fleet postmortem: the controller ring + decision audit land
        # next to the replica dirs, where the supervisor's postmortem
        # glob (and any operator) finds them
        self.flight_flush(f"replica {name} dead: {why}")

    def _rec_expiry(self, header: dict, rec: dict) -> Optional[float]:
        """A parked migration rec's TTL, re-based from the source clock
        (``header["clock"]``) onto OURS — the fleet-queue deadline
        sweep covers parked recs with it, whatever path parked them
        (manifest absorption, a capacity bounce, death re-placement)."""
        ttl = rec.get("params", {}).get("deadline_s")
        arr = rec.get("arrival")
        if ttl is None or arr is None:
            return None
        return arr + (self._clock() - (header.get("clock") or 0.0)) + ttl

    def _absorb_manifest(self, manifest: dict, source: str) -> None:
        """Fold a migration manifest into fleet accounting: fill each
        stream's delivery record from the journal segment (tokens the
        source journaled but never delivered — the commit→callback
        crash window — redeliver HERE, exactly the missing indices),
        then queue the records for placement.

        A manifest carrying a journal-salvage ``damage`` report may
        hold FEWER tokens than we delivered (the corrupt tail was cut);
        the delivery record is then the authority — tokens the client
        already saw are committed whatever the rotted journal says, so
        the rec is extended back to the delivered prefix and recompute
        resumes from there.  Without damage, a shorter journal still
        means the journal-precedes-callback invariant broke: assert."""
        damaged = manifest.get("damage") is not None
        header = _manifest_header(manifest)
        for rec in manifest.get("requests", ()):
            rid = rec["rid"]
            if rid not in self.streams:
                continue  # not fleet traffic (foreign journal entry)
            if rid in self.outputs:
                continue  # finished-and-delivered: salvage must never
                #           resurrect a retired stream
            cur = self.placement.get(rid)
            if cur is not None and cur != source:
                other = self.replicas.get(cur)
                if (other is not None and other.engine is not None
                        and other.state is not ReplicaState.DEAD):
                    continue  # the stream is LIVE on another replica —
                    #           a salvaged journal missing its mig
                    #           receipt must not double-place it
            toks = rec.get("tokens", [])
            d = len(self.streams[rid])
            if damaged and d > len(toks):
                rec["tokens"] = toks = [int(t) for t in
                                        self.streams[rid]]
                # token timestamps past the salvaged prefix are gone
                # with the corrupt lines; the adopting engine treats a
                # short tok_ts like a pre-timestamp manifest (re-bases)
                if rec.get("tok_ts") is not None:
                    rec["tok_ts"] = rec["tok_ts"][:len(toks)]
            assert d <= len(toks), (
                f"{rid}: delivered {d} tokens but the journal only "
                f"holds {len(toks)} — the journal-precedes-callback "
                f"invariant broke")
            self.streams[rid].extend(int(t) for t in toks[d:])
            self.placement.pop(rid, None)
            self._pending_recs.append((header, rec,
                                       self._rec_expiry(header, rec)))

    def _finalize(self, out: RequestOutput, name: str) -> None:
        rid = out.request_id
        # SLO burn windows: every deadline miss / shed fleet-wide feeds
        # here, whichever layer retired it (engine sweep, fleet-queue
        # sweep, admission shed)
        if out.finish_reason is FinishReason.DEADLINE:
            self._slo_deadline.observe(self._clock())
        elif out.finish_reason is FinishReason.SHED:
            self._slo_shed.observe(self._clock())
        self.outputs[rid] = out
        s = self.streams.get(rid)
        if s is not None and len(s) < len(out.token_ids):
            # a disabled/raising user callback starves the delivery
            # record; the retirement's authoritative token list
            # reconciles it
            s.extend(out.token_ids[len(s):])
        self.placement.pop(rid, None)
        # the terminal callback, exactly once per rid (pop), whatever
        # path retired the stream — engine step, journal backfill,
        # fleet-queue sweep, or an admission shed that never reached an
        # engine.  Same containment rule as the engine's callbacks.
        cb = self._finish_cbs.pop(rid, None)
        if cb is not None:
            try:
                cb(out)
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:  # noqa: BLE001 — callback containment
                self._carry.callback_errors += 1
                print(f"[fleet] on_finish callback for {rid} raised "
                      f"{type(e).__name__}: {e}", file=sys.stderr)

    def _finalize_from_journal(self, f: dict, name: str) -> None:
        rm = RequestMetrics(arrival_time=self._clock())
        out = RequestOutput(
            request_id=f["rid"],
            prompt=np.asarray(f.get("prompt", []), np.int32),
            token_ids=[int(t) for t in f["tokens"]],
            finish_reason=FinishReason(f["reason"]),
            metrics=rm, error=f.get("err"))
        self._finalize(out, name)

    # -- observability ----------------------------------------------------

    def aggregate_metrics(self) -> ServeMetrics:
        """The fleet as ONE ``ServeMetrics``: every live replica's
        metrics plus the dead lives' carry, merged via
        ``ServeMetrics.merge`` — counters add, the SLO histograms merge
        bucket-EXACTLY (``LogHistogram.merge``), so
        ``fleet_summary()["latency"]`` percentiles equal percentiles
        over the pooled per-replica samples (the chaos test pins the
        equality).  This is the in-process aggregation path; subprocess
        fleets get the same numbers from :func:`merge_scrapes` over the
        per-replica ``/metrics`` texts."""
        agg = ServeMetrics()
        agg.merge(self._carry)
        # compile-stall and trace-event totals ride as object
        # registries, not counters, so merge() skips them; re-register
        # dead lives' frozen wrappers + every live engine's so the
        # in-process exposition reports the same sums the subprocess
        # scrape-and-merge path would (serve_compile_misses,
        # serve_trace_events_total, serve_trace_dropped)
        agg.compiled_fns.extend(self._carry.compiled_fns)
        recorders = list(self._carry_recorders)
        for rep in self.replicas.values():
            if rep.engine is not None:
                m = rep.engine.metrics
                agg.merge(m)
                agg.compiled_fns.extend(m.compiled_fns)
                if m.recorder is not None:
                    recorders.append(m.recorder)
        if recorders:
            from types import SimpleNamespace
            agg.recorder = SimpleNamespace(
                emitted=sum(r.emitted for r in recorders),
                dropped=sum(r.dropped for r in recorders))
        return agg

    def explain(self, rid: str) -> list[dict]:
        """The decision-audit trail for one request — why it landed
        where it did and why it moved (route/migrate/shed entries still
        in the bounded ring)."""
        return self.audit.for_request(rid)

    def slo_stats(self) -> dict:
        """Windowed SLO burn (fleet_summary()["slo"]): deadline misses
        and sheds over the trailing ``slo_window_s`` — the burn-rate
        numbers an alert fires on, next to the all-time totals."""
        now = self._clock()
        return {
            "window_s": self.slo_window_s,
            "deadline_miss_window": self._slo_deadline.count(now),
            "shed_window": self._slo_shed.count(now),
            "deadline_miss_per_s": self._slo_deadline.rate(now),
            "shed_per_s": self._slo_shed.rate(now),
            "deadline_miss_total": self._slo_deadline.total,
            "shed_total": self._slo_shed.total,
        }

    def fleet_summary(self) -> dict:
        """One dict of fleet state: per-replica health/lives/load, the
        routing + migration counters, the MERGED SLO latency percentiles
        (``latency`` — exact histogram merge across replicas, dead lives
        included), the windowed SLO burn (``slo``), and the decision-
        audit occupancy (``audit``) — the fleet twin of
        ``ServeMetrics.summary``."""
        reps = {}
        for name, rep in self.replicas.items():
            r = {
                "state": rep.state.value,
                "role": rep.role,
                "life": rep.life,
                "restarts": rep.restarts,
                "death_reason": rep.death_reason,
            }
            if rep.engine is not None:
                load = rep.load()
                r.update(queue_depth=load.queue_depth,
                         running=load.running,
                         kv_util=round(load.kv_util, 4),
                         completed=rep.engine.metrics.completed,
                         migrated_in=rep.engine.metrics.migrated_in,
                         migrated_out=rep.engine.metrics.migrated_out,
                         pushed_in=rep.engine.metrics.pushed_in,
                         pushed_out=rep.engine.metrics.pushed_out)
            reps[name] = r
        return {
            "fleet_id": self.fleet_id,
            "replicas": reps,
            "steps": self.steps,
            "deaths": self.deaths,
            "migrations": self.migrations,
            "completed": len(self.outputs),
            "pending": len(self._pending_reqs) + len(self._pending_recs),
            "latency": self.aggregate_metrics().latency_stats(),
            "slo": self.slo_stats(),
            "pressure_smoothed": round(self._scale_state["ema"], 4),
            "scale": {"ups": self.scale_ups, "downs": self.scale_downs,
                      "retired": sorted(self.retired)},
            "ingress_shed": dict(sorted(
                self.ingress_shed_by_class.items())),
            "audit": {"recorded": self.audit.recorded,
                      "dropped": self.audit.dropped},
        }

    def to_prometheus(self) -> str:
        """The fleet's Prometheus exposition: the per-engine ``serve_*``
        series AGGREGATED across replicas (counters summed, histograms
        bucket-exactly merged — :meth:`aggregate_metrics`), plus the
        controller-level ``fleet_*`` series (:data:`FLEET_SERIES`,
        documented in docs/observability.md).  Subprocess fleets build
        the same serve_* aggregate with :func:`merge_scrapes`."""
        now = self._clock()
        states: dict[str, int] = {}
        for rep in self.replicas.values():
            states[rep.state.value] = states.get(rep.state.value, 0) + 1
        L = ["# TYPE fleet_replicas gauge"]
        for state in sorted(states):
            L.append(f'fleet_replicas{{state="{state}"}} {states[state]}')
        # per-replica one-hot health: pressure alone can look fine
        # while a breaker is open — alerting needs to see WHICH replica
        # is SUSPECT/DEAD
        L.extend(replica_state_lines(
            (name, self.replicas[name].state)
            for name in sorted(self.replicas)))
        # per-replica routing role — the disagg tier's shape next to
        # its health (constant "both" one-hots for homogeneous fleets)
        L.extend(replica_role_lines(
            (name, self.replicas[name].role)
            for name in sorted(self.replicas)))
        L.append("# TYPE fleet_lives_total counter")
        L.append(f"fleet_lives_total "
                 f"{sum(r.life for r in self.replicas.values())}")
        L.append("# TYPE fleet_deaths_total counter")
        L.append(f"fleet_deaths_total {self.deaths}")
        L.append("# TYPE fleet_migrations_total counter")
        L.append(f"fleet_migrations_total {self.migrations}")
        L.append("# TYPE fleet_completed_total counter")
        L.append(f"fleet_completed_total {len(self.outputs)}")
        L.append("# TYPE fleet_steps_total counter")
        L.append(f"fleet_steps_total {self.steps}")
        L.append("# TYPE fleet_pending gauge")
        L.append(f"fleet_pending "
                 f"{len(self._pending_reqs) + len(self._pending_recs)}")
        L.append("# TYPE fleet_deadline_miss_window gauge")
        L.append(f"fleet_deadline_miss_window "
                 f"{self._slo_deadline.count(now)}")
        L.append("# TYPE fleet_shed_window gauge")
        L.append(f"fleet_shed_window {self._slo_shed.count(now)}")
        L.append("# TYPE fleet_deadline_miss_per_s gauge")
        L.append(f"fleet_deadline_miss_per_s "
                 f"{self._slo_deadline.rate(now):.6g}")
        L.append("# TYPE fleet_shed_per_s gauge")
        L.append(f"fleet_shed_per_s {self._slo_shed.rate(now):.6g}")
        L.append("# TYPE fleet_audit_records_total counter")
        L.append(f"fleet_audit_records_total {self.audit.recorded}")
        L.append("# TYPE fleet_pressure_smoothed gauge")
        L.append(f"fleet_pressure_smoothed "
                 f"{self._scale_state['ema']:.6g}")
        L.append("# TYPE fleet_scale_ups_total counter")
        L.append(f"fleet_scale_ups_total {self.scale_ups}")
        L.append("# TYPE fleet_scale_downs_total counter")
        L.append(f"fleet_scale_downs_total {self.scale_downs}")
        L.append("# TYPE fleet_ingress_shed_total counter")
        for k in SLO_CLASSES:
            L.append(f'fleet_ingress_shed_total{{slo_class="{k}"}} '
                     f'{self.ingress_shed_by_class.get(k, 0)}')
        return "\n".join(L) + "\n" + self.aggregate_metrics().to_prometheus()

    # -- the merged fleet timeline ----------------------------------------

    def _trace_sources(self) -> list:
        """``[(name, pid, events), ...]`` — the controller ring plus one
        entry per replica: the live engine's ring, preceded by every
        dead life's postmortem flight events (the ring dies with the
        life; the crash-path ``force=True`` flush is where it
        survives)."""
        sources = [("fleet", FLEET_PID, self.trace.events())]
        for i, (name, rep) in enumerate(self.replicas.items()):
            evs: list = []
            for life in range(1, rep.life + 1):
                if rep.engine is not None and life == rep.life:
                    continue   # the live ring below covers this life
                fl = latest_flight(os.path.join(rep.root, f"life{life}"))
                if fl is None:
                    continue
                try:
                    evs.extend(tuple(e)
                               for e in load_flight(fl).get("events", ()))
                except (OSError, ValueError):
                    continue
            if rep.engine is not None:
                evs.extend(rep.engine.trace.events())
            sources.append((name, FLEET_REPLICA_PID_BASE + i, evs))
        return sources

    def to_perfetto(self) -> dict:
        """ONE fleet timeline as a Chrome trace: the controller's
        routing/health track plus every replica's engine timeline under
        its own replica-namespaced pid, with Perfetto flow arrows
        linking each ``migrate_out``→``migrate_in`` pair — a migrated
        request reads as one continuous journey across replica tracks
        (docs/observability.md "Fleet observability").  Dead lives'
        events come from their postmortem flight files; a request's
        carried ring tail also re-renders on its adopting replica (the
        same journey seen from both sides — intentional)."""
        srcs = self._trace_sources()
        events: list[dict] = []
        tids: dict[int, dict] = {}
        for name, pid, evs in srcs:
            pname = ("fleet controller" if pid == FLEET_PID
                     else f"replica {name} (serve engine)")
            tids[pid] = {}
            events.extend(events_to_perfetto(evs, pid=pid,
                                             process_name=pname,
                                             tids_out=tids[pid]))
        # flows bind replica-side events only: the controller also logs
        # migrate_in, and anchoring there would draw arrows to the
        # routing track instead of across replicas
        events.extend(link_migration_flows(
            [(pid, evs) for _, pid, evs in srcs if pid != FLEET_PID],
            tids))
        return {"traceEvents": events}

    def export_perfetto(self, path: str) -> str:
        """Write :meth:`to_perfetto` to ``path`` (gzipped on ``.gz``)."""
        return write_trace(self.to_perfetto(), path)

    def export_profile(self, job_dir: str, rank: int = 0) -> str:
        """Drop the merged fleet timeline where
        ``runtime.profiling.merge_rank_traces`` globs per-rank traces
        (``{job_dir}/rank{rank}/fleet.trace.json.gz``): run a
        ``group_profile`` capture into the same ``job_dir``, call this,
        then merge — ONE ui.perfetto.dev file holds the device timeline,
        the controller, and every replica side by side
        (docs/observability.md has the recipe)."""
        out = os.path.join(job_dir, f"rank{rank}", "fleet.trace.json.gz")
        return write_trace(self.to_perfetto(), out)

    def flight_flush(self, reason: str) -> Optional[str]:
        """Fleet postmortem: the controller ring + the decision audit to
        ``{root}/flight_<step>.json`` (the supervisor's postmortem glob
        and ``load_flight`` both read it).  Deliberately UNthrottled
        within a step: a second replica death in the same fleet step
        re-flushes — overwriting the same file with a superset of the
        ring — instead of silently losing the later death's record;
        flush volume is bounded by death count anyway.  Best-effort
        like the engine's."""
        if self.trace.level <= 0:
            return None
        self.trace.set_step(self.steps)
        try:
            return self.trace.flush(
                self.root, reason=reason,
                extra={"audit": self.audit.entries(),
                       "slo": self.slo_stats()})
        except Exception:  # noqa: BLE001 — crash-path best effort
            return None


# ---------------------------------------------------------------------------
# Subprocess fleets: scrape-and-merge metrics + flight-file timeline
# assembly (no in-process controller to ask)
# ---------------------------------------------------------------------------

#: Histogram base names in the ``serve_*`` exposition (the five SLO
#: histograms ``ServeMetrics.to_prometheus`` emits) — what
#: :func:`merge_scrapes` reconstructs bucket-exactly instead of summing
#: raw series.
SCRAPE_HISTOGRAMS = (
    "serve_ttft_seconds", "serve_itl_seconds",
    "serve_queue_time_seconds", "serve_step_time_seconds",
    "serve_snapshot_seconds",
)

#: The labeled per-program wall-time histogram family
#: (``serve_program_ms{program="..."}``, docs/observability.md "Kernel
#: observability"): :func:`merge_scrapes` discovers the program labels
#: per scrape and rebuilds each program's histogram bucket-exactly,
#: like the unlabeled SLO histograms above.
PROGRAM_HISTOGRAM = "serve_program_ms"


def _scrape_program_labels(series: dict) -> list:
    """Program names present in one scrape's ``serve_program_ms``
    family (from the ``_count{program="..."}`` series)."""
    prefix = PROGRAM_HISTOGRAM + '_count{program="'
    return [key[len(prefix):-2] for key in series
            if key.startswith(prefix)]


def merge_scrapes(texts: list) -> str:
    """Merge per-replica ``/metrics`` scrape texts into ONE fleet-level
    ``serve_*`` exposition — the subprocess twin of
    ``FleetController.aggregate_metrics`` (docs/observability.md "Fleet
    observability").

    Counters (and labeled counter families) sum per series; gauges sum
    except ``serve_kv_utilization`` (a ratio: the merged exposition
    reports the max — the pressure signal an operator actually wants)
    and ``serve_kv_bytes_per_token`` (re-derived from the summed
    pool-bytes / token-slots series, never summed as a quotient);
    the five SLO histograms are REBUILT per scrape
    (``LogHistogram.from_prom`` de-accumulates the dense cumulative
    buckets) and merged count-wise, so the merged percentiles equal the
    pooled-sample histogram bucket-exactly even when replicas reached
    different bucket depths — summing raw ``_bucket`` series per ``le``
    would undercount exactly there (the tier-1 merge-vs-pooled test
    pins this)."""
    hists = {h: LogHistogram() for h in SCRAPE_HISTOGRAMS}
    prog_hists: dict[str, LogHistogram] = {}
    sums: dict[str, float] = {}
    maxes: dict[str, float] = {}
    types: dict[str, str] = {}
    order: list[str] = []
    for text in texts:
        g = parse_prometheus(text)
        for h, acc in hists.items():
            acc.merge(LogHistogram.from_prom(g, h))
        # per-program wall-time family: rebuild each labeled member
        # bucket-exactly (a program only one replica ran still joins)
        for prog in _scrape_program_labels(g):
            prog_hists.setdefault(prog, LogHistogram()).merge(
                LogHistogram.from_prom(g, PROGRAM_HISTOGRAM,
                                       labels=f'program="{prog}"'))
        for key, v in g.items():
            base = key.split("{", 1)[0]
            if any(base == h or base.startswith(h + "_")
                   for h in SCRAPE_HISTOGRAMS + (PROGRAM_HISTOGRAM,)):
                continue   # histogram series: rebuilt above
            if key not in sums and key not in maxes:
                order.append(key)
            if base == "serve_kv_utilization":
                maxes[key] = max(maxes.get(key, 0.0), v)
            else:
                sums[key] = sums.get(key, 0.0) + v
        for line in text.splitlines():
            if line.startswith("# TYPE "):
                parts = line.split()
                if len(parts) == 4:
                    types.setdefault(parts[2], parts[3])
    # bytes/token is a RATIO: summing per-replica quotients is
    # meaningless — re-derive it from the summed pool-bytes and
    # token-slots series so a mixed int8/fp fleet reports its true
    # blended quotient (the serve-side twin of ServeMetrics.merge)
    if "serve_kv_bytes_per_token" in sums:
        slots = sums.get("serve_kv_token_slots", 0.0)
        sums["serve_kv_bytes_per_token"] = (
            sums.get("serve_kv_pool_bytes", 0.0) / slots if slots
            else 0.0)
    L: list[str] = []
    typed: set = set()
    for key in order:
        base = key.split("{", 1)[0]
        if base in types and base not in typed:
            typed.add(base)
            L.append(f"# TYPE {base} {types[base]}")
        v = maxes.get(key, sums.get(key, 0.0))
        L.append(f"{key} {v:.17g}")
    for h, acc in hists.items():
        L.extend(acc.prom_lines(h))
    for i, prog in enumerate(sorted(prog_hists)):
        L.extend(prog_hists[prog].prom_lines(
            PROGRAM_HISTOGRAM, labels=f'program="{prog}"', typed=i == 0))
    return "\n".join(L) + "\n"


def assemble_fleet_trace(sources: list, path: str) -> Optional[str]:
    """Assemble a merged fleet Perfetto file for a SUBPROCESS fleet from
    the per-replica artifacts the supervisor already knows: ``sources``
    is ``[(name, dir_or_path), ...]`` — a replica's snapshot directory
    (every ``flight_*.json`` under it is read, life subdirectories
    included, plus any exported ``*.trace.json[.gz]`` engine traces) or
    one such file directly.

    Flight-file events render under the replica's own pid
    (``FLEET_REPLICA_PID_BASE + index``) with migration flow arrows
    linked across replicas, exactly like the in-process
    ``FleetController.export_perfetto``; already-rendered engine-trace
    documents pass through re-pid'd onto the same replica pid — the
    supervisor's ``--fleet-trace-out`` writes this at exit.  Returns
    the written path, or ``None`` when no source held any events."""
    import gzip
    import json

    srcs = []
    rendered: list[dict] = []
    for i, (name, src) in enumerate(sources):
        pid = FLEET_REPLICA_PID_BASE + i
        flight_paths, trace_paths = [], []
        if os.path.isdir(src):
            # newest flight per directory level only (the replica dir
            # itself + each life subdir): successive flushes of one
            # life carry OVERLAPPING ring tails, and rendering them all
            # would duplicate every span — same dedupe rule as the
            # in-process _trace_sources
            flight_paths = [p for p in
                            [latest_flight(src)]
                            + [latest_flight(d) for d in sorted(
                                glob.glob(os.path.join(src, "life*")))]
                            if p is not None]
            trace_paths = sorted(
                glob.glob(os.path.join(src, "**", "*.trace.json"),
                          recursive=True)
                + glob.glob(os.path.join(src, "**", "*.trace.json.gz"),
                            recursive=True))
        elif os.path.exists(src):
            if src.endswith((".trace.json", ".trace.json.gz")):
                trace_paths = [src]
            else:
                flight_paths = [src]
        evs: list = []
        for p in flight_paths:
            try:
                evs.extend(tuple(e)
                           for e in load_flight(p).get("events", ()))
            except (OSError, ValueError):
                continue
        for p in trace_paths:
            try:
                opener = gzip.open if p.endswith(".gz") else open
                with opener(p, "rt") as f:
                    doc = json.load(f)
            except (OSError, ValueError):
                continue
            for ev in doc.get("traceEvents", ()):
                if "pid" in ev:
                    ev = {**ev, "pid": pid}
                rendered.append(ev)
        srcs.append((name, pid, evs))
    if not any(evs for _, _, evs in srcs) and not rendered:
        return None
    events: list[dict] = []
    tids: dict[int, dict] = {}
    for name, pid, evs in srcs:
        if evs:
            tids[pid] = {}
            events.extend(events_to_perfetto(
                evs, pid=pid,
                process_name=f"replica {name} (serve engine)",
                tids_out=tids[pid]))
    events.extend(rendered)
    events.extend(link_migration_flows(
        [(pid, evs) for _, pid, evs in srcs], tids))
    return write_trace({"traceEvents": events}, path)
