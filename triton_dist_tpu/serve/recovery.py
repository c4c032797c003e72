"""Crash-resilient serving: engine snapshot/restore + the token journal.

PR 3 contained faults *within* a live engine process; this module makes
the process itself expendable.  A TPU preemption, an OOM-kill, or a host
crash used to lose every in-flight request and every block of paged KV —
here the full serving state becomes durable and a fresh process resumes
every stream **bit-identically** to the uninterrupted run (the MegaScale
/ Llumnix primitive: snapshot + exactly-once replay).

Two cooperating artifacts live under one snapshot directory:

``journal.jsonl``
    An append-only token journal.  ``submit`` records (prompt, sampling
    params — including the PRNG seed whose per-token ``fold_in`` stream
    makes sampled recompute deterministic), one ``tok`` record per
    committed token (appended the moment the engine commits, BEFORE the
    ``on_token`` callback fires), and a ``fin`` record per retirement.
    The journal is flushed per record, so it is never behind the tokens
    the engine has emitted by more than the record being written.

``kv/<step>/``
    Orbax KV snapshots via :class:`runtime.checkpoint.CheckpointManager`
    (tmp-dir + rename: a kill mid-snapshot leaves the previous snapshot
    intact).  Each step dir holds the paged K/V pools plus a
    ``meta.json`` manifest written into the SAME rename barrier: engine
    geometry, block tables + free-list implied state, and per-request
    device state (kv_lens, pending token, slot, deadline-relevant
    timestamps).  The manifest also embeds each request's prompt,
    params, and emitted tokens, so a snapshot is self-contained even
    without the journal.

**The exactly-once argument.**  The journal is the source of truth for
*emission*; the KV snapshot is only an accelerator.  A token is emitted
iff it is journaled; generation is deterministic given (prompt, params,
emission index) — greedy by argmax, sampled via the per-request
``fold_in(key(seed), index)`` stream — so on restore:

- tokens **in** the journal are restored into ``generated`` and never
  re-derived → never double-emitted, even when the crash landed between
  the device KV commit and the journal append (the device-side token
  simply recomputes to the identical value);
- tokens the device committed but the journal never saw are re-derived
  bit-identically through the exact-recompute preemption path
  (``work_prompt = prompt + generated``) → never dropped.

When the KV snapshot lags the journal (incremental mode:
``snapshot_every=N`` steps while the journal appends per commit), the
journal-ahead suffix replays through that same recompute path; a request
whose journal count matches the snapshot resumes *in place* — pools,
block table, pending token — with zero recompute.  Restore onto a
DIFFERENT engine geometry degrades the same way: requests whose blocks
no longer fit re-queue through admission and recompute, and streams stay
bit-exact because the per-request token function never depended on the
geometry.  Quarantined (ERROR), shed, and expired requests restore as
*finished* — a poisoned request is never resurrected.

Callback delivery across the crash is at-most-once for the single
in-flight token (journaled, then the process died before its
``on_token`` ran); ``restore(..., replay_tokens=True)`` flips that to
at-least-once by re-firing callbacks for every journaled token.  The
emitted *stream* is exactly-once either way.

See docs/serving.md "Crash recovery"; chaos coverage lives in
tests/test_serve_recovery.py (kill/restart at every crash window).
"""

from __future__ import annotations

import json
import os
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Optional, Union

import jax
import numpy as np

from triton_dist_tpu.runtime import checkpoint as ck
from triton_dist_tpu.runtime.faults import CORRUPT_ACTIONS, corrupt_bytes
from triton_dist_tpu.serve.integrity import (
    DOC_CRC,
    atomic_write_json,
    canonical_crc,
    crc32_bytes,
    rec_crc_ok,
    stamp_crc,
    verify_json_doc,
)
from triton_dist_tpu.serve.metrics import RequestMetrics
from triton_dist_tpu.serve.request import (
    FinishReason,
    Request,
    RequestOutput,
    SamplingParams,
)
from triton_dist_tpu.serve.scheduler import ReqState, Status

SNAPSHOT_FORMAT = 1
JOURNAL_NAME = "journal.jsonl"
KV_SUBDIR = "kv"
META_NAME = "meta.json"
#: meta.json's self-digest field (over the manifest minus this key)
META_CRC = "meta_crc"


class JournalCorrupt(RuntimeError):
    """A journal with INTERIOR damage (an undecodable or CRC-mismatched
    non-final line, or a token-index gap) — distinct from the tolerated
    torn FINAL line a crash mid-append leaves.  Carries the salvaged
    state (every record that still authenticates, ``state``) and the structured
    :class:`JournalDamage` report (``damage``): a caller that can
    salvage goes through :func:`salvage_journal`; one that cannot must
    fail loudly rather than silently absorb token loss."""

    def __init__(self, damage: "JournalDamage",
                 state: dict[str, "JournalRequest"]):
        super().__init__(str(damage))
        self.damage = damage
        self.state = state


class SnapshotCorrupt(RuntimeError):
    """A PUBLISHED snapshot failed digest verification (a pool leaf or
    the meta.json manifest) — bit rot, not a torn write (torn writes
    never survive the tmp-dir + rename publish and fall back to the
    previous step).  Never caught by the restore fallback walk: a
    corrupt snapshot must fail loudly naming the bad leaf, and the
    operator (or ``scripts/serve_fsck.py --salvage``) quarantines the
    step so restore can use an older snapshot + the journal."""


# ---------------------------------------------------------------------------
# The token journal
# ---------------------------------------------------------------------------


class TokenJournal:
    """Append-only JSONL journal of submissions, token commits, and
    retirements.  Flushed per record (optionally fsynced with
    ``fsync=True`` — the engine's ``journal_fsync``); :meth:`sync`
    forces durability at snapshot barriers regardless.

    **Group commit** (``fsync_interval_s=``, ROADMAP #5a): a per-record
    ``fsync`` costs a disk round trip per token — batching it to at most
    one fsync per interval keeps the power-loss window bounded by the
    interval instead of unbounded (flush-only) without paying the
    per-token sync.  ``sync()`` (the snapshot barrier) always fsyncs,
    so the KV snapshot can never publish ahead of the journal.

    **Compaction** (:meth:`rewrite`): the engine rewrites the journal at
    snapshot barriers — finished requests collapse into single ``done``
    records — through an atomic tmp + rename, so the file stops growing
    with every token ever served; a crash anywhere during the rewrite
    leaves either the old or the new journal whole.

    **Integrity framing** (docs/serving.md "Durability & integrity"):
    every appended/rewritten record carries a CRC32 of its canonical
    JSON under ``"c"`` — :func:`replay_journal` verifies per line and
    distinguishes a torn final line (tolerated, as ever) from interior
    corruption (loud salvage).  ``faults=`` threads the engine's
    injector so the ``integrity`` point can damage a line's bytes
    BEFORE they hit disk (the chaos seam the verifiers are proved
    against)."""

    def __init__(self, path: str | os.PathLike, *, fsync: bool = False,
                 fsync_interval_s: Optional[float] = None, faults=None):
        self.faults = faults
        self.path = os.path.abspath(os.fspath(path))
        parent = os.path.dirname(self.path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        # A tmp file is an aborted rewrite (the process died between
        # writing it and the rename): the original journal is whole, the
        # orphan is garbage.
        try:
            os.unlink(self.path + ".tmp")
        except OSError:
            pass
        self._heal_torn_tail()
        self._f = open(self.path, "a", encoding="utf-8")
        self.fsync = bool(fsync)
        self.fsync_interval_s = fsync_interval_s
        self._last_fsync = time.monotonic()
        self._dirty = False  # flushed-but-not-fsynced tail
        self.records = 0   # appended by THIS process (not the file total)
        self.bytes = 0
        self.file_bytes = os.path.getsize(self.path)

    def _heal_torn_tail(self) -> None:
        """Truncate a partial final line before appending: a crash
        mid-append leaves a torn record, and appending to it would glue
        the NEXT record onto the garbage — corrupting a healthy commit,
        not just the already-lost one.  Scans backward in windows, so a
        torn record of ANY size (a submit with a very long prompt can
        exceed one window) truncates to the last complete line rather
        than taking healthy earlier records with it."""
        if not os.path.exists(self.path):
            return
        with open(self.path, "rb+") as f:
            f.seek(0, os.SEEK_END)
            size = f.tell()
            if not size:
                return
            pos = size
            while pos > 0:
                back = min(pos, 1 << 16)
                f.seek(pos - back)
                chunk = f.read(back)
                if pos == size and chunk.endswith(b"\n"):
                    return            # tail is whole
                cut = chunk.rfind(b"\n")
                if cut >= 0:
                    f.truncate(pos - back + cut + 1)
                    return
                pos -= back
            f.truncate(0)             # a single torn line was the file

    def append(self, rec: dict) -> None:
        rec = stamp_crc(rec)
        body = json.dumps(rec, separators=(",", ":"))
        if self.faults is not None:
            act = self.faults.fire("integrity", op="journal",
                                   rid=rec.get("rid"))
            if act in CORRUPT_ACTIONS:
                # damage the LINE bytes, keep the line framing: the
                # corruption lands inside one record, which is exactly
                # the interior-damage class replay must catch loudly
                raw = corrupt_bytes(body.encode("utf-8"), act)
                body = raw.decode("utf-8", errors="replace")
        line = body + "\n"
        self._f.write(line)
        self._f.flush()
        self._dirty = True
        if self.fsync:
            os.fsync(self._f.fileno())
            self._last_fsync = time.monotonic()
            self._dirty = False
        elif self.fsync_interval_s is not None:
            now = time.monotonic()
            if now - self._last_fsync >= self.fsync_interval_s:
                os.fsync(self._f.fileno())
                self._last_fsync = now
                self._dirty = False
        self.records += 1
        self.bytes += len(line)
        self.file_bytes += len(line)

    def submit(self, req: Request) -> None:
        rec = {"t": "submit", "rid": req.request_id,
               "prompt": [int(x) for x in req.prompt],
               "params": req.params.to_dict(),
               "slo": req.slo_class,
               "ts": req.arrival_time}
        if getattr(req, "trace", None):
            # the distributed-tracing context rides the journal so a
            # crash-path manifest (manifest_from_journal) hands the
            # journey — trace id + hop — to the adopting replica
            rec["trace"] = req.trace
        self.append(rec)

    def token(self, rid: str, index: int, tok: int, ts: float) -> None:
        self.append({"t": "tok", "rid": rid, "i": int(index),
                     "tok": int(tok), "ts": ts})

    def finish(self, rid: str, reason: str, error: Optional[str],
               n_tokens: int, ts: float) -> None:
        self.append({"t": "fin", "rid": rid, "reason": reason,
                     "err": error, "n": int(n_tokens), "ts": ts})

    def migrate(self, rid: str, n_tokens: int, ts: float) -> None:
        """Record a live-migration hand-off: ``rid`` left this engine
        for another replica (docs/serving.md "Fleet serving").  The
        record is the ownership transfer — a restore of THIS journal
        must never resurrect the request (the target replica's journal
        now owns its remaining stream), which is exactly what makes the
        cross-replica token union exactly-once."""
        self.append({"t": "mig", "rid": rid, "n": int(n_tokens),
                     "ts": ts})

    def sync(self) -> None:
        """Force everything appended so far to disk (snapshot barrier)."""
        self._f.flush()
        os.fsync(self._f.fileno())
        self._last_fsync = time.monotonic()
        self._dirty = False

    def maybe_sync(self) -> None:
        """Group-commit deadline sweep — the engine calls this every
        step.  ``append`` only checks the fsync interval when the NEXT
        record arrives, so without a sweep the last record of a burst
        would sit in the OS page cache for as long as traffic pauses —
        exactly the unbounded power-loss window ``fsync_interval_s``
        exists to bound."""
        if (self._dirty and self.fsync_interval_s is not None
                and time.monotonic() - self._last_fsync
                >= self.fsync_interval_s):
            self.sync()

    def rewrite(self, records: list[dict]) -> None:
        """Atomically replace the journal's contents with ``records``
        (the engine's snapshot-barrier compaction).  tmp + fsync +
        rename: readers and a crash at any instant see either the old
        journal or the complete new one, never a torn mix.  Every
        record is (re-)stamped with its CRC framing — compaction
        produces fresh record shapes, so digests must be recomputed."""
        tmp = self.path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            for rec in records:
                f.write(json.dumps(stamp_crc(rec),
                                   separators=(",", ":")) + "\n")
            f.flush()
            os.fsync(f.fileno())
        self._f.close()
        os.replace(tmp, self.path)
        self._f = open(self.path, "a", encoding="utf-8")
        self._last_fsync = time.monotonic()
        self._dirty = False
        self.file_bytes = os.path.getsize(self.path)

    def close(self) -> None:
        try:
            self._f.close()
        except Exception:  # noqa: BLE001 — crash-path best effort
            pass


@dataclass
class JournalRequest:
    """One request's journal view after :func:`replay_journal`."""

    rid: str
    prompt: Optional[np.ndarray] = None
    params: Optional[SamplingParams] = None
    arrival: Optional[float] = None
    tokens: dict = field(default_factory=dict)   # index -> (tok, ts)
    finish: Optional[dict] = None                # {"reason","err","n","ts"}
    # ownership left this journal via a live-migration hand-off ("mig"
    # record): restore must not resurrect the request, and the request
    # is not part of this engine's finish accounting either
    migrated: bool = False
    # first-token timestamp carried by rotation records ("ftt"): the
    # compacted tts/ts lists None-pad their head past the bounded
    # token-time window, so the restored TTFT needs this explicitly
    first_tok: Optional[float] = None
    # distributed-tracing context from the submit record ({"trace_id",
    # "hop"}) — crash-path manifests carry it so the journey survives
    # the replica (docs/observability.md "Fleet observability")
    trace: Optional[dict] = None
    # SLO class from the submit record — a restored/migrated request
    # keeps its service tier ("interactive" covers pre-slo journals)
    slo: str = "interactive"

    def token_list(self) -> list[int]:
        """Emitted tokens in order (the contiguous prefix from 0).  A
        gap is journal corruption — :func:`scan_journal` reports it as
        damage (never silently absorbed; the pre-integrity silent
        truncation was the ISSUE-20 bug) and the salvage keeps exactly
        this contiguous prefix."""
        out = []
        i = 0
        while i in self.tokens:
            out.append(self.tokens[i][0])
            i += 1
        return out

    def token_times(self) -> list[float]:
        out = []
        i = 0
        while i in self.tokens:
            out.append(self.tokens[i][1])
            i += 1
        return out


def _apply_record(out: dict[str, JournalRequest], rec: dict) -> None:
    """Fold one decoded journal record into the replay state (shared by
    the salvage scan and any future incremental reader)."""
    rid = rec.get("rid")
    if rid is None:
        return
    jr = out.setdefault(rid, JournalRequest(rid=rid))
    t = rec.get("t")
    if t == "submit":
        if jr.prompt is None:
            jr.prompt = np.asarray(rec["prompt"], np.int32)
            jr.params = SamplingParams.from_dict(rec["params"])
            jr.arrival = rec.get("ts")
            jr.slo = rec.get("slo", "interactive")
            if jr.first_tok is None:
                jr.first_tok = rec.get("ftt")
            if jr.trace is None:
                jr.trace = rec.get("trace")
        # a submit AFTER a mig receipt re-opens ownership: the
        # request was handed off (push/drain) and later
        # re-admitted HERE (the disagg push fallback path) —
        # this journal owns its stream again, and a crash must
        # recover it rather than skip it as migrated
        jr.migrated = False
    elif t == "tok":
        jr.tokens.setdefault(int(rec["i"]),
                             (int(rec["tok"]), rec.get("ts")))
    elif t == "fin" and jr.finish is None:
        jr.finish = {"reason": rec["reason"],
                     "err": rec.get("err"),
                     "n": rec.get("n"), "ts": rec.get("ts")}
    elif t == "mig":
        jr.migrated = True
    elif t == "done":
        # One-line compacted request (a snapshot-barrier journal
        # rotation): submit + every tok + fin folded together.
        if jr.prompt is None:
            jr.prompt = np.asarray(rec["prompt"], np.int32)
            jr.params = SamplingParams.from_dict(rec["params"])
            jr.arrival = rec.get("arrival")
            jr.slo = rec.get("slo", "interactive")
        if jr.first_tok is None:
            jr.first_tok = rec.get("ftt")
        tts = rec.get("tts") or []
        for i, tok in enumerate(rec.get("toks", [])):
            jr.tokens.setdefault(
                i, (int(tok), tts[i] if i < len(tts) else None))
        if jr.finish is None:
            jr.finish = {"reason": rec["reason"],
                         "err": rec.get("err"),
                         "n": len(rec.get("toks", [])),
                         "ts": rec.get("fts")}


@dataclass
class JournalDamage:
    """Structured damage report for a corrupt journal (what the salvage
    kept and what it lost) — the payload of :class:`JournalCorrupt`,
    the ``corrupt`` trace event, and the crash-path manifest's
    ``damage`` field."""

    path: str
    #: (1-based line number, reason) per damaged line — every line the
    #: salvage skipped (the records around them still apply: each line
    #: authenticates independently)
    bad_lines: list = field(default_factory=list)
    #: (rid, first missing token index) per token-index gap — damage
    #: even in a pre-integrity journal (an interior tok line vanished)
    gaps: list = field(default_factory=list)
    #: rids that lost records (bad-line owners where readable, gap
    #: owners, rids dropped for a rotted submit)
    affected_rids: list = field(default_factory=list)
    #: last contiguous token index the salvage kept, per affected rid
    #: (-1 when nothing of the stream survived)
    last_good_tok: dict = field(default_factory=dict)
    total_lines: int = 0
    salvaged_lines: int = 0
    #: where the damaged original went (``journal.jsonl.corrupt-<ts>``),
    #: once :func:`salvage_journal` quarantined it
    quarantine: Optional[str] = None

    def summary(self) -> dict:
        """JSON-able form (wire manifests, trace events)."""
        return {
            "path": self.path,
            "bad_lines": [[int(n), why] for n, why in self.bad_lines],
            "gaps": [[rid, int(i)] for rid, i in self.gaps],
            "affected_rids": list(self.affected_rids),
            "last_good_tok": {r: int(i)
                              for r, i in self.last_good_tok.items()},
            "total_lines": self.total_lines,
            "salvaged_lines": self.salvaged_lines,
            "quarantine": self.quarantine,
        }

    def __str__(self) -> str:
        first = self.bad_lines[0] if self.bad_lines else None
        what = (f"line {first[0]} ({first[1]})" if first
                else f"token gap {self.gaps[0]}" if self.gaps
                else "damage")
        return (f"journal {self.path} corrupt at {what}: salvaged "
                f"{self.salvaged_lines}/{self.total_lines} lines, "
                f"{len(self.affected_rids)} request(s) affected "
                f"({', '.join(self.affected_rids[:4])}"
                f"{'...' if len(self.affected_rids) > 4 else ''})")


def scan_journal(path: str | os.PathLike) \
        -> tuple[dict[str, JournalRequest], Optional[JournalDamage]]:
    """Parse a journal into per-request state (submit order) plus a
    damage report when the file holds more than crash-shaped damage.

    The tolerance contract (pinned by tests): a torn FINAL line — the
    one shape a crash mid-append leaves — is healed silently, exactly
    as before.  Everything else is damage, and the salvage keeps every
    record that still AUTHENTICATES: records are independently
    CRC-framed and self-describing (explicit token indices,
    first-submit-wins, idempotent fin/mig receipts), so a rotted line
    costs exactly the records on that line, not the suffix behind it —
    at fleet scale the suffix holds migrated-in submits whose prompts
    exist nowhere else.  A skipped tok line surfaces as a token-index
    gap (also a pre-integrity journal's only corruption signature) that
    truncates that rid to its contiguous prefix — and unfinishes it,
    when its ``fin`` receipt counted the lost token; a rid whose submit
    line rotted is dropped from state entirely (its prompt is
    unrecoverable here).  Both are REPORTED, never silently absorbed.
    Pre-integrity records (no ``"c"`` field) are accepted unverified —
    back-compat.  Returns ``({}, None)`` when no journal exists."""
    out: dict[str, JournalRequest] = {}
    if not os.path.exists(path):
        return out, None
    with open(path, encoding="utf-8") as f:
        lines = f.readlines()
    n_content = len(lines)
    while n_content and not lines[n_content - 1].strip():
        n_content -= 1  # trailing blank lines are not records
    bad: list = []
    affected: list[str] = []
    salvaged = 0
    for idx in range(n_content):
        line = lines[idx].strip()
        if not line:
            salvaged += 1
            continue
        why = None
        rec = None
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            why = "undecodable"
        if rec is not None and rec_crc_ok(rec) is False:
            why = "crc mismatch"
        if (why == "undecodable" and idx == n_content - 1
                and not lines[idx].endswith("\n")):
            # the torn final line a crash mid-append leaves (buffered
            # writes land prefixes, so a torn record never has its
            # newline): healed, not damage.  A newline-TERMINATED
            # garbage final line — or a CRC mismatch on a parseable
            # one — is real corruption: a torn write cannot re-close
            # the framing.
            break
        if why is not None:
            bad.append((idx + 1, why))
            # best-effort owner classification (report only — a record
            # that failed its CRC is never applied to state)
            if rec is not None:
                rid = rec.get("rid")
                if rid is not None and rid not in affected:
                    affected.append(rid)
            continue
        _apply_record(out, rec)
        salvaged += 1
    damage: Optional[JournalDamage] = None
    # a rid whose submit line ROTTED leaves orphan tok/fin records with
    # no prompt to recompute from: drop it from state (a half request
    # must not reach placement) and report it lost.  Only when damage
    # was seen — an undamaged journal that opens mid-stream (tok lines
    # with no submit) is the long-tolerated partial-state shape
    if bad:
        for rid in [r for r, jr in out.items()
                    if jr.prompt is None and not jr.migrated]:
            del out[rid]
            if rid not in affected:
                affected.append(rid)
    # token-index gaps inside the trusted records: the pre-integrity
    # corruption signature (a deleted/garbled interior tok line whose
    # loss JSON alone cannot see) — report it and truncate the stream
    # to its contiguous prefix instead of silently absorbing it
    gaps: list = []
    for rid, jr in out.items():
        if not jr.tokens:
            continue
        contiguous = len(jr.token_list())
        if max(jr.tokens) + 1 > contiguous:
            gaps.append((rid, contiguous))
            jr.tokens = {i: jr.tokens[i] for i in range(contiguous)}
            if rid not in affected:
                affected.append(rid)
    # a finish receipt counts its stream: one that claims more tokens
    # than still authenticate (a rotted tok line, the LAST one included —
    # no index gap shows that) is not a finish any more.  Kept, a restore
    # would adopt the shortened stream as complete; dropped, the request
    # re-queues and recomputes from its contiguous prefix, bit-exactly.
    if bad or gaps:
        for rid, jr in out.items():
            n = (jr.finish or {}).get("n")
            if n is not None and n != len(jr.token_list()):
                jr.finish = None
                if rid not in affected:
                    affected.append(rid)
    if bad or gaps:
        damage = JournalDamage(
            path=os.path.abspath(os.fspath(path)), bad_lines=bad,
            gaps=gaps, affected_rids=affected,
            last_good_tok={rid: len(out[rid].token_list()) - 1
                           if rid in out else -1 for rid in affected},
            total_lines=n_content, salvaged_lines=salvaged)
    return out, damage


def replay_journal(path: str | os.PathLike) -> dict[str, JournalRequest]:
    """Parse a journal into per-request state, in submit order.

    Tolerant of exactly the damage a crash can cause: a torn final line
    (the process died mid-append) is healed, and a duplicate record
    keeps its first occurrence.  Returns ``{}`` when no journal exists.
    ANY other damage — an interior undecodable line, a CRC mismatch, a
    token-index gap — raises :class:`JournalCorrupt` (carrying the
    salvaged state + damage report): silent absorption of committed
    tokens was the bug this layer exists to kill.  Callers that own the
    directory and can quarantine go through :func:`salvage_journal`."""
    state, damage = scan_journal(path)
    if damage is not None:
        raise JournalCorrupt(damage, state)
    return state


def _serialize_state(state: dict[str, JournalRequest]) -> list[dict]:
    """Re-serialize replayed state as plain journal records (the
    salvage writer): submit + contiguous toks + fin/mig per request, in
    submit order.  Equivalent-for-replay to the damaged journal's
    surviving records."""
    recs: list[dict] = []
    for rid, jr in state.items():
        if jr.prompt is not None:
            rec = {"t": "submit", "rid": rid,
                   "prompt": [int(x) for x in jr.prompt],
                   "params": jr.params.to_dict(),
                   "slo": jr.slo, "ts": jr.arrival}
            if jr.first_tok is not None:
                rec["ftt"] = jr.first_tok
            if jr.trace is not None:
                rec["trace"] = jr.trace
            recs.append(rec)
        for i, tok in enumerate(jr.token_list()):
            recs.append({"t": "tok", "rid": rid, "i": i,
                         "tok": int(tok), "ts": jr.tokens[i][1]})
        if jr.finish is not None:
            recs.append({"t": "fin", "rid": rid,
                         "reason": jr.finish["reason"],
                         "err": jr.finish.get("err"),
                         "n": jr.finish.get("n"),
                         "ts": jr.finish.get("ts")})
        if jr.migrated:
            recs.append({"t": "mig", "rid": rid,
                         "n": len(jr.token_list()),
                         "ts": jr.arrival or 0.0})
    return recs


def quarantine_path(path: str) -> str:
    """The ``<journal>.corrupt-<ts>`` name a damaged original moves to
    (unique even for same-second salvages)."""
    base = f"{path}.corrupt-{int(time.time())}"
    cand, n = base, 0
    while os.path.exists(cand):
        n += 1
        cand = f"{base}.{n}"
    return cand


def salvage_journal(path: str | os.PathLike, *, quarantine: bool = True) \
        -> tuple[dict[str, JournalRequest], Optional[JournalDamage]]:
    """Replay ``path`` with salvage semantics: an undamaged (or merely
    torn-tail) journal returns ``(state, None)`` untouched; a corrupt
    one QUARANTINES the damaged original (``journal.jsonl.corrupt-<ts>``
    — evidence survives for the postmortem, and no later writer appends
    onto rot) and atomically rewrites ``path`` with every record that
    still authenticates, CRC-framed, before anything else touches it.
    Returns the salvaged state + the damage report; the caller owns the
    LOUD part (counter, ``corrupt`` trace event, re-queue escalation)."""
    state, damage = scan_journal(path)
    if damage is None:
        return state, None
    path = os.path.abspath(os.fspath(path))
    if quarantine:
        qp = quarantine_path(path)
        os.replace(path, qp)
        damage.quarantine = qp
        tmp = path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            for rec in _serialize_state(state):
                f.write(json.dumps(stamp_crc(rec),
                                   separators=(",", ":")) + "\n")
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    print(f"[recovery] {damage}"
          + (f"; original quarantined at {damage.quarantine}"
             if damage.quarantine else ""), file=sys.stderr)
    return state, damage


# ---------------------------------------------------------------------------
# Snapshot
# ---------------------------------------------------------------------------


def _pool_tree(engine) -> dict:
    """The paged pools as a flat dict orbax round-trips losslessly.

    Spec engines (PR 7) also carry the DRAFT's device state: the
    slot-indexed batch caches, its lengths/logits, and the target's
    round-opening logits — everything a restored spec engine needs to
    resume rounds IN PLACE instead of re-prefilling every draft row
    through the preemption path (the recorded PR 5 follow-up).  A
    BAILED-OUT engine (``_spec_off``) snapshots pools-only: its draft
    state is untrusted by definition — and may reference buffers a
    failed chain's donation consumed, which orbax could not serialize
    anyway (the manifest omits ``draft`` in lockstep, so the reader
    never expects the keys)."""
    tree = {}
    for i, (k, v) in enumerate(engine._pools):
        if isinstance(k, dict):
            # int8 pools: quant + scale planes snapshot AS THEY ARE —
            # restore adopts the bytes verbatim (a dequant/requant round
            # trip would break bit-exactness; quantization isn't
            # idempotent)
            tree[f"l{i}_k_q"] = k["q"]
            tree[f"l{i}_k_s"] = k["s"]
            tree[f"l{i}_v_q"] = v["q"]
            tree[f"l{i}_v_s"] = v["s"]
        else:
            tree[f"l{i}_k"] = k
            tree[f"l{i}_v"] = v
    if engine.spec_k and not engine._spec_off:
        sd = engine._draft_state
        for i, (k, v) in enumerate(sd.caches):
            tree[f"d{i}_k"] = k
            tree[f"d{i}_v"] = v
        tree["draft_kv_lens"] = sd.kv_lens
        tree["draft_last_logits"] = sd.last_logits
        tree["spec_last_logits"] = engine._last_logits
    return tree


def _capture_meta(engine, now: float, *, journal_here: bool) -> dict:
    reqs = {}
    for rid, rs in engine._states.items():
        if rid.startswith("__warmup_") or rs.status is Status.FINISHED:
            continue
        reqs[rid] = {
            "status": rs.status.value,
            "slot": rs.slot,
            "kv_len": rs.kv_len,
            "gen": [int(t) for t in rs.generated],
            "pending": (int(rs.pending_token)
                        if rs.pending_token is not None else None),
            "seq": rs.seq,
            "cb_off": rs.callback_disabled,
            "arrival": rs.req.arrival_time,
            "prompt": [int(x) for x in np.asarray(rs.req.prompt)],
            "params": rs.req.params.to_dict(),
            "slo": rs.req.slo_class,
            "first_sched": rs.metrics.first_scheduled_time,
            "first_tok": rs.metrics.first_token_time,
            "token_times": list(rs.metrics.token_times),
            "n_preempt": rs.metrics.n_preemptions,
            "cached_prefix": rs.cached_prefix,
            "committed_pages": rs.committed_pages,
        }
    # Finished requests ride the manifest only when this directory has
    # no co-located journal to carry them (a one-shot snapshot to a
    # foreign dir): with the journal here, every retirement already has
    # its submit/tok/fin records (restore backfills prior lives), and
    # re-serializing the full served history into every capture would
    # make the snapshot hot-path cost grow with total requests served.
    outs = {}
    if not journal_here:
        for rid, out in engine._outputs.items():
            if rid.startswith("__warmup_"):
                continue
            outs[rid] = {
                "prompt": [int(x) for x in np.asarray(out.prompt)],
                "tokens": [int(t) for t in out.token_ids],
                "reason": out.finish_reason.value,
                "error": out.error,
                "arrival": out.metrics.arrival_time,
            }
    cfg = engine.cfg
    eng_meta = {
        "num_blocks": engine.bm.num_blocks,
        "page_size": engine.page,
        "max_batch": engine.max_batch,
        "max_seq": engine.gen.max_seq,
        "prefill_chunk": engine.scheduler.prefill_chunk,
        # the budget the engine was BUILT with (a brownout rung halves
        # the scheduler's; the width of a prefill call derives from this
        # one, alike on both sides of a restore)
        "prefill_budget": engine._base_prefill_budget,
        "horizon": engine.horizon,
        "pipeline": engine.pipeline,
        "spec_k": engine.spec_k,
        "prefix_cache": engine.prefix_cache,
        "snapshot_every": engine.snapshot_every,
        "n_layers": cfg.n_layers,
        "n_kv_heads": cfg.n_kv_heads,
        "head_dim": cfg.head_dim,
        "vocab": cfg.vocab,
        "kv_dtype": str(np.dtype(cfg.dtype)),
        # int8 pools change the tree layout (l{i}_k_q/_s planes) AND the
        # restore contract: quantized restores only into quantized
        # (tolerated absent by the reader — pre-quant snapshots are fp).
        "kv_quant": engine.kv_quant,
    }
    if engine.mesh is not None:
        # Mesh/sharding spec (docs/serving.md "Sharded serving"):
        # recorded so operators (and the fleet controller) can see what
        # layout produced a snapshot — restore does NOT require it.
        # Pools are saved as GLOBAL arrays (orbax assembles shards), so
        # a snapshot restores onto ANY mesh shape: the restoring
        # engine's own mesh= override decides the new layout, pools are
        # re-laid-out by one device_put, and block tables that violate
        # the new partition placement (seq layouts of a different
        # world) re-queue through exact recompute.  Tolerated absent by
        # every reader (pre-mesh snapshots restore fine).
        eng_meta["mesh"] = {
            "world": engine.mesh_world,
            "axis": engine.tp_axis,
            "kv_shard": engine.kv_shard,
            # 2D layouts record both axes (tolerated absent by every
            # reader — 1D and pre-mesh snapshots omit them)
            "sp_axis": engine.sp_axis,
            "sp_world": engine.sp_world,
        }
    if engine.spec_k and not engine._spec_off:
        # Draft-state geometry: the snapshot reader needs it to build
        # abstract targets for the draft arrays in the pool tree, and
        # restore checks it against the caller's draft before resuming
        # spec rows in place (mismatch -> exact-recompute requeue).
        # Omitted in lockstep with _pool_tree's draft subtree (a
        # spec_off snapshot is pools-only).
        dcfg = engine.draft.cfg
        eng_meta["draft"] = {
            "n_layers": dcfg.n_layers,
            "n_kv_heads": dcfg.n_kv_heads,
            "head_dim": dcfg.head_dim,
            "max_seq": engine.draft.max_seq,
            "vocab": dcfg.vocab,
            "dtype": str(np.dtype(dcfg.dtype)),
        }
    return {
        "format": SNAPSHOT_FORMAT,
        "clock": now,
        "engine": eng_meta,
        "spec_off": engine._spec_off,
        "seq_counter": engine.scheduler._seq,
        "waiting": [rs.req.request_id for rs in engine.scheduler.waiting
                    if not rs.req.request_id.startswith("__warmup_")],
        "tables": {rid: list(t) for rid, t in engine.bm._tables.items()
                   if not rid.startswith("__warmup_")},
        # Prefix cache (docs/serving.md "Prefix caching"): the content
        # index [block, parent, tokens-in-block] plus the LRU order of
        # the warm cache tier — restore re-registers live shared blocks
        # and re-admits the tier, so the warm cache survives a restart
        # (admit_cached as cache admission, the ROADMAP #3 design).
        "prefix": {
            "index": [[b, p, list(t)] for b, (p, t)
                      in engine.bm._meta.items()],
            "cached": [int(b) for b in engine.bm._cached],
        },
        "requests": reqs,
        "outputs": outs,
        # flight-recorder tail (serve/trace.py): the newest engine
        # events ride every snapshot, so a restored engine's ring opens
        # with its previous life's trail — postmortems after a restart
        # still see what led up to the crash (tolerated absent by the
        # reader: pre-PR-8 snapshots restore fine).
        "flight": (engine.trace.tail(256)
                   if getattr(engine, "trace", None) is not None
                   else []),
    }


def snapshot_engine(engine, directory: str | os.PathLike) -> dict:
    """Durably capture ``engine``'s full serving state under
    ``directory`` (called between steps — no dispatch may be in
    flight).  Returns ``{"step", "ms"}``; latency and counts land in
    ``engine.metrics`` (``summary()["recovery"]``).

    Ordering is the correctness contract: the journal syncs FIRST (the
    KV snapshot may lag the journal, never the reverse), then pools +
    manifest publish atomically through the checkpoint manager's
    tmp-dir + rename barrier.  The ``snapshot`` fault point fires twice
    per capture — before the KV write (call 2k+1) and inside the
    tmp-written-but-unrenamed window (call 2k+2) — so the chaos tests
    can land a kill in either crash window.
    """
    t0 = time.perf_counter()
    directory = os.path.abspath(os.fspath(directory))
    os.makedirs(directory, exist_ok=True)
    now = engine._clock()
    journal_here = (engine._journal is not None
                    and os.path.dirname(engine._journal.path) == directory)
    if engine._journal is not None:
        engine._journal.sync()
    meta = _capture_meta(engine, now, journal_here=journal_here)
    if engine.faults is not None:
        engine.faults.fire("snapshot")
    tree = _pool_tree(engine)
    # Leaf digests + manifest self-digest (docs/serving.md "Durability
    # & integrity"): meta.json records a CRC32 per pool leaf and one
    # over itself, computed from the in-memory arrays BEFORE the bytes
    # hit disk — restore verifies against exactly what the engine
    # meant to persist, so stored-byte rot can never restore as
    # subtly-wrong KV.
    meta["digests"] = {
        name: crc32_bytes(np.ascontiguousarray(
            np.asarray(arr)).tobytes())
        for name, arr in tree.items()}
    meta[META_CRC] = canonical_crc(meta, exclude=(META_CRC,))
    if engine.faults is not None:
        # integrity chaos, the SILENT-rot class: damage one leaf after
        # its digest was recorded and before the bytes hit disk.  The
        # published checkpoint is internally valid (tensorstore's own
        # framing CRC passes, orbax restores it without complaint) —
        # only the meta.json leaf digests can refuse it at restore.
        act = engine.faults.fire("integrity", op="snapshot")
        if act in CORRUPT_ACTIONS:
            _corrupt_pool_leaf(tree, act)
    # The home-directory manager is cached on the engine: its init
    # scans the directory (stale-.tmp GC + cross-host sync) — once is
    # enough on the periodic capture path that snapshot_ms meters.  A
    # one-shot snapshot to a FOREIGN directory must not disturb the
    # home state: it gets its own manager and step numbering, and the
    # engine's periodic cadence (_snap_seq, cached manager) is
    # untouched.
    kvdir = os.path.abspath(os.path.join(directory, KV_SUBDIR))
    home = (engine.snapshot_dir is not None
            and os.path.abspath(engine.snapshot_dir) == directory)
    mgr = engine._snap_mgr if home else None
    if mgr is None or mgr.directory != kvdir:
        mgr = ck.CheckpointManager(kvdir, max_to_keep=2)
        if home:
            engine._snap_mgr = mgr
    hook = None
    if engine.faults is not None:
        def hook(_tmp_path, _f=engine.faults):
            _f.fire("snapshot")
    if home:
        step = engine._snap_seq
    else:
        last = mgr.latest_step()
        step = 0 if last is None else last + 1
    mgr.save(step, tree,
             extras={META_NAME: json.dumps(meta)},
             on_before_finalize=hook)
    if home:
        engine._snap_seq = step + 1
    ms = (time.perf_counter() - t0) * 1e3
    m = engine.metrics
    m.snapshots += 1
    m.snapshot_ms_last = ms
    m.snapshot_ms_total += ms
    return {"step": step, "ms": ms}


def _corrupt_pool_leaf(tree: dict, action: str) -> Optional[str]:
    """Rot one pool leaf IN MEMORY (the ``op="snapshot"`` integrity
    seam): picks the largest leaf, corrupts its bytes, and rebuilds it
    at the original shape/dtype (truncation zero-fills the tail) so
    the checkpoint write itself succeeds.  Because the rot lands after
    the digest was recorded and before serialization, the stored step
    is internally valid — only the restore-time digest check can catch
    it.  Returns the rotted leaf name."""
    if not tree:
        return None
    name = max(sorted(tree),
               key=lambda n: np.asarray(tree[n]).nbytes)
    arr = np.ascontiguousarray(np.asarray(tree[name]))
    raw = arr.tobytes()
    rot = (corrupt_bytes(raw, action) + b"\x00" * len(raw))[:len(raw)]
    tree[name] = np.frombuffer(rot, dtype=arr.dtype).reshape(arr.shape)
    return name


def _corrupt_snapshot_leaf(step_dir: str, action: str) -> Optional[str]:
    """Damage the largest READ-PATH data file under a published
    ``step_dir`` (test/fsck utility for the on-disk rot class).  The
    per-process OCDBT staging copies (``ocdbt.process_*``) are skipped
    — restore never reads them, so damage there is invisible.  Note
    tensorstore frames its b-tree nodes with its own CRC-32C, so this
    class surfaces as a restore ERROR (torn-snapshot fallback), not as
    silently-wrong values — the in-memory seam above is what exercises
    the digest check.  Returns the damaged path."""
    best, size = None, -1
    for root, dirs, files in os.walk(step_dir):
        dirs[:] = [d for d in dirs if not d.startswith("ocdbt.process")]
        for name in files:
            if name.endswith(".json"):
                continue
            p = os.path.join(root, name)
            s = os.path.getsize(p)
            if s > size:
                best, size = p, s
    if best is None:
        return None
    with open(best, "rb") as f:
        data = f.read()
    with open(best, "wb") as f:
        f.write(corrupt_bytes(data, action))
    return best


def verify_snapshot_step(step_dir: str | os.PathLike) -> list[dict]:
    """Offline digest verification of one published snapshot step (the
    ``scripts/serve_fsck.py`` core): returns per-artifact findings
    ``{"artifact", "ok", "why"}`` — meta.json's self-digest first, then
    every pool leaf against its recorded digest.  A pre-integrity
    snapshot (no digests) reports a single unverified finding."""
    step_dir = os.path.abspath(os.fspath(step_dir))
    out: list[dict] = []
    meta_path = os.path.join(step_dir, META_NAME)
    try:
        with open(meta_path, encoding="utf-8") as f:
            meta = json.load(f)
    except Exception as e:  # noqa: BLE001 — unreadable IS the finding
        return [{"artifact": meta_path, "ok": False,
                 "why": f"unreadable: {e}"}]
    mc = meta.get(META_CRC)
    if mc is None:
        return [{"artifact": meta_path, "ok": True,
                 "why": "pre-integrity snapshot (no digests): "
                        "unverified"}]
    if int(mc) != canonical_crc(meta, exclude=(META_CRC,)):
        return [{"artifact": meta_path, "ok": False,
                 "why": "meta.json self-digest mismatch"}]
    out.append({"artifact": meta_path, "ok": True, "why": "digest ok"})
    digs = meta.get("digests") or {}
    try:
        like = _abstract_pool_tree(meta)
        pools = ck.restore(step_dir, like)
    except Exception as e:  # noqa: BLE001 — unreadable IS the finding
        out.append({"artifact": step_dir, "ok": False,
                    "why": f"pool tree unreadable: {e}"})
        return out
    for name in sorted(like):
        want = digs.get(name)
        got = crc32_bytes(np.ascontiguousarray(
            np.asarray(pools[name])).tobytes())
        if want is None:
            out.append({"artifact": f"{step_dir}:{name}", "ok": False,
                        "why": "no recorded digest for leaf"})
        elif int(want) != got:
            out.append({"artifact": f"{step_dir}:{name}", "ok": False,
                        "why": f"leaf digest mismatch "
                               f"(recorded {want}, stored {got})"})
        else:
            out.append({"artifact": f"{step_dir}:{name}", "ok": True,
                        "why": "digest ok"})
    return out


def has_restorable_state(directory: str | os.PathLike) -> bool:
    """True when :func:`restore_engine` has anything to rebuild from: a
    non-empty journal or at least one PUBLISHED KV snapshot step.  A
    bare ``journal.jsonl`` the constructor touched before the process
    died carries no state — resuming from it would fail, and a fresh
    engine may safely reopen the directory."""
    d = os.fspath(directory)
    j = os.path.join(d, JOURNAL_NAME)
    if os.path.exists(j) and os.path.getsize(j) > 0:
        return True
    kvdir = os.path.join(d, KV_SUBDIR)
    if not os.path.isdir(kvdir):
        return False
    return any(name.isdigit() for name in os.listdir(kvdir))


def _abstract_pool_tree(meta: dict) -> dict:
    """ShapeDtypeStruct targets for a snapshot manifest's pool tree —
    the reader-side twin of :func:`_pool_tree` (shared by restore and
    the offline fsck verifier)."""
    e = meta["engine"]
    dtype = np.dtype(e["kv_dtype"])
    shape = (e["num_blocks"], e["n_kv_heads"], e["page_size"],
             e["head_dim"])
    like = {}
    if e.get("kv_quant"):
        s_shape = shape[:3]
        for i in range(e["n_layers"]):
            for kv in ("k", "v"):
                like[f"l{i}_{kv}_q"] = jax.ShapeDtypeStruct(
                    shape, np.int8)
                like[f"l{i}_{kv}_s"] = jax.ShapeDtypeStruct(
                    s_shape, np.float32)
    else:
        for i in range(e["n_layers"]):
            like[f"l{i}_k"] = jax.ShapeDtypeStruct(shape, dtype)
            like[f"l{i}_v"] = jax.ShapeDtypeStruct(shape, dtype)
    d = e.get("draft")
    if e.get("spec_k") and d and "vocab" in e:
        # Spec snapshots carry the draft's device state in the
        # same tree (see _pool_tree); the manifest's draft
        # geometry shapes the abstract targets.  Pre-PR-7
        # manifests lack "draft" and restore pools-only.
        ddt = np.dtype(d["dtype"])
        dshape = (e["max_batch"], d["n_kv_heads"], d["max_seq"],
                  d["head_dim"])
        for i in range(d["n_layers"]):
            like[f"d{i}_k"] = jax.ShapeDtypeStruct(dshape, ddt)
            like[f"d{i}_v"] = jax.ShapeDtypeStruct(dshape, ddt)
        like["draft_kv_lens"] = jax.ShapeDtypeStruct(
            (e["max_batch"],), np.int32)
        like["draft_last_logits"] = jax.ShapeDtypeStruct(
            (e["max_batch"], d["vocab"]), np.float32)
        like["spec_last_logits"] = jax.ShapeDtypeStruct(
            (e["max_batch"], e["vocab"]), np.float32)
    return like


def _load_latest_snapshot(directory: str) -> Optional[tuple]:
    """(step, meta, pools dict) for the newest READABLE snapshot, or
    None.  Walks newest → oldest like ``restore_latest`` — a snapshot
    torn by a concurrent kill falls back to the previous one.  Opens
    the manager read-only (``clean_tmp=False``): restore may run while
    another process is mid-snapshot (a standby peeking at a live
    engine's directory), and GC-ing ``.tmp`` here would tear that
    writer's save; orphans are reclaimed by the next WRITER instead
    (the restored engine's first snapshot).

    Digest verification (docs/serving.md "Durability & integrity"):
    a snapshot whose meta.json self-digest or pool-leaf digest
    mismatches raises :class:`SnapshotCorrupt` LOUDLY, naming the bad
    leaf — it never joins the torn-write fallback walk, because orbax
    restores a flipped bit without complaint and walking past would
    either adopt subtly-wrong KV or silently resume from stale state.
    Pre-integrity snapshots (no digests) restore with a one-line
    unverified warning."""
    kvdir = os.path.join(directory, KV_SUBDIR)
    if not os.path.isdir(kvdir):
        return None
    mgr = ck.CheckpointManager(kvdir, max_to_keep=2, clean_tmp=False)
    for step in reversed(mgr.all_steps()):
        step_dir = os.path.join(kvdir, str(step))
        try:
            with open(os.path.join(step_dir, META_NAME)) as f:
                meta = json.load(f)
        except Exception:  # noqa: BLE001 — torn snapshot: fall back
            continue
        # A format mismatch is a code/snapshot version skew, not a torn
        # write — raise it instead of silently walking past (the
        # fallback would otherwise resume from a stale snapshot or fail
        # later with an unrelated journal-only error).
        if meta.get("format") != SNAPSHOT_FORMAT:
            raise ValueError(
                f"snapshot {step_dir} has format {meta.get('format')}; "
                f"this build reads format {SNAPSHOT_FORMAT}")
        mc = meta.get(META_CRC)
        if mc is not None and int(mc) != canonical_crc(
                meta, exclude=(META_CRC,)):
            raise SnapshotCorrupt(
                f"snapshot {step_dir}: meta.json self-digest mismatch "
                f"— refusing to adopt; quarantine the step "
                f"(scripts/serve_fsck.py --salvage) to restore from an "
                f"older snapshot + the journal")
        try:
            like = _abstract_pool_tree(meta)
            pools = ck.restore(step_dir, like)
        except Exception:  # noqa: BLE001 — torn snapshot: fall back
            continue
        digs = meta.get("digests")
        if digs is None:
            print(f"[recovery] snapshot {step_dir} predates leaf "
                  f"digests: restoring unverified", file=sys.stderr)
        else:
            for name in sorted(like):
                got = crc32_bytes(np.ascontiguousarray(
                    np.asarray(pools[name])).tobytes())
                want = digs.get(name)
                if want is None or int(want) != got:
                    raise SnapshotCorrupt(
                        f"snapshot {step_dir}: pool leaf {name!r} "
                        f"digest mismatch (recorded {want}, stored "
                        f"{got}) — refusing to adopt corrupt KV; "
                        f"quarantine the step (scripts/serve_fsck.py "
                        f"--salvage) to restore from an older "
                        f"snapshot + the journal")
        return step, meta, pools
    return None


# ---------------------------------------------------------------------------
# Restore
# ---------------------------------------------------------------------------


def _resolve_callback(on_token, rid: str) -> Optional[Callable]:
    if on_token is None:
        return None
    if callable(on_token):
        return on_token
    return on_token.get(rid)


def _shift(ts: Optional[float], offset: float) -> Optional[float]:
    return None if ts is None else ts + offset


# the manifest keys restore hands the constructor; others it carries
# (an older snapshot's ``spec_fused`` among them) are not options
_META_KW = ("num_blocks", "page_size", "max_batch", "prefill_chunk",
            "prefill_budget", "horizon", "pipeline", "snapshot_every",
            "prefix_cache")


def restore_engine(directory: str | os.PathLike, gen, params, *,
                   draft=None, draft_params=None,
                   clock=time.monotonic,
                   on_token: Union[None, Callable, dict] = None,
                   replay_tokens: bool = False,
                   faults=None, journal_fsync: bool = False,
                   journal_fsync_interval_s: Optional[float] = None,
                   journal_rotate_bytes: Optional[int] = None,
                   **overrides):
    """Rebuild a :class:`ServeEngine` from the snapshot + journal under
    ``directory`` (the implementation of ``ServeEngine.restore``).

    ``gen``/``params`` (and ``draft``/``draft_params`` for speculative
    engines) are the caller's — model weights are not snapshotted, like
    any serving deployment they come from the model store.  Engine
    geometry defaults to the snapshot manifest's; any ``overrides``
    (``num_blocks=``, ``max_batch=``, ``horizon=``, ...) win, and
    requests that no longer fit the overridden geometry re-queue through
    admission and recompute (streams stay bit-exact — see the module
    docstring).  ``on_token`` re-attaches streaming callbacks (one
    callable for all requests, or a ``{rid: callable}`` map);
    ``replay_tokens=True`` re-fires them for every journaled token
    (at-least-once delivery for the crash-window token instead of the
    default at-most-once).
    """
    from triton_dist_tpu.serve.engine import ServeEngine

    directory = os.path.abspath(os.fspath(directory))
    snap = _load_latest_snapshot(directory)
    # Salvage, don't just replay: interior journal corruption quarantines
    # the damaged file and resumes from the records that still verify —
    # the snapshot manifest + fleet delivery record reconcile anything
    # the salvage lost (see the merge below and fleet._absorb_manifest).
    journal, jdamage = salvage_journal(os.path.join(directory, JOURNAL_NAME))
    if snap is None and not journal:
        raise FileNotFoundError(
            f"no restorable snapshot or journal under {directory}")
    step, meta, pools_raw = snap if snap is not None else (None, None, None)

    kw: dict[str, Any] = {}
    if meta is not None:
        for k in _META_KW:
            if k in meta["engine"]:  # tolerate pre-prefix-cache manifests
                kw[k] = meta["engine"][k]
        if draft is not None:
            kw["spec_k"] = meta["engine"]["spec_k"]
    kw.update(overrides)
    if "num_blocks" not in kw or "page_size" not in kw:
        raise ValueError(
            "journal-only restore (no KV snapshot) needs explicit engine "
            "geometry: pass num_blocks=, page_size=, ... as overrides")
    snap_every = kw.pop("snapshot_every", None)
    if snap_every is not None and snap_every < 1:
        raise ValueError(f"snapshot_every must be >= 1, got {snap_every}")
    # Constructed journal-less, then wired by hand: the engine refuses
    # a populated snapshot_dir at construction (a FRESH life appending
    # there would corrupt replay) — restore is the one sanctioned way
    # to reopen it.
    engine = ServeEngine(gen, params, draft=draft,
                         draft_params=draft_params, clock=clock,
                         faults=faults, **kw)
    engine.snapshot_dir = directory
    engine.snapshot_every = snap_every
    engine.journal_fsync_interval_s = journal_fsync_interval_s
    engine.journal_rotate_bytes = journal_rotate_bytes
    engine._journal = TokenJournal(
        os.path.join(directory, JOURNAL_NAME), fsync=journal_fsync,
        fsync_interval_s=journal_fsync_interval_s, faults=faults)
    if meta is not None:
        engine._snap_seq = step + 1
        engine._spec_off = bool(meta.get("spec_off", False))

    # -- pools: reusable iff the per-page geometry survived ---------------
    pools_ok = False
    if pools_raw is not None:
        e = meta["engine"]
        cfg = engine.cfg
        # Pool quantization mismatches are LOUD, not a silent requeue:
        # adopting fp bytes into int8 pools (or vice versa) would need a
        # quantization pass that cannot be bit-exact, and silently
        # recomputing every request would mask a deployment error (the
        # operator pointed a differently-configured engine at live
        # state).  Cross-dtype moves go through drain/migrate requeue by
        # design; restore demands the same engine class.
        if bool(e.get("kv_quant", False)) != engine.kv_quant:
            raise ValueError(
                f"snapshot under {directory} holds "
                f"{'int8-quantized' if e.get('kv_quant') else 'float'} "
                f"KV pools but the restoring engine allocates "
                f"{'int8-quantized' if engine.kv_quant else 'float'} "
                f"pools (Generator kv_dtype mismatch) — restore with a "
                f"matching kv_dtype, or migrate the requests through a "
                f"drain manifest (cross-dtype adoption requeues for "
                f"exact recompute)")
        same_geom = (e["page_size"] == engine.page
                     and e["n_layers"] == cfg.n_layers
                     and e["n_kv_heads"] == cfg.n_kv_heads
                     and e["head_dim"] == cfg.head_dim
                     and e["kv_dtype"] == str(np.dtype(cfg.dtype)))
        if same_geom:
            import jax.numpy as jnp

            n_copy = min(e["num_blocks"], engine.bm.num_blocks)

            def adopt(cur, saved):
                if saved.shape == cur.shape:
                    return jnp.asarray(saved)
                # Different block count: the overlapping pool rows
                # carry over; requests whose tables reach past them
                # recompute instead of resuming in place.
                return cur.at[:n_copy].set(jnp.asarray(saved)[:n_copy])

            new_pools = []
            for i, (k, v) in enumerate(engine._pools):
                if engine.kv_quant:
                    new_pools.append(
                        ({"q": adopt(k["q"], pools_raw[f"l{i}_k_q"]),
                          "s": adopt(k["s"], pools_raw[f"l{i}_k_s"])},
                         {"q": adopt(v["q"], pools_raw[f"l{i}_v_q"]),
                          "s": adopt(v["s"], pools_raw[f"l{i}_v_s"])}))
                else:
                    new_pools.append(
                        (adopt(k, pools_raw[f"l{i}_k"]),
                         adopt(v, pools_raw[f"l{i}_v"])))
            # One device_put per leaf lays the (global) restored pools
            # out on the restoring engine's mesh — restore across mesh
            # shapes is exactly this re-layout (no-op off-mesh).
            engine._pools = engine._place_pools(new_pools)
            pools_ok = True

    # -- spec device state: draft caches + round-opening logits -----------
    # Restorable iff the snapshot carried it AND the caller's draft has
    # the exact geometry (the draft caches are slot-indexed [max_batch]
    # arrays, so max_batch must match too).  Without it, spec rows
    # requeue through the exact-recompute path — bit-exact either way.
    spec_ok = False
    if (pools_ok and engine.spec_k and not engine._spec_off
            and meta["engine"].get("spec_k") == engine.spec_k
            and meta["engine"].get("max_batch") == engine.max_batch
            and meta["engine"].get("draft")
            and "draft_kv_lens" in pools_raw):
        from triton_dist_tpu.models.generate import GenerationState

        d = meta["engine"]["draft"]
        dcfg = engine.draft.cfg
        if (d["n_layers"] == dcfg.n_layers
                and d["n_kv_heads"] == dcfg.n_kv_heads
                and d["head_dim"] == dcfg.head_dim
                and d["max_seq"] == engine.draft.max_seq
                and d["vocab"] == dcfg.vocab
                and d["dtype"] == str(np.dtype(dcfg.dtype))):
            import jax.numpy as jnp

            engine._draft_state = GenerationState(
                caches=[(jnp.asarray(pools_raw[f"d{i}_k"]),
                         jnp.asarray(pools_raw[f"d{i}_v"]))
                        for i in range(d["n_layers"])],
                kv_lens=jnp.asarray(pools_raw["draft_kv_lens"]),
                last_logits=jnp.asarray(
                    pools_raw["draft_last_logits"]))
            engine._last_logits = jnp.asarray(
                pools_raw["spec_last_logits"])
            spec_ok = True

    # -- merge journal over manifest --------------------------------------
    m_reqs = meta["requests"] if meta is not None else {}
    m_outs = meta["outputs"] if meta is not None else {}
    m_tables = meta["tables"] if meta is not None else {}

    resolved: dict[str, dict] = {}
    order: list[str] = []

    def slot_for(rid) -> dict:
        if rid not in resolved:
            resolved[rid] = {"rid": rid}
            order.append(rid)
        return resolved[rid]

    for rid in list(m_reqs) + [r for r in m_outs if r not in m_reqs]:
        r = slot_for(rid)
        src = m_reqs.get(rid) or m_outs[rid]
        r["prompt"] = np.asarray(src["prompt"], np.int32)
        r["params"] = (SamplingParams.from_dict(src["params"])
                       if "params" in src else SamplingParams())
        r["arrival"] = src.get("arrival")
        r["slo"] = src.get("slo", "interactive")
        if rid in m_reqs:
            r["tokens"] = list(m_reqs[rid]["gen"])
            r["tok_ts"] = list(m_reqs[rid].get("token_times", []))
        else:
            r["tokens"] = list(m_outs[rid]["tokens"])
            r["tok_ts"] = []
        if rid in m_outs:
            r["finish"] = {"reason": m_outs[rid]["reason"],
                           "err": m_outs[rid]["error"], "ts": None}
    for rid, jr in journal.items():
        r = slot_for(rid)
        if jr.prompt is not None:
            r.setdefault("prompt", jr.prompt)
            r.setdefault("params", jr.params)
            r.setdefault("arrival", jr.arrival)
            r.setdefault("slo", jr.slo)
        toks = jr.token_list()
        # The journal syncs before every snapshot, so it is a superset
        # of the manifest's token view — prefer it whenever longer (the
        # journal-ahead suffix is what recompute replays).
        if len(toks) >= len(r.get("tokens", [])):
            r["tokens"] = toks
            r["tok_ts"] = jr.token_times()
        if jr.first_tok is not None:
            r.setdefault("first_tok", jr.first_tok)
        if jr.trace is not None:
            r.setdefault("trace", jr.trace)
        if jr.finish is not None:
            r["finish"] = jr.finish
    # A rid only ever seen as a finish/token record (its submit line was
    # torn away with the crash) cannot be rebuilt — drop it.  A rid the
    # journal marks MIGRATED is owned by another replica now (its "mig"
    # record is the hand-off receipt — docs/serving.md "Fleet serving"):
    # resurrecting it here would double-serve the stream, even when a
    # pre-drain KV snapshot still lists it, so it is dropped outright
    # (the target replica's journal carries its past and its future).
    order = [rid for rid in order
             if resolved[rid].get("prompt") is not None
             and not (rid in journal and journal[rid].migrated)]

    if meta is not None:
        old_now = meta["clock"]
    else:
        # Journal-only restore: the newest old-clock timestamp anywhere
        # in the journal (token commit, submit, or finish) stands in for
        # the snapshot clock.  Token times alone are not enough — a kill
        # before the first commit would leave old_now at 0, pushing every
        # re-based arrival into the future and deadline TTLs with it.
        old_now = max(
            [ts for jr in journal.values()
             for _, ts in jr.tokens.values() if ts is not None] +
            [jr.arrival for jr in journal.values()
             if jr.arrival is not None] +
            [jr.finish["ts"] for jr in journal.values()
             if jr.finish is not None and jr.finish.get("ts") is not None],
            default=0.0)
    offset = engine._clock() - (old_now or 0.0)

    # -- rebuild finished requests (accounting only; never re-queued) -----
    m = engine.metrics

    def finish_restored(rid: str, reason: FinishReason,
                        finish_ts: Optional[float],
                        err: Optional[str] = None) -> ReqState:
        # Every timestamp lands on the new clock base (shifted by
        # offset, like build_state's live rows) so restored durations
        # never mix clock lives.
        r = resolved[rid]
        rm = RequestMetrics(
            arrival_time=_shift(r["arrival"], offset) or 0.0)
        # explicit first-token stamp BEFORE seeding: a rotated journal's
        # tts None-pads its head past the bounded window, and seeding
        # from the first RETAINED stamp would inflate the restored TTFT
        # by the whole decode (seed_token_times only fills a None)
        rm.first_token_time = _shift(r.get("first_tok"), offset)
        rm.seed_token_times(
            [_shift(t, offset) for t in (r.get("tok_ts") or [])],
            total=len(r["tokens"]))
        rm.finish_time = finish_ts
        req = Request(rid, r["prompt"], r["params"],
                      arrival_time=rm.arrival_time,
                      slo_class=r.get("slo", "interactive"))
        rs = ReqState(req=req, metrics=rm, status=Status.FINISHED)
        rs.generated = list(r["tokens"])
        out = RequestOutput(request_id=rid, prompt=req.prompt,
                            token_ids=list(r["tokens"]),
                            finish_reason=reason, metrics=rm, error=err)
        engine._states[rid] = rs
        engine._outputs[rid] = out
        m.observe_finish(rid, rm, reason, slo_class=req.slo_class)
        return rs

    inflight: list[str] = []
    for rid in order:
        r = resolved[rid]
        if r.get("finish") is None:
            inflight.append(rid)
            continue
        reason = FinishReason(r["finish"]["reason"])
        finish_restored(rid, reason, _shift(r["finish"].get("ts"), offset),
                        err=r["finish"].get("err"))
        if reason is FinishReason.SHED:
            m.shed += 1
        elif reason is FinishReason.DEADLINE:
            m.deadline_expired += 1
        elif reason is FinishReason.ERROR:
            m.quarantined += 1

    # -- close the commit→retire crash window -----------------------------
    # A kill can land after a token's journal append but before the
    # retire that token triggers (its EOS, or the max_new_tokens
    # boundary).  The journal then shows a COMPLETE stream with no fin
    # record; re-queueing it would generate past the request's budget.
    # Finish it here — bit-identical to the retire the crash swallowed.
    def stream_done(rid: str) -> Optional[FinishReason]:
        r = resolved[rid]
        p = r["params"]
        if (p.eos_id is not None and r["tokens"]
                and r["tokens"][-1] == p.eos_id):
            return FinishReason.EOS
        if len(r["tokens"]) >= p.max_new_tokens:
            return FinishReason.LENGTH
        return None

    still = []
    window_finished: list[str] = []
    for rid in inflight:
        reason = stream_done(rid)
        if reason is None:
            still.append(rid)
            continue
        rs = finish_restored(rid, reason, engine._clock())
        m.restored_tokens += len(rs.generated)
        window_finished.append(rid)
        # the swallowed retire's fin record lands via the journal
        # backfill below (the single fin writer at restore)
    inflight = still

    # -- classify in-flight requests: resume in place vs recompute --------
    # A RUNNING row resumes in place iff its snapshot invariant matches
    # how THIS engine will serve it.  Plain serving needs the pending
    # token (kv_len rows + one emitted-but-unconsumed token); fused spec
    # serving has no pending token — its round state is the snapshotted
    # draft caches + logits rows (``spec_ok``), which are SLOT-indexed,
    # so the row must come back in its original slot.  Rows from a spec
    # snapshot restored into a plain (or draft-less) engine fail the
    # pending check and requeue through exact recompute — bit-exact
    # either way.
    spec_live = bool(engine.spec_k) and not engine._spec_off

    def resumable(rid: str) -> bool:
        mr = m_reqs.get(rid)
        if not (pools_ok and mr is not None
                and mr["status"] == Status.RUNNING.value):
            return False
        if spec_live:
            if not spec_ok or mr["pending"] is not None \
                    or mr.get("slot") is None:
                return False
        elif mr["pending"] is None:
            return False
        r = resolved[rid]
        if len(r["tokens"]) != len(mr["gen"]):
            return False  # journal ran ahead of the KV snapshot
        table = m_tables.get(rid)
        if table is None or len(table) > engine.n_pages_max:
            return False
        if any(b >= engine.bm.num_blocks for b in table):
            return False  # shrunk pool: those rows don't exist any more
        if not engine.bm.placement_ok(table):
            # A table snapshotted under a different mesh shape
            # (kv_shard='seq' partitions moved): the pages' bytes are
            # in the restored pools but in the WRONG ranks' partitions
            # — recompute, exactly like a shrunk-geometry restore.
            return False
        total = int(r["prompt"].shape[0]) + r["params"].max_new_tokens
        return total <= engine.gen.max_seq

    resume = [rid for rid in inflight if resumable(rid)]
    resume.sort(key=lambda rid: m_reqs[rid]["seq"])
    resume_set = set(resume)
    requeue = [rid for rid in inflight if rid not in resume_set]
    # Re-queue order: previously admitted rows first (admission order),
    # then the old waiting line, then post-snapshot journal-only
    # arrivals in submit order — FCFS fairness survives the crash.
    requeue_set = set(requeue)
    admitted = sorted((rid for rid in requeue if rid in m_reqs
                       and m_reqs[rid]["status"] != Status.WAITING.value),
                      key=lambda rid: m_reqs[rid]["seq"])
    waiting = [rid for rid in meta["waiting"] if rid in requeue_set] \
        if meta is not None else []
    placed = set(admitted) | set(waiting)
    rest = [rid for rid in requeue if rid not in placed]
    requeue = admitted + waiting + rest

    free_slots = [i for i in range(engine.max_batch)]

    def build_state(rid: str) -> ReqState:
        r = resolved[rid]
        mr = m_reqs.get(rid, {})
        rm = RequestMetrics(
            arrival_time=_shift(r["arrival"], offset) or engine._clock())
        rm.first_scheduled_time = _shift(mr.get("first_sched"), offset)
        ft = mr.get("first_tok")
        if ft is None:
            ft = r.get("first_tok")   # rotated-journal "ftt" record
        rm.first_token_time = _shift(ft, offset)
        rm.seed_token_times(
            [_shift(t, offset) for t in (r.get("tok_ts") or [])],
            total=len(r["tokens"]))
        rm.n_preemptions = mr.get("n_preempt", 0)
        req = Request(rid, r["prompt"], r["params"],
                      arrival_time=rm.arrival_time,
                      on_token=_resolve_callback(on_token, rid),
                      slo_class=r.get("slo", "interactive"),
                      trace=r.get("trace")
                      or {"trace_id": rid, "hop": 0})
        rs = ReqState(req=req, metrics=rm)
        rs.generated = list(r["tokens"])
        rs.journal_base = len(rs.generated)
        rs.callback_disabled = bool(mr.get("cb_off", False))
        # a restore is the SAME life continuing (same replica, same
        # journal dir): the journey keeps its hop — only a migration
        # to another replica bumps it
        engine._trace_ctx[rid] = req.trace
        return rs

    resumed: list[str] = []
    for rid in resume:
        mr = m_reqs[rid]
        if spec_live:
            # The draft caches/logits rows are slot-indexed: a spec row
            # resumes in ITS slot or not at all.
            slot = mr["slot"] if mr["slot"] in free_slots else None
        else:
            slot = mr["slot"] if mr["slot"] in free_slots else (
                free_slots[0] if free_slots else None)
        if slot is None:  # geometry shrank under us: recompute instead
            requeue.insert(0, rid)
            continue
        free_slots.remove(slot)
        rs = build_state(rid)
        # shared_ok under the prefix cache: snapshot tables legitimately
        # overlap on shared prefix blocks (refcounts rebuild from the
        # overlap itself); without it, overlap still means corruption.
        engine.bm.adopt(rid, m_tables[rid],
                        shared_ok=engine.bm.prefix_cache)
        rs.status = Status.RUNNING
        rs.slot = slot
        rs.kv_len = mr["kv_len"]
        rs.pending_token = mr["pending"]
        rs.seq = mr["seq"]
        rs.cached_prefix = mr.get("cached_prefix", 0)
        rs.committed_pages = mr.get("committed_pages", 0)
        rs.metrics.cached_prefix_tokens = rs.cached_prefix
        engine.slots[slot] = rs
        engine._states[rid] = rs
        resumed.append(rid)
        m.restored_in_place += 1
        m.restored_tokens += len(rs.generated)

    for rid in requeue:
        r = resolved[rid]
        total = int(r["prompt"].shape[0]) + r["params"].max_new_tokens
        rs = build_state(rid)
        if (total > engine.gen.max_seq
                or engine.bm.fit_error(total) is not None):
            # The restored geometry can NEVER serve this request; parking
            # it in the queue would wedge FCFS admission forever.
            rs.status = Status.FINISHED
            msg = (f"restored engine cannot serve {total} tokens "
                   f"(max_seq {engine.gen.max_seq}, "
                   f"{engine.bm.num_allocatable} allocatable blocks)")
            rm2 = rs.metrics
            rm2.finish_time = engine._clock()
            out = RequestOutput(request_id=rid, prompt=rs.req.prompt,
                                token_ids=list(rs.generated),
                                finish_reason=FinishReason.ERROR,
                                metrics=rm2, error=msg)
            engine._states[rid] = rs
            engine._outputs[rid] = out
            m.quarantined += 1
            m.observe_finish(rid, rm2, FinishReason.ERROR)
            # fin record lands via the backfill below; its tokens were
            # NOT carried anywhere, so restored_tokens excludes them
            continue
        if rs.generated:
            rs.work_prompt = np.concatenate(
                [rs.req.prompt, np.asarray(rs.generated, np.int32)])
        rs.status = Status.WAITING
        engine._states[rid] = rs
        engine.scheduler.add(rs)
        m.restored_requeued += 1
        m.restored_tokens += len(rs.generated)

    # -- prefix cache: index + warm tier survive the restart --------------
    # Live shared blocks re-register first (their tables were just
    # re-adopted), then the snapshot's LRU cache tier re-admits in order
    # — restore's adopt path doubling as cache admission, so a restarted
    # engine's first warm prompt still skips its prefill.  Gated on
    # pools_ok: without the restored pool bytes a "warm" block would
    # certify KV that no longer exists.
    pfx = meta.get("prefix") if meta is not None else None
    if pfx and pools_ok and engine.bm.prefix_cache:
        n_valid = min(meta["engine"]["num_blocks"], engine.bm.num_blocks)
        index = [(int(b), int(p), t) for b, p, t in pfx.get("index", ())
                 if 0 < int(b) < n_valid]
        engine.bm.restore_index(index)
        by_block = {b: (p, t) for b, p, t in index}
        for b in pfx.get("cached", ()):
            if int(b) in by_block:
                p, t = by_block[int(b)]
                engine.bm.admit_cached(int(b), p, t)

    seqs = [s.seq for s in engine.slots if s is not None]
    engine.scheduler._seq = max(
        [meta["seq_counter"] if meta is not None else 0] +
        [s + 1 for s in seqs])

    # -- journal backfill: keep the journal self-contained ----------------
    # A restored engine appends future commits at index journal_base;
    # when the state came from a manifest the journal never saw (a
    # snapshot taken by an engine without a journal, or a journal lost
    # with its disk), those earlier indices would be a GAP — and a
    # second crash would replay a truncated stream.  Backfill the
    # missing submit/token/finish records now, so every life leaves a
    # journal any later restore can trust on its own.
    if engine._journal is not None:
        for rid, rs in engine._states.items():
            jr = journal.get(rid)
            if jr is None or jr.prompt is None:
                engine._journal.submit(rs.req)
            have = len(jr.token_list()) if jr is not None else 0
            for i in range(have, len(rs.generated)):
                ts = rs.metrics.time_at(i)
                engine._journal.token(rid, i, rs.generated[i],
                                      engine._clock() if ts is None
                                      else ts)
            if (rs.status is Status.FINISHED
                    and (jr is None or jr.finish is None)):
                out = engine._outputs[rid]
                engine._journal.finish(
                    rid, out.finish_reason.value, out.error,
                    len(out.token_ids),
                    rs.metrics.finish_time or engine._clock())
        engine._note_journal()

    if replay_tokens and on_token is not None:
        for rid in resumed + requeue:
            rs = engine._states[rid]
            cb = rs.req.on_token
            if (cb is None or rs.callback_disabled
                    or rs.status is Status.FINISHED):
                continue  # finished-at-restore rows don't re-stream
            for tok in rs.generated[:rs.journal_base]:
                cb(rid, tok)
        # A stream that completed exactly at the crash (fin record
        # swallowed) still owes its in-flight callback — a fin record
        # on disk proves the pre-crash retire (and with it every
        # callback) ran, its absence proves nothing.  Re-fire the whole
        # journaled stream: same at-least-once contract as live rows.
        for rid in window_finished:
            cb = _resolve_callback(on_token, rid)
            if cb is None or m_reqs.get(rid, {}).get("cb_off", False):
                continue
            for tok in engine._states[rid].generated:
                cb(rid, tok)

    # -- flight-recorder provenance ---------------------------------------
    # The snapshot's ring tail seeds the restored recorder (the previous
    # life's trail precedes this life's events), and the restore itself
    # is an event: a later postmortem shows the lineage.
    if meta is not None and meta.get("flight"):
        engine.trace.seed(meta["flight"])
    if jdamage is not None:
        m.journal_corrupt += 1
        engine.trace.emit("corrupt", None, artifact="journal",
                          **jdamage.summary())
    engine.trace.emit("restore", None, in_place=m.restored_in_place,
                      requeued=m.restored_requeued,
                      tokens=m.restored_tokens)
    m.restores += 1
    return engine


# ---------------------------------------------------------------------------
# Live migration: journal-segment hand-off between replicas
# ---------------------------------------------------------------------------
#
# A migration MANIFEST is the unit of request hand-off between engine
# replicas (docs/serving.md "Fleet serving").  It carries, per request,
# everything a target ``ServeEngine.migrate_in`` needs to continue the
# stream exactly-once: prompt, sampling params (the per-token PRNG
# stream), the journaled token prefix with timestamps, and — on the
# cooperative ``ServeEngine.drain`` path — the live KV pages + pending
# token so the target resumes mid-stream with zero recompute.  Two
# producers exist:
#
# - ``ServeEngine.drain(rids)`` on a LIVE source: the engine gathers the
#   per-request KV pages, journals a ``mig`` record per request (the
#   ownership receipt), and frees its own state.
# - :func:`manifest_from_journal` on a DEAD replica's directory: the
#   durable journal is the source of truth for what was emitted, so the
#   manifest is exact even though the process is gone (no KV rides —
#   the target replays through the exact-recompute path, bit-identical
#   by the PR 5 argument).  ``mark=True`` appends the ``mig`` receipts
#   to the dead journal so a later ``--resume`` of that directory can
#   never resurrect the handed-off requests.

MANIFEST_FORMAT = 1


def manifest_from_journal(directory: str | os.PathLike, *,
                          mark: bool = False) -> dict:
    """Build a migration manifest for every UNFINISHED, un-migrated
    request in ``directory``'s token journal (the crash-path producer —
    the replica is dead, its journal is what survives).

    Returns ``{"format", "clock", "requests": [...], "finished": [...]}``
    where ``finished`` lists requests whose ``fin`` record landed but
    whose output the fleet controller may not have collected (the dying
    step's retirements) — accounting, never re-served.  ``mark=True``
    appends a ``mig`` record per handed-off request (safe only once the
    source process is dead: two writers on one journal corrupt it).

    Trace continuity on the crash path: each record carries the
    journal's trace context, and — when the dying step managed its
    ``force=True`` flight flush (it does on anything escaping,
    ``InjectedKill`` included) — the request's ring-event tail recovered
    from the newest ``flight_*.json``, so the adopting replica's ring
    and the merged fleet timeline show the dead life's events too
    (docs/observability.md "Fleet observability").
    """
    from triton_dist_tpu.serve.trace import (
        MIGRATE_EVENT_TAIL,
        latest_flight,
        load_flight,
    )

    directory = os.path.abspath(os.fspath(directory))
    if not os.path.exists(os.path.join(directory, JOURNAL_NAME)):
        # A replica that died during init (subprocess spawn, model
        # build) never opened a journal: it owned nothing, so the
        # hand-off is empty — not an error (the network fleet hits
        # this when a child is killed before the engine exists).
        return {"format": MANIFEST_FORMAT, "clock": 0.0,
                "requests": [], "finished": []}
    # The replica is already dead — corruption here must not kill the
    # crash path too.  Salvage the longest-valid prefix and carry the
    # damage report in the manifest so the controller can reconcile the
    # lost tail against its delivery record (fleet._absorb_manifest).
    journal, jdamage = salvage_journal(os.path.join(directory, JOURNAL_NAME))
    # per-rid event tails from the dead life's postmortem flush (best
    # effort: a SIGKILL with no flush just means no carried events)
    tails: dict[str, list] = {}
    fl = latest_flight(directory)
    if fl is not None:
        try:
            for ev in load_flight(fl).get("events", ()):
                ts, step, etype, rid, data = ev
                if rid is not None:
                    tails.setdefault(rid, []).append(
                        [ts, step, etype, data])
        except (OSError, ValueError, json.JSONDecodeError):
            tails = {}
    # Clock re-base (the restore_engine rule): the newest source-clock
    # stamp anywhere in the journal stands in for "now" on the source.
    old_now = max(
        [ts for jr in journal.values()
         for _, ts in jr.tokens.values() if ts is not None] +
        [jr.arrival for jr in journal.values() if jr.arrival is not None] +
        [jr.finish["ts"] for jr in journal.values()
         if jr.finish is not None and jr.finish.get("ts") is not None],
        default=0.0)
    reqs, finished, handed = [], [], []
    for rid, jr in journal.items():
        if jr.prompt is None or jr.migrated:
            continue
        toks = jr.token_list()
        if jr.finish is not None:
            finished.append({
                "rid": rid,
                "prompt": [int(x) for x in jr.prompt],
                "tokens": toks,
                "reason": jr.finish["reason"],
                "err": jr.finish.get("err"),
            })
            continue
        reqs.append({
            "rid": rid,
            "prompt": [int(x) for x in jr.prompt],
            "params": jr.params.to_dict(),
            "arrival": jr.arrival,
            "slo": jr.slo,
            "tokens": toks,
            "tok_ts": jr.token_times(),
            "first_tok": jr.first_tok,
            "trace": jr.trace or {"trace_id": rid, "hop": 0},
            "events": tails.get(rid, [])[-MIGRATE_EVENT_TAIL:],
        })
        handed.append((rid, len(toks)))
    if mark and handed:
        j = TokenJournal(os.path.join(directory, JOURNAL_NAME))
        try:
            for rid, n in handed:
                j.migrate(rid, n, old_now)
            j.sync()
        finally:
            j.close()
    out = {"format": MANIFEST_FORMAT, "clock": old_now,
           "requests": reqs, "finished": finished}
    if jdamage is not None:
        out["damage"] = jdamage.summary()
    return out


def save_manifest(manifest: dict, path: str | os.PathLike) -> str:
    """Write a manifest as JSON (atomic tmp + rename + whole-document
    digest, via :func:`integrity.atomic_write_json`) — the subprocess
    hand-off format (``examples/serve.py --migrate-in``).  KV payloads
    are dropped: the JSON manifest is the journal-segment crash path,
    and the target replays through exact recompute."""
    path = os.path.abspath(os.fspath(path))
    doc = dict(manifest)
    doc["requests"] = [{k: v for k, v in r.items() if k not in
                        ("kv", "kv_len", "pending", "s_ext")}
                       for r in manifest.get("requests", [])]
    return atomic_write_json(path, doc)


def load_manifest(path: str | os.PathLike) -> dict:
    with open(path, encoding="utf-8") as f:
        m = json.load(f)
    if m.get("format") != MANIFEST_FORMAT:
        raise ValueError(f"manifest {path} has format {m.get('format')}; "
                         f"this build reads format {MANIFEST_FORMAT}")
    # Pre-integrity manifests carry no digest (tri-state None passes).
    if verify_json_doc(m) is False:
        raise ValueError(
            f"manifest {path}: whole-document digest mismatch — the "
            f"file is corrupt; regenerate it from the source journal "
            f"(manifest_from_journal) or scripts/serve_fsck.py")
    m.pop(DOC_CRC, None)
    return m
