"""Disaggregated prefill→decode serving: role-aware routing with
per-request KV-page PUSH.

The fleet layer (serve/fleet.py) treats every replica as interchangeable
— good for availability, bad for interference: one long-prompt prefill
stalls every decode sharing its batch, and the PR-7/PR-11 ITL
percentiles eat it.  The DistServe/Splitwise answer is to SPLIT the
tier: prefill replicas absorb the compute-bound bursts, decode replicas
run steady memory-bound token generation, and a request's KV pages move
from the one to the other exactly once, at prefill completion — the TPU
analog of the reference's producer/consumer signal-and-put hand-off,
applied at the serving tier instead of inside a kernel.

This module adds exactly that on top of the existing machinery, re-using
the migration substrate instead of inventing a second transport:

- **Roles** — :class:`~serve.fleet.FleetController` grows a ``role`` per
  replica (``prefill`` | ``decode`` | ``both``; default ``both`` keeps
  homogeneous fleets bit-identical).  Roles are routing POLICY, not
  capability: submits prefer the prefill pool by least-pressure,
  migrated/pushed records prefer decode-capable replicas, and
  availability always beats policy — a lone surviving replica of either
  role serves everything rather than strand work.

- **Per-request PUSH** — when a prefill replica finishes a request's
  prompt chunks (the row reaches RUNNING with a pending first token —
  ``ServeEngine.push_ready``), the controller extracts its single-request
  hand-off (``push_out``: the journal segment + live KV pages via the
  same ``load_pages`` gather ``drain`` uses, framed as ``push_out`` in
  the ring) and offers it to the request's pre-stamped decode target
  (``admit_pushed``): capacity admission first, then IN-PLACE adoption —
  ``fill_pages`` scatter, the row resumes RUNNING at its exact stream
  position with the pending-token invariant, zero recompute.  Cross
  process the pair rides ``POST /push`` with the NetClient retry ladder
  and an idempotency-key replay cache, so a lost ack can never
  double-admit.

- **No request is ever lost** — the decode target is chosen at admission
  and re-chosen on decode-replica death; a rejecting target sends the
  controller down the decode ranking; if EVERY decode-capable replica
  rejects, the record falls back to the general placer (any healthy
  replica — the source included — adopts it, exact recompute in the
  worst case).  Exactly-once holds by the same journal argument as
  migration: the source journals ``mig`` receipts before the manifest
  leaves, the target journals the carried segment before serving
  resumes, and the cross-journal union owns every token once.

Every push decision lands in the router audit (``kind="push"`` /
``"decode_target"``) so ``FleetController.explain(rid)`` answers "why
did it decode there" with the pressures and the rejected-capacity walk.

See docs/serving.md "Disaggregated serving" for the operator recipe and
the idempotency argument; ``examples/serve.py --disagg P:D`` drives
it, and ``tests/test_serve_disagg.py`` holds its chaos harnesses.
"""

from __future__ import annotations

from typing import Callable, Optional

import numpy as np

from triton_dist_tpu.serve.fleet import (
    FleetController,
    ReplicaState,
    _manifest_header,
)
from triton_dist_tpu.serve.net import NetError
from triton_dist_tpu.serve.request import Request


def parse_disagg(spec: str) -> tuple[int, int]:
    """``"P:D"`` → ``(prefill, decode)`` replica counts, both >= 1 —
    the CLI shape of a disagg tier (``--disagg 2:2``)."""
    parts = str(spec).split(":")
    if len(parts) != 2:
        raise ValueError(
            f"--disagg wants PREFILL:DECODE (e.g. 1:2), got {spec!r}")
    try:
        p, d = int(parts[0]), int(parts[1])
    except ValueError:
        raise ValueError(
            f"--disagg wants integer counts, got {spec!r}") from None
    if p < 1 or d < 1:
        raise ValueError(
            f"--disagg needs >= 1 replica per role, got {spec!r}")
    return p, d


class DisaggController(FleetController):
    """A :class:`FleetController` whose fleet is a two-role tier:
    replicas ``r0..r{P-1}`` hold role ``prefill``, ``r{P}..r{P+D-1}``
    hold ``decode`` (module docstring; docs/serving.md "Disaggregated
    serving").

    Drive it exactly like the base controller — :meth:`submit` then
    :meth:`step`/``run`` — plus, each tick after the replicas step, the
    controller sweeps the prefill tier for prefill-complete rows and
    pushes each to its stamped decode target.  Extra state:

    - :attr:`decode_targets` — rid → the decode replica stamped at
      admission (re-stamped when that replica dies or rejects);
    - :attr:`pushes` / :attr:`push_fallbacks` — hand-offs completed /
      hand-offs that exhausted the decode ranking and fell back to the
      general placer.
    """

    def __init__(self, factory: Callable, prefill: int, decode: int, *,
                 root: str, **kw):
        if "roles" in kw:
            raise ValueError(
                "DisaggController derives roles from the prefill/decode "
                "counts; pass counts, not a roles map")
        if prefill < 1 or decode < 1:
            raise ValueError(
                f"need >= 1 replica per role, got "
                f"prefill={prefill}, decode={decode}")
        roles = {f"r{i}": ("prefill" if i < prefill else "decode")
                 for i in range(prefill + decode)}
        super().__init__(factory, prefill + decode, root=root,
                         roles=roles, **kw)
        self.n_prefill = prefill
        self.n_decode = decode
        #: rid -> decode replica chosen at admission (None while no
        #: decode-capable replica is healthy; re-stamped at push time)
        self.decode_targets: dict[str, Optional[str]] = {}
        self.pushes = 0
        self.push_fallbacks = 0
        # submitted Request objects, kept until retirement: the orphan
        # rescue (below) rebuilds a requeue record from prompt + params
        # + the delivered stream when a crash window leaves a request
        # with no owner
        self._reqs: dict[str, Request] = {}
        # rids whose push exhausted the decode ranking: they stay on
        # their fallback placement (every later tick would re-offer to
        # the same full pool — churn, not progress) until retirement
        self._no_push: set[str] = set()
        # per-tier autoscaler trackers: prefill and decode scale on
        # INDEPENDENT smoothed-pressure signals (a prompt burst must
        # grow the prefill tier without inflating decode, and vice
        # versa) — the base controller's single tracker becomes the
        # max-of-tiers gauge
        self._role_scale = {
            role: {"ema": 0.0, "t": None, "dwell": 0}
            for role in ("prefill", "decode")}

    # -- autoscaling (per tier) --------------------------------------------

    def _autoscale_step(self, now: float) -> None:
        for role in ("prefill", "decode"):
            reps = [(n, r) for n, r in self.replicas.items()
                    if r.role == role]
            # fresh unplaced work waits on prefill capacity; parked
            # migration/push records wait on decode capacity
            pending = (bool(self._pending_reqs) if role == "prefill"
                       else bool(self._pending_recs))
            spawned, retired = self._autoscale_tier(
                now, self._role_scale[role], reps, role=role,
                pending=pending)
            delta = (1 if spawned else 0) - (1 if retired else 0)
            if role == "prefill":
                self.n_prefill += delta
            else:
                self.n_decode += delta
        # the fleet-level gauge reports the hotter tier
        self._scale_state["ema"] = max(
            s["ema"] for s in self._role_scale.values())
        self._scale_state["t"] = now

    # -- admission ---------------------------------------------------------

    def submit(self, req: Request) -> None:
        super().submit(req)
        rid = req.request_id
        self._reqs[rid] = req
        self._stamp_decode_target(rid)

    def _stamp_decode_target(self, rid: str,
                             exclude: frozenset = frozenset()
                             ) -> Optional[str]:
        """Choose (or re-choose) ``rid``'s decode replica by
        least-pressure over the healthy decode pool, and audit the
        choice (``kind="decode_target"``) so ``explain(rid)`` shows why
        the decode landed where it did."""
        cands = [(n, l) for n, l in self._healthy("decode")
                 if n not in exclude]
        pressures = ({n: round(self.router.pressure(l), 4)
                      for n, l in cands}
                     if self.audit.enabled else None)
        target = self.router.pick(cands) if cands else None
        self.decode_targets[rid] = target
        if self.audit.enabled:
            self.audit.record(self._clock(), self.steps, "decode_target",
                              rid, chosen=target, pressures=pressures)
        return target

    # -- the tick ----------------------------------------------------------

    def step(self) -> list:
        outs = super().step()
        self._sweep_pushes()
        for rid in [r for r in self._reqs if r in self.outputs]:
            self._reqs.pop(rid, None)
            self.decode_targets.pop(rid, None)
            self._no_push.discard(rid)
        return outs

    def _sweep_pushes(self) -> None:
        """Push every prefill-complete row off the prefill tier.  A row
        is ready once it is RUNNING with a pending token — prefill done,
        first token sampled — so the decode replica adopts it IN PLACE
        and generates every remaining token (``ServeEngine.push_ready``;
        the remote twin reads the last health answer)."""
        for name, rep in self.replicas.items():
            if (rep.role != "prefill"
                    or rep.state is not ReplicaState.HEALTHY
                    or rep.engine is None):
                continue
            for rid in list(rep.engine.push_ready()):
                if self.placement.get(rid) != name:
                    continue   # moved or retired since the snapshot
                if rid in self._no_push:
                    continue   # already fell back; stay put
                self._push_request(name, rep, rid)

    def _push_request(self, name: str, rep, rid: str) -> None:
        target = self.decode_targets.get(rid)
        trep = self.replicas.get(target) if target is not None else None
        if (trep is None or target == name
                or trep.state is not ReplicaState.HEALTHY):
            target = self._stamp_decode_target(
                rid, exclude=frozenset((name,)))
        try:
            m = rep.engine.push_out(rid)
        except NetError:
            # unreachable mid-push: retry next tick — the drain
            # idempotency key replays a landed-but-unacked extraction,
            # and a death instead resolves through the journal
            return
        recs = m.get("requests", ())
        if not recs:
            return   # raced a retirement (remote push_ready is stale)
        header = _manifest_header(m)
        for rec in recs:
            prid = rec["rid"]
            # fill the delivery record from the manifest's journal
            # segment (the remote poll may lag the drained tokens —
            # same journal-precedes-callback argument as
            # _absorb_manifest)
            stream = self.streams.get(prid)
            toks = rec.get("tokens", [])
            if stream is not None:
                d = len(stream)
                assert d <= len(toks), (
                    f"{prid}: delivered {d} tokens but the push "
                    f"manifest only holds {len(toks)}")
                stream.extend(int(t) for t in toks[d:])
            self.placement.pop(prid, None)
            if not self._place_push(header, rec, preferred=target):
                self._pending_recs.append(
                    (header, rec, self._rec_expiry(header, rec)))

    def _place_push(self, header: dict, rec: dict, *,
                    preferred: Optional[str]) -> bool:
        """Offer one PUSH record to the decode pool — the stamped
        target first, then the decode ranking; a rejecting replica
        (capacity admission) passes it along.  Exhausting the pool
        falls back to the general placer: ANY healthy replica — the
        source included — adopts it rather than lose the request
        (exact recompute in the worst case; the manifest still carries
        KV, so even the fallback usually adopts in place)."""
        rid = rec["rid"]
        cands = self._healthy("decode")
        pressures = ({n: round(self.router.pressure(l), 4)
                      for n, l in cands}
                     if self.audit.enabled else None)
        rest = [(n, l) for n, l in cands if n != preferred]
        order = ([preferred] if any(n == preferred for n, _ in cands)
                 else [])
        if rest:
            order += self.router.rank(rest)
        rejected = {}
        for cname in order:
            crep = self.replicas[cname]
            res = crep.engine.admit_pushed(
                {**header, "requests": [rec]},
                on_token={rid: self._cbs.get(rid)})
            if rid in res["rejected"]:
                rejected[cname] = res["rejected"][rid]
                continue
            self.pushes += 1
            in_place = rid in res["adopted"]
            self.trace.emit("push_in", rid, replica=cname,
                            state=crep.state.value, in_place=in_place)
            if self.audit.enabled:
                self.audit.record(self._clock(), self.steps, "push",
                                  rid, chosen=cname, target=preferred,
                                  in_place=in_place,
                                  pressures=pressures,
                                  rejected=rejected)
            self.placement[rid] = cname
            self.history[rid].append(cname)
            self.decode_targets[rid] = cname
            return True
        # every decode-capable replica rejected (or none is healthy):
        # the ultimate fallback is the general placer over ALL healthy
        # replicas — no request is ever lost to role policy
        self.push_fallbacks += 1
        self._no_push.add(rid)
        if self.audit.enabled:
            self.audit.record(self._clock(), self.steps, "push", rid,
                              chosen=None, target=preferred,
                              fallback=True, pressures=pressures,
                              rejected=rejected)
        return self._place_rec(header, rec)

    # -- failure handling --------------------------------------------------

    def _on_replica_death(self, name: str, why: str, now: float) -> None:
        already = self.replicas[name].state is ReplicaState.DEAD
        super()._on_replica_death(name, why, now)
        if already:
            return
        # decode targets stamped onto the dead replica re-choose from
        # the survivors (the ISSUE's re-chosen-on-death contract)
        for rid, tgt in list(self.decode_targets.items()):
            if tgt == name and rid not in self.outputs:
                self._stamp_decode_target(rid,
                                          exclude=frozenset((name,)))
        self._rescue_orphans()

    def _rescue_orphans(self) -> None:
        """Close the one crash window the journal walk cannot see: a
        remote push_out LANDED (the source journaled its ``mig``
        receipts), the ack was lost, and the source died before the
        key-replay retry — the dead journal rightly skips the rid
        (receipted = handed off) but the manifest it cached died with
        the process, so after the base death path the request has NO
        owner.  Rebuild a requeue record from the submitted Request +
        the delivered stream (deterministic re-derivation: the replay
        is bit-identical by the PR 5 argument) and park it for
        placement.  Single-ownership holds — the dead journal's receipt
        already disowned the rid."""
        parked = {req.request_id for req in self._pending_reqs}
        parked |= {rec["rid"] for _, rec, _ in self._pending_recs}
        for rid in self.streams:
            if (rid in self.outputs or rid in self.placement
                    or rid in parked):
                continue
            req = self._reqs.get(rid)
            if req is None:
                continue
            from triton_dist_tpu.serve.recovery import MANIFEST_FORMAT
            header = {"format": MANIFEST_FORMAT, "clock": self._clock()}
            rec = {
                "rid": rid,
                "prompt": [int(x) for x in np.asarray(req.prompt)],
                "params": req.params.to_dict(),
                "arrival": req.arrival_time,
                "slo": req.slo_class,
                "tokens": [int(t) for t in self.streams[rid]],
                "trace": req.trace,
            }
            self.audit.record(self._clock(), self.steps, "push", rid,
                              chosen=None, orphan_rescue=True)
            self._pending_recs.append(
                (header, rec, self._rec_expiry(header, rec)))
        self._drain_pending()

    # -- observability -----------------------------------------------------

    def fleet_summary(self) -> dict:
        s = super().fleet_summary()
        s["disagg"] = {
            "prefill": self.n_prefill,
            "decode": self.n_decode,
            "pushes": self.pushes,
            "push_fallbacks": self.push_fallbacks,
        }
        return s
