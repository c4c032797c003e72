"""Fleet serving (serve/fleet.py, docs/serving.md "Fleet serving"):
the multi-replica router, live request migration, and the fleet chaos
harness.

Fast tier (all of it — this file is the tier-1 gate for ROADMAP #4):

- engine-level migration: ``ServeEngine.drain`` → ``migrate_in`` moves
  a request mid-stream between engines — in place (live KV + pending
  token, zero recompute) and through exact recompute — with streams
  bit-identical to the single-engine oracle, ``mig`` journal receipts
  blocking resurrection on the source, and capacity admission
  rejecting what the target cannot hold;
- the crash-path manifest: a dead replica's journal rebuilds the exact
  hand-off segment (``manifest_from_journal``), and ``mark=True``
  makes a later ``--resume`` of that directory migration-safe;
- THE fleet chaos harness: kill one of N replicas mid-decode under
  staggered greedy+sampled load — every stream finishes bit-identical
  to the single-engine oracle, zero lost and zero duplicated tokens
  (delivery record AND cross-journal union), at least one in-flight
  request completes on a DIFFERENT replica than it started on, and the
  router never placed onto a non-HEALTHY replica;
- health: SUSPECT circuit-breaking (no admissions, recovery on
  progress), WatchdogTimeout as replica death, fleet outage when every
  budget is spent;
- :class:`RestartBackoff` (exponential growth, cap, jitter bounds,
  healthy-uptime budget reset, exhaustion) and the :class:`Router`
  pressure policy + Prometheus scrape parsing;
- the supervisor satellites: ``run_once``'s stall-detector ARMING
  boundary (a child that first beats at the grace edge is not killed;
  a wedged child inside grace survives until armed) and
  ``postmortem``'s already-reported dedup.
"""

import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from triton_dist_tpu.models import llama
from triton_dist_tpu.models.generate import Generator
from triton_dist_tpu.runtime.faults import FaultInjector
from triton_dist_tpu.runtime.watchdog import WatchdogTimeout
from triton_dist_tpu.serve import (
    Request,
    SamplingParams,
    ServeEngine,
    replay_journal,
)
from triton_dist_tpu.serve.fleet import (
    FleetController,
    ReplicaLoad,
    ReplicaState,
    RestartBackoff,
    Router,
    parse_prometheus,
)
from triton_dist_tpu.serve.recovery import (
    JOURNAL_NAME,
    load_manifest,
    manifest_from_journal,
    save_manifest,
)
from triton_dist_tpu.serve.request import FinishReason

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "scripts"))


class _Tick:
    """Deterministic shared fleet clock: +dt per reading."""

    def __init__(self, dt=0.01):
        self.t = 0.0
        self.dt = dt

    def __call__(self):
        self.t += self.dt
        return self.t


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.LlamaConfig(vocab=64, dim=16, n_layers=1, n_heads=2,
                            n_kv_heads=1, ffn_dim=32, max_seq=64,
                            dtype=jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    params = llama.init_params(cfg, jax.random.key(3))
    gen = Generator(cfg, mesh, axis="sp", max_seq=64)
    return cfg, params, gen


def _engine(gen, params, **kw):
    kw.setdefault("num_blocks", 40)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_batch", 2)
    kw.setdefault("prefill_chunk", 4)
    return ServeEngine(gen, params, **kw)


def _oracle(gen, params, reqs):
    """Per-request single-engine streams (generation depends only on
    (prompt, params, index), so one clean engine pins every fleet
    configuration's expectation)."""
    out = {}
    for r in reqs:
        eng = _engine(gen, params)
        eng.submit(Request(r.request_id, r.prompt, r.params))
        out[r.request_id] = list(eng.run()[r.request_id].token_ids)
    return out


def _mixed_reqs(cfg, n, *, new_tokens=8, on_token=None):
    """Staggered greedy + seeded-sampled traffic."""
    rng = np.random.default_rng(1)
    reqs = []
    for i in range(n):
        if i % 3 == 0:
            p = SamplingParams(max_new_tokens=new_tokens,
                               temperature=0.5, top_k=8, seed=i)
        else:
            p = SamplingParams(max_new_tokens=new_tokens)
        reqs.append(Request(
            f"q{i}", rng.integers(0, cfg.vocab, size=5 + i % 4)
            .astype(np.int32), p, on_token=on_token))
    return reqs


# ---------------------------------------------------------------------------
# engine-level migration: drain -> migrate_in
# ---------------------------------------------------------------------------


def test_drain_migrate_in_place_mid_stream(tiny, tmp_path):
    """The cooperative hand-off: a RUNNING row drains with its live KV
    pages + pending token and the target adopts it MID-STREAM — zero
    recompute (the target pays no prefill), stream bit-identical to the
    uninterrupted oracle, and the delivery record seamless across the
    hand-off."""
    cfg, params, gen = tiny
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab, size=7).astype(np.int32)
    sp = SamplingParams(max_new_tokens=8)
    oracle = _oracle(gen, params, [Request("a", prompt, sp)])["a"]

    got = []
    a = _engine(gen, params, snapshot_dir=str(tmp_path / "A"))
    b = _engine(gen, params, snapshot_dir=str(tmp_path / "B"))
    a.submit(Request("a", prompt, sp,
                     on_token=lambda r, t: got.append(int(t))))
    for _ in range(6):
        a.step()
    assert got == oracle[:len(got)] and 0 < len(got) < len(oracle)

    manifest = a.drain()
    (rec,) = manifest["requests"]
    assert "kv" in rec and rec["pending"] == oracle[len(got) - 1]
    # source side: gone, receipted, no retirement accounting
    assert not a.has_work() and not a._states
    assert a.metrics.migrated_out == 1 and a.metrics.completed == 0

    res = b.migrate_in(manifest,
                       on_token={"a": lambda r, t: got.append(int(t))})
    assert res == {"adopted": ["a"], "requeued": [], "rejected": {}}
    outs = b.run()
    assert list(outs["a"].token_ids) == oracle
    assert got == oracle                       # exactly-once delivery
    assert b.metrics.prefill_tokens == 0       # zero recompute paid
    assert b.metrics.migrated_in_place == 1
    assert b.metrics.migrated_tokens == len(rec["tokens"])
    assert outs["a"].finish_reason is FinishReason.LENGTH


def test_drain_migrate_recompute_sampled_exact(tiny, tmp_path):
    """``include_kv=False`` forces the exact-recompute path; a SAMPLED
    stream stays bit-identical (the per-token fold_in stream survives
    the hand-off like it survives preemption/restore)."""
    cfg, params, gen = tiny
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab, size=7).astype(np.int32)
    sp = SamplingParams(max_new_tokens=8, temperature=0.7, top_k=8,
                        seed=5)
    oracle = _oracle(gen, params, [Request("a", prompt, sp)])["a"]
    a = _engine(gen, params, snapshot_dir=str(tmp_path / "A"))
    b = _engine(gen, params, snapshot_dir=str(tmp_path / "B"))
    a.submit(Request("a", prompt, sp))
    for _ in range(5):
        a.step()
    res = b.migrate_in(a.drain(include_kv=False))
    assert res["requeued"] == ["a"] and not res["adopted"]
    assert list(b.run()["a"].token_ids) == oracle
    assert b.metrics.prefill_tokens > 0   # recompute was paid


def test_drain_receipt_blocks_resurrection(tiny, tmp_path):
    """The source journal's ``mig`` record is the ownership transfer: a
    restore of the drained directory must NOT resurrect the request —
    that would double-serve the stream the target now owns."""
    cfg, params, gen = tiny
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab, size=7).astype(np.int32)
    a = _engine(gen, params, snapshot_dir=str(tmp_path / "A"),
                snapshot_every=2)
    a.submit(Request("a", prompt, SamplingParams(max_new_tokens=8)))
    for _ in range(5):
        a.step()   # a periodic KV snapshot lands BEFORE the drain
    a.drain()
    jr = replay_journal(tmp_path / "A" / JOURNAL_NAME)["a"]
    assert jr.migrated
    a2 = ServeEngine.restore(str(tmp_path / "A"), gen, params)
    assert not a2.has_request("a") and not a2.has_work()
    assert "a" not in a2._outputs


def test_migrate_in_capacity_admission(tiny, tmp_path):
    """Capacity admission: a duplicate id, a request that can never fit
    the target geometry, and a target whose waiting queue is at bound
    are REJECTED (nothing journaled on the target) — the fleet placer
    tries the next replica."""
    cfg, params, gen = tiny
    rng = np.random.default_rng(0)
    long_p = rng.integers(0, cfg.vocab, size=30).astype(np.int32)
    short_p = rng.integers(0, cfg.vocab, size=6).astype(np.int32)
    a = _engine(gen, params, snapshot_dir=str(tmp_path / "A"))
    a.submit(Request("big", long_p, SamplingParams(max_new_tokens=20)))
    a.submit(Request("dup", short_p, SamplingParams(max_new_tokens=4)))
    a.submit(Request("small", short_p, SamplingParams(max_new_tokens=4)))
    a.step()
    manifest = a.drain()
    assert len(manifest["requests"]) == 3
    # target: tiny pool (cannot EVER hold "big"), a pre-existing "dup",
    # and a waiting queue already at its bound (rejects "small")
    b = _engine(gen, params, num_blocks=6, max_queue=1,
                snapshot_dir=str(tmp_path / "B"))
    b.submit(Request("dup", short_p, SamplingParams(max_new_tokens=4)))
    res = b.migrate_in(manifest)
    assert set(res["rejected"]) == {"big", "dup", "small"}
    assert "blocks" in res["rejected"]["big"]
    assert "duplicate" in res["rejected"]["dup"]
    assert "queue at bound" in res["rejected"]["small"]
    jb = replay_journal(tmp_path / "B" / JOURNAL_NAME)
    assert "big" not in jb    # a rejection leaves no journal trace
    # with room, the same manifest places every request
    c = _engine(gen, params, snapshot_dir=str(tmp_path / "C"))
    res2 = c.migrate_in(manifest)
    assert not res2["rejected"]
    assert (set(res2["requeued"]) | set(res2["adopted"])
            == {"big", "dup", "small"})


def test_manifest_from_journal_crash_path(tiny, tmp_path):
    """The crash-path producer: a dead replica's journal rebuilds the
    exact hand-off segment (tokens in order), ``mark=True`` receipts it
    against resurrection, finished requests ride as accounting, and the
    JSON round trip (the subprocess hand-off) is lossless."""
    cfg, params, gen = tiny
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab, size=7).astype(np.int32)
    sp = SamplingParams(max_new_tokens=8)
    oracle = _oracle(gen, params, [Request("a", prompt, sp)])["a"]
    d = str(tmp_path / "dead")
    a = _engine(gen, params, snapshot_dir=d)
    a.submit(Request("a", prompt, sp))
    a.submit(Request("f", prompt[:4], SamplingParams(max_new_tokens=2)))
    for _ in range(6):
        a.step()
    assert a._states["f"].status.value == "finished"
    n_a = len(a._states["a"].generated)
    assert 0 < n_a < 8
    # "the process dies": only the durable journal remains
    a._journal.close()
    m = manifest_from_journal(d, mark=True)
    assert [r["rid"] for r in m["requests"]] == ["a"]
    assert m["requests"][0]["tokens"] == oracle[:n_a]
    assert [f["rid"] for f in m["finished"]] == ["f"]
    # marked: a restore of the dead dir does not resurrect "a" (but
    # keeps the finished request's accounting)
    a2 = ServeEngine.restore(d, gen, params, num_blocks=40, page_size=4,
                             max_batch=2)
    assert not a2.has_request("a") and a2.has_request("f")
    # JSON round trip, then the target finishes the stream bit-exactly
    m2 = load_manifest(save_manifest(m, os.path.join(d, "m.json")))
    b = _engine(gen, params, snapshot_dir=str(tmp_path / "B"))
    assert b.migrate_in(m2)["requeued"] == ["a"]
    assert list(b.run()["a"].token_ids) == oracle


# ---------------------------------------------------------------------------
# THE fleet chaos harness (the ROADMAP #4 acceptance gate)
# ---------------------------------------------------------------------------


def _fleet(gen, params, root, clock, *, n=3, injector_for=None, **kw):
    def factory(d):
        faults = injector_for(d) if injector_for is not None else None
        return _engine(gen, params, snapshot_dir=d, faults=faults,
                       clock=clock)
    kw.setdefault("suspect_after_s", 50.0)
    kw.setdefault("dead_after_s", 100.0)
    kw.setdefault("backoff_base_s", 0.01)
    kw.setdefault("backoff_cap_s", 0.1)
    return FleetController(factory, n, root=str(root), clock=clock,
                           seed=0, **kw)


def _drive_fleet(fc, reqs, *, stagger=1, max_steps=1000):
    sub = steps = 0
    while fc.has_work() or sub < len(reqs):
        if steps % stagger == 0 and sub < len(reqs):
            fc.submit(reqs[sub])
            sub += 1
        fc.step()
        steps += 1
        assert steps < max_steps
    return steps


def _assert_no_route_to_unhealthy(fc):
    """Replay the fleet trace: no route/migrate_in placement may target
    a replica that was not HEALTHY at that moment (the circuit-breaking
    contract)."""
    state = {name: ReplicaState.HEALTHY.value for name in fc.replicas}
    for ts, step, etype, rid, data in fc.trace.events():
        if etype == "replica_state":
            state[data["replica"]] = data["state"]
        elif etype in ("route", "migrate_in"):
            assert state[data["replica"]] == "healthy", (
                f"{etype} of {rid} onto {data['replica']} while "
                f"{state[data['replica']]}")
            assert data["state"] == "healthy"


def test_fleet_chaos_kill_mid_decode(tiny, tmp_path):
    """Kill one of three replicas mid-decode under staggered load: every
    stream finishes bit-identical to the single-engine oracle, zero
    lost / zero duplicated tokens (delivery record AND the cross-
    journal union), at least one in-flight request completes on a
    DIFFERENT replica than it started on, and the router never placed
    onto a non-HEALTHY replica."""
    cfg, params, gen = tiny
    clock = _Tick()
    # replica r0's first life carries the killer: an InjectedKill out
    # of a paged-decode dispatch (the PR 5 process-death stand-in)
    inj = FaultInjector(seed=0).inject("forward", kill=True, at_call=14)

    def injector_for(d):
        if (os.sep + "r0" + os.sep) in d and d.endswith("life1"):
            return inj
        return None

    fc = _fleet(gen, params, tmp_path / "fleet", clock,
                injector_for=injector_for)
    reqs = _mixed_reqs(cfg, 8)
    oracle = _oracle(gen, params, reqs)
    _drive_fleet(fc, reqs, stagger=2)

    assert fc.deaths == 1 and inj.fire_count("forward") == 1
    assert fc.replicas["r0"].restarts == 1       # backoff restart ran
    assert fc.replicas["r0"].state is ReplicaState.HEALTHY
    # every stream bit-identical, exactly-once delivery
    assert set(fc.outputs) == set(oracle)
    for rid, toks in oracle.items():
        assert list(fc.outputs[rid].token_ids) == toks, rid
        assert fc.streams[rid] == toks, rid      # no loss, no dup
        assert fc.outputs[rid].finish_reason is FinishReason.LENGTH
    # live migration exercised: an in-flight request finished on a
    # different replica than it started on
    moved = [r for r, h in fc.history.items() if len(set(h)) > 1]
    assert moved, fc.history
    assert fc.migrations >= 1
    _assert_no_route_to_unhealthy(fc)
    # cross-journal exactly-once: for each request, token values agree
    # at every index across ALL replica journals, and exactly one
    # journal owns the finished stream (no mig receipt + fin record)
    import glob
    owners: dict = {}
    values: dict = {}
    for jp in glob.glob(os.path.join(str(tmp_path / "fleet"), "*",
                                     "life*", JOURNAL_NAME)):
        for rid, jr in replay_journal(jp).items():
            for i, (tok, _) in jr.tokens.items():
                values.setdefault(rid, {}).setdefault(i, set()).add(tok)
            if not jr.migrated and jr.finish is not None:
                owners[rid] = owners.get(rid, 0) + 1
    for rid, toks in oracle.items():
        assert owners.get(rid) == 1, (rid, owners)
        assert sorted(values[rid]) == list(range(len(toks)))
        assert [values[rid][i] == {toks[i]}
                for i in range(len(toks))] == [True] * len(toks)


def test_fleet_drain_replica_live_migration(tiny, tmp_path):
    """Cooperative maintenance drain: every in-flight request moves OFF
    a live replica mid-stream — RUNNING rows adopt in place on their
    new replica (live KV, zero recompute) — and the drained replica
    takes no further admissions until new traffic routes to it."""
    cfg, params, gen = tiny
    clock = _Tick()
    fc = _fleet(gen, params, tmp_path / "fleet", clock, n=2)
    reqs = _mixed_reqs(cfg, 4)
    oracle = _oracle(gen, params, reqs)
    for r in reqs:
        fc.submit(r)
    for _ in range(4):
        fc.step()
    victim = next(name for name, rep in fc.replicas.items()
                  if any(s is not None for s in rep.engine.slots))
    other = next(n for n in fc.replicas if n != victim)
    n_moved = fc.drain_replica(victim)
    assert n_moved >= 1
    assert not fc.replicas[victim].engine.has_work()
    fc.run()
    assert {r: list(fc.outputs[r].token_ids) for r in oracle} == oracle
    assert {r: fc.streams[r] for r in oracle} == oracle
    assert fc.replicas[other].engine.metrics.migrated_in >= n_moved
    moved = [r for r, h in fc.history.items() if len(set(h)) > 1]
    assert len(moved) >= n_moved


def test_fleet_suspect_circuit_breaking(tiny, tmp_path):
    """A SUSPECT replica stops receiving admissions (circuit-broken out
    of the router's candidate set) and recovers to HEALTHY the moment
    progress resumes — without ever being killed."""
    cfg, params, gen = tiny
    clock = _Tick()
    stalled = {"r0": False}

    def probe(rep, now):
        return 10.0 if stalled.get(rep.name) else 0.0

    fc = _fleet(gen, params, tmp_path / "fleet", clock, n=2,
                suspect_after_s=5.0, dead_after_s=1000.0, probe=probe)
    stalled["r0"] = True
    fc.step()
    assert fc.replicas["r0"].state is ReplicaState.SUSPECT
    reqs = _mixed_reqs(cfg, 4, new_tokens=4)
    for r in reqs:
        fc.submit(r)
    fc.step()
    # every placement avoided the suspect replica
    routes = [d["replica"] for _, _, e, _, d in fc.trace.events()
              if e == "route"]
    assert routes and set(routes) == {"r1"}
    assert not fc.replicas["r0"].engine.has_work()
    stalled["r0"] = False
    fc.run()
    assert fc.replicas["r0"].state is ReplicaState.HEALTHY
    assert fc.deaths == 0
    assert len(fc.outputs) == len(reqs)
    _assert_no_route_to_unhealthy(fc)


def test_fleet_watchdog_trip_is_replica_death(tiny, tmp_path, monkeypatch):
    """A WatchdogTimeout escaping a replica's step — the engine-level
    stall signal — is a replica death: the wedged replica is killed,
    its in-flight requests migrate from the journal, and the fleet
    still finishes every stream bit-exactly."""
    cfg, params, gen = tiny
    clock = _Tick()
    fc = _fleet(gen, params, tmp_path / "fleet", clock, n=2)
    reqs = _mixed_reqs(cfg, 4)
    oracle = _oracle(gen, params, reqs)
    for r in reqs:
        fc.submit(r)
    for _ in range(3):
        fc.step()
    victim = next(name for name, rep in fc.replicas.items()
                  if rep.engine.has_work())
    eng = fc.replicas[victim].engine

    def wedged():
        raise WatchdogTimeout("decode wedged past step_timeout_s")

    monkeypatch.setattr(eng, "step", wedged)
    fc.step()
    assert fc.replicas[victim].state is ReplicaState.DEAD
    assert "watchdog" in fc.replicas[victim].death_reason
    fc.run()
    assert {r: list(fc.outputs[r].token_ids) for r in oracle} == oracle
    assert {r: fc.streams[r] for r in oracle} == oracle


def test_fleet_outage_when_budget_exhausted(tiny, tmp_path):
    """Every replica dead with its restart budget spent and work still
    pending is a fleet-level outage: run() raises instead of spinning
    forever."""
    cfg, params, gen = tiny
    clock = _Tick()
    fc = _fleet(gen, params, tmp_path / "fleet", clock, n=2,
                max_restarts=0)
    reqs = _mixed_reqs(cfg, 2)
    for r in reqs:
        fc.submit(r)
    fc.step()
    fc.kill_replica("r0", "test")
    fc.kill_replica("r1", "test")
    assert all(r.state is ReplicaState.DEAD
               for r in fc.replicas.values())
    assert all(r.restart_at is None for r in fc.replicas.values())
    with pytest.raises(RuntimeError, match="fleet outage"):
        fc.run()


def test_fleet_summary_and_events(tiny, tmp_path):
    """fleet_summary() carries per-replica state + the migration/route
    counters, and the new event types are registered in the trace
    taxonomy."""
    from triton_dist_tpu.serve import trace as trace_mod

    for ev in ("migrate_out", "migrate_in", "route", "replica_state"):
        assert ev in trace_mod.EVENT_TYPES
    cfg, params, gen = tiny
    clock = _Tick()
    fc = _fleet(gen, params, tmp_path / "fleet", clock, n=2)
    reqs = _mixed_reqs(cfg, 2, new_tokens=4)
    for r in reqs:
        fc.submit(r)
    fc.run()
    s = fc.fleet_summary()
    assert set(s["replicas"]) == {"r0", "r1"}
    assert s["completed"] == 2 and s["deaths"] == 0
    assert all(r["state"] == "healthy" for r in s["replicas"].values())


def test_drain_is_atomic_on_bad_rid(tiny, tmp_path):
    """A drain that fails validation partway (an unknown rid) must
    leave the engine EXACTLY as it was: no ``mig`` receipts journaled,
    no state freed — a partially-drained engine whose receipted
    requests never reached a manifest would lose their streams
    irrecoverably."""
    cfg, params, gen = tiny
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab, size=7).astype(np.int32)
    sp = SamplingParams(max_new_tokens=8)
    oracle = _oracle(gen, params, [Request("a", prompt, sp)])["a"]
    a = _engine(gen, params, snapshot_dir=str(tmp_path / "A"))
    a.submit(Request("a", prompt, sp))
    for _ in range(4):
        a.step()
    with pytest.raises(ValueError, match="typo"):
        a.drain(["a", "typo"])
    assert a.has_request("a") and a.has_work()
    assert a.metrics.migrated_out == 0
    assert not replay_journal(tmp_path / "A" / JOURNAL_NAME)["a"].migrated
    assert list(a.run()["a"].token_ids) == oracle  # serving unharmed


def test_fleet_sheds_only_when_every_replica_full(tiny, tmp_path):
    """The bounded-admission contract holds fleet-wide: while ANY
    healthy replica has queue room the request places there; once
    every queue is at its bound the fleet SHEDS (a final verdict the
    caller sees) instead of growing an unbounded pending queue; and
    with NO healthy replica it queues (transient outage) where the
    fleet-level deadline sweep can still expire it."""
    cfg, params, gen = tiny
    clock = _Tick()

    def factory(d):
        return _engine(gen, params, snapshot_dir=d, clock=clock,
                       max_queue=1)

    fc = FleetController(factory, 2, root=str(tmp_path / "fleet"),
                         clock=clock, suspect_after_s=50.0,
                         dead_after_s=100.0, backoff_base_s=0.01,
                         backoff_cap_s=0.1, max_restarts=0, seed=0)
    rng = np.random.default_rng(0)

    def req(rid, deadline=None):
        return Request(rid, rng.integers(0, cfg.vocab, size=6)
                       .astype(np.int32),
                       SamplingParams(max_new_tokens=4,
                                      deadline_s=deadline))

    for i in range(2):   # one queued request per replica: both at bound
        fc.submit(req(f"fill{i}"))
    fc.submit(req("over"))
    out = fc.outputs["over"]
    assert out.finish_reason is FinishReason.SHED
    assert "queue at bound" in out.error
    assert fc.streams["over"] == []
    # outage window: every replica dead -> queued, then the FLEET
    # deadline sweep expires it (no engine ever saw it)
    fc.kill_replica("r0", "test")
    fc.kill_replica("r1", "test")
    fc.submit(req("ttl", deadline=0.5))
    assert "ttl" not in fc.outputs    # queued, not shed
    clock.t += 5.0
    fc.step()
    out = fc.outputs["ttl"]
    assert out.finish_reason is FinishReason.DEADLINE
    assert "fleet queue" in out.error


# ---------------------------------------------------------------------------
# RestartBackoff + Router units
# ---------------------------------------------------------------------------


def test_restart_backoff_growth_cap_and_jitter():
    b = RestartBackoff(base_s=1.0, cap_s=8.0, jitter=0.5,
                       healthy_reset_s=100.0, seed=7)
    delays = []
    t = 0.0
    for _ in range(6):
        b.on_start(t)
        t += 1.0      # dies after 1s of uptime every time
        delays.append(b.on_death(t))
    # exponential envelope with bounded jitter, capped at cap_s * 1.5
    for i, d in enumerate(delays):
        lo = min(8.0, 1.0 * 2 ** i)
        assert lo <= d <= lo * 1.5, (i, d)
    assert delays[-1] <= 12.0


def test_restart_backoff_healthy_uptime_resets_budget():
    b = RestartBackoff(base_s=1.0, cap_s=64.0, jitter=0.0,
                       healthy_reset_s=10.0, max_restarts=3)
    t = 0.0
    for _ in range(3):   # three fast crashes: budget nearly spent
        b.on_start(t)
        t += 0.1
        assert b.on_death(t) is not None
    b.on_start(t)
    t += 0.1
    assert b.on_death(t) is None          # 4th fast crash: exhausted
    # ...but a long healthy life forgives the attempt count
    b2 = RestartBackoff(base_s=1.0, cap_s=64.0, jitter=0.0,
                        healthy_reset_s=10.0, max_restarts=3)
    t = 0.0
    for _ in range(3):
        b2.on_start(t)
        t += 0.1
        assert b2.on_death(t) is not None
    b2.on_start(t)
    t += 50.0                             # healthy for 50s >> reset
    d = b2.on_death(t)
    assert d == 1.0                       # attempt count back to 1


def test_router_least_pressure_and_deadline_weighting():
    r = Router()
    idle = ReplicaLoad(queue_depth=0, running=1, max_batch=4)
    busy = ReplicaLoad(queue_depth=3, running=4, max_batch=4)
    assert r.pick([("a", busy), ("b", idle)]) == "b"
    # one queued request outweighs even a fully occupied batch
    q1 = ReplicaLoad(queue_depth=1, running=0, max_batch=4)
    full = ReplicaLoad(queue_depth=0, running=4, max_batch=4)
    assert r.pick([("a", q1), ("b", full)]) == "b"
    # a deadline request weighs the queue even harder
    assert (r.pressure(q1, deadline=True) > r.pressure(q1)
            > r.pressure(full))
    # exact ties rotate (round robin): both orders appear over calls
    same = ReplicaLoad(queue_depth=0, running=0, max_batch=4)
    picks = {r.pick([("a", same), ("b", same)]) for _ in range(8)}
    assert picks == {"a", "b"}
    assert r.pick([]) is None


def test_parse_prometheus_and_replica_load():
    text = "\n".join([
        "# HELP serve_queue_depth waiting requests",
        "# TYPE serve_queue_depth gauge",
        "serve_queue_depth 3",
        "serve_running 2",
        "serve_kv_utilization 0.25",
        'serve_finished_total{reason="length"} 7',
        "serve_ttft_seconds_sum 0.123",
        "garbage line without a value x",
    ])
    g = parse_prometheus(text)
    assert g["serve_queue_depth"] == 3.0
    assert g['serve_finished_total{reason="length"}'] == 7.0
    load = ReplicaLoad.from_prometheus(text, max_batch=4)
    assert (load.queue_depth, load.running, load.kv_util) == (3, 2, 0.25)
    r = Router()
    assert r.pressure(load) > r.pressure(ReplicaLoad(max_batch=4))


# ---------------------------------------------------------------------------
# supervisor satellites: run_once arming boundary + postmortem dedup
# ---------------------------------------------------------------------------


def _beat_child(body: str) -> list:
    """A tiny jax-free child for run_once tests (python -c)."""
    return [sys.executable, "-c", textwrap.dedent(body)]


def test_run_once_first_beat_at_grace_edge_survives(tmp_path):
    """A child whose FIRST beat lands right at the grace_s edge must
    not be killed: inside the grace window the stall detector is not
    armed (model init + warmup beat nothing), and at arming time the
    fresh beat reads healthy."""
    from serve_supervisor import run_once

    hb = str(tmp_path / "hb")
    child = _beat_child(f"""
        import time
        time.sleep(1.2)            # silent through most of the grace
        end = time.time() + 1.2    # first beat near the arming edge,
        while time.time() < end:   # then a healthy cadence
            open({hb!r}, "w").write("beat")
            time.sleep(0.05)
    """)
    t0 = time.monotonic()
    # grace leaves ~1.3s of slack past the first beat so a slow child
    # startup on a loaded host cannot push the beat past arming
    rc, stalled = run_once(child, hb, hb_interval=0.2, grace_s=2.5,
                           poll_s=0.05)
    assert rc == 0 and not stalled, (rc, stalled)
    assert time.monotonic() - t0 >= 2.0   # ran to completion, unkilled


def test_run_once_wedged_child_survives_until_armed(tmp_path):
    """A WEDGED child (beats once, then never again) survives the whole
    grace window and is killed only once the detector arms and the
    beat goes stale — never before."""
    from serve_supervisor import run_once

    hb = str(tmp_path / "hb")
    child = _beat_child(f"""
        import time
        open({hb!r}, "w").write("beat")
        time.sleep(60)             # wedged forever
    """)
    t0 = time.monotonic()
    rc, stalled = run_once(child, hb, hb_interval=0.1, grace_s=1.0,
                           poll_s=0.05)
    dt = time.monotonic() - t0
    assert rc == -9 and stalled
    assert dt >= 1.0, f"killed inside the grace window ({dt:.2f}s)"
    assert dt < 20.0


def test_postmortem_dedup(tmp_path, capsys):
    """postmortem() reports a flight file ONCE: restarts that produced
    no new flush print nothing, a fresh flush (new path or rewritten
    file) reports again."""
    from serve_supervisor import postmortem

    d = str(tmp_path)
    p1 = os.path.join(d, "flight_3.json")
    with open(p1, "w") as f:
        json.dump({"reason": "kill", "step": 3, "events": [[0, 3, "x",
                                                            None, None]],
                   "statline": "step 3"}, f)
    seen: dict = {}
    assert postmortem(d, seen) == p1
    assert "flight_3.json" in capsys.readouterr().out
    # same file, next restart: silence
    assert postmortem(d, seen) is None
    assert capsys.readouterr().out == ""
    # a NEWER flush reports
    p2 = os.path.join(d, "flight_9.json")
    with open(p2, "w") as f:
        json.dump({"reason": "watchdog", "step": 9, "events": []}, f)
    os.utime(p2, (time.time() + 5, time.time() + 5))
    assert postmortem(d, seen) == p2
    assert "flight_9.json" in capsys.readouterr().out
    # stateless call (no seen map): legacy behavior, always reports
    assert postmortem(d) == p2


def test_supervisor_refuses_a_multi_process_fleet_off_the_cpu(tmp_path):
    """``--fleet N`` with N > 1 starts N JAX processes that would each
    open every chip of the host; off ``JAX_PLATFORMS=cpu`` it is refused
    with the supported single-process forms named.  (The supervisor is
    jax-free, so it reads the platform its children will inherit.)"""
    sup = os.path.join(REPO, "scripts", "serve_supervisor.py")
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    out = subprocess.run(
        [sys.executable, sup, "--fleet", "2", "--snapshot-dir",
         str(tmp_path), "--", sys.executable, "-c", "pass"],
        env=env, capture_output=True, text=True, timeout=60)
    assert out.returncode == 2, out.stderr[-2000:]
    assert "ONE process" in out.stderr and "--mesh N" in out.stderr, \
        out.stderr[-2000:]


def test_supervisor_signal_forwarding(tmp_path):
    """SIGTERM to the supervisor forwards to the child and reaps it —
    a killed supervisor must not orphan a running engine.  The child
    here is a jax-free sleeper that records its pid and its demise."""
    sup = os.path.join(REPO, "scripts", "serve_supervisor.py")
    pidfile = str(tmp_path / "pid")
    child = (f"import os, signal, sys, time\n"
             f"open({pidfile!r}, 'w').write(str(os.getpid()))\n"
             f"signal.signal(signal.SIGTERM, lambda *a: sys.exit(0))\n"
             f"time.sleep(120)\n")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, sup, "--snapshot-dir", str(tmp_path),
         "--poll-s", "0.1", "--", sys.executable, "-c", child],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)
    try:
        deadline = time.monotonic() + 60
        while not os.path.exists(pidfile):
            assert time.monotonic() < deadline, "child never started"
            assert proc.poll() is None
            time.sleep(0.1)
        child_pid = int(open(pidfile).read())
        proc.send_signal(15)  # SIGTERM to the SUPERVISOR
        out, _ = proc.communicate(timeout=60)
        assert proc.returncode == 128 + 15, out
        assert "forwarding" in out, out
        # the child is gone (reaped, not orphaned)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            try:
                os.kill(child_pid, 0)
            except ProcessLookupError:
                break
            time.sleep(0.1)
        else:
            os.kill(child_pid, 9)
            raise AssertionError("child survived the supervisor")
    finally:
        if proc.poll() is None:
            proc.kill()


# ---------------------------------------------------------------------------
# ISSUE 11: fleet-wide distributed tracing + exact SLO aggregation
# ---------------------------------------------------------------------------


def test_fleet_taxonomy_and_series_documented(tiny, tmp_path):
    """The PR-8 meta-test extended to the fleet: every event type the
    recorder knows (the controller-side route/migrate/replica_state
    ones included) and every controller-level Prometheus series
    (``fleet.FLEET_SERIES``) must appear in docs/observability.md — and
    the controller must actually emit what FLEET_SERIES declares, so
    code, doc, and exposition cannot drift apart."""
    from triton_dist_tpu.serve import trace as trace_mod
    from triton_dist_tpu.serve.fleet import FLEET_SERIES

    with open(os.path.join(REPO, "docs", "observability.md"),
              encoding="utf-8") as f:
        doc = f.read()
    for ev in sorted(trace_mod.EVENT_TYPES):
        assert f"`{ev}`" in doc, (
            f"event type {ev!r} is not documented in "
            f"docs/observability.md")
    for name in FLEET_SERIES:
        assert name in doc, (
            f"fleet Prometheus series {name!r} is not documented in "
            f"docs/observability.md")
    # every controller-side emit() call uses a registered event type
    import re
    with open(os.path.join(REPO, "triton_dist_tpu", "serve",
                           "fleet.py"), encoding="utf-8") as f:
        emitted = set(re.findall(r'\.emit\(\s*"(\w+)"', f.read()))
    assert emitted and emitted <= trace_mod.EVENT_TYPES
    # ...and the exposition emits every declared series
    cfg, params, gen = tiny
    clock = _Tick()
    fc = _fleet(gen, params, tmp_path / "fleet", clock, n=1)
    text = fc.to_prometheus()
    for name in FLEET_SERIES:
        assert name in text, name
    # histogram min/max gauges are documented too (scrape exactness)
    assert "_min" in doc and "_max" in doc


def test_merge_scrapes_bucket_exact_at_different_depths():
    """The satellite-2 pin: two replicas whose histograms reached
    DIFFERENT bucket depths merge through the scrape path
    (text -> parse -> from_prom -> merge) into exactly the pooled-
    sample histogram — buckets, count, sum, min/max, and percentiles
    all bucket-exact — and the merged exposition stays monotone and
    complete.  Counters sum per series; kv_utilization reports max."""
    import numpy as _np

    from triton_dist_tpu.serve.fleet import merge_scrapes
    from triton_dist_tpu.serve.metrics import ServeMetrics
    from triton_dist_tpu.serve.trace import LogHistogram

    rng = _np.random.default_rng(11)
    a, b = ServeMetrics(), ServeMetrics()
    pooled = LogHistogram()
    for x in rng.lognormal(-7.0, 0.8, size=400):     # µs-range: shallow
        a.hist_ttft.observe(float(x))
        pooled.observe(float(x))
    for x in rng.lognormal(0.5, 1.0, size=300):      # sec-range: deep
        b.hist_ttft.observe(float(x))
        pooled.observe(float(x))
    a.completed, b.completed = 3, 5
    a.kv_util_last, b.kv_util_last = 0.2, 0.7
    a.finish_reasons["length"] = 3
    b.finish_reasons["length"] = 4
    b.finish_reasons["shed"] = 1
    merged = merge_scrapes([a.to_prometheus(), b.to_prometheus()])
    g = parse_prometheus(merged)
    got = LogHistogram.from_prom(g, "serve_ttft_seconds")
    assert got.counts == pooled.counts
    assert got.count == pooled.count
    assert got.min == pooled.min and got.max == pooled.max
    assert got.sum == pytest.approx(pooled.sum)
    for p in (50, 95, 99):
        assert got.percentile(p) == pooled.percentile(p), p
    assert g["serve_completed_total"] == 8
    assert g["serve_kv_utilization"] == 0.7           # max, not sum
    assert g['serve_finished_total{reason="length"}'] == 7
    assert g['serve_finished_total{reason="shed"}'] == 1
    # monotone + complete: cumulative buckets never decrease and +Inf
    # equals count, even though a and b reached disjoint depths
    buckets = [(k, v) for k, v in g.items()
               if k.startswith("serve_ttft_seconds_bucket")]
    vals = [v for _, v in buckets]
    assert vals == sorted(vals)
    assert g['serve_ttft_seconds_bucket{le="+Inf"}'] == \
        g["serve_ttft_seconds_count"]


def test_trace_context_propagates_through_migration(tiny, tmp_path):
    """Trace-context propagation at the engine level: a drained
    request's manifest record carries its trace id + hop + ring-event
    tail; the adopting engine bumps the hop, journals the context, and
    seeds the carried events ahead of its own — so a crash-path
    manifest built later from the TARGET's journal still knows the
    journey."""
    cfg, params, gen = tiny
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab, size=7).astype(np.int32)
    sp = SamplingParams(max_new_tokens=8)
    a = _engine(gen, params, snapshot_dir=str(tmp_path / "A"))
    b = _engine(gen, params, snapshot_dir=str(tmp_path / "B"))
    a.submit(Request("a", prompt, sp,
                     trace={"trace_id": "fleet0/a", "hop": 0}))
    for _ in range(5):
        a.step()
    manifest = a.drain()
    (rec,) = manifest["requests"]
    assert rec["trace"] == {"trace_id": "fleet0/a", "hop": 0}
    assert rec["events"], "the ring tail must ride the manifest"
    assert any(et == "submit" for _, _, et, _ in rec["events"])
    # the source's migrate_out named the flow the adopter will close
    mig_out = [e for e in a.trace.events() if e[2] == "migrate_out"]
    assert mig_out[0][4]["flow"] == "fleet0/a#1"

    assert b.migrate_in(manifest)["adopted"] == ["a"]
    assert b._trace_ctx["a"] == {"trace_id": "fleet0/a", "hop": 1}
    mig_in = [e for e in b.trace.events() if e[2] == "migrate_in"]
    assert mig_in[0][4]["flow"] == "fleet0/a#1"
    # carried events precede the adoption in B's ring
    b_evs = b.trace.events()
    assert [e[2] for e in b_evs].index("submit") < \
        [e[2] for e in b_evs].index("migrate_in")
    # the adopter's journal carries the bumped context: a crash-path
    # manifest from B's directory continues the journey at hop 1
    b._journal.sync()
    jb = replay_journal(tmp_path / "B" / JOURNAL_NAME)
    assert jb["a"].trace == {"trace_id": "fleet0/a", "hop": 1}
    m2 = manifest_from_journal(str(tmp_path / "B"))
    assert m2["requests"][0]["trace"] == {"trace_id": "fleet0/a",
                                          "hop": 1}
    assert list(b.run()["a"].token_ids)  # still serves to completion


class _RecordingHist:
    """LogHistogram wrapper capturing raw samples (the pooled-sample
    oracle for the exact-merge assertions)."""

    def __new__(cls, sink):
        from triton_dist_tpu.serve.trace import LogHistogram

        class _H(LogHistogram):
            def observe(self, x):
                sink.append(float(x))
                super().observe(x)
        return _H()


def test_fleet_chaos_merged_timeline_and_exact_latency(tiny, tmp_path):
    """THE ISSUE-11 acceptance gate: kill 1 of 3 replicas mid-decode
    (live migration, same harness as the PR-9 chaos test), then assert
    (a) the merged Perfetto export shows the migrated request as
    connected spans on BOTH replicas with a flow link between them, and
    (b) fleet_summary()['latency'] percentiles equal the histogram over
    the POOLED per-replica samples bucket-exactly (dead life's samples
    included via the death-time carry)."""
    import json as _json

    from triton_dist_tpu.serve.trace import (
        FLEET_PID,
        FLEET_REPLICA_PID_BASE,
        LogHistogram,
    )

    cfg, params, gen = tiny
    clock = _Tick()
    inj = FaultInjector(seed=0).inject("forward", kill=True, at_call=14)
    ttft_samples: list = []

    def injector_for(d):
        if (os.sep + "r0" + os.sep) in d and d.endswith("life1"):
            return inj
        return None

    def factory(d):
        eng = _engine(gen, params, snapshot_dir=d,
                      faults=injector_for(d), clock=clock)
        eng.metrics.hist_ttft = _RecordingHist(ttft_samples)
        return eng

    fc = FleetController(factory, 3, root=str(tmp_path / "fleet"),
                         clock=clock, seed=0, suspect_after_s=50.0,
                         dead_after_s=100.0, backoff_base_s=0.01,
                         backoff_cap_s=0.1)
    reqs = _mixed_reqs(cfg, 8)
    oracle = _oracle(gen, params, reqs)
    _drive_fleet(fc, reqs, stagger=2)
    assert fc.deaths == 1
    for rid, toks in oracle.items():
        assert list(fc.outputs[rid].token_ids) == toks, rid
    moved = [r for r, h in fc.history.items() if len(set(h)) > 1]
    assert moved

    # (b) exact latency merge: merged == pooled, bucket-exactly
    pooled = LogHistogram()
    for x in ttft_samples:
        pooled.observe(x)
    merged = fc.aggregate_metrics().hist_ttft
    assert pooled.count == len(oracle)       # one TTFT per request
    assert merged.counts == pooled.counts
    assert merged.count == pooled.count
    assert merged.min == pooled.min and merged.max == pooled.max
    lat = fc.fleet_summary()["latency"]["ttft"]
    for p, key in ((50, "p50"), (95, "p95"), (99, "p99")):
        assert lat[key] == pooled.percentile(p), key

    # (a) the merged timeline: one journey across replicas
    path = fc.export_perfetto(str(tmp_path / "fleet.trace.json"))
    with open(path) as f:
        doc = _json.load(f)
    evs = doc["traceEvents"]
    rid = moved[0]
    # the migrated request has a THREAD on >= 2 replica pids...
    tid_by_pid = {e["pid"]: e["tid"] for e in evs
                  if e.get("ph") == "M" and e.get("name") == "thread_name"
                  and e["args"]["name"] == rid
                  and e["pid"] != FLEET_PID}
    assert len(tid_by_pid) >= 2, (rid, tid_by_pid)
    assert all(p >= FLEET_REPLICA_PID_BASE for p in tid_by_pid)
    # ...with actual SPANS on both sides (not just metadata)
    for pid, tid in tid_by_pid.items():
        spans = [e for e in evs if e.get("ph") == "X"
                 and e["pid"] == pid and e["tid"] == tid]
        assert spans, (rid, pid)
    # ...and a flow link (s/f sharing an id) across two replica pids
    flows = [e for e in evs if e.get("cat") == "migration"
             and e.get("args", {}).get("rid") == rid]
    starts = {e["id"]: e["pid"] for e in flows if e["ph"] == "s"}
    finishes = {e["id"]: e["pid"] for e in flows if e["ph"] == "f"}
    linked = [fid for fid in starts
              if fid in finishes and starts[fid] != finishes[fid]]
    assert linked, (rid, flows)
    assert fc.fleet_id in linked[0]          # fleet-unique trace id
    # the controller's own track is present
    assert any(e.get("pid") == FLEET_PID for e in evs)


def test_decision_audit_answers_placement_and_movement(tiny, tmp_path):
    """The router decision audit: a routed request's entry carries the
    candidate pressures and the chosen replica; a migration carries the
    capacity-admission walk; a fleet-full shed is recorded; explain(rid)
    returns exactly that request's trail; and the audit rides the fleet
    postmortem flight file where the supervisor's postmortem reports
    it."""
    import sys as _sys

    cfg, params, gen = tiny
    clock = _Tick()
    fc = _fleet(gen, params, tmp_path / "fleet", clock, n=2)
    reqs = _mixed_reqs(cfg, 4)
    for r in reqs:
        fc.submit(r)
    for _ in range(4):
        fc.step()
    victim = next(name for name, rep in fc.replicas.items()
                  if any(s is not None for s in rep.engine.slots))
    moved_rid = next(rid for rid, name in fc.placement.items()
                     if name == victim)
    fc.drain_replica(victim)
    fc.run()
    trail = fc.explain(moved_rid)
    kinds = [e["kind"] for e in trail]
    assert "route" in kinds and "migrate" in kinds
    route = next(e for e in trail if e["kind"] == "route")
    assert route["chosen"] == fc.history[moved_rid][0]
    assert set(route["pressures"]) <= set(fc.replicas)
    assert all(isinstance(v, float) for v in route["pressures"].values())
    mig = next(e for e in trail if e["kind"] == "migrate")
    assert mig["chosen"] == fc.history[moved_rid][-1] != victim
    # a fleet postmortem carries the audit; the supervisor reports it
    path = fc.flight_flush("test postmortem")
    assert path is not None
    with open(path) as f:
        rec = json.load(f)
    assert rec["audit"] and rec["slo"]["window_s"] == fc.slo_window_s
    _sys.path.insert(0, os.path.join(REPO, "scripts"))
    from serve_supervisor import postmortem
    import io
    from contextlib import redirect_stdout
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert postmortem(str(tmp_path / "fleet")) == path
    assert "routing decisions" in buf.getvalue()


def test_fleet_slo_burn_windows_and_shed_audit(tiny, tmp_path):
    """Windowed SLO burn: a shed lands in fleet_summary()['slo'] (and
    the fleet_* exposition) inside the window and ages out of it; the
    deadline-miss window counts fleet-queue expiries too."""
    cfg, params, gen = tiny
    clock = _Tick()

    def factory(d):
        return _engine(gen, params, snapshot_dir=d, clock=clock,
                       max_queue=1)

    fc = FleetController(factory, 2, root=str(tmp_path / "fleet"),
                         clock=clock, suspect_after_s=50.0,
                         dead_after_s=100.0, backoff_base_s=0.01,
                         backoff_cap_s=0.1, max_restarts=0,
                         slo_window_s=20.0, seed=0)
    rng = np.random.default_rng(0)

    def req(rid, deadline=None):
        return Request(rid, rng.integers(0, cfg.vocab, size=6)
                       .astype(np.int32),
                       SamplingParams(max_new_tokens=4,
                                      deadline_s=deadline))

    for i in range(2):
        fc.submit(req(f"fill{i}"))
    fc.submit(req("over"))
    assert fc.outputs["over"].finish_reason is FinishReason.SHED
    s = fc.fleet_summary()["slo"]
    assert s["shed_window"] == 1 and s["shed_total"] == 1
    assert s["shed_per_s"] == pytest.approx(1 / 20.0, rel=1e-6)
    text = fc.to_prometheus()
    assert "fleet_shed_window 1" in text
    assert [e for e in fc.audit.entries() if e["kind"] == "shed"]
    # fleet-queue deadline expiry feeds the deadline window
    fc.kill_replica("r0", "test")
    fc.kill_replica("r1", "test")
    fc.submit(req("ttl", deadline=0.5))
    clock.t += 5.0
    fc.step()
    assert fc.outputs["ttl"].finish_reason is FinishReason.DEADLINE
    assert fc.fleet_summary()["slo"]["deadline_miss_window"] == 1
    # the window FORGETS: past slo_window_s both counts age to zero
    clock.t += 50.0
    s2 = fc.fleet_summary()["slo"]
    assert s2["shed_window"] == 0 and s2["deadline_miss_window"] == 0
    assert s2["shed_total"] == 1          # totals keep counting


def test_assemble_fleet_trace_from_flight_files(tmp_path):
    """Subprocess-fleet timeline assembly (jax-free): per-replica
    flight_*.json postmortems render under replica-namespaced pids with
    the migration flow linked across them — what the supervisor's
    --fleet-trace-out writes at exit."""
    from triton_dist_tpu.serve.fleet import assemble_fleet_trace
    from triton_dist_tpu.serve.trace import FLEET_REPLICA_PID_BASE

    r0, r1 = tmp_path / "r0", tmp_path / "r1"
    os.makedirs(r0)
    os.makedirs(r1 / "life1")
    flow = "fleet/q0#1"
    with open(r0 / "flight_5.json", "w") as f:
        json.dump({"reason": "kill", "step": 5, "events": [
            [1.0, 1, "submit", "q0", {"prompt": 5}],
            [1.5, 2, "admit", "q0", None],
            [2.0, 3, "prefill_done", "q0", None],
            [3.0, 5, "fault", None, {"point": "crash"}],
        ]}, f)
    with open(r1 / "life1" / "flight_9.json", "w") as f:
        json.dump({"reason": "drain", "step": 9, "events": [
            [3.5, 7, "migrate_in", "q0",
             {"in_place": False, "flow": flow}],
            [4.0, 8, "retire", "q0", {"reason": "length"}],
        ]}, f)
    out = assemble_fleet_trace([("r0", str(r0)), ("r1", str(r1))],
                               str(tmp_path / "fleet.trace.json"))
    assert out is not None
    with open(out) as f:
        evs = json.load(f)["traceEvents"]
    pids = {e["pid"] for e in evs}
    assert {FLEET_REPLICA_PID_BASE, FLEET_REPLICA_PID_BASE + 1} <= pids
    flows = [e for e in evs if e.get("cat") == "migration"]
    assert {e["ph"] for e in flows} == {"s", "f"}
    assert all(e["id"] == flow for e in flows)
    assert {e["pid"] for e in flows} == {FLEET_REPLICA_PID_BASE,
                                         FLEET_REPLICA_PID_BASE + 1}
    # an empty source set yields no file
    assert assemble_fleet_trace([("rX", str(tmp_path / "nope"))],
                                str(tmp_path / "none.json")) is None


def test_fleet_trace_level_zero_disables_ring_and_audit(tiny, tmp_path):
    """trace_level=0 on the controller: no controller events, no audit
    entries, no flight flush: off is off."""
    cfg, params, gen = tiny
    clock = _Tick()
    fc = _fleet(gen, params, tmp_path / "fleet", clock, n=2,
                trace_level=0)
    reqs = _mixed_reqs(cfg, 2, new_tokens=4)
    for r in reqs:
        fc.submit(r)
    fc.run()
    assert len(fc.outputs) == 2
    assert fc.trace.events() == [] and fc.trace.emitted == 0
    assert fc.audit.recorded == 0 and fc.audit.entries() == []
    assert fc.flight_flush("noop") is None


def test_fleet_queue_expires_parked_migration_recs(tiny, tmp_path):
    """A deadline-carrying request whose migration rec is STRANDED in
    the fleet queue (full outage: no healthy replica to adopt it) must
    expire there — engines sweep WAITING rows whatever their carried
    progress, and a rec no engine can see would otherwise be served
    arbitrarily long past its TTL once a replica healed (review
    regression: the sweep only covered fresh _pending_reqs)."""
    cfg, params, gen = tiny
    clock = _Tick()
    fc = _fleet(gen, params, tmp_path / "fleet", clock, n=2,
                max_restarts=0)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab, size=6).astype(np.int32)
    fc.submit(Request("d0", prompt,
                      SamplingParams(max_new_tokens=32, deadline_s=30.0)))
    for _ in range(6):
        fc.step()   # decoding: tokens already generated on its replica
    assert len(fc.streams["d0"]) > 0
    fc.kill_replica("r0", "test")
    fc.kill_replica("r1", "test")
    assert fc._pending_recs, "the rec must be parked (full outage)"
    carried = list(fc.streams["d0"])
    clock.t += 100.0          # TTL long gone
    fc.step()
    out = fc.outputs["d0"]
    assert out.finish_reason is FinishReason.DEADLINE
    assert "fleet queue (migrated)" in out.error
    assert list(out.token_ids) == carried   # partial stream reported
    assert not fc._pending_recs and not fc.has_work()
    assert fc.fleet_summary()["slo"]["deadline_miss_total"] == 1
