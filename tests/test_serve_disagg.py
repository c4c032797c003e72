"""Disaggregated prefill→decode serving (serve/disagg.py,
docs/serving.md "Disaggregated serving"): role-aware routing and the
per-request KV-page PUSH.

Fast tier (all of it — the ISSUE-16 gate):

- the engine pair: ``push_ready`` → ``push_out`` → ``admit_pushed``
  moves one request at prefill completion — adopted IN PLACE (live KV +
  pending token, zero recompute), stream bit-identical to the
  single-engine oracle, the source's ``mig`` receipt blocking
  resurrection, and a fallback re-admission to the SOURCE journal
  re-opening ownership so crash recovery stays single-owner;
- the tier: a 1:2 DisaggController serves greedy + seeded-sampled
  traffic bit-identical to the oracle with every push adopted in place
  (decode replicas run ZERO prefill tokens) and the audit answering
  "why did it decode there" (``decode_target`` + ``push`` records,
  rejected-capacity walk included);
- fallbacks: a rejecting decode tier walks the ranking and ultimately
  falls back to the general placer — no request is ever lost to role
  policy;
- the wire: ``POST /push`` retried after a lost ack replays the
  idempotency cache — the decode engine admits each request ONCE;
- THE disagg chaos harness: 3 REAL replica processes (1 prefill + 2
  decode), SIGKILL the prefill mid-push AND a decode replica
  post-adopt — every stream bit-exact, cross-journal token union
  exactly-once, single journal ownership.
"""

import glob
import os
import signal
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from triton_dist_tpu.models import llama
from triton_dist_tpu.models.generate import Generator
from triton_dist_tpu.runtime.faults import FaultInjector
from triton_dist_tpu.serve import Request, SamplingParams, ServeEngine
from triton_dist_tpu.serve.disagg import DisaggController, parse_disagg
from triton_dist_tpu.serve.engine import Status
from triton_dist_tpu.serve.fleet import RemoteReplica, ReplicaState
from triton_dist_tpu.serve.net import (
    PORT_FILE,
    InProcessReplica,
    read_port_file,
)
from triton_dist_tpu.serve.recovery import (
    JOURNAL_NAME,
    manifest_from_journal,
    replay_journal,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "workers", "net_replica.py")


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
    out = {}
    for r in reqs:
        eng = _engine(gen, params)
        eng.submit(Request(r.request_id, r.prompt, r.params))
        out[r.request_id] = list(eng.run()[r.request_id].token_ids)
    return out


def _mixed_reqs(cfg, n, *, new_tokens=8):
    """Greedy AND seeded-sampled — the acceptance bar covers both."""
    rng = np.random.default_rng(7)
    reqs = []
    for i in range(n):
        p = rng.integers(0, cfg.vocab, size=5 + i % 4).astype(np.int32)
        sp = SamplingParams(max_new_tokens=new_tokens,
                            temperature=0.0 if i % 2 == 0 else 0.6,
                            top_k=8, seed=i)
        reqs.append(Request(f"q{i}", p, sp))
    return reqs


class _Tick:
    def __init__(self, dt=0.01):
        self.t = 0.0
        self.dt = dt

    def __call__(self):
        self.t += self.dt
        return self.t


def _disagg(gen, params, root, clock, *, prefill=1, decode=2,
            engine_kw_for=None, **kw):
    def factory(d):
        ekw = engine_kw_for(d) if engine_kw_for is not None else {}
        return _engine(gen, params, snapshot_dir=d, clock=clock, **ekw)
    kw.setdefault("suspect_after_s", 50.0)
    kw.setdefault("dead_after_s", 100.0)
    kw.setdefault("backoff_base_s", 0.01)
    kw.setdefault("backoff_cap_s", 0.1)
    return DisaggController(factory, prefill, decode, root=str(root),
                            clock=clock, seed=0, **kw)


def _drive(fc, reqs, *, stagger=2, max_steps=2000):
    sub = steps = 0
    while fc.has_work() or sub < len(reqs):
        if steps % stagger == 0 and sub < len(reqs):
            fc.submit(reqs[sub])
            sub += 1
        fc.step()
        steps += 1
        assert steps < max_steps
    return steps


def _assert_journal_single_ownership(root, oracle):
    fins: dict = {}
    for jp in glob.glob(os.path.join(str(root), "r*", "life*",
                                     JOURNAL_NAME)):
        for rid, jr in replay_journal(jp).items():
            if jr.finish is not None and not jr.migrated:
                fins.setdefault(rid, []).append(jp)
    for rid in oracle:
        assert len(fins.get(rid, [])) == 1, (rid, fins.get(rid))


# ---------------------------------------------------------------------------
# the engine pair: push_out -> admit_pushed
# ---------------------------------------------------------------------------


def test_parse_disagg():
    assert parse_disagg("1:2") == (1, 2)
    assert parse_disagg("4:12") == (4, 12)
    for bad in ("2", "1:2:3", "a:b", "0:2", "1:0", "-1:2"):
        with pytest.raises(ValueError):
            parse_disagg(bad)


def test_engine_pair_push_inplace_and_receipts(tiny, tmp_path):
    """One request prefills on A, pushes at prefill completion, and
    decodes on B: adopted IN PLACE with the pending-token invariant
    (RUNNING at the exact stream position, zero recompute), the stream
    bit-identical to the oracle, A's ``mig`` receipt blocking
    resurrection — and a fallback re-admission to A's OWN journal
    re-opening ownership for crash recovery."""
    cfg, params, gen = tiny
    req = _mixed_reqs(cfg, 1, new_tokens=10)[0]
    rid = req.request_id
    oracle = _oracle(gen, params, [req])[rid]
    a_dir = str(tmp_path / "A")
    a = _engine(gen, params, snapshot_dir=a_dir)
    b = _engine(gen, params, snapshot_dir=str(tmp_path / "B"))
    a.submit(Request(rid, req.prompt, req.params))
    steps = 0
    while not a.push_ready():
        a.step()
        steps += 1
        assert steps < 100
    assert a.push_ready() == [rid]
    res = a.push_out(rid, target=b)
    assert res["adopted"] == [rid] and not res["rejected"]
    # counters: the push taxonomy, not the migration one
    assert a.metrics.pushed_out == 1 and a.metrics.migrated_out == 0
    assert b.metrics.pushed_in == 1 and b.metrics.migrated_in == 0
    # the ring frames it as a push on both sides
    assert any(e[2] == "push_out" and e[3] == rid
               for e in a.trace.events())
    assert any(e[2] == "push_in" and e[3] == rid
               for e in b.trace.events())
    # pending-token invariant on the adopting side: RUNNING at the
    # exact stream position, one emitted-but-unconsumed token
    rs = b._states[rid]
    assert rs.status is Status.RUNNING
    assert rs.pending_token is not None
    assert rs.kv_len == len(req.prompt) + len(rs.generated) - 1
    # zero recompute: B never ran a prefill token for it
    outs = b.run()
    assert list(outs[rid].token_ids) == oracle
    assert b.metrics.prefill_tokens == 0
    # A's journal holds the mig receipt: no resurrection
    j = replay_journal(os.path.join(a_dir, JOURNAL_NAME))
    assert j[rid].migrated
    assert manifest_from_journal(a_dir)["requests"] == []
    # ...and a fallback re-admission back into A (the live source — the
    # controller's ultimate fallback) re-opens ownership: the journal's
    # submit-after-receipt rule means crash recovery replays it again
    c = _engine(gen, params)
    c.submit(Request(rid, req.prompt, req.params))
    while not c.push_ready():
        c.step()
    m2 = c.drain([rid], push=True)
    assert a.admit_pushed(m2)["rejected"] == {}
    j2 = replay_journal(os.path.join(a_dir, JOURNAL_NAME))
    assert not j2[rid].migrated
    assert [r["rid"] for r in
            manifest_from_journal(a_dir)["requests"]] == [rid]


def test_push_ready_gating(tiny, tmp_path):
    """``push_ready`` lists exactly the RUNNING rows holding a pending
    token — nothing mid-prefill, nothing finished."""
    cfg, params, gen = tiny
    a = _engine(gen, params, snapshot_dir=str(tmp_path / "A"),
                prefill_chunk=2)
    reqs = _mixed_reqs(cfg, 2, new_tokens=4)
    for r in reqs:
        a.submit(Request(r.request_id, r.prompt, r.params))
    assert a.push_ready() == []          # nothing admitted yet
    seen = set()
    steps = 0
    while a.has_work():
        for rid in a.push_ready():
            rs = a._states[rid]
            assert rs.status is Status.RUNNING
            assert rs.pending_token is not None
            seen.add(rid)
        a.step()
        steps += 1
        assert steps < 200
    assert seen == {r.request_id for r in reqs}
    assert a.push_ready() == []          # all finished


# ---------------------------------------------------------------------------
# the tier: role-aware routing + per-request PUSH
# ---------------------------------------------------------------------------


def test_disagg_tier_bitexact_inplace_and_audit(tiny, tmp_path):
    """THE happy-path acceptance bar: a 1:2 tier serves greedy +
    seeded-sampled traffic bit-identical to the single-engine oracle;
    every request prefills on r0, pushes once, and decodes in place on
    a decode replica (zero prefill tokens there); ``explain(rid)``
    answers the journey with ``route`` → ``decode_target`` → ``push``
    audit records."""
    cfg, params, gen = tiny
    clock = _Tick()
    fc = _disagg(gen, params, tmp_path / "tier", clock)
    reqs = _mixed_reqs(cfg, 6)
    oracle = _oracle(gen, params, reqs)
    _drive(fc, reqs)

    assert set(fc.outputs) == set(oracle)
    for rid, toks in oracle.items():
        assert list(fc.outputs[rid].token_ids) == toks, rid
        assert fc.streams[rid] == toks, rid
    assert fc.pushes == len(reqs) and fc.push_fallbacks == 0
    # roles took: every journey is prefill -> one decode replica
    for rid, h in fc.history.items():
        assert h[0] == "r0" and len(h) == 2 and h[1] in ("r1", "r2"), h
    # zero recompute on the decode tier: in-place adoption only
    for name in ("r1", "r2"):
        assert fc.replicas[name].engine.metrics.prefill_tokens == 0
        assert fc.replicas[name].role == "decode"
    assert fc.replicas["r0"].role == "prefill"
    # the audit answers "why did it decode there"
    for rid in oracle:
        kinds = [e["kind"] for e in fc.explain(rid)]
        assert kinds.count("route") == 1
        assert "decode_target" in kinds
        pushes = [e for e in fc.explain(rid) if e["kind"] == "push"]
        assert len(pushes) == 1
        e = pushes[0]
        assert e["chosen"] == fc.history[rid][1]
        assert e["in_place"] is True
        assert isinstance(e["pressures"], dict) and e["pressures"]
        assert e["rejected"] == {}
    # push events carried replica + state for the circuit-break replay
    for ts, step, etype, rid, data in fc.trace.events():
        if etype == "push_in":
            assert data["state"] == "healthy"
    # taxonomy surfaces: role gauge + push counters in the exposition
    text = fc.to_prometheus()
    assert 'fleet_replica_role{replica="r0",role="prefill"} 1' in text
    assert 'fleet_replica_role{replica="r1",role="decode"} 1' in text
    assert 'fleet_replica_role{replica="r1",role="both"} 0' in text
    assert f"serve_pushed_out_total {len(reqs)}" in text
    assert f"serve_pushed_in_total {len(reqs)}" in text
    assert fc.fleet_summary()["disagg"] == {
        "prefill": 1, "decode": 2,
        "pushes": len(reqs), "push_fallbacks": 0}


def test_push_capacity_walk_in_audit(tiny, tmp_path):
    """Satellite: a decode target whose capacity admission rejects sends
    the controller down the decode ranking, and the audit's ``push``
    record carries the rejected walk — ``explain(rid)`` shows WHY the
    decode landed on the runner-up."""
    cfg, params, gen = tiny
    clock = _Tick()

    def engine_kw_for(d):
        # r1: too few pages to ever admit (fit_error rejects), so any
        # push stamped there must walk to r2
        if (os.sep + "r1" + os.sep) in d:
            return {"num_blocks": 2}
        return {}

    fc = _disagg(gen, params, tmp_path / "walk", clock,
                 engine_kw_for=engine_kw_for)
    reqs = _mixed_reqs(cfg, 6)
    oracle = _oracle(gen, params, reqs)
    _drive(fc, reqs)
    for rid, toks in oracle.items():
        assert list(fc.outputs[rid].token_ids) == toks, rid
        assert fc.streams[rid] == toks, rid
    walked = [e for e in fc.audit.entries()
              if e["kind"] == "push" and e.get("rejected")]
    assert walked, "no push ever walked the rejection ranking"
    for e in walked:
        assert "r1" in e["rejected"]       # the full replica is named
        assert e["chosen"] == "r2"         # ...and the walk landed
    # the walk is queryable per request
    rid = walked[0]["rid"]
    assert any(e.get("rejected", {}).get("r1")
               for e in fc.explain(rid) if e["kind"] == "push")


def test_push_fallback_to_general_placer_no_loss(tiny, tmp_path):
    """Exhausting the DECODE ranking falls back to the general placer —
    the source (prefill) replica re-admits its own push, its journal
    re-opens ownership, and no request is lost to role policy."""
    cfg, params, gen = tiny
    clock = _Tick()

    def engine_kw_for(d):
        if (os.sep + "r1" + os.sep) in d:     # the only decode replica
            return {"num_blocks": 2}          # rejects everything
        return {}

    fc = _disagg(gen, params, tmp_path / "fb", clock, prefill=1,
                 decode=1, engine_kw_for=engine_kw_for)
    reqs = _mixed_reqs(cfg, 3)
    oracle = _oracle(gen, params, reqs)
    _drive(fc, reqs)
    for rid, toks in oracle.items():
        assert list(fc.outputs[rid].token_ids) == toks, rid
        assert fc.streams[rid] == toks, rid
    assert fc.push_fallbacks == len(reqs) and fc.pushes == 0
    assert not fc._no_push        # cleared as each request retires
    # the fallback landed back on the source and ownership is single
    for rid, h in fc.history.items():
        assert h == ["r0", "r0"], h
    _assert_journal_single_ownership(tmp_path / "fb", oracle)
    # audited: the fallback push record names the rejection
    fb = [e for e in fc.audit.entries()
          if e["kind"] == "push" and e.get("fallback")]
    assert len(fb) == len(reqs)
    assert all("r1" in e["rejected"] for e in fb)


def test_disagg_chaos_inprocess_kill_both_tiers(tiny, tmp_path):
    """In-process chaos twin: kill the decode replica holding adopted
    pushes, then the prefill replica — every stream still bit-exact,
    exactly-once, and the cross-journal union single-owner."""
    cfg, params, gen = tiny
    clock = _Tick()
    fc = _disagg(gen, params, tmp_path / "chaos", clock,
                 max_restarts=None)
    reqs = _mixed_reqs(cfg, 6, new_tokens=12)
    oracle = _oracle(gen, params, reqs)
    sub = steps = 0
    killed_decode = killed_prefill = False
    while fc.has_work() or sub < len(reqs):
        if steps % 4 == 0 and sub < len(reqs):
            fc.submit(reqs[sub])
            sub += 1
        # chaos checks run BEFORE the tick: the sweep inside step()
        # pushes prefill-complete rows off r0 in the same call, so this
        # is the window where the prefill tier provably holds work
        if not killed_decode and fc.pushes >= 1:
            victims = {fc.placement.get(rid) for rid in fc.streams
                       if rid not in fc.outputs} & {"r1", "r2"}
            if victims:
                fc.kill_replica(sorted(victims)[0], "chaos: post-adopt")
                killed_decode = True
        elif (killed_decode and not killed_prefill
              and fc.replicas["r0"].state is ReplicaState.HEALTHY
              and any(p == "r0" for p in fc.placement.values())):
            fc.kill_replica("r0", "chaos: mid-push")
            killed_prefill = True
        fc.step()
        steps += 1
        assert steps < 3000
    assert killed_decode and killed_prefill
    assert fc.deaths == 2
    for rid, toks in oracle.items():
        assert list(fc.outputs[rid].token_ids) == toks, rid
        assert fc.streams[rid] == toks, rid
    _assert_journal_single_ownership(tmp_path / "chaos", oracle)
    # token values agree at every index across ALL journals
    values: dict = {}
    for jp in glob.glob(os.path.join(str(tmp_path / "chaos"), "r*",
                                     "life*", JOURNAL_NAME)):
        for rid, jr in replay_journal(jp).items():
            for i, (tok, _) in jr.tokens.items():
                values.setdefault(rid, {}).setdefault(i, set()).add(tok)
    for rid, toks in oracle.items():
        for i, t in enumerate(toks):
            assert values[rid].get(i, {t}) == {t}, (rid, i)


def test_decode_target_restamped_on_death(tiny, tmp_path):
    """A decode target that dies before the push re-stamps onto a
    surviving decode replica — and the audit records both choices."""
    cfg, params, gen = tiny
    clock = _Tick()
    fc = _disagg(gen, params, tmp_path / "restamp", clock)
    reqs = _mixed_reqs(cfg, 4)
    oracle = _oracle(gen, params, reqs)
    for r in reqs:
        fc.submit(r)
    victim = next(t for t in fc.decode_targets.values()
                  if t is not None)
    fc.kill_replica(victim, "chaos: target death")
    survivor = ({"r1", "r2"} - {victim}).pop()
    assert all(t == survivor for rid, t in fc.decode_targets.items()
               if rid not in fc.outputs)
    steps = 0
    while fc.has_work():
        fc.step()
        steps += 1
        assert steps < 2000
    for rid, toks in oracle.items():
        assert list(fc.outputs[rid].token_ids) == toks, rid
    restamped = [e for e in fc.audit.entries()
                 if e["kind"] == "decode_target"]
    assert any(e["chosen"] == victim for e in restamped)
    assert any(e["chosen"] == survivor for e in restamped)


# ---------------------------------------------------------------------------
# the wire: POST /push idempotency
# ---------------------------------------------------------------------------


def test_push_retried_after_lost_ack_never_double_admits(tiny, tmp_path):
    """The ISSUE-16 idempotency bar: the first ``POST /push`` LANDS but
    its ack drops at the server_resp seam — the keyed retry replays the
    cached admission verdict, the decode engine admits each request
    ONCE, and the stream completes bit-exactly."""
    cfg, params, gen = tiny
    reqs = _mixed_reqs(cfg, 2, new_tokens=12)
    oracle = _oracle(gen, params, reqs)
    src = _engine(gen, params, snapshot_dir=str(tmp_path / "src"))
    for r in reqs:
        src.submit(Request(r.request_id, r.prompt, r.params))
    while len(src.push_ready()) < len(reqs):
        src.step()
    manifest = src.drain([r.request_id for r in reqs], push=True)
    assert src.metrics.pushed_out == len(reqs)
    server_inj = FaultInjector(seed=0).inject(
        "net", drop=True, op="push", where="server_resp", max_fires=1)
    dst_eng = _engine(gen, params, snapshot_dir=str(tmp_path / "dst"),
                      max_batch=4)
    rep = InProcessReplica(dst_eng, faults=server_inj)
    try:
        rr = RemoteReplica("r1", rep.url, kill=rep.kill, retries=3,
                           retry_base_s=0.01)
        res = rr.admit_pushed(manifest)
        assert not res["rejected"]
        assert dst_eng.metrics.pushed_in == len(reqs)   # ONCE each
        t0 = time.monotonic()
        while (dst_eng.metrics.net_dup_hits < 1
               and time.monotonic() - t0 < 10.0):
            time.sleep(0.01)
        assert dst_eng.metrics.net_dup_hits >= 1        # cache replay
        deadline = time.monotonic() + 90.0
        done: dict = {}
        while len(done) < len(reqs):
            assert time.monotonic() < deadline
            for out in rr.step():
                done[out.request_id] = out
            time.sleep(0.01)
        for r in reqs:
            assert list(done[r.request_id].token_ids) == \
                oracle[r.request_id], r.request_id
    finally:
        rep.kill()


# ---------------------------------------------------------------------------
# THE subprocess chaos harness (the ISSUE-16 acceptance gate)
# ---------------------------------------------------------------------------


def _spawn_worker(life_dir, *, deadline_s, step_sleep_s=0.02):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    os.makedirs(life_dir, exist_ok=True)
    return subprocess.Popen(
        [sys.executable, WORKER, "--snapshot-dir", life_dir,
         "--deadline-s", str(deadline_s),
         "--step-sleep-s", str(step_sleep_s)],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)


def test_disagg_subprocess_chaos_sigkill_prefill_and_decode(tiny,
                                                            tmp_path):
    """THE ISSUE-16 acceptance bar: a 1:2 disagg tier of REAL replica
    processes — SIGKILL the prefill replica mid-push AND the decode
    replica holding adopted pushes — every stream completes bit-exact
    with zero lost / zero duplicated tokens, single journal ownership
    across every life of every process."""
    cfg, params, gen = tiny
    reqs = _mixed_reqs(cfg, 5, new_tokens=16)
    oracle = _oracle(gen, params, reqs)
    root = tmp_path / "disaggproc"
    procs: dict = {}
    HARD_DEADLINE_S = 240.0
    t_start = time.monotonic()

    def factory(life_dir):
        name = os.path.basename(os.path.dirname(life_dir))
        proc = _spawn_worker(str(life_dir), deadline_s=HARD_DEADLINE_S)
        procs[name] = proc

        def kill():
            if proc.poll() is None:
                proc.send_signal(signal.SIGKILL)
            proc.wait(timeout=30)

        port = read_port_file(os.path.join(str(life_dir), PORT_FILE),
                              deadline_s=120.0)
        rr = RemoteReplica(name, f"http://127.0.0.1:{port}", kill=kill,
                           retries=2, retry_base_s=0.02,
                           retry_cap_s=0.1, timeout_s=5.0)
        return rr.wait_ready(60.0)

    fc = DisaggController(factory, 1, 2, root=str(root),
                          suspect_after_s=1.0, dead_after_s=2.5,
                          backoff_base_s=0.05, backoff_cap_s=0.1,
                          max_restarts=0)
    try:
        sub = 0
        killed_decode = killed_prefill = False
        while fc.has_work() or sub < len(reqs):
            assert time.monotonic() - t_start < HARD_DEADLINE_S, (
                f"disagg fleet not drained inside {HARD_DEADLINE_S}S: "
                f"outputs={sorted(fc.outputs)}, states="
                f"{[(n, r.state.value) for n, r in fc.replicas.items()]}"
            )
            # staggered submission: fresh work keeps landing on the
            # prefill tier so the mid-push kill window stays open
            if sub < len(reqs) and (sub < 2 or killed_decode):
                r = reqs[sub]
                fc.submit(Request(r.request_id, r.prompt, r.params))
                sub += 1
            if not killed_decode and fc.pushes >= 1:
                victims = {fc.placement.get(rid) for rid in fc.streams
                           if rid not in fc.outputs} & {"r1", "r2"}
                if victims:
                    victim = sorted(victims)[0]
                    procs[victim].send_signal(signal.SIGKILL)
                    killed_decode = True
            elif (killed_decode and not killed_prefill
                  and fc.replicas["r0"].state is ReplicaState.HEALTHY
                  and any(p == "r0" for p in fc.placement.values())):
                procs["r0"].send_signal(signal.SIGKILL)
                killed_prefill = True
            fc.step()
            time.sleep(0.005)
        assert killed_decode and killed_prefill, (
            "the workload drained before both chaos kills landed")
        assert fc.deaths == 2
        assert fc.pushes >= 1
        for r in reqs:
            rid = r.request_id
            assert list(fc.outputs[rid].token_ids) == oracle[rid], rid
            assert fc.streams[rid] == oracle[rid], rid
        _assert_journal_single_ownership(root, oracle)
        # no token index appears with two values anywhere
        values: dict = {}
        for jp in glob.glob(os.path.join(str(root), "r*", "life*",
                                         JOURNAL_NAME)):
            for rid, jr in replay_journal(jp).items():
                for idx, (tok, _) in jr.tokens.items():
                    values.setdefault((rid, idx), set()).add(tok)
        for (rid, idx), vals in values.items():
            assert len(vals) == 1, (rid, idx, vals)
    finally:
        for proc in procs.values():
            if proc.poll() is None:
                proc.send_signal(signal.SIGKILL)
        for proc in procs.values():
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                pass


# ---------------------------------------------------------------------------
# configuration guards
# ---------------------------------------------------------------------------


def test_controller_role_validation(tiny, tmp_path):
    cfg, params, gen = tiny
    clock = _Tick()
    with pytest.raises(ValueError, match="role"):
        _disagg(gen, params, tmp_path / "v1", clock, prefill=0,
                decode=2)
    with pytest.raises(ValueError, match="roles"):
        DisaggController(lambda d: _engine(gen, params, snapshot_dir=d),
                         1, 1, root=str(tmp_path / "v2"),
                         roles={"r0": "both"})
    from triton_dist_tpu.serve.fleet import FleetController
    with pytest.raises(ValueError, match="unknown role"):
        FleetController(lambda d: _engine(gen, params, snapshot_dir=d),
                        1, root=str(tmp_path / "v3"),
                        roles={"r0": "decoder"})
    with pytest.raises(ValueError, match="unknown replicas"):
        FleetController(lambda d: _engine(gen, params, snapshot_dir=d),
                        1, root=str(tmp_path / "v4"),
                        roles={"r9": "decode"})
