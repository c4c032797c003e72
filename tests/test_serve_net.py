"""Network serving plane (serve/net.py + fleet.RemoteReplica,
docs/serving.md "Network fleet serving"): the cross-process fleet and
its deterministic network chaos.

Fast tier (all of it — this file is the tier-1 gate for ISSUE 12):

- the ``net`` fault point (runtime/faults.py): drop / delay /
  duplicate / partition actions, ``target``/``where`` filters,
  ``at_call`` pinning, ``heal()``, audit entries;
- wire round trip: requests submitted over HTTP against an
  :class:`~serve.net.InProcessReplica` stream bit-identical to the
  single-engine oracle;
- RETRY IDEMPOTENCY in isolation (the satellite units): a duplicate
  submit is a no-op, a drain retried after a lost ack replays the
  CACHED manifest (the engine drained once — and a fresh drain of the
  receipted rids is empty), and stream-since-index re-delivery serves
  the same prefix again without re-deriving a single token;
- client retry/backoff: a dropped call retries and succeeds, an
  exhausted retry budget raises :class:`~serve.net.NetError`,
  and every retry lands a ``net_retry`` ring event;
- ambiguous submits: a submit whose every retry failed stays BOUND to
  the replica and reconciles idempotently once the partition heals;
- the IN-PROCESS net fleet chaos: FleetController over RemoteReplica
  clients, one replica killed plus one partitioned to DEAD — every
  stream bit-exact, journal ownership single, SUSPECT→DEAD flips and
  retries in the decision audit;
- THE subprocess chaos harness (the ISSUE-12 acceptance bar): N real
  replica processes, SIGKILL one mid-decode AND partition another —
  bit-exact streams, exactly-once cross-process token union, bounded
  by an explicit wall-clock deadline so a wedged child cannot hang
  tier-1;
- ``fleet_replica_state`` per-replica health exposition (controller
  and supervisor aggregate).
"""

import glob
import json
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
from triton_dist_tpu.runtime.faults import (
    FaultInjector,
    InjectedNetFault,
)
from triton_dist_tpu.serve import Request, SamplingParams, ServeEngine
from triton_dist_tpu.serve.fleet import (
    FleetController,
    RemoteReplica,
    ReplicaState,
)
from triton_dist_tpu.serve.net import (
    PORT_FILE,
    InProcessReplica,
    NetClient,
    NetError,
    NetUnreachable,
    decode_manifest,
    encode_manifest,
    read_port_file,
)
from triton_dist_tpu.serve.recovery import JOURNAL_NAME, replay_journal
from triton_dist_tpu.serve.request import FinishReason

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
    kw.setdefault("num_blocks", 60)
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
    rng = np.random.default_rng(11)
    reqs = []
    for i in range(n):
        p = rng.integers(0, cfg.vocab, size=5 + (i % 3)).astype(np.int32)
        sp = SamplingParams(max_new_tokens=new_tokens,
                            temperature=0.0 if i % 2 == 0 else 0.7,
                            seed=i)
        reqs.append(Request(f"q{i}", p, sp))
    return reqs


def _wait_metric(eng, attr, want, deadline_s=10.0):
    """The serve loop's NEXT pump flushes the wire counters into the
    engine metrics; wait for it rather than racing it."""
    t0 = time.monotonic()
    while (getattr(eng.metrics, attr) < want
           and time.monotonic() - t0 < deadline_s):
        time.sleep(0.01)
    return getattr(eng.metrics, attr)


def _drive_remote(rr, oracle, deadline_s=90.0):
    """Poll one RemoteReplica until every oracle stream finishes."""
    done = {}
    t0 = time.monotonic()
    while len(done) < len(oracle):
        assert time.monotonic() - t0 < deadline_s, (
            f"streams not drained: have {sorted(done)}, "
            f"want {sorted(oracle)}")
        for o in rr.step():
            done[o.request_id] = o
        time.sleep(0.005)
    return done


# ---------------------------------------------------------------------------
# the `net` fault point
# ---------------------------------------------------------------------------


def test_net_injector_actions():
    inj = FaultInjector(seed=0)
    inj.inject("net", drop=True, at_call=2)
    assert inj.fire("net") is None                       # call 1
    with pytest.raises(InjectedNetFault) as ei:
        inj.fire("net")                                  # call 2
    assert ei.value.action == "drop"
    assert inj.fire("net") is None                       # one-shot
    assert inj.fired[0][2] == "drop"

    dup = FaultInjector(seed=0).inject("net", duplicate=True,
                                       op="submit")
    assert dup.fire("net", op="submit") == "duplicate"
    assert dup.fire("net", op="drain") is None           # op filter

    d = FaultInjector(seed=0).inject("net", delay_s=0.05)
    t0 = time.monotonic()
    d.fire("net")
    assert time.monotonic() - t0 >= 0.04


def test_net_injector_partition_target_where_and_heal():
    inj = FaultInjector(seed=0)
    inj.inject("net", partition=True, target="r2", where="client")
    # persistent for the matching (target, where) pair...
    for _ in range(3):
        with pytest.raises(InjectedNetFault) as ei:
            inj.fire("net", target="r2", where="client")
        assert ei.value.action == "partition"
    # ...invisible to other peers and seam sides
    assert inj.fire("net", target="r1", where="client") is None
    assert inj.fire("net", target="r2", where="server_recv") is None
    # heal() closes the window; a target mismatch heals nothing
    assert inj.heal(target="r0") == 0
    assert inj.heal(target="r2") == 1
    assert inj.fire("net", target="r2", where="client") is None
    kinds = {f[2] for f in inj.fired}
    assert kinds == {"partition"}


def test_net_injector_requires_action_and_exclusive():
    inj = FaultInjector(seed=0)
    with pytest.raises(ValueError):
        inj.inject("net")
    with pytest.raises(ValueError):
        inj.inject("net", drop=True, duplicate=True)


def test_manifest_wire_roundtrip():
    rng = np.random.default_rng(0)
    k = rng.standard_normal((1, 2, 4, 8)).astype(np.float32)
    v = rng.standard_normal((1, 2, 4, 8)).astype(np.float32)
    m = {"format": 3, "clock": 1.5, "page_size": 4,
         "kv_geom": {"n_layers": 1},
         "requests": [
             {"rid": "a", "prompt": [1, 2], "tokens": [3],
              "kv": [(k, v)], "kv_len": 7, "pending": 9},
             {"rid": "b", "prompt": [4], "tokens": []},
         ], "finished": []}
    doc = json.loads(json.dumps(encode_manifest(m)))   # the real wire
    back = decode_manifest(doc)
    assert back["requests"][1].get("kv") is None
    bk, bv = back["requests"][0]["kv"][0]
    np.testing.assert_array_equal(bk, k)
    np.testing.assert_array_equal(bv, v)
    assert back["requests"][0]["pending"] == 9


# ---------------------------------------------------------------------------
# wire round trip + idempotency units
# ---------------------------------------------------------------------------


def test_net_roundtrip_bitexact_vs_oracle(tiny, tmp_path):
    cfg, params, gen = tiny
    reqs = _mixed_reqs(cfg, 4)
    oracle = _oracle(gen, params, reqs)
    rep = InProcessReplica(_engine(gen, params,
                                   snapshot_dir=str(tmp_path / "r")))
    try:
        rr = RemoteReplica("r0", rep.url, kill=rep.kill, retries=2,
                           retry_base_s=0.01)
        assert rr.ping()
        streams = {r.request_id: [] for r in reqs}
        for r in reqs:
            r.on_token = lambda rid, t: streams[rid].append(int(t))
            assert rr.submit(r) is None
        done = _drive_remote(rr, oracle)
        for rid, want in oracle.items():
            assert list(done[rid].token_ids) == want, rid
            assert streams[rid] == want, rid
            assert done[rid].finish_reason is FinishReason.LENGTH
    finally:
        rep.kill()


def test_duplicate_submit_is_noop(tiny, tmp_path):
    """Satellite unit 1: the same rid submitted twice (a retried submit
    whose first attempt landed, or an injected duplicate delivery)
    enters the engine ONCE."""
    cfg, params, gen = tiny
    req = _mixed_reqs(cfg, 1)[0]
    oracle = _oracle(gen, params, [req])
    # the transport-level duplicate: every submit is sent TWICE
    client_inj = FaultInjector(seed=0).inject("net", duplicate=True,
                                              op="submit")
    rep = InProcessReplica(_engine(gen, params,
                                   snapshot_dir=str(tmp_path / "r")))
    try:
        rr = RemoteReplica("r0", rep.url, kill=rep.kill, retries=2,
                           retry_base_s=0.01, faults=client_inj)
        assert rr.submit(req) is None
        # ...and an explicit client-level retry of the same rid
        resp = rr.client.call("submit", "/submit", method="POST", body={
            "rid": req.request_id,
            "prompt": [int(x) for x in req.prompt],
            "params": req.params.to_dict()})
        assert resp.get("dup") is True
        done = _drive_remote(rr, oracle)
        assert list(done[req.request_id].token_ids) == \
            oracle[req.request_id]
        eng = rep.engine
        assert eng.metrics.completed == 1          # served exactly once
        assert _wait_metric(eng, "net_dup_hits", 2) >= 2  # both deduped
        j = replay_journal(os.path.join(str(tmp_path / "r"),
                                        JOURNAL_NAME))
        assert list(j) == [req.request_id]         # one journal entry
    finally:
        rep.kill()


def test_stream_since_index_redelivers_never_rederives(tiny, tmp_path):
    """Satellite unit 3: polling the same indices again re-SERVES the
    same tokens (an ack lost to the network) — the engine never
    re-derives one (its counters and journal see a single emission)."""
    cfg, params, gen = tiny
    req = _mixed_reqs(cfg, 1, new_tokens=6)[0]
    oracle = _oracle(gen, params, [req])[req.request_id]
    rep = InProcessReplica(_engine(gen, params,
                                   snapshot_dir=str(tmp_path / "r")))
    try:
        rr = RemoteReplica("r0", rep.url, kill=rep.kill, retries=2,
                           retry_base_s=0.01)
        rr.submit(req)
        _drive_remote(rr, {req.request_id: oracle})
        rid = req.request_id
        a = rr.client.call("stream", f"/stream?rid={rid}&since=0")
        b = rr.client.call("stream", f"/stream?rid={rid}&since=0")
        c = rr.client.call("stream", f"/stream?rid={rid}&since=3")
        assert a["tokens"] == oracle and a["done"]
        assert b["tokens"] == oracle               # same prefix again
        assert c["tokens"] == oracle[3:]
        assert c["next"] == len(oracle)
        eng = rep.engine
        assert _wait_metric(eng, "net_redelivered_tokens",
                            len(oracle)) >= len(oracle)
        # exactly-once derivation: the journal holds each index once
        j = replay_journal(os.path.join(str(tmp_path / "r"),
                                        JOURNAL_NAME))
        assert j[rid].token_list() == oracle
        unknown = rr.client
        with pytest.raises(NetError):
            unknown.call("stream", "/stream?rid=nope&since=0")
    finally:
        rep.kill()


def test_drain_retried_after_lost_ack_is_noop(tiny, tmp_path):
    """Satellite unit 2: the first drain LANDS (receipts written, state
    released) but its ack is dropped at the server_resp seam — the
    client's keyed retry replays the cached manifest, the engine
    drains exactly once, and a FRESH drain of those rids is empty."""
    cfg, params, gen = tiny
    reqs = _mixed_reqs(cfg, 3, new_tokens=24)
    oracle = _oracle(gen, params, reqs)
    # first matching arrival only: the drain's response seam (at_call
    # would pin the Nth arrival at the whole `net` point — every
    # endpoint and seam counts there — so filter + max_fires is the
    # way to pin "the first drain ack")
    server_inj = FaultInjector(seed=0).inject(
        "net", drop=True, op="drain", where="server_resp", max_fires=1)
    src_dir = str(tmp_path / "src")
    eng = _engine(gen, params, snapshot_dir=src_dir)
    rep = InProcessReplica(eng, faults=server_inj, step_sleep_s=0.01)
    try:
        rr = RemoteReplica("r0", rep.url, kill=rep.kill, retries=3,
                           retry_base_s=0.01)
        for r in reqs:
            rr.submit(r)
        # wait until everything is genuinely in flight server-side
        t0 = time.monotonic()
        while True:
            h = rr.client.call("health", "/health")
            if h["unfinished"] == len(reqs):
                break
            assert time.monotonic() - t0 < 60
            time.sleep(0.01)
        m = rr.drain()     # first ack dropped; keyed retry returns cache
        assert sorted(r["rid"] for r in m["requests"]) == \
            sorted(o.request_id for o in reqs)
        assert eng.metrics.migrated_out == len(reqs)   # ONCE, not twice
        assert _wait_metric(eng, "net_dup_hits", 1) >= 1  # cache replay
        assert eng.unfinished_rids() == []
        # receipts make a FRESH drain (new key) of the same rids empty
        m2 = rr.drain([r.request_id for r in reqs])
        assert m2["requests"] == []
        # the journal's mig receipts block resurrection
        j = replay_journal(os.path.join(src_dir, JOURNAL_NAME))
        assert all(j[r.request_id].migrated for r in reqs)
        # and the manifest completes bit-exactly elsewhere
        dst = _engine(gen, params, max_batch=4)
        res = dst.migrate_in(m)
        assert not res["rejected"]
        outs = dst.run()
        for r in reqs:
            assert list(outs[r.request_id].token_ids) == \
                oracle[r.request_id], r.request_id
    finally:
        rep.kill()


def test_migrate_in_retried_after_lost_ack_is_noop(tiny, tmp_path):
    """A migrate_in whose ack is dropped replays from the response
    cache on retry — the target adopts each request once."""
    cfg, params, gen = tiny
    reqs = _mixed_reqs(cfg, 2, new_tokens=16)
    oracle = _oracle(gen, params, reqs)
    src = _engine(gen, params, snapshot_dir=str(tmp_path / "src"))
    for r in reqs:
        src.submit(Request(r.request_id, r.prompt, r.params))
    for _ in range(4):
        src.step()
    manifest = src.drain()
    server_inj = FaultInjector(seed=0).inject(
        "net", drop=True, op="migrate_in", where="server_resp",
        max_fires=1)
    dst_dir = str(tmp_path / "dst")
    dst_eng = _engine(gen, params, snapshot_dir=dst_dir, max_batch=4)
    rep = InProcessReplica(dst_eng, faults=server_inj)
    try:
        rr = RemoteReplica("r1", rep.url, kill=rep.kill, retries=3,
                           retry_base_s=0.01)
        res = rr.migrate_in(manifest)
        assert not res["rejected"]
        assert dst_eng.metrics.migrated_in == len(reqs)   # once each
        assert _wait_metric(dst_eng, "net_dup_hits", 1) >= 1
        done = _drive_remote(rr, oracle)
        for r in reqs:
            assert list(done[r.request_id].token_ids) == \
                oracle[r.request_id]
    finally:
        rep.kill()


# ---------------------------------------------------------------------------
# client retry / backoff / ambiguity
# ---------------------------------------------------------------------------


def test_client_retry_succeeds_and_traces(tiny, tmp_path):
    cfg, params, gen = tiny
    req = _mixed_reqs(cfg, 1)[0]
    oracle = _oracle(gen, params, [req])
    # drop the submit's FIRST send only; the backoff retry lands it
    # (the ping path deliberately does NOT retry — it is the
    # single-probe liveness check — so the retried op is a submit)
    client_inj = FaultInjector(seed=0).inject(
        "net", drop=True, op="submit", where="client", max_fires=1)
    rep = InProcessReplica(_engine(gen, params,
                                   snapshot_dir=str(tmp_path / "r")))
    try:
        rr = RemoteReplica("r0", rep.url, kill=rep.kill, retries=2,
                           retry_base_s=0.01, faults=client_inj)
        assert rr.submit(req) is None   # retried to success: no maybe
        assert req.request_id not in rr._maybe_reqs
        evs = [e for e in rr.trace.events() if e[2] == "net_retry"]
        assert len(evs) == 1
        assert evs[0][4]["op"] == "submit"
        assert evs[0][4]["attempt"] == 1
        done = _drive_remote(rr, oracle)
        assert list(done[req.request_id].token_ids) == \
            oracle[req.request_id]
    finally:
        rep.kill()


def test_client_retries_exhaust_to_neterror(tiny):
    inj = FaultInjector(seed=0).inject("net", partition=True)
    c = NetClient("http://127.0.0.1:9", timeout_s=0.2, retries=2,
                  retry_base_s=0.01, retry_cap_s=0.02, faults=inj)
    retries = []
    c.on_retry = lambda op, attempt, delay, err: retries.append(attempt)
    with pytest.raises(NetError):
        c.call("health", "/health")
    assert retries == [1, 2]
    # delays grew under the exponential law (jitter keeps them >= base)
    assert inj.fire_count("net") == 3   # initial + 2 retries


def test_ambiguous_submit_binds_and_reconciles(tiny, tmp_path):
    """A submit whose every retry failed stays BOUND to the replica
    (it may have landed); once the partition heals, reconciliation
    re-sends it idempotently and the stream completes exactly once."""
    cfg, params, gen = tiny
    req = _mixed_reqs(cfg, 1)[0]
    oracle = _oracle(gen, params, [req])
    client_inj = FaultInjector(seed=0)
    # drop the submit AND its retries at the client seam: ambiguous
    client_inj.inject("net", partition=True, op="submit",
                      target="r0", where="client")
    rep = InProcessReplica(_engine(gen, params,
                                   snapshot_dir=str(tmp_path / "r")))
    try:
        rr = RemoteReplica("r0", rep.url, kill=rep.kill, retries=1,
                           retry_base_s=0.01, faults=client_inj)
        assert rr.submit(req) is None          # optimistic binding
        assert rr.has_work()
        assert req.request_id in rr._maybe_reqs
        # still unreachable for submits: a step ping succeeds (health
        # is not partitioned) and reconcile keeps failing quietly
        rr.step()
        assert req.request_id in rr._maybe_reqs
        client_inj.heal()
        done = _drive_remote(rr, oracle)
        assert list(done[req.request_id].token_ids) == \
            oracle[req.request_id]
        assert rep.engine.metrics.completed == 1
    finally:
        rep.kill()


def test_unreachable_replica_raises_netunreachable(tiny, tmp_path):
    cfg, params, gen = tiny
    rep = InProcessReplica(_engine(gen, params,
                                   snapshot_dir=str(tmp_path / "r")))
    rr = RemoteReplica("r0", rep.url, kill=rep.kill, retries=1,
                       retry_base_s=0.01, timeout_s=0.5)
    rr.submit(_mixed_reqs(cfg, 1)[0])
    rep.kill()      # connection refused from here on
    assert not rr.ping()
    with pytest.raises(NetUnreachable):
        rr.step()


def test_dead_serve_loop_reads_as_down(tiny, tmp_path):
    """The HTTP listener outliving a dead engine thread must NOT look
    healthy: /health flips ok=false once the loop stops pumping."""
    cfg, params, gen = tiny
    eng = _engine(gen, params, snapshot_dir=str(tmp_path / "r"))
    rep = InProcessReplica(eng, stall_after_s=0.3)
    try:
        rr = RemoteReplica("r0", rep.url, retries=1, retry_base_s=0.01)
        assert rr.ping()
        rep.server.request_shutdown()   # the loop exits; listener stays
        rep._thread.join(timeout=10)
        time.sleep(0.4)
        assert not rr.ping()
    finally:
        rep.kill()


# ---------------------------------------------------------------------------
# the net fleet: in-process chaos (kill + partition-to-DEAD)
# ---------------------------------------------------------------------------


def _net_fleet(gen, params, root, *, n=3, client_inj=None,
               step_sleep_s=0.02, max_restarts=0, dead_after_s=1.5):
    procs: dict = {}
    clients: dict = {}

    def factory(life_dir):
        name = os.path.basename(os.path.dirname(life_dir))
        eng = _engine(gen, params, snapshot_dir=life_dir)
        rep = InProcessReplica(eng, stall_after_s=5.0,
                               step_sleep_s=step_sleep_s)
        procs[name] = rep
        rr = RemoteReplica(name, rep.url, kill=rep.kill, retries=2,
                           retry_base_s=0.01, retry_cap_s=0.05,
                           timeout_s=3.0, faults=client_inj)
        clients[name] = rr
        return rr.wait_ready(30)

    fc = FleetController(factory, n, root=str(root),
                         suspect_after_s=0.6, dead_after_s=dead_after_s,
                         backoff_base_s=0.05, backoff_cap_s=0.1,
                         max_restarts=max_restarts)
    return fc, procs, clients


def _assert_journal_single_ownership(root, oracle):
    """Every finished stream's ``fin`` record lives in EXACTLY one
    un-receipted journal across all lives of all replicas."""
    fins: dict = {}
    for jp in glob.glob(os.path.join(str(root), "r*", "life*",
                                     JOURNAL_NAME)):
        for rid, jr in replay_journal(jp).items():
            if jr.finish is not None and not jr.migrated:
                fins.setdefault(rid, []).append(jp)
    for rid in oracle:
        assert len(fins.get(rid, [])) == 1, (rid, fins.get(rid))


def test_net_fleet_chaos_kill_and_partition_inprocess(tiny, tmp_path):
    """The in-process twin of the subprocess harness: 3 wire-only
    replicas, one's process killed mid-decode and another cut off by a
    client-side partition until the ladder declares it DEAD — every
    stream bit-exact, token union exactly-once, retries/backoff and
    SUSPECT→DEAD flips in the audit ring and trace events."""
    cfg, params, gen = tiny
    reqs = _mixed_reqs(cfg, 6, new_tokens=24)
    oracle = _oracle(gen, params, reqs)
    client_inj = FaultInjector(seed=5)
    root = tmp_path / "netfleet"
    fc, procs, clients = _net_fleet(gen, params, root,
                                    client_inj=client_inj)
    for r in reqs:
        fc.submit(Request(r.request_id, r.prompt, r.params))
    kill_name = fc.placement[reqs[0].request_id]
    part_name = next(n for n in fc.replicas if n != kill_name)
    killed = False
    deadline = time.monotonic() + 120.0
    while fc.has_work():
        assert time.monotonic() < deadline, (
            f"fleet not drained: outputs={sorted(fc.outputs)}, states="
            f"{[(n, r.state.value) for n, r in fc.replicas.items()]}")
        fc.step()
        if not killed and sum(len(s) for s in fc.streams.values()) >= 1:
            procs[kill_name].kill()                      # SIGKILL analog
            client_inj.inject("net", partition=True,     # and a network
                              target=part_name)          # partition
            killed = True
    # every stream bit-identical to the single-engine oracle, and the
    # delivery record exactly-once
    for r in reqs:
        rid = r.request_id
        assert list(fc.outputs[rid].token_ids) == oracle[rid], rid
        assert fc.streams[rid] == oracle[rid], rid
    assert fc.deaths == 2
    _assert_journal_single_ownership(root, oracle)
    # the partition walked the ladder: SUSPECT then DEAD, audited
    audit = fc.audit.entries()
    sus = {e["replica"] for e in audit if e["kind"] == "replica_state"
           and e.get("state") == "suspect"}
    dead = {e["replica"] for e in audit if e["kind"] == "replica_state"
            and e.get("state") == "dead"}
    assert part_name in sus
    assert dead == {kill_name, part_name}
    assert any(e["kind"] == "net_retry" for e in audit)
    # ...and in the replica client's own ring
    assert any(ev[2] == "net_retry"
               for ev in clients[part_name].trace.events())
    # the one-hot health exposition reports the outcome per replica
    text = fc.to_prometheus()
    for n, rep in fc.replicas.items():
        assert (f'fleet_replica_state{{replica="{n}",'
                f'state="{rep.state.value}"}} 1') in text
    for rep in procs.values():
        rep.kill()


def test_net_fleet_partition_heals_to_healthy(tiny, tmp_path):
    """A partition shorter than ``dead_after_s`` circuit-breaks to
    SUSPECT (no admissions) and recovers to HEALTHY on heal — no
    migration, no death, streams exact."""
    cfg, params, gen = tiny
    reqs = _mixed_reqs(cfg, 4, new_tokens=24)
    oracle = _oracle(gen, params, reqs)
    client_inj = FaultInjector(seed=5)
    # SUSPECT must be SEEN between two controller passes: at the other
    # tests' 1.5 s one slow pass (a first compile beside five other
    # workers) carries a replica from HEALTHY straight to DEAD
    fc, procs, _ = _net_fleet(gen, params, tmp_path / "healfleet", n=2,
                              client_inj=client_inj, dead_after_s=15.0)
    for r in reqs:
        fc.submit(Request(r.request_id, r.prompt, r.params))
    part_name = fc.placement[reqs[0].request_id]
    client_inj.inject("net", partition=True, target=part_name)
    saw_suspect = False
    deadline = time.monotonic() + 120.0
    while fc.has_work():
        assert time.monotonic() < deadline
        fc.step()
        if (not saw_suspect and fc.replicas[part_name].state
                is ReplicaState.SUSPECT):
            saw_suspect = True
            client_inj.heal(target=part_name)
    assert saw_suspect
    assert fc.deaths == 0
    assert fc.replicas[part_name].state is ReplicaState.HEALTHY
    for r in reqs:
        assert list(fc.outputs[r.request_id].token_ids) == \
            oracle[r.request_id]
        assert fc.streams[r.request_id] == oracle[r.request_id]
    for rep in procs.values():
        rep.kill()


# ---------------------------------------------------------------------------
# THE subprocess chaos harness (ISSUE-12 acceptance)
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


def test_net_fleet_subprocess_chaos_sigkill_plus_partition(tiny,
                                                           tmp_path):
    """THE ISSUE-12 acceptance bar: 3 REAL replica processes behind the
    controller, SIGKILL one mid-decode AND partition another (client
    seam) — every stream completes bit-exact with zero lost / zero
    duplicated tokens, the cross-process token union is exactly-once,
    and retries/backoff/SUSPECT→DEAD flips appear in the DecisionAudit
    ring and trace events.  Bounded by an explicit wall-clock deadline
    at every layer: worker ``--deadline-s``, spawn readiness, and the
    drive loop — a wedged child cannot hang tier-1."""
    cfg, params, gen = tiny
    reqs = _mixed_reqs(cfg, 6, new_tokens=24)
    oracle = _oracle(gen, params, reqs)
    client_inj = FaultInjector(seed=5)
    root = tmp_path / "procfleet"
    procs: dict = {}
    clients: dict = {}
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
                           retry_cap_s=0.1, timeout_s=5.0,
                           faults=client_inj)
        clients[name] = rr
        return rr.wait_ready(60.0)

    fc = FleetController(factory, 3, root=str(root),
                         suspect_after_s=1.0, dead_after_s=2.5,
                         backoff_base_s=0.05, backoff_cap_s=0.1,
                         max_restarts=0)
    try:
        for r in reqs:
            fc.submit(Request(r.request_id, r.prompt, r.params))
        kill_name = fc.placement[reqs[0].request_id]
        part_name = next(n for n in fc.replicas if n != kill_name)
        killed = False
        while fc.has_work():
            assert time.monotonic() - t_start < HARD_DEADLINE_S, (
                f"subprocess fleet not drained inside "
                f"{HARD_DEADLINE_S}s: outputs={sorted(fc.outputs)}, "
                f"states={[(n, r.state.value) for n, r in fc.replicas.items()]}")
            fc.step()
            if (not killed
                    and sum(len(s) for s in fc.streams.values()) >= 1):
                procs[kill_name].send_signal(signal.SIGKILL)  # real one
                client_inj.inject("net", partition=True,
                                  target=part_name)
                killed = True
            time.sleep(0.005)
        assert killed, "the workload drained before the chaos landed"
        # bit-exact streams + exactly-once delivery record
        for r in reqs:
            rid = r.request_id
            assert list(fc.outputs[rid].token_ids) == oracle[rid], rid
            assert fc.streams[rid] == oracle[rid], rid
        assert fc.deaths == 2
        # cross-PROCESS token union exactly-once: single journal
        # ownership across every life of every replica process
        _assert_journal_single_ownership(root, oracle)
        # ...and no token index appears with two values anywhere
        owners: dict = {}
        for jp in glob.glob(os.path.join(str(root), "r*", "life*",
                                         JOURNAL_NAME)):
            for rid, jr in replay_journal(jp).items():
                for idx, (tok, _) in jr.tokens.items():
                    owners.setdefault((rid, idx), set()).add(tok)
        for (rid, idx), vals in owners.items():
            assert len(vals) == 1, (rid, idx, vals)
        audit = fc.audit.entries()
        dead = {e["replica"] for e in audit
                if e["kind"] == "replica_state"
                and e.get("state") == "dead"}
        sus = {e["replica"] for e in audit
               if e["kind"] == "replica_state"
               and e.get("state") == "suspect"}
        assert dead == {kill_name, part_name}
        assert part_name in sus
        assert any(e["kind"] == "net_retry" for e in audit)
        assert any(ev[2] == "net_retry" for ev in
                   clients[part_name].trace.events())
        # at least one in-flight request finished on a DIFFERENT
        # replica than it started on (the migration actually moved it)
        assert any(len(set(h)) > 1 for h in fc.history.values())
    finally:
        for proc in procs.values():
            if proc.poll() is None:
                proc.send_signal(signal.SIGKILL)
        for proc in procs.values():
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                pass


def test_rejected_submit_leaves_no_ghost_stream(tiny, tmp_path):
    """An engine-rejected submit (bad geometry) must not register a
    stream: a ghost entry would answer dup:true to every retry of a
    request the engine never accepted — and the client surfaces the
    rejection as the same ValueError an in-process submit raises."""
    cfg, params, gen = tiny
    rep = InProcessReplica(_engine(gen, params,
                                   snapshot_dir=str(tmp_path / "r")))
    try:
        rr = RemoteReplica("r0", rep.url, kill=rep.kill, retries=2,
                           retry_base_s=0.01)
        bad = Request("ghost", np.arange(4, dtype=np.int32),
                      SamplingParams(max_new_tokens=500))  # > max_seq
        with pytest.raises(ValueError):
            rr.submit(bad)
        assert "ghost" not in rr._live
        # a retry is NOT a dup — the server kept no state for it
        resp = rr.client.call("submit", "/submit", method="POST", body={
            "rid": "ghost", "prompt": [1, 2],
            "params": SamplingParams(max_new_tokens=500).to_dict()})
        assert resp.get("rejected") and not resp.get("dup")
        with rep.server._lock:
            assert "ghost" not in rep.server._streams
    finally:
        rep.kill()


def test_drain_key_reuse_recovers_landed_but_unacked_drain(tiny,
                                                           tmp_path):
    """A drain that LANDS but whose ack is lost past the whole retry
    ladder is not stranded: the next drain() call re-uses the
    outstanding idempotency key and recovers the cached manifest (the
    engine's receipts exclude those rids from any crash manifest, so
    this replay is the only cooperative way back)."""
    cfg, params, gen = tiny
    reqs = _mixed_reqs(cfg, 2, new_tokens=24)
    oracle = _oracle(gen, params, reqs)
    # drop the drain ack EVERY time until healed: the client's whole
    # retry ladder fails, drain() raises, yet the engine drained
    server_inj = FaultInjector(seed=0).inject(
        "net", drop=True, op="drain", where="server_resp")
    eng = _engine(gen, params, snapshot_dir=str(tmp_path / "src"))
    rep = InProcessReplica(eng, faults=server_inj, step_sleep_s=0.01)
    try:
        rr = RemoteReplica("r0", rep.url, kill=rep.kill, retries=1,
                           retry_base_s=0.01)
        for r in reqs:
            rr.submit(r)
        t0 = time.monotonic()
        while rr.client.call("health",
                             "/health")["unfinished"] < len(reqs):
            assert time.monotonic() - t0 < 60
            time.sleep(0.01)
        with pytest.raises(NetError):
            rr.drain()
        assert _wait_metric(eng, "migrated_out", len(reqs)) == \
            len(reqs)                      # it LANDED
        server_inj.heal()
        m = rr.drain()                     # same key → cached manifest
        assert sorted(r["rid"] for r in m["requests"]) == \
            sorted(o.request_id for o in reqs)
        assert eng.metrics.migrated_out == len(reqs)   # still once
        dst = _engine(gen, params, max_batch=4)
        res = dst.migrate_in(m)
        assert not res["rejected"]
        outs = dst.run()
        for r in reqs:
            assert list(outs[r.request_id].token_ids) == \
                oracle[r.request_id]
    finally:
        rep.kill()


def test_server_stream_retention_bounded(tiny, tmp_path):
    """The delivery-log map is bounded (the engine's ``requests_retain``
    twin): finished streams past ``streams_retain`` are pruned, live
    ones never are."""
    cfg, params, gen = tiny
    reqs = _mixed_reqs(cfg, 6, new_tokens=4)
    oracle = _oracle(gen, params, reqs)
    rep = InProcessReplica(_engine(gen, params,
                                   snapshot_dir=str(tmp_path / "r"),
                                   max_batch=4),
                           streams_retain=2)
    try:
        rr = RemoteReplica("r0", rep.url, kill=rep.kill, retries=2,
                           retry_base_s=0.01)
        # sequential: retention bounds COMPLETED history, never a
        # stream a client is still polling — each request finishes and
        # is delivered before the next arrives
        for r in reqs:
            rr.submit(r)
            done = _drive_remote(rr, {r.request_id:
                                      oracle[r.request_id]})
            assert list(done[r.request_id].token_ids) == \
                oracle[r.request_id]
        with rep.server._lock:
            n = len(rep.server._streams)
        assert n <= 2, n    # only the newest terminal streams survive
    finally:
        rep.kill()


# ---------------------------------------------------------------------------
# satellites: health-state exposition + floor file
# ---------------------------------------------------------------------------


def test_supervisor_aggregate_exposes_replica_state():
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    import serve_supervisor as sup

    class FakeRep:
        def __init__(self, name, state):
            self.name = name
            self.state = state

        def scrape_text(self):
            return None

    agg = sup._ScrapeAggregate([FakeRep("r0", ReplicaState.HEALTHY),
                                FakeRep("r1", ReplicaState.DEAD)])
    text = agg.to_prometheus()
    assert 'fleet_replica_state{replica="r0",state="healthy"} 1' in text
    assert 'fleet_replica_state{replica="r0",state="dead"} 0' in text
    assert 'fleet_replica_state{replica="r1",state="dead"} 1' in text
    assert "fleet_scraped_replicas 0" in text
