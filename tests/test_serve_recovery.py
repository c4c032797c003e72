"""Crash-resilient serving (serve/recovery.py, docs/serving.md "Crash
recovery"): engine snapshot/restore over the Orbax checkpoint path, the
append-per-commit token journal with exactly-once resumption, and the
kill/restart chaos harness.

Fast tier: journal replay (torn-tail tolerance), the snapshot/restore
round trip with in-place resume + journal-ahead recompute, THE
kill/restart chaos sweep (kills injected mid-prefill, mid-horizon-chain,
post-commit pre-snapshot, and mid-snapshot in both crash windows; every
restarted engine's streams bit-identical to the uninterrupted run with
exact finish accounting and a whole free list), the exactly-once
commit→callback crash window, restore onto a different engine geometry,
poisoned-request non-resurrection, and deadline-remaining carry.

Slow tier: the randomized (seeded, reproducible) kill-point soak.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from triton_dist_tpu.models import llama
from triton_dist_tpu.models.generate import Generator
from triton_dist_tpu.runtime.faults import FaultInjector, InjectedKill
from triton_dist_tpu.serve import (
    Request,
    SamplingParams,
    ServeEngine,
    TokenJournal,
    replay_journal,
)
from triton_dist_tpu.serve.recovery import has_restorable_state
from triton_dist_tpu.serve.request import FinishReason
from triton_dist_tpu.serve.scheduler import Status


class _Clock:
    """Manually-advanced engine clock (deadline tests)."""

    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class _Tick:
    """Deterministic engine clock: +1 per reading."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
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


# The shared chaos traffic: greedy + seeded-sampled, staggered lengths.
_LENS = {"g0": 5, "s1": 7, "g2": 9, "g3": 6}
_N_NEW = 6


def _prompts(cfg):
    rng = np.random.default_rng(42)
    return {r: rng.integers(0, cfg.vocab, size=n).astype(np.int32)
            for r, n in _LENS.items()}


def _make_reqs(prompts, on_token=None):
    """Fresh Request objects per engine life (arrival_time is mutated)."""
    out = []
    for rid in sorted(prompts):
        if rid.startswith("s"):
            p = SamplingParams(max_new_tokens=_N_NEW, temperature=0.8,
                               top_k=16, seed=11)
        else:
            p = SamplingParams(max_new_tokens=_N_NEW)
        out.append(Request(rid, prompts[rid], p, on_token=on_token))
    return out


def _drive(eng, reqs, *, stagger=2, arm=None, max_steps=500):
    """Staggered submit + step loop.  ``arm(step, eng)`` lets a test
    arm kill specs mid-flight.  Returns True when drained, False when
    an InjectedKill 'crashed the process'."""
    submitted = step = 0
    try:
        while eng.has_work() or submitted < len(reqs):
            if step % stagger == 0 and submitted < len(reqs):
                if not eng.has_request(reqs[submitted].request_id):
                    eng.submit(reqs[submitted])
                submitted += 1
            if arm is not None:
                arm(step, eng)
            eng.step()
            step += 1
            assert step < max_steps
    except InjectedKill:
        return False
    return True


def _reference(gen, params, prompts):
    """Streams of the uninterrupted run (per-request deterministic, so
    one clean engine drain pins every configuration's expectation)."""
    eng = _engine(gen, params, clock=_Tick())
    assert _drive(eng, _make_reqs(prompts))
    outs = dict(eng._outputs)
    assert all(o.finish_reason is FinishReason.LENGTH
               for o in outs.values())
    return {r: list(o.token_ids) for r, o in outs.items()}


def _assert_bit_exact(eng, ref):
    outs = dict(eng._outputs)
    assert sorted(outs) == sorted(ref)
    for rid, want in ref.items():
        got = outs[rid].token_ids
        assert got == want, f"{rid}: {got} != {want}"
        assert outs[rid].finish_reason is FinishReason.LENGTH
    assert eng.bm.num_free == eng.bm.num_allocatable
    assert all(s is None for s in eng.slots)
    assert not eng.has_work()


# ---------------------------------------------------------------------------
# fast tier: the journal itself (no engine)
# ---------------------------------------------------------------------------


def test_journal_roundtrip_tolerates_torn_tail(tmp_path):
    path = tmp_path / "j.jsonl"
    j = TokenJournal(path)
    req = Request("a", np.array([1, 2, 3], np.int32),
                  SamplingParams(max_new_tokens=4, temperature=0.5,
                                 top_k=8, seed=9, deadline_s=2.5),
                  arrival_time=1.0)
    j.submit(req)
    j.token("a", 0, 17, 2.0)
    j.token("a", 1, 23, 3.0)
    j.finish("a", "length", None, 2, 4.0)
    assert j.records == 4 and j.bytes > 0
    j.close()
    # a crash mid-append tears the final line
    with open(path, "a") as f:
        f.write('{"t":"tok","rid":"a","i":2,"to')

    state = replay_journal(path)
    jr = state["a"]
    assert jr.token_list() == [17, 23]
    assert jr.token_times() == [2.0, 3.0]
    assert jr.finish["reason"] == "length" and jr.finish["n"] == 2
    assert list(jr.prompt) == [1, 2, 3]
    # sampling params round-trip exactly (seed drives the PRNG stream)
    assert jr.params == req.params
    assert jr.arrival == 1.0
    # duplicates keep their first occurrence; a token-index GAP is
    # damage now (ISSUE 20): replay refuses loudly, salvage truncates
    # the stream to its contiguous prefix and reports the rid
    from triton_dist_tpu.serve.recovery import (JournalCorrupt,
                                                salvage_journal)
    j2 = TokenJournal(path)
    j2.token("a", 2, 31, 5.0)
    j2.token("a", 2, 99, 6.0)     # duplicate index: ignored
    j2.token("a", 4, 77, 7.0)     # gap at 3: missing token
    j2.close()
    with pytest.raises(JournalCorrupt) as exc:
        replay_journal(path)
    assert "a" in exc.value.damage.affected_rids
    state, damage = salvage_journal(path)
    assert state["a"].token_list() == [17, 23, 31]
    assert "a" in damage.affected_rids
    assert replay_journal(tmp_path / "missing.jsonl") == {}


def test_torn_record_larger_than_scan_window(tmp_path):
    """Regression: a torn final record BIGGER than one backward-scan
    window (a submit with a very long prompt) must truncate to the last
    complete line — not wipe the healthy records before it."""
    path = tmp_path / "big.jsonl"
    j = TokenJournal(path)
    j.token("a", 0, 17, 1.0)
    j.token("a", 1, 23, 2.0)
    j.close()
    with open(path, "a") as f:       # ~80 KiB torn line, no newline
        f.write('{"t":"submit","rid":"b","prompt":['
                + ",".join("7" for _ in range(40000)))
    j2 = TokenJournal(path)          # heals on reopen
    j2.token("a", 2, 31, 3.0)
    j2.close()
    jr = replay_journal(path)
    assert jr["a"].token_list() == [17, 23, 31]
    assert "b" not in jr


def test_queuefull_rejection_never_journaled(tiny, tmp_path):
    """Regression: a request rejected with QueueFull (overload='raise')
    was told it never entered the engine — it must leave no journal
    trace, so a restore cannot resurrect and serve it."""
    from triton_dist_tpu.serve import QueueFull

    cfg, params, gen = tiny
    prompts = _prompts(cfg)
    d = tmp_path / "qfull"
    eng = _engine(gen, params, max_queue=1, overload="raise",
                  clock=_Tick(), snapshot_dir=str(d))
    eng.submit(Request("ok", prompts["g0"],
                       SamplingParams(max_new_tokens=3)))
    with pytest.raises(QueueFull):
        eng.submit(Request("rejected", prompts["g3"],
                           SamplingParams(max_new_tokens=3)))
    js = replay_journal(os.path.join(str(d), "journal.jsonl"))
    assert "rejected" not in js and "ok" in js

    eng2 = ServeEngine.restore(str(d), gen, params, clock=_Tick(),
                               num_blocks=40, page_size=4, max_batch=2,
                               prefill_chunk=4)
    assert eng2.has_request("ok") and not eng2.has_request("rejected")
    outs = eng2.run()
    assert sorted(outs) == ["ok"]


def test_fresh_engine_refuses_populated_snapshot_dir(tiny, tmp_path):
    """Regression: a FRESH engine pointed at a directory holding a
    previous life's journal/snapshots must refuse — appending a second
    life would interleave reused request ids and corrupt replay (only
    restore() may reopen the directory)."""
    cfg, params, gen = tiny
    prompts = _prompts(cfg)
    d = tmp_path / "secondlife"
    eng = _engine(gen, params, clock=_Tick(), snapshot_dir=str(d))
    eng.submit(_make_reqs(prompts)[0])
    eng.step()
    with pytest.raises(ValueError, match="previous life"):
        _engine(gen, params, clock=_Tick(), snapshot_dir=str(d))
    # restore IS the sanctioned reopen
    eng2 = ServeEngine.restore(str(d), gen, params, clock=_Tick(),
                               num_blocks=40, page_size=4, max_batch=2,
                               prefill_chunk=4)
    assert eng2.has_work()


# ---------------------------------------------------------------------------
# fast tier: snapshot/restore round trip
# ---------------------------------------------------------------------------


def test_snapshot_restore_roundtrip_bit_exact(tiny, tmp_path):
    """Mixed greedy + seeded-sampled traffic, snapshots every 3 steps;
    the engine 'dies' mid-flight and a restored engine finishes every
    stream bit-identically — journal-matching rows resume IN PLACE on
    the restored KV pools, journal-ahead rows replay through exact
    recompute."""
    cfg, params, gen = tiny
    prompts = _prompts(cfg)
    ref = _reference(gen, params, prompts)
    d = tmp_path / "snap"

    eng = _engine(gen, params, clock=_Tick(), snapshot_dir=str(d),
                  snapshot_every=3)
    reqs = _make_reqs(prompts)
    submitted = 0
    for step in range(6):          # mid-flight: some done, some running
        if step % 2 == 0 and submitted < len(reqs):
            eng.submit(reqs[submitted])
            submitted += 1
        eng.step()
    assert eng.metrics.snapshots == 2
    assert eng.has_work()          # genuinely mid-flight

    # the 'crash' lands exactly on a snapshot boundary (the 6th step is
    # a snapshot_every=3 capture), so journal-matching rows resume in
    # place with live KV
    eng2 = ServeEngine.restore(str(d), gen, params, clock=_Tick())
    r = eng2.metrics.recovery_stats()
    assert r["restores"] == 1
    assert r["restored_in_place"] >= 1
    assert r["restored_tokens"] > 0
    assert _drive(eng2, _make_reqs(prompts))   # submits any stragglers
    _assert_bit_exact(eng2, ref)
    # recovery counters ride the summary
    s = eng2.metrics.summary()["recovery"]
    assert s["restores"] == 1
    assert s["journal_records"] > 0


def test_oneshot_snapshot_without_journal(tiny, tmp_path):
    """ServeEngine.snapshot(dir) works without a journal attached (the
    manifest is self-contained) — and restore is non-destructive, so
    one snapshot restores twice."""
    cfg, params, gen = tiny
    prompts = _prompts(cfg)
    ref = _reference(gen, params, prompts)
    eng = _engine(gen, params, clock=_Tick())
    with pytest.raises(ValueError, match="snapshot"):
        eng.snapshot()             # no dir anywhere
    reqs = _make_reqs(prompts)
    for r in reqs:
        eng.submit(r)
    for _ in range(6):
        eng.step()
    d = tmp_path / "oneshot"
    info = eng.snapshot(str(d))
    assert info["step"] == 0 and info["ms"] > 0
    assert eng.metrics.snapshots == 1
    for i in range(2):
        eng2 = ServeEngine.restore(str(d), gen, params, clock=_Tick())
        assert _drive(eng2, _make_reqs(prompts)), f"restore {i}"
        _assert_bit_exact(eng2, ref)


# ---------------------------------------------------------------------------
# fast tier: THE kill/restart chaos sweep (acceptance)
# ---------------------------------------------------------------------------


def test_kill_restart_chaos_bit_exact(tiny, tmp_path):
    """For every injected kill point — mid-prefill, mid-horizon-chain
    (between a burst's device commit and its host callbacks),
    post-commit pre-snapshot (journal ahead of the KV snapshot), and
    mid-snapshot in BOTH crash windows (before the KV write; after the
    tmp write, before the rename) — the restarted engine's completed
    streams are bit-identical to an uninterrupted run, no token is
    dropped or double-emitted, finish accounting is exact, and the
    block free list is whole after the drain."""
    cfg, params, gen = tiny
    prompts = _prompts(cfg)
    ref = _reference(gen, params, prompts)

    def arm_at(step_at, point, **kw):
        """Arm a kill mid-flight, at engine step ``step_at`` (the next
        matching arrival at ``point`` then dies)."""
        def arm(step, eng):
            if step == step_at:
                eng.faults.inject(point, kill=True, **kw)
        return arm

    cases = {
        # 2nd prefill-chunk dispatch: mid-prompt, nothing emitted yet (a
        # budget of one chunk: g0's 5 tokens are two calls of 4 rows, a
        # step each — at the default budget a call is 16 rows and every
        # prompt here is one)
        "mid_prefill": dict(
            horizon=1, prefill_budget=4,
            pre=lambda inj: inj.inject("forward", op="prefill_chunk",
                                       at_call=2, kill=True),
            arm=None),
        # crash inside a fused horizon drain, after some of the burst's
        # tokens were committed + journaled (the callback seam fires
        # per committed token; call 12 lands deep in a token burst) —
        # the device is ahead of the host when the process dies
        "mid_horizon_chain": dict(
            horizon=4,
            pre=lambda inj: inj.inject("callback", at_call=12,
                                       kill=True),
            arm=None),
        # several decode commits after the last snapshot: the journal
        # runs ahead, restore replays the suffix through recompute
        "post_commit_pre_snapshot": dict(
            horizon=1, pre=None,
            arm=arm_at(7, "forward", op="paged_decode")),
        # kill before the KV write begins: the previous snapshot serves
        "mid_snapshot_pre_kv": dict(
            horizon=1, pre=None,
            arm=arm_at(5, "snapshot")),
        # kill with the tmp dir fully written but not yet renamed (the
        # snapshot point's 2nd arrival per capture): the torn snapshot
        # stays invisible and is garbage-collected on restore
        "mid_snapshot_torn": dict(
            horizon=1, pre=None,
            arm=lambda step, eng: (
                eng.faults.inject(
                    "snapshot", kill=True,
                    at_call=eng.faults.calls.get("snapshot", 0) + 2)
                if step == 5 else None)),
    }

    for name, case in cases.items():
        d = tmp_path / name
        inj = FaultInjector(seed=1)
        if case["pre"] is not None:
            case["pre"](inj)
        on_token = (lambda rid, t: None)
        eng = _engine(gen, params, clock=_Tick(), snapshot_dir=str(d),
                      snapshot_every=3, horizon=case["horizon"],
                      prefill_budget=case.get("prefill_budget"),
                      faults=inj)
        drained = _drive(eng, _make_reqs(prompts, on_token=on_token),
                         arm=case["arm"])
        assert not drained, f"{name}: the kill never fired"
        assert any(k[2] == "kill" for k in inj.fired), name
        # audit log pins the kill to an engine step for the post-mortem
        assert all(len(k) == 5 for k in inj.fired), name

        # geometry passed explicitly: a kill can land before the FIRST
        # snapshot (mid_prefill does), leaving a journal-only restore —
        # the deployment config supplies what no manifest can
        eng2 = ServeEngine.restore(str(d), gen, params, clock=_Tick(),
                                   num_blocks=40, page_size=4,
                                   max_batch=2, prefill_chunk=4,
                                   horizon=case["horizon"])
        assert _drive(eng2, _make_reqs(prompts)), name
        _assert_bit_exact(eng2, ref)
        # exact finish-reason accounting across the crash
        assert (eng2.metrics.summary()["failures"]["finish_reasons"]
                == {"length": len(prompts)}), name

    # the journal-ahead case really exercised recompute replay
    # (re-restore its directory and inspect provenance)
    eng3 = ServeEngine.restore(str(tmp_path / "post_commit_pre_snapshot"),
                               gen, params, clock=_Tick())
    # fin records were appended by the drained restore above, so this
    # second restore sees everything finished — accounting only
    assert eng3.metrics.completed == len(prompts)
    _assert_bit_exact(eng3, ref)


# ---------------------------------------------------------------------------
# fast tier: the exactly-once argument at the commit/callback window
# ---------------------------------------------------------------------------


def test_exactly_once_across_commit_callback_window(tiny, tmp_path):
    """Kill BETWEEN a token's device commit (+ journal append) and its
    on_token callback: the restarted stream contains that token exactly
    once (never re-derived, never dropped); callback delivery is
    at-most-once for it by default and at-least-once under
    restore(replay_tokens=True)."""
    cfg, params, gen = tiny
    prompts = _prompts(cfg)
    ref = _reference(gen, params, prompts)

    for replay in (False, True):
        d = tmp_path / f"window_{replay}"
        pre, post = [], []
        inj = FaultInjector().inject("callback", at_call=7, kill=True)
        eng = _engine(gen, params, clock=_Tick(), snapshot_dir=str(d),
                      snapshot_every=4, faults=inj)
        reqs = _make_reqs(prompts,
                          on_token=lambda rid, t: pre.append((rid, t)))
        assert not _drive(eng, reqs)
        assert inj.fired[-1][2] == "kill"

        eng2 = ServeEngine.restore(
            str(d), gen, params, clock=_Tick(),
            num_blocks=40, page_size=4, max_batch=2, prefill_chunk=4,
            on_token=lambda rid, t: post.append((rid, t)),
            replay_tokens=replay)
        assert _drive(eng2, _make_reqs(
            prompts, on_token=lambda rid, t: post.append((rid, t))))
        _assert_bit_exact(eng2, ref)

        missed_total = 0
        for rid, want in ref.items():
            a = [t for r, t in pre if r == rid]
            b = [t for r, t in post if r == rid]
            # pre-crash delivery is a prefix of the true stream
            assert a == want[:len(a)], rid
            if replay:
                # at-least-once: a restored in-flight request replays
                # its journaled prefix then streams the rest (b == the
                # full stream); a pre-crash-finished one replays
                # nothing (its a is already complete)
                assert b == want or (b == [] and a == want), rid
            else:
                # at-most-once: the restored tail resumes AFTER the
                # journaled tokens — b is a suffix, it never overlaps a
                # (journal count >= delivered count), and at most ONE
                # token per request (the crash-window one, journaled
                # but never delivered) goes missing
                assert b == want[len(want) - len(b):], rid
                assert len(a) + len(b) <= len(want), rid   # no double
                missed = len(want) - len(a) - len(b)
                assert missed in (0, 1), rid
                missed_total += missed
        if not replay:
            # exactly the one in-flight crash-window token at most
            assert missed_total in (0, 1)


# ---------------------------------------------------------------------------
# fast tier: restore onto a different configuration
# ---------------------------------------------------------------------------


def test_restore_onto_different_config(tiny, tmp_path):
    """The snapshot is geometry-portable: restore with fewer batch
    slots, a smaller block pool (KV recomputed where blocks don't fit),
    or a decode horizon — requests re-queue through admission where
    needed and every stream stays bit-exact."""
    cfg, params, gen = tiny
    prompts = _prompts(cfg)
    ref = _reference(gen, params, prompts)
    d = tmp_path / "geom"

    eng = _engine(gen, params, clock=_Tick(), snapshot_dir=str(d),
                  snapshot_every=3)
    reqs = _make_reqs(prompts)
    submitted = 0
    for step in range(9):
        if step % 2 == 0 and submitted < len(reqs):
            eng.submit(reqs[submitted])
            submitted += 1
        eng.step()
    assert eng.has_work() and eng.metrics.snapshots >= 2

    for tag, overrides in (
            ("fewer_slots", dict(max_batch=1)),
            ("smaller_pool", dict(num_blocks=12)),
            ("horizon", dict(horizon=4)),
            ("bigger_pool", dict(num_blocks=64, max_batch=3))):
        eng2 = ServeEngine.restore(str(d), gen, params, clock=_Tick(),
                                   **overrides)
        assert _drive(eng2, _make_reqs(prompts)), tag
        _assert_bit_exact(eng2, ref)
        if tag == "smaller_pool":
            # 12 blocks cannot hold the old tables' high block ids:
            # those requests re-queued and recomputed
            assert eng2.metrics.restored_in_place == 0, tag


def test_restore_journal_only_and_missing_dir(tiny, tmp_path):
    """With no KV snapshot at all (crash before the first capture) the
    journal alone restores every request through recompute — geometry
    must then come from the caller.  An empty directory refuses."""
    cfg, params, gen = tiny
    prompts = _prompts(cfg)
    ref = _reference(gen, params, prompts)
    d = tmp_path / "jonly"
    eng = _engine(gen, params, clock=_Tick(), snapshot_dir=str(d),
                  snapshot_every=1000)     # journal only, no KV capture
    reqs = _make_reqs(prompts)
    for r in reqs:
        eng.submit(r)
    for _ in range(4):
        eng.step()
    assert eng.metrics.snapshots == 0

    with pytest.raises(ValueError, match="geometry"):
        ServeEngine.restore(str(d), gen, params)
    eng2 = ServeEngine.restore(str(d), gen, params, clock=_Tick(),
                               num_blocks=40, page_size=4, max_batch=2,
                               prefill_chunk=4)
    assert eng2.metrics.restored_in_place == 0
    assert eng2.metrics.restored_requeued == len(prompts)
    assert _drive(eng2, _make_reqs(prompts))
    _assert_bit_exact(eng2, ref)

    with pytest.raises(FileNotFoundError, match="no restorable"):
        ServeEngine.restore(str(tmp_path / "nothing_here"), gen, params)


def test_poisoned_request_not_resurrected(tiny, tmp_path):
    """A quarantined (ERROR) request in the snapshot restores as
    FINISHED accounting only — never re-queued, never re-served — and
    its error string survives."""
    cfg, params, gen = tiny
    prompts = _prompts(cfg)
    d = tmp_path / "poison"
    inj = FaultInjector().inject("forward", rid="g2", op="paged_decode",
                                 error="poison row")
    eng = _engine(gen, params, clock=_Tick(), snapshot_dir=str(d),
                  snapshot_every=2, faults=inj, fault_retries=0)
    assert _drive(eng, _make_reqs(prompts))
    outs = dict(eng._outputs)
    assert outs["g2"].finish_reason is FinishReason.ERROR
    eng.snapshot()

    eng2 = ServeEngine.restore(str(d), gen, params, clock=_Tick())
    assert eng2.has_request("g2")
    assert eng2._states["g2"].status is Status.FINISHED
    assert not eng2.has_work()             # nothing resurrected
    out = eng2._outputs["g2"]
    assert out.finish_reason is FinishReason.ERROR
    assert "poison row" in out.error
    assert out.token_ids == outs["g2"].token_ids
    f = eng2.metrics.summary()["failures"]
    assert f["finish_reasons"]["error"] == 1
    assert f["quarantined"] == 1


def test_deadline_remaining_carries_across_restore(tiny, tmp_path):
    """The deadline TTL is measured in *remaining* time across the
    crash: a request 5s into a 10s TTL restores with ~5s left on the
    NEW engine clock — it neither expires instantly nor gets a fresh
    10s."""
    cfg, params, gen = tiny
    prompts = _prompts(cfg)
    d = tmp_path / "ttl"
    clock = _Clock(t=100.0)
    eng = _engine(gen, params, max_batch=1, prefill_budget=4,
                  clock=clock, snapshot_dir=str(d), snapshot_every=2)
    eng.submit(Request("hold", prompts["g0"],
                       SamplingParams(max_new_tokens=10)))
    eng.submit(Request("ttl", prompts["g3"],
                       SamplingParams(max_new_tokens=4, deadline_s=10.0)))
    eng.step()                     # "hold" owns the only slot
    eng.step()
    assert eng._states["ttl"].status is Status.WAITING
    clock.advance(5.0)             # 5s spent waiting
    eng.snapshot()

    clock2 = _Clock(t=7000.0)      # a fresh process, unrelated clock
    eng2 = ServeEngine.restore(str(d), gen, params, clock=clock2)
    eng2.step()
    assert eng2._states["ttl"].status is not Status.FINISHED  # ~5s left
    clock2.advance(6.0)            # 5 + 6 > 10: now it expires
    outs = eng2.run()
    assert outs["ttl"].finish_reason is FinishReason.DEADLINE
    assert outs["hold"].finish_reason is FinishReason.LENGTH
    assert eng2.bm.num_free == eng2.bm.num_allocatable


def test_snapshot_manifest_contents(tiny, tmp_path):
    """The manifest pins the documented format: engine geometry, block
    tables, per-request journal state (prompt, params, tokens, kv_len,
    status, pending) — the restore contract of docs/serving.md."""
    cfg, params, gen = tiny
    prompts = _prompts(cfg)
    d = tmp_path / "manifest"
    eng = _engine(gen, params, clock=_Tick(), snapshot_dir=str(d))
    for r in _make_reqs(prompts):
        eng.submit(r)
    for _ in range(4):
        eng.step()
    eng.snapshot()
    step_dir = os.path.join(str(d), "kv", "0")
    with open(os.path.join(step_dir, "meta.json")) as f:
        meta = json.load(f)
    assert meta["format"] == 1
    e = meta["engine"]
    assert e["num_blocks"] == 40 and e["page_size"] == 4
    assert e["max_batch"] == 2 and e["kv_dtype"] == "float32"
    running = [r for r in meta["requests"].values()
               if r["status"] == "running"]
    assert running, "traffic should be mid-decode at the capture"
    for r in running:
        assert r["kv_len"] > 0 and r["pending"] is not None
        assert r["params"]["max_new_tokens"] == _N_NEW
        assert len(r["gen"]) >= 1
    for rid in meta["tables"]:
        assert meta["tables"][rid], rid
    # journal and manifest agree at the snapshot barrier
    js = replay_journal(os.path.join(str(d), "journal.jsonl"))
    for rid, r in meta["requests"].items():
        assert js[rid].token_list()[:len(r["gen"])] == r["gen"]


def test_empty_journal_not_restorable_and_reopenable(tiny, tmp_path):
    """A crash after engine construction but before any submit leaves
    only an empty journal.jsonl: that is NOT restorable state (restore
    raises), and a FRESH engine may reopen the directory — a supervisor
    retrying --resume would otherwise wedge on an early crash forever."""
    cfg, params, gen = tiny
    d = tmp_path / "empty"
    _engine(gen, params, snapshot_dir=str(d))      # life 1: dies pre-submit
    assert os.path.exists(d / "journal.jsonl")
    assert not has_restorable_state(str(d))
    with pytest.raises(FileNotFoundError):
        ServeEngine.restore(str(d), gen, params,
                            num_blocks=40, page_size=4)
    eng = _engine(gen, params, clock=_Tick(), snapshot_dir=str(d))
    prompts = _prompts(cfg)
    assert _drive(eng, _make_reqs(prompts))        # life 2: serves fine
    assert has_restorable_state(str(d))            # and now it IS state


def test_replay_redelivers_stream_finished_at_crash(tiny, tmp_path):
    """Kill on the FINAL token's callback: the journal holds a complete
    stream whose fin record and last callback were both swallowed.  The
    restored engine finishes the row at restore (exactly-once stream,
    no recompute), and replay_tokens=True still redelivers its
    callbacks — at-least-once covers streams that completed exactly at
    the crash, not just rows that resume live."""
    cfg, params, gen = tiny
    prompts = _prompts(cfg)
    ref = _reference(gen, params, prompts)

    # Probe life: the global callback-seam call count of the LAST
    # delivered token — by construction the final token of the
    # last-finishing request (the engine is deterministic, so the kill
    # life below replays the identical schedule).
    probe = []
    engp = _engine(gen, params, clock=_Tick(),
                   snapshot_dir=str(tmp_path / "probe"), snapshot_every=4)
    assert _drive(engp, _make_reqs(
        prompts, on_token=lambda rid, t: probe.append(rid)))
    last_rid, n_calls = probe[-1], len(probe)

    for replay in (False, True):
        d = tmp_path / f"final_{replay}"
        pre, post = [], []
        inj = FaultInjector().inject("callback", at_call=n_calls,
                                     kill=True)
        eng1 = _engine(gen, params, clock=_Tick(), snapshot_dir=str(d),
                       snapshot_every=4, faults=inj)
        assert not _drive(eng1, _make_reqs(
            prompts, on_token=lambda rid, t: pre.append((rid, t))))
        assert inj.fired[-1][2] == "kill"

        eng2 = ServeEngine.restore(
            str(d), gen, params, clock=_Tick(),
            on_token=lambda rid, t: post.append((rid, t)),
            replay_tokens=replay)
        # every stream had completed at the kill: nothing resumes live
        assert not eng2.has_work()
        _assert_bit_exact(eng2, ref)

        want = ref[last_rid]
        a = [t for r, t in pre if r == last_rid]
        b = [t for r, t in post if r == last_rid]
        assert a == want[:-1]            # the final callback was lost
        if replay:
            assert b == want             # ... and is redelivered
        else:
            assert b == []               # at-most-once: stays lost


def test_oneshot_foreign_snapshot_keeps_periodic_cadence(tiny, tmp_path):
    """A one-shot snapshot() to a foreign directory must not delay the
    next periodic home capture, consume home step numbers, or evict the
    cached home-directory manager."""
    cfg, params, gen = tiny
    home = tmp_path / "home"
    eng = _engine(gen, params, clock=_Tick(), snapshot_dir=str(home),
                  snapshot_every=2)
    eng.submit(_make_reqs(_prompts(cfg))[0])
    eng.step()
    eng.step()                           # periodic capture lands here
    n0 = eng.metrics.snapshots
    seq0, mgr0, last0 = eng._snap_seq, eng._snap_mgr, eng._last_snap_step
    assert n0 >= 1 and mgr0 is not None

    info = eng.snapshot(str(tmp_path / "foreign"))
    assert (tmp_path / "foreign" / "kv" / str(info["step"])).is_dir()
    assert eng._snap_seq == seq0         # home numbering untouched
    assert eng._snap_mgr is mgr0         # home manager cache kept
    assert eng._last_snap_step == last0  # periodic cadence untouched

    eng.step()
    eng.step()                           # next periodic capture on time
    assert eng.metrics.snapshots == n0 + 2   # foreign one + periodic one
    assert eng._snap_seq == seq0 + 1
    # and the foreign copy restores on its own
    eng2 = ServeEngine.restore(str(tmp_path / "foreign"), gen, params,
                               clock=_Tick())
    assert _drive(eng2, _make_reqs(_prompts(cfg)))
    _assert_bit_exact(eng2, _reference(gen, params, _prompts(cfg)))


# ---------------------------------------------------------------------------
# slow tier: randomized kill-point soak (seeded, reproducible)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_randomized_kill_soak_reproducible(tiny, tmp_path):
    """Seeded random kills across the forward/callback/snapshot seams:
    however many times the engine dies, restarts from disk drain every
    stream bit-identically to the kill-free twin — and the same seed
    reproduces the same lives and outcomes."""
    cfg, params, gen = tiny
    rng = np.random.default_rng(7)
    lens = [3, 5, 7, 9, 4, 6, 8, 10]
    prompts = {f"r{i}": rng.integers(0, cfg.vocab, size=n)
               .astype(np.int32) for i, n in enumerate(lens)}

    def make_reqs():
        return [Request(rid, prompts[rid],
                        SamplingParams(max_new_tokens=5, temperature=(
                            0.7 if int(rid[1:]) % 3 == 2 else 0.0),
                            top_k=16, seed=int(rid[1:])),
                        on_token=lambda rid_, t: None)
                for rid in sorted(prompts)]

    ref_eng = _engine(gen, params, max_batch=3, clock=_Tick())
    assert _drive(ref_eng, make_reqs())
    ref = {r: (o.finish_reason.value, tuple(o.token_ids))
           for r, o in ref_eng._outputs.items()}

    def soak(seed, tag):
        d = tmp_path / f"soak_{tag}"

        def inj(life):
            return (FaultInjector(seed=seed * 1000 + life)
                    .inject("forward", rate=0.02, kill=True)
                    .inject("callback", rate=0.02, kill=True)
                    .inject("snapshot", rate=0.15, kill=True))

        eng = _engine(gen, params, max_batch=3, clock=_Tick(),
                      snapshot_dir=str(d), snapshot_every=3,
                      faults=inj(0))
        lives = 0
        while not _drive(eng, make_reqs(), max_steps=2000):
            lives += 1
            assert lives < 25, "soak not converging"
            eng = ServeEngine.restore(str(d), gen, params,
                                      clock=_Tick(), faults=inj(lives))
        assert eng.bm.num_free == eng.bm.num_allocatable
        return lives, {r: (o.finish_reason.value, tuple(o.token_ids))
                       for r, o in eng._outputs.items()}

    lives_a, a = soak(21, "a")
    assert a == ref                       # bit-exact despite the kills
    lives_b, b = soak(21, "b")
    assert (lives_a, a) == (lives_b, b)   # same seed, same story
    assert lives_a >= 1                   # the chaos actually bit
