"""Fault-contained serving (PR 3): deadlines, admission control,
poison-request quarantine, watchdog-guarded steps, and the
fault-injection harness (`runtime/faults.py`, docs/serving.md "Failure
containment").

Fast tier: the injector itself, deadline sweeps, queue-bound shedding,
callback containment, forward-poison bisection + quarantine, THE
deterministic chaos drain (fixed fault schedule -> exact
SHED/DEADLINE/ERROR accounting + bit-exact untouched streams + a whole
pool), and the watchdog/heartbeat stall path.

Slow tier: speculative-round bailout exactness and the randomized
(seeded, reproducible) chaos soak.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from triton_dist_tpu.models import llama
from triton_dist_tpu.models.generate import Generator
from triton_dist_tpu.runtime.faults import FaultInjector, InjectedFault
from triton_dist_tpu.runtime.watchdog import Heartbeat, WatchdogTimeout
from triton_dist_tpu.serve import (
    QueueFull,
    Request,
    SamplingParams,
    ServeEngine,
)
from triton_dist_tpu.serve.request import FinishReason
from triton_dist_tpu.serve.scheduler import Status


# ---------------------------------------------------------------------------
# fast tier: the injector itself (no engine, no jax compiles)
# ---------------------------------------------------------------------------


def test_injector_scheduled_and_filtered():
    inj = FaultInjector(seed=0)
    inj.inject("forward", at_call=2, error="boom")          # one-shot
    inj.inject("forward", rid="bad", op="decode", error="poison")
    inj.fire("forward", op="prefill", rids=("a", "b"))      # call 1: clean
    with pytest.raises(InjectedFault, match="fault #2"):
        inj.fire("forward", op="prefill", rids=("a",))      # call 2: boom
    inj.fire("forward", op="prefill", rids=("a",))          # one-shot spent
    inj.fire("forward", op="decode", rids=("a", "ok"))      # rid filter
    with pytest.raises(InjectedFault, match="poison"):
        inj.fire("forward", op="decode", rids=("a", "bad"))
    inj.fire("forward", op="prefill", rids=("bad",))        # op filter
    with pytest.raises(InjectedFault):                      # rid= ctx form
        inj.fire("forward", op="decode", rid="bad")
    assert inj.fire_count("forward") == 3
    assert inj.calls["forward"] == 7
    assert [x[1] for x in inj.fired] == [2, 5, 7]


def test_injector_rate_seeded_and_deterministic():
    def draw(seed):
        inj = FaultInjector(seed=seed)
        inj.inject("callback", rate=0.3, error="flaky")
        hits = []
        for i in range(50):
            try:
                inj.fire("callback", rid=f"r{i}")
                hits.append(0)
            except InjectedFault:
                hits.append(1)
        return hits

    a, b = draw(7), draw(7)
    assert a == b                        # same seed, same schedule
    assert 0 < sum(a) < 50               # actually probabilistic
    assert draw(8) != a                  # seed matters


def test_injector_stall_and_skew_direct():
    """Satellite: the stall and skew actions covered directly (only
    `raise` was exercised by the chaos drain).  A stall sleeps at the
    fault point for its full budget; a skew jumps every subsequent
    reading of the wrapped clock by the accumulated amount."""
    inj = FaultInjector()
    inj.inject("forward", at_call=2, stall_s=0.15)
    t0 = time.perf_counter()
    inj.fire("forward")                          # call 1: no stall
    assert time.perf_counter() - t0 < 0.1
    t0 = time.perf_counter()
    inj.fire("forward")                          # call 2: stalls
    assert time.perf_counter() - t0 >= 0.15
    inj.fire("forward")                          # one-shot spent
    assert [x[2] for x in inj.fired] == ["stall"]

    inj2 = FaultInjector()
    inj2.inject("clock", skew_s=10.0, max_fires=2)
    clk = inj2.wrap_clock(lambda: 5.0)
    assert clk() == 15.0                         # +10
    assert clk() == 25.0                         # +10 again (cumulative)
    assert clk() == 25.0                         # max_fires: skew frozen
    assert [x[2] for x in inj2.fired] == ["skew", "skew"]


def test_audit_log_records_step_index(tiny):
    """Satellite: every audit entry carries the engine's monotonic step
    index (set_step, driven by ServeEngine.step), so a chaos schedule
    replays deterministically post-mortem: (step, point, call) pins each
    firing to one seam arrival of one iteration."""
    inj = FaultInjector()
    inj.set_step(4)
    inj.inject("forward", at_call=1, error="x")
    with pytest.raises(InjectedFault):
        inj.fire("forward")
    assert inj.fired == [("forward", 1, "error", None, 4)]

    # engine-driven: the fired steps are the steps the engine executed,
    # nondecreasing, and consistent with when the poison row decoded
    cfg, params, gen = tiny
    rng = np.random.default_rng(12)
    p = rng.integers(0, cfg.vocab, size=5).astype(np.int32)
    inj2 = FaultInjector()
    inj2.inject("forward", rid="r", op="paged_decode", error="boom",
                max_fires=2)
    eng = _engine(gen, params, faults=inj2, fault_retries=1,
                  clock=_Tick())
    eng.submit(Request("r", p, SamplingParams(max_new_tokens=4)))
    eng.run()
    assert len(inj2.fired) == 2                  # first try + retry
    steps = [x[4] for x in inj2.fired]
    assert steps == sorted(steps)                # monotonic step index
    assert all(0 <= s <= eng.metrics.steps for s in steps)
    assert all(x[3] == "r" for x in inj2.fired)


def test_injector_disabled_and_clock_skew():
    inj = FaultInjector()
    inj.inject("forward", at_call=1, error="x")
    with inj.disabled():
        inj.fire("forward")              # no count, no fire
    assert inj.calls.get("forward", 0) == 0
    with pytest.raises(InjectedFault):
        inj.fire("forward")              # first ENABLED arrival

    inj2 = FaultInjector()
    inj2.inject("clock", at_call=3, skew_s=100.0)
    clk = inj2.wrap_clock(lambda: 1.0)
    assert clk() == 1.0 and clk() == 1.0
    assert clk() == 101.0                # skew lands on the 3rd reading
    assert clk() == 101.0                # and stays
    with pytest.raises(ValueError, match="action"):
        inj2.inject("forward")
    with pytest.raises(ValueError, match="rate"):
        inj2.inject("forward", rate=1.5, error="x")


# ---------------------------------------------------------------------------
# engine fixtures (shared tiny model: compiles once per module)
# ---------------------------------------------------------------------------


class _Clock:
    """Manually-advanced engine clock (deadline tests)."""

    def __init__(self):
        self.t = 0.0

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


def _oracle(gen, params, prompt, n_new):
    st = gen.prefill(params, jnp.asarray(np.asarray(prompt)[None]))
    toks, _ = gen.generate(params, st, n_new)
    return [int(t) for t in np.asarray(toks[0])]


def _engine(gen, params, **kw):
    kw.setdefault("num_blocks", 40)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_batch", 2)
    kw.setdefault("prefill_chunk", 4)
    return ServeEngine(gen, params, **kw)


# ---------------------------------------------------------------------------
# fast tier: deadlines + bounded admission
# ---------------------------------------------------------------------------


def test_deadline_expires_waiting_and_prefill(tiny):
    cfg, params, gen = tiny
    clock = _Clock()
    rng = np.random.default_rng(0)
    pl = rng.integers(0, cfg.vocab, size=6).astype(np.int32)
    pw = rng.integers(0, cfg.vocab, size=5).astype(np.int32)
    pp = rng.integers(0, cfg.vocab, size=12).astype(np.int32)
    eng = _engine(gen, params, max_batch=1, prefill_budget=4,
                  clock=clock)
    eng.submit(Request("hold", pl, SamplingParams(max_new_tokens=8)))
    eng.submit(Request("ttl", pw, SamplingParams(max_new_tokens=4,
                                                 deadline_s=10.0)))
    eng.step()                       # "hold" owns the only slot
    assert eng._states["ttl"].status is Status.WAITING
    clock.advance(11.0)
    outs = eng.run()
    assert outs["ttl"].finish_reason is FinishReason.DEADLINE
    assert outs["ttl"].token_ids == [] and "deadline" in outs["ttl"].error
    assert outs["hold"].token_ids == _oracle(gen, params, pl, 8)
    assert eng.metrics.deadline_expired == 1

    # mid-PREFILL expiry: 12-token prompt through a 4-token/step budget,
    # the TTL passes after the first chunk -> swept with blocks freed
    eng2 = _engine(gen, params, max_batch=1, prefill_budget=4,
                   clock=(c2 := _Clock()))
    eng2.submit(Request("pf", pp, SamplingParams(max_new_tokens=4,
                                                 deadline_s=5.0)))
    eng2.step()
    rs = eng2._states["pf"]
    assert rs.status is Status.PREFILL and 0 < rs.prefill_pos < 12
    c2.advance(6.0)
    outs2 = eng2.run()
    assert outs2["pf"].finish_reason is FinishReason.DEADLINE
    assert "prefill" in outs2["pf"].error
    assert eng2.bm.num_free == eng2.bm.num_allocatable
    assert all(s is None for s in eng2.slots)
    # decoding rows are exempt: no deadline output carries tokens
    s = eng2.metrics.summary()["failures"]
    assert s["deadline_expired"] == 1
    assert s["finish_reasons"] == {"deadline": 1}


def test_queue_bound_shed_and_raise(tiny):
    cfg, params, gen = tiny
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab, size=5).astype(np.int32)
               for _ in range(3)]
    eng = _engine(gen, params, max_queue=1, clock=_Tick())
    assert eng.submit(Request("a", prompts[0], SamplingParams(
        max_new_tokens=3))) is None
    shed = eng.submit(Request("b", prompts[1], SamplingParams(
        max_new_tokens=3)))
    assert shed is not None and shed.finish_reason is FinishReason.SHED
    assert shed.token_ids == [] and "max_queue" in shed.error
    outs = eng.run()
    assert outs["a"].token_ids == _oracle(gen, params, prompts[0], 3)
    assert outs["b"].finish_reason is FinishReason.SHED
    assert eng.metrics.shed == 1
    assert eng.metrics.summary()["failures"]["shed"] == 1

    eng2 = _engine(gen, params, max_queue=0, overload="raise",
                   clock=_Tick())
    with pytest.raises(QueueFull, match="max_queue"):
        eng2.submit(Request("x", prompts[2],
                            SamplingParams(max_new_tokens=3)))
    with pytest.raises(ValueError, match="overload"):
        _engine(gen, params, overload="drop")


# ---------------------------------------------------------------------------
# fast tier: poison containment
# ---------------------------------------------------------------------------


def test_callback_exception_contained(tiny):
    """Satellite: a buggy on_token callback must not unwind step() after
    the token is committed — log once, disable the callback, keep
    serving, stream stays exact."""
    cfg, params, gen = tiny
    rng = np.random.default_rng(2)
    p = rng.integers(0, cfg.vocab, size=5).astype(np.int32)
    calls = []

    def buggy(rid, tok):
        calls.append(tok)
        if len(calls) == 2:
            raise ValueError("frontend bug")

    eng = _engine(gen, params, clock=_Tick())
    eng.submit(Request("cb", p, SamplingParams(max_new_tokens=5),
                       on_token=buggy))
    outs = eng.run()
    assert outs["cb"].finish_reason is FinishReason.LENGTH
    assert outs["cb"].token_ids == _oracle(gen, params, p, 5)
    assert len(calls) == 2                  # disabled after the raise
    assert eng.metrics.callback_errors == 1
    assert eng._states["cb"].callback_disabled


def test_poison_forward_bisected_and_quarantined(tiny):
    """A rid-poisoned batched decode: the batch retries, bisects to the
    poison row, quarantines it (ERROR, blocks freed) — and the healthy
    slot-mates' streams stay bit-identical to a fault-free run."""
    cfg, params, gen = tiny
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (5, 6, 7)]
    n_new = 4

    def drive(faults):
        eng = _engine(gen, params, max_batch=2, faults=faults,
                      fault_retries=1, clock=_Tick())
        for i, p in enumerate(prompts):
            eng.submit(Request(f"p{i}", p,
                               SamplingParams(max_new_tokens=n_new)))
        outs = eng.run()
        return eng, outs

    inj = FaultInjector(seed=0)
    inj.inject("forward", rid="p1", op="paged_decode", error="bad row")
    eng, outs = drive(inj)
    _, clean = drive(None)

    assert outs["p1"].finish_reason is FinishReason.ERROR
    assert "bad row" in outs["p1"].error
    assert len(outs["p1"].token_ids) == 1   # prefill token, then poison
    for rid in ("p0", "p2"):
        assert outs[rid].finish_reason is FinishReason.LENGTH
        assert outs[rid].token_ids == clean[rid].token_ids
    f = eng.metrics.summary()["failures"]
    assert f["quarantined"] == 1
    assert f["forward_bisections"] >= 1
    assert f["forward_retries"] >= 1
    assert eng.bm.num_free == eng.bm.num_allocatable
    assert all(s is None for s in eng.slots)


def test_one_step_horizon_link_is_contained_like_the_single_step(tiny):
    """A ``horizon=8`` engine beside a mid-prefill slot decodes through
    ONE ``decode_horizon`` link at H = 1 a step, and nothing host-side
    mutates before that link drains — so a transient ``forward`` fault on
    it is absorbed by the retry, a poison row bisects to quarantine, the
    slot-mates' streams are a fault-free run's, and an aborted attempt
    (it never reached the device) counts no dispatch."""
    cfg, params, gen = tiny
    rng = np.random.default_rng(17)
    prompts = {"p0": 5, "p1": 6, "long": 30}   # "long": 8 steps of prefill
    prompts = {rid: rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for rid, n in prompts.items()}

    def drive(faults):
        eng = _engine(gen, params, max_batch=3, prefill_budget=4,
                      horizon=8, pipeline=2, faults=faults,
                      fault_retries=1, clock=_Tick())
        faulted, call = [], eng._device_call

        def device_call(op, rids, fn, *a, **kw):
            try:
                return call(op, rids, fn, *a, **kw)
            except RuntimeError:
                faulted.append((op, kw.get("H")))
                raise

        eng._device_call = device_call
        for rid, p in prompts.items():
            eng.submit(Request(rid, p, SamplingParams(
                max_new_tokens=3 if rid == "long" else 8)))
        return eng, eng.run(), faulted

    inj = FaultInjector(seed=0)
    inj.inject("forward", op="decode_horizon", error="transient",
               max_fires=1)
    inj.inject("forward", rid="p1", op="decode_horizon", error="bad row")
    eng, outs, faulted = drive(inj)
    _, clean, none = drive(None)
    assert not none

    assert outs["p1"].finish_reason is FinishReason.ERROR
    assert "bad row" in outs["p1"].error
    assert len(outs["p1"].token_ids) == 1   # prefill token, then poison
    for rid in ("p0", "long"):
        assert outs[rid].finish_reason is FinishReason.LENGTH
        assert outs[rid].token_ids == clean[rid].token_ids
        assert outs[rid].token_ids == _oracle(
            gen, params, prompts[rid], len(clean[rid].token_ids))
    # p0 decoded beside p1's prefill, p1 beside long's: every faulted
    # attempt was a one-step link (the transient, then p1's pair twice)
    assert len(faulted) >= 4
    assert set(faulted) == {("decode_horizon", 1)}, faulted
    f = eng.metrics.summary()["failures"]
    assert f["quarantined"] == 1
    assert f["forward_retries"] >= 2 and f["forward_bisections"] >= 1
    # a dispatch is a link that reached the device
    assert eng.metrics.dispatches == (eng._horizon_fn.hits
                                      + eng._horizon_fn.misses)
    assert eng._decode_fn.hits + eng._decode_fn.misses == 0
    assert eng.bm.num_free == eng.bm.num_allocatable
    assert all(s is None for s in eng.slots)


def test_block_alloc_fault_quarantines_grower(tiny):
    cfg, params, gen = tiny
    rng = np.random.default_rng(4)
    pg = rng.integers(0, cfg.vocab, size=6).astype(np.int32)
    ph = rng.integers(0, cfg.vocab, size=5).astype(np.int32)
    inj = FaultInjector().inject("block_alloc", rid="grow",
                                 error="alloc died")
    eng = _engine(gen, params, faults=inj, clock=_Tick())
    # "grow" allocates blocks_for(7)=2 pages (8 rows) and must extend at
    # kv_len 8 -> the injected alloc failure quarantines it there.
    eng.submit(Request("grow", pg, SamplingParams(max_new_tokens=6)))
    eng.submit(Request("ok", ph, SamplingParams(max_new_tokens=6)))
    outs = eng.run()
    assert outs["grow"].finish_reason is FinishReason.ERROR
    assert "alloc died" in outs["grow"].error
    assert 1 <= len(outs["grow"].token_ids) < 6   # partial output kept
    assert outs["ok"].token_ids == _oracle(gen, params, ph, 6)
    assert eng.bm.num_free == eng.bm.num_allocatable


def test_post_dispatch_pool_loss_escalates_not_cascades(tiny):
    """The batched forwards donate the KV pools: a failure that already
    consumed them (a genuine mid-execution device error, unlike the
    injector's pre-dispatch seam faults) must ESCALATE out of step() —
    retrying or bisecting over deleted buffers would quarantine every
    healthy request while the engine kept reporting clean steps."""
    cfg, params, gen = tiny
    rng = np.random.default_rng(10)
    p = rng.integers(0, cfg.vocab, size=5).astype(np.int32)
    eng = _engine(gen, params, fault_retries=2, clock=_Tick())
    eng.submit(Request("v", p, SamplingParams(max_new_tokens=6)))
    eng.step()                             # prefill + first token

    real = eng._decode_fn

    def device_died(params_, pools, *a, **kw):
        for x in jax.tree_util.tree_leaves(pools):
            x.delete()                     # donation consumed the pools
        raise RuntimeError("device exploded mid-execution")

    eng._decode_fn = device_died
    with pytest.raises(RuntimeError, match="device exploded"):
        eng.run()
    # escalated on the FIRST failure: no retries burned, nobody
    # quarantined, the wedge is the caller's to handle
    assert eng.metrics.quarantined == 0
    assert eng.metrics.forward_retries == 0
    assert eng._states["v"].status is Status.RUNNING
    eng._decode_fn = real                  # (pools are gone regardless)


# ---------------------------------------------------------------------------
# fast tier: THE deterministic chaos drain (acceptance)
# ---------------------------------------------------------------------------


def test_deterministic_chaos_drain(tiny):
    """Fixed fault schedule over staggered traffic: the engine drains
    without crashing, faulted requests retire ERROR/SHED/DEADLINE with
    their blocks freed (free list back to full), accounting is exact,
    and every untouched request's stream is bit-identical to the
    fault-free twin run."""
    cfg, params, gen = tiny
    rng = np.random.default_rng(5)
    lens = {"c0": 5, "c1": 5, "c2": 6, "c3": 6, "c4": 5, "c5": 5}
    prompts = {r: rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for r, n in lens.items()}

    def drive(faults):
        eng = _engine(gen, params, max_batch=2, max_queue=3,
                      overload="shed", faults=faults, fault_retries=1,
                      clock=_Clock())

        def req(r, **kw):
            return Request(r, prompts[r],
                           SamplingParams(max_new_tokens=4, **kw),
                           on_token=((lambda rid, t: None)
                                     if r == "c2" else None))
        sheds = []
        for r in ("c0", "c1"):
            eng.submit(req(r))
        eng.step()                       # c0/c1 admitted, queue empty
        for r in ("c2", "c3", "c4", "c5"):
            kw = {"deadline_s": 5.0} if r == "c4" else {}
            out = eng.submit(req(r, **kw))
            if out is not None:
                sheds.append(out.request_id)
        outs = eng.run(max_steps=500)
        return eng, outs, sheds

    inj = FaultInjector(seed=11)
    inj.inject("forward", rid="c1", op="paged_decode", error="poison row")
    inj.inject("callback", rid="c2", error="frontend bug")
    inj.inject("block_alloc", rid="c3", error="alloc fault")
    inj.inject("clock", at_call=15, skew_s=1000.0)   # expires c4's TTL
    eng, outs, sheds = drive(inj)
    _, clean, clean_sheds = drive(None)

    # the queue bound fires identically with or without faults: c5
    # arrives at depth 3 >= max_queue both times
    assert sheds == clean_sheds == ["c5"]
    want = {"c0": FinishReason.LENGTH, "c1": FinishReason.ERROR,
            "c2": FinishReason.LENGTH, "c3": FinishReason.ERROR,
            "c4": FinishReason.DEADLINE, "c5": FinishReason.SHED}
    assert {r: o.finish_reason for r, o in outs.items()} == want
    assert "poison row" in outs["c1"].error
    assert "alloc fault" in outs["c3"].error
    # untouched streams bit-identical to the fault-free twin (c2's
    # callback fault must not perturb its tokens either)
    for r in ("c0", "c2"):
        assert outs[r].token_ids == clean[r].token_ids
        assert outs[r].token_ids == _oracle(gen, params, prompts[r], 4)
    # partial streams of the faulted rows are prefixes of their oracles
    for r in ("c1", "c3"):
        assert outs[r].token_ids == _oracle(
            gen, params, prompts[r], 4)[:len(outs[r].token_ids)]
    # the pool comes back whole; no slot is leaked
    assert eng.bm.num_free == eng.bm.num_allocatable
    assert all(s is None for s in eng.slots)
    assert not eng.has_work()
    # exact failure accounting on the metrics path
    f = eng.metrics.summary()["failures"]
    assert f["shed"] == 1
    assert f["deadline_expired"] == 1
    assert f["quarantined"] == 2
    assert f["callback_errors"] == 1
    assert f["forward_bisections"] >= 1
    assert f["finish_reasons"] == {"length": 2, "error": 2,
                                   "deadline": 1, "shed": 1}
    assert inj.fire_count() >= 4         # every armed fault class fired


# ---------------------------------------------------------------------------
# fast tier: watchdog-guarded steps + heartbeat
# ---------------------------------------------------------------------------


def test_injected_stall_trips_watchdog_and_heartbeat(tiny, tmp_path):
    """A forward stalled via the injector must trip the step watchdog
    within the budget instead of hanging run() forever — and the
    heartbeat file (driven synchronously by the step loop) goes stale so
    Heartbeat.is_stalled sees the wedge."""
    cfg, params, gen = tiny
    rng = np.random.default_rng(6)
    p = rng.integers(0, cfg.vocab, size=5).astype(np.int32)
    hb = tmp_path / "hb"
    inj = FaultInjector().inject("forward", op="paged_decode",
                                 stall_s=2.0, max_fires=1)
    eng = _engine(gen, params, faults=inj, step_timeout_s=0.3,
                  heartbeat=str(hb), heartbeat_interval_s=0.05)
    eng.submit(Request("w", p, SamplingParams(max_new_tokens=4)))
    t0 = time.perf_counter()
    with pytest.raises(WatchdogTimeout, match="paged_decode"):
        eng.run()
    assert time.perf_counter() - t0 < 1.9   # budget, not the full stall
    assert eng.metrics.watchdog_trips == 1
    # beats stopped with the wedge: the file exists but is already stale
    # at the supervisor's cadence
    assert Heartbeat.age_s(hb) is not None
    time.sleep(0.2)
    assert Heartbeat.is_stalled(hb, interval_s=0.05)


def test_watchdogged_engine_serves_normally(tiny, tmp_path):
    """The watchdog + heartbeat guards are pure overhead-free pass-
    throughs on the healthy path: same streams, fresh beats."""
    cfg, params, gen = tiny
    rng = np.random.default_rng(7)
    p = rng.integers(0, cfg.vocab, size=6).astype(np.int32)
    hb = tmp_path / "hb_ok"
    eng = _engine(gen, params, step_timeout_s=30.0, heartbeat=str(hb),
                  heartbeat_interval_s=1.0)
    eng.submit(Request("n", p, SamplingParams(max_new_tokens=4)))
    outs = eng.run()
    assert outs["n"].token_ids == _oracle(gen, params, p, 4)
    assert eng.metrics.watchdog_trips == 0
    assert not Heartbeat.is_stalled(hb, interval_s=1.0)


# ---------------------------------------------------------------------------
# slow tier: speculative bailout + the randomized chaos soak
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("where", ["dispatch", "drain"])
def test_spec_round_bailout_stays_exact(tiny, where):
    """A speculative chain that fails MID-chain — its second round's
    dispatch dies, or the fetch of its second round's burst does, after
    the first round's tokens were committed — latches speculation off
    and degrades to plain decode: the proven tokens stand (a committed
    row re-opens its last token as pending), and every stream stays
    bit-identical to the oracle."""
    cfg, params, gen = tiny
    dcfg = llama.LlamaConfig(vocab=cfg.vocab, dim=16, n_layers=1,
                             n_heads=1, n_kv_heads=1, ffn_dim=32,
                             max_seq=64, dtype=jnp.float32)
    d_params = llama.init_params(dcfg, jax.random.key(9))
    draft = Generator(dcfg, gen.mesh, axis="sp", max_seq=64)
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (5, 8)]
    n_new = 12
    eng = _engine(gen, params, page_size=8, prefill_chunk=8, draft=draft,
                  draft_params=d_params, spec_k=2, pipeline=2,
                  clock=_Tick())
    for i, p in enumerate(prompts):
        eng.submit(Request(f"s{i}", p, SamplingParams(max_new_tokens=n_new)))
    seen = {"n": 0}

    def second(hit: bool) -> bool:
        seen["n"] += hit
        return hit and seen["n"] == 2

    real_call, real_get = eng._device_call, jax.device_get

    def call(op, *args, **kw):
        if where == "dispatch" and second(op == "spec_round"):
            raise RuntimeError("round 2 died")
        return real_call(op, *args, **kw)

    def get(x):
        # the 3-tuple fetch is unique to the spec drain
        if where == "drain" and second(isinstance(x, tuple)
                                       and len(x) == 3):
            raise RuntimeError("burst 2 died")
        return real_get(x)

    eng._device_call, jax.device_get = call, get
    try:
        outs = eng.run()
    finally:
        jax.device_get = real_get
    assert eng.metrics.spec_bailouts == 1 and eng._spec_off
    # the drain case committed round 1's burst before it bailed out
    assert eng.metrics.spec_rounds == (1 if where == "drain" else 0)
    for i, p in enumerate(prompts):
        assert outs[f"s{i}"].token_ids == _oracle(gen, params, p, n_new), i
    assert eng.bm.num_free == eng.bm.num_allocatable
    assert all(s is None for s in eng.slots)


@pytest.mark.slow
def test_randomized_chaos_soak_reproducible(tiny):
    """Seeded random faults across every point: the engine always
    drains with a whole pool and an output per request, and the same
    seed reproduces the same outcomes bit-for-bit."""
    cfg, params, gen = tiny
    rng = np.random.default_rng(9)
    lens = [3, 5, 7, 9, 11, 4, 6, 8, 10, 12]
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in lens]

    def soak(seed):
        inj = (FaultInjector(seed=seed)
               .inject("forward", rate=0.04, error="transient")
               .inject("callback", rate=0.15, error="flaky ui")
               .inject("block_alloc", rate=0.05, error="alloc blip"))
        eng = _engine(gen, params, max_batch=3, max_queue=4,
                      faults=inj, fault_retries=1, clock=_Tick())
        outs = {}
        submitted = step = 0
        while eng.has_work() or submitted < len(prompts):
            if step % 2 == 0 and submitted < len(prompts):
                kw = ({"deadline_s": 40.0} if submitted % 4 == 3 else {})
                shed = eng.submit(Request(
                    f"r{submitted}", prompts[submitted],
                    SamplingParams(max_new_tokens=5, **kw),
                    on_token=(lambda rid, t: None)))
                if shed is not None:
                    outs[shed.request_id] = shed
                submitted += 1
            for o in eng.step():
                outs[o.request_id] = o
            step += 1
            assert step < 2000
        assert eng.bm.num_free == eng.bm.num_allocatable
        assert all(s is None for s in eng.slots)
        return {r: (o.finish_reason.value, tuple(o.token_ids))
                for r, o in outs.items()}

    a = soak(21)
    assert sorted(a) == [f"r{i}" for i in range(len(prompts))]
    assert a == soak(21)                 # same seed -> same story
    reasons = {v[0] for v in a.values()}
    assert reasons <= {"length", "error", "shed", "deadline"}
