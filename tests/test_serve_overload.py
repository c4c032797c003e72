"""Overload-robust serving (ISSUE 18): SLO classes, the graceful-
degradation brownout ladder, token-bucket ingress admission, and
pressure-driven autoscaling (docs/serving.md "Overload, SLO classes &
autoscaling").

Fast tier (the whole file): the defaults-inert oracle (class_aware /
brownout off or idle leave every stream bit-identical), class-aware
admission + door displacement, the brownout ladder walk (white-box rung
semantics and black-box climb-under-pressure), best_effort output caps,
the seeded trace-shaped workload generator, token-bucket ingress with
downward borrowing, the autoscaler's spawn / exactly-once-drain-retire
cycle with journal receipts, the chaos kill during scale-up (zero
admitted-interactive loss, no slot double-adoption), the
shed-always-lands-a-terminal regression, and the shed-paths-observable
lint rule."""

import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from triton_dist_tpu.models import llama
from triton_dist_tpu.models.generate import Generator
from triton_dist_tpu.serve import Request, SamplingParams, ServeEngine
from triton_dist_tpu.serve.fleet import FleetController
from triton_dist_tpu.serve.recovery import JOURNAL_NAME, replay_journal
from triton_dist_tpu.serve.request import (
    SLO_CLASSES,
    FinishReason,
    slo_rank,
)
from triton_dist_tpu.serve.scheduler import Status


class _Clock:
    """Manually-advanced clock shared by engines and the controller."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


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


def _prompts(cfg, n, lens=None, seed=0):
    rng = np.random.default_rng(seed)
    lens = lens or [6] * n
    return [rng.integers(0, cfg.vocab, size=lens[i]).astype(np.int32)
            for i in range(n)]


# ---------------------------------------------------------------------------
# SLO classes: the type layer
# ---------------------------------------------------------------------------


def test_slo_classes_rank_and_validation(tiny):
    cfg, params, gen = tiny
    assert SLO_CLASSES == ("interactive", "batch", "best_effort")
    assert [slo_rank(c) for c in SLO_CLASSES] == [0, 1, 2]
    (p,) = _prompts(cfg, 1)
    r = Request("a", p, SamplingParams(max_new_tokens=2))
    assert r.slo_class == "interactive"          # default: old behavior
    with pytest.raises(ValueError, match="slo_class"):
        Request("b", p, SamplingParams(max_new_tokens=2),
                slo_class="premium")
    # the wire dict stays exactly the pre-change 7 keys: slo_class rides
    # in a separate "slo" field everywhere it is serialized
    assert len(SamplingParams(max_new_tokens=2).to_dict()) == 7


# ---------------------------------------------------------------------------
# the tentpole inertness oracle: defaults stay bit-identical
# ---------------------------------------------------------------------------


def test_defaults_bit_identical_streams(tiny):
    """class_aware=True with single-class traffic and an armed-but-idle
    brownout ladder must serve every stream BIT-IDENTICAL to the
    default engine (and the default engine to the Generator oracle):
    the overload machinery is provably inert until it triggers."""
    cfg, params, gen = tiny
    ps = _prompts(cfg, 3, lens=[6, 5, 7])
    reqs = [("g0", ps[0], SamplingParams(max_new_tokens=6)),
            ("g1", ps[1], SamplingParams(max_new_tokens=5)),
            ("s0", ps[2], SamplingParams(max_new_tokens=6,
                                         temperature=0.8, seed=11))]

    def run(**kw):
        eng = _engine(gen, params, **kw)
        for rid, p, sp in reqs:
            assert eng.submit(Request(rid, p, sp)) is None
        outs = eng.run()
        return {rid: list(outs[rid].token_ids) for rid, _, _ in reqs}, eng

    base, eng0 = run()
    aware, _ = run(class_aware=True)
    armed, eng2 = run(class_aware=True,
                      brownout=dict(high=0.99, low=0.98))
    assert base == aware == armed
    assert base["g0"] == _oracle(gen, params, ps[0], 6)
    # inert means inert: with brownout=None the pressure EMA is never
    # even evaluated, and the armed-but-quiet ladder never left rung 0
    assert eng0._pressure_t is None
    assert eng2.brownout_rung == 0
    assert eng2.metrics.slo_stats()["brownout_transitions"] == 0


# ---------------------------------------------------------------------------
# class-aware scheduling: admission order + door displacement
# ---------------------------------------------------------------------------


def test_class_aware_admission_order(tiny):
    cfg, params, gen = tiny
    ps = _prompts(cfg, 3)
    sp = SamplingParams(max_new_tokens=4)

    def first_admitted(class_aware):
        eng = _engine(gen, params, max_batch=2,
                      class_aware=class_aware)
        eng.submit(Request("be", ps[0], sp, slo_class="best_effort"))
        eng.submit(Request("b", ps[1], sp, slo_class="batch"))
        eng.submit(Request("i", ps[2], sp, slo_class="interactive"))
        eng.step()
        return {rid for rid, rs in eng._states.items()
                if rs.status is not Status.WAITING}

    # class-aware: the later-arriving interactive + batch go first;
    # default: plain FCFS order is untouched
    assert first_admitted(True) == {"i", "b"}
    assert first_admitted(False) == {"be", "b"}


def test_door_displacement_sheds_lowest_class(tiny):
    cfg, params, gen = tiny
    ps = _prompts(cfg, 4)
    sp = SamplingParams(max_new_tokens=3)
    eng = _engine(gen, params, max_batch=1, max_queue=1,
                  class_aware=True)
    eng.submit(Request("run", ps[0], sp))
    eng.step()                                  # "run" occupies the slot
    assert eng.submit(Request("be", ps[1], sp,
                              slo_class="best_effort")) is None
    # queue at bound; an interactive arrival displaces the waiting
    # best_effort instead of being refused
    assert eng.submit(Request("i", ps[2], sp)) is None
    assert eng._states["i"].status is Status.WAITING
    # the victim's terminal output joins the NEXT step's finished batch
    # (a polling controller finalizes its stream exactly once)
    outs = {o.request_id: o for o in eng.step()}
    assert outs["be"].finish_reason is FinishReason.SHED
    assert "displaced by i" in outs["be"].error
    assert eng.metrics.slo_stats()["shed"] == {"best_effort": 1}
    # all-interactive queue: a best_effort arrival has no victim below
    # it and sheds itself, with its own receipt
    out = eng.submit(Request("be2", ps[3], sp,
                             slo_class="best_effort"))
    assert out is not None and out.finish_reason is FinishReason.SHED
    assert eng.metrics.slo_stats()["shed"] == {"best_effort": 2}
    eng.run()


# ---------------------------------------------------------------------------
# the brownout ladder
# ---------------------------------------------------------------------------


def test_brownout_rung_semantics_white_box(tiny):
    """Each rung's effect, pinned: prefill budget halves at 2, door
    sheds walk best_effort -> batch -> interactive at 4/5/6, every
    transition lands a trace event and moves the counters, and descent
    restores full service."""
    cfg, params, gen = tiny
    ps = _prompts(cfg, 8)
    sp = SamplingParams(max_new_tokens=2)
    eng = _engine(gen, params, max_batch=2,
                  class_aware=True, brownout=dict(high=0.9, low=0.2))
    base_budget = eng.scheduler.prefill_budget

    eng._set_brownout(2)
    assert eng.scheduler.prefill_budget == max(
        eng.scheduler.prefill_chunk, base_budget // 2)
    # the halving fills prefill calls less and compiles nothing: a call's
    # rows, and the budget a snapshot hands the restored engine, are the
    # ones the engine was built with
    from triton_dist_tpu.serve import recovery
    from triton_dist_tpu.serve.engine import prefill_width

    assert eng.prefill_width == prefill_width(
        eng.scheduler.prefill_chunk, base_budget)
    assert recovery._capture_meta(eng, 0.0, journal_here=False)[
        "engine"]["prefill_budget"] == base_budget

    eng._set_brownout(4)
    out = eng.submit(Request("be", ps[0], sp, slo_class="best_effort"))
    assert out.finish_reason is FinishReason.SHED
    assert "brownout rung 4" in out.error
    assert eng.submit(Request("b1", ps[1], sp,
                              slo_class="batch")) is None
    assert eng.submit(Request("i1", ps[2], sp)) is None

    eng._set_brownout(5)
    assert eng.submit(Request("b2", ps[3], sp, slo_class="batch")
                      ).finish_reason is FinishReason.SHED
    assert eng.submit(Request("i2", ps[4], sp)) is None

    eng._set_brownout(6)
    assert eng.submit(Request("i3", ps[5], sp)
                      ).finish_reason is FinishReason.SHED

    eng._set_brownout(0)
    assert eng.scheduler.prefill_budget == base_budget
    assert eng.submit(Request("be2", ps[6], sp,
                              slo_class="best_effort")) is None
    slo = eng.metrics.slo_stats()
    assert slo["shed"] == {"best_effort": 1, "batch": 1,
                           "interactive": 1}
    assert slo["brownout_rung_peak"] == 6
    # 2 -> 4 -> 5 -> 6 -> 0 is five observable transitions
    assert slo["brownout_transitions"] == 5
    rungs = [d["rung"] for _, _, et, _, d in eng.trace.events()
             if et == "brownout"]
    assert rungs == [2, 4, 5, 6, 0]
    prom = eng.metrics.to_prometheus()
    assert "serve_brownout_rung 0" in prom
    assert 'serve_class_shed_total{slo_class="batch"} 1' in prom
    eng.run()


def test_brownout_climbs_and_recovers_under_pressure(tiny):
    """Black-box ladder walk: a sustained backlog (pressure = queue
    depth over 4*max_batch, no bound set) climbs the rung through the
    dwell hysteresis, a best_effort arriving at rung >= 4 is refused at
    the door, draining descends back to rung 0 and re-admits, and every
    submitted request still lands exactly one healthy terminal."""
    cfg, params, gen = tiny
    clock = _Clock()
    ps = _prompts(cfg, 12, lens=[5] * 12)
    sp = SamplingParams(max_new_tokens=8)
    eng = _engine(gen, params, max_batch=1, class_aware=True,
                  clock=clock,
                  brownout=dict(high=0.6, low=0.3, window_s=0.0,
                                dwell_steps=2))
    for i in range(10):
        assert eng.submit(Request(f"r{i}", ps[i], sp)) is None
    late = None
    for _ in range(200):
        if not eng.has_work():
            break
        eng.step()
        clock.advance(0.1)
        if late is None and eng.brownout_rung >= 4:
            late = eng.submit(Request("late_be", ps[10], sp,
                                      slo_class="best_effort"))
    assert eng.metrics.slo_stats()["brownout_rung_peak"] >= 4
    assert late is not None
    assert late.finish_reason is FinishReason.SHED
    # idle pressure decays the EMA below low: full service restored
    for _ in range(40):
        if eng.brownout_rung == 0:
            break
        eng.step()
        clock.advance(0.1)
    assert eng.brownout_rung == 0
    assert eng.submit(Request("late_be2", ps[11], sp,
                              slo_class="best_effort")) is None
    outs = eng.run()
    for i in range(10):
        assert outs[f"r{i}"].finish_reason in (FinishReason.EOS,
                                               FinishReason.LENGTH)
    assert outs["late_be2"].finish_reason is not FinishReason.SHED


def test_brownout_caps_best_effort_output(tiny):
    """Rung 3: best_effort emission caps at best_effort_cap — live rows
    keep >= 1 token of headroom and retire through a normal LENGTH
    commit; interactive rows are untouched; a cap released before the
    request finishes restores its full budget."""
    cfg, params, gen = tiny
    ps = _prompts(cfg, 3)
    eng = _engine(gen, params, max_batch=2, class_aware=True,
                  brownout=dict(high=0.9, low=0.2, best_effort_cap=2))
    eng.submit(Request("be", ps[0], SamplingParams(max_new_tokens=8),
                       slo_class="best_effort"))
    eng.submit(Request("i", ps[1], SamplingParams(max_new_tokens=8)))
    eng._set_brownout(3)
    # door cap: a best_effort ADMITTED during rung 3 is capped too
    eng.submit(Request("be2", ps[2], SamplingParams(max_new_tokens=8),
                       slo_class="best_effort"))
    outs = eng.run()
    assert outs["be"].finish_reason is FinishReason.LENGTH
    assert len(outs["be"].token_ids) <= 2
    assert len(outs["be2"].token_ids) <= 2
    assert len(outs["i"].token_ids) == 8          # interactive untouched
    assert outs["i"].token_ids == _oracle(gen, params, ps[1], 8)


# ---------------------------------------------------------------------------
# fleet: token-bucket ingress with downward borrowing
# ---------------------------------------------------------------------------


def _fleet(gen, params, root, clock, *, n=1, **kw):
    kw.setdefault("suspect_after_s", 1e6)
    kw.setdefault("dead_after_s", 2e6)
    kw.setdefault("backoff_base_s", 0.01)
    kw.setdefault("backoff_cap_s", 0.1)
    engine_kw = kw.pop("engine_kw", {})

    def factory(d):
        return _engine(gen, params, snapshot_dir=d, clock=clock,
                       **engine_kw)

    return FleetController(factory, n, root=str(root), clock=clock,
                           seed=0, **kw)


def test_ingress_token_bucket_borrows_downward_only(tiny, tmp_path):
    cfg, params, gen = tiny
    clock = _Clock()
    fc = _fleet(gen, params, tmp_path / "fleet", clock,
                ingress={"rate": 0.001, "burst": 1.0,
                         "per_class": {"interactive": {"burst": 2.0}}})
    ps = _prompts(cfg, 7)
    sp = SamplingParams(max_new_tokens=2)
    finals = {}
    # buckets at t=0: interactive 2, batch 1, best_effort 1 (rate is
    # negligible, so no refill during the test)
    for i in range(5):
        fc.submit(Request(f"i{i}", ps[i], sp,
                          on_finish=lambda o: finals.setdefault(
                              o.request_id, o)))
    # i0/i1 spend interactive's own budget, i2/i3 borrow batch then
    # best_effort downward, i4 finds every bucket empty
    assert fc.ingress_shed_by_class == {"interactive": 1}
    assert finals["i4"].finish_reason is FinishReason.SHED
    assert "ingress token bucket" in finals["i4"].error
    # a LOWER class never borrows upward: interactive still has no
    # tokens but best_effort's were spent by the borrow — shed, even
    # though nothing ever refused batch's own arrivals before this
    fc.submit(Request("be", ps[5], sp, slo_class="best_effort",
                      on_finish=lambda o: finals.setdefault(
                          o.request_id, o)))
    assert finals["be"].finish_reason is FinishReason.SHED
    assert fc.ingress_shed_by_class == {"interactive": 1,
                                        "best_effort": 1}
    # refill is clock-driven: an hour later a token is back
    clock.advance(3600.0)
    fc.submit(Request("late", ps[6], sp, slo_class="best_effort"))
    assert "late" not in {o.request_id for o in finals.values()}
    while fc.has_work():
        fc.step()
    # every shed landed a terminal + the per-class counters; admitted
    # requests all finished
    assert sorted(fc.outputs) == ["be", "i0", "i1", "i2", "i3", "i4",
                                  "late"]
    shed = fc.aggregate_metrics().slo_stats()["shed"]
    assert shed == {"interactive": 1, "best_effort": 1}
    # the decision audit answers "why was this shed"
    kinds = [e["kind"] for e in fc.explain("i4")]
    assert "ingress_shed" in kinds
    prom = fc.to_prometheus()
    assert 'fleet_ingress_shed_total{slo_class="interactive"} 1' in prom
    assert 'fleet_ingress_shed_total{slo_class="batch"} 0' in prom


# ---------------------------------------------------------------------------
# fleet: pressure-driven autoscaling
# ---------------------------------------------------------------------------


def test_autoscaler_spawns_and_retires_with_receipts(tiny, tmp_path):
    """Sustained pressure spawns r1 from the factory; the drained-out
    low-water retire walks the exactly-once path — every request the
    leaver owned shows a ``mig`` receipt or a finish record in its
    journal, streams stay bit-exact, and the name is never reused."""
    cfg, params, gen = tiny
    clock = _Clock()
    fc = _fleet(gen, params, tmp_path / "fleet", clock,
                engine_kw=dict(max_batch=1),
                autoscale={"min": 1, "max": 2, "high": 0.5, "low": 0.1,
                           "window_s": 0.0, "dwell_steps": 2})
    ps = _prompts(cfg, 8)
    sp = SamplingParams(max_new_tokens=4)
    oracle = {f"r{i}": _oracle(gen, params, ps[i], 4) for i in range(8)}
    for i in range(8):
        fc.submit(Request(f"r{i}", ps[i], sp))
    steps = 0
    while fc.has_work():
        fc.step()
        clock.advance(0.05)
        steps += 1
        assert steps < 2000
    assert fc.scale_ups >= 1 and "r1" in fc.replicas
    # drain to idle: the low-water retire fires within a few idle ticks
    for _ in range(20):
        if fc.scale_downs:
            break
        fc.step()
        clock.advance(0.05)
    assert fc.scale_downs >= 1 and fc.retired
    retired = next(iter(fc.retired))
    rep = fc.replicas[retired]
    assert rep.engine is None and rep.restart_at is None
    assert rep.death_reason == "retired (scaled down)"
    # zero loss, exactly once: every stream bit-exact, no dangling rid
    for rid, want in oracle.items():
        assert list(fc.outputs[rid].token_ids) == want
        assert list(fc.streams[rid]) == want
    # journal receipts on the retired life: anything it owned either
    # finished there or carries the mig ownership-transfer mark
    owned = fin = mig = 0
    for jp in glob.glob(str(tmp_path / "fleet" / retired / "life*"
                            / JOURNAL_NAME)):
        for rid, jr in replay_journal(jp).items():
            owned += 1
            assert jr.migrated or jr.finished, (
                f"{rid} left dangling on retired {retired}")
            fin += bool(jr.finished)
            mig += bool(jr.migrated)
    assert owned == fin + mig
    # scale decisions are audited + traced with the pressure they saw
    acts = [(e["action"], e["replica"]) for e in fc.audit.entries()
            if e["kind"] == "scale"]
    assert ("up", "r1") in acts
    assert ("down", retired) in acts
    ups = [d for _, _, et, _, d in fc.trace.events() if et == "scale"
           and d["action"] == "up"]
    assert ups and all(d["pressure"] >= 0.5 for d in ups)
    # monotonic naming: a later spawn could never re-adopt the name
    assert fc._next_index == 2
    prom = fc.to_prometheus()
    assert "fleet_scale_ups_total" in prom
    assert "fleet_pressure_smoothed" in prom
    s = fc.fleet_summary()
    assert s["scale"]["ups"] == fc.scale_ups
    assert retired in s["scale"]["retired"]


def test_chaos_kill_during_scale_up(tiny, tmp_path):
    """SIGKILL (in-process stand-in) of the original replica RIGHT as
    the autoscaler brings a new one up, mid-burst: every admitted
    interactive request finishes bit-exact with exactly-once terminals,
    and the scaler never double-adopts the dead replica's slot (names
    stay monotonic; the new replica is not the dead one's)."""
    cfg, params, gen = tiny
    clock = _Clock()
    fc = _fleet(gen, params, tmp_path / "fleet", clock,
                engine_kw=dict(max_batch=1),
                autoscale={"min": 1, "max": 3, "high": 0.5, "low": 0.05,
                           "window_s": 0.0, "dwell_steps": 2})
    lens = [5, 6, 4, 5, 6, 4, 5, 6]
    ps = _prompts(cfg, 8, lens=lens)
    slos = ["interactive", "best_effort"] * 4
    sp = SamplingParams(max_new_tokens=4)
    oracle = {f"r{i}": _oracle(gen, params, ps[i], 4) for i in range(8)}
    finals = {}
    for i in range(8):
        fc.submit(Request(f"r{i}", ps[i], sp, slo_class=slos[i],
                          on_finish=lambda o: finals.setdefault(
                              o.request_id, []).append(o)))
    killed = False
    steps = 0
    while fc.has_work():
        fc.step()
        clock.advance(0.05)
        steps += 1
        assert steps < 4000
        if fc.scale_ups >= 1 and not killed:
            fc.kill_replica("r0", "chaos: killed during scale-up")
            killed = True
    assert killed and fc.deaths >= 1
    # zero admitted-interactive loss: nothing was shed (no ingress, no
    # max_queue), so EVERY stream must be bit-exact — including the
    # killed replica's crash-migrated rows
    for rid, want in oracle.items():
        assert list(fc.outputs[rid].token_ids) == want, rid
        assert list(fc.streams[rid]) == want, rid
    # exactly-once terminal per request, no dangling callback
    assert sorted(finals) == sorted(oracle)
    assert all(len(v) == 1 for v in finals.values())
    # no double-adoption: scale-ups only ever minted fresh names, and
    # r0's crash migration did not race a new life onto its slot
    spawned = {d["replica"] for _, _, et, _, d in fc.trace.events()
               if et == "scale" and d["action"] == "up"}
    assert "r0" not in spawned
    assert len(fc.replicas) == 1 + fc.scale_ups
    assert fc._next_index == 1 + fc.scale_ups


# ---------------------------------------------------------------------------
# regression: every shed path lands a terminal + a counter
# ---------------------------------------------------------------------------


def test_all_shed_paths_land_terminals(tiny, tmp_path):
    """The audit that motivated the bugfix satellite: engine door shed,
    fleet-wide full shed, and the fleet-queue deadline sweep each land
    exactly one terminal callback and bump the per-class counter — no
    shed request ever leaves its stream dangling."""
    cfg, params, gen = tiny
    ps = _prompts(cfg, 6)

    # engine door shed fires on_finish + counters on a bare engine
    # (max_queue=1: "run" decodes in the slot, "w" holds the one queue
    # seat, "s" arrives at the bound)
    eng = _engine(gen, params, max_batch=1, max_queue=1)
    hits = []
    eng.submit(Request("run", ps[0], SamplingParams(max_new_tokens=8)))
    eng.step()
    eng.submit(Request("w", ps[2], SamplingParams(max_new_tokens=2)))
    out = eng.submit(Request("s", ps[1],
                             SamplingParams(max_new_tokens=2),
                             slo_class="batch",
                             on_finish=lambda o: hits.append(o)))
    assert out.finish_reason is FinishReason.SHED
    assert [o.request_id for o in hits] == ["s"]
    assert eng.metrics.slo_stats()["shed"] == {"batch": 1}
    eng.run()

    # fleet-wide full: every replica at its bound -> _shed lands the
    # terminal, the carry counters, and the audit record
    clock = _Clock()
    fc = _fleet(gen, params, tmp_path / "f1", clock,
                engine_kw=dict(max_batch=1, max_queue=0))
    finals = {}
    fc.submit(Request("b", ps[3], SamplingParams(max_new_tokens=2),
                      slo_class="best_effort",
                      on_finish=lambda o: finals.setdefault(
                          o.request_id, o)))
    assert finals["b"].finish_reason is FinishReason.SHED
    assert list(fc.streams["b"]) == []
    assert "b" in fc.outputs
    assert (fc.aggregate_metrics().slo_stats()["shed"]
            == {"best_effort": 1})
    assert "shed" in [e["kind"] for e in fc.explain("b")]

    # fleet-queue deadline sweep: no healthy replica, the TTL passes in
    # the fleet queue -> DEADLINE terminal + per-class counter
    clock2 = _Clock()
    fc2 = _fleet(gen, params, tmp_path / "f2", clock2,
                 backoff_base_s=1e5, backoff_cap_s=1e6)
    fc2.kill_replica("r0", "test")
    fc2.submit(Request("d", ps[4],
                       SamplingParams(max_new_tokens=2, deadline_s=0.5),
                       slo_class="batch",
                       on_finish=lambda o: finals.setdefault(
                           o.request_id, o)))
    clock2.advance(1.0)
    fc2.step()
    assert finals["d"].finish_reason is FinishReason.DEADLINE
    assert "d" in fc2.outputs
    agg = fc2.aggregate_metrics()
    assert agg.slo_stats()["deadline_expired"] == {"batch": 1}
    assert agg.deadline_expired == 1


def test_finish_callback_contained_and_exactly_once(tiny, tmp_path):
    """A throwing on_finish is contained (counted, not fatal) and still
    consumed exactly once — fleet-level terminals cannot re-fire."""
    cfg, params, gen = tiny
    clock = _Clock()
    fc = _fleet(gen, params, tmp_path / "fleet", clock)
    (p,) = _prompts(cfg, 1)
    calls = []

    def bad(out):
        calls.append(out.request_id)
        raise RuntimeError("boom")

    fc.submit(Request("a", p, SamplingParams(max_new_tokens=2),
                      on_finish=bad))
    while fc.has_work():
        fc.step()
    assert calls == ["a"]
    assert fc._carry.callback_errors == 1
    assert list(fc.outputs["a"].token_ids) == _oracle(gen, params, p, 2)


# ---------------------------------------------------------------------------
# lint: shed paths must be observable
# ---------------------------------------------------------------------------


def test_shed_paths_observable_rule_clean():
    from triton_dist_tpu.analysis.rules import RULES, run_rule

    assert "shed-paths-observable" in RULES
    assert run_rule("shed-paths-observable") == []
