"""One-dispatch speculative decoding (serve/engine.py, docs/serving.md
"Speculative decoding"): the whole draft-propose / verify / accept /
closing-decode round fused into ONE traced program, chained on a
device-resident carry, with adaptive per-row k.

Fast tier: the scheduler's spec planning policy + adaptive-k chooser;
THE spec oracle (greedy streams through the fused round bit-identical
to per-request ``Generator.generate``; seeded-sampled streams bit-identical to the draft-less engine and
reproducible); dispatch economics (spec tokens/dispatch >= plain fused
decode at H=8, <= 0.15 dispatches/token); warmup sweeping the k-ladder
to a flat miss counter; adaptive-k convergence under a low-acceptance
draft; spec x prefix-cache (generated pages commit, warm admits skip the
DRAFT prefix too); spec x fault injection (bailout to plain decode with
bit-exact streams, then plain-path bisect/quarantine); spec engine
snapshot/restore (kill mid-stream sweep -> bit-exact resumed streams,
draft state resumed IN PLACE).
"""

import os
import shutil
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from triton_dist_tpu.models import llama
from triton_dist_tpu.models.generate import Generator
from triton_dist_tpu.runtime.faults import FaultInjector
from triton_dist_tpu.serve import (
    BlockManager,
    FCFSScheduler,
    Request,
    SamplingParams,
    ServeEngine,
)
from triton_dist_tpu.serve.request import FinishReason
from triton_dist_tpu.serve.scheduler import ReqState


# ---------------------------------------------------------------------------
# fast tier: planning policy + adaptive-k chooser (no jax compiles)
# ---------------------------------------------------------------------------


def test_plan_spec_policy():
    sched = FCFSScheduler(BlockManager(8, 4), prefill_budget=8,
                          prefill_chunk=4)
    kw = dict(prefilling=False, deadline_waiting=False)
    assert sched.plan_spec(2, **kw) == 2
    assert sched.plan_spec(1, **kw) == 1
    # the per-step contracts clamp chaining back to one round per step
    assert sched.plan_spec(2, prefilling=True,
                           deadline_waiting=False) == 1
    assert sched.plan_spec(2, prefilling=False,
                           deadline_waiting=True) == 1


def _rs_with_window(pairs):
    from triton_dist_tpu.serve.metrics import RequestMetrics

    rs = ReqState(req=Request("x", np.zeros((2,), np.int32)),
                  metrics=RequestMetrics(arrival_time=0.0))
    rs.spec_window = list(pairs)
    return rs


def test_choose_spec_k_policy():
    sched = FCFSScheduler(BlockManager(8, 4), prefill_budget=8,
                          prefill_chunk=4)
    # optimistic until the window holds >= one full round of evidence
    assert sched.choose_spec_k(_rs_with_window([]), 8) == 8
    assert sched.choose_spec_k(_rs_with_window([(4, 4)]), 8) == 8
    # perfect acceptance keeps full depth; zero collapses to 1
    assert sched.choose_spec_k(
        _rs_with_window([(8, 8), (8, 8)]), 8) == 8
    assert sched.choose_spec_k(
        _rs_with_window([(8, 0), (8, 0)]), 8) == 1
    # alpha = 0.5 with floor 0.25 -> k = 2; monotone in alpha
    assert sched.choose_spec_k(
        _rs_with_window([(8, 4), (8, 4)]), 8) == 2
    k_hi = sched.choose_spec_k(_rs_with_window([(10, 9)]* 2), 8)
    k_lo = sched.choose_spec_k(_rs_with_window([(10, 3)]* 2), 8)
    assert 1 <= k_lo < k_hi <= 8
    # the window bounds the evidence (older rounds age out)
    rs = _rs_with_window([(8, 0)] * 20 + [(8, 8)] * 4)
    assert sched.choose_spec_k(rs, 8, window=4) == 8
    assert sched.choose_spec_k(_rs_with_window([(4, 4)]), 1) == 1
    # review regression: a COLLAPSED row's window (k=1 rounds: fewer
    # than k_max proposals) must STAY collapsed — the old `prop <
    # k_max` bootstrap reset it to full depth every few rounds, and
    # one such row drags the whole batch's k-rung back up
    assert sched.choose_spec_k(
        _rs_with_window([(1, 0)] * 8), 12, window=8) == 1


def test_spec_params_validated():
    cfg, params, gen, dcfg, d_params, draft = _models()
    with pytest.raises(ValueError, match="spec_adaptive"):
        ServeEngine(gen, params, num_blocks=8, page_size=4, max_batch=1,
                    draft=draft, draft_params=d_params, spec_k=2,
                    spec_adaptive=-1)
    # sampled requests ride the seeded accept chain
    eng2 = ServeEngine(gen, params, num_blocks=16, page_size=4,
                       max_batch=1, draft=draft, draft_params=d_params,
                       spec_k=2)
    assert eng2.submit(Request("s", np.zeros((2,), np.int32),
                               SamplingParams(max_new_tokens=2,
                                              temperature=0.5,
                                              seed=1))) is None


# ---------------------------------------------------------------------------
# shared tiny models (1 layer: cheap enough for the tier-1 gate)
# ---------------------------------------------------------------------------


def _models():
    cfg = llama.LlamaConfig(vocab=64, dim=16, n_layers=1, n_heads=2,
                            n_kv_heads=1, ffn_dim=32, max_seq=64,
                            dtype=jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    params = llama.init_params(cfg, jax.random.key(3))
    gen = Generator(cfg, mesh, axis="sp", max_seq=64)
    dcfg = llama.LlamaConfig(vocab=64, dim=16, n_layers=1, n_heads=1,
                             n_kv_heads=1, ffn_dim=32, max_seq=64,
                             dtype=jnp.float32)
    d_params = llama.init_params(dcfg, jax.random.key(7))
    draft = Generator(dcfg, mesh, axis="sp", max_seq=64)
    return cfg, params, gen, dcfg, d_params, draft


class _Tick:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


def _oracle(gen, params, prompt, n_new):
    st = gen.prefill(params, jnp.asarray(np.asarray(prompt)[None]))
    toks, _ = gen.generate(params, st, n_new)
    return [int(t) for t in np.asarray(toks[0])]


def _drive(eng, reqs, stagger=2):
    submitted = step = 0
    outs = {}
    while eng.has_work() or submitted < len(reqs):
        if step % stagger == 0 and submitted < len(reqs):
            eng.submit(reqs[submitted])
            submitted += 1
        for o in eng.step():
            outs[o.request_id] = o
        step += 1
        assert step < 2000
    return outs


# ---------------------------------------------------------------------------
# fast tier: THE spec oracle — the fused round == Generator.generate
# ---------------------------------------------------------------------------


def test_spec_fused_greedy_oracle_exact():
    """Greedy streams through the fused one-dispatch round (pipelined
    chains, staggered admission interleaving prefill with live rounds)
    must be bit-identical to per-request Generator.generate — and a
    round must beat one-token-per-dispatch economics whenever the draft
    agrees at all."""
    cfg, params, gen, dcfg, d_params, draft = _models()
    rng = np.random.default_rng(7)
    lens = [5, 9, 3, 12]
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in lens]
    n_new = 11
    want = {f"r{i}": _oracle(gen, params, p, n_new)
            for i, p in enumerate(prompts)}
    reqs = lambda: [Request(f"r{i}", p,                     # noqa: E731
                            SamplingParams(max_new_tokens=n_new))
                    for i, p in enumerate(prompts)]

    for pipe in (2, 1):
        eng = ServeEngine(gen, params, num_blocks=40, page_size=4,
                          max_batch=3, prefill_chunk=4, draft=draft,
                          draft_params=d_params, spec_k=3, pipeline=pipe,
                          clock=_Tick())
        outs = _drive(eng, reqs())
        for rid, w in want.items():
            assert outs[rid].token_ids == w, (pipe, rid)
            assert outs[rid].finish_reason is FinishReason.LENGTH
        assert eng.bm.num_free == eng.bm.num_allocatable
        assert all(s is None for s in eng.slots)
        assert eng.metrics.spec_rounds >= 1
        assert eng.metrics.spec_dispatches >= 1


def test_spec_fused_sampled_matches_plain_engine_and_reproduces():
    """Seeded-sampled streams through the fused round must equal the
    DRAFT-LESS engine's token for token (the accept chain emits the
    target's own fold_in(key(seed), index) stream — docs/serving.md) and
    reproduce under the same seed; a greedy slot-mate stays oracle-exact
    in the same mixed batch.  A self-draft pins the coupled-draw claim:
    shared per-index randomness makes draft and target draws coincide,
    so acceptance is ~1 even for the sampled row."""
    cfg, params, gen, _, _, _ = _models()
    draft = Generator(cfg, gen.mesh, axis="sp", max_seq=64)  # self-draft
    rng = np.random.default_rng(8)
    pg = rng.integers(0, cfg.vocab, size=7).astype(np.int32)
    ps = rng.integers(0, cfg.vocab, size=5).astype(np.int32)
    reqs = lambda: [Request("g", pg,                        # noqa: E731
                            SamplingParams(max_new_tokens=9)),
                    Request("s", ps, SamplingParams(
                        max_new_tokens=9, temperature=0.8, top_k=16,
                        top_p=0.9, seed=2**31 + 11))]

    plain = ServeEngine(gen, params, num_blocks=40, page_size=4,
                        max_batch=2, prefill_chunk=4, clock=_Tick())
    for r in reqs():
        plain.submit(r)
    po = plain.run()

    def spec_run():
        eng = ServeEngine(gen, params, num_blocks=40, page_size=4,
                          max_batch=2, prefill_chunk=4, draft=draft,
                          draft_params=params, spec_k=4, pipeline=2,
                          clock=_Tick())
        for r in reqs():
            eng.submit(r)
        return eng, eng.run()

    eng, so = spec_run()
    _, so2 = spec_run()
    assert so["g"].token_ids == po["g"].token_ids == _oracle(
        gen, params, pg, 9)
    assert so["s"].token_ids == po["s"].token_ids    # spec == draft-less
    assert so["s"].token_ids == so2["s"].token_ids   # seeded reproducible
    sp = eng.metrics.spec_stats()
    assert sp["accept_rate"] > 0.8, sp  # coupled draws: self-draft agrees


@pytest.mark.parametrize("k", [2, 4, 8])
def test_spec_dispatch_economics_vs_plain_horizon(k):
    """The target as its own draft (acceptance ~1, so the counts isolate
    the fused round from draft quality): a round of depth ``k`` commits
    up to k + 1 tokens a row in ONE dispatch, so on the same requests it
    delivers at least the tokens a dispatch of the plain engine that
    fuses ``k`` decode steps.  At k = 8 that is the ISSUE-7 acceptance
    bar (spec >= plain fused decode at H=8) and the spec engine pays
    <= 0.15 dispatches/token.  Counters only, no clock."""
    cfg, params, gen, _, _, _ = _models()
    draft = Generator(cfg, gen.mesh, axis="sp", max_seq=64)  # self-draft
    rng = np.random.default_rng(9)
    n_new = 33
    prompts = [rng.integers(0, cfg.vocab, size=6).astype(np.int32)
               for _ in range(2)]

    def run(**kw):
        eng = ServeEngine(gen, params, num_blocks=40, page_size=4,
                          max_batch=2, prefill_chunk=4, pipeline=2,
                          clock=_Tick(), **kw)
        for i, p in enumerate(prompts):
            eng.submit(Request(f"d{i}", p,
                               SamplingParams(max_new_tokens=n_new)))
        outs = eng.run()
        assert all(len(o.token_ids) == n_new for o in outs.values())
        return eng.metrics.summary()

    s_spec = run(draft=draft, draft_params=params, spec_k=k)
    d_spec, d_plain = s_spec["decode"], run(horizon=k)["decode"]
    assert (d_spec["tokens_per_dispatch"]
            >= d_plain["tokens_per_dispatch"]), (d_spec, d_plain)
    sp = s_spec["spec"]
    assert sp["accept_rate"] > 0.8, sp
    assert sp["bailouts"] == 0 and set(sp["chosen_k"]) == {k}, sp
    assert sp["spec_tokens_per_dispatch"] >= k, sp
    if k == 8:
        assert d_spec["dispatches_per_token"] <= 0.15, d_spec


# ---------------------------------------------------------------------------
# fast tier: bounded compilation + adaptive k
# ---------------------------------------------------------------------------


def test_spec_warmup_flat_misses_across_k_ladder():
    """warmup() sweeps the fused-round k-ladder (greedy AND mixed
    variants per rung) — mixed-length, mixed-sampler spec traffic then
    never compiles, the fused round and draft-side prefix programs
    included."""
    cfg, params, gen, dcfg, d_params, draft = _models()
    eng = ServeEngine(gen, params, num_blocks=40, page_size=4,
                      max_batch=2, prefill_chunk=4, draft=draft,
                      draft_params=d_params, spec_k=2, pipeline=2,
                      clock=_Tick())
    w = eng.warmup()
    assert w["programs"] > 0
    spec_misses = eng._spec_fn.misses
    # one greedy + one mixed-sampler program per k-ladder rung
    assert spec_misses == 2 * len(eng._k_ladder), (
        eng._spec_fn.stats())
    flat = eng.metrics.compile_misses
    rng = np.random.default_rng(15)
    reqs = []
    for i, n in enumerate([3, 5, 9, 13, 17]):
        kw = (dict(temperature=0.7, top_p=0.9, seed=i) if i % 2 else {})
        reqs.append(Request(
            f"r{i}", rng.integers(0, cfg.vocab, size=n).astype(np.int32),
            SamplingParams(max_new_tokens=9, **kw)))
    outs = _drive(eng, reqs)
    assert len(outs) == len(reqs)
    assert eng.metrics.compile_misses == flat, (
        "spec serving compiled after warmup: "
        f"{eng.metrics.summary()['compilation']}")
    assert eng._spec_fn.misses == spec_misses


def test_spec_adaptive_k_converges_under_low_acceptance():
    """A draft the target disagrees with (independent random weights:
    acceptance ~0) must drive the adaptive per-row k down to 1 — the
    chosen-k histogram concentrates at the bottom rung, rounds stop
    burning k draft steps per emitted token — while every stream stays
    bit-identical to Generator.generate (acceptance never touches WHAT
    is emitted, only how much per dispatch)."""
    cfg, params, gen, dcfg, d_params, draft = _models()
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (5, 7)]
    n_new = 20
    eng = ServeEngine(gen, params, num_blocks=40, page_size=4,
                      max_batch=2, prefill_chunk=4, draft=draft,
                      draft_params=d_params, spec_k=4, spec_adaptive=4,
                      pipeline=1, clock=_Tick())
    for i, p in enumerate(prompts):
        eng.submit(Request(f"a{i}", p,
                           SamplingParams(max_new_tokens=n_new)))
    outs = eng.run()
    for i, p in enumerate(prompts):
        assert outs[f"a{i}"].token_ids == _oracle(gen, params, p, n_new)
    sp = eng.metrics.spec_stats()
    hist = sp["chosen_k"]
    assert sp["rolling_accept_rate"] < 0.3, sp
    # converged: the bottom rung dominates once the window fills
    assert hist.get(1, 0) > sum(v for k, v in hist.items() if k > 1), sp
    # the scheduler now picks k=1 for these rows' windows
    sched = eng.scheduler
    for rid in ("a0", "a1"):
        rs = eng._states[rid]
        assert sched.choose_spec_k(rs, 4, window=4) == 1, rs.spec_window


# ---------------------------------------------------------------------------
# fast tier: spec x prefix cache (target AND draft side)
# ---------------------------------------------------------------------------


def test_spec_prefix_cache_warm_admit_skips_draft_too():
    """Spec x prefix reuse: a warm admit maps the target's cached
    blocks AND skips the draft's prefill for the same prefix via the
    draft-side page cache (the ISSUE-7 fix: spec admission used to
    interact with the prefix cache only through the target).  Generated
    pages still commit under spec rounds, so a follow-up request over
    prompt + generated hits the cache for the whole history."""
    cfg, params, gen, dcfg, d_params, draft = _models()
    rng = np.random.default_rng(12)
    shared = rng.integers(0, cfg.vocab, size=17).astype(np.int32)
    n_new = 8

    eng = ServeEngine(gen, params, num_blocks=40, page_size=4,
                      max_batch=2, prefill_chunk=4, draft=draft,
                      draft_params=d_params, spec_k=3, clock=_Tick())
    eng.submit(Request("cold", shared, SamplingParams(max_new_tokens=n_new)))
    o_cold = eng.run()["cold"]
    assert o_cold.token_ids == _oracle(gen, params, shared, n_new)
    draft_chunks_cold = eng._draft_chunk_fn.hits + eng._draft_chunk_fn.misses
    assert eng.metrics.draft_prefix_skipped_tokens == 0

    # Warm admit: same prompt + a distinct suffix.  The target maps the
    # shared blocks; the draft skips the same chunk-floored prefix.
    suffix = rng.integers(0, cfg.vocab, size=3).astype(np.int32)
    warm_prompt = np.concatenate([shared, suffix])
    eng.submit(Request("warm", warm_prompt,
                       SamplingParams(max_new_tokens=n_new)))
    o_warm = eng.run()["warm"]
    assert o_warm.token_ids == _oracle(gen, params, warm_prompt, n_new)
    assert eng.metrics.prefix_hits >= 1
    assert eng.metrics.prefix_skipped_tokens > 0
    assert eng.metrics.draft_prefix_skipped_tokens > 0
    draft_chunks_warm = (eng._draft_chunk_fn.hits
                         + eng._draft_chunk_fn.misses
                         - draft_chunks_cold)
    # the draft prefilled only the residual (cold paid ceil(17/4) = 5)
    assert draft_chunks_warm < draft_chunks_cold

    # Generated pages commit under spec rounds: the full first
    # conversation (prompt + answer) is a warm prefix for the next turn.
    hist = np.concatenate([shared,
                           np.asarray(o_cold.token_ids, np.int32),
                           rng.integers(0, cfg.vocab, size=2)
                           .astype(np.int32)])
    skipped0 = eng.metrics.prefix_skipped_tokens
    eng.submit(Request("turn2", hist, SamplingParams(max_new_tokens=4)))
    o2 = eng.run()["turn2"]
    assert o2.token_ids == _oracle(gen, params, hist, 4)
    assert eng.metrics.prefix_skipped_tokens > skipped0
    assert eng.bm.num_free == eng.bm.num_allocatable


# ---------------------------------------------------------------------------
# fast tier: spec x fault containment
# ---------------------------------------------------------------------------


def test_spec_fault_bailout_then_plain_bisect_bit_exact():
    """A fused chain eating an injected device fault latches speculation
    OFF and degrades to plain decode with every stream bit-exact (the
    PR-3 containment contract); a rid-poison injected AFTER the bailout
    exercises the plain path's retry/bisect under an engine born
    speculative — the poison row quarantines, slot-mates stay exact."""
    cfg, params, gen, dcfg, d_params, draft = _models()
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (5, 6, 7)]

    def drive(faults):
        eng = ServeEngine(gen, params, num_blocks=40, page_size=4,
                          max_batch=2, prefill_chunk=4, draft=draft,
                          draft_params=d_params, spec_k=3, pipeline=2,
                          faults=faults, fault_retries=1, clock=_Tick())
        for i, p in enumerate(prompts):
            eng.submit(Request(f"p{i}", p,
                               SamplingParams(max_new_tokens=8)))
        return eng, eng.run()

    # 1) one-shot fault at the chain head -> bailout, all streams exact
    inj = FaultInjector(seed=0).inject("forward", op="spec_round",
                                       error="chain boom", max_fires=1)
    eng, outs = drive(inj)
    assert eng.metrics.spec_bailouts == 1
    assert eng._spec_off
    for i, p in enumerate(prompts):
        assert outs[f"p{i}"].finish_reason is FinishReason.LENGTH
        assert outs[f"p{i}"].token_ids == _oracle(gen, params, p, 8), i
    assert eng.bm.num_free == eng.bm.num_allocatable

    # 2) bailout + post-bailout rid poison -> plain bisect/quarantine
    inj2 = (FaultInjector(seed=0)
            .inject("forward", op="spec_round", error="chain boom",
                    max_fires=1)
            .inject("forward", rid="p1", op="paged_decode",
                    error="poison row"))
    eng2, outs2 = drive(inj2)
    assert outs2["p1"].finish_reason is FinishReason.ERROR
    assert "poison row" in outs2["p1"].error
    for rid in ("p0", "p2"):
        assert outs2[rid].finish_reason is FinishReason.LENGTH
        assert outs2[rid].token_ids == _oracle(
            gen, params, prompts[int(rid[1])], 8)
    f = eng2.metrics.summary()["failures"]
    assert f["quarantined"] == 1
    assert f["forward_bisections"] >= 1
    assert eng2.bm.num_free == eng2.bm.num_allocatable
    assert all(s is None for s in eng2.slots)


def test_spec_bailout_mid_drain_uses_opening_logits():
    """Review regression: a device failure surfacing at the DRAIN (the
    chain dispatched fine, the first device_get died) must bail out
    from the PRE-CHAIN round-opening logits — by then the engine's
    carry already advanced through the whole chain, and sampling the
    uncommitted rows from it would emit tokens from the wrong position
    and fork the stream."""
    cfg, params, gen, dcfg, d_params, draft = _models()
    rng = np.random.default_rng(17)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (5, 7)]
    n_new = 10
    eng = ServeEngine(gen, params, num_blocks=40, page_size=4,
                      max_batch=2, prefill_chunk=4, draft=draft,
                      draft_params=d_params, spec_k=3, pipeline=2,
                      clock=_Tick())
    for i, p in enumerate(prompts):
        eng.submit(Request(f"m{i}", p,
                           SamplingParams(max_new_tokens=n_new)))
    # fail the FIRST spec-chain drain fetch (the 3-tuple device_get is
    # unique to the spec drain), once
    real_get = jax.device_get
    state = {"armed": True}

    def flaky_get(x):
        if (state["armed"] and isinstance(x, tuple) and len(x) == 3):
            state["armed"] = False
            raise RuntimeError("drain died")
        return real_get(x)

    jax.device_get = flaky_get
    try:
        outs = eng.run()
    finally:
        jax.device_get = real_get
    assert eng.metrics.spec_bailouts == 1 and eng._spec_off
    for i, p in enumerate(prompts):
        assert outs[f"m{i}"].token_ids == _oracle(gen, params, p, n_new), i
    assert eng.bm.num_free == eng.bm.num_allocatable


def test_spec_tail_draft_failure_bails_out_exact():
    """Review regression: the k<=0 tail's draft step failing AFTER the
    target decode must still bail out from the round-opening logits
    (the tail's tokens came from them; overwriting the carry first
    would re-derive a wrong token) — the request at the very end of its
    cache finishes bit-exactly."""
    cfg, params, gen, dcfg, d_params, draft = _models()
    rng = np.random.default_rng(18)
    p = rng.integers(0, cfg.vocab, size=50).astype(np.int32)
    n_new = 14  # 50 + 14 = 64 = max_seq: the last token has k_cap == 0
    want = _oracle(gen, params, p, n_new)
    inj = FaultInjector().inject("forward", op="draft_tail_step",
                                 error="tail draft died")
    # pipeline=1: a chain's second link would otherwise cover the
    # last-slot round internally and the step never STARTS at the edge
    eng = ServeEngine(gen, params, num_blocks=40, page_size=4,
                      max_batch=1, prefill_chunk=4, draft=draft,
                      draft_params=d_params, spec_k=3, pipeline=1,
                      faults=inj, clock=_Tick())
    eng.submit(Request("t", p, SamplingParams(max_new_tokens=n_new)))
    outs = eng.run()
    assert eng.metrics.spec_bailouts == 1 and eng._spec_off
    assert outs["t"].token_ids == want
    assert outs["t"].finish_reason is FinishReason.LENGTH


def test_spec_bailed_engine_snapshots_and_restores():
    """Review regression: a bailed-out spec engine keeps snapshotting —
    the capture omits the (untrusted, possibly donation-consumed) draft
    subtree, the manifest omits the draft geometry in lockstep, and a
    restore of the spec_off snapshot serves the rows plain,
    bit-exactly."""
    cfg, params, gen, dcfg, d_params, draft = _models()
    rng = np.random.default_rng(19)
    p = rng.integers(0, cfg.vocab, size=6).astype(np.int32)
    want = _oracle(gen, params, p, 10)
    d = tempfile.mkdtemp(prefix="spec_bail_")
    try:
        inj = FaultInjector().inject("forward", op="spec_round",
                                     error="boom", max_fires=1)
        eng = ServeEngine(gen, params, num_blocks=40, page_size=4,
                          max_batch=1, prefill_chunk=4, draft=draft,
                          draft_params=d_params, spec_k=3, faults=inj,
                          snapshot_dir=d, snapshot_every=1,
                          clock=_Tick())
        eng.submit(Request("b", p, SamplingParams(max_new_tokens=10)))
        for _ in range(4):  # bailout fires, snapshots keep landing
            eng.step()
        assert eng._spec_off and eng.metrics.snapshots >= 2
        eng2 = ServeEngine.restore(d, gen, params, draft=draft,
                                   draft_params=d_params, clock=_Tick())
        assert eng2._spec_off  # the latch survives the restart
        outs = dict(eng2._outputs)
        outs.update(eng2.run())
        assert outs["b"].token_ids == want
    finally:
        shutil.rmtree(d, ignore_errors=True)


# ---------------------------------------------------------------------------
# fast tier: spec engine crash recovery (draft state resumes in place)
# ---------------------------------------------------------------------------


def test_spec_snapshot_restore_mid_stream_bit_exact():
    """Chaos-kill a spec engine mid-round and restore: every resumed
    stream is bit-identical to the uninterrupted run, and rows at
    snapshot parity resume IN PLACE — the snapshotted draft caches +
    round-opening logits come back instead of re-prefilling every draft
    row through the preemption path (the recorded PR 5 follow-up)."""
    cfg, params, gen, dcfg, d_params, draft = _models()
    rng = np.random.default_rng(14)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (5, 9)]
    reqs = lambda: [Request(f"r{i}", p,                     # noqa: E731
                            SamplingParams(max_new_tokens=12))
                    for i, p in enumerate(prompts)]

    def mk(snapdir=None, clock=None):
        return ServeEngine(gen, params, num_blocks=40, page_size=4,
                           max_batch=2, prefill_chunk=4, draft=draft,
                           draft_params=d_params, spec_k=3, pipeline=2,
                           snapshot_dir=snapdir,
                           snapshot_every=1 if snapdir else None,
                           clock=clock or _Tick())

    ref_eng = mk()
    for r in reqs():
        ref_eng.submit(r)
    ref = ref_eng.run()

    in_place_total = 0
    for kill_at in (2, 3, 4):
        d = tempfile.mkdtemp(prefix="spec_rec_")
        try:
            eng = mk(d)
            for r in reqs():
                eng.submit(r)
            for _ in range(kill_at):
                if eng.has_work():
                    eng.step()
            # abandon the engine object like a SIGKILL would, restart
            # from the journal + snapshot on disk
            eng2 = ServeEngine.restore(d, gen, params, draft=draft,
                                       draft_params=d_params,
                                       clock=_Tick())
            outs = dict(eng2._outputs)
            outs.update(eng2.run())
            for i in range(len(prompts)):
                assert outs[f"r{i}"].token_ids == ref[f"r{i}"].token_ids, (
                    kill_at, i)
                assert outs[f"r{i}"].finish_reason is FinishReason.LENGTH
            assert eng2.bm.num_free == eng2.bm.num_allocatable
            in_place_total += eng2.metrics.restored_in_place
        finally:
            shutil.rmtree(d, ignore_errors=True)
    # at least one kill point found both rows at snapshot parity and
    # resumed them with live draft state
    assert in_place_total >= 2, in_place_total


def test_spec_fused_is_no_option_and_its_old_snapshots_restore():
    """The round is one program with no option to take it apart:
    ``spec_fused=`` is a ``TypeError``.  A snapshot written while the
    option existed carries the key in its manifest; restoring it ignores
    the key and resumes every stream bit-identically."""
    import glob
    import json

    from triton_dist_tpu.serve.integrity import canonical_crc
    from triton_dist_tpu.serve.recovery import META_CRC

    cfg, params, gen, dcfg, d_params, draft = _models()
    kw = dict(num_blocks=40, page_size=4, max_batch=2, prefill_chunk=4,
              draft=draft, draft_params=d_params, spec_k=3)
    with pytest.raises(TypeError, match="spec_fused"):
        ServeEngine(gen, params, spec_fused=False, **kw)
    rng = np.random.default_rng(15)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (6, 8)]
    reqs = lambda: [Request(f"r{i}", p,                     # noqa: E731
                            SamplingParams(max_new_tokens=10))
                    for i, p in enumerate(prompts)]
    ref_eng = ServeEngine(gen, params, clock=_Tick(), **kw)
    for r in reqs():
        ref_eng.submit(r)
    ref = ref_eng.run()
    d = tempfile.mkdtemp(prefix="spec_old_meta_")
    try:
        eng = ServeEngine(gen, params, snapshot_dir=d, snapshot_every=1,
                          clock=_Tick(), **kw)
        for r in reqs():
            eng.submit(r)
        for _ in range(3):
            eng.step()
        metas = glob.glob(os.path.join(d, "**", "meta.json"),
                          recursive=True)
        assert metas
        for path in metas:
            with open(path) as f:
                meta = json.load(f)
            assert "spec_fused" not in meta["engine"]
            meta["engine"]["spec_fused"] = True
            meta[META_CRC] = canonical_crc(meta, exclude=(META_CRC,))
            with open(path, "w") as f:
                json.dump(meta, f)
        eng2 = ServeEngine.restore(d, gen, params, draft=draft,
                                   draft_params=d_params, clock=_Tick())
        outs = dict(eng2._outputs)
        outs.update(eng2.run())
        for rid, want in ref.items():
            assert outs[rid].token_ids == want.token_ids, rid
    finally:
        shutil.rmtree(d, ignore_errors=True)


def test_spec_snapshot_restore_without_draft_requeues():
    """Restoring a spec snapshot into a DRAFT-LESS engine cannot reuse
    the slot-indexed draft state: rows requeue through exact recompute
    and the streams still come out bit-identical (the journal + seeds
    carry everything the token function needs)."""
    cfg, params, gen, dcfg, d_params, draft = _models()
    rng = np.random.default_rng(16)
    p = rng.integers(0, cfg.vocab, size=6).astype(np.int32)
    want = _oracle(gen, params, p, 10)
    d = tempfile.mkdtemp(prefix="spec_rec2_")
    try:
        eng = ServeEngine(gen, params, num_blocks=40, page_size=4,
                          max_batch=2, prefill_chunk=4, draft=draft,
                          draft_params=d_params, spec_k=3,
                          snapshot_dir=d, snapshot_every=1,
                          clock=_Tick())
        eng.submit(Request("r0", p, SamplingParams(max_new_tokens=10)))
        for _ in range(3):
            eng.step()
        eng2 = ServeEngine.restore(d, gen, params, clock=_Tick())
        assert eng2.metrics.restored_in_place == 0
        assert eng2.metrics.restored_requeued == 1
        outs = eng2.run()
        assert outs["r0"].token_ids == want
    finally:
        shutil.rmtree(d, ignore_errors=True)


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-v"] + sys.argv[1:]))
