"""Device-resident decode horizon (serve/engine.py, docs/serving.md
"Decode horizon"): fused multi-step decode with on-device sampling and
async dispatch pipelining.

Fast tier: ladder/bucket helpers and the scheduler's horizon-clamp
policy; THE horizon oracle (greedy streams at H in {1, 4, 16} bit-
identical to each other and to per-request ``Generator.generate``);
sampled streams identical between the H=1 host sampler and the H>1
device sampler and reproducible under a fixed seed; dispatch economics
(dispatches/token <= 0.15 at H=8 on a steady batch) + amortized ITL
accounting; EOS / abort / deadline interactions (no tokens past retire);
horizon x fault-injection (poison row mid-horizon quarantines without
corrupting slot-mates' committed streams); warmup leaving the horizon
miss counter flat; dispatch counts of a decode-only batch at every
horizon.

Slow tier: preemption-recompute exactness under horizon-sized capacity
reservation, and spec-mode engines clamping fused decode off.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from triton_dist_tpu.models import llama
from triton_dist_tpu.models.generate import Generator
from triton_dist_tpu.runtime.faults import FaultInjector
from triton_dist_tpu.runtime.jit_cache import bucket_down, pow2_ladder
from triton_dist_tpu.serve import (
    BlockManager,
    FCFSScheduler,
    Request,
    SamplingParams,
    ServeEngine,
)
from triton_dist_tpu.serve.request import FinishReason


# ---------------------------------------------------------------------------
# fast tier: ladder + planning policy (no jax compiles)
# ---------------------------------------------------------------------------


def test_pow2_ladder_and_bucket_down():
    assert pow2_ladder(1) == [1]
    assert pow2_ladder(8) == [1, 2, 4, 8]
    assert pow2_ladder(6) == [1, 2, 4, 6]       # cap closes the ladder
    assert pow2_ladder(16) == [1, 2, 4, 8, 16]
    with pytest.raises(ValueError):
        pow2_ladder(0)
    lad = [1, 2, 4, 8]
    assert bucket_down(lad, 1) == 1
    assert bucket_down(lad, 3) == 2
    assert bucket_down(lad, 8) == 8
    assert bucket_down(lad, 100) == 8           # clamps at the top rung
    with pytest.raises(ValueError):
        bucket_down(lad, 0)


def test_plan_horizon_policy():
    sched = FCFSScheduler(BlockManager(8, 4), prefill_budget=8,
                          prefill_chunk=4)
    kw = dict(prefilling=False, spec=False, deadline_waiting=False)
    assert sched.plan_horizon(8, **kw) == 8
    assert sched.plan_horizon(1, **kw) == 1
    # each per-step contract clamps fused decode back to one step
    assert sched.plan_horizon(8, prefilling=True, spec=False,
                              deadline_waiting=False) == 1
    assert sched.plan_horizon(8, prefilling=False, spec=True,
                              deadline_waiting=False) == 1
    assert sched.plan_horizon(8, prefilling=False, spec=False,
                              deadline_waiting=True) == 1


def test_horizon_params_validated():
    cfg, params, gen = _tiny_model()
    with pytest.raises(ValueError, match="horizon"):
        ServeEngine(gen, params, num_blocks=8, page_size=4, max_batch=1,
                    horizon=0)
    with pytest.raises(ValueError, match="pipeline"):
        ServeEngine(gen, params, num_blocks=8, page_size=4, max_batch=1,
                    pipeline=0)


# ---------------------------------------------------------------------------
# shared tiny model (1 layer: cheap enough for the tier-1 gate)
# ---------------------------------------------------------------------------


def _tiny_model():
    cfg = llama.LlamaConfig(vocab=64, dim=16, n_layers=1, n_heads=2,
                            n_kv_heads=1, ffn_dim=32, max_seq=64,
                            dtype=jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    params = llama.init_params(cfg, jax.random.key(3))
    gen = Generator(cfg, mesh, axis="sp", max_seq=64)
    return cfg, params, gen


class _Tick:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


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
# fast tier: THE horizon oracle + sampler equality
# ---------------------------------------------------------------------------


def test_horizon_oracle_exact_h_1_4_16():
    """Greedy streams at H in {1, 4, 16} (pipelined and not) must be
    bit-identical to each other and to per-request Generator.generate —
    staggered arrivals included, so fused decode interleaves with
    admission, prefill clamps, and mid-flight joins."""
    cfg, params, gen = _tiny_model()
    rng = np.random.default_rng(7)
    lens = [5, 9, 3, 12]
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in lens]
    n_new = 13
    want = {f"r{i}": _oracle(gen, params, p, n_new)
            for i, p in enumerate(prompts)}

    for h, pipe in ((1, 1), (4, 1), (16, 2)):
        eng = ServeEngine(gen, params, num_blocks=40, page_size=4,
                          max_batch=3, prefill_chunk=4, horizon=h,
                          pipeline=pipe, clock=_Tick())
        outs = _drive(eng, [Request(f"r{i}", p,
                                    SamplingParams(max_new_tokens=n_new))
                            for i, p in enumerate(prompts)])
        for rid, w in want.items():
            assert outs[rid].token_ids == w, (h, pipe, rid)
            assert outs[rid].finish_reason is FinishReason.LENGTH
        assert eng.bm.num_free == eng.bm.num_allocatable
        assert all(s is None for s in eng.slots)
        d = eng.metrics.summary()["decode"]
        if h > 1:
            # fused decode actually engaged: fewer dispatches than steps
            assert d["dispatches"] < d["decode_steps"], d


def test_horizon_sampled_streams_match_host_and_reproduce():
    """A sampled request's device-side horizon stream (fold_in per-row
    keys inside the scan) must equal the H=1 host `_choose_token` stream
    token for token, and reproduce under the same seed — while a greedy
    slot-mate stays oracle-exact in the same mixed batch."""
    cfg, params, gen = _tiny_model()
    rng = np.random.default_rng(8)
    pg = rng.integers(0, cfg.vocab, size=7).astype(np.int32)
    ps = rng.integers(0, cfg.vocab, size=5).astype(np.int32)

    def run(h):
        eng = ServeEngine(gen, params, num_blocks=40, page_size=4,
                          max_batch=2, prefill_chunk=4, horizon=h,
                          pipeline=2, clock=_Tick())
        eng.submit(Request("g", pg, SamplingParams(max_new_tokens=9)))
        # seed >= 2**31: must stream identically at every H (the engine
        # stacks host-built jax.random.key(seed) rows — an int32 seed
        # array would overflow here and quarantine the request at H>1)
        eng.submit(Request("s", ps, SamplingParams(
            max_new_tokens=9, temperature=0.8, top_k=16, top_p=0.9,
            seed=2**31 + 11)))
        return eng.run()

    o1, o8, o8b = run(1), run(8), run(8)
    assert o1["g"].token_ids == o8["g"].token_ids == _oracle(
        gen, params, pg, 9)
    assert o1["s"].finish_reason is FinishReason.LENGTH
    assert o8["s"].finish_reason is FinishReason.LENGTH
    assert o1["s"].token_ids == o8["s"].token_ids    # host == device
    assert o8["s"].token_ids == o8b["s"].token_ids   # seeded reproducible
    assert all(0 <= t < cfg.vocab for t in o8["s"].token_ids)


@pytest.mark.parametrize("batch,whole", [
    ("candidates", False), ("whole_rows", True), ("mixed", None)])
def test_horizon_on_the_candidate_plan_matches_host_and_counts_paths(
        monkeypatch, batch, whole):
    """A vocabulary forced onto the sampler's candidate plan (1,024 rows
    = 8 groups of 128, a row keeps 4): every request's horizon stream
    equals the ``horizon=1`` engine's, whose tokens come from the one-row
    ``sample_token`` call — whichever way a step's batch found its
    cut-offs — and ``summary()["sample"]`` counts each sampled row-step
    the horizon served: among the candidates where every sampled row's
    ``top_k`` is at most 4, from the whole rows in every step that holds a
    ``top_p`` with no top-k before it or a ``top_k`` above 4."""
    from triton_dist_tpu.models import sampling

    monkeypatch.setattr(sampling, "_CAND_MIN_VOCAB", 1024)
    monkeypatch.setattr(sampling, "_CAND_GROUPS", 4)
    cfg = llama.LlamaConfig(vocab=1024, dim=16, n_layers=1, n_heads=2,
                            n_kv_heads=1, ffn_dim=32, max_seq=64,
                            dtype=jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    params = llama.init_params(cfg, jax.random.key(5))
    gen = Generator(cfg, mesh, axis="sp", max_seq=64)
    rng = np.random.default_rng(9)
    narrow = dict(temperature=0.8, top_k=3, top_p=0.95)
    knobs = {"candidates": [narrow, dict(temperature=1.2, top_k=4)],
             "whole_rows": [dict(temperature=0.8, top_p=0.9),
                            dict(temperature=0.8, top_k=200)],
             "mixed": [narrow, dict(temperature=0.8, top_p=0.9)]}[batch]
    # the first sampled request outlives the second: in "mixed" its last
    # steps run beside the greedy row alone, among the candidates
    reqs = [("g", 7, SamplingParams(max_new_tokens=9))] + [
        (f"s{i}", 5 + i, SamplingParams(max_new_tokens=20 - 12 * i,
                                        seed=2 ** 31 + i, **kw))
        for i, kw in enumerate(knobs)]
    reqs = [(rid, rng.integers(0, cfg.vocab, size=n).astype(np.int32), sp)
            for rid, n, sp in reqs]

    def run(h):
        eng = ServeEngine(gen, params, num_blocks=40, page_size=4,
                          max_batch=3, prefill_chunk=4, horizon=h,
                          pipeline=2, clock=_Tick())
        for rid, prompt, sp in reqs:
            eng.submit(Request(rid, prompt, sp))
        return eng.run(), eng.metrics

    (o1, m1), (o8, m8) = run(1), run(8)
    s1, s8 = m1.summary()["sample"], m8.summary()["sample"]
    for path in ("narrow", "full"):
        assert (f'serve_sample_rows_total{{path="{path}"}} '
                f'{s8[path + "_rows"]}\n') in m8.to_prometheus()
    assert m8.merge(m8).sample_stats()["full_rows"] == 2 * s8["full_rows"]
    assert o8["g"].token_ids == _oracle(gen, params, reqs[0][1], 9)
    for rid, _, sp in reqs:
        assert o8[rid].token_ids == o1[rid].token_ids, rid
        assert len(o8[rid].token_ids) == sp.max_new_tokens
    # a request's first token is the host's choice; the horizon served
    # every later one (the horizon=1 engine runs no horizon: nothing)
    served = sum(sp.max_new_tokens - 1 for _, _, sp in reqs[1:])
    assert s1 == {"narrow_rows": 0, "full_rows": 0, "narrow_share": 0.0}
    assert s8["narrow_rows"] + s8["full_rows"] == served
    if whole is None:
        # every step of the short request is the whole rows' for both
        assert s8["full_rows"] >= 2 * (reqs[2][2].max_new_tokens - 1)
        assert s8["narrow_rows"] >= 4
    else:
        assert s8["full_rows"] == (served if whole else 0)
        assert s8["narrow_share"] == (0.0 if whole else 1.0)


@pytest.mark.parametrize("seeds", [
    [None, 5, 2 ** 31 - 1, 2 ** 31 + 7, 0],     # either side of int32
    [None] * 4,                                 # an all-greedy batch
])
def test_key_batch_is_the_stack_of_the_host_path_keys(seeds):
    """The horizon's base keys go to the device in one transfer; element
    for element they are ``jax.random.key(seed)``, the call the host
    sampler makes, so a stream may cross between the two paths."""
    from triton_dist_tpu.serve.programs import _key_batch

    got = _key_batch(seeds)
    want = jnp.stack([jax.random.key(0 if s is None else s) for s in seeds])
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(jax.random.key_data(got),
                                  jax.random.key_data(want))


def test_horizon_dispatch_economics_and_itl():
    """ISSUE acceptance: a steady decode-only batch at H=8 pays
    dispatches/token <= 0.15 (vs 1.0 per-token), with ITL attributed from
    the device step cadence — per-request gaps stay positive and count
    n_tokens - 1, never collapsing onto the drain instants."""
    cfg, params, gen = _tiny_model()
    rng = np.random.default_rng(9)
    n_new = 33
    eng = ServeEngine(gen, params, num_blocks=40, page_size=4,
                      max_batch=2, prefill_chunk=4, horizon=8,
                      pipeline=2, clock=_Tick())
    for i in range(2):
        eng.submit(Request(f"d{i}",
                           rng.integers(0, cfg.vocab, size=6)
                           .astype(np.int32),
                           SamplingParams(max_new_tokens=n_new)))
    outs = eng.run()
    d = eng.metrics.summary()["decode"]
    assert d["decode_tokens"] == 2 * (n_new - 1)   # first tokens: prefill
    assert d["dispatches_per_token"] <= 0.15, d
    assert d["tokens_per_dispatch"] >= 1 / 0.15 - 1e-9
    assert d["host_syncs"] <= d["dispatches"]
    assert d["decode_steps"] == n_new - 1          # lockstep pair
    for i in range(2):
        m = outs[f"d{i}"].metrics
        itl = m.inter_token_latencies
        assert len(itl) == n_new - 1
        assert all(x > 0 for x in itl), itl        # burst-paced, monotone
    s = eng.metrics.summary()
    assert s["mean_itl"] > 0


def test_horizon_eos_exits_early_and_matches_h1():
    cfg, params, gen = _tiny_model()
    rng = np.random.default_rng(10)
    p = rng.integers(0, cfg.vocab, size=7).astype(np.int32)
    want = _oracle(gen, params, p, 14)
    j = next(i for i in range(2, len(want)) if want[i] not in want[:i])
    eos = want[j]

    def run(h):
        eng = ServeEngine(gen, params, num_blocks=40, page_size=4,
                          max_batch=2, prefill_chunk=4, horizon=h,
                          pipeline=2, clock=_Tick())
        eng.submit(Request("e", p, SamplingParams(max_new_tokens=14,
                                                  eos_id=eos)))
        eng.submit(Request("m", p[:5], SamplingParams(max_new_tokens=14)))
        outs = eng.run()
        assert eng.bm.num_free == eng.bm.num_allocatable
        return outs

    for h in (1, 8):
        outs = run(h)
        assert outs["e"].finish_reason is FinishReason.EOS
        assert outs["e"].token_ids == want[:j + 1], h   # nothing past eos
        assert outs["m"].token_ids == _oracle(gen, params, p[:5], 14)


def test_horizon_deadline_waiting_is_swept_on_time():
    """A WAITING request with a TTL clamps fused decode back to per-step
    sweeps (plan_horizon's deadline_waiting rule): the deadline fires at
    its step, not up to a horizon late, while the decoding row stays
    oracle-exact."""
    cfg, params, gen = _tiny_model()
    clock = _Clock()
    rng = np.random.default_rng(12)
    ph = rng.integers(0, cfg.vocab, size=6).astype(np.int32)
    pw = rng.integers(0, cfg.vocab, size=5).astype(np.int32)
    eng = ServeEngine(gen, params, num_blocks=6, page_size=8,
                      max_batch=1, prefill_chunk=8, horizon=8,
                      pipeline=2, clock=clock)
    eng.submit(Request("hold", ph, SamplingParams(max_new_tokens=16)))
    eng.submit(Request("ttl", pw, SamplingParams(max_new_tokens=4,
                                                 deadline_s=10.0)))
    eng.step()                       # "hold" owns the only slot
    clock.advance(11.0)
    eng.step()                       # the sweep must fire THIS iteration
    assert eng._outputs["ttl"].finish_reason is FinishReason.DEADLINE
    outs = eng.run()
    assert outs["hold"].token_ids == _oracle(gen, params, ph, 16)
    # with the queue drained of deadlines, fused decode re-engaged
    d = eng.metrics.summary()["decode"]
    assert d["dispatches"] < d["decode_steps"], d


def test_horizon_abort_from_callback_no_tokens_past_retire():
    """An `on_token` callback aborting a slot-mate (and later itself)
    mid-burst: commits stop at the retire for both, later-link device
    output is discarded, and the pool comes back whole."""
    cfg, params, gen = _tiny_model()
    rng = np.random.default_rng(11)
    p0 = rng.integers(0, cfg.vocab, size=5).astype(np.int32)
    p1 = rng.integers(0, cfg.vocab, size=6).astype(np.int32)
    eng = ServeEngine(gen, params, num_blocks=40, page_size=4,
                      max_batch=2, prefill_chunk=4, horizon=8,
                      pipeline=2, clock=_Tick())

    def killer(rid, tok):
        if len(eng._states["a0"].generated) == 3:
            eng.abort("a1")
        if len(eng._states["a0"].generated) == 5:
            eng.abort("a0")

    eng.submit(Request("a0", p0, SamplingParams(max_new_tokens=10),
                       on_token=killer))
    eng.submit(Request("a1", p1, SamplingParams(max_new_tokens=10)))
    outs = eng.run()
    assert outs["a0"].finish_reason is FinishReason.ABORT
    assert outs["a0"].token_ids == _oracle(gen, params, p0, 10)[:5]
    assert outs["a1"].finish_reason is FinishReason.ABORT
    w1 = _oracle(gen, params, p1, 10)
    assert outs["a1"].token_ids == w1[:len(outs["a1"].token_ids)]
    assert eng.bm.num_free == eng.bm.num_allocatable
    assert all(s is None for s in eng.slots)


# ---------------------------------------------------------------------------
# fast tier: a clamped step is ONE one-step horizon link
# ---------------------------------------------------------------------------


def _tap(eng):
    """Count what the one-step link must account for: the ``H`` of every
    ``decode_horizon`` dispatch, and every completed prefill (the only
    place such an engine still chooses a token on the host) with whether
    its request is sampled."""
    links, prefills = [], []
    call, finish = eng._device_call, eng._finish_prefill

    def device_call(op, rids, fn, *a, **kw):
        if op == "decode_horizon":
            links.append(kw["H"])
        return call(op, rids, fn, *a, **kw)

    def finish_prefill(rs, *a, **kw):
        prefills.append(not rs.req.params.greedy)
        return finish(rs, *a, **kw)

    eng._device_call, eng._finish_prefill = device_call, finish_prefill
    return links, prefills


_SAMPLED = dict(temperature=0.8, top_k=16, top_p=0.9)


def _clamped_case(case, cfg, oracle):
    """-> (requests, engine keywords, what else must hold).  Every case
    prefills in chunks of 4 on a budget of 4 a step, so a prompt of n
    tokens keeps its slot mid-prefill for n / 4 engine steps while its
    slot-mates decode: those steps are clamped."""
    rng = np.random.default_rng(31)

    def prompt(n):
        return rng.integers(0, cfg.vocab, size=n).astype(np.int32)

    kw = dict(num_blocks=40, max_batch=3)
    if case == "greedy":
        reqs = [Request(f"r{i}", prompt(n), SamplingParams(max_new_tokens=12))
                for i, n in enumerate((6, 19, 11, 23))]
    elif case == "sampled":
        reqs = [Request(f"r{i}", prompt(n), SamplingParams(
                    max_new_tokens=12, seed=40 + i,
                    **(_SAMPLED if i % 2 else dict(temperature=1.3))))
                for i, n in enumerate((6, 19, 11, 23))]
    elif case == "seed_over_int32":
        reqs = [Request("r0", prompt(7), SamplingParams(max_new_tokens=12)),
                Request("r1", prompt(17), SamplingParams(
                    max_new_tokens=12, seed=2 ** 31 + 11, **_SAMPLED)),
                Request("r2", prompt(21), SamplingParams(
                    max_new_tokens=12, seed=2 ** 32 - 1, **_SAMPLED))]
    elif case == "eos_on_the_link":
        # "e" prefills behind r1 and decodes beside r2's nine steps of
        # prefill, so its EOS can only come out of a one-step link
        p = prompt(5)
        want = oracle(p, 14)
        j = next(i for i in range(2, 8) if want[i] not in want[:i])
        reqs = [Request("r1", prompt(40), SamplingParams(max_new_tokens=4)),
                Request("e", p, SamplingParams(max_new_tokens=14,
                                               eos_id=want[j])),
                Request("r2", prompt(36), SamplingParams(max_new_tokens=4))]
    elif case == "preempted_across_it":
        # 9 blocks of 4 hold neither the three rows nor two of them to
        # their ends: the youngest is evicted while a slot-mate decodes,
        # and recomputes (mid-prefill again: more clamped steps)
        kw = dict(num_blocks=9, max_batch=3)
        reqs = [Request("r0", prompt(9), SamplingParams(max_new_tokens=10)),
                Request("r1", prompt(7), SamplingParams(
                    max_new_tokens=10, seed=5, **_SAMPLED)),
                Request("r2", prompt(6), SamplingParams(max_new_tokens=8))]
    return reqs, kw


@pytest.mark.parametrize("case", [
    "greedy", "sampled", "seed_over_int32", "eos_on_the_link",
    "preempted_across_it"])
def test_clamped_step_is_one_horizon_link_with_the_same_stream(case):
    """A ``horizon=8, pipeline=2`` engine whose steps are clamped (a slot
    is mid-prefill) dispatches ONE ``decode_horizon`` link at ``H = 1``
    for each, and emits token for token what a ``horizon=1`` engine (host
    sampler) and ``Generator.generate`` emit.  Its decode never reaches
    the single-step program or ``_choose_token``: the host chooses one
    token a completed prefill, no more."""
    cfg, params, gen = _tiny_model()
    reqs, kw = _clamped_case(
        case, cfg, lambda p, n: _oracle(gen, params, p, n))

    def run(h):
        eng = ServeEngine(gen, params, page_size=4, prefill_chunk=4,
                          prefill_budget=4, horizon=h, pipeline=2,
                          clock=_Tick(), **kw)
        links, prefills = _tap(eng)
        # the H of the newest link at each token's delivery
        by = {r.request_id: [] for r in reqs}
        outs = _drive(eng, [
            Request(r.request_id, r.prompt, r.params,
                    on_token=lambda rid, tok: by[rid].append(links[-1:]))
            for r in reqs], stagger=1)
        assert eng.bm.num_free == eng.bm.num_allocatable
        return eng, outs, links, prefills, by

    e1, o1, _, pre1, _ = run(1)
    e8, o8, links, prefills, by = run(8)
    for r in reqs:
        rid = r.request_id
        assert o8[rid].token_ids == o1[rid].token_ids, (case, rid)
        assert o8[rid].finish_reason is o1[rid].finish_reason
        if r.params.greedy and r.params.eos_id is None:
            assert o8[rid].token_ids == _oracle(
                gen, params, r.prompt, r.params.max_new_tokens)
    # clamped steps happened, each ONE link of one step ...
    assert links.count(1) >= 4, links
    d = e8.metrics.summary()["decode"]
    assert d["dispatches"] == d["host_syncs"] == len(links)
    # ... the single-step program and the host sampler served no decode
    assert e8._decode_fn.hits + e8._decode_fn.misses == 0
    assert e8.metrics.host_choices == len(prefills)
    assert e8._sample_fn.hits + e8._sample_fn.misses == sum(prefills)
    # (the horizon=1 engine chooses every token there)
    assert e1.metrics.host_choices == len(pre1) + e1.metrics.decode_tokens
    if case == "eos_on_the_link":
        assert o8["e"].finish_reason is FinishReason.EOS
        assert len(by["e"]) == len(o8["e"].token_ids) >= 3
        # every decode token of "e", its last included: a one-step link
        assert by["e"][1:] == [[1]] * (len(by["e"]) - 1), by["e"]
    if case == "preempted_across_it":
        assert e8.metrics.preemptions >= 1
        assert len(prefills) > len(reqs)      # a recompute prefilled again


# ---------------------------------------------------------------------------
# fast tier: horizon x fault injection
# ---------------------------------------------------------------------------


def test_horizon_poison_row_bisected_and_quarantined():
    """A rid-poisoned horizon chain retries, bisects to the poison row,
    quarantines it — and the slot-mates' committed streams stay
    bit-identical to a fault-free run (the PR-3 containment contract at
    horizon granularity)."""
    cfg, params, gen = _tiny_model()
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (5, 6, 7)]

    def drive(faults):
        eng = ServeEngine(gen, params, num_blocks=40, page_size=4,
                          max_batch=2, prefill_chunk=4, horizon=8,
                          pipeline=2, faults=faults, fault_retries=1,
                          clock=_Tick())
        for i, p in enumerate(prompts):
            eng.submit(Request(f"p{i}", p,
                               SamplingParams(max_new_tokens=6)))
        return eng, eng.run()

    inj = FaultInjector(seed=0)
    inj.inject("forward", rid="p1", op="decode_horizon", error="bad row")
    eng, outs = drive(inj)
    _, clean = drive(None)
    assert outs["p1"].finish_reason is FinishReason.ERROR
    assert "bad row" in outs["p1"].error
    for rid in ("p0", "p2"):
        assert outs[rid].finish_reason is FinishReason.LENGTH
        assert outs[rid].token_ids == clean[rid].token_ids
        assert outs[rid].token_ids == _oracle(
            gen, params, prompts[int(rid[1])], 6)
    f = eng.metrics.summary()["failures"]
    assert f["quarantined"] == 1
    assert f["forward_bisections"] >= 1
    assert f["forward_retries"] >= 1
    assert eng.bm.num_free == eng.bm.num_allocatable
    assert all(s is None for s in eng.slots)


def test_horizon_transient_fault_absorbed_by_retry():
    """A one-shot injected fault at the chain head is absorbed by the
    retry budget: nothing quarantined, streams exact (the chain fires
    the injector exactly once, BEFORE any pool donation, so the retry
    is safe by construction)."""
    cfg, params, gen = _tiny_model()
    rng = np.random.default_rng(14)
    p = rng.integers(0, cfg.vocab, size=5).astype(np.int32)
    inj = FaultInjector().inject("forward", op="decode_horizon",
                                 error="transient", max_fires=1)
    eng = ServeEngine(gen, params, num_blocks=40, page_size=4,
                      max_batch=2, prefill_chunk=4, horizon=8,
                      pipeline=2, faults=inj, fault_retries=1,
                      clock=_Tick())
    eng.submit(Request("t", p, SamplingParams(max_new_tokens=9)))
    outs = eng.run()
    assert outs["t"].token_ids == _oracle(gen, params, p, 9)
    assert eng.metrics.quarantined == 0
    assert eng.metrics.forward_retries == 1


# ---------------------------------------------------------------------------
# fast tier: bounded compilation + the bench harness
# ---------------------------------------------------------------------------


def test_horizon_warmup_leaves_miss_counter_flat():
    """warmup() sweeps the WHOLE horizon ladder (greedy AND sampled
    variants, serially per rung; rung 1 is the link of a clamped step) —
    mixed-length, mixed-sampler traffic then never compiles, horizon
    programs included, and the single-step program is never built."""
    cfg, params, gen = _tiny_model()
    eng = ServeEngine(gen, params, num_blocks=40, page_size=4,
                      max_batch=2, prefill_chunk=4, prefill_budget=4,
                      horizon=8, pipeline=2, trace_level=1, clock=_Tick())
    w = eng.warmup()
    assert w["programs"] > 0
    hz_misses = eng._horizon_fn.misses
    # every rung compiles a greedy and a mixed-sampler program, rung 1
    # the mixed one alone (it serves a greedy-only batch too)
    assert eng.h_ladder == [1, 2, 4, 8]
    assert hz_misses == 2 * len(eng.h_ladder) - 1, eng._horizon_fn.stats()
    assert eng._decode_fn.misses == eng._decode_fn.hits == 0
    flat = eng.metrics.compile_misses
    rng = np.random.default_rng(15)
    reqs = []
    for i, n in enumerate([3, 5, 9, 13, 17, 23]):
        # three sampler classes, none of them warmup()'s own: the host
        # sampler's knobs are traced, so they share ONE executable
        kw = (dict(temperature=0.7 + i / 10, top_p=0.9,
                   top_k=i if i > 1 else None, seed=i) if i % 2 else {})
        reqs.append(Request(
            f"r{i}", rng.integers(0, cfg.vocab, size=n).astype(np.int32),
            SamplingParams(max_new_tokens=11, **kw)))
    outs = _drive(eng, reqs)
    assert len(outs) == len(reqs)
    assert eng.metrics.compile_misses == flat, (
        "horizon serving compiled after warmup: "
        f"{eng.metrics.summary()['compilation']}")
    assert eng._horizon_fn.misses == hz_misses
    # the staggered arrivals prefill beside running rows (chunks of 4,
    # prompts up to 23): those steps are clamped, and each was ONE
    # one-step link of the warmed rung — never the single-step program
    progs = eng.metrics.summary()["programs"]
    assert progs["decode_horizon[H=1]"]["count"] >= 6, sorted(progs)
    assert "paged_decode" not in progs
    assert eng._decode_fn.misses == eng._decode_fn.hits == 0
    # (at most: jit caches by function, so an earlier engine of this
    # process at the same vocabulary already holds it)
    assert eng._sample_fn.misses <= 1 and eng._sample_fn.hits >= 3


@pytest.mark.parametrize("horizon", [1, 2, 4, 8])
def test_decode_only_batch_dispatch_counts(horizon):
    """A warmed engine drains a steady decode-only batch of two: the
    dispatch count is the walk of the engine's own horizon ladder over
    the decode steps, one link a dispatch — ``ceil(steps / H)`` when H
    divides them, plus the tail rungs the ladder adds when it does not.
    H = 1 pays one dispatch and one sync a STEP (the batch amortises
    rows, the horizon amortises steps); H = 8 pays <= 0.15 dispatches a
    token.  Counts only: what the fused steps are worth in seconds is
    the chip's to say (``engine.tok_per_dispatch``,
    ``engine.step_wall_p50_ms`` in every cell of the benchmark)."""
    cfg, params, gen = _tiny_model()
    eng = ServeEngine(gen, params, num_blocks=1 + 2 * 4, page_size=8,
                      max_batch=2, prefill_chunk=8, horizon=horizon,
                      pipeline=2, clock=_Tick())
    eng.warmup()
    flat = eng.metrics.compile_misses
    rng = np.random.default_rng(0)

    def drain(tag, n_new):
        before = eng.metrics.summary()["decode"]
        for i in range(2):
            eng.submit(Request(
                f"{tag}{i}", rng.integers(0, cfg.vocab, size=8)
                .astype(np.int32), SamplingParams(max_new_tokens=n_new)))
        outs = eng.run()
        assert all(len(outs[f"{tag}{i}"].token_ids) == n_new
                   for i in range(2))
        after = eng.metrics.summary()["decode"]
        return {k: after[k] - before[k] for k in
                ("decode_steps", "decode_tokens", "dispatches",
                 "host_syncs")}

    def links(steps):
        n = 0
        while steps:
            steps -= bucket_down(eng.h_ladder, min(horizon, steps))
            n += 1
        return n

    # 16 decode steps (the first token is prefill's): H divides them
    d = drain("a", 17)
    assert d["decode_tokens"] == 2 * 16 and d["decode_steps"] == 16
    assert d["dispatches"] == links(16) == -(-16 // horizon), d
    assert d["host_syncs"] <= d["dispatches"]
    if horizon == 1:
        assert d["host_syncs"] == d["dispatches"] == 16
    if horizon == 8:
        assert d["dispatches"] / d["decode_tokens"] <= 0.15, d
    # 13 decode steps: the tail runs down the ladder's warmed rungs
    # (H = 8: 8 + 4 + 1), never a longer program with dead steps
    d = drain("b", 14)
    assert d["decode_tokens"] == 2 * 13
    assert d["dispatches"] == links(13), (d, eng.h_ladder)
    assert eng.metrics.compile_misses == flat   # warm: no new program


# ---------------------------------------------------------------------------
# slow tier: preemption + spec interactions
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def model2():
    cfg = llama.LlamaConfig(vocab=128, dim=32, n_layers=2, n_heads=2,
                            n_kv_heads=1, ffn_dim=64, max_seq=64,
                            dtype=jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    params = llama.init_params(cfg, jax.random.key(0))
    gen = Generator(cfg, mesh, axis="sp", max_seq=64)
    return cfg, params, gen


@pytest.mark.slow
def test_horizon_preemption_recompute_exact(model2):
    """Horizon capacity is reserved for the WHOLE planned chain up
    front, so block pressure preempts earlier than per-step decode —
    recompute must still reproduce every stream bit-exactly."""
    cfg, params, gen = model2
    rng = np.random.default_rng(20)
    p0 = rng.integers(0, cfg.vocab, size=16).astype(np.int32)
    p1 = rng.integers(0, cfg.vocab, size=16).astype(np.int32)
    eng = ServeEngine(gen, params, num_blocks=7, page_size=8,
                      max_batch=2, prefill_chunk=8, horizon=8,
                      pipeline=2, clock=_Tick())
    eng.submit(Request("a", p0, SamplingParams(max_new_tokens=16)))
    eng.submit(Request("b", p1, SamplingParams(max_new_tokens=16)))
    outs = eng.run()
    assert eng.metrics.preemptions >= 1
    assert outs["a"].token_ids == _oracle(gen, params, p0, 16)
    assert outs["b"].token_ids == _oracle(gen, params, p1, 16)


@pytest.mark.slow
def test_spec_engine_clamps_horizon_off(model2):
    """A speculative engine constructed with horizon > 1 keeps its round
    machinery (plan_horizon's spec clamp): streams stay greedy-exact and
    the horizon program never compiles — post-bailout decode stays on
    the warmed single-step path."""
    cfg, params, gen = model2
    dcfg = llama.LlamaConfig(vocab=cfg.vocab, dim=16, n_layers=1,
                             n_heads=1, n_kv_heads=1, ffn_dim=32,
                             max_seq=64, dtype=jnp.float32)
    d_params = llama.init_params(dcfg, jax.random.key(7))
    draft = Generator(dcfg, gen.mesh, axis="sp", max_seq=64)
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (5, 9, 3)]
    eng = ServeEngine(gen, params, num_blocks=40, page_size=8,
                      max_batch=3, prefill_chunk=8, horizon=8,
                      draft=draft, draft_params=d_params, spec_k=3,
                      clock=_Tick())
    for i, p in enumerate(prompts):
        eng.submit(Request(f"s{i}", p, SamplingParams(max_new_tokens=7)))
    outs = eng.run()
    for i, p in enumerate(prompts):
        assert outs[f"s{i}"].token_ids == _oracle(gen, params, p, 7)
    assert eng.metrics.verify_rounds >= 1
    assert eng._horizon_fn.misses == 0          # never traced
