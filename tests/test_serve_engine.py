"""Continuous-batching serving engine (`triton_dist_tpu/serve/`).

Fast tier (tier-1 gate): the pure-index machinery — block manager,
scheduler, metrics math — plus the r5-advisor regression fixes
(`_write_rows` overflow skip, the paged SP multi-token assert).

Slow tier: the engine end-to-end on a tiny Llama — the acceptance
oracle is per-request ``Generator.generate`` (greedy continuous batching
over the paged pools must be BIT-IDENTICAL to dedicated decoding),
covering staggered arrivals, block exhaustion → queueing, preemption +
recompute, retire/join mid-flight, speculative rounds, eos, sampling,
streaming callbacks, and the metrics export path.
"""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from triton_dist_tpu.kernels import flash_decode as fd
from triton_dist_tpu.models import llama
from triton_dist_tpu.models.generate import Generator, _write_rows
from triton_dist_tpu.serve import (
    BlockManager,
    FCFSScheduler,
    Request,
    SamplingParams,
    ServeEngine,
)
from triton_dist_tpu.serve.block_manager import BlockExhausted
from triton_dist_tpu.serve.metrics import RequestMetrics, ServeMetrics
from triton_dist_tpu.serve.request import FinishReason
from triton_dist_tpu.serve.scheduler import ReqState, Status


# ---------------------------------------------------------------------------
# fast tier: block manager
# ---------------------------------------------------------------------------


def test_block_manager_alloc_extend_free():
    bm = BlockManager(num_blocks=9, page_size=4)  # 8 allocatable
    assert bm.num_allocatable == 8 and bm.num_free == 8
    a = bm.allocate("a", 9)            # ceil(9/4) = 3 pages
    assert len(a) == 3 and bm.num_free == 5
    assert bm.capacity_tokens("a") == 12
    assert bm.ensure("a", 11) == []    # already covered
    grown = bm.ensure("a", 13)         # needs a 4th page
    assert len(grown) == 1 and bm.capacity_tokens("a") == 16
    assert bm.utilization == pytest.approx(4 / 8)
    bm.allocate("b", 16)
    assert bm.num_free == 0
    with pytest.raises(BlockExhausted):
        bm.ensure("a", 17)
    with pytest.raises(BlockExhausted):
        bm.allocate("c", 1)
    bm.free("b")
    assert bm.num_free == 4 and bm.utilization == pytest.approx(4 / 8)
    with pytest.raises(ValueError):
        bm.allocate("a", 4)            # duplicate rid


def test_block_manager_null_block_reserved():
    bm = BlockManager(num_blocks=5, page_size=8)
    held = bm.allocate("a", 32)        # everything allocatable
    assert 0 not in held               # block 0 is the reserved null block
    padded = bm.padded_table("a", 6)
    assert padded[:4] == held and padded[4:] == [0, 0]
    with pytest.raises(ValueError):
        bm.padded_table("a", 3)        # narrower than the allocation
    bm.free("a")
    assert 0 not in bm._free


# ---------------------------------------------------------------------------
# fast tier: scheduler
# ---------------------------------------------------------------------------


def _rs(rid, n_prompt, max_new=4):
    req = Request(rid, np.zeros((n_prompt,), np.int32),
                  SamplingParams(max_new_tokens=max_new))
    return ReqState(req=req, metrics=RequestMetrics(arrival_time=0.0))


def _sched(num_blocks=9, page=4, budget=8, chunk=4):
    bm = BlockManager(num_blocks, page)
    return FCFSScheduler(bm, prefill_budget=budget,
                         prefill_chunk=chunk), bm


def test_scheduler_fcfs_admission_and_headroom():
    sched, bm = _sched(num_blocks=8, page=4)    # 7 allocatable
    a, b = _rs("a", 26), _rs("b", 2)
    sched.add(a)
    sched.add(b)
    admitted = sched.admit([0, 1], now=1.0)
    # a takes ceil(27/4) = 7 blocks (prompt + 1 decode-headroom token);
    # b stays QUEUED even though a slot is free — FCFS admission never
    # lets a later arrival overtake a blocked head of line.
    assert [r.req.request_id for r in admitted] == ["a"]
    assert sched.queue_depth == 1
    assert a.status is Status.PREFILL and a.slot == 0
    assert a.metrics.first_scheduled_time == 1.0
    bm.free("a")
    assert [r.req.request_id for r in sched.admit([0], 2.0)] == ["b"]


def test_scheduler_prefill_budget_assignment():
    sched, bm = _sched(budget=8, chunk=4, num_blocks=33, page=4)
    rs1, rs2, rs3 = _rs("r1", 20), _rs("r2", 20), _rs("r3", 20)
    for r in (rs1, rs2, rs3):
        sched.add(r)
    sched.admit([0, 1, 2], now=0.0)
    plan = sched.prefill_plan([rs3, rs1, rs2])  # any order in
    # admission order out; budget 8 covers r1's first 8 tokens only
    assert [(r.req.request_id, n) for r, n in plan] == [("r1", 8)]
    rs1.prefill_pos = 18                        # 2 tokens left
    plan = sched.prefill_plan([rs1, rs2, rs3])
    # r1's residual is CHARGED as a full (padded) chunk, so r2 gets the
    # one remaining chunk of budget — never a partial mid-prompt chunk
    # (every _chunk_jit call must be the one fixed shape).
    assert [(r.req.request_id, n) for r, n in plan] == [("r1", 2),
                                                        ("r2", 4)]
    # head-of-line progress: budget below one chunk still prefills
    sched.prefill_budget = 2
    rs1.prefill_pos = 0
    plan = sched.prefill_plan([rs1])
    assert plan == [(rs1, 4)]                   # one full chunk, not 2


def test_scheduler_prefill_plan_full_chunks_only():
    """Every plan assignment is a whole-chunk multiple except a prompt's
    final residual — the engine pads that one up to the fixed chunk
    shape, so mid-prompt partial chunks must never be scheduled."""
    sched, bm = _sched(budget=10, chunk=4, num_blocks=33, page=4)
    rs1, rs2 = _rs("r1", 19), _rs("r2", 19)
    sched.add(rs1)
    sched.add(rs2)
    sched.admit([0, 1], now=0.0)
    for start in range(0, 19, 4):
        rs1.prefill_pos = start
        rs2.prefill_pos = 0
        for rs, n in sched.prefill_plan([rs1, rs2]):
            remaining = 19 - rs.prefill_pos
            assert n % 4 == 0 or n == remaining, (rs.req.request_id, n)


def test_scheduler_preempt_requeues_front_for_recompute():
    sched, bm = _sched(num_blocks=9, page=4)
    a, b = _rs("a", 4), _rs("b", 4)
    sched.add(a)
    sched.add(b)
    sched.admit([0, 1], now=0.0)
    b.generated = [7, 9]
    b.kv_len = 6
    held_before = bm.num_free
    assert sched.pick_victim([a, b], needy=a) is b    # latest admitted
    assert sched.pick_victim([b], needy=b) is None    # never itself
    sched.preempt(b)
    assert bm.num_free > held_before
    assert sched.waiting[0] is b and b.status is Status.WAITING
    assert list(b.work_prompt) == [0, 0, 0, 0, 7, 9]  # prompt + generated
    assert b.kv_len == 0 and b.slot is None
    assert b.metrics.n_preemptions == 1


# ---------------------------------------------------------------------------
# fast tier: request / metrics
# ---------------------------------------------------------------------------


def test_request_and_params_validation():
    with pytest.raises(ValueError):
        Request("x", np.zeros((0,), np.int32))
    with pytest.raises(ValueError):
        SamplingParams(max_new_tokens=0)
    with pytest.raises(ValueError):
        SamplingParams(temperature=-0.5)
    assert SamplingParams().greedy
    assert not SamplingParams(temperature=0.7).greedy


def test_metrics_latency_math():
    rm = RequestMetrics(arrival_time=10.0)
    rm.on_scheduled(12.0)
    rm.on_scheduled(13.0)          # first-write-wins
    for t in (15.0, 16.0, 18.0):
        rm.on_token(t)
    assert rm.ttft == 5.0 and rm.queue_time == 2.0
    assert rm.inter_token_latencies == [1.0, 2.0]
    assert rm.mean_itl == 1.5

    sm = ServeMetrics()
    sm.observe_step(queue_depth=3, running=2, kv_utilization=0.5)
    sm.observe_step(queue_depth=0, running=1, kv_utilization=0.25)
    sm.observe_finish("r", rm)
    s = sm.summary()
    assert s["max_queue_depth"] == 3
    assert s["peak_kv_utilization"] == 0.5
    assert s["mean_ttft"] == 5.0 and s["completed"] == 1
    assert s["requests"]["r"]["n_tokens"] == 3


# ---------------------------------------------------------------------------
# fast tier: shape-bucketed trace cache (the compile-stall killer)
# ---------------------------------------------------------------------------


def test_build_bucket_ladder():
    from triton_dist_tpu.serve.engine import build_bucket_ladder

    assert build_bucket_ladder(8, 63, 8) == [8, 16, 32, 64]
    assert build_bucket_ladder(16, 16, 8) == [16]
    assert build_bucket_ladder(4, 100, 8) == [8, 16, 32, 64, 104]
    ladder = build_bucket_ladder(5, 1000, 4)   # base rounds up to page
    assert ladder[0] == 8 and ladder[-1] == 1000
    assert all(r % 4 == 0 for r in ladder)
    assert all(a < b for a, b in zip(ladder, ladder[1:]))
    with pytest.raises(ValueError):
        build_bucket_ladder(0, 64, 8)


def test_counting_jit_hits_misses():
    from triton_dist_tpu.runtime.jit_cache import CountingJit

    cj = CountingJit(jax.jit(lambda x: x * 2), "dbl")
    cj(jnp.ones((4,)))
    cj(jnp.ones((4,)))                  # same shape: hit
    cj(jnp.ones((8,)))                  # new shape: miss
    assert cj.misses == 2 and cj.hits == 1
    assert cj.compile_time > 0
    s = cj.stats()
    assert s["misses"] == 2 and s["cache_size"] in (2, None)


def test_jit_cache_stats_counts_shard_jit_builds():
    from jax.sharding import PartitionSpec
    from triton_dist_tpu.runtime import jit_cache

    before = jit_cache.cache_stats()
    assert set(before) == {"hits", "misses", "currsize", "maxsize"}
    jit_cache.cached_shard_jit(_echo_builder, _MESH1, (PartitionSpec(),),
                               PartitionSpec())
    mid = jit_cache.cache_stats()
    assert mid["misses"] == before["misses"] + 1      # fresh build
    jit_cache.cached_shard_jit(_echo_builder, _MESH1, (PartitionSpec(),),
                               PartitionSpec())
    after = jit_cache.cache_stats()
    assert after["hits"] == mid["hits"] + 1           # memoized
    assert after["currsize"] == mid["currsize"]


def _echo_builder(x):
    return x


_MESH1 = Mesh(np.array(jax.devices()[:1]), ("x",))


def _tiny_model():
    """1-layer toy small enough for the tier-1 gate to compile twice."""
    cfg = llama.LlamaConfig(vocab=64, dim=16, n_layers=1, n_heads=2,
                            n_kv_heads=1, ffn_dim=32, max_seq=64,
                            dtype=jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    params = llama.init_params(cfg, jax.random.key(3))
    gen = Generator(cfg, mesh, axis="sp", max_seq=64)
    return cfg, params, gen


def _drive(eng, prompts, n_new, stagger=2):
    reqs = [Request(f"r{i}", p, SamplingParams(max_new_tokens=n_new))
            for i, p in enumerate(prompts)]
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


def test_engine_bounded_compilation_and_warmup():
    """THE tentpole acceptance test (tier-1): staggered traffic over >= 8
    DISTINCT prompt lengths compiles O(bucket-ladder) programs, not
    O(distinct shapes); a warmed engine then serves the same traffic with
    the compile-miss counter flat; and the padded/bucketed streams stay
    bit-identical to the per-request oracle."""
    cfg, params, gen = _tiny_model()
    # 10 distinct lengths: not multiples of the chunk (4) or page (4),
    # rung boundaries, rung+1, and the sub-chunk minimum.
    lens = [3, 4, 5, 7, 9, 13, 16, 17, 23, 31]
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in lens]
    n_new = 3

    eng = ServeEngine(gen, params, num_blocks=40, page_size=4,
                      max_batch=2, prefill_chunk=4, clock=_Tick())
    outs = _drive(eng, prompts, n_new)
    assert sorted(outs) == sorted(f"r{i}" for i in range(len(lens)))

    n_rungs = len(eng.ladder)             # [4, 8, 16, 32, 64] here
    assert len(set(lens)) >= 8 > n_rungs - 1
    chunk_stats = eng._chunk_fn.stats()
    assert chunk_stats["misses"] <= n_rungs, (eng.ladder, chunk_stats)
    assert eng._fill_fn.misses <= n_rungs
    assert eng._decode_fn.misses == 1     # one fixed decode shape
    # the counters ride the metrics summary / TDT_DUMP_IR path
    comp = eng.metrics.summary()["compilation"]
    assert comp["programs"]["prefill_chunk"]["misses"] <= n_rungs
    assert comp["total_misses"] == eng.metrics.compile_misses
    assert comp["total_compile_time_s"] > 0
    assert "cached_shard_jit" in comp

    # padded-final-chunk + bucketed-s_ext bit-exactness vs the oracle
    # (3 = sub-chunk, 13 = not a multiple of chunk/page, 16 = exact rung)
    for i in (0, 5, 6):
        want = _oracle(gen, params, prompts[i], n_new)
        assert outs[f"r{i}"].token_ids == want, f"r{i} (len {lens[i]})"

    # A fresh warmed engine: same traffic, zero post-warmup compiles.
    cfg2, params2, gen2 = _tiny_model()
    eng2 = ServeEngine(gen2, params2, num_blocks=40, page_size=4,
                       max_batch=2, prefill_chunk=4, clock=_Tick())
    w = eng2.warmup()
    assert w["programs"] == eng2.metrics.compile_misses > 0
    assert eng2.metrics.warmup_compiles == w["programs"]
    flat = eng2.metrics.compile_misses
    outs2 = _drive(eng2, prompts, n_new)
    assert eng2.metrics.compile_misses == flat, (
        "steady-state serving compiled after warmup: "
        f"{eng2.metrics.summary()['compilation']}")
    for rid, o in outs.items():           # same params key -> same streams
        assert outs2[rid].token_ids == o.token_ids


@pytest.mark.parametrize("budget,width,ladder", [
    (7, 7, [16, 32]),       # a call is one chunk: the ladder of old
    (None, 28, [32]),       # a call is the step's budget, 4 x 7 rows
])
def test_engine_warmup_covers_top_rung_odd_chunk(budget, width, ladder):
    """Regression: with a chunk that divides neither page nor max_seq
    (page 16, chunk 7, max_seq 16 -> ladder [16, 32] at calls of one
    chunk), the top rung is only reachable by near-max-length prompts;
    warmup's per-rung prompt picker must invert _scratch_need exactly or
    that rung stays cold and a 15-token prompt compiles on the admission
    path post-warmup.  At the default budget one call's 28 rows are past
    every prompt: the one rung that holds them is the ladder."""
    cfg = llama.LlamaConfig(vocab=64, dim=16, n_layers=1, n_heads=2,
                            n_kv_heads=1, ffn_dim=32, max_seq=16,
                            dtype=jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    params = llama.init_params(cfg, jax.random.key(3))
    gen = Generator(cfg, mesh, axis="sp", max_seq=16)
    eng = ServeEngine(gen, params, num_blocks=8, page_size=16,
                      max_batch=1, prefill_chunk=7, prefill_budget=budget,
                      clock=_Tick())
    assert eng.prefill_width == width and eng.ladder == ladder
    assert eng._bucket_s_ext(15) == 32      # roundup(15, 7) = 21 > 16
    eng.warmup()
    flat = eng.metrics.compile_misses
    p = np.arange(15, dtype=np.int32) % cfg.vocab
    eng.submit(Request("top", p, SamplingParams(max_new_tokens=1)))
    outs = eng.run()
    assert eng.metrics.compile_misses == flat, (
        eng.metrics.summary()["compilation"])
    assert outs["top"].token_ids == _oracle(gen, params, p, 1)


def test_engine_warmup_tight_pool_falls_back_to_admissible_dummy():
    """Regression: warmup's rung-16 dummy at full length + max_new=2
    (18 tokens -> 5 blocks) exceeds a 4-block pool, but a production
    request reaching that rung (prompt 15, max_new=1 -> 4 blocks) is
    still admittable — warmup must fall back to a smaller dummy rather
    than leave the rung cold."""
    cfg = llama.LlamaConfig(vocab=64, dim=16, n_layers=1, n_heads=2,
                            n_kv_heads=1, ffn_dim=32, max_seq=32,
                            dtype=jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    params = llama.init_params(cfg, jax.random.key(3))
    gen = Generator(cfg, mesh, axis="sp", max_seq=32)
    eng = ServeEngine(gen, params, num_blocks=5, page_size=4,
                      max_batch=1, prefill_chunk=4, clock=_Tick())
    assert 16 in eng.ladder
    eng.warmup()
    flat = eng.metrics.compile_misses
    p = np.arange(15, dtype=np.int32) % cfg.vocab
    eng.submit(Request("tight", p, SamplingParams(max_new_tokens=1)))
    outs = eng.run()
    assert eng.metrics.compile_misses == flat, (
        eng.metrics.summary()["compilation"])
    assert outs["tight"].token_ids == _oracle(gen, params, p, 1)


def test_engine_custom_bucket_ladder_validated():
    cfg, params, gen = _tiny_model()
    with pytest.raises(ValueError, match="bucket_ladder"):
        ServeEngine(gen, params, num_blocks=8, page_size=4, max_batch=1,
                    prefill_chunk=4, bucket_ladder=[6])   # not a page mult
    with pytest.raises(ValueError, match="bucket_ladder"):
        ServeEngine(gen, params, num_blocks=8, page_size=4, max_batch=1,
                    prefill_chunk=8, bucket_ladder=[4])   # < one chunk
    eng = ServeEngine(gen, params, num_blocks=8, page_size=4, max_batch=1,
                      prefill_chunk=4, prefill_budget=4,
                      bucket_ladder=[8, 24])
    assert eng.ladder == [8, 24, 64]      # cap appended to cover max_seq
    assert eng._bucket_s_ext(5) == 8
    assert eng._bucket_s_ext(9) == 24
    assert eng._bucket_s_ext(25) == 64
    assert eng._bucket_s_ext(63) == 64
    # a rung under one call's rows (the default budget: 4 x 4) holds no
    # call: it folds into the first that does, and brings no program
    eng = ServeEngine(gen, params, num_blocks=8, page_size=4, max_batch=1,
                      prefill_chunk=4, bucket_ladder=[4, 8, 24])
    assert eng.prefill_width == 16 and eng.ladder == [16, 24, 64]
    assert eng._bucket_s_ext(5) == 16
    assert eng._bucket_s_ext(17) == 64    # two calls of 16 rows: 32 > 24


# ---------------------------------------------------------------------------
# The width of a prefill call (ISSUE 33): a request's share of a step's
# budget is ONE ``prefill_chunk`` program call of ``prefill_width`` rows
# ---------------------------------------------------------------------------


def _wide_model(kv_dtype=None, max_seq=256):
    """1-layer toy with room for a prompt of three calls and a bit."""
    cfg = llama.LlamaConfig(vocab=64, dim=16, n_layers=1, n_heads=2,
                            n_kv_heads=1, ffn_dim=32, max_seq=max_seq,
                            dtype=jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    params = llama.init_params(cfg, jax.random.key(3))
    gen = Generator(cfg, mesh, axis="sp", max_seq=max_seq,
                    kv_dtype=kv_dtype)
    return cfg, params, gen


def _tap_prefill(eng):
    """Record every ``prefill_chunk`` call of ``eng``: (rows of the token
    buffer, scratch extent, where the call's rows start, rows fed)."""
    calls, seam = [], eng._device_call

    def tapped(op, rids, fn, *a, **kw):
        if op == "prefill_chunk":
            calls.append((a[1].shape[1], kw["extent"], int(a[3]),
                          abs(int(kw["n_valid"]))))
        return seam(op, rids, fn, *a, **kw)

    eng._device_call = tapped
    return calls


def test_prefill_width_rule():
    """``W = max(chunk, min(budget, 256))`` in whole chunks: the Mistral
    cells (chunk 128, budget 512) get 256, the ridge; an engine whose
    chunk is at or past it (gc3: 512 / 2,048; glm5, mellum2: 2,048 /
    16,384), or whose budget is one chunk or less, keeps calls of one
    chunk."""
    from triton_dist_tpu.serve.engine import prefill_width

    assert prefill_width(128, 512) == 256
    assert prefill_width(128, 4096) == 256      # capped at the ridge
    assert prefill_width(96, 400) == 192        # whole chunks only
    assert prefill_width(64, 256) == 256        # the engine's defaults
    assert prefill_width(16, 64) == 64 and prefill_width(4, 16) == 16
    for chunk, budget in ((512, 2048), (2048, 16384), (200, 800),
                          (256, 1024), (64, 64), (64, 32), (7, 7)):
        assert prefill_width(chunk, budget) == chunk


@pytest.mark.parametrize("warm", [False, True], ids=["cold", "warm"])
@pytest.mark.parametrize("kv_dtype", [None, jnp.int8], ids=["float", "int8"])
def test_wide_prefill_calls_serve_the_streams_of_chunk_wide_ones(kv_dtype,
                                                                 warm):
    """An engine at chunk 16 / budget 64 (a call is 64 rows) serves a mix
    of greedy requests token-for-token as the same engine at budget 16 (a
    call is one chunk) does — prompts of 1, W - 1, W, W + 1 and 3 W + 5
    tokens, three slots so that requests start on a step's leftover
    budget and carry an offset to their last call — over float and int8
    pools, and with a warm prefix (the prompts past two pages open with
    the same 32 tokens, which a primer served first leaves in the content
    index: their prefill starts at row 32, off the width's grid)."""
    cfg, params, gen = _wide_model(kv_dtype)
    W = 64
    lens = [1, W - 1, W, W + 1, 3 * W + 5]
    rng = np.random.default_rng(5)
    base = rng.integers(0, cfg.vocab, size=40).astype(np.int32)
    prompts = [np.concatenate([base[:32], rng.integers(
        0, cfg.vocab, size=max(n - 32, 0)).astype(np.int32)])[:n]
        for n in lens]
    served = {}
    for budget in (64, 16):
        eng = ServeEngine(gen, params, num_blocks=80, page_size=16,
                          max_batch=3, prefill_chunk=16,
                          prefill_budget=budget, prefix_cache=warm,
                          clock=_Tick())
        assert eng.prefill_width == budget
        assert eng.kv_quant == (kv_dtype is not None)
        if warm:
            eng.submit(Request("primer", base,
                               SamplingParams(max_new_tokens=2)))
            eng.run()
        calls = _tap_prefill(eng)
        outs = _drive(eng, prompts, 5, stagger=1)
        assert all(c[0] == budget for c in calls)
        served[budget] = ({r: o.token_ids for r, o in outs.items()},
                          len(calls), eng.metrics.summary())
        assert eng.bm.num_free == eng.bm.num_allocatable
    (wide, n_wide, s_wide), (narrow, n_narrow, s_narrow) = (served[64],
                                                            served[16])
    assert wide == narrow and len(wide) == len(lens)
    assert s_wide["prefill"]["width"] == 64
    assert s_narrow["prefill"]["width"] == 16
    assert s_wide["prefill"]["tokens"] == s_narrow["prefill"]["tokens"]
    assert n_wide < n_narrow / 2
    if warm:
        assert s_wide["prefix_cache"]["prefix_skipped_tokens"] > 0
        assert (s_wide["prefix_cache"]["prefix_skipped_tokens"]
                == s_narrow["prefix_cache"]["prefix_skipped_tokens"])


def test_prefill_summary_counts_calls_and_padding():
    """``summary()["prefill"]``: one call for every ``W`` rows (or part)
    of each share the scheduler assigned, and the rows of those calls
    that prefilled nothing, counted by hand from the plan."""
    cfg, params, gen = _wide_model()
    eng = ServeEngine(gen, params, num_blocks=80, page_size=16, max_batch=3,
                      prefill_chunk=16, prefill_budget=128,
                      prefix_cache=False, clock=_Tick())
    W = eng.prefill_width
    assert W == 128 and eng.metrics.summary()["prefill"] == {
        "tokens": 0, "dispatches": 0, "tokens_per_dispatch": 0.0,
        "pad_share": 0.0, "tail_rows": 0, "width": 128,
        "scratch_dispatches": 0}
    shares, plan = [], eng.scheduler.prefill_plan

    def recorded(prefilling):
        out = plan(prefilling)
        shares.extend(n for _, n in out)
        return out

    eng.scheduler.prefill_plan = recorded
    rng = np.random.default_rng(9)
    lens = [200, 37, 129, 16, 90]
    _drive(eng, [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
                 for n in lens], 3, stagger=1)
    got = eng.metrics.summary()["prefill"]
    n_calls = sum(-(-n // W) for n in shares)
    assert sum(shares) == sum(lens) and n_calls > len(lens)
    assert got["tokens"] == sum(lens) and got["dispatches"] == n_calls
    assert got["tokens_per_dispatch"] == pytest.approx(sum(lens) / n_calls)
    assert got["pad_share"] == pytest.approx(
        (n_calls * W - sum(lens)) / (n_calls * W))
    assert eng._chunk_fn.stats()["hits"] + eng._chunk_fn.misses == n_calls
    text = eng.metrics.to_prometheus()
    assert f"serve_prefill_dispatches_total {n_calls}" in text
    assert (f"serve_prefill_pad_tokens_total "
            f"{n_calls * W - sum(lens)}") in text
    # ONE row a prompt goes past the last layer that writes, and the head
    assert got["tail_rows"] == len(lens)
    assert f"serve_prefill_tail_rows_total {len(lens)}" in text
    # one launch a cold request on its way in, whatever its planes
    assert got["scratch_dispatches"] == len(lens)
    assert f"serve_prefill_scratch_dispatches_total {len(lens)}" in text
    # the fleet's aggregate adds the counters and keeps the width
    both = ServeMetrics().merge(eng.metrics).merge(eng.metrics)
    assert both.prefill_stats()["dispatches"] == 2 * n_calls
    assert both.prefill_stats()["scratch_dispatches"] == 2 * len(lens)
    assert both.prefill_stats()["tail_rows"] == 2 * len(lens)
    assert both.prefill_stats()["width"] == W
    assert both.prefill_stats()["pad_share"] == got["pad_share"]


@pytest.mark.parametrize("all_rows", [False, True],
                         ids=["kept_row", "parents_program"])
def test_prefill_tail_rows_and_first_tokens(monkeypatch, all_rows):
    """``summary()["prefill"]["tail_rows"]`` counts the rows of the logits
    a prompt's LAST ``prefill_chunk`` call returned (its other calls say by
    the sign of ``n_valid`` that nobody reads theirs, and skip the head):
    ONE a finished prefill of the program that keeps the row the engine
    reads, ``W`` of a program that carries every row to the head, as the
    parent's did on every call (the layer loop told of no row to keep).
    And every request's greedy first token is the parent's: the argmax of
    the last valid row of the all-rows program on the finishing call's own
    inputs."""
    from chunk_rows import all_chunk_rows, keep_no_row

    if all_rows:
        keep_no_row(monkeypatch)
    cfg, params, gen = _wide_model()
    eng = ServeEngine(gen, params, num_blocks=80, page_size=16, max_batch=3,
                      prefill_chunk=16, prefill_budget=128,
                      prefix_cache=False, clock=_Tick())
    W, parents, seam = eng.prefill_width, {}, eng._device_call

    def tapped(op, rids, fn, *a, **kw):
        if op == "prefill_chunk" and not all_rows and kw["n_valid"] > 0:
            assert rids[0] not in parents       # ONE call a prompt is read
            parents[rids[0]] = int(all_chunk_rows(fn, a, kw)[
                int(kw["n_valid"]) - 1].argmax())
        return seam(op, rids, fn, *a, **kw)

    eng._device_call = tapped
    rng = np.random.default_rng(42)
    lens = [200, 37, 129, 16, 90, 128]
    outs = _drive(eng, [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
                        for n in lens], 2, stagger=1)
    got = eng.metrics.summary()["prefill"]
    assert got["tokens"] == sum(lens) and got["dispatches"] > len(lens)
    if all_rows:
        assert got["tail_rows"] == len(lens) * W
        return
    assert got["tail_rows"] == len(lens)
    assert {rid: o.token_ids[0] for rid, o in outs.items()} == parents
    assert len(set(parents.values())) > 1


@pytest.mark.parametrize("page,chunk,budget,max_seq", [
    (128, 512, 2048, 2048),     # gc3's shape: the chunk is past the ridge
    (16, 16, 16, 256),          # a budget of one chunk
    (16, 32, 8, 256),           # a budget under one chunk
])
def test_an_engine_whose_call_is_one_chunk_is_as_it_was(page, chunk, budget,
                                                        max_seq):
    """Where ``W == prefill_chunk`` the mechanism does not engage: the
    ladder is the one the chunk built, a prompt's scratch the rung it had,
    and every call ``[1, chunk]`` from a chunk multiple — the same program
    keys as before the width existed."""
    from triton_dist_tpu.serve.engine import build_bucket_ladder

    cfg, params, gen = _wide_model(max_seq=max_seq)
    eng = ServeEngine(gen, params, num_blocks=2 * max_seq // page + 4,
                      page_size=page, max_batch=2, prefill_chunk=chunk,
                      prefill_budget=budget, clock=_Tick())
    assert eng.prefill_width == chunk
    assert eng.metrics.summary()["prefill"]["width"] == chunk

    def need(n):            # the sizing formula as it was: pages or chunks
        return max(-(-n // page) * page, -(-n // chunk) * chunk)

    assert eng.ladder == build_bucket_ladder(
        max(page, chunk), need(max_seq - 1), page)
    calls = _tap_prefill(eng)
    rng = np.random.default_rng(2)
    lens = [1, chunk - 1, chunk + 1, max_seq // 2 + 3]
    _drive(eng, [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
                 for n in lens], 2, stagger=1)
    want = sorted((chunk, next(r for r in eng.ladder if r >= need(n)),
                   at, min(chunk, n - at))
                  for n in lens for at in range(0, n, chunk))
    assert sorted(calls) == want
    keys = {(c[0], c[1]) for c in calls}
    assert eng._chunk_fn.misses == len(keys)
    assert eng.metrics.summary()["prefill"]["dispatches"] == len(want)


def test_after_warmup_no_prompt_length_compiles():
    """Every prompt length an engine admits, 1 .. max_seq - 1, served
    after ``warmup()`` — two slots, so that requests start on leftover
    budget and their calls slide back at the scratch's end — compiles
    nothing: one ``[1, W]`` program a rung covers them all."""
    cfg, params, gen = _tiny_model()
    eng = ServeEngine(gen, params, num_blocks=80, page_size=4, max_batch=2,
                      prefill_chunk=4, clock=_Tick())
    assert eng.prefill_width == 16 and eng.ladder == [16, 32, 64]
    eng.warmup()
    flat = eng.metrics.compile_misses
    calls = _tap_prefill(eng)
    slid, window = [], eng._call_window

    def watched(pos, s_ext):
        at = window(pos, s_ext)
        slid.append(pos - at)
        return at

    eng._call_window = watched
    rng = np.random.default_rng(4)
    lens = list(range(1, cfg.max_seq))
    outs = _drive(eng, [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
                        for n in lens],
                  1, stagger=1)
    assert len(outs) == len(lens)
    assert eng.metrics.compile_misses == flat, (
        eng.metrics.summary()["compilation"])
    assert {c[0] for c in calls} == {16}
    assert {c[1] for c in calls} == set(eng.ladder)
    # the sweep reached calls that start off the width's multiples, and
    # ones whose window slid back from where their tokens start
    assert any(at % 16 for _, _, at, _ in calls)
    assert len(slid) == len(calls) and 0 < sum(1 for d in slid if d) \
        < len(calls) / 4
    assert all(at + 16 <= ext for _, ext, at, _ in calls)


@pytest.mark.parametrize("page,chunk,budget", [
    (4, 4, None), (16, 7, None), (16, 16, 64), (8, 32, None), (4, 4, 4),
    (16, 4, 24),
])
def test_no_prefill_call_writes_past_its_scratch(page, chunk, budget):
    """Property: for every admissible prompt and every chunk-multiple
    position a call may start from (budget is metered in chunks: warm
    starts and leftover budget put a request anywhere on that grid), the
    call's ``W``-row window lies inside the scratch ``_scratch_need``
    sized, holds the tokens it prefills, and moves back only where the
    request left the width's grid — ``dynamic_update_slice`` never gets to
    clamp."""
    cfg, params, gen = _tiny_model()
    eng = ServeEngine(gen, params, num_blocks=40, page_size=page,
                      max_batch=1, prefill_chunk=chunk,
                      prefill_budget=budget, clock=_Tick())
    W = eng.prefill_width
    assert eng.ladder[0] >= W
    for n in range(1, cfg.max_seq):
        assert eng._scratch_need(n) >= max(n, -(-n // W) * W)
        ext = eng._bucket_s_ext(n)
        assert ext >= eng._scratch_need(n) and ext % page == 0
        for pos in range(0, n, chunk):
            c = min(W, n - pos)
            at = eng._call_window(pos, ext)
            assert 0 <= at <= pos and at + W <= ext
            assert pos + c <= at + W            # the new tokens fit
            if pos % W == 0:
                assert at == pos                # sized for these as is
            if at < pos:
                assert at + W == ext            # slid back to the end only


def test_attention_kernel_gaps_names_every_silent_xla_reroute():
    """Kernel reach (docs/serving.md): under ``impl="auto"`` a geometry
    the attention kernels cannot tile reroutes to XLA without a word, so
    the engine computes — from the dispatchers' own guards — which of its
    attention paths will miss the Pallas kernels, and why."""
    from triton_dist_tpu.serve.engine import attention_kernel_gaps

    kw = dict(head_dim=128, page_size=128, prefill_chunk=128,
              ladder=[128, 256, 512], kv_itemsize=2, kv_quant=False,
              impl="auto", interpret=True)
    assert attention_kernel_gaps(**kw) == {}          # both kernels reach
    gaps = attention_kernel_gaps(**{**kw, "page_size": 16})
    assert set(gaps) == {"paged_decode"} and "page=16" in gaps["paged_decode"]
    gaps = attention_kernel_gaps(**{**kw, "prefill_chunk": 64})
    assert set(gaps) == {"prefill_chunk"} and "chunk=64" in \
        gaps["prefill_chunk"]
    # chunks <= 32 ride the decode kernel: reach again at a 128-row extent
    assert attention_kernel_gaps(**{**kw, "prefill_chunk": 32}) == {}
    gaps = attention_kernel_gaps(**{**kw, "head_dim": 64})
    assert set(gaps) == {"paged_decode", "prefill_chunk"}
    gaps = attention_kernel_gaps(**{**kw, "kv_quant": True})
    assert set(gaps) == {"paged_decode"} and "int8" in gaps["paged_decode"]
    # seq layouts attend over a 1/sp row span of each extent rung
    gaps = attention_kernel_gaps(**{**kw, "sp_world": 4})
    assert "[128, 256]" in gaps["prefill_chunk"], gaps
    assert attention_kernel_gaps(**{**kw, "sp_world": 4,
                                    "ladder": [512, 1024]}) == {}
    # dispatch itself: xla by request, or auto off a TPU with no
    # interpreter (this CPU host) — every path, with the reason
    for impl, why in (("xla", "asked for"), ("auto", "off a TPU")):
        gaps = attention_kernel_gaps(**{**kw, "impl": impl,
                                        "interpret": False})
        assert set(gaps) == {"paged_decode", "prefill_chunk"}
        assert all(why in g for g in gaps.values()), gaps


def test_warmup_raises_when_a_program_cannot_run():
    """Containment quarantines a failing request and serves on; under
    warm-up that would turn 'the prefill program does not compile' into a
    warm-up that returns normally with nothing warmed (seen on the chip,
    PR 21).  warmup() raises instead, naming the first failure."""
    cfg, params, gen = _tiny_model()
    eng = ServeEngine(gen, params, num_blocks=8, page_size=4, max_batch=1,
                      prefill_chunk=4)

    def broken(*a, **k):
        raise ValueError("RESOURCE_EXHAUSTED: scoped vmem")

    eng._chunk_fn = broken
    with pytest.raises(RuntimeError, match="warm-up requests failed.*vmem"):
        eng.warmup()
    assert not eng.has_work() and eng.bm.num_free == eng.bm.num_allocatable


def test_engine_exposes_its_kernel_gaps_in_the_summary():
    cfg, params, gen = _tiny_model()
    eng = ServeEngine(gen, params, num_blocks=8, page_size=4, max_batch=1,
                      prefill_chunk=4)
    assert set(eng.kernel_gaps) == {"paged_decode", "prefill_chunk"}
    assert eng.metrics.summary()["kernel_gaps"] == eng.kernel_gaps
    # off the paged kernel there is no blocking to report
    assert eng.paged_attn_blocking == {}
    text = eng.metrics.to_prometheus()
    assert "serve_paged_attn_heads_per_step 0" in text
    assert "serve_paged_attn_pages_in_flight 0" in text


@pytest.mark.parametrize("tp,want_heads", [(1, 2), (2, 1)])
def test_engine_reports_the_paged_attention_blocking(tp, want_heads):
    """The start-up report of the paged decode call (ISSUE 25): static,
    so computed once where the programs are built — for the KV heads THIS
    rank holds — and exposed in the summary and as three gauges (the
    third, ISSUE 48: the slots of the kernel's page ring, what the call
    was built with)."""
    cfg = llama.LlamaConfig(vocab=64, dim=512, n_layers=1, n_heads=4,
                            n_kv_heads=2, ffn_dim=64, max_seq=256,
                            dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.key(3))
    gen = Generator(cfg, Mesh(np.array(jax.devices()[:1]), ("sp",)),
                    axis="sp", max_seq=256, interpret=True)
    mesh = {} if tp == 1 else dict(
        mesh=Mesh(np.array(jax.devices()[:tp]), ("tp",)), tp_axis="tp",
        kv_shard="heads")
    eng = ServeEngine(gen, params, num_blocks=8, page_size=128, max_batch=4,
                      prefill_chunk=128, **mesh)
    assert eng.kernel_gaps == {}
    slots = fd.paged_pages_in_flight(want_heads, 128, 128, 4)
    want = {"heads_per_step": want_heads,
            "steps_per_call": 4 * (2 // tp) // want_heads,
            "pages_per_step": "dynamic",
            "pages_in_flight": slots,
            "vmem_bytes": slots * 2 * want_heads * 128 * 128 * 4}
    assert slots >= 2
    assert eng.paged_attn_blocking == want
    assert eng.metrics.summary()["paged_attn_blocking"] == want
    text = eng.metrics.to_prometheus()
    assert f"serve_paged_attn_heads_per_step {want_heads}" in text
    assert f"serve_paged_attn_steps_per_call {want['steps_per_call']}" in text
    assert f"serve_paged_attn_pages_in_flight {slots}" in text


# ---------------------------------------------------------------------------
# fast tier: r5-advisor regressions
# ---------------------------------------------------------------------------


def test_write_rows_skips_overflowing_rows():
    """A retired row whose offset + T overflows the cache must be left
    UNTOUCHED (dynamic_update_slice would clamp the offset and corrupt
    still-valid rows; ADVICE r5 #2)."""
    cache = jnp.arange(2 * 1 * 8 * 2, dtype=jnp.float32).reshape(2, 1, 8, 2)
    new = -jnp.ones((2, 1, 4, 2), jnp.float32)
    out = _write_rows(cache, new, jnp.array([2, 6], jnp.int32))
    out = np.asarray(out)
    # row 0 (fits): rows [2, 6) overwritten
    assert (out[0, 0, 2:6] == -1).all()
    assert (out[0, 0, :2] == np.asarray(cache)[0, 0, :2]).all()
    # row 1 (6 + 4 > 8): untouched, NOT clamped into rows [4, 8)
    assert (out[1] == np.asarray(cache)[1]).all()


@pytest.mark.parametrize("redirect", [False, True],
                         ids=["all_active", "some_to_null"])
@pytest.mark.parametrize("n_tok", [None, 3], ids=["B", "BT"])
@pytest.mark.parametrize("kind", ["bf16", "int8", "latent"])
def test_scatter_kv_equals_a_row_by_row_write(kind, n_tok, redirect):
    """``_scatter_kv`` writes through each plane's merged block-and-head
    view; what lands is, bit for bit, what a plain numpy loop over
    (row, head) puts at ``plane[pool_row, head, in_page]`` — decode
    ``[B]`` and verify ``[B, T]`` indices (a verify chunk crossing a page
    boundary), float, int8 (bytes and scales) and one-head latent
    planes, with and without rows redirected to the null block — and
    every other slot of the pool keeps its bytes."""
    from triton_dist_tpu.kernels.flash_decode import quantize_kv
    from triton_dist_tpu.serve.programs import _scatter_kv

    NB, page, D, B = 11, 4, 16, 4
    hk = 1 if kind == "latent" else 3
    rng = np.random.default_rng(7)

    def plane(dtype, *tail):
        x = rng.integers(-100, 100, (NB, hk, page) + tail)
        return jnp.asarray(x, dtype)

    def layer():
        if kind == "int8":
            return {"q": plane(jnp.int8, D), "s": plane(jnp.float32)}
        return plane(jnp.bfloat16, D)

    pool = (layer(),) if kind == "latent" else (layer(), layer())
    # row b owns blocks 1+2b, 2+2b and stands at position 2 + b % 3 of
    # its first page, so a 3-token chunk crosses into the second
    T = n_tok or 1
    pos = (2 + np.arange(B) % 3)[:, None] + np.arange(T)[None]
    pool_row = 1 + 2 * np.arange(B)[:, None] + pos // page
    in_page = pos % page
    active = np.array([True, False, True, False]) if redirect \
        else np.ones(B, bool)
    pool_row = np.where(active[:, None], pool_row, 0)
    in_page = np.where(active[:, None], in_page, 0)
    if n_tok is None:
        pool_row, in_page = pool_row[:, 0], in_page[:, 0]
    lead = pool_row.shape
    k = jnp.asarray(rng.standard_normal(lead + (hk, D)), jnp.float32)
    v = None if kind == "latent" else jnp.asarray(
        rng.standard_normal(lead + (hk, D)), jnp.float32)

    # op by op, as the reference's quantize_kv below runs: a fused
    # absmax / 127 may round the scale's last bit another way
    got = _scatter_kv(pool, k, v, jnp.asarray(pool_row, jnp.int32),
                      jnp.asarray(in_page, jnp.int32))

    def rows_of(x):
        # the planes of one K or V layer and the rows each receives
        if kind == "int8":
            q, s = quantize_kv(x)
            return {"q": q, "s": s}
        return x.astype(jnp.bfloat16)

    want = jax.tree.map(lambda p: np.array(p), pool)
    new = tuple(rows_of(x) for x in (k, v) if x is not None)
    for w, n in zip(jax.tree.leaves(want), jax.tree.leaves(new)):
        n = np.asarray(n)
        for i in np.ndindex(*lead):
            for h in range(hk):
                w[pool_row[i], h, in_page[i]] = n[i][h]
    assert jax.tree.structure(got) == jax.tree.structure(pool)
    for g, w, p in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                       jax.tree.leaves(pool)):
        g, p = np.array(g), np.asarray(p)
        assert g.dtype == w.dtype and g.shape == w.shape
        # several rows land on the null slot: which one stays is not the
        # write's to say (nothing reads block 0)
        g[0, :, 0], w[0, :, 0] = 0, 0
        assert g.tobytes() == w.tobytes()
        untouched = np.setdiff1d(np.arange(1, NB), pool_row)
        assert untouched.size
        assert g[untouched].tobytes() == p[untouched].tobytes()


def test_sp_paged_decode_accepts_multi_token_q(mesh2):
    """The paged SP decode now honours the 4D-q / q_lens contract
    (ISSUE-19 debt (a)): [B, T, Hq, D] partials combine as a B*T batch.
    Bit-exactness vs the unsharded oracle lives in test_serve_mesh.py;
    here we pin the shape contract and that dead rows stay finite."""
    from triton_dist_tpu.kernels.flash_decode import (
        sp_gqa_decode_paged_shard)

    q4 = jnp.ones((1, 2, 2, 8), jnp.float32)            # [B, T, Hq, D]
    pool = jnp.ones((4, 1, 8, 8), jnp.float32)
    table = jnp.zeros((1, 2), jnp.int32)
    lens = jnp.array([8], jnp.int32)
    fn = jax.shard_map(
        functools.partial(sp_gqa_decode_paged_shard, axis="tp",
                          impl="xla"),
        mesh=mesh2, in_specs=(P(), P("tp"), P("tp"), P(), P()),
        out_specs=P(), check_vma=False)
    out = fn(q4, pool, pool, table, lens)
    assert out.shape == (1, 2, 2, 8)
    assert bool(jnp.isfinite(out).all())


# ---------------------------------------------------------------------------
# slow tier: the engine end-to-end (tiny Llama, world-1 CPU mesh)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def mesh1():
    return Mesh(np.array(jax.devices()[:1]), ("sp",))


@pytest.fixture(scope="module")
def model(mesh1):
    cfg = llama.LlamaConfig(vocab=128, dim=32, n_layers=2, n_heads=2,
                            n_kv_heads=1, ffn_dim=64, max_seq=64,
                            dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.key(0))
    gen = Generator(cfg, mesh1, axis="sp", max_seq=64)
    return cfg, params, gen


class _Tick:
    """Deterministic engine clock: +1 per reading."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


def _oracle(gen, params, prompt, n_new):
    """Per-request greedy reference: dedicated prefill + decode."""
    st = gen.prefill(params, jnp.asarray(np.asarray(prompt)[None]))
    toks, _ = gen.generate(params, st, n_new)
    return [int(t) for t in np.asarray(toks[0])]


@pytest.mark.slow
def test_engine_staggered_arrivals_match_oracle(model):
    """THE acceptance test: >= 8 requests, staggered arrivals, mixed
    prompt lengths, continuous batching over the paged cache — every
    request's greedy stream must be bit-identical to its dedicated
    `Generator.generate`, and TTFT/ITL/KV-utilization must come out
    non-trivial."""
    cfg, params, gen = model
    rng = np.random.default_rng(42)
    lens = [4, 11, 7, 16, 5, 9, 13, 6, 20]          # 9 requests, mixed
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in lens]
    n_new = 8
    eng = ServeEngine(gen, params, num_blocks=24, page_size=8,
                      max_batch=3, prefill_chunk=4, prefill_budget=8,
                      clock=_Tick())
    # Staggered: two up front, one more every other step.
    pending = [Request(f"r{i}", p,
                       SamplingParams(max_new_tokens=n_new))
               for i, p in enumerate(prompts)]
    for r in pending[:2]:
        eng.submit(r)
    submitted, step, finished = 2, 0, []
    while eng.has_work() or submitted < len(pending):
        if step % 2 == 0 and submitted < len(pending):
            eng.submit(pending[submitted])
            submitted += 1
        finished.extend(eng.step())
        step += 1
        assert step < 500
    assert sorted(o.request_id for o in finished) == sorted(
        f"r{i}" for i in range(len(prompts)))

    for i, p in enumerate(prompts):
        out = next(o for o in finished if o.request_id == f"r{i}")
        assert out.token_ids == _oracle(gen, params, p, n_new), (
            f"r{i} diverged from its dedicated-decode oracle")
        assert out.finish_reason is FinishReason.LENGTH
        assert out.metrics.ttft is not None and out.metrics.ttft > 0
        assert len(out.metrics.inter_token_latencies) == n_new - 1
        assert all(x > 0 for x in out.metrics.inter_token_latencies)

    s = eng.metrics.summary()
    assert s["completed"] == len(prompts)
    assert s["max_queue_depth"] >= 1          # 9 requests through 3 slots
    assert 0 < s["peak_kv_utilization"] <= 1
    assert s["mean_ttft"] > 0 and s["mean_itl"] > 0
    assert s["prefill_tokens"] == sum(lens)
    assert s["decode_steps"] > 0


@pytest.mark.slow
def test_engine_block_exhaustion_queues(model):
    """A pool that fits ~one request at a time forces queueing (not
    crashes, not corruption): admission control holds the line."""
    cfg, params, gen = model
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab, size=10).astype(np.int32)
               for _ in range(3)]
    # Each request spans blocks_for(10 + 6) = 2 pages of 8 (+1 headroom
    # block at admission); 4 allocatable blocks => ~one at a time.
    eng = ServeEngine(gen, params, num_blocks=5, page_size=8,
                      max_batch=3, prefill_chunk=8, clock=_Tick())
    for i, p in enumerate(prompts):
        eng.submit(Request(f"q{i}", p, SamplingParams(max_new_tokens=6)))
    outs = eng.run()
    for i, p in enumerate(prompts):
        assert outs[f"q{i}"].token_ids == _oracle(gen, params, p, 6)
    assert eng.metrics.summary()["max_queue_depth"] >= 1
    assert all(s is None for s in eng.slots)
    assert eng.bm.num_free == eng.bm.num_allocatable  # everything freed


@pytest.mark.slow
def test_engine_preemption_recompute_exact(model):
    """Decode-time block exhaustion preempts the latest-admitted request
    (recompute-style); its stream must still be bit-exact."""
    cfg, params, gen = model
    rng = np.random.default_rng(2)
    p0 = rng.integers(0, cfg.vocab, size=16).astype(np.int32)
    p1 = rng.integers(0, cfg.vocab, size=16).astype(np.int32)
    # Each grows to blocks_for(32) = 4 pages; 6 allocatable can admit
    # both (3 + 3) but cannot hold both at full length -> preemption.
    eng = ServeEngine(gen, params, num_blocks=7, page_size=8,
                      max_batch=2, prefill_chunk=8, clock=_Tick())
    eng.submit(Request("a", p0, SamplingParams(max_new_tokens=16)))
    eng.submit(Request("b", p1, SamplingParams(max_new_tokens=16)))
    outs = eng.run()
    assert eng.metrics.preemptions >= 1
    assert outs["b"].metrics.n_preemptions >= 1   # LIFO: b is the victim
    assert outs["a"].token_ids == _oracle(gen, params, p0, 16)
    assert outs["b"].token_ids == _oracle(gen, params, p1, 16)


@pytest.mark.slow
def test_engine_retire_and_join_midflight(model):
    """Rows retire individually and queued requests join the running
    batch mid-flight (iteration-level batching, not batch-at-a-time)."""
    cfg, params, gen = model
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (6, 6, 6, 6)]
    new = [3, 12, 5, 8]                       # retire at different steps
    eng = ServeEngine(gen, params, num_blocks=24, page_size=8,
                      max_batch=2, prefill_chunk=8, clock=_Tick())
    for i, (p, n) in enumerate(zip(prompts, new)):
        eng.submit(Request(f"m{i}", p, SamplingParams(max_new_tokens=n)))
    outs = eng.run()
    for i, (p, n) in enumerate(zip(prompts, new)):
        assert outs[f"m{i}"].token_ids == _oracle(gen, params, p, n)
    # 4 requests through 2 slots: some had to wait for a retirement,
    # and the batch kept running while they joined.
    assert eng.metrics.summary()["max_queue_depth"] >= 1
    first_finish = min(m.finish_time
                       for m in (outs[f"m{i}"].metrics for i in range(4)))
    last_start = max(m.first_scheduled_time
                     for m in (outs[f"m{i}"].metrics for i in range(4)))
    assert last_start > first_finish          # a join AFTER a retirement


@pytest.mark.slow
def test_engine_speculative_rounds_match_greedy(model):
    """Speculative engine mode (draft + paged multi-token verify) emits
    the exact greedy stream, in fewer decode iterations."""
    cfg, params, gen = model
    dcfg = llama.LlamaConfig(vocab=cfg.vocab, dim=16, n_layers=1,
                             n_heads=1, n_kv_heads=1, ffn_dim=32,
                             max_seq=64, dtype=jnp.float32)
    d_params = llama.init_params(dcfg, jax.random.key(7))
    draft = Generator(dcfg, gen.mesh, axis="sp", max_seq=64)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (5, 9, 3, 12)]
    n_new = 7
    eng = ServeEngine(gen, params, num_blocks=40, page_size=8,
                      max_batch=3, prefill_chunk=8, draft=draft,
                      draft_params=d_params, spec_k=3, clock=_Tick())
    for i, p in enumerate(prompts):
        eng.submit(Request(f"s{i}", p,
                           SamplingParams(max_new_tokens=n_new)))
    outs = eng.run()
    for i, p in enumerate(prompts):
        assert outs[f"s{i}"].token_ids == _oracle(gen, params, p, n_new)
    assert eng.metrics.verify_rounds >= 1
    # sampled requests ride the seeded accept chain
    # (tests/test_serve_spec.py)
    assert eng.submit(Request("ok", prompts[0],
                              SamplingParams(max_new_tokens=2,
                                             temperature=0.5,
                                             seed=3))) is None
    eng.run()


@pytest.mark.slow
def test_engine_abort_paths(model):
    """abort() from every state: WAITING (dequeue, no blocks held),
    RUNNING (slot + blocks released), and FINISHED (output passthrough)
    — the pool must come back whole and the batch keeps serving."""
    cfg, params, gen = model
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab, size=6).astype(np.int32)
               for _ in range(3)]
    eng = ServeEngine(gen, params, num_blocks=6, page_size=8,
                      max_batch=1, prefill_chunk=8, clock=_Tick())
    for i, p in enumerate(prompts):
        eng.submit(Request(f"a{i}", p, SamplingParams(max_new_tokens=6)))
    eng.step()                       # a0 admitted+running, a1/a2 queued
    waiting = eng.abort("a1")        # WAITING: dequeued, no blocks held
    assert waiting.finish_reason is FinishReason.ABORT
    assert eng.scheduler.queue_depth == 1
    running = eng.abort("a0")        # RUNNING: slot + blocks released
    assert running.finish_reason is FinishReason.ABORT
    assert len(running.token_ids) >= 1          # partial output kept
    assert eng.bm.num_free == eng.bm.num_allocatable
    assert all(s is None for s in eng.slots)
    outs = eng.run()                 # a2 still serves to completion
    assert outs["a2"].token_ids == _oracle(gen, params, prompts[2], 6)
    assert eng.abort("a2") is outs["a2"]        # FINISHED: passthrough
    assert eng.abort("nope") is None


@pytest.mark.slow
def test_engine_spec_capacity_capped_at_admitted_total(model):
    """A request submit() admitted (prompt + max_new fits the pool
    exactly) must run to completion in spec mode: the round's capacity
    reservation is capped at the admitted total instead of demanding
    kv_len + k + 1 rows it can never emit into (which used to raise
    'pool too small' near the end of generation)."""
    cfg, params, gen = model
    dcfg = llama.LlamaConfig(vocab=cfg.vocab, dim=16, n_layers=1,
                             n_heads=1, n_kv_heads=1, ffn_dim=32,
                             max_seq=64, dtype=jnp.float32)
    d_params = llama.init_params(dcfg, jax.random.key(9))
    draft = Generator(dcfg, gen.mesh, axis="sp", max_seq=64)
    rng = np.random.default_rng(8)
    p = rng.integers(0, cfg.vocab, size=16).astype(np.int32)
    # total = 16 + 16 = 32 tokens = exactly 2 pages of 16; the pool has
    # exactly 2 allocatable blocks.
    eng = ServeEngine(gen, params, num_blocks=3, page_size=16,
                      max_batch=1, prefill_chunk=8, draft=draft,
                      draft_params=d_params, spec_k=2, clock=_Tick())
    eng.submit(Request("cap", p, SamplingParams(max_new_tokens=16)))
    outs = eng.run()
    assert outs["cap"].token_ids == _oracle(gen, params, p, 16)
    assert eng.metrics.preemptions == 0


@pytest.mark.slow
def test_engine_warmup_padded_buckets_oracle(model):
    """Warmed engine + tight pool: mixed non-multiple prompt lengths ride
    the padded-final-chunk and bucketed-s_ext paths through queueing AND
    preemption-recompute, stay bit-exact, and never compile after
    warmup."""
    cfg, params, gen = model
    rng = np.random.default_rng(21)
    lens = [1, 5, 7, 9, 13, 15, 17, 21]     # none a multiple of chunk=4
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in lens]
    n_new = 6
    # 11 allocatable blocks of 8 ≈ two max-size requests -> queueing and
    # decode-time extension pressure.
    eng = ServeEngine(gen, params, num_blocks=12, page_size=8,
                      max_batch=3, prefill_chunk=4, prefill_budget=8,
                      clock=_Tick())
    eng.warmup()
    flat = eng.metrics.compile_misses
    outs = _drive(eng, prompts, n_new)
    assert eng.metrics.compile_misses == flat, (
        eng.metrics.summary()["compilation"])
    for i, p in enumerate(prompts):
        assert outs[f"r{i}"].token_ids == _oracle(gen, params, p, n_new), (
            f"r{i} (len {lens[i]}) diverged")


@pytest.mark.slow
def test_engine_speculative_warmup_compile_free(model):
    """Speculative engine mode: warmup covers the verify pass, the draft
    step, AND the draft's padded chunked prefill + slot splice (its own
    chunk-multiple extent ladder) — spec-mode admission is FULLY
    compile-free under traffic, the old per-prompt-length draft.prefill
    retrace included (the ROADMAP follow-up)."""
    cfg, params, gen = model
    dcfg = llama.LlamaConfig(vocab=cfg.vocab, dim=16, n_layers=1,
                             n_heads=1, n_kv_heads=1, ffn_dim=32,
                             max_seq=64, dtype=jnp.float32)
    d_params = llama.init_params(dcfg, jax.random.key(5))
    draft = Generator(dcfg, gen.mesh, axis="sp", max_seq=64)
    rng = np.random.default_rng(22)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (3, 6, 11, 13)]
    n_new = 6

    eng = ServeEngine(gen, params, num_blocks=40, page_size=8,
                      max_batch=2, prefill_chunk=4, draft=draft,
                      draft_params=d_params, spec_k=3, clock=_Tick())
    eng.warmup()
    flat = eng.metrics.compile_misses         # EVERY program, draft incl.
    outs = _drive(eng, prompts, n_new)
    assert eng.metrics.verify_rounds >= 1
    assert eng.metrics.compile_misses == flat, (
        "spec-mode admission compiled after warmup: "
        f"{eng.metrics.summary()['compilation']}")
    comp = eng.metrics.summary()["compilation"]["programs"]
    # the draft programs are bucketed: O(draft ladder) traces cover the
    # 4 distinct prompt lengths, all compiled during warmup (+1 on the
    # join: the first-ever call sees fresh-zeros batch caches whose
    # layout differs from the steady-state jit-output lineage, so one
    # rung compiles twice — inside warmup, which is the point)
    assert comp["draft_prefill"]["misses"] <= len(eng._draft_ladder)
    assert comp["draft_join"]["misses"] <= len(eng._draft_ladder) + 1
    assert "draft_tail_step" in comp
    for i, p in enumerate(prompts):
        assert outs[f"r{i}"].token_ids == _oracle(gen, params, p, n_new)


@pytest.mark.slow
def test_engine_eos_and_streaming(model, tmp_path, monkeypatch):
    cfg, params, gen = model
    rng = np.random.default_rng(5)
    p = rng.integers(0, cfg.vocab, size=8).astype(np.int32)
    want = _oracle(gen, params, p, 10)
    # eos = a token whose FIRST occurrence is mid-stream (the engine
    # stops at the first hit, so an earlier duplicate would shorten it)
    j = next(i for i in range(2, len(want)) if want[i] not in want[:i])
    eos = want[j]
    streamed = []
    eng = ServeEngine(gen, params, num_blocks=16, page_size=8,
                      max_batch=2, prefill_chunk=8, clock=_Tick())
    eng.submit(Request(
        "e0", p, SamplingParams(max_new_tokens=10, eos_id=eos),
        on_token=lambda rid, t: streamed.append((rid, t))))
    monkeypatch.setenv("TDT_DUMP_IR", str(tmp_path))
    outs = eng.run()
    assert outs["e0"].finish_reason is FinishReason.EOS
    assert outs["e0"].token_ids == want[:j + 1]  # eos included, then stop
    assert streamed == [("e0", t) for t in want[:j + 1]]
    path = eng.metrics.maybe_dump("serve_test")
    data = json.loads(open(path).read())
    assert data["completed"] == 1
    assert data["requests"]["e0"]["n_tokens"] == j + 1


@pytest.mark.slow
def test_engine_mixed_greedy_and_sampled(model):
    """Sampled requests ride the same batch; greedy neighbors stay
    bit-exact, and a sampled request is reproducible across engines
    (per-request PRNG stream keyed by seed + emission index)."""
    cfg, params, gen = model
    rng = np.random.default_rng(6)
    pg = rng.integers(0, cfg.vocab, size=7).astype(np.int32)
    ps = rng.integers(0, cfg.vocab, size=5).astype(np.int32)

    def run_once():
        eng = ServeEngine(gen, params, num_blocks=16, page_size=8,
                          max_batch=2, prefill_chunk=8, clock=_Tick())
        eng.submit(Request("g", pg, SamplingParams(max_new_tokens=6)))
        eng.submit(Request("s", ps, SamplingParams(
            max_new_tokens=6, temperature=0.8, top_k=32, seed=11)))
        return eng.run()

    o1, o2 = run_once(), run_once()
    assert o1["g"].token_ids == _oracle(gen, params, pg, 6)
    assert o1["s"].token_ids == o2["s"].token_ids     # deterministic
    assert all(0 <= t < cfg.vocab for t in o1["s"].token_ids)


# ---------------------------------------------------------------------------
# fast tier: untested failure exits (PR 3 satellites)
# ---------------------------------------------------------------------------


def test_run_max_steps_exhaustion():
    """run(max_steps) must raise (not spin) when the queue cannot drain
    in the budget — the backstop against a scheduling livelock."""
    cfg, params, gen = _tiny_model()
    eng = ServeEngine(gen, params, num_blocks=40, page_size=4,
                      max_batch=2, prefill_chunk=4, clock=_Tick())
    p = np.arange(9, dtype=np.int32) % cfg.vocab
    eng.submit(Request("slowpoke", p, SamplingParams(max_new_tokens=8)))
    with pytest.raises(RuntimeError, match="not drained after 1 steps"):
        eng.run(max_steps=1)
    assert eng.has_work()          # nothing was silently dropped
    outs = eng.run()               # and the engine is still serviceable
    assert len(outs["slowpoke"].token_ids) == 8


def test_ensure_capacity_no_victim_raises_and_is_contained():
    """The no-victim RuntimeError exit (engine.py _ensure_capacity): when
    even preempting every other slot holder cannot cover a grow, the
    helper raises — and step() CONTAINS it, retiring the needy request
    as ERROR with its blocks freed instead of unwinding the engine."""
    cfg, params, gen = _tiny_model()
    eng = ServeEngine(gen, params, num_blocks=6, page_size=4,
                      max_batch=1, prefill_chunk=4, clock=_Tick())
    p = np.arange(4, dtype=np.int32) % cfg.vocab
    eng.submit(Request("needy", p, SamplingParams(max_new_tokens=12)))
    eng.step()                                   # admitted + first token
    rs = eng._states["needy"]
    # A foreign allocation eats the rest of the pool: "needy" holds 2
    # blocks (prompt 4 + headroom), it is the ONLY slot holder (no
    # victim), and its grow to 16 tokens needs blocks that cannot come
    # back.
    eng.bm.allocate("__foreign", 12)
    with pytest.raises(RuntimeError, match="no preemption victim"):
        eng._ensure_capacity(rs, 16)
    # the step loop turns the same exit into a quarantine, not a crash
    outs = eng.run()
    assert outs["needy"].finish_reason is FinishReason.ERROR
    assert "no preemption victim" in outs["needy"].error
    assert len(outs["needy"].token_ids) >= 1     # partial output kept
    assert eng.metrics.quarantined == 1
    eng.bm.free("__foreign")
    assert eng.bm.num_free == eng.bm.num_allocatable
    assert all(s is None for s in eng.slots)


# ---------------------------------------------------------------------------
# slow tier: abort regressions (PR 3 satellite)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_engine_abort_mid_prefill_and_waiting_integrity(model):
    """abort() of a request mid-chunked-prefill (scratch + blocks held,
    nothing decoded) and of a WAITING one must leave the pool whole and
    the survivors bit-exact."""
    cfg, params, gen = model
    rng = np.random.default_rng(30)
    long_p = rng.integers(0, cfg.vocab, size=20).astype(np.int32)
    short_p = rng.integers(0, cfg.vocab, size=6).astype(np.int32)
    # budget = one 4-token chunk per step -> the 20-token prompt needs 5
    # steps of prefill; abort strikes after the first.
    eng = ServeEngine(gen, params, num_blocks=24, page_size=8,
                      max_batch=2, prefill_chunk=4, prefill_budget=4,
                      clock=_Tick())
    eng.submit(Request("mid", long_p, SamplingParams(max_new_tokens=4)))
    eng.submit(Request("wait", short_p, SamplingParams(max_new_tokens=4)))
    eng.submit(Request("live", short_p, SamplingParams(max_new_tokens=4)))
    eng.step()
    rs = eng._states["mid"]
    assert rs.status is Status.PREFILL and 0 < rs.prefill_pos < 20
    out = eng.abort("mid")
    assert out.finish_reason is FinishReason.ABORT
    assert out.token_ids == [] and rs.scratch is None
    waiting = eng.abort("wait")          # still queued behind the batch
    assert waiting.finish_reason is FinishReason.ABORT
    outs = eng.run()
    assert outs["live"].token_ids == _oracle(gen, params, short_p, 4)
    assert eng.bm.num_free == eng.bm.num_allocatable
    assert all(s is None for s in eng.slots)


@pytest.mark.slow
def test_engine_abort_from_callback_mid_decode(model):
    """A callback aborting a slot-mate (and later itself) MID-STEP used
    to double-retire: the commit loop kept committing to the finished
    request and bm.free() hit a missing table.  The status guards keep
    the batch serving and the survivor bit-exact."""
    cfg, params, gen = model
    rng = np.random.default_rng(31)
    p0 = rng.integers(0, cfg.vocab, size=6).astype(np.int32)
    p1 = rng.integers(0, cfg.vocab, size=9).astype(np.int32)
    eng = ServeEngine(gen, params, num_blocks=16, page_size=8,
                      max_batch=2, prefill_chunk=8, clock=_Tick())

    def killer(rid, tok):
        if len(eng._states["k0"].generated) == 3:
            eng.abort("k1")              # slot-mate, mid-step
        if len(eng._states["k0"].generated) == 5:
            eng.abort("k0")              # self-abort from own callback
    eng.submit(Request("k0", p0, SamplingParams(max_new_tokens=8),
                       on_token=killer))
    eng.submit(Request("k1", p1, SamplingParams(max_new_tokens=8)))
    outs = eng.run()
    assert outs["k0"].finish_reason is FinishReason.ABORT
    assert outs["k0"].token_ids == _oracle(gen, params, p0, 8)[:5]
    assert outs["k1"].finish_reason is FinishReason.ABORT
    # k1's stream up to the abort is a prefix of its oracle stream
    want1 = _oracle(gen, params, p1, 8)
    assert outs["k1"].token_ids == want1[:len(outs["k1"].token_ids)]
    assert eng.bm.num_free == eng.bm.num_allocatable
    assert all(s is None for s in eng.slots)


@pytest.mark.slow
def test_engine_abort_from_callback_mid_spec_round(model):
    """Same regression inside a speculative round: the accepted-chain
    commit loop must stop feeding an aborted request (its own abort OR a
    slot-mate's) and the draft state must not wedge later joins."""
    cfg, params, gen = model
    dcfg = llama.LlamaConfig(vocab=cfg.vocab, dim=16, n_layers=1,
                             n_heads=1, n_kv_heads=1, ffn_dim=32,
                             max_seq=64, dtype=jnp.float32)
    d_params = llama.init_params(dcfg, jax.random.key(13))
    draft = Generator(dcfg, gen.mesh, axis="sp", max_seq=64)
    rng = np.random.default_rng(32)
    p0 = rng.integers(0, cfg.vocab, size=5).astype(np.int32)
    p1 = rng.integers(0, cfg.vocab, size=8).astype(np.int32)
    p2 = rng.integers(0, cfg.vocab, size=6).astype(np.int32)
    eng = ServeEngine(gen, params, num_blocks=40, page_size=8,
                      max_batch=2, prefill_chunk=8, draft=draft,
                      draft_params=d_params, spec_k=3, clock=_Tick())

    def killer(rid, tok):
        if len(eng._states["s0"].generated) == 2:
            eng.abort("s1")              # mid-spec-round slot-mate abort
    eng.submit(Request("s0", p0, SamplingParams(max_new_tokens=8),
                       on_token=killer))
    eng.submit(Request("s1", p1, SamplingParams(max_new_tokens=8)))
    eng.submit(Request("s2", p2, SamplingParams(max_new_tokens=8)))
    outs = eng.run()
    assert outs["s0"].token_ids == _oracle(gen, params, p0, 8)
    assert outs["s1"].finish_reason is FinishReason.ABORT
    want1 = _oracle(gen, params, p1, 8)
    assert outs["s1"].token_ids == want1[:len(outs["s1"].token_ids)]
    # s2 joins AFTER the mid-round abort freed a slot — the draft state
    # for the reused slot must be clean
    assert outs["s2"].token_ids == _oracle(gen, params, p2, 8)
    assert eng.bm.num_free == eng.bm.num_allocatable
    assert all(s is None for s in eng.slots)


@pytest.mark.slow
def test_speculative_draft_skip_latches(model):
    """ADVICE r5 #3: once the batch-global draft-step skip fires, a
    retirement used to re-open speculation over a desynced draft cache
    (seed 1 below CRASHED with a draft KV overflow pre-fix).  The latch
    keeps speculation off for the rest of the call — no propose after
    the first fallback — and the stream stays greedy-exact."""
    from triton_dist_tpu.models.speculative import SpeculativeGenerator

    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    key = jax.random.key(1)
    cfg = llama.LlamaConfig(vocab=64, dim=32, n_layers=2, n_heads=2,
                            n_kv_heads=1, ffn_dim=64, max_seq=64,
                            dtype=jnp.float32)
    params = llama.init_params(cfg, key)
    dcfg = llama.LlamaConfig(vocab=64, dim=16, n_layers=1, n_heads=1,
                             n_kv_heads=1, ffn_dim=32, max_seq=16,
                             dtype=jnp.float32)
    d_params = llama.init_params(dcfg, jax.random.fold_in(key, 1))
    tgt = Generator(cfg, mesh, axis="sp", max_seq=64)
    drf = Generator(dcfg, mesh, axis="sp", max_seq=16)  # draft runs out

    events = []

    class Spy(SpeculativeGenerator):
        def _propose_batched(self, *a, **kw):
            events.append("propose")
            return super()._propose_batched(*a, **kw)

        def _fallback_batched(self, logits, key):
            events.append("fallback")
            return super()._fallback_batched(logits, key)

    spec = Spy(tgt, drf, k=3)
    prompt = jax.random.randint(jax.random.fold_in(key, 2), (3, 6), 0,
                                64, jnp.int32)
    toks, stats = spec.generate(params, d_params, prompt, 14)

    st = tgt.prefill(params, prompt)
    want, _ = tgt.generate(params, st, 14)
    assert (np.asarray(toks) == np.asarray(want)).all()
    assert "propose" in events and "fallback" in events  # both phases ran
    first_fb = events.index("fallback")
    assert "propose" not in events[first_fb:], (
        "speculation resumed after the draft-step skip fired")
