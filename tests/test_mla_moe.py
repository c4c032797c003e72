"""Latent attention + expert-share serving (models/mla_moe.py) on the CPU:
small widths, the Pallas interpreter, seeded weights.

The yardstick is ``benchmarks/reference/mla_moe_share.py`` — the plain
float32 reference of the same equations (expanded attention, no cache, its
own weights from the seed), which imports nothing of the program.
"""

import dataclasses
import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from chunk_rows import filed_chunk_call

from triton_dist_tpu.kernels import flash_decode as fd
from triton_dist_tpu.kernels import moe_utils
from triton_dist_tpu.models import mla_moe as M
from triton_dist_tpu.serve import Request, SamplingParams, ServeEngine

group_gemm = importlib.import_module("triton_dist_tpu.kernels.group_gemm")
ref = importlib.import_module("benchmarks.reference.mla_moe_share")

SEED = 2 ** 31 + 5          # past 32 signed bits, like the driver's seeds


def hf_config(cfg: M.MlaMoeConfig) -> dict:
    """The configuration-file keys of ``cfg`` (what the reference reads)."""
    f, orig, fast, slow, m, m_all = cfg.yarn
    return {
        "vocab_size": cfg.vocab, "hidden_size": cfg.dim,
        "num_hidden_layers": cfg.n_layers,
        "num_attention_heads": cfg.n_heads, "q_lora_rank": cfg.q_lora_rank,
        "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "intermediate_size": cfg.ffn_dim,
        "moe_intermediate_size": cfg.moe_ffn_dim,
        "n_routed_experts": cfg.experts_held,
        "share": {"experts_total": cfg.n_experts,
                  "expert_offset": cfg.expert_offset},
        "n_shared_experts": cfg.n_shared_experts,
        "first_k_dense_replace": cfg.first_k_dense, "n_group": cfg.n_group,
        "topk_group": cfg.topk_group, "num_experts_per_tok": cfg.top_k,
        "routed_scaling_factor": cfg.routed_scaling,
        "norm_topk_prob": cfg.norm_topk_prob, "rope_theta": cfg.rope_theta,
        "rope_scaling": {
            "factor": f, "original_max_position_embeddings": orig,
            "beta_fast": fast, "beta_slow": slow, "mscale": m,
            "mscale_all_dim": m_all, "rope_type": "yarn"},
        "rms_norm_eps": cfg.norm_eps, "scoring_func": "sigmoid",
        "topk_method": "noaux_tc", "hidden_act": "silu",
    }


@pytest.fixture(scope="module")
def tiny():
    """One dense + one expert layer, float32."""
    ref.Q_BLOCK = ref.T_BLOCK = 32
    cfg = M.MlaMoeConfig.tiny(n_layers=2)
    params = M.init_params(cfg, ref.weight_key(SEED))
    gen = M.MlaMoeGenerator(cfg, max_seq=256, interpret=True)
    return cfg, params, gen


def _engine(gen, params, **kw):
    kw.setdefault("num_blocks", 40)
    kw.setdefault("page_size", 16)
    kw.setdefault("max_batch", 2)
    kw.setdefault("prefill_chunk", 32)
    kw.setdefault("trace_level", 0)
    return ServeEngine(gen, params, **kw)


def _serve(eng, prompts, n_new, **params):
    for i, p in enumerate(prompts):
        eng.submit(Request(f"r{i}", p, SamplingParams(max_new_tokens=n_new,
                                                      **params)))
    outs = eng.run(2000)
    return [list(outs[f"r{i}"].token_ids) for i in range(len(prompts))]


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, n).astype(np.int32) for n in lens]


# ---------------------------------------------------------------------------
# The engine against the reference: logits
# ---------------------------------------------------------------------------

# float32 program against the float32 reference: the two differ by the
# order of float32 sums (absorbed against expanded attention, blocked
# softmax, grouped against per-expert matmuls) — observed 5e-6 on logits
# of magnitude ~3 through two layers.  The same engine in bfloat16 reads
# ~3e-2: a precision below the one the configuration states fails.
LOGIT_TOL = 1e-4


def _served_logits(gen, params, prompt, n_new):
    """One request through chunked prefill and single-step paged decode,
    with every program's logits kept: -> (tokens, logits [S0 + n_new - 1,
    V] — row j is the model's output at position j)."""
    eng = _engine(gen, params)
    rows = {}
    seam = eng._device_call

    def tapped(op, rids, fn, *a, **kw):
        if op == "prefill_chunk":
            return filed_chunk_call(rows, seam, op, rids, fn, a, kw)
        out = seam(op, rids, fn, *a, **kw)
        if op == "paged_decode":
            rs = eng._states[rids[0]]
            rows[rs.kv_len] = np.asarray(out[1][rs.slot])
        return out

    eng._device_call = tapped
    toks, = _serve(eng, [prompt], n_new)
    assert eng.kernel_gaps == {}
    return toks, np.stack([rows[j] for j in range(len(prompt) + n_new - 1)])


def test_engine_logits_match_reference_and_bf16_does_not(tiny):
    """Chunked prefill (three chunks, the last padded), then paged decode
    through the latent cache, against the reference's one full forward
    pass over prompt + served tokens."""
    cfg, params, gen = tiny
    prompt, = _prompts(cfg, [70])
    toks, got = _served_logits(gen, params, prompt, 10)
    seq = np.concatenate([prompt, np.asarray(toks, np.int32)])
    want = ref.forward_logits(hf_config(cfg), SEED, [seq], [1],
                              dtype=jnp.float32)[0]
    assert got.shape == want.shape
    assert np.abs(got - want).max() < LOGIT_TOL
    # the tolerance is tight enough to fail a lower precision
    low = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    gen16 = M.MlaMoeGenerator(low, max_seq=256, interpret=True)
    p16 = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    _, got16 = _served_logits(gen16, p16, prompt, 2)
    assert np.abs(got16 - want[:got16.shape[0]]).max() > 10 * LOGIT_TOL


@pytest.fixture(scope="module")
def undisturbed(tiny):
    """Greedy and sampled streams of an engine nothing disturbs: single
    step decode, no sharing, a pool with room."""
    cfg, params, gen = tiny
    prompts = _prompts(cfg, [31, 63, 15], seed=3)
    eng = _engine(gen, params, prefix_cache=False, max_batch=3)
    greedy = _serve(eng, prompts, 12)
    sampled = _serve(_engine(gen, params, prefix_cache=False, max_batch=3),
                     prompts, 12, temperature=0.8, top_k=16, top_p=0.9,
                     seed=11)
    return prompts, greedy, sampled


def test_fused_horizon_streams_equal_single_step(tiny, undisturbed):
    cfg, params, gen = tiny
    prompts, greedy, sampled = undisturbed
    eng = _engine(gen, params, horizon=4, pipeline=2, max_batch=3)
    assert _serve(eng, prompts, 12) == greedy
    assert eng.metrics.dispatches < eng.metrics.decode_tokens
    eng = _engine(gen, params, horizon=4, pipeline=2, max_batch=3)
    assert _serve(eng, prompts, 12, temperature=0.8, top_k=16, top_p=0.9,
                  seed=11) == sampled


def test_clamped_steps_are_one_step_links_on_latent_pools(tiny, undisturbed):
    """A prompt is two to four engine steps of prefill here (budget one
    chunk of 16), so the rows already running decode under the
    scheduler's clamp: one ``decode_horizon`` link at H = 1 a step, the
    sampler on the device, the same streams — and neither the
    single-step program nor the host sampler serves a decode token."""
    cfg, params, gen = tiny
    prompts, greedy, sampled = undisturbed
    for want, sp in ((greedy, {}), (sampled, dict(
            temperature=0.8, top_k=16, top_p=0.9, seed=11))):
        eng = _engine(gen, params, horizon=4, pipeline=2, max_batch=3,
                      prefill_chunk=16, prefill_budget=16,
                      prefix_cache=False, trace_level=1)
        assert _serve(eng, prompts, 12, **sp) == want
        progs = eng.metrics.summary()["programs"]
        assert progs["decode_horizon[H=1]"]["count"] >= 4, sorted(progs)
        assert "paged_decode" not in progs
        assert eng.metrics.host_choices == len(prompts)
        assert ("sample_token" in progs) == bool(sp)


def test_prefix_hit_on_latent_pools(tiny, undisturbed):
    cfg, params, gen = tiny
    prompts, greedy, _ = undisturbed
    eng = _engine(gen, params)
    assert _serve(eng, prompts[1:2], 12) == greedy[1:2]
    eng.submit(Request("again", prompts[1], SamplingParams(max_new_tokens=12)))
    assert list(eng.run()["again"].token_ids) == greedy[1]
    assert eng.metrics.prefix_hits == 1
    assert eng.metrics.prefix_hit_tokens == 48       # 3 pages of 16
    assert eng.metrics.prefix_skipped_tokens == 32   # the chunk floor
    assert eng.bm.num_free == eng.bm.num_allocatable


def test_preemption_and_recompute_on_latent_pools(tiny, undisturbed):
    cfg, params, gen = tiny
    prompts, greedy, _ = undisturbed
    # the prompts fit (2 + 4 + 1 pages of 8), their first tokens cross a
    # page each and do not
    eng = _engine(gen, params, num_blocks=9, max_batch=3)
    assert _serve(eng, prompts, 12) == greedy
    assert eng.metrics.preemptions > 0
    assert eng.bm.num_free == eng.bm.num_allocatable


def test_cow_split_on_latent_pools(tiny, undisturbed):
    """A second table over the SAME blocks (beam-style sharing) makes the
    running row's tail page shared: its next write must split it."""
    cfg, params, gen = tiny
    prompts, greedy, _ = undisturbed
    eng = _engine(gen, params, prefix_cache=True)
    eng.submit(Request("r0", prompts[0], SamplingParams(max_new_tokens=12)))
    while eng._states["r0"].kv_len < 40:          # mid-page (page 16)
        eng.step()
    eng.bm.share("ghost", eng.bm.table("r0"))
    got = list(eng.run()["r0"].token_ids)
    assert got == greedy[0]
    assert eng.bm.cow_copies >= 1
    eng.bm.free("ghost")
    assert eng.bm.num_free == eng.bm.num_allocatable


def test_moe_counters_and_latent_row(tiny, undisturbed):
    cfg, params, gen = tiny
    prompts, greedy, _ = undisturbed
    eng = _engine(gen, params, max_batch=3)
    assert _serve(eng, prompts, 12) == greedy
    moe = eng.metrics.summary()["moe"]
    assert moe["assignments"] > 0 and moe["experts_hit"] > 0
    assert 0 < moe["local_assignments"] < moe["assignments"]
    assert moe["pad_rows"] >= 0
    # 4 of 16 experts held (one whole group of the router's four): even
    # routing would land 1/4 here; 128-wide seeded weights over some
    # hundred rows are not that even
    assert 0.1 < moe["local_share"] < 0.5
    text = eng.metrics.to_prometheus()
    for name in ("assignments", "local_assignments", "pad_rows",
                 "experts_hit"):
        assert f"serve_moe_{name}_total {moe[name]}" in text
    kv = eng.metrics.summary()["kv"]
    assert kv["latent_row_width"] == cfg.latent_width == 160
    assert kv["stored_row_width"] == 256
    assert not eng._aux_pending
    # which form each program's routed sum took, fixed when it was built:
    # a chunk of 32 rows walks (128 assignments against 32 + 4 tiles of 8
    # rows), a decode step of 3 rows gathers
    assert moe["combine"] == {"prefill_chunk": "walk",
                              "paged_decode": "gather"}
    assert eng.kernel_gaps == {}


# (configuration file, its builder, rows of the program) -> (form,
# assignments the gather reads, rows the walk reads): ISSUE 46's table
_COMBINE_TABLE = {
    ("laguna-s-2.1-ep8-l9", "swa_moe", "prefill_chunk"):
        ("walk", 20480, 6656),
    ("laguna-s-2.1-ep8-l9", "swa_moe", 1024): ("walk", 10240, 3328),
    ("laguna-s-2.1-ep8-l9", "swa_moe", 256): ("walk", 2560, 1344),
    ("laguna-s-2.1-ep8-l9", "swa_moe", "max_batch"): ("gather", 640, 1104),
    ("gigachat3.1-702b-ep16-l5", "mla_moe", "prefill_chunk"):
        ("walk", 4096, 768),
    ("gigachat3.1-702b-ep16-l5", "mla_moe", "max_batch"):
        ("gather", 512, 544),
    ("glm-5-ep16-l5", "mla_moe", "prefill_chunk"): ("walk", 16384, 1536),
    ("glm-5-ep16-l5", "mla_moe", "max_batch"): ("gather", 256, 528),
    # 64 of 64 held: the walk never reads fewer
    ("mellum2-12b-a2.5b-l8", "swa_moe", "prefill_chunk"):
        ("gather", 16384, 32768),
    ("mellum2-12b-a2.5b-l8", "swa_moe", 256): ("gather", 2048, 4096),
    ("mellum2-12b-a2.5b-l8", "swa_moe", "max_batch"): ("gather", 512, 2560),
}


@pytest.mark.parametrize("name,family,rows", _COMBINE_TABLE)
def test_combine_form_by_the_cells_own_numbers(name, family, rows):
    """The rule over (rows, top_k, experts held, experts, row tile) at the
    four expert configurations' chunk rungs and decode steps."""
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, f"benchmarks/configs/{name}.json")) as f:
        config = json.load(f)
    cfg = importlib.import_module(
        f"benchmarks.builders_{family}").model_config(config)
    n = config["engine"][rows] if isinstance(rows, str) else rows
    form, gathered, walked = _COMBINE_TABLE[name, family, rows]
    assert n * cfg.top_k == gathered
    assert M.walk_rows(n, cfg.top_k, cfg.experts_held, cfg.n_experts,
                       cfg.row_tile(n)) == walked
    assert M.combine_form(n, cfg) == form == (
        "walk" if walked < gathered else "gather")
    # the cells' chunks reach the Mosaic call
    assert M.combine_kernel_gap(cfg, n, impl="pallas",
                                interpret=False) is None


def test_combine_kernel_gap_names_the_reason():
    cfg = M.MlaMoeConfig.tiny()
    assert M.combine_form(32, cfg) == "walk"
    assert M.combine_kernel_gap(cfg, 32, impl="pallas",
                                interpret=False) is None
    assert M.combine_kernel_gap(cfg, 32, impl="xla",
                                interpret=False) == "impl resolves to XLA"
    assert M.combine_kernel_gap(cfg, 2, impl="xla", interpret=False) is None
    odd = dataclasses.replace(cfg, moe_block_m=12)
    assert "block_m%8" in M.combine_kernel_gap(odd, 32, impl="pallas",
                                               interpret=False)
    narrow = dataclasses.replace(cfg, dim=192)
    gen = M.MlaMoeGenerator(narrow, max_seq=64, impl="pallas")
    assert "D%128" in gen.kernel_gaps(page_size=128,
                                      prefill_chunk=32)["moe_combine"]


# ---------------------------------------------------------------------------
# Layers against the reference
# ---------------------------------------------------------------------------


def test_absorbed_attention_equals_expanded(tiny):
    """One layer's attention: the program's absorbed form through the
    latent kernel against the reference's expanded K and V."""
    cfg, params, gen = tiny
    T = 64
    h = jax.random.normal(jax.random.key(1), (1, T, cfg.dim), jnp.float32)
    layer = params["layers"][1]
    pos = jnp.arange(T, dtype=jnp.int32)[None]
    q, lat, v = M.project(h, layer, pos, cfg=cfg)
    assert v is None and lat.shape == (1, T, 1, cfg.head_dim)
    assert not np.asarray(lat[..., cfg.latent_width:]).any()   # zero pad
    o = M.attend_prompt(q, lat, cfg=cfg, impl="pallas", interpret=True)
    got = M.out_proj(o.reshape(T, -1), layer, cfg=cfg)
    w = {k: np.asarray(x, np.float32)
         for k, x in ref.draw_layer(hf_config(cfg), SEED, 1,
                                    jnp.float32).items()}
    with jax.default_matmul_precision("highest"):
        want = ref.mla(h[0], w, ref.sizes(hf_config(cfg)), False) @ w["wo"]
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5


@pytest.mark.parametrize("held", [4, 16])
def test_shares_add_up_to_the_uncut_layer(tiny, held):
    """The routed parts that all four shares of the expert layer give
    (the PROGRAM's layer, told which experts it holds), plus the shared
    expert once, equal the reference's UNCUT layer — and so does the ONE
    share of a layer that holds every expert (offset 0, held = total: a
    pipeline stage's layer, ISSUE 49)."""
    cfg, _, _ = tiny
    cfg = dataclasses.replace(cfg, experts_held=held)
    li, T = 1, 48
    h = jax.random.normal(jax.random.key(2), (T, cfg.dim), jnp.float32)
    total = jnp.zeros((T, cfg.dim), jnp.float32)
    for share in range(cfg.n_experts // cfg.experts_held):
        c = dataclasses.replace(cfg,
                                expert_offset=share * cfg.experts_held)
        layer = M.init_params(c, ref.weight_key(SEED))["layers"][li]
        part, stats = M.routed_experts(h, layer, c, impl="pallas",
                                       interpret=True)
        assert int(stats[0]) == T * cfg.top_k
        total = total + part
    total = total + M._dense_prompt_ffn(h, layer["shared"])
    whole = dict(hf_config(cfg), n_routed_experts=cfg.n_experts,
                 share={"experts_total": cfg.n_experts, "expert_offset": 0})
    w = {k: x.astype(jnp.float32)
         for k, x in ref.draw_layer(whole, SEED, li, jnp.float32).items()}
    with jax.default_matmul_precision("highest"):
        want = ref.ffn(h, w, ref.sizes(whole), False)
    assert np.abs(np.asarray(total) - np.asarray(want)).max() < 2e-5


def _logit(p):
    return np.log(p / (1 - p)).astype(np.float32)


@pytest.mark.parametrize("bias5,chosen", [
    # s' = s.  Groups of 2, a group scores the sum of its two best: g0
    # 1.0, g1 0.9, g2 1.35, g3 0.3 -> g2 and g0 stay.  Expert 2 (0.8, the
    # second best of all) lies in g1 and is OUT: the limit binds.
    (0.0, {0: 0.9, 4: 0.7}),
    # + 0.3 on expert 5: s' = 0.95 beats expert 4's 0.7, so the CHOICE
    # moves to {5, 0}; expert 5's weight is still its s = 0.65.
    (0.3, {5: 0.65, 0: 0.9}),
])
def test_router_hand_worked(bias5, chosen):
    s = np.array([0.9, 0.1, 0.8, 0.1, 0.7, 0.65, 0.2, 0.1], np.float32)
    cfg = M.MlaMoeConfig.tiny(dim=8, n_experts=8, experts_held=8,
                              expert_offset=0, n_group=4, topk_group=2,
                              top_k=2, routed_scaling=2.5)
    bias = np.zeros(8, np.float32)
    bias[5] = bias5
    layer = {"router": jnp.asarray(np.diag(_logit(s))),   # h = ones: logits
             "router_bias": jnp.asarray(bias)}
    h = jnp.eye(8, dtype=jnp.float32).sum(0, keepdims=True)
    # one row whose logits are logit(s): h @ diag = logit(s)
    ids, w = M.route(h, layer, cfg)
    want = {e: 2.5 * v / sum(chosen.values()) for e, v in chosen.items()}
    got = dict(zip(np.asarray(ids[0]).tolist(), np.asarray(w[0]).tolist()))
    assert got.keys() == want.keys()
    for e in want:
        assert abs(got[e] - want[e]) < 1e-5
    # the reference's router, the same case
    hf = dict(hf_config(cfg))
    ch, wt = ref.route(np.asarray(h), {"router": np.asarray(layer["router"]),
                                       "router_bias": bias},
                       ref.sizes(hf), False)
    assert set(np.flatnonzero(np.asarray(ch[0]))) == set(want)
    for e in want:
        assert abs(float(wt[0, e]) - want[e]) < 1e-5


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,B,T,page,n_pages,lens,q_lens,q_rows", [
    ("one_token_rows_partial_last_page", 3, 1, 128, 3, [1, 130, 384], None,
     512),
    ("empty_row", 3, 1, 128, 3, [0, 130, 384], [0, 1, 1], 512),
    ("multi_token_rows", 2, 5, 128, 3, [5, 300], [5, 3], 512),
    ("chunk_in_query_tiles", 2, 12, 16, 20, [12, 300], [12, 7], 16),
])
def test_latent_paged_kernel_against_oracle(monkeypatch, name, B, T, page,
                                            n_pages, lens, q_lens, q_rows):
    monkeypatch.setattr(fd, "MLA_Q_ROWS", q_rows)
    rng = np.random.default_rng(0)
    H, rank, W = 4, 128, 256
    pool = jnp.asarray(rng.standard_normal((B * n_pages + 1, page, W)),
                       jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, T, H, W)), jnp.float32)
    table = jnp.asarray(1 + rng.permutation(B * n_pages)
                        .reshape(B, n_pages), jnp.int32)
    kw = dict(rank=rank, scale=0.2,
              q_lens=None if q_lens is None else jnp.asarray(q_lens,
                                                             jnp.int32))
    lens = jnp.asarray(lens, jnp.int32)
    got = fd.mla_decode_paged_shard(q, pool, table, lens, impl="pallas",
                                    interpret=True, **kw)
    want = fd._mla_decode_xla(q, pool, table, lens, **kw)
    assert got.shape == (B, T, H, rank)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
    if name == "empty_row":
        assert not np.asarray(got[0]).any()


def test_latent_kernel_gap_says_why():
    assert fd.mla_kernel_gap(128, 512, 128) is None
    assert "row%128" in fd.mla_kernel_gap(128, 512, 64)
    with pytest.raises(fd.PallasShapeError):
        fd.mla_decode_paged_shard(
            jnp.zeros((1, 1, 4, 192)), jnp.zeros((2, 16, 192)),
            jnp.zeros((1, 2), jnp.int32), jnp.ones((1,), jnp.int32),
            rank=128, scale=1.0, impl="pallas")


def test_grouped_gemm_over_held_experts():
    """sort_align_held + group_gemm_live: only held experts get rows, the
    held experts' tiles come first, dead tiles are skipped, and every
    local assignment reads its expert's product."""
    rng = np.random.default_rng(0)
    T, topk, E, held, off, bm = 12, 4, 32, 8, 8, 8
    ids = jnp.asarray(np.stack([rng.choice(E, topk, replace=False)
                                for _ in range(T)]), jnp.int32)
    plan = moe_utils.sort_align_held(ids, held, bm, off)
    local = np.asarray(plan["local"]).reshape(T, topk)
    assert (local == ((np.asarray(ids) >= off)
                      & (np.asarray(ids) < off + held))).all()
    n_live = int(plan["n_live_tiles"])
    counts = np.asarray(plan["counts"])
    assert counts.sum() == local.sum()
    assert n_live == sum(-(-c // bm) for c in counts) < plan["m_pad"] // bm
    x = jnp.asarray(rng.standard_normal((T, 128)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((held, 128, 256)), jnp.float32)
    xs = jnp.where(plan["valid_rows"][:, None], x[plan["src_token"]], 0)
    y = group_gemm.group_gemm_live(
        xs, w, plan["tile_expert"], plan["n_live_tiles"], block_m=bm,
        bn=128, bk=128, impl="pallas", interpret=True, name="moe_test")
    dest = np.asarray(plan["dest"]).reshape(T, topk)
    for t in range(T):
        for k in range(topk):
            if local[t, k]:
                want = np.asarray(x[t]) @ np.asarray(w[int(ids[t, k]) - off])
                assert np.abs(np.asarray(y[dest[t, k]]) - want).max() < 1e-3
            else:
                assert dest[t, k] == plan["m_pad"]


# ---------------------------------------------------------------------------
# What has not been carried over refuses by name
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_streams(tiny):
    """``tiny`` inside a residual of four streams (the ``xing4_0`` block)."""
    cfg = dataclasses.replace(tiny[0], hc_mult=4)
    return (cfg, M.init_params(cfg, ref.weight_key(SEED)),
            M.MlaMoeGenerator(cfg, max_seq=256, interpret=True))


@pytest.mark.parametrize("block", ["one_stream", "streams"])
@pytest.mark.parametrize("what", [
    "mesh", "int8_pools", "w8a8", "spec_k", "snapshot_dir", "snapshot",
    "restore", "drain", "migrate_in", "push_out", "admit_pushed"])
def test_latent_pools_refuse_by_name(tiny, tiny_streams, tmp_path, what,
                                     block):
    """What the latent family refuses it refuses with several residual
    streams too: none of these paths takes the ``streams`` seam."""
    cfg, params, gen = tiny if block == "one_stream" else tiny_streams
    with pytest.raises(M.LatentPoolUnsupported):
        if what == "mesh":
            from jax.sharding import Mesh

            _engine(gen, params,
                    mesh=Mesh(np.array(jax.devices()[:2]), ("tp",)))
        elif what == "int8_pools":
            M.MlaMoeGenerator(cfg, max_seq=256, interpret=True,
                              kv_dtype=jnp.int8)
        elif what == "w8a8":
            _engine(gen, params, w8a8=True)
        elif what == "spec_k":
            _engine(gen, params, spec_k=2, draft=gen, draft_params=params)
        elif what == "snapshot_dir":
            _engine(gen, params, snapshot_dir=str(tmp_path))
        elif what == "restore":
            ServeEngine.restore(str(tmp_path), gen, params)
        else:
            eng = _engine(gen, params)
            {"snapshot": lambda: eng.snapshot(str(tmp_path)),
             "drain": eng.drain,
             "migrate_in": lambda: eng.migrate_in({"requests": []}),
             "push_out": lambda: eng.push_out("r0"),
             "admit_pushed": lambda: eng.admit_pushed({"requests": []}),
             }[what]()


# ---------------------------------------------------------------------------
# One layer stack behind one family seam (both families)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("family", ["dense", "latent"])
def test_both_families_enter_the_one_stack_through_the_same_seam(tiny,
                                                                  family):
    """The dense ``Generator`` and ``MlaMoeGenerator`` hand the engine the
    same attributes — cache planes, block seams, program wrapper, kernel
    reach — and their forwards are ``generate._layer_stack`` over them:
    on a contiguous cache a T = 1 call gives the T = 3 call's first
    position (causal: later tokens cannot reach it), so decode IS the
    stack at T = 1."""
    from jax.sharding import Mesh

    from triton_dist_tpu.models import generate as G
    from triton_dist_tpu.models import llama

    if family == "latent":
        cfg, params, gen = tiny
    else:
        cfg = llama.LlamaConfig(vocab=64, dim=32, n_layers=2, n_heads=4,
                                n_kv_heads=2, ffn_dim=64, max_seq=64,
                                dtype=jnp.float32)
        params = llama.init_params(cfg, jax.random.key(3))
        gen = G.Generator(cfg, Mesh(np.array(jax.devices()[:1]), ("sp",)),
                          axis="sp", max_seq=64)
    planes = gen.kv_planes
    assert len(planes) == {"dense": 2, "latent": 1}[family]
    assert set(gen.serve_hooks()) == {"project", "out_proj", "ffn",
                                      "paged_attend"}
    assert isinstance(gen.kernel_gaps(page_size=16, prefill_chunk=32,
                                      ladder=[32, 64], sp_world=1), dict)
    # a family's programs may end in counters of its own (the MoE tally)
    assert len(gen.wrap_program(lambda *a: a)(1, 2)) == {
        "dense": 2, "latent": 3}[family]

    # the stack itself, T = 3 against T = 1, over the family's own seams
    block = {k: v for k, v in gen.serve_hooks().items()
             if k != "paged_attend"}
    attend = (gen._attend_prefix if family == "dense" else
              functools.partial(M.attend_prefix, cfg=cfg, impl="auto",
                                  interpret=True))
    tokens = jnp.asarray([[5, 9, 2], [7, 1, 3]], jnp.int32)

    def run(chunk):
        caches = [tuple(jnp.zeros((2, h, 32, w), cfg.dtype)
                        for h, w in planes) for _ in range(cfg.n_layers)]
        _, logits = G._chunk_forward(params, chunk, caches, jnp.int32(0),
                                     cfg=cfg, quantized=False,
                                     attend=attend, **block)
        return np.asarray(logits)

    np.testing.assert_allclose(run(tokens[:, :1])[:, 0], run(tokens)[:, 0],
                               rtol=2e-5, atol=2e-5)
