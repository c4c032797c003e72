"""Learned sparse attention over latent pools (the ``glm_moe_dsa`` block of
models/mla_moe.py) on the CPU: small widths, the Pallas interpreter, seeded
weights, ``index_topk`` (48) well under the test contexts (70-130).

The yardstick is ``benchmarks/reference/mla_dsa_moe_share.py`` — the plain
float32 reference of the same equations (expanded attention, no cache, the
selection by a plain sort, its own weights from the seed), which imports
nothing of the program.
"""

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from chunk_rows import filed_chunk_call

from triton_dist_tpu.kernels import flash_decode as fd
from triton_dist_tpu.models import mla_moe as M
from triton_dist_tpu.models.llama import _rms_norm
from triton_dist_tpu.serve import Request, SamplingParams, ServeEngine

ref = importlib.import_module("benchmarks.reference.mla_dsa_moe_share")

SEED = 2 ** 31 + 5          # past 32 signed bits, like the driver's seeds


def hf_config(cfg: M.MlaMoeConfig) -> dict:
    """The ``glm_moe_dsa`` configuration-file keys of ``cfg`` (what the
    reference and ``from_hf`` read)."""
    return {
        "model_type": "glm_moe_dsa",
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
        "norm_topk_prob": cfg.norm_topk_prob,
        "rope_parameters": {"rope_theta": cfg.rope_theta,
                            "rope_type": "default"},
        "rope_interleave": cfg.rope_interleave,
        "indexer_rope_interleave": cfg.rope_interleave,
        "index_n_heads": cfg.index_n_heads,
        "index_head_dim": cfg.index_head_dim, "index_topk": cfg.index_topk,
        "rms_norm_eps": cfg.norm_eps, "scoring_func": "sigmoid",
        "topk_method": "noaux_tc", "hidden_act": "silu",
    }


@pytest.fixture(scope="module")
def tiny():
    """One dense + one expert layer with an indexer, float32."""
    ref.Q_BLOCK = ref.T_BLOCK = 32
    cfg = M.MlaMoeConfig.tiny_sparse(n_layers=2)
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
# The engine against the reference: logits, index scores, the selected set
# ---------------------------------------------------------------------------

# float32 program against the float32 reference: the two differ by the
# order of float32 sums (absorbed against expanded attention, blocked
# softmax, grouped against per-expert matmuls) — observed 4e-6 on logits
# of magnitude ~3 through two layers, GIVEN THE SAME SELECTED SET.  A key
# that fell on the other side of a cut-off would move a logit by ~1e-1
# (observed with 4 index heads, where exact 0.0 ties at the cut-off are
# common: the program's cut is by value and keeps every tie, the
# reference's sort keeps the earlier position), so this tolerance also
# says the sets were the same in both layers at every position.  The same
# engine in bfloat16 reads ~3e-2: a precision below the one the
# configuration states fails.
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
    through the latent and index-key planes, every query past
    ``index_topk`` from position 48 on, against the reference's one full
    forward pass over prompt + served tokens."""
    cfg, params, gen = tiny
    prompt, = _prompts(cfg, [70])
    toks, got = _served_logits(gen, params, prompt, 10)
    seq = np.concatenate([prompt, np.asarray(toks, np.int32)])
    probe = []
    want = ref.forward_logits(hf_config(cfg), SEED, [seq], [1],
                              dtype=jnp.float32, probe=probe)[0]
    assert got.shape == want.shape
    assert np.abs(got - want).max() < LOGIT_TOL
    # the reference did select: 48 kept at every position past the 48th
    kept = probe[1][0][1][:len(seq) - 1, :len(seq) - 1]
    assert (kept.sum(1) == np.minimum(np.arange(len(seq) - 1) + 1,
                                      cfg.index_topk)).all()
    # the tolerance is tight enough to fail a lower precision
    low = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    gen16 = M.MlaMoeGenerator(low, max_seq=256, interpret=True)
    p16 = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    _, got16 = _served_logits(gen16, p16, prompt, 2)
    assert np.abs(got16 - want[:got16.shape[0]]).max() > 10 * LOGIT_TOL


def test_index_scores_and_selected_set_match_reference(tiny):
    """Layer 0 (its input is the embedding, equal on both sides): the
    program's index scores through the paged call and its cut-off against
    the reference's scores and plain-sort selection — the sets are equal
    wherever a score is not within 1e-4 of the cut-off (none is, here)."""
    cfg, params, gen = tiny
    T, page = 128, 16
    seq, = _prompts(cfg, [T + 1], seed=4)
    probe = []
    ref.forward_logits(hf_config(cfg), SEED, [seq], [1], dtype=jnp.float32,
                       probe=probe)
    want_scores, want_kept = (a[:T, :T] for a in probe[0][0])
    layer = params["layers"][0]
    h = _rms_norm(params["embed"][jnp.asarray(seq[None, :T])],
                  layer["attn_norm"], cfg.norm_eps)
    (q, qi, w), lat, ki = M.project(h, layer, jnp.arange(T)[None], cfg=cfg)
    assert lat.shape == (1, T, 1, cfg.head_dim) == (1, T, 1, 256)
    assert ki.shape == (1, T, 1, cfg.index_head_dim) == (1, T, 1, 128)
    table = jnp.arange(T // page, dtype=jnp.int32)[None]
    scores = fd.dsa_index_scores(
        qi, w, ki.reshape(T // page, page, -1), table,
        jnp.asarray([T], jnp.int32), impl="pallas", interpret=True)
    cut = M._kth_largest(scores, cfg.index_topk, (1, 3))
    got = np.asarray(scores).transpose(0, 2, 1, 3).reshape(T, T)
    causal = np.tril(np.ones((T, T), bool))
    assert (got[~causal] == fd.NEG_INF).all()
    assert np.abs(got - want_scores)[causal].max() < 2e-5
    cut = np.asarray(cut).reshape(T, 1)
    got_kept = causal & (got >= cut)
    edge = causal & (np.abs(got - cut) < 1e-4) & (np.arange(T) >= 48)[:, None]
    # the cut-off itself is the one score "at the edge" of a full row
    assert edge.sum(1).max() == 1
    assert (got_kept == want_kept).all()


def test_contexts_under_index_topk_take_the_plain_latent_path(tiny):
    """While no more than ``index_topk`` tokens are cached every visible
    row is selected: the served logits are those of the same weights
    WITHOUT an indexer (the gc3 block's dense latent attention)."""
    cfg, params, gen = tiny
    prompt, = _prompts(cfg, [30], seed=5)
    toks, got = _served_logits(gen, params, prompt, 8)       # ctx <= 38
    dense = dataclasses.replace(cfg, index_topk=0, index_n_heads=0)
    gen_d = M.MlaMoeGenerator(dense, max_seq=256, interpret=True)
    toks_d, want = _served_logits(gen_d, params, prompt, 8)
    assert toks == toks_d
    assert np.abs(got - want).max() < 1e-5
    assert gen_d.kv_planes == [(1, 256)]


@pytest.fixture(scope="module")
def undisturbed(tiny):
    """Greedy and sampled streams of an engine nothing disturbs: single
    step decode, no sharing, a pool with room; every prompt past
    ``index_topk``."""
    cfg, params, gen = tiny
    prompts = _prompts(cfg, [63, 95, 50], seed=3)
    eng = _engine(gen, params, prefix_cache=False, max_batch=3)
    greedy = _serve(eng, prompts, 12)
    sampled = _serve(_engine(gen, params, prefix_cache=False, max_batch=3),
                     prompts, 12, temperature=0.8, top_k=16, top_p=0.9,
                     seed=11)
    return prompts, greedy, sampled


def test_two_planes_of_different_widths_in_one_block_manager(tiny):
    cfg, params, gen = tiny
    assert gen.kv_planes == [(1, 256), (1, 128)]
    eng = _engine(gen, params)
    assert eng.latent
    for pool in eng._pools:
        assert [p.shape for p in pool] == [(40, 1, 16, 256),
                                           (40, 1, 16, 128)]
    kv = eng.metrics.summary()["kv"]
    assert kv["latent_row_width"] == cfg.latent_width == 160
    assert kv["stored_row_width"] == 256 and kv["index_key_width"] == 128
    assert kv["latent_bytes_per_token"] == (160 + 128) * 2 * 4


def test_fused_horizon_streams_equal_single_step(tiny, undisturbed):
    cfg, params, gen = tiny
    prompts, greedy, sampled = undisturbed
    eng = _engine(gen, params, horizon=4, pipeline=2, max_batch=3)
    assert _serve(eng, prompts, 12) == greedy
    assert eng.metrics.dispatches < eng.metrics.decode_tokens
    eng = _engine(gen, params, horizon=4, pipeline=2, max_batch=3)
    assert _serve(eng, prompts, 12, temperature=0.8, top_k=16, top_p=0.9,
                  seed=11) == sampled


def test_prefix_hit_moves_both_planes(tiny, undisturbed):
    cfg, params, gen = tiny
    prompts, greedy, _ = undisturbed
    eng = _engine(gen, params)
    assert _serve(eng, prompts[1:2], 12) == greedy[1:2]
    eng.submit(Request("again", prompts[1], SamplingParams(max_new_tokens=12)))
    assert list(eng.run()["again"].token_ids) == greedy[1]
    assert eng.metrics.prefix_hits == 1
    assert eng.metrics.prefix_hit_tokens == 80       # 5 pages of 16
    assert eng.metrics.prefix_skipped_tokens == 64   # the chunk floor
    assert eng.bm.num_free == eng.bm.num_allocatable


def test_preemption_and_recompute_with_two_planes(tiny, undisturbed):
    cfg, params, gen = tiny
    prompts, greedy, _ = undisturbed
    # the prompts fit (4 + 6 + 4 pages of 14), their answers do not
    eng = _engine(gen, params, num_blocks=15, max_batch=3)
    assert _serve(eng, prompts, 12) == greedy
    assert eng.metrics.preemptions > 0
    assert eng.bm.num_free == eng.bm.num_allocatable


def test_cow_split_copies_both_planes(tiny, undisturbed):
    """A second table over the SAME blocks makes the running row's tail
    page shared: its next write must split it, index keys with it."""
    cfg, params, gen = tiny
    prompts, greedy, _ = undisturbed
    eng = _engine(gen, params, prefix_cache=True)
    eng.submit(Request("r0", prompts[0], SamplingParams(max_new_tokens=12)))
    while eng._states["r0"].kv_len < 68:          # mid-page (page 16)
        eng.step()
    eng.bm.share("ghost", eng.bm.table("r0"))
    got = list(eng.run()["r0"].token_ids)
    assert got == greedy[0]
    assert eng.bm.cow_copies >= 1
    eng.bm.free("ghost")
    assert eng.bm.num_free == eng.bm.num_allocatable


def test_dsa_counters(tiny, undisturbed):
    cfg, params, gen = tiny
    prompts, greedy, _ = undisturbed
    eng = _engine(gen, params, max_batch=3, prefix_cache=False)
    assert eng.prefill_width == 128         # chunk 32, budget 4 x 32
    windows, seam = [], eng._device_call

    def tapped(op, rids, fn, *a, **kw):
        if op == "prefill_chunk":
            windows.append(int(a[3]))       # where the call's rows start
        return seam(op, rids, fn, *a, **kw)

    eng._device_call = tapped
    assert _serve(eng, prompts, 12) == greedy
    dsa = eng.metrics.summary()["dsa"]
    L, k = cfg.n_layers, cfg.index_topk
    # every query the programs computed, over both layers — each prefill
    # call's 128 rows from where its window starts (pad queries, and the
    # rows a window that slid back at the scratch's end computed again,
    # too: r1's 95 tokens are 64 on the first step's leftover budget, then
    # 31 in a call that starts at row 0 of its 128-row scratch) and the 11
    # decode steps a request: position p sees p + 1 tokens and reads
    # min(., k)
    assert sorted(windows) == [0, 0, 0, 0]
    seen = [np.arange(at + 1, at + 128 + 1) for at in windows] + [
        np.arange(len(p) + 1, len(p) + 12) for p in prompts]
    assert dsa["indexed_tokens"] == L * sum(int(v.sum()) for v in seen)
    assert dsa["selected_rows"] == L * sum(int(np.minimum(v, k).sum())
                                           for v in seen)
    assert dsa["rows_sparse"] == L * 11 * 3 and dsa["rows_dense"] == 0
    assert dsa["selected_share"] == pytest.approx(
        dsa["selected_rows"] / dsa["indexed_tokens"])
    assert 0.5 < dsa["selected_share"] < 0.9
    text = eng.metrics.to_prometheus()
    for name in ("indexed_tokens", "selected_rows", "rows_sparse",
                 "rows_dense"):
        assert f"serve_dsa_{name}_total {dsa[name]}" in text
    assert eng.metrics.summary()["moe"]["assignments"] > 0
    assert not eng._aux_pending


# ---------------------------------------------------------------------------
# The shares of a layer add up
# ---------------------------------------------------------------------------


def test_shares_add_up_to_the_uncut_layer(tiny):
    """One expert layer of the block, attention and indexer included:
    what every share computes alike (sparse attention off the indexer's
    selection, the shared expert) counted once, plus the routed parts of
    all four shares (the PROGRAM's layer, told which experts it holds),
    equals the reference's UNCUT layer."""
    cfg, _, _ = tiny
    li, T = 1, 64
    x = jax.random.normal(jax.random.key(2), (1, T, cfg.dim), jnp.float32)
    pos = jnp.arange(T, dtype=jnp.int32)[None]
    routed = jnp.zeros((T, cfg.dim), jnp.float32)
    attn = []
    for share in range(cfg.n_experts // cfg.experts_held):
        c = dataclasses.replace(cfg,
                                expert_offset=share * cfg.experts_held)
        layer = M.init_params(c, ref.weight_key(SEED))["layers"][li]
        q, lat, ki = M.project(_rms_norm(x, layer["attn_norm"], c.norm_eps),
                               layer, pos, cfg=c)
        o = M.attend_prompt(q, lat, ki, cfg=c, impl="pallas", interpret=True)
        attn.append(x[0] + M.out_proj(o.reshape(T, -1), layer, cfg=c))
        h2 = _rms_norm(attn[0], layer["mlp_norm"], c.norm_eps)
        part, stats = M.routed_experts(h2, layer, c, impl="pallas",
                                       interpret=True)
        assert int(stats[0]) == T * cfg.top_k
        routed = routed + part
    for a in attn[1:]:                  # every chip computes it alike
        assert np.array_equal(np.asarray(a), np.asarray(attn[0]))
    total = attn[0] + routed + M._dense_prompt_ffn(h2, layer["shared"])
    whole = dict(hf_config(cfg), n_routed_experts=cfg.n_experts,
                 share={"experts_total": cfg.n_experts, "expert_offset": 0})
    w = ref.draw_layer(whole, SEED, li, jnp.float32)
    want = ref._layer(x[0], w, st=ref._static(ref.sizes(whole)), int8=False)
    assert np.abs(np.asarray(total) - np.asarray(want)).max() < 5e-5


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,B,T,page,n_pages,lens,q_lens,q_rows", [
    ("one_token_rows_partial_last_page", 3, 1, 128, 3, [1, 130, 384], None,
     512),
    ("one_token_rows_ten_pages_in_groups_of_five", 2, 1, 16, 10, [37, 160],
     None, 512),
    ("empty_row", 3, 1, 128, 3, [0, 130, 384], [0, 1, 1], 512),
    ("multi_token_rows", 2, 8, 128, 3, [8, 300], [8, 3], 512),
    ("chunk_in_query_tiles", 2, 16, 16, 20, [16, 300], [16, 7], 128),
])
def test_index_and_masked_latent_kernels_against_oracles(
        monkeypatch, name, B, T, page, n_pages, lens, q_lens, q_rows):
    """``dsa_index_scores`` against its jnp oracle, then the latent call
    with a selection mask against ITS oracle, and against the oracle over
    the selected rows gathered out (what a sparse read would see)."""
    monkeypatch.setattr(fd, "DSA_Q_ROWS", q_rows)
    monkeypatch.setattr(fd, "MLA_Q_ROWS", q_rows)
    rng = np.random.default_rng(0)
    Hi, Di, H, rank, W, k = 16, 128, 4, 128, 256, 20   # 16: no 0.0 ties
    arr = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    keys, pool = arr(B * n_pages + 1, page, Di), arr(B * n_pages + 1, page, W)
    qi, w, q = arr(B, T, Hi, Di), arr(B, T, Hi), arr(B, T, H, W)
    table = jnp.asarray(1 + rng.permutation(B * n_pages)
                        .reshape(B, n_pages), jnp.int32)
    ql = None if q_lens is None else jnp.asarray(q_lens, jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    got = fd.dsa_index_scores(qi, w, keys, table, lens, q_lens=ql,
                              impl="pallas", interpret=True)
    want = fd._dsa_index_xla(qi, w, keys, table, lens, q_lens=ql)
    assert got.shape == ((B, n_pages, page) if T == 1
                         else (B, n_pages, T, page))
    rows = fd._sel_rows(got, T)                       # [B, T, n, page]
    assert np.abs(np.asarray(rows) - np.asarray(want)).max() < 1e-4
    sel = got - M._kth_largest(got, k, (1, 2) if T == 1 else (1, 3))
    kw = dict(rank=rank, scale=0.2, q_lens=ql)
    out = fd.mla_decode_paged_shard(q, pool, table, lens, sel=sel,
                                    impl="pallas", interpret=True, **kw)
    oracle = fd._mla_decode_xla(q, pool, table, lens, sel=sel, **kw)
    assert np.abs(np.asarray(out) - np.asarray(oracle)).max() < 1e-5
    # the same as attention over the <= k selected rows alone
    flat = np.asarray(pool)[np.asarray(table)].reshape(B, n_pages * page, W)
    keep = np.asarray(fd._sel_rows(sel, T)).reshape(B, T, -1) >= 0
    vis = np.asarray(fd._visible(B, T, n_pages, page, lens, ql))
    for b in range(B):
        for t in range(T):
            ids = np.flatnonzero(keep[b, t] & vis[b, t])
            assert len(ids) <= k
            if not len(ids):
                assert not np.asarray(out[b, t]).any()
                continue
            s = np.einsum("hw,sw->hs", np.asarray(q[b, t]), flat[b, ids]) * 0.2
            p = np.exp(s - s.max(-1, keepdims=True))
            o = (p / p.sum(-1, keepdims=True)) @ flat[b, ids, :rank]
            assert np.abs(np.asarray(out[b, t]) - o).max() < 1e-4


def test_kth_largest_is_the_sorted_kth():
    rng = np.random.default_rng(1)
    x = rng.standard_normal((3, 5, 2, 40)).astype(np.float32)
    x[0, :, 0, :30] = fd.NEG_INF              # 50 finite of a row's 200
    x[1, 2, 1, 7] = x[1, 3, 0, 9]             # a tie
    for k in (1, 17, 60):
        got = np.asarray(M._kth_largest(jnp.asarray(x), k, (1, 3)))
        rows = x.transpose(0, 2, 1, 3).reshape(3, 2, 200)
        want = -np.sort(-rows, axis=-1)[..., k - 1]
        assert got.shape == (3, 1, 2, 1)
        assert (got[:, 0, :, 0] == want).all()


def test_index_kernel_gap_says_why(tiny):
    cfg, params, _ = tiny
    assert fd.dsa_index_gap(128, 128) is None
    assert "index_head_dim%128" in fd.dsa_index_gap(128, 64)
    gen = M.MlaMoeGenerator(
        dataclasses.replace(cfg, index_head_dim=64), max_seq=256,
        impl="pallas")
    gaps = gen.kernel_gaps(page_size=128)
    assert set(gaps) == {"paged_decode", "prefill_chunk"}
    assert "index_head_dim" in gaps["paged_decode"]
    with pytest.raises(fd.PallasShapeError):
        fd.dsa_index_scores(
            jnp.zeros((1, 1, 4, 64)), jnp.zeros((1, 1, 4)),
            jnp.zeros((2, 16, 64)), jnp.zeros((1, 2), jnp.int32),
            jnp.ones((1,), jnp.int32), impl="pallas")


# ---------------------------------------------------------------------------
# Configuration keys; what has not been carried over refuses by name
# ---------------------------------------------------------------------------


def test_from_hf_reads_glm_moe_dsa_keys_and_refuses_what_it_does_not_serve(
        tiny):
    cfg, _, _ = tiny
    hf = hf_config(cfg)
    got = M.MlaMoeConfig.from_hf(
        hf, max_seq=cfg.max_seq, dtype=jnp.float32,
        experts_total=cfg.n_experts, expert_offset=cfg.expert_offset,
        moe_block_m=cfg.moe_block_m)
    assert got == cfg and got.sparse and got.yarn is None
    for bad, word in (
            ({"model_type": "deepseek_v32"}, "model_type"),
            ({"index_block_size": 64}, "index_block_size"),
            ({"indexer_rope_interleave": False}, "indexer_rope_interleave"),
            ({"rope_parameters": {"rope_theta": 1e6, "rope_type": "yarn"}},
             "rope_parameters")):
        with pytest.raises(ValueError, match=word):
            M.MlaMoeConfig.from_hf({**hf, **bad}, max_seq=64)
    # the DeepSeek-V3 block has no indexer: its keys are refused there,
    # never dropped for a quiet dense attention
    with pytest.raises(ValueError, match="index_topk"):
        M.MlaMoeConfig.from_hf({**hf, "model_type": "deepseek_v3",
                                "rope_theta": 1e4}, max_seq=64)


@pytest.mark.parametrize("what", [
    "mesh", "int8_pools", "w8a8", "spec_k", "snapshot_dir", "snapshot",
    "restore", "drain", "migrate_in", "push_out", "admit_pushed"])
def test_index_plane_refuses_by_name(tiny, tmp_path, what):
    """Two planes a layer do not make these pools the dense family's: what
    the index-key plane does not carry yet refuses like the latent one."""
    cfg, params, gen = tiny
    with pytest.raises(M.LatentPoolUnsupported) as err:
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
    assert "index" in str(err.value)


# ---------------------------------------------------------------------------
# The expanded prefill chunk (a sparse block's chunks of
# ``prefill_expand_min`` queries or more)
# ---------------------------------------------------------------------------


def test_expanded_prefill_chunks_match_reference_and_the_absorbed_ones(
        tiny, monkeypatch):
    """The same request as above with every prefill chunk in the EXPANDED
    form (``W_UK`` / ``W_UV`` over the scratch, flash attention a head
    under the selection; decode stays absorbed): the reference's logits to
    the same tolerance — so the same selected sets — and the absorbed
    chunks' own to float32 rounding."""
    cfg, params, gen = tiny
    assert cfg.expands(256) and not cfg.expands(255)
    monkeypatch.setattr(M, "PREFILL_EXPAND_MIN", 32)
    calls = {"expanded": [], "absorbed": []}
    for kind, name in (("expanded", "mla_expanded_prefill"),
                       ("absorbed", "mla_decode_paged_shard")):
        def spy(q, *a, _f=getattr(M, name), _kind=kind, **kw):
            calls[_kind].append(q.shape)
            return _f(q, *a, **kw)
        monkeypatch.setattr(M, name, spy)
    gen_x = M.MlaMoeGenerator(cfg, max_seq=256, interpret=True)
    prompt, = _prompts(cfg, [70])
    toks, got = _served_logits(gen_x, params, prompt, 6)
    monkeypatch.setattr(M, "PREFILL_EXPAND_MIN", 256)
    # every chunk program — the engine's, and the all-rows form of it that
    # ``_served_logits`` runs beside each call — traced the expanded call
    # once a layer over its 128-row scratch — a call is the step's budget
    # of 4 x 32 rows — and the absorbed one served single queries only
    H, dk = cfg.n_heads, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    assert calls["expanded"] == [(1, 128, H * dk)] * cfg.n_layers * 2
    assert calls["absorbed"] and all(s[1] == 1 for s in calls["absorbed"])
    toks_a, got_a = _served_logits(gen, params, prompt, 6)
    assert toks == toks_a
    assert np.abs(got - got_a).max() < LOGIT_TOL
    seq = np.concatenate([prompt, np.asarray(toks, np.int32)])
    want = ref.forward_logits(hf_config(cfg), SEED, [seq], [1],
                              dtype=jnp.float32)[0]
    assert np.abs(got - want).max() < LOGIT_TOL


@pytest.mark.parametrize("B,H,T,S,page,block,prefix", [
    (2, 4, 16, 64, 8, (8, 16, 2), 0),       # the first chunk: causal only
    (2, 4, 16, 64, 8, (8, 16, 2), 5),       # a prefix that ends mid-block
    (1, 4, 16, 64, 8, (16, 8, 4), 48),      # the last chunk of the scratch
    (1, 3, 24, 96, 8, (8, 32, 2), 40),      # heads and blocks by their gcd
])
def test_expanded_prefill_kernel_against_its_oracle(monkeypatch, B, H, T, S,
                                                    page, block, prefix):
    """The flash call over expanded rows under a random selection: key
    blocks past a query block's last position are skipped, a query that
    kept nothing of a block (or of all) is left out of the softmax."""
    rng = np.random.default_rng(S + prefix)
    monkeypatch.setattr(fd, "MLA_PREFILL_BLOCK", block)
    dk, dv = 16, 8
    q = jnp.asarray(rng.normal(size=(B, T, H * dk)), jnp.float32)
    kv = jnp.asarray(rng.normal(size=(B, S, H * (dk + dv))), jnp.float32)
    sel = rng.normal(size=(B, S // page, T, page)) + 0.3
    sel[:, :, 3] = -1.0                     # one query keeps nothing at all
    sel = jnp.asarray(sel, jnp.float32)
    kw = dict(heads=H, d_qk=dk, scale=0.3)
    got = fd.mla_expanded_prefill(q, kv, sel, jnp.int32(prefix), impl="pallas",
                                  interpret=True, **kw)
    want = fd._mla_prefill_xla(q, kv, sel, jnp.int32(prefix), **kw)
    assert got.shape == (B, T, H * dv)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-6
    assert (np.asarray(got)[:, 3] == 0).all()
    # the oracle itself, a head at a time by hand
    h, t = H - 1, T - 1
    keys = np.asarray(kv).reshape(B, S, H, dk + dv)[0, :, h]
    lg = keys[:, :dk] @ np.asarray(q).reshape(B, T, H, dk)[0, t, h] * 0.3
    keep = (np.asarray(sel)[0, :, t].reshape(S) >= 0) & (
        np.arange(S) <= prefix + t)
    p = np.where(keep, np.exp(lg - lg[keep].max()), 0.0)
    assert np.abs(p @ keys[:, dk:] / p.sum()
                  - np.asarray(want)[0, t, h * dv:(h + 1) * dv]).max() < 1e-5


def test_cutoffs_by_blocks_of_queries(monkeypatch):
    """The cut-offs of a chunk taken some queries at a time are the
    cut-offs of the whole."""
    s = jnp.asarray(np.random.default_rng(2).normal(size=(1, 6, 16, 8)),
                    jnp.float32)
    whole = M._kth_largest(s, 5, (1, 3))
    monkeypatch.setattr(M, "CUT_BLOCK_BYTES", 6 * 4 * 8 * 4)
    got = M._cutoffs(s, 5)
    assert got.shape == (1, 1, 16, 1) and (got == whole).all()


def test_expanded_prefill_gaps_and_where_it_never_runs(tiny):
    cfg, _, gen = tiny
    # a head's value as wide as the latent row: out_proj could not tell
    same = dataclasses.replace(cfg, v_head_dim=cfg.kv_lora_rank)
    assert cfg.expands(4096) and not same.expands(4096)
    assert not dataclasses.replace(cfg, index_topk=0).expands(4096)
    assert fd.mla_prefill_gap(2048, 16384, 256, 256) is None
    assert "qk%128" in fd.mla_prefill_gap(2048, 16384, 192, 256)
    assert "whole blocks" in fd.mla_prefill_gap(2048, 16384 + 64, 256, 256)
    with pytest.raises(fd.PallasShapeError, match="mla_expanded_prefill"):
        fd.mla_expanded_prefill(
            jnp.zeros((1, 8, 2 * 16)), jnp.zeros((1, 16, 2 * 24)),
            jnp.zeros((1, 2, 8, 8)), jnp.int32(0), heads=2, d_qk=16,
            scale=1.0, impl="pallas")
    # the engine is told by name when a rung would fall to XLA
    gen_x = M.MlaMoeGenerator(cfg, max_seq=1024, impl="pallas")
    assert gen_x.kernel_gaps(page_size=128, prefill_chunk=128,
                             ladder=[128, 256, 512, 1024]) == {}
    gaps = gen_x.kernel_gaps(page_size=128, prefill_chunk=256,
                             ladder=[256, 512, 1024])
    assert "qk%128" in gaps["prefill_chunk"]
