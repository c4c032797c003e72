"""The ``laguna`` block (models/swa_moe.py grown: query heads that differ by
layer over the same KV heads, a per-head output gate, a theta and a rotary
width a layer kind, a dense lead layer, a shared expert beside
sigmoid-routed held experts) on the CPU: tiny sizes (F S S S F, 4 / 6
query heads over 2 KV heads, window 16, page 8, 4 of 8 experts held,
top-3, float32), seeded weights.

The yardstick is ``benchmarks/reference/laguna.py`` — the plain float32
reference of the same equations (no cache, its own weights from the seed),
which imports nothing of the program.
"""

import dataclasses
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_swa_moe import (  # the window family's toy engine and its taps
    _engine,
    _gen,
    _prompts,
    _serve,
    _served_logits,
)

from triton_dist_tpu.kernels import flash_decode as fd
from triton_dist_tpu.models import mla_moe
from triton_dist_tpu.models import swa_moe as S
from triton_dist_tpu.models.generate import LayerKind
from triton_dist_tpu.serve.block_manager import KvGroups

ref = importlib.import_module("benchmarks.reference.laguna")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_FILE = os.path.join(ROOT, "benchmarks/configs/laguna-s-2.1-ep8-l9.json")
SEED = 2 ** 31 + 11         # past 32 signed bits, like the driver's seeds


def hf_config(cfg: S.SwaMoeConfig, **over) -> dict:
    """The configuration-file keys of ``cfg`` (what the reference and
    ``from_hf`` read)."""
    factor, orig, fast, slow, att = cfg.yarn
    names = {v: k for k, v in S.ATTN_KINDS.items()}
    n = cfg.n_layers
    dense = list(range(cfg.first_k_dense))
    c = {
        "model_type": "laguna", "vocab_size": cfg.vocab,
        "hidden_size": cfg.dim, "num_hidden_layers": n,
        "num_attention_heads": cfg.heads(0),
        "num_attention_heads_per_layer": [cfg.heads(li) for li in range(n)],
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "intermediate_size": cfg.ffn_dim,
        "moe_intermediate_size": cfg.moe_ffn_dim,
        "shared_expert_intermediate_size": cfg.shared_ffn_dim,
        "num_experts": cfg.experts_held,
        "share": {"experts_total": cfg.n_experts,
                  "expert_offset": cfg.expert_offset},
        "num_experts_per_tok": cfg.top_k,
        "norm_topk_prob": cfg.norm_topk_prob,
        "moe_routed_scaling_factor": cfg.routed_scaling,
        "moe_apply_router_weight_on_input": False,
        "moe_router_logit_softcapping": 0, "decoder_sparse_step": 1,
        "mlp_only_layers": dense,
        "layer_types": [names[t] for t in cfg.layer_types],
        "mlp_layer_types": ["dense" if li in dense else "sparse"
                            for li in range(n)],
        "gating": "per-head", "gating_types": ["per_head"] * n,
        "sliding_window": cfg.sliding_window,
        "rope_parameters": {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": cfg.rope_theta,
                "factor": factor, "original_max_position_embeddings": orig,
                "beta_fast": fast, "beta_slow": slow,
                "attention_factor": att,
                "partial_rotary_factor": cfg.rotary[0]},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": cfg.rope_theta_window,
                                  "partial_rotary_factor": cfg.rotary[1]}},
        "rms_norm_eps": cfg.norm_eps, "attention_bias": False,
        "tie_word_embeddings": False, "max_position_embeddings": 256,
    }
    c.update(over)
    return c


@pytest.fixture(scope="module")
def tiny():
    ref.Q_BLOCK = ref.T_BLOCK = 32
    cfg = S.SwaMoeConfig.tiny_laguna()
    params = S.init_params(cfg, ref.weight_key(SEED))
    return cfg, params


# ---------------------------------------------------------------------------
# The engine against the reference: logits through BOTH groups
# ---------------------------------------------------------------------------

# float32 program against the float32 reference: they differ by the order of
# float32 sums (blocked softmax against one row's, grouped against
# per-expert matmuls, the window as a mask against pages never read) —
# observed ~1.5e-5 on logits of magnitude ~3 through five layers.  The same
# engine in bfloat16 reads ~5e-2: a precision below the one the
# configuration states fails.
LOGIT_TOL = 2e-4


@pytest.fixture(scope="module")
def served(tiny):
    """path -> (prompt, tokens, engine logits, reference logits): one
    request of 70 prompt tokens + 30 served, through the XLA twins and
    through the interpreted Mosaic calls (groups of 2 and 3 query rows a
    KV head)."""
    cfg, params = tiny
    prompt, = _prompts(cfg, [70])
    cache = {}

    def get(path):
        if path not in cache:
            toks, got, eng = _served_logits(
                _gen(cfg, interpret=path == "mosaic"), params, prompt, 30)
            assert isinstance(eng.bm, KvGroups)
            assert eng.metrics.kv_window_released > 0
            seq = np.concatenate([prompt, np.asarray(toks, np.int32)])
            want = ref.forward_logits(hf_config(cfg), SEED, [seq], [1],
                                      dtype=jnp.float32)[0]
            cache[path] = (prompt, toks, got, want)
        return cache[path]

    return get


@pytest.mark.parametrize("path", ["xla", "mosaic"])
def test_engine_logits_match_reference(tiny, served, path):
    """Chunked prefill (five chunks, the last padded), then paged decode
    through the full AND the window group — the context crosses the
    16-token window six times — against the reference's one full forward
    pass over prompt + served tokens."""
    cfg, _ = tiny
    assert cfg.layer_types == ("full", "window", "window", "window", "full")
    assert [cfg.heads(li) for li in range(5)] == [4, 6, 6, 6, 4]
    _, _, got, want = served(path)
    assert got.shape == want.shape
    assert np.abs(got - want).max() < LOGIT_TOL


def test_a_bfloat16_model_fails_the_float32_tolerance(tiny, served):
    cfg, params = tiny
    prompt, _, _, want = served("xla")
    low = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    p16 = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    _, got16, _ = _served_logits(_gen(low), p16, prompt, 2)
    n = len(prompt)       # position for position up to the first served token
    assert np.abs(got16[:n] - want[:n]).max() > 10 * LOGIT_TOL


def _without(c: dict, what: str) -> dict:
    """The configuration with one mechanism taken out of the REFERENCE."""
    c = json.loads(json.dumps(c))
    rp = c["rope_parameters"]
    if what == "gate":
        c["gating"] = "none"
    elif what == "half_rotary":
        rp["full_attention"]["partial_rotary_factor"] = 1.0
    elif what == "second_theta":
        rp["sliding_attention"]["rope_theta"] = \
            rp["full_attention"]["rope_theta"]
    elif what == "shared_expert":
        c["shared_expert_intermediate_size"] = 0
    elif what == "routed_scaling":
        c["moe_routed_scaling_factor"] = 1.0
    return c


@pytest.mark.parametrize("what", ["gate", "half_rotary", "second_theta",
                                  "shared_expert", "routed_scaling"])
def test_each_mechanism_moves_the_logits_when_the_reference_drops_it(
        tiny, served, what):
    """The tolerance sees every mechanism the block adds: a reference
    without the gate, with the whole head rotary, with one theta, without
    the shared expert or the scaling is no longer matched."""
    cfg, _ = tiny
    prompt, toks, got, _ = served("xla")
    seq = np.concatenate([prompt, np.asarray(toks, np.int32)])
    other = ref.forward_logits(_without(hf_config(cfg), what), SEED, [seq],
                               [1], dtype=jnp.float32)[0]
    assert np.abs(got - other).max() > 50 * LOGIT_TOL


def test_whole_prompt_forward_matches_reference(tiny):
    """The cache-free prompt forward (the gate through the same mixer, the
    window as a mask in flash attention) against the reference."""
    cfg, params = tiny
    prompt, = _prompts(cfg, [64], seed=3)
    got = np.asarray(_gen(cfg).forward_logits(params, prompt[None])[0])
    want = ref.forward_logits(
        hf_config(cfg), SEED, [np.concatenate([prompt, prompt[:1]])], [1],
        dtype=jnp.float32)[0]
    assert np.abs(got - want).max() < LOGIT_TOL


@pytest.mark.parametrize("sampled", [False, True])
def test_fused_horizon_equals_single_steps(tiny, sampled):
    cfg, params = tiny
    prompts = _prompts(cfg, [37, 21], seed=1)
    kw = dict(temperature=0.8, top_k=20, seed=11) if sampled else {}
    one = _serve(_engine(_gen(cfg), params), prompts, 40, **kw)
    eng = _engine(_gen(cfg), params, horizon=4, pipeline=2)
    assert _serve(eng, prompts, 40, **kw) == one
    assert eng.bm.num_free == eng.bm.num_allocatable
    stats = eng.metrics.summary()
    # the query heads of each kind's layers, stamped at construction
    assert stats["swa"]["heads"] == {"full": 4, "window": 6}
    # 3 of 8 a row a layer are routed, 4 of 8 held: about half land here
    assert 0.3 < stats["moe"]["local_share"] < 0.7
    assert eng.group_blocks == [64, 1 + 2 * 4]


def test_preemption_and_recompute_equal_an_undisturbed_run(tiny):
    cfg, params = tiny
    prompts = _prompts(cfg, [40, 44], seed=5)
    calm = _serve(_engine(_gen(cfg), params, horizon=4), prompts, 60)
    eng = _engine(_gen(cfg), params, horizon=4, num_blocks=20)
    assert _serve(eng, prompts, 60) == calm
    assert eng.metrics.preemptions > 0


# ---------------------------------------------------------------------------
# The expert layer: the router at one group, the shares, the dense layer
# ---------------------------------------------------------------------------


def test_sigmoid_router_ids_weights_and_a_tie(tiny):
    cfg, params = tiny
    layer = params["layers"][1]
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.standard_normal((9, cfg.dim)), jnp.float32)
    ids, w = mla_moe.route(h, layer, cfg)
    s = ref.sizes(hf_config(cfg))
    chosen, wt = ref.route(h, {"router": layer["router"],
                               "router_bias": layer["router_bias"]}, s, False)
    got = np.zeros((9, cfg.n_experts), np.float32)
    np.put_along_axis(got, np.asarray(ids), np.asarray(w), axis=1)
    assert (np.asarray(chosen) == (got > 0)).all()
    assert np.abs(got - np.asarray(wt)).max() < 1e-6
    assert np.allclose(np.asarray(w).sum(-1), cfg.routed_scaling, atol=1e-5)
    # the bias moves the choice and never the weight; a tie to the lower id
    tied = {"router": jnp.zeros((cfg.dim, cfg.n_experts), jnp.float32),
            "router_bias": jnp.zeros((cfg.n_experts,), jnp.float32)}
    ids, w = mla_moe.route(h, tied, cfg)
    assert (np.asarray(ids) == np.arange(cfg.top_k)[None]).all()
    assert np.allclose(np.asarray(w), cfg.routed_scaling / cfg.top_k)
    lifted = dict(tied, router_bias=jnp.zeros((cfg.n_experts,)).at[7].set(1.))
    ids, w = mla_moe.route(h, lifted, cfg)
    assert (np.asarray(ids)[:, 0] == 7).all()
    assert np.allclose(np.asarray(w), cfg.routed_scaling / cfg.top_k)


@pytest.mark.parametrize("rows", [5, 40])
def test_eight_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(
        tiny, rows):
    """Every chip computes its own experts' part and the shared expert
    alike: the routed parts of all 8 shares (one expert each) + the shared
    expert counted ONCE are what the uncut reference gives for the whole
    expert layer."""
    cfg, _ = tiny
    whole = dataclasses.replace(cfg, experts_held=8, expert_offset=0)
    params = S.init_params(whole, ref.weight_key(SEED))
    layer = params["layers"][2]
    rng = np.random.default_rng(rows)
    h = jnp.asarray(rng.standard_normal((rows, cfg.dim)), jnp.float32)
    total = 0
    for e in range(8):
        share = dataclasses.replace(cfg, experts_held=1, expert_offset=e)
        held = dict(layer, w_gate_up=layer["w_gate_up"][e:e + 1],
                    w_down=layer["w_down"][e:e + 1])
        part, st = mla_moe.routed_experts(h, held, share, interpret=True)
        assert int(st[0]) == rows * cfg.top_k
        # a share's own draw is the uncut layer's slice (global ids)
        own = S.init_params(share, ref.weight_key(SEED))["layers"][2]
        assert np.array_equal(own["w_down"], held["w_down"])
        total = total + part
    with jax.default_matmul_precision("highest"):
        total = total + mla_moe._dense_prompt_ffn(h, layer["shared"])
        uncut = hf_config(whole)
        s = ref.sizes(uncut)
        w = {k: v.astype(jnp.float32)
             for k, v in ref.draw_layer(uncut, SEED, 2, jnp.float32).items()}
        want = ref.mlp(h, w, s, False)
    assert np.abs(np.asarray(total - want)).max() < 1e-4
    # and the program's whole layer (ffn: routed + shared) is the same sum
    got = mla_moe.ffn(h, layer, cfg=whole, interpret=True)
    assert np.abs(np.asarray(got - want)).max() < 1e-4


def test_the_lead_layer_is_dense_and_the_rest_hold_what_the_config_says(tiny):
    cfg, params = tiny
    lead, rest = params["layers"][0], params["layers"][1:]
    assert "router" not in lead and lead["wgate"].shape == (128, 256)
    assert lead["wq"].shape == (128, 4 * 128) and lead["wg"].shape == (128, 4)
    for layer, heads in zip(rest, (6, 6, 6, 4)):
        assert layer["wq"].shape == (128, heads * 128)
        assert layer["wo"].shape == (heads * 128, 128)
        assert layer["wg"].shape == (128, heads)
        assert layer["wk"].shape == layer["wv"].shape == (128, 2 * 128)
        assert layer["router"].shape == (128, 8)
        assert layer["w_down"].shape == (4, 128, 128)
        assert layer["shared"]["wdown"].shape == (128, 128)
    assert cfg.row_tile(64) == 32
    # 32 of 256 at top-10: 2.5 rows an expert a 64-row step, 80 a chunk
    big = dataclasses.replace(cfg, n_experts=256, experts_held=32, top_k=10)
    assert (big.row_tile(64), big.row_tile(2048)) == (32, 128)


# ---------------------------------------------------------------------------
# from_hf: the published file, the refusals
# ---------------------------------------------------------------------------


def test_from_hf_reads_the_published_keys():
    from benchmarks import builders_swa_moe

    with open(CONFIG_FILE) as f:
        c = json.load(f)
    # the builder hands from_hf the WHOLE file, bookkeeping keys included
    cfg = builders_swa_moe.model_config(c)
    assert (cfg.dim, cfg.n_kv_heads, cfg.head_dim) == (3072, 8, 128)
    assert cfg.heads_by_layer == (48, 72, 72, 72, 48, 72, 72, 72, 48)
    assert cfg.layer_types == ("full", "window", "window", "window") * 2 \
        + ("full",)
    assert cfg.heads_by_kind == {"full": 48, "window": 72}
    assert cfg.gated and cfg.sliding_window == 512
    assert (cfg.n_experts, cfg.experts_held, cfg.expert_offset, cfg.top_k,
            cfg.moe_ffn_dim, cfg.shared_ffn_dim) == (256, 32, 0, 10, 1024,
                                                     1024)
    assert (cfg.ffn_dim, cfg.first_k_dense) == (12288, 1)
    assert cfg.router == "sigmoid_noaux" and cfg.routed_scaling == 2.5
    assert (cfg.n_group, cfg.topk_group, cfg.norm_topk_prob) == (1, 1, True)
    assert cfg.vocab == 100352 // 8 and cfg.max_seq == 10240
    assert cfg.yarn == (128.0, 8192, 32.0, 1.0, 1.4852030263919618)
    assert (cfg.rope_theta, cfg.rope_theta_window) == (5e5, 1e4)
    assert cfg.rotary == (0.5, 1.0)
    f, scale = cfg.rope("full")
    w, one = cfg.rope("window")
    assert (f.shape, w.shape, scale, one) == ((32,), (64,), cfg.yarn[4], 1.0)
    assert [k.group for k in cfg.kinds] == [0, 1, 1, 1, 0, 1, 1, 1, 0]
    assert cfg.kinds[1] == LayerKind("window", 512, 1)
    gen = S.SwaMoeGenerator(cfg, max_seq=cfg.max_seq)
    assert gen.kv_planes == [(8, 128)] * 2
    assert [g["heads"] for g in gen.kv_groups] == [48, 72]
    assert [g["layers"] for g in gen.kv_groups] == [(0, 4, 8),
                                                    (1, 2, 3, 5, 6, 7)]


@pytest.mark.parametrize("over,why", [
    ({"qk_layernorm": True}, "qk_layernorm"),
    ({"gating": "per-channel"}, "gating"),
    ({"gating_types": ["per_head"] * 4 + ["none"]}, "gating_types"),
    ({"num_attention_heads_per_layer": [4, 6, 6, 6]},
     "num_attention_heads_per_layer has 4 entries"),
    ({"num_attention_heads_per_layer": [4, 6, 6, 5, 4]}, "multiple of"),
    ({"mlp_layer_types": ["dense", "sparse", "dense", "sparse", "sparse"]},
     "'dense' at layers [0, 2]"),
    ({"mlp_only_layers": [1]}, "mlp_only_layers"),
    ({"moe_apply_router_weight_on_input": True},
     "moe_apply_router_weight_on_input"),
    ({"moe_router_logit_softcapping": 30.0}, "moe_router_logit_softcapping"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"model_type": "qwen3_moe"}, "model_type"),
])
def test_from_hf_refuses_by_name(tiny, over, why):
    cfg, _ = tiny
    with pytest.raises(ValueError) as e:
        S.SwaMoeConfig.from_hf(hf_config(cfg, **over), max_seq=256)
    assert why in str(e.value)


def test_from_hf_round_trips_the_tiny_block(tiny):
    cfg, _ = tiny
    c = hf_config(cfg)
    got = S.SwaMoeConfig.from_hf(
        c, max_seq=256, dtype=jnp.float32, experts_total=8, expert_offset=4)
    assert got == cfg


def test_rope_by_kind_against_the_reference(tiny):
    """YaRN over HALF the head at theta 5e5 on full layers, plain RoPE over
    the whole head at theta 1e4 on window layers — at the published numbers
    too."""
    cfg, _ = tiny
    with open(CONFIG_FILE) as f:
        published = json.load(f)
    big = S.SwaMoeConfig.from_hf(published, max_seq=10240)
    for c, file in ((cfg, hf_config(cfg)), (big, published)):
        s = ref.sizes(file)
        for attn, name in (("full", ref.FULL_KIND),
                           ("window", ref.WINDOW_KIND)):
            got_f, got_s = c.rope(attn)
            want_f, want_s = ref.rope_of(s, name)
            assert np.array_equal(got_f, want_f) and got_s == want_s
    f, _ = big.rope("full")
    w, _ = big.rope("window")
    # fast pairs are left alone, slow pairs interpolated by the factor
    assert f[0] == 1.0 and f[-1] == pytest.approx(
        5e5 ** (-62 / 64) / 128.0)
    assert w[-1] == pytest.approx(1e4 ** (-126 / 128))
    # lanes behind the rotary ones pass through untouched
    x = jnp.asarray(np.random.default_rng(0).standard_normal((1, 3, 2, 128)),
                    jnp.float32)
    pos = jnp.asarray([[5, 6, 7]], jnp.int32)
    out = S._rope_lanes(x, pos, f, 1.25)
    assert np.array_equal(out[..., 64:], x[..., 64:])
    assert not np.allclose(out[..., :64], x[..., :64])


# ---------------------------------------------------------------------------
# The paged call at query groups of 9 and 6 rows a KV head
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("hq,window", [(72, 200), (48, 0)])
def test_paged_kernel_at_the_groups_of_nine_and_six(hq, window):
    """The Mosaic paged call (interpreter) at the published head counts
    over 8 KV heads — q blocks of 9 and 6 rows — against its XLA twin."""
    rng = np.random.default_rng(hq)
    B, Hkv, D, page, n_pages = 2, 8, 128, 128, 4
    pools = rng.standard_normal((2, 1 + B * n_pages, Hkv, page, D)).astype(
        np.float32)
    k_pool, v_pool = jnp.asarray(pools[0]), jnp.asarray(pools[1])
    lens = jnp.asarray([500, 130], jnp.int32)
    tables = jnp.asarray(
        1 + np.arange(B * n_pages, dtype=np.int32).reshape(B, n_pages))
    q = jnp.asarray(rng.standard_normal((B, hq, D)), jnp.float32)
    kind = LayerKind("window" if window else "full", window, 0)
    out, _ = fd.gqa_decode_paged_shard(
        q, k_pool, v_pool, tables, lens, interpret=True, window=window,
        name=kind.call_name)
    want, _ = fd.gqa_decode_paged_shard(
        q, k_pool, v_pool, tables, lens, impl="xla", window=window)
    assert out.shape == (B, hq, D)
    assert np.abs(np.asarray(out - want)).max() < 1e-5
