"""Window and global layers over softmax-routed experts (models/swa_moe.py,
serve/block_manager.py ``KvGroups``) on the CPU: tiny sizes (window 16,
page 8, two periods of three window layers to one full, 8 experts top-2,
float32), seeded weights.

The yardstick is ``benchmarks/reference/swa_moe.py`` — the plain float32
reference of the same equations (no cache, its own weights from the seed),
which imports nothing of the program.
"""

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from chunk_rows import filed_chunk_call

from triton_dist_tpu.kernels import flash_decode as fd
from triton_dist_tpu.models import mla_moe
from triton_dist_tpu.models import swa_moe as S
from triton_dist_tpu.models.generate import LayerKind
from triton_dist_tpu.serve import Request, SamplingParams, ServeEngine
from triton_dist_tpu.serve.block_manager import (
    BlockExhausted,
    BlockManager,
    KvGroups,
    KvGroupsUnsupported,
)

ref = importlib.import_module("benchmarks.reference.swa_moe")

SEED = 2 ** 31 + 7          # past 32 signed bits, like the driver's seeds
WINDOW, PAGE = 16, 8


def hf_config(cfg: S.SwaMoeConfig, **over) -> dict:
    """The configuration-file keys of ``cfg`` (what the reference and
    ``from_hf`` read)."""
    factor, orig, fast, slow, att = cfg.yarn
    names = {v: k for k, v in S.ATTN_KINDS.items()}
    c = {
        "model_type": "mellum", "vocab_size": cfg.vocab,
        "hidden_size": cfg.dim, "num_hidden_layers": cfg.n_layers,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "intermediate_size": 4 * cfg.dim,     # used by no layer
        "moe_intermediate_size": cfg.moe_ffn_dim,
        "num_experts": cfg.experts_held,
        "share": {"experts_total": cfg.n_experts,
                  "expert_offset": cfg.expert_offset},
        "num_experts_per_tok": cfg.top_k,
        "norm_topk_prob": cfg.norm_topk_prob,
        "layer_types": [names[t] for t in cfg.layer_types],
        "mlp_layer_types": ["sparse"] * cfg.n_layers,
        "sliding_window": cfg.sliding_window, "use_sliding_window": True,
        "rope_parameters": {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": cfg.rope_theta,
                "factor": factor, "original_max_position_embeddings": orig,
                "beta_fast": fast, "beta_slow": slow,
                "attention_factor": att},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": cfg.rope_theta}},
        "rms_norm_eps": cfg.norm_eps, "hidden_act": "silu",
        "attention_bias": False, "tie_word_embeddings": False,
    }
    c.update(over)
    return c


@pytest.fixture(scope="module")
def tiny():
    ref.Q_BLOCK = ref.T_BLOCK = 32
    cfg = S.SwaMoeConfig.tiny()
    params = S.init_params(cfg, ref.weight_key(SEED))
    return cfg, params


def _gen(cfg, interpret=False, **kw):
    return S.SwaMoeGenerator(cfg, max_seq=256, interpret=interpret, **kw)


def _engine(gen, params, **kw):
    kw.setdefault("num_blocks", 64)
    kw.setdefault("page_size", PAGE)
    kw.setdefault("max_batch", 2)
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("prefix_cache", False)
    kw.setdefault("trace_level", 0)
    return ServeEngine(gen, params, **kw)


def _serve(eng, prompts, n_new, **params):
    for i, p in enumerate(prompts):
        eng.submit(Request(f"r{i}", p, SamplingParams(max_new_tokens=n_new,
                                                      **params)))
    outs = eng.run(4000)
    return [list(outs[f"r{i}"].token_ids) for i in range(len(prompts))]


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, n).astype(np.int32) for n in lens]


# ---------------------------------------------------------------------------
# The engine against the reference: logits through BOTH groups
# ---------------------------------------------------------------------------

# float32 program against the float32 reference: the two differ by the
# order of float32 sums (blocked softmax against one row's, grouped against
# per-expert matmuls, the window applied as a mask against pages never
# read) — observed ~1e-5 on logits of magnitude ~3 through eight layers.
# The same engine in bfloat16 reads ~5e-2: a precision below the one the
# configuration states fails.
LOGIT_TOL = 2e-4


def _served_logits(gen, params, prompt, n_new):
    """One request through chunked prefill and single-step paged decode
    over both groups' tables, with every program's logits kept: ->
    (tokens, logits [S0 + n_new - 1, V] — row j is the model's output at
    position j, the engine)."""
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
    return (toks, np.stack([rows[j] for j in range(len(prompt) + n_new - 1)]),
            eng)


def test_engine_logits_match_reference_and_bf16_does_not(tiny):
    """Chunked prefill (five chunks, the last padded), then paged decode
    through the full AND the window group — the context crosses the
    16-token window six times, and the window group gives pages back on
    the way — against the reference's one full forward pass over prompt +
    served tokens."""
    cfg, params = tiny
    assert cfg.layer_types == ("window",) * 3 + ("full",) + \
        ("window",) * 3 + ("full",)
    prompt, = _prompts(cfg, [70])
    toks, got, eng = _served_logits(_gen(cfg, interpret=True), params,
                                    prompt, 30)
    assert isinstance(eng.bm, KvGroups)
    assert eng.metrics.kv_window_released > 0
    seq = np.concatenate([prompt, np.asarray(toks, np.int32)])
    want = ref.forward_logits(hf_config(cfg), SEED, [seq], [1],
                              dtype=jnp.float32)[0]
    assert got.shape == want.shape
    assert np.abs(got - want).max() < LOGIT_TOL
    # the tolerance is tight enough to fail a lower precision
    low = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    p16 = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    _, got16, _ = _served_logits(_gen(low), p16, prompt, 2)
    # (position for position until the first served token could differ)
    n = len(prompt)
    assert np.abs(got16[:n] - want[:n]).max() > 10 * LOGIT_TOL


def test_whole_prompt_forward_matches_reference(tiny):
    """The cache-free prompt forward (window as a mask in flash attention)
    against the reference."""
    cfg, params = tiny
    prompt, = _prompts(cfg, [64], seed=3)
    got = np.asarray(_gen(cfg).forward_logits(params, prompt[None])[0])
    want = ref.forward_logits(
        hf_config(cfg), SEED, [np.concatenate([prompt, prompt[:1]])], [1],
        dtype=jnp.float32)[0]
    assert np.abs(got - want).max() < LOGIT_TOL


@pytest.mark.parametrize("sampled", [False, True])
def test_fused_horizon_equals_single_steps(tiny, sampled):
    """The fused horizon (H = 4, two links a chain: the window group holds
    what eight rows ahead need) emits the single-step engine's tokens."""
    cfg, params = tiny
    prompts = _prompts(cfg, [37, 21], seed=1)
    kw = dict(temperature=0.8, top_k=20, seed=11) if sampled else {}
    one = _serve(_engine(_gen(cfg), params), prompts, 40, **kw)
    eng = _engine(_gen(cfg), params, horizon=4, pipeline=2)
    assert _serve(eng, prompts, 40, **kw) == one
    assert eng.metrics.summary()["decode"]["tokens_per_dispatch"] > 2
    assert eng.bm.num_free == eng.bm.num_allocatable


# ---------------------------------------------------------------------------
# The expert layer: the router's kind as data, the shares, the row tile
# ---------------------------------------------------------------------------


def test_softmax_router_ids_weights_and_a_tie(tiny):
    cfg, params = tiny
    layer = params["layers"][0]
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.standard_normal((9, cfg.dim)), jnp.float32)
    ids, w = mla_moe.route(h, layer, cfg)
    s = ref.sizes(hf_config(cfg))
    chosen, wt = ref.route(h, {"router": layer["router"]}, s, False)
    got = np.zeros((9, cfg.n_experts), np.float32)
    np.put_along_axis(got, np.asarray(ids), np.asarray(w), axis=1)
    assert (np.asarray(chosen) == (got > 0)).all()
    assert np.abs(got - np.asarray(wt)).max() < 1e-6
    assert np.allclose(np.asarray(w).sum(-1), 1.0, atol=1e-6)
    # a tie goes to the lower id, in program and reference alike
    tied = {"router": jnp.zeros((cfg.dim, cfg.n_experts), jnp.float32)}
    ids, w = mla_moe.route(h, tied, cfg)
    assert (np.asarray(ids) == np.arange(cfg.top_k)[None]).all()
    assert np.allclose(np.asarray(w), 1.0 / cfg.top_k)
    chosen, _ = ref.route(h, tied, s, False)
    assert (np.asarray(chosen)[:, :cfg.top_k]).all()


@pytest.mark.parametrize("rows", [5, 40])
def test_four_shares_of_two_experts_add_up_to_the_uncut_layer(tiny, rows):
    """Every chip computes its own experts' part: four shares of 2 of the
    8 experts add up to what the uncut layer (and the reference's) gives —
    there is no shared expert to count once."""
    cfg, params = tiny
    layer = params["layers"][1]
    rng = np.random.default_rng(rows)
    h = jnp.asarray(rng.standard_normal((rows, cfg.dim)), jnp.float32)
    whole, stats = mla_moe.routed_experts(h, layer, cfg, interpret=True)
    assert int(stats[0]) == int(stats[1]) == rows * cfg.top_k
    total = 0
    for off in range(0, 8, 2):
        share = dataclasses.replace(cfg, experts_held=2, expert_offset=off)
        held = dict(layer, w_gate_up=layer["w_gate_up"][off:off + 2],
                    w_down=layer["w_down"][off:off + 2])
        part, st = mla_moe.routed_experts(h, held, share, interpret=True)
        total = total + part
    assert np.abs(np.asarray(total - whole)).max() < 1e-5
    s = ref.sizes(hf_config(cfg))
    F = cfg.moe_ffn_dim
    w = {"router": layer["router"], "e_gate": layer["w_gate_up"][..., :F],
         "e_up": layer["w_gate_up"][..., F:], "e_down": layer["w_down"]}
    with jax.default_matmul_precision("highest"):
        want = ref.routed_share(h, w, s, False)
    assert np.abs(np.asarray(whole - want)).max() < 1e-4


def test_row_tile_follows_the_rows_of_the_program():
    """8 rows an expert in a 64-row decode step, 256 in a 2,048-token
    chunk (64 experts, top-8): the tile holds them in one piece; the
    latent family keeps its fixed option."""
    c = S.SwaMoeConfig.tiny(n_experts=64, experts_held=64, top_k=8)
    assert (c.row_tile(64), c.row_tile(512), c.row_tile(2048),
            c.row_tile(8192)) == (32, 64, 256, 256)
    m = mla_moe.MlaMoeConfig.tiny()
    assert m.row_tile(64) == m.row_tile(2048) == m.moe_block_m


# ---------------------------------------------------------------------------
# The cache allocator for window and global layers
# ---------------------------------------------------------------------------


def test_window_manager_skips_and_releases_what_no_query_sees():
    bm = BlockManager(12, PAGE, window=WINDOW)
    # 41 rows: the first query sits at 40 and sees keys 25..40 -> pages 3..5
    assert (bm.first_seen_page(40), bm.pages_held(41)) == (3, 3)
    table = bm.allocate("a", 41)
    assert table[:3] == [0, 0, 0] and 0 not in table[3:] and len(table) == 6
    assert bm.num_free == 11 - 3
    bm.ensure("a", 57)                   # pages 6, 7 for rows 48..56
    assert bm.capacity_tokens("a") == 64
    assert bm.release_unseen("a", 47) == 1   # keys 32.. : page 3 goes
    assert bm.table("a")[:4] == [0, 0, 0, 0] and bm.released == 1
    assert bm.release_unseen("a", 47) == 0
    assert list(bm.page_ids("a", 0, 6, 10)[:4]) == [0, 0, 0, 0]
    bm.free("a")
    assert bm.num_free == bm.num_allocatable == 11
    with pytest.raises(KvGroupsUnsupported):
        BlockManager(12, PAGE, window=WINDOW, prefix_cache=True)
    # without a window nothing is skipped or released
    full = BlockManager(12, PAGE)
    assert 0 not in full.allocate("a", 41) and full.release_unseen("a", 40) == 0


def test_groups_hold_for_both_or_for_neither():
    bm = KvGroups({"full": BlockManager(8, PAGE),
                   "window": BlockManager(6, PAGE, window=WINDOW)})
    assert (bm.num_allocatable, bm.num_free, bm.utilization) == (12, 12, 0.0)
    bm.allocate("a", 41)                 # 6 of 7 full, 3 of 5 window
    assert bm.num_free == 12 - 9 and bm.utilization == pytest.approx(6 / 7)
    # load is the share of the group that grows with the context: a window
    # group filled by the rows of the batch says nothing of it
    lone = KvGroups({"full": BlockManager(64, PAGE),
                     "window": BlockManager(4, PAGE, window=WINDOW)})
    lone.allocate("a", 20)               # 3 of 63 full, 3 of 3 window
    assert lone.groups["window"].utilization == 1.0
    assert lone.utilization == pytest.approx(3 / 63)
    assert lone.group_stats()["window"]["peak"] == 3
    assert BlockManager(8, PAGE).group_stats() == {}
    assert not bm.can_allocate(17)       # the full group has 1 page left
    with pytest.raises(BlockExhausted):
        bm.allocate("b", 17)
    assert bm.groups["window"].num_free == 2     # neither group moved
    assert np.asarray(bm.padded_table("a", 8)).shape == (2, 8)
    assert bm.page_ids("a", 0, 6, 8).shape == (2, 8)
    assert bm.fit_error(100) is not None and bm.fit_error(50) is None
    bm.free("a")
    assert bm.num_free == bm.num_allocatable


def test_window_group_stays_bounded_while_the_full_group_grows(tiny):
    """Two requests decode 150 tokens each past a 40-token prompt: the
    window group never holds more than its derived count, the full group
    grows with the context, and after the drain both free lists are
    whole."""
    cfg, params = tiny
    eng = _engine(_gen(cfg), params, horizon=4, pipeline=2)
    # a row's live span: window 16 + 8 rows ahead -> (16 + 8 - 2) // 8 + 2
    assert eng.group_blocks == [64, 1 + 2 * 4]
    seen = {"full": [], "window": []}
    step = eng.step

    def watched():
        out = step()
        for g, st in eng.bm.group_stats().items():
            seen[g].append(st["in_use"])
        return out

    eng.step = watched
    _serve(eng, _prompts(cfg, [40, 40], seed=4), 150)
    assert max(seen["window"]) <= 8
    assert max(seen["full"]) >= 2 * (190 // PAGE)
    stats = eng.metrics.summary()
    assert stats["kv"]["groups"]["window"]["peak"] <= 8
    assert stats["kv"]["groups"]["window"]["released"] \
        == stats["swa"]["window_released_pages"] > 30
    # 6 window layers read min(ctx, 16) tokens a query, 2 full layers ctx
    assert 0 < stats["swa"]["window_share"] < 6 / 8
    assert eng.bm.num_free == eng.bm.num_allocatable
    text = eng.metrics.to_prometheus()
    assert 'serve_kv_group_blocks_in_use{group="window"} 0' in text
    assert "serve_kv_window_released_total" in text
    assert "serve_swa_window_tokens_total" in text


def test_a_full_window_group_is_not_load(tiny):
    """A full batch of contexts past the window holds the window group at
    its derived count — every row's worst case — while the full group,
    the only one that can run out, is nearly empty: the brownout ladder
    (default ``high`` 0.85) stays on rung 0 and sheds nothing, and the
    engine reports the full group's share as its load."""
    cfg, params = tiny
    eng = _engine(_gen(cfg), params, num_blocks=256, horizon=4, pipeline=2,
                  brownout=dict(dwell_steps=1, window_s=0.0))
    fullest = {"window": 0.0, "load": 0.0, "rung": 0}
    step = eng.step

    def watched():
        out = step()
        w = eng.bm.groups["window"]
        fullest["window"] = max(fullest["window"], w.utilization)
        fullest["load"] = max(fullest["load"], eng.bm.utilization)
        fullest["rung"] = max(fullest["rung"], eng.brownout_rung)
        return out

    eng.step = watched
    toks = _serve(eng, _prompts(cfg, [40, 40], seed=6), 60)
    assert [len(t) for t in toks] == [60, 60]
    assert fullest["window"] > 0.85          # it would have climbed on this
    assert fullest["load"] < 0.15 and fullest["rung"] == 0
    assert eng.metrics.slo_stats()["brownout_transitions"] == 0
    assert eng.metrics.kv_util_peak == pytest.approx(fullest["load"])


def test_preemption_and_recompute_equal_an_undisturbed_run(tiny):
    cfg, params = tiny
    prompts = _prompts(cfg, [40, 44], seed=5)
    calm = _serve(_engine(_gen(cfg), params, horizon=4), prompts, 60)
    eng = _engine(_gen(cfg), params, horizon=4, num_blocks=20)
    assert _serve(eng, prompts, 60) == calm
    assert eng.metrics.preemptions > 0
    assert eng.bm.num_free == eng.bm.num_allocatable


def test_one_group_builds_the_engine_as_it_was(tiny):
    """A model of one layer kind has one table a request, one manager, no
    group axis and the default prefix cache."""
    cfg = S.SwaMoeConfig.tiny(n_layers=2, layer_types=("full", "full"))
    params = S.init_params(cfg, ref.weight_key(SEED))
    gen = _gen(cfg)
    assert len(gen.kv_groups) == 1
    eng = ServeEngine(gen, params, num_blocks=32, page_size=PAGE,
                      max_batch=2, prefill_chunk=16, trace_level=0)
    assert type(eng.bm) is BlockManager and eng.kv_groups is None
    assert eng._tables_shape == (2, 256 // PAGE)
    prompt, = _prompts(cfg, [30], seed=6)
    toks, = _serve(eng, [prompt], 6)
    seq = np.concatenate([prompt, np.asarray(toks, np.int32)])
    want = ref.forward_logits(hf_config(cfg), SEED, [seq], [len(prompt)],
                              dtype=jnp.float32)[0]
    assert list(want.argmax(-1)) == toks


# ---------------------------------------------------------------------------
# What the two-group engine does not carry is refused by name
# ---------------------------------------------------------------------------


def _refusal_cases():
    def build(**kw):
        return lambda cfg, params, tmp: _engine(_gen(cfg), params, **kw)

    def call(method, *args, **kw):
        def run(cfg, params, tmp):
            getattr(_engine(_gen(cfg), params), method)(*args, **kw)
        return run

    def mesh(cfg, params, tmp):
        from jax.sharding import Mesh
        _engine(_gen(cfg), params,
                mesh=Mesh(np.array(jax.devices()[:1]), ("tp",)))

    def int8(cfg, params, tmp):
        _engine(_gen(cfg, kv_dtype=jnp.int8), params)

    def spec(cfg, params, tmp):
        _engine(_gen(cfg), params, spec_k=2, draft=_gen(cfg),
                draft_params=params)

    def snapshot_dir(cfg, params, tmp):
        _engine(_gen(cfg), params, snapshot_dir=str(tmp))

    def restore(cfg, params, tmp):
        ServeEngine.restore(str(tmp), _gen(cfg), params)

    return [
        ("a mesh", mesh), ("int8 pools", int8),
        ("w8a8 weights", build(w8a8=True)), ("speculative rounds", spec),
        ("snapshot_dir", snapshot_dir),
        ("prefix_cache=True", build(prefix_cache=True)),
        ("snapshot()", call("snapshot")), ("restore()", restore),
        ("drain() / migrate-out", call("drain")),
        ("migrate_in()", call("migrate_in", {})),
        ("push_out()", call("push_out", "r0")),
        ("admit_pushed()", call("admit_pushed", {})),
    ]


@pytest.mark.parametrize("what,run", _refusal_cases(),
                         ids=[w for w, _ in _refusal_cases()])
def test_unsupported_over_cache_groups_is_refused_by_name(tiny, tmp_path,
                                                          what, run):
    cfg, params = tiny
    with pytest.raises(KvGroupsUnsupported) as e:
        run(cfg, params, tmp_path)
    assert what in str(e.value) and "full, window" in str(e.value)


# ---------------------------------------------------------------------------
# from_hf, RoPE by kind
# ---------------------------------------------------------------------------


def test_from_hf_reads_the_published_keys():
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "benchmarks/configs/mellum2-12b-a2.5b-l8.json")) as f:
        c = json.load(f)
    cfg = S.SwaMoeConfig.from_hf(c, max_seq=c["engine"]["max_seq"])
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == \
        (2304, 32, 4, 128)
    assert cfg.dim // cfg.n_heads == 72          # head_dim is its own key
    assert (cfg.n_experts, cfg.experts_held, cfg.top_k, cfg.moe_ffn_dim) \
        == (64, 64, 8, 896)
    assert cfg.layer_types == ("window",) * 3 + ("full",) + \
        ("window",) * 3 + ("full",)
    assert cfg.sliding_window == 1024 and cfg.vocab == 98304
    assert cfg.yarn == (16.0, 8192, 32.0, 1.0, 1.2772588722239782)
    assert cfg.router == "softmax" and cfg.norm_topk_prob
    assert [k.group for k in cfg.kinds] == [1, 1, 1, 0] * 2
    assert cfg.kinds[0] == LayerKind("window", 1024, 1)
    assert cfg.kinds[3].call_name == "gqa_paged_full"


@pytest.mark.parametrize("over,why", [
    ({"model_type": "qwen3_moe"}, "model_type"),
    ({"layer_types": ["linear_attention"] * 8}, "linear_attention"),
    ({"mlp_layer_types": ["sparse"] * 7 + ["dense"]}, "dense"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"rope_parameters": {"full_attention": {"rope_type": "llama3",
                                             "rope_theta": 1e4}}},
     "full_attention"),
])
def test_from_hf_refuses_by_name(tiny, over, why):
    cfg, _ = tiny
    with pytest.raises(ValueError) as e:
        S.SwaMoeConfig.from_hf(hf_config(cfg, **over), max_seq=256)
    assert why in str(e.value)


def test_rope_by_kind_against_the_reference(tiny):
    """YaRN's inverse frequencies and cos / sin factor on full layers,
    plain RoPE on window layers — at the published numbers too."""
    cfg, _ = tiny
    big = dataclasses.replace(
        cfg, rope_theta=5e5, yarn=(16.0, 8192, 32.0, 1.0,
                                   1.2772588722239782))
    for c in (cfg, big):
        s = ref.sizes(hf_config(c))
        for attn, name in (("full", ref.FULL_KIND), ("window",
                                                     ref.WINDOW_KIND)):
            got_f, got_s = c.rope(attn)
            want_f, want_s = ref.rope_of(s, name)
            assert np.array_equal(got_f, want_f) and got_s == want_s
    f, scale = big.rope("full")
    plain, one = big.rope("window")
    assert scale == pytest.approx(0.1 * np.log(16.0) + 1.0) and one == 1.0
    # fast pairs are left alone, slow pairs interpolated by the factor
    assert f[0] == plain[0] and f[-1] == pytest.approx(plain[-1] / 16.0)
    # the latent family's rotary columns come from the same function
    m = mla_moe.MlaMoeConfig.tiny()
    assert np.array_equal(
        mla_moe.yarn_inv_freq(m),
        mla_moe.rope_inv_freq(m.qk_rope_head_dim, m.rope_theta, m.yarn))


# ---------------------------------------------------------------------------
# The paged call under a layer's own window and name
# ---------------------------------------------------------------------------


def test_paged_kernel_walks_from_the_window_and_never_reads_released_pages():
    """The Mosaic paged call (interpreter) under a per-layer window over a
    table whose pages behind the window hold the NULL block, filled with
    NaNs: the walk starts at the window's first page."""
    rng = np.random.default_rng(0)
    B, Hq, Hkv, D, page, n_pages, W = 2, 4, 2, 128, 128, 6, 200
    pools = rng.standard_normal((2, 1 + B * n_pages, Hkv, page, D)).astype(
        np.float32)
    pools[:, 0] = np.nan                          # the null block
    k_pool, v_pool = jnp.asarray(pools[0]), jnp.asarray(pools[1])
    lens = np.array([700, 300], np.int32)
    tables = 1 + np.arange(B * n_pages, dtype=np.int32).reshape(B, n_pages)
    held = tables.copy()
    for b in range(B):
        held[b, :(lens[b] - W) // page] = 0       # released
    q = jnp.asarray(rng.standard_normal((B, Hq, D)), jnp.float32)
    kind = LayerKind("window", W, 1)
    out, _ = fd.gqa_decode_paged_shard(
        q, k_pool, v_pool, jnp.asarray(held), jnp.asarray(lens),
        interpret=True, window=kind.window, name=kind.call_name)
    want, _ = fd.gqa_decode_paged_shard(
        q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(lens),
        impl="xla", window=W)
    assert np.isfinite(np.asarray(out)).all()
    assert np.abs(np.asarray(out - want)).max() < 1e-5
