"""A matrix state beside pages (models/gdn_hybrid.py, kernels/gated_delta.py,
serve/block_manager.py ``StateSlots``) on the CPU: tiny sizes (one period:
linear 0 1 2, full 3; 2 full heads of 128; 4 linear heads with keys 32 and
values 64 wide, so a state of ``[32, 256]`` float32 a layer; page 8,
float32), seeded weights.

The yardstick is ``benchmarks/reference/gdn_hybrid.py`` — the plain float32
reference of the same equations (no cache, no state pool, the delta rule a
``lax.scan`` over tokens, its own weights from the seed), which imports
nothing of the program.
"""

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from chunk_rows import filed_chunk_call

from triton_dist_tpu.kernels import gated_delta as K
from triton_dist_tpu.models import gdn_hybrid as G
from triton_dist_tpu.serve import Request, SamplingParams, ServeEngine
from triton_dist_tpu.serve.block_manager import (
    KvGroups,
    KvGroupsUnsupported,
    StateCacheUnsupported,
    StateSlots,
)

ref = importlib.import_module("benchmarks.reference.gdn_hybrid")

SEED = 2 ** 31 + 7          # past 32 signed bits, like the driver's seeds
PAGE = 8


def hf_config(cfg: G.GdnHybridConfig, **over) -> dict:
    """The ``olmo_hybrid`` keys of ``cfg`` (what the reference and
    ``from_hf`` read)."""
    names = {v: k for k, v in G.LAYER_KINDS.items()}
    c = {
        "model_type": "olmo_hybrid", "vocab_size": cfg.vocab,
        "hidden_size": cfg.dim, "intermediate_size": cfg.ffn_dim,
        "num_hidden_layers": cfg.n_layers,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "hidden_act": "silu",
        "max_position_embeddings": cfg.max_seq, "attention_bias": False,
        "rms_norm_eps": cfg.norm_eps, "tie_word_embeddings": False,
        "layer_types": [names[t] for t in cfg.layer_types],
        "linear_num_key_heads": cfg.lin_heads,
        "linear_num_value_heads": cfg.lin_heads,
        "linear_key_head_dim": cfg.lin_k_dim,
        "linear_value_head_dim": cfg.lin_v_dim,
        "linear_conv_kernel_dim": cfg.conv_kernel,
        "linear_allow_neg_eigval": cfg.allow_neg_eigval,
        "rope_parameters": {"rope_theta": None},
    }
    c.update(over)
    return c


@pytest.fixture(scope="module")
def tiny():
    ref.Q_BLOCK = ref.T_BLOCK = 32
    cfg = G.GdnHybridConfig.tiny()
    params = G.init_params(cfg, ref.weight_key(SEED))
    return cfg, params


def _gen(cfg, interpret=False, **kw):
    return G.GdnHybridGenerator(cfg, max_seq=256, interpret=interpret, **kw)


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
# The engine against the reference: logits through both groups
# ---------------------------------------------------------------------------

# float32 program against the float32 reference: the two differ by the
# order of float32 sums (blocked softmax against one row's; the WY form's
# products over a 64-row sub-chunk from a carried state against one rank-1
# update a token) — observed ~8e-5 on logits of magnitude ~4 through four
# post-normed layers.  The same engine in bfloat16 reads ~5e-2, and the
# reference with its matrix state rounded to bfloat16 after every step
# ~1e-2: a precision below the one the configuration states fails.
LOGIT_TOL = 5e-4


def _served_logits(gen, params, prompt, n_new, **kw):
    """One request through chunked prefill and paged decode over both
    groups, with every program's logits kept: -> (tokens, logits by
    position, the engine)."""
    eng = _engine(gen, params, **kw)
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
    return toks, rows, eng


def _reference_rows(cfg, prompt, toks, **kw):
    seq = np.concatenate([prompt, np.asarray(toks, np.int32)])
    return ref.forward_logits(hf_config(cfg), SEED, [seq], [1],
                              dtype=jnp.float32, **kw)[0]


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["xla_twins", "mosaic_interpreted"])
def test_engine_logits_match_reference(tiny, interpret):
    """Chunked prefill (the last chunk padded: the matrix state goes from
    chunk to chunk in the request's scratch), then single-step paged
    decode through the full AND the state group (``gdn_step`` in the
    request's slot) against the reference's ONE full forward pass over
    prompt + served tokens: logits, not tokens.  Once through the XLA
    twins (chunks of 16 rows: every call ends off a 64-boundary), once
    through the Mosaic calls in the interpreter (chunks of 64)."""
    cfg, params = tiny
    prompt, = _prompts(cfg, [70])
    toks, rows, eng = _served_logits(
        _gen(cfg, interpret=interpret), params, prompt, 20,
        prefill_chunk=64 if interpret else 16)
    assert isinstance(eng.bm, KvGroups)
    assert isinstance(eng.bm.groups["state"], StateSlots)
    assert ("gdn_chunk" in eng.kernel_gaps) == (not interpret)
    assert ("gdn_step" in eng.kernel_gaps) == (not interpret)
    got = np.stack([rows[j] for j in range(len(prompt) + 20 - 1)])
    want = _reference_rows(cfg, prompt, toks)
    assert got.shape == want.shape
    assert np.abs(got - want).max() < LOGIT_TOL


def test_a_lower_precision_fails_the_tolerance(tiny):
    """The tolerance is tight enough that a bfloat16 model fails it, and
    that a matrix STATE rounded to bfloat16 after every token alone (the
    reference's control without its int8 operands: weights, activations
    and cache in float32) fails it."""
    cfg, params = tiny
    prompt, = _prompts(cfg, [70])
    want = _reference_rows(cfg, prompt, [0])
    low = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    p16 = G.init_params(low, ref.weight_key(SEED))
    _, rows16, _ = _served_logits(_gen(low), p16, prompt, 2)
    got16 = np.stack([rows16[j] for j in range(len(prompt))])
    assert np.abs(got16 - want).max() > 10 * LOGIT_TOL
    # the state alone: the reference's own recurrence, rounded every step
    rounded = _reference_rows(cfg, prompt, [0], state_bf16=True)
    assert np.abs(rounded - want).max() > 10 * LOGIT_TOL


def test_horizon_decode_logits_match_reference(tiny):
    """The fused horizon (H = 8, two links a chain: ``gdn_step`` on the
    pool inside the scan's carry) serves the tokens of the single-step
    engine, and their reference logits are the reference's best."""
    cfg, params = tiny
    prompts = _prompts(cfg, [37, 21], seed=1)
    one = _serve(_engine(_gen(cfg), params), prompts, 40)
    eng = _engine(_gen(cfg), params, horizon=8, pipeline=2)
    assert _serve(eng, prompts, 40) == one
    assert eng.metrics.summary()["decode"]["tokens_per_dispatch"] > 2
    assert eng.bm.num_free == eng.bm.num_allocatable
    for p, t in zip(prompts, one):
        seq = np.concatenate([p, np.asarray(t, np.int32)])
        want = ref.forward_logits(hf_config(cfg), SEED, [seq], [len(p)],
                                  dtype=jnp.float32)[0]
        gap = want.max(-1) - np.take_along_axis(
            want, np.asarray(t)[:, None], -1)[:, 0]
        assert gap.max() < LOGIT_TOL


@pytest.mark.parametrize("sampled", [False, True])
def test_fused_horizon_equals_single_steps(tiny, sampled):
    cfg, params = tiny
    prompts = _prompts(cfg, [33, 18], seed=2)
    kw = dict(temperature=0.8, top_k=20, seed=11) if sampled else {}
    one = _serve(_engine(_gen(cfg), params), prompts, 24, **kw)
    eng = _engine(_gen(cfg), params, horizon=4, pipeline=2)
    assert _serve(eng, prompts, 24, **kw) == one


def test_interpreted_horizon_steps_the_pool_in_place(tiny):
    """The Mosaic ``gdn_step`` (interpreter) inside the horizon's scan, a
    row finishing before the other (its slot parked on the null slot for
    the rest of the chain), serves the XLA twins' tokens."""
    cfg, params = tiny
    prompts = _prompts(cfg, [20, 12], seed=8)
    want = [_serve(_engine(_gen(cfg), params), [p], n)[0]
            for p, n in zip(prompts, (12, 5))]
    eng = _engine(_gen(cfg, interpret=True), params, horizon=4, pipeline=2,
                  prefill_chunk=64)
    eng.submit(Request("r0", prompts[0], SamplingParams(max_new_tokens=12)))
    eng.submit(Request("r1", prompts[1], SamplingParams(max_new_tokens=5)))
    outs = eng.run(4000)
    assert [list(outs[f"r{i}"].token_ids) for i in (0, 1)] == want
    # the null slot of every state plane is as it was made: zeros
    for li, kind in enumerate(cfg.kinds):
        if kind.state:
            assert not np.asarray(eng._pools[li][1][0]).any()


def test_whole_prompt_forward_matches_reference(tiny):
    cfg, params = tiny
    prompt, = _prompts(cfg, [64], seed=3)
    for interpret in (False, True):
        got = np.asarray(_gen(cfg, interpret=interpret).forward_logits(
            params, prompt[None])[0])
        want = ref.forward_logits(
            hf_config(cfg), SEED, [np.concatenate([prompt, prompt[:1]])],
            [1], dtype=jnp.float32)[0]
        assert np.abs(got - want).max() < LOGIT_TOL


# ---------------------------------------------------------------------------
# The state: N chunks = one pass, a reused slot starts from zero, preemption
# ---------------------------------------------------------------------------


def test_n_chunks_leave_the_state_of_one_pass(tiny):
    """Five prefill chunks (the last padded to the chunk's rows) leave in
    the request's slot, layer for layer, the state ONE pass over the
    prompt leaves, to the order of float32 sums."""
    cfg, params = tiny
    prompt, = _prompts(cfg, [70], seed=4)
    gen = _gen(cfg)
    eng = _engine(gen, params)
    layers = [li for li, k in enumerate(cfg.kinds) if k.state]
    filled = {}
    seam = eng._device_call

    def tapped(op, rids, fn, *a, **kw):
        out = seam(op, rids, fn, *a, **kw)
        if op == "fill_pages":
            slot = eng.bm.groups["state"].table(rids[0])[0]
            filled.update({li: [np.asarray(p[slot]) for p in out[li]]
                           for li in layers}, slot=slot)
        return out

    eng._device_call = tapped
    _serve(eng, [prompt], 4)
    assert filled["slot"] >= 1
    assert eng.metrics.prefill_dispatches == 5
    want = gen.forward_states(params, prompt[None])
    assert len(want) == len(layers) == 3
    for li, (conv, state) in zip(layers, want):
        got_conv, got_state = filled[li]
        assert got_state.shape == (cfg.lin_k_dim, cfg.value_dim)
        assert np.abs(got_conv.reshape(conv.shape[1:])
                      - np.asarray(conv[0])).max() < 1e-4
        assert np.abs(got_state - np.asarray(state[0])).max() < 1e-4


def test_a_reused_slot_starts_from_zero(tiny):
    """Two requests back to back through ONE slot give the streams they
    give alone: the second's first chunk starts from a zero state whatever
    the first left there."""
    cfg, params = tiny
    a, b = _prompts(cfg, [41, 29], seed=5)
    alone = [_serve(_engine(_gen(cfg), params, max_batch=1), [p], 12)[0]
             for p in (a, b)]
    eng = _engine(_gen(cfg), params, max_batch=1, horizon=4)
    assert eng.bm.groups["state"].num_allocatable == 1
    assert _serve(eng, [a, b], 12) == alone
    assert eng.metrics.state_resets == 2
    assert eng.metrics.summary()["gdn"]["state_slots_peak"] == 1


def test_preemption_and_recompute_equal_an_undisturbed_run(tiny):
    cfg, params = tiny
    prompts = _prompts(cfg, [40, 44], seed=6)
    calm = _serve(_engine(_gen(cfg), params, horizon=4), prompts, 60)
    eng = _engine(_gen(cfg), params, horizon=4, num_blocks=20)
    assert _serve(eng, prompts, 60) == calm
    assert eng.metrics.preemptions > 0
    assert eng.metrics.state_recomputed_tokens > 0
    assert eng.bm.num_free == eng.bm.num_allocatable


# ---------------------------------------------------------------------------
# The two kernels against the token-by-token recurrence
# ---------------------------------------------------------------------------


def _recurrence(q, k, v, beta, g, state):
    """The rule a token at a time: q, k [T, H, dk], v [T, H, dv], beta, g
    [T, H], state [dk, H * dv] -> (o [T, H * dv], the state after)."""
    T, H, dk = q.shape
    dv = v.shape[-1]
    S = np.asarray(state, np.float64).reshape(dk, H, dv).transpose(1, 0, 2)
    q, k, v, beta, g = (np.asarray(t, np.float64) for t in (q, k, v, beta, g))
    out = np.zeros((T, H, dv))
    for t in range(T):
        S = S * np.exp(g[t])[:, None, None]
        kept = np.einsum("hk,hkv->hv", k[t], S)
        S = S + k[t][:, :, None] * (beta[t][:, None] * (v[t] - kept))[:, None]
        out[t] = np.einsum("hk,hkv->hv", q[t], S)
    return out.reshape(T, H * dv), S.transpose(1, 0, 2).reshape(dk, H * dv)


def _operands(seed, T, H, dk, dv, slots=None):
    ks = jax.random.split(jax.random.key(seed), 6)
    q, k = (jax.random.normal(kk, (T, H, dk)) for kk in ks[:2])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (T, H, dv))
    beta = 2 * jax.nn.sigmoid(2 * jax.random.normal(ks[3], (T, H)))  # (0, 2)
    g = -jnp.exp(jax.random.normal(ks[4], (T, H)) - 2)
    state = jax.random.normal(ks[5], (slots or 1, dk, H * dv))
    return q, k, v, beta, g, state if slots else state[0]


CHUNK_CASES = [
    # T, H, dk, dv, valid rows, impl
    ("xla_two_sub_chunks", 128, 4, 32, 64, 128, "xla"),
    ("xla_off_a_64_boundary", 100, 2, 96, 192, 100, "xla"),
    ("xla_padded_rows", 128, 4, 32, 64, 77, "xla"),
    ("mosaic_two_sub_chunks", 128, 4, 32, 64, 128, "pallas"),
    ("mosaic_padded_rows", 128, 4, 32, 64, 77, "pallas"),
    ("mosaic_published_head", 64, 2, 96, 192, 50, "pallas"),
]


@pytest.mark.parametrize("name,T,H,dk,dv,valid,impl", CHUNK_CASES,
                         ids=[c[0] for c in CHUNK_CASES])
def test_gdn_chunk_against_the_token_recurrence(name, T, H, dk, dv, valid,
                                                impl):
    """``gdn_chunk`` — the Mosaic call in the interpreter and its XLA twin
    — against the recurrence a token at a time in float64: ``beta`` over
    (0, 2), a non-zero state carried in, a call that ends off a 64-row
    boundary, and padded rows (``beta = g = 0``) that leave the state as
    the last valid row left it."""
    q, k, v, beta, g, state = _operands(1, T, H, dk, dv)
    assert float(beta.max()) > 1.5 and float(beta.min()) < 0.5
    keep = (jnp.arange(T) < valid)[:, None]
    beta, g = jnp.where(keep, beta, 0.0), jnp.where(keep, g, 0.0)
    o, s1 = K.gdn_chunk(q, k, v, beta, g, state, impl=impl,
                        interpret=impl == "pallas")
    want_o, want_s = _recurrence(q[:valid], k[:valid], v[:valid],
                                 beta[:valid], g[:valid], state)
    assert np.abs(np.asarray(o)[:valid] - want_o).max() < 2e-5
    assert np.abs(np.asarray(s1) - want_s).max() < 2e-5


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("H,dk,dv", [(4, 32, 64), (30, 96, 192)],
                         ids=["tiny", "published"])
def test_gdn_step_in_place_against_the_recurrence(impl, H, dk, dv):
    """``gdn_step`` on a pool of 8 slots: each live row's state stepped in
    its slot as one token of the recurrence, the other slots untouched,
    two rows parked on the null slot (``beta = g = 0``) leave it as it
    was."""
    B = 5
    q, k, v, beta, g, pool = _operands(2, B, H, dk, dv, slots=8)
    slots = jnp.asarray([3, 1, 0, 7, 0], jnp.int32)
    live = (slots != 0)[:, None]
    beta, g = jnp.where(live, beta, 0.0), jnp.where(live, g, 0.0)
    o, out = K.gdn_step(q, k, v, beta, g, pool, slots, impl=impl,
                        interpret=impl == "pallas")
    out = np.asarray(out)
    for b, s in enumerate(np.asarray(slots)):
        want_o, want_s = _recurrence(q[b:b + 1], k[b:b + 1], v[b:b + 1],
                                     beta[b:b + 1], g[b:b + 1], pool[s])
        if s:
            assert np.abs(np.asarray(o[b]) - want_o[0]).max() < 1e-5
            assert np.abs(out[s] - want_s).max() < 1e-5
    for s in (0, 2, 4, 5, 6):           # the null slot and the idle ones
        assert np.array_equal(out[s], np.asarray(pool[s])), s


def test_kernel_gaps_name_what_cannot_be_tiled():
    assert K.gdn_chunk_gap(512, 96, 192) is None
    assert "T%64" in K.gdn_chunk_gap(500, 96, 192)
    assert K.gdn_step_gap(30, 96, 192) is None
    assert K._step_heads(30, 96, 192) == 10          # 737,280 B a block
    assert K._lane_group(192) == 2 and K._lane_group(128) == 1
    assert "dk%8" in K.gdn_step_gap(30, 90, 192)
    with pytest.raises(ValueError, match="gdn_chunk"):
        K.gdn_chunk(*_operands(0, 50, 2, 32, 64), impl="pallas",
                    interpret=True)


# ---------------------------------------------------------------------------
# The parameter count, the published keys, the refusals
# ---------------------------------------------------------------------------


def _published():
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "benchmarks/configs/olmo-hybrid-7b-l8.json")) as f:
        config = json.load(f)
    return {k: v for k, v in config.items() if k in G.HF_KEYS}


def test_parameter_count_and_state_at_the_published_widths():
    cfg = G.GdnHybridConfig.from_hf(_published(), max_seq=5120)
    assert cfg.layer_types == ("linear",) * 3 + ("full",) + (
        "linear",) * 3 + ("full",)
    assert cfg.mixer_params("linear") == 88_750_332
    assert cfg.mixer_params("full") == 4 * 3840 ** 2 + 2 * 3840
    assert cfg.n_params() == (
        6 * 215_570_172 + 2 * 185_809_920 + 770_703_360 + 3840
    ) == 2_435_748_072
    assert [sh for sh, _ in cfg.state_planes] == [(270, 128), (96, 5760)]
    assert 5760 % 128 == 0                  # 45 lane tiles, none padded
    assert cfg.state_bytes_per_layer == 2_211_840 + 69_120 == 2_280_960
    assert cfg.state_bytes_per_request == 13_685_760
    # the whole model is the published 32 layers
    whole = G.GdnHybridConfig.from_hf(
        {**_published(), "num_hidden_layers": 32}, max_seq=5120)
    assert whole.layer_types.count("linear") == 24
    assert whole.n_params() == 8 * 832_520_436 + 770_703_360 + 3840
    # the tiny model's count is the sum of its leaves
    tiny = G.GdnHybridConfig.tiny()
    shapes = jax.eval_shape(lambda k: G.init_params(tiny, k),
                            jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == tiny.n_params()


FROM_HF_REFUSALS = [
    ({"model_type": "qwen3_next"}, "olmo_hybrid"),
    ({"linear_use_gate": True}, "linear_use_gate"),
    ({"sliding_window": 4096}, "sliding_window"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"attention_bias": True}, "attention_bias"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"rope_parameters": {"rope_theta": 500000.0}}, "rope_theta"),
    ({"linear_num_key_heads": 15}, "linear_num_key_heads"),
    ({"layer_types": ["linear_attention", "sliding_attention"] * 4},
     "sliding_attention"),
    ({"num_hidden_layers": 40}, "layer_types"),
]


@pytest.mark.parametrize("over,why", FROM_HF_REFUSALS,
                         ids=[w for _, w in FROM_HF_REFUSALS])
def test_from_hf_refuses_by_name(over, why):
    """A key ``olmo_hybrid`` does not have here, and a value that is not
    served, is refused with its name — never dropped."""
    with pytest.raises(ValueError, match=why):
        G.GdnHybridConfig.from_hf({**_published(), **over}, max_seq=5120)


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
def test_unsupported_beside_a_matrix_state_is_refused_by_name(tiny, tmp_path,
                                                              what, run):
    cfg, params = tiny
    with pytest.raises(StateCacheUnsupported) as e:
        run(cfg, params, tmp_path)
    assert what in str(e.value) and "state" in str(e.value)
    assert isinstance(e.value, KvGroupsUnsupported)


# ---------------------------------------------------------------------------
# Counters, summary()["gdn"], and what the engine says of its kernels
# ---------------------------------------------------------------------------


def test_counters_gauges_and_the_gdn_summary(tiny):
    cfg, params = tiny
    prompt, = _prompts(cfg, [40], seed=7)
    eng = _engine(_gen(cfg), params, horizon=4, trace_level=1)
    _serve(eng, [prompt], 10)
    m = eng.metrics
    s = m.summary()
    # 3 carried rows of 512 channels + a [32, 256] state, float32 here
    assert cfg.state_bytes_per_layer == 3 * 512 * 4 + 32 * 256 * 4
    assert s["gdn"] == {
        "state_bytes_per_request": 3 * cfg.state_bytes_per_layer,
        "state_slots_in_use": 0, "state_slots_peak": 1,
        "chunk_calls": 3 * m.prefill_dispatches,
        "step_calls": 3 * m.decode_steps, "rule_tokens": 40,
        "state_resets": 1, "state_recomputed_tokens": 0}
    assert m.prefill_dispatches == 3 and m.decode_steps >= 9
    # 9 decode queries at contexts 41 .. 49 on the one full layer
    assert m.swa_full_tokens == sum(range(41, 50))
    assert m.swa_window_tokens == m.yoco_shared_tokens == 0
    assert s["kv"]["groups"]["state"]["state"] is True
    assert "decode.plan.state" in s["phases"]
    text = m.to_prometheus()
    for name in ("serve_state_slots_in_use", "serve_state_slots_peak",
                 "serve_state_resets_total",
                 "serve_state_recomputed_tokens_total"):
        assert f"\n{name} " in text, name
    # another family's engine reports no such group
    from triton_dist_tpu.serve.metrics import ServeMetrics
    assert ServeMetrics().summary()["gdn"] == {}


def test_kernel_reach_at_the_cells_geometry():
    """At the cell's geometry (page 128, a 512-row chunk, 30 KV heads of
    128, 30 linear heads of 96 x 192) every attention path and both calls
    of the delta rule reach their Mosaic kernels, and the paged call on a
    full layer is named by kind."""
    from triton_dist_tpu.kernels import flash_decode as fd
    from triton_dist_tpu.serve.engine import build_bucket_ladder

    cfg = G.GdnHybridConfig.from_hf(
        {**_published(), "vocab_size": 512, "intermediate_size": 256,
         "num_hidden_layers": 4}, max_seq=5120)
    gen = G.GdnHybridGenerator(cfg, interpret=True)
    assert gen.kv_planes == [(30, 128)] * 2
    assert gen.kernel_gaps(
        page_size=128, prefill_chunk=512,
        ladder=build_bucket_ladder(512, 5120, 128)) == {}
    assert set(G.GdnHybridGenerator(cfg).kernel_gaps(
        page_size=128, prefill_chunk=512, ladder=[512])) == {
            "paged_decode", "prefill_chunk", "gdn_chunk", "gdn_step"}
    # all 30 KV heads of a page ride one step of the paged call
    assert fd.paged_heads_per_step(30, 128, 128, 2) in (15, 30)
    assert [k.call_name for k in cfg.kinds] == [
        "gqa_paged_linear"] * 3 + ["gqa_paged_full"]
    assert [k.state for k in cfg.kinds] == [True] * 3 + [False]


def test_other_families_import_none_of_this_one():
    """Importing the serving package and building another family's engine
    imports neither this family's model nor its kernel."""
    import subprocess
    import sys

    code = (
        "import sys, jax, numpy as np\n"
        "from jax.sharding import Mesh\n"
        "import triton_dist_tpu.serve as serve\n"
        "from triton_dist_tpu.models import llama\n"
        "from triton_dist_tpu.models.generate import Generator\n"
        "cfg = llama.LlamaConfig(vocab=64, dim=16, n_layers=1, n_heads=2,"
        " n_kv_heads=1, ffn_dim=32, max_seq=64)\n"
        "gen = Generator(cfg, Mesh(np.array(jax.devices()[:1]), ('sp',)),"
        " axis='sp', max_seq=64)\n"
        "serve.ServeEngine(gen, llama.init_params(cfg, jax.random.key(0)),"
        " num_blocks=8, page_size=16, max_batch=2)\n"
        "bad = [m for m in sys.modules if m.endswith(('gdn_hybrid',"
        " 'gated_delta'))]\n"
        "assert not bad, bad\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=300)
