"""A state beside pages (models/ssm_yoco.py, kernels/ssm_scan.py,
serve/block_manager.py ``StateSlots``) on the CPU: tiny sizes (8 layers:
ssm 0 2 4, window 1 3, full 5, gmu 6, cross 7; 4 heads of 64 on one pair
of KV heads, window 16, page 8, 512 state-space channels x 16, float32),
seeded weights.

The yardstick is ``benchmarks/reference/ssm_yoco.py`` — the plain float32
reference of the same equations (no cache, no state pool, the scan a
``lax.scan`` over tokens, its own weights from the seed), which imports
nothing of the program.
"""

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from chunk_rows import filed_chunk_call, keep_no_row

from triton_dist_tpu.kernels import flash_decode as fd
from triton_dist_tpu.kernels import ssm_scan as K
from triton_dist_tpu.models import ssm_yoco as Y
from triton_dist_tpu.serve import Request, SamplingParams, ServeEngine
from triton_dist_tpu.serve.block_manager import (
    KvGroups,
    KvGroupsUnsupported,
    StateCacheUnsupported,
    StateSlots,
)

ref = importlib.import_module("benchmarks.reference.ssm_yoco")

SEED = 2 ** 31 + 7          # past 32 signed bits, like the driver's seeds
WINDOW, PAGE = 16, 8


def hf_config(cfg: Y.SsmYocoConfig, **over) -> dict:
    """The configuration-file keys of ``cfg`` (what the reference and
    ``from_hf`` read)."""
    c = {
        "model_type": "phi4flash", "vocab_size": cfg.vocab,
        "hidden_size": cfg.dim, "num_hidden_layers": cfg.n_layers,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "intermediate_size": cfg.ffn_dim,
        "sliding_window": cfg.sliding_window, "mb_per_layer": 2,
        "layer_norm_eps": cfg.norm_eps, "hidden_act": "silu",
        "mlp_bias": False, "lm_head_bias": False,
        "tie_word_embeddings": True,
        "assumed": {"d_state": cfg.d_state, "d_conv": cfg.d_conv,
                    "expand": cfg.expand, "dt_rank": cfg.dt_rank},
    }
    c.update(over)
    return c


@pytest.fixture(scope="module")
def tiny():
    ref.Q_BLOCK = ref.T_BLOCK = 32
    cfg = Y.SsmYocoConfig.tiny()
    params = Y.init_params(cfg, ref.weight_key(SEED))
    return cfg, params


def _gen(cfg, interpret=False, **kw):
    return Y.SsmYocoGenerator(cfg, max_seq=256, interpret=interpret, **kw)


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
# The engine against the reference: logits through all three groups
# ---------------------------------------------------------------------------

# float32 program against the float32 reference: the two differ by the
# order of float32 sums (blocked softmax against one row's; a chunk's scan
# from a carried state against one scan; a 128-wide score of a zero-padded
# query against a 64-wide one is the same sum) — observed ~2e-5 on logits
# of magnitude ~4 through eight layers.  The same engine in bfloat16 reads
# ~5e-2: a precision below the one the configuration states fails.
LOGIT_TOL = 3e-4


def _served_logits(gen, params, prompt, n_new, **kw):
    """One request through chunked prefill and paged decode over the three
    groups, with every program's logits kept: -> (tokens, logits [S0 +
    n_new - 1, V] — row j is the model's output at position j, the
    engine)."""
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


def test_engine_logits_match_reference_and_bf16_does_not(tiny):
    """Chunked prefill (five chunks, the last padded: the state goes from
    chunk to chunk in the request's scratch), then single-step paged
    decode through the full, the window AND the state group — the context
    crosses the 16-token window several times — against the reference's
    ONE full forward pass over prompt + served tokens: logits, not
    tokens."""
    cfg, params = tiny
    assert cfg.layer_types == ("ssm", "window", "ssm", "window", "ssm",
                               "full", "gmu", "cross")
    prompt, = _prompts(cfg, [70])
    toks, rows, eng = _served_logits(_gen(cfg, interpret=True), params,
                                     prompt, 30)
    assert isinstance(eng.bm, KvGroups)
    assert isinstance(eng.bm.groups["state"], StateSlots)
    assert eng.metrics.kv_window_released > 0
    got = np.stack([rows[j] for j in range(len(prompt) + 30 - 1)])
    seq = np.concatenate([prompt, np.asarray(toks, np.int32)])
    want = ref.forward_logits(hf_config(cfg), SEED, [seq], [1],
                              dtype=jnp.float32)[0]
    assert got.shape == want.shape
    assert np.abs(got - want).max() < LOGIT_TOL
    # the tolerance is tight enough to fail a lower precision
    low = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    p16 = Y.init_params(low, ref.weight_key(SEED))
    _, rows16, _ = _served_logits(_gen(low), p16, prompt, 2)
    n = len(prompt)
    got16 = np.stack([rows16[j] for j in range(n)])
    assert np.abs(got16 - want[:n]).max() > 10 * LOGIT_TOL


def test_horizon_decode_logits_match_reference(tiny):
    """The fused horizon (H = 8, two links a chain: the state read and
    written at its slot inside the scan) serves tokens whose reference
    logits are the reference's best, and the tokens of the single-step
    engine that the test above held logit for logit."""
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


def test_whole_prompt_forward_matches_reference(tiny):
    cfg, params = tiny
    prompt, = _prompts(cfg, [64], seed=3)
    got = np.asarray(_gen(cfg).forward_logits(params, prompt[None])[0])
    want = ref.forward_logits(
        hf_config(cfg), SEED, [np.concatenate([prompt, prompt[:1]])], [1],
        dtype=jnp.float32)[0]
    assert np.abs(got - want).max() < LOGIT_TOL


# ---------------------------------------------------------------------------
# The state: N chunks = one scan, a reused slot starts from zero, preemption
# ---------------------------------------------------------------------------


def test_n_chunks_leave_the_state_of_one_scan(tiny):
    """Five prefill chunks (the last padded to the chunk's rows) leave in
    the request's slot, layer for layer, the state ONE scan over the
    prompt leaves, to the order of float32 sums (a chunk's products
    against the whole prompt's)."""
    cfg, params = tiny
    prompt, = _prompts(cfg, [70], seed=4)
    gen = _gen(cfg)
    eng = _engine(gen, params)
    ssm_layers = [li for li, t in enumerate(cfg.layer_types) if t == "ssm"]
    filled = {}
    seam = eng._device_call

    def tapped(op, rids, fn, *a, **kw):
        out = seam(op, rids, fn, *a, **kw)
        if op == "fill_pages":      # the pools as the prompt's last chunk
            slot = eng.bm.groups["state"].table(rids[0])[0]   # left them
            filled.update({li: [np.asarray(p[slot]) for p in out[li]]
                           for li in ssm_layers}, slot=slot)
        return out

    eng._device_call = tapped
    _serve(eng, [prompt], 4)
    assert filled["slot"] >= 1
    assert eng.metrics.prefill_dispatches == 5
    want = gen.forward_states(params, prompt[None])
    assert len(want) == len(ssm_layers) == 3
    for li, (conv, state) in zip(ssm_layers, want):
        got_conv, got_state = filled[li]
        assert np.abs(got_conv.reshape(conv.shape[1:])
                      - np.asarray(conv[0])).max() < 1e-4
        assert np.abs(got_state - np.asarray(state[0])).max() < 1e-4


def _lowered_chunk(cfg, rows, extent, monkeypatch=None):
    """The family's ``prefill_chunk`` lowered on abstract arguments, as the
    engine calls it (``n_valid`` given) — with ``monkeypatch``, over a
    layer loop told of no row to keep: the all-rows program it was."""
    if monkeypatch is not None:
        keep_no_row(monkeypatch)
    s = jax.ShapeDtypeStruct
    scratch = []
    for kind in cfg.kinds:
        if kind.attn in ("full", "window"):
            scratch.append((s((1, cfg.kv_plane[0], extent, cfg.kv_plane[1]),
                              cfg.dtype),) * 2)
        else:
            scratch.append(tuple(s((1, *sh), dt)
                                 for sh, dt in cfg.state_planes)
                           if kind.state else ())
    params = jax.eval_shape(lambda: Y.init_params(cfg, jax.random.key(0)))
    return _gen(cfg)._chunk_jit.lower(
        params, s((1, rows), jnp.int32), scratch, s((), jnp.int32),
        quantized=False, extent=extent, n_valid=s((), jnp.int32))


def test_a_prefill_chunk_stops_its_rows_at_the_shared_cache(monkeypatch):
    """What the architecture was published for (ISSUE 42): past layer L/2
    + 1, the last that leaves anything a later token reads, a prefill
    chunk carries ONE row — through the ``gmu`` and ``cross`` layers and
    the head.  The lowered program holds no [rows, V] float32 array, and
    the compiler counts under 60% of the all-rows program's operations at
    the published proportions (16 layers: 6 of them and the head after the
    full layer; a head worth ~4 layers: V = 8,192 at the toy widths).  The
    toy config itself (8 layers, two of them after the full layer, V =
    256) reads 78%: its state-space layers' scans, which XLA counts at 6 T
    E N a layer, weigh more beside its two small layers and head."""
    rows, extent = 64, 128
    for over, most in ((dict(n_layers=16, vocab=8192), 0.60), ({}, 0.80)):
        cfg = Y.SsmYocoConfig.tiny(**over)
        kept = _lowered_chunk(cfg, rows, extent)
        with monkeypatch.context() as mp:
            every = _lowered_chunk(cfg, rows, extent, mp)
        if over:
            logits = f"{rows}x{cfg.vocab}xf32"
            assert logits in every.as_text()
            assert logits not in kept.as_text()
            assert f"1x1x{cfg.vocab}xf32" in kept.as_text()
        flops = [lo.compile().cost_analysis()["flops"]
                 for lo in (kept, every)]
        assert flops[0] < most * flops[1], (over, flops)


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
    assert eng.metrics.summary()["ssm"]["state_slots_peak"] == 1


def test_preemption_and_recompute_equal_an_undisturbed_run(tiny):
    cfg, params = tiny
    prompts = _prompts(cfg, [40, 44], seed=6)
    calm = _serve(_engine(_gen(cfg), params, horizon=4), prompts, 60)
    eng = _engine(_gen(cfg), params, horizon=4, num_blocks=20)
    assert _serve(eng, prompts, 60) == calm
    assert eng.metrics.preemptions > 0
    assert eng.metrics.state_recomputed_tokens > 0
    assert eng.bm.num_free == eng.bm.num_allocatable


def test_state_group_allocates_for_all_groups_or_none():
    """Admission asks all three groups or none; the state group never
    grows, is left out of ``utilization`` and reported in
    ``group_stats``."""
    from triton_dist_tpu.serve.block_manager import BlockManager

    bm = KvGroups({"full": BlockManager(9, PAGE),
                   "window": BlockManager(9, PAGE, window=WINDOW),
                   "state": StateSlots(1, PAGE)})
    assert bm.can_allocate(20)
    bm.allocate("a", 20)
    assert not bm.can_allocate(8)           # no slot: nobody allocates
    free = bm.groups["full"].num_free
    with pytest.raises(Exception):
        bm.allocate("b", 8)
    assert bm.groups["full"].num_free == free
    bm.ensure("a", 40)                      # pages grow, the slot does not
    assert bm.groups["state"].table("a") == [1]
    assert np.asarray(bm.padded_table("a", 6))[2].tolist() == [1, 0, 0, 0, 0, 0]
    assert bm.page_ids("a", 0, 3, 4)[2].tolist() == [1, 0, 0, 0]
    assert bm.utilization == bm.groups["full"].utilization
    assert bm.group_stats()["state"] == {
        "blocks": 1, "in_use": 1, "peak": 1, "window": 0, "released": 0,
        "state": True}
    bm.free("a")
    assert bm.num_free == bm.num_allocatable


# ---------------------------------------------------------------------------
# Attention at head width 64, the scan kernel, the parameter count
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("ctx", [511, 512, 513])
def test_paired_heads_against_plain_softmax_at_the_window_edge(ctx):
    """Heads 64 wide, stored in pairs as 128-lane rows and attended
    through the paged call at width 128 with zero-padded queries, against
    plain softmax attention of each 64-wide head over the last 512
    positions: a context of 511 / 512 / 513 sees 511 / 512 / 512 keys."""
    rng = np.random.default_rng(ctx)
    Hq, Hkv, hd, page, window = 8, 4, 64, 128, 512
    n_pages = -(-ctx // page)
    q = rng.standard_normal((1, Hq, hd)).astype(np.float32)
    k = rng.standard_normal((ctx, Hkv, hd)).astype(np.float32)
    v = rng.standard_normal((ctx, Hkv, hd)).astype(np.float32)

    def pool(x):
        rows = np.zeros((n_pages * page, Hkv // 2, 128), np.float32)
        rows[:ctx] = np.asarray(fd.pack_kv_pairs(jnp.asarray(x)))
        # block 0 is the null block; the request's pages follow
        pages = rows.reshape(n_pages, page, Hkv // 2, 128).transpose(
            0, 2, 1, 3)
        return jnp.asarray(np.concatenate([np.zeros_like(pages[:1]), pages]))

    table = jnp.arange(1, n_pages + 1, dtype=jnp.int32)[None]
    o, _ = fd.gqa_decode_paged_shard(
        fd.pack_q_pairs(jnp.asarray(q), Hkv), pool(k), pool(v), table,
        jnp.asarray([ctx], jnp.int32), window=window, scale=1 / 8,
        interpret=True)
    got = np.asarray(fd.unpack_out_pairs(o, Hkv))[0]
    lo = max(0, ctx - window)
    for h in range(Hq):
        kh, vh = k[lo:, h // 2], v[lo:, h // 2]
        p = jax.nn.softmax(jnp.asarray(kh @ q[0, h]) / 8.0)
        assert np.abs(got[h] - np.asarray(p) @ vh).max() < 2e-5, h


def test_scan_kernel_against_the_token_loop_and_masked_rows():
    """The Mosaic ``ssm_scan`` (interpreter) against the ``lax.scan`` over
    tokens, from a non-zero state; rows with ``dt = 0`` leave the state as
    it was, and a decode step is one row of the same scan."""
    T, E, N = 32, 512, 16
    ks = jax.random.split(jax.random.key(0), 6)
    x = jax.random.normal(ks[0], (T, E))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (T, E)) - 2)
    B, C = (jax.random.normal(k, (T, N)) for k in ks[2:4])
    A = -jnp.exp(jax.random.normal(ks[4], (N, E)) * 0.3)
    D, s0 = jnp.ones((E,)), jax.random.normal(ks[5], (N, E))
    y0, s1 = K._scan_xla(x, dt, B, C, A, D, s0)
    y, s = K.ssm_scan(x, dt, B, C, A, D, s0, impl="pallas", interpret=True)
    assert float(jnp.abs(y - y0).max()) < 1e-5
    assert float(jnp.abs(s - s1).max()) < 1e-5
    # the last 12 rows masked: the state after row 19
    keep = (jnp.arange(T) < 20)[:, None]
    _, s_mask = K.ssm_scan(x, jnp.where(keep, dt, 0.0), B, C, A, D, s0,
                           impl="pallas", interpret=True)
    _, s_20 = K._scan_xla(x[:20], dt[:20], B[:20], C[:20], A, D, s0)
    assert float(jnp.abs(s_mask - s_20).max()) < 1e-5
    y_step, s_step = K.ssm_step(x[:1], dt[:1], B[:1], C[:1], A, D, s0[None])
    y_one, s_one = K._scan_xla(x[:1], dt[:1], B[:1], C[:1], A, D, s0)
    assert float(jnp.abs(y_step - y_one).max()) < 1e-5
    assert float(jnp.abs(s_step[0] - s_one).max()) < 1e-5
    assert K.ssm_scan_gap(512, 5120, 16) is None
    assert "T%8" in K.ssm_scan_gap(30, 5120, 16)


def test_causal_conv_carries_the_last_valid_rows():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((1, 24, 128)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((4, 128)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((128,)), jnp.float32)
    zero = jnp.zeros((1, 3, 128))
    whole, carry = K.causal_conv(x, zero, w, b)
    a, c1 = K.causal_conv(x[:, :16], zero, w, b)
    # the second chunk padded to 16 rows, 8 of them valid
    pad = jnp.concatenate([x[:, 16:], jnp.zeros((1, 8, 128))], axis=1)
    b2, c2 = K.causal_conv(pad, c1, w, b, n_valid=8)
    got = jnp.concatenate([a, b2[:, :8]], axis=1)
    assert float(jnp.abs(got - whole).max()) < 1e-6
    assert np.array_equal(np.asarray(c2), np.asarray(carry))
    assert np.array_equal(np.asarray(carry), np.asarray(x[:, -3:]))


def test_parameter_count_at_the_published_widths():
    cfg = Y.SsmYocoConfig(
        vocab=200064, dim=2560, n_layers=32, n_heads=40, n_kv_heads=20,
        ffn_dim=10240, sliding_window=512, dtype=jnp.bfloat16)
    kinds = cfg.layer_types
    assert [kinds.count(k) for k in Y.LAYER_KINDS] == [9, 8, 1, 7, 7]
    assert kinds[16] == "ssm" and kinds[17] == "full" and cfg.dt_rank == 160
    assert cfg.n_params() == (
        9 * 19_660_800 + 7 * 13_107_200 + 9 * 41_241_600 + 7 * 26_214_400
        + 32 * (78_643_200 + 4 * 2560) + 512_163_840 + 2 * 2560
    ) == 3_852_451_840
    assert cfg.kv_plane == (10, 128)            # 5,120 B a token a layer
    assert 2 * 10 * 128 * 2 == 5120
    assert cfg.state_bytes_per_request == 9 * 358_400 == 3_225_600
    # the tiny model's count is the sum of its leaves
    tiny = Y.SsmYocoConfig.tiny()
    shapes = jax.eval_shape(lambda k: Y.init_params(tiny, k),
                            jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == tiny.n_params()


def test_from_hf_reads_the_published_keys_and_refuses_by_name():
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "benchmarks/configs/phi-4-mini-flash-reasoning.json")) as f:
        config = json.load(f)
    cfg = Y.SsmYocoConfig.from_hf(config, max_seq=5120)
    assert (cfg.dim, cfg.n_layers, cfg.vocab, cfg.head_dim, cfg.d_inner,
            cfg.sliding_window) == (2560, 32, 200064, 64, 5120, 512)
    assert cfg.n_params() == 3_852_451_840
    for over, why in (({"model_type": "mamba"}, "phi4flash"),
                      ({"tie_word_embeddings": False}, "tie_word_embeddings"),
                      ({"mlp_bias": True}, "mlp_bias"),
                      ({"num_hidden_layers": 30}, "divides by 4")):
        with pytest.raises(ValueError, match=why):
            Y.SsmYocoConfig.from_hf({**config, **over}, max_seq=5120)


# ---------------------------------------------------------------------------
# What the state group does not carry is refused by name
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
def test_unsupported_beside_a_state_group_is_refused_by_name(tiny, tmp_path,
                                                             what, run):
    cfg, params = tiny
    with pytest.raises(StateCacheUnsupported) as e:
        run(cfg, params, tmp_path)
    assert what in str(e.value) and "state" in str(e.value)
    assert isinstance(e.value, KvGroupsUnsupported)


# ---------------------------------------------------------------------------
# Counters, the span, and what the engine says of its kernels
# ---------------------------------------------------------------------------


def test_counters_gauges_and_the_plan_span(tiny):
    cfg, params = tiny
    prompt, = _prompts(cfg, [40], seed=7)
    eng = _engine(_gen(cfg), params, horizon=4, trace_level=1)
    _serve(eng, [prompt], 10)
    m = eng.metrics
    s = m.summary()
    assert s["ssm"] == {"state_slots_in_use": 0, "state_slots_peak": 1,
                        "state_resets": 1, "state_recomputed_tokens": 0,
                        "scan_tokens": 40}
    # 9 decode queries at contexts 41 .. 49: 2 readers of the one cache
    # (the full layer and the cross layer), 2 window layers at 16
    assert m.yoco_shared_tokens == 2 * sum(range(41, 50))
    assert m.yoco_window_tokens == 2 * 9 * WINDOW
    assert m.swa_full_tokens == m.swa_window_tokens == 0
    assert s["kv"]["groups"]["state"]["state"] is True
    assert "decode.plan.state" in s["phases"]
    text = m.to_prometheus()
    for name in ("serve_state_slots_in_use", "serve_state_slots_peak",
                 "serve_state_resets_total",
                 "serve_state_recomputed_tokens_total",
                 "serve_ssm_scan_tokens_total",
                 "serve_yoco_shared_tokens_total",
                 "serve_yoco_window_tokens_total"):
        assert f"\n{name} " in text, name


def test_kernel_reach_at_the_cells_geometry():
    """At the cell's geometry (page 128, a 512-row chunk, heads in pairs)
    every attention path and the scan reach their Mosaic calls; a 64-wide
    head on its own does not, and the message says what does."""
    from triton_dist_tpu.serve.engine import build_bucket_ladder

    cfg = Y.SsmYocoConfig(
        vocab=512, dim=2560, n_layers=8, n_heads=40, n_kv_heads=20,
        ffn_dim=256, sliding_window=512, max_seq=5120, dtype=jnp.bfloat16)
    gen = Y.SsmYocoGenerator(cfg, interpret=True)
    assert gen.kv_planes == [(10, 128)] * 2
    assert gen.kernel_gaps(
        page_size=128, prefill_chunk=512,
        ladder=build_bucket_ladder(512, 5120, 128)) == {}
    assert "pairs" in fd.paged_kernel_gap(128, 64, 2).lower()
    assert fd.paged_kernel_gap(128, 128, 2) is None
    assert [cfg.kinds[li].call_name for li in (1, 5, 7)] == [
        "gqa_paged_window", "gqa_paged_full", "gqa_paged_cross"]


def test_other_families_import_none_of_this_one():
    """Importing the serving package and building another family's engine
    imports neither this family's model nor its kernel (a cell of another
    family pays nothing for them at start-up)."""
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
        "bad = [m for m in sys.modules if m.endswith(('ssm_yoco',"
        " 'ssm_scan'))]\n"
        "assert not bad, bad\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=300)
