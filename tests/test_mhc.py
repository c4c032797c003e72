"""A residual path of several streams (``kernels/hyper_conn.py``; the
``xing4_0`` block of ``models/mla_moe.py``) on the CPU: small widths, the
Pallas interpreter, seeded weights.

The yardstick is ``benchmarks/reference/mla_mhc_moe_share.py`` — the plain
float32 reference of the same equations (the Sinkhorn normalisation as a
plain loop, its own weights from the seed), which imports nothing of the
program.
"""

import dataclasses
import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from chunk_rows import filed_chunk_call
from test_mla_moe import LOGIT_TOL, _engine, _prompts, _serve, hf_config

from triton_dist_tpu.kernels import hyper_conn as hc
from triton_dist_tpu.kernels.gemm import PallasShapeError
from triton_dist_tpu.models import mla_moe as M
from triton_dist_tpu.serve import Request, SamplingParams

ref = importlib.import_module("benchmarks.reference.mla_mhc_moe_share")
base = importlib.import_module("benchmarks.reference.mla_moe_share")

SEED = 2 ** 31 + 7          # past 32 signed bits, like the driver's seeds
KW = dict(iters=20, eps=1e-6, clamp=(-30.0, 30.0), norm_eps=1e-6)
# rows and columns of a 20-times normalised map sum to 1 within this: the
# rows exactly (they are normalised last, float32), the columns as far as
# twenty iterations bring a map whose logits are ~N(0, 2)
ROW_TOL, COL_TOL = 1e-5, 5e-3


def _maps(key, n, D):
    """One sub-layer's maps as ``mla_moe._stream_maps`` draws them."""
    cfg = M.MlaMoeConfig.tiny(dim=D, hc_mult=n)
    return M._stream_maps(cfg, key)


def _as_reference(p, n):
    """The program's stored maps in the reference's (published) shapes."""
    return {"phi": p["phi_t"].T, "alpha": p["alpha"], "bias": p["bias"][:, 0],
            "gain": p["gain"][0]}


def _sizes(n):
    return dict(n=n, iters=KW["iters"], hc_eps=KW["eps"], lo=KW["clamp"][0],
                hi=KW["clamp"][1], eps=KW["norm_eps"])


# ---------------------------------------------------------------------------
# The two calls: interpreter against the XLA twins and the reference's loop
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [2, 4])
@pytest.mark.parametrize("rows", [1, 8, 96, 256])
def test_the_two_calls_against_their_twins_and_the_plain_loop(rows, n):
    """``hc_pre`` / ``hc_post`` in the Pallas interpreter, their XLA twins
    and the reference's equations as they stand give one answer — at one
    row (a verify row), a sublane tile, a decode step's rows and a chunk's
    (two grid steps of the pre-mix, eight of the post-mix)."""
    D = 128
    ks = jax.random.split(jax.random.key(rows * 10 + n), 3)
    x = jax.random.normal(ks[0], (rows, n * D), jnp.float32)
    y = jax.random.normal(ks[1], (rows, D), jnp.float32)
    p = _maps(ks[2], n, D)
    h0, m0 = hc.hc_pre_xla(x, p, n=n, **KW)
    h1, m1 = hc.hc_pre(x, p, n=n, interpret=True, **KW)
    o0 = hc.hc_post_xla(x, y, m0, n=n)
    o1 = hc.hc_post(x, y, m0, n=n, interpret=True)
    for a, b in ((h0, h1), (m0, m1), (o0, o1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                                   atol=2e-5)
    assert not np.asarray(m1[:, hc.n_maps(n):]).any()       # the tile's rest
    # the reference's plain loop, from the published shapes
    X = x.reshape(rows, n, D)
    with jax.default_matmul_precision("highest"):
        pre, post, res = ref.stream_maps(X, _as_reference(p, n), _sizes(n))
        want = ref.sublayer(X, _as_reference(p, n), _sizes(n),
                            lambda h: h * 0 + y)
    got = hc.unpack_maps(m1, n)
    for a, b in zip(got, (pre, post, res)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-5,
                                   atol=5e-5)
    np.testing.assert_allclose(
        np.asarray(h1), np.einsum("tj,tjd->td", pre, X), rtol=5e-5, atol=5e-5)
    np.testing.assert_allclose(np.asarray(o1).reshape(rows, n, D),
                               np.asarray(want), rtol=1e-4, atol=1e-4)
    res = np.asarray(got[2])
    assert np.abs(res.sum(-1) - 1).max() < ROW_TOL
    assert np.abs(res.sum(-2) - 1).max() < COL_TOL
    assert (np.asarray(got[0]) > 0).all() and (np.asarray(got[0]) < 1).all()
    assert (np.asarray(got[1]) > 0).all() and (np.asarray(got[1]) < 2).all()


def test_streams_in_bfloat16_stay_bfloat16_and_the_maps_float32():
    n, D, rows = 4, 128, 32
    ks = jax.random.split(jax.random.key(5), 3)
    x = jax.random.normal(ks[0], (rows, n * D)).astype(jnp.bfloat16)
    y = jax.random.normal(ks[1], (rows, D)).astype(jnp.bfloat16)
    p = _maps(ks[2], n, D)
    h, m = hc.hc_pre(x, p, n=n, interpret=True, **KW)
    out = hc.hc_post(x, y, m, n=n, interpret=True)
    assert (h.dtype, m.dtype, out.dtype) == (jnp.bfloat16, jnp.float32,
                                             jnp.bfloat16)
    h0, m0 = hc.hc_pre_xla(x, p, n=n, **KW)
    np.testing.assert_allclose(np.asarray(m), np.asarray(m0), atol=2e-5)
    # one rounding to bfloat16 each: a float32 sum in another order may
    # fall on the other side of a tie, one ulp (2^-8 of the value)
    np.testing.assert_allclose(np.asarray(h, np.float32),
                               np.asarray(h0, np.float32), rtol=2 ** -7)
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(hc.hc_post_xla(x, y, m, n=n), np.float32), rtol=2 ** -7)


def test_sinkhorn_hand_worked():
    """One iteration of [[1, 2], [3, 4]] by hand: column sums 4 and 6 give
    [[1/4, 1/3], [3/4, 2/3]]; its row sums 7/12 and 17/12 give [[3/7, 4/7],
    [9/17, 8/17]] — in the program's twin and in the reference's loop."""
    m = jnp.asarray([[1.0, 2.0], [3.0, 4.0]])
    want = np.asarray([[3 / 7, 4 / 7], [9 / 17, 8 / 17]])
    for fn in (hc.sinkhorn, ref.sinkhorn):
        np.testing.assert_allclose(np.asarray(fn(m, 1, 0.0)), want, rtol=1e-6)
    # and the kernel's own loop, through a map whose logits are log(m):
    # phi = 0 leaves the bias alone, and exp(log m) is m
    n, D = 2, 128
    p = {"phi_t": jnp.zeros((hc.n_maps(n), n * D)), "alpha": jnp.ones((3,)),
         "bias": jnp.concatenate([jnp.zeros((2 * n,)),
                                  jnp.log(m).reshape(-1)])[:, None],
         "gain": jnp.ones((1, n * D))}
    x = jax.random.normal(jax.random.key(0), (8, n * D), jnp.float32)
    _, maps = hc.hc_pre(x, p, n=n, interpret=True, iters=1, eps=0.0,
                        clamp=(-30.0, 30.0), norm_eps=1e-6)
    pre, post, res = hc.unpack_maps(maps, n)
    np.testing.assert_allclose(np.asarray(res),
                               np.broadcast_to(want, (8, 2, 2)), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(pre), 0.5, rtol=1e-6)   # sigmoid(0)
    np.testing.assert_allclose(np.asarray(post), 1.0, rtol=1e-6)


@pytest.mark.parametrize("interpret", [True, False])
def test_the_clamp_holds_at_its_bounds(interpret):
    """A logit far past the clamp gives the map its bound gives — nothing
    overflows, nothing is NaN — in the kernel and in its twin."""
    n, D = 4, 128
    k = hc.n_maps(n)
    x = jax.random.normal(jax.random.key(1), (8, n * D), jnp.float32)
    signs = np.where(np.arange(n * n) % 3 == 0, 1.0, -1.0)

    def maps(mag):
        p = {"phi_t": jnp.zeros((k, n * D)), "alpha": jnp.ones((3,)),
             "bias": jnp.concatenate([jnp.zeros((2 * n,)),
                                      jnp.asarray(mag * signs)])[:, None]
             .astype(jnp.float32), "gain": jnp.ones((1, n * D))}
        return np.asarray(hc.hc_pre(x, p, n=n, interpret=interpret,
                                    **KW)[1])

    far, at = maps(1e4), maps(30.0)
    assert np.isfinite(far).all()
    np.testing.assert_array_equal(far, at)
    assert not np.array_equal(at, maps(29.0))


def test_gap_names_the_reason_and_pallas_raises():
    assert hc.hc_gap(96, 4, 3584) is None and hc.hc_gap(512, 4, 3584) is None
    assert "D%128" in hc.hc_gap(96, 4, 100)
    assert "rows%16" in hc.hc_gap(8, 4, 128)            # bfloat16 rows
    assert hc.hc_gap(8, 4, 128, itemsize=4) is None
    assert "2n + n^2" in hc.hc_gap(16, 11, 128)
    assert hc.blocking(8, 4, 128) == {}
    x = jnp.zeros((8, 4 * 100), jnp.bfloat16)
    with pytest.raises(PallasShapeError, match="hc_pre"):
        hc.hc_pre(x, _maps(jax.random.key(0), 4, 128), n=4, impl="pallas",
                  **KW)
    with pytest.raises(PallasShapeError, match="hc_post"):
        hc.hc_post(x, jnp.zeros((8, 100), jnp.bfloat16),
                   jnp.zeros((8, hc.MAPS_WIDTH)), n=4, impl="pallas")


def test_blocking_at_the_cells_rows():
    """Rows a grid step and bytes in flight at the benchmark cell's rows: a
    decode step's 96 rows are ONE step of the pre-mix (its maps' lanes) and
    three of the post-mix; a 512-row chunk four and sixteen."""
    dec, chunk = hc.blocking(96, 4, 3584), hc.blocking(512, 4, 3584)
    assert (dec["pre_rows_per_step"], dec["post_rows_per_step"]) == (96, 32)
    assert (chunk["pre_rows_per_step"],
            chunk["post_rows_per_step"]) == (128, 32)
    assert chunk["pre_bytes_in_flight"] < hc.HC_VMEM
    assert chunk["post_bytes_in_flight"] < hc.HC_VMEM


# ---------------------------------------------------------------------------
# The engine against the reference, on a tiny ``xing4_0`` block
# ---------------------------------------------------------------------------


def hf_streams(cfg: M.MlaMoeConfig) -> dict:
    """The configuration-file keys of a block with streams."""
    return dict(hf_config(cfg), model_type="xing4_0", hc_mult=cfg.hc_mult,
                hc_sinkhorn_iters=cfg.hc_sinkhorn_iters, hc_eps=cfg.hc_eps,
                mhc_h_res_clamp_min=cfg.hc_clamp[0],
                mhc_h_res_clamp_max=cfg.hc_clamp[1])


@pytest.fixture(scope="module")
def tiny():
    """One dense + one expert layer that holds EVERY expert, four streams,
    float32."""
    base.Q_BLOCK = base.T_BLOCK = ref.T_BLOCK = 32
    cfg = M.MlaMoeConfig.tiny(n_layers=2, hc_mult=4, experts_held=16,
                              expert_offset=0)
    params = M.init_params(cfg, ref.weight_key(SEED))
    gen = M.MlaMoeGenerator(cfg, max_seq=256, interpret=True)
    return cfg, params, gen


def test_from_hf_reads_the_residual_keys_and_refuses_them_elsewhere(tiny):
    cfg, _, _ = tiny
    hf = hf_streams(cfg)
    got = M.MlaMoeConfig.from_hf(
        hf, max_seq=cfg.max_seq, dtype=jnp.float32,
        experts_total=cfg.n_experts, expert_offset=0,
        moe_block_m=cfg.moe_block_m)
    assert got == cfg and got.hc_mult == 4 and not got.sparse
    with pytest.raises(ValueError, match="hc_gate_bias"):
        M.MlaMoeConfig.from_hf({**hf, "hc_gate_bias": True}, max_seq=64)
    with pytest.raises(ValueError, match="index_topk"):
        M.MlaMoeConfig.from_hf({**hf, "index_topk": 64}, max_seq=64)
    with pytest.raises(KeyError, match="hc_eps"):
        M.MlaMoeConfig.from_hf({k: v for k, v in hf.items()
                                if k != "hc_eps"}, max_seq=64)


@pytest.mark.parametrize("kind", ["deepseek_v3", "glm_moe_dsa"])
@pytest.mark.parametrize("key", ["hc_mult", "hc_sinkhorn_iters", "hc_eps",
                                 "mhc_h_res_clamp_min",
                                 "mhc_h_res_clamp_max"])
def test_residual_keys_are_refused_by_name_on_the_other_blocks(tiny, kind,
                                                               key):
    """A block of one stream does not drop a key that asks for several."""
    cfg, _, _ = tiny
    hf = dict(hf_config(cfg), model_type=kind, **{key: 4})
    with pytest.raises(ValueError, match=key):
        M.MlaMoeConfig.from_hf(hf, max_seq=64)


def test_the_other_leaves_are_the_seeds_own_with_streams_and_without(tiny):
    """The maps draw from subkeys no other leaf uses: attention, router,
    experts and vocabulary are what the seed gives a block of one stream."""
    cfg, params, _ = tiny
    plain = M.init_params(dataclasses.replace(cfg, hc_mult=0),
                          ref.weight_key(SEED))
    for a, b in zip(params["layers"], plain["layers"]):
        maps = {k: a[k] for k in ("hc_attn", "hc_mlp")}
        rest = {k: v for k, v in a.items() if k not in maps}
        assert "hc_attn" not in b and jax.tree.structure(
            rest) == jax.tree.structure(b)
        for x, y in zip(jax.tree.leaves(rest), jax.tree.leaves(b)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        assert all(x.dtype == jnp.float32 for x in jax.tree.leaves(maps))
    np.testing.assert_array_equal(np.asarray(params["embed"]),
                                  np.asarray(plain["embed"]))
    # and the reference draws the same maps, independently
    want = ref.draw_maps(hf_streams(cfg), SEED, 1)["hc_mlp"]
    got = _as_reference(params["layers"][1]["hc_mlp"], cfg.hc_mult)
    for k in want:
        np.testing.assert_array_equal(np.asarray(got[k]),
                                      np.asarray(want[k]))


def _served_logits(gen, params, prompt, n_new, **kw):
    """``test_mla_moe._served_logits`` with the engine's options open."""
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
    assert eng.kernel_gaps == {}
    return toks, np.stack([rows[j] for j in range(len(prompt) + n_new - 1)])


def test_whole_prompt_prefill_matches_the_reference(tiny):
    cfg, params, gen = tiny
    prompt, = _prompts(cfg, [90], seed=1)
    got = np.asarray(gen.forward_logits(params, jnp.asarray(prompt)[None]))[0]
    seq = np.concatenate([prompt, prompt[:1]])      # the last feeds nothing
    want = ref.forward_logits(hf_streams(cfg), SEED, [seq], [1],
                              dtype=jnp.float32)[0]
    assert got.shape == want.shape
    assert np.abs(got - want).max() < LOGIT_TOL


def test_engine_logits_match_reference_and_bf16_does_not(tiny):
    """Chunked prefill (three chunks, the last padded; its kept row), then
    paged decode through the latent cache, against the reference's one
    full forward pass over prompt + served tokens — and the control: the
    int8 reference with its maps rounded to bfloat16 is far off."""
    cfg, params, gen = tiny
    prompt, = _prompts(cfg, [70])
    toks, got = _served_logits(gen, params, prompt, 10)
    seq = np.concatenate([prompt, np.asarray(toks, np.int32)])
    want = ref.forward_logits(hf_streams(cfg), SEED, [seq], [1],
                              dtype=jnp.float32)[0]
    assert got.shape == want.shape
    assert np.abs(got - want).max() < LOGIT_TOL
    low = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    gen16 = M.MlaMoeGenerator(low, max_seq=256, interpret=True)
    p16 = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16) if x.ndim != 0 else x, {
            **params, "layers": [
                {k: v for k, v in layer.items() if not k.startswith("hc_")}
                for layer in params["layers"]]})
    for layer, own in zip(p16["layers"], params["layers"]):
        layer.update({k: own[k] for k in ("hc_attn", "hc_mlp")})  # float32
    _, got16 = _served_logits(gen16, p16, prompt, 2)
    assert np.abs(got16 - want[:got16.shape[0]]).max() > 10 * LOGIT_TOL
    control = ref.forward_logits(hf_streams(cfg), SEED, [seq], [1],
                                 dtype=jnp.float32, int8=True)[0]
    assert np.abs(control - want).max() > 100 * LOGIT_TOL


@pytest.fixture(scope="module")
def undisturbed(tiny):
    """Greedy and sampled streams of an engine nothing disturbs (their
    logits are the reference's: the test above)."""
    cfg, params, gen = tiny
    prompts = _prompts(cfg, [31, 63, 15], seed=3)
    greedy = _serve(_engine(gen, params, prefix_cache=False, max_batch=3),
                    prompts, 12)
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


def test_prefix_hit_with_streams(tiny, undisturbed):
    cfg, params, gen = tiny
    prompts, greedy, _ = undisturbed
    eng = _engine(gen, params)
    assert _serve(eng, prompts[1:2], 12) == greedy[1:2]
    eng.submit(Request("again", prompts[1],
                       SamplingParams(max_new_tokens=12)))
    assert list(eng.run()["again"].token_ids) == greedy[1]
    assert eng.metrics.prefix_hits == 1
    assert eng.bm.num_free == eng.bm.num_allocatable


def test_preemption_and_recompute_with_streams(tiny, undisturbed):
    cfg, params, gen = tiny
    prompts, greedy, _ = undisturbed
    eng = _engine(gen, params, num_blocks=9, max_batch=3)
    assert _serve(eng, prompts, 12) == greedy
    assert eng.metrics.preemptions > 0
    assert eng.bm.num_free == eng.bm.num_allocatable


def test_the_kept_row_is_the_all_rows_chunks_row_and_unread_is_zeros(tiny):
    """``keep`` / ``read`` act on the whole row of streams: the chunk
    program that keeps row ``n_valid - 1`` returns the all-rows program's
    logits of that row, and a chunk nobody reads returns zeros and the same
    scratch."""
    cfg, params, gen = tiny
    chunk = jnp.asarray(_prompts(cfg, [32], seed=9)[0])[None]
    scratch = [tuple(jnp.zeros((1, h, 64, w), cfg.dtype)
                     for h, w in gen.kv_planes) for _ in range(cfg.n_layers)]
    copy = functools.partial(jax.tree.map, jnp.copy)
    every = gen._chunk_jit(params, chunk, copy(scratch), jnp.int32(0),
                           quantized=False, extent=64)
    kept = gen._chunk_jit(params, chunk, copy(scratch), jnp.int32(0),
                          quantized=False, extent=64, n_valid=jnp.int32(20))
    unread = gen._chunk_jit(params, chunk, copy(scratch), jnp.int32(0),
                            quantized=False, extent=64,
                            n_valid=jnp.int32(-20))
    assert kept[1].shape == (1, 1, cfg.vocab)
    np.testing.assert_allclose(np.asarray(kept[1][0, 0]),
                               np.asarray(every[1][0, 19]), rtol=2e-5,
                               atol=2e-5)
    assert not np.asarray(unread[1]).any()
    for a, b in zip(jax.tree.leaves(kept[0]), jax.tree.leaves(unread[0])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_summary_holds_the_gauge_and_the_blocking(tiny):
    cfg, params, gen = tiny
    eng = _engine(gen, params, max_batch=3, prefix_cache=False)
    _serve(eng, _prompts(cfg, [40], seed=2), 5)
    m = eng.metrics
    stats = m.summary()["hc"]
    assert stats["streams"] == 4 and stats["sublayers"] == 2 * cfg.n_layers
    assert set(stats["blocking"]) == {"prefill_chunk", "paged_decode"}
    text = m.to_prometheus()
    assert "serve_hc_streams 4" in text
    assert "serve_hc_mix" not in text
    # a block of one stream says so
    from test_mla_moe import SEED as S1

    plain = dataclasses.replace(cfg, hc_mult=0)
    one = _engine(M.MlaMoeGenerator(plain, max_seq=256, interpret=True),
                  M.init_params(plain, base.weight_key(S1)))
    assert one.metrics.summary()["hc"] == {
        "streams": 0, "sublayers": 0, "blocking": {}}
    assert "serve_hc_streams 0" in one.metrics.to_prometheus()
    assert "streams" not in one.gen.serve_hooks()


def test_off_the_interpreter_the_gaps_are_named(tiny, monkeypatch):
    """On a chip a decode step of 3 rows does not tile: the engine files
    the reason with its kernel gaps, by program."""
    from triton_dist_tpu.runtime import topology

    cfg, _, _ = tiny
    monkeypatch.setattr(topology, "is_tpu", lambda: True)
    gen = M.MlaMoeGenerator(dataclasses.replace(cfg, dtype=jnp.bfloat16),
                            max_seq=256)
    got = gen.stream_rows({"prefill_chunk": 64, "paged_decode": 3})
    assert got["gaps"] == {"hc_pre+hc_post@paged_decode":
                           "rows=3: needs rows%16"}
    assert got["blocking"]["prefill_chunk"]["pre_rows_per_step"] == 64
    assert got["blocking"]["paged_decode"] == {}
    assert "hc_pre+hc_post@prefill_chunk" in gen.kernel_gaps(
        page_size=128, prefill_chunk=24)
