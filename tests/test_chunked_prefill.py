"""Chunked prefill (Generator.prefill_chunked)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from chunk_rows import keep_no_row
from test_regions import FAMILIES, _build, _lowered

from triton_dist_tpu.models.generate import Generator
from triton_dist_tpu.models.llama import LlamaConfig, init_params


def _cfg():
    return LlamaConfig(vocab=64, dim=64, n_layers=2, n_heads=4,
                       n_kv_heads=2, ffn_dim=64, max_seq=32,
                       dtype=jnp.float32)


def test_chunked_matches_one_shot(mesh4, key):
    cfg = _cfg()
    params = init_params(cfg, key)
    gen = Generator(cfg, mesh4, axis="tp", max_seq=32)
    tokens = jax.random.randint(key, (2, 12), 0, cfg.vocab, jnp.int32)

    ref = gen.prefill(params, tokens)
    for chunk in (4, 5, 12):            # even, ragged-tail, single-chunk
        got = gen.prefill_chunked(params, tokens, chunk_size=chunk)
        np.testing.assert_allclose(np.asarray(got.last_logits),
                                   np.asarray(ref.last_logits),
                                   rtol=1e-4, atol=1e-4, err_msg=str(chunk))
        np.testing.assert_array_equal(np.asarray(got.kv_lens),
                                      np.asarray(ref.kv_lens))
        # Caches agree on the written prefix rows.
        k_ref = np.asarray(ref.caches[0][0])
        k_got = np.asarray(got.caches[0][0])
        np.testing.assert_allclose(k_got[:, :, :12], k_ref[:, :, :12],
                                   rtol=1e-4, atol=1e-4)


def test_chunked_then_decode(mesh4, key):
    """Generation continues identically from a chunked prefill."""
    cfg = _cfg()
    params = init_params(cfg, key)
    gen = Generator(cfg, mesh4, axis="tp", max_seq=32)
    tokens = jax.random.randint(key, (2, 10), 0, cfg.vocab, jnp.int32)

    t_ref, _ = gen.generate(params, gen.prefill(params, tokens), 5)
    t_chk, _ = gen.generate(
        params, gen.prefill_chunked(params, tokens, chunk_size=4), 5)
    np.testing.assert_array_equal(np.asarray(t_chk), np.asarray(t_ref))


def test_chunked_int8_cache(mesh4, key):
    """Chunked prefill into an int8 cache: decode stays reproducible and
    mostly agrees with the float path."""
    cfg = _cfg()
    params = init_params(cfg, key)
    gen_q = Generator(cfg, mesh4, axis="tp", max_seq=32, kv_dtype=jnp.int8)
    tokens = jax.random.randint(key, (2, 10), 0, cfg.vocab, jnp.int32)

    s1 = gen_q.prefill_chunked(params, tokens, chunk_size=4)
    s2 = gen_q.prefill_chunked(params, tokens, chunk_size=4)
    assert s1.caches[0][0]["q"].dtype == jnp.int8
    t1, _ = gen_q.generate(params, s1, 4)
    t2, _ = gen_q.generate(params, s2, 4)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))

    gen_f = Generator(cfg, mesh4, axis="tp", max_seq=32)
    t_f, _ = gen_f.generate(params, gen_f.prefill(params, tokens), 4)
    assert (np.asarray(t1) == np.asarray(t_f)).mean() >= 0.5


def test_chunked_moe(mesh4, key):
    from triton_dist_tpu.models import moe
    from triton_dist_tpu.models.generate_moe import (
        MoEGenerator, place_params_serving)

    cfg = moe.MoEConfig(vocab=64, dim=64, n_layers=1, n_heads=4,
                        n_kv_heads=4, n_experts=8, topk=2,
                        expert_ffn_dim=64, max_seq=32, block_m=8,
                        dtype=jnp.float32)
    params = place_params_serving(moe.init_params(cfg, key), cfg, mesh4,
                                  axis="tp")
    gen = MoEGenerator(cfg, mesh4, axis="tp", max_seq=32)
    tokens = jax.random.randint(key, (2, 8), 0, cfg.vocab, jnp.int32)
    ref = gen.prefill(params, tokens)
    got = gen.prefill_chunked(params, tokens, chunk_size=3)
    np.testing.assert_allclose(np.asarray(got.last_logits),
                               np.asarray(ref.last_logits),
                               rtol=1e-4, atol=1e-4)


def test_chunked_flash_path_reached(key, monkeypatch):
    """The flash-kernel branch of the serving prefill (head_dim 128,
    128-aligned chunks, world-1 mesh, interpret) — the exact path real-TPU
    serving takes — is exercised on the CPU mesh AND asserted reached via
    a kernel spy (the strict-pallas rule: a test that can silently fall
    back to XLA covers nothing).  Chunked must match one-shot bitwise-
    closely; both must match a world-2 (dense, SP-sharded cache) run."""
    import sys

    import triton_dist_tpu.kernels.flash_attention  # noqa: F401
    from jax.sharding import Mesh

    # the package __init__ re-exports the flash_attention FUNCTION, which
    # shadows the submodule on attribute access — go through sys.modules
    fa = sys.modules["triton_dist_tpu.kernels.flash_attention"]

    cfg = LlamaConfig(vocab=64, dim=256, n_layers=2, n_heads=2,
                      n_kv_heads=1, ffn_dim=128, max_seq=512,
                      dtype=jnp.float32)
    assert cfg.head_dim == 128
    params = init_params(cfg, key)
    tokens = jax.random.randint(key, (2, 256), 0, cfg.vocab, jnp.int32)

    calls = {"n": 0}
    real = fa._flash_pallas

    def spy(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(fa, "_flash_pallas", spy)

    mesh1 = Mesh(np.array(jax.devices()[:1]), ("sp",))
    gen = Generator(cfg, mesh1, max_seq=512, interpret=True)
    ref = gen.prefill(params, tokens)
    assert calls["n"] > 0, "one-shot prefill never reached the flash kernel"
    n_prompt = calls["n"]
    got = gen.prefill_chunked(params, tokens, chunk_size=128)
    assert calls["n"] > n_prompt, "chunked prefill never reached the kernel"
    np.testing.assert_allclose(np.asarray(got.last_logits),
                               np.asarray(ref.last_logits),
                               rtol=1e-4, atol=1e-4)

    # world-2: the SP path — per-shard flash inside shard_map + LSE
    # combine (sp_flash_attention_shard) — must ALSO reach the kernel
    # and agree with the world-1 answer.
    n_before = calls["n"]
    mesh2 = Mesh(np.array(jax.devices()[:2]), ("sp",))
    gen2 = Generator(cfg, mesh2, max_seq=512, interpret=True)
    got2 = gen2.prefill_chunked(params, tokens, chunk_size=128)
    assert calls["n"] > n_before, "SP chunked prefill never reached flash"
    np.testing.assert_allclose(np.asarray(got2.last_logits),
                               np.asarray(ref.last_logits),
                               rtol=1e-4, atol=1e-4)


def test_chunked_int8_flash_path(key, monkeypatch):
    """int8-cache chunked prefill rides the fused int8 flash kernel at
    head_dim 128 (world 1 and the SP path at world 2), reach-asserted,
    and matches the float generator closely."""
    import sys

    import triton_dist_tpu.kernels.flash_attention  # noqa: F401
    from jax.sharding import Mesh

    fa = sys.modules["triton_dist_tpu.kernels.flash_attention"]
    calls = {"n": 0}
    real = fa._flash_pallas

    def spy(*a, **kw):
        if kw.get("k_scale") is not None or len(a) > 10:
            calls["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(fa, "_flash_pallas", spy)

    cfg = LlamaConfig(vocab=64, dim=256, n_layers=1, n_heads=2,
                      n_kv_heads=1, ffn_dim=128, max_seq=512,
                      dtype=jnp.float32)
    params = init_params(cfg, key)
    tokens = jax.random.randint(key, (1, 256), 0, cfg.vocab, jnp.int32)

    ref = None
    for world in (1, 2):
        mesh = Mesh(np.array(jax.devices()[:world]), ("sp",))
        gen_f = Generator(cfg, mesh, max_seq=512, interpret=True)
        gen_q = Generator(cfg, mesh, max_seq=512, interpret=True,
                          kv_dtype=jnp.int8)
        n0 = calls["n"]
        got = gen_q.prefill_chunked(params, tokens, chunk_size=128)
        assert calls["n"] > n0, f"world={world}: int8 flash not reached"
        if ref is None:
            ref = gen_f.prefill_chunked(params, tokens, chunk_size=128)
        # int8 rounding: loose tolerance vs the float path
        np.testing.assert_allclose(np.asarray(got.last_logits),
                                   np.asarray(ref.last_logits),
                                   rtol=0.2, atol=0.2)


# ---------------------------------------------------------------------------
# A prefill chunk keeps the ONE row its caller reads (ISSUE 42)
# ---------------------------------------------------------------------------

# the families' own engine-against-reference logit tolerances (float32:
# test_serve_engine, test_mla_moe, test_mla_dsa, test_swa_moe,
# test_ssm_yoco, test_gdn_hybrid, test_laguna): a one-row product sums in
# another order
KEEP_TOL = {"dense": 1e-4, "latent": 1e-4, "sparse": 1e-4, "window": 2e-4,
            "state": 3e-4, "matrix": 5e-4, "gated": 2e-4, "streams": 2e-4}


@pytest.fixture(scope="module")
def keep_pair():
    """family -> (toy engine, chunk rows, extent, the engine's own chunk
    program, the all-rows twin of it): built and compiled once."""
    cache = {}

    def get(family):
        if family in cache:
            return cache[family]
        eng, chunk = _build(family)
        extent = 2 * chunk
        kept = _lowered(eng, chunk, "prefill_chunk", extent=extent).compile()
        with pytest.MonkeyPatch.context() as mp:
            keep_no_row(mp)
            twin, _ = _build(family)            # fresh jits: nothing cached
            every = _lowered(twin, chunk, "prefill_chunk",
                             extent=extent).compile()
        cache[family] = (eng, chunk, extent, kept, every)
        return cache[family]

    return get


@pytest.mark.parametrize("prefix", ["prefix0", "prefixed"])
@pytest.mark.parametrize("rows", ["full", "residual", "unread"])
@pytest.mark.parametrize("family", FAMILIES)
def test_chunk_keeps_the_row_its_caller_reads(keep_pair, family, rows,
                                              prefix):
    """The chunk program an engine calls returns logits [1, 1, V]: row
    ``n_valid - 1`` of the all-rows program's within the family's logit
    tolerance, at a full chunk and at a padded residual, from an empty
    scratch and over a chunk already prefilled — and every cache, state
    and carried-convolution plane it leaves is BITWISE the all-rows
    program's (the rows it no longer carries past the last layer that
    writes were computed and dropped).  ``unread``: a NEGATIVE ``n_valid``
    (a residual that is not its prompt's last call) skips the rest of the
    program — the logits are zeros — and leaves the same planes."""
    eng, chunk, extent, kept, every = keep_pair(family)
    vocab = eng.gen.cfg.vocab
    rng = np.random.default_rng([FAMILIES.index(family), 42])
    tokens = rng.integers(0, vocab, (2, 1, chunk)).astype(np.int32)
    n = chunk if rows == "full" else chunk - chunk // 3 - 1
    scratch = lambda: eng._zero_fn(s_ext=extent)      # noqa: E731
    pos = 0
    if prefix == "prefixed":
        first = (eng.params, jnp.asarray(tokens[0]))
        filled, *_ = kept(*first, scratch(), np.int32(0),
                          n_valid=np.int32(chunk))
        twin, *_ = every(*first, scratch(), np.int32(0),
                         n_valid=np.int32(chunk))
        pos = chunk
        scratch = None
    buf = tokens[1].copy()
    buf[0, n:] = 0                                    # the engine's padding
    call = (eng.params, jnp.asarray(buf))
    got_c, got, *_ = kept(*call, filled if scratch is None else scratch(),
                          np.int32(pos),
                          n_valid=np.int32(-n if rows == "unread" else n))
    want_c, want, *_ = every(*call, twin if scratch is None else scratch(),
                             np.int32(pos), n_valid=np.int32(n))
    assert got.shape == (1, 1, vocab) and want.shape == (1, chunk, vocab)
    assert got.dtype == want.dtype == jnp.float32
    if rows == "unread":
        assert not np.asarray(got).any()
    else:
        np.testing.assert_allclose(np.asarray(got[0, 0]),
                                   np.asarray(want[0, n - 1]),
                                   atol=KEEP_TOL[family], rtol=0)
    # far from any other row's: the row is the right one
    assert np.abs(np.asarray(want[0, n - 2] - want[0, n - 1])).max() > 1e-2
    got_l, want_l = jax.tree.leaves(got_c), jax.tree.leaves(want_c)
    assert len(got_l) == len(want_l) > 0
    for g, w in zip(got_l, want_l):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
