"""Paged-KV (block_table) decode vs the contiguous cache path.

Reference analog: the ``block_table`` argument of the reference's
``SpGQAFlashDecodeAttention.forward`` (sp_flash_decode_layer.py:78) —
decode reads the KV cache through a page table.  Equivalence oracle: a
paged pool holding the same rows as a contiguous cache (under a random
page permutation) must decode identically.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.kernels.flash_decode import (
    gqa_decode_paged_shard,
    gqa_decode_shard,
)
from triton_dist_tpu.kernels.gemm import PallasShapeError


def _paged_from_contiguous(k, v, page, rng):
    """Scatter a contiguous [B, Hkv, S, D] cache into a permuted page
    pool; returns (k_pool, v_pool, table [B, S//page])."""
    B, Hkv, S, D = k.shape
    n = S // page
    N = B * n
    perm = rng.permutation(N)
    table = perm.reshape(B, n).astype(np.int32)
    k_pool = np.zeros((N, Hkv, page, D), k.dtype)
    v_pool = np.zeros((N, Hkv, page, D), v.dtype)
    for b in range(B):
        for i in range(n):
            k_pool[table[b, i]] = np.asarray(k[b, :, i * page:(i + 1) * page])
            v_pool[table[b, i]] = np.asarray(v[b, :, i * page:(i + 1) * page])
    return jnp.asarray(k_pool), jnp.asarray(v_pool), jnp.asarray(table)


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_paged_matches_contiguous(key, impl):
    B, Hq, Hkv, D, S, page = 2, 4, 2, 128, 1024, 256
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, Hq, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Hkv, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Hkv, S, D), jnp.float32)
    lens = jnp.array([S, S - 300], jnp.int32)  # ragged second row

    k_pool, v_pool, table = _paged_from_contiguous(
        k, v, page, np.random.default_rng(0))
    out_p, lse_p = gqa_decode_paged_shard(q, k_pool, v_pool, table, lens,
                                          impl=impl, interpret=True)
    out_c, lse_c = gqa_decode_shard(q, k, v, lens, impl="xla")
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_c),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse_p), np.asarray(lse_c),
                               rtol=2e-5, atol=2e-5)


def test_paged_strict_raises(key):
    q = jnp.zeros((1, 2, 128), jnp.float32)
    pool = jnp.zeros((4, 1, 64, 128), jnp.float32)  # page 64: not %128
    table = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(PallasShapeError):
        gqa_decode_paged_shard(q, pool, pool, table,
                               jnp.array([256], jnp.int32),
                               impl="pallas", interpret=True)


def test_paged_layer_sp(mesh2, key):
    """Layer-level paged SP decode (world 2): per-rank pool shards +
    a rank-owned permuted table == the contiguous SP layer."""
    from triton_dist_tpu.layers.sp_flash_decode import (
        SpGQAFlashDecodeAttention)

    B, Hq, Hkv, D, page, n_loc = 2, 4, 2, 128, 128, 4
    world = 2
    S = world * n_loc * page                         # 1024
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, Hq, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Hkv, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Hkv, S, D), jnp.float32)
    lens = jnp.array([S, S - 257], jnp.int32)

    layer = SpGQAFlashDecodeAttention(mesh2, axis="tp", interpret=True)
    k_pool, v_pool, table = layer.init_paged_cache(
        B, Hkv, page, pages_per_seq=world * n_loc, head_dim=D,
        dtype=jnp.float32)
    # Permute the returned table within each rank's ownership range (a
    # serving allocator's freedom), then fill pool rows per the table.
    N_loc = B * n_loc
    rng = np.random.default_rng(1)
    tab = np.array(table)
    for r in range(world):
        cols = slice(r * n_loc, (r + 1) * n_loc)
        flat = tab[:, cols].reshape(-1) - r * N_loc
        flat = r * N_loc + rng.permutation(N_loc)[
            np.argsort(np.argsort(flat))]  # relabel rows, keep validity
        tab[:, cols] = flat.reshape(B, n_loc)
    kp = np.array(k_pool)  # np.array: writable copy (asarray is RO)
    vp = np.array(v_pool)
    for b in range(B):
        for logical in range(world * n_loc):
            sl = slice(logical * page, (logical + 1) * page)
            kp[tab[b, logical]] = np.asarray(k[b, :, sl])
            vp[tab[b, logical]] = np.asarray(v[b, :, sl])
    k_pool = jax.device_put(jnp.asarray(kp), layer.pool_sharding())
    v_pool = jax.device_put(jnp.asarray(vp), layer.pool_sharding())

    got = layer(q, k_pool, v_pool, lens, block_table=jnp.asarray(tab))

    kc, vc = layer.init_cache(B, Hkv, S, D, dtype=jnp.float32,
                              k_init=k, v_init=v)
    want = layer(q, kc, vc, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_paged_vmem_guard(key):
    """An over-budget page (it cannot shrink — it IS the cache layout)
    raises the curated error under explicit pallas and falls back under
    auto, instead of failing deep in Mosaic."""
    q = jnp.zeros((1, 2, 256), jnp.float32)
    pool = jnp.zeros((2, 1, 8192, 256), jnp.bfloat16)  # 16 MiB K+V blocks
    table = jnp.zeros((1, 2), jnp.int32)
    lens = jnp.array([8192], jnp.int32)
    with pytest.raises(PallasShapeError):
        gqa_decode_paged_shard(q, pool, pool, table, lens,
                               impl="pallas", interpret=True)
    out, _ = gqa_decode_paged_shard(q, pool, pool, table, lens,
                                    impl="auto")
    assert out.shape == q.shape


# ---------------------------------------------------------------------------
# ISSUE 25: every KV head of a page in one step, live pages only
# ---------------------------------------------------------------------------

from triton_dist_tpu.kernels import flash_decode as fd  # noqa: E402

_PAGE, _NP = 128, 4           # table width 4: 512 slots a row
# one batch for every case: an empty row, rows of 1 token, of exactly one
# page, one short of the table, and ragged ones in between
_LENS = [0, 1, _PAGE, _NP * _PAGE - 1, 300, _PAGE + 1, 0, 2 * _PAGE]


def _paged_case(key, hkv, n_tok):
    B, g, D = len(_LENS), 4, 128
    N = B * _NP + 1
    ks = jax.random.split(key, 3)
    shape = (B, hkv * g, D) if n_tok == 1 else (B, n_tok, hkv * g, D)
    q = jax.random.normal(ks[0], shape, jnp.float32)
    k_pool = jax.random.normal(ks[1], (N, hkv, _PAGE, D), jnp.float32)
    v_pool = jax.random.normal(ks[2], (N, hkv, _PAGE, D), jnp.float32)
    rng = np.random.default_rng(3)
    table = np.zeros((B, _NP), np.int32)      # dead entries: the null block
    free = list(rng.permutation(np.arange(1, N)))
    for b, n in enumerate(_LENS):
        for i in range(-(-n // _PAGE)):
            table[b, i] = free.pop()
    return q, k_pool, v_pool, jnp.asarray(table), jnp.asarray(_LENS, jnp.int32)


@pytest.mark.parametrize("window", [0, 200])
@pytest.mark.parametrize("mode", ["decode", "verify", "verify_qlens",
                                  "decode_qlens"])
@pytest.mark.parametrize("hkv", [8, 2, 1])
def test_paged_kernel_matches_xla_over_heads_rows_windows(key, hkv, mode,
                                                          window):
    """``impl="pallas"`` (interpreter) against ``_local_decode_xla`` over
    the gathered view, on ``out`` and ``lse``: local Hkv of a whole model,
    a TP-4 rank and a single head; rows = g and T * g; a sliding window;
    ``q_lens`` with dead rows; and a batch holding empty rows, rows of 1,
    of one page and of one token short of the table."""
    n_tok = 1 if mode.startswith("decode") else 3
    q, k_pool, v_pool, table, lens = _paged_case(key, hkv, n_tok)
    q_lens = None
    if mode.endswith("qlens"):
        q_lens = jnp.asarray(np.minimum(
            np.array([1, 0, 3, 2, 0, 1, 3, 2]), n_tok), jnp.int32)
    # the verify contract: the T queries' K/V already sit in the cache
    lens = jnp.maximum(lens, n_tok) if n_tok > 1 else lens
    kw = dict(window=window, q_lens=q_lens)
    got_o, got_l = fd.gqa_decode_paged_shard(
        q, k_pool, v_pool, table, lens, impl="pallas", interpret=True, **kw)
    want_o, want_l = fd._local_decode_xla(
        q, fd._paged_gather(k_pool, table), fd._paged_gather(v_pool, table),
        lens, scale=1.0 / np.sqrt(q.shape[-1]), **kw)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_l), np.asarray(want_l),
                               rtol=2e-5, atol=2e-5)
    if n_tok == 1 and q_lens is None:
        # a row with len == 0 walks nothing and reports empty partials
        assert float(jnp.abs(got_o[0]).max()) == 0.0
        assert float(got_l[0].max()) == np.float32(fd.NEG_INF)


def test_paged_kernel_never_reads_a_dead_table_entry(key):
    """The walk stops at the row's last live page: poisoning every pool
    page a dead table entry could reach (the null block, and pages past
    each row's length) changes nothing."""
    q, k_pool, v_pool, table, lens = _paged_case(key, 2, 1)
    run = lambda k, v, t: fd.gqa_decode_paged_shard(  # noqa: E731
        q, k, v, t, lens, impl="pallas", interpret=True)
    want_o, want_l = run(k_pool, v_pool, table)
    live = np.unique(np.concatenate(
        [np.asarray(table)[b, :-(-n // _PAGE)] for b, n in enumerate(_LENS)]))
    dead = np.setdiff1d(np.arange(k_pool.shape[0]), live)
    nan_k = k_pool.at[dead].set(jnp.nan)
    nan_v = v_pool.at[dead].set(jnp.nan)
    # dead entries aimed at a poisoned page instead of the null block
    dead_tab = np.asarray(table).copy()
    for b, n in enumerate(_LENS):
        dead_tab[b, -(-n // _PAGE):] = dead[-1]
    got_o, got_l = run(nan_k, nan_v, jnp.asarray(dead_tab))
    np.testing.assert_array_equal(np.asarray(got_o), np.asarray(want_o))
    np.testing.assert_array_equal(np.asarray(got_l), np.asarray(want_l))


@pytest.mark.parametrize("hkv,page,d,itemsize,want", [
    (8, 128, 128, 2, 8),      # Mistral / llama3-8B whole: 1 MiB of VMEM
    (2, 128, 128, 2, 2),      # the TP-4 rank of the same models
    (1, 128, 128, 2, 1),
    (8, 128, 128, 4, 8),      # float32 pools
    (8, 1024, 256, 2, 4),     # 8 heads would take 16 MiB: half of them
    (6, 2048, 256, 2, 3),     # 3 heads take exactly 12 MiB, 6 twice that
    (8, 8192, 256, 2, 0),     # not even one head fits
])
def test_paged_heads_per_step_rule(hkv, page, d, itemsize, want):
    """The blocking is chosen from the shapes alone: the largest divisor
    of the local Hkv whose double-buffered K+V blocks fit the stated
    budget; when even one head does not fit, ``paged_kernel_gap`` names
    the reroute (never a Mosaic failure)."""
    hh = fd.paged_heads_per_step(hkv, page, d, itemsize)
    blocking = fd.paged_kernel_blocking(hkv, page, d, itemsize, batch=32)
    gap = fd.paged_kernel_gap(page, d, itemsize)
    if want == 0:
        assert hh == 0 and "exceed 12 MiB VMEM" in gap
        assert blocking["heads_per_step"] == blocking["steps_per_call"] == 0
        return
    assert gap is None
    assert hh == want and hkv % hh == 0
    assert 4 * hh * page * d * itemsize <= fd.PAGED_VMEM_BUDGET
    # the LARGEST such divisor
    assert all(4 * h * page * d * itemsize > fd.PAGED_VMEM_BUDGET
               for h in range(hh + 1, hkv + 1) if hkv % h == 0)
    assert blocking == {"heads_per_step": hh,
                        "steps_per_call": 32 * (hkv // hh),
                        "pages_per_step": "dynamic",
                        "vmem_bytes": 4 * hh * page * d * itemsize}
