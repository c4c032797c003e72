"""Flash-decode tests: local kernel, SP combine, layer, cache append.

Reference analog: test/nvidia/test_decode_attn.py + test_sp_decode_attn.py —
correctness vs a dense softmax-attention reference with randomized inputs and
ragged per-batch kv lengths.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from triton_dist_tpu.kernels.flash_decode import (
    combine_partials,
    create_sp_decode_context,
    gqa_decode_shard,
    sp_gqa_decode,
)
from triton_dist_tpu.layers.sp_flash_decode import SpGQAFlashDecodeAttention


def dense_reference(q, k, v, lens):
    """Full softmax GQA attention over the first lens[b] KV rows."""
    B, Hq, D = q.shape
    _, Hkv, S, _ = k.shape
    g = Hq // Hkv
    qf = q.astype(jnp.float32).reshape(B, Hkv, g, D)
    logits = jnp.einsum("bhgd,bhsd->bhgs", qf, k.astype(jnp.float32))
    logits = logits / np.sqrt(D)
    valid = jnp.arange(S)[None, :] < lens[:, None]
    logits = jnp.where(valid[:, None, None, :], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgs,bhsd->bhgd", p, v.astype(jnp.float32))
    return out.reshape(B, Hq, D)


def make_inputs(key, B, Hq, Hkv, S, D, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, Hq, D), dtype)
    k = jax.random.normal(kk, (B, Hkv, S, D), dtype)
    v = jax.random.normal(kv, (B, Hkv, S, D), dtype)
    return q, k, v


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("g", [1, 4])
def test_local_decode_matches_dense(impl, g, dtype):
    """bf16 covers the serving path the Pallas kernel optimizes: K/V feed
    the MXU in storage dtype and P is downcast for the PV matmul."""
    B, Hkv, S, D = 2, 2, 512, 128
    Hq = g * Hkv
    q, k, v = make_inputs(jax.random.key(0), B, Hq, Hkv, S, D, dtype)
    lens = jnp.array([S, 200], jnp.int32)
    out, lse = gqa_decode_shard(q, k, v, lens, block_s=128, impl=impl,
                                interpret=(impl == "pallas"))
    ref = dense_reference(q, k, v, lens)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=tol, atol=tol)
    assert np.isfinite(np.asarray(lse)).all()


def test_local_decode_empty_shard():
    """A shard wholly past kv_len returns zero out and -inf-proxy lse."""
    B, Hq, Hkv, S, D = 1, 4, 2, 256, 128
    q, k, v = make_inputs(jax.random.key(1), B, Hq, Hkv, S, D)
    lens = jnp.zeros((B,), jnp.int32)
    out, lse = gqa_decode_shard(q, k, v, lens, impl="pallas", interpret=True)
    assert np.all(np.asarray(out) == 0.0)
    assert np.all(np.asarray(lse) < -1e29)


def test_combine_partials_matches_monolithic():
    """Splitting KV into W chunks + LSE-combining == attention over all KV."""
    B, Hq, Hkv, S, D, W = 2, 4, 2, 256, 128, 4
    q, k, v = make_inputs(jax.random.key(2), B, Hq, Hkv, W * S, D)
    lens = jnp.array([W * S, W * S - 100], jnp.int32)
    outs, lses = [], []
    for r in range(W):
        lr = jnp.clip(lens - r * S, 0, S)
        o, l = gqa_decode_shard(q, k[:, :, r * S:(r + 1) * S],
                                v[:, :, r * S:(r + 1) * S], lr, impl="xla")
        outs.append(o)
        lses.append(l)
    merged = combine_partials(jnp.stack(outs), jnp.stack(lses))
    ref = dense_reference(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(merged), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_sp_decode(impl):
    W = 4
    mesh = Mesh(np.array(jax.devices()[:W]), ("sp",))
    B, Hq, Hkv, D = 2, 8, 2, 128
    S = W * 256
    q, k, v = make_inputs(jax.random.key(3), B, Hq, Hkv, S, D)
    lens = jnp.array([S, 300], jnp.int32)

    ctx = create_sp_decode_context(mesh, axis="sp", block_s=128, impl=impl,
                                   interpret=(impl == "pallas"))
    sh = NamedSharding(mesh, P(None, None, "sp"))
    out = sp_gqa_decode(q, jax.device_put(k, sh), jax.device_put(v, sh),
                        lens, ctx)
    ref = dense_reference(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_layer_append_and_decode():
    """Greedy-decode loop: append K/V then attend, vs dense on the host."""
    W = 4
    mesh = Mesh(np.array(jax.devices()[:W]), ("sp",))
    layer = SpGQAFlashDecodeAttention(mesh, axis="sp", impl="xla")
    B, Hq, Hkv, D, S = 2, 4, 2, 128, W * 128

    k_cache, v_cache = layer.init_cache(B, Hkv, S, D, jnp.float32)
    key = jax.random.key(4)
    lens = jnp.array([0, 0], jnp.int32)

    host_k = np.zeros((B, Hkv, S, D), np.float32)
    host_v = np.zeros((B, Hkv, S, D), np.float32)
    for t in range(3):
        key, k1, k2, k3 = jax.random.split(key, 4)
        nk = jax.random.normal(k1, (B, Hkv, D), jnp.float32)
        nv = jax.random.normal(k2, (B, Hkv, D), jnp.float32)
        k_cache, v_cache = layer.append_kv(k_cache, v_cache, nk, nv, lens)
        host_k[:, :, t] = np.asarray(nk)
        host_v[:, :, t] = np.asarray(nv)
        lens = lens + 1

        q = jax.random.normal(k3, (B, Hq, D), jnp.float32)
        out = layer(q, k_cache, v_cache, lens)
        ref = dense_reference(q, jnp.asarray(host_k), jnp.asarray(host_v),
                              lens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


def test_layer_ragged_append():
    """Batch rows appending at different positions land on different ranks."""
    W = 4
    mesh = Mesh(np.array(jax.devices()[:W]), ("sp",))
    layer = SpGQAFlashDecodeAttention(mesh, axis="sp", impl="xla")
    B, Hkv, D, S = 2, 2, 128, W * 128
    k_cache, v_cache = layer.init_cache(B, Hkv, S, D, jnp.float32)

    # Row 0 appends at position 5 (rank 0); row 1 at 3*128+7 (rank 3).
    lens = jnp.array([5, 3 * 128 + 7], jnp.int32)
    nk = jax.random.normal(jax.random.key(5), (B, Hkv, D), jnp.float32)
    nv = jax.random.normal(jax.random.key(6), (B, Hkv, D), jnp.float32)
    k_cache, _ = layer.append_kv(k_cache, v_cache, nk, nv, lens)
    kc = np.asarray(k_cache)
    np.testing.assert_allclose(kc[0, :, 5], np.asarray(nk)[0], rtol=1e-6)
    np.testing.assert_allclose(kc[1, :, 3 * 128 + 7], np.asarray(nk)[1],
                               rtol=1e-6)
    assert np.all(kc[0, :, :5] == 0) and np.all(kc[0, :, 6:] == 0)


def test_sp_combine_kernel_matches_epilogue(mesh4, key):
    """The comm-fused combine kernel (remote DMA + in-kernel LSE merge)
    equals the gather + combine_partials epilogue on distinct per-rank
    partials."""
    import functools
    from jax.sharding import PartitionSpec as P
    from triton_dist_tpu.kernels.flash_decode import (
        combine_partials,
        sp_combine_shard,
    )

    world, B, H, D = 4, 2, 8, 128
    ks = jax.random.split(key, 2)
    outs = jax.random.normal(ks[0], (world, B, H, D), jnp.float32)
    lses = jax.random.normal(ks[1], (world, B, H), jnp.float32)

    def shard_fn(outs_ref, lses_ref):
        r = jax.lax.axis_index("tp")
        return sp_combine_shard(outs_ref[r], lses_ref[r], axis="tp",
                                interpret=True)

    got = jax.jit(jax.shard_map(shard_fn, mesh=mesh4, in_specs=(P(), P()),
                                out_specs=P(), check_vma=False))(outs, lses)
    want = combine_partials(outs, lses)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_oversize_partials_combine_in_row_blocks(mesh4, key):
    """The fused combine keeps every rank's plane in VMEM — sized for a
    decode step's B*Hq rows.  A seq-layout prefill chunk combines c*Hq
    rows: 128 x 32 at llama3-8B widths needed 32 MB of the v5e's 16 MiB
    scoped VMEM and failed to compile ON THE CHIP (PR 21).  Past the
    budget the same kernel runs once per block of rows; 13 x 128 = 1664
    rows at world 4 is just over (two blocks), and 1665 needs the last
    block padded."""
    from jax.sharding import PartitionSpec as P
    from triton_dist_tpu.kernels.flash_decode import (
        combine_partials,
        sp_combine_shard,
    )

    world, D = 4, 128
    for B, H in ((13, 128), (1665, 1)):
        ks = jax.random.split(jax.random.fold_in(key, B), 2)
        outs = jax.random.normal(ks[0], (world, B, H, D), jnp.float32)
        lses = jax.random.normal(ks[1], (world, B, H), jnp.float32)

        def shard_fn(outs_ref, lses_ref):
            r = jax.lax.axis_index("tp")
            return sp_combine_shard(outs_ref[r], lses_ref[r], axis="tp",
                                    interpret=True)

        fn = jax.jit(jax.shard_map(shard_fn, mesh=mesh4,
                                   in_specs=(P(), P()), out_specs=P(),
                                   check_vma=False))
        assert "while" in fn.lower(outs, lses).as_text()  # blocked
        np.testing.assert_allclose(
            np.asarray(fn(outs, lses)),
            np.asarray(combine_partials(outs, lses)),
            rtol=1e-5, atol=1e-5)


def test_bf16_vmem_fit_shrink(key):
    """Large-D bf16 caches shrink the KV block to fit VMEM instead of
    raising (r4 review: the shrink floor was the int8 1024, wrongly
    rejecting legal bf16 blocks below it).  S=1024, D=2048 bf16 needs
    16 MiB at the full-shard default; the 512 divisor (8 MiB) is legal."""
    from triton_dist_tpu.kernels.flash_decode import gqa_decode_shard

    B, Hq, Hkv, D, S = 1, 2, 1, 2048, 1024
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, Hq, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, Hkv, S, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, Hkv, S, D), jnp.bfloat16)
    lens = jnp.full((B,), S, jnp.int32)
    out, lse = gqa_decode_shard(q, k, v, lens, impl="pallas",
                                interpret=True)
    ref, ref_lse = gqa_decode_shard(q, k, v, lens, impl="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=5e-2, atol=5e-2)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               rtol=1e-2, atol=1e-2)


def test_soft_cap_decode(key):
    """Gemma-2 logit capping through every decode variant (bf16, int8,
    paged) vs a direct dense computation with the cap applied."""
    from triton_dist_tpu.kernels.flash_decode import (
        gqa_decode_paged_shard,
        quantize_kv,
    )

    B, Hq, Hkv, D, S, cap = 1, 2, 1, 128, 512, 30.0
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, Hq, D), jnp.float32) * 4  # big logits
    k = jax.random.normal(ks[1], (B, Hkv, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Hkv, S, D), jnp.float32)
    lens = jnp.full((B,), S, jnp.int32)

    # direct dense oracle
    g = Hq // Hkv
    logits = jnp.einsum("bhgd,bhsd->bhgs",
                        q.reshape(B, Hkv, g, D), k) / np.sqrt(D)
    logits = cap * jnp.tanh(logits / cap)
    p = jax.nn.softmax(logits, axis=-1)
    want = jnp.einsum("bhgs,bhsd->bhgd", p, v).reshape(B, Hq, D)

    out, _ = gqa_decode_shard(q, k, v, lens, impl="pallas", interpret=True,
                              soft_cap=cap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # capping must actually change the answer at this logit magnitude
    out0, _ = gqa_decode_shard(q, k, v, lens, impl="pallas", interpret=True)
    assert float(jnp.max(jnp.abs(out - out0))) > 1e-3

    kq8, ksc = quantize_kv(k)
    vq8, vsc = quantize_kv(v)
    out_i8, _ = gqa_decode_shard(q, kq8, vq8, lens, impl="pallas",
                                 interpret=True, k_scale=ksc, v_scale=vsc,
                                 soft_cap=cap)
    np.testing.assert_allclose(np.asarray(out_i8), np.asarray(want),
                               rtol=2e-2, atol=2e-2)

    page = 128
    n = S // page
    pool_k = (k.reshape(B, Hkv, n, page, D).transpose(0, 2, 1, 3, 4)
              .reshape(B * n, Hkv, page, D))
    pool_v = (v.reshape(B, Hkv, n, page, D).transpose(0, 2, 1, 3, 4)
              .reshape(B * n, Hkv, page, D))
    table = jnp.arange(B * n, dtype=jnp.int32).reshape(B, n)
    out_p, _ = gqa_decode_paged_shard(q, pool_k, pool_v, table, lens,
                                      impl="pallas", interpret=True,
                                      soft_cap=cap)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_soft_cap_xla_fallback(key):
    """Regression (r4 review): the xla/non-pallas dispatch branches must
    cap too — impl='xla' bf16, int8-under-xla, and a ragged shape all
    agree with the capped pallas result."""
    from triton_dist_tpu.kernels.flash_decode import quantize_kv

    B, Hq, Hkv, D, S, cap = 1, 2, 1, 128, 256, 15.0
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, Hq, D), jnp.float32) * 4
    k = jax.random.normal(ks[1], (B, Hkv, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Hkv, S, D), jnp.float32)
    lens = jnp.full((B,), S, jnp.int32)

    want, _ = gqa_decode_shard(q, k, v, lens, impl="pallas",
                               interpret=True, soft_cap=cap)
    got, _ = gqa_decode_shard(q, k, v, lens, impl="xla", soft_cap=cap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    kq8, ksc = quantize_kv(k)
    vq8, vsc = quantize_kv(v)
    got_i8, _ = gqa_decode_shard(q, kq8, vq8, lens, impl="xla",
                                 k_scale=ksc, v_scale=vsc, soft_cap=cap)
    np.testing.assert_allclose(np.asarray(got_i8), np.asarray(want),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_sliding_window_sp_decode(impl, key):
    """r5: the GLOBAL window rule under SP sharding (world 4).  Lengths
    chosen so the window straddles a shard boundary on row 0 and leaves
    shard 0 FULLY outside on row 1 (its partial must no-op in the
    combine); shards past the length stay all-masked as before."""
    from triton_dist_tpu.layers.sp_flash_decode import (
        SpGQAFlashDecodeAttention)

    W = 4
    mesh = Mesh(np.array(jax.devices()[:W]), ("sp",))
    B, Hq, Hkv, D, w = 2, 4, 2, 128, 160
    S = W * 128
    q, k, v = make_inputs(jax.random.key(7), B, Hq, Hkv, S, D)
    lens = jnp.array([S, 300], jnp.int32)
    # row 0: window [352, 512) — shard 2 partial, shard 3 live
    # row 1: window [140, 300) — shard 0 wholly outside, 1 partial,
    #        2 partial-by-length, 3 wholly past the length

    g = Hq // Hkv
    logits = jnp.einsum("bhgd,bhsd->bhgs",
                        q.reshape(B, Hkv, g, D), k) / np.sqrt(D)
    pos = jnp.arange(S)[None, :]
    valid = (pos < lens[:, None]) & (pos >= lens[:, None] - w)
    logits = jnp.where(valid[:, None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    want = jnp.einsum("bhgs,bhsd->bhgd", p, v).reshape(B, Hq, D)

    ctx = create_sp_decode_context(mesh, axis="sp", block_s=128, impl=impl,
                                   interpret=(impl == "pallas"), window=w)
    sh = NamedSharding(mesh, P(None, None, "sp"))
    out = sp_gqa_decode(q, jax.device_put(k, sh), jax.device_put(v, sh),
                        lens, ctx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)

    # int8 cache through the layer (window + SP + quantized combine)
    layer = SpGQAFlashDecodeAttention(mesh, axis="sp", impl=impl,
                                      interpret=(impl == "pallas"),
                                      kv_dtype=jnp.int8, window=w)
    kc, vc = layer.init_cache(B, Hkv, S, D, dtype=jnp.float32,
                              k_init=k, v_init=v)
    out_i8 = layer(q, kc, vc, lens)
    np.testing.assert_allclose(np.asarray(out_i8), np.asarray(want),
                               rtol=2e-2, atol=2e-2)

    # paged pools (window + SP + block_table)
    layer_p = SpGQAFlashDecodeAttention(mesh, axis="sp", impl=impl,
                                        interpret=(impl == "pallas"),
                                        window=w)
    pk, pv, table = layer_p.init_paged_cache(B, Hkv, 128, S // 128, D,
                                             dtype=jnp.float32)
    # fill pools through the table layout: logical page i of batch b
    for b in range(B):
        for i in range(S // 128):
            row = int(table[b, i])
            pk = pk.at[row].set(k[b, :, i * 128:(i + 1) * 128])
            pv = pv.at[row].set(v[b, :, i * 128:(i + 1) * 128])
    out_pg = layer_p(q, jax.device_put(pk, layer_p.pool_sharding()),
                     jax.device_put(pv, layer_p.pool_sharding()),
                     lens, block_table=table)
    np.testing.assert_allclose(np.asarray(out_pg), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_sliding_window_decode(key):
    """Window decode across bf16/int8/paged variants vs a directly
    windowed dense oracle (query at llen-1 sees the last `window` keys;
    chunks wholly outside the window are skipped)."""
    from triton_dist_tpu.kernels.flash_decode import (
        gqa_decode_paged_shard,
        quantize_kv,
    )

    B, Hq, Hkv, D, S, w = 2, 2, 1, 128, 512, 160
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, Hq, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Hkv, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Hkv, S, D), jnp.float32)
    lens = jnp.array([S, S - 100], jnp.int32)

    g = Hq // Hkv
    logits = jnp.einsum("bhgd,bhsd->bhgs",
                        q.reshape(B, Hkv, g, D), k) / np.sqrt(D)
    pos = jnp.arange(S)[None, :]
    valid = (pos < lens[:, None]) & (pos >= lens[:, None] - w)
    logits = jnp.where(valid[:, None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    want = jnp.einsum("bhgs,bhsd->bhgd", p, v).reshape(B, Hq, D)

    out, _ = gqa_decode_shard(q, k, v, lens, impl="pallas",
                              interpret=True, window=w, block_s=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    kq8, ksc = quantize_kv(k)
    vq8, vsc = quantize_kv(v)
    out_i8, _ = gqa_decode_shard(q, kq8, vq8, lens, impl="pallas",
                                 interpret=True, k_scale=ksc,
                                 v_scale=vsc, window=w)
    np.testing.assert_allclose(np.asarray(out_i8), np.asarray(want),
                               rtol=2e-2, atol=2e-2)
    page = 128
    n = S // page
    pool_k = (k.reshape(B, Hkv, n, page, D).transpose(0, 2, 1, 3, 4)
              .reshape(B * n, Hkv, page, D))
    pool_v = (v.reshape(B, Hkv, n, page, D).transpose(0, 2, 1, 3, 4)
              .reshape(B * n, Hkv, page, D))
    table = jnp.arange(B * n, dtype=jnp.int32).reshape(B, n)
    out_p, _ = gqa_decode_paged_shard(q, pool_k, pool_v, table, lens,
                                      impl="pallas", interpret=True,
                                      window=w)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # the xla fallback agrees
    out_x, _ = gqa_decode_shard(q, k, v, lens, impl="xla", window=w)
    np.testing.assert_allclose(np.asarray(out_x), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_multitoken_decode(impl, key):
    """r5 q_lens verify decode: T query tokens ride the kernel as T*G
    block rows; per-request q_lens marks dead padding rows (lse=NEG).
    Oracle: dense attention with the per-token causal rule
    pos < end - (q_lens-1-t), with and without window+cap."""
    B, T, Hq, Hkv, D, S = 2, 4, 4, 2, 128, 512
    ks = jax.random.split(jax.random.key(11), 3)
    q = jax.random.normal(ks[0], (B, T, Hq, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Hkv, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Hkv, S, D), jnp.float32)
    lens = jnp.array([S, 300], jnp.int32)
    qlens = jnp.array([4, 3], jnp.int32)
    g = Hq // Hkv

    def dense(window=0, cap=0.0):
        logits = jnp.einsum("bthgd,bhsd->bhtgs",
                            q.reshape(B, T, Hkv, g, D), k) / np.sqrt(D)
        if cap:
            logits = cap * jnp.tanh(logits / cap)
        pos = jnp.arange(S)[None, None, :]
        d = qlens[:, None] - 1 - jnp.arange(T)[None, :]
        valid = ((pos < lens[:, None, None]) & (d[..., None] >= 0)
                 & (pos < (lens[:, None] - d)[..., None]))
        if window:
            valid = valid & (pos >= (lens[:, None] - d)[..., None] - window)
        logits = jnp.where(valid[:, None, :, None, :], logits, -1e30)
        p = jnp.where(valid[:, None, :, None, :],
                      jax.nn.softmax(logits, axis=-1), 0.0)
        return jnp.einsum("bhtgs,bhsd->bthgd", p, v).reshape(B, T, Hq, D)

    live = (jnp.arange(T)[None, :] < qlens[:, None])[..., None, None]
    for win, cap in [(0, 0.0), (160, 5.0)]:
        want = dense(win, cap)
        out, lse = gqa_decode_shard(q, k, v, lens, impl=impl,
                                    interpret=(impl == "pallas"),
                                    q_lens=qlens, window=win,
                                    soft_cap=cap, block_s=128)
        np.testing.assert_allclose(np.asarray(out * live),
                                   np.asarray(want * live),
                                   atol=2e-5, rtol=2e-5)
        assert bool(jnp.all(lse[1, 3] < -1e29)), "dead row lse must be NEG"
    # int8 cache twin
    from triton_dist_tpu.kernels.flash_decode import quantize_kv
    kq8, ksc = quantize_kv(k)
    vq8, vsc = quantize_kv(v)
    out_i8, _ = gqa_decode_shard(q, kq8, vq8, lens, impl=impl,
                                 interpret=(impl == "pallas"),
                                 k_scale=ksc, v_scale=vsc, q_lens=qlens)
    np.testing.assert_allclose(np.asarray(out_i8 * live),
                               np.asarray(dense() * live),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_multitoken_sp_decode(impl, key):
    """Multi-token verify over a SHARDED cache (world 4): the T queries'
    partials combine per (b, t) like a B*T decode batch."""
    W = 4
    mesh = Mesh(np.array(jax.devices()[:W]), ("sp",))
    B, T, Hq, Hkv, D = 2, 4, 4, 2, 128
    S = W * 128
    ks = jax.random.split(jax.random.key(12), 3)
    q = jax.random.normal(ks[0], (B, T, Hq, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Hkv, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Hkv, S, D), jnp.float32)
    lens = jnp.array([S, 300], jnp.int32)
    g = Hq // Hkv

    logits = jnp.einsum("bthgd,bhsd->bhtgs",
                        q.reshape(B, T, Hkv, g, D), k) / np.sqrt(D)
    pos = jnp.arange(S)[None, None, :]
    d = T - 1 - jnp.arange(T)[None, :]
    valid = (pos < (lens[:, None] - d)[..., None])
    logits = jnp.where(valid[:, None, :, None, :], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    want = jnp.einsum("bhtgs,bhsd->bthgd", p, v).reshape(B, T, Hq, D)

    import functools

    from triton_dist_tpu.kernels.flash_decode import sp_gqa_decode_shard
    from jax.sharding import PartitionSpec as P

    fn = jax.jit(jax.shard_map(
        functools.partial(sp_gqa_decode_shard, axis="sp", impl=impl,
                          interpret=(impl == "pallas")),
        mesh=mesh,
        in_specs=(P(), P(None, None, "sp"), P(None, None, "sp"), P()),
        out_specs=P(), check_vma=False))
    out = fn(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_speculative_verify_reaches_decode_kernel(key, monkeypatch):
    """The k-token verify chunk must ride the multi-token DECODE kernel
    (r5), not the padded prefill path: spy on gqa_decode_shard through
    the generate module."""
    import sys

    import triton_dist_tpu.models.generate  # noqa: F401
    from triton_dist_tpu.kernels import flash_decode as fd
    from triton_dist_tpu.models.generate import Generator
    from triton_dist_tpu.models.llama import LlamaConfig, init_params

    calls = {"n": 0, "T": None}
    real = fd.gqa_decode_shard

    def spy(q, *a, **kw):
        if q.ndim == 4:
            calls["n"] += 1
            calls["T"] = q.shape[1]
        return real(q, *a, **kw)

    monkeypatch.setattr(fd, "gqa_decode_shard", spy)
    cfg = LlamaConfig(vocab=64, dim=256, n_layers=2, n_heads=2,
                      n_kv_heads=1, ffn_dim=128, max_seq=256,
                      dtype=jnp.float32)
    params = init_params(cfg, key)
    mesh1 = Mesh(np.array(jax.devices()[:1]), ("sp",))
    gen = Generator(cfg, mesh1, max_seq=256, interpret=True)
    st = gen.prefill(params, jax.random.randint(key, (1, 64), 0, 64))
    chunk = jnp.zeros((1, 4), jnp.int32)  # a k=4 verify chunk
    gen._chunk_jit(params, chunk, st.caches, jnp.int32(64),
                   quantized=False, extent=128)
    assert calls["n"] > 0 and calls["T"] == 4, calls


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_qlens_dead_slot_single_token(impl):
    """q_lens with T == 1 marks dead batch slots (mixed batches where a
    request has no query this step): both impls must return out = 0 and
    lse = NEG for the dead row — the review-caught divergence."""
    B, Hq, Hkv, D, S = 2, 4, 2, 128, 256
    q, k, v = make_inputs(jax.random.key(13), B, Hq, Hkv, S, D)
    lens = jnp.array([S, S], jnp.int32)
    qlens = jnp.array([1, 0], jnp.int32)  # row 1 dead
    out, lse = gqa_decode_shard(q[:, None], k, v, lens, impl=impl,
                                interpret=(impl == "pallas"),
                                q_lens=qlens)
    ref = dense_reference(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(out[0, 0]), np.asarray(ref[0]),
                               rtol=2e-5, atol=2e-5)
    assert np.all(np.asarray(out[1]) == 0.0)
    assert np.all(np.asarray(lse[1]) < -1e29)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_multitoken_paged_decode(impl):
    """r5 symmetry: the k-token verify over a PAGED cache — q_lens
    raggedness through the block-table kernel, vs the dense oracle."""
    from triton_dist_tpu.kernels.flash_decode import gqa_decode_paged_shard

    B, T, Hq, Hkv, D, S = 2, 4, 4, 2, 128, 512
    ks = jax.random.split(jax.random.key(21), 3)
    q = jax.random.normal(ks[0], (B, T, Hq, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Hkv, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Hkv, S, D), jnp.float32)
    lens = jnp.array([S, 300], jnp.int32)
    qlens = jnp.array([4, 3], jnp.int32)
    g = Hq // Hkv

    logits = jnp.einsum("bthgd,bhsd->bhtgs",
                        q.reshape(B, T, Hkv, g, D), k) / np.sqrt(D)
    pos = jnp.arange(S)[None, None, :]
    d = qlens[:, None] - 1 - jnp.arange(T)[None, :]
    valid = ((pos < lens[:, None, None]) & (d[..., None] >= 0)
             & (pos < (lens[:, None] - d)[..., None]))
    logits = jnp.where(valid[:, None, :, None, :], logits, -1e30)
    p = jnp.where(valid[:, None, :, None, :],
                  jax.nn.softmax(logits, axis=-1), 0.0)
    want = jnp.einsum("bhtgs,bhsd->bthgd", p, v).reshape(B, T, Hq, D)

    page = 128
    n = S // page
    pool_k = (k.reshape(B, Hkv, n, page, D).transpose(0, 2, 1, 3, 4)
              .reshape(B * n, Hkv, page, D))
    pool_v = (v.reshape(B, Hkv, n, page, D).transpose(0, 2, 1, 3, 4)
              .reshape(B * n, Hkv, page, D))
    table = jnp.arange(B * n, dtype=jnp.int32).reshape(B, n)
    out, lse = gqa_decode_paged_shard(q, pool_k, pool_v, table, lens,
                                      impl=impl,
                                      interpret=(impl == "pallas"),
                                      q_lens=qlens)
    live = (jnp.arange(T)[None, :] < qlens[:, None])[..., None, None]
    np.testing.assert_allclose(np.asarray(out * live),
                               np.asarray(want * live),
                               atol=2e-5, rtol=2e-5)
    assert bool(jnp.all(lse[1, 3] < -1e29)), "dead row lse must be NEG"
