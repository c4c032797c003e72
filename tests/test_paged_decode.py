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


def _paged_case(key, hkv, n_tok, lens=_LENS, width=_NP):
    B, g, D = len(lens), 4, 128
    N = B * width + 1
    ks = jax.random.split(key, 3)
    shape = (B, hkv * g, D) if n_tok == 1 else (B, n_tok, hkv * g, D)
    q = jax.random.normal(ks[0], shape, jnp.float32)
    k_pool = jax.random.normal(ks[1], (N, hkv, _PAGE, D), jnp.float32)
    v_pool = jax.random.normal(ks[2], (N, hkv, _PAGE, D), jnp.float32)
    rng = np.random.default_rng(3)
    table = np.zeros((B, width), np.int32)    # dead entries: the null block
    free = list(rng.permutation(np.arange(1, N)))
    for b, n in enumerate(lens):
        for i in range(-(-n // _PAGE)):
            table[b, i] = free.pop()
    return q, k_pool, v_pool, jnp.asarray(table), jnp.asarray(lens, jnp.int32)


def _assert_matches_xla(q, k_pool, v_pool, table, lens, *, scale=None, **kw):
    """``impl="pallas"`` (interpreter) against ``_local_decode_xla`` over
    the gathered view, on ``out`` and ``lse``; returns the kernel's."""
    scale = 1.0 / np.sqrt(q.shape[-1]) if scale is None else scale
    got_o, got_l = fd.gqa_decode_paged_shard(
        q, k_pool, v_pool, table, lens, impl="pallas", interpret=True,
        scale=scale, **kw)
    want_o, want_l = fd._local_decode_xla(
        q, fd._paged_gather(k_pool, table), fd._paged_gather(v_pool, table),
        lens, scale=scale, **kw)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_l), np.asarray(want_l),
                               rtol=2e-5, atol=2e-5)
    return got_o, got_l


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
    got_o, got_l = _assert_matches_xla(q, k_pool, v_pool, table, lens,
                                       window=window, q_lens=q_lens)
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
    of the local Hkv of which a ring of TWO K+V slots fits the stated
    budget — what it was before the ring grew (ISSUE 48: a deeper ring
    never costs a head); when even one head does not fit,
    ``paged_kernel_gap`` names the reroute (never a Mosaic failure).  The
    ring's depth follows from the bytes of one slot, and the report counts
    the ring's bytes."""
    hh = fd.paged_heads_per_step(hkv, page, d, itemsize)
    blocking = fd.paged_kernel_blocking(hkv, page, d, itemsize, batch=32)
    gap = fd.paged_kernel_gap(page, d, itemsize)
    if want == 0:
        assert hh == 0 and "exceed 12 MiB VMEM" in gap
        assert blocking["heads_per_step"] == blocking["steps_per_call"] == 0
        assert blocking["pages_in_flight"] == blocking["vmem_bytes"] == 0
        return
    assert gap is None
    assert hh == want and hkv % hh == 0
    assert 4 * hh * page * d * itemsize <= fd.PAGED_VMEM_BUDGET
    # the LARGEST such divisor
    assert all(4 * h * page * d * itemsize > fd.PAGED_VMEM_BUDGET
               for h in range(hh + 1, hkv + 1) if hkv % h == 0)
    slot = 2 * hh * page * d * itemsize
    slots = fd.paged_pages_in_flight(hh, page, d, itemsize)
    # as many slots as hold the bytes in flight, never under two nor over
    # the budget
    assert slots == max(2, min(-(-fd.PAGED_BYTES_IN_FLIGHT // slot),
                               fd.PAGED_VMEM_BUDGET // slot))
    assert 2 <= slots and slots * slot <= fd.PAGED_VMEM_BUDGET
    assert blocking == {"heads_per_step": hh,
                        "steps_per_call": 32 * (hkv // hh),
                        "pages_per_step": "dynamic",
                        "pages_in_flight": slots,
                        "vmem_bytes": slots * slot}


@pytest.mark.parametrize("hkv,slots", [(2, 12), (4, 6), (8, 3), (10, 3),
                                       (30, 2)])
def test_ring_depth_at_the_cells_geometries(hkv, slots):
    """bf16, page 128, D 128 — the heads a page the cells run (mellum2's
    4, Mistral's / Laguna's 8, phi-4's 10 pairs, olmo's 30) and a TP-4
    rank's 2: every head of a page in one step as before; three slots
    where the chip read two as too few (8 and 10 heads), more where a slot
    is small, and the 30-head slot (1.9 MiB) stays at two."""
    assert fd.paged_heads_per_step(hkv, 128, 128, 2) == hkv
    assert fd.paged_pages_in_flight(hkv, 128, 128, 2) == slots


# ---------------------------------------------------------------------------
# ISSUE 48: a ring of page slots kept full across the seam between rows
# ---------------------------------------------------------------------------

import functools  # noqa: E402

from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

from triton_dist_tpu.language.interpret import maybe_interpret  # noqa: E402


def _two_slot_kernel(lens_ref, table_ref, q_ref, k_hbm, v_hbm, out_ref,
                     lse_ref, k_buf, v_buf, sem, acc_ref, m_ref, l_ref, *,
                     page, hh, n_pages, scale, soft_cap, window, n_tok,
                     use_qlens):
    """The walk the ring replaced, kept here as its oracle: two slots, one
    page ahead, every grid step starting its row cold.  Same helpers, same
    page order — so the ring must reproduce it bit for bit."""
    b = pl.program_id(0)
    h0 = pl.program_id(1) * hh
    llen, wlen, qlen = fd._read_lens(lens_ref, b, window=window,
                                     use_qlens=use_qlens)
    lo, hi = fd._live_pages(llen, wlen, qlen, page=page, n_pages=n_pages,
                            window=window, n_tok=n_tok)
    rows = q_ref.shape[2]

    def page_copies(i, slot):
        row = table_ref[b, i]
        return (pltpu.make_async_copy(k_hbm.at[row, pl.ds(h0, hh)],
                                      k_buf.at[slot], sem.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[row, pl.ds(h0, hh)],
                                      v_buf.at[slot], sem.at[1, slot]))

    @pl.when(lo < hi)
    def _():
        for c in page_copies(lo, 0):
            c.start()

    fd._softmax_state_init(acc_ref, m_ref, l_ref)

    def page_step(i, _):
        slot = jax.lax.rem(i - lo, 2)

        @pl.when(i + 1 < hi)
        def _():
            for c in page_copies(i + 1, 1 - slot):
                c.start()

        for c in page_copies(i, slot):
            c.wait()
        pos = i * page + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page), 1)
        valid = fd._chunk_valid(pos, llen, wlen, qlen, window=window,
                                group=rows // n_tok)
        fd._online_softmax_step(q_ref[0], k_buf[slot], v_buf[slot], valid,
                                acc_ref, m_ref, l_ref, scale=scale,
                                soft_cap=soft_cap)

    jax.lax.fori_loop(lo, hi, page_step, None)
    out_ref[0], lse_ref[0] = fd._softmax_state_emit(acc_ref, m_ref, l_ref)


def _two_slot_call(q, k_pool, v_pool, table, lens, *, hh, scale, soft_cap=0.0,
                   window=0, q_lens=None):
    """``_two_slot_kernel`` under the call ``gqa_decode_paged_shard``
    builds (interpreter), at ``hh`` heads a step."""
    multi = q.ndim == 4
    n_tok = q.shape[1] if multi else 1
    B, Hq, D = q.shape[0], q.shape[-2], q.shape[-1]
    _, Hkv, Pg, _ = k_pool.shape
    rows = n_tok * (Hq // Hkv)
    lens_arg, use_qlens = fd._pack_lens_arg(lens, None, q_lens, n_tok=n_tok,
                                            window=window)
    kern = functools.partial(_two_slot_kernel, page=Pg, hh=hh,
                             n_pages=table.shape[1], scale=scale,
                             soft_cap=soft_cap, window=window, n_tok=n_tok,
                             use_qlens=use_qlens)
    block = lambda w: pl.BlockSpec((1, hh, rows, w),  # noqa: E731
                                   lambda b, h, lens, tab: (b, h, 0, 0))
    out, lse = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, Hkv // hh),
            in_specs=[block(D), pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[block(D), block(128)],
            scratch_shapes=[pltpu.VMEM((2, hh, Pg, D), k_pool.dtype),
                            pltpu.VMEM((2, hh, Pg, D), v_pool.dtype),
                            pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.VMEM((hh, rows, D), jnp.float32),
                            pltpu.VMEM((hh, rows, 128), jnp.float32),
                            pltpu.VMEM((hh, rows, 128), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((B, Hkv, rows, D), jnp.float32),
                   jax.ShapeDtypeStruct((B, Hkv, rows, 128), jnp.float32)],
        interpret=maybe_interpret(True),
    )(lens_arg, table, fd._fold_q_rows(q, n_tok, Hkv), k_pool, v_pool)
    return fd._unfold_out(out, lse, multi, n_tok, Hq)


def _ring_case(key, pages, *, hkv=2, n_tok=1):
    """Rows of ``pages[b]`` live pages, the last one 7 tokens short (0
    pages: an empty row), over a permuted pool; table width 20."""
    return _paged_case(key, hkv, n_tok, width=20,
                       lens=[max(n * _PAGE - 7, 0) for n in pages])


def _ring_rows(slots):
    """No page, one, fewer than the ring's slots, exactly as many, many."""
    return [0, 1, 2, slots - 1, slots, slots + 1, 3 * slots]


def _set_ring(monkeypatch, slots, *, hkv=2, head_groups=1):
    """A ring of ``slots`` slots over float32 pools of ``hkv`` heads, a
    step carrying ``hkv // head_groups`` of them (more than one group: as
    under a budget that two slots of every head would not fit)."""
    hh = hkv // head_groups
    slot = 2 * hh * _PAGE * 128 * 4
    monkeypatch.setattr(fd, "PAGED_BYTES_IN_FLIGHT", slots * slot)
    if head_groups > 1:
        monkeypatch.setattr(fd, "paged_heads_per_step", lambda *a: hh)
    assert fd.paged_kernel_blocking(hkv, _PAGE, 128, 4, batch=1) == {
        "heads_per_step": hh, "steps_per_call": head_groups,
        "pages_per_step": "dynamic", "pages_in_flight": slots,
        "vmem_bytes": slots * slot}
    return hh


def _assert_ring(q, k_pool, v_pool, table, lens, *, hh, scale=None, **kw):
    """The ring against ``_local_decode_xla`` AND, bit for bit, against
    the two-slot cold-start walk."""
    scale = 1.0 / np.sqrt(q.shape[-1]) if scale is None else scale
    got_o, got_l = _assert_matches_xla(q, k_pool, v_pool, table, lens,
                                       scale=scale, **kw)
    ref_o, ref_l = _two_slot_call(q, k_pool, v_pool, table, lens, hh=hh,
                                  scale=scale, **kw)
    np.testing.assert_array_equal(np.asarray(got_o), np.asarray(ref_o))
    np.testing.assert_array_equal(np.asarray(got_l), np.asarray(ref_l))


_SEAM_CASES = ["up", "down", "dead_between", "dead_last", "qlens_dead",
               "window", "soft_cap", "pairs"]


@pytest.mark.parametrize("n_tok", [1, 5])
@pytest.mark.parametrize("case", _SEAM_CASES)
@pytest.mark.parametrize("head_groups", [1, 2])
@pytest.mark.parametrize("slots", [2, 3, 6])
def test_ring_matches_xla_and_the_two_slot_walk_bit_for_bit(
        key, monkeypatch, capfd, slots, head_groups, case, n_tok):
    """The page ring in the interpreter, on ``out`` and ``lse``, at three
    depths, with every head of a page in one step and with two steps a row
    (the seam then runs inside a row, ``(b, h)`` to ``(b, h + 1)``, and
    between rows): rows of 0, 1, 2, N - 1, N, N + 1 and 3N pages in one
    batch, shortest first and longest first; empty rows (``len == 0``)
    between live ones and as the last steps; rows with no live query
    (``q_lens == 0``) first, last and between; a window with ``lo > 0``;
    a soft cap; 64-wide heads stored in pairs; one token and five.  Every
    copy started is waited for: the interpreter names a semaphore left
    signalled when a kernel ends, and names none."""
    hkv = 2
    hh = _set_ring(monkeypatch, slots, hkv=hkv, head_groups=head_groups)
    rows = _ring_rows(slots)
    kw = {}
    if case == "down":
        rows = rows[::-1]
    elif case == "dead_between":
        rows = [0, 3 * slots, 0, 0, 1, slots + 1, 0, 2, 1]
    elif case == "dead_last":
        rows = [2, slots + 1, 0, slots, 0, 0]
    elif case == "window":
        kw["window"] = 200
    elif case == "soft_cap":
        kw["soft_cap"] = 30.0
    q, k_pool, v_pool, table, lens = _ring_case(key, rows, hkv=hkv,
                                                n_tok=n_tok)
    if case == "pairs":
        # 64-wide heads, two to a 128-lane pool row: the zero half of a
        # packed query multiplies the other head's keys away
        q = fd.pack_q_pairs(q[..., :64], 2 * hkv)
        kw["scale"] = 0.125
    if case == "qlens_dead":
        # dead rows first, last and between live ones
        dead = np.arange(len(rows)) % 3 == 0
        dead[-1] = True
        kw["q_lens"] = jnp.asarray(np.where(dead, 0, n_tok), jnp.int32)
    elif n_tok > 1:
        # every q_lens mode's lens layout; an empty row has no live query
        kw["q_lens"] = jnp.asarray(
            np.where(np.asarray(lens) > 0, n_tok, 0), jnp.int32)
    if n_tok > 1:
        # the verify contract: the T queries' K/V already sit in the cache
        lens = jnp.where(lens > 0, jnp.maximum(lens, n_tok), 0)
    _assert_ring(q, k_pool, v_pool, table, lens, hh=hh, **kw)
    assert "non-zero count" not in capfd.readouterr().out


def test_a_copy_never_waited_for_is_named_by_the_interpreter(capfd):
    """The control of the check above: a kernel that starts a copy and
    ends without waiting for it is what the interpreter prints about."""
    def leaky(x_hbm, o_ref, buf, sem):
        pltpu.make_async_copy(x_hbm, buf, sem).start()
        o_ref[...] = jnp.zeros_like(o_ref)

    pl.pallas_call(
        leaky, in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
        scratch_shapes=[pltpu.VMEM((8, 128), jnp.float32),
                        pltpu.SemaphoreType.DMA(())],
        interpret=maybe_interpret(True),
    )(jnp.ones((8, 128), jnp.float32)).block_until_ready()
    assert "non-zero count" in capfd.readouterr().out


@pytest.mark.parametrize("hkv", [2, 4, 8, 30])
def test_one_multiply_whatever_the_depth(hkv):
    """The kernel's jaxpr holds exactly ONE pair of ``dot_general`` (scores
    and values), one loop and one site that starts a K and a V copy, at
    every ring depth the cells run (12, 6, 3 and 2 slots): a deeper ring
    never unrolls the multiply, which is what a WARM start pays to trace
    and lower sixteen times a program (PERF.md §6, PR 48)."""
    q = jax.ShapeDtypeStruct((4, hkv * 4, 128), jnp.bfloat16)
    pool = jax.ShapeDtypeStruct((9, hkv, 128, 128), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(functools.partial(
        fd.gqa_decode_paged_shard, impl="pallas", window=512))(
        q, pool, pool, jax.ShapeDtypeStruct((4, 8), jnp.int32),
        jax.ShapeDtypeStruct((4,), jnp.int32))
    count = {}

    def walk(jp):
        for e in jp.eqns:
            count[e.primitive.name] = count.get(e.primitive.name, 0) + 1
            for sub in jax.core.jaxprs_in_params(e.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    assert count["dot_general"] == 2
    assert count["dma_start"] == count["dma_wait"] == 2
    assert count["while"] == 1
