"""Distributed GQA flash-decode — sequence-parallel attention over sharded KV.

Reference analog: ``python/triton_dist/kernels/nvidia/flash_decode.py`` — the
reference's long-context scaling story (SURVEY.md §5): each rank runs split-KV
flash-decode on its KV shard (:129-280), combines its own splits (:392-480),
then the ranks' partial (out, lse) pairs are allgathered and merged by an
LSE-weighted online-softmax combine (`kernel_inter_rank_gqa_fwd_batch_decode_
combine_kv`, :481-532).

TPU-native design (NOT a port):

* **Split-KV + intra-rank combine collapse into one kernel.**  The GPU
  version launches parallel KV splits and then a combine kernel because CUDA
  blocks run concurrently.  TPU Pallas grids are *sequential* per core, so
  the split dimension becomes the KV-chunk grid axis with an online-softmax
  accumulator carried in VMEM scratch across iterations — the Mosaic pipeline
  overlaps the next chunk's HBM→VMEM DMA with the current chunk's compute,
  which is exactly the latency-hiding the GPU gets from parallel splits
  (decode is HBM-bandwidth-bound; the MXU is never the bottleneck).
* **Inter-rank combine is comm-fused** (``sp_combine_shard``): each rank
  remote-DMAs its packed (out ⊕ lse) partial plane into every peer's VMEM
  (the ``dl.fcollect`` verb) and the LSE merge runs on the VPU in the SAME
  Pallas kernel — the reference's LL-gather + combine kernel pair in one
  launch.  Explicit ``impl="xla"`` (or a head_dim not lane-divisible)
  keeps the latency gather + fused XLA epilogue instead; note int8-KV
  under ``auto`` runs an XLA *local* decode but still the fused combine
  (the partials are f32 either way).
* The (out ⊕ lse) payload packing of the reference's decode layer
  (sp_flash_decode_layer.py:135-137) is kept in both paths: one plane/
  gather moves both.
* Per-batch KV lengths ride as **scalar-prefetch** arguments (SMEM), the
  Pallas analog of the reference's ``gqa_fwd_batch_decode`` kv_lens tensor.

Layout contract (shard level, inside shard_map over ``axis``):
  q:        [B, Hq, D]        replicated (decode queries are tiny)
  k/v:      [B, Hkv, S_loc, D] sequence-sharded KV cache (head-major so a
                               KV chunk is one contiguous DMA)
  kv_lens:  [B] int32          *global* sequence lengths
  out:      [B, Hq, D]
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

import triton_dist_tpu.language as dl
from triton_dist_tpu.kernels.gemm import (
    PallasShapeError,
    apply_soft_cap,
    resolve_impl,
    use_fallback,
)
from triton_dist_tpu.kernels.low_latency_allgather import (
    fast_allgather_shard,
    pack_payload,
    unpack_payload,
)
from triton_dist_tpu.language.interpret import maybe_interpret
from triton_dist_tpu.runtime.jit_cache import cached_shard_jit

NEG_INF = -1.0e30  # finite -inf proxy: survives exp/log without NaNs

from triton_dist_tpu.kernels.collective_ids import SP_DECODE as SP_DECODE_COLLECTIVE_ID


# ---------------------------------------------------------------------------
# Local shard kernel: online-softmax split-KV decode
# ---------------------------------------------------------------------------


def _read_lens(lens_ref, b, *, window, use_qlens):
    """Decode the lens prefetch operand (layout depends on the STATIC
    window/use_qlens flags):

    * plain decode — [B]: clipped local lens only;
    * windowed (r5 SP window) — [2, B]: + the UNCLIPPED local end
      position (kv_len - shard offset), whose last ``window`` rows are
      visible: the global window rule in shard coordinates;
    * q_lens mode (r5 multi-token verify, incl. T == 1 with dead batch
      slots) — [3, B]: + the per-batch live query count (q rows
      t >= qlen are dead padding).

    Returns (llen, wlen, qlen); qlen is None unless use_qlens.
    """
    if use_qlens:
        return lens_ref[0, b], lens_ref[1, b], lens_ref[2, b]
    if window:
        return lens_ref[0, b], lens_ref[1, b], None
    llen = lens_ref[b]
    return llen, llen, None


def _chunk_valid(pos, llen, wlen, qlen, *, window, group):
    """Visibility of cache position ``pos`` [R, bs] to decode-query row
    r = t * group + g (token t's query sits at global end - (qlen-1-t)):
    THE masking rule shared by the bf16/int8 kernels and the XLA
    fallback.  Without q_lens (qlen None) this degenerates to the
    classic decode rule; dead rows (t >= qlen) mask everything and
    surface lse = NEG_INF."""
    valid = pos < llen
    if qlen is not None:
        t = jax.lax.broadcasted_iota(jnp.int32, pos.shape, 0) // group
        d = qlen - 1 - t                       # distance from the last q
        valid = valid & (d >= 0) & (pos < wlen - d)
        if window:
            valid = valid & (pos >= wlen - d - window)
    elif window:
        valid = valid & (pos >= wlen - window)
    return valid


def _pack_lens_arg(local_lens, window_lens, q_lens, *, n_tok, window):
    """Build the lens prefetch operand — THE one place the [B]/[2,B]/
    [3,B] layout is encoded (``_read_lens`` is its reader); shared by the
    contiguous and paged wrappers so they can never desynchronize.
    Returns (lens_arg, use_qlens)."""
    wl = local_lens if window_lens is None else window_lens
    use_qlens = n_tok > 1 or q_lens is not None
    if use_qlens:
        ql = (jnp.full(local_lens.shape, n_tok, jnp.int32)
              if q_lens is None else q_lens.astype(jnp.int32))
        return jnp.stack([local_lens.astype(jnp.int32),
                          wl.astype(jnp.int32), ql]), True     # [3, B]
    if window:
        return jnp.stack([local_lens.astype(jnp.int32),
                          wl.astype(jnp.int32)]), False        # [2, B]
    return local_lens, False


def _fold_q_rows(q, n_tok, Hkv):
    """[B, (T,) Hq, D] → [B, Hkv, T*g, D], row r = t*g + head-group g —
    the kernel's q-block layout (its inverse is :func:`_unfold_out`)."""
    B, Hq, D = q.shape[0], q.shape[-2], q.shape[-1]
    g = Hq // Hkv
    if q.ndim == 4:
        return (q.reshape(B, n_tok, Hkv, g, D).transpose(0, 2, 1, 3, 4)
                .reshape(B, Hkv, n_tok * g, D))
    return q.reshape(B, Hkv, g, D)


def _unfold_out(out, lse, multi, n_tok, Hq):
    """Kernel outputs [B, Hkv, T*g, D] / [B, Hkv, T*g, 128] → the public
    (out, lse) shapes ([B, T, Hq, D]/[B, T, Hq] when multi)."""
    B, Hkv = out.shape[0], out.shape[1]
    D = out.shape[-1]
    g = Hq // Hkv
    if multi:
        o = (out.reshape(B, Hkv, n_tok, g, D).transpose(0, 2, 1, 3, 4)
             .reshape(B, n_tok, Hq, D))
        s = (lse[..., 0].reshape(B, Hkv, n_tok, g)
             .transpose(0, 2, 1, 3).reshape(B, n_tok, Hq))
        return o, s
    return out.reshape(B, Hq, D), lse[..., 0].reshape(B, Hq)


def _online_softmax_step(q, k, v, valid, acc_ref, m_ref, l_ref, *, scale,
                         soft_cap):
    """Fold one KV block into the online-softmax state: THE block update
    of the contiguous and the paged bf16 kernels.  q [R, D] with k/v
    [bs, D], or all three under ONE leading head axis (q [Hh, R, D], k/v
    [Hh, bs, D]: a matmul batched over the heads of a page); ``valid``
    [R, bs] is shared by the heads; the state refs are shaped like
    q / ``[..., R, 128]``.

    K/V stay in their storage dtype: the MXU multiplies bf16 natively
    with f32 accumulation, and skipping the per-chunk [bs, D] VPU casts
    is worth ~10% at S=8192 (the cast traffic used to rival the exp
    math).  P is cast DOWN to the V dtype for the PV matmul — the
    standard flash-attention practice, and what keeps both matmuls on
    the MXU's double-rate path."""
    heads = tuple(range(q.ndim - 2))
    r = q.ndim - 1                                   # the D / bs axis
    logits = jax.lax.dot_general(
        q, k, (((r,), (r,)), (heads, heads)),
        preferred_element_type=jnp.float32) * scale            # [.., R, bs]
    logits = apply_soft_cap(logits, soft_cap)
    logits = jnp.where(valid, logits, NEG_INF)

    m_cur = m_ref[...]                                          # [.., R, 128]
    row_max = jnp.max(logits, axis=-1, keepdims=True)           # [.., R, 1]
    m_new = jnp.maximum(m_cur, row_max)                         # [.., R, 128]
    alpha = jnp.exp(m_cur[..., :1] - m_new[..., :1])            # [.., R, 1]
    p = jnp.where(valid, jnp.exp(logits - m_new[..., :1]), 0.0)
    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((r,), (r - 1,)), (heads, heads)),
        preferred_element_type=jnp.float32)


def _softmax_state_init(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def _softmax_state_emit(acc_ref, m_ref, l_ref):
    """(out [.., R, D], lse [.., R, 128]) of the accumulated state.  Rows
    that saw no key (a shard wholly past kv_len, a dead q row, an empty
    batch slot) give out = 0 and lse = NEG_INF, which the inter-rank
    combine ignores.  lse rides a full-lane buffer (every lane the same
    value): Mosaic requires output block lane dims of 128 or the full
    array dim."""
    l = l_ref[...]                                              # [.., R, 128]
    nonempty = l > 0.0
    out = jnp.where(nonempty[..., :1], acc_ref[...] / jnp.where(
        nonempty[..., :1], l[..., :1], 1.0), 0.0)
    lse = jnp.where(nonempty,
                    m_ref[...] + jnp.log(jnp.where(nonempty, l, 1.0)),
                    NEG_INF)
    return out, lse


def _decode_kernel(lens_ref, q_ref, k_ref, v_ref, out_ref, lse_ref,
                   acc_ref, m_ref, l_ref, *, block_s, n_s, scale,
                   soft_cap=0.0, window=0, n_tok=1, use_qlens=False):
    """Grid (B, Hkv, n_s); one (batch, kv-head) pair accumulates across the
    sequential KV-chunk axis.

    Reference analog: ``kernel_gqa_fwd_batch_decode_split_kv``
    (flash_decode.py:129-280) — the Triton version parallelizes over splits
    and re-merges; here the s axis is sequential so the merge is the loop.

    ``n_tok`` > 1 (r5): the q block carries T tokens' queries as
    R = T * G rows (reference analog: the ``q_lens`` batch-verify entry,
    flash_decode.py:763,847) — mixed speculative-verify/decode batches
    ride ONE kernel with the causal rule ``pos < wlen - (qlen-1-t)``.
    """
    b = pl.program_id(0)
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _():
        _softmax_state_init(acc_ref, m_ref, l_ref)

    llen, wlen, qlen = _read_lens(lens_ref, b, window=window,
                                  use_qlens=use_qlens)

    # Chunks entirely past the valid length — or, with a sliding window,
    # entirely before it — are compute-skipped (their DMAs still stream
    # in; the pipeline cannot be shortened data-dependently).  The window
    # tail bound is conservative for multi-token (earliest query's
    # window reaches back n_tok-1 more rows).
    live = s * block_s < llen
    if window:
        live = live & ((s + 1) * block_s > wlen - (n_tok - 1) - window)

    @pl.when(live)
    def _():
        q = q_ref[0, 0]                              # [R, D], R = n_tok*G
        pos = s * block_s + jax.lax.broadcasted_iota(
            jnp.int32, (q.shape[0], block_s), 1)
        valid = _chunk_valid(pos, llen, wlen, qlen, window=window,
                             group=q.shape[0] // n_tok)
        _online_softmax_step(q, k_ref[0, 0], v_ref[0, 0], valid, acc_ref,
                             m_ref, l_ref, scale=scale, soft_cap=soft_cap)

    @pl.when(s == n_s - 1)
    def _():
        out_ref[0, 0], lse_ref[0, 0] = _softmax_state_emit(acc_ref, m_ref,
                                                           l_ref)


def _decode_kernel_i8(lens_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                      out_ref, lse_ref, acc_ref, m_ref, l_ref,
                      *, block_s, n_s, scale, soft_cap=0.0, window=0,
                      n_tok=1, use_qlens=False):
    """int8-KV twin of :func:`_decode_kernel` (VERDICT r3 #5): the cache
    streams from HBM as int8 (half the bytes — decode is bandwidth-bound,
    so that is the whole win) with per-position f32 scales riding as two
    extra [B, Hkv, S] prefetch planes.  Dequant fuses into the chunk
    loop: K's scale applies AFTER the QK matmul (a column rescale of the
    logits), V's scale folds into P BEFORE the PV matmul — both matmuls
    stay on the MXU in bf16 (int8 values cast exactly), no f32 cast
    traffic.  Reference bar: its decode kernel IS the serving path
    (flash_decode.py:129-280).
    """
    b = pl.program_id(0)
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _():
        _softmax_state_init(acc_ref, m_ref, l_ref)

    llen, wlen, qlen = _read_lens(lens_ref, b, window=window,
                                  use_qlens=use_qlens)
    live = s * block_s < llen
    if window:
        live = live & ((s + 1) * block_s > wlen - (n_tok - 1) - window)

    @pl.when(live)
    def _():
        q = q_ref[0, 0]                          # [R, D] bf16/f32, R=n_tok*G
        k = k_ref[0, 0].astype(q.dtype)                  # [bs, D] i8→q dtype
        # Scales ride LANE-PACKED [B, Hkv, S//128, 128] (row r, lane l =
        # position r*128+l): each chunk's bs scales are ONE dense
        # [bs//128, 128] f32 transfer.  A [bs, 1] layout instead DMAs
        # thousands of 4-byte strided rows per chunk and ran 9x slower
        # than XLA on hardware (r4 measurement).
        ksc = ks_ref[0, 0].reshape(-1)                   # [bs] f32
        vsc = vs_ref[0, 0].reshape(-1)                   # [bs] f32

        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        logits = logits * (ksc[None, :] * scale)         # [G, bs]
        logits = apply_soft_cap(logits, soft_cap)
        pos = s * block_s + jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, 1)
        valid = _chunk_valid(pos, llen, wlen, qlen, window=window,
                             group=q.shape[0] // n_tok)
        logits = jnp.where(valid, logits, NEG_INF)

        m_cur = m_ref[:]
        row_max = jnp.max(logits, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_cur, row_max)
        alpha = jnp.exp(m_cur[:, :1] - m_new[:, :1])
        p = jnp.where(valid, jnp.exp(logits - m_new[:, :1]), 0.0)
        m_ref[:] = m_new
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0, 0].astype(q.dtype)                  # [bs, D]
        pv = (p * vsc[None, :]).astype(q.dtype)          # fold V's scale
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            pv, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(s == n_s - 1)
    def _():
        out_ref[0, 0], lse_ref[0, 0] = _softmax_state_emit(acc_ref, m_ref,
                                                           l_ref)


def _local_decode_xla(q, k, v, local_lens, *, scale, k_scale=None,
                      v_scale=None, soft_cap=0.0, window=0,
                      window_lens=None, q_lens=None):
    """Dense fallback for ragged shapes / non-TPU (reference analog: the
    non-TMA dispatch path).  Same (out, lse) contract as the Pallas kernel.

    ``k_scale``/``v_scale`` [B, Hkv, S] dequantize an int8 KV cache
    (kernels-level int8-KV support; see layers/sp_flash_decode.py).  The
    scale applies *after* the QK matmul / *before* the PV matmul, so XLA
    streams the cache from HBM as int8 — decode is bandwidth-bound, and
    halving the cache bytes is the point.

    ``q`` may be [B, Hq, D] (one decode token) or [B, T, Hq, D]
    (multi-token verify; ``q_lens`` [B] live query counts, default T).
    """
    multi = q.ndim == 4
    if not multi:
        q = q[:, None]                                 # T = 1
    B, T, Hq, D = q.shape
    _, Hkv, S, _ = k.shape
    g = Hq // Hkv
    qf = q.astype(jnp.float32).reshape(B, T, Hkv, g, D)
    logits = jnp.einsum("bthgd,bhsd->bhtgs", qf,
                        k.astype(jnp.float32)) * scale
    if k_scale is not None:
        logits = logits * k_scale[:, :, None, None, :]
    logits = apply_soft_cap(logits, soft_cap)
    wl = local_lens if window_lens is None else window_lens
    ql = (jnp.full((B,), T, jnp.int32) if q_lens is None
          else q_lens.astype(jnp.int32))
    pos = jnp.arange(S)[None, None, :]                          # [1, 1, S]
    d = ql[:, None] - 1 - jnp.arange(T)[None, :]                # [B, T]
    valid = ((pos < local_lens[:, None, None])
             & (d[..., None] >= 0)
             & (pos < (wl[:, None] - d)[..., None]))            # [B, T, S]
    if window:
        valid = valid & (pos >= (wl[:, None] - d)[..., None] - window)
    logits = jnp.where(valid[:, None, :, None, :], logits, NEG_INF)
    m = jnp.max(logits, axis=-1)                             # [B,Hkv,T,g]
    # All-masked rows: keep everything finite, flag via lse = NEG_INF.
    nonempty = m > NEG_INF / 2
    p = jnp.where(valid[:, None, :, None, :],
                  jnp.exp(logits - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    pv = p if v_scale is None else p * v_scale[:, :, None, None, :]
    out = jnp.einsum("bhtgs,bhsd->bthgd", pv, v.astype(jnp.float32))
    out = jnp.where(nonempty.transpose(0, 2, 1, 3)[..., None],
                    out / jnp.where(nonempty, l, 1.0)
                    .transpose(0, 2, 1, 3)[..., None], 0.0)
    lse = jnp.where(nonempty, m + jnp.log(jnp.where(nonempty, l, 1.0)),
                    NEG_INF).transpose(0, 2, 1, 3)              # [B,T,Hkv,g]
    out = out.reshape(B, T, Hq, D)
    lse = lse.reshape(B, T, Hq)
    if not multi:
        return out[:, 0], lse[:, 0]
    return out, lse


def _register_aot():
    """AOT export spaces for the decode kernels.

    Reference: ``scripts/aot_kernels.txt`` lists 5 flash-decode kernels as
    the AOT surface; signatures/algo-infos live in the
    ``@aot_compile_spaces`` tables (flash_decode.py:534-585).  Shapes below
    are the decode-serving points the reference tests use (GQA 32/4,
    head_dim 128).
    """
    from triton_dist_tpu.tools.compile_aot import aot_compile_spaces

    b, hq, hkv, d, s = 4, 32, 4, 128, 4096
    sig = [
        [((b, hq, d), "bfloat16"), ((b, hkv, s, d), "bfloat16"),
         ((b, hkv, s, d), "bfloat16"), ((b,), "int32")],
        [((b, hq, d), "float32"), ((b, hkv, s, d), "float32"),
         ((b, hkv, s, d), "float32"), ((b,), "int32")],
    ]
    # The pallas split-KV variants can only be exported for a platform
    # that can lower them (TPU; the CPU backend lowers pallas_call in
    # interpret mode only).  Resolved at
    # export time from the target platforms: registration runs at import,
    # which must never initialize the JAX backend (a ``jax.devices()``
    # probe here would break a later ``jax.distributed.initialize``).
    def algos(platforms):
        out = [{"impl": "xla"}]
        if "tpu" in platforms:
            out += [{"block_s": 2048, "impl": "pallas"},
                    {"block_s": 1024, "impl": "pallas"}]
        return out

    return aot_compile_spaces({
        "gqa_decode": {
            "signature": sig,
            "algo_infos": algos,
        },
    })


def quantize_kv(x):
    """[..., S, D] float → ([..., S, D] int8, [..., S] f32 scales):
    symmetric per-position row quant (the standard int8-KV layout; shares
    the one recipe in kernels/quant.py)."""
    from triton_dist_tpu.kernels.quant import symmetric_quantize

    return symmetric_quantize(x, -1)


# What the legality messages say of a 64-wide head since heads ride in pairs.
_PAIRS_NOTE = (" (a 64-wide head reaches the kernel stored in PAIRS, as "
               "128-lane rows: pack_kv_pairs / pack_q_pairs)")


def pack_kv_pairs(x):
    """K or V rows of 64-wide heads, two heads a 128-lane row: ``[..., Hkv,
    64] -> [..., Hkv / 2, 128]`` (a reshape: heads ``2p`` and ``2p + 1``
    side by side).  The cache then holds what the heads hold — no lane of
    it is padding — and every 128-wide kernel of this file and of
    ``flash_attention.py`` reads it unchanged."""
    *lead, hkv, d = x.shape
    assert d == 64 and hkv % 2 == 0, (hkv, d)
    return x.reshape(*lead, hkv // 2, 128)


def pack_q_pairs(q, n_kv_heads: int):
    """Queries of 64-wide heads for a cache stored by :func:`pack_kv_pairs`:
    ``[..., Hq, 64] -> [..., Hq, 128]``, each zero-padded into the half its
    KV head lives in (``[q | 0]`` for an even KV head, ``[0 | q]`` for an odd
    one), so that GQA ``Hq`` on ``Hkv / 2`` at width 128 scores ``q . k``
    of its own head exactly (the other half multiplies zeros) and
    :func:`unpack_out_pairs` keeps the matching half of the result.  The
    scores' scale stays ``1 / sqrt(64)``: pass ``scale=0.125`` to the call.
    Twice the MXU work of calls that are bound by the cache's bytes."""
    *lead, hq, d = q.shape
    assert d == 64 and hq % n_kv_heads == 0, (hq, n_kv_heads, d)
    odd = (jnp.arange(hq) // (hq // n_kv_heads)) % 2 == 1       # [Hq]
    zero = jnp.zeros_like(q)
    return jnp.where(odd[:, None], jnp.concatenate([zero, q], -1),
                     jnp.concatenate([q, zero], -1))


def unpack_out_pairs(o, n_kv_heads: int):
    """The half of each 128-wide result that is the head's own:
    ``[..., Hq, 128] -> [..., Hq, 64]``."""
    hq = o.shape[-2]
    odd = (jnp.arange(hq) // (hq // n_kv_heads)) % 2 == 1
    return jnp.where(odd[:, None], o[..., 64:], o[..., :64])


def decode_kernel_gap(s: int, head_dim: int) -> str | None:
    """Why :func:`gqa_decode_shard` would take its XLA path over a cache
    of ``s`` rows (``None``: the split-KV kernel tiles it).  The ONE copy
    of the guard — the dispatcher below and the serving engine's
    construction-time kernel-reach report both read it."""
    if head_dim % 128 or s % 128:
        return (f"(D={head_dim}, S={s}) needs D%128 == S%128 == 0"
                f"{_PAIRS_NOTE if head_dim == 64 else ''}")
    return None


@_register_aot()
def gqa_decode_shard(q, k, v, local_lens, *, block_s=None, impl="auto",
                     interpret=False, k_scale=None, v_scale=None,
                     soft_cap=0.0, window=0, window_lens=None,
                     q_lens=None, scale=None):
    """Single-shard GQA decode: q [B, Hq, D], k/v [B, Hkv, S_loc, D],
    local_lens [B] (valid rows in this shard).  Returns float32 partials
    (out [B, Hq, D], lse [B, Hq]).

    MULTI-TOKEN (r5): q may be [B, T, Hq, D] — T query tokens per request
    whose K/V already sit in the cache at the last T valid positions
    (speculative verify / mixed decode-verify batches; reference analog:
    the per-request ``q_lens`` of its decode entry, flash_decode.py:763,
    847).  ``q_lens`` [B] (optional, <= T, default T) gives each
    request's LIVE query count: rows t >= q_lens[b] are padding and
    return lse = NEG_INF.  Query t of request b sits at global position
    ``end_b - (q_lens[b] - t)`` where end_b is the cache length.
    Returns (out [B, T, Hq, D], lse [B, T, Hq]).  The queries ride the
    kernel as T*G extra block rows — decode stays HBM-bound, so a
    k-token verify costs ~the same cache stream as one decode step
    (vs the chunked-prefill verify's 128-row padded q blocks).

    Reference analog: ``gqa_fwd_batch_decode_intra_rank``
    (flash_decode.py:763-860) minus the separate combine launch.

    ``window`` (sliding-window attention, Mistral-style): only the last
    ``window`` keys are visible to the decode query; chunks wholly
    outside the window are compute-skipped.  ``window_lens`` [B] gives
    the UNCLIPPED local end position (kv_len - shard offset) so an SP
    caller evaluates the GLOBAL window in shard coordinates (rows
    >= window_lens - window are visible; default: local_lens — the
    world-1 rule).  A shard wholly outside the window reports
    lse = NEG_INF partials, which the inter-rank combine ignores.

    ``impl`` note: decode is HBM-bandwidth-bound (stream the KV cache
    once).  Since round 2's kernel tuning (K/V fed to the MXU in their
    storage dtype, P cast down for the PV matmul, parallel (b, h)
    dimension semantics) the Pallas split-KV kernel matches-or-beats XLA's
    fused attention at the serving shapes (measured table and protocol:
    docs/perf.md "GQA flash decode"), so ``auto`` selects the Pallas
    kernel whenever the shapes allow it — including, since round 4,
    int8-KV caches: the fused int8 split-KV kernel (dequant in the chunk
    loop, lane-packed scale planes) reads 168 µs vs XLA's ~200 at the
    serving shape.  ``impl='xla'`` keeps the XLA program (dequant fused
    into the attention stream).
    """
    multi = q.ndim == 4
    n_tok = q.shape[1] if multi else 1
    B, Hq, D = q.shape[0], q.shape[-2], q.shape[-1]
    _, Hkv, S, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    g = Hq // Hkv
    if scale is None:       # (64-wide heads in pairs: pack_q_pairs)
        scale = 1.0 / math.sqrt(D)
    raw_impl = impl
    impl = resolve_impl(impl, interpret)

    gap = decode_kernel_gap(S, D)
    quantized = k_scale is not None
    if use_fallback(raw_impl, impl, gap is None, "flash_decode",
                    gap or "") or (
            quantized and impl != "pallas"):
        # int8-KV under resolved-XLA dispatch: dequant fuses into the XLA
        # attention stream.  ``impl='pallas'`` (explicit OR auto-on-TPU)
        # runs the fused int8 split-KV kernel below (r4; it was an XLA
        # reroute before the kernel existed).
        return _local_decode_xla(q, k, v, local_lens, scale=scale,
                                 k_scale=k_scale, v_scale=v_scale,
                                 soft_cap=soft_cap, window=window,
                                 window_lens=window_lens, q_lens=q_lens)

    defaulted = block_s is None
    if defaulted:
        # Full-shard default, both dtypes (real-chip sweeps, docs/perf.md):
        # fewer online-softmax chunk boundaries and one long MXU stream
        # put the kernel at the HBM floor — int8 168 µs vs 208 at bs=2048;
        # bf16 B=8 ~285-319 µs vs ~354-361 at bs=2048 across two sessions
        # (B=32 is a wash — the r4 re-sweep that retired the old 2048
        # bf16 default).  VMEM fit-shrink below handles large D.
        block_s = min(S, 8192)
    bs = block_s
    while S % bs:
        bs //= 2
    bs = max(bs, 128)
    if quantized and (bs // 128) % 8 and bs != S:
        # Lane-packed scale planes (below) need the (1, 1, bs//128, 128)
        # block's sublane dim bs//128 to be %8 — or the block to span
        # all of S.  Bump to the smallest DIVISOR of S that satisfies
        # it (a non-divisor bs would truncate n_s = S//bs and silently
        # drop the cache tail — e.g. S=1152 with a flat min(S, 1024)
        # bump attended only the first 1024 positions), falling back to
        # bs = S when no such divisor exists (S/128 with no multiple-
        # of-8 factor).  Any legal divisor is >= 1024, the int8
        # kernel's measured sweet spot anyway (docs/perf.md).
        bs = next((c for c in range(bs, S, 128)
                   if S % c == 0 and (c // 128) % 8 == 0), S)
    # Double-buffered K+V blocks: 4 * bs * D * itemsize must fit VMEM.
    # Only a DEFAULTED block shrinks for PERF reasons; an explicit
    # block_s that does not fit keeps its loud failure (a sweep must
    # never report a block size the kernel didn't run for tuning
    # reasons).  The LEGALITY normalizations above (divisor halving,
    # int8 scale-plane snap-up) still apply to explicit values — they
    # are documented contracts, not silent tuning.
    vmem_budget = 12 * 2 ** 20
    itemsize = jnp.dtype(k.dtype).itemsize
    if defaulted and 4 * bs * D * itemsize > vmem_budget:
        # Over budget (large D and/or bs == S): try the LARGEST legal
        # smaller divisor that fits (e.g. int8 S=8192 D=512: 8192 -> 1024)
        # before concluding this shape cannot tile the kernel.  int8
        # additionally needs the lane-packed scale-plane constraint.
        def legal(c):
            return S % c == 0 and (not quantized or (c // 128) % 8 == 0)

        # int8's lane-packed scale planes need (c//128)%8 == 0, i.e. a
        # multiple of 1024; plain caches may shrink all the way to 128.
        floor = 1024 if quantized else 128
        fit = max((c for c in range(floor, bs, 128)
                   if legal(c) and 4 * c * D * itemsize <= vmem_budget),
                  default=None)
        if fit is None:
            if raw_impl == "pallas":
                need = ("a multiple-of-1024 divisor of S"
                        if quantized else "a multiple-of-128 divisor of S")
                raise PallasShapeError(
                    f"flash_decode{' int8-KV' if quantized else ''}: S={S},"
                    f" D={D} has no legal KV block that fits VMEM (needs "
                    f"{need} with 4*bs*D*itemsize <= 12 MiB)")
            return _local_decode_xla(q, k, v, local_lens, scale=scale,
                                     k_scale=k_scale, v_scale=v_scale,
                                     soft_cap=soft_cap, window=window,
                                     window_lens=window_lens,
                                     q_lens=q_lens)
        bs = fit
    n_s = S // bs

    lens_arg, use_qlens = _pack_lens_arg(local_lens, window_lens, q_lens,
                                         n_tok=n_tok, window=window)
    rows = n_tok * g
    qg = _fold_q_rows(q, n_tok, Hkv)
    grid = (B, Hkv, n_s)
    q_spec = pl.BlockSpec((1, 1, rows, D),
                          lambda b, h, s, lens: (b, h, 0, 0))
    kv_spec = pl.BlockSpec((1, 1, bs, D), lambda b, h, s, lens: (b, h, s, 0))
    if quantized:
        # Scale layout: position p lives at (row p//128, lane p%128) —
        # each chunk's bs scales are ONE dense [bs//128, 128] transfer.
        sc_spec = pl.BlockSpec((1, 1, bs // 128, 128),
                               lambda b, h, s, lens: (b, h, s, 0))
        kern = functools.partial(_decode_kernel_i8, block_s=bs, n_s=n_s,
                                 scale=scale, soft_cap=soft_cap,
                                 window=window, n_tok=n_tok,
                                 use_qlens=use_qlens)
        in_specs = [q_spec, kv_spec, kv_spec, sc_spec, sc_spec]
        args = (lens_arg, qg, k, v,
                k_scale.reshape(B, Hkv, S // 128, 128),
                v_scale.reshape(B, Hkv, S // 128, 128))
    else:
        kern = functools.partial(_decode_kernel, block_s=bs, n_s=n_s,
                                 scale=scale, soft_cap=soft_cap,
                                 window=window, n_tok=n_tok,
                                 use_qlens=use_qlens)
        in_specs = [q_spec, kv_spec, kv_spec]
        args = (lens_arg, qg, k, v)
    out, lse = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, 1, rows, D),
                             lambda b, h, s, lens: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, rows, 128),
                             lambda b, h, s, lens: (b, h, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((rows, D), jnp.float32),
                pltpu.VMEM((rows, 128), jnp.float32),
                pltpu.VMEM((rows, 128), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, rows, D), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, rows, 128), jnp.float32),
        ],
        # (b, h) blocks are independent; only the KV-chunk axis carries the
        # online-softmax accumulator.  Telling Mosaic so lets it pipeline
        # across (b, h) boundaries (same knob as the 96%-MXU GEMM config).
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=maybe_interpret(interpret),
    )(*args)
    return _unfold_out(out, lse, multi, n_tok, Hq)


# ---------------------------------------------------------------------------
# Paged KV cache (block_table) decode
# ---------------------------------------------------------------------------
#
# Reference analog: the decode layer's ``block_table`` argument
# (sp_flash_decode_layer.py:78-103 — its kernel reads the KV cache through
# a page table).  TPU-native design: the page table rides as a SECOND
# scalar-prefetch operand and the pools stay in HBM (``memory_space=ANY``);
# the kernel walks a batch row's LIVE pages itself and copies each page's
# block in through the table, through a ring of page slots (the structure
# of ``jax.experimental.pallas.ops.tpu.paged_attention``, whose two buffers
# are the ring's least depth).
#
# * Blocking: the pool is ``[N_pages, Hkv, page, D]``, so one page of
#   EVERY local KV head is one contiguous block.  A loop step carries
#   ``Hh`` heads of a page — the largest divisor of the local ``Hkv`` of
#   which two K+V slots fit ``PAGED_VMEM_BUDGET``
#   (:func:`paged_heads_per_step`; ``Hh = Hkv`` at llama/Mistral widths,
#   whole or head-sharded) — and the grid is ``(B, Hkv // Hh)``.
# * The ring: as many slots as hold ``PAGED_BYTES_IN_FLIGHT``
#   (:func:`paged_pages_in_flight`: the smaller a slot, the more of them),
#   kept full through a row AND across the seam between one grid step and
#   the next — a row's last multiplies run over the next row's first
#   copies.  The ring's cursor outlives a step, so the grid is walked in
#   order (both axes ``"arbitrary"``).
# * Only ``ceil(len / page)`` pages of a row are walked (from the window's
#   first page under a sliding window): a dead table entry costs neither a
#   DMA nor a step, and a row with ``len == 0`` only writes its empty
#   partials.  Dead entries must still hold an in-range pool index.
# * The call carries the name its caller gives it (``name=``): a family
#   whose layers differ in kind names the call by the layer's kind
#   (``gqa_paged_window`` / ``gqa_paged_full``, models/swa_moe.py), so a
#   device trace tells a window layer's walk from a full layer's.  Where no
#   name is given — the dense family's call — it reaches a device trace
#   under the name of the scope around it, ``closed_call`` in the fused
#   horizon's scan and ``_unknown_`` in the single-step program: the two
#   names ``benchmarks/layer_metrics/paged_attn_roofline.json`` sums
#   (docs/paged_decode.md).


def _paged_gather(pool, table):
    """[N, Hkv, P, D] pool + [B, n] table → [B, Hkv, n*P, D] contiguous
    view (the XLA fallback materializes it; the pallas path never does)."""
    g = pool[table]                                   # [B, n, Hkv, P, D]
    B, n, Hkv, Pg, D = g.shape
    return g.transpose(0, 2, 1, 3, 4).reshape(B, Hkv, n * Pg, D)


def _paged_gather_scale(scale_pool, table):
    """[N, Hkv, P] per-position scale pool + [B, n] table →
    [B, Hkv, n*P] contiguous scale view (the twin of
    :func:`_paged_gather` for an int8 pool's scale plane)."""
    g = scale_pool[table]                             # [B, n, Hkv, P]
    B, n, Hkv, Pg = g.shape
    return g.transpose(0, 2, 1, 3).reshape(B, Hkv, n * Pg)


# Scoped VMEM the paged kernel may plan for its ring of K+V page slots, of
# Mosaic's 16 MiB (the q / partial blocks and the softmax state are KiB
# beside them).
PAGED_VMEM_BUDGET = 12 * 2 ** 20
# Bytes of K+V the page walk keeps in flight: its ring has as many slots as
# hold them, the page being multiplied among them.  On the chip (PR 47: the
# call alone, 16 calls chained, bf16, page 128, D 128; rows of 5 / 10 / 64
# pages; fixed cost a page step beside the page's bytes at 819 GB/s, us, by
# slots 2 | 3 | 4 and more) — 2 heads a page (128 KiB a slot) 0.33 | 0.24 |
# 0.24; 4 heads 0.26 | 0.11 | 0.10; 8 heads 0.16 | 0.06 | 0.06; 10 heads
# (640 KiB) 0.11 | 0.07 | 0.07; 30 heads (1.9 MiB) 0.20 | 0.20 | 0.20.  At
# two slots the next copy is issued only when a multiply ends and its
# latency shows once a page; a second copy ahead hides it, a third adds
# nothing at any size, and a 1.9 MiB copy outlasts its multiply by more
# than the latency already.  1.5 MiB gives 3 slots from 512 KiB a slot up
# to 768, 2 above, more below (where they cost nothing measurable).
PAGED_BYTES_IN_FLIGHT = 3 * 2 ** 19


def _paged_block_bytes(heads: int, page: int, head_dim: int,
                       itemsize: int, slots: int = 2) -> int:
    """VMEM of ``slots`` K+V page slots at ``heads`` heads a step (two: the
    least ring, what :func:`paged_heads_per_step` plans with)."""
    return slots * 2 * heads * page * head_dim * itemsize


def paged_heads_per_step(hkv: int, page: int, head_dim: int,
                         itemsize: int) -> int:
    """KV heads of a page one step of the paged kernel carries: the
    largest divisor of the LOCAL ``hkv`` of which a ring of two K+V slots
    stays inside ``PAGED_VMEM_BUDGET``; 0 when not even one head does
    (:func:`paged_kernel_gap` then names the reroute).  Chosen from the
    shapes alone — the same rule for a whole model and a head-sharded
    rank."""
    return max((h for h in range(1, hkv + 1) if hkv % h == 0
                and _paged_block_bytes(h, page, head_dim, itemsize)
                <= PAGED_VMEM_BUDGET), default=0)


def paged_pages_in_flight(heads: int, page: int, head_dim: int,
                          itemsize: int) -> int:
    """Slots of the page ring at ``heads`` heads a step: as many as hold
    ``PAGED_BYTES_IN_FLIGHT`` — at least 2, at most what
    ``PAGED_VMEM_BUDGET`` holds (the heads a step are chosen first and
    never shrink for the ring's sake); 0 with no head."""
    if not heads:
        return 0
    slot = _paged_block_bytes(heads, page, head_dim, itemsize, slots=1)
    return max(2, min(-(-PAGED_BYTES_IN_FLIGHT // slot),
                      PAGED_VMEM_BUDGET // slot))


def paged_kernel_blocking(hkv: int, page: int, head_dim: int,
                          itemsize: int, *, batch: int) -> dict:
    """How the paged decode call is blocked at this geometry (static: it
    is decided where the program is built): the heads a step carries, the
    grid steps of one call — the page walk inside a step is as long as
    the row's live context, hence ``"dynamic"`` — the slots of its page
    ring and the VMEM they take."""
    hh = paged_heads_per_step(hkv, page, head_dim, itemsize)
    depth = paged_pages_in_flight(hh, page, head_dim, itemsize)
    return {"heads_per_step": hh,
            "steps_per_call": batch * (hkv // hh) if hh else 0,
            "pages_per_step": "dynamic",
            "pages_in_flight": depth,
            "vmem_bytes": _paged_block_bytes(hh, page, head_dim, itemsize,
                                             slots=depth)}


def paged_kernel_gap(page: int, head_dim: int, itemsize: int, *,
                     quantized: bool = False) -> str | None:
    """Why :func:`gqa_decode_paged_shard` would NOT run its Pallas
    kernel over a pool of this geometry (``None``: it would).  The ONE
    copy of the guard, like :func:`decode_kernel_gap`: off the kernel the
    attend materialises the whole ``[B, Hkv, n*page, D]`` gathered view
    per layer per step, so a serving engine must be able to say which
    side of it it is on before the first request."""
    if quantized:
        return ("int8 pool: the paged attend is the XLA gather + "
                "in-program dequant (no paged int8 kernel yet)")
    if head_dim % 128 or page % 128:
        return (f"(page={page}, D={head_dim}) needs "
                f"page%128 == D%128 == 0"
                f"{_PAIRS_NOTE if head_dim == 64 else ''}")
    # A page is the kernel's KV block — it cannot shrink (it IS the cache
    # layout), so a page of which not even ONE head fits must
    # reroute/raise, not reach Mosaic's opaque VMEM failure.
    if not paged_heads_per_step(1, page, head_dim, itemsize):
        return (f"(page={page}, D={head_dim}): double-buffered K+V page "
                f"blocks exceed 12 MiB VMEM")
    return None


def gqa_decode_paged_shard(q, k_pool, v_pool, block_table, local_lens, *,
                           impl="auto", interpret=False, soft_cap=0.0,
                           window=0, window_lens=None, q_lens=None,
                           k_scale=None, v_scale=None,
                           name: str | None = None,
                           scale: float | None = None):
    """Single-shard GQA decode over a PAGED KV cache.

    q [B, Hq, D]; k/v_pool [N_pages, Hkv, page, D] (the physical page
    pool); block_table [B, n_pages] int32 — logical page i of batch b
    lives at pool row ``block_table[b, i]``; local_lens [B] valid rows.
    Returns float32 partials (out [B, Hq, D], lse [B, Hq]).  Only a
    row's live pages are read, as many KV heads of a page a step as
    :func:`paged_heads_per_step` allows (the comment above).

    INT8 POOLS: ``k_scale``/``v_scale`` [N_pages, Hkv, page] float32
    per-position scale pools dequantize int8 k/v pools (the paged twin
    of :func:`gqa_decode_shard`'s contiguous int8 path — scales ride
    the same page indirection as their pages).  The quantized paged
    attend runs the fused-dequant XLA path: the dedicated Pallas
    paged-int8 kernel (lane-packed scale planes copied in through the
    table) is a recorded debt — on a 128-aligned-page TPU layout
    the float kernel's gate would apply unchanged.

    MULTI-TOKEN (r5, same contract as :func:`gqa_decode_shard`): q may
    be [B, T, Hq, D] with optional per-request ``q_lens`` [B] — the
    k-token verify over a PAGED cache (mixed decode/verify batches);
    returns (out [B, T, Hq, D], lse [B, T, Hq]).

    ``name`` is the Mosaic call's name in a device trace (the comment
    above the budget: none by default).  ``scale`` is the scores' where it
    is not ``1 / sqrt(D)`` of the pool's rows (64-wide heads stored in
    pairs: :func:`pack_q_pairs`).
    """
    multi = q.ndim == 4
    n_tok = q.shape[1] if multi else 1
    B, Hq, D = q.shape[0], q.shape[-2], q.shape[-1]
    N, Hkv, Pg, _ = k_pool.shape
    n_pages = block_table.shape[1]
    assert Hq % Hkv == 0, (Hq, Hkv)
    g = Hq // Hkv
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    raw_impl = impl
    impl = resolve_impl(impl, interpret)

    if k_scale is not None or v_scale is not None:
        assert k_scale is not None and v_scale is not None, (
            "int8 paged pools carry BOTH scale planes")
        return _local_decode_xla(
            q, _paged_gather(k_pool, block_table),
            _paged_gather(v_pool, block_table), local_lens, scale=scale,
            k_scale=_paged_gather_scale(k_scale, block_table),
            v_scale=_paged_gather_scale(v_scale, block_table),
            soft_cap=soft_cap, window=window, window_lens=window_lens,
            q_lens=q_lens)

    itemsize = jnp.dtype(k_pool.dtype).itemsize
    gap = paged_kernel_gap(Pg, D, itemsize)
    if use_fallback(raw_impl, impl, gap is None, "paged_decode",
                    gap or ""):
        return _local_decode_xla(q, _paged_gather(k_pool, block_table),
                                 _paged_gather(v_pool, block_table),
                                 local_lens, scale=scale,
                                 soft_cap=soft_cap, window=window,
                                 window_lens=window_lens, q_lens=q_lens)

    lens_arg, use_qlens = _pack_lens_arg(local_lens, window_lens, q_lens,
                                         n_tok=n_tok, window=window)
    rows = n_tok * g
    qg = _fold_q_rows(q, n_tok, Hkv)
    hh = paged_heads_per_step(Hkv, Pg, D, itemsize)
    depth = paged_pages_in_flight(hh, Pg, D, itemsize)
    kern = functools.partial(_paged_decode_kernel, page=Pg, hh=hh,
                             depth=depth, n_pages=n_pages, scale=scale,
                             soft_cap=soft_cap, window=window, n_tok=n_tok,
                             use_qlens=use_qlens)
    out, lse = pl.pallas_call(
        kern,
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # (lens, block_table)
            grid=(B, Hkv // hh),
            in_specs=[
                pl.BlockSpec((1, hh, rows, D),
                             lambda b, h, lens, tab: (b, h, 0, 0)),
                # THE paging trick: the pools stay in HBM and the body
                # copies logical page i of batch b in from physical pool
                # row tab[b, i] — live pages only.
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[
                pl.BlockSpec((1, hh, rows, D),
                             lambda b, h, lens, tab: (b, h, 0, 0)),
                pl.BlockSpec((1, hh, rows, 128),
                             lambda b, h, lens, tab: (b, h, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((depth, hh, Pg, D), k_pool.dtype),
                pltpu.VMEM((depth, hh, Pg, D), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, depth)),  # (k | v, slot)
                pltpu.SMEM((1,), jnp.int32),          # the ring's cursor
                pltpu.VMEM((hh, rows, D), jnp.float32),
                pltpu.VMEM((hh, rows, 128), jnp.float32),
                pltpu.VMEM((hh, rows, 128), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, rows, D), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, rows, 128), jnp.float32),
        ],
        # the ring's cursor lives across grid steps, in grid order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=maybe_interpret(interpret),
    )(lens_arg, block_table, qg, k_pool, v_pool)
    return _unfold_out(out, lse, multi, n_tok, Hq)


def _live_pages(llen, wlen, qlen, *, page, n_pages, window, n_tok):
    """[lo, hi): the logical pages of a row that hold a position some
    live query may see.  ``hi`` covers the valid length; under a sliding
    window ``lo`` is the page of the earliest query's window start
    (conservative for multi-token: it reaches back ``n_tok - 1`` more
    rows).  A row with no live query (q_lens mode, ``qlen == 0``) or no
    length walks nothing."""
    hi = jnp.minimum((llen + page - 1) // page, n_pages)
    if qlen is not None:
        hi = jnp.where(qlen > 0, hi, 0)
    if not window:
        return 0, hi
    lo = jnp.maximum(wlen - (n_tok - 1) - window, 0) // page
    return jnp.minimum(lo, hi), hi


def _paged_decode_kernel(lens_ref, table_ref, q_ref, k_hbm, v_hbm, out_ref,
                         lse_ref, k_buf, v_buf, sem, cur_ref, acc_ref, m_ref,
                         l_ref, *, page, hh, depth, n_pages, scale,
                         soft_cap=0.0, window=0, n_tok=1, use_qlens=False):
    """Grid (B, Hkv // hh), walked in order; one step is one batch row
    under ``hh`` KV heads, and walks the row's live pages in order with
    the online softmax of :func:`_decode_kernel` batched over the head axis
    (same page order, same f32 state, same masking rule).

    The pages stream through a ring of ``depth`` slots that outlives the
    step.  The copies are ONE sequence: this step's ``n`` live pages, then
    the NEXT step's (``(b, h + 1)``, else ``(b + 1, 0)``; none after the
    last).  Copy ``t`` of it lands in slot ``(cursor + t) % depth`` and is
    started ``depth - 1`` multiplies before its own, so every step hands
    the next its first ``min(depth - 1, pages)`` copies in flight — which
    is what a step counts on (the first of the grid alone starts cold) —
    and a row's last multiplies run over the first copies of the row after
    it.  One loop does it all: iteration ``s`` starts copy ``s + depth -
    1`` and multiplies page ``s``; it begins below 0, multiplying nothing,
    only where copies under ``depth - 1`` are still to start.  Every copy
    started is waited for once, by the step that multiplies it."""
    # Scalars go through ``lax`` by name: an operator on a traced value is
    # a jitted ``jnp`` function, traced anew at every call site of every
    # program of every process — ~1.4 ms apiece on the chip's host, and 56
    # of them a call were PR 47's +10 to +16 s of WARM set-up (PERF.md §6).
    add, sub, sel = jax.lax.add, jax.lax.sub, jax.lax.select
    b, h = pl.program_id(0), pl.program_id(1)
    n_b, n_h = table_ref.shape[0], k_hbm.shape[1] // hh     # the grid
    ahead = depth - 1

    def walk(bb):
        lens = _read_lens(lens_ref, bb, window=window, use_qlens=use_qlens)
        return lens, _live_pages(*lens, page=page, n_pages=n_pages,
                                 window=window, n_tok=n_tok)

    (llen, wlen, qlen), (lo, hi) = walk(b)
    n = sub(hi, lo)
    first = jax.lax.eq(add(b, h), 0)
    if n_h == 1:                       # every head of a page in one step
        nb, nh = add(b, 1), 0
    else:
        wrap = jax.lax.eq(h, n_h - 1)
        nb, nh = sel(wrap, add(b, 1), b), sel(wrap, 0, add(h, 1))
    _, (nlo, nhi) = walk(jax.lax.min(nb, n_b - 1))
    total = add(n, sel(jax.lax.lt(nb, n_b), sub(nhi, nlo), 0))
    rows = q_ref.shape[2]

    base = sel(first, 0, cur_ref[0])   # the slot of this step's first page
    cur_ref[0] = jax.lax.rem(add(base, n), depth)
    # copies under ``ahead`` still to start: all of them on the first step,
    # else the next step's where this one has fewer than ``ahead`` pages
    pre = sel(first, 0, jax.lax.min(n, ahead))
    s0 = sel(jax.lax.lt(pre, jax.lax.min(total, ahead)), sub(pre, ahead), 0)

    def page_copies(row, h0, slot):
        return (pltpu.make_async_copy(k_hbm.at[row, pl.ds(h0, hh)],
                                      k_buf.at[slot], sem.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[row, pl.ds(h0, hh)],
                                      v_buf.at[slot], sem.at[1, slot]))

    _softmax_state_init(acc_ref, m_ref, l_ref)

    def page_step(s, slot):            # slot: copy ``s + ahead``'s
        t = add(s, ahead)

        @pl.when(jax.lax.lt(t, total))
        def _():
            own = jax.lax.lt(t, n)
            row = table_ref[sel(own, b, nb),
                            sel(own, add(lo, t), add(nlo, sub(t, n)))]
            h0 = 0 if n_h == 1 else jax.lax.mul(sel(own, h, nh), hh)
            for c in page_copies(row, h0, slot):
                c.start()

        slot = sel(jax.lax.eq(slot, ahead), 0, add(slot, 1))    # page s's

        @pl.when(jax.lax.ge(s, 0))
        def _():
            for c in page_copies(0, 0, slot):  # a wait reads the slot alone
                c.wait()
            pos = add(jax.lax.broadcasted_iota(jnp.int32, (rows, page), 1),
                      jax.lax.mul(add(lo, s), page))
            valid = _chunk_valid(pos, llen, wlen, qlen, window=window,
                                 group=rows // n_tok)
            _online_softmax_step(q_ref[0], k_buf[slot], v_buf[slot], valid,
                                 acc_ref, m_ref, l_ref, scale=scale,
                                 soft_cap=soft_cap)
        return slot

    jax.lax.fori_loop(s0, n, page_step,
                      jax.lax.rem(add(base, add(s0, ahead)), depth))
    out_ref[0], lse_ref[0] = _softmax_state_emit(acc_ref, m_ref, l_ref)


# ---------------------------------------------------------------------------
# Paged decode over a LATENT cache (multi-head latent attention, absorbed)
# ---------------------------------------------------------------------------
#
# The cache holds ONE row a token a layer: ``[c_kv | k_rope]``, ``rank +
# rope`` wide (512 + 64 at the DeepSeek-V3 widths), padded with zeros to
# the next lane tile by whoever writes it (640: Mosaic cannot cut a page
# out of a 576-wide plane, which the chip lays out 640 wide anyway; the
# query's pad columns are zero too, so they add nothing to a score).  In
# the absorbed form
# every query head scores against the whole row and sums the first ``rank``
# columns of the same row as its value, so a page is copied in once and
# serves as key and as value for all heads: 64 heads x T tokens are the
# rows of one [R, rank + rope] x [rank + rope, page] product and one
# [R, page] x [page, rank] product a page.  Same walk as the GQA body above
# (a row's live pages only, page i + 1 streaming under page i's products),
# same masking rule and online softmax through the shared helpers.

# Query rows (tokens x heads) one step of the latent kernel carries: a
# decode row is 64, a prefill chunk of 128 tokens is cut into tiles of this
# many rows and each tile walks only the pages its last token may see.
MLA_Q_ROWS = 512
MLA_CALL_NAME = "mla_paged_decode"
# Pages in flight (VMEM: 160 KiB a buffer).  On the chip (PR 26: 5 calls,
# B 64, 88.8k live tokens) one page ahead read 2.38 ms and two or more 2.20
# (28% of the call's roofline): copy latency is a small part — at 64 query
# rows a page the step is bound by loading each page into the MXU for two
# small products, ~0.6 us a page, not by the page's bytes.
MLA_PAGES_IN_FLIGHT = 3


def mla_kernel_gap(page: int, rank: int, rope: int) -> str | None:
    """Why :func:`mla_decode_paged_shard` would NOT run its Pallas kernel
    over a latent pool of this geometry (``None``: it would)."""
    if page % 128 or rank % 128 or (rank + rope) % 128:
        return (f"(page={page}, rank={rank}, row={rank + rope}) needs "
                f"page%128 == rank%128 == row%128 == 0 (a row narrower "
                f"than its lane tile is padded by the caller: the chip "
                f"stores a 576-wide row as 640 either way)")
    return None


def _visible(B, T, n, Pg, local_lens, q_lens):
    """[B, T, n * Pg] bool: the multi-token visibility rule of
    :func:`_chunk_valid` in dense form (the XLA fallbacks' mask)."""
    ql = (jnp.full((B,), T, jnp.int32) if q_lens is None
          else q_lens.astype(jnp.int32))
    pos = jnp.arange(n * Pg)[None, None, :]
    d = ql[:, None] - 1 - jnp.arange(T)[None, :]                # [B, T]
    return ((d[..., None] >= 0)
            & (pos < (local_lens[:, None] - d)[..., None]))     # [B, T, S]


def _mla_decode_xla(q, pool, block_table, local_lens, *, rank, scale,
                    q_lens=None, sel=None):
    """Dense form of the latent paged attend (the interpreter-free
    fallback and the kernel's oracle): gather the row's pages, score all
    heads against the whole row, sum the first ``rank`` columns."""
    lat = pool[block_table].astype(jnp.float32)        # [B, n, page, W]
    B, n, Pg, W = lat.shape
    lat = lat.reshape(B, n * Pg, W)
    T = q.shape[1]
    logits = jnp.einsum("bthw,bsw->bths", q.astype(jnp.float32),
                        lat) * scale
    valid = _visible(B, T, n, Pg, local_lens, q_lens)
    if sel is not None:
        valid = valid & (_sel_rows(sel, T).reshape(B, T, n * Pg) >= 0)
    valid = valid[:, :, None, :]
    logits = jnp.where(valid, logits, NEG_INF)
    p = jnp.where(valid, jnp.exp(logits - jnp.max(logits, -1,
                                                  keepdims=True)), 0.0)
    l = jnp.sum(p, -1, keepdims=True)
    p = p / jnp.where(l > 0, l, 1.0)
    return jnp.einsum("bths,bsr->bthr", p, lat[..., :rank])


def _sel_rows(sel, T):
    """A selection operand in its call layout ([B, n, page] at T == 1,
    [B, n, T, page] above) -> [B, T, n, page]."""
    return sel[:, None] if T == 1 else sel.transpose(0, 2, 1, 3)


def _tokens_per_tile(T: int, heads: int, row_cap: int,
                     blocked: bool = False) -> int:
    """Query tokens one grid step of a latent-family call carries: at most
    ``row_cap`` rows (tokens x heads), a divisor of ``T``; ``blocked``
    (an operand blocked by token: its tile must be 8-aligned, or whole)
    also a multiple of 8 unless it is all of ``T``."""
    tq = max(1, min(T, row_cap // heads))
    while T % tq or (blocked and 1 < tq < T and tq % 8):
        tq -= 1
    return tq


def _tile_walk(lens_ref, b, t0, *, tq, page, n_pages, n_tok, use_qlens):
    """What a (batch row, query tile) step of a latent-family kernel
    walks: ``(llen, wlen, qlen_t, hi)`` — the lens, the row's live query
    count shifted so that ``_chunk_valid``'s token index is local to the
    tile starting at token ``t0`` (None without q_lens), and the pages up
    to the last one the tile's own last token may see."""
    llen, wlen, qlen = _read_lens(lens_ref, b, window=0,
                                  use_qlens=use_qlens)
    if qlen is None:
        qlen_t, seen = None, llen
    else:
        qlen_t = qlen - t0
        seen = llen - jnp.maximum(qlen_t - tq, 0)
    _, hi = _live_pages(seen, seen, qlen_t, page=page, n_pages=n_pages,
                        window=0, n_tok=n_tok)
    return llen, wlen, qlen_t, hi


def mla_decode_paged_shard(q, pool, block_table, local_lens, *, rank: int,
                           scale: float, q_lens=None, sel=None,
                           impl="auto", interpret=False):
    """Absorbed latent attention over a PAGED latent cache.

    q [B, T, H, rank + rope] (the absorbed query ``[q_nope W_UK | q_rope]``;
    T == 1 is a decode step, T > 1 a verify row or a prefill chunk: query t
    of row b sits at position ``local_lens[b] - (T or q_lens[b]) + t``);
    pool [N, page, rank + rope]; block_table [B, n_pages] int32;
    local_lens [B] valid rows INCLUDING the queries' own.  Returns float32
    ``[B, T, H, rank]``: softmax(q . row * scale) @ row[:rank], still in
    the latent space (the caller applies W_UV).  Rows with no live query
    or no length give zeros.

    ``sel`` (learned sparse attention: :func:`dsa_index_scores` less each
    query's cut-off, in that call's layout — float32 [B, n_pages, page] at
    T == 1, [B, n_pages, T, page] above) keeps of the visible rows those
    with ``sel >= 0``: the same page walk with a selection mask, dense
    bytes.
    """
    B, T, H, W = q.shape
    N, Pg, W2 = pool.shape
    assert W == W2 and rank < W, (q.shape, pool.shape, rank)
    n_pages = block_table.shape[1]
    raw_impl = impl
    impl = resolve_impl(impl, interpret)
    gap = mla_kernel_gap(Pg, rank, W - rank)
    if use_fallback(raw_impl, impl, gap is None or interpret,
                    MLA_CALL_NAME, gap or ""):
        return _mla_decode_xla(q, pool, block_table, local_lens, rank=rank,
                               scale=scale, q_lens=q_lens, sel=sel)

    # the [3, B] lens layout whenever a row may hold dead queries
    lens_arg, use_qlens = _pack_lens_arg(local_lens, None, q_lens,
                                         n_tok=T, window=0)
    tq = _tokens_per_tile(T, H, MLA_Q_ROWS, blocked=sel is not None)
    rows = tq * H
    qr = q.reshape(B, T * H, W)
    depth = max(2, MLA_PAGES_IN_FLIGHT)
    kern = functools.partial(_mla_paged_kernel, page=Pg, rank=rank,
                             n_pages=n_pages, scale=scale, n_tok=T, tq=tq,
                             heads=H, use_qlens=use_qlens, depth=depth,
                             has_sel=sel is not None)
    sel_spec, sel_arg = [], []
    if sel is not None:
        sel_arg = [sel]
        sel_spec = [pl.BlockSpec((1, n_pages, Pg),
                                 lambda b, j, lens, tab: (b, 0, 0))
                    if T == 1 else
                    pl.BlockSpec((1, n_pages, tq, Pg),
                                 lambda b, j, lens, tab: (b, 0, j, 0))]
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # (lens, block_table)
            grid=(B, T // tq),
            in_specs=[
                pl.BlockSpec((1, rows, W),
                             lambda b, j, lens, tab: (b, j, 0)),
                pl.BlockSpec(memory_space=pl.ANY),    # the pool stays in HBM
                *sel_spec,
            ],
            out_specs=pl.BlockSpec((1, rows, rank),
                                   lambda b, j, lens, tab: (b, j, 0)),
            scratch_shapes=[
                pltpu.VMEM((depth, Pg, W), pool.dtype),
                pltpu.SemaphoreType.DMA((depth,)),
                pltpu.VMEM((rows, rank), jnp.float32),
                pltpu.VMEM((rows, 128), jnp.float32),
                pltpu.VMEM((rows, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, T * H, rank), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=maybe_interpret(interpret),
        name=MLA_CALL_NAME,
    )(lens_arg, block_table, qr, pool, *sel_arg)
    return out.reshape(B, T, H, rank)


def _mla_paged_kernel(lens_ref, table_ref, q_ref, pool_hbm, *refs, page,
                      rank, n_pages, scale, n_tok, tq, heads, use_qlens,
                      depth, has_sel=False):
    """Grid (B, T // tq); one step is ``tq`` query tokens of one batch row
    under every head, walking the row's live pages up to the last one its
    own last token may see, ``depth - 1`` pages copied ahead of the one
    being multiplied.  With a selection operand (``has_sel``) a page's
    rows count only where the tile's token kept them."""
    sel_ref = refs[0] if has_sel else None
    out_ref, buf, sem, acc_ref, m_ref, l_ref = refs[int(has_sel):]
    b = pl.program_id(0)
    llen, wlen, qlen_t, hi = _tile_walk(
        lens_ref, b, pl.program_id(1) * tq, tq=tq, page=page,
        n_pages=n_pages, n_tok=n_tok, use_qlens=use_qlens)
    rows = q_ref.shape[1]

    def page_copy(i, slot):
        return pltpu.make_async_copy(pool_hbm.at[table_ref[b, i]],
                                     buf.at[slot], sem.at[slot])

    for j in range(depth - 1):
        @pl.when(j < hi)
        def _():
            page_copy(j, j).start()

    _softmax_state_init(acc_ref, m_ref, l_ref)

    def page_step(i, _):
        slot = jax.lax.rem(i, depth)
        ahead = i + depth - 1

        @pl.when(ahead < hi)
        def _():
            page_copy(ahead, jax.lax.rem(ahead, depth)).start()

        page_copy(i, slot).wait()
        pos = i * page + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page), 1)
        valid = _chunk_valid(pos, llen, wlen, qlen_t, window=0,
                             group=heads)
        if sel_ref is not None and n_tok == 1:
            valid = valid & (sel_ref[0, pl.ds(i, 1), :] >= 0.0)
        elif sel_ref is not None:
            kept = sel_ref[0, i]                        # [tq, page]
            valid = valid & (jnp.broadcast_to(
                kept[:, None, :], (tq, heads, page)).reshape(rows, page)
                >= 0.0)
        row = buf[slot]                                 # [page, rank + rope]
        _online_softmax_step(q_ref[0], row, row[:, :rank], valid, acc_ref,
                             m_ref, l_ref, scale=scale, soft_cap=0.0)

    jax.lax.fori_loop(0, hi, page_step, None)
    out_ref[0], _ = _softmax_state_emit(acc_ref, m_ref, l_ref)


# ---------------------------------------------------------------------------
# A prefill chunk over a latent scratch, in the EXPANDED form
# ---------------------------------------------------------------------------
#
# The absorbed form above suits a decode step: 64 query rows a page, and a
# page's bytes are what it costs.  A prefill chunk is bound by the products,
# and there the absorbed form pays ``2 x (rank + rope + rank)`` operations a
# query-key pair a head (2,176 at the published widths) where the expanded
# form pays ``2 x (nope + rope + v)`` (1,024): the caller expands the
# contiguous scratch once a chunk (one product of its rows with ``[W_UK |
# I | W_UV]``: a head's key beside its value, 42 M operations a cached row
# against a chunk's ``T x 65.5 k``) and this call is flash attention over
# it, a head's own keys and values, with the indexer's selection as a mask
# the heads share.  Everything stays in the layout a matmul leaves it in
# ([tokens, heads x width]): a step cuts its heads out by lanes.  On the
# chip (PERF.md §6, PR 30; one layer, 2,048 queries over 16,384 visible
# rows): the absorbed walk 62.9 ms, this call 12 ms at 86% of the MXU's peak.

MLA_PREFILL_CALL_NAME = "mla_expanded_prefill"
# (query rows, key rows, heads) of one grid step.
MLA_PREFILL_BLOCK = (512, 1024, 4)
MLA_PREFILL_VMEM = 64 * 2 ** 20


def _prefill_blocks(T, S, H):
    bq, bk, hb = MLA_PREFILL_BLOCK
    return math.gcd(bq, T), math.gcd(bk, S), math.gcd(hb, H)


def mla_prefill_gap(T: int, S: int, d_qk: int, d_v: int,
                    page: int = 128) -> str | None:
    """Why :func:`mla_expanded_prefill` would NOT run its Pallas kernel on
    a chunk of ``T`` queries over ``S`` scratch rows (``None``: it would)."""
    bq, bk, _ = _prefill_blocks(T, S, 1)
    if d_qk % 128 or d_v % 128 or page % 128 or bq % 8 or bk % page:
        return (f"(chunk={T}, extent={S}, qk={d_qk}, v={d_v}, page={page}) "
                f"needs qk%128 == v%128 == page%128 == 0 and whole blocks "
                f"of queries (a multiple of 8: {bq}) and of rows (whole "
                f"pages: {bk})")
    return None


def _mla_prefill_xla(q, kv, sel, prefix_len, *, heads, d_qk, scale):
    """Dense form of :func:`mla_expanded_prefill` (the interpreter-free
    fallback and the kernel's oracle)."""
    B, T, _ = q.shape
    S = kv.shape[1]
    q = q.reshape(B, T, heads, d_qk).astype(jnp.float32)
    kv = kv.reshape(B, S, heads, -1).astype(jnp.float32)
    logits = jnp.einsum("bthd,bshd->bhts", q, kv[..., :d_qk]) * scale
    keep = sel.transpose(0, 2, 1, 3).reshape(B, T, S) >= 0.0
    keep = keep & (jnp.arange(S)[None, None, :]
                   <= prefix_len + jnp.arange(T)[None, :, None])
    keep = keep[:, None]
    logits = jnp.where(keep, logits, NEG_INF)
    p = jnp.where(keep, jnp.exp(logits - jnp.max(logits, -1, keepdims=True)),
                  0.0)
    l = jnp.sum(p, -1, keepdims=True)
    p = p / jnp.where(l > 0, l, 1.0)
    return jnp.einsum("bhts,bshd->bthd", p,
                      kv[..., d_qk:]).reshape(B, T, -1)


def mla_expanded_prefill(q, kv, sel, prefix_len, *, heads: int, d_qk: int,
                         scale: float, impl="auto", interpret=False):
    """Causal attention of a prefill chunk over EXPANDED keys and values,
    under a selection the heads share.

    q [B, T, H * Dk] (query t sits at position ``prefix_len + t``), kv [B,
    S, H * (Dk + Dv)] (row s at position s, a head's key beside its value;
    rows past the chunk's end are never read), sel float32 [B, S // page,
    T, page] in :func:`dsa_index_scores`' layout (a row counts where ``sel
    >= 0`` AND the query may see it), prefix_len int32 scalar.  Returns
    [B, T, H * Dv] in q's dtype; a query with nothing kept gives zeros.
    """
    B, T, HD = q.shape
    S = kv.shape[1]
    d_v = kv.shape[2] // heads - d_qk
    n_pages, page = sel.shape[1], sel.shape[3]
    assert HD == heads * d_qk and d_v > 0 and kv.shape == (
        B, S, heads * (d_qk + d_v)), (q.shape, kv.shape, heads, d_qk)
    assert sel.shape == (B, n_pages, T, page) and n_pages * page == S, (
        sel.shape, S)
    raw_impl = impl
    impl = resolve_impl(impl, interpret)
    gap = mla_prefill_gap(T, S, d_qk, d_v, page)
    if use_fallback(raw_impl, impl, gap is None or interpret,
                    MLA_PREFILL_CALL_NAME, gap or ""):
        return _mla_prefill_xla(q, kv, sel, prefix_len, heads=heads,
                                d_qk=d_qk, scale=scale).astype(q.dtype)
    bq, bk, hb = _prefill_blocks(T, S, heads)
    assert bk % page == 0, (bk, page)
    n_k = S // bk
    plen = jnp.asarray(prefix_len, jnp.int32).reshape(1)

    def last_live(i, plen_ref):
        # the last key block query block i may see
        return jnp.minimum((plen_ref[0] + (i + 1) * bq - 1) // bk, n_k - 1)

    kern = functools.partial(_mla_prefill_kernel, bq=bq, bk=bk, hb=hb,
                             n_k=n_k, page=page, d_qk=d_qk, d_v=d_v,
                             scale=scale)
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # prefix_len
            grid=(B, heads // hb, T // bq, n_k),
            in_specs=[
                pl.BlockSpec((None, bq, hb * d_qk),
                             lambda b, h, i, j, plen_ref: (b, i, h)),
                pl.BlockSpec((None, bk, hb * (d_qk + d_v)),
                             lambda b, h, i, j, plen_ref: (
                                 b, jnp.minimum(j, last_live(i, plen_ref)),
                                 h)),
                pl.BlockSpec((None, bk // page, bq, page),
                             lambda b, h, i, j, plen_ref: (
                                 b, jnp.minimum(j, last_live(i, plen_ref)),
                                 i, 0)),
            ],
            out_specs=pl.BlockSpec((None, bq, hb * d_v),
                                   lambda b, h, i, j, plen_ref: (b, i, h)),
            scratch_shapes=[
                pltpu.VMEM((hb, bq, d_v), jnp.float32),
                pltpu.VMEM((hb, bq, 128), jnp.float32),
                pltpu.VMEM((hb, bq, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, T, heads * d_v), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=MLA_PREFILL_VMEM),
        interpret=maybe_interpret(interpret),
        name=MLA_PREFILL_CALL_NAME,
    )(plen, q, kv, sel)


def _mla_prefill_kernel(plen_ref, q_ref, kv_ref, sel_ref, out_ref, acc_ref,
                        m_ref, l_ref, *, bq, bk, hb, n_k, page, d_qk, d_v,
                        scale):
    """Grid (B, H // hb, T // bq, S // bk), the key axis innermost and
    sequential: one step folds a block of ``bk`` expanded rows into the
    online softmax of ``bq`` queries, ``hb`` heads one after the other
    (each its own lanes of the blocks, the mask built once).  Key blocks
    wholly past the query block's last position are skipped (their index
    maps point at the last live block, so nothing is copied for them)."""
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _():
        _softmax_state_init(acc_ref, m_ref, l_ref)

    q0 = plen_ref[0] + i * bq            # position of the block's first query
    k0 = j * bk

    @pl.when(k0 <= q0 + bq - 1)
    def _():
        kept = jnp.concatenate(
            [sel_ref[g] for g in range(bk // page)], axis=-1)   # [bq, bk]
        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        valid = (kept >= 0.0) & (kpos <= qpos)
        w = d_qk + d_v
        for h in range(hb):
            _online_softmax_step(
                q_ref[:, h * d_qk:(h + 1) * d_qk],
                kv_ref[:, h * w:h * w + d_qk],
                kv_ref[:, h * w + d_qk:(h + 1) * w], valid,
                acc_ref.at[h], m_ref.at[h], l_ref.at[h], scale=scale,
                soft_cap=0.0)

    @pl.when(j == n_k - 1)
    def _():
        for h in range(hb):
            out, _ = _softmax_state_emit(acc_ref.at[h], m_ref.at[h],
                                         l_ref.at[h])
            out_ref[:, h * d_v:(h + 1) * d_v] = out.astype(out_ref.dtype)


# ---------------------------------------------------------------------------
# Learned sparse attention over a latent cache: the indexer's scores
# ---------------------------------------------------------------------------
#
# Beside the latent row the cache holds one INDEX KEY a token a layer (128
# wide at the published widths).  A query scores every cached token with a
# few small heads, ``I(t, s) = sum_j w[t, j] * relu(qI[t, j] . kI[s])``,
# and attends only to the ``index_topk`` best.  This call computes the
# scores over the paged index-key plane: the same live-page walk as the
# latent kernel, but a page is 32 KiB and one [heads, 128] x [128, page]
# product, so ``DSA_PAGES_PER_STEP`` pages are multiplied at once and the
# next group streams in under them.  The cut-off and what is read of the
# latent plane afterwards are the caller's (models/mla_moe.py).

DSA_INDEX_CALL_NAME = "dsa_index_scores"
DSA_PAGES_PER_STEP = 8
DSA_GROUPS_IN_FLIGHT = 3
# Query rows (tokens x index heads) of one step: a decode row is 32, a
# prefill chunk is cut into tiles of 16 tokens.
DSA_Q_ROWS = 512


def dsa_index_gap(page: int, dim: int) -> str | None:
    """Why :func:`dsa_index_scores` would NOT run its Pallas kernel over
    an index-key plane of this geometry (``None``: it would)."""
    if page % 128 or dim % 128:
        return (f"(page={page}, index_head_dim={dim}) needs page%128 == "
                f"index_head_dim%128 == 0")
    return None


def _dsa_index_xla(qi, w, pool, block_table, local_lens, *, q_lens=None):
    """Dense form of the index scores (the interpreter-free fallback and
    the kernel's oracle) -> [B, T, n_pages, page]."""
    keys = pool[block_table].astype(jnp.float32)       # [B, n, page, Di]
    B, n, Pg, _ = keys.shape
    T = qi.shape[1]
    dots = jnp.einsum("bthd,bnpd->bthnp", qi.astype(jnp.float32), keys)
    s = jnp.einsum("bth,bthnp->btnp", w.astype(jnp.float32),
                   jnp.maximum(dots, 0.0))
    valid = _visible(B, T, n, Pg, local_lens, q_lens).reshape(B, T, n, Pg)
    return jnp.where(valid, s, NEG_INF)


def dsa_index_scores(qi, w, pool, block_table, local_lens, *, q_lens=None,
                     impl="auto", interpret=False):
    """The indexer's scores of every cached token, over a PAGED index-key
    plane.

    qi [B, T, Hi, Di] (the index queries, RoPE applied), w [B, T, Hi]
    float32 (the head weights), pool [N, page, Di] (index keys),
    block_table [B, n_pages], local_lens [B] valid rows INCLUDING the
    queries' own (query t of row b sits at ``local_lens[b] - (T or
    q_lens[b]) + t``, as in :func:`mla_decode_paged_shard`).  Returns
    float32 ``sum_j w[.., j] * relu(qi[.., j] . key)`` per cached token,
    ``NEG_INF`` where the query may not see it, laid out by page as the
    latent call's ``sel`` wants it: [B, n_pages, page] at T == 1,
    [B, n_pages, T, page] above.
    """
    B, T, Hi, Di = qi.shape
    N, Pg, Di2 = pool.shape
    assert Di == Di2 and w.shape == (B, T, Hi), (qi.shape, w.shape,
                                                 pool.shape)
    n_pages = block_table.shape[1]
    raw_impl = impl
    impl = resolve_impl(impl, interpret)
    gap = dsa_index_gap(Pg, Di)
    if use_fallback(raw_impl, impl, gap is None or interpret,
                    DSA_INDEX_CALL_NAME, gap or ""):
        s = _dsa_index_xla(qi, w, pool, block_table, local_lens,
                           q_lens=q_lens)
        return s[:, 0] if T == 1 else s.transpose(0, 2, 1, 3)

    lens_arg, use_qlens = _pack_lens_arg(local_lens, None, q_lens,
                                         n_tok=T, window=0)
    tq = _tokens_per_tile(T, Hi, DSA_Q_ROWS, blocked=True)
    rows = tq * Hi
    G = next(g for g in range(min(DSA_PAGES_PER_STEP, n_pages), 0, -1)
             if n_pages % g == 0)
    depth = max(2, DSA_GROUPS_IN_FLIGHT)
    kern = functools.partial(_dsa_index_kernel, page=Pg, n_pages=n_pages,
                             n_tok=T, tq=tq, heads=Hi, use_qlens=use_qlens,
                             G=G, depth=depth)
    single = T == 1
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # (lens, block_table)
            grid=(B, T // tq),
            in_specs=[
                pl.BlockSpec((1, rows, Di),
                             lambda b, j, lens, tab: (b, j, 0)),
                pl.BlockSpec((1, rows, 1),
                             lambda b, j, lens, tab: (b, j, 0)),
                pl.BlockSpec(memory_space=pl.ANY),    # the plane stays in HBM
            ],
            out_specs=(pl.BlockSpec((1, n_pages, Pg),
                                    lambda b, j, lens, tab: (b, 0, 0))
                       if single else
                       pl.BlockSpec((1, n_pages, tq, Pg),
                                    lambda b, j, lens, tab: (b, 0, j, 0))),
            scratch_shapes=[
                pltpu.VMEM((depth, G * Pg, Di), pool.dtype),
                pltpu.SemaphoreType.DMA((depth, G)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(
            (B, n_pages, Pg) if single else (B, n_pages, T, Pg),
            jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=maybe_interpret(interpret),
        name=DSA_INDEX_CALL_NAME,
    )(lens_arg, block_table, qi.reshape(B, T * Hi, Di),
      w.astype(jnp.float32).reshape(B, T * Hi, 1), pool)


def _dsa_index_kernel(lens_ref, table_ref, q_ref, w_ref, pool_hbm, out_ref,
                      buf, sem, *, page, n_pages, n_tok, tq, heads,
                      use_qlens, G, depth):
    """Grid (B, T // tq); one step is ``tq`` query tokens of one batch row
    under every index head, walking the row's live pages ``G`` at a time
    (``G`` divides ``n_pages``; a group's dead pages are copied too — the
    table pads with the null block — and masked), ``depth - 1`` groups
    copied ahead of the one being multiplied."""
    b = pl.program_id(0)
    llen, wlen, qlen_t, hi = _tile_walk(
        lens_ref, b, pl.program_id(1) * tq, tq=tq, page=page,
        n_pages=n_pages, n_tok=n_tok, use_qlens=use_qlens)
    n_groups = (hi + G - 1) // G

    def copies(g, slot):
        return [pltpu.make_async_copy(
            pool_hbm.at[table_ref[b, g * G + j]],
            buf.at[slot, pl.ds(j * page, page)], sem.at[slot, j])
            for j in range(G)]

    for d in range(depth - 1):
        @pl.when(d < n_groups)
        def _():
            for c in copies(d, d):
                c.start()

    out_ref[...] = jnp.full(out_ref.shape, NEG_INF, out_ref.dtype)

    def group_step(g, _):
        slot = jax.lax.rem(g, depth)
        ahead = g + depth - 1

        @pl.when(ahead < n_groups)
        def _():
            for c in copies(ahead, jax.lax.rem(ahead, depth)):
                c.start()

        for c in copies(g, slot):
            c.wait()
        dots = jax.lax.dot_general(
            q_ref[0], buf[slot], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)         # [rows, G * page]
        s = jnp.maximum(dots, 0.0) * w_ref[0]
        if tq == 1:
            s = jnp.sum(s, axis=0, keepdims=True)
        else:
            s = jnp.sum(s.reshape(tq, heads, G * page), axis=1)
        pos = g * (G * page) + jax.lax.broadcasted_iota(
            jnp.int32, (tq, G * page), 1)
        valid = _chunk_valid(pos, llen, wlen, qlen_t, window=0, group=1)
        s = jnp.where(valid, s, NEG_INF)
        for j in range(G):
            part = s[:, j * page:(j + 1) * page]
            if n_tok == 1:
                out_ref[0, pl.ds(g * G + j, 1), :] = part
            else:
                out_ref[0, g * G + j] = part

    jax.lax.fori_loop(0, n_groups, group_step, None)


def sp_gqa_decode_paged_shard(q, k_pool, v_pool, block_table, kv_lens, *,
                              axis, impl="auto", interpret=False,
                              soft_cap=0.0, window=0, q_lens=None,
                              k_scale=None, v_scale=None):
    """Per-device SP decode over a paged cache: each rank's pool holds
    the pages of ITS sequence shard and ``block_table`` [B, n_local]
    holds local pool indices for the rank's logical pages.  ``kv_lens``
    are GLOBAL lengths; shard ownership follows n_local * page rows per
    rank (the contiguous-cache rule with S_loc = n_local * page).
    ``k_scale``/``v_scale`` [N, Hkv, page] dequantize int8 pools — each
    rank's scale plane shards with its pages, the combine is unchanged
    (partials are float either way).

    MULTI-TOKEN (ISSUE 19 debt (a)): q may be [B, T, Hq, D] with optional
    per-request ``q_lens`` [B] — the k-token verify over a sharded paged
    cache.  Per-token causality under SP uses the unclipped local ``ends``
    as ``window_lens`` (the same device kernel contract as the contiguous
    path); [B, T, ...] partials combine like a B*T batch — dead rows carry
    lse = NEG on every rank and merge to 0."""
    multi = q.ndim == 4
    B, Hq, D = q.shape[0], q.shape[-2], q.shape[-1]
    n_local = block_table.shape[1]
    s_loc = n_local * k_pool.shape[2]
    me = jax.lax.axis_index(axis)
    ends = (kv_lens - me * s_loc).astype(jnp.int32)
    local_lens = jnp.clip(ends, 0, s_loc)

    out, lse = gqa_decode_paged_shard(q, k_pool, v_pool, block_table,
                                      local_lens, impl=impl,
                                      interpret=interpret,
                                      soft_cap=soft_cap, window=window,
                                      window_lens=ends if (window or multi)
                                      else None,
                                      q_lens=q_lens,
                                      k_scale=k_scale, v_scale=v_scale)
    if multi:
        T = out.shape[1]
        c = _combine_across_ranks(out.reshape(B * T, Hq, D),
                                  lse.reshape(B * T, Hq), q.dtype,
                                  axis=axis, impl=impl, interpret=interpret)
        return c.reshape(B, T, Hq, D)
    return _combine_across_ranks(out, lse, q.dtype, axis=axis, impl=impl,
                                 interpret=interpret)


def _combine_across_ranks(out, lse, out_dtype, *, axis, impl, interpret):
    """The one inter-rank combine dispatch, shared by the contiguous and
    paged SP decodes: comm-fused pallas combine by default; packed
    LL-gather + XLA epilogue for xla mode / non-lane-divisible head_dim;
    world-1 passthrough."""
    world = jax.lax.axis_size(axis)
    B, Hq, D = out.shape
    if world == 1:
        return out.astype(out_dtype)
    if resolve_impl(impl, interpret) == "xla" or D % 128:
        packed = pack_payload(out, lse)
        gathered = fast_allgather_shard(
            packed, axis=axis, impl=impl, interpret=interpret,
            collective_id=SP_DECODE_COLLECTIVE_ID)
        gathered = gathered.reshape(world, B, Hq, D + 1)
        outs, lses = unpack_payload(gathered)
        return combine_partials(outs, lses).astype(out_dtype)
    return sp_combine_shard(out, lse, axis=axis,
                            interpret=interpret).astype(out_dtype)


# ---------------------------------------------------------------------------
# Inter-rank combine
# ---------------------------------------------------------------------------


def _sp_combine_kernel(plane_in, final_ref, gath, send_sem, recv_sem,
                       copy_sem, *, axis, world, d):
    """Comm-fused inter-rank combine: each rank pushes its packed
    (out ⊕ lse) partial plane to every peer's VMEM slot and LSE-merges the
    arrivals in-kernel — the remote DMA and the combine live in ONE Pallas
    kernel, no host-level gather + XLA epilogue remains.

    Reference analog: the dedicated LL-gather + inter-rank combine pair
    (``low_latency_allgather.py:700-779`` + ``flash_decode.py:481-532``),
    collapsed into a single kernel because a Mosaic kernel can both move
    and compute.  ``plane_in`` [BH, d+128] packs out rows with the
    lane-broadcast lse (one DMA per peer, one semaphore stream — the
    [BH, d] ⊕ [BH, 128] split costs one extra 128-lane block but halves
    the descriptor count vs two planes).
    """
    dl.barrier_all(axis)  # nobody lands data in a peer still outside

    # The gather round IS the fcollect verb: stage my slot (overlapped
    # with the peer fan-out, which reads the input ref), push to every
    # peer, drain, wait arrivals.
    dl.fcollect(plane_in, gath, send_sem, recv_sem, axis,
                copy_sem=copy_sem)

    # LSE-weighted merge on the VPU (combine_partials' math, in-kernel).
    bh = plane_in.shape[0]
    planes = gath[:].reshape(world, bh, d + 128)
    lses = planes[:, :, d:]                             # [W, BH, 128]
    m = jnp.max(lses, axis=0)                           # [BH, 128]
    w = jnp.exp(lses - m[None])                         # [W, BH, 128]
    denom = jnp.sum(w, axis=0)                          # [BH, 128]
    out = jnp.sum(planes[:, :, :d] * w[:, :, :1], axis=0)  # [BH, D]
    final_ref[:] = out / denom[:, :1]


# Scoped VMEM the fused combine may plan for, of Mosaic's 16 MiB: the
# gather scratch plus the merge that reads it back as one value — the v5e
# measured 32.4 MB for a 16 MiB scratch and ran a 4 MiB one (chip runs,
# PR 21), so the kernel is sized at twice its scratch.  The same 12 MiB
# the paged kernel budgets for its page blocks.
_SP_COMBINE_VMEM = 12 * 2 ** 20


def sp_combine_shard(out, lse, *, axis, interpret=False,
                     collective_id=SP_DECODE_COLLECTIVE_ID):
    """Fused gather+combine of per-rank decode partials; call inside
    shard_map.  out [B, Hq, D] f32, lse [B, Hq] f32 → [B, Hq, D] f32.

    The kernel keeps every rank's plane in VMEM, which fits a decode
    step's B*Hq rows but not a seq-layout prefill chunk's c*Hq or a
    k-token verify's.  Rows are independent, so past the budget the plane
    is cut into equal 8-row-aligned blocks (zero rows pad the last: they
    merge to 0 and are dropped) and the SAME kernel runs once per block,
    in order, under ``lax.map``."""
    world = jax.lax.axis_size(axis)
    if world == 1:
        return out
    B, Hq, D = out.shape
    BH = B * Hq
    plane = jnp.concatenate(
        [out.reshape(BH, D),
         jnp.broadcast_to(lse.reshape(BH, 1), (BH, 128))], axis=1)

    def fused(plane):
        rows = plane.shape[0]
        return pl.pallas_call(
            functools.partial(_sp_combine_kernel, axis=axis, world=world,
                              d=D),
            out_shape=jax.ShapeDtypeStruct((rows, D), jnp.float32),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[
                # flat [world*rows, D+128]: dl.fcollect's slot layout
                pltpu.VMEM((world * rows, D + 128), jnp.float32),
                pltpu.SemaphoreType.DMA,
                pltpu.SemaphoreType.DMA,
                pltpu.SemaphoreType.DMA,
            ],
            compiler_params=dl.collective_compiler_params(world,
                                                          collective_id),
            interpret=maybe_interpret(interpret),
        )(plane)

    n = -(-2 * world * BH * (D + 128) * 4 // _SP_COMBINE_VMEM)
    if n == 1:
        return fused(plane).reshape(B, Hq, D)
    blk = -(-BH // (8 * n)) * 8
    plane = jnp.pad(plane, ((0, n * blk - BH), (0, 0)))
    final = jax.lax.map(fused, plane.reshape(n, blk, D + 128))
    return final.reshape(n * blk, D)[:BH].reshape(B, Hq, D)


def combine_partials(outs, lses):
    """LSE-weighted merge of per-rank partials: outs [W, B, H, D] f32,
    lses [W, B, H] f32 -> [B, H, D] f32.

    Reference analog: ``kernel_inter_rank_gqa_fwd_batch_decode_combine_kv``
    (flash_decode.py:481-532) — the same online-softmax rescale, as a fused
    XLA elementwise pass instead of a hand kernel (decode partials are KB).
    """
    m = jnp.max(lses, axis=0, keepdims=True)                    # [1, B, H]
    w = jnp.exp(lses - m)                                       # [W, B, H]
    denom = jnp.sum(w, axis=0)                                  # [B, H]
    out = jnp.sum(outs * w[..., None], axis=0)                  # [B, H, D]
    return out / denom[..., None]


# ---------------------------------------------------------------------------
# Sequence-parallel decode (shard + host entries)
# ---------------------------------------------------------------------------


def sp_gqa_decode_shard(q, k_shard, v_shard, kv_lens, *, axis, block_s=None,
                        impl="auto", interpret=False, k_scale=None,
                        v_scale=None, soft_cap=0.0, window=0, q_lens=None):
    """Per-device SP decode: local split-KV partials -> comm-fused combine
    (``sp_combine_shard``; the XLA-only mode falls back to LL gather +
    epilogue).  ``kv_lens`` are GLOBAL lengths; the shard
    owns global rows [me*S_loc, (me+1)*S_loc).  Optional ``k/v_scale``
    [B, Hkv, S_loc] dequantize an int8 cache shard.

    Reference analog: ``SpGQAFlashDecodeAttention.forward``
    (sp_flash_decode_layer.py:78-184).
    """
    B, Hq, D = q.shape[0], q.shape[-2], q.shape[-1]
    multi = q.ndim == 4
    S_loc = k_shard.shape[2]
    me = jax.lax.axis_index(axis)
    world = jax.lax.axis_size(axis)
    ends = (kv_lens - me * S_loc).astype(jnp.int32)  # unclipped local end
    local_lens = jnp.clip(ends, 0, S_loc)

    out, lse = gqa_decode_shard(q, k_shard, v_shard, local_lens,
                                block_s=block_s, impl=impl,
                                interpret=interpret, k_scale=k_scale,
                                v_scale=v_scale, soft_cap=soft_cap,
                                window=window,
                                window_lens=ends if (window or multi)
                                else None,
                                q_lens=q_lens)
    # Comm-fused combine kernel by default — remote DMA of the (out, lse)
    # partial planes and the LSE merge in ONE Pallas kernel (VERDICT
    # round-1 missing #2); xla mode keeps the packed LL gather + epilogue.
    if multi:
        # [B, T, ...] partials combine like a B*T batch; dead rows carry
        # lse = NEG on every rank and merge to 0.
        T = out.shape[1]
        c = _combine_across_ranks(out.reshape(B * T, Hq, D),
                                  lse.reshape(B * T, Hq), q.dtype,
                                  axis=axis, impl=impl, interpret=interpret)
        return c.reshape(B, T, Hq, D)
    return _combine_across_ranks(out, lse, q.dtype, axis=axis, impl=impl,
                                 interpret=interpret)


@dataclass
class SpDecodeContext:
    """Sizing/mesh context (reference analog: the create_*_context factories,
    flash_decode.py:534-585)."""

    mesh: Mesh
    axis: str = "sp"
    block_s: int | None = None  # None = full-shard chunk (min(S, 8192))
    impl: str = "auto"
    interpret: bool = False
    soft_cap: float = 0.0  # Gemma-2 logit capping; 0 = off
    window: int = 0  # sliding window (global rule, any world; 0 = off)

    @property
    def world(self) -> int:
        return self.mesh.shape[self.axis]


def create_sp_decode_context(mesh, axis="sp", block_s=None, impl="auto",
                             interpret=False, soft_cap=0.0,
                             window=0) -> SpDecodeContext:
    # ``window`` composes with SP sharding (r5): each shard intersects
    # the global window [kv_len - window, kv_len) with its own range via
    # the unclipped ``window_lens``; shards wholly outside contribute
    # lse = NEG_INF partials that the combine ignores.
    return SpDecodeContext(mesh=mesh, axis=axis, block_s=block_s, impl=impl,
                           interpret=interpret, soft_cap=soft_cap,
                           window=window)


def sp_gqa_decode(q, k_cache, v_cache, kv_lens, ctx: SpDecodeContext):
    """Host entry.  q [B, Hq, D] replicated; k/v_cache [B, Hkv, S, D] sharded
    on the sequence dim over ``ctx.axis``; kv_lens [B] global lengths.
    Returns [B, Hq, D] replicated.

    Reference analog: ``gqa_fwd_batch_decode`` host wrappers
    (flash_decode.py:763-1160).
    """
    fn = cached_shard_jit(
        sp_gqa_decode_shard,
        ctx.mesh,
        (P(), P(None, None, ctx.axis), P(None, None, ctx.axis), P()),
        P(),
        axis=ctx.axis, block_s=ctx.block_s, impl=ctx.impl,
        interpret=ctx.interpret, soft_cap=ctx.soft_cap, window=ctx.window,
    )
    # Launch metadata (profiling.annotate contract): decode is the
    # HBM-bound KV-shard read per rank; wire = the packed (out ⊕ lse)
    # partial planes every rank exchanges for the combine.
    from triton_dist_tpu.runtime.profiling import annotate

    B, Hq, D = q.shape[0], q.shape[-2], q.shape[-1]
    world = max(ctx.world, 1)
    el = jnp.dtype(k_cache.dtype).itemsize
    with annotate("sp_gqa_decode",
                  flops=4 * B * Hq * (k_cache.shape[2] // world) * D,
                  bytes_accessed=(k_cache.nbytes + v_cache.nbytes)
                  // world
                  + B * Hq * (D + 1) * 4 * (world - 1)):
        return fn(q, k_cache, v_cache, kv_lens)
