"""Pallas flash-attention prefill — blockwise causal GQA forward.

Reference analog: none file-for-file — the reference's attention story is
decode-side only (``flash_decode.py``); its prefill runs through whatever
dense attention the host model uses.  This module closes the gap the other
way round: the repo's model families (llama.py / moe.py / ulysses) computed
prefill attention as a dense XLA einsum that materializes the full
[B, H, S, S] logits tensor in HBM — at S = 8192, Hq = 32 that is 8.6 GB of
f32 score traffic *per layer*, which caps practical context length and
wastes the bandwidth the MXU needs.  Flash attention keeps the working set
at one [block_q, block_k] tile per step and carries online-softmax
statistics in VMEM — O(S) memory, one pass over K/V.

TPU-native design (the same shape as the repo's split-KV decode kernel,
``flash_decode.py:_decode_kernel``, applied to prefill):

* Grid ``(B, Hkv, nQ, nK)``; the KV axis is innermost and sequential
  ("arbitrary"), carrying the online-softmax accumulator (acc, m, l) in
  VMEM scratch across KV blocks; (B, Hkv, nQ) are ``parallel`` so Mosaic
  pipelines across block boundaries (the +14% knob from the GEMM sweep).
* GQA is folded into the q block: the q-head group dimension G = Hq//Hkv
  rides inside the block ([G, bq, D] per (batch, kv-head)), so the QK and
  PV matmuls are single MXU calls of [G*bq, D] x [D, bk] — no K/V
  ``jnp.repeat`` ever materializes (the dense path repeats K/V G times).
* K/V feed the MXU in their storage dtype; P casts down to V's dtype for
  the PV matmul (both matmuls stay on the MXU fast path — the round-2
  decode-kernel lesson).
* ``q_offset``/``kv_offset`` ride as **scalar prefetch** (SMEM), so the
  chunked-prefill caller (models/generate.py:_attend_prefix, whose
  ``prefix_len`` is a traced scalar) reuses ONE trace across chunks.
* Fully-masked causal blocks (k_start > q_end) skip their compute via
  ``pl.when`` — ~2x fewer MXU ops for causal prefill.  Their DMAs still
  stream (the rectangular grid cannot be shortened data-dependently), but
  prefill at real S is MXU-bound, not bandwidth-bound.
* ``return_lse`` exposes the per-row log-sum-exp in the same [G-packed]
  f32 layout the decode combine uses — the building block for ring /
  sequence-parallel prefill merging (the blockwise LSE-merge math of
  ``flash_decode.combine_partials``).
"""

from __future__ import annotations

import functools
import math
import operator

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu.kernels.gemm import (
    apply_soft_cap,
    largest_divisor_block,
    resolve_impl,
    use_fallback,
)
from triton_dist_tpu.language.interpret import maybe_interpret

NEG_INF = -1.0e30  # finite -inf proxy: survives exp/log without NaNs


# ---------------------------------------------------------------------------
# Kernel
# ---------------------------------------------------------------------------


def _visibility_mask(q_start, k_start, *, causal, window, group, bq, bk):
    """THE masking rule, shared by the forward/int8/backward kernels so
    they can never diverge: key at kpos is visible to the query at qpos
    iff (not causal or qpos >= kpos) and (not window or
    qpos - kpos < window).  Returns a [G, bq, bk] bool mask (only called
    when causal or window is set)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (group, bq, bk), 1)
    cols = jax.lax.broadcasted_iota(jnp.int32, (group, bq, bk), 2)
    qpos = q_start + rows
    kpos = k_start + cols
    if causal and window:
        return (qpos >= kpos) & (qpos - kpos < window)
    if causal:
        return qpos >= kpos
    return qpos - kpos < window


def _block_live(q_start, k_start, *, causal, window, bq, bk):
    """Whole-block skip predicate matching :func:`_visibility_mask`:
    False when no (qpos, kpos) pair in the block is visible."""
    live = True
    if causal:
        # block entirely in the future of every q row
        live = k_start <= q_start + (bq - 1)
    if window:
        # block entirely past every q row's window
        live = live & (k_start + (bk - 1) > q_start - window)
    return live


def _block_full(q_start, k_start, *, causal, window, bq, bk):
    """Whole-block FULL-visibility predicate matching
    :func:`_visibility_mask`: True when EVERY (qpos, kpos) pair in the
    block is visible — such blocks route to a mask-free kernel body (r5:
    the ceiling experiment showed the per-element mask build, not the
    MXU feed, bounds the causal prefill; at bq=128/bk=1024 ~7 of 8 live
    causal blocks qualify).  Shared by the bf16/int8 kernels so the
    routing can never diverge from the mask itself."""
    full = True
    if causal:
        # every row's last visible key covers the whole block
        full = q_start >= k_start + (bk - 1)
    if window:
        # ...and the earliest row's window still reaches column 0
        full = full & ((q_start + (bq - 1)) - k_start < window)
    return full


def _flash_kernel(qoffs_ref, koffs_ref, q_ref, k_ref, v_ref, out_ref,
                  lse_ref, acc_ref, m_ref, l_ref, *, bq, bk, n_k, causal,
                  scale, group, soft_cap=0.0, window=0):
    """Grid (B, Hkv, nQ, nK); one (batch, kv-head, q-block) accumulates
    across the sequential KV-block axis.

    Block shapes: q/out [1, 1, G, bq, D]; k/v [1, 1, bk, D];
    lse [1, 1, G, bq] f32.  Scratch: acc [G, bq, D], m/l [G, bq] f32 —
    3D/2D per-row state so every reshape in the kernel only splits or
    collapses LEADING dims (free in Mosaic; lane-changing reshapes are
    relayouts).

    ``qoffs/koffs`` [nQ]/[nK] scalar-prefetch vectors give each BLOCK its
    global start position — contiguous layouts get an arithmetic ramp;
    segmented layouts (the zigzag CP shard: two position runs per device)
    get per-run ramps.  Rows within one block are always contiguous.
    """
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    iq = pl.program_id(2)
    q_start = qoffs_ref[iq]               # global position of q row 0
    k_start = koffs_ref[ik]               # global position of k row 0

    def body(masked):
        q = q_ref[0, 0].reshape(group * bq, -1)           # [G*bq, D]
        k = k_ref[0, 0]                                   # [bk, D]
        v = v_ref[0, 0]                                   # [bk, D]

        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).reshape(
                group, bq, bk) * scale                    # [G, bq, bk]
        logits = apply_soft_cap(logits, soft_cap)
        # (A base-2 exp fold — exp2 with log2e in the scale — measured
        # NO gain here: Mosaic already lowers exp that way.  r5 ceiling
        # experiment, scripts/exp_prefill_ceiling.py at git d7c7cac.)

        if masked:
            mask = _visibility_mask(q_start, k_start, causal=causal,
                                    window=window, group=group, bq=bq,
                                    bk=bk)
            logits = jnp.where(mask, logits, NEG_INF)

        m_cur = m_ref[:]                                  # [G, bq]
        m_new = jnp.maximum(m_cur, jnp.max(logits, axis=-1))
        # m only grows; rows with nothing visible yet stay at NEG_INF and
        # exp(NEG - NEG) = 1 would poison them — mask p explicitly.
        p = jnp.exp(logits - m_new[..., None])            # [G, bq, bk]
        if masked:
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_cur - m_new)                    # [G, bq]
        m_ref[:] = m_new
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(
            p.reshape(group * bq, bk).astype(v.dtype), v,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [G*bq, D]
        acc_ref[:] = (acc_ref[:] * alpha[..., None]
                      + pv.reshape(group, bq, -1))

    if causal or window:
        # Skip blocks with no visible (qpos, kpos) pair — their DMAs
        # already streamed; compute is the prefill bottleneck.  Among
        # the LIVE blocks, route fully-visible ones to the MASK-FREE
        # body (the r5 ceiling fix, docs/perf.md "Flash-attention
        # prefill": +7.5% paired; see _block_full).
        live = _block_live(q_start, k_start, causal=causal,
                           window=window, bq=bq, bk=bk)
        full = _block_full(q_start, k_start, causal=causal,
                           window=window, bq=bq, bk=bk)
        pl.when(live & full)(functools.partial(body, False))
        pl.when(live & jnp.logical_not(full))(functools.partial(body, True))
    else:
        body(False)

    @pl.when(ik == n_k - 1)
    def _():
        l = l_ref[:]                                      # [G, bq]
        # All-masked rows (ring: KV wholly in future) have acc == 0 and
        # l == 0: clamping the divisor yields 0/tiny = 0 without a bool
        # minor-dim insert (Mosaic only supports those for 32-bit types).
        out = acc_ref[:] / jnp.maximum(l, 1e-30)[..., None]
        out_ref[0, 0] = out.astype(out_ref.dtype)
        lse_ref[0, 0] = jnp.where(
            l > 0.0, m_ref[:] + jnp.log(jnp.maximum(l, 1e-30)), NEG_INF)


def _flash_kernel_i8(qoffs_ref, koffs_ref, q_ref, k_ref, v_ref, ks_ref,
                     vs_ref, out_ref, lse_ref, acc_ref, m_ref, l_ref, *,
                     bq, bk, n_k, causal, scale, group, soft_cap=0.0,
                     window=0):
    """int8-KV twin of :func:`_flash_kernel` (the decode `_decode_kernel_i8`
    recipe applied to prefill): K/V stream as int8 with per-position f32
    scales riding LANE-PACKED [B, Hkv, Sk/128, 128] planes — K's scale
    rescales the logit columns after the QK matmul, V's folds into P
    before the PV matmul; both matmuls stay on the MXU in q's dtype."""
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    iq = pl.program_id(2)
    q_start = qoffs_ref[iq]
    k_start = koffs_ref[ik]

    def body(masked):
        q = q_ref[0, 0].reshape(group * bq, -1)           # [G*bq, D]
        k = k_ref[0, 0].astype(q.dtype)                   # [bk, D] i8→q
        v = v_ref[0, 0].astype(q.dtype)
        ksc = ks_ref[0, 0].reshape(-1)                    # [bk] f32
        vsc = vs_ref[0, 0].reshape(-1)

        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        logits = (logits * (ksc[None, :] * scale)).reshape(group, bq, bk)
        logits = apply_soft_cap(logits, soft_cap)

        if masked:
            mask = _visibility_mask(q_start, k_start, causal=causal,
                                    window=window, group=group, bq=bq,
                                    bk=bk)
            logits = jnp.where(mask, logits, NEG_INF)

        m_cur = m_ref[:]
        m_new = jnp.maximum(m_cur, jnp.max(logits, axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        if masked:
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_cur - m_new)
        m_ref[:] = m_new
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(
            (p.reshape(group * bq, bk) * vsc[None, :]).astype(v.dtype), v,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[:] = (acc_ref[:] * alpha[..., None]
                      + pv.reshape(group, bq, -1))

    if causal or window:
        # Mask-free routing for fully-visible blocks (see _block_full).
        live = _block_live(q_start, k_start, causal=causal,
                           window=window, bq=bq, bk=bk)
        full = _block_full(q_start, k_start, causal=causal,
                           window=window, bq=bq, bk=bk)
        pl.when(live & full)(functools.partial(body, False))
        pl.when(live & jnp.logical_not(full))(functools.partial(body, True))
    else:
        body(False)

    @pl.when(ik == n_k - 1)
    def _():
        l = l_ref[:]
        out = acc_ref[:] / jnp.maximum(l, 1e-30)[..., None]
        out_ref[0, 0] = out.astype(out_ref.dtype)
        lse_ref[0, 0] = jnp.where(
            l > 0.0, m_ref[:] + jnp.log(jnp.maximum(l, 1e-30)), NEG_INF)


# ---------------------------------------------------------------------------
# Backward kernels (flash gradient — no S^2 materialization)
# ---------------------------------------------------------------------------
#
# Standard flash-attention backward split into two kernels so each output
# has one sequential accumulation axis:
#   dq kernel : grid (B, Hkv, nQ, nK) — KV innermost, dq block in scratch
#   dkv kernel: grid (B, Hkv, nK, nQ) — Q innermost, dk/dv blocks in scratch
# Both recompute P from (q, k, lse) blockwise:
#   p_ij  = exp(scale * q_i k_j - lse_i)          (0 where causally masked)
#   dv_j  = sum_i p_ij do_i
#   dp_ij = do_i . v_j
#   ds_ij = p_ij * (dp_ij - delta_i) * scale,  delta_i = sum(do_i * out_i)
#   dq_i  = sum_j ds_ij k_j ;  dk_j = sum_i ds_ij q_i
# delta is a cheap elementwise rowsum computed in XLA before the kernels.


def _recompute_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, q_start,
                    k_start, *, causal, scale, group, bq, bk,
                    soft_cap=0.0, window=0, masked=True):
    """Shared backward block math: recompute P from (q, k, lse) and form
    dS — the one place the masking/NEG_INF rules live for both backward
    kernels.  Returns (p, ds) [G, bq, bk] f32 plus the flat q/do views.

    exp may produce inf in lanes the mask discards (fully-masked rows
    carry lse = NEG_INF); the where keeps them out of the matmuls.
    ``masked=False`` (r5): the caller proved the whole block fully
    visible (`_block_full`) — skip the per-element mask build, the same
    routing as the forward kernels.
    """
    q = q_ref[0, 0].reshape(group * bq, -1)               # [G*bq, D]
    k = k_ref[0, 0]                                       # [bk, D]
    v = v_ref[0, 0]
    do = do_ref[0, 0].reshape(group * bq, -1)             # [G*bq, D]
    lse = lse_ref[0, 0]                                   # [G, bq]
    dl = dl_ref[0, 0]                                     # [G, bq]

    s_raw = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32).reshape(group, bq, bk) * scale
    if soft_cap:
        t = jnp.tanh(s_raw / soft_cap)
        s = soft_cap * t
        dcap = 1.0 - t * t          # d(cap*tanh(x/cap))/dx
    else:
        s = s_raw
        dcap = None
    e = jnp.exp(s - lse[..., None])
    if masked and (causal or window):
        p = jnp.where(_visibility_mask(q_start, k_start, causal=causal,
                                       window=window, group=group, bq=bq,
                                       bk=bk), e, 0.0)
    else:
        p = e
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32).reshape(group, bq, bk)
    ds = p * (dp - dl[..., None]) * scale                 # [G, bq, bk]
    if dcap is not None:
        ds = ds * dcap              # chain rule through the capping tanh
    return p, ds, q, do


def _flash_bwd_dq_kernel(qoffs_ref, koffs_ref, q_ref, k_ref, v_ref,
                         do_ref, lse_ref, dl_ref, dq_ref, acc_ref, *, bq,
                         bk, n_k, causal, scale, group, soft_cap=0.0,
                         window=0):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    iq = pl.program_id(2)
    q_start = qoffs_ref[iq]
    k_start = koffs_ref[ik]

    def body(masked):
        k = k_ref[0, 0]                                   # [bk, D]
        _, ds, _, _ = _recompute_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, q_start,
            k_start, causal=causal, scale=scale, group=group, bq=bq, bk=bk,
            soft_cap=soft_cap, window=window, masked=masked)
        upd = jax.lax.dot_general(
            ds.reshape(group * bq, bk).astype(k.dtype), k,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [G*bq, D]
        acc_ref[:] = acc_ref[:] + upd.reshape(group, bq, -1)

    if causal or window:
        live = _block_live(q_start, k_start, causal=causal,
                           window=window, bq=bq, bk=bk)
        full = _block_full(q_start, k_start, causal=causal,
                           window=window, bq=bq, bk=bk)
        pl.when(live & full)(functools.partial(body, False))
        pl.when(live & jnp.logical_not(full))(functools.partial(body, True))
    else:
        body(False)

    @pl.when(ik == n_k - 1)
    def _():
        dq_ref[0, 0] = acc_ref[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(qoffs_ref, koffs_ref, q_ref, k_ref, v_ref,
                          do_ref, lse_ref, dl_ref, dk_ref, dv_ref, dk_acc,
                          dv_acc, *, bq, bk, n_q, causal, scale, group,
                          soft_cap=0.0, window=0):
    iq = pl.program_id(3)

    @pl.when(iq == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    ikb = pl.program_id(2)
    q_start = qoffs_ref[iq]
    k_start = koffs_ref[ikb]

    def body(masked):
        p, ds, q, do = _recompute_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, q_start,
            k_start, causal=causal, scale=scale, group=group, bq=bq, bk=bk,
            soft_cap=soft_cap, window=window, masked=masked)
        # dv_j = sum_i p_ij do_i  — contract over the G*bq row axis.
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            p.reshape(group * bq, bk).astype(do.dtype), do,
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [bk, D]
        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
            ds.reshape(group * bq, bk).astype(q.dtype), q,
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [bk, D]

    live = True
    if causal:
        # This KV block gets gradient only from q rows at positions
        # >= k_start; skip inner q blocks entirely before it.
        live = q_start + (bq - 1) >= k_start
    if window:
        # ...and only from q rows whose window still reaches it.
        live = live & (q_start < k_start + (bk - 1) + window)
    if causal or window:
        full = _block_full(q_start, k_start, causal=causal,
                           window=window, bq=bq, bk=bk)
        pl.when(live & full)(functools.partial(body, False))
        pl.when(live & jnp.logical_not(full))(functools.partial(body, True))
    else:
        body(False)

    @pl.when(iq == n_q - 1)
    def _():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _as_starts(starts_or_offset):
    """Normalize an offset-like argument to a tuple of run starts: a
    scalar offset means ONE contiguous run."""
    if isinstance(starts_or_offset, (tuple, list)):
        return tuple(starts_or_offset)
    return (starts_or_offset,)


def _block_starts(starts, total, blk):
    """[n_blocks] int32 per-block global start positions: ``total`` rows
    split evenly over ``len(starts)`` runs, each run split into ``blk``-row
    blocks.  Works for python ints and traced scalars alike (the result
    rides scalar prefetch)."""
    n_runs = len(starts)
    run = total // n_runs
    assert run % blk == 0, (total, n_runs, blk)
    ramp = jnp.arange(run // blk, dtype=jnp.int32) * blk
    return (jnp.stack([jnp.asarray(s, jnp.int32) for s in starts])[:, None]
            + ramp[None, :]).reshape(-1)


def _bwd_blocks(Sq, Sk, n_runs_q, n_runs_k, block_q, block_k):
    """Backward block sizes, clamped to the RUN length so every block's
    rows are position-contiguous (segmented layouts)."""
    bq = largest_divisor_block(Sq // n_runs_q, block_q or 128, 128)
    bk = largest_divisor_block(Sk // n_runs_k, block_k or 512, 128)
    return bq, bk


def _flash_bwd_pallas(q, k, v, out, lse, do, q_offset, kv_offset, causal,
                      scale, interpret, soft_cap=0.0, block_q=None,
                      block_k=None, window=0, grad_dtype=None):
    """Blockwise gradients (dq, dk, dv) in the primal dtypes, or in
    ``grad_dtype`` when set (the ring caller asks for f32 so its cross-ring
    accumulation never rounds per-block summands to bf16).

    ``q_offset``/``kv_offset`` may each be a scalar (one contiguous run)
    or a tuple of run starts (segmented layout — the zigzag CP shard).

    Default blocks (bq=128, bk=512) from the r4 chip sweep
    (docs/perf.md "Flash-attention prefill"); both kernels keep more
    operands resident than the forward (q, k, v, do + two accumulators),
    so the forward's bk=1024 does NOT transfer."""
    B, Hq, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    g = Hq // Hkv
    q_starts = _as_starts(q_offset)
    kv_starts = _as_starts(kv_offset)
    bq, bk = _bwd_blocks(Sq, Sk, len(q_starts), len(kv_starts), block_q,
                         block_k)
    n_q, n_k = Sq // bq, Sk // bk
    dq_dtype = grad_dtype or q.dtype
    dk_dtype = grad_dtype or k.dtype
    dv_dtype = grad_dtype or v.dtype

    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                               # [B, Hq, Sq]
    qg = q.reshape(B, Hkv, g, Sq, D)
    dog = do.reshape(B, Hkv, g, Sq, D)
    lseg = lse.reshape(B, Hkv, g, Sq)
    dlg = delta.reshape(B, Hkv, g, Sq)
    qoffs = _block_starts(q_starts, Sq, bq)
    koffs = _block_starts(kv_starts, Sk, bk)

    q_spec = pl.BlockSpec((1, 1, g, bq, D),
                          lambda b, h, i, j, qo, ko: (b, h, 0, i, 0))
    row_spec = pl.BlockSpec((1, 1, g, bq),
                            lambda b, h, i, j, qo, ko: (b, h, 0, i))
    kv_spec = pl.BlockSpec((1, 1, bk, D),
                           lambda b, h, i, j, qo, ko: (b, h, j, 0))
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, bq=bq, bk=bk, n_k=n_k,
                          causal=causal, scale=float(scale), group=g,
                          soft_cap=soft_cap, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, Hkv, n_q, n_k),
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
            out_specs=[q_spec],
            scratch_shapes=[pltpu.VMEM((g, bq, D), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, Hkv, g, Sq, D), dq_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=maybe_interpret(interpret),
    )(qoffs, koffs, qg, k, v, dog, lseg, dlg)[0]

    # dkv: Q axis innermost/sequential; note the (i, j) grid roles swap.
    q_spec2 = pl.BlockSpec((1, 1, g, bq, D),
                           lambda b, h, j, i, qo, ko: (b, h, 0, i, 0))
    row_spec2 = pl.BlockSpec((1, 1, g, bq),
                             lambda b, h, j, i, qo, ko: (b, h, 0, i))
    kv_spec2 = pl.BlockSpec((1, 1, bk, D),
                            lambda b, h, j, i, qo, ko: (b, h, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, bq=bq, bk=bk, n_q=n_q,
                          causal=causal, scale=float(scale), group=g,
                          soft_cap=soft_cap, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, Hkv, n_k, n_q),
            in_specs=[q_spec2, kv_spec2, kv_spec2, q_spec2, row_spec2,
                      row_spec2],
            out_specs=[kv_spec2, kv_spec2],
            scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                            pltpu.VMEM((bk, D), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, Hkv, Sk, D), dk_dtype),
                   jax.ShapeDtypeStruct((B, Hkv, Sk, D), dv_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=maybe_interpret(interpret),
    )(qoffs, koffs, qg, k, v, dog, lseg, dlg)
    return dq.reshape(B, Hq, Sq, D), dk, dv


# ---------------------------------------------------------------------------
# Dense fallback (XLA) — same contract incl. offsets and lse
# ---------------------------------------------------------------------------


def _run_positions(starts, total):
    """[total] int32 global positions for ``total`` rows split evenly over
    the runs in ``starts`` (scalar offset ≡ one run)."""
    starts = _as_starts(starts)
    run = total // len(starts)
    ramp = jnp.arange(run, dtype=jnp.int32)
    return (jnp.stack([jnp.asarray(s, jnp.int32) for s in starts])[:, None]
            + ramp[None, :]).reshape(-1)


def _flash_xla(q, k, v, *, causal, scale, q_offset, kv_offset,
               k_scale=None, v_scale=None, soft_cap=0.0, window=0):
    """O(S^2)-memory reference path: out [B, Hq, Sq, D] in q.dtype,
    lse [B, Hq, Sq] f32.  Optional ``k/v_scale`` [B, Hkv, Sk] dequantize
    an int8 K/V (the decode `_local_decode_xla` recipe).  Offsets may be
    run-start tuples (segmented layouts)."""
    B, Hq, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    g = Hq // Hkv
    qf = q.astype(jnp.float32).reshape(B, Hkv, g, Sq, D)
    logits = jnp.einsum("bhgsd,bhtd->bhgst", qf,
                        k.astype(jnp.float32)) * scale
    if k_scale is not None:
        logits = logits * k_scale[:, :, None, None, :]
    logits = apply_soft_cap(logits, soft_cap)
    if causal or window:
        rows = _run_positions(q_offset, Sq)[:, None]
        cols = _run_positions(kv_offset, Sk)[None, :]
        mask = (rows >= cols) if causal else jnp.ones(
            (Sq, Sk), bool)                               # [Sq, Sk]
        if window:
            mask = mask & (rows - cols < window)
        logits = jnp.where(mask[None, None, None], logits, NEG_INF)
    m = jnp.max(logits, axis=-1)                          # [B,Hkv,g,Sq]
    nonempty = m > NEG_INF / 2
    p = jnp.exp(logits - m[..., None])
    if causal or window:
        p = jnp.where(mask[None, None, None], p, 0.0)
    l = jnp.sum(p, axis=-1)
    if v_scale is not None:
        p = p * v_scale[:, :, None, None, :]
    out = jnp.einsum("bhgst,bhtd->bhgsd", p, v.astype(jnp.float32))
    out = jnp.where(nonempty[..., None],
                    out / jnp.where(nonempty, l, 1.0)[..., None], 0.0)
    lse = jnp.where(nonempty, m + jnp.log(jnp.where(nonempty, l, 1.0)),
                    NEG_INF)
    return (out.reshape(B, Hq, Sq, D).astype(q.dtype),
            lse.reshape(B, Hq, Sq))


# ---------------------------------------------------------------------------
# Dispatcher
# ---------------------------------------------------------------------------


def flash_shapes_ok(sq: int, sk: int, d: int, n_runs_q: int = 1,
                    n_runs_k: int = 1) -> bool:
    """Lane/sublane legality for the flash tiles: q/k blocks need 128-lane
    D, and the lse output block's lane dim is the q-block (so Sq must tile
    by 128); Sk tiles by 128 for the KV blocks.  Segmented layouts need
    each RUN to tile by 128 (blocks never straddle a run boundary)."""
    return (d % 128 == 0 and sq % n_runs_q == 0 and sk % n_runs_k == 0
            and (sq // n_runs_q) % 128 == 0 and (sk // n_runs_k) % 128 == 0)


def flash_attention(q, k, v, *, causal=True, scale=None, q_offset=0,
                    kv_offset=0, block_q=None, block_k=None, impl="auto",
                    interpret=False, return_lse=False, k_scale=None,
                    v_scale=None, soft_cap=0.0, window=0):
    """Public entry: :func:`_flash_attention_dispatch` under a
    ``profiling.annotate`` launch-metadata span (name/flops/bytes land
    in the profiler timeline — the contract every public kernel entry
    point keeps, enforced by the tests/test_observability.py
    annotation meta-test).  Causal masking halves the score flops."""
    from triton_dist_tpu.runtime.profiling import annotate

    B, Hq, Sq, D = q.shape
    Sk = k.shape[2]
    el = jnp.dtype(q.dtype).itemsize
    flops = 4 * B * Hq * Sq * Sk * D // (2 if causal else 1)
    with annotate("flash_attention", flops=flops,
                  bytes_accessed=(q.size + k.size + v.size
                                  + q.size) * el):
        return _flash_attention_dispatch(
            q, k, v, causal=causal, scale=scale, q_offset=q_offset,
            kv_offset=kv_offset, block_q=block_q, block_k=block_k,
            impl=impl, interpret=interpret, return_lse=return_lse,
            k_scale=k_scale, v_scale=v_scale, soft_cap=soft_cap,
            window=window)


def _flash_attention_dispatch(q, k, v, *, causal=True, scale=None,
                              q_offset=0, kv_offset=0, block_q=None,
                              block_k=None, impl="auto",
                              interpret=False, return_lse=False,
                              k_scale=None, v_scale=None, soft_cap=0.0,
                              window=0):
    """Blockwise GQA attention: q [B, Hq, Sq, D], k/v [B, Hkv, Sk, D] →
    out [B, Hq, Sq, D] in q.dtype (+ lse [B, Hq, Sq] f32 when
    ``return_lse``).

    ``q_offset``/``kv_offset`` are the global positions of q row 0 / k
    row 0 (python ints or traced scalars — they ride scalar prefetch, so
    chunked prefill reuses one trace across chunks).  The causal rule is
    ``q_offset + i >= kv_offset + j``.

    ``k_scale``/``v_scale`` [B, Hkv, Sk] f32 dequantize an int8 K/V
    (the serving int8-KV cache): the pallas path fuses the scales into
    the block loop (``_flash_kernel_i8``), the fallback into the dense
    stream.  The quantized path is forward-only (serving).

    ``window`` (sliding-window attention, Mistral-style): key at kpos is
    visible iff ``qpos - kpos < window`` (the current token counts, so
    position qpos attends to [qpos - window + 1, qpos]); composes with
    the offsets and with ``causal``, and blocks wholly outside the
    window skip their compute — differentiable like the causal path.

    SEGMENTED layouts: ``q_offset``/``kv_offset`` may each be a TUPLE of
    run starts — the rows then consist of len(tuple) equal-length
    position-contiguous runs (the zigzag CP shard holds chunks i and
    2w-1-i).  Blocks never straddle runs; each run must tile by 128 for
    the pallas path.
    """
    B, Hq, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    assert Hq % Hkv == 0, (Hq, Hkv)
    g = Hq // Hkv
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    raw_impl = impl
    impl = resolve_impl(impl, interpret)
    quantized = k_scale is not None
    n_runs_q = len(_as_starts(q_offset))
    n_runs_k = len(_as_starts(kv_offset))
    seg_q, seg_k = Sq // max(n_runs_q, 1), Sk // max(n_runs_k, 1)

    if use_fallback(raw_impl, impl,
                    flash_shapes_ok(Sq, Sk, D, n_runs_q, n_runs_k),
                    "flash_attention",
                    f"(Sq={Sq}, Sk={Sk}, D={D}, runs={n_runs_q}/{n_runs_k})"
                    f" needs each run %128 == 0 and D%128 == 0 (a 64-wide"
                    f" head: in pairs, flash_decode.pack_kv_pairs)"):
        out, lse = _flash_xla(q, k, v, causal=causal, scale=scale,
                              q_offset=q_offset, kv_offset=kv_offset,
                              k_scale=k_scale, v_scale=v_scale,
                              soft_cap=soft_cap, window=window)
        return (out, lse) if return_lse else out

    # Block defaults from the real-chip sweep (docs/perf.md): SMALL q
    # blocks win for causal prefill — bq=128 at G=4 runs ~107 TFLOPS vs
    # ~60 for bq=512/bk=512 (finer causal-skip granularity: the diagonal
    # blocks waste bq*bk/2 masked MXU ops, so shrinking bq cuts the waste
    # and the skip test prunes more k blocks per q row).  bk=1024 beats
    # 512 (longer MXU streams per grid step) and 2048+ (VMEM pressure
    # crowds the pipeline).  G*bq ~ 512 MXU rows balances group sizes.
    want_q = block_q or max(128, (512 // g) // 128 * 128)
    # Blocks fit the RUN (== the whole axis for contiguous layouts).
    bq = largest_divisor_block(seg_q, want_q, 128)
    bk = largest_divisor_block(seg_k, block_k or 1024, 128)

    if quantized:
        # Lane-packed scale planes need (bk//128) % 8 == 0 or bk == Sk
        # (the decode kernel's constraint — the bk == Sk escape is
        # WHOLE-ARRAY-block legality, so it does not apply to a segmented
        # run); bump to the smallest legal divisor of the run.
        # Forward-only — serving reads an int8 cache; training does not
        # quantize K/V.
        if (bk // 128) % 8 and bk != Sk:
            legal = next((c for c in range(bk, seg_k + 1, 128)
                          if seg_k % c == 0 and (c // 128) % 8 == 0), None)
            if legal is None and n_runs_k == 1:
                legal = Sk          # whole-array-block escape
            if legal is None:
                # Segmented run with no lane-pack-legal block: dense path.
                out, lse = _flash_xla(
                    q, k, v, causal=causal, scale=scale,
                    q_offset=q_offset, kv_offset=kv_offset,
                    k_scale=k_scale, v_scale=v_scale, soft_cap=soft_cap,
                    window=window)
                return (out, lse) if return_lse else out
            bk = legal
        out, lse = _flash_pallas(q, k, v, q_offset, kv_offset, causal,
                                 float(scale), bq, bk, interpret,
                                 k_scale=k_scale, v_scale=v_scale,
                                 soft_cap=soft_cap, window=window)
        return (out, lse) if return_lse else out

    def _static_int(x):
        """Any index-like (int, np.integer, concrete 0-d array) → int;
        run-start tuples → tuple of ints (hashable for the custom-VJP
        nondiff slot); traced offsets → None (they ride scalar prefetch,
        raw path)."""
        try:
            if isinstance(x, (tuple, list)):
                return tuple(operator.index(e) for e in x)
            return operator.index(x)
        except TypeError:
            return None

    qo, ko = _static_int(q_offset), _static_int(kv_offset)
    if not return_lse and qo is not None and ko is not None:
        # Static offsets (model forward paths): differentiable wrapper.
        # The backward is the blockwise flash gradient (dq + dkv pallas
        # kernels recomputing P from the saved lse) — O(S) memory on
        # both passes.
        return _flash_diff(q, k, v, qo, ko, causal,
                           float(scale), bq, bk, interpret, soft_cap,
                           window)
    out, lse = _flash_pallas(q, k, v, q_offset, kv_offset, causal,
                             float(scale), bq, bk, interpret,
                             soft_cap=soft_cap, window=window)
    return (out, lse) if return_lse else out


def _flash_pallas(q, k, v, q_offset, kv_offset, causal, scale, bq, bk,
                  interpret, k_scale=None, v_scale=None, soft_cap=0.0,
                  window=0):
    """The raw pallas_call: out [B, Hq, Sq, D] in q.dtype, lse f32.
    ``q_offset``/``kv_offset``: scalar or tuple of run starts (segmented
    layouts — the caller guarantees the run length divides by the block)."""
    B, Hq, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    g = Hq // Hkv
    n_q, n_k = Sq // bq, Sk // bk

    qg = q.reshape(B, Hkv, g, Sq, D)
    qoffs = _block_starts(_as_starts(q_offset), Sq, bq)
    koffs = _block_starts(_as_starts(kv_offset), Sk, bk)
    quantized = k_scale is not None
    if quantized:
        kern = functools.partial(_flash_kernel_i8, bq=bq, bk=bk, n_k=n_k,
                                 causal=causal, scale=float(scale), group=g,
                                 soft_cap=soft_cap, window=window)
    else:
        kern = functools.partial(_flash_kernel, bq=bq, bk=bk, n_k=n_k,
                                 causal=causal, scale=float(scale), group=g,
                                 soft_cap=soft_cap, window=window)
    in_specs = [
        pl.BlockSpec((1, 1, g, bq, D),
                     lambda b, h, i, j, qo, ko: (b, h, 0, i, 0)),
        pl.BlockSpec((1, 1, bk, D),
                     lambda b, h, i, j, qo, ko: (b, h, j, 0)),
        pl.BlockSpec((1, 1, bk, D),
                     lambda b, h, i, j, qo, ko: (b, h, j, 0)),
    ]
    args = [qoffs, koffs, qg, k, v]
    if quantized:
        # Lane-packed [B, Hkv, Sk//128, 128] scale planes: each block's
        # bk scales are ONE dense [bk//128, 128] f32 transfer (the
        # decode kernel's layout — a [bk, 1] plane DMAs thousands of
        # strided 4-byte rows and measured 9x slower).
        sc_spec = pl.BlockSpec((1, 1, bk // 128, 128),
                               lambda b, h, i, j, qo, ko: (b, h, j, 0))
        in_specs += [sc_spec, sc_spec]
        args += [k_scale.reshape(B, Hkv, Sk // 128, 128),
                 v_scale.reshape(B, Hkv, Sk // 128, 128)]
    out, lse = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, Hkv, n_q, n_k),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, 1, g, bq, D),
                             lambda b, h, i, j, qo, ko: (b, h, 0, i, 0)),
                pl.BlockSpec((1, 1, g, bq),
                             lambda b, h, i, j, qo, ko: (b, h, 0, i)),
            ],
            scratch_shapes=[
                pltpu.VMEM((g, bq, D), jnp.float32),
                pltpu.VMEM((g, bq), jnp.float32),
                pltpu.VMEM((g, bq), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, g, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((B, Hkv, g, Sq), jnp.float32),
        ],
        # Only the KV axis carries the accumulator; (b, h, iq) blocks are
        # independent — declaring them parallel lets Mosaic pipeline
        # across block boundaries (the 96%-MXU GEMM knob).
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=maybe_interpret(interpret),
    )(*args)
    return out.reshape(B, Hq, Sq, D), lse.reshape(B, Hq, Sq)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11))
def _flash_diff(q, k, v, q_offset, kv_offset, causal, scale, bq, bk,
                interpret, soft_cap=0.0, window=0):
    return _flash_pallas(q, k, v, q_offset, kv_offset, causal, scale, bq,
                         bk, interpret, soft_cap=soft_cap,
                         window=window)[0]


def _flash_diff_fwd(q, k, v, q_offset, kv_offset, causal, scale, bq, bk,
                    interpret, soft_cap=0.0, window=0):
    out, lse = _flash_pallas(q, k, v, q_offset, kv_offset, causal, scale,
                             bq, bk, interpret, soft_cap=soft_cap,
                             window=window)
    return out, (q, k, v, out, lse)


def _flash_diff_bwd(q_offset, kv_offset, causal, scale, bq, bk, interpret,
                    soft_cap, window, res, g):
    q, k, v, out, lse = res
    return _flash_bwd_pallas(q, k, v, out, lse, g, q_offset, kv_offset,
                             causal, scale, interpret, soft_cap=soft_cap,
                             window=window)


_flash_diff.defvjp(_flash_diff_fwd, _flash_diff_bwd)


# ---------------------------------------------------------------------------
# Autotuned entry + AOT registration (tooling parity with the GEMM family)
# ---------------------------------------------------------------------------

from triton_dist_tpu.autotuner import Config as _Cfg, autotune as _autotune

# Real-chip sweep (docs/perf.md): bq=128/bk=1024 wins causal prefill by
# ~25% over bq=512 (finer causal-skip granularity); the space brackets it.
FLASH_TUNE_SPACE = (
    _Cfg(block_q=128, block_k=1024),
    _Cfg(block_q=128, block_k=512),
    _Cfg(block_q=256, block_k=1024),
    _Cfg(block_q=512, block_k=512),
)


@_autotune(configs=FLASH_TUNE_SPACE, key=())
def _flash_tunable(q, k, v, *, causal, scale, interpret, block_q=None,
                   block_k=None):
    return flash_attention(q, k, v, causal=causal, scale=scale,
                           block_q=block_q, block_k=block_k,
                           impl="pallas", interpret=interpret)


def flash_attention_autotuned(q, k, v, *, causal=True, scale=None,
                              interpret=False):
    """:func:`flash_attention` with (block_q, block_k) selected by the
    autotuner — same lockstep/``is_dist`` rules as ``ag_gemm_autotuned``
    (winners cached per shape/dtype)."""
    return _flash_tunable(q, k, v, causal=causal, scale=scale,
                          interpret=interpret)


def _register_flash_aot():
    """AOT export spaces for the prefill kernel (serving shapes: GQA
    32/8, head_dim 128 — the bench/serving point of docs/perf.md)."""
    from triton_dist_tpu.tools.compile_aot import aot_compile_spaces

    b, hq, hkv, d = 1, 32, 8, 128
    sig = [
        [((b, hq, 4096, d), "bfloat16"), ((b, hkv, 4096, d), "bfloat16"),
         ((b, hkv, 4096, d), "bfloat16")],
        [((b, hq, 512, d), "float32"), ((b, hkv, 512, d), "float32"),
         ((b, hkv, 512, d), "float32")],
    ]

    def algos(platforms):
        out = [{"impl": "xla"}]
        if "tpu" in platforms:
            out += [{"block_q": 128, "block_k": 1024, "impl": "pallas"},
                    {"block_q": 512, "block_k": 512, "impl": "pallas"}]
        return out

    return aot_compile_spaces({
        "flash_prefill": {
            "signature": sig,
            "algo_infos": algos,
        },
    })


@_register_flash_aot()
def flash_prefill_aot(q, k, v, *, impl="auto", block_q=None, block_k=None,
                      interpret=False):
    """AOT-exportable causal prefill entry (fixed causal=True surface —
    the serving path; the full API is :func:`flash_attention`)."""
    return flash_attention(q, k, v, causal=True, block_q=block_q,
                           block_k=block_k, impl=impl, interpret=interpret)


def sp_flash_attention_shard(q, k_shard, v_shard, *, axis, causal=True,
                             scale=None, q_offset=0, impl="auto",
                             interpret=False, k_scale=None, v_scale=None,
                             soft_cap=0.0, window=0):
    """Sequence-parallel prefill attention; call inside shard_map.

    q [B, Hq, Sq, D] replicated (the current chunk's queries); k/v_shard
    [B, Hkv, S_loc, D] sequence-sharded over ``axis``.  Each device runs
    flash over its KV shard at its global offset, then the per-shard
    (out, lse) partials LSE-merge — the decode SP recipe
    (flash_decode.sp_gqa_decode_shard) applied to prefill.  ``q_offset``
    may be traced (chunked prefill's ``prefix_len``).

    Under ``impl="auto"`` each shard's local attention takes the flash
    kernel when shapes allow and the dense fallback otherwise — both
    yield (out, lse) partials, so the combine is impl-agnostic.
    """
    world = jax.lax.axis_size(axis)
    me = jax.lax.axis_index(axis)
    s_loc = k_shard.shape[2]
    out, lse = flash_attention(
        q, k_shard, v_shard, causal=causal, scale=scale,
        q_offset=q_offset, kv_offset=me * s_loc, impl=impl,
        interpret=interpret, return_lse=True, k_scale=k_scale,
        v_scale=v_scale, soft_cap=soft_cap, window=window)
    if world == 1:
        return out
    # Weighted-REDUCE combine (combine_partials' math as collectives):
    # pmax of the small lse plane, then two psums — the payload crosses
    # the wire once as a reduction instead of materializing W gathered
    # copies per device.  All-masked rows (lse = NEG_INF everywhere):
    # m = NEG, w = exp(0) = 1, out = 0 → psum(0)/W = 0, never NaN.
    m = jax.lax.pmax(lse, axis)                           # [B, Hq, Sq]
    w = jnp.exp(lse - m)
    num = jax.lax.psum(out.astype(jnp.float32) * w[..., None], axis)
    denom = jax.lax.psum(w, axis)
    return (num / denom[..., None]).astype(q.dtype)


def flash_gqa_attention(q, k, v, *, causal=True, scale=None, impl="auto",
                        interpret=False, window=0, soft_cap=0.0):
    """Drop-in for ``attention.dense_gqa_attention`` — the model families'
    [S, B, H, D] layout.  q [S, B, Hq, D]; k/v [S, B, Hkv, D]; returns
    [S, B, Hq, D] in q's dtype."""
    qt = q.transpose(1, 2, 0, 3)                          # [B, Hq, S, D]
    kt = k.transpose(1, 2, 0, 3)
    vt = v.transpose(1, 2, 0, 3)
    out = flash_attention(qt, kt, vt, causal=causal, scale=scale,
                          impl=impl, interpret=interpret, window=window,
                          soft_cap=soft_cap)
    return out.transpose(2, 0, 1, 3)
