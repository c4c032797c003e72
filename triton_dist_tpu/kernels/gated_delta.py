"""The gated delta rule of a Gated-DeltaNet linear-attention layer
(arXiv:2412.06464): a MATRIX state a head, erased and written a token.

One head, token ``t``, everything float32 — ``q_t``, ``k_t`` [dk] (unit
length, ``q`` scaled by the caller), ``v_t`` [dv], ``beta_t`` in (0, 2),
``g_t <= 0`` (the log of the decay)::

    S   = exp(g_t) * S_{t-1}                      [dk, dv]
    S_t = S + k_t (x) (beta_t * (v_t - k_t^T S))  erase what k held, write v
    o_t = q_t^T S_t                               [dv]

``S`` is the request's STATE: it does not grow with ``t``.  It is laid out
``[dk, H * dv]`` — the key index on sublanes, (head, value index) on lanes
— so a float32 state of 96 x (30 x 192) takes its 2,211,840 bytes in HBM
and no padded lane (a ``[.., 192]`` float32 plane would be laid 256 wide:
a third more bytes a step).  A token with ``beta = 0`` and ``g = 0`` leaves
the state as it was: that is how a caller masks a padded chunk row and an
inactive decode row.

:func:`gdn_chunk` is ONE prefill chunk of one request as a Mosaic call
(``gdn_chunk`` in a device trace): ``T`` rows in sub-chunks of 64.  Inside
a sub-chunk the ``C`` sequential rank-1 updates collapse into products on
the MXU (the WY / UT form): with ``G_i`` the cumulative ``g`` inside the
sub-chunk, ``D[i, j] = exp(G_i - G_j)`` for ``i >= j`` (differences only:
nothing overflows) and ``L = tril(beta_i k_i . k_j D[i, j], -1)``,

    P = (I + L)^-1            blocks of 8 by doubling (L is nilpotent), then
                              block forward substitution: P + P E P a level
    u = P (beta v),   w = P (beta k exp(G))
    v' = u - w S_in
    o  = (q exp(G)) S_in + tril(q k^T D) v'
    S_out = exp(G_C) S_in + (k exp(G_C - G))^T v'

— the grid runs heads (``parallel``) by sub-chunks (``arbitrary``), the
head's state carried in VMEM from sub-chunk to sub-chunk: no ``T x dk x
dv`` tensor exists in HBM.  Every product is float32 at the highest
precision the MXU gives.

:func:`gdn_step` is the decode step's update of ``B`` requests (one row
each) as a Mosaic call (``gdn_step``) that reads and writes each request's
state IN ITS SLOT of the pool: the slot ids are scalar-prefetched, the
grid runs rows by head blocks, the pool is aliased in and out — a step
moves each live state once in and once out, where XLA's gather, update and
scatter would move it three times.  Rows redirected to the null slot must
come with ``beta = 0`` and ``g = 0``: they then write back what they read.

Each has an XLA twin (the CPU's path, and the reroute of shapes the
kernels cannot tile, which :func:`gdn_chunk_gap` / :func:`gdn_step_gap`
name).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu.kernels.gemm import resolve_impl, use_fallback
from triton_dist_tpu.language.interpret import maybe_interpret

_LANES = 128
SUB_CHUNK = 64          # rows the WY form collapses into one set of products
_BASE = 8               # rows of a diagonal block inverted by doubling
_STEP_BLOCK_BYTES = 1 << 20     # a head block of one state, at most


def _dot(a, b):
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Prefill: one chunk of one request
# ---------------------------------------------------------------------------


def gdn_chunk_gap(T: int, dk: int, dv: int) -> str | None:
    """Why :func:`gdn_chunk` would run as XLA at these shapes (``None``:
    the Mosaic call tiles them)."""
    if T % SUB_CHUNK or dk % 8 or dv % 8:
        return (f"(T={T}, dk={dk}, dv={dv}) needs T%{SUB_CHUNK} == dk%8 == "
                f"dv%8 == 0")
    return None


def _sub_chunk(q, k, kt, v, b, gcol, grow, S):
    """The WY form over one head's sub-chunk: q, k [C, dk]; kt [dk, C]; v
    [C, dv]; b, gcol [C, 1]; grow [1, C] (``gcol`` again, along lanes); S
    [dk, dv] -> (o [C, dv], the state after row C - 1)."""
    C = q.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    # exp(G_i - G_j), i >= j: the difference is <= 0 there
    D = jnp.where(row >= col, jnp.exp(jnp.minimum(gcol - grow, 0.0)), 0.0)
    kb = k * b
    N = jnp.where(row > col, -(_dot(kb, kt) * D), 0.0)      # -L
    # (I - N)^-1 by BLOCKS, not by N's power series: the powers of a 64-row
    # N whose keys resemble each other grow to ~e^|N| before they vanish
    # and cancel, and float32 loses the inverse in the cancellation.
    # Diagonal blocks of _BASE rows by doubling (their powers stay small),
    # then block forward substitution a level: with P the inverse of the
    # block diagonal and E the blocks beside it inside the next size up,
    # (P E)^2 = 0, so the next inverse is exactly P + P E P.
    def same_block(shift):
        return (row >> shift) == (col >> shift)

    shift = _BASE.bit_length() - 1
    M = jnp.where(same_block(shift), N, 0.0)
    P = jnp.where(row == col, 1.0, 0.0) + M
    span = 2
    while span < _BASE:     # P <- P (I + M^span): powers below 2 * span
        M = _dot(M, M)
        P = P + _dot(P, M)
        span *= 2
    while (1 << shift) < C:
        E = jnp.where(same_block(shift + 1) & ~same_block(shift), N, 0.0)
        P = P + _dot(P, _dot(E, P))
        shift += 1
    eg = jnp.exp(gcol)
    u = _dot(P, v * b)
    w = _dot(P, kb * eg)
    v_new = u - _dot(w, S)
    o = _dot(q * eg, S) + _dot(_dot(q, kt) * D, v_new)
    g_last = grow[:, C - 1:C]                                # [1, 1]
    S = S * jnp.exp(g_last) + _dot(kt * jnp.exp(g_last - grow), v_new)
    return o, S


def _chunk_kernel(q_ref, k_ref, kt_ref, v_ref, b_ref, gc_ref, gr_ref, s0_ref,
                  o_ref, s1_ref, s_scr):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _():
        s_scr[...] = s0_ref[0]

    o, S = _sub_chunk(q_ref[0, 0], k_ref[0, 0], kt_ref[0, 0], v_ref[0, 0],
                      b_ref[0, 0], gc_ref[0, 0], gr_ref[0, 0], s_scr[...])
    o_ref[0, 0] = o
    s_scr[...] = S

    @pl.when(ci == pl.num_programs(1) - 1)
    def _():
        s1_ref[0] = S


def _by_head(q, k, v, beta, g, state):
    """The caller's row-major operands as the sub-chunk form reads them:
    head-major, rows in sub-chunks (padded with ``beta = g = 0`` rows up to
    a whole one), the cumulative ``g`` of a sub-chunk along sublanes and
    along lanes."""
    T, H, dk = q.shape
    dv = v.shape[-1]
    C = SUB_CHUNK
    pad = -T % C
    if pad:
        q, k, v, beta, g = (jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
                            for t in (q, k, v, beta, g))
    nc = (T + pad) // C

    def heads(t):          # [T, H, w] -> [H, nc, C, w]
        return t.transpose(1, 0, 2).reshape(H, nc, C, t.shape[-1])

    qh, kh, vh = heads(q), heads(k), heads(v)
    gc = jnp.cumsum(g.T.reshape(H, nc, C), axis=-1)
    return (qh, kh, kh.transpose(0, 1, 3, 2), vh,
            beta.T.reshape(H, nc, C, 1), gc[..., None], gc[:, :, None, :],
            state.reshape(dk, H, dv).transpose(1, 0, 2))


def _from_heads(o, s1, T):
    H, nc, C, dv = o.shape
    dk = s1.shape[1]
    return (o.reshape(H, nc * C, dv).transpose(1, 0, 2)[:T].reshape(
        T, H * dv), s1.transpose(1, 0, 2).reshape(dk, H * dv))


def _chunk_xla(q, k, v, beta, g, state):
    ops = _by_head(q, k, v, beta, g, state)

    def head(qh, kh, kth, vh, bh, gch, grh, s0):
        def step(S, x):
            o, S = _sub_chunk(*x, S)
            return S, o
        s1, o = jax.lax.scan(step, s0, (qh, kh, kth, vh, bh, gch, grh))
        return o, s1

    o, s1 = jax.vmap(head)(*ops)
    return _from_heads(o, s1, q.shape[0])


def gdn_chunk(q, k, v, beta, g, state, *, impl: str = "auto",
              interpret: bool = False):
    """One chunk's gated delta rule over ``T`` rows of one request.  ``q``,
    ``k`` [T, H, dk] (unit length, ``q`` scaled); ``v`` [T, H, dv];
    ``beta``, ``g`` [T, H]; ``state`` [dk, H * dv] — all float32 -> (``o``
    [T, H * dv], the state after row ``T - 1``)."""
    T, H, dk = q.shape
    dv = v.shape[-1]
    raw = impl
    impl = resolve_impl(impl, interpret)
    gap = gdn_chunk_gap(T, dk, dv)
    if use_fallback(raw, impl, gap is None, "gdn_chunk", gap or ""):
        return _chunk_xla(q, k, v, beta, g, state)
    C = SUB_CHUNK
    nc = T // C

    def rows(*tile):
        return pl.BlockSpec((1, 1, *tile), lambda h, c: (h, c, 0, 0))

    whole = pl.BlockSpec((1, dk, dv), lambda h, c: (h, 0, 0))
    o, s1 = pl.pallas_call(
        _chunk_kernel,
        name="gdn_chunk",
        grid=(H, nc),
        in_specs=[rows(C, dk), rows(C, dk), rows(dk, C), rows(C, dv),
                  rows(C, 1), rows(C, 1), rows(1, C), whole],
        out_specs=[rows(C, dv), whole],
        out_shape=[jax.ShapeDtypeStruct((H, nc, C, dv), jnp.float32),
                   jax.ShapeDtypeStruct((H, dk, dv), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=maybe_interpret(interpret),
    )(*_by_head(q, k, v, beta, g, state))
    return _from_heads(o, s1, T)


# ---------------------------------------------------------------------------
# Decode: one row a request, the state in its slot
# ---------------------------------------------------------------------------


def _lane_group(dv: int) -> int:
    """Heads whose value lanes together end on a lane tile (192 -> 2)."""
    return next(n for n in (1, 2, 4, 8, 16) if n * dv % _LANES == 0)


def _step_heads(H: int, dk: int, dv: int) -> int:
    """Heads of one row's state a grid step carries: whole lane groups that
    divide ``H``, as many as stay under ``_STEP_BLOCK_BYTES`` (one group at
    least); 0: ``H`` does not divide into lane groups."""
    n = _lane_group(dv)
    return max((h for h in range(n, H + 1, n) if H % h == 0 and (
        h == n or dk * h * dv * 4 <= _STEP_BLOCK_BYTES)), default=0)


def gdn_step_gap(H: int, dk: int, dv: int) -> str | None:
    """Why :func:`gdn_step` would run as XLA at these shapes."""
    if dk % 8 or dv % 8 or not _step_heads(H, dk, dv):
        return (f"(H={H}, dk={dk}, dv={dv}) needs dk%8 == dv%8 == 0 and a "
                f"head count that divides into blocks of whole lane tiles")
    return None


def _step_kernel(slots_ref, qt_ref, kt_ref, v_ref, b_ref, d_ref, s_ref,
                 o_ref, so_ref, *, hb, dv, n):
    del slots_ref               # read by the index maps
    dk = s_ref.shape[1]
    qt, kt = qt_ref[0, 0], kt_ref[0, 0]                 # [dk, hb]
    wide = n * dv
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, wide), 1)

    def columns(t, h0):
        """Heads h0 .. h0 + n of ``t`` [dk, hb], each broadcast over its
        own ``dv`` lanes: [dk, n * dv]."""
        out = jnp.broadcast_to(t[:, h0:h0 + 1], (dk, wide))
        for j in range(1, n):
            out = jnp.where(lane >= j * dv, jnp.broadcast_to(
                t[:, h0 + j:h0 + j + 1], (dk, wide)), out)
        return out

    for gi in range(hb // n):
        at = pl.ds(gi * wide, wide)
        kx, qx = columns(kt, gi * n), columns(qt, gi * n)
        S = s_ref[0, :, at] * d_ref[0, :, at]
        delta = b_ref[0, :, at] * (
            v_ref[0, :, at] - jnp.sum(kx * S, axis=0, keepdims=True))
        S = S + kx * delta
        o_ref[0, :, at] = jnp.sum(qx * S, axis=0, keepdims=True)
        so_ref[0, :, at] = S


def _step_xla(q, k, v, beta, g, pool, slots):
    B, H, dk = q.shape
    dv = v.shape[-1]
    S = pool[slots].reshape(B, dk, H, dv) * jnp.exp(g)[:, None, :, None]
    kc, qc = (t.transpose(0, 2, 1)[..., None] for t in (k, q))  # [B,dk,H,1]
    delta = beta[..., None] * (v - jnp.sum(kc * S, axis=1))
    S = S + kc * delta[:, None]
    o = jnp.sum(qc * S, axis=1)
    return (o.reshape(B, H * dv),
            pool.at[slots].set(S.reshape(B, dk, H * dv)))


def gdn_step(q, k, v, beta, g, pool, slots, *, impl: str = "auto",
             interpret: bool = False):
    """One decode step of ``B`` requests.  ``q``, ``k`` [B, H, dk]; ``v``
    [B, H, dv]; ``beta``, ``g`` [B, H] — float32; ``pool`` [slots, dk, H *
    dv] float32; ``slots`` [B] int32, row ``b``'s state at ``pool[slots[b]]``
    -> (``o`` [B, H * dv], the pool with every row's state stepped in
    place).  Rows that share a slot (the null slot of inactive rows) must
    carry ``beta = g = 0``."""
    B, H, dk = q.shape
    dv = v.shape[-1]
    raw = impl
    impl = resolve_impl(impl, interpret)
    gap = gdn_step_gap(H, dk, dv)
    if use_fallback(raw, impl, gap is None, "gdn_step", gap or ""):
        return _step_xla(q, k, v, beta, g, pool, slots)
    hb = _step_heads(H, dk, dv)
    nb = H // hb

    def columns(t):        # [B, H, dk] -> [B, nb, dk, hb]
        return t.reshape(B, nb, hb, dk).transpose(0, 1, 3, 2)

    def lanes(t):          # [B, H] -> [B, 1, H * dv]: a head's own lanes
        return jnp.repeat(t, dv, axis=-1)[:, None, :]

    cols = pl.BlockSpec((1, 1, dk, hb), lambda b, j, s: (b, j, 0, 0))
    row = pl.BlockSpec((1, 1, hb * dv), lambda b, j, s: (b, 0, j))
    slot = pl.BlockSpec((1, dk, hb * dv), lambda b, j, s: (s[b], 0, j))
    o, pool = pl.pallas_call(
        functools.partial(_step_kernel, hb=hb, dv=dv, n=_lane_group(dv)),
        name="gdn_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, nb),
            in_specs=[cols, cols, row, row, row, slot],
            out_specs=[row, slot]),
        out_shape=[jax.ShapeDtypeStruct((B, 1, H * dv), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operand 6 (after the prefetched slots): the pool, stepped in place
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=maybe_interpret(interpret),
    )(slots.astype(jnp.int32), columns(q), columns(k),
      v.reshape(B, 1, H * dv), lanes(beta), lanes(jnp.exp(g)), pool)
    return o[:, 0], pool
