"""The selective scan of a Mamba-1 state-space layer, and the causal
convolution with its carried rows beside it.

The recurrence of one request's rows ``t = 0 .. T - 1`` over channels ``e``
and state index ``n``::

    S_t[n, e] = exp(dt_t[e] * A[n, e]) * S_{t-1}[n, e] + dt_t[e] * x_t[e] * B_t[n]
    y_t[e]    = sum_n S_t[n, e] * C_t[n] + D[e] * x_t[e]

``S`` is the request's STATE: it does not grow with ``t``.  The state is
laid out ``[N, E]`` — the state index on sublanes, channels on lanes — so a
float32 state of 16 x 5,120 takes its 327,680 bytes and no padding in HBM,
and one time step is a handful of whole-vreg operations a 128-channel tile.

:func:`ssm_scan` is ONE prefill chunk's scan as a Mosaic call (named
``ssm_scan`` in a device trace): the initial state in, the final state
out, blocked over channels (``parallel``) and over time (``arbitrary``,
the state carried in VMEM in float32).  No ``T x E x N`` tensor exists in
HBM — XLA's associative scan would write several.  A row whose ``dt`` is 0
leaves the state as it was (``exp(0) = 1``, ``0 * x * B = 0``): that is how
a caller masks a padded chunk's rows.

:func:`ssm_step` is the decode step's update (one row a request, ``B``
requests): plain ``jax.numpy`` under the caller's region.  A step reads and
writes every row's state once and does 6 operations a number; nothing about
it needs a kernel's blocking (PERF.md §6).

:func:`causal_conv` is the ``K``-tap depthwise convolution before the scan,
with the ``K - 1`` input rows it carries from chunk to chunk.
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
_ROWS = 8               # time steps a loop iteration: one aligned f32 tile
_BLOCK_E = 512          # channels a grid step
_BLOCK_T = 128          # time steps a grid step


def ssm_scan_gap(T: int, E: int, N: int) -> str | None:
    """Why :func:`ssm_scan` would run as XLA at these shapes (``None``: the
    Mosaic call tiles them)."""
    if T % _ROWS or E % _LANES or N % 8:
        return (f"(T={T}, E={E}, N={N}) needs T%{_ROWS} == E%{_LANES} == "
                f"N%8 == 0")
    return None


def _blocks(T: int, E: int) -> tuple:
    be = next(b for b in (_BLOCK_E, 256, _LANES) if E % b == 0)
    tb = next(b for b in (_BLOCK_T, 64, 32, 16, _ROWS) if T % b == 0)
    return tb, be


def _scan_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, s0_ref,
                 y_ref, s_ref, s_scr, *, tb, be):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _():
        s_scr[...] = s0_ref[...]

    a = a_ref[...]                                        # [N, be]
    d = d_ref[...]                                        # [1, be]
    reps = be // _LANES

    def body(i, S):
        t0 = pl.multiple_of(i * _ROWS, _ROWS)
        x8 = x_ref[pl.ds(t0, _ROWS), :]                   # [8, be]
        dt8 = dt_ref[pl.ds(t0, _ROWS), :]
        ys = []
        for j in range(_ROWS):
            xv, dt = x8[j:j + 1], dt8[j:j + 1]            # [1, be]
            # B_t, C_t arrive broadcast over one lane tile: [N, 128]
            bt = jnp.concatenate([b_ref[t0 + j]] * reps, axis=1)
            ct = jnp.concatenate([c_ref[t0 + j]] * reps, axis=1)
            S = jnp.exp(dt * a) * S + (dt * xv) * bt      # [N, be]
            ys.append(jnp.sum(S * ct, axis=0, keepdims=True) + d * xv)
        y_ref[pl.ds(t0, _ROWS), :] = jnp.concatenate(ys, axis=0)
        return S

    S = jax.lax.fori_loop(0, tb // _ROWS, body, s_scr[...])
    s_scr[...] = S

    @pl.when(ti == pl.num_programs(1) - 1)
    def _():
        s_ref[...] = S


def _scan_xla(x, dt, B, C, A, D, state):
    def step(S, row):
        xv, dtv, bt, ct = row
        S = jnp.exp(dtv[None, :] * A) * S + (dtv * xv)[None, :] * bt[:, None]
        return S, jnp.sum(S * ct[:, None], axis=0) + D * xv
    state, y = jax.lax.scan(step, state, (x, dt, B, C))
    return y, state


def ssm_scan(x, dt, B, C, A, D, state, *, impl: str = "auto",
             interpret: bool = False, name: str = "ssm_scan"):
    """One chunk's selective scan.  ``x``, ``dt`` [T, E]; ``B``, ``C``
    [T, N]; ``A`` [N, E] (negative); ``D`` [E]; ``state`` [N, E] — all
    float32 -> (``y`` [T, E], the state after row ``T - 1``)."""
    T, E = x.shape
    N = A.shape[0]
    raw = impl
    impl = resolve_impl(impl, interpret)
    gap = ssm_scan_gap(T, E, N)
    if use_fallback(raw, impl, gap is None, "ssm_scan", gap or ""):
        return _scan_xla(x, dt, B, C, A, D, state)
    tb, be = _blocks(T, E)

    def lanes(m):          # [T, N] -> [T, N, 128]: a row a time step
        return jnp.broadcast_to(m[:, :, None], (T, N, _LANES))

    rows = pl.BlockSpec((tb, be), lambda e, t: (t, e))
    cols = pl.BlockSpec((tb, N, _LANES), lambda e, t: (t, 0, 0))
    chan = pl.BlockSpec((N, be), lambda e, t: (0, e))
    return pl.pallas_call(
        functools.partial(_scan_kernel, tb=tb, be=be),
        name=name,
        grid=(E // be, T // tb),
        in_specs=[rows, rows, cols, cols, chan,
                  pl.BlockSpec((1, be), lambda e, t: (0, e)), chan],
        out_specs=[rows, chan],
        out_shape=[jax.ShapeDtypeStruct((T, E), jnp.float32),
                   jax.ShapeDtypeStruct((N, E), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((N, be), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=maybe_interpret(interpret),
    )(x, dt, lanes(B), lanes(C), A, D[None, :], state)


def ssm_step(x, dt, B, C, A, D, state):
    """One decode step of ``R`` requests: ``x``, ``dt`` [R, E]; ``B``, ``C``
    [R, N]; ``state`` [R, N, E] -> (``y`` [R, E], the states after it)."""
    state = (jnp.exp(dt[:, None, :] * A) * state
             + (dt * x)[:, None, :] * B[:, :, None])
    return jnp.sum(state * C[:, :, None], axis=1) + D * x, state


def causal_conv(x, carry, w, b, n_valid=None):
    """``K``-tap causal depthwise convolution + SiLU over rows ``x`` [B, T,
    E], the ``K - 1`` rows before them in ``carry`` [B, K - 1, E] (zeros
    before a request's first token): ``silu(b + sum_k w[k] * x_{t-K+1+k})``
    with ``w`` [K, E].  -> (the result [B, T, E] in ``x``'s dtype, the
    carry for the rows after: the last ``K - 1`` inputs — of the first
    ``n_valid`` rows where a chunk's tail is padding).  ``b`` may be
    ``None``: a convolution without a bias."""
    T, K = x.shape[1], w.shape[0]
    seq = jnp.concatenate([carry.astype(x.dtype), x], axis=1)
    acc = 0.0 if b is None else b.astype(jnp.float32)
    for k in range(K):
        acc = acc + w[k].astype(jnp.float32) * seq[:, k:k + T]
    at = T if n_valid is None else n_valid
    new = jax.lax.dynamic_slice_in_dim(seq, at, K - 1, axis=1)
    return jax.nn.silu(acc).astype(x.dtype), new.astype(carry.dtype)
