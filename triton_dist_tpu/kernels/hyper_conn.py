"""The residual path of ``n`` streams (manifold-constrained hyper-connections):
the per-token maps and the two mixes around a sub-layer, as two Mosaic calls.

A token's residual is ``X in R^{n x D}``, held as ONE row ``[n . D]`` — stream
``j`` is columns ``[j D, (j + 1) D)``, so a row's streams sit side by side
along the lanes and neither call pays for a sublane tile padded from ``n``
rows (a ``[rows, n, D]`` array lays ``n = 4`` out as 8 or 16).  Per token, per
sub-layer ``F`` (its own ``phi``, ``alpha``, ``bias``, ``gain``)::

    x~      = gain * vec(X) * (mean(vec(X)^2) + norm_eps)^-1/2        over n.D
    u       = x~ phi                      phi [n.D, 2n + n^2], float32
    H_pre   = sigmoid(alpha[0] u[:n] + bias[:n])
    H_post  = 2 sigmoid(alpha[1] u[n:2n] + bias[n:2n])
    M       = exp(clip(alpha[2] mat(u[2n:]) + mat(bias[2n:]), lo, hi))
    iters x : M <- M / (column sums + eps);  M <- M / (row sums + eps)
    h       = sum_j H_pre[j] X[j]                        -> F's input
    X'[i]   = sum_j M[i, j] X[j] + H_post[i] F(h)

:func:`hc_pre` (Mosaic call ``hc_pre``) does the first six lines in one pass
over a row's streams: ``X`` is read once, ``phi`` once a call, and out come
``h`` and the row's MAPS packed in one float32 lane tile ``[H_pre | H_post |
M row-major | 0]`` (:data:`MAPS_WIDTH`; :func:`unpack_maps`) — so that
nothing sits between it and :func:`hc_post` (Mosaic call ``hc_post``), which
reads ``X``, ``F(h)`` and the maps once and writes ``X'`` once, in ``X``'s
place where the caller lets it.  Statistic, product, maps, the ``iters``
normalisations and both mixes' sums are float32; streams stay in the model's
dtype.

Inside ``hc_pre`` the maps are computed with the ROWS ON THE LANES (``phi^T
x~^T``: ``[2n + n^2, rows]``): a normalisation is then a few whole-vreg
operations a row tile where rows on sublanes would spend a vreg on every eight
rows' one number; one transposition brings them back beside their rows.
Every operation of both bodies is a ``lax`` primitive: a ``jnp`` operator on
a traced value is a jitted function traced anew at each of a program's call
sites (PERF.md §6, PR 48).

The XLA twins (:func:`hc_pre_xla`, :func:`hc_post_xla`) are the CPU path and
the oracle; :func:`hc_gap` says why a shape does not reach the calls.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu.kernels.gemm import resolve_impl, use_fallback
from triton_dist_tpu.language.interpret import maybe_interpret

HC_PRE_CALL, HC_POST_CALL = "hc_pre", "hc_post"
_LANES = 128
MAPS_WIDTH = _LANES         # a row's packed maps: one float32 lane tile
_PRE_ROWS = 128             # rows a grid step of hc_pre: the maps' lanes
_POST_ROWS = 32             # rows a grid step of hc_post
HC_VMEM = 64 * 2 ** 20
_HIGHEST = lax.Precision.HIGHEST


def n_maps(n: int) -> int:
    """Numbers a row's maps hold: ``H_pre`` and ``H_post`` (n each) and the
    n x n residual map."""
    return 2 * n + n * n


def unpack_maps(maps, n: int):
    """[rows, MAPS_WIDTH] packed -> (H_pre [rows, n], H_post [rows, n], H_res
    [rows, n, n])."""
    return (maps[:, :n], maps[:, n:2 * n],
            maps[:, 2 * n:n_maps(n)].reshape(-1, n, n))


def sinkhorn(m, iters: int, eps: float):
    """``iters`` times: columns to sum 1, then rows (m [..., n, n] > 0)."""
    for _ in range(iters):
        m = m / (m.sum(-2, keepdims=True) + eps)
        m = m / (m.sum(-1, keepdims=True) + eps)
    return m


def stream_maps(x, p, *, n: int, iters: int, eps: float, clamp: tuple,
                norm_eps: float):
    """The three maps of rows x [rows, n . D] under a sub-layer's ``p``
    (float32, in the shapes :func:`hc_pre` reads them with the rows on the
    lanes: ``phi_t`` [2n + n^2, n . D] — phi transposed —, ``alpha`` [3],
    ``bias`` [2n + n^2, 1], ``gain`` [1, n . D]) -> (H_pre [rows, n], H_post
    [rows, n], H_res [rows, n, n]), float32: plain ``jax.numpy``."""
    xf = x.astype(jnp.float32)
    xt = xf * lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + norm_eps) \
        * p["gain"]
    u = lax.dot_general(xt, p["phi_t"], (((1,), (1,)), ((), ())),
                        precision=_HIGHEST,
                        preferred_element_type=jnp.float32)
    a, b = p["alpha"], p["bias"][:, 0]
    pre = jax.nn.sigmoid(a[0] * u[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * u[:, n:2 * n] + b[n:2 * n])
    m = jnp.exp(jnp.clip(a[2] * u[:, 2 * n:] + b[2 * n:], *clamp))
    return pre, post, sinkhorn(m.reshape(-1, n, n), iters, eps)


def hc_pre_xla(x, p, *, n: int, iters: int, eps: float, clamp: tuple,
               norm_eps: float):
    """:func:`hc_pre` as plain ``jax.numpy``: -> (h [rows, D] in x's dtype,
    maps [rows, MAPS_WIDTH] float32)."""
    rows, D = x.shape[0], x.shape[1] // n
    pre, post, res = stream_maps(x, p, n=n, iters=iters, eps=eps,
                                 clamp=clamp, norm_eps=norm_eps)
    h = jnp.einsum("rj,rjd->rd", pre,
                   x.reshape(rows, n, D).astype(jnp.float32))
    maps = jnp.concatenate(
        [pre, post, res.reshape(rows, n * n),
         jnp.zeros((rows, MAPS_WIDTH - n_maps(n)), jnp.float32)], axis=-1)
    return h.astype(x.dtype), maps


def hc_post_xla(x, y, maps, *, n: int):
    """:func:`hc_post` as plain ``jax.numpy``: x [rows, n . D], y [rows, D],
    maps [rows, MAPS_WIDTH] -> x' [rows, n . D] in x's dtype."""
    rows, D = y.shape
    _, post, res = unpack_maps(maps, n)
    out = jnp.einsum("rij,rjd->rid", res,
                     x.reshape(rows, n, D).astype(jnp.float32)) \
        + post[:, :, None] * y.astype(jnp.float32)[:, None, :]
    return out.reshape(rows, n * D).astype(x.dtype)


def _sublanes(itemsize: int) -> int:
    """Rows of a (packed) sublane tile: 8 of float32, 16 of bfloat16."""
    return 8 * max(1, 4 // itemsize)


def gap_key(program: str) -> str:
    """The name a program's gap goes by in an engine's ``kernel_gaps``."""
    return f"{HC_PRE_CALL}+{HC_POST_CALL}@{program}"


def hc_gap(rows: int, n: int, width: int, itemsize: int = 2) -> str | None:
    """Why rows [rows, n . width] would run the mixes as XLA (``None``: the
    Mosaic calls tile them): a stream fills whole lane tiles, a row tile
    whole (packed) sublane tiles, and a row's maps one lane tile."""
    sub = _sublanes(itemsize)
    if width % _LANES:
        return f"D={width}: needs D%{_LANES}"
    if rows % sub:
        return f"rows={rows}: needs rows%{sub}"
    if n_maps(n) > MAPS_WIDTH:
        return f"hc_mult={n}: 2n + n^2 must fit {MAPS_WIDTH} lanes"
    return None


def _row_tile(rows: int, cap: int, sub: int) -> int:
    """The largest divisor of ``rows`` that is whole sublane tiles and at
    most ``cap`` (``rows`` itself when it is no larger)."""
    if rows <= cap:
        return rows
    return max((t for t in range(sub, cap + 1, sub) if rows % t == 0),
               default=rows)


def blocking(rows: int, n: int, width: int, itemsize: int = 2) -> dict:
    """How the two calls are blocked at ``rows`` rows (static): rows a grid
    step and the bytes a step holds in flight (both buffers of each block),
    or ``{}`` where :func:`hc_gap` names a reason."""
    if hc_gap(rows, n, width, itemsize):
        return {}
    sub = _sublanes(itemsize)
    pre, post = (_row_tile(rows, c, sub) for c in (_PRE_ROWS, _POST_ROWS))
    row = n * width * itemsize
    return {
        "pre_rows_per_step": pre, "post_rows_per_step": post,
        "pre_bytes_in_flight": 2 * (pre * (row + width * itemsize
                                           + 4 * MAPS_WIDTH)
                                    + n_maps(n) * n * width * 4),
        "post_bytes_in_flight": 2 * post * (2 * row + width * itemsize
                                            + 4 * MAPS_WIDTH),
    }


# ---------------------------------------------------------------------------
# hc_pre
# ---------------------------------------------------------------------------


def _pre_kernel(alpha_ref, x_ref, phi_ref, bias_ref, gain_ref, h_ref,
                maps_ref, mt_ref, *, n: int, width: int, iters: int,
                eps: float, clamp: tuple, norm_eps: float):
    f32 = jnp.float32
    add, mul, div, cut = lax.add, lax.mul, lax.div, lax.slice_in_dim
    tr = x_ref.shape[0]
    k = n_maps(n)
    xf = lax.convert_element_type(x_ref[...], f32)            # [tr, n.D]
    ms = mul(lax.reshape(lax.reduce_sum(mul(xf, xf), (1,)), (tr, 1)),
             f32(1.0 / (n * width)))
    xt = mul(mul(xf, lax.rsqrt(add(ms, f32(norm_eps)))), gain_ref[...])
    # rows on the lanes from here: u^T = phi^T x~^T [k, tr]
    ut = lax.dot_general(phi_ref[...], xt, (((1,), (1,)), ((), ())),
                         precision=_HIGHEST, preferred_element_type=f32)
    at = lax.broadcasted_iota(jnp.int32, (k, 1), 0)
    alpha = lax.select(
        lax.lt(at, n), lax.full((k, 1), alpha_ref[0], f32),
        lax.select(lax.lt(at, 2 * n), lax.full((k, 1), alpha_ref[1], f32),
                   lax.full((k, 1), alpha_ref[2], f32)))
    ht = add(mul(ut, alpha), bias_ref[...])                   # [k, tr]
    gates = lax.logistic(cut(ht, 0, 2 * n))
    m = lax.exp(lax.clamp(f32(clamp[0]), cut(ht, 2 * n, k), f32(clamp[1])))

    def tile(rows_, times):     # [r, tr] -> its rows repeated: [r . times, tr]
        return lax.concatenate([rows_] * times, 0)

    def spread(cols):           # [n, tr] -> each row n times: [n . n, tr]
        return lax.concatenate(
            [lax.broadcast_in_dim(cut(cols, i, i + 1), (n, tr), (0, 1))
             for i in range(n)], 0)

    def normalise(_, m):
        cols = cut(m, 0, n)
        for i in range(1, n):                   # sum over i of M[i, j]
            cols = add(cols, cut(m, i * n, (i + 1) * n))
        m = div(m, tile(add(cols, f32(eps)), n))
        rows_ = lax.concatenate(                # sum over j of M[i, j]
            [lax.reshape(lax.reduce_sum(cut(m, i * n, (i + 1) * n), (0,)),
                         (1, tr)) for i in range(n)], 0)
        return div(m, spread(add(rows_, f32(eps))))

    m = lax.fori_loop(0, iters, normalise, m)
    # [H_pre | H_post | M] back beside their rows: one transposition of a
    # whole tile (rows k.. of the scratch stay zero)
    mt_ref[...] = lax.full(mt_ref.shape, 0.0, f32)
    mt_ref[0:n, :] = cut(gates, 0, n)
    mt_ref[n:2 * n, :] = mul(cut(gates, n, 2 * n), f32(2.0))
    mt_ref[2 * n:k, :] = m
    maps = lax.transpose(mt_ref[...], (1, 0))                 # [tr, 128]
    maps_ref[...] = maps
    h = mul(cut(xf, 0, width, axis=1), cut(maps, 0, 1, axis=1))
    for j in range(1, n):
        h = add(h, mul(cut(xf, j * width, (j + 1) * width, axis=1),
                       cut(maps, j, j + 1, axis=1)))
    h_ref[...] = lax.convert_element_type(h, h_ref.dtype)


def hc_pre(x, p, *, n: int, iters: int, eps: float, clamp: tuple,
           norm_eps: float, impl: str = "auto", interpret: bool = False):
    """Maps and pre-mix of rows x [rows, n . D] (module docstring) under a
    sub-layer's ``p`` (:func:`stream_maps`) -> (h [rows, D] in x's dtype,
    maps [rows, MAPS_WIDTH] float32 for :func:`hc_post`)."""
    rows, D = x.shape[0], x.shape[1] // n
    kw = dict(n=n, iters=iters, eps=eps, clamp=tuple(clamp),
              norm_eps=norm_eps)
    raw = impl
    impl = resolve_impl(impl, interpret)
    # the interpreter tiles nothing: any shape reaches the body there
    gap = None if interpret else hc_gap(rows, n, D, x.dtype.itemsize)
    if use_fallback(raw, impl, gap is None, HC_PRE_CALL, gap or ""):
        return hc_pre_xla(x, p, **kw)
    k = n_maps(n)
    tr = _row_tile(rows, _PRE_ROWS, _sublanes(x.dtype.itemsize))
    whole = lambda i, a: (0, 0)     # noqa: E731
    return pl.pallas_call(
        functools.partial(_pre_kernel, width=D, **kw),
        name=HC_PRE_CALL,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows // tr,),
            in_specs=[pl.BlockSpec((tr, n * D), lambda i, a: (i, 0)),
                      pl.BlockSpec((k, n * D), whole),
                      pl.BlockSpec((k, 1), whole),
                      pl.BlockSpec((1, n * D), whole)],
            out_specs=[pl.BlockSpec((tr, D), lambda i, a: (i, 0)),
                       pl.BlockSpec((tr, MAPS_WIDTH), lambda i, a: (i, 0))],
            scratch_shapes=[pltpu.VMEM((MAPS_WIDTH, tr), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((rows, D), x.dtype),
                   jax.ShapeDtypeStruct((rows, MAPS_WIDTH), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=HC_VMEM),
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * n * D * (k + 3), transcendentals=rows * k,
            bytes_accessed=rows * (n + 1) * D * x.dtype.itemsize
            + 4 * (k + 1) * n * D + 4 * rows * MAPS_WIDTH),
        interpret=maybe_interpret(interpret),
    )(p["alpha"], x, p["phi_t"], p["bias"], p["gain"])


# ---------------------------------------------------------------------------
# hc_post
# ---------------------------------------------------------------------------


def _post_kernel(x_ref, y_ref, maps_ref, out_ref, *, n: int, width: int):
    f32 = jnp.float32
    add, mul, cut = lax.add, lax.mul, lax.slice_in_dim
    maps = maps_ref[...]                                       # [tr, 128]
    y = lax.convert_element_type(y_ref[...], f32)
    xs = [lax.convert_element_type(
        x_ref[:, j * width:(j + 1) * width], f32) for j in range(n)]
    for i in range(n):
        acc = mul(y, cut(maps, n + i, n + i + 1, axis=1))
        for j in range(n):
            at = 2 * n + i * n + j
            acc = add(acc, mul(xs[j], cut(maps, at, at + 1, axis=1)))
        out_ref[:, i * width:(i + 1) * width] = lax.convert_element_type(
            acc, out_ref.dtype)


def hc_post(x, y, maps, *, n: int, impl: str = "auto",
            interpret: bool = False):
    """The post-mix (module docstring): x [rows, n . D], the sub-layer's
    output y [rows, D], :func:`hc_pre`'s maps -> x' [rows, n . D] in x's
    dtype — aliased onto ``x``: written over it where the caller's ``x``
    is not read again (the compiler copies where it is)."""
    rows, D = y.shape
    raw = impl
    impl = resolve_impl(impl, interpret)
    gap = None if interpret else hc_gap(rows, n, D, x.dtype.itemsize)
    if use_fallback(raw, impl, gap is None, HC_POST_CALL, gap or ""):
        return hc_post_xla(x, y, maps, n=n)
    tr = _row_tile(rows, _POST_ROWS, _sublanes(x.dtype.itemsize))
    block = lambda w: pl.BlockSpec((tr, w), lambda i: (i, 0))  # noqa: E731
    return pl.pallas_call(
        functools.partial(_post_kernel, n=n, width=D),
        name=HC_POST_CALL,
        grid=(rows // tr,),
        in_specs=[block(n * D), block(D), block(MAPS_WIDTH)],
        out_specs=block(n * D),
        out_shape=jax.ShapeDtypeStruct((rows, n * D), x.dtype),
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=HC_VMEM),
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * n * (n + 1) * D, transcendentals=0,
            bytes_accessed=rows * (2 * n + 1) * D * x.dtype.itemsize
            + 4 * rows * MAPS_WIDTH),
        interpret=maybe_interpret(interpret),
    )(x, y.astype(x.dtype), maps)


def mixes(*, n: int, iters: int, eps: float, clamp: tuple, norm_eps: float,
          impl: str = "auto", interpret: bool = False) -> tuple:
    """The pair ``generate._layer_stack`` takes as its ``streams`` seam: a
    family's config and dispatch bound in.  ``pre(x, p) -> (h, maps)``;
    ``post(x, y, maps) -> x'``."""
    kw = dict(impl=impl, interpret=interpret)
    return (functools.partial(hc_pre, n=n, iters=iters, eps=eps,
                              clamp=tuple(clamp), norm_eps=norm_eps, **kw),
            functools.partial(hc_post, n=n, **kw))
