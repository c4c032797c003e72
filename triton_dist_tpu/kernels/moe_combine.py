"""The routed sum of a layer that holds SOME of the experts: each token's
weighted sum of the rows its held experts produced.

Two forms of one result, ``out[t] = sum_k [local[t, k]] w[t, k] *
float32(y[dest[t, k]])`` (float32 weights, products and sum):

- :func:`combine_gather` — by ASSIGNMENT: gather a row of the sorted buffer
  for each of the ``T . top_k`` assignments, mask those that went to
  another chip, sum over ``k``.  Right where most assignments land here or
  the rows are few (a decode step); at a prefill chunk of a layer that
  holds 1 expert in 8 it reads eight rows for every one that exists.
- :func:`combine_live` — by ROW: walk the live tiles of the sorted buffer
  (``moe_utils.sort_align_held`` puts them first) and add each row that
  landed into its token's row of a float32 result that stays in VMEM.
  The third call of ``group_gemm_live``'s shape: ``n_live`` in SMEM ahead
  of the grid, dead steps pinned to the last live step's blocks (they copy
  nothing) with the body under ``pl.when``.  A token's terms are summed
  in expert order where the gather sums them in ``k`` order.

:func:`combine_live` falls back to :func:`combine_gather` off the chip
(``impl`` resolves to XLA) and where :func:`combine_gap` names a reason.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu.kernels.gemm import resolve_impl, use_fallback
from triton_dist_tpu.language.interpret import maybe_interpret

COMBINE_CALL = "moe_combine"
_LANES = 128
# rows of bfloat16 that fill a packed sublane tile (float32's 8 divide it):
# a step's rows, and the rows a loop turn widens, are whole ones
_PACKED = 16
# rows of the sorted buffer a grid step walks (a multiple of the row tile):
# a dead step costs ~0.35 us whatever it holds, so a tile of 32 rows is
# walked sixteen at a time — as far as the step's two buffers of the input
# and its float32 copy fit beside the result
_STEP_ROWS, _STEP_BYTES = 512, 12 * 2 ** 20
# the float32 result block that stays in VMEM across the walk (the
# pipeline keeps two): [2048, 3072] whole, [1024, 6144] in two passes
_OUT_BLOCK_BYTES = 24 * 2 ** 20
COMBINE_VMEM = 64 * 2 ** 20


def combine_gather(y, plan, w):
    """The routed sum by assignment.  ``y`` [m_pad, D] the sorted buffer
    (rows of dead tiles may hold anything), ``plan`` of
    ``moe_utils.sort_align_held``, ``w`` [T, top_k] float32 -> float32
    [T, D]."""
    T, top_k = w.shape
    local = plan["local"].reshape(T, top_k)
    rows = jnp.minimum(plan["dest"], plan["m_pad"] - 1).reshape(T, top_k)
    picked = jnp.where(local[..., None], y[rows].astype(jnp.float32), 0.0)
    return jnp.einsum("tk,tkd->td", jnp.where(local, w, 0.0), picked)


def walk_rows(rows: int, top_k: int, held: int, n_experts: int,
              block_m: int) -> int:
    """Rows of the sorted buffer the walk reads for a program of ``rows``
    rows under even routing: the assignments that land here plus at most a
    tile of padding a held expert (the gather reads ``rows . top_k``)."""
    return -(-rows * top_k * held // n_experts) + held * block_m


def combine_gap(width: int, block_m: int):
    """Why the walk cannot run as the Mosaic call, or None: the buffer's
    rows must fill lanes, a row tile whole sublanes."""
    if width % _LANES:
        return f"D={width}: needs D%{_LANES}"
    if block_m % 8:
        return f"block_m={block_m}: needs block_m%8"
    return None


def _blocks(n_tokens: int, m_pad: int, width: int, block_m: int):
    """-> (buffer rows a step, token rows of a result block).  A step's
    rows are whole tiles and whole packed sublane tiles (``_PACKED``) —
    or, where no such count divides the tiles (a tile of 8 rows, an odd
    number of them), the whole buffer."""
    tiles = m_pad // block_m
    cap = min(_STEP_ROWS, _STEP_BYTES // (8 * width))
    per = max((d for d in range(1, max(1, cap // block_m) + 1)
               if tiles % d == 0 and d * block_m % _PACKED == 0),
              default=tiles)
    fit = max(8, _OUT_BLOCK_BYTES // (4 * width))
    tb = n_tokens if n_tokens <= fit else max(
        (d for d in range(8, fit + 1, 8) if n_tokens % d == 0),
        default=n_tokens)
    return per * block_m, tb


def _walk_kernel(nl_ref, tok_ref, w_ref, y_ref, out_ref, rows_ref, *,
                 step_rows: int, tb: int, group: int):
    """One grid step: a live step's rows to float32 (``group`` rows a loop
    turn), then each added into its token's row of the result block.
    Every loop stays ROLLED: with the row loop unrolled eight rows a turn
    and the zero fill and the widening unrolled whole the walk read ~5%
    faster alone, the Mosaic call took 1.8 s to compile where this takes
    0.4, and a WARM ``setup_s`` read +16% where this reads +0.6% (chip
    runs, PR 46: PERF.md §6)."""
    blk, step = pl.program_id(0), pl.program_id(1)

    @pl.when(step == 0)
    def _():
        rows = next((n for n in (64, 32, 16, 8) if tb % n == 0), tb)

        def clear(g, carry):
            at = pl.ds(pl.multiple_of(g * rows, rows), rows)
            out_ref[at, :] = jnp.zeros((rows, out_ref.shape[1]), jnp.float32)
            return carry

        jax.lax.fori_loop(0, tb // rows, clear, 0)

    @pl.when(step < nl_ref[0])
    def _():
        def widen(g, carry):
            at = pl.ds(pl.multiple_of(g * group, group), group)
            rows_ref[at, :] = y_ref[at, :].astype(jnp.float32)
            return carry

        jax.lax.fori_loop(0, step_rows // group, widen, 0)
        lo = blk * tb

        def row(r, carry):
            t = tok_ref[0, 0, r] - lo       # a padding row's token is -1

            @pl.when((t >= 0) & (t < tb))
            def _():
                at = pl.ds(t, 1)
                out_ref[at, :] = (out_ref[at, :] + w_ref[0, 0, r]
                                  * rows_ref[pl.ds(r, 1), :])
            return carry

        jax.lax.fori_loop(0, step_rows, row, 0)


def combine_live(y, plan, w, *, block_m: int, impl: str = "auto",
                 interpret: bool = False):
    """The routed sum by row (module docstring): ``y`` [m_pad, D], ``plan``
    of ``sort_align_held(.., assignment=True)`` at this ``block_m``, ``w``
    [T, top_k] float32 -> float32 [T, D].  Correct under any routing —
    every assignment local, none, one expert taking every row."""
    T, top_k = w.shape
    m_pad, D = y.shape
    assert m_pad == plan["m_pad"] and m_pad % block_m == 0, (m_pad, block_m)
    raw = impl
    impl = resolve_impl(impl, interpret)
    gap = combine_gap(D, block_m)
    if use_fallback(raw, impl, gap is None, COMBINE_CALL, gap or ""):
        return combine_gather(y, plan, w)
    valid = plan["valid_rows"]
    tok = jnp.where(valid, plan["src_token"], -1).astype(jnp.int32)
    w_row = jnp.where(valid, w.reshape(-1)[plan["src_assignment"]],
                      0.0).astype(jnp.float32)
    return _walk(y, tok, w_row, plan["n_live_tiles"], n_tokens=T,
                 block_m=block_m, interpret=interpret)


def _walk(y, tok, w_row, n_live_tiles, *, n_tokens: int, block_m: int,
          interpret: bool):
    """The Mosaic call: ``tok`` [m_pad] each buffer row's token (-1: a
    padding row), ``w_row`` [m_pad] float32 its weight."""
    T = n_tokens
    m_pad, D = y.shape
    step_rows, tb = _blocks(T, m_pad, D, block_m)
    group = _PACKED if step_rows % _PACKED == 0 else step_rows
    n_steps = m_pad // step_rows
    n_live = -(-n_live_tiles * block_m // step_rows)

    def pin(b, s, nl):              # a dead step stays on the last live one
        return jnp.minimum(s, jnp.maximum(nl[0] - 1, 0))

    scalars = pl.BlockSpec((1, 1, step_rows),
                           lambda b, s, nl: (pin(b, s, nl), 0, 0),
                           memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_walk_kernel, step_rows=step_rows, tb=tb,
                          group=group),
        name=COMBINE_CALL,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(T // tb, n_steps),
            in_specs=[scalars, scalars,
                      pl.BlockSpec((step_rows, D),
                                   lambda b, s, nl: (pin(b, s, nl), 0))],
            out_specs=pl.BlockSpec((tb, D), lambda b, s, nl: (b, 0)),
            scratch_shapes=[pltpu.VMEM((step_rows, D), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((T, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=COMBINE_VMEM),
        cost_estimate=pl.CostEstimate(
            flops=2 * m_pad * D, transcendentals=0,
            bytes_accessed=m_pad * D * y.dtype.itemsize + 4 * T * D),
        interpret=maybe_interpret(interpret),
    )(jnp.reshape(n_live, (1,)).astype(jnp.int32),
      tok.reshape(n_steps, 1, step_rows),
      w_row.reshape(n_steps, 1, step_rows), y)
