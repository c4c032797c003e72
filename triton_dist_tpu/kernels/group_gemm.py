"""Grouped (expert-blocked) Pallas GEMM — the MoE MXU workhorse.

Reference analog: the token-sorted GroupGEMM producers in
``python/triton_dist/kernels/nvidia/moe_reduce_rs.py`` (tile loop keyed by
``gather_a_index``/``expert_idx`` tables) and
``allgather_group_gemm.py:200-330`` — every ``block_m``-row tile of the
expert-sorted token buffer belongs to exactly ONE expert, so each row tile
loads that expert's weight slab and runs a dense matmul.  The CUDA side gets
its tile→expert map from ``csrc/moe_utils.cu``; ours comes from
``moe_utils.sort_align`` (same contract: sorted rows padded per expert to the
tile size).

TPU-native design: a scalar-prefetch grid spec carries the ``tile_expert``
map into SMEM ahead of the grid, and the weight BlockSpec's index map reads
it to steer each row tile's slab to ``w[tile_expert[i]]``.  The Mosaic
pipeline then streams tokens and the selected expert slab HBM→VMEM onto the
MXU exactly like the dense matmul — no gathered copy of the weights is ever
materialized (the reference needs neither, and neither do we).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu.kernels.gemm import (
    group_gemm_pipeline_body,
    largest_divisor_block,
    pallas_shapes_ok,
    resolve_impl,
    use_fallback,
)
from triton_dist_tpu.language.interpret import maybe_interpret


def group_gemm_xla(x_sorted, w_stack, tile_expert, block_m: int, out_dtype=None):
    """Dense-einsum fallback: gather one weight slab per row tile.

    Keeps shapes static (n_tiles × [block_m, K] @ [K, N]); XLA turns the
    weight gather into per-tile dynamic slices.  Runs everywhere — the
    correctness baseline for the pallas path.
    """
    quantized = x_sorted.dtype == jnp.int8
    out_dtype = out_dtype or (jnp.int32 if quantized else x_sorted.dtype)
    m_pad, k_dim = x_sorted.shape
    n_tiles = m_pad // block_m
    xt = x_sorted.reshape(n_tiles, block_m, k_dim)
    wt = w_stack[tile_expert]  # [n_tiles, K, N]
    yt = jnp.einsum("tbk,tkn->tbn", xt, wt,
                    preferred_element_type=(jnp.int32 if quantized
                                            else jnp.float32))
    return yt.astype(out_dtype).reshape(m_pad, w_stack.shape[-1])


def load_aware_block_m(total_rows: int, n_experts: int,
                       floor: int = 128) -> int:
    """Load-aware sort/GEMM row-tile size (VERDICT r3 #4).

    The real-chip sweep (docs/perf.md "Grouped GEMM MFU") says tile
    height is the whole game: 128-row tiles reach 42-54% MFU, 512-row
    tiles ~87% — but a 512 tile on a sparsely-loaded expert is mostly
    sort padding (wasted rows ≈ E * block_m/2).  Rule: the largest of
    {128, 256, 512} not exceeding the *balanced* per-expert load
    ``total_rows / n_experts`` — dense prefill gets the 512 MFU winner,
    sparse serving degrades toward the padding-lean 128.
    """
    per_expert = max(total_rows // max(n_experts, 1), 1)
    best = floor
    for b in (256, 512):
        if per_expert >= b:
            best = b
    return best


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "bn", "bk", "out_dtype", "impl", "interpret"),
)
def group_gemm(
    x_sorted: jax.Array,     # [M_pad, K] expert-sorted tokens (padding rows 0)
    w_stack: jax.Array,      # [E, K, N] per-expert weights
    tile_expert: jax.Array,  # [M_pad // block_m] int32 expert of each row tile
    *,
    block_m: int,
    bn: int | None = None,
    bk: int | None = None,
    out_dtype=None,
    impl: str = "auto",
    interpret: bool = False,
) -> jax.Array:
    """y[M_pad, N] where row tile i is ``x_tile @ w_stack[tile_expert[i]]``.

    ``block_m`` must be the block size given to ``moe_utils.sort_align`` (it
    defines the tile→expert granularity).  Larger row tiles feed the MXU
    better (real-chip grouped-only MFU at the DeepSeek serving shape:
    block_m 128 → ~54%, 512 → ~87% bf16; ~46% → ~87% int8 — docs/perf.md)
    at the cost of more
    per-expert sort padding; callers with dense expert loads should raise
    it.  ``bn``/``bk`` default to the swept winners per dtype (bf16
    (512, 1024); int8 (1024, 1024) — int8 wants double-depth k just like
    the dense kernel).  Differentiable: see :func:`_group_gemm_core` (dx is
    a grouped GEMM against transposed slabs; dW segment-sums per-tile outer
    products by expert).
    """
    if bn is None:
        bn = 1024 if x_sorted.dtype == jnp.int8 else 512
    if bk is None:
        bk = 1024
    # Launch metadata (profiling.annotate contract): every padded row
    # tile runs one [block_m, K] x [K, N] expert GEMM.
    from triton_dist_tpu.runtime.profiling import annotate

    M_pad, K = x_sorted.shape
    N = w_stack.shape[2]
    el = jnp.dtype(x_sorted.dtype).itemsize
    with annotate("group_gemm", flops=2 * M_pad * K * N,
                  bytes_accessed=(M_pad * K + M_pad * N) * el
                  + w_stack.size * el):
        return _group_gemm_core(x_sorted, w_stack, tile_expert, block_m,
                                bn, bk, out_dtype, impl, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _group_gemm_core(x_sorted, w_stack, tile_expert, block_m, bn, bk,
                     out_dtype, impl, interpret):
    return _group_gemm_fwd_impl(x_sorted, w_stack, tile_expert, block_m, bn,
                                bk, out_dtype, impl, interpret)


def _group_gemm_vjp_fwd(x_sorted, w_stack, tile_expert, block_m, bn, bk,
                        out_dtype, impl, interpret):
    y = _group_gemm_fwd_impl(x_sorted, w_stack, tile_expert, block_m, bn, bk,
                             out_dtype, impl, interpret)
    return y, (x_sorted, w_stack, tile_expert)


def _group_gemm_vjp_bwd(block_m, bn, bk, out_dtype, impl, interpret,
                        res, dy):
    x_sorted, w_stack, tile_expert = res
    # dx tile i = dy tile i @ W[te[i]]^T — the same grouped GEMM shape.
    dx = _group_gemm_core(
        dy.astype(x_sorted.dtype), jnp.swapaxes(w_stack, 1, 2), tile_expert,
        block_m, bk, bn, x_sorted.dtype, impl, interpret)
    # dW[e] = Σ_{i: te[i]=e} x_tile_i^T @ dy_tile_i (padding rows are zero in
    # x_sorted, so they contribute nothing).  Contract tiles directly into
    # expert slots via a one-hot factor: peak memory E*K*N, not the
    # n_tiles*K*N a per-tile outer-product + scatter-add would materialize
    # (which is GBs at Mixtral shapes).
    n_tiles = tile_expert.shape[0]
    n_experts = w_stack.shape[0]
    xt = x_sorted.reshape(n_tiles, block_m, -1)
    dyt = dy.reshape(n_tiles, block_m, -1)
    onehot = jax.nn.one_hot(tile_expert, n_experts, dtype=jnp.float32)
    dw = jnp.einsum("te,tbk,tbn->ekn", onehot, xt, dyt,
                    preferred_element_type=jnp.float32).astype(w_stack.dtype)
    return dx, dw, np.zeros(tile_expert.shape, jax.dtypes.float0)


_group_gemm_core.defvjp(_group_gemm_vjp_fwd, _group_gemm_vjp_bwd)


def group_gemm_live(x_sorted, w_stack, tile_expert, n_live, *, block_m: int,
                    bn: int = 2048, bk: int = 1024, out_dtype=None,
                    impl: str = "auto", interpret: bool = False,
                    name: str | None = None):
    """:func:`group_gemm` for the few-rows-an-expert regime of serving
    (decode steps, prefill chunks): only the first ``n_live`` row tiles
    hold assignments (``moe_utils.sort_align_held`` puts the held
    experts' tiles first), and the grid skips the rest — a dead tile
    multiplies nothing and, its blocks pinned to the last live step's,
    copies nothing.  Output rows of dead tiles are NOT written: mask them
    (``valid_rows``) before use.  Each live tile streams its expert's
    whole slab once, so the call is bound by the weights of the experts
    hit; the wide default blocks keep the steps few.  ``name`` is the
    Mosaic call's name in a device trace.  Forward only."""
    return _group_gemm_fwd_impl(x_sorted, w_stack, tile_expert, block_m,
                                bn, bk, out_dtype, impl, interpret,
                                n_live=n_live, name=name)


def _group_gemm_fwd_impl(x_sorted, w_stack, tile_expert, block_m, bn, bk,
                         out_dtype, impl, interpret, n_live=None,
                         name=None):
    m_pad, k_dim = x_sorted.shape
    n_experts, k2, n_dim = w_stack.shape
    assert k_dim == k2, (x_sorted.shape, w_stack.shape)
    assert m_pad % block_m == 0, (m_pad, block_m)
    # A block_m mismatched with the sort_align plan would silently steer
    # tiles to garbage expert slabs on the pallas path (te[i] read OOB).
    assert tile_expert.shape == (m_pad // block_m,), (
        tile_expert.shape, m_pad, block_m)
    # int8 inputs: exact i32 accumulation/output on the MXU double-rate
    # path (W8A8 expert compute; dequant happens at the caller).
    quantized = x_sorted.dtype == jnp.int8
    out_dtype = out_dtype or (jnp.int32 if quantized else x_sorted.dtype)
    acc_dtype = jnp.int32 if quantized else jnp.float32

    raw_impl = impl
    impl = resolve_impl(impl, interpret)
    if use_fallback(raw_impl, impl, pallas_shapes_ok(block_m, n_dim, k_dim),
                    "group_gemm", f"(block_m={block_m}, N={n_dim}, K={k_dim}); needs m%8, n%128, k%128"):
        return group_gemm_xla(x_sorted, w_stack, tile_expert, block_m, out_dtype)

    bn = largest_divisor_block(n_dim, bn, 128)
    bk = largest_divisor_block(k_dim, bk, 128)
    n_tiles, n_n, n_k = m_pad // block_m, n_dim // bn, k_dim // bk

    if n_live is None:
        prefetch = (tile_expert,)
        x_map = lambda i, j, k, te: (i, k)                    # noqa: E731
        w_map = lambda i, j, k, te: (te[i], k, j)             # noqa: E731
        o_map = lambda i, j, k, te: (i, j)                    # noqa: E731

        def _kernel(te_ref, x_ref, w_ref, out_ref, acc_ref):
            group_gemm_pipeline_body(x_ref, w_ref, out_ref, acc_ref,
                                     n_k=n_k, out_dtype=out_dtype)
    else:
        # Dead tiles (i >= n_live) keep every block index at the LAST
        # live step's: the pipeline sees no index change, so it copies
        # nothing in and writes nothing back until the grid ends.
        prefetch = (tile_expert, jnp.reshape(n_live, (1,)).astype(jnp.int32))

        def _pin(i, nl, live, dead):
            return jnp.where(i < nl[0], live, dead)

        def x_map(i, j, k, te, nl):
            last = jnp.maximum(nl[0] - 1, 0)
            return _pin(i, nl, i, last), _pin(i, nl, k, n_k - 1)

        def w_map(i, j, k, te, nl):
            last = jnp.maximum(nl[0] - 1, 0)
            return (te[jnp.minimum(i, last)], _pin(i, nl, k, n_k - 1),
                    _pin(i, nl, j, n_n - 1))

        def o_map(i, j, k, te, nl):
            last = jnp.maximum(nl[0] - 1, 0)
            return _pin(i, nl, i, last), _pin(i, nl, j, n_n - 1)

        def _kernel(te_ref, nl_ref, x_ref, w_ref, out_ref, acc_ref):
            @pl.when(pl.program_id(0) < nl_ref[0])
            def _():
                group_gemm_pipeline_body(x_ref, w_ref, out_ref, acc_ref,
                                         n_k=n_k, out_dtype=out_dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(n_tiles, n_n, n_k),
        in_specs=[
            pl.BlockSpec((block_m, bk), x_map),
            pl.BlockSpec((1, bk, bn), w_map),
        ],
        out_specs=pl.BlockSpec((block_m, bn), o_map),
        scratch_shapes=[pltpu.VMEM((block_m, bn), acc_dtype)],
    )

    return pl.pallas_call(
        _kernel,
        name=name,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m_pad, n_dim), out_dtype),
        # Row tiles and n-blocks are independent; only k accumulates.
        # Same knob as the dense matmul's 96%-MXU config (gemm.py).
        # (the live form revisits the last live output block from its dead
        # tiles, which only an in-order grid may do)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
            if n_live is None else ("arbitrary",) * 3),
        cost_estimate=pl.CostEstimate(
            flops=2 * m_pad * n_dim * k_dim,
            bytes_accessed=(m_pad * k_dim + n_experts * k_dim * n_dim)
            * x_sorted.dtype.itemsize
            + m_pad * n_dim * jnp.dtype(out_dtype).itemsize,
            transcendentals=0,
        ),
        interpret=maybe_interpret(interpret),
    )(*prefetch, x_sorted, w_stack)


def moe_ffn_sorted(x_sorted, w_gate, w_up, w_down, tile_expert, *,
                   block_m: int, impl: str = "auto", interpret: bool = False):
    """SwiGLU expert FFN over the sorted buffer: three grouped GEMMs.

    y = (silu(x @ Wg[e]) * (x @ Wu[e])) @ Wd[e] per expert tile — the
    per-expert MLP the reference's MoE tests build from its GroupGEMM.
    """
    gg = functools.partial(group_gemm, tile_expert=tile_expert,
                           block_m=block_m, impl=impl, interpret=interpret)
    gate = gg(x_sorted, w_gate)
    up = gg(x_sorted, w_up)
    hidden = (jax.nn.silu(gate.astype(jnp.float32))
              * up.astype(jnp.float32)).astype(x_sorted.dtype)
    return gg(hidden, w_down)
