"""Overlapped AllGather-GroupGEMM — MoE tensor-parallel forward (AG side).

Reference analog: ``python/triton_dist/kernels/nvidia/allgather_group_gemm.py``
(499 LoC) — tokens are allgathered across the TP group while a grouped GEMM
consumes them; each GEMM tile spins on the barrier of the source rank whose
tokens it needs (``dl.wait(block_barrier_ptr + offs_barrier, 1, "gpu",
"acquire")`` :482); the host pre-sorts gathered tokens by expert (:106-188).

TPU-native design (NOT a port):

* The reference sorts the *full* gathered buffer, so one tile can mix tokens
  from several source ranks and must wait on several barriers.  We instead
  sort **per source segment**: every device pre-sorts its own tokens by
  expert (static-padded via ``moe_utils.sort_align``), the sorted segments
  ride the same ring schedule as ``allgather_gemm.py``, and each ring step
  runs a grouped GEMM over exactly one segment.  Expert math is unchanged
  (a token's topk contributions never cross segments) and each tile depends
  on exactly one recv-semaphore — the multi-barrier wait disappears by
  construction.
* Routing metadata (topk expert ids + weights) is tiny, so it goes through
  one XLA allgather up front; every device then derives the *same* per-
  segment sort plans (the reference ships precomputed index tables to all
  ranks for the same reason, :106-188).
* Tile→expert weight steering inside the ring kernel reads the per-segment
  ``tile_expert`` map from SMEM in the inner pipeline's BlockSpec index map
  — the Mosaic analog of the scalar-prefetch steering in
  ``kernels/group_gemm.py`` (same contract, one map per ring slot).

Sharding contract (1-D TP over ``axis``; E experts, topk assignments):
  x:       [T, D]        P(axis, None)   tokens (per-device [t_loc, D])
  weights: [T, topk]     P(axis, None)   routing weights
  experts: [T, topk]     P(axis, None)   routing expert ids (int32)
  w_stack: [E, D, F]     P(None, None, axis)  expert weights (per-dev F_loc)
  out:     [T, F]        P(None, axis)   combined expert outputs
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

import triton_dist_tpu.language as dl
from triton_dist_tpu.kernels.gemm import (
    MatmulConfig,
    group_gemm_pipeline_body,
    largest_divisor_block,
    pallas_shapes_ok,
    resolve_impl,
    use_fallback,
)
from triton_dist_tpu.kernels.group_gemm import group_gemm_xla
from triton_dist_tpu.kernels.moe_utils import (
    combine_topk,
    gather_sorted,
    padded_rows,
    sort_align,
)
from triton_dist_tpu.language.interpret import maybe_interpret
from triton_dist_tpu.runtime.jit_cache import cached_shard_jit

from triton_dist_tpu.kernels.collective_ids import AG_GROUP_GEMM as AG_GROUP_GEMM_COLLECTIVE_ID


@dataclass
class AGGroupGEMMContext:
    """Reference analog: the context of ``create_ag_group_gemm_context``
    (allgather_group_gemm.py) — symm workspace/streams replaced by the
    kernel's own output buffer and DMA queues."""

    mesh: Mesh
    n_experts: int
    topk: int
    axis: str = "tp"
    # sort_align tile granularity == GEMM row-tile size.  None = derive
    # load-aware at the host entry (dense loads get the measured 512 MFU
    # winner, sparse loads stay padding-lean; group_gemm.load_aware_block_m).
    block_m: int | None = None
    impl: str = "auto"
    config: MatmulConfig = field(default_factory=MatmulConfig)
    interpret: bool = False

    @property
    def world(self) -> int:
        return self.mesh.shape[self.axis]


def create_ag_group_gemm_context(mesh, n_experts, topk, axis="tp",
                                 block_m=None, impl="auto", config=None,
                                 interpret=False) -> AGGroupGEMMContext:
    return AGGroupGEMMContext(
        mesh=mesh, n_experts=n_experts, topk=topk, axis=axis,
        block_m=block_m, impl=impl, config=config or MatmulConfig(),
        interpret=interpret,
    )


def _ag_group_gemm_kernel(
    te_ref,     # [world, n_tiles] SMEM: per-segment tile→expert maps
    x_ref,      # [m_pad, D]       ANY: local expert-sorted segment
    w_ref,      # [E, D, f_loc]    ANY: expert weight slabs (local F shard)
    ag_ref,     # [world*m_pad, D] ANY out: gathered sorted segments
    out_ref,    # [world*m_pad, f_loc] ANY out: grouped-GEMM outputs
    send_sem, recv_sem, copy_sem,
    acc_ref,    # VMEM (block_m, bn) f32
    *,
    axis, world, m_pad, block_m, bn, bk, out_dtype,
):
    me = jax.lax.axis_index(axis)
    right = jax.lax.rem(me + 1, world)
    left = jax.lax.rem(me + world - 1, world)

    cp = pltpu.make_async_copy(x_ref, ag_ref.at[pl.ds(me * m_pad, m_pad)], copy_sem)
    cp.start()
    cp.wait()

    if world > 1:
        barrier = pltpu.get_barrier_semaphore()
        pltpu.semaphore_signal(barrier, inc=1, device_id={axis: left},
                               device_id_type=pltpu.DeviceIdType.MESH)
        pltpu.semaphore_signal(barrier, inc=1, device_id={axis: right},
                               device_id_type=pltpu.DeviceIdType.MESH)
        pltpu.semaphore_wait(barrier, 2)

    D = x_ref.shape[1]
    f_loc = w_ref.shape[2]
    n_tiles, n_n, n_k = m_pad // block_m, f_loc // bn, D // bk

    for s in range(world):
        slot = jax.lax.rem(me - s + world, world)
        seg = ag_ref.at[pl.ds(slot * m_pad, m_pad)]
        if s > 0:
            pltpu.make_async_copy(seg, seg, recv_sem).wait()
        if s < world - 1:
            dl.remote_copy(seg, seg, send_sem, recv_sem, axis, right).start()

        # Grouped GEMM over this segment: row tile i uses expert slab
        # te[slot, i].  The SMEM read in the index map is the scalar-prefetch
        # steering (group_gemm.py) adapted to the in-kernel pipeline.
        inner = pltpu.emit_pipeline(
            functools.partial(group_gemm_pipeline_body, n_k=n_k,
                              out_dtype=out_dtype),
            grid=(n_tiles, n_n, n_k),
            in_specs=[
                pl.BlockSpec((block_m, bk), lambda i, j, k: (i, k)),
                pl.BlockSpec(
                    (1, bk, bn),
                    lambda i, j, k, slot=slot: (te_ref[slot, i], k, j)),
            ],
            out_specs=[pl.BlockSpec((block_m, bn), lambda i, j, k: (i, j))],
        )
        inner(seg, w_ref, out_ref.at[pl.ds(slot * m_pad, m_pad)],
              scratches=(acc_ref,))

        if s < world - 1:
            pltpu.make_async_copy(seg, seg, send_sem).wait()


def _segment_plans(experts_all, n_experts: int, block_m: int):
    """Identical-on-every-device per-segment sort plans.

    experts_all: [world, t_loc, topk].  Returns (dest [world, t_loc*topk],
    tile_expert [world, n_tiles], m_pad).
    """

    def plan(e):
        p = sort_align(e, n_experts, block_m)
        return p["dest"], p["tile_expert"]

    dest, te = jax.vmap(plan)(experts_all)
    _, t_loc, topk = experts_all.shape
    m_pad = padded_rows(t_loc * topk, n_experts, block_m)
    return dest, te, m_pad


def ag_group_gemm_shard(x_loc, weights_loc, experts_loc, w_stack, *,
                        axis, n_experts, topk, block_m, bn, bk, impl,
                        interpret):
    """Per-device AG-GroupGEMM; call inside shard_map.

    Returns out [T, f_loc]: token-major combined expert outputs for the FULL
    gathered token set (every device computes all tokens against its local
    slice of every expert — standard MoE TP, reference allgather_group_gemm).
    """
    raw_impl = impl
    impl = resolve_impl(impl, interpret)
    world = jax.lax.axis_size(axis)
    t_loc, d_model = x_loc.shape
    f_loc = w_stack.shape[2]
    me = jax.lax.axis_index(axis)

    # Small metadata gather: routing for every segment, identical everywhere.
    experts_all = jax.lax.all_gather(experts_loc, axis, axis=0)   # [w,t,topk]
    weights_all = jax.lax.all_gather(weights_loc, axis, axis=0)
    dest_all, te_all, m_pad = _segment_plans(experts_all, n_experts, block_m)

    # Pre-sort the local segment (reference host-side sort, :106-188).
    dest_me = jax.lax.dynamic_index_in_dim(dest_all, me, keepdims=False)
    xs_loc = gather_sorted(x_loc, dest_me, m_pad)

    if use_fallback(raw_impl, impl, pallas_shapes_ok(block_m, f_loc, d_model),
                    "ag_group_gemm",
                    f"(block_m={block_m}, f_loc={f_loc}, d={d_model}); needs m%8, n%128, k%128"):
        xs_all = jax.lax.all_gather(xs_loc, axis, axis=0, tiled=True)
        ys = group_gemm_xla(xs_all, w_stack, te_all.reshape(-1), block_m)
    else:
        bn_ = largest_divisor_block(f_loc, bn, 128)
        bk_ = largest_divisor_block(d_model, bk, 128)
        _, ys = pl.pallas_call(
            functools.partial(
                _ag_group_gemm_kernel, axis=axis, world=world, m_pad=m_pad,
                block_m=block_m, bn=bn_, bk=bk_, out_dtype=x_loc.dtype,
            ),
            out_shape=[
                jax.ShapeDtypeStruct((world * m_pad, d_model), x_loc.dtype),
                jax.ShapeDtypeStruct((world * m_pad, f_loc), x_loc.dtype),
            ],
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[pl.BlockSpec(memory_space=pl.ANY),
                       pl.BlockSpec(memory_space=pl.ANY)],
            scratch_shapes=[
                pltpu.SemaphoreType.DMA,
                pltpu.SemaphoreType.DMA,
                pltpu.SemaphoreType.DMA,
                pltpu.VMEM((block_m, bn_), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                has_side_effects=True,
                collective_id=AG_GROUP_GEMM_COLLECTIVE_ID if world > 1 else None,
            ),
            interpret=maybe_interpret(interpret),
        )(te_all, xs_loc, w_stack)

    # Per-segment topk combine back to token order (reference: the topk
    # scatter/reduce epilogue).  Segment s's tokens land at rows
    # [s*t_loc, (s+1)*t_loc).
    ys_seg = ys.reshape(world, m_pad, f_loc)
    out = jax.vmap(combine_topk)(ys_seg, dest_all, weights_all)
    return out.reshape(world * t_loc, f_loc)


def ag_group_gemm(x, weights, experts, w_stack, ctx: AGGroupGEMMContext):
    """out[T, F] = MoE-FFN(allgather(x)) with AG overlapped into the grouped
    GEMM.  Host entry (reference ``ag_group_gemm``)."""
    from triton_dist_tpu.kernels.group_gemm import load_aware_block_m

    cfg = ctx.config
    T = x.shape[0]
    block_m = ctx.block_m or load_aware_block_m(T * ctx.topk, ctx.n_experts)
    fn = cached_shard_jit(
        ag_group_gemm_shard,
        ctx.mesh,
        (P(ctx.axis, None), P(ctx.axis, None), P(ctx.axis, None),
         P(None, None, ctx.axis)),
        P(None, ctx.axis),
        axis=ctx.axis, n_experts=ctx.n_experts, topk=ctx.topk,
        block_m=block_m, bn=cfg.block_n, bk=cfg.block_k,
        impl=ctx.impl, interpret=ctx.interpret,
    )
    # Launch metadata: every device multiplies all T*topk (padded) rows
    # against its F shard of every expert.
    from triton_dist_tpu.runtime.profiling import annotate

    d_model = x.shape[1]
    f_loc = w_stack.shape[2] // max(ctx.world, 1)
    el = jnp.dtype(x.dtype).itemsize
    with annotate("ag_group_gemm",
                  flops=2 * T * ctx.topk * d_model * f_loc,
                  bytes_accessed=(T * d_model + T * ctx.topk * f_loc) * el
                  + w_stack.size // max(ctx.world, 1) * el):
        return fn(x, weights, experts, w_stack)


# ---------------------------------------------------------------------------
# Autotuned entry (VERDICT r3 #4: the grouped overlapped kernels sweep too,
# as round 3 did for the dense ag_gemm/gemm_rs pair).
# ---------------------------------------------------------------------------

from triton_dist_tpu.autotuner import Config as _Cfg, autotune as _autotune

# Row-tile height is the dominant knob (128 → 42-54% MFU, 512 → ~87%;
# docs/perf.md "Grouped GEMM MFU"); (bn, bk) pairs are the measured bf16
# and int8 winners plus the old defaults for contrast.
AG_GROUP_GEMM_TUNE_SPACE = [
    _Cfg(block_m=128, bn=512, bk=512),
    _Cfg(block_m=256, bn=512, bk=1024),
    _Cfg(block_m=512, bn=512, bk=1024),   # bf16 sweep winner
    _Cfg(block_m=512, bn=1024, bk=1024),  # int8 sweep winner
]


@_autotune(configs=AG_GROUP_GEMM_TUNE_SPACE, key=())
def _ag_group_gemm_tunable(x, weights, experts, w_stack, *, ctx,
                           block_m=None, bn=None, bk=None):
    tuned = AGGroupGEMMContext(
        mesh=ctx.mesh, n_experts=ctx.n_experts, topk=ctx.topk,
        axis=ctx.axis, block_m=block_m, impl=ctx.impl,
        config=MatmulConfig(ctx.config.block_m, bn, bk),
        interpret=ctx.interpret)
    return ag_group_gemm(x, weights, experts, w_stack, tuned)


def ag_group_gemm_autotuned(x, weights, experts, w_stack,
                            ctx: AGGroupGEMMContext):
    """:func:`ag_group_gemm` with (block_m, bn, bk) selected by the
    autotuner.  Each config re-traces the WHOLE overlapped op — the sort
    plans change with block_m, so the measurement covers the real cost of
    a tile height, padding included.  Same lockstep/is_dist rules as
    ``ag_gemm_autotuned``."""
    return _ag_group_gemm_tunable(x, weights, experts, w_stack, ctx=ctx)
