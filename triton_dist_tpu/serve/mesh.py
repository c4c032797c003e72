"""Mesh placement for :class:`serve.engine.ServeEngine` — TP weights +
sharded paged KV under ``shard_map`` (docs/serving.md "Sharded serving").

The engine's device programs (paged decode, multi-token verify, the
fused decode horizon, chunked prefill, the page scatter/gather/COW
trio, the fused speculative round) are all parameterized over cache
addressing and the two weight-reduction seams (``generate._token_forward``
/ ``_multitoken_forward`` / ``_chunk_forward``'s ``write_kv`` /
``attend`` / ``ffn`` / ``out_proj`` hooks) — this module instantiates
them PER-SHARD and wraps each in ``jax.jit(jax.shard_map(...))`` so the
same engine step loop, scheduler, and block tables drive a multi-chip
forward.  Two KV layouts:

- ``kv_shard="heads"`` — Megatron-style tensor parallelism: weights
  shard by ``models.llama.param_specs`` (QKV/up-gate column-parallel,
  attn-out/down row-parallel + ``psum``), the paged pools shard on the
  KV-head axis, and each rank runs ``gqa_decode_paged_shard`` over its
  own heads (attention is head-independent, so no inter-rank combine
  exists on the attention path).  Supports everything the world-1
  engine does, speculative rounds included (the draft model runs
  replicated per rank — its batch caches are slot-indexed host-managed
  state that must stay whole on every rank).
- ``kv_shard="seq"`` — SP flash-decode (the reference's headline 1→32
  scaling, SURVEY.md §5): pools shard on the BLOCK axis, each rank
  holds the pages of its contiguous sequence span, attention goes
  through ``sp_gqa_decode_paged_shard`` (per-rank local lengths + the
  LSE combine) with the rank's slice of the block table rebased to
  local pool rows.  Weights stay replicated (the decode-serving layout
  of models/generate.py: the sharded thing is the KV cache).  Since
  ISSUE 19 the layout is first-class: the paged SP combine merges
  queries×heads 4D partials, so multi-token verify — and therefore
  speculative decode — runs under seq, and chunked prefill attends
  over the rank-local slice of the scratch (per-shard partials + the
  same LSE combine) instead of computing replicated.
- ``kv_shard="heads+seq"`` — the 2D composition (ISSUE 19): one
  ``Mesh((tp, sp))`` where weights and attention heads shard on the
  ``tp`` axis (psum only at the out-proj/FFN row-parallel seams,
  exactly the heads layout) while the paged pools and the partitioned
  BlockManager shard on the block axis over ``sp`` (partition count =
  sp world, NOT total world).  Every per-shard body is the seq body
  with the TP seams threaded through (``fwd_cfg``/``ffn``/
  ``out_proj``), so attention runs per-rank over (local heads × local
  blocks) and combines on ``sp`` only — KV capacity (sp) and per-step
  latency (tp) scale on independent axes.

**The executable-cache fork (the PR-7 problem, solved here).**  A
mesh-placed program's outputs carry ``NamedSharding`` while host-built
arrays carry single-device placements, and jax's jit cache keys on the
argument shardings — so one traced program would split into host-built
vs device-carried executable flavors that ``warmup()`` cannot
enumerate (the compile-miss counter would tick under traffic).
:class:`ShardedProgram` therefore CANONICALIZES every argument at the
call seam: each arg is ``device_put`` onto its declared
``NamedSharding`` unless it already carries it, so every call of a
program presents ONE signature and the cache holds exactly one
executable per (shapes, statics) — ``warmup()`` reaches the same
compile fixed point as world-1 and the miss counter stays flat.

Bit-exactness note: per-head attention, column-parallel projections and
the replicated sampling/commit path are arithmetically identical to
world-1; the row-parallel ``psum`` seams reduce in shard-major order,
which the oracle tests pin stream-exact on the test models (the same
standard tests/test_generate.py holds the SP combine to at world 4).
"""

from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.kernels.flash_decode import (
    sp_gqa_decode_paged_shard,
    sp_gqa_decode_shard,
)
from triton_dist_tpu.models.generate import (
    _chunk_forward,
    _multitoken_forward,
    _token_forward,
)
from triton_dist_tpu.models.llama import param_specs
from triton_dist_tpu.runtime import jit_cache


# ---------------------------------------------------------------------------
# Geometry validation — the loud construction-time rejection matrix
# ---------------------------------------------------------------------------


KV_SHARDS = ("heads", "seq", "heads+seq")


def _check_heads_geometry(cfg, world, kv_shard, label):
    """The heads-TP divisibility rules, parameterized over the axis
    label so a 2D rejection names WHICH axis failed."""
    if cfg.n_kv_heads % world:
        raise ValueError(
            f"kv_shard={kv_shard!r} needs n_kv_heads ({cfg.n_kv_heads}) "
            f"divisible by the {label} ({world}) — each rank "
            f"must own whole KV heads of the paged pools")
    if cfg.n_heads % world:
        raise ValueError(
            f"kv_shard={kv_shard!r} needs n_heads ({cfg.n_heads}) "
            f"divisible by the {label} ({world}) — the "
            f"column-parallel QKV split assigns whole query heads "
            f"per rank")
    if cfg.ffn_dim % world:
        raise ValueError(
            f"TP weights need ffn_dim ({cfg.ffn_dim}) divisible by "
            f"the {label} ({world}) — wgate/wup shard by "
            f"columns, wdown by rows")


def _check_seq_geometry(max_seq, num_blocks, page_size, world, kv_shard,
                        label):
    """The seq-SP divisibility rules, axis-labeled like the heads
    twin."""
    n_pages = max_seq // page_size
    if n_pages % world:
        raise ValueError(
            f"kv_shard={kv_shard!r} needs max_seq/page_size ({n_pages} "
            f"logical pages) divisible by the {label} ({world}) "
            f"— each rank owns a contiguous span of "
            f"{n_pages}//{world} logical pages")
    if num_blocks % world:
        raise ValueError(
            f"kv_shard={kv_shard!r} needs num_blocks ({num_blocks}) "
            f"divisible by the {label} ({world}) — the pool "
            f"splits into equal per-rank partitions")
    if num_blocks // world < 2:
        raise ValueError(
            f"kv_shard={kv_shard!r} needs num_blocks//world >= 2 "
            f"({num_blocks}//{world} = {num_blocks // world}): "
            f"every partition reserves its own null block and "
            f"still needs at least one allocatable page")
    if page_size % world:
        raise ValueError(
            f"kv_shard={kv_shard!r} needs page_size ({page_size}) "
            f"divisible by the {label} ({world}) — the sharded "
            f"chunked-prefill attend splits every scratch-extent rung "
            f"(a page multiple) into equal per-rank row spans")


def validate_mesh_geometry(*, mesh, tp_axis, kv_shard, cfg, max_seq,
                           num_blocks, page_size, spec_k=0,
                           sp_axis=None) -> int:
    """Reject impossible (mesh, engine-geometry) combinations with a
    loud ``ValueError`` at CONSTRUCTION — the alternative is a shape
    error deep inside a traced forward, long after the caller can tell
    which knob was wrong.  Returns the TOTAL mesh world the layout
    spans: the size along ``tp_axis`` for the 1-axis layouts, tp × sp
    for ``"heads+seq"`` (the 2D rejection matrix names which axis a
    failed divisibility belongs to).  ``spec_k`` rides along for
    API stability only — speculative decode serves every layout since
    the 4D-q SP combine landed (ISSUE 19)."""
    del spec_k  # spec × seq works now: the combine merges 4D partials
    if tp_axis not in mesh.axis_names:
        raise ValueError(
            f"tp_axis {tp_axis!r} is not an axis of the mesh "
            f"{mesh.axis_names}")
    if kv_shard not in KV_SHARDS:
        raise ValueError(
            f"kv_shard must be one of {KV_SHARDS}, got {kv_shard!r}")
    world = int(mesh.shape[tp_axis])
    if world < 1:
        raise ValueError(f"mesh axis {tp_axis!r} has size {world}")
    if kv_shard == "heads":
        _check_heads_geometry(cfg, world, kv_shard, "mesh world")
    elif kv_shard == "seq":
        _check_seq_geometry(max_seq, num_blocks, page_size, world,
                            kv_shard, "mesh world")
    else:  # heads+seq: the world must factor as tp x sp on NAMED axes
        if sp_axis is None:
            raise ValueError(
                "kv_shard='heads+seq' needs an sp_axis: the world must "
                "factor as tp x sp over two named mesh axes (weights/"
                "heads on tp, KV blocks on sp)")
        if sp_axis not in mesh.axis_names:
            raise ValueError(
                f"sp_axis {sp_axis!r} is not an axis of the mesh "
                f"{mesh.axis_names}")
        if sp_axis == tp_axis:
            raise ValueError(
                f"kv_shard='heads+seq' needs DISTINCT tp/sp axes, got "
                f"{tp_axis!r} for both — a 1-axis mesh cannot factor "
                f"the world as tp x sp")
        sp = int(mesh.shape[sp_axis])
        _check_heads_geometry(cfg, world, kv_shard,
                              f"tp axis {tp_axis!r}")
        _check_seq_geometry(max_seq, num_blocks, page_size, sp,
                            kv_shard, f"sp axis {sp_axis!r}")
        world = world * sp
    return world


@dataclasses.dataclass(frozen=True)
class _ShardCfg:
    """The per-shard config view the shared forwards see under TP:
    LOCAL head counts with the GLOBAL ``head_dim``/``dim`` — a plain
    ``dataclasses.replace(cfg, n_heads=...)`` would silently corrupt
    ``LlamaConfig.head_dim`` (a ``dim // n_heads`` property), so the
    fields the forwards read are pinned explicitly here."""

    n_heads: int
    n_kv_heads: int
    head_dim: int
    dim: int
    norm_eps: float
    rope_theta: float
    dtype: object
    attn_window: int
    attn_soft_cap: float


def _local_cfg(cfg, world: int):
    """The per-shard view of a TP-sharded model: local head counts (the
    shared forwards reshape QKV by ``cfg.n_heads``/``n_kv_heads``, and
    each rank's column shards hold exactly ``1/world`` of the heads).
    Everything else — dim, head_dim, norms, rope — stays global."""
    return _ShardCfg(n_heads=cfg.n_heads // world,
                     n_kv_heads=cfg.n_kv_heads // world,
                     head_dim=cfg.head_dim, dim=cfg.dim,
                     norm_eps=cfg.norm_eps, rope_theta=cfg.rope_theta,
                     dtype=cfg.dtype, attn_window=cfg.attn_window,
                     attn_soft_cap=cfg.attn_soft_cap)


# ---------------------------------------------------------------------------
# The two TP reduction seams (generate.py's ffn / out_proj hooks)
# ---------------------------------------------------------------------------


def _tp_out_proj(o2, layer, *, axis):
    """Row-parallel attention output projection: each rank contracts its
    local head columns against its ``wo`` row shard, ``psum`` completes
    the sum — ``generate._default_out_proj`` with the contraction split
    across ranks."""
    return jax.lax.psum(o2 @ layer["wo"], axis)


def _tp_ffn(h2, layer, *, axis):
    """Megatron MLP: column-parallel gate/up on the replicated
    activations, row-parallel down + ``psum`` — the same SwiGLU math as
    ``generate._dense_prompt_ffn`` over the local feature shard."""
    act = (jax.nn.silu((h2 @ layer["wgate"]).astype(jnp.float32))
           .astype(h2.dtype) * (h2 @ layer["wup"]))
    return jax.lax.psum(act @ layer["wdown"], axis)


# ---------------------------------------------------------------------------
# Per-shard forward bodies (call inside shard_map)
# ---------------------------------------------------------------------------


def tp_paged_decode_shard(params, pools, tables, kv_lens, token, active,
                          *, cfg, page, axis, world, impl, interpret,
                          ffn=None, out_proj=None):
    """Head-sharded twin of ``engine._paged_decode_forward``: QKV
    project onto the rank's head columns, the K/V scatter lands in the
    rank's pool shard, attention runs ``gqa_decode_paged_shard`` over
    the local heads (no combine — heads are independent), and the
    output/FFN row-parallel matmuls ``psum``.  ``tables``/``kv_lens``
    are replicated (the host-managed index is global); the returned
    logits are replicated, so sampling and commit stay bit-identical to
    the world-1 path.  The block-table addressing is the ENGINE's own
    forward — this only supplies the TP seams (local-head cfg + psum
    hooks), so the addressing can never diverge between world-1 and
    mesh.  ``ffn``/``out_proj`` override the default TP seams (the
    w8a8 serving hooks ride here — same psum count, quantized
    contraction)."""
    from triton_dist_tpu.serve.engine import _paged_decode_forward

    return _paged_decode_forward(
        params, pools, tables, kv_lens, token, active, cfg=cfg,
        page=page, impl=impl, interpret=interpret,
        fwd_cfg=_local_cfg(cfg, world),
        ffn=ffn or functools.partial(_tp_ffn, axis=axis),
        out_proj=out_proj or functools.partial(_tp_out_proj, axis=axis))


def tp_paged_verify_shard(params, pools, tables, kv_lens, chunk, active,
                          *, cfg, page, axis, world, impl, interpret,
                          ffn=None, out_proj=None):
    """Head-sharded twin of ``engine._paged_verify_forward`` — the
    multi-token verify under shard_map; like the decode twin, the
    engine's own forward with the TP seams supplied."""
    from triton_dist_tpu.serve.engine import _paged_verify_forward

    return _paged_verify_forward(
        params, pools, tables, kv_lens, chunk, active, cfg=cfg,
        page=page, impl=impl, interpret=interpret,
        fwd_cfg=_local_cfg(cfg, world),
        ffn=ffn or functools.partial(_tp_ffn, axis=axis),
        out_proj=out_proj or functools.partial(_tp_out_proj, axis=axis))


def _rebase_local(ids, *, axis, world, num_blocks):
    """THE global→local block-id rebase of the seq layout, shared by
    every per-shard body that touches the pools: rank ``r`` owns global
    blocks ``[r*nb_loc, (r+1)*nb_loc)``; returns ``(mine, local)``
    where foreign/padded ids (another rank's blocks, the global null)
    map to local row 0 — the rank's own reserved null, so a non-owner's
    write or copy degenerates to a null self-touch exactly like an
    inactive row's."""
    nb_loc = num_blocks // world
    lo = jax.lax.axis_index(axis) * nb_loc
    mine = (ids >= lo) & (ids < lo + nb_loc)
    return mine, jnp.where(mine, ids - lo, 0)


def sp_paged_decode_shard(params, pools, tables, kv_lens, token, active,
                          *, cfg, page, axis, world, num_blocks,
                          n_pages_max, impl, interpret, fwd_cfg=None,
                          ffn=None, out_proj=None):
    """Sequence-sharded twin of ``engine._paged_decode_forward``:
    weights replicated, pools sharded on the BLOCK axis — rank ``r``
    holds global blocks ``[r*nb_loc, (r+1)*nb_loc)``, which the
    partitioned :class:`serve.block_manager.BlockManager` dedicates to
    the logical pages of rank ``r``'s sequence span.  The block table
    is global; each rank slices its span and rebases the ids to local
    pool rows (foreign/padded entries — including another rank's
    blocks and the global null — map to local row 0, the rank's own
    reserved null).  Attention goes through
    ``sp_gqa_decode_paged_shard`` (local lengths + LSE combine), so
    the returned logits are replicated.  Quantized pools ride through
    unchanged: ``_scatter_kv`` and ``_pool_views`` are both
    dict-aware, and the per-page scales feed the combine's dequant.

    ``axis``/``world`` are the SP axis; ``fwd_cfg``/``ffn``/
    ``out_proj`` thread the heads-TP seams through for the 2D
    ``"heads+seq"`` layout (local-head cfg + psum hooks on the tp
    axis) — the pool's head axis then holds the rank's local KV heads
    and the block addressing is untouched, so ONE body serves both
    layouts."""
    from triton_dist_tpu.serve.engine import (
        _page_slots,
        _pool_views,
        _scatter_kv,
    )

    n_loc = n_pages_max // world
    inc = active.astype(kv_lens.dtype)

    # The next write's physical slot, rebased: only the owning rank
    # writes the real row; everyone else's write redirects to ITS null
    # (local row 0) exactly like an inactive row.
    pool_row_g, in_page = _page_slots(tables, kv_lens, active, page=page)
    mine, pool_row = _rebase_local(pool_row_g, axis=axis, world=world,
                                   num_blocks=num_blocks)
    mine = mine & active
    pool_row = jnp.where(mine, pool_row, 0)
    in_page = jnp.where(mine, in_page, 0)

    def write_kv(li, pool, k, v):
        return _scatter_kv(pool, k, v, pool_row, in_page)

    me = jax.lax.axis_index(axis)
    lt = jax.lax.dynamic_slice_in_dim(tables, me * n_loc, n_loc, axis=1)
    _, lt = _rebase_local(lt, axis=axis, world=world,
                          num_blocks=num_blocks)

    def attend(li, q, pool):
        kq, vq, ks, vs = _pool_views(pool)
        return sp_gqa_decode_paged_shard(
            q, kq, vq, lt, kv_lens + inc, axis=axis,
            impl=impl, interpret=interpret, soft_cap=cfg.attn_soft_cap,
            window=cfg.attn_window, k_scale=ks, v_scale=vs)

    return _token_forward(params, pools, token, kv_lens,
                          cfg=fwd_cfg or cfg, write_kv=write_kv,
                          attend=attend, ffn=ffn, out_proj=out_proj)


def sp_paged_verify_shard(params, pools, tables, kv_lens, chunk, active,
                          *, cfg, page, axis, world, num_blocks,
                          n_pages_max, impl, interpret, fwd_cfg=None,
                          ffn=None, out_proj=None):
    """Sequence-sharded twin of ``engine._paged_verify_forward`` — the
    multi-token verify over block-sharded pools (ISSUE 19 debt (a):
    this body exists because ``sp_gqa_decode_paged_shard`` now merges
    queries×heads 4D partials).  The [B, T] write addressing is the
    engine forward's own math with the seq rebase applied elementwise:
    each of a row's T scatter targets redirects to the rank's local
    null unless the rank owns that block, so a verify chunk spanning a
    page boundary (and therefore possibly TWO ranks' partitions)
    writes each row exactly once fleet-wide.  Attention reads back
    through the rank's rebased table slice with GLOBAL ``kv_lens + T``
    — per-token causality rides the combine's unclipped local ends,
    exactly the contiguous SP verify contract.  TP seams as in
    :func:`sp_paged_decode_shard` (the 2D layout)."""
    from triton_dist_tpu.serve.engine import _pool_views, _scatter_kv

    n_loc = n_pages_max // world
    T = chunk.shape[1]
    n_pages = tables.shape[1]
    pos = kv_lens[:, None] + jnp.arange(T, dtype=jnp.int32)[None]  # [B, T]
    logical = jnp.minimum(pos // page, n_pages - 1)
    pool_row_g = jnp.take_along_axis(tables, logical, axis=1)      # [B, T]
    in_page = pos % page
    mine, pool_row = _rebase_local(pool_row_g, axis=axis, world=world,
                                   num_blocks=num_blocks)
    mine = mine & active[:, None]
    pool_row = jnp.where(mine, pool_row, 0)
    in_page = jnp.where(mine, in_page, 0)

    def write_kv(li, pool, k, v):
        return _scatter_kv(pool, k, v, pool_row, in_page)

    me = jax.lax.axis_index(axis)
    lt = jax.lax.dynamic_slice_in_dim(tables, me * n_loc, n_loc, axis=1)
    _, lt = _rebase_local(lt, axis=axis, world=world,
                          num_blocks=num_blocks)

    def attend(li, q, pool):
        kq, vq, ks, vs = _pool_views(pool)
        return sp_gqa_decode_paged_shard(
            q, kq, vq, lt, kv_lens + T, axis=axis,
            impl=impl, interpret=interpret, soft_cap=cfg.attn_soft_cap,
            window=cfg.attn_window, k_scale=ks, v_scale=vs)

    return _multitoken_forward(params, pools, chunk, pos,
                               cfg=fwd_cfg or cfg, write_kv=write_kv,
                               attend=attend, ffn=ffn,
                               out_proj=out_proj)


def tp_paged_decode_horizon_shard(params, pools, tables, kv_lens, token,
                                  active, eos_done, limits, counts,
                                  base_keys, temps, top_ks, top_ps,
                                  greedy, eos_ids, *, H, all_greedy, cfg,
                                  page, axis, world, impl, interpret,
                                  ffn=None, out_proj=None):
    """The fused decode horizon under shard_map (heads): the engine's
    ``_paged_decode_horizon`` scan with the TP per-step forward swapped
    in — on-device sampling and every carry stay replicated, so the
    token bursts are bit-identical to the world-1 scan."""
    from triton_dist_tpu.serve.engine import _paged_decode_horizon

    fwd = functools.partial(tp_paged_decode_shard, cfg=cfg, page=page,
                            axis=axis, world=world, impl=impl,
                            interpret=interpret, ffn=ffn,
                            out_proj=out_proj)
    return _paged_decode_horizon(
        params, pools, tables, kv_lens, token, active, eos_done, limits,
        counts, base_keys, temps, top_ks, top_ps, greedy, eos_ids, H=H,
        all_greedy=all_greedy, cfg=cfg, page=page, impl=impl,
        interpret=interpret, decode_fwd=fwd)


def sp_paged_decode_horizon_shard(params, pools, tables, kv_lens, token,
                                  active, eos_done, limits, counts,
                                  base_keys, temps, top_ks, top_ps,
                                  greedy, eos_ids, *, H, all_greedy, cfg,
                                  page, axis, world, num_blocks,
                                  n_pages_max, impl, interpret,
                                  fwd_cfg=None, ffn=None, out_proj=None):
    """The fused decode horizon over sequence-sharded pools: the same
    scan with the SP per-step forward (local spans + LSE combine).
    TP seams thread through for the 2D layout."""
    from triton_dist_tpu.serve.engine import _paged_decode_horizon

    fwd = functools.partial(sp_paged_decode_shard, cfg=cfg, page=page,
                            axis=axis, world=world,
                            num_blocks=num_blocks,
                            n_pages_max=n_pages_max, impl=impl,
                            interpret=interpret, fwd_cfg=fwd_cfg,
                            ffn=ffn, out_proj=out_proj)
    return _paged_decode_horizon(
        params, pools, tables, kv_lens, token, active, eos_done, limits,
        counts, base_keys, temps, top_ks, top_ps, greedy, eos_ids, H=H,
        all_greedy=all_greedy, cfg=cfg, page=page, impl=impl,
        interpret=interpret, decode_fwd=fwd)


def tp_spec_round_shard(params, draft_params, pools, dcaches, tables,
                        kv_lens, active, done, last_logits, dlast_logits,
                        counts, limits, k_rows, base_keys, temps, top_ks,
                        top_ps, greedy, eos_ids, *, K, all_greedy, cfg,
                        dcfg, page, axis, world, impl, interpret,
                        dimpl, dinterpret):
    """The whole fused speculative round under shard_map (heads): the
    target's verify + decode legs run head-sharded TP, the draft steps
    REPLICATED per rank (its slot-indexed batch caches are host-managed
    whole-batch state — sharding them would put the accept chain's
    inputs behind a gather), and the seeded accept/sampling math runs on
    replicated logits — bit-identical emissions per rank."""
    from triton_dist_tpu.serve.engine import (
        _draft_decode_forward,
        _spec_round_fused,
    )

    decode_fwd = functools.partial(tp_paged_decode_shard, cfg=cfg,
                                   page=page, axis=axis, world=world,
                                   impl=impl, interpret=interpret)
    verify_fwd = functools.partial(tp_paged_verify_shard, cfg=cfg,
                                   page=page, axis=axis, world=world,
                                   impl=impl, interpret=interpret)
    draft_step = functools.partial(_draft_decode_forward, cfg=dcfg,
                                   impl=dimpl, interpret=dinterpret)
    return _spec_round_fused(
        params, draft_params, pools, dcaches, tables, kv_lens, active,
        done, last_logits, dlast_logits, counts, limits, k_rows,
        base_keys, temps, top_ks, top_ps, greedy, eos_ids, K=K,
        all_greedy=all_greedy, cfg=cfg, page=page, impl=impl,
        interpret=interpret, draft_step=draft_step,
        decode_fwd=decode_fwd, verify_fwd=verify_fwd)


def sp_spec_round_shard(params, draft_params, pools, dcaches, tables,
                        kv_lens, active, done, last_logits, dlast_logits,
                        counts, limits, k_rows, base_keys, temps, top_ks,
                        top_ps, greedy, eos_ids, *, K, all_greedy, cfg,
                        dcfg, page, axis, world, num_blocks, n_pages_max,
                        impl, interpret, dimpl, dinterpret, fwd_cfg=None,
                        ffn=None, out_proj=None):
    """The fused speculative round over sequence-sharded pools (ISSUE 19
    debt (a) unlocked this: the 4D-q SP combine lets the verify leg run
    under ``seq``).  Target decode/verify use the SP bodies — local
    pool spans + LSE combine — while the draft steps stay REPLICATED
    per rank for the same host-managed-cache reason as the heads
    layout; accept/sampling math runs on replicated logits.  TP seams
    (``fwd_cfg``/``ffn``/``out_proj``) thread into the target legs for
    ``heads+seq``; the draft is NEVER head-sharded (its cfg would need
    its own local view for marginal win)."""
    from triton_dist_tpu.serve.engine import (
        _draft_decode_forward,
        _spec_round_fused,
    )

    decode_fwd = functools.partial(sp_paged_decode_shard, cfg=cfg,
                                   page=page, axis=axis, world=world,
                                   num_blocks=num_blocks,
                                   n_pages_max=n_pages_max,
                                   impl=impl, interpret=interpret,
                                   fwd_cfg=fwd_cfg, ffn=ffn,
                                   out_proj=out_proj)
    verify_fwd = functools.partial(sp_paged_verify_shard, cfg=cfg,
                                   page=page, axis=axis, world=world,
                                   num_blocks=num_blocks,
                                   n_pages_max=n_pages_max,
                                   impl=impl, interpret=interpret,
                                   fwd_cfg=fwd_cfg, ffn=ffn,
                                   out_proj=out_proj)
    draft_step = functools.partial(_draft_decode_forward, cfg=dcfg,
                                   impl=dimpl, interpret=dinterpret)
    return _spec_round_fused(
        params, draft_params, pools, dcaches, tables, kv_lens, active,
        done, last_logits, dlast_logits, counts, limits, k_rows,
        base_keys, temps, top_ks, top_ps, greedy, eos_ids, K=K,
        all_greedy=all_greedy, cfg=cfg, page=page, impl=impl,
        interpret=interpret, draft_step=draft_step,
        decode_fwd=decode_fwd, verify_fwd=verify_fwd)


def tp_chunk_forward_shard(params, chunk, caches, prefix_len, n_valid, *,
                           cfg, extent, axis, world, impl, interpret,
                           quantized=False, ffn=None, out_proj=None):
    """Head-sharded chunked prefill: ``generate._chunk_forward`` with
    the local-head cfg and the TP reduction hooks — each rank computes
    its head columns of the chunk's K/V into its shard of the prefill
    scratch, attention runs per-head over the local scratch, and the
    out-proj/FFN seams ``psum``.  ``mesh``/``axis`` stay None inside:
    the per-rank scratch is head-local, never sequence-sharded.
    ``quantized`` writes the chunk's K/V into int8+scale scratch
    (the rank's local heads quantize independently — same per-(head,
    position) absmax math as world-1, so the pages are bit-identical)."""
    return _chunk_forward(
        params, chunk, caches, prefix_len, cfg=_local_cfg(cfg, world),
        quantized=quantized,
        ffn=ffn or functools.partial(_tp_ffn, axis=axis),
        out_proj=out_proj or functools.partial(_tp_out_proj, axis=axis),
        extent=extent, n_valid=n_valid, impl=impl, interpret=interpret)


def rep_chunk_forward_shard(params, chunk, caches, prefix_len, n_valid,
                            *, cfg, extent, impl, interpret,
                            quantized=False):
    """Replicated chunked prefill (the DRAFT model under any mesh):
    every rank runs the identical world-1 chunk forward.  The target
    model no longer rides this under ``kv_shard='seq'`` — ISSUE 19
    debt (b) moved it to :func:`sp_chunk_forward_shard`."""
    return _chunk_forward(params, chunk, caches, prefix_len, cfg=cfg,
                          quantized=quantized, extent=extent,
                          n_valid=n_valid, impl=impl, interpret=interpret)


def sp_chunk_forward_shard(params, chunk, caches, prefix_len, n_valid,
                           *, cfg, extent, axis, world, impl, interpret,
                           quantized=False, fwd_cfg=None, ffn=None,
                           out_proj=None):
    """Sequence-sharded chunked prefill (ISSUE 19 debt (b)): the chunk's
    QKV/FFN math and the scratch K/V WRITE stay replicated — the
    partitioned allocator's page→partition map does not align with an
    even row-split of an extent-``m`` scratch, so the scratch must hold
    the whole extent on every rank for the downstream page scatter —
    but the O(c·extent) attention read, the term that dominates long
    prompts, now shards: each rank slices its ``extent/world`` span out
    of the cache view (geometry guarantees ``page_size % world``, and
    every ladder rung is a page multiple, so the split is exact) and
    attends via ``sp_gqa_decode_shard``; the partials LSE-combine over
    ``axis``.  The causal rule rides the combine's unclipped local ends
    — chunk row ``i`` sees positions ``<= prefix + i`` exactly as the
    dense mask does, and padded K rows (``n_valid``) stay hidden the
    same way they do in world-1.  TP seams (``fwd_cfg``/``ffn``/
    ``out_proj``) thread through for ``heads+seq``, where the scratch's
    head axis is already the rank's local shard."""
    me = jax.lax.axis_index(axis)

    def attend(q, k_view, v_view, plen, *, k_scale=None, v_scale=None):
        s_loc = k_view.shape[2] // world

        def loc(x):
            return (None if x is None else
                    jax.lax.dynamic_slice_in_dim(x, me * s_loc, s_loc,
                                                 axis=2))

        B, c = q.shape[0], q.shape[1]
        lens = jnp.full((B,), c, jnp.int32) + plen
        # generate._attend_prefix's convention: attention dispatches
        # "auto" unless "xla" was asked for by name, which pins the XLA
        # program (an engine built for reference must hold no kernel)
        return sp_gqa_decode_shard(
            q, loc(k_view), loc(v_view), lens, axis=axis,
            impl="xla" if impl == "xla" else "auto",
            interpret=interpret, k_scale=loc(k_scale),
            v_scale=loc(v_scale), soft_cap=cfg.attn_soft_cap,
            window=cfg.attn_window).astype(jnp.float32)

    return _chunk_forward(params, chunk, caches, prefix_len,
                          cfg=fwd_cfg or cfg, quantized=quantized,
                          ffn=ffn, out_proj=out_proj, extent=extent,
                          n_valid=n_valid, impl=impl, interpret=interpret,
                          attend=attend)


# -- page scatter / gather / COW over sharded pools -------------------------


def sp_fill_pool_pages_shard(pools, scratch, ids, *, page, axis, world,
                             num_blocks):
    """Sequence-sharded page scatter: ``ids`` are GLOBAL block ids per
    scratch page; each rank rebases its own ids to local pool rows and
    scatters only those pages — foreign and padded entries land in the
    rank's local null (row 0), exactly where world-1 scatters its
    padding."""
    from triton_dist_tpu.serve.engine import _fill_pool_pages

    _, loc = _rebase_local(ids, axis=axis, world=world,
                           num_blocks=num_blocks)
    return _fill_pool_pages(pools, scratch, loc, page=page)


def sp_gather_pool_pages_shard(pools, ids, *, page, axis, world,
                               num_blocks):
    """Sequence-sharded page gather (the warm-prefix / drain read-back):
    each rank gathers its own pages into the replicated scratch layout,
    zeroes the rows it does not own, and a ``psum`` assembles the full
    scratch — every row has exactly one owner, so the sum is exact
    (adding zeros never perturbs floats)."""
    from triton_dist_tpu.serve.engine import _gather_pool_pages

    mine, loc = _rebase_local(ids, axis=axis, world=world,
                              num_blocks=num_blocks)
    sc = _gather_pool_pages(pools, loc, page=page)
    rows = jnp.repeat(mine, page)

    def _own(x):
        # scratch row axis is 2 for both layouts: [1,H,S,D] pages and
        # [1,H,S] per-page scales — broadcast the ownership mask over
        # whatever trails it (int8 pages psum exactly: one owner per
        # row, everyone else contributes true zeros)
        r = rows.reshape((1, 1, -1) + (1,) * (x.ndim - 3))
        return jnp.where(r, x, jnp.zeros((), x.dtype))

    sc = jax.tree_util.tree_map(_own, sc)
    return jax.lax.psum(sc, axis)


def sp_copy_pool_block_shard(pools, src, dst, *, axis, world, num_blocks):
    """Sequence-sharded COW page copy: the partitioned allocator keeps
    both halves of a split in one partition, so exactly the owning rank
    copies (everyone else degenerates to a null→null self-copy)."""
    from triton_dist_tpu.serve.engine import _copy_pool_block

    _, s = _rebase_local(src, axis=axis, world=world,
                         num_blocks=num_blocks)
    # the allocator keeps both halves of a split in one partition, so
    # dst rebases under the same ownership (foreign ranks get 0 -> 0)
    _, d = _rebase_local(dst, axis=axis, world=world,
                         num_blocks=num_blocks)
    return _copy_pool_block(pools, s, d)


# ---------------------------------------------------------------------------
# ShardedProgram — jit(shard_map) + canonical argument placement
# ---------------------------------------------------------------------------


def _place(x, sharding):
    """Commit ``x`` onto ``sharding`` unless it already carries it —
    the one-signature-per-program guarantee (module docstring).
    Tracers pass through: under a re-trace (the jaxpr auditor replaying
    a captured signature) placement is a runtime concern and a tracer
    carries no sharding to inspect."""
    if isinstance(x, jax.core.Tracer):
        return x
    if isinstance(x, jax.Array) and x.sharding == sharding:
        return x
    return jax.device_put(x, sharding)


def _shardings_of(mesh, spec_tree):
    """PartitionSpec tree → NamedSharding tree (specs are pytrees of
    tuples, so they must be treated as leaves)."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


class ShardedProgram:
    """One engine device program on a mesh: ``jax.jit(jax.shard_map(
    body))`` with per-argument canonical placement and a bounded
    static-kwargs ladder.

    - Positional args are pytrees matched leaf-wise against
      ``in_specs``; every leaf is ``device_put`` to its declared
      ``NamedSharding`` unless already there — host-built and
      device-carried calls hit the SAME executable (the PR-7 cache-fork
      fix; module docstring).
    - Keyword args are STATIC trace parameters (the horizon's ``H``,
      the spec round's ``K``, ...): each distinct combination memoizes
      one jitted closure, exactly like ``static_argnames`` — and
      ``_cache_size()`` sums the inner caches so ``CountingJit``'s
      hit/miss accounting (and warmup's fixed-point test) keep working
      unchanged.
    - ``donate_argnums`` applies to the placed arrays; the engine
      already reassigns donated carries from the outputs.
    - ``timer`` (optional ``(label, ms)`` callable, the
      ``jit_cache.CountingJit`` protocol): every call's wall time —
      placement included, it is part of what the program costs — is
      reported under ``name`` suffixed with the ``timed_statics``
      kwargs' values (``decode_horizon[H=8]``).  The engine wires its
      CountingJit wrapper's timer instead (one seam for mesh and
      world-1 programs); this hook serves direct ShardedProgram users.
    """

    def __init__(self, body, mesh, in_specs, out_specs, *,
                 donate_argnums=(), name=None, timer=None,
                 timed_statics=()):
        self.body = body
        self.mesh = mesh
        self.in_specs = tuple(in_specs)
        self.out_specs = out_specs
        self.donate_argnums = tuple(donate_argnums)
        self.name = name or getattr(body, "__name__", "sharded_program")
        self.timer = timer
        self.timed_statics = tuple(timed_statics)
        self._placements = tuple(_shardings_of(mesh, s)
                                 for s in self.in_specs)
        self._jits: dict = {}
        #: statics-key -> abstracted args of the first call per rung
        #: (the jaxpr auditor's re-trace seed, like CountingJit's)
        self.captured: dict = {}

    def _prog(self, statics: tuple):
        prog = self._jits.get(statics)
        if prog is None:
            fn = (functools.partial(self.body, **dict(statics))
                  if statics else self.body)
            prog = jax.jit(
                jax.shard_map(fn, mesh=self.mesh, in_specs=self.in_specs,
                              out_specs=self.out_specs, check_vma=False),
                donate_argnums=self.donate_argnums)
            self._jits[statics] = prog
        return prog

    def place(self, i: int, value):
        """Canonical placement of argument ``i`` (exposed so the engine
        can pre-place long-lived carries like the pools at init)."""
        return jax.tree_util.tree_map(_place, value, self._placements[i])

    def __call__(self, *args, **statics):
        timer = self.timer
        before = self._cache_size() if timer is not None else 0
        t0 = time.perf_counter() if timer is not None else 0.0
        placed = tuple(
            jax.tree_util.tree_map(_place, a, p)
            for a, p in zip(args, self._placements))
        key = tuple(sorted(statics.items()))
        if key not in self.captured and \
                len(self.captured) < jit_cache.MAX_CAPTURED_SIGNATURES:
            self.captured[key] = jit_cache.abstract_signature(
                placed, dict(statics))
        out = self._prog(key)(*placed)
        # compile calls (cache grew) stay out of the distributions —
        # the same rule as CountingJit: stalls are compile accounting,
        # not program wall time
        if timer is not None and self._cache_size() == before:
            label = self.name
            for k in self.timed_statics:
                v = statics.get(k)
                if v is not None:
                    label = f"{label}[{k}={v}]"
            timer(label, (time.perf_counter() - t0) * 1e3)
        return out

    def _cache_size(self) -> int:
        # CountingJit keys its miss accounting on this (a fresh static
        # rung AND a fresh signature within a rung both count — the
        # same events a plain jit's cache growth reports).
        return sum(p._cache_size() for p in self._jits.values())


class MeshChunkJit:
    """The mesh chunk-prefill program behind ``Generator._chunk_jit``'s
    call convention (``(params, buf, scratch, prefix, *, quantized,
    extent, n_valid)`` with ``quantized``/``extent`` static and
    ``n_valid`` traced): one :class:`ShardedProgram` per extent rung,
    ``n_valid`` folded into the positional args.  ``quantized`` is a
    CONSTRUCTION property here, not a per-call rung: the pool dtype is
    engine geometry, the chunk bodies are built for exactly one dtype,
    and a call asking for the other is a wiring bug worth an assert."""

    def __init__(self, maker, *, quantized=False):
        self._maker = maker     # extent -> ShardedProgram
        self._progs: dict = {}
        self._quantized = bool(quantized)

    def __call__(self, params, buf, scratch, prefix, *, quantized,
                 extent, n_valid):
        assert quantized == self._quantized, (
            "mesh chunk prefill was built for "
            f"quantized={self._quantized}; called with {quantized}")
        prog = self._progs.get(extent)
        if prog is None:
            prog = self._maker(extent)
            self._progs[extent] = prog
        return prog(params, buf, scratch, prefix, n_valid)

    def _cache_size(self) -> int:
        return sum(p._cache_size() for p in self._progs.values())


# ---------------------------------------------------------------------------
# Program construction (the engine's mesh-mode __init__ calls this)
# ---------------------------------------------------------------------------


def collective_seams(cfg, *, kv_shard: str, draft_cfg=None) -> dict:
    """Declared collective seams per engine program — the contract the
    jaxpr auditor (``analysis/jaxpr_audit.py``) enforces: any
    collective primitive a program traces that is NOT declared here is
    a violation, and declared counts must match exactly.

    ``kv_shard="heads"`` (Megatron TP): the ONLY collectives in any
    forward are the two row-parallel ``psum``s per layer (attn
    out-proj, ``_tp_out_proj``; FFN down, ``_tp_ffn``) — 2 x n_layers
    per forward, nothing in per-rank attention, sampling, or the page
    programs.  ``kv_shard="seq"`` (SP flash-decode): one inter-rank
    LSE-combine gather per layer in EVERY forward — decode, verify,
    horizon AND chunked prefill, whose attention read shards since
    ISSUE 19 debt (b) (``sp_chunk_forward_shard``) — and one ``psum``
    in the page gather (``sp_gather_pool_pages_shard`` zeroes unowned
    rows and psum-assembles the full gather).  Spec rounds chain draft
    (replicated — collective-free) and target forwards: K+1 target
    forwards for the K-step draft scan + verify + closing decode... the
    spec round's exact chain is 2 target forwards traced (verify +
    closing decode, the draft scan is replicated), so 2x the
    per-forward seam count.  ``kv_shard="heads+seq"`` composes: every
    target forward carries BOTH the 2 TP psums and the 1 SP gather per
    layer (the axes never mix — psum on tp, all_gather on sp; the
    schedule-level story is the ``hier_sp_combine`` two-phase proof in
    analysis/comm_schedule.py), and the page programs keep the seq
    layout's counts (the head axis moves no bytes between ranks).
    """
    n = cfg.n_layers
    if kv_shard == "heads":
        fwd = {"psum": 2 * n}
        seams = {
            "paged_decode": dict(fwd),
            "paged_verify": dict(fwd),
            "decode_horizon": dict(fwd),
            "prefill_chunk": dict(fwd),
            # page scatter/gather/COW move KV bytes inside each rank's
            # own head shard: collective-free.
            "fill_pages": {}, "load_pages": {}, "cow_copy": {},
            # spec round: draft scan replicated (collective-free),
            # verify + closing decode are 2 target forwards.
            "spec_round": {"psum": 2 * (2 * n)},
            "draft_tail_step": {},
            "draft_prefill": {}, "draft_join": {}, "draft_step": {},
            "draft_fill_pages": {}, "draft_load_pages": {},
        }
        return seams
    if kv_shard in ("seq", "heads+seq"):
        fwd = {"all_gather": n}
        if kv_shard == "heads+seq":
            fwd["psum"] = 2 * n
        spec = {k: 2 * v for k, v in fwd.items()}
        return {
            "paged_decode": dict(fwd),
            "paged_verify": dict(fwd),
            "decode_horizon": dict(fwd),
            # chunked prefill shards its attention read (debt (b)):
            # same per-layer combine gather as the decode forwards.
            "prefill_chunk": dict(fwd),
            "fill_pages": {},
            "load_pages": {"psum": 1},
            "cow_copy": {},
            "spec_round": spec,
            "draft_tail_step": {},
            "draft_prefill": {}, "draft_join": {}, "draft_step": {},
            "draft_fill_pages": {}, "draft_load_pages": {},
        }
    raise ValueError(f"unknown kv_shard {kv_shard!r}")


def replicated_like(tree):
    """All-``P()`` spec tree matching ``tree``'s structure."""
    return jax.tree_util.tree_map(lambda _: P(), tree)


def build_programs(*, mesh, tp_axis, kv_shard, cfg, params, page_size,
                   num_blocks, n_pages_max, impl, interpret,
                   horizon: int, draft=None, draft_params=None,
                   spec_fused: bool = False,
                   prefix_cache: bool = False,
                   kv_quant: bool = False,
                   w8a8: bool = False,
                   sp_axis=None) -> dict:
    """All mesh device programs for one engine, keyed by the engine's
    program names (``paged_decode``, ``paged_verify``, ``fill_pages``,
    ``load_pages``, ``cow_copy``, ``decode_horizon``, ``prefill_chunk``
    — plus the draft family on spec engines).  Shapes/donation mirror
    the world-1 programs exactly, so warmup, metrics, and the step loop
    need no mesh-specific branches past construction.

    ``kv_quant`` swaps every pool/scratch spec for the dict-structured
    ``{"q": spec, "s": spec}`` twin — the SAME PartitionSpec legally
    covers both planes (heads shards axis 1 = Hkv of the 4D pages and
    the 3D scales alike; seq shards the shared block axis 0), and the
    forward/page bodies are already dict-aware, so the program set and
    its collective seams are unchanged.  ``w8a8`` (heads only — the
    engine rejects it elsewhere) swaps ``param_specs`` for
    ``w8a8_serve_param_specs`` and the TP reduction seams for the
    quantized serving hooks: same one-psum-per-seam shape, int8
    contraction inside.

    ``kv_shard="heads+seq"`` composes the two layouts on a 2D mesh:
    params/scratch shard their head axes on ``tp_axis`` exactly as the
    heads layout, pools shard ``P(sp_axis, tp_axis)`` — block axis over
    sp, head axis over tp — and every body is the SP body with the TP
    seams (local-head cfg + psum hooks) threaded through.  The
    BlockManager partition count is the SP world (``out["sp_world"]``),
    not the total world."""
    axis = tp_axis
    heads = kv_shard == "heads"
    two_d = kv_shard == "heads+seq"
    if two_d:
        tp_world = int(mesh.shape[tp_axis])
        sp_world = int(mesh.shape[sp_axis])
        world = tp_world * sp_world
        sp = sp_axis
    else:
        world = int(mesh.shape[axis])
        tp_world = world if heads else 1
        sp_world = 1 if heads else world
        sp = axis
    if heads:
        pool_spec = P(None, axis)
    elif two_d:
        pool_spec = P(sp_axis, tp_axis)
    else:
        pool_spec = P(axis)
    kv_spec = ({"q": pool_spec, "s": pool_spec} if kv_quant
               else pool_spec)
    pools_specs = [(kv_spec, kv_spec)] * cfg.n_layers
    sp_hooks = {}
    if heads:
        if w8a8:
            from triton_dist_tpu.models.llama_w8a8 import (
                w8a8_serve_ffn,
                w8a8_serve_out_proj,
                w8a8_serve_param_specs,
            )

            p_specs = w8a8_serve_param_specs(cfg, axis)
            hooks = {
                "ffn": functools.partial(
                    w8a8_serve_ffn, axis=axis, impl=impl,
                    interpret=interpret),
                "out_proj": functools.partial(
                    w8a8_serve_out_proj, axis=axis, impl=impl,
                    interpret=interpret),
            }
        else:
            p_specs = param_specs(cfg, axis)
            hooks = {}
    elif two_d:
        p_specs = param_specs(cfg, tp_axis)
        sp_hooks = {
            "fwd_cfg": _local_cfg(cfg, tp_world),
            "ffn": functools.partial(_tp_ffn, axis=tp_axis),
            "out_proj": functools.partial(_tp_out_proj, axis=tp_axis),
        }
    else:
        p_specs = replicated_like(params)
    scratch_spec = P(None, tp_axis) if (heads or two_d) else P()
    sc_spec = ({"q": scratch_spec, "s": scratch_spec} if kv_quant
               else scratch_spec)

    out = {"pool_spec": pool_spec, "params_specs": p_specs,
           "world": world, "tp_world": tp_world, "sp_world": sp_world}

    if heads:
        decode_body = functools.partial(
            tp_paged_decode_shard, cfg=cfg, page=page_size, axis=axis,
            world=world, impl=impl, interpret=interpret, **hooks)
        verify_body = functools.partial(
            tp_paged_verify_shard, cfg=cfg, page=page_size, axis=axis,
            world=world, impl=impl, interpret=interpret, **hooks)
        horizon_body = functools.partial(
            tp_paged_decode_horizon_shard, cfg=cfg, page=page_size,
            axis=axis, world=world, impl=impl, interpret=interpret,
            **hooks)
        fill_body = functools.partial(
            __import_engine()._fill_pool_pages, page=page_size)
        load_body = functools.partial(
            __import_engine()._gather_pool_pages, page=page_size)
        cow_body = __import_engine()._copy_pool_block
        chunk_body = functools.partial(
            tp_chunk_forward_shard, cfg=cfg, axis=axis, world=world,
            impl=impl, interpret=interpret, quantized=kv_quant, **hooks)
    else:
        decode_body = functools.partial(
            sp_paged_decode_shard, cfg=cfg, page=page_size, axis=sp,
            world=sp_world, num_blocks=num_blocks,
            n_pages_max=n_pages_max, impl=impl, interpret=interpret,
            **sp_hooks)
        verify_body = functools.partial(
            sp_paged_verify_shard, cfg=cfg, page=page_size, axis=sp,
            world=sp_world, num_blocks=num_blocks,
            n_pages_max=n_pages_max, impl=impl, interpret=interpret,
            **sp_hooks)
        horizon_body = functools.partial(
            sp_paged_decode_horizon_shard, cfg=cfg, page=page_size,
            axis=sp, world=sp_world, num_blocks=num_blocks,
            n_pages_max=n_pages_max, impl=impl, interpret=interpret,
            **sp_hooks)
        fill_body = functools.partial(
            sp_fill_pool_pages_shard, page=page_size, axis=sp,
            world=sp_world, num_blocks=num_blocks)
        load_body = functools.partial(
            sp_gather_pool_pages_shard, page=page_size, axis=sp,
            world=sp_world, num_blocks=num_blocks)
        cow_body = functools.partial(
            sp_copy_pool_block_shard, axis=sp, world=sp_world,
            num_blocks=num_blocks)
        chunk_body = functools.partial(
            sp_chunk_forward_shard, cfg=cfg, axis=sp, world=sp_world,
            impl=impl, interpret=interpret, quantized=kv_quant,
            **sp_hooks)

    # (params, pools, tables, kv_lens, token/chunk, active)
    fwd_in = (p_specs, pools_specs, P(), P(), P(), P())
    out["paged_decode"] = ShardedProgram(
        decode_body, mesh, fwd_in, (pools_specs, P()),
        donate_argnums=(1,))
    out["paged_verify"] = ShardedProgram(
        verify_body, mesh, fwd_in, (pools_specs, P()),
        donate_argnums=(1,))
    if horizon > 1:
        out["decode_horizon"] = ShardedProgram(
            horizon_body, mesh,
            (p_specs, pools_specs) + (P(),) * 13,
            (pools_specs,) + (P(),) * 6, donate_argnums=(1,))
    out["fill_pages"] = ShardedProgram(
        fill_body, mesh,
        (pools_specs, [(sc_spec, sc_spec)] * cfg.n_layers, P()),
        pools_specs, donate_argnums=(0,))
    out["load_pages"] = ShardedProgram(
        load_body, mesh, (pools_specs, P()),
        [(sc_spec, sc_spec)] * cfg.n_layers)
    out["cow_copy"] = ShardedProgram(
        cow_body, mesh, (pools_specs, P(), P()), pools_specs,
        donate_argnums=(0,))

    def make_chunk(extent: int) -> ShardedProgram:
        return ShardedProgram(
            functools.partial(chunk_body, extent=extent), mesh,
            (p_specs, P(),
             [(sc_spec, sc_spec)] * cfg.n_layers, P(), P()),
            ([(sc_spec, sc_spec)] * cfg.n_layers, P()),
            donate_argnums=(2,))

    out["prefill_chunk"] = MeshChunkJit(make_chunk, quantized=kv_quant)

    if draft is not None and spec_fused:
        dcfg = draft.cfg
        d_specs = replicated_like(draft_params)
        dpools_specs = [(P(), P())] * dcfg.n_layers
        if heads:
            spec_body = functools.partial(
                tp_spec_round_shard, cfg=cfg, dcfg=dcfg, page=page_size,
                axis=axis, world=world, impl=impl, interpret=interpret,
                dimpl=draft.attn.ctx.impl,
                dinterpret=draft.attn.ctx.interpret)
        else:
            spec_body = functools.partial(
                sp_spec_round_shard, cfg=cfg, dcfg=dcfg, page=page_size,
                axis=sp, world=sp_world, num_blocks=num_blocks,
                n_pages_max=n_pages_max, impl=impl, interpret=interpret,
                dimpl=draft.attn.ctx.impl,
                dinterpret=draft.attn.ctx.interpret, **sp_hooks)
        out["spec_round"] = ShardedProgram(
            spec_body, mesh,
            (p_specs, d_specs, pools_specs, dpools_specs)
            + (P(),) * 15,
            (pools_specs, dpools_specs) + (P(),) * 9,
            donate_argnums=(2, 3))
        tail_body = functools.partial(
            __import_engine()._draft_decode_forward, cfg=dcfg,
            impl=draft.attn.ctx.impl, interpret=draft.attn.ctx.interpret)
        out["draft_tail_step"] = ShardedProgram(
            tail_body, mesh, (d_specs, dpools_specs, P(), P(), P()),
            (dpools_specs, P(), P()), donate_argnums=(1,))
        out["draft_join"] = ShardedProgram(
            __import_engine()._splice_draft_rows, mesh,
            (dpools_specs, P(), P(),
             [(P(), P())] * dcfg.n_layers, P(), P(), P()),
            (dpools_specs, P(), P()), donate_argnums=(0, 1, 2))
        dchunk_body = functools.partial(
            rep_chunk_forward_shard, cfg=dcfg,
            impl=draft.attn.ctx.impl, interpret=draft.attn.ctx.interpret)

        def make_draft_chunk(extent: int) -> ShardedProgram:
            return ShardedProgram(
                functools.partial(dchunk_body, extent=extent), mesh,
                (d_specs, P(), [(P(), P())] * dcfg.n_layers, P(), P()),
                ([(P(), P())] * dcfg.n_layers, P()), donate_argnums=(2,))

        out["draft_prefill"] = MeshChunkJit(make_draft_chunk)
        if prefix_cache:
            out["draft_fill_pages"] = ShardedProgram(
                functools.partial(__import_engine()._fill_pool_pages,
                                  page=page_size), mesh,
                (dpools_specs, [(P(), P())] * dcfg.n_layers, P()),
                dpools_specs, donate_argnums=(0,))
            out["draft_load_pages"] = ShardedProgram(
                functools.partial(__import_engine()._gather_pool_pages,
                                  page=page_size), mesh,
                (dpools_specs, P()), [(P(), P())] * dcfg.n_layers)
    return out


def __import_engine():
    """Deferred engine import: engine.py imports this module inside its
    constructor, so a module-level back-import would be circular."""
    from triton_dist_tpu.serve import engine

    return engine
