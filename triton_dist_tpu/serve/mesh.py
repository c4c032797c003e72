"""Mesh placement for :class:`serve.engine.ServeEngine` — TP weights +
sharded paged KV under ``shard_map`` (docs/serving.md "Sharded serving").

The engine's device programs (``serve/programs.py``: paged decode, the
fused decode horizon, the fused speculative round with its multi-token
verify, the page scatter/gather/COW trio; ``generate._chunk_forward``)
are all parameterized over the model family's seams (``project`` /
``out_proj`` / ``ffn`` / the attends of ``generate._layer_stack``) and
the block-table addressing (``slots``) — this module instantiates them
PER-SHARD (:func:`build_programs`: the dense family over a rank's
local-head config, psum hooks, a sequence-sharded rank's own ``slots``
and attends) and wraps each in ``jax.jit(jax.shard_map(...))`` so the
same engine step loop, scheduler, and block tables drive a multi-chip
forward.  Three KV layouts:

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
  over the TP family (local-head ``project``, psum ``ffn`` /
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
    _attend_prefix,
    _chunk_forward,
    _pool_views,
    dense_block,
    paged_attend,
)
from triton_dist_tpu.models.llama import param_specs
from triton_dist_tpu.models.sampling import takes_candidates
from triton_dist_tpu.runtime import jit_cache
from triton_dist_tpu.serve.programs import (
    _copy_pool_block,
    _draft_decode_forward,
    _fill_pool_pages,
    _gather_pool_pages,
    _page_slots,
    _paged_decode_forward,
    _paged_decode_horizon,
    _paged_verify_forward,
    _spec_round_fused,
    _splice_draft_rows,
    _zero_scratch,
)


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
# The two TP reduction seams (``generate._layer_stack``'s out_proj / ffn)
# ---------------------------------------------------------------------------


def _tp_out_proj(o2, layer, *, axis):
    """Row-parallel attention output projection: each rank contracts its
    local head columns against its ``wo`` row shard, ``psum`` completes
    the sum — ``generate._dense_out_proj`` with the contraction split
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
# The sequence-sharded layout's addressing and attends (inside shard_map)
# ---------------------------------------------------------------------------


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


def _sp_slots(tables, pos, active, *, page, axis, world, num_blocks):
    """``programs._page_slots`` over BLOCK-sharded pools — rank ``r``
    holds global blocks ``[r*nb_loc, (r+1)*nb_loc)``, which the
    partitioned :class:`serve.block_manager.BlockManager` dedicates to
    the logical pages of rank ``r``'s sequence span: the engine's own
    addressing with the rebase applied elementwise, so only the owning
    rank writes the real row and everyone else's write redirects to ITS
    null (local row 0) exactly like an inactive row's.  A verify chunk
    spanning a page boundary (and therefore possibly TWO ranks'
    partitions) writes each row exactly once fleet-wide."""
    pool_row, in_page = _page_slots(tables, pos, active, page=page)
    mine, pool_row = _rebase_local(pool_row, axis=axis, world=world,
                                   num_blocks=num_blocks)
    return pool_row, jnp.where(mine, in_page, 0)


def _sp_paged_attend(q, pool, tables, lens, *, cfg, axis, world, num_blocks,
                     n_pages_max, impl, interpret):
    """``generate.paged_attend`` over block-sharded pools: the block table
    is global; each rank slices its span and rebases the ids to local
    pool rows (foreign/padded entries — another rank's blocks, the
    global null — map to local row 0), and attention goes through
    ``sp_gqa_decode_paged_shard`` (local lengths + LSE combine), so the
    result is replicated.  ``lens`` stays GLOBAL: per-token causality of
    a multi-token q rides the combine's unclipped local ends, exactly
    the contiguous SP verify contract.  Quantized pools ride through
    unchanged (the per-page scales feed the combine's dequant); under
    ``heads+seq`` the pool's head axis holds the rank's local KV heads
    and the block addressing is untouched."""
    n_loc = n_pages_max // world
    lt = jax.lax.dynamic_slice_in_dim(
        tables, jax.lax.axis_index(axis) * n_loc, n_loc, axis=1)
    _, lt = _rebase_local(lt, axis=axis, world=world, num_blocks=num_blocks)
    kq, vq, ks, vs = _pool_views(pool)
    return sp_gqa_decode_paged_shard(
        q, kq, vq, lt, lens, axis=axis, impl=impl, interpret=interpret,
        soft_cap=cfg.attn_soft_cap, window=cfg.attn_window, k_scale=ks,
        v_scale=vs)


def _sp_attend_prefix(q, k_view, v_view, plen, *, k_scale=None, v_scale=None,
                      cfg, axis, world, impl, interpret):
    """Sequence-sharded chunk attention (ISSUE 19 debt (b)): the chunk's
    QKV/FFN math and the scratch K/V WRITE stay replicated — the
    partitioned allocator's page→partition map does not align with an
    even row-split of an extent-``m`` scratch, so the scratch must hold
    the whole extent on every rank for the downstream page scatter —
    but the O(c·extent) attention read, the term that dominates long
    prompts, shards: each rank slices its ``extent/world`` span out of
    the cache view (geometry guarantees ``page_size % world``, and every
    ladder rung is a page multiple, so the split is exact) and attends
    via ``sp_gqa_decode_shard``; the partials LSE-combine over ``axis``.
    The causal rule rides the combine's unclipped local ends — chunk row
    ``i`` sees positions ``<= prefix + i`` exactly as the dense mask
    does, and padded K rows (``n_valid``) stay hidden the same way they
    do in world-1."""
    me = jax.lax.axis_index(axis)
    s_loc = k_view.shape[2] // world

    def loc(x):
        return (None if x is None else
                jax.lax.dynamic_slice_in_dim(x, me * s_loc, s_loc, axis=2))

    B, c = q.shape[0], q.shape[1]
    lens = jnp.full((B,), c, jnp.int32) + plen
    # generate._attend_prefix's convention: attention dispatches "auto"
    # unless "xla" was asked for by name, which pins the XLA program (an
    # engine built for reference must hold no kernel)
    return sp_gqa_decode_shard(
        q, loc(k_view), loc(v_view), lens, axis=axis,
        impl="xla" if impl == "xla" else "auto", interpret=interpret,
        k_scale=loc(k_scale), v_scale=loc(v_scale),
        soft_cap=cfg.attn_soft_cap,
        window=cfg.attn_window).astype(jnp.float32)


def _chunk_shard(params, chunk, caches, prefix_len, n_valid, **kw):
    """``generate._chunk_forward`` under shard_map's positional calling
    convention (``n_valid`` is an array argument there — always given, so
    a mesh chunk returns its last valid row's logits, ``[1, 1, V]``)."""
    return _chunk_forward(params, chunk, caches, prefix_len,
                          n_valid=n_valid, **kw)


# -- page scatter / gather / COW over sharded pools -------------------------


def sp_fill_pool_pages_shard(pools, scratch, ids, *, page, axis, world,
                             num_blocks):
    """Sequence-sharded page scatter: ``ids`` are GLOBAL block ids per
    scratch page; each rank rebases its own ids to local pool rows and
    scatters only those pages — foreign and padded entries land in the
    rank's local null (row 0), exactly where world-1 scatters its
    padding."""
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
            body = jax.shard_map(fn, mesh=self.mesh, in_specs=self.in_specs,
                                 out_specs=self.out_specs, check_vma=False)
            # the HLO module, and so every execution in a device trace,
            # reads jit_<name> (a partial body would be jit__unknown)
            body.__name__ = body.__qualname__ = self.name
            prog = jax.jit(body, donate_argnums=self.donate_argnums)
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
    ISSUE 19 debt (b) (``_sp_attend_prefix``) — and one ``psum``
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
            "decode_horizon": dict(fwd),
            "prefill_chunk": dict(fwd),
            # page scatter/gather/COW move KV bytes inside each rank's
            # own head shard: collective-free.
            "fill_pages": {}, "load_pages": {}, "cow_copy": {},
            "zero_scratch": {}, "draft_zero_scratch": {},
            # spec round: draft scan replicated (collective-free),
            # verify + closing decode are 2 target forwards.
            "spec_round": {"psum": 2 * (2 * n)},
            "draft_tail_step": {},
            "draft_prefill": {}, "draft_join": {},
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
            "decode_horizon": dict(fwd),
            # chunked prefill shards its attention read (debt (b)):
            # same per-layer combine gather as the decode forwards.
            "prefill_chunk": dict(fwd),
            "fill_pages": {},
            "load_pages": {"psum": 1},
            "cow_copy": {},
            "zero_scratch": {}, "draft_zero_scratch": {},
            "spec_round": spec,
            "draft_tail_step": {},
            "draft_prefill": {}, "draft_join": {},
            "draft_fill_pages": {}, "draft_load_pages": {},
        }
    raise ValueError(f"unknown kv_shard {kv_shard!r}")


def replicated_like(tree):
    """All-``P()`` spec tree matching ``tree``'s structure."""
    return jax.tree_util.tree_map(lambda _: P(), tree)


def build_programs(*, mesh, tp_axis, kv_shard, cfg, params, page_size,
                   num_blocks, n_pages_max, impl, interpret,
                   horizon: int, draft=None, draft_params=None,
                   prefix_cache: bool = False,
                   kv_quant: bool = False,
                   w8a8: bool = False,
                   sp_axis=None) -> dict:
    """All mesh device programs for one engine, keyed by the engine's
    program names (``paged_decode``, ``fill_pages``, ``load_pages``,
    ``cow_copy``, ``zero_scratch``, ``decode_horizon``, ``prefill_chunk``
    — plus ``spec_round`` and the draft family with a ``draft``).  Shapes /
    donation mirror the world-1 programs exactly, so warmup, metrics,
    and the step loop need no mesh-specific branches past construction.

    Every forward is the engine's OWN body (``serve/programs.py``,
    ``generate._chunk_forward``) over this layout's instantiation of the
    seams, so the block-table addressing can never diverge between
    world-1 and mesh:

    - ``heads`` (Megatron TP): the dense family over the local-head
      config, ``out_proj`` / ``ffn`` row-parallel + ``psum``; QKV project
      onto the rank's head columns, the K/V scatter lands in the rank's
      pool shard, attention runs per rank over its own heads (no combine
      — heads are independent).  ``tables`` / ``kv_lens`` are replicated
      (the host-managed index is global) and so are the returned logits:
      sampling and commit stay bit-identical to the world-1 path.
    - ``seq`` (SP flash-decode): weights replicated, ``slots`` /
      ``paged_attend`` / the chunk attend the block-sharded ones above
      (:func:`_sp_slots`, :func:`_sp_paged_attend`,
      :func:`_sp_attend_prefix`).
    - ``heads+seq`` composes them on a 2D mesh: params/scratch shard
      their head axes on ``tp_axis`` exactly as the heads layout, pools
      shard ``P(sp_axis, tp_axis)`` — block axis over sp, head axis over
      tp.  The BlockManager partition count is the SP world
      (``out["sp_world"]``), not the total world.

    The draft of a speculative engine steps REPLICATED per rank (its
    slot-indexed batch caches are host-managed whole-batch state —
    sharding them would put the accept chain's inputs behind a gather)
    and is never head-sharded; the seeded accept/sampling math runs on
    replicated logits — bit-identical emissions per rank.

    ``kv_quant`` swaps every pool/scratch spec for the dict-structured
    ``{"q": spec, "s": spec}`` twin — the SAME PartitionSpec legally
    covers both planes (heads shards axis 1 = Hkv of the 4D pages and
    the 3D scales alike; seq shards the shared block axis 0), and the
    forward/page bodies are already dict-aware, so the program set and
    its collective seams are unchanged.  ``w8a8`` (heads only — the
    engine rejects it elsewhere) swaps ``param_specs`` for
    ``w8a8_serve_param_specs`` and the TP reduction seams for the
    quantized serving hooks: same one-psum-per-seam shape, int8
    contraction inside."""
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
    if heads or two_d:
        block = dense_block(
            _local_cfg(cfg, tp_world),
            ffn=functools.partial(_tp_ffn, axis=tp_axis),
            out_proj=functools.partial(_tp_out_proj, axis=tp_axis))
        p_specs = param_specs(cfg, tp_axis)
    else:
        block = dense_block(cfg)
        p_specs = replicated_like(params)
    if w8a8:
        from triton_dist_tpu.models.llama_w8a8 import (
            w8a8_serve_ffn,
            w8a8_serve_out_proj,
            w8a8_serve_param_specs,
        )

        p_specs = w8a8_serve_param_specs(cfg, axis)
        block.update(
            ffn=functools.partial(w8a8_serve_ffn, axis=axis, impl=impl,
                                  interpret=interpret),
            out_proj=functools.partial(w8a8_serve_out_proj, axis=axis,
                                       impl=impl, interpret=interpret))
    scratch_spec = P(None, tp_axis) if (heads or two_d) else P()
    sc_spec = ({"q": scratch_spec, "s": scratch_spec} if kv_quant
               else scratch_spec)

    out = {"pool_spec": pool_spec, "params_specs": p_specs,
           "world": world, "tp_world": tp_world, "sp_world": sp_world}

    ctx = dict(cfg=cfg, impl=impl, interpret=interpret)
    if heads:
        fwd = dict(cfg=cfg, page=page_size, **block,
                   paged_attend=functools.partial(paged_attend, **ctx))
        chunk_attend = functools.partial(
            _attend_prefix, impl=impl, interpret=interpret,
            window=cfg.attn_window, soft_cap=cfg.attn_soft_cap)
        fill_body = functools.partial(_fill_pool_pages, page=page_size)
        load_body = functools.partial(_gather_pool_pages, page=page_size)
        cow_body = _copy_pool_block
    else:
        own = dict(axis=sp, world=sp_world, num_blocks=num_blocks)
        fwd = dict(cfg=cfg, page=page_size, **block,
                   slots=functools.partial(_sp_slots, **own),
                   paged_attend=functools.partial(
                       _sp_paged_attend, n_pages_max=n_pages_max, **own,
                       **ctx))
        chunk_attend = functools.partial(_sp_attend_prefix, axis=sp,
                                         world=sp_world, **ctx)
        fill_body = functools.partial(sp_fill_pool_pages_shard,
                                      page=page_size, **own)
        load_body = functools.partial(sp_gather_pool_pages_shard,
                                      page=page_size, **own)
        cow_body = functools.partial(sp_copy_pool_block_shard, **own)
    decode_body = functools.partial(_paged_decode_forward, **fwd)
    chunk_body = functools.partial(_chunk_shard, cfg=cfg, quantized=kv_quant,
                                   attend=chunk_attend, **block)

    # (params, pools, tables, kv_lens, token, active)
    out["paged_decode"] = ShardedProgram(
        decode_body, mesh, (p_specs, pools_specs, P(), P(), P(), P()),
        (pools_specs, P()), donate_argnums=(1,))
    if horizon > 1:
        out["decode_horizon"] = ShardedProgram(
            functools.partial(_paged_decode_horizon, decode_fwd=decode_body),
            mesh, (p_specs, pools_specs) + (P(),) * 13,
            # a wide vocabulary's sampler count rides last
            (pools_specs,) + (P(),) * (6 + takes_candidates(cfg.vocab)),
            donate_argnums=(1,))
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
    # a cold request's scratch, born on the spec the chunk program takes
    # it on (each rank zeroes its own heads; static ``s_ext``)
    out["zero_scratch"] = ShardedProgram(
        functools.partial(
            _zero_scratch, quantized=kv_quant, dtype=cfg.dtype,
            specs=[((cfg.n_kv_heads // tp_world, cfg.head_dim),) * 2]
            * cfg.n_layers),
        mesh, (), [(sc_spec, sc_spec)] * cfg.n_layers)

    def make_chunk(extent: int) -> ShardedProgram:
        return ShardedProgram(
            functools.partial(chunk_body, extent=extent), mesh,
            (p_specs, P(),
             [(sc_spec, sc_spec)] * cfg.n_layers, P(), P()),
            ([(sc_spec, sc_spec)] * cfg.n_layers, P()),
            donate_argnums=(2,), name="prefill_chunk")

    out["prefill_chunk"] = MeshChunkJit(make_chunk, quantized=kv_quant)

    if draft is not None:
        dcfg = draft.cfg
        dctx = dict(impl=draft.attn.ctx.impl,
                    interpret=draft.attn.ctx.interpret)
        d_specs = replicated_like(draft_params)
        dpools_specs = [(P(), P())] * dcfg.n_layers
        draft_step = functools.partial(_draft_decode_forward, cfg=dcfg,
                                       **dctx)
        out["spec_round"] = ShardedProgram(
            functools.partial(
                _spec_round_fused, draft_step=draft_step,
                decode_fwd=decode_body,
                verify_fwd=functools.partial(_paged_verify_forward, **fwd)),
            mesh,
            (p_specs, d_specs, pools_specs, dpools_specs)
            + (P(),) * 15,
            (pools_specs, dpools_specs) + (P(),) * 9,
            donate_argnums=(2, 3))
        out["draft_tail_step"] = ShardedProgram(
            draft_step, mesh, (d_specs, dpools_specs, P(), P(), P()),
            (dpools_specs, P(), P()), donate_argnums=(1,))
        out["draft_join"] = ShardedProgram(
            _splice_draft_rows, mesh,
            (dpools_specs, P(), P(),
             [(P(), P())] * dcfg.n_layers, P(), P(), P()),
            (dpools_specs, P(), P()), donate_argnums=(0, 1, 2))
        # every rank runs the identical world-1 chunk forward
        dchunk_body = functools.partial(
            _chunk_shard, cfg=dcfg, quantized=False,
            attend=functools.partial(
                _attend_prefix, window=dcfg.attn_window,
                soft_cap=dcfg.attn_soft_cap, **dctx),
            **dense_block(dcfg))

        def make_draft_chunk(extent: int) -> ShardedProgram:
            return ShardedProgram(
                functools.partial(dchunk_body, extent=extent), mesh,
                (d_specs, P(), [(P(), P())] * dcfg.n_layers, P(), P()),
                ([(P(), P())] * dcfg.n_layers, P()), donate_argnums=(2,),
                name="draft_prefill")

        out["draft_prefill"] = MeshChunkJit(make_draft_chunk)
        out["draft_zero_scratch"] = ShardedProgram(
            functools.partial(
                _zero_scratch, quantized=False, dtype=dcfg.dtype,
                specs=[((dcfg.n_kv_heads, dcfg.head_dim),) * 2]
                * dcfg.n_layers),
            mesh, (), [(P(), P())] * dcfg.n_layers)
        if prefix_cache:
            out["draft_fill_pages"] = ShardedProgram(
                functools.partial(_fill_pool_pages, page=page_size), mesh,
                (dpools_specs, [(P(), P())] * dcfg.n_layers, P()),
                dpools_specs, donate_argnums=(0,))
            out["draft_load_pages"] = ShardedProgram(
                functools.partial(_gather_pool_pages, page=page_size), mesh,
                (dpools_specs, P()), [(P(), P())] * dcfg.n_layers)
    # each program under the engine's name for it (its key here), which
    # its HLO module and so a device trace then read: jit_<name>
    for key, prog in out.items():
        if isinstance(prog, ShardedProgram):
            prog.name = key
    return out
