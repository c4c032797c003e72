"""Autoregressive generation over the sequence-parallel KV cache.

The serving-side capability the reference's decode stack exists for, taken
end-to-end: prefill writes per-layer K/V into sequence-sharded caches, and
every decode step runs the SP flash-decode path — local split-KV partials
on each rank's shard, low-latency allgather, LSE combine
(layers/sp_flash_decode.py; reference sp_flash_decode_layer.py:43-184 has
the attention module but no model or loop around it).

Weights are replicated (the decode-serving layout: the sharded thing is
the KV cache); works on any mesh axis, including world 1.
"""

from __future__ import annotations

import contextlib
import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from triton_dist_tpu.kernels.flash_decode import (
    decode_kernel_gap,
    gqa_decode_paged_shard,
    paged_kernel_gap,
)
from triton_dist_tpu.kernels.gemm import resolve_impl
from triton_dist_tpu.layers.sp_flash_decode import SpGQAFlashDecodeAttention
from triton_dist_tpu.models.llama import LlamaConfig, _rms_norm
from triton_dist_tpu.runtime.jit_cache import named
from triton_dist_tpu.runtime.profiling import region


@dataclass(frozen=True)
class LayerKind:
    """What ONE layer is, as a static the seams see at trace time: a model
    whose layers differ in kind hands :func:`_layer_stack` one of these a
    layer (``kinds``), and ``project``, the attend pair, ``write_kv`` and a
    family's ``mixer`` read ``kinds[li]``.  A model with one kind of layer
    passes none.

    ``attn`` names the layer's mixer, six kinds:

    * ``"full"`` / ``"window"`` — causal attention over the whole context /
      the last ``window`` positions (``models/swa_moe.py``), writing its own
      K and V rows;
    * ``"cross"`` — a query and an output projection only: it reads the
      cache an EARLIER full layer of the same forward wrote
      (``models/ssm_yoco.py``: one cache, eight readers) and holds none;
    * ``"ssm"`` — a selective state-space layer: no cache that grows, a
      fixed state a request (its group is a STATE group);
    * ``"linear"`` — a linear-attention layer (``models/gdn_hybrid.py``):
      a fixed MATRIX state a head a request, in a STATE group like ``ssm``;
    * ``"gmu"`` — a gate over what an earlier state-space layer handed
      down for the same token: no cache, no state.

    ``window`` is the attention's reach in positions (0: the whole
    context).  ``group`` is the cache group — block table and pool
    geometry (serve/block_manager.py ``KvGroups``) — whose table the layer
    reads: its own for ``full`` / ``window`` / ``ssm`` / ``linear``, the
    full layer's for ``cross``, -1 for a layer that reads none (``gmu``)."""

    attn: str = "full"
    window: int = 0
    group: int = 0

    @property
    def state(self) -> bool:
        """The layer holds a fixed state a request (a slot of a STATE
        group) and no cache that grows."""
        return self.attn in ("ssm", "linear")

    @property
    def owns(self) -> bool:
        """The layer leaves something a LATER token reads — cache rows or
        a state; a ``cross`` or ``gmu`` layer leaves nothing."""
        return self.attn not in ("cross", "gmu")

    @property
    def call_name(self) -> str:
        """The paged attention call's name in a device trace."""
        return f"gqa_paged_{self.attn}"


def _kind_kw(kinds, li) -> dict:
    return {} if kinds is None else {"kind": kinds[li]}


@dataclass
class GenerationState:
    """Per-layer sharded KV caches + global lengths."""

    caches: list  # [(k_cache, v_cache)] per layer, [B, Hkv, S, D] sharded
    kv_lens: jax.Array  # [B] int32 — tokens currently in the cache
    last_logits: jax.Array  # [B, vocab] f32 — logits for the next token


def _rope(x, pos, theta):
    """RoPE (Llama pairing) at per-token positions: x [B, T, H, hd];
    pos [B, T] int32 — global position of token t of row b (a leading 1
    broadcasts: one chunk's positions for every row)."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = pos[..., None].astype(jnp.float32) * freqs      # [B, T, hd/2]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                          axis=-1)
    return out.astype(x.dtype)


class Generator:
    """Greedy autoregressive decoder for the Llama family.

    Usage::

        gen = Generator(cfg, mesh, axis="sp", max_seq=4096)
        state = gen.prefill(params, prompt_tokens)       # [B, S0]
        tokens, state = gen.generate(params, state, n_new=64)
    """

    def __init__(self, cfg: LlamaConfig, mesh: Mesh, *, axis: str = "sp",
                 max_seq: int | None = None, impl: str = "auto",
                 interpret: bool = False, kv_dtype=None):
        self.cfg = cfg
        self.mesh = mesh
        self.axis = axis
        self.max_seq = max_seq or cfg.max_seq
        self.attn = SpGQAFlashDecodeAttention(
            mesh, axis=axis, impl=impl, interpret=interpret,
            check_bounds=False,  # Generator guards lengths itself (below)
            kv_dtype=kv_dtype,   # jnp.int8 = quantized KV cache
            soft_cap=cfg.attn_soft_cap, window=cfg.attn_window)
        # The block's seams, as every forward of this file and of
        # serve/programs.py takes them (``_layer_stack``).  A subclass
        # with another MLP overrides ``_ffn_prompt`` / ``_ffn_decode``.
        self._hooks = dense_block(cfg, ffn=self._ffn_prompt)
        # Chunk attention at world > 1 enters shard_map over the
        # sequence-SHARDED cache (per-shard flash + LSE combine, the
        # decode SP recipe on prefill) — mesh/axis carry the topology in.
        self._attend_prefix = functools.partial(
            _attend_prefix, impl=impl, interpret=interpret, mesh=mesh,
            axis=axis, window=cfg.attn_window, soft_cap=cfg.attn_soft_cap)
        self._prefill_jit = jax.jit(functools.partial(
            _prompt_forward, cfg=cfg, **self._hooks,
            attend=functools.partial(_attend_prompt, cfg=cfg, impl=impl,
                                     interpret=interpret)))
        self._chunk_jit = self.chunk_program()
        # Batched speculative-verify pass (r5): per-row cache lengths
        # through the multi-token decode kernel; cached here so serving
        # loops don't recompile per generate() call.
        self._verify_jit = jax.jit(
            functools.partial(_verify_forward, cfg=cfg, impl=impl,
                              interpret=interpret, **self._hooks),
            donate_argnums=(2,))
        self._step_jit = jax.jit(self._step_impl)
        # generate_onchip programs, keyed by (n_new, sampled, knobs) —
        # one compiled scan per distinct call signature.
        self._onchip_cache: dict = {}

    # -- the engine's view (``MlaMoeGenerator`` has the same five) ---------

    latent = False  # K and V planes (``MlaMoeGenerator``: latent pools)

    @property
    def kv_planes(self) -> list:
        """(heads, width) of each plane of a layer's cache: K and V."""
        return [(self.cfg.n_kv_heads, self.cfg.head_dim)] * 2

    def serve_hooks(self) -> dict:
        """Keyword seams for the engine's paged forwards."""
        ctx = self.attn.ctx
        return dict(self._hooks, paged_attend=functools.partial(
            paged_attend, cfg=self.cfg, impl=ctx.impl,
            interpret=ctx.interpret))

    def wrap_program(self, fwd):
        return fwd

    def kernel_gaps(self, *, page_size: int, prefill_chunk: int,
                    ladder: list, sp_world: int = 1) -> dict:
        """Attention paths that will NOT reach a Pallas kernel
        (:func:`attention_kernel_gaps` at this model's widths)."""
        ctx = self.attn.ctx
        return attention_kernel_gaps(
            head_dim=self.cfg.head_dim, page_size=page_size,
            prefill_chunk=prefill_chunk, ladder=ladder,
            kv_itemsize=jnp.dtype(self.cfg.dtype).itemsize,
            kv_quant=bool(self.attn.quantized), impl=ctx.impl,
            interpret=ctx.interpret, sp_world=sp_world)

    def chunk_program(self, **hooks):
        """The chunked-prefill program with ``hooks`` over the family's
        own (the engine's ``w8a8`` pair rides here).  Caches are donated:
        each chunk's dynamic-update happens in place instead of copying
        every layer's full-size cache per chunk.  Named: the serving
        engine runs it as ``prefill_chunk``, and a device trace shows
        ``jit_<name>``."""
        return jax.jit(
            named(_chunk_forward, "prefill_chunk", cfg=self.cfg,
                  attend=self._attend_prefix, **{**self._hooks, **hooks}),
            static_argnames=("quantized", "extent"), donate_argnums=(2,))

    # -- prefill ----------------------------------------------------------

    def prefill(self, params, tokens) -> GenerationState:
        """Run the prompt [B, S0], fill the caches, return the state."""
        cfg = self.cfg
        B, S0 = tokens.shape
        if S0 > self.max_seq:
            raise ValueError(f"prompt length {S0} > max_seq {self.max_seq}")
        kvs, logits = self._prefill_jit(params, tokens)
        lens = jnp.full((B,), S0, jnp.int32)
        caches = []
        for (k_new, v_new) in kvs:  # [B, Hkv, S0, hd] each
            caches.append(self.attn.init_cache(
                B, cfg.n_kv_heads, self.max_seq, cfg.head_dim,
                dtype=cfg.dtype, k_init=k_new, v_init=v_new))
        return GenerationState(caches=caches, kv_lens=lens,
                               last_logits=logits[:, -1])

    def prefill_chunked(self, params, tokens,
                        chunk_size: int = 512) -> GenerationState:
        """Prefill in fixed-size chunks against the growing KV cache.

        Activation memory is bounded by the chunk (scores are [c, S]
        instead of the one-shot prefill's [S0, S0]); each chunk's K/V
        lands in the cache (quantized for int8 caches) and later chunks
        attend to it.  Same final state as :meth:`prefill` up to KV-cache
        quantization of earlier chunks.
        """
        cfg = self.cfg
        B, S0 = tokens.shape
        if S0 > self.max_seq:
            raise ValueError(f"prompt length {S0} > max_seq {self.max_seq}")
        caches = [self.attn.init_cache(B, cfg.n_kv_heads, self.max_seq,
                                       cfg.head_dim, dtype=cfg.dtype)
                  for _ in range(cfg.n_layers)]
        logits = None
        # Attention only needs cache rows [0, S0); slicing to a fixed
        # extent keeps scores at [chunk, ~S0] instead of [chunk, max_seq]
        # (one trace per extent — constant across this prefill's chunks).
        extent = min(self.max_seq,
                     -(-S0 // chunk_size) * chunk_size)
        for off in range(0, S0, chunk_size):
            chunk = tokens[:, off:off + chunk_size]
            caches, logits = self._chunk_jit(
                params, chunk, caches, jnp.int32(off),
                quantized=self.attn.quantized, extent=extent)
        return GenerationState(caches=caches,
                               kv_lens=jnp.full((B,), S0, jnp.int32),
                               last_logits=logits[:, -1])

    # -- decode -----------------------------------------------------------

    def step(self, params, state: GenerationState, token,
             active=None) -> GenerationState:
        """One decode step: token [B] int32 → next state.

        ``active`` [B] bool (optional, r5): rows with ``active[b] ==
        False`` are FROZEN — their cache length does not advance (the
        dummy K/V write lands in the dead slot at ``kv_lens[b]``, masked
        by length; at ``kv_lens[b] == max_seq`` the owner check makes it
        a no-op).  The batched speculative loop retires finished rows
        this way so lockstep rounds cannot overflow a tightly
        provisioned cache.

        Raises on cache overflow when lengths are concrete (a dropped
        append would silently leave attention reading stale zero rows);
        jit-traced callers must bound steps themselves (``generate`` does).
        """
        if not isinstance(state.kv_lens, jax.core.Tracer):
            lens = state.kv_lens
            if active is not None:
                lens = jnp.where(active, lens, -1)  # frozen rows exempt
            top = int(jnp.max(lens))
            if top >= self.max_seq:
                raise ValueError(
                    f"KV cache overflow: decode at position {top} but "
                    f"max_seq={self.max_seq}")
        new_caches, kv_lens, logits = self._step_jit(
            params, state.caches, state.kv_lens, token, active)
        return GenerationState(caches=new_caches, kv_lens=kv_lens,
                               last_logits=logits)

    def _ffn_prompt(self, h2, layer):
        """FFN hook of the prompt-shaped programs (prefill, chunks,
        verify): ``h2`` [rows, D] -> [rows, D]."""
        return _dense_prompt_ffn(h2, layer)

    def _ffn_decode(self, h, layer):
        """Decode-step FFN hook: ``h`` [B, D] -> [B, D].  MoEGenerator
        overrides with the EP masked-expert path."""
        return self._ffn_prompt(h, layer)

    def _step_impl(self, params, caches, kv_lens, token, active=None):
        inc = (jnp.ones_like(kv_lens) if active is None
               else active.astype(kv_lens.dtype))

        def write_kv(li, cache, k, v):
            return self.attn.append_kv(*cache, k[:, 0], v[:, 0], kv_lens)

        def attend(li, q, cache):
            with region("attn"):
                return self.attn(q[:, 0], *cache, kv_lens + inc)[:, None]

        new_caches, logits = _layer_stack(
            params, token[:, None], kv_lens[:, None], caches, cfg=self.cfg,
            write_kv=write_kv, attend=attend,
            **dict(self._hooks, ffn=self._ffn_decode))
        return new_caches, kv_lens + inc, logits[:, 0]

    def generate(self, params, state: GenerationState, n_new: int,
                 sample=None, key=None, eos_id: int | None = None):
        """Generate up to ``n_new`` tokens.  Returns (tokens [B, n_new],
        state).

        Token choice per step:
        - default: greedy argmax;
        - ``key``: stochastic sampling — ``sample(logits, subkey)`` with a
          fresh subkey per step (``sample`` defaults to
          :func:`models.sampling.sample_logits`; pass
          ``sampling.make_sampler(temperature=..., top_k=..., top_p=...)``
          for the serving knobs);
        - ``sample`` without ``key``: deterministic ``sample(logits)``.

        ``eos_id``: rows that emit it keep emitting ``eos_id`` for the
        rest of the call (their caches still advance — batch rows stay in
        lockstep); the loop exits early once every row has finished.
        """
        if not isinstance(state.kv_lens, jax.core.Tracer):
            top = int(jnp.max(state.kv_lens))
            if top + n_new > self.max_seq:
                raise ValueError(
                    f"generate({n_new}) from position {top} would overflow "
                    f"max_seq={self.max_seq}")
        if key is not None and sample is None:
            from triton_dist_tpu.models.sampling import sample_logits
            sample = sample_logits
        outs = []
        done = None
        for _ in range(n_new):
            if key is not None:
                key, sub = jax.random.split(key)
                token = sample(state.last_logits, sub)
            elif sample is not None:
                token = sample(state.last_logits)
            else:
                token = jnp.argmax(state.last_logits, axis=-1).astype(
                    jnp.int32)
            if eos_id is not None:
                if done is None:
                    done = jnp.zeros(token.shape, bool)
                token = jnp.where(done, jnp.int32(eos_id), token)
                done = done | (token == eos_id)
            state = self.step(params, state, token)
            outs.append(token)
            if eos_id is not None and bool(jnp.all(done)):
                break
        tokens = jnp.stack(outs, axis=1)
        if eos_id is not None and tokens.shape[1] < n_new:
            pad = jnp.full((tokens.shape[0], n_new - tokens.shape[1]),
                           eos_id, jnp.int32)
            tokens = jnp.concatenate([tokens, pad], axis=1)
        return tokens, state

    def generate_onchip(self, params, state: GenerationState, n_new: int,
                        *, temperature: float = 1.0,
                        top_k: int | None = None,
                        top_p: float | None = None, key=None,
                        eos_id: int | None = None):
        """Device-resident decode: all ``n_new`` steps run as ONE traced
        ``lax.scan`` with on-device token choice — the host dispatches
        once and fetches a ``[B, n_new]`` buffer, instead of paying a
        dispatch + logits sync + host argmax/sample round trip per token
        (:meth:`generate`'s loop).  This is the single-model form of the
        serving engine's decode horizon (docs/serving.md).

        Emitted tokens are IDENTICAL to :meth:`generate` with the same
        arguments: greedy (no ``key``) is per-step argmax; with ``key``
        the scan splits it per step and draws through
        ``sampling.sample_logits`` exactly like the host loop, so the
        stream matches token for token — the sampler knobs default to
        ``sample_logits``'s own defaults (temperature 1.0), matching
        ``generate(key=k)``'s default sampler, and apply only when
        ``key`` is given.  ``eos_id`` rows keep emitting
        ``eos_id`` once they hit it — but the scan cannot break early, so
        the returned state always reflects ``n_new`` steps (the host loop
        stops stepping once every row is done; only the post-done cache
        tail differs, never a token)."""
        if not isinstance(state.kv_lens, jax.core.Tracer):
            top = int(jnp.max(state.kv_lens))
            if top + n_new > self.max_seq:
                raise ValueError(
                    f"generate_onchip({n_new}) from position {top} would "
                    f"overflow max_seq={self.max_seq}")
        sampled = key is not None
        sig = (int(n_new), sampled, float(temperature), top_k, top_p)
        fn = self._onchip_cache.get(sig)
        if fn is None:
            fn = self._build_onchip(int(n_new), sampled,
                                    float(temperature), top_k, top_p)
            self._onchip_cache[sig] = fn
        if key is None:
            key = jax.random.key(0)  # untraced-by-choice: greedy ignores it
        caches, kv_lens, logits, toks = fn(
            params, state.caches, state.kv_lens, state.last_logits, key,
            jnp.int32(-1 if eos_id is None else eos_id))
        return toks, GenerationState(caches=caches, kv_lens=kv_lens,
                                     last_logits=logits)

    def _build_onchip(self, n_new, sampled, temperature, top_k, top_p):
        from triton_dist_tpu.models.sampling import sample_logits

        def run(params, caches, kv_lens, last_logits, key, eos):
            has_eos = eos >= 0

            def step(carry, _):
                caches, kv_lens, logits, key, done = carry
                if sampled:
                    key, sub = jax.random.split(key)
                    token = sample_logits(logits, sub,
                                          temperature=temperature,
                                          top_k=top_k, top_p=top_p)
                else:
                    token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                token = jnp.where(done, eos, token)
                done = done | (has_eos & (token == eos))
                caches, kv_lens, logits = self._step_impl(
                    params, caches, kv_lens, token, None)
                return (caches, kv_lens, logits, key, done), token

            done0 = jnp.zeros(kv_lens.shape, bool)
            (caches, kv_lens, logits, _, _), toks = jax.lax.scan(
                step, (caches, kv_lens, last_logits, key, done0), None,
                length=n_new)
            return caches, kv_lens, logits, toks.T

        return jax.jit(run)


def _layer_norm(x, w, b, eps):
    """LayerNorm with weight and bias, the statistics in float32."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w + b


def _norm(x, at: dict, name: str, cfg):
    """The model's norm over ``at[name]`` — the config's, as data:
    ``cfg.norm == "layer"`` is LayerNorm with ``at[name + "_bias"]``, a
    config that does not say is RMSNorm."""
    if getattr(cfg, "norm", "rms") == "layer":
        return _layer_norm(x, at[name], at[name + "_bias"], cfg.norm_eps)
    return _rms_norm(x, at[name], cfg.norm_eps)


def _head(x, params, cfg):
    """Float32 logits: over ``lm_head``, or — ``cfg.tie_embeddings`` — over
    the embedding's own rows (no second table is held)."""
    if getattr(cfg, "tie_embeddings", False):
        return jax.lax.dot_general(
            x, params["embed"], (((x.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    return jnp.dot(x, params["lm_head"], preferred_element_type=jnp.float32)


def _tail_start(kinds, n_layers: int) -> int:
    """The first layer after the LAST that leaves anything a later token
    reads (``LayerKind.owns``): from there on only the rows whose logits
    are wanted need computing.  A model of one kind of layer (no
    ``kinds``), or whose last layer owns a cache, has no such layer."""
    if kinds is None:
        return n_layers
    return max((li + 1 for li, k in enumerate(kinds) if k.owns), default=0)


def _layer_stack(params, tokens, pos, caches, *, cfg, project, out_proj,
                 ffn, write_kv, attend, kinds=None, mixer=None, shared=None,
                 keep=None, read=None, streams=None):
    """THE layer loop of serving and its oracles: tokens [B, T] at global
    positions pos [B, T] (a leading 1 broadcasts) through every layer of
    ``params`` -> (new caches, logits [B, T, V] float32).  Decode is this
    at T = 1, speculative verify at T = k + 1, a prefill chunk at B = 1,
    whole-prompt prefill at T = S; what differs between them is the
    cache access pair, and between model families the three block seams.

    ``keep`` (a traced row index, optional) says that ONE row's logits are
    wanted: -> (new caches, logits [B, 1, V]).  All T rows go through the
    layers up to the last that leaves anything a later token reads
    (:func:`_tail_start`, read off ``kinds``: every layer, for a model
    that passes none) — every cache row and state is what it is without
    ``keep`` — and from there row ``keep`` alone, in the T = 1 layout,
    through the remaining layers, the final norm and the head.  A
    ``mixer`` finds the row in ``shared["keep"]`` and slices what it
    carries a row of; the access pair is handed the one-row query and
    knows where it sits.  ``read`` (a traced bool beside ``keep``,
    optional) says whether anyone WILL read the row: where it is false
    the rest of the program — those layers, the norm, the head — is
    skipped (a ``lax.cond`` in the one program) and the logits are zeros.
    With no ``keep`` nothing of this is traced.

    The family's seams (``Generator.serve_hooks`` / ``dense_block``;
    ``models/mla_moe.py`` for latent attention + experts):

    - ``project(h [B, T, D], layer, pos) -> (q, k, v)``, the attention's
      front half: q [B, T, Hq, .] and the token's cache rows k, v
      [B, T, Hkv, .] — a family with ONE plane a layer (a latent row)
      returns ``v = None``, and its pair and ``out_proj`` know what that
      means;
    - ``out_proj(o2 [rows, .], layer) -> [rows, D]`` and ``ffn(h2 [rows,
      D], layer) -> [rows, D]``: the two seams a tensor-parallel caller
      reduces across ranks (serve/mesh.py: row-parallel matmul + psum
      over a local-head ``project``) and ``w8a8`` quantizes.

    The cache access pair:

    - ``write_kv(li, cache, k, v) -> cache'`` lands the rows in layer
      ``li``'s cache (contiguous append, pool-page scatter, a chunk at
      a scalar offset, or just keeping them);
    - ``attend(li, q, cache') -> [B, T, Hq, .]`` scores the queries
      against the updated cache.

    Each seam runs under its :func:`profiling.region` (``embed``, ``proj``,
    ``kv_write``, ``out_proj``, ``ffn``, ``head``; a family opens finer
    ones inside), so a device trace says what the model was doing in every
    operation (``benchmarks/regions.py``).  ``attn`` is the PAIR's to open,
    not this loop's: XLA names a Mosaic call with no name of its own after
    the scope around it, and the dense family's paged call must keep the
    name the benchmark reads it by (:func:`paged_attend`).

    ``kinds`` (a :class:`LayerKind` a layer, static) is given by a model
    whose layers differ in kind: ``project`` then takes ``kind=kinds[li]``
    (a RoPE per kind), and the pair, which has ``li``, looks its own up.

    ``mixer`` is given by a family whose layers are not all "project ->
    write K/V -> attend -> out_proj" (``models/ssm_yoco.py``): it stands in
    place of that quartet, ``mixer(li, h [B, T, D], layer, pos, cache,
    shared, write_kv=, attend=) -> (rows [B * T, D], cache', shared')``,
    and is handed the caller's access pair for its attention layers.
    ``shared`` (a dict) is what a layer hands to LATER layers of the same
    forward — a state-space layer's output for the gates below it, the one
    cache eight layers read — and what the caller tells the mixer of its
    own addressing (a decode step's state slots, a chunk's valid rows).
    Without ``mixer`` nothing of this is traced: the program is the one it
    was (tests/test_regions.py).  The norm and the head are the config's
    (:func:`_norm`, :func:`_head`): data, not a branch a family.  So is
    WHERE the norm sits: ``cfg.norm_after`` (the Olmo-2/3 lineage,
    ``models/gdn_hybrid.py``) puts it on a sub-layer's OUTPUT — ``x +
    norm(mixer(x))``, ``x + norm(ffn(x))`` — the mixer is then handed the
    residual stream itself and norms its own rows (inside its last region),
    and the MLP's output is normed here.

    So is the residual PATH (``cfg.hc_mult``, ``models/mla_moe.py``): a
    config of ``n > 1`` streams comes with ``streams``, the pair ``(pre,
    post)`` of ``kernels/hyper_conn.py`` under its family's dispatch.  The
    residual is then ``n`` streams a token, side by side along the last
    axis (stream ``j`` is columns ``[j D, (j + 1) D)``); the embedding
    fills every stream; a sub-layer — the attention with its norm, the MLP
    with its norm — opens with ``pre(X, layer["hc_attn" | "hc_mlp"]) ->
    (h, maps)``, runs on ``h`` exactly as it runs on ``x``, and closes with
    ``post(X, y, maps) -> X'`` where one stream has ``x + y`` (regions
    ``hc.pre`` / ``hc.post``); the streams are summed before the final
    norm.  ``keep`` / ``read`` / the tail act on the whole row.  Without
    ``streams`` nothing of this is traced: the program is the one it was.

    The residual is ``[B, T, W]`` — and ``[B, W]`` at T = 1 —, ``W = D`` for
    one stream and ``n D`` for ``n``; T is read off ``pos`` ([B | 1, T]),
    which every caller passes at its own T (the tail's one row included).
    The T = 1 layout is by evidence, not taste (PERF.md §6, PR 28): the
    chip's compiler folds
    the ``[B, T, D] -> [B * T, D] -> [B, T, H, hd]`` reshapes around the
    dense q / k / v products into a windowed convolution over re-laid
    copies of the weights, which a 128-token prefill chunk repays (the
    product comes out head-major, as the cache write and flash attention
    want it: 11.4 against 12.4 ms a chunk with flat rows) and a decode
    step does not (12.7 against 12.4 ms a step with its rows flat)."""
    B, T = tokens.shape
    post = getattr(cfg, "norm_after", False)
    layers = params["layers"]
    n = getattr(cfg, "hc_mult", 0) if streams is not None else 0

    def enter(x, maps):
        """A sub-layer's input off the residual: ``x`` itself, or the
        streams' pre-mix (-> the one-stream layout, and what closes it)."""
        if not n:
            return x, None
        with region("hc.pre"):
            h, mixed = streams[0](x.reshape(-1, x.shape[-1]), maps)
        return h.reshape(*x.shape[:-1], -1), mixed

    def leave(x, y, mixed):
        """The residual after a sub-layer's output ``y`` [rows, D]."""
        if not n:
            return x + y.reshape(x.shape)
        with region("hc.post"):
            return streams[1](x.reshape(-1, x.shape[-1]), y,
                              mixed).reshape(x.shape)

    def block(li, x, pos, shared):
        """Layer ``li`` over the residual ``x`` ([B, T, W]; [B, W] at T = 1)
        -> (x', the layer's cache, shared')."""
        layer, T = layers[li], pos.shape[1]
        xin, mixed = enter(x, layer.get("hc_attn"))
        if mixer is not None:
            h = xin if post else _norm(xin, layer, "attn_norm", cfg)
            rows, cache, shared = mixer(
                li, h.reshape(B, T, -1), layer, pos, caches[li], shared,
                write_kv=write_kv, attend=attend)
            x = leave(x, rows, mixed)
        else:
            with region("proj"):
                h = _norm(xin, layer, "attn_norm", cfg)
                q, k, v = project(h.reshape(B, T, -1), layer, pos,
                                  **_kind_kw(kinds, li))
            with region("kv_write"):
                cache = write_kv(li, caches[li], k, v)
            o = attend(li, q, cache)                     # [B, T, Hq, .]
            with region("out_proj"):
                o2 = o.reshape(B * T, -1).astype(cfg.dtype)
                x = leave(x, out_proj(o2, layer), mixed)
        with region("ffn"):
            xin, mixed = enter(x, layer.get("hc_mlp"))
            if post:
                y = _norm(ffn(xin.reshape(B * T, -1), layer), layer,
                          "mlp_norm", cfg)
            else:
                h2 = _norm(xin, layer, "mlp_norm", cfg)
                y = ffn(h2.reshape(B * T, -1), layer)
            x = leave(x, y, mixed)
        return x, cache, shared

    def head(x):
        with region("head"):
            if n:       # the streams' sum, in float32, rounded once
                D = x.shape[-1] // n
                x = sum(jax.lax.slice_in_dim(x, j * D, (j + 1) * D, axis=-1)
                        .astype(jnp.float32) for j in range(n)).astype(
                            x.dtype)
            logits = _head(_norm(x, params, "final_norm", cfg), params, cfg)
        return logits.reshape(B, -1, logits.shape[-1])

    with region("embed"):
        x = params["embed"][tokens.reshape((B,) if T == 1 else (B, T))]
        if n:           # every stream starts as the embedding
            x = jnp.tile(x, n)
    one_row = keep is not None and T > 1
    tail_at = _tail_start(kinds, len(layers)) if one_row else len(layers)
    new_caches = []
    for li in range(tail_at):
        x, cache, shared = block(li, x, pos, shared)
        new_caches.append(cache)
    if not one_row:
        return new_caches, head(x)

    def tail():
        row = jax.lax.dynamic_index_in_dim(x, keep, 1, False)
        at = jax.lax.dynamic_slice_in_dim(pos, keep, 1, axis=1)
        own = shared if mixer is None else {**shared, "keep": keep}
        for li in range(tail_at, len(layers)):
            row, _, own = block(li, row, at, own)
        return head(row)

    # the layers of the tail leave nothing: their entries go as they came
    new_caches.extend(caches[tail_at:])
    if read is None:
        return new_caches, tail()
    # zeros of the head's shape, asked of the head alone: tracing the
    # layers of ``tail`` a second time is seconds of every warm-up
    unread = jax.eval_shape(
        head, jax.ShapeDtypeStruct((B, x.shape[-1]), x.dtype))
    return new_caches, jax.lax.cond(
        read, tail, lambda: jnp.zeros(unread.shape, unread.dtype))


# ---------------------------------------------------------------------------
# The dense family's seams (the latent family's: models/mla_moe.py)
# ---------------------------------------------------------------------------


def _dense_project(h, layer, pos, *, cfg):
    """``wq / wk / wv`` + RoPE: h [B, T, D] -> q [B, T, Hq, hd], k and v
    [B, T, Hkv, hd].  ``cfg`` may be a rank's local-head view."""
    B, T, _ = h.shape
    h2 = h.reshape(B * T, cfg.dim)
    q = (h2 @ layer["wq"]).reshape(B, T, cfg.n_heads, cfg.head_dim)
    k = (h2 @ layer["wk"]).reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
    v = (h2 @ layer["wv"]).reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
    return (_rope(q, pos, cfg.rope_theta), _rope(k, pos, cfg.rope_theta),
            v)


def _dense_out_proj(o2, layer):
    """Attention output projection on replicated weights: ``o2`` is the
    flattened attention output ``[rows, Hq*hd]``."""
    return o2 @ layer["wo"]


def _dense_prompt_ffn(h2, layer):
    """The dense family's SwiGLU MLP over flattened tokens."""
    act = (jax.nn.silu((h2 @ layer["wgate"]).astype(jnp.float32))
           .astype(h2.dtype) * (h2 @ layer["wup"]))
    return act @ layer["wdown"]


def dense_block(cfg, **over) -> dict:
    """The dense block's three seams as :func:`_layer_stack` takes them,
    ``over`` replacing any (a subclass's MLP, the psum pair of a
    tensor-parallel rank)."""
    return {"project": functools.partial(_dense_project, cfg=cfg),
            "out_proj": _dense_out_proj, "ffn": _dense_prompt_ffn, **over}


def _pool_views(pool):
    """``(k, v, k_scale, v_scale)`` kernel views of one pool layer: bare
    float pools give ``(k, v, None, None)``; int8 dict pools expose
    their quant and scale planes so attend closures pass them straight
    to the paged kernels without branching on layout anywhere else."""
    k_pool, v_pool = pool
    if isinstance(k_pool, dict):
        return k_pool["q"], v_pool["q"], k_pool["s"], v_pool["s"]
    return k_pool, v_pool, None, None


def paged_attend(q, pool, tables, lens, *, cfg, impl, interpret,
                 kind: LayerKind | None = None, scale=None):
    """The engine's paged attend over ONE layer's ``(K, V)`` pool
    (``[NB, Hkv, page, hd]`` planes, float or int8 ``{"q", "s"}``):
    q [B, (T,) Hq, hd] -> [B, (T,) Hq, hd] through the block-table
    kernel.  The window is the model's one (``cfg.attn_window``) or, with
    a ``kind``, the layer's own — and the call then carries the kind's
    name.  ``scale`` is the scores' where it is not ``1 / sqrt(hd)`` of the
    pool's rows (heads stored in pairs: ``flash_decode.pack_q_pairs``)."""
    kq, vq, ks, vs = _pool_views(pool)
    # a call with no name of its own takes its instruction's name from the
    # scope around it (``closed_call`` / ``_unknown_``: what the benchmark's
    # ``paged_attn_roofline`` matches), so it alone runs outside ``attn``
    # and ``benchmarks/regions.py`` files it there by that name
    with contextlib.nullcontext() if kind is None else region("attn"):
        o, _ = gqa_decode_paged_shard(
            q, kq, vq, tables, lens, impl=impl, interpret=interpret,
            soft_cap=cfg.attn_soft_cap,
            window=cfg.attn_window if kind is None else kind.window,
            k_scale=ks, v_scale=vs,
            name=None if kind is None else kind.call_name,
            **({} if scale is None else {"scale": scale}))
    return o


def _attend_prompt(q, k, v, *, cfg, impl, interpret, kind=None):
    """Whole-prompt causal attention of :func:`_prompt_forward`:
    q [B, S, Hq, hd] over its own rows k, v [B, S, Hkv, hd] (under the
    layer's own window where a ``kind`` is given)."""
    from triton_dist_tpu.kernels.flash_attention import flash_attention

    out = flash_attention(
        *(t.transpose(0, 2, 1, 3) for t in (q, k, v)), causal=True,
        scale=1.0 / np.sqrt(cfg.head_dim),
        impl="xla" if impl == "xla" else "auto", interpret=interpret,
        window=cfg.attn_window if kind is None else kind.window,
        soft_cap=cfg.attn_soft_cap)
    return out.transpose(0, 2, 1, 3)


def attention_kernel_gaps(*, head_dim: int, page_size: int,
                          prefill_chunk: int, ladder: list,
                          kv_itemsize: int, kv_quant: bool, impl: str,
                          interpret: bool, sp_world: int = 1) -> dict:
    """Which of a dense-family engine's attention paths will NOT reach a
    Pallas kernel, and why: ``{"paged_decode" | "prefill_chunk":
    reason}``, empty when both do.  Under ``impl="auto"`` a shape the
    kernels cannot tile reroutes to XLA without a word (that is what
    ``auto`` is for), so an engine that "works" may have exercised no
    kernel of this repo; :class:`serve.engine.ServeEngine` asks its
    generator at construction (``kernel_gaps``: the same guards the
    dispatchers apply) and puts the answer where it can be seen.

    ``paged_decode`` stands for every program that attends through the
    block table (``paged_decode``, ``decode_horizon``, ``spec_round``) —
    they share ``gqa_decode_paged_shard``.  ``prefill_chunk`` is judged
    per scratch-extent rung; ``sp_world`` > 1 is the seq / heads+seq
    layout, whose prefill attends over a ``1/sp_world`` row span of the
    scratch through the decode kernel (``serve/mesh.py``)."""
    if resolve_impl(impl, interpret) == "xla":
        why = ("impl='xla' was asked for" if impl == "xla" else
               "impl='auto' resolves to XLA off a TPU (no interpreter)")
        return {"paged_decode": why, "prefill_chunk": why}
    gaps = {}
    gap = paged_kernel_gap(page_size, head_dim, kv_itemsize,
                           quantized=kv_quant)
    if gap is not None:
        gaps["paged_decode"] = gap
    if sp_world > 1:
        def rung_gap(r):
            return decode_kernel_gap(r // sp_world, head_dim)
    else:
        def rung_gap(r):
            return prefill_kernel_gap(prefill_chunk, r, head_dim)
    missed = {r: g for r in ladder if (g := rung_gap(r)) is not None}
    if missed:
        gaps["prefill_chunk"] = (
            f"extent rungs {sorted(missed)} of {list(ladder)}: "
            f"{next(iter(missed.values()))}")
    return gaps


# Chunks up to this many tokens ride the multi-token DECODE kernel in
# :func:`_attend_prefix`; larger ones the flash prefill kernel.
_DECODE_CHUNK_MAX = 32


def prefill_kernel_gap(chunk: int, extent: int, head_dim: int) -> str | None:
    """Why world-1 :func:`_attend_prefix` would run the dense XLA program
    for one (chunk, extent) pair (``None``: a Pallas kernel tiles it) —
    the same guards its two kernel dispatchers apply, for the serving
    engine's construction-time kernel-reach report."""
    from triton_dist_tpu.kernels.flash_attention import flash_shapes_ok
    from triton_dist_tpu.kernels.flash_decode import decode_kernel_gap

    if chunk <= _DECODE_CHUNK_MAX:
        return decode_kernel_gap(extent, head_dim)
    if not flash_shapes_ok(chunk, extent, head_dim):
        return (f"(chunk={chunk}, extent={extent}, D={head_dim}) needs "
                f"chunk%128 == extent%128 == D%128 == 0")
    return None


def _attend_prefix(q, k_all, v_all, prefix_len, *, k_scale=None,
                   v_scale=None, impl="auto", interpret=False,
                   mesh=None, axis=None, window=0, soft_cap=0.0,
                   kind=None, scale=None):
    """Chunk attention against the cache prefix + itself (under the
    layer's own window where a ``kind`` is given; ``scale``: the scores'
    where it is not ``1 / sqrt(hd)``: world 1 and the dense program).

    q [B, c, Hq, hd]; k/v_all [B, Hkv, S, hd] (the full cache, chunk rows
    already written at [prefix, prefix+c)); position j is visible to chunk
    row i iff j <= prefix + i.  Scores are [c, S] — the bounded-memory
    core of chunked prefill.  Optional scales dequantize an int8 cache.

    Both cache dtypes ride the flash prefill kernel (``prefix_len`` is
    traced — it enters as scalar prefetch, one trace per extent); an
    int8 cache's scales fuse into the block loop (``_flash_kernel_i8``).
    With ``mesh``/``axis`` given and world > 1, the cache stays
    sequence-SHARDED: each device runs flash over its KV shard and the
    partials LSE-merge (``sp_flash_attention_shard`` — the decode SP
    recipe on prefill; r4).  The dense program below remains for
    ``impl="xla"`` and the non-divisible-extent world>1 corner.

    Dispatch note: attention here always runs ``impl="auto"`` — the
    model-level ``impl`` contract is about the COLLECTIVE kernels
    (models/llama.py:_attention records the same design), so
    ``impl="pallas"`` does not force flash onto shapes it cannot tile
    (head_dim < 128, non-divisible extents); only explicit ``"xla"``
    pins the dense program.  Flash's own strict-dispatch mode is
    exercised by tests/test_flash_attention.py and the kernel-reach spy
    in tests/test_chunked_prefill.py.
    """
    if kind is not None:
        window = kind.window
    if impl != "xla":
        from triton_dist_tpu.kernels.flash_attention import (
            flash_attention,
            sp_flash_attention_shard,
        )
        from triton_dist_tpu.kernels.flash_decode import (
            gqa_decode_shard,
            sp_gqa_decode_shard,
        )

        qt = q.transpose(0, 2, 1, 3)                  # [B, Hq, c, hd]
        world = 1 if mesh is None else mesh.shape[axis]
        B, c = q.shape[0], q.shape[1]
        S_all = k_all.shape[2]
        # Small chunks (speculative verify: k draft tokens) ride the
        # MULTI-TOKEN DECODE kernel (r5): the queries are c*G block rows
        # instead of a 128-row-padded prefill q block, and the cache
        # streams once at the decode kernel's HBM-floor blocks.  The
        # prefill kernel keeps the large-chunk path (its q tiling wins
        # when c itself is MXU-sized).
        use_decode = c <= _DECODE_CHUNK_MAX
        if world == 1:
            if use_decode:
                lens = jnp.full((B,), c, jnp.int32) + prefix_len
                out, _ = gqa_decode_shard(
                    q, k_all, v_all, lens, impl="auto",
                    interpret=interpret, k_scale=k_scale, v_scale=v_scale,
                    soft_cap=soft_cap, window=window,
                    **({} if scale is None else {"scale": scale}))
                return out.astype(jnp.float32)
            out = flash_attention(
                qt, k_all, v_all, causal=True, q_offset=prefix_len,
                impl="auto", interpret=interpret, k_scale=k_scale,
                v_scale=v_scale, window=window, soft_cap=soft_cap,
                scale=scale)
            return out.transpose(0, 2, 1, 3).astype(jnp.float32)
        if use_decode and S_all % world == 0:
            from jax.sharding import PartitionSpec as P

            def spd(q_, k_, v_, lens_, *scs):
                ksc, vsc = scs if scs else (None, None)
                return sp_gqa_decode_shard(
                    q_, k_, v_, lens_, axis=axis, impl="auto",
                    interpret=interpret, k_scale=ksc, v_scale=vsc,
                    soft_cap=soft_cap, window=window)

            seq_spec = P(None, None, axis)
            lens = jnp.full((B,), c, jnp.int32) + prefix_len
            args = [q, k_all, v_all, lens]
            specs = [P(), seq_spec, seq_spec, P()]
            if k_scale is not None:
                args += [k_scale, v_scale]
                specs += [seq_spec, seq_spec]
            out = jax.shard_map(
                spd, mesh=mesh, in_specs=tuple(specs), out_specs=P(),
                check_vma=False,
            )(*args)
            return out.astype(jnp.float32)
        if k_all.shape[2] % world == 0:
            from jax.sharding import PartitionSpec as P

            def sp(qt_, k_, v_, off, *scs):
                ksc, vsc = scs if scs else (None, None)
                # The prefill kernel's window mask is GLOBAL-position
                # based (qpos = q_offset + i, kpos = me*s_loc + j), so
                # windowed SP chunked prefill just works; decode's window
                # is global too since r5 (unclipped window_lens per shard).
                return sp_flash_attention_shard(
                    qt_, k_, v_, axis=axis, causal=True, q_offset=off,
                    impl="auto", interpret=interpret, k_scale=ksc,
                    v_scale=vsc, soft_cap=soft_cap, window=window)

            seq_spec = P(None, None, axis)
            args = [qt, k_all, v_all, prefix_len]
            specs = [P(), seq_spec, seq_spec, P()]
            if k_scale is not None:
                args += [k_scale, v_scale]
                specs += [seq_spec, seq_spec]
            out = jax.shard_map(
                sp, mesh=mesh, in_specs=tuple(specs),
                out_specs=P(), check_vma=False,
            )(*args)
            return out.transpose(0, 2, 1, 3).astype(jnp.float32)
        # world > 1 with a non-divisible extent: the dense program below
        # is the only path that can live in the partitioned jit (a plain
        # pallas call cannot).
    B, c, Hq, hd = q.shape
    _, Hkv, S, _ = k_all.shape
    g = Hq // Hkv
    qf = q.astype(jnp.float32).reshape(B, c, Hkv, g, hd)
    logits = jnp.einsum("bchgd,bhsd->bhgcs", qf, k_all.astype(jnp.float32))
    logits = logits / np.sqrt(hd) if scale is None else logits * scale
    if k_scale is not None:
        logits = logits * k_scale[:, :, None, None, :]
    if soft_cap:
        logits = soft_cap * jnp.tanh(logits / soft_cap)
    pos = jnp.arange(S)[None, :]                     # [1, S]
    limit = prefix_len + jnp.arange(c)[:, None]      # [c, 1]
    mask = pos <= limit                              # [c, S]
    if window:
        mask = mask & (limit - pos < window)
    logits = jnp.where(mask[None, None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    if v_scale is not None:
        p = p * v_scale[:, :, None, None, :]
    out = jnp.einsum("bhgcs,bhsd->bchgd", p, v_all.astype(jnp.float32))
    return out.reshape(B, c, Hq, hd)


def _write_chunk(cache, new, prefix_len, quantized):
    """Write chunk K or V rows [B, Hkv, c, hd] at ``prefix_len``; for a
    quantized cache dict, rows quantize and the scale plane updates too."""
    from triton_dist_tpu.kernels.flash_decode import quantize_kv

    if not quantized:
        return jax.lax.dynamic_update_slice(
            cache, new.astype(cache.dtype), (0, 0, prefix_len, 0))
    q8, s = quantize_kv(new)
    return {
        "q": jax.lax.dynamic_update_slice(cache["q"], q8,
                                          (0, 0, prefix_len, 0)),
        "s": jax.lax.dynamic_update_slice(cache["s"], s,
                                          (0, 0, prefix_len)),
    }


def _chunk_forward(params, chunk, caches, prefix_len, *, cfg, quantized: bool,
                   project, out_proj, ffn, attend,
                   extent: int | None = None, n_valid=None, kinds=None,
                   mixer=None, streams=None):
    """One prompt chunk [B, c] against the cached prefix; returns
    (new_caches, logits [B, c, V] — position i predicts the token after
    chunk[:, i] — or, with ``n_valid``, [B, 1, V]: the last valid row's):
    :func:`_layer_stack` with the pair of a CONTIGUOUS
    cache written at the scalar ``prefix_len``.  The chunk's own K/V are
    written to the cache first (quantized if the cache is), then
    attention reads the cache back — so later chunks and the current one
    see identical (possibly quantized) K/V, matching the decode path's
    behavior.  ``extent`` (static) bounds the cache
    rows attention reads — scores stay [c, extent] instead of
    [c, max_seq].

    ``n_valid`` (traced scalar, optional) marks chunk rows >= n_valid as
    PADDING: their K/V write to the cache as exact zeros, so a final
    prompt chunk padded up to a fixed shape leaves the cache bit-identical
    to an unpadded run (pad rows match the zero-init rows it never wrote).
    Padded QUERY rows need no mask — causality already hides rows >=
    n_valid from every valid query (row i attends to positions <=
    prefix + i < prefix + n_valid).  One trace serves every residual
    chunk length — the serving engine's admission path never retraces on
    prompt shape (docs/serving.md: the bucket ladder).

    A caller that says how many rows are valid is PREFILLING, and of a
    prefill only the row that predicts the next token is ever read
    (``engine._finish_prefill``, ``_join_draft``): the chunk then keeps row
    ``n_valid - 1`` alone past the last layer that writes anything
    (:func:`_layer_stack`'s ``keep``: no argument of its own) and returns
    its logits as ``[B, 1, V]``.  And only of a prompt's LAST chunk: a
    caller that will read no row at all says so in the one scalar it
    already sends (a further argument is a further host transfer, with
    the device idle) — a NEGATIVE ``n_valid`` marks ``-n_valid`` rows valid
    and the logits unread (``_layer_stack``'s ``read``): everything past
    the last layer that writes is skipped inside the same program and the
    logits come back as zeros.  All rows are kept for the callers that
    pass no ``n_valid`` and consume every position — speculative
    verification (models/speculative.py), ``Generator.prefill_chunked`` —
    whose program is the one it was.

    ``attend(q, *views, prefix_len, k_scale=, v_scale=)`` is the
    family's prefix attention over the extent-bounded views of the
    layer's planes (scale views None unless ``quantized``):
    :func:`_attend_prefix` for (K, V) planes, one latent view for
    ``models/mla_moe.py``; serve/mesh.py's sequence-sharded chunk
    prefill supplies one that slices the rank-local span out of the
    views and LSE-combines across ranks — the K/V write stays whole, so
    cache contents never depend on the layout.  A tensor-parallel caller
    (already inside its own ``shard_map``, its scratch head-local)
    passes the family over its local-head config."""
    c = chunk.shape[1]
    positions = prefix_len + jnp.arange(c, dtype=jnp.int32)
    keep = read = None
    if n_valid is not None:
        read, n_valid = n_valid > 0, jnp.abs(n_valid)
        keep = n_valid - 1
    pad_mask = (None if n_valid is None else
                (jnp.arange(c, dtype=jnp.int32) < n_valid)[None, :, None,
                                                           None])

    def write_kv(li, planes, k, v):
        rows = (k,) if v is None else (k, v)
        if pad_mask is not None:
            rows = tuple(jnp.where(pad_mask, t, jnp.zeros((), t.dtype))
                         for t in rows)
        return tuple(_write_chunk(p, t.transpose(0, 2, 1, 3), prefix_len,
                                  quantized)
                     for p, t in zip(planes, rows))

    def attend_views(li, q, planes):
        ext = extent or (planes[0]["q"] if quantized
                         else planes[0]).shape[2]
        # ONE query of a wider chunk is the kept row's, from a layer past
        # the last that writes: it sits at its own position, the chunk's
        # rows under it (q: an array, or a family's tuple led by one)
        at = (prefix_len if jax.tree.leaves(q)[0].shape[1] == c
              else prefix_len + keep)
        with region("attn"):
            if quantized:
                k_c, v_c = planes
                return attend(q, k_c["q"][:, :, :ext], v_c["q"][:, :, :ext],
                              at, k_scale=k_c["s"][:, :, :ext],
                              v_scale=v_c["s"][:, :, :ext],
                              **_kind_kw(kinds, li))
            return attend(q, *(p[:, :, :ext] for p in planes), at,
                          k_scale=None, v_scale=None, **_kind_kw(kinds, li))

    # a family's ``mixer`` is told the chunk's valid rows: a state carried
    # from chunk to chunk must not scan a residual's padding
    more = {} if mixer is None else {
        "mixer": mixer, "shared": {"n_valid": n_valid}}
    if streams is not None:     # a residual of several streams: its mixes
        more["streams"] = streams
    return _layer_stack(params, chunk, positions[None], caches, cfg=cfg,
                        project=project, out_proj=out_proj, ffn=ffn,
                        write_kv=write_kv, attend=attend_views, kinds=kinds,
                        keep=keep, read=read, **more)


def _write_rows(cache, new, offs):
    """Per-row chunk write: cache [B, Hkv, S, D] <- new [B, Hkv, T, D] at
    row offsets offs [B] (each request's own cache length).

    Rows whose write would overflow the cache (offs[b] + T > S) are
    SKIPPED, not clamped: dynamic_update_slice would clamp the offset and
    silently overwrite still-valid rows.  Retired rows in the batched
    speculative loop (and the serving engine) sit exactly there — their
    outputs are discarded, but their caches must stay intact (ADVICE r5
    finding #2)."""
    T = new.shape[2]
    ok = offs + T <= cache.shape[2]                   # [B] bool

    def per(c, n, o, keep):
        upd = jax.lax.dynamic_update_slice(c, n.astype(c.dtype), (0, o, 0))
        return jnp.where(keep, upd, c)

    return jax.vmap(per)(cache, new, offs, ok)


def _verify_forward(params, chunk, caches, kv_lens, *, cfg: LlamaConfig,
                    project, out_proj, ffn, impl: str = "auto",
                    interpret: bool = False):
    """Batched speculative-verify forward (r5): score chunk [B, T] draft
    tokens against PER-ROW cache lengths ``kv_lens`` [B] in one pass.

    The per-row machinery `_chunk_forward` cannot express (its
    ``prefix_len`` is one scalar): RoPE at positions kv_lens[b] + t,
    K/V written at per-row offsets, and attention through the
    MULTI-TOKEN decode kernel (q_lens path — query t of row b sits at
    global position kv_lens[b] + t, exactly the kernel's
    ``pos < wlen - (T-1-t)`` rule).  Returns (new_caches,
    logits [B, T, V]).  World-1, float caches (the batch-1 path keeps
    full SP + int8 support via `_chunk_forward`).
    """
    from triton_dist_tpu.kernels.flash_decode import gqa_decode_shard

    T = chunk.shape[1]
    pos = kv_lens[:, None] + jnp.arange(T, dtype=jnp.int32)[None]

    def write_kv(li, cache, k, v):
        k_c, v_c = cache
        return (_write_rows(k_c, k.transpose(0, 2, 1, 3), kv_lens),
                _write_rows(v_c, v.transpose(0, 2, 1, 3), kv_lens))

    def attend(li, q, cache):
        with region("attn"):
            o, _ = gqa_decode_shard(q, cache[0], cache[1], kv_lens + T,
                                    impl=impl, interpret=interpret,
                                    soft_cap=cfg.attn_soft_cap,
                                    window=cfg.attn_window)
        return o

    return _layer_stack(params, chunk, pos, caches, cfg=cfg,
                        project=project, out_proj=out_proj, ffn=ffn,
                        write_kv=write_kv, attend=attend)


def _prompt_forward(params, tokens, *, cfg, project, out_proj, ffn, attend,
                    kinds=None, mixer=None, streams=None):
    """Full-sequence forward on replicated weights that also returns the
    per-layer cache rows (post-RoPE, cache layout [B, Hkv, S, .], one per
    plane) and logits: :func:`_layer_stack` with a pair that keeps the
    rows it is handed.  ``attend(q [B, S, Hq, .], *rows [B, S, Hkv, .])
    -> [B, S, Hq, .]`` is the family's causal attention over them
    (:func:`_attend_prompt`; ``mla_moe.attend_prompt``).  ``mixer``: a
    family's, handed on with nothing to tell it (``models/swa_moe.py``'s
    gate); ``streams``: a family's residual mixes, likewise."""
    def attend_rows(li, q, kv):
        with region("attn"):
            return attend(q, *kv, **_kind_kw(kinds, li))

    more = {} if mixer is None else {"mixer": mixer, "shared": {}}
    if streams is not None:
        more["streams"] = streams

    rows, logits = _layer_stack(
        params, tokens, jnp.arange(tokens.shape[1], dtype=jnp.int32)[None],
        [None] * len(params["layers"]), cfg=cfg, project=project,
        out_proj=out_proj, ffn=ffn,
        write_kv=lambda li, _, k, v: (k,) if v is None else (k, v),
        attend=attend_rows, kinds=kinds, **more)
    return [tuple(t.transpose(0, 2, 1, 3) for t in kv) for kv in rows], logits
