"""Llama-style dense transformer, TPU-native and kernel-wired.

The flagship model: every TP linear in the network runs through the
overlapped AG-GEMM / GEMM-RS Pallas kernels (sequence-parallel Megatron
layout), forward and backward, under one ``shard_map``.

Reference analog: the reference's model surface is its LLaMA-shape kernel
test configs (``test/nvidia/test_ag_gemm.py --shape_id LLaMA-3.1-70B`` etc.)
plus inference layers; it has no trainer.  Here the same shapes run as an
actual model with a training step — the capability the kernels exist for.

Layout conventions (Megatron sequence-parallel, seq-major):

* Activations between blocks: ``[S_loc, B, D]`` — sequence sharded over the
  ``tp`` axis, batch sharded over ``dp``.
* QKV / up / gate projections: column-parallel (AG over sequence fused with
  the GEMM); attention and the MLP nonlinearity run on full sequence with
  local heads / local FFN columns; out / down projections: row-parallel
  (GEMM fused with RS back to sequence-sharded).
* GQA attention with RoPE; RMSNorm; SwiGLU — the Llama-3 recipe.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from triton_dist_tpu.layers.tp_linear import (
    column_parallel_linear,
    row_parallel_linear,
)


@dataclass(frozen=True)
class LlamaConfig:
    vocab: int = 32000
    dim: int = 512
    n_layers: int = 2
    n_heads: int = 8
    n_kv_heads: int = 4
    ffn_dim: int = 1408
    max_seq: int = 2048
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: object = jnp.float32
    # Attention variants (r4): sliding window (Mistral) and logit
    # soft-capping (Gemma-2), threaded to the flash kernels by every
    # model path.  0 = off.
    attn_window: int = 0
    attn_soft_cap: float = 0.0

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @staticmethod
    def llama3_70b() -> "LlamaConfig":
        """The reference's benchmark shape (test_ag_gemm.py LLaMA-3.1-70B)."""
        return LlamaConfig(vocab=128256, dim=8192, n_layers=80, n_heads=64,
                           n_kv_heads=8, ffn_dim=28672, dtype=jnp.bfloat16)

    # Presets mirroring the rest of the reference's --shape_id table
    # (test_ag_gemm.py:149-154): K = dim, N = ffn_dim.

    @staticmethod
    def llama3_8b() -> "LlamaConfig":
        return LlamaConfig(vocab=128256, dim=4096, n_layers=32, n_heads=32,
                           n_kv_heads=8, ffn_dim=14336, dtype=jnp.bfloat16)

    @staticmethod
    def llama3_405b() -> "LlamaConfig":
        return LlamaConfig(vocab=128256, dim=16384, n_layers=126,
                           n_heads=128, n_kv_heads=8, ffn_dim=53248,
                           dtype=jnp.bfloat16)

    @staticmethod
    def mistral_7b() -> "LlamaConfig":
        # NOTE: the presets mirror the reference's GEMM-shape table, so
        # attention variants stay off by default; Mistral's real sliding
        # window is ``replace(cfg, attn_window=4096)`` — windowed
        # prefill, training, and (since r5) SP decode work on any mesh.
        return LlamaConfig(vocab=32000, dim=4096, n_layers=32, n_heads=32,
                           n_kv_heads=8, ffn_dim=14336, rope_theta=1e6,
                           dtype=jnp.bfloat16)

    @staticmethod
    def qwen2_72b() -> "LlamaConfig":
        return LlamaConfig(vocab=152064, dim=8192, n_layers=80, n_heads=64,
                           n_kv_heads=8, ffn_dim=29568, rope_theta=1e6,
                           dtype=jnp.bfloat16)

    @staticmethod
    def tiny(dtype=jnp.float32) -> "LlamaConfig":
        """CPU-mesh test size; every PER-SHARD dim on a tp=4 mesh still
        tiles the MXU legally (n%128, k%128 of the shard — the strict
        impl='pallas' gate enforces it): kv-proj N = n_kv_heads*head_dim
        = 512 and o-proj K = dim = 1024 both leave 128+ per device."""
        return LlamaConfig(vocab=512, dim=1024, n_layers=2, n_heads=8,
                           n_kv_heads=4, ffn_dim=1024, max_seq=256,
                           dtype=dtype)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "sharding"))
def _dense_on(k, denom, *, shape, dtype, sharding):
    """One seeded matrix drawn directly ONTO ``sharding``: each device
    generates its own shard (the threefry PRNG is partitionable), so a
    model that only fits spread over a mesh never exists whole on one
    chip.  ``denom`` is a traced scalar on purpose: a constant divisor
    would be folded into a multiply by its reciprocal, one ulp off the
    eager draw — this way the values are bit-identical to it."""
    w = (jax.random.normal(k, shape, jnp.float32) / denom).astype(dtype)
    return jax.lax.with_sharding_constraint(w, sharding)


def init_params(cfg: LlamaConfig, key, shardings: dict | None = None) -> dict:
    """Parameter pytree.  TP-sharded matrices carry their full (unsharded)
    shapes; ``param_specs`` says how each leaf is laid out on the mesh.

    ``shardings`` (a ``NamedSharding`` tree shaped like ``param_specs``)
    draws every leaf directly on its mesh layout — same values, but no
    leaf is ever materialised on a single device (llama3-8B is 15 GiB in
    bf16: it fits four 16 GB chips TP-sharded and not the first of
    them).  Without it, leaves land on the default device."""
    hd = cfg.head_dim

    def dense(k, fan_in, shape, sharding):
        if sharding is not None:
            return _dense_on(k, jnp.float32(math.sqrt(fan_in)), shape=shape,
                             dtype=cfg.dtype, sharding=sharding)
        return (jax.random.normal(k, shape, jnp.float32)
                / math.sqrt(fan_in)).astype(cfg.dtype)

    def ones(sharding):
        return jnp.ones((cfg.dim,), cfg.dtype, device=sharding)

    sh = shardings or jax.tree_util.tree_map(
        lambda _: None, param_specs(cfg), is_leaf=lambda x: isinstance(x, P))
    keys = jax.random.split(key, 2 + cfg.n_layers)
    params = {
        "embed": dense(keys[0], 1, (cfg.vocab, cfg.dim), sh["embed"]),
        "lm_head": dense(keys[1], cfg.dim, (cfg.dim, cfg.vocab),
                         sh["lm_head"]),
        "final_norm": ones(sh["final_norm"]),
        "layers": [],
    }
    # Q/K/V are separate column-sharded matrices (head-major columns, so a
    # contiguous tp split assigns whole heads per device); the forward
    # concatenates the *local* shards and runs ONE fused AG-GEMM.
    # name -> (subkey index, fan_in, shape)
    matrices = {
        "wq": (0, cfg.dim, (cfg.dim, cfg.n_heads * hd)),
        "wk": (5, cfg.dim, (cfg.dim, cfg.n_kv_heads * hd)),
        "wv": (2, cfg.dim, (cfg.dim, cfg.n_kv_heads * hd)),
        "wo": (1, cfg.n_heads * hd, (cfg.n_heads * hd, cfg.dim)),
        "wgate": (3, cfg.dim, (cfg.dim, cfg.ffn_dim)),
        "wup": (4, cfg.dim, (cfg.dim, cfg.ffn_dim)),
        "wdown": (6, cfg.ffn_dim, (cfg.ffn_dim, cfg.dim)),
    }
    for i, ls in enumerate(sh["layers"]):
        lk = jax.random.split(keys[2 + i], 7)
        params["layers"].append({
            "attn_norm": ones(ls["attn_norm"]),
            "mlp_norm": ones(ls["mlp_norm"]),
            **{name: dense(lk[j], fan_in, shape, ls[name])
               for name, (j, fan_in, shape) in matrices.items()},
        })
    return params


def param_shardings(cfg: LlamaConfig, mesh: Mesh, axis: str = "tp") -> dict:
    """``param_specs`` as a ``NamedSharding`` tree on ``mesh`` — what
    :func:`init_params` takes as ``shardings``."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), param_specs(cfg, axis),
        is_leaf=lambda x: isinstance(x, P))


def param_specs(cfg: LlamaConfig, axis: str = "tp") -> dict:
    """PartitionSpec tree matching :func:`init_params` (sharded over the
    tensor-parallel ``axis`` only; replicate over dp)."""
    layer = {
        "attn_norm": P(), "mlp_norm": P(),
        "wq": P(None, axis),       # column-parallel (whole heads per device)
        "wk": P(None, axis),
        "wv": P(None, axis),
        "wo": P(axis, None),       # row-parallel
        "wgate": P(None, axis),
        "wup": P(None, axis),
        "wdown": P(axis, None),
    }
    return {
        "embed": P(), "lm_head": P(), "final_norm": P(),
        "layers": [dict(layer) for _ in range(cfg.n_layers)],
    }


# ---------------------------------------------------------------------------
# Shard-level forward (call inside shard_map)
# ---------------------------------------------------------------------------


def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def _rope(x, positions, theta):
    """x: [S, B, H, hd]; rotate pairs (Llama convention)."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    angles = positions[:, None].astype(jnp.float32) * freqs[None, :]  # [S, hd/2]
    cos = jnp.cos(angles)[:, None, None, :]
    sin = jnp.sin(angles)[:, None, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _attention(q, k, v, cfg: LlamaConfig, *, impl="auto", interpret=False):
    """Causal GQA attention on local heads.  q: [S, B, Hq_loc, hd],
    k/v: [S, B, Hkv_loc, hd].  Full sequence, local heads (TP over heads).

    Routed through the flash prefill kernel (O(S) memory, blockwise
    online softmax) whenever shapes allow; the dense path only remains
    for ragged shapes / head_dim < 128.  The model-level ``impl``
    contract is about the collective kernels, so anything but an explicit
    ``"xla"`` leaves attention dispatch at ``"auto"`` (flash's strict
    mode is exercised by its own tests — tests/test_flash_attention.py)."""
    from triton_dist_tpu.kernels.flash_attention import flash_gqa_attention

    return flash_gqa_attention(q, k, v, causal=True,
                               scale=1.0 / math.sqrt(cfg.head_dim),
                               impl="xla" if impl == "xla" else "auto",
                               interpret=interpret,
                               window=cfg.attn_window,
                               soft_cap=cfg.attn_soft_cap)


def attention_block_shard(x, layer, cfg: LlamaConfig, *, axis, impl,
                          interpret):
    """Sequence-parallel TP attention sub-block shared by the model families
    (Llama dense, MoE): RMSNorm → fused-QKV column-parallel AG-GEMM → RoPE →
    causal GQA on local heads → row-parallel GEMM-RS, residual added.
    x: [S_loc, B, D].  ``layer`` needs attn_norm/wq/wk/wv/wo shards."""
    world = jax.lax.axis_size(axis)
    s_loc, b, _ = x.shape
    hd = cfg.head_dim
    hq_loc = cfg.n_heads // world
    hkv_loc = cfg.n_kv_heads // world
    full_positions = jnp.arange(world * s_loc, dtype=jnp.int32)
    lin_c = functools.partial(column_parallel_linear, axis=axis, impl=impl,
                              interpret=interpret)
    lin_r = functools.partial(row_parallel_linear, axis=axis, impl=impl,
                              interpret=interpret)

    h = _rms_norm(x, layer["attn_norm"], cfg.norm_eps)
    # Local Q/K/V column shards concatenate into one fused weight so the
    # sequence-allgather happens once per block.
    wqkv = jnp.concatenate([layer["wq"], layer["wk"], layer["wv"]], axis=1)
    qkv = lin_c(h.reshape(s_loc * b, cfg.dim), wqkv)
    qkv = qkv.reshape(world * s_loc, b, (hq_loc + 2 * hkv_loc) * hd)
    q, k, v = jnp.split(
        qkv, [hq_loc * hd, (hq_loc + hkv_loc) * hd], axis=-1)
    q = _rope(q.reshape(-1, b, hq_loc, hd), full_positions, cfg.rope_theta)
    k = _rope(k.reshape(-1, b, hkv_loc, hd), full_positions, cfg.rope_theta)
    v = v.reshape(-1, b, hkv_loc, hd)
    o = _attention(q, k, v, cfg, impl=impl,
                   interpret=interpret)  # [S, B, Hq_loc, hd]
    o = o.reshape(world * s_loc * b, hq_loc * hd)
    return x + lin_r(o, layer["wo"]).reshape(s_loc, b, cfg.dim)


def mlp_block_shard(x, layer, cfg: LlamaConfig, *, axis, impl, interpret):
    """SwiGLU MLP sub-block (sequence-parallel residual): RMSNorm →
    column-parallel gate/up AG-GEMMs → silu·mul → row-parallel down
    GEMM-RS, residual added.  x: [S_loc, B, D]."""
    s_loc, b, _ = x.shape
    lin_c = functools.partial(column_parallel_linear, axis=axis, impl=impl,
                              interpret=interpret)
    lin_r = functools.partial(row_parallel_linear, axis=axis, impl=impl,
                              interpret=interpret)
    h = _rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
    h2 = h.reshape(s_loc * b, cfg.dim)
    gate = lin_c(h2, layer["wgate"])
    up = lin_c(h2, layer["wup"])
    act = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    return x + lin_r(act, layer["wdown"]).reshape(s_loc, b, cfg.dim)


def forward_shard(params, tokens_shard, cfg: LlamaConfig, *, axis="tp",
                  impl="auto", interpret=False):
    """Per-device forward.  tokens_shard: [S_loc, B_loc] int32 (seq-major,
    sequence sharded over ``axis``).  Returns logits [S_loc, B_loc, vocab].

    Every projection is an overlapped distributed GEMM; weight shards arrive
    pre-sliced by shard_map according to :func:`param_specs`.
    """
    world = jax.lax.axis_size(axis)
    assert cfg.n_heads % world == 0 and cfg.n_kv_heads % world == 0, (
        f"TP over {world} devices needs n_heads ({cfg.n_heads}) and "
        f"n_kv_heads ({cfg.n_kv_heads}) divisible by it")

    x = params["embed"][tokens_shard]  # [S_loc, B, D]

    for layer in params["layers"]:
        x = attention_block_shard(x, layer, cfg, axis=axis, impl=impl,
                                  interpret=interpret)
        x = mlp_block_shard(x, layer, cfg, axis=axis, impl=impl,
                            interpret=interpret)

    x = _rms_norm(x, params["final_norm"], cfg.norm_eps)
    # Vocab projection: local tokens x replicated lm_head (seq stays sharded).
    return jnp.dot(x, params["lm_head"],
                   preferred_element_type=jnp.float32)


def loss_shard(params, tokens_shard, targets_shard, cfg: LlamaConfig, *,
               axis="tp", dp_axis=None, impl="auto", interpret=False):
    """Per-device *contribution* to the global mean next-token CE loss
    (``psum`` of this over all devices == the global mean).

    Deliberately local: autodiff must NOT pass through a ``psum`` — under
    ``shard_map(check_vma=False)`` its transpose over-counts by the axis
    size.  Cross-device gradient flow for the TP weights happens correctly
    through the AG↔RS duality of the custom VJPs in ``tp_linear``; grads of
    locally-used replicated leaves (embed/lm_head/norms) are psum'd by the
    train step."""
    logits = forward_shard(params, tokens_shard, cfg, axis=axis, impl=impl,
                           interpret=interpret)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets_shard[..., None].astype(jnp.int32),
                             axis=-1)[..., 0]
    denom = ll.size * jax.lax.axis_size(axis)
    if dp_axis is not None:
        denom = denom * jax.lax.axis_size(dp_axis)
    return -jnp.sum(ll) / denom


# ---------------------------------------------------------------------------
# Host-level entries
# ---------------------------------------------------------------------------


def make_forward(cfg: LlamaConfig, mesh: Mesh, *, axis="tp", dp_axis=None,
                 impl="auto", interpret=False):
    """jit(shard_map(forward)) over the mesh.  Input tokens: [S, B] int32."""
    batch_spec = P(axis, dp_axis) if dp_axis else P(axis)
    specs = param_specs(cfg)

    fn = jax.shard_map(
        functools.partial(forward_shard, cfg=cfg, axis=axis, impl=impl,
                          interpret=interpret),
        mesh=mesh,
        in_specs=(specs, batch_spec),
        out_specs=P(axis, dp_axis) if dp_axis else P(axis),
        check_vma=False,
    )
    return jax.jit(fn)


def make_train_step(cfg: LlamaConfig, mesh: Mesh, *, axis="tp", dp_axis=None,
                    impl="auto", interpret=False, lr=1e-3):
    """Full SGD training step: loss, grads through the overlapped kernels
    (custom VJPs), psum over dp, parameter update.  Returns (step, specs).

    The multi-chip training story the driver dry-runs
    (``__graft_entry__.dryrun_multichip``)."""
    specs = param_specs(cfg)
    batch_spec = P(axis, dp_axis) if dp_axis else P(axis)

    def step_shard(params, tokens, targets):
        local_loss, grads = jax.value_and_grad(loss_shard)(
            params, tokens, targets, cfg, axis=axis, dp_axis=dp_axis,
            impl=impl, interpret=interpret)
        all_axes = (axis,) if dp_axis is None else (axis, dp_axis)
        loss = jax.lax.psum(local_loss, all_axes)  # reported, not diff'd

        # Gradient reductions: each device holds only its local contribution
        # for leaves it shares with other devices.  Replicated leaves (embed,
        # lm_head, norms) need a psum over tp (each tp device saw only its
        # sequence chunk) and dp; tp-sharded weight grads are complete per
        # shard (the custom VJPs gather the full-sequence cotangent) but
        # still need summing over dp batches.
        def _reduce(g, spec):
            sharded_on_tp = any(s == axis for s in spec)
            axes = () if sharded_on_tp else (axis,)
            if dp_axis is not None:
                axes = axes + (dp_axis,)
            return jax.lax.psum(g, axes) if axes else g

        grads = jax.tree.map(_reduce, grads, specs,
                             is_leaf=lambda x: isinstance(x, P))
        new_params = jax.tree.map(lambda p, g: p - lr * g.astype(p.dtype),
                                  params, grads)
        return new_params, loss

    fn = jax.shard_map(
        step_shard,
        mesh=mesh,
        in_specs=(specs, batch_spec, batch_spec),
        out_specs=(specs, P()),
        check_vma=False,
    )
    return jax.jit(fn), specs


def place_params(params, cfg: LlamaConfig, mesh: Mesh) -> dict:
    """Device-put a host param tree according to ``param_specs``."""
    return jax.tree.map(jax.device_put, params, param_shardings(cfg, mesh))
