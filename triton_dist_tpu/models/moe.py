"""MoE transformer (Mixtral/DeepSeek-class), expert-parallel and kernel-wired.

The second model family: the Llama attention/TP stack (models/llama.py) with
the dense FFN replaced by a top-k routed expert FFN running over the
framework's EP machinery — token dispatch/combine through the low-latency
AllToAll (kernels/all_to_all.py, differentiable via its custom VJP) and
expert compute through the grouped Pallas GEMM (kernels/group_gemm.py) fed
by the device-side sort/align (kernels/moe_utils.py).

Reference analog: the reference exercises its MoE path as kernel tests
(test_ep_moe_inference.py, test_ag_moe.py, test_moe_reduce_rs.py with
Qwen/DeepSeek FFN shapes) and an inference layer (``EPAll2AllLayer``); it
has no MoE *model* and no training story.  Here the same machinery runs as
a full transformer with a train step — gradients flow through the AllToAll
(its transpose is the inverse AllToAll), the scatter/gather routing, and
the grouped GEMMs.

Parallelism layout (one mesh axis, Megatron-style + EP):

* Attention: TP over heads, sequence-parallel residual stream — identical
  to the Llama model (shared code).
* MoE FFN: experts sharded over the same axis (expert ``e`` lives on rank
  ``e // (E // world)``, the reference's contiguous layout); tokens travel
  to their experts and back each block.
* Router: replicated; aux load-balance loss (Switch-style) accumulated
  across layers.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from triton_dist_tpu.kernels.group_gemm import moe_ffn_sorted
from triton_dist_tpu.kernels.moe_utils import (
    gather_sorted,
    sort_align,
    topk_routing,
)
from triton_dist_tpu.layers.ep_a2a import ep_combine_shard, ep_dispatch_shard
from triton_dist_tpu.models.llama import (
    LlamaConfig,
    _rms_norm,
    attention_block_shard,
)


@dataclass(frozen=True)
class MoEConfig:
    vocab: int = 512
    dim: int = 256
    n_layers: int = 2
    n_heads: int = 8
    n_kv_heads: int = 4
    n_experts: int = 8
    topk: int = 2
    expert_ffn_dim: int = 256
    max_seq: int = 2048
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    aux_loss_coef: float = 0.01
    # group-GEMM row-tile size; also the expert padding granularity.
    block_m: int = 128
    # per-destination-rank token capacity; None = lossless worst case
    # (t_loc * topk, every local assignment bound for one rank).
    max_tokens: int | None = None
    dtype: object = jnp.float32
    # Attention variants (r4), same semantics as LlamaConfig.
    attn_window: int = 0
    attn_soft_cap: float = 0.0

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    def as_llama(self) -> LlamaConfig:
        """Attention-side view (shared _rope/_attention take a LlamaConfig)."""
        return LlamaConfig(
            vocab=self.vocab, dim=self.dim, n_layers=self.n_layers,
            n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
            ffn_dim=self.expert_ffn_dim, max_seq=self.max_seq,
            rope_theta=self.rope_theta, norm_eps=self.norm_eps,
            dtype=self.dtype, attn_window=self.attn_window,
            attn_soft_cap=self.attn_soft_cap)

    @staticmethod
    def mixtral_8x7b() -> "MoEConfig":
        """Mixtral-8x7B shapes (the DeepEP/EP-MoE benchmark class)."""
        return MoEConfig(vocab=32000, dim=4096, n_layers=32, n_heads=32,
                         n_kv_heads=8, n_experts=8, topk=2,
                         expert_ffn_dim=14336, dtype=jnp.bfloat16)

    @staticmethod
    def a2a_e128_k8_d7168() -> "MoEConfig":
        """A SHAPE preset, not a model: the reference's low-latency
        AllToAll benchmark point (README.md:87 / test_all_to_all.py: 128
        experts, topk 8, hidden 7168) on this file's block — GQA attention,
        softmax top-k router, no shared expert.  The DeepSeek-V3 block
        itself (latent attention, sigmoid group-limited routing, a shared
        expert) is ``models/mla_moe.py``, built from its ``config.json``."""
        return MoEConfig(vocab=129280, dim=7168, n_layers=61, n_heads=128,
                         n_kv_heads=128, n_experts=128, topk=8,
                         expert_ffn_dim=2048, dtype=jnp.bfloat16)

    @staticmethod
    def tiny(dtype=jnp.float32) -> "MoEConfig":
        """CPU-mesh test size (block_m small enough for tiny token counts)."""
        # Per-shard pallas-legal on a tp=4 mesh (strict impl='pallas'
        # gate): head_dim 128 keeps kv/o projections at n%128/k%128 per
        # device; expert_ffn 512 leaves f_loc = 128.
        return MoEConfig(vocab=512, dim=512, n_layers=2, n_heads=4,
                         n_kv_heads=4, n_experts=8, topk=2,
                         expert_ffn_dim=512, max_seq=128, block_m=8,
                         dtype=dtype)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def init_params(cfg: MoEConfig, key) -> dict:
    """Expert stacks are full [E, ...] arrays; ``param_specs`` shards their
    leading (expert) dim over the mesh axis — EP by construction."""
    hd = cfg.head_dim

    def dense(k, fan_in, shape):
        return (jax.random.normal(k, shape, jnp.float32)
                / math.sqrt(fan_in)).astype(cfg.dtype)

    keys = jax.random.split(key, 2 + cfg.n_layers)
    params = {
        "embed": dense(keys[0], 1, (cfg.vocab, cfg.dim)),
        "lm_head": dense(keys[1], cfg.dim, (cfg.dim, cfg.vocab)),
        "final_norm": jnp.ones((cfg.dim,), cfg.dtype),
        "layers": [],
    }
    E, F = cfg.n_experts, cfg.expert_ffn_dim
    for i in range(cfg.n_layers):
        lk = jax.random.split(keys[2 + i], 9)
        params["layers"].append({
            "attn_norm": jnp.ones((cfg.dim,), cfg.dtype),
            "mlp_norm": jnp.ones((cfg.dim,), cfg.dtype),
            "wq": dense(lk[0], cfg.dim, (cfg.dim, cfg.n_heads * hd)),
            "wk": dense(lk[1], cfg.dim, (cfg.dim, cfg.n_kv_heads * hd)),
            "wv": dense(lk[2], cfg.dim, (cfg.dim, cfg.n_kv_heads * hd)),
            "wo": dense(lk[3], cfg.n_heads * hd, (cfg.n_heads * hd, cfg.dim)),
            # Router in fp32: routing decisions are precision-sensitive.
            "router": (jax.random.normal(lk[4], (cfg.dim, E), jnp.float32)
                       / math.sqrt(cfg.dim)),
            "w_gate": dense(lk[5], cfg.dim, (E, cfg.dim, F)),
            "w_up": dense(lk[6], cfg.dim, (E, cfg.dim, F)),
            "w_down": dense(lk[7], F, (E, F, cfg.dim)),
        })
    return params


def param_specs(cfg: MoEConfig, axis: str = "tp") -> dict:
    layer = {
        "attn_norm": P(), "mlp_norm": P(),
        "wq": P(None, axis), "wk": P(None, axis), "wv": P(None, axis),
        "wo": P(axis, None),
        "router": P(),
        "w_gate": P(axis, None, None),   # EP: expert dim sharded
        "w_up": P(axis, None, None),
        "w_down": P(axis, None, None),
    }
    return {
        "embed": P(), "lm_head": P(), "final_norm": P(),
        "layers": [dict(layer) for _ in range(cfg.n_layers)],
    }


# ---------------------------------------------------------------------------
# MoE FFN (shard level)
# ---------------------------------------------------------------------------


def moe_ffn_shard(h2, layer, cfg: MoEConfig, *, axis, impl, interpret):
    """Routed expert FFN over local tokens h2 [T_loc, D].

    dispatch (AllToAll) → sort received tokens by local expert →
    grouped-GEMM SwiGLU → inverse AllToAll → topk-weighted combine.
    Returns (out [T_loc, D], aux_loss_contribution scalar).
    """
    world = jax.lax.axis_size(axis)
    me = jax.lax.axis_index(axis)
    E = cfg.n_experts
    epr = E // world
    t_loc = h2.shape[0]

    logits = jnp.dot(h2.astype(jnp.float32), layer["router"])
    weights, experts = topk_routing(logits, cfg.topk)

    # Switch-style load-balance aux: E * sum_e f_e * p_e over LOCAL tokens
    # (f = fraction of assignments to e, p = mean router prob of e).  The
    # global aux is the mean over devices of these local-batch values (the
    # standard per-group variant — balancing each device's own dispatch is
    # what bounds EP capacity overflow), not the single-global-batch value.
    probs = jax.nn.softmax(logits, axis=-1)
    frac = (jnp.zeros((E,), jnp.float32)
            .at[experts.reshape(-1)].add(1.0) / (t_loc * cfg.topk))
    aux = E * jnp.sum(frac * jnp.mean(probs, axis=0)) / world

    # zero_undefined: this is the TRAINING path — recv feeds differentiated
    # matmuls, whose weight gradients contract over padding rows too
    # (0-cotangent x NaN-garbage = NaN without the mask).
    recv, recv_expert, _splits, plan, _dropped = ep_dispatch_shard(
        h2.astype(cfg.dtype), experts, axis=axis, n_experts=E,
        max_tokens=cfg.max_tokens, impl=impl, interpret=interpret,
        zero_undefined=True)
    max_tokens = recv.shape[1]  # dispatch owns the None→worst-case rule

    # Local expert compute over the received buffer.  Zero (padding) rows
    # pass through the bias-free FFN as zeros, so steering them to expert 0
    # is harmless; their contributions are masked again at combine.
    T = world * max_tokens
    local_e = jnp.clip(recv_expert.reshape(T, 1) - me * epr, 0, epr - 1)
    splan = sort_align(local_e, epr, cfg.block_m)
    x_sorted = gather_sorted(recv.reshape(T, cfg.dim), splan["dest"],
                             splan["m_pad"])
    y_sorted = moe_ffn_sorted(
        x_sorted, layer["w_gate"], layer["w_up"], layer["w_down"],
        splan["tile_expert"], block_m=cfg.block_m, impl=impl,
        interpret=interpret)
    y = y_sorted[splan["dest"]].reshape(world, max_tokens, cfg.dim)

    out = ep_combine_shard(y, weights, plan, axis=axis, impl=impl,
                           interpret=interpret)
    return out.astype(cfg.dtype), aux


def moe_block_shard(x, layer, cfg: MoEConfig, *, axis, impl, interpret):
    """MoE FFN sub-block with residual: RMSNorm → routed expert FFN.
    x: [S_loc, B, D].  Returns (x', aux contribution).  Shared by the plain
    forward and the pipelined path (models/pp.py)."""
    s_loc, b, _ = x.shape
    h = _rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
    out, aux = moe_ffn_shard(h.reshape(s_loc * b, cfg.dim), layer, cfg,
                             axis=axis, impl=impl, interpret=interpret)
    return x + out.reshape(s_loc, b, cfg.dim), aux


# ---------------------------------------------------------------------------
# Forward / loss (shard level)
# ---------------------------------------------------------------------------


def forward_shard(params, tokens_shard, cfg: MoEConfig, *, axis="tp",
                  impl="auto", interpret=False):
    """Per-device forward.  tokens_shard [S_loc, B] int32, sequence sharded.
    Returns (logits [S_loc, B, vocab] fp32, aux_loss scalar)."""
    lcfg = cfg.as_llama()
    world = jax.lax.axis_size(axis)
    assert cfg.n_heads % world == 0 and cfg.n_kv_heads % world == 0
    assert cfg.n_experts % world == 0

    s_loc, b = tokens_shard.shape
    x = params["embed"][tokens_shard]  # [S_loc, B, D]
    aux_total = jnp.float32(0.0)

    for layer in params["layers"]:
        # --- attention (TP over heads; shared Llama code path) ---
        x = attention_block_shard(x, layer, lcfg, axis=axis, impl=impl,
                                  interpret=interpret)
        # --- MoE FFN (EP over the same axis) ---
        x, aux = moe_block_shard(x, layer, cfg, axis=axis, impl=impl,
                                 interpret=interpret)
        aux_total = aux_total + aux

    x = _rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.dot(x, params["lm_head"], preferred_element_type=jnp.float32)
    return logits, aux_total


def loss_shard(params, tokens_shard, targets_shard, cfg: MoEConfig, *,
               axis="tp", dp_axis=None, impl="auto", interpret=False):
    """Per-device contribution to global mean CE + aux balance loss (psum of
    this over all devices == the global loss; see llama.loss_shard for why
    the psum must stay outside autodiff)."""
    logits, aux = forward_shard(params, tokens_shard, cfg, axis=axis,
                                impl=impl, interpret=interpret)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets_shard[..., None].astype(jnp.int32),
                             axis=-1)[..., 0]
    denom = ll.size * jax.lax.axis_size(axis)
    if dp_axis is not None:
        denom = denom * jax.lax.axis_size(dp_axis)
        # aux from forward_shard is already divided by the EP axis size
        # (per-device contribution); spread it over the dp copies too.
        aux = aux / jax.lax.axis_size(dp_axis)
    return -jnp.sum(ll) / denom + cfg.aux_loss_coef * aux


# ---------------------------------------------------------------------------
# Host-level entries (mirror models/llama.py)
# ---------------------------------------------------------------------------


def make_forward(cfg: MoEConfig, mesh: Mesh, *, axis="tp", dp_axis=None,
                 impl="auto", interpret=False):
    batch_spec = P(axis, dp_axis) if dp_axis else P(axis)
    specs = param_specs(cfg, axis)
    all_axes = (axis,) if dp_axis is None else (axis, dp_axis)

    def fwd_shard(params, tokens):
        logits, aux = forward_shard(params, tokens, cfg, axis=axis,
                                    impl=impl, interpret=interpret)
        # aux is a per-device contribution; the psum (safe here — this
        # entry is not differentiated) reports the global value.
        n_dp = 1 if dp_axis is None else jax.lax.axis_size(dp_axis)
        return logits, jax.lax.psum(aux / n_dp, all_axes)

    fn = jax.shard_map(
        fwd_shard,
        mesh=mesh,
        in_specs=(specs, batch_spec),
        out_specs=(P(axis, dp_axis) if dp_axis else P(axis), P()),
        check_vma=False,
    )
    return jax.jit(fn)


def make_train_step(cfg: MoEConfig, mesh: Mesh, *, axis="tp", dp_axis=None,
                    impl="auto", interpret=False, lr=1e-3):
    """SGD step through attention TP kernels, the AllToAll VJP, and the
    grouped GEMMs.  Same reduction logic as llama.make_train_step: leaves
    whose spec mentions ``axis`` hold complete local grads; replicated
    leaves psum over ``axis``; everything sums over ``dp_axis``."""
    specs = param_specs(cfg, axis)
    batch_spec = P(axis, dp_axis) if dp_axis else P(axis)

    def step_shard(params, tokens, targets):
        local_loss, grads = jax.value_and_grad(loss_shard)(
            params, tokens, targets, cfg, axis=axis, dp_axis=dp_axis,
            impl=impl, interpret=interpret)
        all_axes = (axis,) if dp_axis is None else (axis, dp_axis)
        loss = jax.lax.psum(local_loss, all_axes)

        def _reduce(g, spec):
            sharded_on_axis = any(s == axis for s in spec)
            axes = () if sharded_on_axis else (axis,)
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


def place_params(params, cfg: MoEConfig, mesh: Mesh, axis="tp") -> dict:
    specs = param_specs(cfg, axis)
    return jax.tree.map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)), params, specs)
