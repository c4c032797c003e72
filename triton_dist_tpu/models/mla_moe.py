"""Latent attention + sigmoid-routed expert layers (the DeepSeek-V3 block)
for the serving engine, as ONE CHIP'S SHARE of an expert-parallel
deployment.

The block, per layer: ``x += W_o . MLA(rms(x))``, ``x += FFN(rms(x))``.

* **Latent attention (MLA).**  Queries go through a low-rank pair
  (``wq_a`` -> RMSNorm -> ``wq_b``) to ``n_heads x (nope | rope)``; keys
  and values come from ONE latent row a token, ``[c_kv | k_rope]`` (``wkv_a``
  -> RMSNorm on the ``c_kv`` part, RoPE on the ``k_rope`` part, which every
  head shares).  The cache holds that row and nothing else.  Attention
  runs in the ABSORBED form: ``q~_h = q_nope_h W_UK,h^T`` scores against
  the whole row, the value is the row's ``c_kv`` part, and ``W_UV,h`` is
  applied to the result (``out_proj``) — the expanded K and V (``c_kv
  W_kvb``) are never cached, and formed only for a sparse block's long
  prefill chunk (below).  RoPE is YaRN-scaled (:func:`yarn_inv_freq`).
* **Expert layer.**  ``noaux_tc`` routing: sigmoid scores, a bias that
  enters the SELECTION only, group-limited top-k, weights renormalised
  and scaled; a shared expert beside the routed ones.  The layer is told
  which experts it HOLDS (``experts_held`` from ``expert_offset``): it
  routes over all ``n_experts``, computes its own experts' part for the
  rows routed to them (``moe_utils.sort_align_held`` + the grouped GEMM),
  and leaves the rest out — what the absent experts would add is absent,
  nothing stands in for the other chips.  Leading layers
  (``first_k_dense``) carry a dense SwiGLU MLP instead.

* **Learned sparse attention** (``index_topk > 0``: the ``glm_moe_dsa``
  block).  Beside the latent row a layer caches one INDEX KEY a token
  (``LayerNorm(W_ik x)``, RoPE on its leading columns); a query scores
  every cached token with a few small heads off the same query latent,
  ``I(t, s) = sum_j w[t, j] relu(qI[t, j] . kI[s])``, and attends to the
  ``index_topk`` best only (all of them while fewer are cached).  The
  cache is then TWO planes of different widths a layer, written by the one
  ``write_kv`` seam; the scores are a paged call of their own
  (``dsa_index_scores``), the cut-off is the k-th value by bisection on
  counts (as ``models/sampling.py``), and the latent call walks the row's
  pages with the selection as a mask (:func:`paged_attend`).  A prefill
  chunk of ``PREFILL_EXPAND_MIN`` queries or more is bound by its products,
  not by the cache's bytes, and attends in the EXPANDED form instead
  (:func:`attend_prefix`: the scratch through ``W_UK`` / ``W_UV`` once a
  chunk, flash attention a head under the same selection).

Everything enters the engine's programs through the seams of
``models/generate.py`` (``project`` / ``write_kv`` / ``attend`` /
``out_proj`` / ``ffn``), so the forwards stay one copy each.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import types

import jax
import jax.numpy as jnp
import numpy as np

from triton_dist_tpu.kernels import moe_utils
from triton_dist_tpu.kernels.flash_decode import (
    dsa_index_gap,
    dsa_index_scores,
    mla_decode_paged_shard,
    mla_expanded_prefill,
    mla_kernel_gap,
    mla_prefill_gap,
)
from triton_dist_tpu.kernels.gemm import resolve_impl
from triton_dist_tpu.kernels.group_gemm import group_gemm_live
from triton_dist_tpu.kernels.moe_combine import (
    COMBINE_CALL,
    combine_gap,
    combine_gather,
    combine_live,
    walk_rows,
)
from triton_dist_tpu.models.generate import (
    _chunk_forward,
    _dense_prompt_ffn,
    _prompt_forward,
)
from triton_dist_tpu.models.llama import _rms_norm
from triton_dist_tpu.models.sampling import (
    _from_ordered_bits,
    _largest_threshold,
    _ordered_bits,
)
from triton_dist_tpu.runtime.jit_cache import named
from triton_dist_tpu.runtime.profiling import region

LANES = 128
GATE_UP_CALL, DOWN_CALL = "moe_gate_up", "moe_down"
# A sparse block's prefill chunk of at least this many queries attends in
# the EXPANDED form (:func:`attend_prefix`): under it the expansion of the
# whole scratch, once a chunk, outweighs what the cheaper pairs save (29.4 M
# operations a cached row against 74 k a query saved), and verify rows stay
# on the decode path's kernel.
PREFILL_EXPAND_MIN = 256


class LatentPoolUnsupported(NotImplementedError):
    """A serving feature that has not been carried over to latent (MLA)
    pools was asked for: raised where the engine or generator is built,
    or where the entry point is called — never a quiet fallback."""


# model_type -> the keys its config.json may carry beyond the block's own:
# an indexer's (``index*``), a residual path's (``hc_*`` / ``mhc_*``)
_MODEL_TYPES = {
    "deepseek_v3": frozenset(),
    "glm_moe_dsa": frozenset({"index_n_heads", "index_head_dim",
                              "index_topk", "indexer_rope_interleave"}),
    "xing4_0": frozenset({"hc_mult", "hc_sinkhorn_iters", "hc_eps",
                          "mhc_h_res_clamp_min", "mhc_h_res_clamp_max"}),
}
_EXTRA_STEMS = ("index", "hc_", "mhc_")
# fold_in tag of a layer's residual-path draws: sub-layer s (0: attention,
# 1: MLP / experts) draws from fold_in(layer key, HC_FOLD + s) — the
# sixteen subkeys split() hands a layer are all taken
HC_FOLD = 4096


@dataclasses.dataclass(frozen=True)
class MlaMoeConfig:
    vocab: int                  # rows of the vocabulary held here
    dim: int
    n_layers: int
    n_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    ffn_dim: int                # the dense layers' MLP
    moe_ffn_dim: int            # one expert (routed or shared)
    n_experts: int              # the router's width
    experts_held: int           # routed experts this chip holds ...
    expert_offset: int = 0      # ... ids offset .. offset + held - 1
    n_shared_experts: int = 1
    first_k_dense: int = 1
    n_group: int = 1
    topk_group: int = 1
    top_k: int = 8
    routed_scaling: float = 1.0
    norm_topk_prob: bool = True
    rope_theta: float = 10000.0
    # YaRN: (factor, original_max_position_embeddings, beta_fast,
    # beta_slow, mscale, mscale_all_dim), or None for plain RoPE
    yarn: tuple | None = None
    norm_eps: float = 1e-6
    max_seq: int = 2048
    dtype: object = jnp.float32
    moe_block_m: int = 32       # grouped-GEMM row tile (see routed_experts)
    router: str = "sigmoid_noaux"   # the router's kind (see route)
    rope_interleave: bool = False   # rotary pairs (2i, 2i + 1), else halves
    # learned sparse attention (0 heads / 0 rows: none)
    index_n_heads: int = 0
    index_head_dim: int = 128
    index_topk: int = 0
    index_norm_eps: float = 1e-6    # the index key's LayerNorm
    # the residual path (0: ONE stream, ``x + f(norm(x))``; n > 1: n
    # streams mixed per token by learned maps — kernels/hyper_conn.py)
    hc_mult: int = 0
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: tuple = (-30.0, 30.0)

    # -- what the serving engine reads of a model config -------------------
    @property
    def latent_width(self) -> int:
        """Numbers the cache holds a token a layer."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def head_dim(self) -> int:
        """Width of a cache row AS STORED: the latent row padded to whole
        lane tiles (576 -> 640; see kernels/flash_decode.py)."""
        return -(-self.latent_width // LANES) * LANES

    @property
    def n_kv_heads(self) -> int:
        return 1                # one latent row serves every head

    @property
    def softmax_scale(self) -> float:
        s = (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        if self.yarn is not None:
            factor, _, _, _, _, all_dim = self.yarn
            s *= _yarn_mscale(factor, all_dim) ** 2
        return s

    @property
    def sparse(self) -> bool:
        """Learned sparse attention: an indexer beside every layer's
        latent attention and an index-key plane in its cache."""
        return self.index_topk > 0

    def expands(self, n_queries: int) -> bool:
        """Whether a sparse block's ``n_queries`` of one row attend in the
        EXPANDED form (:func:`attend_prefix`).  Never where a head's value
        is as wide as the latent row: ``out_proj`` tells the two results
        apart by their width."""
        return (self.sparse and n_queries >= PREFILL_EXPAND_MIN
                and self.v_head_dim != self.kv_lora_rank)

    def is_moe_layer(self, li: int) -> bool:
        return li >= self.first_k_dense

    def stream_kw(self) -> dict:
        """What the residual path's two mixes take of the config."""
        return dict(n=self.hc_mult, iters=self.hc_sinkhorn_iters,
                    eps=self.hc_eps, clamp=tuple(self.hc_clamp),
                    norm_eps=self.norm_eps)

    def row_tile(self, rows: int) -> int:
        """The grouped GEMMs' row tile in a program of ``rows`` rows: this
        family's is the one fixed option (``moe_block_m``), whatever the
        program (:func:`row_tile` is the rule that follows the rows)."""
        return self.moe_block_m

    @staticmethod
    def from_hf(c: dict, *, max_seq: int, dtype=jnp.bfloat16,
                experts_total: int | None = None, expert_offset: int = 0,
                **over) -> "MlaMoeConfig":
        """From the keys of a ``deepseek_v3``, a ``glm_moe_dsa`` or a
        ``xing4_0`` ``config.json`` (docs/serving.md lists them).  In a
        share's file ``n_routed_experts`` counts the experts HELD and
        ``vocab_size`` the rows held; ``experts_total`` is the router's
        published width.  An unknown ``model_type``, ``index*`` or
        ``hc_*`` / ``mhc_*`` key is refused by name."""
        kind = c.get("model_type", "deepseek_v3")
        if kind not in _MODEL_TYPES:
            raise ValueError(f"model_type {kind!r}: served are "
                             f"{sorted(_MODEL_TYPES)}")
        unknown = sorted(k for k in c if k.startswith(_EXTRA_STEMS)
                         and k not in _MODEL_TYPES[kind])
        if unknown:
            raise ValueError(
                f"{unknown}: not a key this {kind} block serves (beyond "
                f"the block's own: {sorted(_MODEL_TYPES[kind]) or 'none'})")
        rs = c.get("rope_scaling")
        yarn = None
        if rs:
            if rs.get("rope_type", rs.get("type")) != "yarn":
                raise ValueError(f"rope_scaling {rs!r}: only yarn is served")
            yarn = (float(rs["factor"]),
                    int(rs["original_max_position_embeddings"]),
                    float(rs["beta_fast"]), float(rs["beta_slow"]),
                    float(rs.get("mscale", 1.0)),
                    float(rs.get("mscale_all_dim", 0.0)))
        rp = c.get("rope_parameters") or {}
        if rp.get("rope_type", "default") != "default":
            raise ValueError(f"rope_parameters {rp!r}: only the default "
                             f"(plain) RoPE is served there")
        if kind == "glm_moe_dsa":
            inter = bool(c.get("rope_interleave", False))
            if bool(c.get("indexer_rope_interleave", inter)) != inter:
                raise ValueError("indexer_rope_interleave must equal "
                                 "rope_interleave: one pairing is served")
            over = dict(rope_interleave=inter,
                        index_n_heads=c["index_n_heads"],
                        index_head_dim=c["index_head_dim"],
                        index_topk=c["index_topk"], **over)
        if kind == "xing4_0":
            over = dict(hc_mult=int(c["hc_mult"]),
                        hc_sinkhorn_iters=int(c["hc_sinkhorn_iters"]),
                        hc_eps=float(c["hc_eps"]),
                        hc_clamp=(float(c["mhc_h_res_clamp_min"]),
                                  float(c["mhc_h_res_clamp_max"])), **over)
        for key, want in (("scoring_func", "sigmoid"),
                          ("topk_method", "noaux_tc"),
                          ("hidden_act", "silu")):
            if c.get(key, want) != want:
                raise ValueError(f"{key} {c[key]!r}: only {want!r} is served")
        if c.get("moe_layer_freq", 1) != 1:
            raise ValueError("moe_layer_freq != 1 is not served")
        return MlaMoeConfig(
            vocab=c["vocab_size"], dim=c["hidden_size"],
            n_layers=c["num_hidden_layers"],
            n_heads=c["num_attention_heads"],
            q_lora_rank=c["q_lora_rank"], kv_lora_rank=c["kv_lora_rank"],
            qk_nope_head_dim=c["qk_nope_head_dim"],
            qk_rope_head_dim=c["qk_rope_head_dim"],
            v_head_dim=c["v_head_dim"], ffn_dim=c["intermediate_size"],
            moe_ffn_dim=c["moe_intermediate_size"],
            n_experts=experts_total or c["n_routed_experts"],
            experts_held=c["n_routed_experts"], expert_offset=expert_offset,
            n_shared_experts=c.get("n_shared_experts") or 0,
            first_k_dense=c["first_k_dense_replace"],
            n_group=c["n_group"], topk_group=c["topk_group"],
            top_k=c["num_experts_per_tok"],
            routed_scaling=float(c["routed_scaling_factor"]),
            norm_topk_prob=bool(c["norm_topk_prob"]),
            rope_theta=float(rp.get("rope_theta", c.get("rope_theta"))),
            yarn=yarn,
            norm_eps=float(c["rms_norm_eps"]), max_seq=max_seq,
            dtype=dtype, **over)

    @staticmethod
    def tiny(dtype=jnp.float32, **over) -> "MlaMoeConfig":
        """CPU test size: every mechanism of the block, kernel-legal
        shapes (rank and row a whole number of lane tiles)."""
        kw = dict(vocab=256, dim=128, n_layers=3, n_heads=4,
                  q_lora_rank=64, kv_lora_rank=128, qk_nope_head_dim=32,
                  qk_rope_head_dim=32, v_head_dim=48, ffn_dim=256,
                  moe_ffn_dim=128, n_experts=16, experts_held=4,
                  expert_offset=4, n_shared_experts=1, first_k_dense=1,
                  n_group=4, topk_group=2, top_k=4, routed_scaling=2.5,
                  rope_theta=1e5, yarn=(64.0, 64, 32.0, 1.0, 1.0, 1.0),
                  max_seq=512, dtype=dtype, moe_block_m=8)
        kw.update(over)
        return MlaMoeConfig(**kw)

    @staticmethod
    def tiny_sparse(dtype=jnp.float32, **over) -> "MlaMoeConfig":
        """:meth:`tiny` with an indexer whose ``index_topk`` lies well
        under the test contexts, plain interleaved RoPE."""
        return MlaMoeConfig.tiny(dtype, **{**dict(
            yarn=None, rope_theta=1e4, rope_interleave=True,
            index_n_heads=16, index_head_dim=128, index_topk=48), **over})


# ---------------------------------------------------------------------------
# RoPE (YaRN)
# ---------------------------------------------------------------------------


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg: MlaMoeConfig) -> np.ndarray:
    """:func:`rope_inv_freq` of this block's rotary columns."""
    return rope_inv_freq(cfg.qk_rope_head_dim, cfg.rope_theta, cfg.yarn)


def rope_inv_freq(d: int, theta: float, yarn: tuple | None) -> np.ndarray:
    """Inverse frequencies of the ``d / 2`` rotary pairs: plain RoPE's, or
    YaRN's blend (``yarn``: factor, original context, beta_fast,
    beta_slow, ...) of the interpolated (``/ factor``) and the
    extrapolated ones along a linear ramp between the pairs that turn
    ``beta_fast`` and ``beta_slow`` times over the original context.  The
    ONE copy: the latent block's rotary columns and a dense head's whole
    width (models/swa_moe.py) both come here."""
    extra = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    if yarn is None:
        return extra.astype(np.float32)
    factor, orig, beta_fast, beta_slow = yarn[:4]

    def corr(n_rot):
        return (d * math.log(orig / (n_rot * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return (extra / factor * ramp + extra * (1.0 - ramp)).astype(np.float32)


def yarn_cos_sin_scale(cfg: MlaMoeConfig) -> float:
    if cfg.yarn is None:
        return 1.0
    factor, _, _, _, mscale, all_dim = cfg.yarn
    return _yarn_mscale(factor, mscale) / _yarn_mscale(factor, all_dim)


def _rope(x, pos, inv_freq, scale, interleave=False):
    """x [B, T, H, d] at positions pos [B, T] (or [1, T]).  Pair i is the
    columns (i, i + d/2) (rotate-half) or, ``interleave``, (2i, 2i + 1) as
    ``glm_moe_dsa`` publishes them; the result is laid out by halves
    either way (q and k alike, so every dot product is the published
    one)."""
    ang = pos[..., None].astype(jnp.float32) * inv_freq       # [B, T, d/2]
    cos = (jnp.cos(ang) * scale)[:, :, None, :]
    sin = (jnp.sin(ang) * scale)[:, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = ((xf[..., 0::2], xf[..., 1::2]) if interleave
              else jnp.split(xf, 2, axis=-1))
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

# name -> (subkey index, fan_in, shape) of a layer's matrices; subkeys are
# split(layer_key, 16).  The recipe (normal / sqrt(fan_in), norms 1, the
# router's bias normal / 100, rounded once to the serving dtype) is stated
# by the benchmark's configuration file and drawn again, independently, by
# its reference.  A routed expert's matrices derive from its GLOBAL id
# (fold_in), so the 16 shares of a layer tile the uncut layer.


def _attn_matrices(c: MlaMoeConfig) -> dict:
    H, D = c.n_heads, c.dim
    return {
        "wq_a": (0, D, (D, c.q_lora_rank)),
        "wq_b": (1, c.q_lora_rank,
                 (c.q_lora_rank, H * (c.qk_nope_head_dim
                                      + c.qk_rope_head_dim))),
        "wkv_a": (2, D, (D, c.latent_width)),
        "wkv_b": (3, c.kv_lora_rank,
                  (c.kv_lora_rank, H * (c.qk_nope_head_dim + c.v_head_dim))),
        "wo": (4, H * c.v_head_dim, (H * c.v_head_dim, D)),
    }


def _index_matrices(c: MlaMoeConfig) -> dict:
    """The indexer's: its heads' queries off the query latent, the one
    key a token and the head weights off the layer's input."""
    return {
        "idx_wq": (13, c.q_lora_rank,
                   (c.q_lora_rank, c.index_n_heads * c.index_head_dim)),
        "idx_wk": (14, c.dim, (c.dim, c.index_head_dim)),
        "idx_ww": (15, c.dim, (c.dim, c.index_n_heads)),
    }


def _mlp_matrices(D: int, F: int, base: int) -> dict:
    return {"wgate": (base, D, (D, F)), "wup": (base + 1, D, (D, F)),
            "wdown": (base + 2, F, (F, D))}


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _draw(key, denom, *, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) / denom).astype(dtype)


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _draw_experts(key, ids, denom, *, shape, dtype):
    def one(e):
        return jax.random.normal(jax.random.fold_in(key, e), shape,
                                 jnp.float32) / denom
    return jax.vmap(one)(ids).astype(dtype)


def _stream_maps(c: MlaMoeConfig, key) -> dict:
    """One sub-layer's residual-path maps, float32, in the shapes
    ``kernels/hyper_conn.py`` reads them: ``phi`` drawn at its published
    shape [n D, 2n + n^2] (normal / sqrt(n D)) and stored transposed,
    ``bias`` normal, ``alpha`` and ``gain`` 1 — every map then depends on
    the token."""
    n, D = c.hc_mult, c.dim
    k = 2 * n + n * n
    kp, kb = jax.random.split(key, 2)
    f32 = jnp.float32
    return {"phi_t": _draw(kp, f32(math.sqrt(n * D)), shape=(n * D, k),
                           dtype=f32).T,
            "alpha": jnp.ones((3,), f32),
            "bias": _draw(kb, f32(1.0), shape=(k, 1), dtype=f32),
            "gain": jnp.ones((1, n * D), f32)}


def init_params(cfg: MlaMoeConfig, key) -> dict:
    """Seeded weights, drawn on the default device leaf by leaf.

    ``wkv_b`` is drawn at its published shape and stored split per head
    as the absorbed form uses it: ``w_uk`` [H, nope, rank] (into the
    query) and ``w_uv`` [H, rank, v] (out of the latent result).  Gate
    and up of the routed experts are stored side by side (``w_gate_up``
    [held, D, 2F]): one grouped GEMM serves both."""
    c, dt = cfg, cfg.dtype

    def dense(k, fan_in, shape):
        return _draw(k, jnp.float32(math.sqrt(fan_in)), shape=shape, dtype=dt)

    def mats(lk, table):
        return {n: dense(lk[j], fi, sh) for n, (j, fi, sh) in table.items()}

    keys = jax.random.split(key, 2 + c.n_layers)
    params = {
        "embed": dense(keys[0], 1, (c.vocab, c.dim)),
        "lm_head": dense(keys[1], c.dim, (c.dim, c.vocab)),
        "final_norm": jnp.ones((c.dim,), dt),
        "layers": [],
    }
    H, R = c.n_heads, c.kv_lora_rank
    held = jnp.arange(c.expert_offset, c.expert_offset + c.experts_held)
    for li in range(c.n_layers):
        lk = jax.random.split(keys[2 + li], 16)
        layer = mats(lk, _attn_matrices(c))
        kvb = layer.pop("wkv_b").reshape(
            R, H, c.qk_nope_head_dim + c.v_head_dim)
        layer["w_uk"] = kvb[:, :, :c.qk_nope_head_dim].transpose(1, 2, 0)
        layer["w_uv"] = kvb[:, :, c.qk_nope_head_dim:].transpose(1, 0, 2)
        layer.update(attn_norm=jnp.ones((c.dim,), dt),
                     mlp_norm=jnp.ones((c.dim,), dt),
                     q_norm=jnp.ones((c.q_lora_rank,), dt),
                     kv_norm=jnp.ones((R,), dt))
        if c.sparse:
            layer.update(mats(lk, _index_matrices(c)),
                         idx_k_norm=jnp.ones((c.index_head_dim,), dt),
                         idx_k_bias=jnp.zeros((c.index_head_dim,), dt))
        if not c.is_moe_layer(li):
            layer.update(mats(lk, _mlp_matrices(c.dim, c.ffn_dim, 5)))
        else:
            F = c.moe_ffn_dim
            layer["router"] = dense(lk[8], c.dim, (c.dim, c.n_experts))
            layer["router_bias"] = _draw(lk[9], jnp.float32(100.0),
                                         shape=(c.n_experts,), dtype=dt)
            if c.n_shared_experts:
                layer["shared"] = mats(lk, _mlp_matrices(
                    c.dim, F * c.n_shared_experts, 5))

            def experts(j, fan_in, shape):
                return _draw_experts(lk[j], held,
                                     jnp.float32(math.sqrt(fan_in)),
                                     shape=shape, dtype=dt)

            layer["w_gate_up"] = jnp.concatenate(
                [experts(10, c.dim, (c.dim, F)),
                 experts(11, c.dim, (c.dim, F))], axis=-1)
            layer["w_down"] = experts(12, F, (F, c.dim))
        if c.hc_mult > 1:
            # subkeys of their own: the leaves above are what they are for
            # a seed with streams and without
            for s, name in enumerate(("hc_attn", "hc_mlp")):
                layer[name] = _stream_maps(c, jax.random.fold_in(
                    keys[2 + li], HC_FOLD + s))
        params["layers"].append(layer)
    return params


# ---------------------------------------------------------------------------
# The seams: project / out_proj / ffn
# ---------------------------------------------------------------------------


def project(h, layer, pos, *, cfg: MlaMoeConfig):
    """The attention's front half.  h [B, T, D], pos [B, T] (or [1, T])
    -> (q [B, T, H, W] absorbed ``[q_nope W_UK | q_rope | 0]``, latent
    [B, T, 1, W] ``[rms(c_kv) | rope(k_r) | 0]``, None): there is no V —
    the value is the first ``kv_lora_rank`` columns of the same row.

    With an indexer (``cfg.sparse``) the query is the triple ``(q, qI
    [B, T, Hi, Di], w [B, T, Hi] float32)`` and the third result is the
    token's index key [B, T, 1, Di], the cache's second plane.  A chunk of
    ``PREFILL_EXPAND_MIN`` queries or more carries a fourth member,
    what the expanded form needs of the layer: ``(q [B, T, H, nope + rope]
    un-absorbed, W_UK, W_UV)``."""
    c = cfg
    B, T, D = h.shape
    H, R, dn, dr = (c.n_heads, c.kv_lora_rank, c.qk_nope_head_dim,
                    c.qk_rope_head_dim)
    inv_freq, cs = yarn_inv_freq(c), yarn_cos_sin_scale(c)
    h2 = h.reshape(B * T, D)
    cq = _rms_norm(h2 @ layer["wq_a"], layer["q_norm"], c.norm_eps)
    q = (cq @ layer["wq_b"]).reshape(B, T, H, dn + dr)
    ckv = h2 @ layer["wkv_a"]                          # [B*T, R + dr]
    c_kv = _rms_norm(ckv[:, :R], layer["kv_norm"], c.norm_eps)
    rope = functools.partial(_rope, pos=pos, inv_freq=inv_freq, scale=cs,
                             interleave=c.rope_interleave)
    k_r = rope(ckv[:, R:].reshape(B, T, 1, dr))
    q_r = rope(q[..., dn:])
    q_abs = jnp.einsum("bthn,hnr->bthr", q[..., :dn], layer["w_uk"])
    pad = c.head_dim - c.latent_width
    zq = [jnp.zeros((B, T, H, pad), q.dtype)] if pad else []
    zk = [jnp.zeros((B, T, 1, pad), q.dtype)] if pad else []
    q_abs = jnp.concatenate([q_abs, q_r] + zq, axis=-1)
    latent = jnp.concatenate([c_kv.reshape(B, T, 1, R), k_r] + zk, axis=-1)
    if not c.sparse:
        return q_abs, latent, None
    Hi, Di = c.index_n_heads, c.index_head_dim
    with region("dsa.index"):
        qi = (cq @ layer["idx_wq"]).reshape(B, T, Hi, Di)
        qi = jnp.concatenate([rope(qi[..., :dr]), qi[..., dr:]], axis=-1)
        ki = jnp.dot(h2, layer["idx_wk"], preferred_element_type=jnp.float32)
        mu = jnp.mean(ki, -1, keepdims=True)
        ki = ((ki - mu) * jax.lax.rsqrt(
            jnp.mean(jnp.square(ki - mu), -1, keepdims=True)
            + c.index_norm_eps)
            * layer["idx_k_norm"].astype(jnp.float32)
            + layer["idx_k_bias"].astype(jnp.float32)).reshape(B, T, 1, Di)
        ki = jnp.concatenate([rope(ki[..., :dr]), ki[..., dr:]],
                             axis=-1).astype(h.dtype)
        w = jnp.dot(h2, layer["idx_ww"],
                    preferred_element_type=jnp.float32) * Hi ** -0.5
    query = (q_abs, qi, w.reshape(B, T, Hi))
    if c.expands(T):
        query += ((jnp.concatenate([q[..., :dn], q_r], axis=-1),
                   layer["w_uk"], layer["w_uv"]),)
    return query, latent, ki


def out_proj(o2, layer, *, cfg: MlaMoeConfig):
    """o2 [rows, H * rank] (the latent-space attention result) ->
    [rows, D]: ``W_UV`` per head, then ``W_o``.  The expanded form's
    result [rows, H * v] has been through ``W_UV`` already (the two widths
    differ wherever :meth:`MlaMoeConfig.expands`)."""
    rows = o2.shape[0]
    if (o2.shape[1] == cfg.n_heads * cfg.v_head_dim
            and cfg.v_head_dim != cfg.kv_lora_rank):
        return o2 @ layer["wo"]
    o = jnp.einsum("rhc,hcv->rhv",
                   o2.reshape(rows, cfg.n_heads, cfg.kv_lora_rank),
                   layer["w_uv"])
    return o.reshape(rows, cfg.n_heads * cfg.v_head_dim) @ layer["wo"]


def _top_k(x, k: int):
    """``lax.top_k`` over the last axis for a handful of picks among a few
    hundred columns: ``k`` rounds of argmax-and-mask (ties go to the lower
    index, as there).  ``lax.top_k`` lowers to a full sort on the chip —
    three a layer, 1.7 ms of a 12 ms decode step (chip trace, PR 26)."""
    cols = jnp.arange(x.shape[-1], dtype=jnp.int32)
    vals, ids = [], []
    for _ in range(k):
        i = jnp.argmax(x, axis=-1).astype(jnp.int32)
        vals.append(jnp.max(x, axis=-1))
        ids.append(i)
        x = jnp.where(cols == i[..., None], -jnp.inf, x)
    return jnp.stack(vals, axis=-1), jnp.stack(ids, axis=-1)


def route(h2, layer, cfg):
    """h2 [T, D] -> (ids [T, top_k] int32 over all ``n_experts``, weights
    [T, top_k] float32), scores in float32.  The router's KIND is data of
    the one expert layer (``cfg.router``):

    - ``"sigmoid_noaux"`` (``noaux_tc``): sigmoid scores, a bias that
      moves the choice and never the weight, group-limited top-k, weights
      renormalised and scaled;
    - ``"softmax"``: a softmax over all experts, the ``top_k`` largest
      (ties to the lower id), renormalised over the chosen
      (``norm_topk_prob``): no bias, no groups, no scaling."""
    c = cfg
    T = h2.shape[0]
    scores = jnp.dot(h2, layer["router"], preferred_element_type=jnp.float32)
    if c.router == "softmax":
        p = jax.nn.softmax(scores, axis=-1)
        w, ids = _top_k(p, c.top_k)
        if c.norm_topk_prob:
            w = w / w.sum(-1, keepdims=True)
        return ids.astype(jnp.int32), w
    if c.router != "sigmoid_noaux":
        raise ValueError(f"router {c.router!r}: 'sigmoid_noaux' or 'softmax'")
    s = jax.nn.sigmoid(scores)
    sb = s + layer["router_bias"].astype(jnp.float32)
    per = c.n_experts // c.n_group
    best2 = _top_k(sb.reshape(T, c.n_group, per), min(2, per))[0]
    _, groups = _top_k(best2.sum(-1), c.topk_group)           # [T, tg]
    keep = jnp.any(groups[:, :, None]
                   == jnp.arange(c.n_group)[None, None, :], axis=1)
    sb = jnp.where(jnp.repeat(keep, per, axis=1), sb, -jnp.inf)
    _, ids = _top_k(sb, c.top_k)
    w = jnp.take_along_axis(s, ids, axis=1)
    if c.norm_topk_prob:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return ids.astype(jnp.int32), w * c.routed_scaling


ROW_TILES = (32, 64, 128, 256)


def row_tile(rows: int, top_k: int, n_experts: int) -> int:
    """The grouped GEMMs' row tile that FOLLOWS the rows an expert gets in
    a program of ``rows`` rows under even routing (``rows . top_k /
    n_experts``): the smallest of ``ROW_TILES`` that holds them in one
    piece.  ``group_gemm_live`` streams an expert's slab once a row tile,
    so 256 rows an expert at tile 32 read it 8 times; a tile under 128
    rows costs the MXU what any such tile costs, so 32 is the floor."""
    per_expert = -(-rows * top_k // n_experts)
    return next((t for t in ROW_TILES if t >= per_expert), ROW_TILES[-1])


def combine_form(rows: int, cfg) -> str:
    """How a program of ``rows`` rows sums the held experts' products into
    its tokens, by the rows of the sorted buffer each form reads: the
    ``"gather"`` reads one for every assignment (``rows . top_k``), the
    ``"walk"`` those of the live tiles — under even routing the
    assignments that land here and at most a tile of padding a held
    expert (``moe_combine.walk_rows``).  The walk where it reads fewer: a
    prefill chunk of a layer that holds 1 expert in 8 or 16 (20,480
    against 6,656 at 2,048 tokens, top-10, 32 of 256, tile 128); the
    gather at a decode step (640 against 1,104) and wherever every expert
    is held.  Both are right under any routing: the rule is about speed —
    alone on the chip its side read the faster form, or an equal one (a
    256-row rung), at each of ten shapes of the four configurations, both
    sides (PERF.md §6, PR 46)."""
    c = cfg
    return "walk" if walk_rows(
        rows, c.top_k, c.experts_held, c.n_experts,
        c.row_tile(rows)) < rows * c.top_k else "gather"


def combine_kernel_gap(cfg, rows: int, *, impl, interpret):
    """Why a program of ``rows`` rows whose combine is the walk will NOT
    reach the Mosaic call ``moe_combine`` (it then sums by assignment, as
    the gather does), or None."""
    if combine_form(rows, cfg) != "walk":
        return None
    if resolve_impl(impl, interpret) == "xla":
        return "impl resolves to XLA"
    return combine_gap(cfg.dim, cfg.row_tile(rows))


def combine_forms(cfg, rows: dict, *, impl, interpret) -> dict:
    """program -> the form its routed sum takes at the ``rows`` it carries
    (``summary()["moe"]["combine"]``): :func:`combine_form`, and
    ``"gather"`` too for a walk that cannot reach its kernel — it then
    sums by assignment (:func:`combine_kernel_gap`)."""
    return {prog: "walk" if combine_form(n, cfg) == "walk"
            and not combine_kernel_gap(cfg, n, impl=impl,
                                       interpret=interpret) else "gather"
            for prog, n in rows.items()}


def routed_experts(h2, layer, cfg, *, impl="auto", interpret=False):
    """The held experts' part of the routed sum for rows h2 [T, D] ->
    (float32 [T, D], stats int32 [4]).

    Rows routed here are gathered expert by expert into row tiles of
    ``cfg.row_tile(T)`` rows (the latent family: its fixed ``moe_block_m``
    — a decode step of 64 rows gives a held expert ~2, a prefill chunk
    ~4; a tile of 32 holds an expert's rows in one piece, so its weights
    stream once, and costs the MXU what any tile under 128 rows costs;
    ``models/swa_moe.py``: :func:`row_tile`, read off this program's
    static shapes), then two grouped GEMMs: gate and up side by side, and
    down.  The plan that feeds them (``moe_utils.sort_align_held``, region
    ``moe.align``) is linear in the rows: ``T . top_k`` assignments against
    the held experts, the row tiles and a tile's positions — at a
    2,048-token chunk over 64 held experts 16,384 x (64 + 128 + 256)
    compares and two ``[128, 16,384] @ [16,384, 256]`` products a layer,
    at a decode step of 64 rows 512 x (64 + 78 + 32) and one.  The
    products come back to their tokens (region ``moe.combine``) in the
    form :func:`combine_form` reads off the same static shapes: a row
    gathered for every assignment, or — a prefill chunk of a layer that
    holds few of the experts — the Mosaic call ``moe_combine`` walking the
    live tiles, its plan carrying each row's assignment.  ``stats``:
    assignments routed, those that landed here, pad rows of the live
    tiles, experts hit."""
    c = cfg
    T, D = h2.shape
    F = c.moe_ffn_dim
    block_m = c.row_tile(T)
    walk = combine_form(T, c) == "walk"
    with region("moe.route"):
        ids, w = route(h2, layer, c)
    with region("moe.align"):
        plan = moe_utils.sort_align_held(ids, c.experts_held, block_m,
                                         c.expert_offset, assignment=walk)
    with region("moe.experts"):
        live = plan["valid_rows"][:, None]
        x_sorted = jnp.where(live, h2[plan["src_token"]],
                             jnp.zeros((), h2.dtype))
        gg = functools.partial(group_gemm_live,
                               tile_expert=plan["tile_expert"],
                               n_live=plan["n_live_tiles"],
                               block_m=block_m, impl=impl,
                               interpret=interpret)
        gu = gg(x_sorted, layer["w_gate_up"], name=GATE_UP_CALL)
        # dead tiles are not written: whatever they hold stays out of the sum
        act = jnp.where(live, (jax.nn.silu(gu[:, :F].astype(jnp.float32))
                               .astype(h2.dtype) * gu[:, F:]),
                        jnp.zeros((), h2.dtype))
        y = gg(act, layer["w_down"], name=DOWN_CALL)
    with region("moe.combine"):
        out = (combine_live(y, plan, w, block_m=block_m, impl=impl,
                            interpret=interpret)
               if walk else combine_gather(y, plan, w))
    n_local = jnp.sum(plan["local"].astype(jnp.int32))
    stats = jnp.stack([jnp.int32(T * c.top_k), n_local,
                       plan["n_live_tiles"] * block_m - n_local,
                       jnp.sum((plan["counts"] > 0).astype(jnp.int32))])
    return out, stats


class MoeTally:
    """Trace-time collector of the counts the layers of ONE program
    leave behind: the seams return activations only, so each expert layer
    leaves its ``stats`` here (``rows``), each sparse attention call its
    own (``dsa``, when the block has an indexer), and
    :func:`with_moe_stats` hands their sums out as the program's last
    output — int32 [4], or [8] with the indexer's four behind."""

    def __init__(self, sparse: bool = False):
        self.sparse = sparse
        self.rows: list = []
        self.dsa: list = []

    def clear(self):
        self.rows.clear()
        self.dsa.clear()

    def drain(self):
        out = [sum(got) if got else jnp.zeros((4,), jnp.int32)
               for got in (self.rows, self.dsa)[:1 + self.sparse]]
        self.clear()
        return jnp.concatenate(out)


def with_moe_stats(fwd, tally: MoeTally):
    """``fwd`` with the tally's sums appended to its outputs."""
    @functools.wraps(fwd)
    def run(*args, **kwargs):
        tally.clear()           # a trace that raised may have left some
        out = fwd(*args, **kwargs)
        return (*out, tally.drain())
    return run


def ffn(h2, layer, *, cfg, tally: MoeTally | None = None, impl="auto",
        interpret=False):
    """Dense SwiGLU on the leading layers; shared expert + the held
    routed experts on the rest."""
    if "router" not in layer:
        return _dense_prompt_ffn(h2, layer)
    routed, stats = routed_experts(h2, layer, cfg, impl=impl,
                                   interpret=interpret)
    if tally is not None:
        tally.rows.append(stats)
    if "shared" in layer:
        with region("moe.shared"):
            routed = routed + _dense_prompt_ffn(
                h2, layer["shared"]).astype(jnp.float32)
    return routed.astype(h2.dtype)


# ---------------------------------------------------------------------------
# Attention over latent caches
# ---------------------------------------------------------------------------


def _kth_largest(x, k: int, axes: tuple):
    """The k-th largest value of float32 ``x`` over ``axes`` (kept as
    ones): the largest ``t`` with ``count(x >= t) >= k``, by the sampler's
    bisection over a float32's 32 bits — 32 compare-and-count passes,
    nothing sorted (``models/sampling.py`` has the measurement).  Fewer
    than ``k`` finite members give a value at or under the masked ones'."""
    shape = tuple(1 if a in axes else d for a, d in enumerate(x.shape))
    return _from_ordered_bits(_largest_threshold(
        _ordered_bits(x),
        lambda kept: kept.sum(axes, keepdims=True) >= k, shape))


def _dsa_stats(visible, k: int, rows: bool):
    """int32 [4] of one sparse attention call, ``visible`` the cached
    tokens each query may see: tokens the indexer scored, rows the
    attention read, and (``rows``: decode programs) the queries whose
    context lies past / at or under ``index_topk``."""
    v = visible.astype(jnp.int32).reshape(-1)
    past = jnp.sum((v > k).astype(jnp.int32))
    return jnp.stack([jnp.sum(v), jnp.sum(jnp.minimum(v, k)),
                      past if rows else jnp.int32(0),
                      jnp.sum((v > 0).astype(jnp.int32)) - past if rows
                      else jnp.int32(0)])


def _attend_pages(q, planes, tables, lens, *, cfg: MlaMoeConfig, impl,
                  interpret, q_lens=None):
    """Latent attention of q [B, T, ...] over paged ``planes`` ([N, page,
    W] latent rows; with an indexer q is ``project``'s triple and a
    second plane [N, page, Di] holds the index keys) -> [B, T, H, rank].

    Sparse: the indexer's scores of every visible row (one paged call),
    each query's cut-off at its ``index_topk``-th score, then the latent
    call's page walk with ``score - cut-off`` as its selection mask — the
    same mathematics as reading the selected rows alone, dense bytes.  A
    table that cannot hold more than ``index_topk`` rows takes the dense
    call as it is (every visible row is selected)."""
    kw = dict(rank=cfg.kv_lora_rank, scale=cfg.softmax_scale, q_lens=q_lens,
              impl=impl, interpret=interpret)
    if not cfg.sparse:
        return mla_decode_paged_shard(q, planes[0], tables, lens, **kw)
    q, qi, w = q[:3]
    T = q.shape[1]
    if tables.shape[1] * planes[0].shape[1] <= cfg.index_topk:
        return mla_decode_paged_shard(q, planes[0], tables, lens, **kw)
    with region("dsa.index"):
        scores = dsa_index_scores(qi, w, planes[1], tables, lens,
                                  q_lens=q_lens, impl=impl,
                                  interpret=interpret)
    with region("dsa.select"):
        if T == 1:      # [B, n_pages, page]: one flat row a query
            cut = _kth_largest(scores.reshape(scores.shape[0], -1),
                               cfg.index_topk, (1,))[:, :, None]
        else:           # [B, n_pages, T, page]
            cut = _kth_largest(scores, cfg.index_topk, (1, 3))
        sel = scores - cut
    return mla_decode_paged_shard(q, planes[0], tables, lens, sel=sel, **kw)


def paged_attend(q, pool, tables, lens, *, cfg: MlaMoeConfig, impl,
                 interpret, q_lens=None, tally: MoeTally | None = None):
    """The engine's paged attend over ONE layer's latent pool (planes [N,
    1, page, .]: the latent rows, and the index keys of a sparse block):
    q [B, (T,) H, W] — ``project``'s triple when sparse — -> [B, (T,) H,
    rank]."""
    single = jax.tree.leaves(q)[0].ndim == 3
    if single:
        q = jax.tree.map(lambda t: t[:, None], q)
    T = jax.tree.leaves(q)[0].shape[1]
    if cfg.sparse and tally is not None:
        first = lens - (T if q_lens is None else q_lens)
        tally.dsa.append(_dsa_stats(
            jnp.where(lens[:, None] > 0,
                      first[:, None] + 1 + jnp.arange(T), 0),
            cfg.index_topk, rows=True))
    with region("attn"):    # the paged pair's, opened by the family
        out = _attend_pages(
            q, [p.reshape(p.shape[0], *p.shape[2:]) for p in pool], tables,
            lens, cfg=cfg, impl=impl, interpret=interpret, q_lens=q_lens)
    return out[:, 0] if single else out


def _scratch_block(ext: int) -> int:
    return next(b for b in (128, 64, 32, 16, 8, 4, 2, 1) if ext % b == 0)


def attend_prefix(q, *views_and_len, cfg: MlaMoeConfig, impl, interpret,
                  k_scale=None, v_scale=None, tally: MoeTally | None = None):
    """Chunk attention of ``generate._chunk_forward`` over CONTIGUOUS
    scratch planes [B, 1, S, .] (the latent rows, and the index keys of a
    sparse block; the chunk's rows already written at ``prefix_len``, the
    last positional): the scratch read as pages under an identity table,
    through the same calls in their multi-token form.

    A sparse block's chunk that carries ``project``'s fourth member (and a
    scratch longer than ``index_topk``) attends in the EXPANDED form: the
    scratch's rows through ``W_UK`` / ``W_UV`` once a chunk, then flash
    attention a head over its own keys and values under the indexer's
    selection — 1,024 operations a query-key pair a head where the
    absorbed page walk pays 2,176 (``kernels/flash_decode.py``).  Its
    result is in VALUE space, [B, T, H, v]: ``out_proj`` tells by width."""
    *views, prefix_len = views_and_len
    B, c = jax.tree.leaves(q)[0].shape[:2]
    S = views[0].shape[2]
    page = _scratch_block(S)
    n = S // page
    tables = (jnp.arange(B, dtype=jnp.int32)[:, None] * n
              + jnp.arange(n, dtype=jnp.int32)[None, :])
    lens = jnp.full((B,), c, jnp.int32) + prefix_len
    if cfg.sparse and tally is not None:
        tally.dsa.append(_dsa_stats(
            jnp.broadcast_to(prefix_len + 1 + jnp.arange(c), (B, c)),
            cfg.index_topk, rows=False))
    planes = [v.reshape(B * n, page, v.shape[3]) for v in views]
    if not (cfg.sparse and len(q) == 4 and S > cfg.index_topk):
        return _attend_pages(q, planes, tables, lens, cfg=cfg, impl=impl,
                             interpret=interpret)
    _, qi, w, (q_raw, w_uk, w_uv) = q
    kw = dict(impl=impl, interpret=interpret)
    with region("dsa.index"):
        scores = dsa_index_scores(qi, w, planes[1], tables, lens, **kw)
    with region("dsa.select"):
        sel = scores - _cutoffs(scores, cfg.index_topk)
    H, dk = cfg.n_heads, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    with region("mla.expand"):
        kv = views[0][:, 0] @ _expansion(w_uk, w_uv, cfg.qk_rope_head_dim,
                                         views[0].shape[3])
    return mla_expanded_prefill(
        q_raw.reshape(B, c, H * dk), kv, sel, prefix_len, heads=H,
        d_qk=dk, scale=cfg.softmax_scale, **kw).reshape(B, c, H, -1)


def _expansion(w_uk, w_uv, dr: int, width: int):
    """[width, H * (nope + rope + v)]: what takes a cache row ``[c_kv |
    k_rope | 0]`` to every head's key ``[W_UK c_kv | k_rope]`` beside its
    value ``W_UV c_kv`` in one product."""
    H, dn, R = w_uk.shape
    eye = jnp.broadcast_to(jnp.eye(dr, dtype=w_uk.dtype)[:, None], (dr, H, dr))
    top = jnp.concatenate([w_uk.transpose(2, 0, 1),
                           jnp.zeros((R, H, dr), w_uk.dtype),
                           w_uv.transpose(1, 0, 2)], axis=-1)
    mid = jnp.concatenate([jnp.zeros((dr, H, dn), w_uk.dtype), eye,
                           jnp.zeros((dr, H, w_uv.shape[2]), w_uk.dtype)],
                          axis=-1)
    rest = jnp.zeros((width - R - dr, H, top.shape[2]), w_uk.dtype)
    return jnp.concatenate([top, mid, rest], axis=0).reshape(width, -1)


# The cut-off's 32 passes read the scores 32 times: a block that stays in
# VMEM between them (the chip has 128 MiB) costs a tenth of one that does
# not (PERF.md §6, PR 30: 0.63 ms over 67 MB, 6.2 ms over 134 MB).
CUT_BLOCK_BYTES = 72 * 2 ** 20


def _cutoffs(scores, k: int):
    """Each query's k-th largest score: [B, n_pages, T, page] -> [B, 1, T,
    1], the queries taken ``CUT_BLOCK_BYTES`` of scores at a time."""
    B, n, T, page = scores.shape
    tb = next(t for t in range(T, 0, -1) if T % t == 0
              and (t == 1 or B * n * t * page * 4 <= CUT_BLOCK_BYTES))
    if tb == T:
        return _kth_largest(scores, k, (1, 3))
    blocks = scores.reshape(B, n, T // tb, tb, page).transpose(2, 0, 1, 3, 4)
    cuts = jax.lax.map(lambda s: _kth_largest(s, k, (1, 3)), blocks)
    return cuts.transpose(1, 2, 0, 3, 4).reshape(B, 1, T, 1)


def attend_prompt(q, *rows, cfg: MlaMoeConfig, impl, interpret):
    """Whole-prompt causal attention of ``generate._prompt_forward``:
    q [B, S, H, W] over its own cache rows [B, S, 1, .] a plane."""
    return attend_prefix(q, *(r.transpose(0, 2, 1, 3) for r in rows),
                         jnp.int32(0), cfg=cfg, impl=impl,
                         interpret=interpret)


# ---------------------------------------------------------------------------
# The generator the engine is built over
# ---------------------------------------------------------------------------


class MlaMoeGenerator:
    """What ``ServeEngine`` needs of a model: its config, the latent
    cache's planes, the seam hooks of its block, and the chunked-prefill
    program.  The contiguous-cache decode loop of
    :class:`~triton_dist_tpu.models.generate.Generator` is not provided:
    this family decodes through the engine's paged pools."""

    def __init__(self, cfg: MlaMoeConfig, mesh=None, *, axis: str = "sp",
                 max_seq: int | None = None, impl: str = "auto",
                 interpret: bool = False, kv_dtype=None):
        if kv_dtype is not None:
            raise LatentPoolUnsupported(
                f"kv_dtype={kv_dtype}: latent pools are served in the "
                f"model's dtype only (no int8 latent rows yet"
                + (", nor int8 index keys)" if cfg.sparse else ")"))
        if mesh is not None and math.prod(mesh.shape.values()) != 1:
            raise LatentPoolUnsupported(
                "latent pools are served on one chip: no sequence- or "
                "head-sharded latent cache yet"
                + (", and no selection over a sharded index-key plane"
                   if cfg.sparse else ""))
        self.cfg, self.mesh, self.axis = cfg, mesh, axis
        self.max_seq = max_seq or cfg.max_seq
        self.attn = types.SimpleNamespace(
            world=1, quantized=False,
            ctx=types.SimpleNamespace(impl=impl, interpret=interpret))
        self.tally = MoeTally(cfg.sparse)
        kw = dict(cfg=cfg, impl=impl, interpret=interpret)
        self._hooks = {
            "project": functools.partial(project, cfg=cfg),
            "out_proj": functools.partial(out_proj, cfg=cfg),
            "ffn": functools.partial(ffn, tally=self.tally, **kw),
        }
        if cfg.hc_mult > 1:
            # the residual path's two mixes, imported where they are used:
            # a config of one stream hands the layer loop no ``streams``
            # and pays nothing for the module
            from triton_dist_tpu.kernels import hyper_conn

            self._hooks["streams"] = hyper_conn.mixes(
                **cfg.stream_kw(), impl=impl, interpret=interpret)
        self._chunk_jit = jax.jit(
            named(self.wrap_program(functools.partial(
                _chunk_forward, cfg=cfg, **self._hooks,
                attend=functools.partial(attend_prefix, tally=self.tally,
                                         **kw))),
                "prefill_chunk"),
            static_argnames=("quantized", "extent"), donate_argnums=(2,))
        self._prompt_jit = jax.jit(self.wrap_program(functools.partial(
            _prompt_forward, cfg=cfg, **self._hooks,
            attend=functools.partial(attend_prompt, **kw))))

    # -- the engine's view --------------------------------------------------

    latent = True   # the engine's name for this family's pools

    @property
    def kv_planes(self) -> list:
        """(heads, width) of each plane of a layer's cache: one latent
        plane, where the dense family has a K and a V plane — and, with
        an indexer, the index keys' plane of ITS width beside it."""
        c = self.cfg
        return [(1, c.head_dim)] + [(1, c.index_head_dim)] * c.sparse

    def serve_hooks(self) -> dict:
        """Keyword seams for the engine's paged forwards."""
        ctx = self.attn.ctx
        return dict(self._hooks, paged_attend=functools.partial(
            paged_attend, cfg=self.cfg, impl=ctx.impl,
            interpret=ctx.interpret, tally=self.tally))

    def wrap_program(self, fwd):
        return with_moe_stats(fwd, self.tally)

    def moe_combine_forms(self, rows: dict) -> dict:
        """:func:`combine_forms` under this generator's dispatch."""
        ctx = self.attn.ctx
        return combine_forms(self.cfg, rows, impl=ctx.impl,
                             interpret=ctx.interpret)

    def stream_rows(self, rows: dict) -> dict:
        """``summary()["hc"]`` of a block with residual streams ({} for
        one stream): the streams, the sub-layers a row's mixes run in, the
        rows each program carries through them (``rows``: program ->
        rows), how the two calls are blocked there (``hyper_conn.blocking``;
        {} where they run as XLA) and, by program, why its rows do not
        reach them (``gaps``: the engine files these with its
        ``kernel_gaps``)."""
        c, ctx = self.cfg, self.attn.ctx
        if c.hc_mult <= 1:
            return {}
        from triton_dist_tpu.kernels import hyper_conn

        on_chip = resolve_impl(ctx.impl, ctx.interpret) != "xla"
        item = jnp.dtype(c.dtype).itemsize
        # (the interpreter tiles nothing: no shape misses the calls there)
        gaps = {prog: hyper_conn.hc_gap(n, c.hc_mult, c.dim, item)
                for prog, n in rows.items()} \
            if on_chip and not ctx.interpret else {}
        return {"streams": c.hc_mult, "sublayers": 2 * c.n_layers,
                "rows": dict(rows),
                "blocking": {prog: hyper_conn.blocking(
                    n, c.hc_mult, c.dim, item) if on_chip else {}
                    for prog, n in rows.items()},
                "gaps": {hyper_conn.gap_key(prog): why
                         for prog, why in gaps.items() if why}}

    def kernel_gaps(self, *, page_size: int, **_prefill_geometry) -> dict:
        """Attention paths that will NOT reach the latent Pallas kernel
        (``generate.attention_kernel_gaps`` for this family): decode and
        the absorbed prefill chunk share one kernel and one answer; a
        sparse block's EXPANDED chunk (``prefill_chunk`` queries over each
        rung of ``ladder``) has a call and an answer of its own — and so
        has the chunk's expert combine (:func:`combine_kernel_gap`), and
        the residual streams' two mixes at the chunk's rows
        (``hyper_conn.hc_gap``; :meth:`stream_rows` says it of the decode
        programs' rows, which this call is not told)."""
        ctx, c = self.attn.ctx, self.cfg
        chunk = _prefill_geometry.get("prefill_chunk")
        why = chunk and combine_kernel_gap(c, chunk, impl=ctx.impl,
                                           interpret=ctx.interpret)
        gaps = {COMBINE_CALL: why} if why else {}
        if chunk:
            gaps.update(self.stream_rows({"prefill_chunk": chunk}).get(
                "gaps", {}))
        if resolve_impl(ctx.impl, ctx.interpret) == "xla":
            why = ("impl='xla' was asked for" if ctx.impl == "xla" else
                   "impl='auto' resolves to XLA off a TPU (no interpreter)")
            return {"paged_decode": why, "prefill_chunk": why, **gaps}
        gap = None if ctx.interpret else (
            mla_kernel_gap(page_size, c.kv_lora_rank,
                           c.head_dim - c.kv_lora_rank)
            or (c.sparse and dsa_index_gap(page_size, c.index_head_dim))
            or None)
        if gap is not None:
            gaps.update(paged_decode=gap, prefill_chunk=gap)
        if gap is None and not ctx.interpret and chunk and c.expands(chunk):
            for ext in _prefill_geometry.get("ladder") or ():
                why = ext > c.index_topk and mla_prefill_gap(
                    chunk, ext, c.qk_nope_head_dim + c.qk_rope_head_dim,
                    c.v_head_dim, _scratch_block(ext))
                if why:
                    gaps["prefill_chunk"] = why
        return gaps

    def forward_logits(self, params, tokens):
        """Logits [B, S, V] of whole prompts in one pass (no cache kept):
        the absorbed forward the tests hold against the reference."""
        _, logits, _ = self._prompt_jit(params, tokens)
        return logits
