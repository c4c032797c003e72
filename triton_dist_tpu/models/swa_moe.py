"""Grouped-query attention whose KIND differs by layer — sliding-window
layers beside full ones, a RoPE a kind — over routed small experts (the
``mellum`` block, and the ``laguna`` block that grows out of it), for the
serving engine.

The block, per layer ``li`` of kind ``layer_types[li]``: ``x += W_o .
GQA(rms(x))``, ``x += MoE(rms(x))``.

* **Attention.**  Dense q / k / v products (``head_dim`` is a key of its
  own, not ``hidden_size / heads``), an RMSNorm per head on q and on k
  before RoPE, RoPE over the whole head (rotate-half), then causal GQA.  A
  ``sliding_attention`` layer sees the last ``sliding_window`` positions
  (key ``j`` for query ``i`` iff ``i - j < window``) under plain RoPE; a
  ``full_attention`` layer sees everything under YaRN (the blend of
  ``mla_moe.rope_inv_freq``, cos and sin times ``attention_factor``).  The
  kind reaches every seam as a static (:class:`generate.LayerKind`):
  ``project`` picks the RoPE, the attend pair the window and the paged
  call's trace name (``gqa_paged_window`` / ``gqa_paged_full``).
* **Cache groups.**  Layers fall into groups by kind, and a group has its
  own block table and its own pool geometry (serve/block_manager.py
  ``KvGroups``): the full group grows with the context, the window group
  holds the pages a window layer can still see.  ``LayerKind.group`` says
  which a layer reads.
* **Expert layer.**  The one the latent family runs
  (``mla_moe.routed_experts``: ``sort_align_held`` + ``group_gemm_live``,
  ``MoeTally``), its router a plain softmax top-k, renormalised — no bias,
  no groups, no scaling, no shared expert, no leading dense layer — and
  its row tile read off each program's rows (``mla_moe.row_tile``).  It is
  told which experts it holds (``experts_held`` from ``expert_offset``).
* **What ``laguna`` adds** (every default of the config is ``mellum``'s,
  whose programs are the ones they were).  QUERY heads that differ by
  layer over the same KV heads (``heads_by_layer``: ``wq`` / ``wo`` and the
  gate are a layer's own width, and ``project`` reads the width off
  ``wq``; the cache planes, so the groups, do not change).  A per-head
  OUTPUT GATE: ``sigmoid(h . wg)`` of the layer's normed input, a number a
  head a token, times the attention output before ``wo`` — the one step
  the project / write / attend / out_proj quartet cannot say, since
  ``out_proj`` never sees ``h``: a gated model hands ``_layer_stack`` a
  :func:`mixer` (the seam ``models/ssm_yoco.py`` brought) that runs the
  quartet with the gate's two halves under the region ``attn.gate``.  A
  theta and a rotary WIDTH a kind (``rope_theta_window``, ``rotary``: the
  first lanes of a head rotate, the rest pass).  Leading dense MLP layers,
  a shared expert and the ``sigmoid_noaux`` router at one group with
  ``routed_scaling`` — ``mla_moe.ffn`` and ``mla_moe.route`` as the latent
  family runs them, fed by this family's parameters.

Everything enters the engine's programs through the seams of
``models/generate.py``; there is no layer loop and no forward here.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import types

import jax
import jax.numpy as jnp

from triton_dist_tpu.models import mla_moe
from triton_dist_tpu.models.generate import (
    LayerKind,
    _attend_prefix,
    _attend_prompt,
    _chunk_forward,
    _dense_out_proj,
    _prompt_forward,
    attention_kernel_gaps,
    paged_attend,
)
from triton_dist_tpu.models.llama import _rms_norm
from triton_dist_tpu.runtime.jit_cache import named
from triton_dist_tpu.runtime.profiling import region

# config.json's names of the layer kinds -> LayerKind.attn
ATTN_KINDS = {"full_attention": "full", "sliding_attention": "window"}


@dataclasses.dataclass(frozen=True)
class SwaMoeConfig:
    vocab: int
    dim: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int               # a key of its own: not dim / n_heads
    moe_ffn_dim: int            # one expert
    n_experts: int              # the router's width
    experts_held: int           # routed experts this chip holds ...
    expert_offset: int = 0      # ... ids offset .. offset + held - 1
    top_k: int = 8
    norm_topk_prob: bool = True
    layer_types: tuple = ()     # "window" | "full", one a layer
    sliding_window: int = 0
    rope_theta: float = 500000.0
    # the full layers' YaRN: (factor, original_max_position_embeddings,
    # beta_fast, beta_slow, attention_factor), or None for plain RoPE;
    # window layers always rotate plainly
    yarn: tuple | None = None
    norm_eps: float = 1e-6
    max_seq: int = 2048
    dtype: object = jnp.float32
    router: str = "softmax"     # mla_moe.route's kind
    attn_soft_cap: float = 0.0
    # -- what the ``laguna`` block adds; each default is ``mellum``'s ------
    heads_by_layer: tuple = ()  # query heads a layer; (): n_heads for all
    gated: bool = False         # per-head sigmoid gate on the output
    rope_theta_window: float | None = None  # None: rope_theta on both kinds
    rotary: tuple = (1.0, 1.0)  # (full, window): the share of a head's
    #                             lanes that rotate, from lane 0
    ffn_dim: int = 0            # the leading dense layers' MLP
    first_k_dense: int = 0
    shared_ffn_dim: int = 0     # the shared expert (0: none)
    routed_scaling: float = 1.0     # route's sigmoid_noaux scales by it
    # ... and reads its group stage off the config: ONE group in this
    # family (constants of the class, not fields)
    n_group = 1
    topk_group = 1

    def __post_init__(self):
        bad = sorted(set(self.layer_types) - set(ATTN_KINDS.values()))
        if bad or len(self.layer_types) != self.n_layers:
            raise ValueError(
                f"layer_types {self.layer_types}: one of "
                f"{sorted(ATTN_KINDS.values())} for each of the "
                f"{self.n_layers} layers")
        if "window" in self.layer_types and self.sliding_window < 1:
            raise ValueError("window layers need sliding_window >= 1")
        if self.heads_by_layer and len(self.heads_by_layer) != self.n_layers:
            raise ValueError(
                f"heads_by_layer {self.heads_by_layer}: one count for each "
                f"of the {self.n_layers} layers")
        bad = sorted({h for h in self.heads_by_layer or (self.n_heads,)
                      if h < 1 or h % self.n_kv_heads})
        if bad:
            raise ValueError(f"query heads {bad}: not a multiple of the "
                             f"{self.n_kv_heads} KV heads")
        if self.first_k_dense and not self.ffn_dim:
            raise ValueError("leading dense layers need ffn_dim")

    # -- the layers' kinds and the cache groups they fall into -------------
    @property
    def group_names(self) -> tuple:
        """The cache groups, full first: a model of one kind has one."""
        return tuple(k for k in ("full", "window") if k in self.layer_types)

    @property
    def kinds(self) -> tuple:
        """One :class:`LayerKind` a layer."""
        groups = self.group_names
        return tuple(LayerKind(
            attn=t, window=self.sliding_window if t == "window" else 0,
            group=groups.index(t)) for t in self.layer_types)

    def heads(self, li: int) -> int:
        """Query heads of layer ``li``."""
        return self.heads_by_layer[li] if self.heads_by_layer \
            else self.n_heads

    @property
    def heads_by_kind(self) -> dict:
        """{kind: the query heads of its layers} — one count a kind, or
        the counts in layer order where a kind's layers differ."""
        out = {}
        for li, t in enumerate(self.layer_types):
            out.setdefault(t, []).append(self.heads(li))
        return {t: h[0] if len(set(h)) == 1 else tuple(h)
                for t, h in out.items()}

    def is_moe_layer(self, li: int) -> bool:
        return li >= self.first_k_dense

    def rope(self, attn: str) -> tuple:
        """(inverse frequencies, cos / sin factor) of a layer kind: over
        the lanes that rotate (``rotary``: the whole head, or its first
        share), at the kind's own theta."""
        full = attn == "full"
        yarn = self.yarn if full else None
        theta = self.rope_theta if full or self.rope_theta_window is None \
            else self.rope_theta_window
        lanes = int(self.head_dim * self.rotary[0 if full else 1])
        return (mla_moe.rope_inv_freq(lanes, theta, yarn),
                1.0 if yarn is None else float(yarn[4]))

    def row_tile(self, rows: int) -> int:
        """The grouped GEMMs' row tile follows the rows an expert gets in
        THIS program (8 in a 64-row decode step, 256 in a 2,048-token
        chunk at 64 experts, top-8): ``mla_moe.row_tile``."""
        return mla_moe.row_tile(rows, self.top_k, self.n_experts)

    @staticmethod
    def from_hf(c: dict, *, max_seq: int, dtype=jnp.bfloat16,
                experts_total: int | None = None, expert_offset: int = 0,
                **over) -> "SwaMoeConfig":
        """From the keys of a ``mellum`` or a ``laguna`` ``config.json``
        (docs/serving.md lists them).  In a share's file ``num_experts``
        counts the experts HELD and ``vocab_size`` the rows held;
        ``experts_total`` is the router's published width.  An unknown
        ``model_type``, an unknown layer kind, a ``dense`` entry in
        ``mlp_layer_types`` where the block has none (``mellum``: anywhere;
        ``laguna``: past layer 0) and a RoPE type that is not served are
        refused by name — and, of a ``laguna`` file, every key that is
        neither the model's (:data:`LAGUNA_KEYS`), nor a ``config.json``'s
        housekeeping, nor a deployment file's (:data:`FILE_KEYS`)."""
        kind = c.get("model_type")
        if kind not in ("mellum", "laguna"):
            raise ValueError(f"model_type {kind!r}: served here are "
                             f"'mellum' and 'laguna'")
        unknown = sorted(set(c["layer_types"]) - set(ATTN_KINDS))
        if unknown:
            raise ValueError(f"layer_types {unknown}: served are "
                             f"{sorted(ATTN_KINDS)}")
        n_layers = c["num_hidden_layers"]
        mlp = c.get("mlp_layer_types") or ["sparse"] * len(c["layer_types"])
        if len(c["layer_types"]) != n_layers or len(mlp) != n_layers:
            raise ValueError("layer_types / mlp_layer_types must have "
                             "num_hidden_layers entries")
        for key, want in (("hidden_act", "silu"), ("attention_bias", False)):
            if c.get(key, want) != want:
                raise ValueError(f"{key} {c[key]!r}: only {want!r} is served")
        if "sliding_attention" in c["layer_types"] \
                and not c.get("use_sliding_window", True):
            raise ValueError("sliding_attention layers with "
                             "use_sliding_window false")
        rp = c["rope_parameters"]
        full = rp["full_attention"]
        sliding = rp.get("sliding_attention", {"rope_theta": full["rope_theta"]})
        if sliding.get("rope_type", "default") != "default":
            raise ValueError(f"rope_parameters.sliding_attention {sliding!r}: "
                             f"only plain RoPE is served on window layers")
        yarn = None
        if full.get("rope_type", "default") == "yarn":
            yarn = (float(full["factor"]),
                    int(full["original_max_position_embeddings"]),
                    float(full["beta_fast"]), float(full["beta_slow"]),
                    float(full.get("attention_factor")
                          or 0.1 * math.log(float(full["factor"])) + 1.0))
        elif full.get("rope_type", "default") != "default":
            raise ValueError(f"rope_parameters.full_attention {full!r}: "
                             f"served are 'default' and 'yarn'")
        if kind == "mellum":
            if set(mlp) != {"sparse"}:
                raise ValueError(
                    f"mlp_layer_types {sorted(set(mlp) - {'sparse'})}: every "
                    f"layer's MLP is the expert layer here ('sparse'); no "
                    f"dense MLP is served in this block")
            if float(sliding["rope_theta"]) != float(full["rope_theta"]):
                raise ValueError("one rope_theta for both layer kinds is "
                                 "served in a mellum block")
        else:
            over = dict(_laguna_keys(c, mlp, full, sliding), **over)
        return SwaMoeConfig(
            vocab=c["vocab_size"], dim=c["hidden_size"], n_layers=n_layers,
            n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            moe_ffn_dim=c["moe_intermediate_size"],
            n_experts=experts_total or c["num_experts"],
            experts_held=c["num_experts"], expert_offset=expert_offset,
            top_k=c["num_experts_per_tok"],
            norm_topk_prob=bool(c["norm_topk_prob"]),
            layer_types=tuple(ATTN_KINDS[t] for t in c["layer_types"]),
            sliding_window=int(c.get("sliding_window") or 0),
            rope_theta=float(full["rope_theta"]), yarn=yarn,
            norm_eps=float(c["rms_norm_eps"]), max_seq=max_seq, dtype=dtype,
            **over)

    @staticmethod
    def tiny(dtype=jnp.float32, **over) -> "SwaMoeConfig":
        """CPU test size: periods of (window, window, window, full) over
        ``n_layers`` (8: two of them) unless ``layer_types`` is given,
        window 16, 8 experts top-2, kernel-legal head and expert widths."""
        kw = dict(vocab=256, dim=128, n_layers=8, n_heads=4, n_kv_heads=2,
                  head_dim=128, moe_ffn_dim=128, n_experts=8, experts_held=8,
                  top_k=2, sliding_window=16, rope_theta=1e4,
                  yarn=(4.0, 32, 32.0, 1.0, 1.1386), max_seq=256, dtype=dtype)
        kw.update(over)
        period = ("window",) * 3 + ("full",)
        kw.setdefault("layer_types", tuple(
            period[i % 4] for i in range(kw["n_layers"])))
        return SwaMoeConfig(**kw)

    @staticmethod
    def tiny_laguna(dtype=jnp.float32, **over) -> "SwaMoeConfig":
        """CPU test size of the ``laguna`` block: F S S S F (5 layers: the
        dense lead layer + a period), 4 query heads on full layers and 6 on
        window layers over 2 KV heads (3 : 2, as 72 : 48), a gate, half the
        head rotary on full layers under YaRN and a second theta on window
        layers, a shared expert, 4 of 8 experts held (ids 4-7), top-3 by
        biased sigmoid scores."""
        types = ("full",) + ("window",) * 3 + ("full",)
        kw = dict(n_layers=5, layer_types=types,
                  heads_by_layer=tuple(4 if t == "full" else 6
                                       for t in types),
                  gated=True, rope_theta=5e5, rope_theta_window=1e4,
                  rotary=(0.5, 1.0), ffn_dim=256, first_k_dense=1,
                  shared_ffn_dim=128, experts_held=4, expert_offset=4,
                  top_k=3, router="sigmoid_noaux", routed_scaling=2.5)
        kw.update(over)
        return SwaMoeConfig.tiny(dtype, **kw)


# What a ``laguna`` config.json may carry: the keys ``from_hf`` reads ...
LAGUNA_KEYS = frozenset({
    "model_type", "vocab_size", "hidden_size", "intermediate_size",
    "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
    "head_dim", "max_position_embeddings", "attention_bias", "hidden_act",
    "rms_norm_eps", "num_experts", "num_experts_per_tok",
    "moe_intermediate_size", "shared_expert_intermediate_size",
    "norm_topk_prob", "decoder_sparse_step", "mlp_only_layers",
    "tie_word_embeddings", "gating", "gating_types", "sliding_window",
    "use_sliding_window", "rope_parameters", "layer_types",
    "mlp_layer_types", "moe_apply_router_weight_on_input",
    "moe_routed_scaling_factor", "num_attention_heads_per_layer",
    "moe_router_logit_softcapping"})
# ... the housekeeping every config.json has, which says nothing of shape ...
_INERT_KEYS = frozenset({
    "architectures", "torch_dtype", "dtype", "transformers_version",
    "bos_token_id", "eos_token_id", "pad_token_id", "initializer_range",
    "use_cache", "attention_dropout"})
# ... and what a deployment's file states beside them (docs/serving.md "The
# share keys"): ``share`` is read by the caller (``experts_total``,
# ``expert_offset``), the rest is the file's account of itself.
FILE_KEYS = frozenset({
    "name", "source", "reduced", "deployment", "share", "assumed", "not_run",
    "weights", "builder", "reference", "chips", "engine", "engine_derived",
    "engine_moved", "kv_bytes_per_token", "correct"})


def _laguna_keys(c: dict, mlp: list, full: dict, sliding: dict) -> dict:
    """The ``laguna`` keys beyond ``mellum``'s -> config fields; every
    unknown key and unserved value refused by name."""
    unknown = sorted(set(c) - LAGUNA_KEYS - _INERT_KEYS - FILE_KEYS)
    if unknown:
        raise ValueError(f"{unknown}: not a key of a laguna config.json "
                         f"this block serves (docs/serving.md lists them)")
    n_layers, kv = c["num_hidden_layers"], c["num_key_value_heads"]
    for key, want in (("gating", "per-head"), ("tie_word_embeddings", False),
                      ("moe_apply_router_weight_on_input", False),
                      ("moe_router_logit_softcapping", 0),
                      ("decoder_sparse_step", 1)):
        if c.get(key, want) != want:
            raise ValueError(f"{key} {c[key]!r}: only {want!r} is served")
    gates = c.get("gating_types") or ["per_head"] * n_layers
    if len(gates) != n_layers or set(gates) != {"per_head"}:
        raise ValueError(f"gating_types {sorted(set(gates))} x {len(gates)}:"
                         f" 'per_head' for each of the {n_layers} layers")
    heads = c.get("num_attention_heads_per_layer") \
        or [c["num_attention_heads"]] * n_layers
    if len(heads) != n_layers:
        raise ValueError(
            f"num_attention_heads_per_layer has {len(heads)} entries for "
            f"{n_layers} layers")
    if any(h % kv for h in heads):
        raise ValueError(
            f"num_attention_heads_per_layer {sorted(set(heads))}: every "
            f"count must be a multiple of num_key_value_heads ({kv})")
    dense = [li for li, t in enumerate(mlp) if t == "dense"]
    if set(mlp) - {"dense", "sparse"} or dense not in ([], [0]):
        raise ValueError(
            f"mlp_layer_types: 'dense' at layers {dense}; served is a "
            f"dense MLP at layer 0 and 'sparse' on every other layer")
    if sorted(c.get("mlp_only_layers", dense)) != dense:
        raise ValueError(f"mlp_only_layers {c['mlp_only_layers']} against "
                         f"'dense' at layers {dense} of mlp_layer_types")
    return dict(
        heads_by_layer=tuple(int(h) for h in heads), gated=True,
        rope_theta_window=float(sliding["rope_theta"]),
        rotary=(float(full.get("partial_rotary_factor", 1.0)),
                float(sliding.get("partial_rotary_factor", 1.0))),
        ffn_dim=c["intermediate_size"] if dense else 0,
        first_k_dense=len(dense),
        shared_ffn_dim=int(c.get("shared_expert_intermediate_size") or 0),
        router="sigmoid_noaux",
        routed_scaling=float(c.get("moe_routed_scaling_factor", 1.0)))


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

# name -> (subkey index, fan_in, shape) of a layer's attention matrices;
# subkeys are split(layer_key, 16): the gate's is 4, a dense or shared MLP's
# 5-7, the router's 8, its bias's 9 and the experts' 10-12 (a routed
# expert's matrices derive from its GLOBAL id), as in models/mla_moe.py.
# The recipe (normal / sqrt(fan_in), norms 1, the router's bias normal /
# 100, rounded once to the serving dtype) is stated by the benchmark's
# configuration file and drawn again, independently, by its reference.


def _attn_matrices(c: SwaMoeConfig, li: int) -> dict:
    D, q, kv = c.dim, c.heads(li) * c.head_dim, c.n_kv_heads * c.head_dim
    mats = {"wq": (0, D, (D, q)), "wk": (1, D, (D, kv)),
            "wv": (2, D, (D, kv)), "wo": (3, q, (q, D))}
    if c.gated:
        mats["wg"] = (4, D, (D, c.heads(li)))
    return mats


def init_params(cfg: SwaMoeConfig, key) -> dict:
    """Seeded weights, drawn on the default device leaf by leaf.  Gate and
    up of the experts are stored side by side (``w_gate_up`` [held, D,
    2F]): one grouped GEMM serves both.  A leading dense layer holds an MLP
    (``wgate`` / ``wup`` / ``wdown``) and no router; an expert layer of a
    block with a shared expert holds it under ``shared``."""
    c, dt = cfg, cfg.dtype

    def dense(k, fan_in, shape):
        return mla_moe._draw(k, jnp.float32(math.sqrt(fan_in)), shape=shape,
                             dtype=dt)

    def mats(lk, table):
        return {n: dense(lk[j], fi, sh) for n, (j, fi, sh) in table.items()}

    keys = jax.random.split(key, 2 + c.n_layers)
    params = {
        "embed": dense(keys[0], 1, (c.vocab, c.dim)),
        "lm_head": dense(keys[1], c.dim, (c.dim, c.vocab)),
        "final_norm": jnp.ones((c.dim,), dt),
        "layers": [],
    }
    held = jnp.arange(c.expert_offset, c.expert_offset + c.experts_held)
    F = c.moe_ffn_dim
    for li in range(c.n_layers):
        lk = jax.random.split(keys[2 + li], 16)
        layer = mats(lk, _attn_matrices(c, li))
        layer.update(attn_norm=jnp.ones((c.dim,), dt),
                     mlp_norm=jnp.ones((c.dim,), dt),
                     q_norm=jnp.ones((c.head_dim,), dt),
                     k_norm=jnp.ones((c.head_dim,), dt))
        if not c.is_moe_layer(li):
            layer.update(mats(lk, mla_moe._mlp_matrices(c.dim, c.ffn_dim, 5)))
            params["layers"].append(layer)
            continue
        layer["router"] = dense(lk[8], c.dim, (c.dim, c.n_experts))
        if c.router == "sigmoid_noaux":
            layer["router_bias"] = mla_moe._draw(
                lk[9], jnp.float32(100.0), shape=(c.n_experts,), dtype=dt)
        if c.shared_ffn_dim:
            layer["shared"] = mats(lk, mla_moe._mlp_matrices(
                c.dim, c.shared_ffn_dim, 5))

        def experts(j, fan_in, shape):
            return mla_moe._draw_experts(
                lk[j], held, jnp.float32(math.sqrt(fan_in)), shape=shape,
                dtype=dt)

        layer["w_gate_up"] = jnp.concatenate(
            [experts(10, c.dim, (c.dim, F)), experts(11, c.dim, (c.dim, F))],
            axis=-1)
        layer["w_down"] = experts(12, F, (F, c.dim))
        params["layers"].append(layer)
    return params


# ---------------------------------------------------------------------------
# The seams this family brings: project, and a gated block's mixer (the rest
# are the dense family's attention and the latent family's expert layer, told
# the layer's kind)
# ---------------------------------------------------------------------------


def _rope_lanes(x, pos, inv_freq, scale):
    """RoPE over the first ``2 . len(inv_freq)`` lanes of every head
    (rotate-half within them), the lanes behind them as they are."""
    lanes = 2 * inv_freq.shape[0]
    if lanes == x.shape[-1]:
        return mla_moe._rope(x, pos=pos, inv_freq=inv_freq, scale=scale)
    return jnp.concatenate(
        [mla_moe._rope(x[..., :lanes], pos=pos, inv_freq=inv_freq,
                       scale=scale), x[..., lanes:]], axis=-1)


def project(h, layer, pos, *, cfg: SwaMoeConfig, kind: LayerKind):
    """``wq / wk / wv``, an RMSNorm a head on q and k, RoPE of the layer's
    kind: h [B, T, D] -> q [B, T, Hq, hd], k and v [B, T, Hkv, hd].  ``Hq``
    is the LAYER's (``cfg.heads_by_layer``), read off its ``wq``."""
    B, T, _ = h.shape
    h2 = h.reshape(B * T, cfg.dim)
    n_heads = layer["wq"].shape[1] // cfg.head_dim
    q = (h2 @ layer["wq"]).reshape(B, T, n_heads, cfg.head_dim)
    k = (h2 @ layer["wk"]).reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
    v = (h2 @ layer["wv"]).reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
    inv_freq, scale = cfg.rope(kind.attn)
    rope = functools.partial(_rope_lanes, pos=pos, inv_freq=inv_freq,
                             scale=scale)
    return (rope(_rms_norm(q, layer["q_norm"], cfg.norm_eps)),
            rope(_rms_norm(k, layer["k_norm"], cfg.norm_eps)), v)


def mixer(li, h, layer, pos, cache, shared, *, write_kv, attend,
          cfg: SwaMoeConfig):
    """``generate._layer_stack``'s ``mixer`` of a GATED block: the quartet
    (project -> write K / V -> attend -> out_proj, each under its region,
    through the caller's access pair) with the per-head output gate between
    attention and ``wo`` — ``sigmoid(h . wg)`` in float32, a number a head
    a token, of the layer's normed input ``h`` [B, T, D], which
    ``out_proj`` never sees.  -> (rows [B * T, D], the layer's cache,
    ``shared`` as it came)."""
    B, T, _ = h.shape
    with region("proj"):
        q, k, v = project(h, layer, pos, cfg=cfg, kind=cfg.kinds[li])
    with region("attn.gate"):
        gate = jax.nn.sigmoid(jnp.dot(
            h.reshape(B * T, cfg.dim), layer["wg"],
            preferred_element_type=jnp.float32))
    with region("kv_write"):
        cache = write_kv(li, cache, k, v)
    o = attend(li, q, cache)                         # [B, T, Hq, hd]
    with region("attn.gate"):
        o = o.astype(jnp.float32) * gate.reshape(B, T, -1, 1)
    with region("out_proj"):
        rows = _dense_out_proj(o.reshape(B * T, -1).astype(cfg.dtype), layer)
    return rows, cache, shared


# ---------------------------------------------------------------------------
# The generator the engine is built over
# ---------------------------------------------------------------------------


class SwaMoeGenerator:
    """What ``ServeEngine`` needs of a model (``MlaMoeGenerator`` has the
    same view): its config, the planes and GROUPS of its cache, the seam
    hooks of its block with the layers' kinds, and the chunked-prefill
    program.  It decodes through the engine's paged pools only."""

    latent = False

    def __init__(self, cfg: SwaMoeConfig, mesh=None, *, axis: str = "sp",
                 max_seq: int | None = None, impl: str = "auto",
                 interpret: bool = False, kv_dtype=None):
        if mesh is not None and math.prod(mesh.shape.values()) != 1:
            raise ValueError("SwaMoeGenerator stays world-1 (the engine "
                             "owns mesh placement)")
        self.cfg, self.mesh, self.axis = cfg, mesh, axis
        self.max_seq = max_seq or cfg.max_seq
        # int8 pools are the ENGINE's to refuse by name where the cache
        # has groups (a one-kind model would take the dense family's path)
        self.attn = types.SimpleNamespace(
            world=1, quantized=kv_dtype is not None,
            ctx=types.SimpleNamespace(impl=impl, interpret=interpret))
        self.tally = mla_moe.MoeTally()
        kw = dict(cfg=cfg, impl=impl, interpret=interpret)
        self._hooks = {
            "project": functools.partial(project, cfg=cfg),
            "out_proj": _dense_out_proj,
            "ffn": functools.partial(mla_moe.ffn, tally=self.tally, **kw),
            "kinds": cfg.kinds,
        }
        if cfg.gated:
            self._hooks["mixer"] = functools.partial(mixer, cfg=cfg)
        self._chunk_jit = jax.jit(
            named(self.wrap_program(functools.partial(
                _chunk_forward, cfg=cfg, **self._hooks,
                attend=functools.partial(
                    _attend_prefix, impl=impl, interpret=interpret,
                    soft_cap=cfg.attn_soft_cap))), "prefill_chunk"),
            static_argnames=("quantized", "extent"), donate_argnums=(2,))
        self._prompt_jit = jax.jit(self.wrap_program(functools.partial(
            _prompt_forward, cfg=cfg, **self._hooks,
            attend=functools.partial(_attend_prompt, **kw))))

    # -- the engine's view --------------------------------------------------

    @property
    def kv_planes(self) -> list:
        """(heads, width) of each plane of a layer's cache: K and V."""
        return [(self.cfg.n_kv_heads, self.cfg.head_dim)] * 2

    @property
    def kv_groups(self) -> list:
        """The cache groups, in ``LayerKind.group`` order: each with its
        name, the reach of its layers (0: the whole context) and the
        layers in it — and their QUERY heads, which the engine stamps on
        its metrics (``summary()["swa"]["heads"]``).  One group: the engine
        it builds is the one-table engine, whatever the kind."""
        c = self.cfg
        return [{"name": g,
                 "window": c.sliding_window if g == "window" else 0,
                 "layers": tuple(li for li, t in enumerate(c.layer_types)
                                 if t == g),
                 "heads": c.heads_by_kind[g]}
                for g in c.group_names]

    def serve_hooks(self) -> dict:
        """Keyword seams for the engine's paged forwards."""
        ctx = self.attn.ctx
        return dict(self._hooks, paged_attend=functools.partial(
            paged_attend, cfg=self.cfg, impl=ctx.impl,
            interpret=ctx.interpret))

    def wrap_program(self, fwd):
        return mla_moe.with_moe_stats(fwd, self.tally)

    # the expert layers are mla_moe's: so is what says which form they take
    moe_combine_forms = mla_moe.MlaMoeGenerator.moe_combine_forms

    def kernel_gaps(self, *, page_size: int, prefill_chunk: int,
                    ladder: list, sp_world: int = 1) -> dict:
        """Paths that will NOT reach a Pallas kernel: the dense family's
        attention calls at this model's head width, and the chunk's
        expert combine (``mla_moe.combine_kernel_gap``)."""
        ctx = self.attn.ctx
        gaps = attention_kernel_gaps(
            head_dim=self.cfg.head_dim, page_size=page_size,
            prefill_chunk=prefill_chunk, ladder=ladder,
            kv_itemsize=jnp.dtype(self.cfg.dtype).itemsize,
            kv_quant=bool(self.attn.quantized), impl=ctx.impl,
            interpret=ctx.interpret, sp_world=sp_world)
        why = mla_moe.combine_kernel_gap(
            self.cfg, prefill_chunk, impl=ctx.impl, interpret=ctx.interpret)
        if why:
            gaps[mla_moe.COMBINE_CALL] = why
        return gaps

    def forward_logits(self, params, tokens):
        """Logits [B, S, V] of whole prompts in one pass (no cache kept):
        what the tests hold against the reference."""
        _, logits, _ = self._prompt_jit(params, tokens)
        return logits
