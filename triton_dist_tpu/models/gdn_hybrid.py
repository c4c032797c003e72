"""A Gated-DeltaNet hybrid (the ``olmo_hybrid`` family) for the serving
engine: linear-attention layers that hold a MATRIX state a head beside
full-attention layers, in periods of three to one.

``D`` hidden, ``F`` MLP; full layers have ``Hq = Hkv`` heads of ``hd = D /
Hq``; linear layers ``H`` heads with keys ``dk`` and values ``dv`` wide and
a ``K``-tap convolution.  The block is the Olmo-2/3 lineage's — the norm
sits on a sub-layer's OUTPUT (``cfg.norm_after``, which
``generate._layer_stack`` reads as data)::

    x <- x + RMSNorm(mixer_l(x))
    x <- x + RMSNorm(W_down (silu(x W_gate) * x W_up))

RMSNorm with a weight, eps ``rms_norm_eps``; logits ``RMSNorm_f(x) W_head``
(untied).  NO positional encoding on any layer (the config's
``rope_theta`` is null).  The mixer by ``layer_types[l]``:

* **``full``** — ``q = RMSNorm_D(x W_q)``, ``k = RMSNorm_D(x W_k)`` (the
  norm over the whole projection, a weight each), ``v = x W_v``; causal
  softmax attention at scale ``1 / sqrt(hd)``; ``W_o``; no bias.  Its K and
  V rows are the layer's growing cache: ``2 Hkv hd`` numbers a token.
* **``linear``** — Gated DeltaNet (arXiv:2412.06464).  ``[q | k | v] =
  silu(conv_K(x W_qkv))``: one causal depthwise convolution over the
  ``2 H dk + H dv`` projected channels, zeros before the request's first
  token, no bias (``kernels/ssm_scan.causal_conv``); ``q``, ``k`` L2-normed
  a head (eps 1e-6), ``q`` scaled by ``dk^-1/2``; ``beta = s * sigmoid(x
  W_b)`` (``s = 2`` with ``linear_allow_neg_eigval``); ``g = -exp(A_log) *
  softplus(x W_a + dt_bias)``; the delta rule of
  ``kernels/gated_delta.py`` a head in float32 (the Mosaic call
  ``gdn_chunk`` a prefill chunk, ``gdn_step`` a decode step, in place);
  output ``(RMSNorm_dv(o) * w_norm * silu(x W_z)) W_o`` — the norm a head.

**Cache groups** (``kv_groups``, serve/block_manager.py): ``full`` (the
full layers, a page at a time) and ``state`` (one fixed slot a running
request: each linear layer's float32 state ``[dk, H * dv]`` — the key index
on sublanes, (head, value index) on lanes: 45 lane tiles at the published
widths and no padded lane — and its ``K - 1`` carried convolution inputs,
2,280,960 B a layer).

Everything enters the engine's programs through the seams of
``models/generate.py`` — this family's is ``mixer`` — and the dense SwiGLU
``ffn`` is ``generate``'s own.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import types

import jax
import jax.numpy as jnp

from triton_dist_tpu.kernels import gated_delta
from triton_dist_tpu.kernels.gemm import resolve_impl
from triton_dist_tpu.kernels.ssm_scan import causal_conv
from triton_dist_tpu.models.generate import (
    LayerKind,
    _attend_prefix,
    _attend_prompt,
    _chunk_forward,
    _dense_out_proj,
    _dense_prompt_ffn,
    _layer_stack,
    _norm,
    attention_kernel_gaps,
    paged_attend,
)
from triton_dist_tpu.models.llama import _rms_norm
from triton_dist_tpu.runtime.jit_cache import named
from triton_dist_tpu.runtime.profiling import region

LAYER_KINDS = {"linear_attention": "linear", "full_attention": "full"}
GROUPS = ("full", "state")
_LANES = 128
_L2_EPS = 1e-6

# every key of an ``olmo_hybrid`` config.json this family reads or checks;
# any other is refused by name (:meth:`GdnHybridConfig.from_hf`)
HF_KEYS = frozenset((
    "model_type", "vocab_size", "hidden_size", "intermediate_size",
    "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
    "hidden_act", "max_position_embeddings", "attention_bias",
    "rms_norm_eps", "tie_word_embeddings", "layer_types",
    "linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim",
    "linear_value_head_dim", "linear_conv_kernel_dim",
    "linear_allow_neg_eigval", "rope_parameters", "torch_dtype"))


@dataclasses.dataclass(frozen=True)
class GdnHybridConfig:
    vocab: int
    dim: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    ffn_dim: int
    layer_types: tuple          # "linear" | "full", one a layer
    lin_heads: int
    lin_k_dim: int
    lin_v_dim: int
    conv_kernel: int = 4
    allow_neg_eigval: bool = True
    norm_eps: float = 1e-6
    max_seq: int = 2048
    dtype: object = jnp.float32
    # what generate._layer_stack and paged_attend read as data (the norm is
    # RMSNorm and the head is untied: a config that does not say)
    norm_after: bool = True
    attn_soft_cap: float = 0.0

    def __post_init__(self):
        bad = sorted(set(self.layer_types) - set(LAYER_KINDS.values()))
        if bad or len(self.layer_types) != self.n_layers:
            raise ValueError(
                f"layer_types {self.layer_types}: one of "
                f"{sorted(LAYER_KINDS.values())} a layer, {self.n_layers} of "
                f"them")
        if self.dim % self.n_heads or self.head_dim != 128:
            raise ValueError(f"head width {self.dim / self.n_heads}: served "
                             f"is 128")
        if self.conv_channels % _LANES:
            raise ValueError(
                f"the convolution's {self.conv_channels} channels must "
                f"divide by {_LANES}")

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def key_dim(self) -> int:
        return self.lin_heads * self.lin_k_dim

    @property
    def value_dim(self) -> int:
        return self.lin_heads * self.lin_v_dim

    @property
    def conv_channels(self) -> int:
        return 2 * self.key_dim + self.value_dim

    @property
    def kinds(self) -> tuple:
        """One :class:`LayerKind` a layer."""
        return tuple(LayerKind(attn=t, group=GROUPS.index(
            "state" if t == "linear" else "full")) for t in self.layer_types)

    @property
    def state_planes(self) -> list:
        """(shape, dtype) of one request's state in one linear layer: the
        ``K - 1`` carried convolution inputs — flat, in 128-lane rows — and
        the float32 matrix state ``[dk, H * dv]``, heads side by side along
        lanes."""
        return [(((self.conv_kernel - 1) * self.conv_channels // _LANES,
                  _LANES), self.dtype),
                ((self.lin_k_dim, self.value_dim), jnp.float32)]

    @property
    def state_bytes_per_layer(self) -> int:
        return sum(math.prod(s) * jnp.dtype(d).itemsize
                   for s, d in self.state_planes)

    @property
    def state_bytes_per_request(self) -> int:
        return self.state_bytes_per_layer * self.layer_types.count("linear")

    def mixer_params(self, kind: str) -> int:
        D, H = self.dim, self.lin_heads
        if kind == "full":
            return 4 * D * D + 2 * D            # + the q and k norms
        return (D * self.conv_channels + 2 * D * self.value_dim + 2 * D * H
                + self.conv_kernel * self.conv_channels + 2 * H
                + self.lin_v_dim)

    def n_params(self) -> int:
        """Parameters of the model: mixers, MLPs with both norms of a
        layer, embedding, head and the final norm."""
        D = self.dim
        return (sum(self.mixer_params(t) for t in self.layer_types)
                + self.n_layers * (3 * D * self.ffn_dim + 2 * D)
                + 2 * self.vocab * D + D)

    @staticmethod
    def from_hf(c: dict, *, max_seq: int,
                dtype=jnp.bfloat16) -> "GdnHybridConfig":
        """From the keys of an ``olmo_hybrid`` ``config.json``.  A key this
        family does not know, and a value it does not serve, is refused by
        name.  ``layer_types`` may be longer than ``num_hidden_layers`` (a
        stage of the model: the first ``num_hidden_layers`` entries)."""
        kind = c.get("model_type")
        if kind != "olmo_hybrid":
            raise ValueError(f"model_type {kind!r}: served here is "
                             f"'olmo_hybrid'")
        unknown = sorted(set(c) - HF_KEYS)
        if unknown:
            raise ValueError(
                f"olmo_hybrid config keys {unknown}: not known to this "
                f"family (served: {sorted(HF_KEYS)})")
        for key, want in (("hidden_act", "silu"), ("attention_bias", False),
                          ("tie_word_embeddings", False)):
            if c.get(key, want) != want:
                raise ValueError(f"{key} {c[key]!r}: only {want!r} is served")
        theta = (c.get("rope_parameters") or {}).get("rope_theta")
        if theta is not None:
            raise ValueError(
                f"rope_parameters.rope_theta {theta!r}: the full layers are "
                f"served without positional encoding (null)")
        n = c["num_hidden_layers"]
        types_ = list(c["layer_types"])[:n]
        bad = sorted(set(types_) - set(LAYER_KINDS))
        if bad or len(types_) != n:
            raise ValueError(f"layer_types {bad or types_}: served are "
                             f"{sorted(LAYER_KINDS)}, one a layer")
        if c["linear_num_key_heads"] != c["linear_num_value_heads"]:
            raise ValueError(
                f"linear_num_key_heads {c['linear_num_key_heads']} != "
                f"linear_num_value_heads {c['linear_num_value_heads']}: "
                f"keys shared between value heads are not served")
        return GdnHybridConfig(
            vocab=c["vocab_size"], dim=c["hidden_size"], n_layers=n,
            n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"],
            ffn_dim=c["intermediate_size"],
            layer_types=tuple(LAYER_KINDS[t] for t in types_),
            lin_heads=c["linear_num_value_heads"],
            lin_k_dim=c["linear_key_head_dim"],
            lin_v_dim=c["linear_value_head_dim"],
            conv_kernel=c["linear_conv_kernel_dim"],
            allow_neg_eigval=bool(c.get("linear_allow_neg_eigval", False)),
            norm_eps=float(c["rms_norm_eps"]), max_seq=max_seq, dtype=dtype)

    @staticmethod
    def tiny(dtype=jnp.float32, **over) -> "GdnHybridConfig":
        """CPU test size: one period (linear 0 1 2, full 3), 2 full heads
        of 128, 4 linear heads with keys 32 and values 64 wide (a state of
        ``[32, 256]``: kernel-legal, two heads a lane group)."""
        kw = dict(vocab=256, dim=256, n_layers=4, n_heads=2, n_kv_heads=2,
                  ffn_dim=256, layer_types=("linear",) * 3 + ("full",),
                  lin_heads=4, lin_k_dim=32, lin_v_dim=64, max_seq=256,
                  dtype=dtype)
        kw.update(over)
        return GdnHybridConfig(**kw)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

# name -> (subkey index of split(layer_key, 16), fan_in, shape).  The
# recipe — normal / sqrt(fan_in) a matrix, every norm weight 1, A_log =
# log U(0, 16) and dt_bias the inverse softplus of a step log-uniform in
# [0.001, 0.1] a head (the FLA initialisation); rounded once to the serving
# dtype, A_log / dt_bias kept float32 — is stated by the benchmark's
# configuration file and drawn again, independently, by its reference
# (benchmarks/reference/gdn_hybrid.py).


def layer_matrices(c: GdnHybridConfig, kind: str) -> dict:
    D, F, H, K = c.dim, c.ffn_dim, c.lin_heads, c.conv_kernel
    mats = {"wgate": (4, D, (D, F)), "wup": (5, D, (D, F)),
            "wdown": (6, F, (F, D))}
    if kind == "full":
        mats.update(wq=(0, D, (D, D)), wk=(1, D, (D, D)), wv=(2, D, (D, D)),
                    wo=(3, D, (D, D)))
    else:
        mats.update(wq=(0, D, (D, c.key_dim)), wk=(1, D, (D, c.key_dim)),
                    wv=(2, D, (D, c.value_dim)),
                    wo=(3, c.value_dim, (c.value_dim, D)),
                    w_z=(7, D, (D, c.value_dim)), w_a=(8, D, (D, H)),
                    w_b=(9, D, (D, H)),
                    conv_w=(10, K, (K, c.conv_channels)))
    return mats


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def init_params(cfg: GdnHybridConfig, key) -> dict:
    """Seeded weights, drawn on the default device leaf by leaf.  A linear
    layer's ``wq | wk | wv`` are drawn apart and held side by side as
    ``w_qkv`` (one product, one convolution), ``w_a | w_b`` as ``w_ab``."""
    c, dt = cfg, cfg.dtype
    D, H = c.dim, c.lin_heads
    keys = jax.random.split(key, 3 + c.n_layers)
    params = {
        "embed": _normal(keys[0], (c.vocab, D), 1.0, dt),
        "lm_head": _normal(keys[1], (D, c.vocab), 1.0 / math.sqrt(D), dt),
        "final_norm": jnp.ones((D,), dt),
        "layers": [],
    }
    for li, kind in enumerate(c.layer_types):
        lk = jax.random.split(keys[3 + li], 16)
        layer = {n: _normal(lk[j], sh, 1.0 / math.sqrt(fi), dt)
                 for n, (j, fi, sh) in layer_matrices(c, kind).items()}
        layer.update(attn_norm=jnp.ones((D,), dt),
                     mlp_norm=jnp.ones((D,), dt))
        if kind == "full":
            layer.update(q_norm=jnp.ones((D,), dt), k_norm=jnp.ones((D,), dt))
        else:
            layer["w_qkv"] = jnp.concatenate(
                [layer.pop(n) for n in ("wq", "wk", "wv")], axis=1)
            layer["w_ab"] = jnp.concatenate(
                [layer.pop(n) for n in ("w_a", "w_b")], axis=1)
            step = jnp.exp(jax.random.uniform(lk[12], (H,), jnp.float32)
                           * (math.log(0.1) - math.log(0.001))
                           + math.log(0.001))
            layer.update(
                A_log=jnp.log(jax.random.uniform(
                    lk[11], (H,), jnp.float32, minval=1e-3, maxval=16.0)),
                dt_bias=step + jnp.log(-jnp.expm1(-step)),
                o_norm=jnp.ones((c.lin_v_dim,), dt))
        params["layers"].append(layer)
    return params


# ---------------------------------------------------------------------------
# The seam this family brings: mixer
# ---------------------------------------------------------------------------


def _l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                             + _L2_EPS)


def _linear_mixer(h, layer, cache, shared, *, cfg, impl, interpret):
    """One Gated-DeltaNet layer over ``h`` [B, T, D].  ``cache`` is the
    layer's state: ``None`` (whole prompts: zeros in), the request's own
    planes ``[1, ..]`` (a prefill chunk's scratch), or the pool's
    ``[slots, ..]`` planes stepped in place at ``shared["slot"]`` [B] (a
    decode step: T = 1)."""
    c = cfg
    B, T, _ = h.shape
    H, dk, dv, K = c.lin_heads, c.lin_k_dim, c.lin_v_dim, c.conv_kernel
    f32 = jnp.float32
    slot, n_valid = shared.get("slot"), shared.get("n_valid")
    with region("gdn.in"):
        h2 = h.reshape(B * T, c.dim)
        qkv = (h2 @ layer["w_qkv"]).reshape(B, T, c.conv_channels)
        z = h2 @ layer["w_z"]
        ab = (h2 @ layer["w_ab"]).astype(f32)
    if cache is None:
        conv0 = jnp.zeros((B, K - 1, c.conv_channels), c.dtype)
    else:
        conv_p, state_p = cache
        conv0 = (conv_p if slot is None else conv_p[slot]).reshape(
            B, K - 1, c.conv_channels)
    with region("gdn.conv"):
        qkv, conv1 = causal_conv(qkv, conv0, layer["conv_w"], None, n_valid)
    with region("gdn.rule"):
        qkv = qkv.astype(f32)
        q = _l2_norm(qkv[..., :c.key_dim].reshape(B, T, H, dk)) * dk ** -0.5
        k = _l2_norm(qkv[..., c.key_dim:2 * c.key_dim].reshape(B, T, H, dk))
        v = qkv[..., 2 * c.key_dim:].reshape(B, T, H, dv)
        beta = (2.0 if c.allow_neg_eigval else 1.0) * jax.nn.sigmoid(
            ab[:, H:]).reshape(B, T, H)
        g = (-jnp.exp(layer["A_log"]) * jax.nn.softplus(
            ab[:, :H] + layer["dt_bias"])).reshape(B, T, H)
        # a padded chunk row, and a decode row parked on the null slot 0,
        # leaves the state as it was
        keep = None
        if slot is not None:
            keep = (slot != 0)[:, None, None]
        elif n_valid is not None:
            keep = (jnp.arange(T) < n_valid)[None, :, None]
        if keep is not None:
            beta, g = jnp.where(keep, beta, 0.0), jnp.where(keep, g, 0.0)
        if slot is not None:
            o, state1 = gated_delta.gdn_step(
                q[:, 0], k[:, 0], v[:, 0], beta[:, 0], g[:, 0], state_p,
                slot, impl=impl, interpret=interpret)
        else:
            s0 = (jnp.zeros((B, dk, H * dv), f32) if cache is None
                  else state_p)
            outs = [gated_delta.gdn_chunk(
                q[b], k[b], v[b], beta[b], g[b], s0[b], impl=impl,
                interpret=interpret) for b in range(B)]
            o = jnp.stack([x[0] for x in outs])
            state1 = jnp.stack([x[1] for x in outs])
    with region("gdn.out"):
        o = _rms_norm(o.reshape(B * T, H, dv), layer["o_norm"].astype(f32),
                      c.norm_eps)
        gated = (o.reshape(B * T, H * dv)
                 * jax.nn.silu(z.astype(f32))).astype(c.dtype)
        rows = _norm(gated @ layer["wo"], layer, "attn_norm", c)
    if cache is None:
        cache = (conv1, state1)
    else:
        conv1 = conv1.reshape(B, *conv_p.shape[1:])
        cache = (conv1 if slot is None else conv_p.at[slot].set(conv1),
                 state1)
    return rows, cache, shared


def mixer(li, h, layer, pos, cache, shared, *, write_kv, attend, cfg, impl,
          interpret):
    """``generate._layer_stack``'s ``mixer`` for both kinds of layer: h [B,
    T, D] — the residual stream itself (``cfg.norm_after``) -> (rows [B *
    T, D] NORMED, the layer's cache, ``shared``).  A full layer goes
    through the caller's ``write_kv`` / ``attend`` pair as the quartet
    would."""
    c = cfg
    B, T, _ = h.shape
    if c.kinds[li].attn == "linear":
        return _linear_mixer(h, layer, cache, shared, cfg=c, impl=impl,
                             interpret=interpret)
    h2 = h.reshape(B * T, c.dim)
    with region("proj"):
        q = _rms_norm(h2 @ layer["wq"], layer["q_norm"], c.norm_eps)
        k = _rms_norm(h2 @ layer["wk"], layer["k_norm"], c.norm_eps)
        q, k, v = (t.reshape(B, T, -1, c.head_dim)
                   for t in (q, k, h2 @ layer["wv"]))
    with region("kv_write"):
        cache = write_kv(li, cache, k, v)
    o = attend(li, q, cache)                            # [B, T, Hq, hd]
    with region("out_proj"):
        rows = _norm(_dense_out_proj(o.reshape(B * T, -1).astype(c.dtype),
                                     layer), layer, "attn_norm", c)
    return rows, cache, shared


def _prompt_forward(params, tokens, *, cfg, hooks, impl, interpret):
    """Whole prompts in one pass from zero states: (each layer's rows or
    final state, logits [B, S, V])."""
    def attend_rows(li, q, kv):
        with region("attn"):
            return _attend_prompt(q, *kv, cfg=cfg, impl=impl,
                                  interpret=interpret, kind=cfg.kinds[li])

    return _layer_stack(
        params, tokens, jnp.arange(tokens.shape[1], dtype=jnp.int32)[None],
        [None] * cfg.n_layers, cfg=cfg, **hooks,
        write_kv=lambda li, _, k, v: (k, v), attend=attend_rows, shared={})


# ---------------------------------------------------------------------------
# The generator the engine is built over
# ---------------------------------------------------------------------------


class GdnHybridGenerator:
    """What ``ServeEngine`` needs of a model (``SsmYocoGenerator`` has the
    same view): its config, the GROUPS of its cache with the planes of
    each, the seam hooks of its block with the layers' kinds, and the
    chunked-prefill program.  It decodes through the engine's pools only."""

    latent = False

    def __init__(self, cfg: GdnHybridConfig, mesh=None, *, axis: str = "sp",
                 max_seq: int | None = None, impl: str = "auto",
                 interpret: bool = False, kv_dtype=None):
        if mesh is not None and math.prod(mesh.shape.values()) != 1:
            raise ValueError("GdnHybridGenerator stays world-1 (the engine "
                             "owns mesh placement)")
        self.cfg, self.mesh, self.axis = cfg, mesh, axis
        self.max_seq = max_seq or cfg.max_seq
        # int8 pools are the ENGINE's to refuse by name (StateCacheUnsupported)
        self.attn = types.SimpleNamespace(
            world=1, quantized=kv_dtype is not None,
            ctx=types.SimpleNamespace(impl=impl, interpret=interpret))
        self._hooks = {
            "project": None, "out_proj": _dense_out_proj,
            "ffn": _dense_prompt_ffn, "kinds": cfg.kinds,
            "mixer": functools.partial(mixer, cfg=cfg, impl=impl,
                                       interpret=interpret),
        }
        self._chunk_jit = jax.jit(
            named(functools.partial(
                _chunk_forward, cfg=cfg, **self._hooks,
                attend=functools.partial(_attend_prefix, impl=impl,
                                         interpret=interpret)),
                "prefill_chunk"),
            static_argnames=("quantized", "extent"), donate_argnums=(2,))
        self._prompt_jit = jax.jit(functools.partial(
            _prompt_forward, cfg=cfg, hooks=self._hooks, impl=impl,
            interpret=interpret))

    # -- the engine's view --------------------------------------------------

    @property
    def kv_planes(self) -> list:
        """(heads, width) of each plane of a full layer's cache: K and V."""
        return [(self.cfg.n_kv_heads, self.cfg.head_dim)] * 2

    @property
    def kv_groups(self) -> list:
        """The cache groups, in ``LayerKind.group`` order, each with the
        layers that own a pool in it.  The state group has ``state_planes``
        — (shape, dtype) of one slot — in place of pages, and says what
        kind of state it is (``summary()["gdn"]``)."""
        c = self.cfg
        owns = {k: tuple(li for li, t in enumerate(c.layer_types) if t == k)
                for k in ("full", "linear")}
        return [
            {"name": "full", "window": 0, "layers": owns["full"]},
            {"name": "state", "window": 0, "layers": owns["linear"],
             "state_planes": c.state_planes, "kind": "gdn"},
        ]

    def serve_hooks(self) -> dict:
        """Keyword seams for the engine's paged forwards."""
        ctx = self.attn.ctx
        return dict(self._hooks, paged_attend=functools.partial(
            paged_attend, cfg=self.cfg, impl=ctx.impl,
            interpret=ctx.interpret))

    def wrap_program(self, fwd):
        return fwd              # no counters of the family's own

    def kernel_gaps(self, *, page_size: int, prefill_chunk: int,
                    ladder: list, sp_world: int = 1) -> dict:
        """Paths that will NOT reach a Mosaic kernel: the dense family's
        attention calls, the chunk's delta rule and the decode step's."""
        ctx, c = self.attn.ctx, self.cfg
        gaps = attention_kernel_gaps(
            head_dim=c.head_dim, page_size=page_size,
            prefill_chunk=prefill_chunk, ladder=ladder,
            kv_itemsize=jnp.dtype(c.dtype).itemsize,
            kv_quant=bool(self.attn.quantized), impl=ctx.impl,
            interpret=ctx.interpret, sp_world=sp_world)
        xla = resolve_impl(ctx.impl, ctx.interpret) == "xla"
        for name, why in (
                ("gdn_chunk", gated_delta.gdn_chunk_gap(
                    prefill_chunk, c.lin_k_dim, c.lin_v_dim)),
                ("gdn_step", gated_delta.gdn_step_gap(
                    c.lin_heads, c.lin_k_dim, c.lin_v_dim))):
            if xla or why:
                gaps[name] = "impl resolves to XLA" if xla else why
        return gaps

    def forward_logits(self, params, tokens):
        """Logits [B, S, V] of whole prompts in one pass (no cache kept):
        what the tests hold against the reference."""
        return self._prompt_jit(params, tokens)[1]

    def forward_states(self, params, tokens):
        """Each linear layer's (carried inputs, state) after whole prompts
        in one pass: what N chunks must leave behind."""
        rows = self._prompt_jit(params, tokens)[0]
        return [rows[li] for li, t in enumerate(self.cfg.layer_types)
                if t == "linear"]
