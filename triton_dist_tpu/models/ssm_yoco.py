"""A decoder-hybrid-decoder block (the ``phi4flash`` family) for the serving
engine: state-space layers with a FIXED state a request beside window and
full attention, ONE full-attention cache read by every cross-attention
layer after it, gated-memory layers that hold nothing.

``D`` hidden, ``F`` MLP, ``Hq`` query / ``Hkv`` KV heads of ``hd = D / Hq``,
window ``W``, ``E = expand * D`` state-space channels, ``N = d_state``,
``K = d_conv``, ``R = dt_rank``.  Every layer ``l``::

    x <- x + mixer_l(LN1_l(x))
    x <- x + W_down (silu(g) * u),   g = LN2_l(x) W_gate, u = LN2_l(x) W_up

LN is LayerNorm with weight and bias; the logits are ``LN_f(x) . embed^T``
(the head is the embedding: ``tie_embeddings``); there is NO positional
encoding anywhere.  The mixer by layer index (``layer_types``; even layers
are state-space, ``l % mb_per_layer == 0``; ``L`` layers, the self-decoder
is layers ``0 .. L/2 + 1``):

* **``ssm``**, ``l = 0, 2, .., L/2`` — Mamba-1.  ``[x | z] = h W_in`` (D x
  2E).  ``x_t <- silu(b_c + sum_k w_c[k] * x_{t-K+1+k})`` a channel, zeros
  before the request's first token (``kernels/ssm_scan.causal_conv``).
  ``[d_t | B_t | C_t] = x_t W_x`` (E x (R + 2N)); ``Delta_t = softplus(d_t
  W_dt + b_dt)`` (R x E); ``A = -exp(A_log)``.  State ``S_t = exp(Delta_t
  (x) A) * S_{t-1} + (Delta_t * x_t) (x) B_t``, ``S_{-1} = 0``, in float32;
  ``y_t = S_t C_t + D_skip * x_t`` (``kernels/ssm_scan``: the Mosaic call
  ``ssm_scan`` a prefill chunk, ``ssm_step`` a decode step).  Output ``(y_t
  * silu(z_t)) W_out`` (E x D).  **Layer L/2 also hands ``m_t = y_t``
  (before the gate) down to the ``gmu`` layers.**
* **``window``**, ``l = 1, 3, .., L/2 - 1`` — causal softmax attention over
  the last ``W`` positions, the query's own included (key ``j`` for query
  ``i`` iff ``i - j < W``: ``models/swa_moe.py``'s convention), scale ``1 /
  sqrt(hd)``, GQA, ``W_q`` (D x Hq hd), ``W_k``, ``W_v`` (D x Hkv hd),
  ``W_o``; no bias.
* **``full``**, ``l = L/2 + 1`` — the same with no window.  Its K and V rows
  are the model's ONE growing cache.
* **``gmu``**, ``l = L/2 + 2, L/2 + 4, ..`` — ``(silu(h_t W_g) * m_t) W_out``
  (D x E, E x D), ``m_t`` layer L/2's for the same token.  No cache, no
  state.
* **``cross``**, ``l = L/2 + 3, L/2 + 5, ..`` — ``q = h W_q``; causal softmax
  attention of ``q`` over the FULL layer's K and V (positions <= t);
  ``W_o``.  No ``W_k``, no ``W_v``, no cache of its own.

**Cache groups** (``kv_groups``, serve/block_manager.py): ``full`` (one
layer, grows a page at a time), ``window`` (pages behind the window go
back), ``state`` (one fixed slot a running request: the float32 ``[N, E]``
state and the ``K - 1`` carried convolution inputs of each ``ssm`` layer,
358,400 B a layer at the published widths).  ``gmu`` layers belong to no
group, ``cross`` layers read the ``full`` group's table.

**Heads 64 wide** are stored in PAIRS as 128-lane rows
(``flash_decode.pack_kv_pairs``): the cache holds ``2 Hkv hd`` numbers a
token a layer and not one padded lane, and every attention kernel of the
repo reads it at width 128 (``pack_q_pairs``; scale ``1 / 8`` passed on).

Everything enters the engine's programs through the seams of
``models/generate.py`` — this family's is ``mixer``, in place of the
project / write / attend / out_proj quartet — and the dense SwiGLU ``ffn``
is ``generate``'s own.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import types

import jax
import jax.numpy as jnp

from triton_dist_tpu.kernels import ssm_scan as ssm_kernels
from triton_dist_tpu.kernels.flash_decode import (
    pack_kv_pairs,
    pack_q_pairs,
    unpack_out_pairs,
)
from triton_dist_tpu.kernels.gemm import resolve_impl
from triton_dist_tpu.models.generate import (
    LayerKind,
    _attend_prefix,
    _attend_prompt,
    _chunk_forward,
    _dense_out_proj,
    _dense_prompt_ffn,
    _layer_stack,
    attention_kernel_gaps,
    paged_attend,
)
from triton_dist_tpu.runtime.jit_cache import named
from triton_dist_tpu.runtime.profiling import region

LAYER_KINDS = ("ssm", "window", "full", "gmu", "cross")
GROUPS = ("full", "window", "state")
_LANES = 128


def layer_types_of(n_layers: int, mb_per_layer: int = 2) -> tuple:
    """The published split, derived from the depth as the modeling code
    does (``config.json`` has no per-layer list): the self-decoder is
    layers ``0 .. L/2 + 1`` — state-space where ``l % mb_per_layer == 0``,
    window attention between them, the full layer at ``L/2 + 1`` — and
    after it gated-memory layers (even) and cross-attention layers (odd)."""
    half = n_layers // 2
    if mb_per_layer != 2 or n_layers % 4:
        raise ValueError(
            f"num_hidden_layers {n_layers}, mb_per_layer {mb_per_layer}: "
            f"served is mb_per_layer 2 at a depth that divides by 4 (layer "
            f"L/2 must be a state-space layer)")
    out = []
    for li in range(n_layers):
        if li <= half + 1:
            out.append("ssm" if li % 2 == 0 else
                       "full" if li == half + 1 else "window")
        else:
            out.append("gmu" if li % 2 == 0 else "cross")
    return tuple(out)


@dataclasses.dataclass(frozen=True)
class SsmYocoConfig:
    vocab: int
    dim: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    ffn_dim: int
    sliding_window: int
    mb_per_layer: int = 2
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0            # 0: ceil(dim / 16), the family's convention
    norm_eps: float = 1e-5
    max_seq: int = 2048
    dtype: object = jnp.float32
    # what generate._layer_stack reads as data: LayerNorm, a tied head
    norm: str = "layer"
    tie_embeddings: bool = True
    attn_soft_cap: float = 0.0

    def __post_init__(self):
        if self.dt_rank == 0:
            object.__setattr__(self, "dt_rank", -(-self.dim // 16))
        if self.head_dim not in (64, 128):
            raise ValueError(
                f"head width {self.head_dim}: served are 128 and 64 (in "
                f"pairs)")
        if self.pairs and self.n_kv_heads % 2:
            raise ValueError("64-wide heads ride in pairs: an even number "
                             "of KV heads")
        if self.d_inner % _LANES:
            raise ValueError(f"expand * hidden_size = {self.d_inner} must "
                             f"divide by {_LANES}")
        layer_types_of(self.n_layers, self.mb_per_layer)

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def pairs(self) -> bool:
        """64-wide heads: stored and attended two to a 128-lane row."""
        return self.head_dim == 64

    @property
    def d_inner(self) -> int:
        return self.expand * self.dim

    @property
    def layer_types(self) -> tuple:
        return layer_types_of(self.n_layers, self.mb_per_layer)

    @property
    def memory_layer(self) -> int:
        """The state-space layer whose output the ``gmu`` layers gate."""
        return self.n_layers // 2

    @property
    def kinds(self) -> tuple:
        """One :class:`LayerKind` a layer (``group``: see its docstring)."""
        group = {"full": 0, "window": 1, "ssm": 2, "cross": 0, "gmu": -1}
        return tuple(LayerKind(
            attn=t, window=self.sliding_window if t == "window" else 0,
            group=group[t]) for t in self.layer_types)

    @property
    def kv_plane(self) -> tuple:
        """(heads, width) of a K or V plane as the cache stores it."""
        if self.pairs:
            return (self.n_kv_heads // 2, 2 * self.head_dim)
        return (self.n_kv_heads, self.head_dim)

    @property
    def state_planes(self) -> list:
        """(shape, dtype) of one request's state in one ``ssm`` layer: the
        ``K - 1`` carried convolution inputs — flat, in 128-lane rows, so
        that 3 rows of a bfloat16 plane are not padded to a 16-row tile —
        and the float32 state ``[N, E]``."""
        E = self.d_inner
        return [(((self.d_conv - 1) * E // _LANES, _LANES), self.dtype),
                ((self.d_state, E), jnp.float32)]

    @property
    def state_bytes_per_request(self) -> int:
        per = sum(math.prod(s) * jnp.dtype(d).itemsize
                  for s, d in self.state_planes)
        return per * self.layer_types.count("ssm")

    def n_params(self) -> int:
        """Parameters of the model (the embedding counted once)."""
        D, F, E, N, R, K = (self.dim, self.ffn_dim, self.d_inner,
                            self.d_state, self.dt_rank, self.d_conv)
        q, kv = self.n_heads * self.head_dim, self.n_kv_heads * self.head_dim
        per = {"window": 2 * D * q + 2 * D * kv, "cross": 2 * D * q,
               "ssm": (2 * D * E + E * K + E + E * (R + 2 * N) + R * E + E
                       + E * N + E + E * D),
               "gmu": 2 * D * E}
        per["full"] = per["window"]
        return (sum(per[t] for t in self.layer_types)
                + self.n_layers * (3 * D * F + 4 * D)
                + self.vocab * D + 2 * D)

    @staticmethod
    def from_hf(c: dict, *, max_seq: int, dtype=jnp.bfloat16,
                **over) -> "SsmYocoConfig":
        """From the keys of a ``phi4flash`` ``config.json``.  The Mamba-1
        sizes it does not carry (``d_state``, ``d_conv``, ``expand``,
        ``dt_rank``) default to the family's convention unless ``over``
        gives them.  What is not served is refused by name."""
        kind = c.get("model_type")
        if kind != "phi4flash":
            raise ValueError(f"model_type {kind!r}: served here is "
                             f"'phi4flash'")
        for key, want in (("hidden_act", "silu"), ("mlp_bias", False),
                          ("lm_head_bias", False),
                          ("tie_word_embeddings", True)):
            if c.get(key, want) != want:
                raise ValueError(f"{key} {c[key]!r}: only {want!r} is served")
        return SsmYocoConfig(
            vocab=c["vocab_size"], dim=c["hidden_size"],
            n_layers=c["num_hidden_layers"],
            n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"],
            ffn_dim=c["intermediate_size"],
            sliding_window=int(c["sliding_window"]),
            mb_per_layer=int(c.get("mb_per_layer", 2)),
            norm_eps=float(c["layer_norm_eps"]), max_seq=max_seq,
            dtype=dtype, **over)

    @staticmethod
    def tiny(dtype=jnp.float32, **over) -> "SsmYocoConfig":
        """CPU test size: 8 layers (ssm 0 2 4, window 1 3, full 5, gmu 6,
        cross 7), 4 heads of 64 on 2 KV heads (one pair), window 16,
        kernel-legal state-space widths (E = 512, N = 16)."""
        kw = dict(vocab=256, dim=256, n_layers=8, n_heads=4, n_kv_heads=2,
                  ffn_dim=256, sliding_window=16, max_seq=256, dtype=dtype)
        kw.update(over)
        return SsmYocoConfig(**kw)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

# name -> (subkey index of split(layer_key, 24), fan_in, shape).  The
# recipe — normal / sqrt(fan_in) a matrix, LayerNorm weights 1, every bias
# 0.1 * normal, A_log = log(1 .. N) a channel, D_skip 1, b_dt the inverse
# softplus of a step drawn log-uniform in [0.001, 0.1]; rounded once to the
# serving dtype, A_log / D_skip / b_dt kept float32 — is stated by the
# benchmark's configuration file and drawn again, independently, by its
# reference (benchmarks/reference/ssm_yoco.py).


def layer_matrices(c: SsmYocoConfig, kind: str) -> dict:
    D, F, E, N, R, K = (c.dim, c.ffn_dim, c.d_inner, c.d_state, c.dt_rank,
                        c.d_conv)
    q, kv = c.n_heads * c.head_dim, c.n_kv_heads * c.head_dim
    mats = {"wgate": (4, D, (D, F)), "wup": (5, D, (D, F)),
            "wdown": (6, F, (F, D))}
    if kind in ("window", "full", "cross"):
        mats.update(wq=(0, D, (D, q)), wo=(3, q, (q, D)))
    if kind in ("window", "full"):
        mats.update(wk=(1, D, (D, kv)), wv=(2, D, (D, kv)))
    if kind == "ssm":
        mats.update(w_in=(8, D, (D, 2 * E)), conv_w=(9, K, (K, E)),
                    w_x=(10, E, (E, R + 2 * N)), w_dt=(11, R, (R, E)),
                    w_out=(12, E, (E, D)))
    if kind == "gmu":
        mats.update(w_g=(8, D, (D, E)), w_out=(12, E, (E, D)))
    return mats


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def init_params(cfg: SsmYocoConfig, key) -> dict:
    """Seeded weights, drawn on the default device leaf by leaf.  The
    embedding is drawn at ``1 / sqrt(D)``: it is also the head."""
    c, dt = cfg, cfg.dtype
    D, E, N = c.dim, c.d_inner, c.d_state
    keys = jax.random.split(key, 2 + c.n_layers)
    fk = jax.random.split(keys[1], 2)
    params = {
        "embed": _normal(keys[0], (c.vocab, D), 1.0 / math.sqrt(D), dt),
        "final_norm": jnp.ones((D,), dt),
        "final_norm_bias": _normal(fk[0], (D,), 0.1, dt),
        "layers": [],
    }
    for li, kind in enumerate(c.layer_types):
        lk = jax.random.split(keys[2 + li], 24)
        layer = {n: _normal(lk[j], sh, 1.0 / math.sqrt(fi), dt)
                 for n, (j, fi, sh) in layer_matrices(c, kind).items()}
        layer.update(attn_norm=jnp.ones((D,), dt),
                     attn_norm_bias=_normal(lk[16], (D,), 0.1, dt),
                     mlp_norm=jnp.ones((D,), dt),
                     mlp_norm_bias=_normal(lk[17], (D,), 0.1, dt))
        if kind == "ssm":
            step = jnp.exp(jax.random.uniform(lk[14], (E,), jnp.float32)
                           * (math.log(0.1) - math.log(0.001))
                           + math.log(0.001))
            layer.update(
                conv_b=_normal(lk[13], (E,), 0.1, dt),
                b_dt=step + jnp.log(-jnp.expm1(-step)),
                A_log=jnp.broadcast_to(jnp.log(jnp.arange(
                    1, N + 1, dtype=jnp.float32))[:, None], (N, E)),
                D_skip=jnp.ones((E,), jnp.float32))
        params["layers"].append(layer)
    return params


# ---------------------------------------------------------------------------
# The seam this family brings: mixer
# ---------------------------------------------------------------------------


def _ssm_mixer(h, layer, cache, shared, *, cfg, hand_down, impl, interpret):
    """One Mamba-1 layer over ``h`` [B, T, D].  ``cache`` is the layer's
    state: ``None`` (a whole prompt: zeros in), the request's own planes
    ``[1, ..]`` (a prefill chunk's scratch), or the pool's ``[slots, ..]``
    planes read and written at ``shared["slot"]`` [B] (a decode step)."""
    c = cfg
    B, T, _ = h.shape
    E, N, R, K = c.d_inner, c.d_state, c.dt_rank, c.d_conv
    f32 = jnp.float32
    slot, n_valid = shared.get("slot"), shared.get("n_valid")
    with region("ssm.in"):
        xz = h.reshape(B * T, c.dim) @ layer["w_in"]
        x = xz[:, :E].reshape(B, T, E)
        z = xz[:, E:].reshape(B, T, E)
    if cache is None:
        conv0 = jnp.zeros((B, K - 1, E), c.dtype)
        s0 = jnp.zeros((B, N, E), f32)
    else:
        conv_p, ssm_p = cache
        conv0 = (conv_p if slot is None else conv_p[slot]).reshape(
            B, K - 1, E)
        s0 = ssm_p if slot is None else ssm_p[slot]
    with region("ssm.conv"):
        xc, conv1 = ssm_kernels.causal_conv(
            x, conv0, layer["conv_w"], layer["conv_b"], n_valid)
    with region("ssm.scan"):
        dbc = xc.reshape(B * T, E) @ layer["w_x"]
        dt = jax.nn.softplus((dbc[:, :R] @ layer["w_dt"]).astype(f32)
                             + layer["b_dt"]).reshape(B, T, E)
        Bm = dbc[:, R:R + N].astype(f32).reshape(B, T, N)
        Cm = dbc[:, R + N:].astype(f32).reshape(B, T, N)
        A = -jnp.exp(layer["A_log"])
        if n_valid is not None:
            # a padded row leaves the state as it was (exp(0) = 1)
            dt = jnp.where((jnp.arange(T) < n_valid)[None, :, None], dt, 0.0)
        xf = xc.astype(f32)
        if T == 1:
            y, s1 = ssm_kernels.ssm_step(xf[:, 0], dt[:, 0], Bm[:, 0],
                                         Cm[:, 0], A, layer["D_skip"], s0)
            y = y[:, None]
        else:
            outs = [ssm_kernels.ssm_scan(
                xf[b], dt[b], Bm[b], Cm[b], A, layer["D_skip"], s0[b],
                impl=impl, interpret=interpret) for b in range(B)]
            y = jnp.stack([o[0] for o in outs])
            s1 = jnp.stack([o[1] for o in outs])
    with region("ssm.out"):
        gated = (y * jax.nn.silu(z.astype(f32))).astype(c.dtype)
        rows = gated.reshape(B * T, E) @ layer["w_out"]
    if cache is None:
        cache = (conv1, s1)
    else:
        conv1 = conv1.reshape(B, *conv_p.shape[1:])
        cache = ((conv1, s1) if slot is None else
                 (conv_p.at[slot].set(conv1), ssm_p.at[slot].set(s1)))
    if hand_down:
        shared = {**shared, "m": y.astype(c.dtype)}
    return rows, cache, shared


def mixer(li, h, layer, pos, cache, shared, *, write_kv, attend, cfg, impl,
          interpret):
    """``generate._layer_stack``'s ``mixer`` for every kind of layer this
    family has: h [B, T, D] (normed) -> (rows [B * T, D], the layer's
    cache, what later layers of this forward read).  The attention kinds
    go through the caller's ``write_kv`` / ``attend`` pair as the quartet
    would; ``cross`` attends the cache the full layer left in ``shared``.
    Past the last layer that writes (``_layer_stack``'s ``keep``) ``h`` is
    ONE row of the chunk: ``gmu`` takes that row of ``m``, and ``cross``'s
    one query is placed by the caller's ``attend``."""
    c, kind = cfg, cfg.kinds[li]
    B, T, _ = h.shape
    if kind.attn == "ssm":
        return _ssm_mixer(h, layer, cache, shared, cfg=c,
                          hand_down=li == c.memory_layer, impl=impl,
                          interpret=interpret)
    h2 = h.reshape(B * T, c.dim)
    if kind.attn == "gmu":
        with region("gmu"):
            gate = jax.nn.silu((h2 @ layer["w_g"]).astype(jnp.float32))
            m = shared["m"]
            if m.shape[1] != T:     # one row kept of the chunk: its own m
                m = jax.lax.dynamic_slice_in_dim(m, shared["keep"], 1, 1)
            m = m.reshape(B * T, -1).astype(jnp.float32)
            return (gate * m).astype(c.dtype) @ layer["w_out"], cache, shared
    with region("proj"):
        q = (h2 @ layer["wq"]).reshape(B, T, c.n_heads, c.head_dim)
        if c.pairs:
            q = pack_q_pairs(q, c.n_kv_heads)
        if kind.attn != "cross":
            k, v = ((h2 @ layer[w]).reshape(B, T, c.n_kv_heads, c.head_dim)
                    for w in ("wk", "wv"))
            if c.pairs:
                k, v = pack_kv_pairs(k), pack_kv_pairs(v)
    if kind.attn == "cross":
        kv = shared["kv"]
    else:
        with region("kv_write"):
            kv = cache = write_kv(li, cache, k, v)
        if kind.attn == "full":
            shared = {**shared, "kv": cache}
    o = attend(li, q, kv)                            # [B, T, Hq, .] float32
    if c.pairs:
        o = unpack_out_pairs(o, c.n_kv_heads)
    with region("out_proj"):
        rows = _dense_out_proj(o.reshape(B * T, -1).astype(c.dtype), layer)
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


class SsmYocoGenerator:
    """What ``ServeEngine`` needs of a model (``SwaMoeGenerator`` has the
    same view): its config, the GROUPS of its cache with the planes of
    each, the seam hooks of its block with the layers' kinds, and the
    chunked-prefill program.  It decodes through the engine's pools only."""

    latent = False

    def __init__(self, cfg: SsmYocoConfig, mesh=None, *, axis: str = "sp",
                 max_seq: int | None = None, impl: str = "auto",
                 interpret: bool = False, kv_dtype=None):
        if mesh is not None and math.prod(mesh.shape.values()) != 1:
            raise ValueError("SsmYocoGenerator stays world-1 (the engine "
                             "owns mesh placement)")
        self.cfg, self.mesh, self.axis = cfg, mesh, axis
        self.max_seq = max_seq or cfg.max_seq
        # int8 pools are the ENGINE's to refuse by name (StateCacheUnsupported)
        self.attn = types.SimpleNamespace(
            world=1, quantized=kv_dtype is not None,
            ctx=types.SimpleNamespace(impl=impl, interpret=interpret))
        scale = {"scale": 1.0 / math.sqrt(cfg.head_dim)} if cfg.pairs else {}
        self._scale = scale
        self._hooks = {
            "project": None, "out_proj": _dense_out_proj,
            "ffn": _dense_prompt_ffn, "kinds": cfg.kinds,
            "mixer": functools.partial(mixer, cfg=cfg, impl=impl,
                                       interpret=interpret),
        }
        self._chunk_jit = jax.jit(
            named(functools.partial(
                _chunk_forward, cfg=cfg, **self._hooks,
                attend=functools.partial(
                    _attend_prefix, impl=impl, interpret=interpret,
                    **scale)), "prefill_chunk"),
            static_argnames=("quantized", "extent"), donate_argnums=(2,))
        self._prompt_jit = jax.jit(functools.partial(
            _prompt_forward, cfg=cfg, hooks=self._hooks, impl=impl,
            interpret=interpret))

    # -- the engine's view --------------------------------------------------

    @property
    def kv_planes(self) -> list:
        """(heads, width) of each plane of an attention layer's cache: K
        and V, heads in pairs where they are 64 wide."""
        return [self.cfg.kv_plane] * 2

    @property
    def kv_groups(self) -> list:
        """The cache groups, in ``LayerKind.group`` order, each with the
        layers that OWN a pool in it (a ``cross`` layer reads the full
        group's and owns none; a ``gmu`` layer is in no group).  The state
        group has ``state_planes`` — (shape, dtype) of one slot — in place
        of pages."""
        c = self.cfg
        owns = {g: tuple(li for li, t in enumerate(c.layer_types) if t == k)
                for g, k in zip(GROUPS, ("full", "window", "ssm"))}
        return [
            {"name": "full", "window": 0, "layers": owns["full"]},
            {"name": "window", "window": c.sliding_window,
             "layers": owns["window"]},
            {"name": "state", "window": 0, "layers": owns["state"],
             "state_planes": c.state_planes},
        ]

    def serve_hooks(self) -> dict:
        """Keyword seams for the engine's paged forwards."""
        ctx = self.attn.ctx
        return dict(self._hooks, paged_attend=functools.partial(
            paged_attend, cfg=self.cfg, impl=ctx.impl,
            interpret=ctx.interpret, **self._scale))

    def wrap_program(self, fwd):
        return fwd              # no counters of the family's own

    def kernel_gaps(self, *, page_size: int, prefill_chunk: int,
                    ladder: list, sp_world: int = 1) -> dict:
        """Paths that will NOT reach a Mosaic kernel: the dense family's
        attention calls at the STORED head width, and the chunk's scan."""
        ctx, c = self.attn.ctx, self.cfg
        gaps = attention_kernel_gaps(
            head_dim=c.kv_plane[1], page_size=page_size,
            prefill_chunk=prefill_chunk, ladder=ladder,
            kv_itemsize=jnp.dtype(c.dtype).itemsize,
            kv_quant=bool(self.attn.quantized), impl=ctx.impl,
            interpret=ctx.interpret, sp_world=sp_world)
        why = ("impl resolves to XLA"
               if resolve_impl(ctx.impl, ctx.interpret) == "xla" else
               ssm_kernels.ssm_scan_gap(prefill_chunk, c.d_inner, c.d_state))
        if why:
            gaps["ssm_scan"] = why
        return gaps

    def forward_logits(self, params, tokens):
        """Logits [B, S, V] of whole prompts in one pass (no cache kept):
        what the tests hold against the reference."""
        return self._prompt_jit(params, tokens)[1]

    def forward_states(self, params, tokens):
        """Each state-space layer's (carried inputs, state) after whole
        prompts in one pass: what N chunks must leave behind."""
        rows = self._prompt_jit(params, tokens)[0]
        return [rows[li] for li, t in enumerate(self.cfg.layer_types)
                if t == "ssm"]
