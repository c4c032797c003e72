"""Plain float32 reference of the ``mellum`` block — grouped-query
attention whose kind differs by layer (sliding-window layers beside full
ones, a RoPE a kind) over softmax-routed small experts — and its int8
control.

Straight ``jax.numpy`` under ``jax.default_matmul_precision("highest")``:
no cache, no kernels, no batching, attention in blocks of query rows so
that a 19k-token sequence fits beside nothing else.  It imports nothing of
the program and takes nothing the program made: the weights are drawn
here, from the seed, by the recipe the configuration file states
(``"weights"``) — normal / sqrt(fan_in) per matrix (embedding fan_in 1),
norms 1, rounded once to the serving dtype — one layer at a time, upcast,
used and dropped.  An expert's matrices derive from its GLOBAL id, so the
shares of a layer tile the uncut layer.

The equations (the published ``config.json`` of ``model_type: mellum``;
its keys are the Qwen3-MoE lineage's).  Layer ``li`` of kind ``t =
layer_types[li]``, RMSNorm eps ``rms_norm_eps``, no bias anywhere:

* ``h = rms(x) . attn_norm``; ``q = W_q h`` -> ``H`` heads x ``head_dim``;
  ``k = W_k h``, ``v = W_v h`` -> ``Hkv`` heads x ``head_dim`` (``head_dim``
  is a key of its own, not ``hidden_size / heads``).
* ASSUMED (the config has no key for it; the file lists it): q and k are
  RMS-normed per head over ``head_dim`` with a learned weight before RoPE,
  as the lineage's attention always is.
* RoPE over the whole head, rotate-half pairing.  ``sliding_attention``
  layers: ``inv_freq_i = theta^(-2i / d)``, cos / sin as they are.
  ``full_attention`` layers, YaRN: ``extra_i = theta^(-2i / d)``,
  ``inter_i = extra_i / factor``, ``corr(r) = d . ln(orig / (2 pi r)) /
  (2 ln theta)``, ``low = floor(corr(beta_fast))``, ``high =
  ceil(corr(beta_slow))``, ``ramp_i = clip((i - low) / (high - low), 0,
  1)``, ``inv_freq_i = inter_i . ramp_i + extra_i . (1 - ramp_i)``; cos
  and sin times ``attention_factor``.
* ``o = softmax(q k^T / sqrt(head_dim)) v``, causal, ``H / Hkv`` query
  heads a KV head; on a ``sliding_attention`` layer key ``j`` is seen by
  query ``i`` iff ``i - j < sliding_window``.  ``x += W_o o``.
* ``h2 = rms(x) . mlp_norm``; ``p = softmax(W_r h2)`` over all experts in
  float32; the ``num_experts_per_tok`` largest (ties to the lower id);
  ``w_e = p_e / sum of the chosen`` (``norm_topk_prob``); ``x += sum over
  the chosen experts HELD HERE of w_e . W_down,e (silu(W_gate,e h2) *
  W_up,e h2)``.  No bias, no groups, no scaling, no shared expert, no
  leading dense layer (``intermediate_size`` is used by no layer).
* ``logits = (rms(x) . final_norm) @ lm_head``, untied.
* Not run: the multi-token-prediction head the model card names (the
  config gives it no key; it adds nothing to the next-token logits).

``forward_logits`` is teacher-forced like ``llama_dense``'s.  With
``int8=True`` every matmul operand and the cached K and V rows go through
symmetric int8: the control ``correct`` has to reject.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 256      # query rows per attention block
T_BLOCK = 1024     # sequences are padded to multiples of this: few shapes

WINDOW_KIND, FULL_KIND = "sliding_attention", "full_attention"


def weight_key(seed: int):
    """The key all weights derive from.  Seeds may pass 2**31: the low 31
    bits seed the key and the rest is folded in."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def sizes(cfg: dict) -> dict:
    """The sizes from the configuration file's keys.  ``num_experts``
    counts the experts HELD; ``share`` (optional) gives the router's
    published width and the first expert id held."""
    share = cfg.get("share", {})
    held = cfg["num_experts"]
    rp = cfg["rope_parameters"]
    full = rp[FULL_KIND]
    yarn = None
    if full.get("rope_type", "default") == "yarn":
        yarn = (float(full["factor"]),
                int(full["original_max_position_embeddings"]),
                float(full["beta_fast"]), float(full["beta_slow"]),
                float(full["attention_factor"]))
    return dict(
        D=cfg["hidden_size"], L=cfg["num_hidden_layers"],
        H=cfg["num_attention_heads"], Hkv=cfg["num_key_value_heads"],
        hd=cfg["head_dim"], V=cfg["vocab_size"],
        Fe=cfg["moe_intermediate_size"],
        E=share.get("experts_total", held), held=held,
        offset=share.get("expert_offset", 0),
        topk=cfg["num_experts_per_tok"], norm=bool(cfg["norm_topk_prob"]),
        window=int(cfg.get("sliding_window") or 0),
        kinds=tuple(cfg["layer_types"]), theta=float(full["rope_theta"]),
        yarn=yarn, eps=float(cfg["rms_norm_eps"]))


# -- the seeded weights --------------------------------------------------------
# name -> (subkey index, fan_in, shape); subkeys: split(layer_key, 16).

def _layer_matrices(s: dict) -> dict:
    D, q, kv = s["D"], s["H"] * s["hd"], s["Hkv"] * s["hd"]
    return {"wq": (0, D, (D, q)), "wk": (1, D, (D, kv)),
            "wv": (2, D, (D, kv)), "wo": (3, q, (q, D)),
            "router": (8, D, (D, s["E"]))}


_EXPERT_MATRICES = {"e_gate": (10, "D", ("D", "Fe")),
                    "e_up": (11, "D", ("D", "Fe")),
                    "e_down": (12, "Fe", ("Fe", "D"))}


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _draw(key, denom, *, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) / denom).astype(dtype)


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _draw_experts(key, ids, denom, *, shape, dtype):
    def one(e):
        return jax.random.normal(jax.random.fold_in(key, e), shape,
                                 jnp.float32) / denom
    return jax.vmap(one)(ids).astype(dtype)


def _keys(s: dict, seed: int):
    return jax.random.split(weight_key(seed), 2 + s["L"])


def draw_layer(cfg: dict, seed: int, li: int, dtype=jnp.bfloat16) -> dict:
    s = sizes(cfg)
    lk = jax.random.split(_keys(s, seed)[2 + li], 16)
    w = {n: _draw(lk[j], jnp.float32(math.sqrt(fi)), shape=sh, dtype=dtype)
         for n, (j, fi, sh) in _layer_matrices(s).items()}
    ids = jnp.arange(s["offset"], s["offset"] + s["held"])
    for n, (j, fi, sh) in _EXPERT_MATRICES.items():
        w[n] = _draw_experts(lk[j], ids, jnp.float32(math.sqrt(s[fi])),
                             shape=tuple(s[d] for d in sh), dtype=dtype)
    return w


def draw_embed(cfg: dict, seed: int, dtype=jnp.bfloat16):
    s = sizes(cfg)
    return _draw(_keys(s, seed)[0], jnp.float32(1.0),
                 shape=(s["V"], s["D"]), dtype=dtype)


def draw_lm_head(cfg: dict, seed: int, dtype=jnp.bfloat16):
    s = sizes(cfg)
    return _draw(_keys(s, seed)[1], jnp.float32(math.sqrt(s["D"])),
                 shape=(s["D"], s["V"]), dtype=dtype)


# -- int8, for the control -----------------------------------------------------

def _q8(x, axis):
    """Symmetric int8 through absmax over ``axis``, returned dequantized."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _mm(x, w, int8: bool):
    if int8:
        x, w = _q8(x, -1), _q8(w, 0)    # per token row, per output channel
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)


# -- RoPE ------------------------------------------------------------------------

def inv_freq(d: int, theta: float, yarn) -> np.ndarray:
    """Inverse frequencies of the ``d / 2`` rotary pairs of one layer kind
    (``yarn`` None: plain RoPE): the module docstring's blend."""
    extra = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    if yarn is None:
        return extra.astype(np.float32)
    factor, orig, fast, slow, _ = yarn

    def corr(turns):
        return d * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(corr(fast)), 0)
    high = min(math.ceil(corr(slow)), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (extra / factor * ramp + extra * (1.0 - ramp)).astype(np.float32)


def rope_of(s: dict, kind: str) -> tuple:
    """(inverse frequencies, cos / sin factor) of a layer kind."""
    yarn = s["yarn"] if kind == FULL_KIND else None
    return inv_freq(s["hd"], s["theta"], yarn), (1.0 if yarn is None
                                                 else yarn[4])


def _rope(x, pos, freqs, cs):
    """x [T, heads, d], pos [T]: rotate-half pairing."""
    ang = pos[:, None].astype(jnp.float32) * freqs[None, :]
    cos, sin = (jnp.cos(ang) * cs)[:, None, :], (jnp.sin(ang) * cs)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


# -- the block ---------------------------------------------------------------------

def attention(h, w, s: dict, kind: str, int8: bool):
    """GQA over a whole sequence h [T, D] float32 (T a multiple of
    Q_BLOCK) -> [T, H * hd]."""
    T = h.shape[0]
    H, Hkv, hd = s["H"], s["Hkv"], s["hd"]
    g = H // Hkv
    pos = jnp.arange(T, dtype=jnp.int32)
    freqs, cs = rope_of(s, kind)
    freqs = jnp.asarray(freqs)
    q = _rms(_mm(h, w["wq"], int8).reshape(T, H, hd), s["eps"]) * w["q_norm"]
    k = _rms(_mm(h, w["wk"], int8).reshape(T, Hkv, hd), s["eps"]) * w["k_norm"]
    v = _mm(h, w["wv"], int8).reshape(T, Hkv, hd)
    q, k = _rope(q, pos, freqs, cs), _rope(k, pos, freqs, cs)
    if int8:                       # the int8 pool: per cached row and head
        k, v = _q8(k, -1), _q8(v, -1)
    window = s["window"] if kind == WINDOW_KIND else 0

    def block(qb, q0):
        qpos = q0 + jnp.arange(qb.shape[0])
        sc = jnp.einsum("qkgd,tkd->kgqt", qb.reshape(-1, Hkv, g, hd),
                        k) / math.sqrt(hd)
        seen = pos[None, :] <= qpos[:, None]
        if window:
            seen = seen & (qpos[:, None] - pos[None, :] < window)
        sc = jnp.where(seen[None, None], sc, -jnp.inf)
        o = jnp.einsum("kgqt,tkd->qkgd", jax.nn.softmax(sc, -1), v)
        return o.reshape(-1, H * hd)

    nb = T // Q_BLOCK
    o = jax.lax.map(lambda a: block(*a), (q.reshape(nb, Q_BLOCK, H, hd),
                                          jnp.arange(nb) * Q_BLOCK))
    return o.reshape(T, H * hd)


def swiglu(h, wg, wu, wd, int8: bool):
    return _mm(jax.nn.silu(_mm(h, wg, int8)) * _mm(h, wu, int8), wd, int8)


def route(h, w, s: dict, int8: bool):
    """-> (chosen [T, E] bool, weight [T, E] float32, zero off the
    chosen): softmax over all E experts, the ``topk`` largest (ties to the
    lower id), renormalised over the chosen."""
    p = jax.nn.softmax(_mm(h, w["router"], int8), axis=-1)
    rank = jnp.argsort(jnp.argsort(-p, axis=-1, stable=True), axis=-1)
    chosen = rank < s["topk"]
    wt = jnp.where(chosen, p, 0.0)
    if s["norm"]:
        wt = wt / wt.sum(-1, keepdims=True)
    return chosen, wt


def routed_share(h, w, s: dict, int8: bool):
    """The held experts' part of the routed sum: every held expert over
    every row, weighted by the router's weight (zero where not chosen)."""
    _, wt = route(h, w, s, int8)
    held = wt[:, s["offset"]:s["offset"] + s["held"]].T         # [held, T]

    def add(out, e):
        wg, wu, wd, w_e = e
        return out + w_e[:, None] * swiglu(h, wg, wu, wd, int8), None

    out, _ = jax.lax.scan(add, jnp.zeros_like(h),
                          (w["e_gate"], w["e_up"], w["e_down"], held))
    return out


def _static(s: dict) -> tuple:
    return tuple(sorted(s.items()))


@functools.partial(jax.jit, static_argnames=("st", "kind", "int8"))
def _layer(x, w, *, st, kind, int8):
    with jax.default_matmul_precision("highest"):
        s = dict(st)
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        # norms are 1 by the recipe: present so the equations read whole
        w.setdefault("q_norm", jnp.ones((s["hd"],), jnp.float32))
        w.setdefault("k_norm", jnp.ones((s["hd"],), jnp.float32))
        x = x + _mm(attention(_rms(x, s["eps"]), w, s, kind, int8), w["wo"],
                    int8)
        return x + routed_share(_rms(x, s["eps"]), w, s, int8)


@functools.partial(jax.jit, static_argnames=("eps", "int8"))
def _head(x, lm_head, *, eps, int8):
    with jax.default_matmul_precision("highest"):
        return _mm(_rms(x, eps), lm_head.astype(jnp.float32), int8)


def forward_logits(cfg: dict, seed: int, sequences: list, n_prompts: list, *,
                   int8: bool = False, dtype=jnp.bfloat16) -> list:
    """Logits at every served position of each sequence (see
    ``llama_dense.forward_logits``: the same contract).  Layers are the
    outer loop: each layer's weights are drawn once, used for every
    sequence and dropped."""
    s = sizes(cfg)
    embed = draw_embed(cfg, seed, dtype)
    xs = []
    for seq in sequences:
        seq = np.asarray(seq, np.int32)[:-1]     # the last token feeds nothing
        T = -(-len(seq) // T_BLOCK) * T_BLOCK
        ids = np.zeros((T,), np.int32)
        ids[:len(seq)] = seq
        xs.append(embed[jnp.asarray(ids)].astype(jnp.float32))
    del embed
    st = _static(s)
    for li in range(s["L"]):
        w = draw_layer(cfg, seed, li, dtype)
        xs = [_layer(x, w, st=st, kind=s["kinds"][li], int8=int8) for x in xs]
        del w
    lm_head = draw_lm_head(cfg, seed, dtype)
    out = []
    for x, seq, n0 in zip(xs, sequences, n_prompts):
        rows = x[n0 - 1:len(seq) - 1]
        out.append(np.asarray(_head(rows, lm_head, eps=s["eps"], int8=int8)))
    return out
