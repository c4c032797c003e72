"""Plain float32 reference of the ``olmo_hybrid`` block — Gated-DeltaNet
linear-attention layers (a matrix state a head) beside full-attention
layers — and its lower-precision control.

Straight ``jax.numpy`` under ``jax.default_matmul_precision("highest")``:
no cache, no state pool, no kernels, no chunking, no batching; the delta
rule is the TOKEN-BY-TOKEN recurrence (a plain ``lax.scan`` over tokens),
attention runs in blocks of query rows.  It imports nothing of the program
and takes nothing the program made: the weights are drawn here, from the
seed, by the recipe the configuration file states (``"weights"``), one
layer at a time, upcast, used and dropped.

The equations.  ``D = hidden_size``, ``F = intermediate_size``; full layers
``Hq = Hkv`` heads of ``hd = D / Hq``; linear layers ``H =
linear_num_value_heads`` heads, keys ``dk = linear_key_head_dim``, values
``dv = linear_value_head_dim``, ``K = linear_conv_kernel_dim`` taps.  RMS is
RMSNorm with a weight, eps ``rms_norm_eps``.  ASSUMED (the catalogued
config.json carries no key for any of it; the file's ``assumed``): the
block layout of the Olmo-2/3 lineage — the norm on a sub-layer's OUTPUT —

    x <- x + RMS(mixer_l(x));    x <- x + RMS(W_down (silu(x W_gate) * x W_up))

``logits = RMS_f(x) W_head`` (``tie_word_embeddings`` false), and NO
positional encoding (``rope_parameters.rope_theta`` is null).  The mixer by
``layer_types[l]`` (the first ``num_hidden_layers`` entries):

* ``full_attention`` — ``q = RMS_D(x W_q)``, ``k = RMS_D(x W_k)`` (over the
  whole projection), ``v = x W_v``; causal softmax attention, scale ``1 /
  sqrt(hd)``; ``W_o``; no bias.
* ``linear_attention`` — Gated DeltaNet (arXiv:2412.06464, spelled as
  ``transformers`` 4.57.6 ``qwen3_next`` spells it): ``[q | k | v] =
  silu(conv_K(x [W_q | W_k | W_v]))`` — one causal depthwise convolution,
  zeros before the first token, no bias; ``q``, ``k`` divided by their
  length a head (eps 1e-6 under the root), ``q`` times ``dk^-1/2``; ``beta
  = 2 sigmoid(x W_b)`` (``linear_allow_neg_eigval``; 1 x without); ``g =
  -exp(A_log) softplus(x W_a + dt_bias)``; a head's state from ``S = 0``:

      S <- exp(g_t) S;   S <- S + k_t (x) (beta_t (v_t - k_t^T S));   o_t = q_t^T S

  output ``(RMS_dv(o_t) * silu(x W_z)) W_o`` — the norm a head, its weight
  ``[dv]``.

``forward_logits`` is teacher-forced like ``llama_dense``'s.  With
``int8=True`` every matmul operand and the kept K and V rows go through
symmetric int8, and the matrix state is rounded to bfloat16 after every
step: the control ``correct`` has to reject.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 256      # query rows per attention block
T_BLOCK = 1024     # sequences are padded to multiples of this: few shapes
KINDS = {"linear_attention": "linear", "full_attention": "full"}


def weight_key(seed: int):
    """The key all weights derive from.  Seeds may pass 2**31: the low 31
    bits seed the key and the rest is folded in."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def sizes(cfg: dict) -> dict:
    D, Hq = cfg["hidden_size"], cfg["num_attention_heads"]
    L = cfg["num_hidden_layers"]
    assert cfg["num_key_value_heads"] == Hq
    assert cfg["linear_num_key_heads"] == cfg["linear_num_value_heads"]
    assert (cfg.get("rope_parameters") or {}).get("rope_theta") is None
    H, dk, dv = (cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])
    return dict(
        D=D, L=L, Hq=Hq, hd=D // Hq, V=cfg["vocab_size"],
        F=cfg["intermediate_size"], H=H, dk=dk, dv=dv,
        K=cfg["linear_conv_kernel_dim"], C=2 * H * dk + H * dv,
        beta_scale=2.0 if cfg.get("linear_allow_neg_eigval") else 1.0,
        eps=float(cfg["rms_norm_eps"]),
        kinds=tuple(KINDS[t] for t in cfg["layer_types"][:L]))


# -- the seeded weights --------------------------------------------------------
# name -> (subkey index, fan_in, shape); subkeys: split(layer_key, 16).

def _layer_matrices(s: dict, kind: str) -> dict:
    D, F, H, dk, dv, K = s["D"], s["F"], s["H"], s["dk"], s["dv"], s["K"]
    mats = {"wgate": (4, D, (D, F)), "wup": (5, D, (D, F)),
            "wdown": (6, F, (F, D))}
    if kind == "full":
        mats.update(wq=(0, D, (D, D)), wk=(1, D, (D, D)), wv=(2, D, (D, D)),
                    wo=(3, D, (D, D)))
    else:
        mats.update(wq=(0, D, (D, H * dk)), wk=(1, D, (D, H * dk)),
                    wv=(2, D, (D, H * dv)), wo=(3, H * dv, (H * dv, D)),
                    w_z=(7, D, (D, H * dv)), w_a=(8, D, (D, H)),
                    w_b=(9, D, (D, H)), conv_w=(10, K, (K, s["C"])))
    return mats


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _keys(s: dict, seed: int):
    return jax.random.split(weight_key(seed), 3 + s["L"])


def draw_layer(cfg: dict, seed: int, li: int, dtype=jnp.bfloat16) -> dict:
    s = sizes(cfg)
    kind = s["kinds"][li]
    D, H = s["D"], s["H"]
    lk = jax.random.split(_keys(s, seed)[3 + li], 16)
    w = {n: _normal(lk[j], sh, 1.0 / math.sqrt(fi), dtype)
         for n, (j, fi, sh) in _layer_matrices(s, kind).items()}
    w.update(post_mixer_norm=jnp.ones((D,), dtype),
             post_mlp_norm=jnp.ones((D,), dtype))
    if kind == "full":
        w.update(q_norm=jnp.ones((D,), dtype), k_norm=jnp.ones((D,), dtype))
    else:
        step = jnp.exp(jax.random.uniform(lk[12], (H,), jnp.float32)
                       * (math.log(0.1) - math.log(0.001))
                       + math.log(0.001))
        w.update(A_log=jnp.log(jax.random.uniform(
                     lk[11], (H,), jnp.float32, minval=1e-3, maxval=16.0)),
                 dt_bias=step + jnp.log(-jnp.expm1(-step)),
                 o_norm=jnp.ones((s["dv"],), dtype))
    return w


def draw_embed(cfg: dict, seed: int, dtype=jnp.bfloat16):
    s = sizes(cfg)
    return _normal(_keys(s, seed)[0], (s["V"], s["D"]), 1.0, dtype)


def draw_head(cfg: dict, seed: int, dtype=jnp.bfloat16) -> tuple:
    """(the final norm's weight, ``W_head`` [D, V])."""
    s = sizes(cfg)
    return (jnp.ones((s["D"],), dtype),
            _normal(_keys(s, seed)[1], (s["D"], s["V"]),
                    1.0 / math.sqrt(s["D"]), dtype))


# -- lower precision, for the control ------------------------------------------

def _q8(x, axis):
    """Symmetric int8 through absmax over ``axis``, returned dequantized."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _mm(x, w, int8: bool):
    if int8:
        x, w = _q8(x, -1), _q8(w, 0)    # per token row, per output channel
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


# -- the block ---------------------------------------------------------------------

def attention(q, k, v, s: dict):
    """Causal attention of q over k, v [T, Hq, hd] (T a multiple of
    Q_BLOCK) -> [T, Hq * hd]."""
    T = q.shape[0]
    Hq, hd = s["Hq"], s["hd"]
    pos = jnp.arange(T, dtype=jnp.int32)

    def block(qb, q0):
        qpos = q0 + jnp.arange(qb.shape[0])
        sc = jnp.einsum("qhd,thd->hqt", qb, k) / math.sqrt(hd)
        sc = jnp.where((pos[None, :] <= qpos[:, None])[None], sc, -jnp.inf)
        o = jnp.einsum("hqt,thd->qhd", jax.nn.softmax(sc, -1), v)
        return o.reshape(-1, Hq * hd)

    nb = T // Q_BLOCK
    o = jax.lax.map(lambda a: block(*a), (q.reshape(nb, Q_BLOCK, Hq, hd),
                                          jnp.arange(nb) * Q_BLOCK))
    return o.reshape(T, Hq * hd)


def delta_net(x, w, s: dict, int8: bool, state_bf16: bool):
    """One Gated-DeltaNet mixer over a whole sequence x [T, D] from a zero
    state -> [T, D] (before the block's norm).  ``state_bf16``: the state
    rounded to bfloat16 after every token (the control's part that a test
    can ask for alone)."""
    T = x.shape[0]
    H, dk, dv, K = s["H"], s["dk"], s["dv"], s["K"]
    qkv = jnp.concatenate([_mm(x, w[n], int8) for n in ("wq", "wk", "wv")],
                          axis=1)
    xp = jnp.concatenate([jnp.zeros((K - 1, s["C"]), qkv.dtype), qkv])
    qkv = jax.nn.silu(sum(w["conv_w"][j] * xp[j:j + T] for j in range(K)))
    q = qkv[:, :H * dk].reshape(T, H, dk)
    k = qkv[:, H * dk:2 * H * dk].reshape(T, H, dk)
    v = qkv[:, 2 * H * dk:].reshape(T, H, dv)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6)
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    q = q * dk ** -0.5
    beta = s["beta_scale"] * jax.nn.sigmoid(_mm(x, w["w_b"], int8))   # [T, H]
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(
        _mm(x, w["w_a"], int8) + w["dt_bias"])

    def step(S, row):                   # S [H, dk, dv]
        qt, kt, vt, bt, gt = row
        S = jnp.exp(gt)[:, None, None] * S
        kept = jnp.sum(kt[:, :, None] * S, axis=1)                    # [H, dv]
        S = S + kt[:, :, None] * (bt[:, None] * (vt - kept))[:, None, :]
        if state_bf16:  # the control: the state kept in bfloat16
            S = S.astype(jnp.bfloat16).astype(jnp.float32)
        return S, jnp.sum(qt[:, :, None] * S, axis=1)

    _, o = jax.lax.scan(step, jnp.zeros((H, dk, dv), jnp.float32),
                        (q, k, v, beta, g))
    o = _rms(o, w["o_norm"], s["eps"]).reshape(T, H * dv)
    return _mm(o * jax.nn.silu(_mm(x, w["w_z"], int8)), w["wo"], int8)


def _static(s: dict) -> tuple:
    return tuple(sorted(s.items()))


@functools.partial(jax.jit,
                   static_argnames=("st", "kind", "int8", "state_bf16"))
def _layer(x, w, *, st, kind, int8, state_bf16):
    """x [T, D] through one layer."""
    with jax.default_matmul_precision("highest"):
        s = dict(st)
        T = x.shape[0]
        Hq, hd = s["Hq"], s["hd"]
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        if kind == "linear":
            out = delta_net(x, w, s, int8, state_bf16)
        else:
            q = _rms(_mm(x, w["wq"], int8), w["q_norm"], s["eps"])
            k = _rms(_mm(x, w["wk"], int8), w["k_norm"], s["eps"])
            q, k, v = (t.reshape(T, Hq, hd)
                       for t in (q, k, _mm(x, w["wv"], int8)))
            if int8:                # the int8 pool: per cached row and head
                k, v = _q8(k, -1), _q8(v, -1)
            out = _mm(attention(q, k, v, s), w["wo"], int8)
        x = x + _rms(out, w["post_mixer_norm"], s["eps"])
        mlp = _mm(jax.nn.silu(_mm(x, w["wgate"], int8))
                  * _mm(x, w["wup"], int8), w["wdown"], int8)
        return x + _rms(mlp, w["post_mlp_norm"], s["eps"])


@functools.partial(jax.jit, static_argnames=("eps", "int8"))
def _head(x, norm_w, w_head, *, eps, int8):
    with jax.default_matmul_precision("highest"):
        return _mm(_rms(x, norm_w.astype(jnp.float32), eps),
                   w_head.astype(jnp.float32), int8)


def forward_logits(cfg: dict, seed: int, sequences: list, n_prompts: list, *,
                   int8: bool = False, state_bf16: bool | None = None,
                   dtype=jnp.bfloat16) -> list:
    """Logits at every served position of each sequence (see
    ``llama_dense.forward_logits``: the same contract).  Layers are the
    outer loop: each layer's weights are drawn once, used for every
    sequence and dropped.  ``state_bf16`` follows ``int8`` unless given."""
    s = sizes(cfg)
    state_bf16 = int8 if state_bf16 is None else state_bf16
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
        xs = [_layer(x, w, st=st, kind=s["kinds"][li], int8=int8,
                     state_bf16=state_bf16) for x in xs]
        del w
    norm_w, w_head = draw_head(cfg, seed, dtype)
    out = []
    for x, seq, n0 in zip(xs, sequences, n_prompts):
        rows = x[n0 - 1:len(seq) - 1]
        out.append(np.asarray(_head(rows, norm_w, w_head, eps=s["eps"],
                                    int8=int8)))
    return out
