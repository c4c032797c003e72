"""Plain float32 reference of the ``phi4flash`` block — a decoder-hybrid-
decoder: Mamba-1 state-space layers beside window and full attention, ONE
full-attention cache read by the cross-attention layers after it, gated-
memory layers — and its lower-precision control.

Straight ``jax.numpy`` under ``jax.default_matmul_precision("highest")``:
no cache, no state pool, no kernels, no batching; the selective scan is a
plain ``lax.scan`` over tokens, attention runs in blocks of query rows.  It
imports nothing of the program and takes nothing the program made: the
weights are drawn here, from the seed, by the recipe the configuration file
states (``"weights"``), one layer at a time, upcast, used and dropped.

The equations.  ``D = hidden_size``, ``F = intermediate_size``, ``H`` query
and ``Hkv`` KV heads of ``hd = D / H``, window ``W = sliding_window``, and —
ASSUMED, the config carries none of them (the file's ``assumed``) — ``N =
d_state``, ``K = d_conv``, ``E = expand * D``, ``R = dt_rank``.  LN is
LayerNorm with weight and bias, eps ``layer_norm_eps``.  NO positional
encoding.  Every layer ``l``:

    x <- x + mixer_l(LN1_l(x));   x <- x + W_down (silu(W_gate h) * W_up h),
    h = LN2_l(x)

and ``logits = LN_f(x) . embed^T`` (``tie_word_embeddings``).  The mixer by
layer index (derived from ``num_hidden_layers`` = L and ``mb_per_layer`` =
2 as the published modeling code does; ASSUMED, the file's ``split``):

* ``l`` even, ``l <= L/2`` — Mamba-1: ``[x | z] = h W_in``; ``x_t <-
  silu(b_c + sum_k w_c[k] * x_{t-K+1+k})`` a channel, zeros before the
  first token; ``[d_t | B_t | C_t] = x_t W_x``; ``Delta_t = softplus(d_t
  W_dt + b_dt)``; ``A = -exp(A_log)``; ``S_t = exp(Delta_t (x) A) * S_{t-1}
  + (Delta_t * x_t) (x) B_t`` from ``S_{-1} = 0``; ``y_t = S_t C_t + D_skip
  * x_t``; output ``(y_t * silu(z_t)) W_out``.  Layer ``L/2`` also hands
  ``m_t = y_t`` (before the gate) to the gated-memory layers.
* ``l`` odd, ``l < L/2 + 1`` — causal softmax attention over the last ``W``
  positions, the query's own included (key ``j`` for query ``i`` iff ``i -
  j < W``), scale ``1 / sqrt(hd)``, ``H / Hkv`` query heads a KV head, no
  bias.
* ``l = L/2 + 1`` — the same with no window; its K and V are kept.
* ``l`` even, ``l > L/2 + 1`` — gated memory: ``(silu(h_t W_g) * m_t)
  W_out``.
* ``l`` odd, ``l > L/2 + 1`` — cross attention: ``q = h W_q`` over layer
  ``L/2 + 1``'s K and V (positions <= t), ``W_o``.
* Not run: differential attention (the paper describes it; the catalogued
  config carries none of its keys), dropout.

``forward_logits`` is teacher-forced like ``llama_dense``'s.  With
``int8=True`` every matmul operand and the kept K and V rows go through
symmetric int8, and the recurrent state is rounded to bfloat16 after every
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


def weight_key(seed: int):
    """The key all weights derive from.  Seeds may pass 2**31: the low 31
    bits seed the key and the rest is folded in."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def layer_kinds(n_layers: int) -> tuple:
    half = n_layers // 2
    return tuple(
        ("ssm" if li % 2 == 0 else "full" if li == half + 1 else "window")
        if li <= half + 1 else ("gmu" if li % 2 == 0 else "cross")
        for li in range(n_layers))


def sizes(cfg: dict) -> dict:
    a = cfg["assumed"]
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    assert cfg["mb_per_layer"] == 2 and cfg["num_hidden_layers"] % 4 == 0
    return dict(
        D=D, L=cfg["num_hidden_layers"], H=H,
        Hkv=cfg["num_key_value_heads"], hd=D // H, V=cfg["vocab_size"],
        F=cfg["intermediate_size"], W=int(cfg["sliding_window"]),
        N=a["d_state"], K=a["d_conv"], E=a["expand"] * D, R=a["dt_rank"],
        eps=float(cfg["layer_norm_eps"]),
        kinds=layer_kinds(cfg["num_hidden_layers"]))


# -- the seeded weights --------------------------------------------------------
# name -> (subkey index, fan_in, shape); subkeys: split(layer_key, 24).

def _layer_matrices(s: dict, kind: str) -> dict:
    D, F, E, N, R, K = s["D"], s["F"], s["E"], s["N"], s["R"], s["K"]
    q, kv = s["H"] * s["hd"], s["Hkv"] * s["hd"]
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


def _keys(s: dict, seed: int):
    return jax.random.split(weight_key(seed), 2 + s["L"])


def draw_layer(cfg: dict, seed: int, li: int, dtype=jnp.bfloat16) -> dict:
    s = sizes(cfg)
    kind = s["kinds"][li]
    D, E, N = s["D"], s["E"], s["N"]
    lk = jax.random.split(_keys(s, seed)[2 + li], 24)
    w = {n: _normal(lk[j], sh, 1.0 / math.sqrt(fi), dtype)
         for n, (j, fi, sh) in _layer_matrices(s, kind).items()}
    w.update(ln1_w=jnp.ones((D,), dtype),
             ln1_b=_normal(lk[16], (D,), 0.1, dtype),
             ln2_w=jnp.ones((D,), dtype),
             ln2_b=_normal(lk[17], (D,), 0.1, dtype))
    if kind == "ssm":
        step = jnp.exp(jax.random.uniform(lk[14], (E,), jnp.float32)
                       * (math.log(0.1) - math.log(0.001))
                       + math.log(0.001))
        w.update(conv_b=_normal(lk[13], (E,), 0.1, dtype),
                 b_dt=step + jnp.log(-jnp.expm1(-step)),
                 A_log=jnp.broadcast_to(jnp.log(jnp.arange(
                     1, N + 1, dtype=jnp.float32))[:, None], (N, E)),
                 D_skip=jnp.ones((E,), jnp.float32))
    return w


def draw_embed(cfg: dict, seed: int, dtype=jnp.bfloat16):
    s = sizes(cfg)
    return _normal(_keys(s, seed)[0], (s["V"], s["D"]),
                   1.0 / math.sqrt(s["D"]), dtype)


def draw_final_norm(cfg: dict, seed: int, dtype=jnp.bfloat16) -> tuple:
    s = sizes(cfg)
    fk = jax.random.split(_keys(s, seed)[1], 2)
    return jnp.ones((s["D"],), dtype), _normal(fk[0], (s["D"],), 0.1, dtype)


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


def _ln(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


# -- the block ---------------------------------------------------------------------

def attention(q, k, v, s: dict, window: int):
    """Causal GQA of q [T, H, hd] over k, v [T, Hkv, hd] (T a multiple of
    Q_BLOCK) -> [T, H * hd]."""
    T = q.shape[0]
    H, Hkv, hd = s["H"], s["Hkv"], s["hd"]
    g = H // Hkv
    pos = jnp.arange(T, dtype=jnp.int32)

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


def mamba(h, w, s: dict, int8: bool):
    """One Mamba-1 layer over a whole sequence h [T, D] from a zero state
    -> (its output [T, D], y [T, E] before the gate)."""
    T = h.shape[0]
    E, N, R, K = s["E"], s["N"], s["R"], s["K"]
    xz = _mm(h, w["w_in"], int8)
    x, z = xz[:, :E], xz[:, E:]
    xp = jnp.concatenate([jnp.zeros((K - 1, E), x.dtype), x])
    x = jax.nn.silu(w["conv_b"] + sum(w["conv_w"][k] * xp[k:k + T]
                                      for k in range(K)))
    dbc = _mm(x, w["w_x"], int8)
    dt = jax.nn.softplus(_mm(dbc[:, :R], w["w_dt"], int8) + w["b_dt"])
    Bm, Cm = dbc[:, R:R + N], dbc[:, R + N:]
    A = -jnp.exp(w["A_log"])                                  # [N, E]

    def step(S, row):
        xt, dtt, bt, ct = row
        S = jnp.exp(dtt[None, :] * A) * S + (dtt * xt)[None, :] * bt[:, None]
        if int8:        # the control: the state kept in bfloat16
            S = S.astype(jnp.bfloat16).astype(jnp.float32)
        return S, jnp.sum(S * ct[:, None], axis=0)

    _, y = jax.lax.scan(step, jnp.zeros((N, E), jnp.float32),
                        (x, dt, Bm, Cm))
    y = y + w["D_skip"] * x
    return _mm(y * jax.nn.silu(z), w["w_out"], int8), y


def _static(s: dict) -> tuple:
    return tuple(sorted(s.items()))


@functools.partial(jax.jit, static_argnames=("st", "kind", "int8"))
def _layer(x, w, carry, *, st, kind, int8):
    """x [T, D] through one layer; ``carry`` = (m, k, v) as earlier layers
    of this sequence left them (None before)."""
    with jax.default_matmul_precision("highest"):
        s = dict(st)
        T = x.shape[0]
        H, Hkv, hd = s["H"], s["Hkv"], s["hd"]
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        m, kc, vc = carry
        h = _ln(x, w["ln1_w"], w["ln1_b"], s["eps"])
        if kind == "ssm":
            out, y = mamba(h, w, s, int8)
            m = y                   # the last state-space layer's is kept
        elif kind == "gmu":
            out = _mm(jax.nn.silu(_mm(h, w["w_g"], int8)) * m, w["w_out"],
                      int8)
        else:
            q = _mm(h, w["wq"], int8).reshape(T, H, hd)
            if kind == "cross":
                k, v = kc, vc
            else:
                k = _mm(h, w["wk"], int8).reshape(T, Hkv, hd)
                v = _mm(h, w["wv"], int8).reshape(T, Hkv, hd)
                if int8:            # the int8 pool: per cached row and head
                    k, v = _q8(k, -1), _q8(v, -1)
                if kind == "full":
                    kc, vc = k, v
            out = _mm(attention(q, k, v, s, s["W"] if kind == "window" else 0),
                      w["wo"], int8)
        x = x + out
        h2 = _ln(x, w["ln2_w"], w["ln2_b"], s["eps"])
        x = x + _mm(jax.nn.silu(_mm(h2, w["wgate"], int8))
                    * _mm(h2, w["wup"], int8), w["wdown"], int8)
        return x, (m, kc, vc)


@functools.partial(jax.jit, static_argnames=("eps", "int8"))
def _head(x, embed, ln_w, ln_b, *, eps, int8):
    with jax.default_matmul_precision("highest"):
        h = _ln(x, ln_w.astype(jnp.float32), ln_b.astype(jnp.float32), eps)
        return _mm(h, embed.astype(jnp.float32).T, int8)


def forward_logits(cfg: dict, seed: int, sequences: list, n_prompts: list, *,
                   int8: bool = False, dtype=jnp.bfloat16) -> list:
    """Logits at every served position of each sequence (see
    ``llama_dense.forward_logits``: the same contract).  Layers are the
    outer loop: each layer's weights are drawn once, used for every
    sequence and dropped; a sequence carries what later layers read of
    earlier ones (the memory, the one kept K and V)."""
    s = sizes(cfg)
    embed = draw_embed(cfg, seed, dtype)
    xs = []
    for seq in sequences:
        seq = np.asarray(seq, np.int32)[:-1]     # the last token feeds nothing
        T = -(-len(seq) // T_BLOCK) * T_BLOCK
        ids = np.zeros((T,), np.int32)
        ids[:len(seq)] = seq
        xs.append(embed[jnp.asarray(ids)].astype(jnp.float32))
    st = _static(s)
    carries = [(None, None, None)] * len(xs)
    for li in range(s["L"]):
        w = draw_layer(cfg, seed, li, dtype)
        outs = [_layer(x, w, c, st=st, kind=s["kinds"][li], int8=int8)
                for x, c in zip(xs, carries)]
        xs, carries = [o[0] for o in outs], [o[1] for o in outs]
        del w, outs
    del carries
    ln_w, ln_b = draw_final_norm(cfg, seed, dtype)
    out = []
    for x, seq, n0 in zip(xs, sequences, n_prompts):
        rows = x[n0 - 1:len(seq) - 1]
        out.append(np.asarray(_head(rows, embed, ln_w, ln_b, eps=s["eps"],
                                    int8=int8)))
    return out
