"""Plain float32 reference of the ``xing4_0`` block (Xing4.0-29B-A4B: the
DeepSeek-V3 block — multi-head latent attention, sigmoid-routed experts
beside a shared expert — inside a residual path of ``hc_mult`` STREAMS mixed
per token by learned maps), and its control.

Straight ``jax.numpy`` under ``jax.default_matmul_precision("highest")``:
no cache, no kernels, the Sinkhorn normalisation as a plain loop.  It
imports nothing of the program and takes nothing the program made.  What it
shares with ``mla_moe_share`` (the DeepSeek-V3 block's reference) it imports
from there: the seed's key and the drawing recipe, ``_mm`` / ``_q8`` /
``_rms``, the latent attention, the router and the expert layer.  The
weights' recipe is the one the configuration file states (``"weights"``),
extended to the maps: sub-layer ``s`` (0: attention, 1: MLP / experts) of
layer ``l`` draws from ``fold_in(layer key, 4096 + s)`` split in two —
``phi`` normal / sqrt(n D), ``bias`` normal — with ``alpha`` = 1 and
``gain`` = 1, float32, never rounded.

The equations (``config.json``'s ``hc_mult`` / ``hc_sinkhorn_iters`` /
``hc_eps`` / ``mhc_h_res_clamp_*``: manifold-constrained hyper-connections),
n = ``hc_mult``, per token, per SUB-layer F (a layer's attention with its
``attn_norm``, then its dense MLP or expert layer with its ``mlp_norm``;
each has its own phi, alpha, bias, gain).  The residual is ``X in R^{n x D}``:

1. ``x~ = gain * vec(X) * (mean(vec(X)^2) + rms_norm_eps)^-1/2`` over all
   ``n D`` numbers;
2. ``[u_pre | u_post | u_res] = x~ phi``, ``phi in R^{n D x (2n + n^2)}``;
   ``H~_pre = alpha_pre u_pre + b_pre``, ``H~_post = alpha_post u_post +
   b_post`` (in R^n), ``H~_res = alpha_res mat(u_res) + b_res`` (n x n);
3. ``H_pre = sigmoid(H~_pre)``; ``H_post = 2 sigmoid(H~_post)``; ``M =
   exp(clip(H~_res, clamp_min, clamp_max))``, then ``hc_sinkhorn_iters``
   times ``M <- M / (column sums + hc_eps)``, ``M <- M / (row sums +
   hc_eps)``; ``H_res = M``;
4. ``h = sum_j H_pre[j] X[j]``; ``y = F(h)`` — F is the sub-layer of
   ``mla_moe_share``, its own RMSNorm included; ``X'[i] = sum_j H_res[i, j]
   X[j] + H_post[i] y``;
5. the model: ``X0[j] = embed(token)`` for every j; after the last layer
   ``x = sum_j X[j]``, then the final norm and the head.

What the config does not fix is listed in the configuration file's
``assumed`` (both sub-layers of every layer carry maps; columns before
rows, ``hc_eps`` in both denominators; the statistic's epsilon is
``rms_norm_eps`` and ``gain`` a learned weight; copies in, a sum out;
float32 maps).  Not run: the multi-token-prediction module.

``forward_logits`` is teacher-forced like ``llama_dense``'s.  With
``int8=True`` every matmul operand and the latent cache rows go through
symmetric int8 (the family's control) AND the three maps are rounded to
bfloat16 before they are applied (the program holds them in float32): the
control ``correct`` has to reject.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import mla_moe_share as base
from benchmarks.reference.mla_moe_share import (
    T_BLOCK,
    _draw,
    _head,
    _keys,
    _mm,
    _rms,
    _static,
    draw_embed,
    draw_layer,
    draw_lm_head,
    ffn,
    mla,
    weight_key,  # noqa: F401 — the recipe's key, kept with the reference
)

HC_FOLD = 4096      # fold_in tag of a layer's map draws (+ the sub-layer)
SUBLAYERS = ("hc_attn", "hc_mlp")


def sizes(cfg: dict) -> dict:
    """``mla_moe_share.sizes`` and the residual path's five keys."""
    s = base.sizes(cfg)
    s.update(n=int(cfg["hc_mult"]), iters=int(cfg["hc_sinkhorn_iters"]),
             hc_eps=float(cfg["hc_eps"]),
             lo=float(cfg["mhc_h_res_clamp_min"]),
             hi=float(cfg["mhc_h_res_clamp_max"]))
    return s


def draw_maps(cfg: dict, seed: int, li: int) -> dict:
    """Layer ``li``'s maps, a dict a sub-layer: ``phi`` [n D, 2n + n^2],
    ``alpha`` [3], ``bias`` [2n + n^2], ``gain`` [n D], float32."""
    s = sizes(cfg)
    n, D = s["n"], s["D"]
    k = 2 * n + n * n
    out = {}
    for sub, name in enumerate(SUBLAYERS):
        kp, kb = jax.random.split(jax.random.fold_in(
            _keys(s, seed)[2 + li], HC_FOLD + sub), 2)
        out[name] = {
            "phi": _draw(kp, jnp.float32(math.sqrt(n * D)), shape=(n * D, k),
                         dtype=jnp.float32),
            "alpha": jnp.ones((3,), jnp.float32),
            "bias": _draw(kb, jnp.float32(1.0), shape=(k, 1),
                          dtype=jnp.float32)[:, 0],
            "gain": jnp.ones((n * D,), jnp.float32)}
    return out


def sinkhorn(m, iters: int, eps: float):
    """The plain loop: columns to sum 1, then rows, ``iters`` times."""
    for _ in range(iters):
        m = m / (m.sum(axis=-2, keepdims=True) + eps)
        m = m / (m.sum(axis=-1, keepdims=True) + eps)
    return m


def stream_maps(X, p, s: dict, low: bool = False):
    """X [T, n, D] -> (H_pre [T, n], H_post [T, n], H_res [T, n, n]):
    equations 1-3.  ``low``: the control's maps, rounded to bfloat16."""
    T, n, D = X.shape
    v = X.reshape(T, n * D)
    xt = p["gain"] * v * jax.lax.rsqrt(
        jnp.mean(jnp.square(v), -1, keepdims=True) + s["eps"])
    u = jnp.dot(xt, p["phi"], preferred_element_type=jnp.float32)
    a, b = p["alpha"], p["bias"]
    pre = jax.nn.sigmoid(a[0] * u[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * u[:, n:2 * n] + b[n:2 * n])
    m = jnp.exp(jnp.clip(
        a[2] * u[:, 2 * n:].reshape(T, n, n) + b[2 * n:].reshape(n, n),
        s["lo"], s["hi"]))
    res = sinkhorn(m, s["iters"], s["hc_eps"])
    if low:
        pre, post, res = (t.astype(jnp.bfloat16).astype(jnp.float32)
                          for t in (pre, post, res))
    return pre, post, res


def sublayer(X, p, s: dict, f, low: bool = False):
    """Equation 4 around ``f`` ([T, D] -> [T, D])."""
    pre, post, res = stream_maps(X, p, s, low)
    y = f(jnp.einsum("tj,tjd->td", pre, X))
    return jnp.einsum("tij,tjd->tid", res, X) + post[:, :, None] * y[:, None]


@functools.partial(jax.jit, static_argnames=("st", "int8"))
def _layer(X, w, maps, *, st, int8):
    with jax.default_matmul_precision("highest"):
        s = dict(st)
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        X = sublayer(X, maps["hc_attn"], s, lambda h: _mm(
            mla(_rms(h, s["eps"]), w, s, int8), w["wo"], int8), int8)
        return sublayer(X, maps["hc_mlp"], s, lambda h: ffn(
            _rms(h, s["eps"]), w, s, int8), int8)


def forward_logits(cfg: dict, seed: int, sequences: list, n_prompts: list, *,
                   int8: bool = False, dtype=jnp.bfloat16) -> list:
    """Logits at every served position of each sequence (see
    ``llama_dense.forward_logits``: the same contract).  Layers are the
    outer loop: each layer's weights are drawn once, used for every
    sequence and dropped."""
    s = sizes(cfg)
    embed = draw_embed(cfg, seed, dtype)
    Xs = []
    for seq in sequences:
        seq = np.asarray(seq, np.int32)[:-1]     # the last token feeds nothing
        T = -(-len(seq) // T_BLOCK) * T_BLOCK
        ids = np.zeros((T,), np.int32)
        ids[:len(seq)] = seq
        x = embed[jnp.asarray(ids)].astype(jnp.float32)
        Xs.append(jnp.repeat(x[:, None, :], s["n"], axis=1))   # equation 5
    del embed
    st = _static(s)
    for li in range(s["L"]):
        w, maps = draw_layer(cfg, seed, li, dtype), draw_maps(cfg, seed, li)
        Xs = [_layer(X, w, maps, st=st, int8=int8) for X in Xs]
        del w, maps
    lm_head = draw_lm_head(cfg, seed, dtype)
    out = []
    for X, seq, n0 in zip(Xs, sequences, n_prompts):
        rows = X[n0 - 1:len(seq) - 1].sum(axis=1)
        out.append(np.asarray(_head(rows, lm_head, eps=s["eps"], int8=int8)))
    return out
