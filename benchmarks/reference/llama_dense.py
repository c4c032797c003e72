"""Plain float32 reference of the dense GQA + SwiGLU decoder block
(Llama / Mistral family), and its int8 control.

Straight ``jax.numpy`` under ``jax.default_matmul_precision("highest")``.
It imports nothing of the program and takes nothing the program made: the
weights are drawn here, from the seed, by the recipe the benchmark states
for this family (``configs/*.json`` "weights") — a normal draw over
sqrt(fan_in) per matrix, rounded once to the serving dtype — one layer at
a time, upcast, used and dropped, so the reference fits in what the freed
engine leaves.

The equations, as published for the family: token embedding; per layer
RMSNorm, Q/K/V projections, rotary embedding on Q and K (rotate-half
pairing, ``rope_theta``), causal grouped-query attention scaled by
1/sqrt(head_dim), output projection, residual; RMSNorm, SwiGLU
(silu(gate) * up, down), residual; final RMSNorm; vocabulary projection.
No sliding window (``sliding_window`` null in the v0.2 config).

``forward_logits`` is teacher-forced: one pass over a prompt with the
tokens that were served after it gives the reference's logits at every
served position.  With ``int8=True`` every matmul operand and the K/V
rows go through symmetric int8 (weights per output channel, activations
and K/V per row): the control — the precision below bf16 that a later PR
would be tempted by — which ``correct`` has to reject.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512      # query rows per attention block: scores stay < 200 MB


def weight_key(seed: int):
    """The key all weights derive from.  Seeds may pass 2**31: the low 31
    bits seed the key and the rest is folded in."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def _sizes(cfg: dict) -> dict:
    hd = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    return dict(D=cfg["hidden_size"], L=cfg["num_hidden_layers"],
                H=cfg["num_attention_heads"], KV=cfg["num_key_value_heads"],
                hd=hd, F=cfg["intermediate_size"], V=cfg["vocab_size"],
                theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]))


# name -> (subkey index, fan_in, shape): the recipe of the family's
# seeded weights.  Subkeys: split(layer_key, 7).
def _layer_matrices(s: dict) -> dict:
    D, H, KV, hd, F = s["D"], s["H"], s["KV"], s["hd"], s["F"]
    return {
        "wq": (0, D, (D, H * hd)), "wk": (5, D, (D, KV * hd)),
        "wv": (2, D, (D, KV * hd)), "wo": (1, H * hd, (H * hd, D)),
        "wgate": (3, D, (D, F)), "wup": (4, D, (D, F)),
        "wdown": (6, F, (F, D)),
    }


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _draw(key, denom, *, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) / denom).astype(dtype)


def draw_layer(cfg: dict, seed: int, li: int, dtype=jnp.bfloat16) -> dict:
    s = _sizes(cfg)
    keys = jax.random.split(weight_key(seed), 2 + s["L"])
    lk = jax.random.split(keys[2 + li], 7)
    return {name: _draw(lk[j], jnp.float32(math.sqrt(fan_in)), shape=shape,
                        dtype=dtype)
            for name, (j, fan_in, shape) in _layer_matrices(s).items()}


def draw_embed(cfg: dict, seed: int, dtype=jnp.bfloat16):
    s = _sizes(cfg)
    keys = jax.random.split(weight_key(seed), 2 + s["L"])
    return _draw(keys[0], jnp.float32(1.0), shape=(s["V"], s["D"]),
                 dtype=dtype)


def draw_lm_head(cfg: dict, seed: int, dtype=jnp.bfloat16):
    s = _sizes(cfg)
    keys = jax.random.split(weight_key(seed), 2 + s["L"])
    return _draw(keys[1], jnp.float32(math.sqrt(s["D"])),
                 shape=(s["D"], s["V"]), dtype=dtype)


# -- int8, for the control ---------------------------------------------------

def _q8(x, axis):
    """Symmetric int8 through absmax over ``axis``, returned dequantized:
    int8 x int8 products are integers that float32 sums exactly enough
    (|sum| < 2**28 at these widths), so the value is the int8 GEMM's."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _mm(x, w, int8: bool):
    if int8:
        x, w = _q8(x, -1), _q8(w, 0)    # per token row, per output channel
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)


def _rope(x, pos, theta):
    """x [T, heads, hd], pos [T]: rotate-half pairing."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("H", "KV", "hd", "theta", "eps",
                                             "int8"))
def _layer(x, w, *, H, KV, hd, theta, eps, int8):
    """One block over a whole sequence x [T, D] float32 (T a multiple of
    Q_BLOCK; rows past the real length are causal-masked away from every
    real row)."""
    with jax.default_matmul_precision("highest"):
        T = x.shape[0]
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        pos = jnp.arange(T, dtype=jnp.int32)
        h = _rms(x, eps)
        q = _rope(_mm(h, w["wq"], int8).reshape(T, H, hd), pos, theta)
        k = _rope(_mm(h, w["wk"], int8).reshape(T, KV, hd), pos, theta)
        v = _mm(h, w["wv"], int8).reshape(T, KV, hd)
        if int8:                       # the int8 KV pool: per (row, head)
            k, v = _q8(k, -1), _q8(v, -1)
        g = H // KV
        kk = jnp.repeat(k, g, axis=1)  # [T, H, hd]
        vv = jnp.repeat(v, g, axis=1)

        def block(qb, q0):
            sc = jnp.einsum("qhd,khd->hqk", qb, kk) / math.sqrt(hd)
            qpos = q0 + jnp.arange(qb.shape[0])
            sc = jnp.where(pos[None, None, :] <= qpos[None, :, None], sc,
                           -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), vv)

        nb = T // Q_BLOCK
        o = jax.lax.map(lambda a: block(a[0], a[1]),
                        (q.reshape(nb, Q_BLOCK, H, hd),
                         jnp.arange(nb) * Q_BLOCK))
        x = x + _mm(o.reshape(T, H * hd), w["wo"], int8)
        h = _rms(x, eps)
        act = jax.nn.silu(_mm(h, w["wgate"], int8)) * _mm(h, w["wup"], int8)
        return x + _mm(act, w["wdown"], int8)


@functools.partial(jax.jit, static_argnames=("eps", "int8"))
def _head(x, lm_head, *, eps, int8):
    with jax.default_matmul_precision("highest"):
        return _mm(_rms(x, eps), lm_head.astype(jnp.float32), int8)


def forward_logits(cfg: dict, seed: int, sequences: list, n_prompts: list, *,
                   int8: bool = False, dtype=jnp.bfloat16) -> list:
    """Logits at every served position of each sequence.

    ``sequences[i]`` is prompt + served tokens (int array), ``n_prompts[i]``
    the prompt's length.  Returns, per sequence, float32 logits
    [n_served, vocab]: row j is the distribution the j-th served token was
    chosen from (the model's output at position n_prompt - 1 + j).
    Layers are the outer loop: each layer's weights are drawn once, used
    for every sequence and dropped.
    """
    s = _sizes(cfg)
    embed = draw_embed(cfg, seed, dtype)
    xs = []
    for seq in sequences:
        seq = np.asarray(seq, np.int32)[:-1]     # the last token feeds nothing
        T = -(-len(seq) // Q_BLOCK) * Q_BLOCK
        ids = np.zeros((T,), np.int32)
        ids[:len(seq)] = seq
        xs.append(embed[jnp.asarray(ids)].astype(jnp.float32))
    del embed
    kw = dict(H=s["H"], KV=s["KV"], hd=s["hd"], theta=s["theta"],
              eps=s["eps"], int8=int8)
    for li in range(s["L"]):
        w = draw_layer(cfg, seed, li, dtype)
        xs = [_layer(x, w, **kw) for x in xs]
        del w
    lm_head = draw_lm_head(cfg, seed, dtype)
    out = []
    for x, seq, n0 in zip(xs, sequences, n_prompts):
        rows = x[n0 - 1:len(seq) - 1]
        out.append(np.asarray(_head(rows, lm_head, eps=s["eps"], int8=int8)))
    return out
