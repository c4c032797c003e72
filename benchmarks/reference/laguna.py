"""Plain float32 reference of the ``laguna`` block — grouped-query attention
whose kind AND query-head count differ by layer (sliding-window layers of
72 heads beside full ones of 48, over the same 8 KV heads), a per-head
output gate, a RoPE a kind (theta and rotary width), a dense lead layer,
sigmoid-routed small experts beside a shared one — as ONE CHIP'S SHARE of
an expert-parallel deployment, and its int8 control.

Straight ``jax.numpy`` under ``jax.default_matmul_precision("highest")``:
no cache, no kernels, no batching, attention in blocks of query rows so
that a 10k-token sequence fits beside nothing else.  It imports nothing of
the program and takes nothing the program made: the weights are drawn
here, from the seed, by the recipe the configuration file states
(``"weights"``) — normal / sqrt(fan_in) per matrix (embedding fan_in 1),
the router's bias normal / 100, norms 1, rounded once to the serving dtype
— one layer at a time, upcast, used and dropped.  An expert's matrices
derive from its GLOBAL id, so the shares of a layer tile the uncut layer.

The equations (the published ``config.json`` of ``model_type: laguna``;
what it has no key for is ASSUMED and listed in the configuration file).
Layer ``l`` of kind ``t = layer_types[l]`` with ``H_l =
num_attention_heads_per_layer[l]`` query heads over ``Hkv`` KV heads of
``d = head_dim``; RMSNorm eps ``rms_norm_eps``; no bias anywhere:

1. ``h = rms(x) . attn_norm``; ``q = h W_q,l`` -> ``[T, H_l, d]``; ``k = h
   W_k``, ``v = h W_v`` -> ``[T, Hkv, d]``; with ``gating: per-head``,
   ``g = sigmoid(h W_g,l)`` -> ``[T, H_l]`` (``W_g,l`` ``[D, H_l]``).
2. ASSUMED: q and k RMS-normed a head over ``d`` with a learned weight,
   before RoPE (the key lineage's attention always has it).
3. RoPE, rotate-half, over the FIRST ``d . partial_rotary_factor`` lanes
   of a head (ASSUMED: which lanes), the rest unrotated.
   ``full_attention``: YaRN's blend over THOSE ``r`` lanes
   (``swa_moe.inv_freq(r, theta, yarn)``: the sibling reference's
   docstring has the formula), cos and sin times ``attention_factor``.
   ``sliding_attention``: plain, its own theta.
4. ``o[t, i] = sum_j softmax_j(q[t, i] . k[j, i // (H_l / Hkv)] /
   sqrt(d)) v[j, i // (H_l / Hkv)]`` over ``j <= t``, and ``t - j <
   sliding_window`` on a window layer; float32 softmax.
5. ``x += ((g[..., None] * o).reshape(T, H_l . d)) W_o,l`` (ASSUMED: the
   gate's place — after attention, before ``W_o`` — and its sigmoid).
6. ``h2 = rms(x) . mlp_norm``.  ``mlp_layer_types[l] == "dense"``: ``x +=
   (silu(h2 W_gate) * (h2 W_up)) W_down`` at ``intermediate_size``.
   ``"sparse"``: ``s = sigmoid(h2 W_r)`` over all experts in float32; the
   ``num_experts_per_tok`` largest of ``s + b`` (``b``: the selection bias,
   ASSUMED as are the sigmoid and the absence of groups; ties to the lower
   id); ``w_e = moe_routed_scaling_factor . s_e / sum of the chosen s``;
   ``x += sum over the chosen experts HELD HERE of w_e . E_e(h2) +
   S(h2)``, ``E_e`` and the shared expert ``S`` SwiGLU at
   ``moe_intermediate_size`` / ``shared_expert_intermediate_size``
   (ASSUMED: the shared expert added ungated).
7. ``logits = (rms(x) . final_norm) @ lm_head``, untied, over the rows of
   the vocabulary held here.

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

# the plain pieces the two blocks of this family share, from the sibling
# reference (as ``mla_dsa_moe_share`` takes ``mla_moe_share``'s): the key,
# the draws, int8, RMSNorm, SwiGLU, the head, YaRN's frequencies over the
# lanes that rotate
from benchmarks.reference.swa_moe import (
    _EXPERT_MATRICES,
    FULL_KIND,
    WINDOW_KIND,
    _draw,
    _draw_experts,
    _head,
    _mm,
    _q8,
    _rms,
    _static,
    inv_freq,
    swiglu,
    weight_key,
)

Q_BLOCK = 256      # query rows per attention block
T_BLOCK = 1024     # sequences are padded to multiples of this: few shapes

__all__ = ["FULL_KIND", "WINDOW_KIND", "forward_logits", "weight_key"]


def _yarn(p: dict):
    if p.get("rope_type", "default") != "yarn":
        return None
    return (float(p["factor"]), int(p["original_max_position_embeddings"]),
            float(p["beta_fast"]), float(p["beta_slow"]),
            float(p["attention_factor"]))


def sizes(cfg: dict) -> dict:
    """The sizes from the configuration file's keys.  ``num_experts``
    counts the experts HELD; ``share`` (optional) gives the router's
    published width and the first expert id held."""
    share = cfg.get("share", {})
    held = cfg["num_experts"]
    L = cfg["num_hidden_layers"]
    rp = cfg["rope_parameters"]
    # (theta, lanes that rotate, yarn) of each layer kind
    rope = {kind: (float(p["rope_theta"]),
                   int(cfg["head_dim"] * float(p.get("partial_rotary_factor",
                                                     1.0))), _yarn(p))
            for kind, p in rp.items()}
    return dict(
        D=cfg["hidden_size"], L=L, Hkv=cfg["num_key_value_heads"],
        heads=tuple(cfg.get("num_attention_heads_per_layer")
                    or [cfg["num_attention_heads"]] * L),
        hd=cfg["head_dim"], V=cfg["vocab_size"],
        F=cfg["intermediate_size"], Fe=cfg["moe_intermediate_size"],
        Fs=int(cfg.get("shared_expert_intermediate_size") or 0),
        E=share.get("experts_total", held), held=held,
        offset=share.get("expert_offset", 0),
        topk=cfg["num_experts_per_tok"], norm=bool(cfg["norm_topk_prob"]),
        scaling=float(cfg.get("moe_routed_scaling_factor", 1.0)),
        gated=cfg.get("gating") == "per-head",
        window=int(cfg.get("sliding_window") or 0),
        kinds=tuple(cfg["layer_types"]),
        mlp=tuple(cfg.get("mlp_layer_types") or ["sparse"] * L),
        rope=tuple(sorted(rope.items())), eps=float(cfg["rms_norm_eps"]))


# -- the seeded weights --------------------------------------------------------
# name -> (subkey index, fan_in, shape); subkeys: split(layer_key, 16).

def _mlp(D: int, F: int) -> dict:
    return {"wgate": (5, D, (D, F)), "wup": (6, D, (D, F)),
            "wdown": (7, F, (F, D))}


def _layer_matrices(s: dict, li: int) -> dict:
    D, H = s["D"], s["heads"][li]
    q, kv = H * s["hd"], s["Hkv"] * s["hd"]
    m = {"wq": (0, D, (D, q)), "wk": (1, D, (D, kv)),
         "wv": (2, D, (D, kv)), "wo": (3, q, (q, D))}
    if s["gated"]:
        m["wg"] = (4, D, (D, H))
    if s["mlp"][li] == "dense":
        m.update(_mlp(D, s["F"]))
    else:
        m["router"] = (8, D, (D, s["E"]))
        if s["Fs"]:
            m.update({"s_" + n: v for n, v in _mlp(D, s["Fs"]).items()})
    return m


def _keys(s: dict, seed: int):
    return jax.random.split(weight_key(seed), 2 + s["L"])


def draw_layer(cfg: dict, seed: int, li: int, dtype=jnp.bfloat16) -> dict:
    s = sizes(cfg)
    lk = jax.random.split(_keys(s, seed)[2 + li], 16)
    w = {n: _draw(lk[j], jnp.float32(math.sqrt(fi)), shape=sh, dtype=dtype)
         for n, (j, fi, sh) in _layer_matrices(s, li).items()}
    if s["mlp"][li] == "dense":
        return w
    w["router_bias"] = _draw(lk[9], jnp.float32(100.0), shape=(s["E"],),
                             dtype=dtype)
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


# -- RoPE ------------------------------------------------------------------------

def rope_of(s: dict, kind: str) -> tuple:
    """(inverse frequencies over the lanes that rotate, cos / sin factor)
    of a layer kind."""
    theta, lanes, yarn = dict(s["rope"])[kind]
    return inv_freq(lanes, theta, yarn), (1.0 if yarn is None else yarn[4])


def _rope(x, pos, freqs, cs):
    """x [T, heads, d], pos [T]: the first ``2 . len(freqs)`` lanes rotate
    (rotate-half within them), the rest pass."""
    r = 2 * freqs.shape[0]
    ang = pos[:, None].astype(jnp.float32) * freqs[None, :]
    cos, sin = (jnp.cos(ang) * cs)[:, None, :], (jnp.sin(ang) * cs)[:, None, :]
    x1, x2 = jnp.split(x[..., :r], 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                            x[..., r:]], -1)


# -- the block ---------------------------------------------------------------------

def attention(h, w, s: dict, kind: str, int8: bool):
    """Gated GQA over a whole sequence h [T, D] float32 (T a multiple of
    Q_BLOCK) -> [T, H * hd], H read off the layer's ``wq``."""
    T = h.shape[0]
    Hkv, hd = s["Hkv"], s["hd"]
    H = w["wq"].shape[1] // hd
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
        return o.reshape(-1, H, hd)

    nb = T // Q_BLOCK
    o = jax.lax.map(lambda a: block(*a), (q.reshape(nb, Q_BLOCK, H, hd),
                                          jnp.arange(nb) * Q_BLOCK))
    o = o.reshape(T, H, hd)
    if s["gated"]:
        o = jax.nn.sigmoid(_mm(h, w["wg"], int8))[..., None] * o
    return o.reshape(T, H * hd)


def route(h, w, s: dict, int8: bool):
    """-> (chosen [T, E] bool, weight [T, E] float32, zero off the
    chosen): sigmoid scores over all E experts, the ``topk`` largest of
    score + bias (ties to the lower id; one group), the chosen SCORES
    renormalised, then scaled."""
    sc = jax.nn.sigmoid(_mm(h, w["router"], int8))
    biased = sc + w["router_bias"]
    rank = jnp.argsort(jnp.argsort(-biased, axis=-1, stable=True), axis=-1)
    chosen = rank < s["topk"]
    wt = jnp.where(chosen, sc, 0.0)
    if s["norm"]:
        wt = wt / wt.sum(-1, keepdims=True)
    return chosen, wt * s["scaling"]


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


def mlp(h, w, s: dict, int8: bool):
    """Step 6 of one layer: the dense MLP, or the held experts' part of
    the routed sum + the shared expert."""
    if "router" not in w:
        return swiglu(h, w["wgate"], w["wup"], w["wdown"], int8)
    out = routed_share(h, w, s, int8)
    if "s_wgate" in w:
        out = out + swiglu(h, w["s_wgate"], w["s_wup"], w["s_wdown"], int8)
    return out


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
        return x + mlp(_rms(x, s["eps"]), w, s, int8)


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
