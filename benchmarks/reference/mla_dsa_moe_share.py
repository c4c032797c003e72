"""Plain float32 reference of the ``glm_moe_dsa`` block (GLM-5: multi-head
latent attention read through LEARNED SPARSE ATTENTION, sigmoid-routed
experts beside a shared expert) as ONE CHIP'S SHARE of an expert-parallel
deployment, and its int8 control.

Straight ``jax.numpy`` under ``jax.default_matmul_precision("highest")``:
no cache, no kernels, the EXPANDED (published) form of the attention, the
selection by a plain sort.  It imports nothing of the program and takes
nothing the program made.  What it shares with ``mla_moe_share`` (the
DeepSeek-V3 block's reference) it imports from there: the seed's key and
the drawing recipe, ``_mm`` / ``_q8`` / ``_rms``, the router and the
expert layer — a share's expert layer is the same in both families.  The
weights' recipe is the one the configuration file states (``"weights"``),
extended to the indexer's three matrices (subkeys 13-15 of a layer's 16;
the index key's LayerNorm has weight 1 and bias 0).

The equations, per layer, ``x`` the RMS-normed input (GLM-5 ``config.json``,
``model_type: glm_moe_dsa``; the attention and indexer as published for the
DeepSeek-V3.2 block it takes over):

* Latent attention: ``c_q = rms(W_qa x)``; ``[q_nope | q_rope]_h = W_qb,h
  c_q`` (192 | 64); ``[c_kv | k_r] = W_kva x`` (512 | 64), ``c_kv =
  rms(c_kv)``; plain RoPE (``rope_parameters.rope_theta``, no scaling) on
  ``q_rope`` and on the one ``k_r`` all heads share, over the INTERLEAVED
  pairs ``(2i, 2i + 1)`` (``rope_interleave``); ``[k_nope | v]_h = W_kvb,h
  c_kv`` (192 | 256); ``score = (q_nope.k_nope + q_rope.k_r) (nope +
  rope)^-1/2``.
* Indexer: ``qI_j = W_iq,j c_q`` for the ``index_n_heads`` heads,
  ``index_head_dim`` wide, RoPE on the first ``qk_rope_head_dim`` columns;
  ``kI_s = LayerNorm(W_ik x_s)`` (with bias), RoPE on its first columns
  likewise; ``w_j = (W_iw x)_j index_n_heads^-1/2``; ``I(t, s) = sum_j
  w_t,j relu(qI_t,j . kI_s)`` for ``s <= t``; ``S_t`` = the ``index_topk``
  highest-scoring ``s`` (ties to the earlier position; all of them while
  ``t < index_topk``).
* Sparse attention: ``o_t,h = sum_{s in S_t} softmax_{s in S_t}(score) v_s,h``
  — here as the causal softmax with everything outside ``S_t`` masked.
* Expert layer, leading dense layers, head: as ``mla_moe_share`` (``n_group
  1``: the group limit keeps every expert).

Departures, each stated in the configuration file: index keys are
float32 here and bfloat16 in the served cache (the published inference
code holds them in FP8 after a Hadamard rotation of q and k; the rotation
is orthogonal and leaves ``qI . kI`` unchanged, so neither is part of the
model); ``assumed``: the LayerNorm's epsilon (1e-6, the published code's)
and the indexer's RoPE base (the attention's); not run: the
multi-token-prediction module.

``forward_logits`` is teacher-forced like ``llama_dense``'s.  With
``int8=True`` every matmul operand, the latent cache rows AND the index
keys go through symmetric int8: the control ``correct`` has to reject.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.mla_moe_share import (
    _draw,
    _draw_experts,
    _head,
    _mm,
    _q8,
    _rms,
    _static,
    ffn,
    weight_key,
)

Q_BLOCK = 256      # query rows per attention block
T_BLOCK = 1024     # sequences are padded to multiples of this: few shapes
LN_EPS = 1e-6      # the index key's LayerNorm (assumed: the published code's)


def sizes(cfg: dict) -> dict:
    """The share's sizes from the configuration file's keys (a share's
    ``n_routed_experts`` / ``vocab_size`` count what is HELD; ``share``
    gives the router's published width and the first expert id held)."""
    share = cfg.get("share", {})
    held = cfg["n_routed_experts"]
    return dict(
        D=cfg["hidden_size"], L=cfg["num_hidden_layers"],
        H=cfg["num_attention_heads"], V=cfg["vocab_size"],
        rq=cfg["q_lora_rank"], R=cfg["kv_lora_rank"],
        dn=cfg["qk_nope_head_dim"], dr=cfg["qk_rope_head_dim"],
        dv=cfg["v_head_dim"], F=cfg["intermediate_size"],
        Fe=cfg["moe_intermediate_size"],
        Hi=cfg["index_n_heads"], Di=cfg["index_head_dim"],
        topk_index=cfg["index_topk"],
        interleave=bool(cfg["rope_interleave"]),
        E=share.get("experts_total", held), held=held,
        offset=share.get("expert_offset", 0),
        shared=cfg.get("n_shared_experts") or 0,
        dense=cfg["first_k_dense_replace"], groups=cfg["n_group"],
        topk_group=cfg["topk_group"], topk=cfg["num_experts_per_tok"],
        scale=float(cfg["routed_scaling_factor"]),
        norm=bool(cfg["norm_topk_prob"]),
        theta=float(cfg["rope_parameters"]["rope_theta"]),
        eps=float(cfg["rms_norm_eps"]))


# -- the seeded weights --------------------------------------------------------
# name -> (subkey index, fan_in, shape); subkeys: split(layer_key, 16).

def _layer_matrices(s: dict, moe: bool) -> dict:
    D, H = s["D"], s["H"]
    F = s["Fe"] * s["shared"] if moe else s["F"]
    m = {
        "wq_a": (0, D, (D, s["rq"])),
        "wq_b": (1, s["rq"], (s["rq"], H * (s["dn"] + s["dr"]))),
        "wkv_a": (2, D, (D, s["R"] + s["dr"])),
        "wkv_b": (3, s["R"], (s["R"], H * (s["dn"] + s["dv"]))),
        "wo": (4, H * s["dv"], (H * s["dv"], D)),
        "idx_wq": (13, s["rq"], (s["rq"], s["Hi"] * s["Di"])),
        "idx_wk": (14, D, (D, s["Di"])),
        "idx_ww": (15, D, (D, s["Hi"])),
    }
    if not moe or s["shared"]:
        m.update(wgate=(5, D, (D, F)), wup=(6, D, (D, F)),
                 wdown=(7, F, (F, D)))
    if moe:
        m["router"] = (8, D, (D, s["E"]))
    return m


_EXPERT_MATRICES = {"e_gate": (10, "D", ("D", "Fe")),
                    "e_up": (11, "D", ("D", "Fe")),
                    "e_down": (12, "Fe", ("Fe", "D"))}


def _keys(s: dict, seed: int):
    return jax.random.split(weight_key(seed), 2 + s["L"])


def draw_layer(cfg: dict, seed: int, li: int, dtype=jnp.bfloat16) -> dict:
    s = sizes(cfg)
    moe = li >= s["dense"]
    lk = jax.random.split(_keys(s, seed)[2 + li], 16)
    w = {n: _draw(lk[j], jnp.float32(math.sqrt(fi)), shape=sh, dtype=dtype)
         for n, (j, fi, sh) in _layer_matrices(s, moe).items()}
    if moe:
        w["router_bias"] = _draw(lk[9], jnp.float32(100.0),
                                 shape=(s["E"],), dtype=dtype)
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


# -- RoPE, LayerNorm -----------------------------------------------------------

def _rope(x, pos, s: dict):
    """x [T, heads, dr], pos [T]: plain RoPE over the interleaved pairs
    (2i, 2i + 1) (``rope_interleave``; over the halves otherwise).  The
    rotated pair is laid out by halves, for q and k alike: every dot
    product is the published one."""
    dr = x.shape[-1]
    inv_freq = 1.0 / (s["theta"] ** (jnp.arange(0, dr, 2,
                                                dtype=jnp.float32) / dr))
    ang = pos[:, None].astype(jnp.float32) * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = ((x[..., 0::2], x[..., 1::2]) if s["interleave"]
              else jnp.split(x, 2, axis=-1))
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer_norm(x):
    """LayerNorm with weight 1 and bias 0 (the recipe's norms)."""
    mu = jnp.mean(x, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(
        jnp.mean(jnp.square(x - mu), -1, keepdims=True) + LN_EPS)


# -- the block -----------------------------------------------------------------

def index_parts(h, c_q, w, s: dict, int8: bool):
    """The indexer's (qI [T, Hi, Di], kI [T, Di], head weights [T, Hi])
    of a whole sequence."""
    T = h.shape[0]
    Hi, Di, dr = s["Hi"], s["Di"], s["dr"]
    pos = jnp.arange(T, dtype=jnp.int32)
    qi = _mm(c_q, w["idx_wq"], int8).reshape(T, Hi, Di)
    qi = jnp.concatenate([_rope(qi[..., :dr], pos, s), qi[..., dr:]], -1)
    ki = _layer_norm(_mm(h, w["idx_wk"], int8))
    ki = jnp.concatenate([_rope(ki[:, None, :dr], pos, s)[:, 0],
                          ki[:, dr:]], -1)
    if int8:                       # the int8 index-key plane: per cached row
        ki = _q8(ki, -1)
    return qi, ki, _mm(h, w["idx_ww"], int8) * Hi ** -0.5


def select(idx, visible, k: int):
    """idx [Q, T] index scores, visible [Q, T] bool -> kept [Q, T] bool:
    each query's ``k`` best visible positions by a plain (stable) sort,
    ties to the earlier position; all of them where fewer are visible."""
    order = jnp.argsort(-jnp.where(visible, idx, -jnp.inf), axis=-1,
                        stable=True)
    rank = jnp.argsort(order, axis=-1)
    return visible & (rank < k)


def mla_dsa(h, w, s: dict, int8: bool, probe=None):
    """Sparse latent attention over a whole sequence h [T, D] float32 (T
    a multiple of Q_BLOCK), expanded form -> [T, H * dv].  ``probe``, a
    list, receives (index scores [T, T], kept [T, T])."""
    T = h.shape[0]
    H, R, dn, dr, dv = s["H"], s["R"], s["dn"], s["dr"], s["dv"]
    pos = jnp.arange(T, dtype=jnp.int32)
    sm = (dn + dr) ** -0.5
    c_q = _rms(_mm(h, w["wq_a"], int8), s["eps"])
    q = _mm(c_q, w["wq_b"], int8).reshape(T, H, dn + dr)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], pos, s)
    ckv = _mm(h, w["wkv_a"], int8)
    c_kv = _rms(ckv[:, :R], s["eps"])
    k_r = _rope(ckv[:, None, R:], pos, s)[:, 0]                 # [T, dr]
    if int8:                       # the int8 latent pool: per cached row
        row = _q8(jnp.concatenate([c_kv, k_r], -1), -1)
        c_kv, k_r = row[:, :R], row[:, R:]
    kv = _mm(c_kv, w["wkv_b"], int8).reshape(T, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    qi, ki, wi = index_parts(h, c_q, w, s, int8)

    def block(qn, qr, qib, wib, q0):
        visible = pos[None, :] <= (q0 + jnp.arange(qn.shape[0]))[:, None]
        idx = jnp.einsum("qh,qhk->qk", wib, jax.nn.relu(
            jnp.einsum("qhd,kd->qhk", qib, ki)))
        kept = select(idx, visible, s["topk_index"])
        sc = (jnp.einsum("qhd,khd->hqk", qn, k_nope)
              + jnp.einsum("qhd,kd->hqk", qr, k_r)) * sm
        sc = jnp.where(kept[None], sc, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)
        return (o, idx, kept) if probe is not None else (o,)

    nb = T // Q_BLOCK
    o, *seen = jax.lax.map(
        lambda a: block(*a),
        (q_nope.reshape(nb, Q_BLOCK, H, dn),
         q_rope.reshape(nb, Q_BLOCK, H, dr),
         qi.reshape(nb, Q_BLOCK, s["Hi"], s["Di"]),
         wi.reshape(nb, Q_BLOCK, s["Hi"]), jnp.arange(nb) * Q_BLOCK))
    if probe is not None:
        probe.append(tuple(a.reshape(T, T) for a in seen))
    return o.reshape(T, H * dv)


@functools.partial(jax.jit, static_argnames=("st", "int8", "probed"))
def _layer(x, w, *, st, int8, probed=False):
    with jax.default_matmul_precision("highest"):
        s = dict(st)
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        probe = [] if probed else None
        x = x + _mm(mla_dsa(_rms(x, s["eps"]), w, s, int8, probe), w["wo"],
                    int8)
        x = x + ffn(_rms(x, s["eps"]), w, s, int8)
        return (x, probe[0]) if probed else x


def forward_logits(cfg: dict, seed: int, sequences: list, n_prompts: list, *,
                   int8: bool = False, dtype=jnp.bfloat16,
                   probe: list | None = None) -> list:
    """Logits at every served position of each sequence (see
    ``llama_dense.forward_logits``: the same contract).  Layers are the
    outer loop: each layer's weights are drawn once, used for every
    sequence and dropped.  ``probe``, a list, receives per layer a list
    over the sequences of (index scores, kept) as numpy arrays, padded
    rows included (tests; small sizes only)."""
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
        if probe is None:
            xs = [_layer(x, w, st=st, int8=int8) for x in xs]
        else:
            got = [_layer(x, w, st=st, int8=int8, probed=True) for x in xs]
            xs = [g[0] for g in got]
            probe.append([tuple(np.asarray(a) for a in g[1]) for g in got])
        del w
    lm_head = draw_lm_head(cfg, seed, dtype)
    out = []
    for x, seq, n0 in zip(xs, sequences, n_prompts):
        rows = x[n0 - 1:len(seq) - 1]
        out.append(np.asarray(_head(rows, lm_head, eps=s["eps"], int8=int8)))
    return out
