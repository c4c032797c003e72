"""Plain float32 reference of the DeepSeek-V3 block (multi-head latent
attention, sigmoid-routed experts beside a shared expert) as ONE CHIP'S
SHARE of an expert-parallel deployment, and its int8 control.

Straight ``jax.numpy`` under ``jax.default_matmul_precision("highest")``:
no cache, no kernels, the EXPANDED (published) form of the attention.  It
imports nothing of the program and takes nothing the program made: the
weights are drawn here, from the seed, by the recipe the configuration
file states (``"weights"``) — normal / sqrt(fan_in) per matrix, norms 1,
the router's bias normal / 100 (a selection bias that balances load is
small beside the scores' spread: at / 10 the bias, not the token, chose the
experts, and how many of the experts held here got rows followed the seed),
rounded once to the serving dtype — one
layer at a time, upcast, used and dropped (an expert layer's share is
3.5 GB in float32).  A routed expert's matrices derive from its GLOBAL
id, so the shares of a layer tile the uncut layer.

The equations (DeepSeek-V3 technical report; ``modeling_deepseek.py`` of
the ``deepseek_v3`` model type).  Per layer ``x += W_o . MLA(rms(x))``,
``x += FFN(rms(x))``; final RMSNorm; head over the vocabulary rows held.

* MLA: ``c_q = rms(h W_qa)``; ``[q_nope | q_rope]_h = c_q W_qb``;
  ``[c_kv | k_r] = h W_kva``, ``c_kv = rms(c_kv)``; RoPE (rotate-half
  pairing; with seeded weights the published interleaved pairing is a
  column permutation) on ``q_rope`` and on the one ``k_r`` all heads
  share, YaRN inverse frequencies (:func:`yarn_inv_freq`), cos/sin factor
  ``mscale(f, mscale) / mscale(f, mscale_all_dim)``; ``[k_nope | v]_h =
  c_kv W_kvb``; ``score = (q_nope.k_nope + q_rope.k_r) . s``, ``s =
  (nope + rope)^-0.5 . mscale(f, mscale_all_dim)^2``; causal softmax;
  ``o_h = P v_h``; ``W_o`` over the heads' ``v_head_dim`` outputs.
* Router (``noaux_tc``): ``s = sigmoid(h W_r)`` in float32; ``s' = s +
  b``; a group's score is the sum of its two best ``s'``; the best
  ``topk_group`` groups stay; the best ``num_experts_per_tok`` experts of
  those by ``s'``; weights ``w_i = scale . s_i / (sum s + 1e-20)`` from
  ``s`` WITHOUT ``b``.  ``FFN(h) = shared(h) + sum over the chosen experts
  HELD HERE of w_i E_i(h)``, ``E(h) = W_d(silu(W_g h) * W_u h)``.  What the
  absent experts would add is left out, and that partial result goes on.
* Leading layers (``first_k_dense_replace``) carry a dense SwiGLU MLP.
* Not run: the multi-token-prediction module (``num_nextn_predict_layers``)
  — it adds nothing to the next-token logits.

``forward_logits`` is teacher-forced like ``llama_dense``'s.  With
``int8=True`` every matmul operand and the latent cache rows go through
symmetric int8: the control ``correct`` has to reject.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 256      # query rows per attention block: 64 heads of scores
T_BLOCK = 1024     # sequences are padded to multiples of this: few shapes


def weight_key(seed: int):
    """The key all weights derive from.  Seeds may pass 2**31: the low 31
    bits seed the key and the rest is folded in."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def sizes(cfg: dict) -> dict:
    """The share's sizes from the configuration file's keys.  In a
    share's file ``n_routed_experts`` counts the experts HELD and
    ``vocab_size`` the rows held; ``share`` gives the router's published
    width and the first expert id held."""
    share = cfg.get("share", {})
    held = cfg["n_routed_experts"]
    rs = cfg.get("rope_scaling") or None
    return dict(
        D=cfg["hidden_size"], L=cfg["num_hidden_layers"],
        H=cfg["num_attention_heads"], V=cfg["vocab_size"],
        rq=cfg["q_lora_rank"], R=cfg["kv_lora_rank"],
        dn=cfg["qk_nope_head_dim"], dr=cfg["qk_rope_head_dim"],
        dv=cfg["v_head_dim"], F=cfg["intermediate_size"],
        Fe=cfg["moe_intermediate_size"],
        E=share.get("experts_total", held), held=held,
        offset=share.get("expert_offset", 0),
        shared=cfg.get("n_shared_experts") or 0,
        dense=cfg["first_k_dense_replace"], groups=cfg["n_group"],
        topk_group=cfg["topk_group"], topk=cfg["num_experts_per_tok"],
        scale=float(cfg["routed_scaling_factor"]),
        norm=bool(cfg["norm_topk_prob"]), theta=float(cfg["rope_theta"]),
        yarn=None if rs is None else (
            float(rs["factor"]), int(rs["original_max_position_embeddings"]),
            float(rs["beta_fast"]), float(rs["beta_slow"]),
            float(rs.get("mscale", 1.0)),
            float(rs.get("mscale_all_dim", 0.0))),
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

def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_inv_freq(dr: int, theta: float, yarn) -> np.ndarray:
    """Inverse frequencies of the ``dr / 2`` rotary pairs.  YaRN: pair i
    is interpolated (``/ factor``) by the share ``ramp(i)`` and
    extrapolated by the rest; the ramp rises linearly from the pair that
    turns ``beta_fast`` times over the original context to the one that
    turns ``beta_slow`` times."""
    extra = 1.0 / (theta ** (np.arange(0, dr, 2, dtype=np.float64) / dr))
    if yarn is None:
        return extra.astype(np.float32)
    factor, orig, fast, slow, _, _ = yarn

    def pair_of(turns):
        return dr * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(theta))

    lo = max(math.floor(pair_of(fast)), 0)
    hi = min(math.ceil(pair_of(slow)), dr - 1)
    ramp = np.clip((np.arange(dr // 2) - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return (extra / factor * ramp + extra * (1.0 - ramp)).astype(np.float32)


def _rope(x, pos, inv_freq, cs):
    """x [T, heads, dr], pos [T]: rotate-half pairing."""
    ang = pos[:, None].astype(jnp.float32) * inv_freq[None, :]
    cos, sin = (jnp.cos(ang) * cs)[:, None, :], (jnp.sin(ang) * cs)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


# -- the block ---------------------------------------------------------------------

def mla(h, w, s: dict, int8: bool):
    """Latent attention over a whole sequence h [T, D] float32 (T a
    multiple of Q_BLOCK), expanded form -> [T, H * dv]."""
    T = h.shape[0]
    H, R, dn, dr, dv = s["H"], s["R"], s["dn"], s["dr"], s["dv"]
    pos = jnp.arange(T, dtype=jnp.int32)
    inv_freq = jnp.asarray(yarn_inv_freq(dr, s["theta"], s["yarn"]))
    cs, sm = 1.0, (dn + dr) ** -0.5
    if s["yarn"] is not None:
        factor, _, _, _, m, m_all = s["yarn"]
        cs = _mscale(factor, m) / _mscale(factor, m_all)
        sm *= _mscale(factor, m_all) ** 2
    c_q = _rms(_mm(h, w["wq_a"], int8), s["eps"])
    q = _mm(c_q, w["wq_b"], int8).reshape(T, H, dn + dr)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], pos, inv_freq, cs)
    ckv = _mm(h, w["wkv_a"], int8)
    c_kv = _rms(ckv[:, :R], s["eps"])
    k_r = _rope(ckv[:, None, R:], pos, inv_freq, cs)[:, 0]    # [T, dr]
    if int8:                       # the int8 latent pool: per cached row
        row = _q8(jnp.concatenate([c_kv, k_r], -1), -1)
        c_kv, k_r = row[:, :R], row[:, R:]
    kv = _mm(c_kv, w["wkv_b"], int8).reshape(T, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]

    def block(qn, qr, q0):
        sc = (jnp.einsum("qhd,khd->hqk", qn, k_nope)
              + jnp.einsum("qhd,kd->hqk", qr, k_r)) * sm
        qpos = q0 + jnp.arange(qn.shape[0])
        sc = jnp.where(pos[None, None, :] <= qpos[None, :, None], sc,
                       -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)

    nb = T // Q_BLOCK
    o = jax.lax.map(lambda a: block(*a),
                    (q_nope.reshape(nb, Q_BLOCK, H, dn),
                     q_rope.reshape(nb, Q_BLOCK, H, dr),
                     jnp.arange(nb) * Q_BLOCK))
    return o.reshape(T, H * dv)


def swiglu(h, wg, wu, wd, int8: bool):
    return _mm(jax.nn.silu(_mm(h, wg, int8)) * _mm(h, wu, int8), wd, int8)


def route(h, w, s: dict, int8: bool):
    """-> (chosen [T, E] bool, weight [T, E] float32, zero off the
    chosen): ``noaux_tc`` over all E experts."""
    T, E, G = h.shape[0], s["E"], s["groups"]
    sc = jax.nn.sigmoid(_mm(h, w["router"], int8))
    sb = sc + w["router_bias"]
    per = E // G
    best2 = -jnp.sort(-sb.reshape(T, G, per), axis=-1)[..., :min(2, per)]
    gscore = best2.sum(-1)                                      # [T, G]
    g_rank = jnp.argsort(jnp.argsort(-gscore, axis=-1, stable=True), axis=-1)
    keep = jnp.repeat(g_rank < s["topk_group"], per, axis=1)   # [T, E]
    sb = jnp.where(keep, sb, -jnp.inf)
    e_rank = jnp.argsort(jnp.argsort(-sb, axis=-1, stable=True), axis=-1)
    chosen = e_rank < s["topk"]
    wt = jnp.where(chosen, sc, 0.0)
    if s["norm"]:
        wt = wt / (wt.sum(-1, keepdims=True) + 1e-20)
    return chosen, wt * s["scale"]


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


def ffn(h, w, s: dict, int8: bool):
    if "router" not in w:
        return swiglu(h, w["wgate"], w["wup"], w["wdown"], int8)
    out = routed_share(h, w, s, int8)
    if s["shared"]:
        out = out + swiglu(h, w["wgate"], w["wup"], w["wdown"], int8)
    return out


def _static(s: dict) -> tuple:
    return tuple(sorted(s.items()))


@functools.partial(jax.jit, static_argnames=("st", "int8"))
def _layer(x, w, *, st, int8):
    with jax.default_matmul_precision("highest"):
        s = dict(st)
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        x = x + _mm(mla(_rms(x, s["eps"]), w, s, int8), w["wo"], int8)
        return x + ffn(_rms(x, s["eps"]), w, s, int8)


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
        xs = [_layer(x, w, st=st, int8=int8) for x in xs]
        del w
    lm_head = draw_lm_head(cfg, seed, dtype)
    out = []
    for x, seq, n0 in zip(xs, sequences, n_prompts):
        rows = x[n0 - 1:len(seq) - 1]
        out.append(np.asarray(_head(rows, lm_head, eps=s["eps"], int8=int8)))
    return out
