"""What one decode step, and one prefill chunk's scan, NEED of the
decoder-hybrid-decoder family (configs with ``"reference": "ssm_yoco"``),
from its shapes alone — the twin of ``shapes.py`` for the ``phi4flash``
block held whole on a chip.

"Needs" is the algorithm's minimum on this chip, whatever implements it:
every weight read once a step whatever the batch (the embedding is the
head: counted once, and a row of it a sequence for the token's embedding);
the cached K and V rows a query may SEE — the ONE full-attention cache once
for each of its readers (the full layer and every cross-attention layer), a
window layer's last ``sliding_window`` positions a row and never more than
its context — at the bytes the heads hold (64-wide heads: no padded lane,
no doubled product); each state-space layer's state read and written; each
matmul's multiply-adds.  The Mamba-1 sizes come from the file's
``assumed``.
"""

from __future__ import annotations

from benchmarks.reference.ssm_yoco import layer_kinds  # the file's split


def _sizes(cfg: dict) -> dict:
    a = cfg["assumed"]
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    kinds = layer_kinds(cfg["num_hidden_layers"])
    return dict(
        L=cfg["num_hidden_layers"], D=D, V=cfg["vocab_size"], H=H,
        Hkv=cfg["num_key_value_heads"], hd=D // H,
        F=cfg["intermediate_size"], W=cfg["sliding_window"],
        N=a["d_state"], K=a["d_conv"], E=a["expand"] * D, R=a["dt_rank"],
        n={k: kinds.count(k) for k in ("ssm", "window", "full", "gmu",
                                       "cross")})


def mixer_params(cfg: dict, kind: str) -> int:
    """One layer's mixer, by kind."""
    s = _sizes(cfg)
    D, E, N, R, K = s["D"], s["E"], s["N"], s["R"], s["K"]
    q, kv = s["H"] * s["hd"], s["Hkv"] * s["hd"]
    return {"window": 2 * D * q + 2 * D * kv, "full": 2 * D * q + 2 * D * kv,
            "cross": 2 * D * q, "gmu": 2 * D * E,
            "ssm": (2 * D * E + E * K + E + E * (R + 2 * N) + R * E + E
                    + E * N + E + E * D)}[kind]


def params_total(cfg: dict) -> int:
    """Every parameter of the model, the tied embedding counted once."""
    s = _sizes(cfg)
    return (sum(n * mixer_params(cfg, k) for k, n in s["n"].items())
            + s["L"] * (3 * s["D"] * s["F"] + 4 * s["D"])
            + s["V"] * s["D"] + 2 * s["D"])


def kv_bytes_per_token_layer(cfg: dict, itemsize: int = 2) -> int:
    """K and V rows of one token in one layer, all KV heads."""
    s = _sizes(cfg)
    return 2 * s["Hkv"] * s["hd"] * itemsize


def state_bytes_per_request(cfg: dict, itemsize: int = 2) -> int:
    """The float32 state and the carried convolution inputs of every
    state-space layer."""
    s = _sizes(cfg)
    return s["n"]["ssm"] * (s["E"] * s["N"] * 4
                            + s["E"] * (s["K"] - 1) * itemsize)


def shared_readers(cfg: dict) -> int:
    """Layers that read the ONE full-attention cache: the full layer and
    every cross-attention layer."""
    s = _sizes(cfg)
    return s["n"]["full"] + s["n"]["cross"]


def window_tokens(cfg: dict, *, rows: float, ctx_sum: float) -> float:
    return min(rows * _sizes(cfg)["W"], ctx_sum)


def _paged_calls(cfg, tokens, rows, layers, itemsize):
    """``layers`` paged GQA calls over ``tokens`` cached tokens each: K
    and V once, the queries in, float32 partials (out + lse) out; QK^T and
    PV at the heads' own width."""
    s = _sizes(cfg)
    kv = tokens * layers * kv_bytes_per_token_layer(cfg, itemsize)
    q_in = rows * s["H"] * s["hd"] * itemsize * layers
    out = rows * s["H"] * (s["hd"] + 1) * 4 * layers
    return {"flops": 4 * tokens * s["H"] * s["hd"] * layers,
            "bytes": kv + q_in + out}


def shared_attention(cfg: dict, *, rows: float, ctx_sum: float,
                     itemsize: int = 2) -> dict:
    """The paged calls of one decode step over the shared cache: the whole
    live context, once a reader."""
    return _paged_calls(cfg, ctx_sum, rows, shared_readers(cfg), itemsize)


def window_attention(cfg: dict, *, rows: float, ctx_sum: float,
                     itemsize: int = 2) -> dict:
    """The window layers' paged calls of one decode step."""
    return _paged_calls(cfg, window_tokens(cfg, rows=rows, ctx_sum=ctx_sum),
                        rows, _sizes(cfg)["n"]["window"], itemsize)


def ssm_scan(cfg: dict, *, rows: float = 0.0, ctx_sum: float = 0.0,
             itemsize: int = 2) -> dict:
    """ONE selective-scan call of a prefill chunk (``T`` = the engine's
    ``prefill_chunk`` rows of one request): x and Delta in and y out at T x
    E, B and C at T x N, the state in and out at E x N float32; 6 T E N
    operations (two products and a sum into the state, a product and a sum
    out of it, the decay's product — its exponential runs on the vector
    unit, for which ``peaks.json`` has no peak, so the share reads low by
    construction).  The gate ``z`` is not read by the call (it runs beside
    the output projection) and is not counted."""
    s = _sizes(cfg)
    T, E, N = cfg["engine"]["prefill_chunk"], s["E"], s["N"]
    return {"flops": 6 * T * E * N,
            "bytes": 3 * T * E * itemsize + 2 * T * N * itemsize
            + 2 * E * N * 4}


def decode_step(cfg: dict, *, rows: float, ctx_sum: float,
                itemsize: int = 2) -> dict:
    """One decode step of ``rows`` live sequences whose contexts sum to
    ``ctx_sum`` tokens: every weight once (the embedding as the head, and a
    row of it a sequence), the cache read by layer kind, the cache rows
    written (window layers and the full layer), the state read and
    written, the logits out."""
    s = _sizes(cfg)
    D, V, E, N = s["D"], s["V"], s["E"], s["N"]
    shared = shared_attention(cfg, rows=rows, ctx_sum=ctx_sum,
                              itemsize=itemsize)
    win = window_attention(cfg, rows=rows, ctx_sum=ctx_sum, itemsize=itemsize)
    per_tok = kv_bytes_per_token_layer(cfg, itemsize)
    kv = (ctx_sum * shared_readers(cfg)
          + window_tokens(cfg, rows=rows, ctx_sum=ctx_sum) * s["n"]["window"]
          ) * per_tok
    kv_write = rows * (s["n"]["window"] + s["n"]["full"]) * per_tok
    state = rows * state_bytes_per_request(cfg, itemsize) * 2
    matmul = params_total(cfg)          # the head's product counts V x D
    return {"flops": (2 * rows * matmul + shared["flops"] + win["flops"]
                      + 6 * rows * s["n"]["ssm"] * E * N),
            "bytes": params_total(cfg) * itemsize + rows * D * itemsize
            + kv + kv_write + state + rows * V * 4}


FUNCTIONS = {"shared_attention": shared_attention,
             "window_attention": window_attention, "ssm_scan": ssm_scan,
             "decode_step": decode_step}
