"""What one decode step NEEDS of the ``laguna`` block as one chip's share of
an expert-parallel stage (configs with ``"reference": "laguna"``), from its
shapes alone — the twin of ``shapes_swa_moe.py`` for a block whose QUERY
heads differ by layer, with a per-head gate, a dense lead layer and a
shared expert beside the held routed ones.

"Needs" is the algorithm's minimum on this chip: every weight held here
read once a step whatever the batch — of the held experts those that get a
row, counted under EVEN routing (``experts_hit``: 32 (1 - (255/256)^(10
rows)), ~29 of 32 at 64 rows) — the cached K and V rows a query may SEE
(the whole live context on a full layer; on a window layer the last
``sliding_window`` positions a row, never more than its context), each
matmul's multiply-adds.  Counts that depend on a layer's heads are summed
layer by layer (``num_attention_heads_per_layer``); the cache's bytes do
not (8 KV heads x 128 in every layer).
"""

from __future__ import annotations

WINDOW_KIND, FULL_KIND = "sliding_attention", "full_attention"


def _sizes(cfg: dict) -> dict:
    share = cfg.get("share", {})
    held = cfg["num_experts"]
    L = cfg["num_hidden_layers"]
    kinds, mlp = cfg["layer_types"], cfg["mlp_layer_types"]
    return dict(
        L=L, D=cfg["hidden_size"], V=cfg["vocab_size"],
        heads=list(cfg["num_attention_heads_per_layer"]),
        Hkv=cfg["num_key_value_heads"], hd=cfg["head_dim"],
        F=cfg["intermediate_size"], Fe=cfg["moe_intermediate_size"],
        Fs=int(cfg.get("shared_expert_intermediate_size") or 0), held=held,
        E=share.get("experts_total", held),
        topk=cfg["num_experts_per_tok"], W=cfg["sliding_window"],
        gated=cfg.get("gating") == "per-head",
        window=[li for li in range(L) if kinds[li] == WINDOW_KIND],
        full=[li for li in range(L) if kinds[li] == FULL_KIND],
        moe=[li for li in range(L) if mlp[li] == "sparse"],
        dense=[li for li in range(L) if mlp[li] == "dense"])


def attention_params(cfg: dict, li: int) -> int:
    """Layer ``li``'s attention: q, k, v, o at the layer's own heads, the
    gate's matrix and the two head norms."""
    s = _sizes(cfg)
    q, kv = s["heads"][li] * s["hd"], s["Hkv"] * s["hd"]
    gate = s["D"] * s["heads"][li] if s["gated"] else 0
    return s["D"] * q + 2 * s["D"] * kv + q * s["D"] + gate + 2 * s["hd"]


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up, down."""
    s = _sizes(cfg)
    return 3 * s["D"] * s["Fe"]


def shared_params(cfg: dict) -> int:
    s = _sizes(cfg)
    return 3 * s["D"] * s["Fs"]


def dense_params(cfg: dict) -> int:
    """The dense lead layer's MLP."""
    s = _sizes(cfg)
    return 3 * s["D"] * s["F"]


def row_params(cfg: dict, li: int) -> int:
    """What EVERY row multiplies in layer ``li``: attention and gate, and
    the dense MLP or the router and the shared expert."""
    s = _sizes(cfg)
    if li in s["dense"]:
        return attention_params(cfg, li) + dense_params(cfg)
    return attention_params(cfg, li) + s["D"] * s["E"] + shared_params(cfg)


def layer_params_held(cfg: dict, li: int) -> int:
    """Parameters of layer ``li`` held on this chip: :func:`row_params`,
    its two norms and — an expert layer — the router's bias and the held
    routed experts."""
    s = _sizes(cfg)
    own = row_params(cfg, li) + 2 * s["D"]
    if li in s["dense"]:
        return own
    return own + s["E"] + s["held"] * expert_params(cfg)


def params_held(cfg: dict) -> int:
    s = _sizes(cfg)
    return (sum(layer_params_held(cfg, li) for li in range(s["L"]))
            + 2 * s["D"] * s["V"] + s["D"])


def kv_bytes_per_token_layer(cfg: dict, itemsize: int = 2) -> int:
    """K and V rows of one token in one layer, all KV heads."""
    s = _sizes(cfg)
    return 2 * s["Hkv"] * s["hd"] * itemsize


def window_tokens(cfg: dict, *, rows: float, ctx_sum: float) -> float:
    """Cached tokens a window layer's queries see in one step: the last
    ``sliding_window`` a row, never more than there are."""
    return min(rows * _sizes(cfg)["W"], ctx_sum)


def routed_rows(cfg: dict, rows: float) -> float:
    """Rows an expert layer routes to the experts held here, under even
    routing."""
    s = _sizes(cfg)
    return rows * s["topk"] * s["held"] / s["E"]


def experts_hit(cfg: dict, rows: float) -> float:
    """Held experts that get at least one of a step's ``rows . topk``
    assignments when each lands on any of the router's experts alike."""
    s = _sizes(cfg)
    return s["held"] * (1.0 - (1.0 - 1.0 / s["E"]) ** (rows * s["topk"]))


def _paged_calls(cfg, tokens, rows, layers, itemsize):
    """One paged GQA call a layer of ``layers`` over ``tokens`` cached
    tokens each: K and V once, the layer's queries in, float32 partials
    (out + lane-padded lse) out; QK^T and PV at the layer's heads."""
    s = _sizes(cfg)
    heads = sum(s["heads"][li] for li in layers)
    kv = tokens * len(layers) * kv_bytes_per_token_layer(cfg, itemsize)
    q_in = rows * heads * s["hd"] * itemsize
    out = rows * heads * (s["hd"] + 128) * 4
    return {"flops": 4 * tokens * heads * s["hd"], "bytes": kv + q_in + out}


def window_attention(cfg: dict, *, rows: float, ctx_sum: float,
                     itemsize: int = 2) -> dict:
    """The window layers' paged calls of one decode step."""
    return _paged_calls(cfg, window_tokens(cfg, rows=rows, ctx_sum=ctx_sum),
                        rows, _sizes(cfg)["window"], itemsize)


def full_attention(cfg: dict, *, rows: float, ctx_sum: float,
                   itemsize: int = 2) -> dict:
    """The full layers' paged calls of one decode step."""
    return _paged_calls(cfg, ctx_sum, rows, _sizes(cfg)["full"], itemsize)


def expert_ffn(cfg: dict, *, rows: float, ctx_sum: float = 0.0,
               itemsize: int = 2) -> dict:
    """The grouped GEMMs of one decode step (the expert layers have them):
    the weights of the held experts that get a row, once; the rows routed
    here in and out."""
    s = _sizes(cfg)
    n = len(s["moe"])
    r = routed_rows(cfg, rows)
    w = n * experts_hit(cfg, rows) * expert_params(cfg)
    acts = n * r * (s["D"] + 2 * s["Fe"] + s["Fe"] + s["D"])
    return {"flops": 2 * r * expert_params(cfg) * n,
            "bytes": (w + acts) * itemsize}


def decode_step(cfg: dict, *, rows: float, ctx_sum: float,
                itemsize: int = 2) -> dict:
    """One decode step of ``rows`` live sequences whose contexts sum to
    ``ctx_sum`` tokens: every weight held here once (the embedding by the
    row; of the routed experts those hit), the cache read by layer kind,
    the cache write, the logits out."""
    s = _sizes(cfg)
    L, D, V = s["L"], s["D"], s["V"]
    idle = len(s["moe"]) * (s["held"] - experts_hit(cfg, rows)) \
        * expert_params(cfg)
    w = sum(layer_params_held(cfg, li) for li in range(L)) + D * V + D - idle
    per_row = sum(row_params(cfg, li) for li in range(L)) + D * V
    win = window_attention(cfg, rows=rows, ctx_sum=ctx_sum, itemsize=itemsize)
    full = full_attention(cfg, rows=rows, ctx_sum=ctx_sum, itemsize=itemsize)
    ffn = expert_ffn(cfg, rows=rows, itemsize=itemsize)
    per_tok = kv_bytes_per_token_layer(cfg, itemsize)
    kv = (window_tokens(cfg, rows=rows, ctx_sum=ctx_sum) * len(s["window"])
          + ctx_sum * len(s["full"])) * per_tok
    kv_write = rows * L * per_tok
    return {"flops": (2 * rows * per_row + ffn["flops"] + win["flops"]
                      + full["flops"]),
            "bytes": w * itemsize + rows * D * itemsize + kv + kv_write
            + rows * V * 4}


FUNCTIONS = {"window_attention": window_attention,
             "full_attention": full_attention, "expert_ffn": expert_ffn,
             "decode_step": decode_step}
