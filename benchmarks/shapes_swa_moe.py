"""What one decode step NEEDS of the window + full attention,
softmax-routed expert family (configs with ``"reference": "swa_moe"``),
from its shapes alone — the twin of ``shapes.py`` for the ``mellum`` block
as one pipeline stage held whole on a chip.

"Needs" is the algorithm's minimum on this chip: every weight held here
read once a step whatever the batch — of the experts those that get a row,
counted under EVEN routing (``experts_hit``: 64 (1 - (63/64)^(8 rows)),
all of them from ~40 rows on) — the cached K and V rows a query may SEE
(the whole live context on a full layer; on a window layer the last
``sliding_window`` positions a row, never more than its context), each
matmul's multiply-adds.  A window layer's count needs no per-row lengths
where every row is past the window (the cell's mix: every prompt is at or
past it); where the live contexts sum to less than ``rows . window`` the
count is capped by them.
"""

from __future__ import annotations

WINDOW_KIND, FULL_KIND = "sliding_attention", "full_attention"


def _sizes(cfg: dict) -> dict:
    share = cfg.get("share", {})
    held = cfg["num_experts"]
    kinds = cfg["layer_types"]
    return dict(
        L=cfg["num_hidden_layers"], D=cfg["hidden_size"],
        V=cfg["vocab_size"], H=cfg["num_attention_heads"],
        Hkv=cfg["num_key_value_heads"], hd=cfg["head_dim"],
        Fe=cfg["moe_intermediate_size"], held=held,
        E=share.get("experts_total", held),
        topk=cfg["num_experts_per_tok"], W=cfg["sliding_window"],
        L_window=sum(k == WINDOW_KIND for k in kinds),
        L_full=sum(k == FULL_KIND for k in kinds))


def attention_params(cfg: dict) -> int:
    """One layer's attention: q, k, v, o and the two head norms."""
    s = _sizes(cfg)
    q, kv = s["H"] * s["hd"], s["Hkv"] * s["hd"]
    return s["D"] * q + 2 * s["D"] * kv + q * s["D"] + 2 * s["hd"]


def expert_params(cfg: dict) -> int:
    """One expert: gate, up, down."""
    s = _sizes(cfg)
    return 3 * s["D"] * s["Fe"]


def layer_params_held(cfg: dict) -> int:
    """Parameters of one layer held on this chip (with its two norms)."""
    s = _sizes(cfg)
    return (attention_params(cfg) + s["D"] * s["E"]
            + s["held"] * expert_params(cfg) + 2 * s["D"])


def params_held(cfg: dict) -> int:
    s = _sizes(cfg)
    return (s["L"] * layer_params_held(cfg) + 2 * s["D"] * s["V"] + s["D"])


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
    """``layers`` paged GQA calls over ``tokens`` cached tokens each: K
    and V once, the queries in, float32 partials (out + lane-padded lse)
    out; QK^T and PV."""
    s = _sizes(cfg)
    kv = tokens * layers * kv_bytes_per_token_layer(cfg, itemsize)
    q_in = rows * s["H"] * s["hd"] * itemsize * layers
    out = rows * s["H"] * (s["hd"] + 128) * 4 * layers
    return {"flops": 4 * tokens * s["H"] * s["hd"] * layers,
            "bytes": kv + q_in + out}


def window_attention(cfg: dict, *, rows: float, ctx_sum: float,
                     itemsize: int = 2) -> dict:
    """The window layers' paged calls of one decode step."""
    return _paged_calls(cfg, window_tokens(cfg, rows=rows, ctx_sum=ctx_sum),
                        rows, _sizes(cfg)["L_window"], itemsize)


def full_attention(cfg: dict, *, rows: float, ctx_sum: float,
                   itemsize: int = 2) -> dict:
    """The full layers' paged calls of one decode step."""
    return _paged_calls(cfg, ctx_sum, rows, _sizes(cfg)["L_full"], itemsize)


def expert_ffn(cfg: dict, *, rows: float, ctx_sum: float = 0.0,
               itemsize: int = 2) -> dict:
    """The grouped GEMMs of one decode step (every layer has them): the
    weights of the held experts that get a row, once; the rows routed here
    in and out."""
    s = _sizes(cfg)
    r = routed_rows(cfg, rows)
    w = s["L"] * experts_hit(cfg, rows) * expert_params(cfg)
    acts = s["L"] * r * (s["D"] + 2 * s["Fe"] + s["Fe"] + s["D"])
    return {"flops": 2 * r * expert_params(cfg) * s["L"],
            "bytes": (w + acts) * itemsize}


def decode_step(cfg: dict, *, rows: float, ctx_sum: float,
                itemsize: int = 2) -> dict:
    """One decode step of ``rows`` live sequences whose contexts sum to
    ``ctx_sum`` tokens: every weight held here once (the embedding by the
    row; of the experts those hit), the cache read by layer kind, the
    cache write, the logits out."""
    s = _sizes(cfg)
    L, D, V = s["L"], s["D"], s["V"]
    idle = L * (s["held"] - experts_hit(cfg, rows)) * expert_params(cfg)
    w = L * layer_params_held(cfg) + D * V + D - idle
    per_row = L * (attention_params(cfg) + D * s["E"]) + D * V
    win = window_attention(cfg, rows=rows, ctx_sum=ctx_sum, itemsize=itemsize)
    full = full_attention(cfg, rows=rows, ctx_sum=ctx_sum, itemsize=itemsize)
    ffn = expert_ffn(cfg, rows=rows, itemsize=itemsize)
    per_tok = kv_bytes_per_token_layer(cfg, itemsize)
    kv = (window_tokens(cfg, rows=rows, ctx_sum=ctx_sum) * s["L_window"]
          + ctx_sum * s["L_full"]) * per_tok
    kv_write = rows * L * per_tok
    return {"flops": (2 * rows * per_row + ffn["flops"] + win["flops"]
                      + full["flops"]),
            "bytes": w * itemsize + rows * D * itemsize + kv + kv_write
            + rows * V * 4}


FUNCTIONS = {"window_attention": window_attention,
             "full_attention": full_attention, "expert_ffn": expert_ffn,
             "decode_step": decode_step}
