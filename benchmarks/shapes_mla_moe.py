"""What one decode step NEEDS of the latent-attention + expert-share
family (configs with ``"reference": "mla_moe_share"``), from its shapes
alone — the twin of ``shapes.py`` for the DeepSeek-V3 block as ONE CHIP'S
SHARE of an expert-parallel deployment.

"Needs" is the algorithm's minimum on this chip: every weight HELD HERE
read once a step whatever the batch — of the held experts those that get
a row, counted under EVEN routing (``experts_hit``: 13.8 of 16 at 64 rows;
counting all 16 read 104% on the chip, PR 26) — the LIVE contexts' latent rows
at their PUBLISHED width (576 numbers: the 64 pad columns the chip stores
them with are not needed, so they show as lost share), each matmul's
multiply-adds.
"""

from __future__ import annotations


def _sizes(cfg: dict) -> dict:
    share = cfg.get("share", {})
    held = cfg["n_routed_experts"]
    return dict(
        L=cfg["num_hidden_layers"], D=cfg["hidden_size"],
        V=cfg["vocab_size"], H=cfg["num_attention_heads"],
        rq=cfg["q_lora_rank"], R=cfg["kv_lora_rank"],
        dn=cfg["qk_nope_head_dim"], dr=cfg["qk_rope_head_dim"],
        dv=cfg["v_head_dim"], F=cfg["intermediate_size"],
        Fe=cfg["moe_intermediate_size"], held=held,
        E=share.get("experts_total", held),
        shared=cfg.get("n_shared_experts") or 0,
        topk=cfg["num_experts_per_tok"],
        L_dense=min(cfg["first_k_dense_replace"],
                    cfg["num_hidden_layers"]))


def attention_params(cfg: dict) -> int:
    """One layer's latent attention: q_a, q_b, kv_a, kv_b, o."""
    s = _sizes(cfg)
    H = s["H"]
    return (s["D"] * s["rq"] + s["rq"] * H * (s["dn"] + s["dr"])
            + s["D"] * (s["R"] + s["dr"]) + s["R"] * H * (s["dn"] + s["dv"])
            + H * s["dv"] * s["D"])


def expert_params(cfg: dict) -> int:
    """One routed (or shared) expert: gate, up, down."""
    s = _sizes(cfg)
    return 3 * s["D"] * s["Fe"]


def layer_params_held(cfg: dict, moe: bool) -> int:
    """Parameters of one layer held on this chip."""
    s = _sizes(cfg)
    if not moe:
        return attention_params(cfg) + 3 * s["D"] * s["F"]
    return (attention_params(cfg) + s["D"] * s["E"] + s["E"]
            + (s["shared"] + s["held"]) * expert_params(cfg))


def latent_bytes_per_token_layer(cfg: dict, itemsize: int = 2) -> int:
    s = _sizes(cfg)
    return (s["R"] + s["dr"]) * itemsize


def routed_rows(cfg: dict, rows: float) -> float:
    """Rows an expert layer routes to the experts held here, under even
    routing."""
    s = _sizes(cfg)
    return rows * s["topk"] * s["held"] / s["E"]


def experts_hit(cfg: dict, rows: float) -> float:
    """Held experts that get at least one of a step's ``rows . topk``
    assignments when each lands on any of the router's experts alike:
    an expert no row chose is not read."""
    s = _sizes(cfg)
    return s["held"] * (1.0 - (1.0 - 1.0 / s["E"]) ** (rows * s["topk"]))


def mla_paged_attention(cfg: dict, *, rows: float, ctx_sum: float,
                        itemsize: int = 2) -> dict:
    """The latent paged attention calls of one decode step (all layers):
    the live rows once, the absorbed queries in, float32 results out;
    ``2 . H . (row + rank)`` operations a cached token a layer."""
    s = _sizes(cfg)
    L, H, W = s["L"], s["H"], s["R"] + s["dr"]
    kv = ctx_sum * L * latent_bytes_per_token_layer(cfg, itemsize)
    q_in = rows * H * W * itemsize * L
    out = rows * H * s["R"] * 4 * L
    return {"flops": 2 * H * (W + s["R"]) * ctx_sum * L,
            "bytes": kv + q_in + out}


def expert_ffn(cfg: dict, *, rows: float, ctx_sum: float = 0.0,
               itemsize: int = 2) -> dict:
    """The grouped GEMMs of one decode step (all expert layers): the
    weights of the held experts that get a row, once; the rows routed here
    in and out."""
    s = _sizes(cfg)
    L_moe = s["L"] - s["L_dense"]
    r = routed_rows(cfg, rows)
    w = L_moe * experts_hit(cfg, rows) * expert_params(cfg)
    acts = L_moe * r * (s["D"] + 2 * s["Fe"] + s["Fe"] + s["D"])
    return {"flops": 2 * r * expert_params(cfg) * L_moe,
            "bytes": (w + acts) * itemsize}


def decode_step(cfg: dict, *, rows: float, ctx_sum: float,
                itemsize: int = 2) -> dict:
    """One decode step of ``rows`` live sequences whose contexts sum to
    ``ctx_sum`` tokens: every weight held here once (the embedding by the
    row), the live latent cache, the logits out."""
    s = _sizes(cfg)
    L, D, V = s["L"], s["D"], s["V"]
    L_moe = L - s["L_dense"]
    idle = L_moe * (s["held"] - experts_hit(cfg, rows)) * expert_params(cfg)
    w = (s["L_dense"] * layer_params_held(cfg, False)
         + L_moe * layer_params_held(cfg, True) + D * V - idle)
    per_row = (L * attention_params(cfg) + s["L_dense"] * 3 * D * s["F"]
               + L_moe * (D * s["E"] + s["shared"] * expert_params(cfg))
               + D * V)
    attn = mla_paged_attention(cfg, rows=rows, ctx_sum=ctx_sum,
                               itemsize=itemsize)
    ffn = expert_ffn(cfg, rows=rows, itemsize=itemsize)
    kv = ctx_sum * L * latent_bytes_per_token_layer(cfg, itemsize)
    kv_write = rows * L * latent_bytes_per_token_layer(cfg, itemsize)
    return {"flops": 2 * rows * per_row + ffn["flops"] + attn["flops"],
            "bytes": w * itemsize + rows * D * itemsize + kv + kv_write
            + rows * V * 4}


FUNCTIONS = {"mla_paged_attention": mla_paged_attention,
             "expert_ffn": expert_ffn, "decode_step": decode_step}
