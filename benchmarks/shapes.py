"""What one call NEEDS, from its shapes alone: operations and bytes of one
decode step and of the paged attention inside it, and the least time the
chip could take for them.  Kept with the benchmark so that no PR that
claims a gain can change the yardstick.

Counts are for one chip holding the whole model.  "Needs" means the algorithm's minimum: every weight read once per
step whatever the batch, the LIVE contexts' K and V rows (not padded
pages, not the table's full width), each matmul's multiply-adds.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind.startswith("_") or device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"({[k for k in table if not k.startswith('_')]}): "
                       f"add it with its source, there is no default")
    return table[device_kind]


def layer_params(cfg: dict) -> int:
    """Parameters of one block's seven matrices."""
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    hd = cfg.get("head_dim") or D // cfg["num_attention_heads"]
    q = D * cfg["num_attention_heads"] * hd
    kv = D * cfg["num_key_value_heads"] * hd
    return q + 2 * kv + q + 3 * D * F


def kv_bytes_per_token_layer(cfg: dict, itemsize: int = 2) -> int:
    """K and V rows of one token in one layer, all KV heads."""
    hd = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    return 2 * cfg["num_key_value_heads"] * hd * itemsize


def decode_step(cfg: dict, *, rows: float, ctx_sum: float,
                itemsize: int = 2) -> dict:
    """One decode step of ``rows`` live sequences whose contexts sum to
    ``ctx_sum`` tokens -> {"flops", "bytes"}."""
    L, D, V = cfg["num_hidden_layers"], cfg["hidden_size"], cfg["vocab_size"]
    H = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or D // H
    w = L * layer_params(cfg) + D * V          # and the lm_head
    attn_flops = 4 * ctx_sum * H * hd * L      # QK^T and PV
    kv = ctx_sum * L * kv_bytes_per_token_layer(cfg, itemsize)
    kv_write = rows * L * kv_bytes_per_token_layer(cfg, itemsize)
    return {"flops": 2 * rows * w + attn_flops,
            "bytes": w * itemsize + rows * D * itemsize + kv + kv_write
            + rows * V * 4}                         # float32 logits out


def paged_attention(cfg: dict, *, rows: float, ctx_sum: float,
                    itemsize: int = 2) -> dict:
    """The paged split-KV attention calls of one decode step (all layers):
    read the live K and V once, Q in, float32 partials out."""
    L, H = cfg["num_hidden_layers"], cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // H
    kv = ctx_sum * L * kv_bytes_per_token_layer(cfg, itemsize)
    q_in = rows * H * hd * itemsize * L
    out = rows * H * (hd + 128) * 4 * L        # out + lane-padded lse
    return {"flops": 4 * ctx_sum * H * hd * L,
            "bytes": kv + q_in + out}


def least_seconds(need: dict, pk: dict) -> tuple:
    """-> (seconds, which bound): the larger of operations over peak
    FLOP/s and bytes over peak bytes/s."""
    t_flops = need["flops"] / pk["bf16_flops_per_s"]
    t_bytes = need["bytes"] / pk["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops > t_bytes else (t_bytes, "memory")


FUNCTIONS = {"decode_step": decode_step, "paged_attention": paged_attention}
