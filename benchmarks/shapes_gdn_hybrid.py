"""What one decode step, one ``gdn_step`` call and one ``gdn_chunk`` call
NEED of the Gated-DeltaNet hybrid family (configs with ``"reference":
"gdn_hybrid"``), from its shapes alone — the twin of ``shapes.py`` for the
``olmo_hybrid`` block.

"Needs" is the algorithm's minimum on this chip, whatever implements it:
every weight read once a step whatever the batch (embedding AND head: they
are not tied; of the embedding a row a sequence); on a full layer the
cached K and V rows of the live context, all ``Hkv`` heads; on a linear
layer each live request's state read once and written once at the numbers
it HOLDS (``dk x H dv`` float32: a value head is 192 wide and is counted
192 wide, whatever lane tile a layout would pad it to) with its carried
convolution inputs; each matmul's multiply-adds.
"""

from __future__ import annotations

from benchmarks.reference.gdn_hybrid import KINDS

SUB_CHUNK = 64          # rows the prefill call's WY form works on at once


def _sizes(cfg: dict) -> dict:
    D, Hq = cfg["hidden_size"], cfg["num_attention_heads"]
    L = cfg["num_hidden_layers"]
    kinds = [KINDS[t] for t in cfg["layer_types"][:L]]
    H, dk, dv = (cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])
    return dict(L=L, D=D, V=cfg["vocab_size"], Hq=Hq,
                Hkv=cfg["num_key_value_heads"], hd=D // Hq,
                F=cfg["intermediate_size"], H=H, dk=dk, dv=dv,
                K=cfg["linear_conv_kernel_dim"], C=2 * H * dk + H * dv,
                n={k: kinds.count(k) for k in ("linear", "full")})


def mixer_params(cfg: dict, kind: str) -> int:
    """One layer's mixer, by kind (a full layer's with its q and k norms;
    a linear layer's W_q, W_k, W_v, W_z, W_o, W_a, W_b, the taps, A_log,
    dt_bias and the head norm)."""
    s = _sizes(cfg)
    D, H = s["D"], s["H"]
    if kind == "full":
        return 4 * D * D + 2 * D
    return (D * s["C"] + 2 * D * H * s["dv"] + 2 * D * H + s["K"] * s["C"]
            + 2 * H + s["dv"])


def params_total(cfg: dict) -> int:
    """Every parameter held here: the layers (mixer, MLP, both norms), the
    embedding, the head and the final norm."""
    s = _sizes(cfg)
    return (sum(n * mixer_params(cfg, k) for k, n in s["n"].items())
            + s["L"] * (3 * s["D"] * s["F"] + 2 * s["D"])
            + 2 * s["V"] * s["D"] + s["D"])


def kv_bytes_per_token_layer(cfg: dict, itemsize: int = 2) -> int:
    """K and V rows of one token in one full layer, all KV heads."""
    s = _sizes(cfg)
    return 2 * s["Hkv"] * s["hd"] * itemsize


def state_bytes_per_layer(cfg: dict, itemsize: int = 2) -> int:
    """One request's slot in one linear layer: the float32 matrix state
    and the K - 1 carried convolution inputs."""
    s = _sizes(cfg)
    return s["dk"] * s["H"] * s["dv"] * 4 + (s["K"] - 1) * s["C"] * itemsize


def state_bytes_per_request(cfg: dict, itemsize: int = 2) -> int:
    return _sizes(cfg)["n"]["linear"] * state_bytes_per_layer(cfg, itemsize)


def full_attention(cfg: dict, *, rows: float, ctx_sum: float,
                   itemsize: int = 2) -> dict:
    """The paged calls of one decode step on the full layers: the whole
    live context's K and V once a layer, the queries in, float32 partials
    (out + lse) out; QK^T and PV (no grouping: a query head a KV head)."""
    s = _sizes(cfg)
    n = s["n"]["full"]
    kv = ctx_sum * n * kv_bytes_per_token_layer(cfg, itemsize)
    q_in = rows * s["Hq"] * s["hd"] * itemsize * n
    out = rows * s["Hq"] * (s["hd"] + 1) * 4 * n
    return {"flops": 4 * ctx_sum * s["Hq"] * s["hd"] * n,
            "bytes": kv + q_in + out}


def gdn_step(cfg: dict, *, rows: float, ctx_sum: float = 0.0,
             itemsize: int = 2) -> dict:
    """ONE ``gdn_step`` call (one linear layer, ``rows`` live requests):
    each state in and out in float32; q, k, v, beta and the decay in and o
    out in float32; a head's four passes over its state (decay, k^T S, the
    rank-1 write, q^T S: 7 operations a number)."""
    s = _sizes(cfg)
    H, dk, dv = s["H"], s["dk"], s["dv"]
    return {"flops": 7 * rows * H * dk * dv,
            "bytes": rows * (2 * dk * H * dv + 2 * H * dk + 2 * H * dv
                             + 2 * H) * 4}


def gdn_chunk(cfg: dict, *, rows: float = 0.0, ctx_sum: float = 0.0,
              itemsize: int = 2) -> dict:
    """ONE ``gdn_chunk`` call (``T`` = the engine's ``prefill_chunk`` rows
    of one request through one linear layer): q, k, v, beta, g in and o
    out in float32, the state in and out; the products of the WY form a
    sub-chunk of 64 rows a head — K K^T and Q K^T (2 x C C dk), the
    inverse by doubling (2 log2(C) - 2 products of C^3), P (beta V) and P
    (beta K) (C C (dv + dk)), the two products against the carried state
    and the state's update (3 x C dk dv), the inner P V (C C dv) — each
    multiply-add two operations.  They run in float32 at the MXU's highest
    precision (six bfloat16 passes), which ``peaks.json``'s bfloat16 peak
    does not know: the share reads low by construction."""
    s = _sizes(cfg)
    H, dk, dv, C = s["H"], s["dk"], s["dv"], SUB_CHUNK
    T = cfg["engine"]["prefill_chunk"]
    doublings = 2 * (C.bit_length() - 1) - 2
    macs = (2 * C * C * dk + doublings * C ** 3 + C * C * (dv + dk)
            + 3 * C * dk * dv + C * C * dv)
    return {"flops": 2 * macs * H * (T // C),
            "bytes": (T * (2 * H * dk + 2 * H * dv + 2 * H)
                      + 2 * dk * H * dv) * 4}


def decode_step(cfg: dict, *, rows: float, ctx_sum: float,
                itemsize: int = 2) -> dict:
    """One decode step of ``rows`` live sequences whose contexts sum to
    ``ctx_sum`` tokens: every weight once (and a row of the embedding a
    sequence), the full layers' cache read and a row a layer written, each
    linear layer's state read and written, the float32 logits out."""
    s = _sizes(cfg)
    attn = full_attention(cfg, rows=rows, ctx_sum=ctx_sum, itemsize=itemsize)
    per_tok = kv_bytes_per_token_layer(cfg, itemsize)
    kv = ctx_sum * s["n"]["full"] * per_tok
    kv_write = rows * s["n"]["full"] * per_tok
    state = rows * state_bytes_per_request(cfg, itemsize) * 2
    weights = params_total(cfg) - s["V"] * s["D"]   # the embedding: rows only
    return {"flops": (2 * rows * weights + attn["flops"]
                      + 7 * rows * s["n"]["linear"] * s["H"] * s["dk"]
                      * s["dv"]),
            "bytes": weights * itemsize + rows * s["D"] * itemsize
            + kv + kv_write + state + rows * s["V"] * 4}


FUNCTIONS = {"full_attention": full_attention, "gdn_step": gdn_step,
             "gdn_chunk": gdn_chunk, "decode_step": decode_step}
