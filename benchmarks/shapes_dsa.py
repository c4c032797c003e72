"""What one decode step NEEDS of the latent + LEARNED SPARSE attention +
expert-share family (configs with ``"reference": "mla_dsa_moe_share"``:
the ``glm_moe_dsa`` block), from its shapes alone — ``shapes_mla_moe.py``
with an indexer beside every layer's attention.

"Needs" is the SPARSE algorithm's minimum on this chip, whatever
implements it: every weight held here once a step (the indexer's with the
rest), the LIVE contexts' index keys once (the indexer scores every cached
token), and of the latent rows ONLY those a query selected — ``index_topk``
a row a layer at the published 576 numbers, or its whole context while
that is shorter.  A program that walks every live latent row to apply the
selection as a mask reads more than this and shows it as lost share.  No
gather is counted: the algorithm needs the selected rows read once, not a
compacted copy of them.
"""

from __future__ import annotations

from benchmarks import shapes_mla_moe as base


def _index(cfg: dict) -> dict:
    return dict(Hi=cfg["index_n_heads"], Di=cfg["index_head_dim"],
                topk=cfg["index_topk"], rq=cfg["q_lora_rank"],
                D=cfg["hidden_size"], L=cfg["num_hidden_layers"])


def index_params(cfg: dict) -> int:
    """One layer's indexer: its heads' queries off the query latent, the
    key and the head weights off the layer's input, the key's LayerNorm."""
    i = _index(cfg)
    return (i["rq"] * i["Hi"] * i["Di"] + i["D"] * i["Di"]
            + i["D"] * i["Hi"] + 2 * i["Di"])


def layer_params_held(cfg: dict, moe: bool) -> int:
    return base.layer_params_held(cfg, moe) + index_params(cfg)


def params_held(cfg: dict) -> int:
    """Every parameter this chip holds: the layers, both vocabulary
    matrices, the final norm."""
    s = base._sizes(cfg)
    return (s["L_dense"] * layer_params_held(cfg, False)
            + (s["L"] - s["L_dense"]) * layer_params_held(cfg, True)
            + 2 * s["D"] * s["V"] + s["D"])


def cache_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """A cached token over all layers: the latent row and the index key."""
    i = _index(cfg)
    return i["L"] * (base.latent_bytes_per_token_layer(cfg, itemsize)
                     + i["Di"] * itemsize)


def selected_rows(cfg: dict, *, rows: float, ctx_sum: float) -> float:
    """Latent rows one layer's attention reads for ``rows`` decoding
    sequences: ``index_topk`` each — exact where every context lies past
    it, as in this family's cell; never more than there are."""
    return min(rows * _index(cfg)["topk"], ctx_sum)


def index_scores(cfg: dict, *, rows: float, ctx_sum: float,
                 itemsize: int = 2) -> dict:
    """The indexer's score calls of one decode step (all layers): the live
    index keys once, index queries and head weights in, a float32 score a
    cached token out; ``2 . Hi . Di`` operations a cached token a layer."""
    i = _index(cfg)
    keys = ctx_sum * i["Di"] * itemsize
    q_in = rows * i["Hi"] * (i["Di"] * itemsize + 4)
    return {"flops": 2 * i["Hi"] * i["Di"] * ctx_sum * i["L"],
            "bytes": (keys + q_in + ctx_sum * 4) * i["L"]}


def sparse_attention(cfg: dict, *, rows: float, ctx_sum: float,
                     itemsize: int = 2) -> dict:
    """The latent attention of one decode step (all layers) over the
    SELECTED rows: those rows once, the absorbed queries in, float32
    results out; ``2 . H . (row + rank)`` operations a selected row."""
    return base.mla_paged_attention(
        cfg, rows=rows, ctx_sum=selected_rows(cfg, rows=rows,
                                              ctx_sum=ctx_sum),
        itemsize=itemsize)


def decode_step(cfg: dict, *, rows: float, ctx_sum: float,
                itemsize: int = 2) -> dict:
    """One decode step of ``rows`` live sequences whose contexts sum to
    ``ctx_sum`` tokens: ``shapes_mla_moe.decode_step`` over the selected
    rows in place of the live ones, plus the indexer — its weights once,
    its products for the rows, the live index keys, the new keys' write."""
    i = _index(cfg)
    sel = selected_rows(cfg, rows=rows, ctx_sum=ctx_sum)
    step = base.decode_step(cfg, rows=rows, ctx_sum=sel, itemsize=itemsize)
    idx = index_scores(cfg, rows=rows, ctx_sum=ctx_sum, itemsize=itemsize)
    w = i["L"] * index_params(cfg)
    return {"flops": step["flops"] + idx["flops"] + 2 * rows * w,
            "bytes": step["bytes"] + idx["bytes"] + w * itemsize
            + rows * i["L"] * i["Di"] * itemsize}


FUNCTIONS = {"index_scores": index_scores,
             "sparse_attention": sparse_attention,
             "decode_step": decode_step}
