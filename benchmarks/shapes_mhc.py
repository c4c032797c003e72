"""What the residual path of several streams NEEDS, from its shapes alone
(configs with ``"reference": "mla_mhc_moe_share"``): the twin of
``shapes_mla_moe.py`` for the ``xing4_0`` block, whose attention and expert
layer are that family's (``shapes_mla_moe``'s functions count them from
the file: all heads, every expert held) and whose residual is ``hc_mult``
streams mixed per token.

"Needs" is what the EQUATIONS cannot avoid, whatever implements them (one
call, two, a fused form).  Of a decode step that is every weight once, the
live latent rows, the logits out and, a sub-layer, the residual path's
parameters once (``phi``, its bias, gate and gain: float32) and the small
product with ``phi``.  The streams themselves (:func:`stream_bytes`:
``(2n + 1) . D`` numbers a row and sub-layer, 87 MB a step at 96 rows) are
NOT counted as HBM bytes: the first chip run of PR 49 read the post-mix at
156% of such a count — the call takes less time than HBM would need for its
operands, so the count is no lower bound on this chip.  What bounds a mix
alone is its float32 arithmetic on the vector unit, and ``peaks.json`` has
no sourced peak for that unit: the two calls have no roofline share of their
own until it has (PERF.md §7); their device time is read as a share of the
decode programs' (``hc.decode_share_pct``).
"""

from __future__ import annotations

from benchmarks import shapes_mla_moe as base

MAPS_ITEMSIZE = 4       # phi, bias, alpha, gain and a row's maps: float32


def _n(cfg: dict) -> int:
    return int(cfg["hc_mult"])


def n_maps(cfg: dict) -> int:
    """Numbers a row's maps hold: H_pre, H_post (n each), H_res (n x n)."""
    return 2 * _n(cfg) + _n(cfg) ** 2


def map_params(cfg: dict) -> int:
    """One SUB-layer's residual-path parameters: phi, bias, alpha, gain."""
    nD = _n(cfg) * cfg["hidden_size"]
    return nD * n_maps(cfg) + n_maps(cfg) + 3 + nD


def sublayers(cfg: dict) -> int:
    return 2 * cfg["num_hidden_layers"]


def layer_params_held(cfg: dict, moe: bool) -> int:
    """Parameters of one layer held on this chip, its two sub-layers'
    maps included."""
    return base.layer_params_held(cfg, moe) + 2 * map_params(cfg)


def stream_bytes(cfg: dict, rows: float, itemsize: int = 2) -> float:
    """Bytes of the streams one sub-layer's equations move for ``rows``
    rows: X in once, the sub-layer's row once, X' out once."""
    return rows * (2 * _n(cfg) + 1) * cfg["hidden_size"] * itemsize


def decode_step(cfg: dict, *, rows: float, ctx_sum: float,
                itemsize: int = 2) -> dict:
    """One decode step: ``shapes_mla_moe.decode_step`` (every weight held
    once with the experts hit under even routing, the live latent rows,
    the logits out) and, a sub-layer, the maps' parameters once and the
    product with ``phi``; the streams are not counted (see above)."""
    step = base.decode_step(cfg, rows=rows, ctx_sum=ctx_sum,
                            itemsize=itemsize)
    calls = sublayers(cfg)
    return {"flops": step["flops"] + calls * rows * 2 * _n(cfg)
            * cfg["hidden_size"] * n_maps(cfg),
            "bytes": step["bytes"] + calls * map_params(cfg) * MAPS_ITEMSIZE}


FUNCTIONS = {"decode_step": decode_step}
