"""Reader of the ``laguna`` block's roofline shares: ``"reader":
"benchmarks.readers_laguna:roofline"`` in a ``layer_metrics/<name>.json``.

``readers_swa_moe.roofline``'s reading — least time for what the call
needs over the device time it took — with the counting functions of
``shapes_laguna.py`` (heads by layer, the gate, the dense lead layer, the
shared expert).  Where the trace holds no operation of the name it reads,
or the configuration states no heads by layer (any other family's; a
program that cannot run this one), it returns nothing and the line leaves
the metric out.
"""

from __future__ import annotations

from benchmarks import readers, shapes, shapes_laguna


def roofline(args, ctx):
    took = readers._device_time(args["time"], ctx)
    rows = ctx["counters"].get("decode.rows_mean")
    ctx_sum = ctx["counters"].get("decode.ctx_sum_mean")
    if not took or not rows \
            or "num_attention_heads_per_layer" not in ctx["config"]:
        return None
    need = shapes_laguna.FUNCTIONS[args["shape_fn"]](
        ctx["config"], rows=rows, ctx_sum=ctx_sum)
    least, _ = shapes.least_seconds(need, shapes.peaks(ctx["device_kind"]))
    return 100.0 * least / took
