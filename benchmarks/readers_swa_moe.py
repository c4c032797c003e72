"""Reader of the window + full attention, softmax-routed expert family's
roofline shares: ``"reader": "benchmarks.readers_swa_moe:roofline"`` in a
``layer_metrics/<name>.json``.

``readers_mla_moe.roofline``'s reading — least time for what the call
needs over the device time it took — with the counting functions of
``shapes_swa_moe.py``.  Where the trace holds no operation of the name it
reads (a program without a call named by layer kind), or the configuration
has no layer kinds, it returns nothing and the line leaves the metric out.
"""

from __future__ import annotations

from benchmarks import readers, shapes, shapes_swa_moe


def roofline(args, ctx):
    took = readers._device_time(args["time"], ctx)
    rows = ctx["counters"].get("decode.rows_mean")
    ctx_sum = ctx["counters"].get("decode.ctx_sum_mean")
    if not took or not rows or "layer_types" not in ctx["config"]:
        return None
    need = shapes_swa_moe.FUNCTIONS[args["shape_fn"]](
        ctx["config"], rows=rows, ctx_sum=ctx_sum)
    least, _ = shapes.least_seconds(need, shapes.peaks(ctx["device_kind"]))
    return 100.0 * least / took
