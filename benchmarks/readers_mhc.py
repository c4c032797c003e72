"""Readers of the residual streams' metrics: ``"reader":
"benchmarks.readers_mhc:roofline"`` / ``":share"`` in a
``layer_metrics/<name>.json``.

``roofline`` is ``readers_mla_moe.roofline``'s reading — least time for what
the step needs over the device time it took — with the counting functions
of ``shapes_mhc.py``; ``share`` is the device seconds of the operations that
match over those of the programs that match, in percent.  Where the trace
holds no operation of the name a metric reads, or the configuration states
no ``hc_mult`` (any other family's; a program that cannot run this one),
they return nothing and the line leaves the metric out.
"""

from __future__ import annotations

from benchmarks import readers, shapes, shapes_mhc


def roofline(args, ctx):
    if "hc_mult" not in ctx["config"]:
        return None
    took = readers._device_time(args["time"], ctx)
    rows = ctx["counters"].get("decode.rows_mean")
    ctx_sum = ctx["counters"].get("decode.ctx_sum_mean")
    if not took or not rows:
        return None
    need = shapes_mhc.FUNCTIONS[args["shape_fn"]](
        ctx["config"], rows=rows, ctx_sum=ctx_sum)
    least, _ = shapes.least_seconds(need, shapes.peaks(ctx["device_kind"]))
    return 100.0 * least / took


def share(args, ctx):
    tr = ctx["trace"]
    if tr is None or "hc_mult" not in ctx["config"]:
        return None
    ops = readers._matching(tr["module_op_s"], args["module_ops"])
    whole = readers._matching(tr["module_s"], args["modules"])
    if not ops or not whole:
        return None
    return 100.0 * ops / whole
