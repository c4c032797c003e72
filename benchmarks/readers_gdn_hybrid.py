"""Readers of the Gated-DeltaNet hybrid family's roofline shares:
``"reader": "benchmarks.readers_gdn_hybrid:roofline"`` (a decode step's
shares, from the programs' or the paged calls' time a step) and
``...:call_roofline`` (one execution of a named Mosaic call: ``gdn_step``,
``gdn_chunk``) in a ``layer_metrics/<name>.json``.

``readers_ssm_yoco``'s readings — least time for what the call needs over
the device time it took — with the counting functions of
``shapes_gdn_hybrid.py``.  Where the trace holds no operation of the name
it reads, or the configuration is another family's (the parent commit has
no such program), a reader returns nothing and the line leaves the metric
out.
"""

from __future__ import annotations

import re

from benchmarks import readers, shapes, shapes_gdn_hybrid


def _ours(ctx) -> bool:
    return ctx["config"].get("model_type") == "olmo_hybrid"


def _share(need: dict, took: float, ctx) -> float:
    least, _ = shapes.least_seconds(need, shapes.peaks(ctx["device_kind"]))
    return 100.0 * least / took


def roofline(args, ctx):
    took = readers._device_time(args["time"], ctx)
    rows = ctx["counters"].get("decode.rows_mean")
    ctx_sum = ctx["counters"].get("decode.ctx_sum_mean")
    if not took or not rows or not _ours(ctx):
        return None
    return _share(shapes_gdn_hybrid.FUNCTIONS[args["shape_fn"]](
        ctx["config"], rows=rows, ctx_sum=ctx_sum), took, ctx)


def call_roofline(args, ctx):
    """One execution of the operation ``args["op"]`` (a regular expression
    over operation names): its device seconds over its executions, against
    what one call needs at the window's mean live rows."""
    tr = ctx["trace"]
    if tr is None or not _ours(ctx):
        return None
    rx = re.compile(args["op"])
    secs = sum(v for k, v in tr["op_s"].items() if rx.search(k))
    n = sum(v for k, v in tr["op_n"].items() if rx.search(k))
    need = shapes_gdn_hybrid.FUNCTIONS[args["shape_fn"]](
        ctx["config"], rows=ctx["counters"].get("decode.rows_mean") or 0.0)
    if not secs or not n or not need["bytes"]:
        return None
    return _share(need, secs / n, ctx)
