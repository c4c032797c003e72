"""Per-layer metric readers.

Each per-layer metric is a file ``layer_metrics/<name>.json``: its layer,
unit, source, the end-to-end metric it should move, and ``reader`` — one
of the kinds below with its arguments, or ``"module:function"`` naming a
reader a later PR brings in a file of its own.  A reader takes
``(args, ctx)`` and returns a number, or ``None`` when it finds nothing to
read (the harness then leaves the metric out of the line).

``ctx``: ``counters`` (numbers of the measured window: the program's
counters as deltas, the benchmark's own counts), ``samples`` (lists),
``trace`` (``xplane.reduce`` of the traced window, or None), ``config``,
``device_kind``.
"""

from __future__ import annotations

import importlib
import json
import os
import re

from benchmarks import shapes

HERE = os.path.dirname(os.path.abspath(__file__))


def nearest_rank(values: list, p: float, beyond: int = 0):
    """p-th percentile by nearest rank, or None unless ``beyond`` samples
    lie past it (a p90 of 40 requests is a maximum in disguise)."""
    n = len(values)
    if n == 0:
        return None
    rank = max(1, -(-int(p * n) // 100))        # ceil(p/100 * n)
    if n - rank < beyond:
        return None
    return sorted(values)[rank - 1]


def _counter(args, ctx):
    v = ctx["counters"].get(args["counter"])
    return None if v is None else v * args.get("scale", 1)


def _ratio(args, ctx):
    num = ctx["counters"].get(args["num"])
    den = ctx["counters"].get(args["den"])
    if num is None or not den:
        return None
    return num / den * args.get("scale", 1)


def _percentile(args, ctx):
    return nearest_rank(ctx["samples"].get(args["samples"], []), args["p"])


def _matching(table: dict, pattern: str) -> float:
    rx = re.compile(pattern)
    return sum(v for k, v in table.items() if rx.search(k))


def _device_time(args, ctx):
    """Device seconds of the programs (``modules``) or of the operations
    inside programs (``module_ops``, keys ``<module>|<op>``) that match,
    divided by a counter of the traced window or by their executions."""
    tr = ctx["trace"]
    if tr is None:
        return None
    table = tr["module_op_s"] if "module_ops" in args else tr["module_s"]
    secs = _matching(table, args.get("module_ops") or args["modules"])
    if "per_counter" in args:
        den = ctx["counters"].get(args["per_counter"])
    else:
        den = _matching(tr["module_n"], args["modules"])
    if not secs or not den:
        return None
    return secs / den * args.get("scale", 1)


def _roofline(args, ctx):
    """Least time the chip could take for what the call needs (shapes.py,
    peaks.json) over the device time it took, in percent."""
    took = _device_time(args["time"], ctx)
    rows = ctx["counters"].get("decode.rows_mean")
    ctx_sum = ctx["counters"].get("decode.ctx_sum_mean")
    if not took or not rows:
        return None
    need = shapes.FUNCTIONS[args["shape_fn"]](
        ctx["config"], rows=rows, ctx_sum=ctx_sum)
    least, _ = shapes.least_seconds(need, shapes.peaks(ctx["device_kind"]))
    return 100.0 * least / took


def _idle_share(args, ctx):
    return None if ctx["trace"] is None else 100.0 * ctx["trace"]["idle_share"]


KINDS = {"counter": _counter, "ratio": _ratio, "percentile": _percentile,
         "device_time": _device_time, "roofline": _roofline,
         "idle_share": _idle_share}


def load(name: str) -> dict:
    """The metric's file.  One that says ``same_as`` is the same quantity
    under another name (split because its cells report different end-to-end
    metrics) and is read as the file it names."""
    with open(os.path.join(HERE, "layer_metrics", f"{name}.json")) as f:
        spec = json.load(f)
    return load(spec["same_as"]) if "same_as" in spec else spec


def read(name: str, ctx: dict):
    spec = load(name)
    kind = spec["reader"]
    if ":" in kind:
        mod, _, fn = kind.partition(":")
        reader = getattr(importlib.import_module(mod), fn)
    else:
        reader = KINDS[kind]
    return reader(spec.get("args", {}), ctx)
