"""Reader of the per-layer metrics that read the device programs' regions
(``regions.reduce``'s keys on ``ctx["trace"]``), for ``readers.read``'s
``"benchmarks.region_readers:<fn>"`` hook.  It returns ``None`` when the
trace holds no region — a program from before the scopes, a trace reduced
by ``xplane.reduce`` alone — and never raises for it."""

from __future__ import annotations

import re


def share_pct(args, ctx):
    """Device seconds of the regions ``args["regions"]`` (``-``: under no
    region) in the programs whose name matches ``args["modules"]`` (a
    regular expression), over all device seconds of those programs'
    operations, in percent."""
    trace = ctx.get("trace") or {}
    by_region, by_program = trace.get("region_s"), trace.get("program_s")
    if not by_region or not by_program:
        return None
    rx = re.compile(args["modules"])
    total = sum(v for k, v in by_program.items() if rx.search(k))
    if not total:
        return None
    part = sum(v for k, v in by_region.items()
               if rx.search(k.rsplit("|", 1)[0])
               and k.rsplit("|", 1)[1] in args["regions"])
    return 100.0 * part / total
