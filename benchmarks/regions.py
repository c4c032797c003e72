"""The device programs' regions (``runtime/profiling.py`` ``region``: one
``rg_<name>`` scope a seam of the layer loop) read out of a profiler trace:
every program's device seconds split by what the model was doing.

An ``XLA Ops`` event of a TPU plane is named by its whole HLO line and its
own statistics are times only; the HLO ``op_name`` path that jax wrote at
trace time (``jit(decode_horizon)/while/body/closed_call/rg_ffn/dot_general``)
is a statistic of the event's METADATA record (``tf_op`` on the v5e's
planes: chip trace, PR 36), which ``jax.profiler.ProfileData`` does not hand
out.  So this module reads the ``.xplane.pb`` itself — the protobuf wire
format of four message kinds, nothing imported:

``device_regions(path)``  .xplane.pb -> {plane: [[module, stem, region,
    start_ns, dur_ns], ...]} for every TPU plane: one row an operation,
    ``module`` the program whose execution holds its start, ``stem`` as
    ``xplane.op_name`` cuts it, ``region`` the INNERMOST ``rg_*`` component
    of its ``op_name`` path — else what ``CALL_REGIONS`` files its stem
    under (a Mosaic call with no name of its own must stay outside the
    scopes: XLA would name it after one) — else ``-``.  Containers
    (``xplane.CONTAINERS``) are left out, as ``xplane.reduce`` leaves them.
``reduce(events)``  the ``xplane.extract`` form with that table under
    ``"regions"`` -> device seconds by ``<program>|<region>``, the ten
    heaviest ``<program>|<region>|<stem>``, the remainder by stem, and per
    program its total and attributed share.  ``{}`` for a trace whose
    operations carry no region at all (a program from before the scopes).
``check(red, old)``  every program's total against ``xplane.reduce``'s
    ``module_op_s`` sum (0.5%), and no scoped program wholly unattributed.

What the compiler adds without metadata (a scan's carry copies,
``slice-done``, layout ``copy``) reads ``-``; a fusion carries the path of
ONE of the operations fused into it, so a fusion across a seam (a residual
add into the next norm) counts whole on one side.

NOT WIRED INTO THE CELLS YET, for the reasons ``spans.py`` gives (the
wiring edits ``xplane.extract``, ``run.py`` ``per_layer()`` and ``NAMES``: a
``benchmark`` PR's files, ROADMAP Queue 1).  Until then

    python3 benchmarks/regions.py --workload <cell> --seed <n> [--seconds 15]

is ONE traced run of a cell through ``run.run_cell`` itself: the cell's own
result line plus ``spans`` (the host's split, through ``spans.py``),
``regions`` (the device's) and ``region_metrics`` (``region_readers.py``
through ``readers.read``).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import spans, xplane  # noqa: E402

PREFIX = "rg_"
NONE = "-"
# the statistic of an operation's metadata record that holds its op_name
OP_NAME_STAT = "tf_op"
# instruction stems that ARE a region's work though no scope is around
# them: the dense family's paged attention call has no name of its own and
# reaches a trace under the name of the scope around it, which the accepted
# paged_attn_roofline matches — so it runs outside ``rg_attn``
CALL_REGIONS = {"closed_call": "attn", "_unknown_": "attn"}
# programs whose body is the layer loop or the sampler: one of these with
# device time and no region at all was not built from this source
SCOPED = re.compile(r"decode_horizon|paged_decode|prefill_chunk|"
                    r"sample_token|spec_round")
TOLERANCE = 0.005
# the per-layer metrics that read the regions (layer_metrics/<name>.json)
METRICS = (
    "dev.decode.attn_share_pct", "dev.decode.ffn_share_pct",
    "dev.decode.head_share_pct", "dev.decode.sample_share_pct",
    "dev.decode.unattributed_share_pct", "dev.prefill.attn_share_pct",
    "dev.prefill.ffn_share_pct", "dev.prefill.head_share_pct",
    "dev.prefill.unattributed_share_pct")


def region_of(op_name: str, stem: str = "") -> str:
    """The innermost region of an ``op_name`` path, dots restored."""
    inner = [p for p in op_name.split("/") if p.startswith(PREFIX)]
    if inner:
        return inner[-1][len(PREFIX):].replace("__", ".")
    return CALL_REGIONS.get(stem, NONE)


# -- the protobuf wire format, as far as an XSpace needs it --------------------
# XSpace{1: planes}  XPlane{2: name, 3: lines, 4: event_metadata<id, M>,
# 5: stat_metadata<id, S>}  XLine{2: name, 3: timestamp_ns, 4: events}
# XEvent{1: metadata_id, 2: offset_ps, 3: duration_ps}
# XEventMetadata{1: id, 2: name, 5: stats}  XStat{1: metadata_id,
# 5: str_value, 7: ref_value}  XStatMetadata{1: id, 2: name}
# (tsl/profiler/protobuf/xplane.proto)


def _varint(buf, i: int) -> tuple:
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if b < 0x80:
            return val, i
        shift += 7


def _fields(buf, i: int, end: int):
    """(field number, value) over one message: an int for a varint or a
    fixed field, a (start, end) pair for a length-delimited one."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
            yield key >> 3, val
        elif wire == 2:
            n, i = _varint(buf, i)
            yield key >> 3, (i, i + n)
            i += n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            yield key >> 3, int.from_bytes(buf[i:i + n], "little")
            i += n
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not an xplane")


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entry(buf, span):
    key, val = 0, None
    for f, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _plane(buf, span) -> dict | None:
    """One TPU plane -> {"name", "ops": [[op name, op_name path, start_ns,
    dur_ns]], "modules": [[name, start_ns, dur_ns]]}; None for another."""
    lines, meta, stat_names, name = [], {}, {}, ""
    for f, v in _fields(buf, *span):
        if f == 2:
            name = _text(buf, v)
            if not name.startswith("/device:TPU:"):
                return None
        elif f == 3:
            lines.append(v)
        elif f == 4:
            meta.update([_map_entry(buf, v)])
        elif f == 5:
            sid, sspan = _map_entry(buf, v)
            stat_names[sid] = next(
                (_text(buf, x) for g, x in _fields(buf, *sspan) if g == 2), "")
    op_stat = {i for i, n in stat_names.items() if n == OP_NAME_STAT}

    def described(mid):
        """(name, op_name path) of one metadata record."""
        nm, path = "", ""
        for f, v in _fields(buf, *meta[mid]):
            if f == 2:
                nm = _text(buf, v)
            elif f == 5:
                sid, sval = 0, ""
                for g, x in _fields(buf, *v):
                    if g == 1:
                        sid = x
                    elif g == 5:
                        sval = _text(buf, x)
                    elif g == 7:    # a string stored once, referred to
                        sval = stat_names.get(x, "")
                if sid in op_stat:
                    path = sval
        return nm, path

    out = {"name": name, "ops": [], "modules": []}
    seen = {}
    for lspan in lines:
        lname, t0, events = "", 0, []
        for f, v in _fields(buf, *lspan):
            if f == 2:
                lname = _text(buf, v)
            elif f == 3:
                t0 = v
            elif f == 4:
                events.append(v)
        key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(lname)
        if key is None:
            continue
        for espan in events:
            mid = off = dur = 0
            for f, v in _fields(buf, *espan):
                if f == 1:
                    mid = v
                elif f == 2:
                    off = v
                elif f == 3:
                    dur = v
            if mid not in seen:
                seen[mid] = described(mid)
            nm, path = seen[mid]
            # ProfileData's clock: the line's start plus the offset
            start = t0 + off // 1000
            if key == "ops":
                out["ops"].append([nm, path, start, dur // 1000])
            else:
                out["modules"].append([xplane.module_name(nm), start,
                                       dur // 1000])
    return out


def device_regions(path: str) -> dict:
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for f, v in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        plane = _plane(buf, v)
        if plane is None:
            continue
        # a row's program is the execution that holds its start (both
        # lines are in time order), ``-`` outside any
        mods = sorted(plane["modules"], key=lambda e: e[1])
        rows, j = [], 0
        for nm, op_path, s, d in sorted(plane["ops"], key=lambda e: e[2]):
            stem = xplane.op_name(nm)
            if stem in xplane.CONTAINERS:
                continue
            while j < len(mods) and mods[j][1] + mods[j][2] <= s:
                j += 1
            inside = j < len(mods) and mods[j][1] <= s
            rows.append([mods[j][0] if inside else NONE, stem,
                         region_of(op_path, stem), s, d])
        out[plane["name"]] = rows
    return out


def reduce(events: dict) -> dict:
    """Seconds of the traced window (``spans.window``: what
    ``xplane.reduce`` clips to), summed over the devices and divided by
    their number, as ``xplane.reduce`` does.

    region_s: ``<program>|<region>`` -> seconds.
    program_s / attributed_pct: per program, its operations' seconds and
        the share of them under a region.
    top: the ten heaviest ``<program>|<region>|<stem>``.
    unattributed: the ten heaviest ``<program>|<stem>`` among the ``-``.
    """
    planes = {k: v for k, v in events.get("regions", {}).items() if v}
    if not any(r[2] != NONE for rows in planes.values() for r in rows):
        return {}
    lo, hi = spans.window(events)
    n_dev = len(planes)
    by_region, by_stem, by_prog = {}, {}, {}
    for rows in planes.values():
        for prog, stem, region, s, d in rows:
            n = min(s + d, hi) - max(s, lo)
            if n <= 0:
                continue
            sec = n / n_dev / 1e9
            for acc, key in ((by_region, f"{prog}|{region}"),
                             (by_stem, f"{prog}|{region}|{stem}"),
                             (by_prog, prog)):
                acc[key] = acc.get(key, 0.0) + sec
    top = sorted(by_stem.items(), key=lambda kv: -kv[1])
    bare = [[k.replace(f"|{NONE}|", "|"), v] for k, v in top
            if f"|{NONE}|" in k]
    return {
        "region_s": by_region,
        "program_s": by_prog,
        "attributed_pct": {
            p: 100.0 * (1.0 - by_region.get(f"{p}|{NONE}", 0.0) / t)
            for p, t in by_prog.items() if t > 0},
        "top": [[k, v] for k, v in top[:10]],
        "unattributed": bare[:10],
    }


def check(red: dict, old: dict) -> list:
    """What is wrong with a reduction, in words: a program whose region
    seconds do not sum to ``xplane.reduce``'s (``old``) for it, and a
    scoped program with no region on any operation."""
    wrong = []
    want = {}
    for key, sec in old["module_op_s"].items():
        prog = key.split("|", 1)[0]
        want[prog] = want.get(prog, 0.0) + sec
    got = red.get("program_s", {})
    for prog in sorted(set(want) | set(got)):
        a, b = want.get(prog, 0.0), got.get(prog, 0.0)
        if abs(a - b) > TOLERANCE * max(a, b):
            wrong.append(f"{prog}: the regions sum to {b:.6f} s, "
                         f"xplane.reduce's operations to {a:.6f} s")
    stale = sorted(p for p, pct in red.get("attributed_pct", {}).items()
                   if SCOPED.search(p) and pct == 0.0)
    if stale or not red:
        wrong.append(
            f"no operation of {stale or 'any program'} carries a region: "
            f"the program is from before the scopes, or its executable was "
            f"fetched from a persistent compile cache written before them "
            f"(the cache's key leaves metadata out unless "
            f"jax_compilation_cache_include_metadata_in_key is set): clear "
            f"that cache and run again")
    return wrong


def main(argv=None) -> int:
    from benchmarks import readers, run

    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=run.TRACE_SECONDS)
    p.add_argument("--keep-trace", action="store_true",
                   help="leave the window's events, spans and regions "
                        "included, under chiprun_out/ (how fixtures/ was "
                        "recorded)")
    args = p.parse_args(argv)
    kept = {}
    extract = xplane.extract

    def with_regions(path):
        kept["events"] = events = extract(path)
        events.update(spans.host_spans(path))
        events["regions"] = device_regions(path)
        return events

    xplane.extract = with_regions
    try:
        result = run.run_cell(argparse.Namespace(
            workload=args.workload, seed=args.seed, seconds=args.seconds,
            trace=1, cpu_dryrun=False, keep_trace=args.keep_trace))
    finally:
        xplane.extract = extract
    events = kept["events"]
    red = reduce(events)
    wrong = check(red, xplane.reduce(events))
    if wrong:
        run.die("; ".join(wrong))
    host = spans.reduce(events)
    ctx = {"trace": {**host, **red}, "counters": {}, "samples": {}}
    if host:
        result["span_metrics"] = {m: readers.read(m, ctx)
                                  for m in spans.METRICS}
        result["spans"] = {
            "idle_gaps": spans.idle_rows(host),
            "idle_in_step_s": host["idle_in_step_s"],
            "self_s": {k: round(v, 6)
                       for k, v in host["span_self_s"].items()}}
    result["region_metrics"] = {m: readers.read(m, ctx) for m in METRICS}
    result["regions"] = {
        "seconds": {k: round(v, 6) for k, v in sorted(
            red["region_s"].items(), key=lambda kv: -kv[1])},
        "attributed_pct": {k: round(v, 2)
                           for k, v in red["attributed_pct"].items()},
        "top": red["top"],
        "unattributed": red["unattributed"],
    }
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
