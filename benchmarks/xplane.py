"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read: device busy and idle time, time per program and
per operation, and the idle gaps by what the host was doing in them.

Two stages, so the second can be checked on the CPU against a recorded
trace (``fixtures/``, ``tests/test_bench_xplane.py``):

``extract(path)``  .xplane.pb -> plain events, nothing but ``jax.profiler``:
    {"host": [[name, start_ns, dur_ns], ...],       spans of HOST_SPANS
     "devices": {plane: {"modules": [...], "ops": [...]}}}
``reduce(events)`` events -> numbers (pure Python).

A TPU plane is named ``/device:TPU:<n>``; its ``XLA Modules`` line holds
one event per executed program (``jit_<fn>(<fingerprint>)``) and its
``XLA Ops`` line one per HLO operation, named by its whole HLO line.  Ops
keep their instruction's stem and modules lose their fingerprint, so a
count keyed by name survives a recompile.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re

# Host spans the benchmark writes around its own calls (run.py); ``reduce``
# splits every idle gap of the device among them.
HOST_SPANS = ("bench.window", "loadgen", "engine.step")
# jax's own host events that mean "a program is being handed to the device"
DISPATCH = re.compile(r"^(PjitFunction|PjRtCApiLoadedExecutable::Execute|"
                      r"ExecuteSharded|Execute)")
_FINGERPRINT = re.compile(r"\(\d+\)$")
_COST = re.compile(r"_flops_\d+_bytes_\d+_sol_ms_\d+$")
# operations that only hold other operations: their time is their
# children's, so they count for busy time and not in a table by name
CONTAINERS = ("while", "conditional", "call")
# the engine's programs are jitted partials and all reach the trace as
# ``jit__unknown``; run.py logs its dispatches in order and ``relabel``
# gives each execution the name of the engine call that launched it
UNNAMED = "jit__unknown"


def op_name(raw: str) -> str:
    """The chip's op events carry whole HLO lines (``%copy.843.remat2 =
    bf16[449,8,128,128]{...} copy(...)``): keep the instruction's stem."""
    stem = raw.lstrip("%").split(" ", 1)[0].split("=", 1)[0]
    return _COST.sub("", stem.split(".", 1)[0]) or "_unknown_"


def module_name(raw: str) -> str:
    return _FINGERPRINT.sub("", raw)


def newest_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def extract(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {"host": [], "devices": {}}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"modules": [], "ops": []}
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(
                    line.name)
                if key is None:
                    continue
                namer = module_name if key == "modules" else op_name
                dev[key] = [[namer(e.name), int(e.start_ns),
                             int(e.duration_ns)] for e in line.events]
            out["devices"][plane.name] = dev
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        out["host"].append([e.name, int(e.start_ns),
                                            int(e.duration_ns)])
                    elif DISPATCH.match(e.name):
                        out["host"].append(["dispatch", int(e.start_ns),
                                            int(e.duration_ns)])
    return out


def relabel(events: dict, dispatched: list) -> int:
    """Name the ``jit__unknown`` executions of every device plane after the
    engine calls that launched them, in order (the device runs one stream,
    and the log starts and ends at a step boundary with nothing in
    flight).  Returns how many were named.  A plane with no unnamed
    execution needs no names; one whose count differs from the log cannot
    be named, and every per-program reading would be wrong: an error."""
    named = 0
    for plane, dev in events["devices"].items():
        mods = sorted((m for m in dev["modules"] if m[0] == UNNAMED),
                      key=lambda m: m[1])
        if not mods:
            continue
        if len(mods) != len(dispatched):
            raise ValueError(
                f"{plane}: {len(mods)} unnamed device executions against "
                f"{len(dispatched)} logged engine dispatches: the "
                f"dispatch seam moved or programs run outside it")
        for m, op in zip(mods, dispatched):
            m[0] = f"engine.{op}"
        named += len(mods)
    return named


def load_events(path: str) -> dict:
    """A recorded trace: the ``extract`` form as (gzipped) JSON."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def save_events(events: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


# -- intervals -----------------------------------------------------------------

def union(intervals: list) -> list:
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def total(intervals: list) -> int:
    return sum(e - s for s, e in intervals)


def clip(intervals: list, lo: int, hi: int) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a: list, b: list) -> list:
    """Parts of merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def _by_name(events: list, lo: int, hi: int) -> dict:
    acc = {}
    for name, s, d in events:
        n = min(s + d, hi) - max(s, lo)
        if n > 0:
            t = acc.setdefault(name, [0, 0])
            t[0] += n
            t[1] += 1
    return acc


def _ops_in_modules(dev: dict, ops: list) -> list:
    """The device's ops renamed ``<module>|<op>`` by the program whose
    interval holds their start (both lines are in time order)."""
    mods = sorted(dev["modules"], key=lambda e: e[1])
    out, j = [], 0
    for name, s, d in sorted(ops, key=lambda e: e[1]):
        while j < len(mods) and mods[j][1] + mods[j][2] <= s:
            j += 1
        inside = j < len(mods) and mods[j][1] <= s
        out.append([f"{mods[j][0] if inside else '-'}|{name}", s, d])
    return out


def reduce(events: dict) -> dict:
    """Numbers of the traced window.

    The window is the ``bench.window`` host span when the trace has one and
    the device events lie inside it (host and device share the trace's
    clock), else the extent of the device events.  Times in seconds.

    busy_s / idle_share: union of the device's op intervals (ops, or
        modules where a plane has no op line), averaged over the devices.
    module_s, module_n / op_s: device time and executions by name, summed
        over the devices and divided by their number (a per-chip figure);
        module_op_s the same keyed ``<module>|<op>``.
    idle_gaps: the ten longest idle gaps of the first device, each
        charged to ``engine.step/dispatch`` (inside engine.step while jax
        hands a program over), ``engine.step/host`` (inside engine.step
        otherwise), ``loadgen`` or ``outside``.
    """
    devices = {k: v for k, v in events["devices"].items()
               if v["ops"] or v["modules"]}
    if not devices:
        raise ValueError("the trace holds no device plane with events: "
                         "nothing ran on the device in the traced window")
    spans = {}
    for name, s, d in events["host"]:
        spans.setdefault(name, []).append([s, s + d])
    dev_lo = min(e[1] for v in devices.values()
                 for e in (v["ops"] or v["modules"]))
    dev_hi = max(e[1] + e[2] for v in devices.values()
                 for e in (v["ops"] or v["modules"]))
    lo, hi = dev_lo, dev_hi
    if spans.get("bench.window"):
        w_lo, w_hi = spans["bench.window"][0]
        if w_lo <= dev_hi and w_hi >= dev_lo:
            lo, hi = w_lo, w_hi
    n_dev = len(devices)
    busy = []
    module_acc, op_acc, inner_acc = {}, {}, {}
    first_busy = None
    for name in sorted(devices):
        dev = devices[name]
        base = dev["ops"] or dev["modules"]
        b = clip(union([[s, s + d] for _, s, d in base]), lo, hi)
        if first_busy is None:
            first_busy = b
        busy.append(total(b))
        leaves = [o for o in dev["ops"] if o[0] not in CONTAINERS]
        for acc, evs in ((module_acc, dev["modules"]), (op_acc, leaves),
                         (inner_acc, _ops_in_modules(dev, leaves))):
            for k, (t, n) in _by_name(evs, lo, hi).items():
                a = acc.setdefault(k, [0, 0])
                a[0] += t
                a[1] += n
    window_ns = hi - lo
    busy_ns = sum(busy) / n_dev
    gaps = subtract([[lo, hi]], first_busy)
    step = union(spans.get("engine.step", []))
    load = union(spans.get("loadgen", []))
    disp = union(spans.get("dispatch", []))
    # whole lists at once: a window holds a gap after almost every operation
    in_step = total(_and(step, gaps))
    dispatch = total(_and(_and(disp, step), gaps))
    loadgen = total(_and(subtract(load, step), gaps))
    by_span = {k: v for k, v in {
        "engine.step/dispatch": dispatch,
        "engine.step/host": in_step - dispatch,
        "loadgen": loadgen,
        "outside": total(gaps) - in_step - loadgen}.items() if v > 0}
    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_ns / 1e9,
        "idle_share": 1.0 - busy_ns / window_ns,
        "n_devices": n_dev,
        "module_s": {k: t / n_dev / 1e9 for k, (t, _) in module_acc.items()},
        "module_n": {k: n / n_dev for k, (_, n) in module_acc.items()},
        "op_s": {k: t / n_dev / 1e9 for k, (t, _) in op_acc.items()},
        "op_n": {k: n / n_dev for k, (_, n) in op_acc.items()},
        "module_op_s": {k: t / n_dev / 1e9 for k, (t, _) in inner_acc.items()},
        "idle_by_span_s": {k: v / 1e9 for k, v in by_span.items()},
        "longest_gap_s": max((e - s for s, e in gaps), default=0) / 1e9,
    }


def _and(a: list, b: list) -> list:
    """Intersection of two merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append([s, e])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def breakdown(red: dict) -> dict:
    """The result line's ``breakdown``: top device operations and where
    the idle time fell, at most ten each."""
    ops = sorted(red["op_s"].items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(red["idle_by_span_s"].items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}
