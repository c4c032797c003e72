"""The source-lint rule registry — grep meta-tests, promoted.

Tier-1 grew several "meta-tests" that lint the source tree instead of
running it: annotation coverage over the kernel entry points, the
trace-taxonomy closure (every ``FinishReason`` and every ``.fire()``
seam has a registered event).  Those assertions now live HERE as
registered rules — one registry, one violation type, one waiver
mechanism — consumed three ways: the original tests call
:func:`run_rule` (same assertions, same failures), ``scripts/
lint_dist.py`` runs the whole registry as a CLI gate (JSON report,
nonzero exit on unwaived violation), and :func:`run_rules` returns
the same report to any other caller.

A rule is a zero-argument callable returning ``list[Violation]``;
register with ``@rule("name")``.  Waivers (``LINT_WAIVERS.json`` at the
repo root) suppress KNOWN violations with a recorded justification —
every waiver must keep matching a live violation or it is reported
stale (so fixed code sheds its waiver instead of keeping a hole open).
"""

from __future__ import annotations

import ast
import dataclasses
import glob
import json
import os
import re

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_SRC = os.path.join(REPO, "triton_dist_tpu")
_KERNELS_DIR = os.path.join(_SRC, "kernels")

#: Default waiver file (docs/analysis.md "Waivers").
WAIVERS_PATH = os.path.join(REPO, "LINT_WAIVERS.json")

#: name -> rule callable; populated by :func:`rule`.
RULES: dict = {}


@dataclasses.dataclass
class Violation:
    rule: str
    message: str
    path: str = ""      # repo-relative file, "" for non-file rules
    line: int = 0
    waived: bool = False
    waiver_reason: str = ""

    @property
    def ident(self) -> str:
        """Stable identity waivers match against (line numbers excluded
        — they drift under unrelated edits)."""
        return f"{self.rule}:{self.path}:{self.message}"

    def __str__(self):
        loc = f"{self.path}:{self.line}: " if self.path else ""
        tag = " [WAIVED]" if self.waived else ""
        return f"[{self.rule}] {loc}{self.message}{tag}"


def rule(name: str):
    def deco(fn):
        RULES[name] = fn
        fn.rule_name = name
        return fn
    return deco


def run_rule(name: str) -> list:
    try:
        fn = RULES[name]
    except KeyError:
        raise ValueError(
            f"unknown rule {name!r}; registered: {sorted(RULES)}"
        ) from None
    return fn()


# ---------------------------------------------------------------------------
# Waivers
# ---------------------------------------------------------------------------


def load_waivers(path: str = None) -> list:
    """[{"rule", "match", "reason"}, ...] from the waiver file (missing
    file = no waivers; a malformed file raises — a torn waiver file
    must not silently un-waive the tree)."""
    path = path or WAIVERS_PATH
    if not os.path.exists(path):
        return []
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    waivers = data["waivers"]
    for w in waivers:
        for k in ("rule", "match", "reason"):
            if not w.get(k):
                raise ValueError(
                    f"waiver {w} missing required field {k!r} — every "
                    f"waiver needs a rule, a match, and a justification")
    return waivers


def apply_waivers(violations: list, waivers: list) -> tuple:
    """Mark waived violations; returns (unwaived, waived,
    stale_waivers) — a stale waiver matches nothing and should be
    deleted."""
    used = [False] * len(waivers)
    for v in violations:
        for i, w in enumerate(waivers):
            if w["rule"] == v.rule and w["match"] in v.ident:
                v.waived = True
                v.waiver_reason = w["reason"]
                used[i] = True
                break
    unwaived = [v for v in violations if not v.waived]
    waived = [v for v in violations if v.waived]
    stale = [w for w, u in zip(waivers, used) if not u]
    return unwaived, waived, stale


def run_rules(names=None, waivers_path: str = None) -> dict:
    """Run rules and fold in waivers; the dict is the JSON-report shape
    ``scripts/lint_dist.py`` emits."""
    names = sorted(RULES) if names is None else list(names)
    violations: list = []
    for name in names:
        violations += run_rule(name)
    unwaived, waived, stale = apply_waivers(
        violations, load_waivers(waivers_path))
    return {
        "rules_run": names,
        "violations": [str(v) for v in unwaived],
        "waived": [{"violation": str(v), "reason": v.waiver_reason}
                   for v in waived],
        "stale_waivers": stale,
        "ok": not unwaived,
    }


# ---------------------------------------------------------------------------
# Shared source scanning
# ---------------------------------------------------------------------------


def _py_files(*roots):
    for root in roots:
        for dirpath, _, names in os.walk(os.path.join(REPO, root)):
            for name in sorted(names):
                if name.endswith(".py"):
                    yield os.path.join(dirpath, name)


def _rel(path):
    return os.path.relpath(path, REPO)


# ---------------------------------------------------------------------------
# Rule: kernel-entry-annotated (from tests/test_observability.py)
# ---------------------------------------------------------------------------

#: Public entry points without a ``ctx: *Context`` parameter that must
#: still be annotated (the discovery heuristic below cannot see them).
ANNOTATE_REQUIRED_ENTRIES = {
    ("flash_attention.py", "flash_attention"),
    ("group_gemm.py", "group_gemm"),
    ("flash_decode.py", "sp_gqa_decode"),
}

#: Floor on the discovered entry-point surface: fewer means the
#: discovery heuristic broke, not that the library shrank.
ANNOTATE_MIN_ENTRIES = 14


def kernel_module_functions():
    """[(module file, FunctionDef node, source segment)] for every
    top-level function in triton_dist_tpu/kernels."""
    out = []
    for path in sorted(glob.glob(os.path.join(_KERNELS_DIR, "*.py"))):
        src = open(path).read()
        for node in ast.parse(src).body:
            if isinstance(node, ast.FunctionDef):
                out.append((os.path.basename(path), node,
                            ast.get_source_segment(src, node) or ""))
    return out


@rule("kernel-entry-annotated")
def check_kernel_entries_annotated() -> list:
    """Every public host-level kernel entry (any top-level
    non-underscore function taking ``ctx: <...>Context``, plus
    :data:`ANNOTATE_REQUIRED_ENTRIES`) must contain ``with annotate(``
    or (transitively) call a function that does — the launch-metadata
    contract the reference keeps via its proton hooks
    (allgather_gemm.py:120-130)."""
    funcs = kernel_module_functions()
    entries = set(ANNOTATE_REQUIRED_ENTRIES)
    for fname, node, seg in funcs:
        if node.name.startswith("_"):
            continue
        for a in node.args.args + node.args.kwonlyargs:
            if a.arg == "ctx" and a.annotation is not None and \
                    "Context" in ast.unparse(a.annotation):
                entries.add((fname, node.name))
    out = []
    if len(entries) < ANNOTATE_MIN_ENTRIES:
        out.append(Violation(
            "kernel-entry-annotated",
            f"entry-point discovery found only {len(entries)} entries "
            f"(expected >= {ANNOTATE_MIN_ENTRIES}) — the ctx-parameter "
            f"heuristic or the required-entries list broke",
            path="triton_dist_tpu/kernels"))
    covered = {node.name for _, node, seg in funcs
               if "with annotate(" in seg}
    if not covered:
        out.append(Violation(
            "kernel-entry-annotated",
            "no annotated kernel entries found at all",
            path="triton_dist_tpu/kernels"))
        return out
    for _ in range(8):   # transitive delegation (autotuned -> tunable
        grew = False     # -> entry is 2 hops)
        for _, node, seg in funcs:
            if node.name in covered:
                continue
            if any(re.search(rf"\b{re.escape(c)}\(", seg)
                   for c in covered):
                covered.add(node.name)
                grew = True
        if not grew:
            break
    for fname, name in sorted(entries):
        if name not in covered:
            out.append(Violation(
                "kernel-entry-annotated",
                f"public kernel entry point {name}() has no "
                f"profiling.annotate launch-metadata span (direct or "
                f"delegated) — add `with annotate(name, flops=, "
                f"bytes_accessed=)` around the dispatch (see "
                f"ag_gemm_gathered)",
                path=f"triton_dist_tpu/kernels/{fname}"))
    return out


# ---------------------------------------------------------------------------
# Rules: trace taxonomy (from tests/test_serve_trace.py)
# ---------------------------------------------------------------------------


@rule("finish-reasons-registered")
def check_finish_reasons_registered() -> list:
    """Every ``FinishReason`` retires through a registered ``retire``
    event — a new retirement reason cannot silently skip the flight
    recorder."""
    from triton_dist_tpu.serve import FinishReason
    from triton_dist_tpu.serve import trace as trace_mod

    out = []
    for fr in FinishReason:
        if fr.value not in trace_mod.RETIRE_REASONS:
            out.append(Violation(
                "finish-reasons-registered",
                f"FinishReason.{fr.name} has no registered retire "
                f"event (add it to serve/trace.RETIRE_REASONS)",
                path="triton_dist_tpu/serve/trace.py"))
    if "retire" not in trace_mod.EVENT_TYPES:
        out.append(Violation(
            "finish-reasons-registered",
            "'retire' missing from serve/trace.EVENT_TYPES",
            path="triton_dist_tpu/serve/trace.py"))
    return out


@rule("fire-points-registered")
def check_fire_points_registered() -> list:
    """Every ``.fire("<point>"`` seam in the source tree maps to a
    registered fault event type — an injection point added without
    registration fails lint (and tier-1) instead of silently skipping
    the recorder."""
    from triton_dist_tpu.serve import trace as trace_mod

    points: dict = {}
    for path in _py_files("triton_dist_tpu"):
        with open(path, encoding="utf-8") as f:
            for m in re.finditer(r'\.fire\(\s*"(\w+)"', f.read()):
                points.setdefault(m.group(1), _rel(path))
    out = []
    if not points:
        out.append(Violation(
            "fire-points-registered",
            "no .fire() seams found at all — expected at least the "
            "PR 3 injection points (the grep broke)",
            path="triton_dist_tpu"))
    for point, path in sorted(points.items()):
        if point not in trace_mod.FAULT_POINT_EVENTS:
            out.append(Violation(
                "fire-points-registered",
                f"fault point '{point}' has no registered event type "
                f"(add it to serve/trace.FAULT_POINT_EVENTS)",
                path=path))
    for point, ev in sorted(trace_mod.FAULT_POINT_EVENTS.items()):
        if ev not in trace_mod.EVENT_TYPES:
            out.append(Violation(
                "fire-points-registered",
                f"FAULT_POINT_EVENTS['{point}'] = '{ev}' is not a "
                f"registered EVENT_TYPE",
                path="triton_dist_tpu/serve/trace.py"))
    return out


# ---------------------------------------------------------------------------
# Rule: no-unseeded-randomness
# ---------------------------------------------------------------------------

#: module-level numpy draws / unseeded constructors that make a run
#: unreproducible; seeded forms (``default_rng(seed)``,
#: ``Random(seed)``, ``np.random.seed`` in scripts) stay legal.
_RANDOM_PATTERNS = (
    # np.random.<draw>( — everything except the seeded constructor
    (re.compile(r"\bnp\.random\.(?!default_rng\b|seed\b|Generator\b)"
                r"(\w+)\s*\("),
     "module-level np.random.{0}() draws from hidden global state"),
    (re.compile(r"\bnp\.random\.default_rng\(\s*\)"),
     "np.random.default_rng() with no seed is entropy-seeded"),
    (re.compile(r"\brandom\.Random\(\s*\)"),
     "random.Random() with no seed is entropy-seeded"),
    (re.compile(r"(?<![\w.])random\.(random|randint|choice|shuffle|"
                r"uniform|randrange|sample|gauss)\s*\("),
     "stdlib random.{0}() draws from the global unseeded RNG"),
)


@rule("no-unseeded-randomness")
def check_no_unseeded_randomness() -> list:
    """Library and script code must not draw from unseeded RNGs: every
    chaos schedule, sampler, and jitter must replay bit-identically
    from its recorded seed (the whole deterministic-chaos story —
    runtime/faults.py — rests on this).  Take a key/seed parameter
    instead; justified exceptions go in LINT_WAIVERS.json."""
    out = []
    self_path = os.path.abspath(__file__)
    for path in _py_files("triton_dist_tpu", "scripts"):
        if os.path.abspath(path) == self_path:
            continue   # the pattern/message table above matches itself
        with open(path, encoding="utf-8") as f:
            for ln, text in enumerate(f, 1):
                stripped = text.split("#", 1)[0]
                for pat, msg in _RANDOM_PATTERNS:
                    m = pat.search(stripped)
                    if m:
                        arg = m.group(1) if m.groups() else ""
                        out.append(Violation(
                            "no-unseeded-randomness",
                            msg.format(arg), path=_rel(path), line=ln))
    return out


# ---------------------------------------------------------------------------
# Rule: shed-paths-observable
# ---------------------------------------------------------------------------

#: Serving-policy modules whose degrade decisions the rule audits (the
#: scheduler is a mechanism layer — its pickers mutate no counters; the
#: caller that acts on the pick is the accountable path).
_SHED_POLICY_MODULES = ("serve/engine.py", "serve/fleet.py",
                       "serve/disagg.py", "serve/net.py")

#: Function names that constitute a shed/downgrade/preempt decision
#: (anchored to name-segment starts: "unfinished"/"pushed" are not
#: sheds).
_SHED_NAME_PAT = re.compile(
    r"(?:^|_)(?:shed|preempt|expire|brownout|degrade)")

#: Evidence the path counts (metrics) and explains itself (trace/audit).
_SHED_METRICS_PAT = re.compile(
    r"self\.metrics\b|\b_carry\.|\bobserve_\w+\(|ingress_shed_by_class")
_SHED_TRACE_PAT = re.compile(r"\.emit\(|\baudit\.record\(")

#: Fewer matching decision paths than this means the name heuristic
#: broke (renames), not that overload handling disappeared.
_SHED_MIN_PATHS = 4


@rule("shed-paths-observable")
def check_shed_paths_observable() -> list:
    """Every shed/downgrade/preempt decision path in the serving policy
    layers must increment a metrics counter AND land a trace/audit
    event — a degrade decision that is invisible to both the scrape and
    the flight recorder is un-debuggable precisely when it matters
    (overload).  A path may instead delegate to another function that
    carries both markers itself (e.g. ``_expire`` retiring through
    ``_retire``); justified exceptions go in LINT_WAIVERS.json."""
    fns: list = []  # (relpath, node, segment)
    for relmod in _SHED_POLICY_MODULES:
        path = os.path.join(REPO, "triton_dist_tpu", relmod)
        src = open(path, encoding="utf-8").read()
        for node in ast.walk(ast.parse(src)):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                fns.append((_rel(path), node,
                            ast.get_source_segment(src, node) or ""))
    # functions that carry both markers themselves are valid delegation
    # targets: calling one makes the caller's decision observable
    observable = {node.name for _, node, seg in fns
                  if _SHED_METRICS_PAT.search(seg)
                  and _SHED_TRACE_PAT.search(seg)}
    out = []
    checked = 0
    for relpath, node, seg in fns:
        if not _SHED_NAME_PAT.search(node.name):
            continue
        checked += 1
        delegates = any(re.search(rf"\b{re.escape(t)}\(", seg)
                        for t in observable if t != node.name)
        has_metrics = bool(_SHED_METRICS_PAT.search(seg)) or delegates
        has_trace = bool(_SHED_TRACE_PAT.search(seg)) or delegates
        if not (has_metrics and has_trace):
            missing = [w for w, ok in (("a metrics increment",
                                        has_metrics),
                                       ("a trace/audit event",
                                        has_trace)) if not ok]
            out.append(Violation(
                "shed-paths-observable",
                f"{node.name}() sheds/degrades without "
                f"{' or '.join(missing)} (and delegates to no "
                f"observable path) — overload decisions must never "
                f"be silent",
                path=relpath, line=node.lineno))
    if checked < _SHED_MIN_PATHS:
        out.append(Violation(
            "shed-paths-observable",
            f"only {checked} shed/preempt/expire/brownout paths found "
            f"(expected >= {_SHED_MIN_PATHS}) — the name heuristic "
            f"broke, update _SHED_NAME_PAT",
            path="triton_dist_tpu/serve"))
    return out


# ---------------------------------------------------------------------------
# Rule: collective-ids-unique
# ---------------------------------------------------------------------------


@rule("collective-ids-unique")
def check_collective_ids_unique() -> list:
    """Every ``collective_id`` in kernels/collective_ids.py must be
    distinct: two collective kernels sharing a barrier-semaphore id can
    cross-satisfy each other's entry barriers on hardware."""
    path = os.path.join(_KERNELS_DIR, "collective_ids.py")
    ids: dict = {}
    out = []
    tree = ast.parse(open(path).read())
    for node in tree.body:
        if isinstance(node, ast.Assign) and \
                isinstance(node.value, ast.Constant) and \
                isinstance(node.value.value, int):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    ids.setdefault(node.value.value, []).append(t.id)
    if not ids:
        out.append(Violation(
            "collective-ids-unique",
            "no integer collective ids found (the parse broke)",
            path=_rel(path)))
    for value, names in sorted(ids.items()):
        if len(names) > 1:
            out.append(Violation(
                "collective-ids-unique",
                f"collective_id {value} assigned to {sorted(names)} — "
                f"ids must be pairwise distinct",
                path=_rel(path)))
    return out


# ---------------------------------------------------------------------------
# Rule: ring-schedules-clean (the CommSchedule checker as a lint rule)
# ---------------------------------------------------------------------------

#: World sizes the lint rule sweeps — 2 (the degenerate ring), a run of
#: non-pow2 sizes (the slot maps' hard cases), and pow2 up to 32.
SCHEDULE_WORLDS = (2, 3, 4, 5, 6, 7, 8, 12, 16, 32)


@rule("ring-schedules-clean")
def check_ring_schedules() -> list:
    """Every registered kernel CommSchedule must simulate clean (no
    deadlock, no stranded credit, happens-before on every remote read,
    write-once outputs, bijective slot maps) at every world size in
    :data:`SCHEDULE_WORLDS`."""
    from triton_dist_tpu.analysis.comm_schedule import (
        SCHEDULE_BUILDERS,
        build_schedule,
    )
    from triton_dist_tpu.analysis.schedule_check import check_schedule

    out = []
    for kernel in sorted(SCHEDULE_BUILDERS):
        for world in SCHEDULE_WORLDS:
            for v in check_schedule(build_schedule(kernel, world)):
                out.append(Violation(
                    "ring-schedules-clean",
                    f"{kernel} world={world}: {v}",
                    path="triton_dist_tpu/analysis/comm_schedule.py"))
    return out


# ---------------------------------------------------------------------------
# Rule: durable-writes-integrity
# ---------------------------------------------------------------------------

#: A write-mode open or a json.dump in the serving layer — the
#: candidate durable-artifact producers the rule audits.
_DW_WRITE_PAT = re.compile(
    r"json\.dump\(|open\([^)\n]*[\"']wt?[\"']")

#: Atomicity evidence: the function publishes via rename (or delegates
#: to the shared helper, which does).
_DW_ATOMIC_PAT = re.compile(r"os\.replace\(|atomic_write_json\(")

#: Digest evidence: the written bytes carry a verifiable CRC stamp.
_DW_DIGEST_PAT = re.compile(
    r"atomic_write_json\(|stamp_crc\(|canonical_crc\(|crc32")

#: Fewer audited write sites than this means the detection pattern
#: broke (refactor moved the writers), not that serving stopped
#: persisting state — the shed-paths-observable self-blindness guard.
_DW_MIN_SITES = 4


@rule("durable-writes-integrity")
def check_durable_writes_integrity() -> list:
    """Every ``json.dump`` / ``open(..., "w")`` write of a durable
    serving artifact under ``serve/`` must route through the shared
    atomic-write + digest helper (``integrity.atomic_write_json``) or
    carry equivalent evidence itself — rename-publish atomicity AND a
    CRC stamp on the bytes (the journal's framing methods).  A durable
    artifact written raw is exactly the silent-corruption surface
    ISSUE 20 closed; justified exceptions (ephemeral discovery files,
    external-tool export formats) go in LINT_WAIVERS.json."""
    out = []
    checked = 0
    serve_dir = os.path.join(REPO, "triton_dist_tpu", "serve")
    for path in sorted(glob.glob(os.path.join(serve_dir, "*.py"))):
        if os.path.basename(path) == "integrity.py":
            continue   # the helper's own implementation
        src = open(path, encoding="utf-8").read()
        tree = ast.parse(src)
        for node in ast.walk(tree):
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            seg = ast.get_source_segment(src, node) or ""
            if not _DW_WRITE_PAT.search(seg):
                continue
            checked += 1
            has_atomic = bool(_DW_ATOMIC_PAT.search(seg))
            has_digest = bool(_DW_DIGEST_PAT.search(seg))
            if not (has_atomic and has_digest):
                missing = [w for w, ok in (
                    ("rename-publish atomicity", has_atomic),
                    ("a CRC digest stamp", has_digest)) if not ok]
                out.append(Violation(
                    "durable-writes-integrity",
                    f"{node.name}() writes a durable artifact without "
                    f"{' or '.join(missing)} — route it through "
                    f"integrity.atomic_write_json",
                    path=_rel(path), line=node.lineno))
    if checked < _DW_MIN_SITES:
        out.append(Violation(
            "durable-writes-integrity",
            f"only {checked} durable write sites found under serve/ "
            f"(expected >= {_DW_MIN_SITES}) — the detection pattern "
            f"broke, update _DW_WRITE_PAT",
            path="triton_dist_tpu/serve"))
    return out
