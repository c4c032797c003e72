"""Jaxpr auditing of the serving engine's compiled device programs.

The serving stack's two worst historical bug classes were both
*trace-level* properties nobody checked mechanically: the
executable-cache fork (PRs 7/12 — one program tracing under two
argument placements, found by hand signature-diffing) and misplaced
collective/donation seams.  Every engine device program is registered
behind a ``jit_cache.CountingJit`` (world-1) or ``serve.mesh.
ShardedProgram`` (mesh) wrapper that captures the abstract signature of
each distinct traced call — so this module can re-trace EVERY program
the engine actually compiled (``jax.make_jaxpr`` over the captured
``ShapeDtypeStruct`` signatures, device-free) and audit the jaxpr:

- **no host callbacks in fused hot paths** — a ``pure_callback`` /
  ``io_callback`` / ``debug_callback`` inside a decode/prefill program
  re-serializes the device loop on the host (the dispatch economics the
  horizon exists to remove);
- **donated buffers actually consumed** — each ``donated_invars`` entry
  of a pjit must be used by the traced computation AND have a
  shape/dtype-matching output XLA can alias it to; an unusable donation
  silently doubles the KV pools' memory footprint;
- **collectives only at declared seams** — the per-program allowed
  collective set (``serve.mesh.collective_seams``: psum at the
  out-proj/FFN row-parallel seams and the sharded-vocab logits seam for
  ``kv_shard="heads"``, the SP combine's gather for ``"seq"``, nothing
  anywhere else; world-1 programs allow none);
- **statics drawn from declared ladders** — every captured static kwarg
  (the horizon's ``H``, the spec round's ``K``) must sit on its
  declared ladder; an off-ladder static is exactly the retrace-hazard /
  cache-fork class warmup's fixed point exists to prevent.

Entry points: :func:`audit_program` for one registry record,
:func:`audit_engine` for a whole ``ServeEngine``
(``engine.program_registry()``).
"""

from __future__ import annotations

import dataclasses
from collections import Counter
from typing import Optional

import jax
from jax.extend import core as jex_core

#: Wire/collective primitives (``psum2`` is psum's shard_map spelling on
#: older traces; kept so a seam table never has to know).  ``pbroadcast``
#: is NOT here: it is shard_map's type-level replication adjustment, no
#: bytes move.
COLLECTIVE_PRIMS = frozenset({
    "psum", "psum2", "ppermute", "pgather", "all_gather",
    "all_gather_invariant", "all_to_all", "reduce_scatter",
    "psum_scatter",
})

#: Host-callback primitives — never legal inside a fused hot path.
CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "outside_call",
})

#: jax spells some collectives differently across entry points /
#: versions; seams declare the canonical name.
_PRIM_CANON = {
    "psum2": "psum",
    "all_gather_invariant": "all_gather",
}


@dataclasses.dataclass
class AuditFinding:
    program: str
    #: "callback" | "donation" | "collective" | "ladder" — plus the
    #: meta outcomes "untraced" (registered but never called) and
    #: "retrace-failed" (captured signature would not re-trace)
    check: str
    message: str

    def __str__(self):
        return f"[{self.check}] {self.program}: {self.message}"


# ---------------------------------------------------------------------------
# Jaxpr walking
# ---------------------------------------------------------------------------


def _iter_subjaxprs(params: dict):
    for v in params.values():
        if isinstance(v, jex_core.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, jex_core.Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            for x in v:
                if isinstance(x, jex_core.ClosedJaxpr):
                    yield x.jaxpr
                elif isinstance(x, jex_core.Jaxpr):
                    yield x


def jaxpr_stats(jaxpr) -> dict:
    """Recursive walk: primitive counts (collectives counted per SEAM,
    see ``walk``) + per-jit donation records.

    Returns ``{"prims": Counter, "donations": [(name, jaxpr,
    donated_invars)]}`` — donations carry the jit's inner jaxpr so
    :func:`_check_donation` can test use + aliasability."""
    prims: Counter = Counter()
    donations: list = []

    def walk(j):
        prev = None
        for eqn in j.eqns:
            name = eqn.primitive.name
            # A collective over a pytree binds one equation per leaf,
            # back to back with identical parameters: that is ONE seam
            # (one call site, one point where ranks meet), so a run of
            # adjacent identical collectives counts once.
            run = ((name, str(eqn.params))
                   if name in COLLECTIVE_PRIMS else None)
            if run is None or run != prev:
                prims[name] += 1
            prev = run
            if name == "jit":
                donated = eqn.params.get("donated_invars", ())
                if any(donated):
                    donations.append(
                        (eqn.params.get("name", "jit"),
                         eqn.params["jaxpr"].jaxpr, tuple(donated)))
            for sub in _iter_subjaxprs(eqn.params):
                walk(sub)

    walk(jaxpr)
    return {"prims": prims, "donations": donations}


def _used_vars(jaxpr) -> set:
    used = set()
    for eqn in jaxpr.eqns:
        for v in eqn.invars:
            if not isinstance(v, jex_core.Literal):
                used.add(v)
    for v in jaxpr.outvars:
        if not isinstance(v, jex_core.Literal):
            used.add(v)
    return used


def _check_donation(program: str, name: str, jaxpr, donated) -> list:
    """Donated pjit invars must be consumed: used by the computation and
    coverable by a shape/dtype-matching output (XLA aliases donated
    buffers only onto identical avals — an unmatched donation is a
    silent no-op that keeps both buffers live)."""
    findings = []
    used = _used_vars(jaxpr)
    out_avals = Counter()
    for v in jaxpr.outvars:
        if hasattr(v, "aval"):
            a = v.aval
            out_avals[(tuple(a.shape), str(a.dtype))] += 1
    for i, (v, d) in enumerate(zip(jaxpr.invars, donated)):
        if not d:
            continue
        a = v.aval
        key = (tuple(a.shape), str(a.dtype))
        if v not in used:
            findings.append(AuditFinding(
                program, "donation",
                f"{name}: donated argument {i} "
                f"({key[1]}{list(key[0])}) is never used by the traced "
                f"computation — the donation frees nothing"))
        elif out_avals[key] <= 0:
            findings.append(AuditFinding(
                program, "donation",
                f"{name}: donated argument {i} "
                f"({key[1]}{list(key[0])}) has no shape/dtype-matching "
                f"output to alias — XLA keeps both buffers live"))
        else:
            out_avals[key] -= 1
    return findings


# ---------------------------------------------------------------------------
# Program tracing (CountingJit / ShardedProgram signatures)
# ---------------------------------------------------------------------------


def _signatures(fn) -> list:
    """Captured (args_abs, kwargs_abs) pairs of ``fn`` — a
    ``CountingJit`` (possibly wrapping a ``ShardedProgram``) or a bare
    ``ShardedProgram``."""
    inner = getattr(fn, "fn", fn)           # unwrap CountingJit
    if hasattr(inner, "_prog") and hasattr(inner, "captured"):
        # ShardedProgram: statics-key -> (placed_args_abs, statics)
        return [(args, kw) for (args, kw) in inner.captured.values()]
    cap = getattr(fn, "captured", None)
    if cap:
        return list(cap.values())
    return []


def _replay(fn, args_abs, kwargs, via):
    """Replay one captured signature of ``fn`` through ``via`` —
    ``jax.make_jaxpr`` (the audits) or ``lambda f: jax.jit(f).lower``
    (:func:`lowered_mosaic_calls`)."""
    inner = getattr(fn, "fn", fn)
    if hasattr(inner, "_prog"):
        prog = inner._prog(tuple(sorted(kwargs.items())))
        return via(prog)(*args_abs)
    # make_jaxpr turns EVERY argument it receives into a tracer — but
    # static kwargs (the horizon's H, prefill's n_valid) were concrete
    # Python values at the real call and must stay concrete here, or
    # the inner jit hashes a tracer as a static / branches on one.
    # Array-shaped kwargs (ShapeDtypeStructs) trace; the rest closes
    # over concretely.
    traced_kw = {k: v for k, v in kwargs.items()
                 if isinstance(v, jax.ShapeDtypeStruct)}
    static_kw = {k: v for k, v in kwargs.items()
                 if not isinstance(v, jax.ShapeDtypeStruct)}

    def call(*args, **tkw):
        return inner(*args, **tkw, **static_kw)

    return via(call)(*args_abs, **traced_kw)


def _trace(fn, args_abs, kwargs):
    return _replay(fn, args_abs, kwargs, jax.make_jaxpr)


#: How a Pallas TPU kernel appears in lowered StableHLO.
MOSAIC_CALL = "tpu_custom_call"


def lowered_mosaic_calls(engine) -> dict:
    """``{program: [Mosaic custom calls per captured signature]}`` —
    every program of ``engine.program_registry()`` re-lowered for THIS
    process's backend from the signatures it was actually called with,
    and the ``tpu_custom_call`` ops counted in the StableHLO text.

    This is the evidence that a Pallas kernel was traced into what the
    engine served (``chip_smoke.py`` fails on a zero): under
    ``impl="auto"`` a guard that reroutes to XLA leaves no other trace,
    and a flag or a config echo is not evidence.  Interpret-mode kernels
    lower to plain HLO and count 0; programs never called have no
    signature and are left out."""
    out = {}
    for rec in engine.program_registry():
        counts = [
            _replay(rec["fn"], args_abs, kwargs,
                    lambda f: jax.jit(f).lower).as_text().count(MOSAIC_CALL)
            for args_abs, kwargs in _signatures(rec["fn"])]
        if counts:
            out[rec["name"]] = counts
    return out


def audit_program(rec: dict) -> list:
    """Audit one registry record ``{"name", "fn", "ladders", "seams"}``.

    ``ladders`` maps static kwarg name -> allowed values; ``seams`` maps
    collective primitive name -> expected occurrence count per trace
    (``None`` = any count > 0 allowed).  Collective primitives absent
    from ``seams`` are violations wherever they appear.  Returns
    [] when every captured signature audits clean; records with no
    captured signatures return a single "untraced" finding so a
    registry entry cannot silently fall out of coverage (callers that
    know a program is legitimately idle filter these).
    """
    name = rec["name"]
    fn = rec["fn"]
    ladders = rec.get("ladders") or {}
    seams = rec.get("seams") or {}
    sigs = _signatures(fn)
    if not sigs:
        return [AuditFinding(
            name, "untraced",
            "no captured trace signature — program never called, so "
            "nothing was audited")]
    findings: list = []
    seen: set = set()
    for args_abs, kwargs in sigs:
        # ladder membership of every captured static
        for k, allowed in ladders.items():
            if k in kwargs and kwargs[k] not in allowed:
                f = AuditFinding(
                    name, "ladder",
                    f"static {k}={kwargs[k]!r} is off the declared "
                    f"ladder {list(allowed)} — every off-ladder static "
                    f"is one more compiled executable (the cache-fork "
                    f"class)")
                if str(f) not in seen:
                    seen.add(str(f))
                    findings.append(f)
        try:
            closed = _trace(fn, args_abs, kwargs)
        except Exception as e:  # noqa: BLE001 — surface, don't crash
            f = AuditFinding(name, "retrace-failed",
                             f"re-trace failed: {type(e).__name__}: {e}")
            if str(f) not in seen:
                seen.add(str(f))
                findings.append(f)
            continue
        stats = jaxpr_stats(closed.jaxpr)
        canon: Counter = Counter()
        for prim, n in stats["prims"].items():
            canon[_PRIM_CANON.get(prim, prim)] += n
        for prim, n in sorted(canon.items()):
            if prim in CALLBACK_PRIMS:
                f = AuditFinding(
                    name, "callback",
                    f"host callback primitive '{prim}' x{n} inside a "
                    f"fused hot-path program")
                if str(f) not in seen:
                    seen.add(str(f))
                    findings.append(f)
            if prim in COLLECTIVE_PRIMS:
                if prim not in seams:
                    f = AuditFinding(
                        name, "collective",
                        f"collective '{prim}' x{n} outside the declared "
                        f"seams {sorted(seams) or '{}'}")
                elif seams[prim] is not None and n != seams[prim]:
                    f = AuditFinding(
                        name, "collective",
                        f"collective '{prim}' appears x{n}, declared "
                        f"seam count is {seams[prim]}")
                else:
                    continue
                if str(f) not in seen:
                    seen.add(str(f))
                    findings.append(f)
        for pjit_name, inner_jaxpr, donated in stats["donations"]:
            for f in _check_donation(name, pjit_name, inner_jaxpr,
                                     donated):
                if str(f) not in seen:
                    seen.add(str(f))
                    findings.append(f)
    return findings


def audit_engine(engine, *, include_untraced: bool = False) -> dict:
    """Audit every program in ``engine.program_registry()``.

    Returns ``{"programs": [name...], "audited": [name...],
    "skipped": [name...], "findings": [AuditFinding...]}`` — skipped =
    registered but never traced (legitimate for paths the engine's
    traffic never exercised, e.g. the verify program on a spec-less
    engine); pass ``include_untraced=True`` to turn those into
    findings instead."""
    report = {"programs": [], "audited": [], "skipped": [],
              "findings": []}
    for rec in engine.program_registry():
        report["programs"].append(rec["name"])
        findings = audit_program(rec)
        if len(findings) == 1 and findings[0].check == "untraced":
            report["skipped"].append(rec["name"])
            if include_untraced:
                report["findings"] += findings
            continue
        report["audited"].append(rec["name"])
        report["findings"] += findings
    return report
