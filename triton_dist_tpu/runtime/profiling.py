"""Profiling: per-process traces merged for whole-job timelines.

Reference analog: ``group_profile`` (utils.py:417-501) — per-rank
torch.profiler chrome traces gathered to rank 0, pid/tid re-namespaced per
rank, merged and gzipped.

TPU-native design: ``jax.profiler`` captures device + host activity per
process into Perfetto/TensorBoard format; ``group_profile`` scopes each
rank's output dir, then rank 0 merges every rank's chrome events into ONE
gzipped timeline with per-rank pid re-namespacing — the same single-
artifact contract as the reference's merge pipeline, minus its
gather-to-rank-0 copy step (ranks write a shared filesystem directly).
The per-rank dirs also remain loadable individually.

The serving engine's flight recorder rides the same merge machinery:
``serve.trace.FlightRecorder.export_profile(job_dir)`` drops the engine
timeline as ``rank{i}/engine.trace.json.gz`` (its events claim
``serve.trace.ENGINE_PID`` — below the Linux pid cap, so the per-rank
pid re-namespacing in :func:`merge_rank_traces` stays injective), and
one merged ui.perfetto.dev file then holds the device timeline and the
engine's request lifecycle spans side by side (docs/observability.md
has the recipe).
"""

from __future__ import annotations

import contextlib
import os

import jax


class group_profile:
    """Context manager: ``with group_profile("ag_gemm", do_prof=True): ...``.

    Writes traces to ``{base_dir}/{name}/rank{process_index}``; view with
    TensorBoard's profile plugin or ui.perfetto.dev.  With ``merge=True``
    (the default), rank 0 additionally merges every rank's chrome trace
    into ONE gzipped timeline at ``{base_dir}/{name}/merged.trace.json.gz``
    — the reference's single-artifact job trace (utils.py:282-501), with
    pids re-namespaced per rank so a 32-chip job loads as one file in
    ui.perfetto.dev.
    """

    def __init__(self, name: str = "trace", do_prof: bool = True,
                 base_dir: str = "prof", merge: bool = True):
        self.name = name
        self.do_prof = do_prof
        self.base_dir = base_dir
        self.merge = merge
        self.merged_path = None
        self._cm = None

    def __enter__(self):
        if self.do_prof:
            out = os.path.join(self.base_dir, self.name, f"rank{jax.process_index()}")
            os.makedirs(out, exist_ok=True)
            self._cm = jax.profiler.trace(out)
            self._cm.__enter__()
        return self

    def __exit__(self, *exc):
        if self._cm is not None:
            self._cm.__exit__(*exc)
            if self.merge:
                if jax.process_count() > 1:
                    # Every rank must finish flushing its trace files
                    # before rank 0 reads them (same sync used by
                    # checkpoint.py).
                    from jax.experimental import multihost_utils

                    multihost_utils.sync_global_devices(
                        "group_profile_merge")
                if jax.process_index() == 0:
                    try:
                        self.merged_path = merge_rank_traces(
                            os.path.join(self.base_dir, self.name))
                    except Exception:
                        self.merged_path = None  # per-rank dirs remain
        return False


def merge_rank_traces(job_dir: str) -> str | None:
    """Merge every ``rank*/`` chrome trace under ``job_dir`` into one
    gzipped timeline ``{job_dir}/merged.trace.json.gz``.

    Each rank's events keep their own pid space, prefixed into a distinct
    range (rank r's pid p becomes ``r * 10_000_000 + p`` — injective since
    Linux pids cap at 4194304) and its process
    names get a ``[rank r]`` suffix — the reference's pid/tid
    re-namespacing (utils.py:282-501) on the TPU trace layout
    (``plugins/profile/<run>/*.trace.json.gz`` per process).  Returns the
    merged path, or None when no per-rank traces exist (e.g. profiling
    was off).  NOTE: on multi-host, every rank must write under a SHARED
    filesystem for rank 0 to see the dirs; otherwise per-rank dirs stay
    separate (perfetto can still load several files side by side).
    """
    import glob
    import gzip
    import json

    merged_events = []
    ranks = sorted(glob.glob(os.path.join(job_dir, "rank*")))
    found = 0
    for rank_dir in ranks:
        m = os.path.basename(rank_dir).replace("rank", "")
        try:
            rank = int(m)
        except ValueError:
            continue
        traces = sorted(glob.glob(
            os.path.join(rank_dir, "**", "*.trace.json.gz"),
            recursive=True))
        for path in traces:
            with gzip.open(path, "rt") as f:
                data = json.load(f)
            found += 1
            for ev in data.get("traceEvents", []):
                if "pid" in ev:
                    ev = dict(ev)
                    ev["pid"] = rank * 10_000_000 + int(ev["pid"])
                    if (ev.get("ph") == "M"
                            and ev.get("name") == "process_name"):
                        args = dict(ev.get("args", {}))
                        args["name"] = (f"{args.get('name', '')} "
                                        f"[rank {rank}]")
                        ev["args"] = args
                merged_events.append(ev)
    if not found:
        return None
    out = os.path.join(job_dir, "merged.trace.json.gz")
    with gzip.open(out, "wt") as f:
        json.dump({"traceEvents": merged_events}, f)
    return out


@contextlib.contextmanager
def annotate(name: str, *, flops: int | None = None,
             bytes_accessed: int | None = None):
    """Named trace span carrying launch metadata (reference analog: the
    launch_metadata proton hooks — GEMMs report name/flops/bytes to the
    profiler, allgather_gemm.py:120-130).

    ``flops``/``bytes_accessed`` are per-device totals for the spanned
    op; they are embedded in the span label together with the derived
    roofline time (max of MXU-bound and HBM-bound, from the same chip
    tables ``kernels/perf_model`` estimates with, via ``topology``), so a
    profiler timeline read against the span directly yields
    achieved-vs-attainable.  The label
    rides BOTH ``TraceAnnotation`` (host timeline) and ``jax.named_scope``
    (baked into HLO op metadata at trace time → device timeline).
    """
    label = name
    if flops is not None or bytes_accessed is not None:
        parts = [name]
        if flops is not None:
            parts.append(f"flops={flops}")
        if bytes_accessed is not None:
            parts.append(f"bytes={bytes_accessed}")
        try:
            from triton_dist_tpu.runtime import topology

            tf = topology.peak_bf16_tflops()
            gbps = topology.hbm_bandwidth_gbps()
            sol_ms = max(
                (flops or 0) / (tf * 1e9),
                (bytes_accessed or 0) / (gbps * 1e6)) if (tf and gbps) else 0.0
            if sol_ms:
                parts.append(f"sol_ms={sol_ms:.3f}")
        except Exception:
            pass
        label = "#".join(parts)
    with jax.profiler.TraceAnnotation(label), jax.named_scope(label):
        yield


# What a device program's body is doing, by SEAM of the one layer loop
# (``models/generate.py`` ``_layer_stack``) and of the family seams under
# it: the closed set :func:`region` takes.  docs/observability.md "Regions
# of a device program" has the seam of each; ``benchmarks/regions.py`` reads
# them back from a chip trace.
REGIONS = (
    "embed", "proj", "kv_write", "attn", "attn.gate", "out_proj", "ffn",
    "head",
    "moe.route", "moe.align", "moe.experts", "moe.combine", "moe.shared",
    "dsa.index", "dsa.select", "mla.expand", "sample",
    "ssm.in", "ssm.conv", "ssm.scan", "ssm.out", "gmu",
    "gdn.in", "gdn.conv", "gdn.rule", "gdn.out",
    "hc.pre", "hc.post",
)
REGION_PREFIX = "rg_"


def region(name: str):
    """``with region("ffn"): ...`` inside a jitted body: every operation
    traced under it carries ``rg_ffn`` in its HLO ``op_name`` path, and a
    device trace read beside the compiled text says what the model was
    doing in each (``benchmarks/regions.py``; the innermost scope wins).
    ``name`` is one of :data:`REGIONS`; a dot becomes ``__``, so that the
    scope is one HLO-legal word that no rule which cuts an instruction's
    name at its first ``.`` shortens (XLA names a Mosaic call with no name
    of its own after the scope around it).

    Metadata only: the scope is entered once, while the body is traced,
    and the lowered computation is the same with it and without (held in
    tests/test_regions.py) — so no ``TraceAnnotation``, no flops / bytes
    label (:func:`annotate` keeps those for the kernels) and no switch."""
    if name not in REGIONS:
        raise ValueError(f"region {name!r}: one of {REGIONS}")
    return jax.named_scope(REGION_PREFIX + name.replace(".", "__"))
