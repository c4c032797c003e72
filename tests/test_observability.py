"""IR dump + merged job trace (reference: dump_ir / group_profile merge)
+ the kernel-layer observability plane (docs/observability.md "Kernel
observability"): the annotation-coverage meta-test and the overlap
scoreboard (runtime/kprobe.py).

Reference analog: per-kernel ``dump_ir`` (moe_reduce_rs.py:1009-1015) and
the single gzipped whole-job timeline (utils.py:282-501).
"""

import glob
import gzip
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.runtime import dump
from triton_dist_tpu.runtime.profiling import group_profile, merge_rank_traces


def test_unknown_device_kind_raises_instead_of_borrowing_a_peak():
    """A roofline row is never a default: only platform ``cpu`` gets the
    virtual-mesh row, and an accelerator the table does not know raises
    (a peak guessed for it would make every utilization fiction)."""
    from triton_dist_tpu.runtime import topology

    assert topology._lookup("TPU v5 lite", "tpu")[0] == 197.0
    assert topology._lookup("cpu", "cpu") == topology._CPU_SPEC
    with pytest.raises(ValueError, match="no roofline row"):
        topology._lookup("TPU v9 mega", "tpu")
    # a TPU is never matched by the cpu row, whatever its kind says
    with pytest.raises(ValueError, match="no roofline row"):
        topology._lookup("cpu", "tpu")
    with pytest.raises(ValueError, match="no measured dense-dot ceiling"):
        topology.measured_dot_ceiling_tflops()      # device_kind "cpu"


def test_require_tpu_refuses_the_cpu():
    """Entry points that report on the device have no CPU fallback."""
    from triton_dist_tpu.runtime import require_tpu

    with pytest.raises(SystemExit, match="chip_smoke.py: needs a TPU"):
        require_tpu("chip_smoke.py")


def test_compile_cache_is_placed_from_outside_or_in_the_checkout(
        monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself and the
    helper sets nothing.  Unset: the fixed ``<checkout>/.jax_cache`` —
    never a tempfile, pid or clock (the path is part of the cache key)."""
    from triton_dist_tpu.runtime import bootstrap

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        assert bootstrap.configure_compile_cache() == "/some/dir"
        assert jax.config.jax_compilation_cache_dir == prev   # untouched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(repo, ".jax_cache")
        assert bootstrap.configure_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert bootstrap.configure_compile_cache() == want     # stable
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_dump_lowered_writes_stablehlo(tmp_path):
    def f(x):
        return jnp.sin(x) * 2.0

    files = dump.dump_lowered(f, jnp.ones((8, 128)), name="sin_op",
                              directory=str(tmp_path))
    assert any(p.endswith(".stablehlo.txt") for p in files)
    text = open(files[0]).read()
    assert "stablehlo" in text or "sine" in text, text[:200]
    # optimized HLO (or a recorded compile error) rides along
    assert len(files) == 2


def test_cached_shard_jit_dump_hook(tmp_path, mesh2, key, monkeypatch):
    """TDT_DUMP_IR makes every cached_shard_jit program dump on first call."""
    from triton_dist_tpu.kernels.allgather import (
        AllGatherContext,
        AllGatherMethod,
        all_gather,
    )
    from triton_dist_tpu.runtime.jit_cache import _build

    monkeypatch.setenv(dump.ENV_VAR, str(tmp_path))
    _build.cache_clear()  # programs built before the env was set won't dump
    x = jax.random.normal(key, (16, 128), jnp.float32)
    ctx = AllGatherContext(mesh=mesh2, axis="tp",
                           method=AllGatherMethod.XLA)
    out = all_gather(x, ctx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x))
    dumped = glob.glob(str(tmp_path / "*.stablehlo.txt"))
    assert dumped, list(tmp_path.iterdir())
    assert "all_gather" in os.path.basename(dumped[0])
    _build.cache_clear()  # drop the wrapped executables (env-dependent)


def test_group_profile_merges_single_artifact(tmp_path, key):
    """group_profile produces ONE gzipped chrome trace for the job."""
    with group_profile("unit", do_prof=True,
                       base_dir=str(tmp_path)) as prof:
        jax.block_until_ready(
            jnp.dot(jax.random.normal(key, (256, 256)),
                    jax.random.normal(key, (256, 256))))
    assert prof.merged_path is not None, \
        list(glob.glob(str(tmp_path / "unit" / "**"), recursive=True))
    with gzip.open(prof.merged_path, "rt") as f:
        data = json.load(f)
    events = data["traceEvents"]
    assert events
    # pid re-namespacing: rank 0 pids keep their own (sub-1e7) range
    pids = {ev["pid"] for ev in events if "pid" in ev}
    assert pids and all(0 <= p < 10_000_000 for p in pids)


def test_merge_rank_traces_renames_ranks(tmp_path):
    """Synthetic 2-rank layout → one merged file, pids disjoint by rank."""
    for rank in (0, 1):
        d = tmp_path / f"rank{rank}" / "plugins" / "profile" / "run1"
        os.makedirs(d)
        events = [
            {"ph": "M", "pid": 1, "name": "process_name",
             "args": {"name": "device"}},
            {"ph": "X", "pid": 1, "tid": 2, "ts": 10 * (rank + 1),
             "dur": 5, "name": f"op{rank}"},
        ]
        with gzip.open(d / "host.trace.json.gz", "wt") as f:
            json.dump({"traceEvents": events}, f)
    merged = merge_rank_traces(str(tmp_path))
    with gzip.open(merged, "rt") as f:
        data = json.load(f)
    pids = sorted({ev["pid"] for ev in data["traceEvents"]})
    assert pids == [1, 10_000_001]
    names = {ev["args"]["name"] for ev in data["traceEvents"]
             if ev.get("ph") == "M"}
    assert names == {"device [rank 0]", "device [rank 1]"}


# ---------------------------------------------------------------------------
# Annotation coverage (the trace-taxonomy meta-test pattern applied to
# the kernel library): every PUBLIC kernel entry point must run under a
# profiling.annotate launch-metadata span — directly, or by delegating
# to an annotated entry — so a new kernel cannot silently skip the
# profiler.  The assertion logic lives in the analysis rule registry
# (ISSUE 15: one registry serves this test, scripts/lint_dist.py, and
# the bench-artifact lint stamp); this test keeps the tier-1 teeth.
# ---------------------------------------------------------------------------


def test_kernel_entry_points_annotated():
    """Source-grep closure via the ``kernel-entry-annotated`` lint rule
    (analysis/rules.py — the migrated meta-test): every public
    host-level kernel entry (any top-level non-underscore function
    taking ``ctx: <...>Context``, plus the registered no-ctx entries)
    must run under ``with annotate(`` directly or by delegation."""
    from triton_dist_tpu.analysis import run_rule
    from triton_dist_tpu.analysis.rules import (
        ANNOTATE_MIN_ENTRIES,
        ANNOTATE_REQUIRED_ENTRIES,
    )

    # the no-ctx required surface is still registered (a deleted entry
    # would silently shrink coverage)
    assert {("flash_attention.py", "flash_attention"),
            ("group_gemm.py", "group_gemm"),
            ("flash_decode.py", "sp_gqa_decode")} \
        <= ANNOTATE_REQUIRED_ENTRIES
    assert ANNOTATE_MIN_ENTRIES >= 14   # the known surface
    violations = run_rule("kernel-entry-annotated")
    assert not violations, "\n".join(str(v) for v in violations)


# ---------------------------------------------------------------------------
# Overlap scoreboard (runtime/kprobe.py)
# ---------------------------------------------------------------------------


def test_kprobe_ag_gemm_report(mesh2):
    """The ag_gemm scoreboard at a small shape: report structure,
    per-step phase slices with perf_model predictions, and the derived
    fields' internal consistency."""
    from triton_dist_tpu.runtime import kprobe

    rep = kprobe.probe_ag_gemm(mesh2, M=128, K=128, n_loc=128,
                               trials=1)
    d = rep.to_dict()
    assert d["kernel"] == "ag_gemm" and d["world"] == 2
    assert d["timings_ms"]["fused"] > 0
    assert d["overlap_efficiency"] > 0
    # world=2 ring: 2 compute slices + 1 comm slice
    phases = [(s["step"], s["phase"]) for s in d["steps"]]
    assert phases == [(0, "comm"), (0, "compute"), (1, "compute")] or \
        sorted(phases) == [(0, "comm"), (0, "compute"), (1, "compute")]
    for s in d["steps"]:
        assert s["measured_ms"] > 0
        assert s["predicted_ms"] >= 0
        if s["phase"] == "compute":
            # arrival-order schedule: rank r consumes slot (r - s) % 2
            assert s["slots"] == [(r - s["step"]) % 2 for r in (0, 1)]
    # critical path fractions partition the per-step maxima
    cp = d["critical_path"]
    assert cp["bound"] in ("compute", "comm")
    # (each of the three is rounded to 4 decimals in the report)
    assert abs(d["timings_ms"]["sliced_critical"]
               - (cp["compute_ms"] + cp["comm_ms"])) < 2e-4
    # the model table is present and finite
    assert d["model"]["model_vs_measured"] >= 0
    # serial >= critical (overlap can only help)
    assert d["timings_ms"]["sliced_serial"] >= \
        d["timings_ms"]["sliced_critical"] - 1e-9


def test_kprobe_report_merges_with_engine_trace(mesh2, tmp_path):
    """The acceptance wiring: a kernel_report Perfetto export and an
    engine FlightRecorder export land in ONE job dir, and
    merge_rank_traces folds both into one valid trace with disjoint
    per-rank pid namespaces (device + engine + kernel in one
    ui.perfetto.dev file)."""
    from triton_dist_tpu.runtime import kprobe
    from triton_dist_tpu.serve.trace import ENGINE_PID, FlightRecorder

    rep = kprobe.probe_ag_gemm(mesh2, M=128, K=128, n_loc=128,
                               trials=1)
    rep.save(str(tmp_path / "ag_gemm.overlap.json"))
    paths = rep.export_profile(str(tmp_path))
    assert len(paths) == 2 and all(os.path.exists(p) for p in paths)

    fr = FlightRecorder(level=1)
    fr.emit("submit", "r0", prompt=4)
    fr.emit("retire", "r0", reason="length")
    fr.export_profile(str(tmp_path))   # rank0/engine.trace.json.gz

    merged = merge_rank_traces(str(tmp_path))
    assert merged is not None
    with gzip.open(merged, "rt") as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    pids = {ev["pid"] for ev in evs if "pid" in ev}
    # rank 0 holds kprobe + engine pids; rank 1 holds the re-namespaced
    # kprobe pid (merge adds rank * 10_000_000)
    assert kprobe.KPROBE_PID in pids
    assert ENGINE_PID in pids
    assert 10_000_000 + kprobe.KPROBE_PID in pids
    names = {ev.get("name") for ev in evs}
    assert any(n and n.startswith("ag_gemm step") for n in names), names
    # the report JSON is valid and carries the roofline table
    d = json.load(open(tmp_path / "ag_gemm.overlap.json"))
    assert {"overlap_efficiency", "critical_path", "model",
            "steps"} <= set(d)


def test_kprobe_unknown_kernel_raises(mesh2):
    from triton_dist_tpu.runtime import kprobe

    with pytest.raises(ValueError, match="unknown kernel"):
        kprobe.run_probe("nope", mesh2)


def test_kprobe_sp_decode_report(mesh2):
    """The SP flash-decode combine scoreboard: local-decode compute
    phase + combine comm phase, overlap efficiency derived from the
    fused leg."""
    from triton_dist_tpu.runtime import kprobe

    rep = kprobe.probe_sp_decode(mesh2, axis="tp", B=2, Hq=4, Hkv=2,
                                 S=128, D=64, trials=1)
    d = rep.to_dict()
    assert [s["phase"] for s in d["steps"]] == ["comm", "compute"] or \
        sorted(s["phase"] for s in d["steps"]) == ["comm", "compute"]
    assert d["timings_ms"]["fused"] > 0 and d["overlap_efficiency"] > 0
