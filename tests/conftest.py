"""Pytest config: force a clean multi-device virtual-CPU JAX for every run.

Two things happen here, both before any JAX *backend* is initialized (PJRT
clients are created lazily):

1. **CPU only.**  Tests never touch an accelerator — a chip belongs to one
   process at a time, and the chip runs are ``chip_smoke.py`` and
   ``scripts/smoke_tpu.py`` — so ``jax_platforms=cpu`` is forced before any
   backend comes up.
2. **Virtual mesh.**  ``--xla_force_host_platform_device_count=N`` (default
   16: 2x the largest 8-device test mesh, so blocked collective kernels can
   never starve the single-core interpreter) gives the "fake cluster" test
   story the reference lacks (SURVEY.md §4: every reference test needs real
   GPUs under torchrun; ours run anywhere).
"""

import importlib.util
import os

# Canonical env recipe (loaded by file path — the package __init__ imports
# jax, which must not happen before the env is set): see
# triton_dist_tpu/runtime/testenv.py for the rationale of each knob.
# 2x headroom over the largest test mesh: when every virtual device is
# blocked inside a collective Pallas kernel (semaphore waits), the
# single-core CPU interpreter needs spare executor slots to keep making
# progress — 8 busy devices of 8 can starve, 8 of 16 never does.
_N_DEVICES = int(os.environ.get("TDT_TEST_DEVICES", "16"))
_TESTENV = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "triton_dist_tpu", "runtime", "testenv.py")
_spec = importlib.util.spec_from_file_location("_tdt_testenv", _TESTENV)
_testenv = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_testenv)
_testenv.apply_virtual_mesh_env(_N_DEVICES)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

assert jax.devices()[0].platform == "cpu", jax.devices()


@pytest.fixture(scope="session")
def mesh8() -> Mesh:
    assert jax.device_count() >= 8, jax.devices()
    return Mesh(np.array(jax.devices()[:8]), ("tp",))


@pytest.fixture(scope="session")
def mesh4() -> Mesh:
    return Mesh(np.array(jax.devices()[:4]), ("tp",))


@pytest.fixture(scope="session")
def mesh2() -> Mesh:
    return Mesh(np.array(jax.devices()[:2]), ("tp",))


@pytest.fixture(scope="session")
def mesh2d() -> Mesh:
    """2×4 mesh for hierarchical (dp × tp) tests."""
    return Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("dp", "tp"))


@pytest.fixture
def key():
    return jax.random.key(0)


# ---------------------------------------------------------------------------
# Fast test gate (VERDICT r2 weak #6): ``pytest -m "not slow"`` runs the
# kernel core — language primitives, collectives, torus schedules, and the
# overlapped AG-GEMM / GEMM-RS kernels — in ~2.5 min (the strict-pallas
# gate forced per-shard-legal, i.e. larger, shapes in r4).  Everything else
# (models, serving, training, tooling) and the heavyweight duplicates
# inside core modules carry the ``slow`` marker.  The full suite is the
# default ``pytest tests/``.
# ---------------------------------------------------------------------------

_FAST_GATE_MODULES = {
    "test_language", "test_allgather", "test_fast_allgather",
    "test_reduce_scatter", "test_torus", "test_all_to_all",
    "test_hierarchical", "test_ag_gemm", "test_gemm_rs", "test_gemm",
    "test_flash_attention", "test_paged_decode",
    # serving engine: the pure-index machinery (block manager, scheduler,
    # metrics) + the r5 regression fixes run in the gate; the end-to-end
    # engine-vs-oracle tests carry explicit @pytest.mark.slow.
    "test_serve_engine",
    # a request's way into the batch (ISSUE 41): a cold admission is ONE
    # named launch with the scratch of the loop it replaced, no eager
    # launch from admission to the last token, one compile a rung — on a
    # toy engine of each family, int8 pools, a 2-device mesh, a draft
    # (~3 min: the latent engines warm up in the interpreter).
    "test_serve_admit",
    # failure containment: the deterministic chaos drain (fixed
    # FaultInjector schedule -> exact SHED/DEADLINE/ERROR accounting,
    # bit-exact untouched streams, whole free list) + watchdog/heartbeat
    # and the speculative chain's mid-chain bailout gate every
    # containment path; the randomized soak carries @pytest.mark.slow.
    "test_serve_faults",
    # decode horizon: the H in {1, 4, 16} greedy oracle, host-vs-device
    # sampler equality, dispatch-economics bound, and horizon-granular
    # fault containment gate the fused decode path; preemption/spec
    # interactions carry @pytest.mark.slow.
    "test_serve_horizon",
    # sharded-engine serving: the mesh geometry rejection matrix, the
    # partitioned block allocator, the mesh-vs-world-1 bit-exactness
    # oracles (TP heads + SP seq, fused horizon, preemption, prefix
    # hits) and restore-across-mesh-shapes gate the shard_map serving
    # path; the spec/horizon sweeps and seq restore legs carry
    # @pytest.mark.slow.
    "test_serve_mesh",
    # crash recovery: the journal replay, snapshot/restore round trip,
    # kill/restart chaos sweep (every injected kill point -> bit-exact
    # restarted streams + whole free list), exactly-once crash-window
    # accounting, and geometry-override restores gate the recovery
    # layer; the randomized kill soak carries @pytest.mark.slow.
    "test_serve_recovery",
    # state integrity (ISSUE 20): CRC journal framing (torn tail pinned
    # vs interior-corruption-is-loud), skip-and-continue salvage +
    # quarantine, snapshot leaf digests (silent-rot refusal + torn
    # fallback), wire manifest digest rejection, the integrity fault
    # point, the serve_fsck CLI, and the corrupt-chaos zero-loss
    # harness all run in the gate (the whole file is the fast tier).
    "test_serve_integrity",
    # prefix reuse: the content-addressed index units (chains, collision
    # safety, id-reuse orphaning, LRU eviction, COW splits), the
    # warm≡cold≡Generator.generate oracles (greedy/sampled/horizon-fused),
    # session hits over generated pages, eviction×preemption, warm-cache
    # snapshot/restore, journal rotation, and shared-prompt traffic as
    # counts all run in the gate (the whole file is the fast tier).
    "test_serve_prefix",
    # flight recorder / observability: taxonomy meta-test (every
    # FinishReason + fault point has a registered event), chaos-drain
    # event completeness, nested Perfetto spans, histogram-vs-numpy,
    # Prometheus exposition + live endpoint, bounded-memory regressions,
    # and the kill -> flight_*.json -> restore-provenance loop (the
    # whole file is the fast tier).
    "test_serve_trace",
    # one-dispatch speculative decoding: the fused-round oracle (greedy
    # == Generator.generate; seeded-sampled == the draft-less engine), k-ladder warmup flatness, adaptive-k
    # convergence, spec × prefix (draft-side skip included), spec ×
    # fault bailout-then-bisect, and the spec snapshot/restore chaos
    # sweep (draft state resumed in place) all run in the gate.
    "test_serve_spec",
    # fleet serving: drain/migrate_in mid-stream hand-off (in-place KV
    # adopt + exact-recompute, mig-receipt non-resurrection, capacity
    # admission), THE fleet chaos harness (kill a replica mid-decode —
    # bit-exact streams, zero lost/dup tokens, cross-replica
    # completion, router-never-routes-dead), SUSPECT circuit breaking,
    # backoff/router units, and the supervisor arming-boundary +
    # postmortem-dedup satellites (the whole file is the fast tier).
    "test_serve_fleet",
    # network serving plane: the net fault point (drop/delay/duplicate/
    # partition + heal), wire round-trip bit-exactness, the retry-
    # idempotency units (duplicate submit no-op, drain after a lost
    # ack, stream-since-index re-delivery), client backoff/ambiguity
    # semantics, the in-process kill+partition chaos, AND the
    # subprocess chaos harness (SIGKILL one replica process mid-decode
    # + partition another, deadline-bounded — the ISSUE-12 acceptance
    # bar; the whole file is the fast tier).
    "test_serve_net",
    # disaggregated serving (ISSUE 16): role-aware routing units, the
    # engine-pair push round trip (in-place adoption, receipts,
    # re-admission), the tier bit-exactness + audit oracle, the
    # capacity-walk / general-placer fallbacks, lost-ack push
    # idempotency, AND both chaos harnesses (in-process and subprocess
    # SIGKILL of either tier mid-hand-off — the ISSUE-16 acceptance
    # bar; the whole file is the fast tier).
    "test_serve_disagg",
    # quantized serving (ISSUE 17): int8-pool bit-reproducibility +
    # continuous-batching-equals-dedicated oracles, the fp-oracle
    # prefix-match floor, the construction rejection matrix, the state
    # plane (quantized snapshot/restore, fp<->int8 loud geometry
    # errors, drain->wire->adopt, cross-dtype requeue, lost-ack push
    # idempotency), the head_dim-64 wire-size bound, the mixed-dtype
    # fleet chaos kill, and w8a8 serving reproducibility; the mesh
    # bit-exactness sweeps carry @pytest.mark.slow.
    "test_serve_kv_int8",
    # overload robustness (ISSUE 18): the defaults-inert bit-identical
    # oracle, class-aware admission + door displacement, the brownout
    # ladder (white-box rung semantics + black-box climb/recover), the
    # seeded trace-shaped workload generator, token-bucket ingress with
    # downward borrowing, the autoscaler spawn/drain-retire cycle with
    # journal receipts, the chaos kill during scale-up, the shed-
    # terminal regression sweep, and the shed-paths-observable lint
    # rule (the whole file is the fast tier).
    "test_serve_overload",
    # kernel-layer observability: the annotation-coverage source-grep
    # meta-test (every public kernel entry point annotated — the
    # ISSUE-14 closure gate), the kprobe overlap-scoreboard reports,
    # and the kprobe-merges-with-engine-trace Perfetto wiring, plus
    # the original dump/group_profile merge units (all cheap).
    "test_observability",
    # dist-lint static analysis (ISSUE 15): the CommSchedule
    # race/deadlock checker over every ring kernel at worlds 2-32
    # (non-pow2 + world=2 edges), the seeded mutation self-test (every
    # corruption class caught), the jaxpr auditor's synthetic-bad-
    # program units AND the real engine/mesh registry zero-findings
    # bar, and the rule-registry/waiver units; only the lint_dist.py
    # subprocess CLI round-trips carry explicit @pytest.mark.slow.
    "test_analysis",
    # latent attention + expert-share serving (ISSUE 26): the engine's
    # logits against the plain float32 reference (and bf16 failing the
    # same tolerance), absorbed == expanded attention, the latent paged
    # kernel against its jnp oracle, the router's hand-worked cases, the
    # shares of an expert layer adding up to the uncut layer, prefix hit /
    # preemption / cow on latent pools, the MoE counters, and the named
    # refusals (the whole file is the fast tier).
    "test_mla_moe",
    # the same block read through learned sparse attention (ISSUE 30, the
    # glm_moe_dsa keys): prefill chunks + paged decode through latent AND
    # index-key planes against the float32 reference's full forward (and
    # bf16 failing the tolerance), index scores and the selected set
    # against its plain sort, contexts under index_topk == the plain
    # latent path, prefix hit / preemption / cow with two planes of
    # different widths, the dsa_* counters, a whole layer's shares adding
    # up, the index and masked-walk kernels against their oracles, and the
    # named refusals (the whole file is the fast tier, ~3 min).
    "test_mla_dsa",
    # the sampler every decode step of a sampled batch runs (ISSUE 29):
    # kept set and token against the two-sort form it replaced over the
    # top-k x top-p x ties grid at both cells' vocabularies, host path
    # == device path, and the filters' edge cases (~30 s, one worker).
    "test_sampling",
    # window and global layers over softmax-routed experts (ISSUE 32, the
    # mellum keys): prefill chunks + paged decode through BOTH cache
    # groups against the float32 reference's full forward (and bf16
    # failing the tolerance), the fused horizon == single steps, the
    # softmax router and its tie, four shares adding up, the window
    # group bounded while the full group grows and both free lists whole
    # after a drain, preemption-and-recompute, the allocator's units,
    # every refusal over cache groups by name, from_hf and its refusals,
    # RoPE by kind, and the paged kernel under a per-layer window and
    # name over a table with released pages (the whole file is the fast
    # tier, ~3 min).
    "test_swa_moe",
    # regions of a device program (ISSUE 36): the closed set's meta-test,
    # the lowered computation of every family's decode_horizon /
    # prefill_chunk / paged_decode identical with and without the scopes,
    # and a product of every seam under its (innermost) region; programs
    # are lowered, never compiled or run (~1 min).
    "test_regions",
    # the grouped GEMMs' feeder (ISSUE 37): sort_align_held against a
    # plain loop over assignments at every shape the expert cells
    # compile, and its largest intermediate linear in the rows (~15 s).
    "test_moe_utils",
    # a state beside pages (ISSUE 38: the phi4flash block): chunked
    # prefill + paged decode through the full, window AND state groups
    # against the float32 reference's one forward, logits; N chunks = one
    # scan; a reused slot from zero; preemption-and-recompute; heads 64
    # wide in pairs at the window edge; the scan kernel in the
    # interpreter; the parameter count; every refusal beside a state
    # group by name (~3 min).
    "test_ssm_yoco",
    "test_gdn_hybrid",
    # the laguna block (ISSUE 44: models/swa_moe.py grown): query heads
    # that differ by layer over the same KV heads, a per-head output gate,
    # a theta and a rotary width a layer kind, a dense lead layer, a
    # shared expert beside 4 held of 8 — engine logits through both groups
    # against benchmarks/reference/laguna.py over the XLA twins and the
    # interpreted Mosaic calls, a bfloat16 model failing the tolerance,
    # every mechanism moving the logits when the reference drops it, the
    # eight shares + the shared expert once adding up to the uncut layer,
    # from_hf's refusals by name, the paged call at groups of 9 and 6
    # (~2 min).
    "test_laguna",
    "test_mhc",
    # a prefill chunk keeps the row its caller reads (ISSUE 42): over the
    # six toy engines, the engine's chunk program against the all-rows one
    # — logits of the kept row, every cache and state plane bitwise — at a
    # full chunk and a residual, from an empty scratch and a prefilled
    # one; and ``prefill_chunked``, which keeps all rows (~1.5 min).
    "test_chunked_prefill",
    # every repo path README.md and five docs name in backticks exists
    # (ISSUE 43): a case a document, no jax, under a second.
    "test_docs_paths",
}

# Heavy tests inside core modules whose coverage is duplicated by a
# cheaper sibling (orientation/dtype/protocol variants): slow-marked so
# the gate keeps one representative of each behavior.
_FAST_GATE_EXCLUDES = {
    # flash-attention gate keeps one fwd, one bwd, strict dispatch, and
    # the paged/SP representatives; sweeps/tuning/dtype twins run in the
    # full suite.
    "test_flash_attention_autotuned",
    "test_flash_backward_block_invariance",
    "test_flash_offsets_chunked_prefill",
    "test_flash_soft_cap_fwd_bwd",
    "test_flash_block_sweep",
    "test_flash_gqa_wrapper_layout",
    "test_flash_backward_bf16",
    "test_flash_backward_matches_xla[False]",
    "test_flash_lse_merges_like_ring",
    "test_flash_bf16",
    "test_flash_backward_masked_rows_finite",
    "test_flash_matches_dense[4-True]",
    "test_flash_matches_dense[4-False]",
    "test_flash_matches_dense[1-False]",
    "test_flash_int8_kv_sp_shard",
    "test_paged_layer_sp",
    "test_torus_gemm_rs_int8_exact",
    "test_torus3d_gemm_rs_fused",
    "test_torus_gemm_rs_fused_epilogue[mesh2x4]",
    "test_torus_gemm_rs_fused_epilogue[mesh4x2]",
    "test_gemm_rs_pallas_matches_xla[bfloat16]",
    # float32 variant: the 1-axis ring kernel is also covered by the
    # cheap test_gemm_rs_world2; 9 s of duplicate coverage.
    "test_gemm_rs_pallas_matches_xla[float32]",
    "test_launcher_two_process_hier_allgather",
    "test_gemm_rs_rerandomized_iterations",
    "test_torus3d_ag_rs_roundtrip",
    "test_torus3d_distinct_partials",
    "test_torus_ag_rs_roundtrip",
    "test_torus2d_reduce_scatter[5-mesh2x4]",
    "test_torus2d_reduce_scatter[5-mesh4x2]",
    "test_torus2d_reduce_scatter[8-mesh4x2]",
    "test_torus2d_reduce_scatter_distinct_partials",
    "test_hier_all_to_all_matches_flat[xla]",
    "test_torus2d_allgather_order_matches_hier",
    "test_torus3d_allgather_bf16_uneven",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        module = item.module.__name__.rsplit(".", 1)[-1]
        if (module not in _FAST_GATE_MODULES
                or item.name in _FAST_GATE_EXCLUDES):
            item.add_marker(pytest.mark.slow)
