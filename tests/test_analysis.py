"""dist-lint: the static-analysis subsystem (docs/analysis.md).

Three layers under test: (1) the CommSchedule checker — every
registered kernel schedule simulates clean across world sizes 2-32
(non-pow2 and world=2 included: the slot maps and the hierarchical
credit balances are easy to get wrong off the pow2 path), the
vector-clock simulator catches hand-built races, and the seeded
mutation sweep proves every corruption class (dropped signal, swapped
slot, doubled wait, double-written tile) is caught; (2) the jaxpr
auditor — synthetic bad programs (host callback, unusable donation,
undeclared collective, off-ladder static) are flagged, and the REAL
engine/mesh program registries audit with zero findings; (3) the
source-lint rule registry + ``scripts/lint_dist.py`` — the shipped
tree lints clean, waivers suppress-with-justification, stale waivers
fail the gate.
"""

import json
import os
import random
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from triton_dist_tpu.analysis import (
    MUTATIONS,
    RULES,
    SCHEDULE_BUILDERS,
    CommSchedule,
    Op,
    arrival_slots,
    audit_engine,
    audit_program,
    build_schedule,
    check_schedule,
    mutate,
    mutation_self_test,
    run_rule,
    run_rules,
)
from triton_dist_tpu.analysis import rules as rules_mod
from triton_dist_tpu.analysis.jaxpr_audit import lowered_mosaic_calls
from triton_dist_tpu.analysis.schedule_check import check_kernel
from triton_dist_tpu.runtime.jit_cache import CountingJit

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: world sizes every schedule must survive — 2 (degenerate ring), the
#: non-pow2 run (the slot maps' hard cases), pow2 up to 32.
WORLDS = (2, 3, 4, 5, 6, 7, 8, 12, 16, 32)


# ---------------------------------------------------------------------------
# Schedule checker: clean kernels at every world size
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kernel", sorted(SCHEDULE_BUILDERS))
def test_schedule_clean_all_worlds(kernel):
    """Every kernel's CommSchedule proves deadlock-free, credit-
    balanced, happens-before-ordered, write-once, and slot-bijective
    at every world size in WORLDS (the ISSUE-15 enumeration bar)."""
    rep = check_kernel(kernel, worlds=WORLDS)
    assert not rep["violations"], rep["violations"][:5]


def test_schedule_world2_edge():
    """world=2 exercises every degenerate branch at once: the RS ring's
    single fold step, ring attention's never-issued credits
    (s < world-2 is empty), and the postlude credit drains — all must
    balance exactly."""
    for kernel in sorted(SCHEDULE_BUILDERS):
        sched = build_schedule(kernel, 2)
        assert not check_schedule(sched), kernel
        # and the op streams are genuinely nonempty two-rank programs
        assert len(sched.ranks) == 2 and all(sched.ranks), kernel


@pytest.mark.parametrize("world", [3, 5, 6, 7, 12])
def test_arrival_slot_map_bijective_non_pow2(world):
    """kprobe's arrival-order decomposition ``slots[r] = (r - s) %
    world`` must be a bijection at EVERY step for non-pow2 worlds (the
    kprobe slot map and hierarchical kernels are easy to get wrong off
    the pow2 path)."""
    for s in range(world):
        slots = arrival_slots(s, world)
        assert sorted(slots) == list(range(world)), (s, slots)
    # and the schedules publish exactly these maps
    sched = build_schedule("ag_gemm", world)
    for s, slots in sched.slot_maps.items():
        assert slots == arrival_slots(s, world)


def test_schedule_rejects_world_1():
    with pytest.raises(ValueError, match="world"):
        build_schedule("ag_gemm", 1)
    with pytest.raises(ValueError, match="unknown kernel"):
        build_schedule("nope", 4)


# ---------------------------------------------------------------------------
# Simulator: hand-built races the vector clocks must catch
# ---------------------------------------------------------------------------


def _two_rank(ops0, ops1, **kw):
    return CommSchedule("hand", 2, [list(ops0), list(ops1)], **kw)


def test_sim_catches_missing_recv_wait():
    """Rank 1 reads the landing slot without consuming the arrival
    credit: no happens-before chain orders the DMA's write before the
    read — a race even though eager simulation delivered the data."""
    s = _two_rank(
        [Op("send", dst=1, src_buf="x", src_slot=0, buf="b", slot=0,
            rsem="recv", ssem="send", label=("d", 0)),
         Op("wait", sem="send")],
        [Op("read", buf="b", slot=0, label=("d", 0)),
         Op("wait", sem="recv")],
        init=[(0, "x", 0, ("d", 0))])
    kinds = {v.kind for v in check_schedule(s)}
    assert "race-read" in kinds, kinds


def test_sim_catches_write_to_inflight_dma_source():
    """Overwriting a buffer an undrained DMA still reads is the exact
    hazard the per-slot send semaphores exist for."""
    s = _two_rank(
        [Op("send", dst=1, src_buf="x", src_slot=0, buf="b", slot=0,
            rsem="recv", ssem="send", label=("d", 0)),
         Op("write", buf="x", slot=0, label=("d", 1)),   # no drain!
         Op("wait", sem="send")],
        [Op("wait", sem="recv"),
         Op("read", buf="b", slot=0, label=("d", 0))],
        init=[(0, "x", 0, ("d", 0))])
    kinds = {v.kind for v in check_schedule(s)}
    assert "race-write" in kinds, kinds


def test_sim_catches_stranded_credit_and_deadlock():
    # stranded: a signal nobody consumes
    s = _two_rank([Op("signal", dst=1, sem="c")], [])
    kinds = {v.kind for v in check_schedule(s)}
    assert kinds == {"stranded-credit"}, kinds
    # deadlock: a wait nobody signals
    s = _two_rank([Op("wait", sem="c")], [])
    kinds = {v.kind for v in check_schedule(s)}
    assert "deadlock" in kinds, kinds


def test_sim_catches_unwritten_and_stale_reads():
    s = _two_rank([Op("read", buf="b", slot=3)], [])
    assert {v.kind for v in check_schedule(s)} == {"unwritten-read"}
    s = _two_rank([Op("read", buf="x", slot=0, label=("seg", 9))], [],
                  init=[(0, "x", 0, ("seg", 1))])
    assert {v.kind for v in check_schedule(s)} == {"stale-read"}


def test_sim_write_once_and_slot_map():
    s = _two_rank(
        [Op("write", buf="o", slot=0, label=("t",), final=True),
         Op("write", buf="o", slot=0, label=("t",), final=True)],
        [Op("write", buf="o", slot=0, label=("t",), final=True)],
        outputs={"o": 1}, slot_maps={0: [1, 1]})
    kinds = {v.kind for v in check_schedule(s)}
    assert kinds == {"write-once", "slot-map"}, kinds


# ---------------------------------------------------------------------------
# Mutation self-test: every corruption class caught (acceptance bar)
# ---------------------------------------------------------------------------


def test_mutation_self_test_all_classes_caught():
    """The ISSUE-15 acceptance criterion: dropped signal, swapped slot,
    doubled wait, double-written tile — each seeded corruption, on
    every kernel schedule, is detected by the checker."""
    tally = mutation_self_test()
    assert set(tally) == set(MUTATIONS)
    assert all(n > 0 for n in tally.values()), tally


@pytest.mark.parametrize("kind", MUTATIONS)
def test_mutation_classes_individually(kind):
    """Per-class spot check on the flagship ring at a non-pow2 world,
    many seeds — no silent corruption."""
    clean = build_schedule("ag_gemm", 3)
    for seed in range(8):
        bad = mutate(clean, kind, random.Random(seed))
        assert check_schedule(bad), f"{kind} seed={seed} not caught"
    # the mutated copy never contaminates the clean schedule
    assert not check_schedule(clean)


def test_mutation_unknown_kind_rejected():
    with pytest.raises(ValueError, match="unknown mutation"):
        mutate(build_schedule("ag_gemm", 2), "bitflip",
               random.Random(0))


# ---------------------------------------------------------------------------
# Jaxpr auditor: synthetic bad programs are flagged
# ---------------------------------------------------------------------------


def _capture(fn, *args, name="prog", **kwargs):
    cj = CountingJit(fn, name)
    cj(*args, **kwargs)
    return cj


def test_audit_flags_host_callback():
    def bad(x):
        jax.debug.callback(lambda v: None, x)
        return x * 2

    cj = _capture(jax.jit(bad), jnp.ones((4,)))
    fs = audit_program({"name": "bad_cb", "fn": cj})
    assert any(f.check == "callback" for f in fs), [str(f) for f in fs]


def test_audit_flags_unused_donation():
    # donated arg never used by the computation
    def f_unused(a, b):
        return b * 2

    cj = _capture(jax.jit(f_unused, donate_argnums=(0,)),
                  jnp.ones((4,)), jnp.ones((4,)))
    fs = audit_program({"name": "don_unused", "fn": cj})
    assert any(f.check == "donation" and "never used" in f.message
               for f in fs), [str(f) for f in fs]

    # donated arg used, but no shape-matching output to alias
    def f_shape(a):
        return jnp.sum(a)

    cj = _capture(jax.jit(f_shape, donate_argnums=(0,)),
                  jnp.ones((8,)))
    fs = audit_program({"name": "don_shape", "fn": cj})
    assert any(f.check == "donation" and "no shape" in f.message
               for f in fs), [str(f) for f in fs]

    # clean donation: consumed in place
    def f_ok(a, b):
        return a + b

    cj = _capture(jax.jit(f_ok, donate_argnums=(0,)),
                  jnp.ones((4,)), jnp.ones((4,)))
    assert audit_program({"name": "don_ok", "fn": cj}) == []


def test_audit_flags_undeclared_collective(mesh2):
    def body(x):
        return jax.lax.psum(x, "tp")

    fn = jax.jit(jax.shard_map(body, mesh=mesh2, in_specs=P("tp"),
                               out_specs=P(), check_vma=False))
    cj = _capture(fn, jnp.ones((4,)))
    # undeclared -> violation
    fs = audit_program({"name": "coll", "fn": cj, "seams": {}})
    assert any(f.check == "collective" for f in fs), [str(f) for f in fs]
    # declared with the right count -> clean (psum2 canonicalizes)
    assert audit_program(
        {"name": "coll", "fn": cj, "seams": {"psum": 1}}) == []
    # declared with the wrong count -> violation
    fs = audit_program(
        {"name": "coll", "fn": cj, "seams": {"psum": 3}})
    assert any("declared seam count is 3" in f.message for f in fs)


def test_audit_counts_a_tree_collective_as_one_seam(mesh2):
    """One ``psum`` over a pytree binds one equation per leaf, back to
    back; that is ONE seam (the seq layout's page gather psums a whole
    per-layer scratch tree at one call site).  Two psums with work
    between them are two."""
    def tree(t):
        return jax.lax.psum(t, "tp")

    fn = jax.jit(jax.shard_map(tree, mesh=mesh2, in_specs=(P(),),
                               out_specs=P(), check_vma=False))
    cj = _capture(fn, [(jnp.ones((4,)), jnp.ones((4,)))] * 2)
    assert audit_program(
        {"name": "tree", "fn": cj, "seams": {"psum": 1}}) == []

    def twice(x):
        return jax.lax.psum(jax.lax.psum(x, "tp") * 2.0, "tp")

    fn = jax.jit(jax.shard_map(twice, mesh=mesh2, in_specs=(P(),),
                               out_specs=P(), check_vma=False))
    cj = _capture(fn, jnp.ones((4,)))
    assert audit_program(
        {"name": "twice", "fn": cj, "seams": {"psum": 2}}) == []


def test_audit_flags_off_ladder_static():
    def f(x, *, H):
        return x * H

    cj = CountingJit(jax.jit(f, static_argnames=("H",)), "lad")
    cj(jnp.ones((4,)), H=3)        # 3 is off the pow2 ladder
    fs = audit_program({"name": "lad", "fn": cj,
                        "ladders": {"H": (1, 2, 4, 8)}})
    assert any(f.check == "ladder" and "H=3" in f.message
               for f in fs), [str(f) for f in fs]
    cj2 = CountingJit(jax.jit(f, static_argnames=("H",)), "lad2")
    cj2(jnp.ones((4,)), H=4)
    assert audit_program({"name": "lad2", "fn": cj2,
                          "ladders": {"H": (1, 2, 4, 8)}}) == []


def test_audit_untraced_program_reported():
    cj = CountingJit(jax.jit(lambda x: x), "idle")
    fs = audit_program({"name": "idle", "fn": cj})
    assert len(fs) == 1 and fs[0].check == "untraced"


def test_counting_jit_captures_signatures_bounded():
    """Signature capture happens on miss only and is bounded."""
    cj = CountingJit(jax.jit(lambda x: x + 1), "cap")
    a = jnp.ones((4,))
    cj(a)
    cj(a)                      # hit: no new capture
    assert len(cj.captured) == 1
    (args_abs, kwargs) = next(iter(cj.captured.values()))
    assert isinstance(args_abs[0], jax.ShapeDtypeStruct)
    assert args_abs[0].shape == (4,)


# ---------------------------------------------------------------------------
# Jaxpr auditor over the REAL engine registries (the satellite bar:
# zero unexplained violations on the shipped tree)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_serving():
    from triton_dist_tpu.models import llama
    from triton_dist_tpu.models.generate import Generator

    cfg = llama.LlamaConfig(vocab=64, dim=32, n_layers=2, n_heads=4,
                            n_kv_heads=4, ffn_dim=64, max_seq=64,
                            dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.key(0))
    mesh1 = Mesh(np.array(jax.devices()[:1]), ("sp",))
    gen = Generator(cfg, mesh1, axis="sp", max_seq=64)
    return cfg, params, gen


def _serve_mixed(eng, cfg, n=2):
    from triton_dist_tpu.serve.request import Request, SamplingParams

    rng = np.random.default_rng(7)
    for i in range(n):
        p = rng.integers(0, cfg.vocab,
                         size=5 + 3 * i).astype(np.int32)
        sp = (SamplingParams(max_new_tokens=5) if i % 2 == 0 else
              SamplingParams(max_new_tokens=5, temperature=0.8,
                             top_k=20, seed=123 + i))
        eng.submit(Request(f"a{i}", p, sp))
    steps = 0
    while eng.has_work():
        eng.step()
        steps += 1
        assert steps < 400


def _build_engine(tiny_serving, **kw):
    from triton_dist_tpu.serve.engine import ServeEngine

    cfg, params, gen = tiny_serving
    return ServeEngine(gen, params, num_blocks=24, page_size=8,
                       max_batch=3, prefill_chunk=4, prefill_budget=8,
                       **kw)


def _decode_program_audited(rep, horizon):
    """The registry is real: the hot programs were audited — an engine
    with a horizon decodes through ``decode_horizon`` alone (a clamped
    step is its H = 1 link), a ``horizon=1`` engine through
    ``paged_decode`` alone."""
    mine, other = (("decode_horizon", "paged_decode") if horizon > 1
                   else ("paged_decode", "decode_horizon"))
    assert mine in rep["audited"] and other not in rep["audited"]


@pytest.mark.parametrize("horizon", [4, 1])
def test_engine_registry_audits_clean_world1(tiny_serving, horizon):
    cfg, params, gen = tiny_serving
    eng = _build_engine(tiny_serving, horizon=horizon)
    eng.warmup()
    _serve_mixed(eng, cfg)
    rep = audit_engine(eng)
    assert not rep["findings"], [str(f) for f in rep["findings"]]
    _decode_program_audited(rep, horizon)
    # (the chunk program is the shared Generator's: its signature is
    # captured by the engine whose call compiled it, the first here)
    assert "prefill_chunk" in rep["audited"] or horizon == 1
    # ... and re-lower from their captured signatures (chip_smoke.py's
    # kernel evidence): on this CPU host they took the XLA path, so every
    # served program lowers with zero Mosaic custom calls
    calls = lowered_mosaic_calls(eng)
    assert set(rep["audited"]) == set(calls), (rep["audited"], calls)
    assert all(max(c) == 0 for c in calls.values()), calls


def _assert_prefill_attend_sharded(eng, cfg):
    """ISSUE-19 debt (b) acceptance: chunked prefill under a seq axis
    no longer computes attention replicated — the traced prefill
    program carries the rank-local-slice attend's LSE-combine
    all_gather at exactly the declared per-layer count (a replicated
    prefill traces to zero collectives, which this pins against).
    The count assertion is needed because the auditor tolerates a
    declared seam with zero occurrences."""
    from triton_dist_tpu.analysis.jaxpr_audit import (
        _PRIM_CANON, _signatures, _trace, jaxpr_stats)

    rec = next(r for r in eng.program_registry()
               if r["name"] == "prefill_chunk")
    sigs = _signatures(rec["fn"])
    assert sigs, "prefill_chunk never traced"
    for args_abs, kwargs in sigs:
        stats = jaxpr_stats(_trace(rec["fn"], args_abs, kwargs).jaxpr)
        canon: dict = {}
        for prim, n in stats["prims"].items():
            k = _PRIM_CANON.get(prim, prim)
            canon[k] = canon.get(k, 0) + n
        assert canon.get("all_gather") == cfg.n_layers, canon


@pytest.mark.parametrize("horizon", [4, 1])
@pytest.mark.parametrize("kv_shard", ["heads", "seq"])
def test_engine_registry_audits_clean_mesh(tiny_serving, mesh2,
                                           kv_shard, horizon):
    """The MESH registry (ShardedPrograms under shard_map) audits with
    zero findings: collectives exactly at the declared psum/gather
    seams, donation consumed, no callbacks, statics on ladders."""
    cfg, params, gen = tiny_serving
    eng = _build_engine(tiny_serving, horizon=horizon, mesh=mesh2,
                        kv_shard=kv_shard)
    eng.warmup()
    _serve_mixed(eng, cfg)
    rep = audit_engine(eng)
    assert not rep["findings"], [str(f) for f in rep["findings"]]
    _decode_program_audited(rep, horizon)
    if kv_shard == "seq":
        _assert_prefill_attend_sharded(eng, cfg)


@pytest.mark.parametrize("horizon", [4, 1])
def test_engine_registry_audits_clean_mesh2d(tiny_serving, horizon):
    """heads+seq on a 2x2 (tp x sp) mesh: the 2-axis registry audits
    with zero findings — psum exactly at the tp out-proj/FFN seams AND
    the LSE-combine gather exactly at the sp seam, in the same traced
    bodies — and the sharded prefill attend shows its sp all_gather."""
    cfg, params, gen = tiny_serving
    mesh22 = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                  ("tp", "sp"))
    eng = _build_engine(tiny_serving, horizon=horizon, mesh=mesh22,
                        kv_shard="heads+seq")
    eng.warmup()
    _serve_mixed(eng, cfg)
    rep = audit_engine(eng)
    assert not rep["findings"], [str(f) for f in rep["findings"]]
    _decode_program_audited(rep, horizon)
    _assert_prefill_attend_sharded(eng, cfg)


@pytest.mark.slow
def test_engine_registry_audits_clean_mesh2d_world8(tiny_serving,
                                                    mesh2d):
    """World 8 re-run of the 2D audit on the hierarchical (dp x tp)
    fixture with the serving axes mapped tp_axis='tp' (4 | heads) and
    sp_axis='dp' (2 | pages)."""
    cfg, params, gen = tiny_serving
    eng = _build_engine(tiny_serving, horizon=4, mesh=mesh2d,
                        kv_shard="heads+seq", tp_axis="tp",
                        sp_axis="dp")
    eng.warmup()
    _serve_mixed(eng, cfg)
    rep = audit_engine(eng)
    assert not rep["findings"], [str(f) for f in rep["findings"]]
    _decode_program_audited(rep, 4)


# ---------------------------------------------------------------------------
# Rule registry + waivers + CLI
# ---------------------------------------------------------------------------


def test_rule_registry_contents():
    """The migrated meta-tests and the new rules are all registered."""
    assert {"kernel-entry-annotated", "finish-reasons-registered",
            "fire-points-registered", "no-unseeded-randomness",
            "collective-ids-unique",
            "ring-schedules-clean"} <= set(RULES)


def test_tree_lints_clean():
    """The shipped tree has zero unexplained violations (the ISSUE-15
    acceptance bar); source rules only — the schedule rule has its own
    sweep above and costs ~1s."""
    rep = run_rules([n for n in sorted(RULES)
                     if n != "ring-schedules-clean"])
    assert rep["ok"], rep["violations"]
    assert not rep["stale_waivers"], rep["stale_waivers"]


def test_waiver_mechanics(tmp_path):
    """Waivers suppress with justification; stale waivers are
    reported; malformed waivers (no reason) are rejected."""
    v = rules_mod.Violation("some-rule", "bad thing at foo",
                            path="pkg/mod.py", line=3)
    unwaived, waived, stale = rules_mod.apply_waivers(
        [v], [{"rule": "some-rule", "match": "bad thing",
               "reason": "known, tracked in ISSUE-99"}])
    assert not unwaived and len(waived) == 1
    assert waived[0].waiver_reason.startswith("known")
    # non-matching waiver: violation survives, waiver is stale
    v2 = rules_mod.Violation("some-rule", "other thing")
    unwaived, waived, stale = rules_mod.apply_waivers(
        [v2], [{"rule": "some-rule", "match": "bad thing",
                "reason": "r"}])
    assert len(unwaived) == 1 and len(stale) == 1
    # malformed waiver file
    p = tmp_path / "w.json"
    p.write_text(json.dumps(
        {"waivers": [{"rule": "x", "match": "y"}]}))
    with pytest.raises(ValueError, match="justification"):
        rules_mod.load_waivers(str(p))


@pytest.mark.slow
def test_lint_cli_clean_tree_and_report(tmp_path):
    """scripts/lint_dist.py exits 0 on the clean tree and writes the
    JSON report."""
    out = tmp_path / "report.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "lint_dist.py"),
         "--json", str(out)],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rep = json.loads(out.read_text())
    assert rep["ok"] and not rep["violations"]
    assert set(rep["rules_run"]) == set(RULES)


@pytest.mark.slow
def test_lint_cli_stale_waiver_fails(tmp_path):
    """A waiver matching nothing fails the gate (exit 1) — fixed code
    must shed its waiver."""
    w = tmp_path / "waivers.json"
    w.write_text(json.dumps({"waivers": [
        {"rule": "collective-ids-unique", "match": "no-such-violation",
         "reason": "stale on purpose"}]}))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "lint_dist.py"),
         "--rules", "collective-ids-unique", "--waivers", str(w)],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "STALE WAIVER" in proc.stdout
