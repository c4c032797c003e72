"""Sharded-engine serving: ServeEngine on a mesh (docs/serving.md
"Sharded serving").

The acceptance bar (ISSUE 13): a mesh-sharded engine — TP weights +
head-sharded paged KV (``kv_shard="heads"``) or replicated weights +
sequence-sharded pools through ``sp_gqa_decode_paged_shard``
(``kv_shard="seq"``) — serves greedy AND seeded-sampled streams
bit-identical to the world-1 oracle, including the fused decode
horizon, preemption recompute, prefix-cache hits, and snapshot/restore
across DIFFERENT mesh shapes, with a flat compile-miss counter after
``warmup()``.  Geometry that cannot divide the mesh is rejected loudly
at construction (the rejection-matrix units), and the partitioned
block allocator (``kv_shard="seq"``) keeps every logical page in its
owning rank's partition.
"""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from triton_dist_tpu.models import llama
from triton_dist_tpu.models.generate import Generator
from triton_dist_tpu.serve.block_manager import (
    BlockExhausted,
    BlockManager,
)
from triton_dist_tpu.serve.engine import ServeEngine
from triton_dist_tpu.serve.request import Request, SamplingParams


@pytest.mark.parametrize("module,may_not_import", [
    ("programs", ("engine", "mesh")), ("mesh", ("engine",))])
def test_the_serving_layers_import_one_way(module, may_not_import):
    """engine -> mesh -> programs -> models -> kernels: the program
    bodies are pure functions of arrays under both modules, so neither
    ``serve/programs.py`` nor ``serve/mesh.py`` may import what stands
    above it — anywhere in the file: an AST walk, so an import deferred
    into a function body is caught too."""
    import ast

    import triton_dist_tpu.serve as serve

    path = os.path.join(os.path.dirname(serve.__file__), f"{module}.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    upward = {f"triton_dist_tpu.serve.{m}" for m in may_not_import}
    found = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            found += [a.name for a in node.names if a.name in upward]
        elif isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if mod in upward or (node.level and mod in may_not_import):
                found.append(mod)
            elif mod == "triton_dist_tpu.serve" or (node.level and not mod):
                found += [a.name for a in node.names
                          if a.name in may_not_import]
    assert found == [], (module, found)


@pytest.fixture(scope="module")
def model():
    # 4 query heads == 4 KV heads: divides mesh2 AND mesh4 (the heads
    # layout needs whole heads per rank); ffn 64 divides both too.
    cfg = llama.LlamaConfig(vocab=64, dim=32, n_layers=2, n_heads=4,
                            n_kv_heads=4, ffn_dim=64, max_seq=64,
                            dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.key(0))
    mesh1 = Mesh(np.array(jax.devices()[:1]), ("sp",))
    gen = Generator(cfg, mesh1, axis="sp", max_seq=64)
    return cfg, params, gen


def _requests(cfg, lens=(5, 11, 7, 16), n_new=8):
    """Mixed greedy + seeded-sampled request set (every even index
    greedy, every odd one a distinct seeded sampler)."""
    rng = np.random.default_rng(7)
    out = []
    for i, n in enumerate(lens):
        p = rng.integers(0, cfg.vocab, size=n).astype(np.int32)
        sp = (SamplingParams(max_new_tokens=n_new) if i % 2 == 0 else
              SamplingParams(max_new_tokens=n_new, temperature=0.8,
                             top_k=20, seed=123 + i))
        out.append(Request(f"r{i}", p, sp))
    return out


def _build(gen, params, *, mesh=None, kv_shard="heads", horizon=1,
           num_blocks=24, page_size=8, **kw):
    return ServeEngine(gen, params, num_blocks=num_blocks,
                       page_size=page_size, max_batch=3,
                       prefill_chunk=4, prefill_budget=8, mesh=mesh,
                       kv_shard=kv_shard, horizon=horizon, **kw)


def _serve(eng, reqs, *, stagger=2):
    """Staggered submission through the step loop; returns
    {rid: tokens}."""
    it = iter(reqs)
    for r in (next(it), next(it)):
        eng.submit(r)
    pending = list(it)
    step = 0
    while eng.has_work() or pending:
        if pending and step % stagger == 0:
            eng.submit(pending.pop(0))
        eng.step()
        step += 1
        assert step < 500
    return {rid: out.token_ids for rid, out in eng._outputs.items()
            if not rid.startswith("__warmup_")}


@pytest.fixture(scope="module")
def oracle(model):
    """World-1 engine streams for the shared request set — THE
    bit-exactness reference every mesh configuration must equal."""
    cfg, params, gen = model
    eng = _build(gen, params)
    return _serve(eng, _requests(cfg))


@pytest.fixture(scope="module")
def mesh22():
    """The 2D serving mesh: 2 tp ranks x 2 sp ranks (kv_shard=
    'heads+seq' — heads/weights over 'tp', KV blocks over 'sp')."""
    return Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                ("tp", "sp"))


# ---------------------------------------------------------------------------
# Construction-time geometry rejection matrix
# ---------------------------------------------------------------------------


def test_mesh_geometry_rejection_matrix(model, mesh4, mesh2):
    cfg, params, gen = model

    def build(**kw):
        base = dict(num_blocks=24, page_size=8, max_batch=2,
                    prefill_chunk=4)
        base.update(kw)
        return ServeEngine(gen, params, **base)

    # unknown axis / unknown layout
    with pytest.raises(ValueError, match="tp_axis"):
        build(mesh=mesh4, tp_axis="nope")
    with pytest.raises(ValueError, match="kv_shard"):
        build(mesh=mesh4, kv_shard="rows")
    # heads: whole heads per rank
    cfg3 = llama.LlamaConfig(vocab=64, dim=48, n_layers=1, n_heads=3,
                             n_kv_heads=3, ffn_dim=64, max_seq=64,
                             dtype=jnp.float32)
    gen3 = Generator(cfg3, Mesh(np.array(jax.devices()[:1]), ("sp",)),
                     axis="sp", max_seq=64)
    p3 = llama.init_params(cfg3, jax.random.key(1))
    with pytest.raises(ValueError, match="n_kv_heads"):
        ServeEngine(gen3, p3, num_blocks=24, page_size=8, mesh=mesh2,
                    kv_shard="heads")
    # heads: ffn divisibility
    cfg5 = llama.LlamaConfig(vocab=64, dim=32, n_layers=1, n_heads=4,
                             n_kv_heads=4, ffn_dim=66, max_seq=64,
                             dtype=jnp.float32)
    gen5 = Generator(cfg5, Mesh(np.array(jax.devices()[:1]), ("sp",)),
                     axis="sp", max_seq=64)
    p5 = llama.init_params(cfg5, jax.random.key(1))
    with pytest.raises(ValueError, match="ffn_dim"):
        ServeEngine(gen5, p5, num_blocks=24, page_size=8, mesh=mesh4,
                    kv_shard="heads")
    # seq: logical pages / num_blocks must divide the world
    with pytest.raises(ValueError, match="logical pages"):
        build(mesh=Mesh(np.array(jax.devices()[:3]), ("tp",)),
              kv_shard="seq")            # 8 pages % 3
    with pytest.raises(ValueError, match="num_blocks"):
        build(mesh=mesh4, kv_shard="seq", num_blocks=26)
    with pytest.raises(ValueError, match="null"):
        build(mesh=mesh4, kv_shard="seq", num_blocks=4)
    # heads+seq 2D matrix: the world must factor over two NAMED axes,
    # and each factor owns its own divisibility rules — the error
    # names the failing axis.
    mesh2d = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                  ("tp", "sp"))
    with pytest.raises(ValueError, match="sp_axis"):
        build(mesh=mesh4, kv_shard="heads+seq")  # no 'sp' on a 1D mesh
    with pytest.raises(ValueError, match="DISTINCT"):
        build(mesh=mesh2d, kv_shard="heads+seq", tp_axis="tp",
              sp_axis="tp")
    with pytest.raises(ValueError, match=r"tp axis 'tp'"):
        # heads fail on the tp factor: 3 KV heads % 2
        ServeEngine(gen3, p3, num_blocks=24, page_size=8, mesh=mesh2d,
                    kv_shard="heads+seq")
    with pytest.raises(ValueError, match=r"sp axis 'sp'"):
        # pages fail on the sp factor: 8 logical pages % 3
        build(mesh=Mesh(np.array(jax.devices()[:6]).reshape(2, 3),
                        ("tp", "sp")), kv_shard="heads+seq")
    with pytest.raises(ValueError, match="num_blocks"):
        build(mesh=mesh2d, kv_shard="heads+seq", num_blocks=25)
    with pytest.raises(ValueError, match="null"):
        build(mesh=mesh2d, kv_shard="heads+seq", num_blocks=2)
    # seq: a span that cannot fit its partition is rejected AT SUBMIT,
    # loudly, not as a shape error inside a traced forward
    eng = build(mesh=mesh2, kv_shard="seq", num_blocks=8)
    with pytest.raises(ValueError, match="partition"):
        eng.submit(Request("long", np.zeros((16,), np.int32),
                           SamplingParams(max_new_tokens=16)))


def test_params_drawn_on_their_mesh_layout_equal_the_default(mesh4):
    """``init_params(shardings=)`` draws every leaf directly on its TP
    layout — no leaf whole on one device (llama3-8B only fits a 16 GB
    chip that way) — with the values of the unsharded draw, and a mesh
    engine's pools are born on their sharding."""
    cfg = llama.LlamaConfig(vocab=64, dim=32, n_layers=2, n_heads=4,
                            n_kv_heads=4, ffn_dim=64, max_seq=64)
    key = jax.random.key(11)
    plain = llama.init_params(cfg, key)
    placed = llama.init_params(cfg, key, llama.param_shardings(cfg, mesh4))
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(placed),
                    strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    wq = placed["layers"][0]["wq"]
    assert wq.sharding.spec == jax.sharding.PartitionSpec(None, "tp")
    assert {s.data.shape for s in wq.addressable_shards} == {(32, 8)}
    gen = Generator(cfg, Mesh(np.array(jax.devices()[:1]), ("sp",)),
                    axis="sp", max_seq=64)
    eng = ServeEngine(gen, placed, num_blocks=8, page_size=8, max_batch=2,
                      prefill_chunk=8, mesh=mesh4, kv_shard="heads")
    k_pool = eng._pools[0][0]
    assert k_pool.sharding == eng._pool_sharding
    assert {s.data.shape for s in k_pool.addressable_shards} == \
        {(8, 1, 8, 8)}


def test_mesh_block_manager_partitions():
    """Partitioned allocator units (kv_shard='seq'): placement, the
    per-partition free walk, COW locality, and the match-prefix
    partition filter."""
    bm = BlockManager(16, 4, shards=4, pages_per_shard=2,
                      prefix_cache=True)
    assert bm.num_allocatable == 12          # one null per partition
    assert sorted(bm._nulls) == [0, 4, 8, 12]
    # logical pages 0-1 -> partition 0, 2-3 -> 1, ...
    t = bm.allocate("a", 4 * 4 + 1)          # 5 pages
    assert [bm.part_of_block(b) for b in t] == [0, 0, 1, 1, 2]
    assert bm.placement_ok(t)
    assert not bm.placement_ok(list(reversed(t)))
    # growth stays partition-correct
    bm.ensure("a", 6 * 4)
    t = bm.table("a")
    assert [bm.part_of_block(b) for b in t] == [0, 0, 1, 1, 2, 2]
    # partition 0 exhausted (2 of 3 held by "a"; 1 left) -> a second
    # 2-page-span request takes it, a third cannot
    bm.allocate("b", 2)
    with pytest.raises(BlockExhausted, match="partition 0"):
        bm.allocate("c", 2)
    assert bm.fit_error(8 * 4) is None       # the full 8-page span fits
    assert bm.fit_error(16 * 4) is not None  # > the pool, ever
    # a span whose partition share exceeds the partition is impossible
    tight = BlockManager(8, 4, shards=4, pages_per_shard=2)
    assert "partition 0" in tight.fit_error(2 * 4)
    assert bm.can_allocate(2) is False       # partition 0 empty
    assert bm.can_allocate(4 * 4) is False
    # COW splits stay in the page's partition
    bm.free("b")
    bm.share("s1", [t[0], t[1]])             # overlap with "a" -> shared
    old, new = bm.cow("s1", 1)
    assert bm.part_of_block(new) == 0
    # content-index hits are filtered to placement-compatible chains
    bm2 = BlockManager(16, 2, shards=4, pages_per_shard=2,
                       prefix_cache=True)
    bm2.allocate("x", 8)
    for logical, toks in enumerate(([1, 2], [3, 4], [5, 6])):
        bm2.commit_block("x", logical, toks)
    assert len(bm2.match_prefix([1, 2, 3, 4, 5, 6, 7, 8])) == 3
    # a block admitted at the WRONG depth for its partition never
    # certifies a chain (the cross-mesh re-admission guard)
    tab = bm2.table("x")
    assert bm2.part_of_block(tab[2]) == 1
    bm2.free("x")
    # a hit whose chain reaches into the LAST partition asked about is
    # claimed from the cache tier as the table's head: the table starts
    # with the shared blocks themselves, at full length
    hit = bm2.match_prefix([1, 2, 3, 4, 5, 6, 7, 8])
    assert hit == tab[:3]
    two = BlockManager(8, 2, shards=2, pages_per_shard=2, prefix_cache=True)
    old = two.allocate("x", 8)
    for logical, toks in enumerate(([1, 2], [3, 4], [5, 6])):
        two.commit_block("x", logical, toks)
    two.free("x")
    shared = two.match_prefix([1, 2, 3, 4, 5, 6, 7, 8])
    assert two.part_of_block(shared[2]) == 1     # cached, last partition
    t2 = two.allocate("y", 8, shared)
    assert t2[:3] == old[:3] and len(t2) == 4 and two.placement_ok(t2)
    bm3 = BlockManager(16, 2, shards=4, pages_per_shard=2,
                       prefix_cache=True)
    # same content, committed under world-1-style placement (all in
    # partition 0's range is impossible here, so simulate by direct
    # registration at a misplaced depth)
    bm3._register(9, 0, (1, 2))              # partition 2 block at depth 0
    assert bm3.match_prefix([1, 2, 3, 4]) == []


# ---------------------------------------------------------------------------
# THE oracle sweep: mesh-k streams == world-1 streams, bit for bit
# ---------------------------------------------------------------------------


def test_mesh_tp_oracle_h8_flat_misses(model, oracle, mesh4):
    """kv_shard='heads' on 4 devices, fused horizon H=8 pipelined:
    greedy + seeded-sampled staggered streams bit-identical to the
    world-1 oracle, zero fresh compiles after warmup."""
    cfg, params, gen = model
    eng = _build(gen, params, mesh=mesh4, kv_shard="heads", horizon=8)
    eng.warmup()
    flat = eng.metrics.compile_misses
    got = _serve(eng, _requests(cfg))
    assert got == oracle
    assert eng.metrics.compile_misses == flat, (
        eng.metrics.summary()["compilation"])


def test_mesh_tp_clamped_steps_are_one_step_links(model, oracle, mesh4):
    """The shard_map horizon takes ``H`` as the world-1 one does: on the
    ``heads`` mesh a step clamped by a mid-prefill slot (a budget of two
    chunks a step) is ONE ``decode_horizon`` link at H = 1, streams equal
    to the world-1 single-step oracle, nothing compiled after warmup and
    no decode token from ``paged_decode`` or the host sampler."""
    cfg, params, gen = model
    eng = _build(gen, params, mesh=mesh4, kv_shard="heads", horizon=8,
                 pipeline=2, trace_level=1)
    eng.warmup()
    flat = eng.metrics.compile_misses
    reqs = _requests(cfg)
    assert _serve(eng, reqs) == oracle
    assert eng.metrics.compile_misses == flat, (
        eng.metrics.summary()["compilation"])
    progs = eng.metrics.summary()["programs"]
    assert progs["decode_horizon[H=1]"]["count"] >= 2, sorted(progs)
    assert "paged_decode" not in progs
    assert eng._decode_fn.hits + eng._decode_fn.misses == 0
    assert eng.metrics.host_choices == len(reqs)


def test_mesh_programs_lower_under_their_own_names(model, mesh4):
    """Every program of the ``heads`` mesh engine lowers to the HLO
    module ``jit_<its name>``: a device trace then tells the shard_map
    programs apart as it does the one-chip ones (none reads
    ``jit__unknown`` or ``jit_sharded_program``), and the per-extent
    chunk programs behind ``MeshChunkJit`` share ``jit_prefill_chunk``."""
    from triton_dist_tpu.analysis.jaxpr_audit import lowered_module_names

    cfg, params, gen = model
    for horizon in (1, 4):
        eng = _build(gen, params, mesh=mesh4, kv_shard="heads",
                     horizon=horizon)
        eng.warmup()
        names = lowered_module_names(eng)
        assert {"paged_decode", "prefill_chunk", "fill_pages",
                "load_pages", "cow_copy", "sample_token"} <= set(names)
        if horizon > 1:
            # its decode is horizon links alone, the one-step link too
            assert names.pop("paged_decode") == set()
            assert "decode_horizon" in names
        for prog, mods in names.items():
            if prog == "paged_verify" and not mods:
                continue        # no draft: never called
            assert mods == {f"jit_{prog}"}, (prog, mods)
    # and the spans of a step on the mesh engine nest like the one-chip
    # engine's: nothing under ``step`` is lost or counted twice
    _serve(eng, _requests(cfg))
    table = eng.trace.phases
    assert table["dispatch.decode_horizon"][0] > 0
    assert sum(s for k, (_, _, s) in table.items() if k != "submit") \
        == table["step"][1]


def test_mesh_seq_oracle_with_preemption(model, mesh2):
    """kv_shard='seq': block-sharded pools + sp_gqa_decode_paged_shard,
    spans crossing rank ownership, preemption recompute — streams
    bit-identical to world-1, flat misses after warmup."""
    cfg, params, gen = model
    rng = np.random.default_rng(2)
    reqs = [Request("a", rng.integers(0, cfg.vocab, 16).astype(np.int32),
                    SamplingParams(max_new_tokens=16)),
            Request("b", rng.integers(0, cfg.vocab, 16).astype(np.int32),
                    SamplingParams(max_new_tokens=16, temperature=0.9,
                                   top_k=16, seed=5))]
    def run(mesh, kv_shard, nb):
        eng = ServeEngine(gen, params, num_blocks=nb, page_size=8,
                          max_batch=2, prefill_chunk=8, mesh=mesh,
                          kv_shard=kv_shard)
        eng.warmup()
        flat = eng.metrics.compile_misses
        for r in reqs:
            eng.submit(r)
        outs = eng.run()
        assert eng.metrics.compile_misses == flat, (
            eng.metrics.summary()["compilation"])
        return ({k: v.token_ids for k, v in outs.items()},
                eng.metrics.preemptions)

    want, _ = run(None, "heads", 24)
    got, preempts = run(mesh2, "seq", 16)
    assert got == want
    # 16 blocks / 2 partitions: both 4-page spans contend for
    # partition 0's 7 allocatable blocks -> the seq allocator preempts
    assert preempts >= 1


def test_mesh_2d_oracle_h8_flat_misses(model, oracle, mesh22):
    """THE tentpole oracle (ISSUE 19): kv_shard='heads+seq' on a 2x2
    (tp x sp) mesh, fused horizon H=8 — head-sharded weights psum on
    tp, block-sharded pools LSE-combine on sp, and every greedy +
    seeded-sampled staggered stream is bit-identical to the world-1
    oracle with zero fresh compiles after warmup."""
    cfg, params, gen = model
    eng = _build(gen, params, mesh=mesh22, kv_shard="heads+seq",
                 horizon=8)
    assert eng.mesh_world == 4 and eng.sp_world == 2
    assert eng.bm.shards == 2          # partitions = SP world, not 4
    eng.warmup()
    flat = eng.metrics.compile_misses
    got = _serve(eng, _requests(cfg))
    assert got == oracle
    assert eng.metrics.compile_misses == flat, (
        eng.metrics.summary()["compilation"])


def test_mesh_seq_spec_oracle(model, mesh2):
    """Speculative rounds under kv_shard='seq' (the spec x seq
    rejection this PR deletes): the 4D-q SP combine runs the
    multi-token verify over block-sharded pools, and greedy + sampled
    streams equal the draft-less world-1 run."""
    cfg, params, gen = model
    rng = np.random.default_rng(3)
    reqs = [Request("a", rng.integers(0, cfg.vocab, 9).astype(np.int32),
                    SamplingParams(max_new_tokens=8)),
            Request("b", rng.integers(0, cfg.vocab, 12).astype(np.int32),
                    SamplingParams(max_new_tokens=8, temperature=0.8,
                                   top_k=16, seed=11))]

    def run(mesh, kv_shard, **kw):
        eng = _build(gen, params, mesh=mesh, kv_shard=kv_shard, **kw)
        eng.warmup()
        for r in reqs:
            eng.submit(r)
        outs = eng.run()
        return ({k: v.token_ids for k, v in outs.items()},
                eng.metrics.spec_rounds)

    want, _ = run(None, "heads")
    mesh1 = Mesh(np.array(jax.devices()[:1]), ("sp",))
    draft = Generator(cfg, mesh1, axis="sp", max_seq=64)
    got, rounds = run(mesh2, "seq", draft=draft, draft_params=params,
                      spec_k=4)
    assert got == want
    assert rounds > 0


def test_mesh_prefix_cache_warm_hit(model, mesh4):
    """A shared system prompt hits the content index on a mesh engine
    exactly like world-1: the second request's prefill skips the cached
    prefix (gathered through the sharded load_pages program) and the
    streams stay bit-exact."""
    cfg, params, gen = model
    shared = np.arange(24, dtype=np.int32) % cfg.vocab
    tails = [np.array([1, 2, 3], np.int32), np.array([4, 5, 6], np.int32)]
    reqs = lambda: [Request(f"s{i}", np.concatenate([shared, t]),
                            SamplingParams(max_new_tokens=6))
                    for i, t in enumerate(tails)]
    def run(mesh):
        eng = ServeEngine(gen, params, num_blocks=24, page_size=8,
                          max_batch=1, prefill_chunk=8, mesh=mesh,
                          kv_shard="heads")
        eng.warmup()
        outs = {}
        for r in reqs():          # serially: s1 admits after s0 commits
            eng.submit(r)
            outs.update({k: v.token_ids for k, v in eng.run().items()})
        return outs, eng.metrics.prefix_hits, \
            eng.metrics.prefix_skipped_tokens

    want, _, _ = run(None)
    got, hits, skipped = run(mesh4)
    assert got == want
    assert hits >= 1 and skipped >= 8


# ---------------------------------------------------------------------------
# Restore across mesh shapes
# ---------------------------------------------------------------------------


def _snap_crash_restore(model, tmp_path, src_mesh, src_shard, dst_mesh,
                        dst_shard, tag):
    cfg, params, gen = model
    rng = np.random.default_rng(2)
    p0 = rng.integers(0, cfg.vocab, 16).astype(np.int32)
    p1 = rng.integers(0, cfg.vocab, 16).astype(np.int32)
    sp1 = SamplingParams(max_new_tokens=16, temperature=0.9, top_k=16,
                         seed=5)

    def fresh(mesh, shard, **kw):
        return ServeEngine(gen, params, num_blocks=24, page_size=8,
                           max_batch=2, prefill_chunk=8, mesh=mesh,
                           kv_shard=shard, **kw)

    want_eng = fresh(None, "heads")
    want_eng.submit(Request("a", p0, SamplingParams(max_new_tokens=16)))
    want_eng.submit(Request("b", p1, sp1))
    want = {k: v.token_ids for k, v in want_eng.run().items()}

    d = str(tmp_path / tag)
    eng = fresh(src_mesh, src_shard, snapshot_dir=d, snapshot_every=2)
    eng.submit(Request("a", p0, SamplingParams(max_new_tokens=16)))
    eng.submit(Request("b", p1, sp1))
    for _ in range(6):
        eng.step()          # abandoned mid-decode == crash
    kw = {}
    if dst_mesh is not None:
        kw.update(mesh=dst_mesh, kv_shard=dst_shard)
    restored = ServeEngine.restore(d, gen, params, **kw)
    got = {k: v.token_ids for k, v in restored.run().items()}
    assert got == want, tag
    return restored


def test_mesh_restore_world1_to_mesh4(model, tmp_path, mesh4):
    """A world-1 snapshot restores IN PLACE onto a 4-device heads mesh
    (pools re-laid-out by one device_put) — resumed streams
    bit-identical to the uninterrupted run."""
    r = _snap_crash_restore(model, tmp_path, None, "heads", mesh4,
                            "heads", "w1_to_m4")
    assert r.metrics.restored_in_place == 2


def test_mesh_restore_mesh4_to_world1(model, tmp_path, mesh4):
    """And back: a mesh-4 snapshot (orbax holds GLOBAL arrays) restores
    onto a plain world-1 engine, in place."""
    r = _snap_crash_restore(model, tmp_path, mesh4, "heads", None,
                            "heads", "m4_to_w1")
    assert r.metrics.restored_in_place == 2


@pytest.mark.slow
def test_mesh_restore_seq_shapes_chaos(model, tmp_path, mesh4, mesh2):
    """The seq legs: seq/4 -> seq/2 adopts in place when the partition
    placement stays compatible; heads/2 -> seq/4 violates placement and
    re-queues through exact recompute — bit-exact either way."""
    r = _snap_crash_restore(model, tmp_path, mesh4, "seq", mesh2, "seq",
                            "s4_to_s2")
    assert r.metrics.restored_in_place == 2
    r = _snap_crash_restore(model, tmp_path, mesh2, "heads", mesh4,
                            "seq", "h2_to_s4")
    assert r.metrics.restored_requeued == 2
    assert r.metrics.restored_in_place == 0


def test_mesh_restore_2d_to_world1_and_heads(model, tmp_path, mesh22,
                                             mesh4):
    """2D snapshot legs (fast tier — the tentpole's recovery story):
    heads+seq/2x2 -> world-1 and -> heads/4 both adopt IN PLACE (pools
    are saved global; both targets are partition-free), streams
    bit-exact either way."""
    r = _snap_crash_restore(model, tmp_path, mesh22, "heads+seq", None,
                            "heads", "2d_to_w1")
    assert r.metrics.restored_in_place == 2
    r = _snap_crash_restore(model, tmp_path, mesh22, "heads+seq", mesh4,
                            "heads", "2d_to_h4")
    assert r.metrics.restored_in_place == 2


@pytest.mark.slow
def test_mesh_restore_2d_layout_pairs(model, tmp_path, mesh22, mesh2,
                                      mesh4):
    """The remaining heads+seq layout pairs: into a COMPATIBLE seq
    partitioning (sp world 2 -> seq world 2: same block partition map)
    restore adopts in place, and so does seq/4 -> 2D/sp2 (4 partitions
    REFINE 2 — every old placement is legal under the coarser map);
    2D/sp2 -> seq/4 goes the other way, breaks placement, and every
    row re-queues through exact recompute; world-1 -> 2D re-queues too
    (unpartitioned tables).  Streams are bit-exact on every leg."""
    r = _snap_crash_restore(model, tmp_path, mesh22, "heads+seq", mesh2,
                            "seq", "2d_to_s2")
    assert r.metrics.restored_in_place == 2
    r = _snap_crash_restore(model, tmp_path, mesh4, "seq", mesh22,
                            "heads+seq", "s4_to_2d")
    assert r.metrics.restored_in_place == 2
    r = _snap_crash_restore(model, tmp_path, mesh22, "heads+seq", mesh4,
                            "seq", "2d_to_s4")
    assert r.metrics.restored_requeued == 2
    assert r.metrics.restored_in_place == 0
    r = _snap_crash_restore(model, tmp_path, None, "heads", mesh22,
                            "heads+seq", "w1_to_2d")
    assert (r.metrics.restored_in_place
            + r.metrics.restored_requeued) == 2


# ---------------------------------------------------------------------------
# Slow tier: spec rounds on a mesh, horizon sweep, live migration
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_mesh_spec_oracle(model, oracle, mesh4):
    """Fused speculative rounds under shard_map (self-draft): the
    multi-token verify runs head-sharded TP, the draft replicated, and
    every stream — greedy and seeded-sampled — is bit-identical to the
    draft-less world-1 oracle."""
    cfg, params, gen = model
    mesh1 = Mesh(np.array(jax.devices()[:1]), ("sp",))
    draft = Generator(cfg, mesh1, axis="sp", max_seq=64)
    eng = _build(gen, params, mesh=mesh4, kv_shard="heads", draft=draft,
                 draft_params=params, spec_k=4)
    eng.warmup()
    flat = eng.metrics.compile_misses
    got = _serve(eng, _requests(cfg))
    assert got == oracle
    assert eng.metrics.compile_misses == flat
    assert eng.metrics.spec_rounds > 0


@pytest.mark.slow
def test_mesh_horizon_sweep(model, oracle, mesh2, mesh22):
    """Horizon in {1, 8} x kv_shard in {heads, seq, heads+seq} all
    equal the oracle (the fast tests cover the other diagonal: heads
    H=8, heads+seq H=8)."""
    cfg, params, gen = model
    for mesh, kv_shard, horizon in ((mesh2, "heads", 1),
                                    (mesh2, "seq", 8),
                                    (mesh22, "heads+seq", 1)):
        eng = _build(gen, params, mesh=mesh, kv_shard=kv_shard,
                     horizon=horizon)
        eng.warmup()
        got = _serve(eng, _requests(cfg))
        assert got == oracle, (kv_shard, horizon)


@pytest.mark.slow
def test_mesh_2d_spec_oracle(model, oracle, mesh22):
    """Fused speculative rounds on the 2D mesh: verify + decode legs
    run head-sharded TP x block-sharded SP (the 4D-q combine under
    both axes at once), draft replicated — streams bit-identical to
    the draft-less world-1 oracle."""
    cfg, params, gen = model
    mesh1 = Mesh(np.array(jax.devices()[:1]), ("sp",))
    draft = Generator(cfg, mesh1, axis="sp", max_seq=64)
    eng = _build(gen, params, mesh=mesh22, kv_shard="heads+seq",
                 draft=draft, draft_params=params, spec_k=4)
    eng.warmup()
    flat = eng.metrics.compile_misses
    got = _serve(eng, _requests(cfg))
    assert got == oracle
    assert eng.metrics.compile_misses == flat
    assert eng.metrics.spec_rounds > 0


@pytest.mark.slow
def test_mesh_2d_prefix_warm_hit(model, mesh22):
    """Warm prefix hits on the 2D mesh: the shared pages span BOTH sp
    partitions and carry tp-local head shards; the masked-psum gather
    re-assembles them and the warm streams stay bit-exact."""
    cfg, params, gen = model
    shared = np.arange(40, dtype=np.int32) % cfg.vocab
    tails = [np.array([1, 2, 3], np.int32), np.array([4, 5, 6], np.int32)]

    def run(mesh, kv_shard):
        eng = ServeEngine(gen, params, num_blocks=24, page_size=8,
                          max_batch=1, prefill_chunk=8, mesh=mesh,
                          kv_shard=kv_shard)
        eng.warmup()
        outs = {}
        for i, t in enumerate(tails):
            eng.submit(Request(f"s{i}", np.concatenate([shared, t]),
                               SamplingParams(max_new_tokens=6)))
            outs.update({k: v.token_ids for k, v in eng.run().items()})
        return outs, eng.metrics.prefix_skipped_tokens

    want, _ = run(None, "heads")
    got, skipped = run(mesh22, "heads+seq")
    assert got == want
    assert skipped >= 8


@pytest.mark.slow
def test_mesh_seq_prefix_warm_hit(model, mesh2):
    """The seq layout's warm-prefix gather: shared pages live in
    different ranks' partitions, the masked psum assembles the full
    scratch, and the warm stream stays bit-exact with world-1."""
    cfg, params, gen = model
    # 40 shared tokens = 5 pages: at W=2 (4 logical pages per rank) the
    # cached prefix genuinely SPANS both ranks' partitions
    shared = np.arange(40, dtype=np.int32) % cfg.vocab
    tails = [np.array([1, 2, 3], np.int32), np.array([4, 5, 6], np.int32)]

    def run(mesh, kv_shard):
        eng = ServeEngine(gen, params, num_blocks=24, page_size=8,
                          max_batch=1, prefill_chunk=8, mesh=mesh,
                          kv_shard=kv_shard)
        eng.warmup()
        outs = {}
        for i, t in enumerate(tails):
            eng.submit(Request(f"s{i}", np.concatenate([shared, t]),
                               SamplingParams(max_new_tokens=6)))
            outs.update({k: v.token_ids for k, v in eng.run().items()})
        return outs, eng.metrics.prefix_skipped_tokens

    want, _ = run(None, "heads")
    got, skipped = run(mesh2, "seq")
    assert got == want
    assert skipped >= 8     # the warm admit really skipped prefill


@pytest.mark.slow
def test_mesh_drain_migrates_to_world1(model, mesh4):
    """Live migration off a mesh: a mesh-4 engine drains mid-stream and
    a world-1 engine adopts IN PLACE (the gathered pages are global
    arrays) — the continued stream is bit-exact."""
    cfg, params, gen = model
    p = np.arange(14, dtype=np.int32) % cfg.vocab
    want_eng = _build(gen, params)
    want_eng.submit(Request("m", p, SamplingParams(max_new_tokens=12)))
    want = want_eng.run()["m"].token_ids

    src = _build(gen, params, mesh=mesh4, kv_shard="heads")
    src.submit(Request("m", p, SamplingParams(max_new_tokens=12)))
    for _ in range(6):
        src.step()
    manifest = src.drain(["m"])
    assert manifest["requests"][0].get("kv") is not None
    dst = _build(gen, params)
    res = dst.migrate_in(manifest)
    assert res["adopted"] == ["m"]
    got = dst.run()["m"].token_ids
    assert got == want


@pytest.mark.slow
def test_mesh_drain_2d_layout_pairs(model, mesh22, mesh2):
    """Live migration off (and onto) the 2D mesh: heads+seq/2x2 drains
    mid-stream into a world-1 adopter AND into a seq/2 adopter (same
    partition map: in-place KV adopt); a heads/2 source drains INTO a
    2D adopter — continued streams bit-exact on every leg."""
    cfg, params, gen = model
    p = np.arange(14, dtype=np.int32) % cfg.vocab
    want_eng = _build(gen, params)
    want_eng.submit(Request("m", p, SamplingParams(max_new_tokens=12)))
    want = want_eng.run()["m"].token_ids

    legs = [(mesh22, "heads+seq", None, "heads"),
            (mesh22, "heads+seq", mesh2, "seq"),
            (mesh2, "heads", mesh22, "heads+seq")]
    for src_mesh, src_shard, dst_mesh, dst_shard in legs:
        src = _build(gen, params, mesh=src_mesh, kv_shard=src_shard)
        src.submit(Request("m", p, SamplingParams(max_new_tokens=12)))
        for _ in range(6):
            src.step()
        manifest = src.drain(["m"])
        kw = ({} if dst_mesh is None
              else dict(mesh=dst_mesh, kv_shard=dst_shard))
        dst = _build(gen, params, **kw)
        res = dst.migrate_in(manifest)
        assert res["adopted"] == ["m"], (src_shard, dst_shard)
        got = dst.run()["m"].token_ids
        assert got == want, (src_shard, dst_shard)


def test_heterogeneous_mesh_fleet_chaos(model, mesh22, oracle, tmp_path):
    """Fleet replicas on DIFFERENT mesh shapes behind one controller
    (the ROADMAP #1 open follow-up, upgraded to the ISSUE 19 2D
    layout): r0 is a 2x2 kv_shard="heads+seq" mesh engine, r1 a plain
    world-1 engine.  Kill the 2D replica mid-decode: every stream
    (migrated ones included) finishes bit-identical to the world-1
    oracle, the cross-replica token union is exactly-once (single
    journal ownership, no index with two values — the
    serve_fleet_zero_loss contract), and the 2D replica restarts
    healthy."""
    from triton_dist_tpu.runtime.faults import FaultInjector
    from triton_dist_tpu.serve.fleet import FleetController
    from triton_dist_tpu.serve.recovery import JOURNAL_NAME, replay_journal

    cfg, params, gen = model
    inj = FaultInjector(seed=0).inject("forward", kill=True, at_call=14)

    def factory(d):
        if (os.sep + "r0" + os.sep) in d:
            return _build(gen, params, mesh=mesh22,
                          kv_shard="heads+seq", snapshot_dir=d,
                          faults=inj if d.endswith("life1") else None)
        return _build(gen, params, snapshot_dir=d)

    fc = FleetController(factory, 2, root=str(tmp_path / "fleet"),
                         suspect_after_s=50.0, dead_after_s=100.0,
                         backoff_base_s=0.01, backoff_cap_s=0.1, seed=0)
    reqs = _requests(cfg)
    sub = steps = 0
    while fc.has_work() or sub < len(reqs):
        if steps % 2 == 0 and sub < len(reqs):
            fc.submit(reqs[sub])
            sub += 1
        fc.step()
        steps += 1
        assert steps < 800
    assert fc.deaths == 1 and inj.fire_count("forward") == 1
    assert fc.replicas["r0"].restarts == 1
    assert fc.replicas["r0"].engine.mesh is not None   # restarted AS mesh
    # every stream bit-identical to the world-1 oracle, exactly-once
    assert set(fc.outputs) == set(oracle)
    for rid, toks in oracle.items():
        assert list(fc.outputs[rid].token_ids) == list(toks), rid
        assert fc.streams[rid] == list(toks), rid
    # the kill landed with requests in flight: something migrated
    moved = [r for r, h in fc.history.items() if len(set(h)) > 1]
    assert moved, fc.history
    # cross-journal union: token values agree at every index across
    # every life's journal, exactly one journal owns each stream
    owners: dict = {}
    values: dict = {}
    for jp in glob.glob(os.path.join(str(tmp_path / "fleet"), "*",
                                     "life*", JOURNAL_NAME)):
        for rid, jr in replay_journal(jp).items():
            for i, (tok, _) in jr.tokens.items():
                values.setdefault(rid, {}).setdefault(i, set()).add(tok)
            if not jr.migrated and jr.finish is not None:
                owners[rid] = owners.get(rid, 0) + 1
    for rid, toks in oracle.items():
        assert owners.get(rid) == 1, (rid, owners)
        assert all(values[rid][i] == {toks[i]}
                   for i in range(len(toks))), rid
