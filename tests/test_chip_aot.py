"""AOT: the serving programs compile for the v5e and hold their kernels.

No chip is used: the programs are lowered and compiled against
``jax.experimental.topologies.get_topology_desc("v5e:2x2")``, which the
installed libtpu provides on a CPU host.  What this pins, at llama3-8B
widths (depth cut to 2 layers — a compile check, not a run):

- world-1 ``paged_decode``, ``decode_horizon[H=8]`` (greedy and sampled) and
  ``[H=1]`` (sampled) and ``prefill_chunk`` at 128 and 256 rows compile for
  the v5e and hold exactly one Mosaic custom call per layer; the Mistral
  cells' 256-row call at all 16 layers on every rung of their ladder;
- the world-4 decode and chunked-prefill programs of ``serve/mesh.py``
  compile for ``heads``, ``seq`` and ``heads+seq`` (2x2), with one Mosaic
  call per layer under ``heads`` and two (attention kernel + SP combine)
  under the seq layouts;
- at the cells' pool geometry no decode-path program (bf16, int8, a
  ``heads`` rank of 4, the latent plane) copies a whole pool plane or
  holds a temporary that grows with the pool: the paged K/V write lands
  in place;
- the sampled horizon and ``sample_token`` hold no sort and no ``TopK``
  call at either cell's rows x vocabulary;
- at the CPU-demo geometry (page 16, chunk 64) the same programs hold NO
  Mosaic call, and the engine's construction-time kernel-reach report
  (``attention_kernel_gaps``) says so in words.

``resolve_impl`` reads the PROCESS platform, so ``impl="auto"`` would resolve
to XLA when lowering from this CPU host; the fixture pins ``is_tpu`` to what
the target is.  Slow tier (each compile is seconds).
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from triton_dist_tpu.analysis.jaxpr_audit import MOSAIC_CALL
from triton_dist_tpu.models import llama
from triton_dist_tpu.models.generate import Generator
from triton_dist_tpu.runtime import topology
from triton_dist_tpu.serve import engine as E
from triton_dist_tpu.serve import mesh as serve_mesh
from triton_dist_tpu.serve import programs as PR

LAYERS = 2
B, MAX_SEQ = 8, 2048
I32 = jnp.int32
HBM_GIB = 15.75     # what the v5e's compiler allows one program


@pytest.fixture(scope="module")
def v5e():
    """The 2x2 v5e topology description, or a LOUD skip."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any libtpu failure is a skip
        pytest.skip(f"SKIPPED LOUDLY: libtpu cannot describe a v5e:2x2 "
                    f"topology on this host, so NO serving program was "
                    f"AOT-compiled for the chip: {type(e).__name__}: {e}")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return topo


@pytest.fixture()
def as_tpu(monkeypatch):
    monkeypatch.setattr(topology, "is_tpu", lambda: True)


def _cfg():
    return dataclasses.replace(llama.LlamaConfig.llama3_8b(),
                               n_layers=LAYERS, max_seq=MAX_SEQ)


def _abstract_params(cfg):
    return jax.eval_shape(functools.partial(llama.init_params, cfg),
                          jax.random.key(0))


def _on(tree, sharding):
    """ShapeDtypeStructs of ``tree`` carrying ``sharding`` (one sharding,
    or a matching tree of them)."""
    if not isinstance(sharding, jax.sharding.Sharding):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree, sharding)
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _compile(jitted, *args, **kw) -> int:
    """Lower + compile for the topology the args live on; the Mosaic
    custom calls in the lowered text."""
    lowered = jitted.lower(*args, **kw)
    lowered.compile()
    return lowered.as_text().count(MOSAIC_CALL)


def _decode_args(cfg, page, num_blocks=257, batch=B, max_seq=MAX_SEQ,
                 params=None, pools=None):
    """Abstract argument tuples of ``paged_decode`` and
    ``decode_horizon`` (no sharding yet — callers place them); the dense
    family's bf16 pools and parameters unless others are passed."""
    s = jax.ShapeDtypeStruct
    if pools is None:
        pool = s((num_blocks, cfg.n_kv_heads, page, cfg.head_dim), cfg.dtype)
        pools = [(pool, pool)] * cfg.n_layers
    keys = jax.eval_shape(lambda: jnp.stack([jax.random.key(0)] * batch))
    vec = lambda dt: s((batch,), dt)  # noqa: E731
    decode = (params or _abstract_params(cfg), pools,
              s((batch, max_seq // page), I32), vec(I32), vec(I32),
              vec(bool))
    horizon = decode + (vec(bool), vec(I32), vec(I32), keys,
                        vec(jnp.float32), vec(I32), vec(jnp.float32),
                        vec(bool), vec(I32))
    return decode, horizon


def _dense_gen(cfg):
    """The dense family's generator, as the benchmark's builder makes it:
    the seams the engine binds into its programs."""
    return Generator(cfg, Mesh(np.array(jax.devices()[:1]), ("sp",)),
                     axis="sp", max_seq=cfg.max_seq)


def _world1_programs(cfg, page):
    gen = _dense_gen(cfg)
    decode_fwd = functools.partial(PR._paged_decode_forward, cfg=cfg,
                                   page=page, **gen.serve_hooks())
    decode = jax.jit(decode_fwd, donate_argnums=(1,))
    horizon = jax.jit(functools.partial(PR._paged_decode_horizon,
                                        decode_fwd=decode_fwd),
                      static_argnames=("H", "all_greedy"),
                      donate_argnums=(1,))
    return decode, horizon, gen._chunk_jit


def _chunk_args(cfg, c, extent):
    """Abstract (positional args, n_valid) of ``prefill_chunk``."""
    s = jax.ShapeDtypeStruct
    sc = s((1, cfg.n_kv_heads, extent, cfg.head_dim), cfg.dtype)
    return ((_abstract_params(cfg), s((1, c), I32),
             [(sc, sc)] * cfg.n_layers, s((), I32)), s((), I32))


def test_world1_programs_compile_with_one_mosaic_call_per_layer(v5e, as_tpu):
    cfg = _cfg()
    put = functools.partial(_on, sharding=SingleDeviceSharding(v5e.devices[0]))
    decode, horizon, chunk = _world1_programs(cfg, page=128)
    d_args, h_args = put(_decode_args(cfg, 128))
    assert _compile(decode, *d_args) == LAYERS
    for H, all_greedy in ((8, True), (8, False), (1, False)):
        assert _compile(horizon, *h_args, H=H,
                        all_greedy=all_greedy) == LAYERS
    # a call of one chunk's rows (budget = chunk), and the 256 rows of the
    # smoke's engine (chunk 128 at the default budget: prefill_width)
    wide = E.prefill_width(128, 4 * 128)
    assert wide == 256
    for rows, extent in ((128, 128), (128, MAX_SEQ), (wide, wide),
                         (wide, MAX_SEQ)):
        args, n_valid = put(_chunk_args(cfg, rows, extent))
        assert _compile(chunk, *args, quantized=False, extent=extent,
                        n_valid=n_valid) == LAYERS
    for rows, ladder in ((128, [128, 256, 512, 1024, 2048]),
                         (wide, [256, 512, 1024, 2048])):
        assert E.attention_kernel_gaps(
            head_dim=cfg.head_dim, page_size=128, prefill_chunk=rows,
            ladder=ladder, kv_itemsize=2, kv_quant=False,
            impl="auto", interpret=False) == {}


def test_the_dense_cells_prefill_call_compiles_on_every_rung(v5e, as_tpu):
    """The Mistral cells' prefill program since PR 33 — ``prefill_chunk`` at
    ``[1, W]``, W = 256 (chunk 128, budget 512: ``engine.prefill_width``) —
    at the file's widths and all 16 layers, on EVERY rung of the ladder the
    engine builds at that width: one Mosaic call a layer, under the name the
    benchmark's reader matches, beside the 7.5 GB of weights in what the
    v5e's compiler allows one program, and no attention path off its
    kernel."""
    import json
    import os

    from benchmarks import builders

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "benchmarks/configs/mistral-7b-v0.2-l16.json")) as f:
        config = json.load(f)
    cfg, eng = builders.llama_config(config), config["engine"]
    page, chunk, max_seq = (eng["page_size"], eng["prefill_chunk"],
                            eng["max_seq"])
    width = E.prefill_width(chunk, 4 * chunk)
    assert width == 256
    ladder = E.build_bucket_ladder(max(page, width), max_seq, page)
    assert ladder == [256, 512, 1024, 2048, 4096, 8192]
    assert _dense_gen(cfg).kernel_gaps(
        page_size=page, prefill_chunk=width, ladder=ladder) == {}
    put = functools.partial(_on, sharding=SingleDeviceSharding(v5e.devices[0]))
    chunk_jit = _dense_gen(cfg)._chunk_jit
    peak = {}
    for extent in ladder:
        args, n_valid = put(_chunk_args(cfg, width, extent))
        compiled = chunk_jit.lower(*args, quantized=False, extent=extent,
                                   n_valid=n_valid).compile()
        text = compiled.as_text()
        assert text.split(",", 1)[0] == "HloModule jit_prefill_chunk"
        assert text.count(MOSAIC_CALL) == cfg.n_layers, extent
        ma = compiled.memory_analysis()
        peak[extent] = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
                        + ma.output_size_in_bytes - ma.alias_size_in_bytes)
        assert peak[extent] < HBM_GIB * 2 ** 30, (extent, peak)
    print(f"prefill_chunk[1, {width}] GiB a program by extent:",
          {e: round(b / 2 ** 30, 2) for e, b in peak.items()})


# (batch, max_seq, pool blocks): the smoke's geometry, and the benchmark
# cells' (Mistral-7B shares llama3-8B's 32 / 8 heads x 128): B 32, table
# width 8192 / 128 = 64, 449 blocks
_GEOMETRIES = [(B, MAX_SEQ, 257), (32, 8192, 449)]


@pytest.mark.parametrize("batch,max_seq,num_blocks", _GEOMETRIES)
def test_named_programs_keep_the_operation_names_the_benchmark_reads(
        v5e, as_tpu, batch, max_seq, num_blocks):
    """The engine's programs as it builds them since PR 24
    (``jit_cache.named``): the HLO module reads ``jit_<program>``, and the
    paged attention call — which XLA names after the scope around it —
    still reads ``_unknown_`` in the single-step program
    (``_paged_decode_step`` keeps it there) and ``closed_call`` in the
    horizon's scan, at H = 8 and at the one-step link's H = 1 (the
    mixed-sampler variant, the one the engine builds there): the two
    names ``benchmarks/layer_metrics/paged_attn_roofline.json`` sums.
    Exactly ONE Mosaic call a layer in both: the paged kernel walks a
    row's live pages and carries every KV head inside one call (no second
    call for a merge)."""
    from triton_dist_tpu.runtime.jit_cache import named

    cfg = dataclasses.replace(_cfg(), max_seq=max_seq)
    put = functools.partial(_on, sharding=SingleDeviceSharding(v5e.devices[0]))
    kw = dict(cfg=cfg, page=128, **_dense_gen(cfg).serve_hooks())
    d_args, h_args = put(_decode_args(cfg, 128, num_blocks=num_blocks,
                                      batch=batch, max_seq=max_seq))

    def calls(jitted, *args, **statics):
        text = jitted.lower(*args, **statics).compile().as_text()
        return text.split(",", 1)[0], _mosaic_names(text)

    module, names = calls(jax.jit(named(
        PR._paged_decode_step, "paged_decode", **kw), donate_argnums=(1,)),
        *d_args)
    assert module == "HloModule jit_paged_decode"
    assert names == {"_unknown_": LAYERS}
    horizon = jax.jit(named(
        PR._paged_decode_horizon, "decode_horizon",
        decode_fwd=functools.partial(PR._paged_decode_forward, **kw)),
        static_argnames=("H", "all_greedy"), donate_argnums=(1,))
    # H = 1 is the link of a clamped step (a slot mid-prefill): XLA drops
    # its one-trip loop, and the call still reads ``closed_call`` — it
    # stays inside the pattern, at one decode step a link
    for H, all_greedy in ((8, True), (1, False)):
        module, names = calls(horizon, *h_args, H=H, all_greedy=all_greedy)
        assert module == "HloModule jit_decode_horizon"
        assert names == {"closed_call": LAYERS}, (H, all_greedy)
    # a cold request's scratch (PR 41) is a program of NO arguments: what
    # it is compiled for is where its outputs live — one chip, or born on
    # a TP-4 mesh under the spec the chunk program takes it on
    zero = jax.jit(
        named(PR._zero_scratch, "zero_scratch", quantized=False,
              dtype=cfg.dtype,
              specs=[((cfg.n_kv_heads, cfg.head_dim),) * 2] * LAYERS),
        static_argnames=("s_ext",),
        out_shardings=SingleDeviceSharding(v5e.devices[0]))
    text = zero.lower(s_ext=max_seq).compile().as_text()
    assert text.split(",", 1)[0] == "HloModule jit_zero_scratch"
    assert not _mosaic_names(text)
    mesh = Mesh(np.array(v5e.devices), ("tp",))
    progs = serve_mesh.build_programs(
        mesh=mesh, tp_axis="tp", kv_shard="heads", cfg=cfg,
        params=d_args[0], page_size=128, num_blocks=num_blocks,
        n_pages_max=max_seq // 128, impl="auto", interpret=False, horizon=8)
    born = progs["zero_scratch"]._prog((("s_ext", max_seq),)).lower().compile()
    assert born.as_text().split(",", 1)[0] == "HloModule jit_zero_scratch"
    k, v = born.output_shardings[0]
    assert k == v == progs["prefill_chunk"]._maker(max_seq)._placements[2][0][0]
    assert jax.tree.leaves(born.out_info)[0].shape == (
        1, cfg.n_kv_heads, max_seq, cfg.head_dim)


# (local KV heads a page, q heads a KV head, batch, table width): the
# cells' paged calls — mellum2's 4 heads, Mistral's 8 (and its TP-4 rank's
# 2, a single head), Laguna's 8 under 9 query heads each, phi-4's 10 PAIRS
# of 64-wide heads, olmo-hybrid's 30
_PAGED_CALLS = [(8, 4, 32, 64), (2, 4, 32, 64), (1, 4, 32, 64),
                (4, 8, 64, 160), (8, 9, 64, 80), (10, 4, 96, 40),
                (30, 1, 96, 40)]


@pytest.mark.parametrize("hkv,g,batch,width", _PAGED_CALLS)
@pytest.mark.parametrize("n_tok", [1, 5])
def test_paged_kernel_compiles_at_the_cell_geometry_and_on_a_tp4_rank(
        v5e, as_tpu, hkv, g, batch, width, n_tok):
    """The paged decode call alone, bf16, page 128, 449 blocks, at every
    cell's heads a page with the page ring the shapes give it (ISSUE 48),
    plain and under a window; one decode token and a 5-token verify.  The
    CPU host reproduces the chip's scoped-VMEM refusals, so this is the
    check before chip time."""
    from triton_dist_tpu.kernels import flash_decode as fd

    D, page = 128, 128
    s = functools.partial(jax.ShapeDtypeStruct,
                          sharding=SingleDeviceSharding(v5e.devices[0]))
    q = s((batch, hkv * g, D) if n_tok == 1 else (batch, n_tok, hkv * g, D),
          jnp.bfloat16)
    pool = s((449, hkv, page, D), jnp.bfloat16)
    for window in (0, 512):
        attend = jax.jit(functools.partial(fd.gqa_decode_paged_shard,
                                           impl="pallas", window=window))
        assert _compile(attend, q, pool, pool, s((batch, width), I32),
                        s((batch,), I32)) == 1
    slots = fd.paged_pages_in_flight(hkv, page, D, 2)
    ring_bytes = slots * 2 * hkv * page * D * 2
    assert fd.paged_kernel_blocking(hkv, page, D, 2, batch=batch) == {
        "heads_per_step": hkv, "steps_per_call": batch,
        "pages_per_step": "dynamic", "pages_in_flight": slots,
        "vmem_bytes": ring_bytes}
    assert ring_bytes <= fd.PAGED_VMEM_BUDGET


def test_cpu_demo_geometry_holds_no_kernel_and_the_engine_says_so(v5e,
                                                                  as_tpu):
    """page 16 / chunk 64 — ``examples/serve.py``'s and ``ServeEngine``'s
    defaults — reach neither attention kernel, at real head_dim."""
    cfg = _cfg()
    put = functools.partial(_on, sharding=SingleDeviceSharding(v5e.devices[0]))
    decode, horizon, chunk = _world1_programs(cfg, page=16)
    d_args, h_args = put(_decode_args(cfg, 16))
    assert _compile(decode, *d_args) == 0
    assert _compile(horizon, *h_args, H=8, all_greedy=True) == 0
    args, n_valid = put(_chunk_args(cfg, 64, 512))
    assert _compile(chunk, *args, quantized=False, extent=512,
                    n_valid=n_valid) == 0
    gaps = E.attention_kernel_gaps(
        head_dim=cfg.head_dim, page_size=16, prefill_chunk=64,
        ladder=[64, 128, 256, 512], kv_itemsize=2, kv_quant=False,
        impl="auto", interpret=False)
    assert "page=16" in gaps["paged_decode"]
    assert "chunk=64" in gaps["prefill_chunk"]
    # int8 pools always take the XLA dequant path, whatever the page
    gaps = E.attention_kernel_gaps(
        head_dim=cfg.head_dim, page_size=128, prefill_chunk=128,
        ladder=[128, 256], kv_itemsize=2, kv_quant=True, impl="auto",
        interpret=False)
    assert "int8" in gaps["paged_decode"] and "prefill_chunk" not in gaps


@pytest.mark.parametrize("kv_shard,shape,per_layer", [
    ("heads", (4,), 1), ("seq", (4,), 2), ("heads+seq", (2, 2), 2)])
def test_world4_programs_compile(v5e, as_tpu, kv_shard, shape, per_layer):
    cfg = _cfg()
    mesh = Mesh(np.array(v5e.devices).reshape(shape),
                ("tp", "sp")[:len(shape)])
    build = functools.partial(
        serve_mesh.build_programs, mesh=mesh, tp_axis="tp",
        kv_shard=kv_shard, cfg=cfg, params=_abstract_params(cfg),
        page_size=128, num_blocks=260, n_pages_max=MAX_SEQ // 128,
        interpret=False, horizon=8, sp_axis="sp")
    progs = build(impl="auto")

    def on_mesh(prog, args):
        return tuple(_on(a, p) for a, p in zip(args, prog._placements))

    # 260 blocks: the pool splits evenly over the sp world
    d_args, h_args = _decode_args(cfg, 128, num_blocks=260)
    dec = progs["paged_decode"]
    assert _compile(dec._prog(()), *on_mesh(dec, d_args)) \
        == per_layer * LAYERS
    hor = progs["decode_horizon"]
    statics = (("H", 8), ("all_greedy", True))
    assert _compile(hor._prog(statics), *on_mesh(hor, h_args)) \
        == per_layer * LAYERS
    # chunked prefill too: under seq its combine merges c x Hq partial
    # rows, which overflowed the fused kernel's VMEM on the chip (PR 21)
    # — a failure this compile reproduces without one; at one chunk's
    # rows and at the 256 of an engine built with the default budget
    # (``engine.prefill_width``: the chip smoke's mesh legs)
    chunk = progs["prefill_chunk"]._maker(512)
    for rows in (256, 128):     # the 128-row arguments serve below too
        args, n_valid = _chunk_args(cfg, rows, 512)
        assert _compile(chunk._prog(()),
                        *on_mesh(chunk, args + (n_valid,))) \
            == per_layer * LAYERS, rows
    # and impl="xla", asked for by name, holds no kernel in any layout
    # (the seq prefill attend once dispatched "auto" regardless: the chip
    # smoke's XLA reference turned out to run the kernel it was judging)
    xla = build(impl="xla")
    assert _compile(xla["paged_decode"]._prog(()),
                    *on_mesh(xla["paged_decode"], d_args)) == 0
    chunk = xla["prefill_chunk"]._maker(512)
    assert _compile(chunk._prog(()),
                    *on_mesh(chunk, args + (n_valid,))) == 0


# ---------------------------------------------------------------------------
# The paged K/V write lands in place (ISSUE 27)
# ---------------------------------------------------------------------------


def _write_program(kind, program, v5e, num_blocks):
    """One decode-path program of one pool family as the engine builds
    it, compiled for the described chip(s) at the benchmark cells'
    geometry with a pool of ``num_blocks`` -> ``(compiled, the planes of
    a layer's K as one device holds them)``."""
    from triton_dist_tpu.runtime.jit_cache import named

    s = jax.ShapeDtypeStruct
    params, ranks = None, 1
    if kind == "latent":
        from triton_dist_tpu.models import mla_moe as M

        config, cfg = _mla_moe_cell()
        eng = config["engine"]
        cfg = dataclasses.replace(cfg, n_layers=LAYERS)
        batch, page, max_seq = (eng["max_batch"], eng["page_size"],
                                eng["max_seq"])
        params = jax.eval_shape(functools.partial(M.init_params, cfg),
                                jax.random.key(0))
        gen = M.MlaMoeGenerator(cfg, max_seq=max_seq)
        layer = (s((num_blocks, 1, page, cfg.head_dim), cfg.dtype),)
    else:
        # Mistral-7B's widths: llama3-8B's with its own vocabulary
        cfg = dataclasses.replace(_cfg(), max_seq=8192, vocab=32000)
        batch, page, max_seq = 32, 128, 8192
        gen = _dense_gen(cfg)
        plane = (num_blocks, cfg.n_kv_heads, page, cfg.head_dim)
        k = s(plane, cfg.dtype)
        if kind == "int8":
            k = {"q": s(plane, jnp.int8), "s": s(plane[:3], jnp.float32)}
        layer = (k, k)
    d_args, h_args = _decode_args(cfg, page, batch=batch, max_seq=max_seq,
                                  params=params, pools=[layer] * LAYERS)
    args = {"paged_decode": d_args, "decode_horizon": h_args,
            "paged_verify": d_args[:4] + (s((batch, 5), I32), d_args[5])}
    name, _, sampler = program.partition("-")
    statics = {"H": 8, "all_greedy": sampler == "greedy"} if sampler else {}
    if kind == "heads4":
        ranks = 4
        progs = serve_mesh.build_programs(
            mesh=Mesh(np.array(v5e.devices), ("tp",)), tp_axis="tp",
            kv_shard="heads", cfg=cfg, params=d_args[0], page_size=page,
            num_blocks=num_blocks, n_pages_max=max_seq // page, impl="auto",
            interpret=False, horizon=8)
        if name == "paged_verify":
            # on a mesh the verify is a leg of ``spec_round``: the same
            # body over the seams ``paged_decode`` was bound with
            dec = progs["paged_decode"]
            prog = serve_mesh.ShardedProgram(
                functools.partial(PR._paged_verify_forward,
                                  **dec.body.keywords),
                dec.mesh, dec.in_specs, dec.out_specs, donate_argnums=(1,),
                name=name)
        else:
            prog = progs[name]
        lowered = prog._prog(tuple(sorted(statics.items()))).lower(
            *(_on(a, p) for a, p in zip(args[name], prog._placements)))
    else:
        kw = dict(cfg=cfg, page=page, **gen.serve_hooks())
        wrap = gen.wrap_program
        if name == "decode_horizon":
            jitted = jax.jit(named(
                PR._paged_decode_horizon, name, decode_fwd=wrap(
                    functools.partial(PR._paged_decode_forward, **kw))),
                static_argnames=("H", "all_greedy"), donate_argnums=(1,))
        else:
            body = {"paged_decode": PR._paged_decode_step,
                    "paged_verify": PR._paged_verify_forward}[name]
            jitted = jax.jit(named(wrap(body), name, **kw),
                             donate_argnums=(1,))
        lowered = jitted.lower(
            *_on(args[name], SingleDeviceSharding(v5e.devices[0])),
            **statics)
    return lowered.compile(), [
        s((p.shape[0], p.shape[1] // ranks) + p.shape[2:], p.dtype)
        for p in jax.tree.leaves(layer[0])]


@pytest.mark.parametrize("program", [
    "paged_decode", "decode_horizon-greedy", "decode_horizon-sampled",
    "paged_verify"])
@pytest.mark.parametrize("kind,num_blocks", [
    ("bf16", 449), ("int8", 449), ("heads4", 449), ("latent", 1216)])
def test_the_paged_write_lands_in_place(v5e, as_tpu, kind, num_blocks,
                                        program):
    """No decode-path program re-lays a pool plane out around its K/V
    write: compiled for the v5e at the cells' pool geometry, the program
    holds no ``copy`` / ``transpose`` whose result is a whole plane (in
    its own shape or its merged block-and-head view), and no temporary
    that grows with the pool — its temp bytes at the cell's pool and at
    a pool of 65 blocks differ by under a quarter of a plane.  (Written
    as ``plane.at[row, :, in_page, :]`` the scatter's two indexed
    dimensions have the heads between them, and the chip copies every
    plane out to a layout where they are adjacent and back: two
    whole-plane copies a plane a step and one plane of temp a write,
    every plane a second time under the horizon's scan.)"""
    import re

    compiled, planes = _write_program(kind, program, v5e, num_blocks)
    shapes = {",".join(map(str, dims))
              for nb, hk, *rest in (p.shape for p in planes)
              for dims in ((nb, hk, *rest), (nb * hk, *rest))}
    moved = re.findall(
        r"^\s*(?:ROOT )?%?([\w.\-]+) = \w+\[(?:" + "|".join(shapes)
        + r")\]\S* (?:copy|transpose)\(", compiled.as_text(), re.M)
    assert moved == [], (kind, program, moved)
    small, _ = _write_program(kind, program, v5e, 65)
    temp, temp_small = (c.memory_analysis().temp_size_in_bytes
                        for c in (compiled, small))
    plane_bytes = max(int(np.prod(p.shape)) * p.dtype.itemsize
                      for p in planes)
    assert temp - temp_small < plane_bytes // 4, (
        kind, program, temp, temp_small, plane_bytes)


# ---------------------------------------------------------------------------
# The latent-attention + expert-share cell (ISSUE 26), at published widths
# ---------------------------------------------------------------------------

def _mla_moe_cell(name="gigachat3.1-702b-ep16-l5"):
    """A latent-family benchmark cell's configuration as its builder
    reads it."""
    import json
    import os

    from benchmarks import builders_mla_moe

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, f"benchmarks/configs/{name}.json")) as f:
        config = json.load(f)
    return config, builders_mla_moe.model_config(config)


_CELLS_MLA_MOE = [
    ("gigachat3.1-702b-ep16-l5", (512, 2048, 8192)),
    # the rungs longctx_sat's prompts (4,186-16,033) reach, and the cap
    ("glm-5-ep16-l5", (8192, 16384, 18432)),
    # ISSUE 49: the same block inside a residual of four streams, every
    # expert held, the whole vocabulary: reason96_sat's rungs and the cap
    ("xing4.0-29b-a4b-stage", (512, 2048, 8192)),
]


def _mosaic_names(text):
    """The Mosaic calls of a compiled module by instruction stem — what
    ``benchmarks/xplane.py`` ``op_name`` keeps of an operation, so what
    the accepted roofline metrics match."""
    import re
    from collections import Counter

    return Counter(n.split(".")[0] for n in re.findall(
        r"^\s*(?:ROOT )?%?([\w.\-]+) = [^\n]*custom-call\([^\n]*"
        + MOSAIC_CALL, text, re.M))


def _decode_programs(gen, kw, d_args, h_args, H):
    """A family's decode programs as the engine builds them — single-step
    decode, the fused horizon (greedy and mixed) and its one-step link
    (rung 1 has the mixed variant alone) — as ``(name, jitted, args,
    statics)``."""
    from triton_dist_tpu.runtime.jit_cache import named

    decode = jax.jit(named(gen.wrap_program(PR._paged_decode_step),
                           "paged_decode", **kw), donate_argnums=(1,))
    horizon = jax.jit(named(
        PR._paged_decode_horizon, "decode_horizon",
        decode_fwd=gen.wrap_program(functools.partial(
            PR._paged_decode_forward, **kw))),
        static_argnames=("H", "all_greedy"), donate_argnums=(1,))
    return [("paged_decode", decode, d_args, {})] + [
        ("decode_horizon", horizon, h_args, dict(H=h, all_greedy=g))
        for h, g in ((H, True), (H, False), (1, False))]


def _mla_moe_programs(name, extents):
    """Every program kind of a latent-family cell at the file's widths
    and engine sizes -> ``[(name, jitted, args, statics, the Mosaic calls
    it must hold)]``: ONE latent attention call a layer (and, with an
    indexer, ONE index-score call a layer beside it) and one gate-up + one
    down grouped GEMM an expert layer, under their trace names."""
    from triton_dist_tpu.kernels import flash_decode as fd
    from triton_dist_tpu.models import mla_moe as M

    config, cfg = _mla_moe_cell(name)
    eng = config["engine"]
    batch, page, max_seq = eng["max_batch"], eng["page_size"], eng["max_seq"]
    s = jax.ShapeDtypeStruct
    params = jax.eval_shape(functools.partial(M.init_params, cfg),
                            jax.random.key(0))
    gen = M.MlaMoeGenerator(cfg, max_seq=max_seq)
    assert gen.kernel_gaps(page_size=page) == {}
    assert gen.kv_planes == [(1, 640)] + [(1, 128)] * cfg.sparse
    pool = tuple(s((eng["num_blocks"], h, page, d), cfg.dtype)
                 for h, d in gen.kv_planes)
    d_args, h_args = _decode_args(cfg, page, batch=batch, max_seq=max_seq,
                                  params=params,
                                  pools=[pool] * cfg.n_layers)
    kw = dict(cfg=cfg, page=page, **gen.serve_hooks())
    n_moe = cfg.n_layers - cfg.first_k_dense
    want = {fd_name: n for fd_name, n in (
        (fd.MLA_CALL_NAME, cfg.n_layers),
        (fd.DSA_INDEX_CALL_NAME, cfg.n_layers * cfg.sparse),
        (M.GATE_UP_CALL, n_moe), (M.DOWN_CALL, n_moe)) if n}
    assert (fd.MLA_CALL_NAME, fd.DSA_INDEX_CALL_NAME) == (
        "mla_paged_decode", "dsa_index_scores")     # the readers' patterns
    if cfg.hc_mult > 1:
        # a residual of several streams: a pre-mix and a post-mix around
        # each of a layer's two sub-layers, under their trace names
        from triton_dist_tpu.kernels import hyper_conn as hc

        assert (hc.HC_PRE_CALL, hc.HC_POST_CALL) == ("hc_pre", "hc_post")
        want.update({hc.HC_PRE_CALL: 2 * cfg.n_layers,
                     hc.HC_POST_CALL: 2 * cfg.n_layers})
        assert gen.stream_rows({"paged_decode": batch})["gaps"] == {}
    programs = [p + (want,) for p in _decode_programs(
        gen, kw, d_args, h_args, eng["horizon"])]
    ladder = E.build_bucket_ladder(max(page, eng["prefill_chunk"]), max_seq,
                                   page)
    assert set(extents) <= set(ladder) and extents[-1] == max_seq
    # a sparse block's chunk of this size attends in the expanded form:
    # the flash call over expanded rows in the absorbed page walk's place
    chunk_want = dict(want)
    # a chunk of a layer that holds 1 expert in 16 sums its experts' rows
    # by walking the live tiles (ISSUE 46); a decode step gathers, and so
    # does every program of a layer that holds ALL its experts
    whole = cfg.experts_held == cfg.n_experts
    assert M.combine_form(eng["prefill_chunk"], cfg) == (
        "gather" if whole else "walk") and \
        M.combine_form(batch, cfg) == "gather"
    if not whole:
        chunk_want[M.COMBINE_CALL] = n_moe
    if cfg.expands(eng["prefill_chunk"]):
        assert fd.MLA_PREFILL_CALL_NAME == "mla_expanded_prefill"
        chunk_want[fd.MLA_PREFILL_CALL_NAME] = chunk_want.pop(
            fd.MLA_CALL_NAME)
        assert gen.kernel_gaps(page_size=page, ladder=ladder,
                               prefill_chunk=eng["prefill_chunk"]) == {}
    for extent in extents:
        sc = tuple(s((1, h, extent, d), cfg.dtype) for h, d in gen.kv_planes)
        programs.append((
            "prefill_chunk", gen._chunk_jit,
            (params, s((1, eng["prefill_chunk"]), I32),
             [sc] * cfg.n_layers, s((), I32)),
            dict(quantized=False, extent=extent, n_valid=s((), I32)),
            chunk_want))
    return programs


def _compiled(v5e, jitted, args, statics):
    put = functools.partial(_on, sharding=SingleDeviceSharding(v5e.devices[0]))
    return jitted.lower(*put(args), **statics).compile()


def _gib(compiled) -> float:
    ma = compiled.memory_analysis()
    return (ma.temp_size_in_bytes + ma.argument_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes) / 2 ** 30


@pytest.mark.parametrize("name,extents", _CELLS_MLA_MOE)
def test_mla_moe_programs_compile_at_published_widths(v5e, as_tpu, name,
                                                      extents):
    """Every program kind of ``gc3_ep16_l5_reason_sat`` and of
    ``glm5_ep16_l5_longctx_sat`` — single-step decode, the fused horizon
    (greedy and mixed) and its one-step link, prefill chunks at the
    shortest, a middle and the cap extent — at the file's widths and
    engine sizes: each holds its calls under their trace names (the
    benchmark's roofline readers match them; :func:`_mla_moe_programs`),
    and fits the chip beside nothing else."""
    for prog, jitted, args, statics, want in _mla_moe_programs(name,
                                                               extents):
        compiled = _compiled(v5e, jitted, args, statics)
        text = compiled.as_text()
        assert text.split(",", 1)[0] == f"HloModule jit_{prog}"
        assert _mosaic_names(text) == want, (prog, statics)
        assert _gib(compiled) < HBM_GIB, (prog, _gib(compiled))
        print(f"[aot] {name} {prog} {statics}: {_gib(compiled):.2f} GiB")


# ---------------------------------------------------------------------------
# Window and global layers over softmax-routed experts (ISSUE 32), at
# published widths
# ---------------------------------------------------------------------------


# cell -> (configuration file, its ladder, [full, window] blocks, KV heads,
# the grouped GEMMs' row tile in a chunk)
_CELLS_SWA_MOE = {
    "mellum2": ("mellum2-12b-a2.5b-l8.json",
                [2048, 4096, 8192, 16384, 20480], [5120, 641], 4, 256),
    # ISSUE 44: 72 / 48 query heads over 8 KV heads (q blocks of 9 and 6
    # rows), 32 of 256 experts held at top-10, a dense lead layer
    "laguna": ("laguna-s-2.1-ep8-l9.json",
               [2048, 4096, 8192, 10240], [2560, 385], 8, 128),
}


def _swa_moe_programs(cell="mellum2"):
    """Every program of ``mellum2_l8_mixedctx_sat`` (or, ``cell="laguna"``,
    of ``lagS_ep8_l9_agentmix_sat``) at the file's widths and engine
    sizes, over BOTH cache groups (the full group's blocks as the file
    states them, the window group's as the engine derives them, one table
    a group) -> ``[(name, jitted, args, statics, the Mosaic calls it must
    hold)]``: the paged call carries its layer kind's name, every expert
    layer one gate-up + one down grouped GEMM; a prefill chunk flash
    attention a layer under its ``annotate`` label (an int, its
    ``moe_combine`` calls: counted by the test); the page fill none."""
    import json
    import os

    from benchmarks import builders_swa_moe
    from triton_dist_tpu.models import mla_moe as M
    from triton_dist_tpu.models import swa_moe as S
    from triton_dist_tpu.runtime.jit_cache import named

    file, rungs, want_blocks, hkv, chunk_tile = _CELLS_SWA_MOE[cell]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks/configs", file)) as f:
        config = json.load(f)
    cfg = builders_swa_moe.model_config(config)
    eng = config["engine"]
    batch, page, max_seq = eng["max_batch"], eng["page_size"], eng["max_seq"]
    s = jax.ShapeDtypeStruct
    params = jax.eval_shape(functools.partial(S.init_params, cfg),
                            jax.random.key(0))
    gen = S.SwaMoeGenerator(cfg, max_seq=max_seq)
    ladder = E.build_bucket_ladder(max(page, eng["prefill_chunk"]), max_seq,
                                   page)
    assert ladder == rungs
    assert gen.kernel_gaps(page_size=page, ladder=ladder,
                           prefill_chunk=eng["prefill_chunk"]) == {}
    assert gen.kv_planes == [(hkv, 128)] * 2
    # the window group's count as the engine derives it
    ahead = eng["horizon"] * eng["pipeline"]
    blocks = [eng["num_blocks"],
              1 + batch * ((cfg.sliding_window + ahead - 2) // page + 2)]
    assert blocks == want_blocks
    kinds = cfg.kinds
    pools = [tuple(s((blocks[k.group], h, page, d), cfg.dtype)
                   for h, d in gen.kv_planes) for k in kinds]
    d_args, h_args = _decode_args(cfg, page, batch=batch, max_seq=max_seq,
                                  params=params, pools=pools)
    tables = s((2, batch, max_seq // page), I32)
    d_args = d_args[:2] + (tables,) + d_args[3:]
    h_args = h_args[:2] + (tables,) + h_args[3:]
    kw = dict(cfg=cfg, page=page, **gen.serve_hooks())
    n_moe = cfg.n_layers - cfg.first_k_dense
    want = {"gqa_paged_window": cfg.layer_types.count("window"),
            "gqa_paged_full": cfg.layer_types.count("full"),
            M.GATE_UP_CALL: n_moe, M.DOWN_CALL: n_moe}
    programs = [p + (want,) for p in _decode_programs(
        gen, kw, d_args, h_args, eng["horizon"])]
    # a prefill chunk: flash attention a layer (the window as a block
    # skip), the grouped GEMMs at the chunk's own row tile
    assert cfg.row_tile(eng["prefill_chunk"]) == chunk_tile and \
        cfg.row_tile(batch) == 32
    fill = jax.jit(named(PR._fill_pool_pages, "fill_pages", page=page,
                         kinds=kinds), donate_argnums=(0,))
    for extent in ladder:
        sc = tuple(s((1, h, extent, d), cfg.dtype) for h, d in gen.kv_planes)
        programs.append((
            "prefill_chunk", gen._chunk_jit,
            (params, s((1, eng["prefill_chunk"]), I32),
             [sc] * cfg.n_layers, s((), I32)),
            dict(quantized=False, extent=extent, n_valid=s((), I32)),
            n_moe * (M.combine_form(eng["prefill_chunk"], cfg) == "walk")))
        programs.append(("fill_pages", fill,
                         (pools, [sc] * cfg.n_layers,
                          s((2, extent // page), I32)), {}, {}))
    return programs


@pytest.mark.parametrize("cell,moe,layers", [("mellum2", 8, 8),
                                             ("laguna", 8, 9)])
def test_swa_moe_programs_compile_at_published_widths(v5e, as_tpu, cell, moe,
                                                      layers):
    """Every program of ``mellum2_l8_mixedctx_sat`` and of
    ``lagS_ep8_l9_agentmix_sat`` — single-step decode, the fused horizon
    (greedy and mixed) and its one-step link, prefill chunks at every rung
    its prompts reach and the cap, the page fill — at the file's widths
    and engine sizes, over BOTH cache groups (:func:`_swa_moe_programs`):
    the paged call carries its layer kind's name (6 ``gqa_paged_window`` +
    2 or 3 ``gqa_paged_full`` a decode step: the readers' patterns — at
    laguna's 72 / 48 query heads over 8 KV heads the call lowers AS IT IS,
    a q block of 9 or 6 rows, no padded form), every expert layer one
    gate-up + one down grouped GEMM, a chunk one flash call a layer, and
    each program fits the chip beside nothing else.  The dense family's
    call keeps no name (the test above)."""
    from triton_dist_tpu.models import mla_moe as M

    worst = {}
    for prog, jitted, args, statics, want in _swa_moe_programs(cell):
        compiled = _compiled(v5e, jitted, args, statics)
        text = compiled.as_text()
        assert text.split(",", 1)[0] == f"HloModule jit_{prog}"
        calls = _mosaic_names(text)
        if isinstance(want, dict):
            assert calls == want, (prog, statics, calls)
        else:
            # ``want``: the chunk's ``moe_combine`` calls — one an expert
            # layer where it holds 32 of 256 (laguna), none where it holds
            # all 64 (mellum2: the walk never reads fewer)
            assert want == {"mellum2": 0, "laguna": moe}[cell]
            assert calls[M.GATE_UP_CALL] == calls[M.DOWN_CALL] == moe
            assert calls.get(M.COMBINE_CALL, 0) == want
            assert sum(calls.values()) == 2 * moe + want + layers, calls
        assert _gib(compiled) < HBM_GIB, (prog, statics, _gib(compiled))
        worst[prog] = max(worst.get(prog, 0), _gib(compiled))
    print("GiB a program:", {k: round(v, 2) for k, v in worst.items()})


# ---------------------------------------------------------------------------
# A state beside pages: the decoder-hybrid-decoder block whole on one chip
# (ISSUE 38), at published widths
# ---------------------------------------------------------------------------


def _group_planes(cfg, gen, blocks, page, lead=None, extent=None):
    """Per layer what the engine builds from ``gen.kv_groups``: K and V
    pages of the layer's group (or, with ``extent``, a request's scratch
    rows), a state group's slot planes (``lead`` slots: the pool's
    ``blocks`` unless given), or nothing."""
    s = jax.ShapeDtypeStruct
    out = [()] * cfg.n_layers
    for g, nb in zip(gen.kv_groups, blocks):
        for li in g["layers"]:
            if "state_planes" in g:
                out[li] = tuple(s((lead or nb, *sh), dt)
                                for sh, dt in g["state_planes"])
            elif extent:
                out[li] = tuple(s((1, h, extent, d), cfg.dtype)
                                for h, d in gen.kv_planes)
            else:
                out[li] = tuple(s((nb, h, page, d), cfg.dtype)
                                for h, d in gen.kv_planes)
    return out


def _ssm_yoco_programs():
    """Every program of ``phi4mf_reason96_sat`` at the file's widths and
    engine sizes over its THREE cache groups (the full group's 2,560
    blocks on layer 17 alone, the window group's derived 577 on 8 layers,
    the state group's 97 slots on 9; cross and gated-memory layers own no
    pool) -> ``[(name, jitted, args, statics, the Mosaic calls it must
    hold)]``: a decode step 8 ``gqa_paged_window`` + 1 ``gqa_paged_full``
    + 7 ``gqa_paged_cross``; a prefill chunk 9 ``ssm_scan`` and flash
    attention on 16 layers (``None``: counted by the test)."""
    import json
    import os

    from benchmarks import builders_ssm_yoco
    from triton_dist_tpu.models import ssm_yoco as Y
    from triton_dist_tpu.runtime.jit_cache import named

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "benchmarks/configs/phi-4-mini-flash-reasoning.json")) as f:
        config = json.load(f)
    cfg = builders_ssm_yoco.model_config(config)
    eng = config["engine"]
    batch, page, max_seq = eng["max_batch"], eng["page_size"], eng["max_seq"]
    chunk = eng["prefill_chunk"]
    s = jax.ShapeDtypeStruct
    params = jax.eval_shape(functools.partial(Y.init_params, cfg),
                            jax.random.key(0))
    gen = Y.SsmYocoGenerator(cfg, max_seq=max_seq)
    ladder = E.build_bucket_ladder(max(page, chunk), max_seq, page)
    assert ladder == [512, 1024, 2048, 4096, 5120]
    assert gen.kernel_gaps(page_size=page, ladder=ladder,
                           prefill_chunk=chunk) == {}
    assert gen.kv_planes == [(10, 128)] * 2
    ahead = eng["horizon"] * eng["pipeline"]
    blocks = [eng["num_blocks"],
              1 + batch * ((cfg.sliding_window + ahead - 2) // page + 2),
              1 + batch]
    assert blocks == [2560, 577, 97]
    groups = gen.kv_groups
    assert [len(g["layers"]) for g in groups] == [1, 8, 9]

    planes = functools.partial(_group_planes, cfg, gen, blocks, page)
    pools = planes(None)
    d_args, h_args = _decode_args(cfg, page, batch=batch, max_seq=max_seq,
                                  params=params, pools=pools)
    tables = s((3, batch, max_seq // page), I32)
    d_args = d_args[:2] + (tables,) + d_args[3:]
    h_args = h_args[:2] + (tables,) + h_args[3:]
    kw = dict(cfg=cfg, page=page, **gen.serve_hooks())
    want = {"gqa_paged_window": 8, "gqa_paged_full": 1, "gqa_paged_cross": 7}
    programs = [p + (want,) for p in _decode_programs(
        gen, kw, d_args, h_args, eng["horizon"])]
    fill = jax.jit(named(PR._fill_pool_pages, "fill_pages", page=page,
                         kinds=cfg.kinds), donate_argnums=(0,))
    for extent in ladder:
        sc = planes(1, extent)
        programs.append((
            "prefill_chunk", gen._chunk_jit,
            (params, s((1, chunk), I32), sc, s((), I32)),
            dict(quantized=False, extent=extent, n_valid=s((), I32)), None))
        programs.append(("fill_pages", fill,
                         (pools, sc, s((3, extent // page), I32)), {}, {}))
    return programs


def test_ssm_yoco_programs_compile_at_published_widths(v5e, as_tpu):
    """Every program of ``phi4mf_reason96_sat`` — single-step decode, the
    fused horizon (greedy and mixed) and its one-step link, prefill chunks
    on every rung, the page-and-state fill — compiled for the v5e at the
    published widths (32 layers, 200,064 rows, 96 rows' pools beside 7.7
    GB of weights): the paged calls carry their layer kind's names (the
    readers' patterns), a chunk holds 9 ``ssm_scan`` calls and reaches a
    kernel in every attention layer, and each program fits the chip beside
    nothing else."""
    worst = {}
    for prog, jitted, args, statics, want in _ssm_yoco_programs():
        compiled = _compiled(v5e, jitted, args, statics)
        text = compiled.as_text()
        assert text.split(",", 1)[0] == f"HloModule jit_{prog}"
        calls = _mosaic_names(text)
        if want is not None:
            assert calls == want, (prog, statics, calls)
        else:
            assert calls["ssm_scan"] == 9, calls
            # flash attention over all rows on the 8 window layers and the
            # full layer; past it ONE row is kept (ISSUE 42) and the 7
            # cross layers' query reads layer 17's scratch through the
            # multi-token decode kernel
            flash = sum(n for name, n in calls.items()
                        if name.startswith("flash_attention"))
            assert flash == 9 and sum(calls.values()) == 9 + 9 + 7, calls
        assert _gib(compiled) < HBM_GIB, (prog, statics, _gib(compiled))
        worst[prog] = max(worst.get(prog, 0), _gib(compiled))
        if prog == "decode_horizon":
            # the cell runs at 93-95% of the chip: the sampler's two ways
            # to a row's cut-offs (ISSUE 39) must not add up — the
            # candidates are 96 x 16,384 float32 = 6 MiB, and no more may
            # come on top of what the program planned before them (PR 38's
            # tree, this test's own reading)
            before = {(8, True): 12.1844, (8, False): 12.1846,
                      (1, False): 12.1866}[statics["H"],
                                           statics["all_greedy"]]
            assert _gib(compiled) <= before + 8 / 1024, (
                statics, _gib(compiled), before)
            assert text.count(" conditional(") == (
                0 if statics["all_greedy"] else 1)
    print("GiB a program:", {k: round(v, 2) for k, v in worst.items()})


def _gdn_hybrid_programs():
    """Every program of ``olmoh_l8_reason96_sat`` at the file's widths and
    engine sizes over its TWO cache groups (the full group's blocks on
    layers 3 and 7, the state group's 97 slots on the six linear layers)
    -> ``[(name, jitted, args, statics, the Mosaic calls it must hold)]``:
    a decode step 2 ``gqa_paged_full`` + 6 ``gdn_step``; a prefill chunk 6
    ``gdn_chunk`` and flash attention on 2 layers (``None``: counted by
    the test)."""
    import json
    import os

    from benchmarks import builders_gdn_hybrid
    from triton_dist_tpu.models import gdn_hybrid as GH
    from triton_dist_tpu.runtime.jit_cache import named

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "benchmarks/configs/olmo-hybrid-7b-l8.json")) as f:
        config = json.load(f)
    cfg = builders_gdn_hybrid.model_config(config)
    eng = config["engine"]
    batch, page, max_seq = eng["max_batch"], eng["page_size"], eng["max_seq"]
    chunk = eng["prefill_chunk"]
    s = jax.ShapeDtypeStruct
    params = jax.eval_shape(functools.partial(GH.init_params, cfg),
                            jax.random.key(0))
    gen = GH.GdnHybridGenerator(cfg, max_seq=max_seq)
    ladder = E.build_bucket_ladder(max(page, chunk), max_seq, page)
    assert ladder == [512, 1024, 2048, 4096, 5120]
    assert gen.kernel_gaps(page_size=page, ladder=ladder,
                           prefill_chunk=chunk) == {}
    assert gen.kv_planes == [(30, 128)] * 2
    blocks = [eng["num_blocks"], 1 + batch]
    groups = gen.kv_groups
    assert [g["layers"] for g in groups] == [(3, 7), (0, 1, 2, 4, 5, 6)]
    assert [sh for sh, _ in groups[1]["state_planes"]] == [(270, 128),
                                                           (96, 5760)]

    planes = functools.partial(_group_planes, cfg, gen, blocks, page)
    pools = planes(None)
    d_args, h_args = _decode_args(cfg, page, batch=batch, max_seq=max_seq,
                                  params=params, pools=pools)
    tables = s((2, batch, max_seq // page), I32)
    d_args = d_args[:2] + (tables,) + d_args[3:]
    h_args = h_args[:2] + (tables,) + h_args[3:]
    kw = dict(cfg=cfg, page=page, **gen.serve_hooks())
    want = {"gqa_paged_full": 2, "gdn_step": 6}
    programs = [p + (want,) for p in _decode_programs(
        gen, kw, d_args, h_args, eng["horizon"])]
    fill = jax.jit(named(PR._fill_pool_pages, "fill_pages", page=page,
                         kinds=cfg.kinds), donate_argnums=(0,))
    for extent in ladder:
        sc = planes(1, extent)
        programs.append((
            "prefill_chunk", gen._chunk_jit,
            (params, s((1, chunk), I32), sc, s((), I32)),
            dict(quantized=False, extent=extent, n_valid=s((), I32)), None))
        programs.append(("fill_pages", fill,
                         (pools, sc, s((2, extent // page), I32)), {}, {}))
    return programs


def test_gdn_hybrid_programs_compile_at_published_widths(v5e, as_tpu):
    """Every program of ``olmoh_l8_reason96_sat`` — single-step decode, the
    fused horizon (greedy and mixed) and its one-step link, prefill chunks
    on every rung, the page-and-state fill — compiled for the v5e at the
    published widths (8 layers, 100,352 rows, 96 rows' 13.7 MB states and
    196,608 cached tokens of 30 KV heads beside 4.9 GB of weights): the
    paged call carries its layer kind's name and the delta rule's two calls
    their own (the readers' patterns), the state pool is stepped in place
    (no program holds a second copy of it), and each program fits the
    chip beside nothing else."""
    worst = {}
    state_pool = 97 * 96 * 5760 * 4
    for prog, jitted, args, statics, want in _gdn_hybrid_programs():
        compiled = _compiled(v5e, jitted, args, statics)
        text = compiled.as_text()
        assert text.split(",", 1)[0] == f"HloModule jit_{prog}"
        calls = _mosaic_names(text)
        if want is not None:
            assert calls == want, (prog, statics, calls)
        else:
            assert calls["gdn_chunk"] == 6, calls
            assert sum(calls.values()) == 6 + 2, calls      # + flash calls
        assert _gib(compiled) < HBM_GIB, (prog, statics, _gib(compiled))
        worst[prog] = max(worst.get(prog, 0), _gib(compiled))
        if prog in ("paged_decode", "decode_horizon"):
            # the six state pools are 1.29 GB: a step that gathered,
            # updated and scattered them would hold copies as temporaries
            ma = compiled.memory_analysis()
            assert ma.temp_size_in_bytes < state_pool, (
                prog, statics, ma.temp_size_in_bytes)
    print("GiB a program:", {k: round(v, 2) for k, v in worst.items()})


# ---------------------------------------------------------------------------
# The region scopes rename no Mosaic call (ISSUE 36, invariant b)
# ---------------------------------------------------------------------------


def _dense_cell_programs():
    """The two Mistral cells' programs at their geometry (32 rows, table
    width 64, 449 blocks, a 256-row prefill call on every rung of its
    ladder) at llama3-8B's head shapes, which are Mistral-7B's, cut to
    ``LAYERS`` layers: a name does not depend on the depth."""
    batch, max_seq, num_blocks = _GEOMETRIES[1]
    cfg = dataclasses.replace(_cfg(), max_seq=max_seq)
    gen = _dense_gen(cfg)
    kw = dict(cfg=cfg, page=128, **gen.serve_hooks())
    d_args, h_args = _decode_args(cfg, 128, num_blocks=num_blocks,
                                  batch=batch, max_seq=max_seq)
    programs = [p + (None,) for p in _decode_programs(gen, kw, d_args,
                                                      h_args, 8)]
    width = E.prefill_width(128, 4 * 128)
    for extent in E.build_bucket_ladder(width, max_seq, 128):
        args, n_valid = _chunk_args(cfg, width, extent)
        programs.append(("prefill_chunk", gen._chunk_jit, args,
                         dict(quantized=False, extent=extent,
                              n_valid=n_valid), None))
    return programs


@pytest.mark.parametrize("cell", ["mistral", "gigachat3.1", "glm-5",
                                  "mellum2", "xing4.0"])
def test_region_scopes_rename_no_mosaic_call(v5e, as_tpu, monkeypatch, cell):
    """XLA names a Mosaic custom call after the scope around it, and the
    accepted roofline metrics match ``closed_call|_unknown_``,
    ``mla_paged_decode``, ``moe_gate_up``, ``moe_down``,
    ``dsa_index_scores``, ``gqa_paged_window``, ``gqa_paged_full`` by
    NAME (``benchmarks/run.py`` ends a traced run whose declared metric
    finds nothing).  So for every program of the five cells' engines that
    runs the layer loop, compiled for the v5e: the multiset of Mosaic
    instruction names with ``profiling.region`` as written equals the one
    with it patched out — the parent's.  A call with a name of its own
    keeps it under a scope; the dense family's paged call, which has
    none, runs outside every region (``generate.paged_attend``)."""
    import contextlib

    from triton_dist_tpu.models import generate as G
    from triton_dist_tpu.models import mla_moe as M

    build = {"mistral": _dense_cell_programs,
             "gigachat3.1": lambda: _mla_moe_programs(*_CELLS_MLA_MOE[0]),
             "glm-5": lambda: _mla_moe_programs(*_CELLS_MLA_MOE[1]),
             # ``hc_pre`` / ``hc_post`` keep their names under ``hc.pre``
             # / ``hc.post``: the cell's four metrics read them by name
             "xing4.0": lambda: _mla_moe_programs(*_CELLS_MLA_MOE[2]),
             "mellum2": _swa_moe_programs}[cell]

    def names():
        """[(program and statics, its Mosaic calls, whether a scope
        reached its module)] over the cell's layer-loop programs."""
        out = []
        for prog, jitted, args, statics, _ in build():
            if prog == "fill_pages":
                continue
            text = _compiled(v5e, jitted, args, statics).as_text()
            key = (prog,) + tuple(statics.get(k) for k in (
                "H", "all_greedy", "extent"))
            out.append((key, _mosaic_names(text), "/rg_ffn/" in text))
        return out

    written = names()
    assert all(scoped for _, _, scoped in written)
    for mod in (G, M, PR):
        monkeypatch.setattr(mod, "region",
                            lambda name: contextlib.nullcontext())
    bare = names()                      # fresh generators, fresh jits
    assert not any(scoped for _, _, scoped in bare)
    assert [w[:2] for w in written] == [b[:2] for b in bare]
    assert all(sum(calls.values()) for _, calls, _ in written)
    if cell == "mistral":
        assert [set(c) for _, c, _ in written[:4]] == [
            {"_unknown_"}, {"closed_call"}, {"closed_call"}, {"closed_call"}]
        assert not any(k.startswith("rg_") for _, c, _ in written for k in c)


# ---------------------------------------------------------------------------
# The sampler sorts nothing (ISSUE 29)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind,num_blocks,rows,vocab", [
    ("bf16", 449, 32, 32000), ("latent", 1216, 64, 16032)])
def test_the_sampler_sorts_nothing(v5e, as_tpu, kind, num_blocks, rows,
                                   vocab):
    """The sampled ``decode_horizon[8]`` at both cells' geometries (32
    rows x 32,000, dense; 64 x 16,032, latent) and ``sample_token`` at
    both vocabularies hold no ``sort`` and no ``TopK`` call: on the v5e
    either costs 0.8-0.9 ms a step at these shapes, and both cut-offs
    come from 32 compare-and-reduce passes each (``models/sampling.py``:
    two ``while`` loops over the logits beside the horizon's scan)."""
    import re

    def sorts(text):
        return (len(re.findall(r" sort\(", text))
                + text.count('custom_call_target="TopK"'))

    compiled, _ = _write_program(kind, "decode_horizon-sampled", v5e,
                                 num_blocks)
    text = compiled.as_text()
    assert f"f32[{rows},{vocab}]" in text and sorts(text) == 0
    greedy, _ = _write_program(kind, "decode_horizon-greedy", v5e,
                               num_blocks)
    # the sampler is the two bisections: the greedy horizon has neither
    assert text.count(" while(") == greedy.as_text().count(" while(") + 2

    s = functools.partial(jax.ShapeDtypeStruct,
                          sharding=SingleDeviceSharding(v5e.devices[0]))
    key = jax.eval_shape(lambda: jax.random.key(0))
    sample = jax.jit(PR._sample_token).lower(
        s((vocab,), jnp.float32), s(key.shape, key.dtype), s((), I32),
        s((), jnp.float32), s((), I32), s((), jnp.float32)).compile()
    assert sorts(sample.as_text()) == 0
