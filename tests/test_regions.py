"""Regions of a device program (``runtime/profiling.py`` ``region``): the
scopes that say, operation by operation, which seam of the one layer loop
a device trace's time belongs to (``benchmarks/regions.py`` reads them).

- the closed set: every ``region("...")`` literal under ``triton_dist_tpu/``
  is a member of ``profiling.REGIONS`` and every member has a use (the
  twin of ``test_serve_trace``'s ``STEP_PHASES`` meta-test);
- INVARIANT (a), the computation is the parent's: for each of the four
  model families, the engine's ``decode_horizon``, ``prefill_chunk`` and
  ``paged_decode`` lower to the same StableHLO text (no debug info) with
  ``region`` as written and with it patched to ``contextlib.nullcontext``;
- INVARIANT (c), a config that says no ``hc_mult`` traces nothing new: the
  seven one-stream families hand the layer loop no ``streams`` and none of
  their programs holds an operation under ``hc.pre`` / ``hc.post``;
- every seam's product carries its region in the lowered module's
  ``op_name`` path, the INNERMOST where two nest — and the dense family's
  paged programs hold no ``attn`` scope at all: their Mosaic call has no
  name of its own and must keep the one the benchmark reads it by.

Nothing runs here beyond building toy engines: the programs are lowered,
not compiled (the slow tier's ``test_chip_aot`` compiles the cells' for the
v5e and holds invariant (b), the Mosaic calls' names).
"""

import contextlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from triton_dist_tpu.models import gdn_hybrid as GH
from triton_dist_tpu.models import generate as G
from triton_dist_tpu.models import llama
from triton_dist_tpu.models import mla_moe as M
from triton_dist_tpu.models import ssm_yoco as Y
from triton_dist_tpu.models import swa_moe as S
from triton_dist_tpu.models.llama import _rms_norm
from triton_dist_tpu.runtime import profiling
from triton_dist_tpu.serve import ServeEngine
from triton_dist_tpu.serve import programs as PR

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILIES = ("dense", "latent", "sparse", "window", "state", "matrix",
            "gated", "streams")
# the four families the benchmark had before the layer loop took a ``mixer``
QUARTET_FAMILIES = FAMILIES[:4]
# the seven whose residual is ONE stream: every family but the last
ONE_STREAM_FAMILIES = FAMILIES[:7]
PROGRAMS = ("decode_horizon", "prefill_chunk", "paged_decode")
# the modules that open regions (they import ``region`` by name)
SCOPED = (G, M, PR, Y, GH, S)
I32 = jnp.int32
B, H, CHUNK = 2, 4, 64


def test_region_takes_its_closed_set_only():
    with pytest.raises(ValueError, match="region 'mlp'"):
        profiling.region("mlp")
    for name in profiling.REGIONS:
        with profiling.region(name):
            pass
    # one HLO-legal word a region, and none that a cut at the first "."
    # (``benchmarks/xplane.py`` ``op_name``) would shorten
    assert all(re.fullmatch(r"[a-z]+(_[a-z]+)?(\.[a-z]+)?", n)
               for n in profiling.REGIONS)
    assert len(set(profiling.REGIONS)) == len(profiling.REGIONS)


def test_every_region_literal_is_a_member_and_every_member_has_a_use():
    literals = set()
    for root, _, files in os.walk(os.path.join(REPO, "triton_dist_tpu")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(root, f)) as fh:
                    literals |= set(re.findall(
                        r'\bregion\(\s*"([^"]+)"', fh.read()))
    assert literals == set(profiling.REGIONS)


# ---------------------------------------------------------------------------
# A toy engine of each family, and its programs' abstract arguments
# ---------------------------------------------------------------------------


def _build(family):
    if family == "dense":
        cfg = llama.LlamaConfig(vocab=64, dim=16, n_layers=2, n_heads=2,
                                n_kv_heads=1, ffn_dim=32, max_seq=256,
                                dtype=jnp.float32)
        gen = G.Generator(cfg, Mesh(np.array(jax.devices()[:1]), ("sp",)),
                          axis="sp", max_seq=256)
        params = llama.init_params(cfg, jax.random.key(3))
    elif family in ("latent", "sparse", "streams"):
        # "streams": the latent block inside a residual of four streams
        cfg = (M.MlaMoeConfig.tiny_sparse(n_layers=2) if family == "sparse"
               else M.MlaMoeConfig.tiny(n_layers=2,
                                        hc_mult=4 * (family == "streams")))
        gen = M.MlaMoeGenerator(cfg, max_seq=256, interpret=True)
        params = M.init_params(cfg, jax.random.key(3))
    elif family == "state":
        cfg = Y.SsmYocoConfig.tiny()
        gen = Y.SsmYocoGenerator(cfg, max_seq=256)
        params = Y.init_params(cfg, jax.random.key(3))
    elif family == "matrix":
        cfg = GH.GdnHybridConfig.tiny()
        gen = GH.GdnHybridGenerator(cfg, max_seq=256)
        params = GH.init_params(cfg, jax.random.key(3))
    else:
        # "gated": the laguna block (heads by layer, a gate through the
        # family's mixer, a dense lead layer, a shared expert)
        cfg = (S.SwaMoeConfig.tiny_laguna() if family == "gated"
               else S.SwaMoeConfig.tiny(n_layers=4))
        gen = S.SwaMoeGenerator(cfg, max_seq=256)
        params = S.init_params(cfg, jax.random.key(3))
    # the sparse block expands a chunk of 256 queries or more
    chunk = M.PREFILL_EXPAND_MIN if family == "sparse" else CHUNK
    eng = ServeEngine(gen, params, num_blocks=40, page_size=16, max_batch=B,
                      prefill_chunk=chunk, horizon=H, prefix_cache=False,
                      trace_level=0)
    return eng, chunk


def _lowered(eng, chunk, program, all_rows=False, extent=256):
    """The engine's own jitted ``program`` lowered on abstract arguments
    of the engine's own shapes (``all_rows``: the chunk program as the
    callers that consume every position call it; ``extent``: its
    scratch's rows)."""
    s = jax.ShapeDtypeStruct
    shapes = lambda tree: jax.tree.map(     # noqa: E731
        lambda x: s(x.shape, x.dtype), tree)
    params, pools = shapes(eng.params), shapes(eng._pools)
    if program == "prefill_chunk":
        # K and V rows a layer — or a state-space layer's slot of state,
        # or nothing (``_plane_specs``: what the engine's own scratch is)
        scratch = [tuple(s((1, p[0], extent, p[1]), eng.gen.cfg.dtype)
                         if isinstance(p[0], int) else s((1, *p[0]), p[1])
                         for p in planes) for planes in eng._plane_specs]
        # the engine says how many rows are valid (and gets ONE row's
        # logits); speculative verify and ``prefill_chunked`` do not
        valid = {} if all_rows else {"n_valid": s((), I32)}
        return eng._chunk_fn.fn.lower(
            params, s((1, chunk), I32), scratch, s((), I32),
            quantized=False, extent=extent, **valid)
    groups = len(eng.kv_groups) if eng.kv_groups else 0
    tables = s(((groups,) if groups else ()) + (B, eng.n_pages_max), I32)
    vec = lambda dt: s((B,), dt)    # noqa: E731
    decode = (params, pools, tables, vec(I32), vec(I32), vec(bool))
    if program == "paged_decode":
        return eng._decode_fn.fn.lower(*decode)
    keys = jax.eval_shape(lambda: jnp.stack([jax.random.key(0)] * B))
    return eng._horizon_fn.fn.lower(
        *decode, vec(bool), vec(I32), vec(I32), keys, vec(jnp.float32),
        vec(I32), vec(jnp.float32), vec(bool), vec(I32), H=H,
        all_greedy=False)


@pytest.fixture(scope="module")
def engines():
    """family -> (engine, chunk), built once with ``region`` as written."""
    cache = {}

    def get(family):
        if family not in cache:
            cache[family] = _build(family)
        return cache[family]

    return get


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("family", FAMILIES)
def test_the_computation_is_the_same_without_the_scopes(
        engines, monkeypatch, family, program):
    """Invariant (a): a scope is metadata."""
    eng, chunk = engines(family)
    written = _lowered(eng, chunk, program).as_text()
    assert "/rg_" not in written          # no debug info: no scope shows
    for mod in SCOPED:
        monkeypatch.setattr(mod, "region",
                            lambda name: contextlib.nullcontext())
    bare, _ = _build(family)              # fresh jits: nothing cached
    patched = _lowered(bare, chunk, program)
    assert "/rg_" not in patched.as_text(debug_info=True)
    assert patched.as_text() == written


# ---------------------------------------------------------------------------
# No ``mixer``, no ``shared``: the layer loop is the quartet's, as it was
# ---------------------------------------------------------------------------


def _quartet_layer_stack(params, tokens, pos, caches, *, cfg, project,
                         out_proj, ffn, write_kv, attend, kinds=None,
                         keep=None, read=None):
    """The layer loop as it stood before a family could bring a ``mixer``
    (ISSUE 38's parent, to the letter): RMSNorm and ``lm_head`` written
    in, the project / write / attend / out_proj quartet for every layer —
    and, since a prefill chunk keeps the row its caller reads (ISSUE 42),
    that row alone through the norm and the head, where it will be read:
    each of these families' last layer writes a cache."""
    region = profiling.region
    B_, T = tokens.shape
    with region("embed"):
        x = params["embed"][tokens.reshape((B_,) if T == 1 else (B_, T))]
    new_caches = []
    for li, layer in enumerate(params["layers"]):
        with region("proj"):
            h = _rms_norm(x, layer["attn_norm"], cfg.norm_eps)
            q, k, v = project(h.reshape(B_, T, -1), layer, pos,
                              **G._kind_kw(kinds, li))
        with region("kv_write"):
            cache = write_kv(li, caches[li], k, v)
        o = attend(li, q, cache)
        with region("out_proj"):
            o2 = o.reshape(B_ * T, -1).astype(cfg.dtype)
            x = x + out_proj(o2, layer).reshape(x.shape)
        with region("ffn"):
            h2 = _rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
            x = x + ffn(h2.reshape(B_ * T, -1), layer).reshape(x.shape)
        new_caches.append(cache)

    def head(x):
        with region("head"):
            x = _rms_norm(x, params["final_norm"], cfg.norm_eps)
            logits = jnp.dot(x, params["lm_head"],
                             preferred_element_type=jnp.float32)
        return logits.reshape(B_, -1, logits.shape[-1])

    if keep is None or T == 1:
        return new_caches, head(x)

    def tail():
        return head(jax.lax.dynamic_index_in_dim(x, keep, 1, False))

    return new_caches, jax.lax.cond(
        read, tail, lambda: jnp.zeros_like(jax.eval_shape(tail)))


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("family", QUARTET_FAMILIES)
def test_without_a_mixer_the_programs_are_the_quartets(
        engines, monkeypatch, family, program):
    """The four families the benchmark already had pass no ``mixer`` and
    no ``shared``, and their configs name no norm and no tied head: their
    engines' programs lower to the same StableHLO text over today's layer
    loop and over the quartet-only loop it replaced."""
    eng, chunk = engines(family)
    written = _lowered(eng, chunk, program).as_text()
    for mod in (G, PR):
        monkeypatch.setattr(mod, "_layer_stack", _quartet_layer_stack)
    bare, _ = _build(family)              # fresh jits: nothing cached
    assert _lowered(bare, chunk, program).as_text() == written


# ---------------------------------------------------------------------------
# No gate, one head count, one theta: the window family's programs are the
# parent's
# ---------------------------------------------------------------------------


def _parent_swa_project(h, layer, pos, *, cfg, kind):
    """``swa_moe.project`` as it stood before a layer could have its own
    head count, theta and rotary width (ISSUE 44's parent, to the letter,
    with the ``rope`` it called)."""
    import functools

    B_, T, _ = h.shape
    h2 = h.reshape(B_ * T, cfg.dim)
    q = (h2 @ layer["wq"]).reshape(B_, T, cfg.n_heads, cfg.head_dim)
    k = (h2 @ layer["wk"]).reshape(B_, T, cfg.n_kv_heads, cfg.head_dim)
    v = (h2 @ layer["wv"]).reshape(B_, T, cfg.n_kv_heads, cfg.head_dim)
    yarn = cfg.yarn if kind.attn == "full" else None
    inv_freq = M.rope_inv_freq(cfg.head_dim, cfg.rope_theta, yarn)
    scale = 1.0 if yarn is None else float(yarn[4])
    rope = functools.partial(M._rope, pos=pos, inv_freq=inv_freq,
                             scale=scale)
    return (rope(_rms_norm(q, layer["q_norm"], cfg.norm_eps)),
            rope(_rms_norm(k, layer["k_norm"], cfg.norm_eps)), v)


@pytest.mark.parametrize("program", PROGRAMS)
def test_an_ungated_block_lowers_as_the_parents(engines, monkeypatch,
                                                program):
    """The ``mellum`` block names no gate, no heads by layer, no second
    theta and no rotary share (each default of the config): its toy
    engine's programs lower to the same StableHLO text over today's
    ``project`` and over the one it replaced, it hands the layer loop no
    ``mixer``, and its seeded weights are the parent's draws."""
    eng, chunk = engines("window")
    assert "mixer" not in eng.gen.serve_hooks()
    assert "wg" not in eng.params["layers"][0]
    written = _lowered(eng, chunk, program).as_text()
    monkeypatch.setattr(S, "project", _parent_swa_project)
    bare, _ = _build("window")            # fresh jits: nothing cached
    assert _lowered(bare, chunk, program).as_text() == written
    for a, b in zip(jax.tree.leaves(eng.params),
                    jax.tree.leaves(bare.params)):
        assert np.array_equal(a, b)


# ---------------------------------------------------------------------------
# No row to keep: every program but the engine's chunk is the parent's
# ---------------------------------------------------------------------------


def _parent_layer_stack(params, tokens, pos, caches, *, cfg, project,
                        out_proj, ffn, write_kv, attend, kinds=None,
                        mixer=None, shared=None, keep=None, read=None):
    """The layer loop as it stood before it could keep a row (ISSUE 42's
    parent, to the letter), refusing to be told of one."""
    assert keep is None and read is None
    region, _norm, _head = profiling.region, G._norm, G._head
    B, T = tokens.shape
    post = getattr(cfg, "norm_after", False)
    with region("embed"):
        x = params["embed"][tokens.reshape((B,) if T == 1 else (B, T))]
    new_caches = []
    for li, layer in enumerate(params["layers"]):
        if mixer is not None:
            h = x if post else _norm(x, layer, "attn_norm", cfg)
            rows, cache, shared = mixer(
                li, h.reshape(B, T, -1), layer, pos, caches[li], shared,
                write_kv=write_kv, attend=attend)
            x = x + rows.reshape(x.shape)
        else:
            with region("proj"):
                h = _norm(x, layer, "attn_norm", cfg)
                q, k, v = project(h.reshape(B, T, -1), layer, pos,
                                  **G._kind_kw(kinds, li))
            with region("kv_write"):
                cache = write_kv(li, caches[li], k, v)
            o = attend(li, q, cache)                     # [B, T, Hq, .]
            with region("out_proj"):
                o2 = o.reshape(B * T, -1).astype(cfg.dtype)
                x = x + out_proj(o2, layer).reshape(x.shape)
        with region("ffn"):
            if post:
                y = _norm(ffn(x.reshape(B * T, -1), layer), layer,
                          "mlp_norm", cfg)
            else:
                h2 = _norm(x, layer, "mlp_norm", cfg)
                y = ffn(h2.reshape(B * T, -1), layer)
            x = x + y.reshape(x.shape)
        new_caches.append(cache)
    with region("head"):
        logits = _head(_norm(x, params, "final_norm", cfg), params, cfg)
    return new_caches, logits.reshape(B, T, -1)


@pytest.mark.parametrize("program", ("decode_horizon", "paged_decode",
                                     "all_rows_chunk"))
@pytest.mark.parametrize("family", ONE_STREAM_FAMILIES)
def test_with_no_row_to_keep_the_programs_are_the_parents(
        engines, monkeypatch, family, program):
    """A prefill chunk keeps the row its caller reads (ISSUE 42) and no
    other program learns of it: every family's ``decode_horizon`` and
    ``paged_decode``, and the chunk program as the callers that consume
    every position call it (no ``n_valid``: speculative verify,
    ``prefill_chunked``), lower to the same StableHLO text over today's
    layer loop and over the loop it replaced."""
    eng, chunk = engines(family)
    chunked = program == "all_rows_chunk"
    program = "prefill_chunk" if chunked else program
    written = _lowered(eng, chunk, program, all_rows=chunked).as_text()
    # and the engine's own call IS another program: one row of logits
    if chunked:
        kept = _lowered(eng, chunk, program).as_text()
        vocab = eng.gen.cfg.vocab
        assert kept != written and f"1x1x{vocab}xf32" in kept
    for mod in SCOPED:
        if hasattr(mod, "_layer_stack"):
            monkeypatch.setattr(mod, "_layer_stack", _parent_layer_stack)
    bare, _ = _build(family)              # fresh jits: nothing cached
    assert _lowered(bare, chunk, program,
                    all_rows=chunked).as_text() == written


# ---------------------------------------------------------------------------
# No ``hc_mult``, no ``streams``: the layer loop is the one-stream loop
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("family", ONE_STREAM_FAMILIES)
def test_without_streams_no_program_holds_a_mix(engines, family, program):
    """The seven families whose configs say no ``hc_mult`` hand the layer
    loop no ``streams`` — its one switch between ``x + f(norm(x))`` and the
    mixes — so no operation of their programs lies under ``hc.pre`` or
    ``hc.post``."""
    eng, chunk = engines(family)
    assert "streams" not in eng.gen.serve_hooks()
    regions = {r for r, _ in _products(_lowered(eng, chunk, program))}
    assert not regions & {"hc.pre", "hc.post"}


def test_with_streams_the_residual_is_n_wide_and_the_mixes_are_calls(engines):
    """The one family that says ``hc_mult``: its programs differ from the
    one-stream block's, hold a pre-mix and a post-mix a sub-layer as Pallas
    calls, and carry a residual ``hc_mult`` times as wide."""
    eng, chunk = engines("streams")
    one, _ = engines("latent")
    cfg = eng.gen.cfg
    assert set(eng.gen.serve_hooks()) - set(one.gen.serve_hooks()) == {
        "streams"}
    for program in PROGRAMS:
        text = _lowered(eng, chunk, program).as_text()
        assert text != _lowered(one, chunk, program).as_text()
        rows = chunk if program == "prefill_chunk" else B
        assert f"tensor<{rows}x{cfg.hc_mult * cfg.dim}x" in text
        calls = {(r, p) for r, p in _products(_lowered(eng, chunk, program))
                 if p == "pallas_call"}
        assert {("hc.pre", "pallas_call"), ("hc.post", "pallas_call")} <= calls


# ---------------------------------------------------------------------------
# On the gather side of the rule the expert layer is the parent's
# ---------------------------------------------------------------------------


# (family, program) -> the form its expert layers' combine takes: every
# decode program gathers, and so does every program of a block that holds
# all of its experts (``window``) or too few rows an expert (``gated``)
_COMBINE_SIDES = {
    ("latent", "decode_horizon"): "gather",
    ("sparse", "decode_horizon"): "gather",
    ("window", "prefill_chunk"): "gather",
    ("gated", "decode_horizon"): "gather",
    ("gated", "prefill_chunk"): "gather",
    ("latent", "prefill_chunk"): "walk",
}


@pytest.mark.parametrize("family,program", _COMBINE_SIDES)
def test_on_the_gather_side_the_expert_layer_is_the_parents(
        engines, monkeypatch, family, program):
    """ISSUE 46's rule (``mla_moe.combine_form``) reads a program's static
    shapes.  Where it says ``gather`` the expert layer takes the parent's
    path and nothing else — the plan carries no assignment, the walk is
    never traced — so the program lowers to the same StableHLO text as
    with the rule held to ``gather`` (``combine_gather``, the parent's sum
    kept as the walk's oracle).  Where it says ``walk`` that text differs:
    the program's ``moe.combine`` region holds a Pallas call."""
    eng, chunk = engines(family)
    side = _COMBINE_SIDES[family, program]
    rows = chunk if program == "prefill_chunk" else B
    assert M.combine_form(rows, eng.gen.cfg) == side
    assert eng.metrics.summary()["moe"]["combine"][program] == (
        side if family in ("latent", "sparse") else "gather")
    took = set()
    plan_of, live = M.moe_utils.sort_align_held, M.combine_live

    def plan_seen(*args, assignment=False, **kw):
        took.add(assignment)
        return plan_of(*args, assignment=assignment, **kw)

    def walk_seen(*args, **kw):
        took.add("walk")
        return live(*args, **kw)

    monkeypatch.setattr(M.moe_utils, "sort_align_held", plan_seen)
    monkeypatch.setattr(M, "combine_live", walk_seen)
    ours, _ = _build(family)              # fresh jits: nothing cached
    written = _lowered(ours, chunk, program)
    assert took == ({False} if side == "gather" else {True, "walk"})
    assert written.as_text() == _lowered(eng, chunk, program).as_text()
    monkeypatch.setattr(M, "combine_form", lambda rows, cfg: "gather")
    bare, _ = _build(family)
    held = _lowered(bare, chunk, program)
    assert (held.as_text() == written.as_text()) == (side == "gather")
    if side == "walk":
        assert ("moe.combine", "pallas_call") in (_products(written)
                                                  - _products(held))


# ---------------------------------------------------------------------------
# Every seam's product carries its region, the innermost where two nest
# ---------------------------------------------------------------------------

# a called function's locations are relative to its call site (the scan
# body's are ``rg_ffn/dot_general``; XLA joins them to ``jit(..)/while/body/
# closed_call/rg_ffn/dot_general`` when it inlines the call)
_PATH = re.compile(r'loc\("([^"]+)"')


def _products(lowered) -> set:
    """{(innermost region or None, primitive)} over the module's ops."""
    out = set()
    for path in _PATH.findall(lowered.as_text(debug_info=True)):
        parts = path.split("/")
        inner = [p for p in parts if p.startswith(profiling.REGION_PREFIX)]
        region = inner[-1][len(profiling.REGION_PREFIX):].replace(
            "__", ".") if inner else None
        out.add((region, parts[-1]))
    return out


# region -> (family, program, a primitive its seam must have produced)
_SEAM_PRODUCTS = {
    "embed": ("dense", "decode_horizon", "gather"),
    "proj": ("dense", "decode_horizon", "dot_general"),
    "kv_write": ("dense", "decode_horizon", "scatter"),
    "attn": ("window", "decode_horizon", "dot_general"),
    "attn.gate": ("gated", "decode_horizon", "logistic"),
    "out_proj": ("dense", "decode_horizon", "dot_general"),
    "ffn": ("dense", "prefill_chunk", "dot_general"),
    "head": ("dense", "prefill_chunk", "dot_general"),
    "moe.route": ("latent", "decode_horizon", "dot_general"),
    "moe.align": ("window", "prefill_chunk", "jit(searchsorted)"),
    "moe.experts": ("latent", "decode_horizon", "pallas_call"),
    "moe.combine": ("window", "decode_horizon", "dot_general"),
    "moe.shared": ("latent", "prefill_chunk", "dot_general"),
    "dsa.index": ("sparse", "decode_horizon", "dot_general"),
    "dsa.select": ("sparse", "decode_horizon", "while"),
    "mla.expand": ("sparse", "prefill_chunk", "dot_general"),
    "sample": ("dense", "decode_horizon", "while"),
    "ssm.in": ("state", "decode_horizon", "dot_general"),
    "ssm.conv": ("state", "prefill_chunk", "dynamic_slice"),
    "ssm.scan": ("state", "decode_horizon", "exp"),
    "ssm.out": ("state", "prefill_chunk", "dot_general"),
    "gmu": ("state", "decode_horizon", "dot_general"),
    "gdn.in": ("matrix", "decode_horizon", "dot_general"),
    "gdn.conv": ("matrix", "prefill_chunk", "dynamic_slice"),
    "gdn.rule": ("matrix", "decode_horizon", "exp"),
    "gdn.out": ("matrix", "prefill_chunk", "dot_general"),
    "hc.pre": ("streams", "decode_horizon", "pallas_call"),
    "hc.post": ("streams", "prefill_chunk", "pallas_call"),
}


def test_the_seam_table_covers_the_closed_set():
    assert set(_SEAM_PRODUCTS) == set(profiling.REGIONS)


@pytest.mark.parametrize("name", profiling.REGIONS)
def test_a_product_of_each_seam_carries_its_region(engines, name):
    family, program, primitive = _SEAM_PRODUCTS[name]
    eng, chunk = engines(family)
    products = _products(_lowered(eng, chunk, program))
    assert (name, primitive) in products, sorted(
        p for r, p in products if r == name)


def test_the_dense_paged_call_stays_outside_attn(engines):
    """The dense family's paged call has no name of its own: XLA names it
    after the scope around it, and the benchmark reads it as
    ``closed_call`` / ``_unknown_``.  So its decode programs open no
    ``attn`` scope (``benchmarks/regions.py`` files the call there by its
    name), while its prefill chunk — flash attention, named by its own
    ``annotate`` scope — does, and a family whose call has a name (the
    window family's carries its layer kind's) scopes its paged call."""
    eng, chunk = engines("dense")
    for program in ("decode_horizon", "paged_decode"):
        regions = {r for r, _ in _products(_lowered(eng, chunk, program))}
        assert "attn" not in regions and {"proj", "ffn", "head"} <= regions
    assert "attn" in {r for r, _ in _products(
        _lowered(eng, chunk, "prefill_chunk"))}
    # the single-step program keeps the scope its call is named after
    text = _lowered(eng, chunk, "paged_decode").as_text(debug_info=True)
    assert "/_unknown_/rg_proj/" in text
    weng, wchunk = engines("window")
    assert "attn" in {r for r, _ in _products(
        _lowered(weng, wchunk, "paged_decode"))}
