"""A request's way into the batch (ISSUE 41): what ``_start_prefill`` and a
``prefill_chunk`` call launch from the host.

A cold admission is ONE named program, ``zero_scratch`` — the whole tree an
eager ``jnp.zeros`` a plane built at two launches each (the fill value's
``convert_element_type``, then the ``broadcast``) — and a prefill call's
scalars are host numbers.  Held here on a toy engine of each family the
benchmark runs (``tests/test_regions.py`` builds them), an int8-pool
engine, a 2-device mesh engine and a speculative one:

- after ``warmup()`` a cold admission dispatches exactly
  ``["zero_scratch"]``, its scratch is the tree, shapes, dtypes and zeros
  of the loop it replaced, and ``scratch_dispatches`` rose by one;
- with ``jnp.zeros`` / ``jnp.int32`` patched to raise a request still runs
  from admission to its last token, to the tokens it gives unpatched (a
  state family's second life in a reused slot included);
- one ``zero_scratch`` a rung after warm-up, none under traffic, and the
  jaxpr auditor finds nothing in it;
- a warm-prefix admission gathers (``load_pages``) and zeroes nothing; the
  draft's temp caches come from the same body under its own name.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding

from tests.test_regions import FAMILIES
from tests.test_regions import _build as _family_engine
from triton_dist_tpu.analysis.jaxpr_audit import audit_program
from triton_dist_tpu.models import llama
from triton_dist_tpu.models.generate import Generator
from triton_dist_tpu.serve import Request, SamplingParams, ServeEngine
from triton_dist_tpu.serve.request import FinishReason

ENGINES = FAMILIES + ("kv_quant", "mesh2")


def _dense(**cfg_kw):
    cfg = llama.LlamaConfig(**{
        "vocab": 64, "dim": 32, "n_layers": 2, "n_heads": 4, "n_kv_heads": 4,
        "ffn_dim": 64, "max_seq": 64, "dtype": jnp.float32, **cfg_kw})
    one = Mesh(np.array(jax.devices()[:1]), ("sp",))
    return cfg, llama.init_params(cfg, jax.random.key(0)), one


def _engine(kind):
    if kind in FAMILIES:
        return _family_engine(kind)[0]
    cfg, params, one = _dense()
    kw = dict(num_blocks=24, page_size=8, max_batch=2, prefill_chunk=4,
              prefill_budget=8, horizon=4, prefix_cache=False)
    if kind == "kv_quant":
        gen = Generator(cfg, one, axis="sp", max_seq=64, kv_dtype=jnp.int8)
        return ServeEngine(gen, params, **kw)   # int8 pools, from the cache
    gen = Generator(cfg, one, axis="sp", max_seq=64)
    return ServeEngine(gen, params, kv_shard="heads", mesh=Mesh(
        np.array(jax.devices()[:2]), ("tp",)), **kw)


@pytest.fixture(scope="module", params=ENGINES)
def eng(request):
    """One warmed-up engine a kind, shared by the tests below: each leaves
    it drained."""
    engine = _engine(request.param)
    engine.warmup()
    return engine


def _requests(engine, tag, lens, n_new=6):
    """Greedy and seeded-sampled by turns, the same prompts every call."""
    rng = np.random.default_rng(41)
    out = []
    for i, n in enumerate(lens):
        prompt = rng.integers(0, engine.cfg.vocab, size=n).astype(np.int32)
        sp = (SamplingParams(max_new_tokens=n_new) if i % 2 == 0 else
              SamplingParams(max_new_tokens=n_new, temperature=0.8,
                             top_k=20, seed=123 + i))
        out.append(Request(f"{tag}{i}", prompt, sp))
    return out


def _serve(engine, reqs):
    """Through ``engine.step()`` until drained -> the streams, in order."""
    for r in reqs:
        engine.submit(r)
    for _ in range(500):
        if not engine.has_work():
            break
        engine.step()
    outs = [engine._outputs[r.request_id] for r in reqs]
    assert all(o.finish_reason is FinishReason.LENGTH for o in outs)
    return [o.token_ids for o in outs]


class _Tap:
    """``benchmarks/run.py``'s ``EngineTap``: the dispatch seam, logged."""

    def __init__(self, engine):
        self.engine, self.ops = engine, []
        seam = engine._device_call

        def logged(op, *a, **kw):
            self.ops.append(op)
            return seam(op, *a, **kw)

        engine._device_call = logged

    def __enter__(self):
        return self.ops

    def __exit__(self, *exc):
        del self.engine._device_call


def _admissions(engine, monkeypatch):
    """-> a list that ``_start_prefill`` fills with (what it dispatched,
    the scratch it left, the rung, the scratch's leaves on the host) for
    every request it starts."""
    seen, start = [], engine._start_prefill

    def recorded(rs):
        with _Tap(engine) as ops:
            start(rs)
        # read before the first chunk takes it: the chunk program donates
        # its scratch where the backend can
        seen.append((list(ops), rs.scratch, rs.s_ext,
                     [np.asarray(x) for x in jax.tree.leaves(rs.scratch)]))

    monkeypatch.setattr(engine, "_start_prefill", recorded)
    return seen


def _parents_scratch(engine, s_ext):
    """The loop ``_start_prefill`` ran before ISSUE 41, as it stood."""
    if engine.kv_quant:
        def _zs(h, d):
            return {"q": jnp.zeros((1, h, s_ext, d), jnp.int8),
                    "s": jnp.zeros((1, h, s_ext), jnp.float32)}
    else:
        def _zs(h, d):
            return jnp.zeros((1, h, s_ext, d), engine.cfg.dtype)
    return [tuple(_zs(*p) if isinstance(p[0], int) else
                  jnp.zeros((1, *p[0]), p[1]) for p in planes)
            for planes in engine._plane_specs]


def test_a_cold_admission_is_one_named_launch(eng, monkeypatch):
    seen = _admissions(eng, monkeypatch)
    before = eng.metrics.scratch_dispatches
    _serve(eng, _requests(eng, "cold", [23]))
    (ops, scratch, s_ext, read), = seen
    assert ops == ["zero_scratch"]
    assert eng.metrics.scratch_dispatches == before + 1
    assert (eng.metrics.summary()["prefill"]["scratch_dispatches"]
            == eng.metrics.scratch_dispatches)
    want = _parents_scratch(eng, s_ext)
    assert jax.tree.structure(scratch) == jax.tree.structure(want)
    assert [type(layer) for layer in scratch] == [tuple] * eng.cfg.n_layers
    for got, ref in zip(jax.tree.leaves(scratch), jax.tree.leaves(want),
                        strict=True):
        assert (got.shape, got.dtype) == (ref.shape, ref.dtype)
    assert all(leaf.size and not leaf.any() for leaf in read)
    if eng.mesh is not None:
        # born where the chunk program takes it: no copy on its way in
        spec = eng._mesh_progs["prefill_chunk"]._progs[s_ext].in_specs[2]
        assert all(leaf.sharding == NamedSharding(eng.mesh, spec[0][0])
                   for leaf in jax.tree.leaves(scratch))


def test_no_eager_launch_from_admission_to_the_last_token(eng, monkeypatch):
    """``jnp.zeros`` with a dtype is two launches and ``jnp.int32`` one:
    neither is called on a request's way through ``engine.step()`` — and
    the tokens are those of the same requests' first life on this engine
    (a state family's slot is reused and starts from zero again)."""
    lens = [17, 40]
    first = _serve(eng, _requests(eng, "life1_", lens))

    def eager(*a, **kw):
        raise AssertionError("an eager launch on the serving path")

    monkeypatch.setattr(jnp, "zeros", eager)
    monkeypatch.setattr(jnp, "int32", eager)
    assert _serve(eng, _requests(eng, "life2_", lens)) == first
    assert all(len(t) == 6 for t in first)


def test_zero_scratch_compiles_once_a_rung_and_never_under_traffic(eng):
    zero = eng._zero_fn
    assert zero.name == "zero_scratch" and zero in eng.metrics.compiled_fns
    assert zero.misses == len(eng.ladder)
    misses, hits = eng.metrics.compile_misses, zero.hits
    # a prompt that reaches every rung
    lens = [min(r, eng.gen.max_seq - 8) for r in eng.ladder]
    assert sorted({eng._bucket_s_ext(n) for n in lens}) == eng.ladder
    _serve(eng, _requests(eng, "rung", lens, n_new=2))
    assert eng.metrics.compile_misses == misses
    assert zero.hits == hits + len(lens)
    stats = eng.metrics.compile_stats()["programs"]["zero_scratch"]
    assert stats["misses"] == len(eng.ladder)


def test_the_auditor_finds_nothing_in_zero_scratch(eng):
    """Its static is on the declared ladder (the engine's rungs) and it
    holds no collective, on one device or a mesh."""
    rec, = [r for r in eng.program_registry() if r["name"] == "zero_scratch"]
    assert rec["ladders"] == {"s_ext": tuple(eng.ladder)}
    assert rec["seams"] == {}
    assert audit_program(rec) == []


def test_a_warm_prefix_admission_gathers_and_zeroes_nothing(monkeypatch):
    cfg, params, one = _dense()
    engine = ServeEngine(Generator(cfg, one, axis="sp", max_seq=64), params,
                         num_blocks=24, page_size=8, max_batch=2,
                         prefill_chunk=8, prefill_budget=8, horizon=4,
                         prefix_cache=True)
    engine.warmup()
    seen = _admissions(engine, monkeypatch)
    a, = _requests(engine, "a", [40])
    b = Request("b", np.concatenate([a.prompt[:32], a.prompt[:5]]),
                SamplingParams(max_new_tokens=6))
    misses = engine.metrics.compile_misses
    _serve(engine, [a])
    _serve(engine, [b])
    assert [ops for ops, *_ in seen] == [["zero_scratch"], ["load_pages"]]
    assert engine.metrics.scratch_dispatches == 1
    assert engine.metrics.prefix_hits == 1
    assert engine.metrics.compile_misses == misses
    assert "serve_prefill_scratch_dispatches_total 1" in \
        engine.metrics.to_prometheus()


def test_the_drafts_temp_caches_come_from_the_same_body(monkeypatch):
    cfg, params, one = _dense(n_layers=1)
    dcfg, d_params, _ = _dense(n_layers=1, n_heads=2, n_kv_heads=2)
    d_params = llama.init_params(dcfg, jax.random.key(7))

    def build(**kw):
        return ServeEngine(Generator(cfg, one, axis="sp", max_seq=64),
                           params, num_blocks=24, page_size=8, max_batch=2,
                           prefill_chunk=8, prefix_cache=False, **kw)

    engine = build(draft=Generator(dcfg, one, axis="sp", max_seq=64),
                   draft_params=d_params, spec_k=2)
    engine.warmup()
    zero = engine._draft_zero_fn
    assert zero.name == "draft_zero_scratch"
    assert zero.misses == len(engine._draft_ladder)
    misses = engine.metrics.compile_misses
    lens = [19, 33]
    greedy = [Request(r.request_id, r.prompt,
                      SamplingParams(max_new_tokens=6))
              for r in _requests(engine, "g", lens)]
    want = _serve(build(), greedy)

    def eager(*a, **kw):
        raise AssertionError("an eager launch on the serving path")

    monkeypatch.setattr(jnp, "zeros", eager)
    monkeypatch.setattr(jnp, "int32", eager)
    with _Tap(engine) as ops:
        assert _serve(engine, greedy) == want
    assert ops.count("zero_scratch") == ops.count("draft_zero_scratch") == 2
    assert engine.metrics.compile_misses == misses
    assert engine.metrics.scratch_dispatches == 2
