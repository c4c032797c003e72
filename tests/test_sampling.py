"""Sampling transforms (models/sampling.py) + Generator integration."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.models.sampling import make_sampler, sample_logits


def _logits(key, B=4, V=32):
    return jax.random.normal(key, (B, V), jnp.float32) * 3.0


def test_temperature_zero_is_greedy(key):
    logits = _logits(key)
    tok = sample_logits(logits, key, temperature=0.0)
    np.testing.assert_array_equal(np.asarray(tok),
                                  np.asarray(jnp.argmax(logits, -1)))


def test_deterministic_given_key(key):
    logits = _logits(key)
    a = sample_logits(logits, key, temperature=0.8, top_k=8, top_p=0.9)
    b = sample_logits(logits, key, temperature=0.8, top_k=8, top_p=0.9)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_top_k_support(key):
    """Sampled tokens always lie in the top-k set."""
    logits = _logits(key, B=2, V=64)
    topk = set()
    for b in range(2):
        topk |= {(b, int(i)) for i in
                 np.argsort(np.asarray(logits[b]))[-5:]}
    for i in range(50):
        tok = sample_logits(logits, jax.random.fold_in(key, i),
                            temperature=1.5, top_k=5)
        for b in range(2):
            assert (b, int(tok[b])) in topk


def test_top_p_keeps_top_token_even_when_tiny_p(key):
    logits = _logits(key)
    tok = sample_logits(logits, key, temperature=1.0, top_p=1e-6)
    np.testing.assert_array_equal(np.asarray(tok),
                                  np.asarray(jnp.argmax(logits, -1)))


def test_top_p_mass_bound(key):
    """With top_p=0.5, sampled tokens come from the smallest prefix whose
    mass reaches 0.5."""
    logits = _logits(key, B=1, V=16)
    probs = np.asarray(jax.nn.softmax(logits, -1))[0]
    order = np.argsort(-probs)
    cum = np.cumsum(probs[order])
    allowed = set(order[:int(np.searchsorted(cum, 0.5) + 1)].tolist())
    for i in range(50):
        tok = sample_logits(logits, jax.random.fold_in(key, i),
                            temperature=1.0, top_p=0.5)
        assert int(tok[0]) in allowed


def test_generator_sampling_path(mesh2, key):
    """End-to-end: stochastic generate() is reproducible under one key and
    in-vocab."""
    from triton_dist_tpu.models.generate import Generator
    from triton_dist_tpu.models.llama import LlamaConfig, init_params

    cfg = LlamaConfig(vocab=64, dim=32, n_layers=1, n_heads=4, n_kv_heads=2,
                      ffn_dim=64, max_seq=32, dtype=jnp.float32)
    params = init_params(cfg, key)
    gen = Generator(cfg, mesh2, axis="tp", max_seq=32)
    prompt = jax.random.randint(key, (2, 4), 0, cfg.vocab, jnp.int32)
    sampler = make_sampler(temperature=0.7, top_k=16, top_p=0.95)
    t1, _ = gen.generate(params, gen.prefill(params, prompt), 6,
                         sample=sampler, key=key)
    t2, _ = gen.generate(params, gen.prefill(params, prompt), 6,
                         sample=sampler, key=key)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    assert t1.shape == (2, 6)
    assert int(jnp.max(t1)) < cfg.vocab and int(jnp.min(t1)) >= 0


def test_top_p_zero_is_greedy(key):
    """top_p=0.0 keeps exactly the top token (regression: it used to cut
    the whole vocab and degenerate to always-token-0)."""
    logits = _logits(key)
    for i in range(10):
        tok = sample_logits(logits, jax.random.fold_in(key, i),
                            temperature=1.0, top_p=0.0)
        np.testing.assert_array_equal(np.asarray(tok),
                                      np.asarray(jnp.argmax(logits, -1)))


def test_zero_temperature_guard(key):
    """filtered_probs(temperature=0) must raise, not return NaN silently
    (sample_logits special-cases greedy before the divide)."""
    from triton_dist_tpu.models.sampling import filtered_probs
    logits = _logits(key)
    with pytest.raises(ValueError, match="temperature"):
        filtered_probs(logits, temperature=0.0)
    tok = sample_logits(logits, key, temperature=0.0)  # greedy path still OK
    np.testing.assert_array_equal(np.asarray(tok),
                                  np.asarray(jnp.argmax(logits, -1)))


_HOST_PATH_ROWS = {
    # greedy, plain-temperature, top-k, top-p, filters-off: today's batch
    "small": dict(V=48, temps=[1.0, 0.8, 1.5, 0.5, 1.0, 0.9],
                  top_ks=[0, 16, 5, 0, 0, 48],              # 48 = off (=V)
                  top_ps=[1.0, 0.9, 1.0, 0.6, 1.0, 0.95], whole=None),
    # a vocabulary on the candidate plan: greedy; both cut-offs among the
    # candidates; a top_p with no top-k before it and a top_k above the
    # groups a row keeps (either sends the BATCH to the whole rows); a
    # top-k alone; no filter
    "wide": dict(V=200064, temps=[1.0, 0.8, 1.5, 0.5, 1.0, 0.9],
                 top_ks=[0, 64, 0, 200, 5, 0],
                 top_ps=[1.0, 0.95, 0.9, 1.0, 1.0, 1.0], whole=True),
    # the same vocabulary, rows the candidates serve: no row falls back
    "wide_narrow": dict(V=200064, temps=[1.0, 0.8, 1.5, 0.5, 1.0, 0.9],
                        top_ks=[0, 64, 128, 1, 5, 0],
                        top_ps=[1.0, 0.95, 0.5, 0.0, 1.0, 1.0],
                        whole=False),
}


@pytest.mark.parametrize("rows", sorted(_HOST_PATH_ROWS))
def test_rowwise_sampler_matches_host_path(key, rows):
    """THE host/device dedup pin (serve engine): for every row,
    `sample_logits_rowwise` (the traced per-row sampler the decode
    horizon runs on device) must emit the SAME token as the scalar
    `sample_logits` host fallback with that row's knobs and key, and as
    the engine's one-row `_sample_token` program — across greedy,
    plain-temperature, top-k, top-p, and filters-off rows in one mixed
    batch, under the engine's fold_in(key(seed), emission) stream; on a
    wide vocabulary whichever way the batch's cut-offs were found."""
    from triton_dist_tpu.models.sampling import sample_logits_rowwise_path
    from triton_dist_tpu.serve.programs import _sample_token

    case = _HOST_PATH_ROWS[rows]
    logits = _logits(key, B=6, V=case["V"])
    seeds = jnp.array([3, 11, 11, 7, 5, 9], jnp.int32)
    counts = jnp.array([0, 4, 9, 2, 0, 31], jnp.int32)
    temps = jnp.array(case["temps"], jnp.float32)
    top_ks = jnp.array(case["top_ks"], jnp.int32)
    top_ps = jnp.array(case["top_ps"], jnp.float32)
    greedy = jnp.array([True, False, False, False, False, False])

    keys = jax.vmap(jax.random.fold_in)(jax.vmap(jax.random.key)(seeds),
                                        counts)
    dev, whole = jax.jit(lambda lo, ks: sample_logits_rowwise_path(
        lo, ks, temperature=temps, top_k=top_ks, top_p=top_ps,
        greedy=greedy))(logits, keys)
    assert (whole if whole is None else bool(whole)) == case["whole"]
    one_row = jax.jit(_sample_token)
    for b in range(6):
        if bool(greedy[b]):
            want = int(np.argmax(np.asarray(logits[b])))
        else:
            k_host = jax.random.fold_in(jax.random.key(int(seeds[b])),
                                        int(counts[b]))
            tk = int(top_ks[b]) or None
            tp = float(top_ps[b])
            want = int(sample_logits(
                logits[b:b + 1], k_host, temperature=float(temps[b]),
                top_k=tk, top_p=tp if tp < 1.0 else None)[0])
            assert int(one_row(logits[b], jax.random.key(int(seeds[b])),
                               counts[b], temps[b], top_ks[b],
                               top_ps[b])) == want, f"row {b}: one-row call"
        assert int(dev[b]) == want, f"row {b}: device {int(dev[b])} != host {want}"


def test_rowwise_sampler_filters_respected(key):
    """Rowwise top-k/top-p draws stay inside their row's allowed set."""
    from triton_dist_tpu.models.sampling import sample_logits_rowwise

    logits = _logits(key, B=2, V=32)
    allowed = set(int(i) for i in np.argsort(np.asarray(logits[0]))[-4:])
    temps = jnp.array([1.5, 1.5], jnp.float32)
    top_ks = jnp.array([4, 0], jnp.int32)
    top_ps = jnp.array([1.0, 0.5], jnp.float32)
    greedy = jnp.zeros((2,), bool)
    probs = np.asarray(jax.nn.softmax(logits[1] / 1.5))
    order = np.argsort(-probs)
    nucleus = set(order[:int(np.searchsorted(np.cumsum(probs[order]),
                                             0.5) + 1)].tolist())
    for i in range(40):
        keys = jax.vmap(jax.random.fold_in)(
            jax.vmap(jax.random.key)(jnp.array([i, i], jnp.int32)),
            jnp.array([0, 0], jnp.int32))
        tok = sample_logits_rowwise(logits, keys, temperature=temps,
                                    top_k=top_ks, top_p=top_ps,
                                    greedy=greedy)
        assert int(tok[0]) in allowed
        assert int(tok[1]) in nucleus


# ---------------------------------------------------------------------------
# The windowed sampler against the two-sort form it replaced (PR 29).  The
# oracle below IS that form, kept here verbatim: one sort of the vocabulary
# for the k-th largest value, a second inside the nucleus rule.
# ---------------------------------------------------------------------------

_NEG_INF = np.float32(-1e30)


def _oracle_top_p(logits, top_p):
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    cut = cum - probs >= top_p
    idx = jax.lax.broadcasted_iota(jnp.int32, cut.shape, cut.ndim - 1)
    cut = cut & (idx > 0)
    cutoff = jnp.where(cut, jnp.float32(jnp.inf), sorted_logits).min(
        axis=-1, keepdims=True)
    return jnp.where(logits < cutoff, _NEG_INF, logits)


def _oracle_rowwise(logits, keys, *, temperature, top_k, top_p, greedy):
    """-> (filtered logits [B, V], token [B])."""
    gr = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    V = logits.shape[-1]
    t = jnp.where(greedy, jnp.float32(1.0), temperature.astype(jnp.float32))
    x = logits.astype(jnp.float32) / t[:, None]
    k = jnp.clip(jnp.where(top_k > 0, top_k, V), 1, V)
    srt = jnp.sort(x, axis=-1)[..., ::-1]
    kth = jnp.take_along_axis(srt, (k - 1)[:, None], axis=-1)
    x = jnp.where(((top_k > 0) & (top_k < V))[:, None],
                  jnp.where(x < kth, _NEG_INF, x), x)
    x = jnp.where((top_p < 1.0)[:, None],
                  _oracle_top_p(x, top_p[:, None].astype(jnp.float32)), x)
    drawn = jax.vmap(
        lambda kk, row: jax.random.categorical(kk, row[None], axis=-1)[0]
    )(keys, x).astype(jnp.int32)
    return x, jnp.where(greedy, gr, drawn)


_TOP_KS = (1, 5, 64, 128, 129, 0)
_TOP_PS = (1.0, 0.95, 0.5, 0.0)
_VOCABS = (32000, 16032, 98304, 200064)
_KINDS = ("f32", "bf16_ties")
#: rank whose value the ``bf16_ties`` logits repeat, and how often: the
#: ties start inside the 64 largest values and run far past the 128th
_TIE_RANK, _TIE_COPIES = 40, 200


def _oracle_logits(kind, V):
    x = np.asarray(jax.random.normal(jax.random.key(V), (4, V),
                                     jnp.float32)) * 3.0
    if kind == "bf16_ties":
        x = np.asarray(jnp.asarray(x).astype(jnp.bfloat16)
                       .astype(jnp.float32)).copy()
        rng = np.random.default_rng(V)
        for row in x:
            tie = np.sort(row)[-_TIE_RANK]
            low = np.argsort(row)[:V // 2]
            row[rng.choice(low, _TIE_COPIES, replace=False)] = tie
    return jnp.asarray(x)


@pytest.fixture(scope="module")
def sampler_and_oracle():
    """Both forms jitted once a vocabulary (every parameter an array)."""
    from triton_dist_tpu.models import sampling

    def new(logits, keys, **kw):
        return (*sampling._filtered_logits_rowwise(logits, **kw),
                sampling.sample_logits_rowwise(logits, keys, **kw))

    return jax.jit(new), jax.jit(_oracle_rowwise)


def _assert_matches_oracle(new, oracle, logits, top_k, top_p):
    """Kept set, kept values and drawn token of ``new`` equal the two-sort
    form's, row for row, in a batch of a greedy row and three sampled
    ones -> (kept [3, V], which way the cut-offs were found)."""
    keys = jax.vmap(jax.random.fold_in)(
        jax.vmap(jax.random.key)(jnp.array([3, 2 ** 31 - 5, 7, 11])),
        jnp.array([0, 4, 9, 31]))
    kw = dict(temperature=jnp.array([0.0, 0.8, 1.0, 1.7], jnp.float32),
              top_k=jnp.full((4,), top_k, jnp.int32),
              top_p=jnp.full((4,), top_p, jnp.float32),
              greedy=jnp.array([True, False, False, False]))
    x_new, whole, tok_new = new(logits, keys, **kw)
    x_old, tok_old = oracle(logits, keys, **kw)
    kept_new = np.asarray(x_new)[1:] > _NEG_INF / 2
    kept_old = np.asarray(x_old)[1:] > _NEG_INF / 2
    np.testing.assert_array_equal(kept_new, kept_old)
    np.testing.assert_array_equal(np.asarray(x_new)[1:][kept_new],
                                  np.asarray(x_old)[1:][kept_old])
    np.testing.assert_array_equal(np.asarray(tok_new), np.asarray(tok_old))
    assert int(tok_new[0]) == int(np.argmax(np.asarray(logits[0])))
    return kept_new, whole


def _falls_back(top_k, top_p, V, spilt_ties):
    """Whether a sampled row of these knobs sends its batch to the whole
    rows on the candidate plan: a ``top_k`` above the groups a row keeps;
    a ``top_p`` with no top-k before it; a ``top_p`` whose k-th value ties
    in more groups than were gathered (``spilt_ties``)."""
    from triton_dist_tpu.models.sampling import _CAND_GROUPS

    use_k, use_p = 0 < top_k < V, top_p < 1.0
    return ((use_k and top_k > _CAND_GROUPS)
            or (use_p and (not use_k or spilt_ties)))


@pytest.mark.parametrize("V", _VOCABS)
@pytest.mark.parametrize("kind", _KINDS)
@pytest.mark.parametrize("top_p", _TOP_PS)
@pytest.mark.parametrize("top_k", _TOP_KS)
def test_sampler_matches_two_sort_oracle(sampler_and_oracle, top_k, top_p,
                                         kind, V):
    """Kept set and drawn token equal the two-sort form's, row for row,
    in a batch that mixes a greedy row with three sampled ones — float32
    logits, and bf16-rounded ones in which 200 more values tie with the
    40th largest (a cut at the 64th or 128th value keeps every one of
    them; the mass they carry moves the nucleus).  The two widest
    vocabularies run the candidate plan, where those ties, scattered over
    the lower half, reach the k-th value in more groups than a row keeps:
    a ``top_p`` behind such a ``top_k`` falls back to the whole rows."""
    from triton_dist_tpu.models.sampling import takes_candidates

    new, oracle = sampler_and_oracle
    kept_new, whole = _assert_matches_oracle(
        new, oracle, _oracle_logits(kind, V), top_k, top_p)
    ties = kind == "bf16_ties" and top_k > _TIE_RANK
    if 0 < top_k < V:
        assert (kept_new.sum(axis=1)
                <= top_k + (_TIE_COPIES if ties else 0) + 8).all()
        if top_p == 1.0:
            assert (kept_new.sum(axis=1) >= top_k).all()
    if takes_candidates(V):
        assert bool(whole) == _falls_back(top_k, top_p, V, ties)
    else:
        assert whole is None


def _edge_logits(edge, V):
    """Float32 rows for the candidate plan's edges: ``ties_in_one_group``
    — 100 copies of the 40th largest value side by side in ONE group of
    128 (no more groups reach the k-th value than a row keeps: nothing
    falls back, and every tie stays); ``ragged`` — plain rows of a
    vocabulary that is no multiple of 128 (the last group is padded)."""
    x = np.asarray(jax.random.normal(jax.random.key(V), (4, V),
                                     jnp.float32)) * 3.0
    if edge == "ties_in_one_group":
        for row in x:
            tie = np.sort(row)[-_TIE_RANK]
            g = int(np.argmin(row.reshape(-1, 128).max(axis=1)))
            row[g * 128:g * 128 + 100] = tie
    return jnp.asarray(x)


@pytest.mark.parametrize("top_p", (1.0, 0.95, 0.0))
@pytest.mark.parametrize("top_k", (5, 64, 128, 129, 0))
@pytest.mark.parametrize("edge,V", [("ties_in_one_group", 32128),
                                    ("ragged", 32001)])
def test_candidate_plan_edges(monkeypatch, edge, V, top_k, top_p):
    """The candidate plan, forced onto a vocabulary under the crossover
    through the module's constant, against the two-sort form: ties that
    all fall inside one group, and a vocabulary that is not a multiple of
    the group (32,001: 251 groups, so a row's 128 are a true subset —
    16,033 would have fewer groups than a row keeps)."""
    from triton_dist_tpu.models import sampling

    monkeypatch.setattr(sampling, "_CAND_MIN_VOCAB", 32001)

    # a function of this test's own: a trace cached for the fixture's
    # would hold the plan chosen under the module's constant
    def new(logits, keys, **kw):
        return (*sampling._filtered_logits_rowwise(logits, **kw),
                sampling.sample_logits_rowwise(logits, keys, **kw))

    kept, whole = _assert_matches_oracle(
        jax.jit(new), jax.jit(_oracle_rowwise), _edge_logits(edge, V),
        top_k, top_p)
    assert bool(whole) == _falls_back(top_k, top_p, V, False)
    if edge == "ties_in_one_group" and 64 <= top_k <= 129 and top_p == 1.0:
        assert (kept.sum(axis=1) == _TIE_RANK + 100).all()


def test_ordered_bits_keep_the_floats_order():
    """The bisection runs on a float32's bits re-ordered so that unsigned
    comparison is the floats' own; the map is its own way back."""
    from triton_dist_tpu.models.sampling import (
        _from_ordered_bits,
        _ordered_bits,
    )

    x = jnp.asarray(np.asarray(
        [-np.inf, -3e38, -1.5, -1e-45, -0.0, 0.0, 1e-45, 1.5, 3e38, np.inf],
        np.float32))
    bits = np.asarray(_ordered_bits(x))
    assert bits.dtype == np.uint32
    assert (np.diff(bits.astype(np.int64)) > 0).all()
    back = np.asarray(_from_ordered_bits(jnp.asarray(bits)))
    np.testing.assert_array_equal(back.view(np.uint32),
                                  np.asarray(x).view(np.uint32))
    # a threshold that held nowhere stays 0: a NaN, which cuts nothing
    assert np.isnan(np.asarray(_from_ordered_bits(jnp.uint32(0))))


def _sorts(hlo):
    """The compiled text's sort and top-k instructions."""
    return [line for line in hlo.splitlines()
            if " sort(" in line or "topk" in line.lower()]


def test_sampler_sorts_nothing(key):
    """Neither sampler surface holds a sort or a top-k any more: both
    cut-offs come from reduces."""
    from triton_dist_tpu.models.sampling import (
        sample_logits,
        sample_positions_rowwise,
    )

    logits = _logits(key, B=3, V=300)
    hlo = sample_logits.lower(logits, key, temperature=0.8, top_k=64,
                              top_p=0.95).compile().as_text()
    assert " sort(" not in hlo and "topk" not in hlo.lower()
    base = jax.vmap(jax.random.key)(jnp.arange(3))
    kw = dict(temperature=jnp.full((3,), 0.8), top_k=jnp.array([64, 0, 5]),
              top_p=jnp.array([0.95, 0.9, 1.0]),
              greedy=jnp.array([False, False, True]))
    hlo = jax.jit(lambda lg: sample_positions_rowwise(
        lg[:, None], base, jnp.zeros((3,), jnp.int32), **kw)).lower(
        logits).compile().as_text()
    assert " sort(" not in hlo and "topk" not in hlo.lower()


def test_wide_sampler_sorts_no_vocabulary(key):
    """On the candidate plan nothing of the vocabulary's length is sorted
    or given to a top-k either.  What IS ordered is the 1,563 group maxima
    a row (``lax.top_k`` picks the groups a row keeps; the compiler makes
    it one sort of ``[rows, 1563]``, ~0.1 ms of the sampler's ~1 at 96
    rows on the v5e: PERF.md §6, PR 39)."""
    from triton_dist_tpu.models.sampling import (
        sample_logits,
        sample_positions_rowwise,
    )

    V = 200064
    logits = jax.ShapeDtypeStruct((3, V), jnp.float32)
    base = jax.vmap(jax.random.key)(jnp.arange(3))
    kw = dict(temperature=jnp.full((3,), 0.8), top_k=jnp.array([64, 0, 5]),
              top_p=jnp.array([0.95, 0.9, 1.0]),
              greedy=jnp.array([False, False, True]))
    for hlo in (
            sample_logits.lower(logits, key, temperature=0.8, top_k=64,
                                top_p=0.95).compile().as_text(),
            jax.jit(lambda lg: sample_positions_rowwise(
                lg[:, None], base, jnp.zeros((3,), jnp.int32), **kw)).lower(
                logits).compile().as_text()):
        found = _sorts(hlo)
        assert found and all(str(V) not in line and "1563" in line
                             for line in found), found
