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


def test_rowwise_sampler_matches_host_path(key):
    """THE host/device dedup pin (serve engine): for every row,
    `sample_logits_rowwise` (the traced per-row sampler the decode
    horizon runs on device) must emit the SAME token as the scalar
    `sample_logits` host fallback with that row's knobs and key — across
    greedy, plain-temperature, top-k, top-p, and filters-off rows in one
    mixed batch, under the engine's fold_in(key(seed), emission) stream."""
    from triton_dist_tpu.models.sampling import sample_logits_rowwise

    logits = _logits(key, B=6, V=48)
    seeds = jnp.array([3, 11, 11, 7, 5, 9], jnp.int32)
    counts = jnp.array([0, 4, 9, 2, 0, 31], jnp.int32)
    temps = jnp.array([1.0, 0.8, 1.5, 0.5, 1.0, 0.9], jnp.float32)
    top_ks = jnp.array([0, 16, 5, 0, 0, 48], jnp.int32)     # 48 = off (=V)
    top_ps = jnp.array([1.0, 0.9, 1.0, 0.6, 1.0, 0.95], jnp.float32)
    greedy = jnp.array([True, False, False, False, False, False])

    keys = jax.vmap(jax.random.fold_in)(jax.vmap(jax.random.key)(seeds),
                                        counts)
    dev = jax.jit(lambda lo, ks: sample_logits_rowwise(
        lo, ks, temperature=temps, top_k=top_ks, top_p=top_ps,
        greedy=greedy))(logits, keys)
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
_VOCABS = (32000, 16032)
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
        return (sampling._filtered_logits_rowwise(logits, **kw),
                sampling.sample_logits_rowwise(logits, keys, **kw))

    return jax.jit(new), jax.jit(_oracle_rowwise)


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
    them; the mass they carry moves the nucleus)."""
    new, oracle = sampler_and_oracle
    logits = _oracle_logits(kind, V)
    keys = jax.vmap(jax.random.fold_in)(
        jax.vmap(jax.random.key)(jnp.array([3, 2 ** 31 - 5, 7, 11])),
        jnp.array([0, 4, 9, 31]))
    kw = dict(temperature=jnp.array([0.0, 0.8, 1.0, 1.7], jnp.float32),
              top_k=jnp.full((4,), top_k, jnp.int32),
              top_p=jnp.full((4,), top_p, jnp.float32),
              greedy=jnp.array([True, False, False, False]))
    x_new, tok_new = new(logits, keys, **kw)
    x_old, tok_old = oracle(logits, keys, **kw)
    kept_new = np.asarray(x_new)[1:] > _NEG_INF / 2
    kept_old = np.asarray(x_old)[1:] > _NEG_INF / 2
    np.testing.assert_array_equal(kept_new, kept_old)
    np.testing.assert_array_equal(np.asarray(x_new)[1:][kept_new],
                                  np.asarray(x_old)[1:][kept_old])
    np.testing.assert_array_equal(np.asarray(tok_new), np.asarray(tok_old))
    assert int(tok_new[0]) == int(np.argmax(np.asarray(logits[0])))
    if 0 < top_k < V:
        ties = _TIE_COPIES if kind == "bf16_ties" and top_k > _TIE_RANK else 0
        assert (kept_new.sum(axis=1) <= top_k + ties + 8).all()
        if top_p == 1.0:
            assert (kept_new.sum(axis=1) >= top_k).all()


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
