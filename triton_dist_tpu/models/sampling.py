"""Token sampling for autoregressive serving: temperature, top-k, top-p.

The reference stops at the decode-attention kernel (no sampling — its
serving story ends at logits); a usable serving stack needs the sampler.
All transforms are shape-static and jit-compatible, so one compiled
sampler serves every step.

Both filters are VALUE cuts: top-k masks what lies below the k-th largest
value, top-p what lies below the smallest value of the nucleus.  Neither
cut-off needs the vocabulary in order — each is the largest threshold
``t`` at which a monotone reduce still holds (``count(x >= t) >= k``;
``mass(x >= t) >= top_p``) — so each is found by bisection over the 32
bits of a float32 (:func:`_largest_threshold`): 32 compare-and-reduce
passes, exact for every ``k`` and ``top_p`` and indifferent to ties.
Nothing of the vocabulary's length is sorted: on the v5e a sort of
``[32, 32000]`` float32 takes 0.92 ms and ``lax.top_k`` at k = 128
0.80 ms (its ``TopK`` call costs by the elements it is given, not by
k), the 32 passes 0.05 ms (PERF.md §6, PR 29).

WHAT the passes run over follows the logits' static last dimension
(:func:`takes_candidates`).  A narrow vocabulary's rows stay on-chip
through all 64 passes (4 MB at 32 x 32,000).  A wide one's do not — 96 x
200,064 float32 logits are 77 MB, and 64 passes over them were 10.9 ms
of a 37 ms decode step — so there ONE pass keeps a row's
CANDIDATES, the ``_CAND_GROUPS`` groups of ``_GROUP`` consecutive logits
with the largest maxima (:func:`_candidates`), both cut-offs are found
among those, bit for bit what the whole row gives, and the one cut is
applied to the whole row in one more pass (:func:`_filter_wide`).
The sampler alone on the v5e, whole rows -> candidates, us an execution
(1 row in 3 sampled, top-k 64, top-p 0.95; PERF.md §6, PR 39): 32 x
32,000 118 -> 148; 64 x 16,032 115 -> 177; 32 x 19,360 90 -> 118; 64 x
98,304 576 -> 428; 96 x 200,064 10,932 -> ~1,450 — so the crossover
``_CAND_MIN_VOCAB`` sits between 32,000 and 98,304.  A batch the
candidates cannot answer (:func:`_filter_wide` says which) runs the
bisections over the whole rows inside the same program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# numpy scalar, NOT jnp: a module-level jnp constant would initialize the
# JAX backend at import time (breaks dryrun_multichip's late CPU pinning).
NEG_INF = np.float32(-1e30)

#: a row is viewed as groups of this many consecutive logits (one lane row)
_GROUP = 128
#: groups a row keeps as candidates: a row whose ``top_k`` is at most this
#: finds both cut-offs among them (:func:`_candidates`)
_CAND_GROUPS = 128
#: vocabularies at least this long take the candidate plan; shorter ones
#: run the bisections over the whole row (:func:`takes_candidates`)
_CAND_MIN_VOCAB = 65536


def _ordered_bits(x):
    """float32 -> uint32 whose unsigned order is the floats' order."""
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(1 << 31))


def _from_ordered_bits(b):
    """Inverse of :func:`_ordered_bits`."""
    u = jnp.where(b >> 31 == 1, b ^ jnp.uint32(1 << 31), ~b)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def _largest_threshold(bits, reaches, shape=None):
    """The largest uint32 ``t`` ``[..., 1]`` (or of ``shape``, what
    ``reaches`` reduces ``bits`` to) at which ``reaches(bits >= t)`` is
    true, for a test that never turns true again once false as ``t``
    grows (the mask only loses members): built from the top bit down, one
    evaluation a bit.  0 where it holds nowhere — the ordered bits of a
    NaN, below which no float compares, so such a row is cut nowhere."""
    def try_bit(i, t):
        trial = t | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        return jnp.where(reaches(bits >= trial), trial, t)

    return jax.lax.fori_loop(
        0, 32, try_bit,
        jnp.zeros(shape or bits.shape[:-1] + (1,), jnp.uint32))


def _kth_bits(logits, top_k):
    """Ordered bits ``[..., 1]`` of each row's ``top_k``-th largest value
    (``top_k`` a python int or a broadcastable ``[..., 1]`` array); 0
    where the row holds fewer than ``top_k`` numbers."""
    return _largest_threshold(
        _ordered_bits(logits),
        lambda kept: kept.sum(axis=-1, keepdims=True) >= top_k)


def _nucleus_bits(logits, top_p):
    """Ordered bits ``[..., 1]`` of the last value of each row's nucleus:
    the largest ``t`` whose own mass and everything above it still reaches
    ``top_p`` (a value lower down is cut exactly when the mass strictly
    above it already does)."""
    probs = jax.nn.softmax(logits, axis=-1)
    bits = _ordered_bits(logits)
    cutoff = _largest_threshold(
        bits, lambda kept: jnp.where(kept, probs, 0.0).sum(
            axis=-1, keepdims=True) >= top_p)
    # The top token is unconditionally kept (guards top_p <= p(top) —
    # including top_p=0.0, which every threshold satisfies and which
    # would otherwise cut the whole vocab and degenerate categorical()
    # to always-token-0).
    return jnp.minimum(cutoff, bits.max(axis=-1, keepdims=True))


def _cut_below(logits, bits):
    """Mask to -inf what lies below the value whose ordered bits are
    ``bits`` ``[..., 1]``: a VALUE cut, so ties with it stay; 0 (a NaN,
    below which no float compares) cuts nothing."""
    return jnp.where(logits < _from_ordered_bits(bits), NEG_INF, logits)


def _apply_top_k(logits, top_k):
    """Keep the k highest logits per row, mask the rest to -inf: a cut
    at the k-th largest VALUE, so ties with it stay.  ``top_k`` (in
    ``[1, V]``) is a python int or a broadcastable ``[..., 1]`` array."""
    return _cut_below(logits, _kth_bits(logits, top_k))


def _apply_top_p(logits, top_p):
    """Nucleus filtering: keep the smallest prefix of the probability-sorted
    vocab whose total mass reaches ``top_p`` (the top token always stays).

    ``top_p`` may be a python float (the static scalar path) or a
    broadcastable ``[..., 1]`` array (the per-row traced path of
    :func:`sample_logits_rowwise`) — :func:`_nucleus_bits` is THE one
    copy of the nucleus math either way."""
    return _cut_below(logits, _nucleus_bits(logits, top_p))


def takes_candidates(vocab: int) -> bool:
    """Whether rows of ``vocab`` logits find their cut-offs among one
    pass's candidates (:func:`_filter_wide`).  Decided at trace time from
    the logits' static last dimension alone — never from the rows — so a
    one-row call and row ``b`` of a batch take the same plan."""
    return vocab >= _CAND_MIN_VOCAB


def _candidates(x):
    """ONE pass over ``x`` [B, V] -> what both cut-offs can be found in:
    ``cand`` [B, _CAND_GROUPS * _GROUP], the whole of the ``_CAND_GROUPS``
    groups of ``_GROUP`` consecutive logits with the largest maxima (every
    group of a row that has no more);
    ``tau`` [B, 1], the smallest of those maxima; ``spill`` [B, 1], whether
    MORE groups reach ``tau`` than were gathered.

    Every logit above ``tau`` lies in a group whose maximum is above
    ``tau``, and fewer than ``_CAND_GROUPS`` groups are: all gathered.  So
    for ``t > tau``, ``count(cand >= t)`` is the row's, and for
    ``t <= tau`` both are at least ``_CAND_GROUPS``: the k-th value of the
    candidates is the row's, bit for bit, for every ``k <= _CAND_GROUPS``.
    The survivors ``x >= kth`` all lie among the candidates unless
    ``kth == tau`` and ``spill``.  The top-k below runs over the
    ``V / _GROUP`` group maxima, never over the vocabulary."""
    B, V = x.shape
    n = -(-V // _GROUP)
    if n * _GROUP != V:
        x = jnp.pad(x, ((0, 0), (0, n * _GROUP - V)),
                    constant_values=-jnp.inf)
    groups = x.reshape(B, n, _GROUP)
    top = groups.max(axis=-1)
    held = min(_CAND_GROUPS, n)
    vals, ids = jax.lax.top_k(top, held)
    tau = vals[:, -1:]
    cand = jnp.take_along_axis(groups, ids[:, :, None], axis=1)
    spill = (top >= tau).sum(axis=-1, keepdims=True) > held
    return cand.reshape(B, held * _GROUP), tau, spill


def _cut_bits(x, top_k, top_p, use_k, use_p):
    """Rows ``x`` [B, N] -> (``kth``, ``cut``), ordered bits [B, 1]: the
    top-k cut-off where ``use_k`` (else 0: nothing cut), and the value
    below which top-k THEN top-p mask a row (the nucleus of what top-k
    left; it never lies below ``kth``).  The filter math of both plans:
    over a whole row, or over its candidates."""
    kth = jnp.where(use_k, _kth_bits(x, top_k), jnp.uint32(0))
    cut = jnp.where(use_p, _nucleus_bits(_cut_below(x, kth), top_p),
                    jnp.uint32(0))
    return kth, jnp.maximum(kth, cut)


def _filter_wide(logits, t, top_k, top_p, use_k, use_p, sampled):
    """The candidate plan: ``logits`` [B, V] float32 over ``t`` -> (what a
    row's draw is taken over, whether the batch fell back to the whole
    row).  ``t``, ``top_k`` (in ``[1, V]``), ``top_p``, ``use_k``,
    ``use_p``, ``sampled`` are scalars or ``[B, 1]``.

    Both cut-offs come from :func:`_cut_bits` over the candidates, and the
    one resulting cut is applied to the whole row in one pass — the same
    kept set and kept values as over the whole row.  Where that cannot be
    known from the candidates — a sampled row whose ``top_k`` is above
    ``_CAND_GROUPS``, or whose ``top_p`` runs with no top-k before it, or
    whose k-th value ties past the gathered groups under a ``top_p`` — the
    BATCH takes :func:`_cut_bits` over the whole rows instead, inside the
    same program (``lax.cond``; under ``vmap`` both sides run)."""
    x = logits / t
    cand, tau, spill = _candidates(x)
    narrow_k = use_k & (top_k <= _CAND_GROUPS)
    kth, cut = _cut_bits(cand, top_k, top_p, narrow_k, use_p)
    missed = spill & (_from_ordered_bits(kth) <= tau)
    whole = (sampled & ((use_k & ~narrow_k)
                        | (use_p & (~use_k | missed)))).any()
    # the whole-row side divides again: as an operand of the conditional
    # ``x`` would be written out, where the passes around it can fuse it
    cut = jax.lax.cond(
        whole,
        lambda: _cut_bits(logits / t, top_k, top_p, use_k, use_p)[1],
        lambda: cut)
    return _cut_below(x, cut), whole


def _filtered_logits(logits, temperature: float, top_k, top_p):
    """The single temperature → top-k → top-p pipeline every sampling
    surface shares (direct sampling AND speculative verification — the
    rejection-sampling identity needs both sides to filter identically).

    ``temperature`` must be > 0: greedy is a separate code path
    (:func:`sample_logits` special-cases it to argmax before reaching here,
    and a greedy *distribution* is a one-hot, not a softmax limit we can
    divide our way to)."""
    if not temperature > 0.0:
        raise ValueError(
            f"temperature must be > 0, got {temperature}; use "
            "sample_logits(temperature=0) for greedy decoding")
    V = logits.shape[-1]
    use_k = top_k is not None and 0 < top_k < V
    use_p = top_p is not None and top_p < 1.0
    if takes_candidates(V) and (use_k or use_p):
        x, _ = _filter_wide(
            logits.astype(jnp.float32).reshape(-1, V), temperature,
            jnp.int32(top_k if use_k else V),
            jnp.float32(top_p if use_p else 1.0),
            jnp.bool_(use_k), jnp.bool_(use_p), jnp.bool_(True))
        return x.reshape(logits.shape)
    x = logits.astype(jnp.float32) / temperature
    if use_k:
        x = _apply_top_k(x, top_k)
    if use_p:
        x = _apply_top_p(x, top_p)
    return x


@functools.partial(jax.jit,
                   static_argnames=("temperature", "top_k", "top_p"))
def filtered_probs(logits, *, temperature: float = 1.0,
                   top_k: int | None = None,
                   top_p: float | None = None) -> jax.Array:
    """logits [..., V] → the post-filter sampling distribution π [..., V]
    (exactly what :func:`sample_logits` draws from)."""
    return jax.nn.softmax(_filtered_logits(logits, temperature, top_k,
                                           top_p), axis=-1)


@functools.partial(jax.jit,
                   static_argnames=("temperature", "top_k", "top_p"))
def sample_logits(logits, key, *, temperature: float = 1.0,
                  top_k: int | None = None,
                  top_p: float | None = None) -> jax.Array:
    """logits [B, vocab] f32 → token [B] int32.

    ``temperature=0`` is greedy argmax; filters compose as top-k then top-p
    (the standard serving order).
    """
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    x = _filtered_logits(logits, temperature, top_k, top_p)
    return jax.random.categorical(key, x, axis=-1).astype(jnp.int32)


def _row_top_k(top_k, V):
    """``top_k`` [B] (0 disables) -> (k in ``[1, V]``, whether the row is
    cut at all: a ``top_k`` of ``V`` or more is off, like the static
    path's skip)."""
    return (jnp.clip(jnp.where(top_k > 0, top_k, V), 1, V),
            (top_k > 0) & (top_k < V))


def _filtered_logits_rowwise(logits, *, temperature, top_k, top_p, greedy):
    """``logits`` [B, V] -> (what each row's draw is taken over: scaled by
    the row's temperature and cut by the row's filters — ``[B]`` arrays,
    as in :func:`sample_logits_rowwise` —, and whether the batch's
    cut-offs came from the whole rows: a scalar bool on the candidate
    plan, None under it, where they always do)."""
    V = logits.shape[-1]
    # Greedy rows divide by a dummy 1.0 (their draw is discarded by the
    # caller's select) — temperature 0 must never reach the division.
    t = jnp.where(greedy, jnp.float32(1.0), temperature.astype(jnp.float32))
    if takes_candidates(V):
        k, use_k = _row_top_k(top_k, V)
        return _filter_wide(
            logits.astype(jnp.float32), t[:, None], k[:, None],
            top_p[:, None].astype(jnp.float32), use_k[:, None],
            (top_p < 1.0)[:, None], ~greedy[:, None])
    x = logits.astype(jnp.float32) / t[:, None]
    # rows with a filter off keep x untouched, exactly like the static
    # path's skip
    k, use_k = _row_top_k(top_k, V)
    x = jnp.where(use_k[:, None], _apply_top_k(x, k[:, None]), x)
    return jnp.where((top_p < 1.0)[:, None],
                     _apply_top_p(x, top_p[:, None].astype(jnp.float32)),
                     x), None


def sample_logits_rowwise(logits, keys, *, temperature, top_k, top_p,
                          greedy) -> jax.Array:
    """Fully-traceable PER-ROW sampler: every knob is a ``[B]`` array, so
    one compiled program serves a batch mixing greedy and sampled requests
    with different temperatures/filters — the sampler the serving engine's
    device-resident decode horizon runs *inside* its fused multi-step scan
    (`serve/engine.py`), where a host round trip per token is exactly what
    it exists to avoid.

    - ``logits`` [B, V] f32, ``keys`` [B] typed PRNG keys;
    - ``temperature`` [B] f32 (> 0 for sampled rows; greedy rows ignore it),
      ``top_k`` [B] int32 (0 disables), ``top_p`` [B] f32 (1.0 disables),
      ``greedy`` [B] bool (argmax, no randomness consumed).

    Row ``b``'s draw is BIT-IDENTICAL to the host fallback
    ``sample_logits(logits[b:b+1], keys[b], temperature=t_b, ...)`` —
    there is one copy of the filter math (temperature scale,
    :func:`_apply_top_k`, :func:`_apply_top_p`), and the per-row draw is the
    same ``jax.random.categorical`` under ``vmap``
    (tests/test_sampling.py pins the equality, so the engine's H=1 host
    path and H>1 device path emit the same streams)."""
    return sample_logits_rowwise_path(
        logits, keys, temperature=temperature, top_k=top_k, top_p=top_p,
        greedy=greedy)[0]


def sample_logits_rowwise_path(logits, keys, *, temperature, top_k, top_p,
                               greedy):
    """:func:`sample_logits_rowwise` -> (tokens [B], and which way the
    batch's cut-offs were found: a scalar bool, true where they came from
    the whole rows, on a vocabulary that :func:`takes_candidates`; None
    under it) — what the decode horizon counts
    (``summary()["sample"]``)."""
    x, whole = _filtered_logits_rowwise(
        logits, temperature=temperature, top_k=top_k, top_p=top_p,
        greedy=greedy)
    drawn = jax.vmap(
        lambda kk, row: jax.random.categorical(kk, row[None], axis=-1)[0]
    )(keys, x).astype(jnp.int32)
    return jnp.where(greedy, jnp.argmax(logits, axis=-1).astype(jnp.int32),
                     drawn), whole


def sample_positions_rowwise(logits, base_keys, counts, *, temperature,
                             top_k, top_p, greedy) -> jax.Array:
    """Multi-position view of :func:`sample_logits_rowwise`: ``logits``
    [B, T, V] → tokens [B, T], where position ``t`` of row ``b`` draws
    with the key ``fold_in(base_keys[b], counts[b] + t)`` — i.e. exactly
    the token the engine's per-row stream emits at emission index
    ``counts[b] + t``, no matter which surface emits it (the host
    ``_choose_token`` fallback, the fused decode horizon's scan, or a
    speculative round's accept chain scoring k+1 candidate positions at
    once).  One draw per (row, emission index) is the invariant that
    makes every decode path bit-interchangeable mid-request."""
    def at(t, lg):
        keys = jax.vmap(jax.random.fold_in)(base_keys, counts + t)
        return sample_logits_rowwise(lg, keys, temperature=temperature,
                                     top_k=top_k, top_p=top_p,
                                     greedy=greedy)

    T = logits.shape[1]
    return jax.vmap(at, in_axes=(0, 1), out_axes=1)(
        jnp.arange(T, dtype=counts.dtype), logits)


def make_sampler(*, temperature: float = 1.0, top_k: int | None = None,
                 top_p: float | None = None):
    """``sample(logits, key) -> token`` with the knobs baked in (one
    compiled executable reused across decode steps)."""
    return functools.partial(sample_logits, temperature=temperature,
                             top_k=top_k, top_p=top_p)
