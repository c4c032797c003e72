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
passes over a row that the chip keeps on-chip, exact for every ``k`` and
``top_p`` and indifferent to ties.  Nothing is sorted: on the v5e a sort
of ``[32, 32000]`` float32 takes 0.92 ms and ``lax.top_k`` at k = 128
0.80 ms (its ``TopK`` call costs by the elements it is given, not by
k), the 32 passes 0.05 ms (PERF.md §6, PR 29).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# numpy scalar, NOT jnp: a module-level jnp constant would initialize the
# JAX backend at import time (breaks dryrun_multichip's late CPU pinning).
NEG_INF = np.float32(-1e30)


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


def _apply_top_k(logits, top_k):
    """Keep the k highest logits per row, mask the rest to -inf: a cut
    at the k-th largest VALUE, so ties with it stay.  ``top_k`` (in
    ``[1, V]``) is a python int or a broadcastable ``[..., 1]`` array."""
    kth = _largest_threshold(
        _ordered_bits(logits),
        lambda kept: kept.sum(axis=-1, keepdims=True) >= top_k)
    return jnp.where(logits < _from_ordered_bits(kth), NEG_INF, logits)


def _apply_top_p(logits, top_p):
    """Nucleus filtering: keep the smallest prefix of the probability-sorted
    vocab whose total mass reaches ``top_p`` (the top token always stays).

    ``top_p`` may be a python float (the static scalar path) or a
    broadcastable ``[..., 1]`` array (the per-row traced path of
    :func:`sample_logits_rowwise`) — the masking rule is THE one copy of
    the nucleus math either way.

    The last value of that prefix is the largest ``t`` whose own mass and
    everything above it still reaches ``top_p``: a value lower down is cut
    exactly when the mass strictly above it already does."""
    probs = jax.nn.softmax(logits, axis=-1)
    bits = _ordered_bits(logits)
    cutoff = _largest_threshold(
        bits, lambda kept: jnp.where(kept, probs, 0.0).sum(
            axis=-1, keepdims=True) >= top_p)
    # The top token is unconditionally kept (guards top_p <= p(top) —
    # including top_p=0.0, which every threshold satisfies and which
    # would otherwise cut the whole vocab and degenerate categorical()
    # to always-token-0).
    cutoff = jnp.minimum(cutoff, bits.max(axis=-1, keepdims=True))
    return jnp.where(logits < _from_ordered_bits(cutoff), NEG_INF, logits)


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
    x = logits.astype(jnp.float32) / temperature
    if top_k is not None and top_k > 0 and top_k < x.shape[-1]:
        x = _apply_top_k(x, top_k)
    if top_p is not None and top_p < 1.0:
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


def _filtered_logits_rowwise(logits, *, temperature, top_k, top_p, greedy):
    """``logits`` [B, V] -> what each row's draw is taken over: scaled by
    the row's temperature and cut by the row's filters (``[B]`` arrays,
    as in :func:`sample_logits_rowwise`)."""
    V = logits.shape[-1]
    # Greedy rows divide by a dummy 1.0 (their draw is discarded by the
    # caller's select) — temperature 0 must never reach the division.
    t = jnp.where(greedy, jnp.float32(1.0), temperature.astype(jnp.float32))
    x = logits.astype(jnp.float32) / t[:, None]
    # rows with a filter off keep x untouched, exactly like the static
    # path's skip
    k = jnp.clip(jnp.where(top_k > 0, top_k, V), 1, V)
    x = jnp.where(((top_k > 0) & (top_k < V))[:, None],
                  _apply_top_k(x, k[:, None]), x)
    return jnp.where((top_p < 1.0)[:, None],
                     _apply_top_p(x, top_p[:, None].astype(jnp.float32)), x)


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
    x = _filtered_logits_rowwise(logits, temperature=temperature,
                                 top_k=top_k, top_p=top_p, greedy=greedy)
    drawn = jax.vmap(
        lambda kk, row: jax.random.categorical(kk, row[None], axis=-1)[0]
    )(keys, x).astype(jnp.int32)
    return jnp.where(greedy, jnp.argmax(logits, axis=-1).astype(jnp.int32),
                     drawn)


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
