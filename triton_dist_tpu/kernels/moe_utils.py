"""MoE routing + token sort/align — feeder for the grouped GEMM.

Reference analog: ``csrc/moe_utils.cu`` — the CUDA kernel
``moe_ag_scatter_align_block_size`` (serial + parallel variants, :61-356)
sorts gathered tokens by expert and pads each expert's row range to the
GEMM block size so every tile is single-expert; plus the host-side topk
preprocessing in ``create_moe_rs_context`` (moe_reduce_rs.py:278+).

TPU-native design: the sort/align runs **on device** as XLA ops (argsort +
cumsum — no host round trip, where the reference needs a custom CUDA kernel
and a pinned-memory readback).  Shapes stay static: the padded total is the
worst-case ``round_up(T*topk + E*(block_m-1), block_m)``, the TPU answer to
dynamic expert loads (SURVEY.md §7 hard part 2).

Data flow (matching the reference's GroupGEMM contract):

  tokens [T, D], router logits [T, E]
  -> topk_routing: weights/experts [T, topk]
  -> sort_align(block_m): dest row for every (token, k) pair, per-tile
     expert map, padded row count
  -> gather_sorted: x_sorted [M_pad, D] (padding rows zero)
  -> group_gemm (kernels/group_gemm.py): y_sorted [M_pad, F]
  -> combine_topk: out [T, F] = sum_k w[t,k] * y_sorted[dest[t,k]]

The serving engine's expert layer holds SOME of the experts and skips dead
tiles (``models/mla_moe.py``, ``group_gemm_live``): its plan is
:func:`sort_align_held`, which neither sorts nor scatters — ``dest`` from
compares over ``[T*topk, n_held]``, the inverse map (buffer row -> token)
from a tile one-hot times a position one-hot on the MXU — so its work is
linear in the rows from a decode step's 64 to a prefill chunk's 2,048.
Its products return to their tokens through ``kernels/moe_combine.py``: by
assignment (:func:`combine_topk`'s form under a mask), or row by row over
the live tiles, for which the plan names each row's assignment.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def round_up(x, m: int):
    """Round up to a multiple of ``m`` (works on ints and jnp arrays)."""
    return (x + m - 1) // m * m


def padded_rows(n_assignments: int, n_experts: int, block_m: int) -> int:
    """Static worst-case row count after per-expert padding."""
    return round_up(n_assignments + n_experts * (block_m - 1), block_m)


def stable_rank_in_group(keys, n_groups: int):
    """Rank of each element among same-key elements, stable by position.

    Returns ``(rank [n] int32, counts [n_groups])``.  This is the scatter-slot
    idiom shared by the expert sort (group GEMM feeder, below) and the EP
    dispatch slot allocation (layers/ep_a2a.py) — the reference computes the
    same thing with atomic counters (moe_utils.cu:61-356 /
    ep_a2a.py:35-146 ``atomic_add_per_warp``).
    """
    n = keys.shape[0]
    counts = jnp.bincount(keys, length=n_groups)
    seg_starts = jnp.concatenate(
        [jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
    order = jnp.argsort(keys, stable=True)
    rank_sorted = (jnp.arange(n, dtype=jnp.int32)
                   - seg_starts[keys[order]].astype(jnp.int32))
    rank = jnp.zeros((n,), jnp.int32).at[order].set(rank_sorted)
    return rank, counts


def topk_routing(logits, topk: int):
    """Softmax-then-topk router (the reference tests' torch preprocessing).

    Returns (weights [T, topk] normalized, experts [T, topk] int32).
    """
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    weights, experts = jax.lax.top_k(probs, topk)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, experts.astype(jnp.int32)


def sort_align(experts, n_experts: int, block_m: int):
    """Stable-sort (token, k) pairs by expert and align groups to block_m.

    experts: [T, topk] int32.  Returns a dict:
      dest      [T*topk]  destination row of each assignment in the sorted buf
      tile_expert [M_pad // block_m] expert id of every row tile
      valid_rows  [M_pad] bool — False for padding rows
      m_pad     int (static)

    Reference: moe_ag_scatter_align_block_size (moe_utils.cu:61-356) —
    same outputs (sorted ids, expert offsets, padded sizes), computed with
    argsort+cumsum instead of a hand-written counting kernel.
    """
    T, topk = experts.shape
    n = T * topk
    flat = experts.reshape(-1)
    m_pad = padded_rows(n, n_experts, block_m)

    # Stable rank within each expert group (original (token, k) order).
    rank, counts = stable_rank_in_group(flat, n_experts)
    padded_counts = round_up(counts, block_m)
    group_starts = jnp.concatenate(
        [jnp.zeros((1,), counts.dtype), jnp.cumsum(padded_counts)[:-1]])
    dest = (group_starts[flat].astype(jnp.int32) + rank)

    n_tiles = m_pad // block_m
    tile_rows = jnp.arange(n_tiles) * block_m
    group_ends = jnp.cumsum(padded_counts)
    tile_expert = jnp.searchsorted(group_ends, tile_rows, side="right")
    tile_expert = jnp.minimum(tile_expert, n_experts - 1).astype(jnp.int32)

    valid = jnp.zeros((m_pad,), bool).at[dest].set(True)
    return {"dest": dest, "tile_expert": tile_expert,
            "valid_rows": valid, "m_pad": m_pad}


# bits of a token index one bf16 product carries: bf16 holds the integers
# up to 256 exactly, float32 accumulates the one term a row has
_DIGIT = 8


def sort_align_held(experts, n_held: int, block_m: int, offset=0, *,
                    assignment: bool = False):
    """:func:`sort_align` for a layer that holds ``n_held`` of the experts
    its router chooses among (expert parallelism: ids ``offset .. offset
    + n_held - 1`` live here, the rest on other chips).  Assignments to
    held experts are sorted and aligned as there, FIRST in the buffer;
    every other assignment has no row.  Same contract plus:

      local        [T*topk] bool — the assignment's expert is held here
      dest         [T*topk] row in the sorted buffer (``m_pad`` — out of
                   range, so a scatter drops it — where not ``local``)
      src_token    [M_pad] token of each buffer row (0 on padding rows)
      n_live_tiles scalar int32: the tiles that hold any row
      counts       [n_held] assignments an expert got
      src_assignment [M_pad] (``assignment`` only) the assignment ``t .
                   topk + k`` that sits in each buffer row (0 on padding
                   rows): a row's weight is ``w.reshape(-1)`` there.  The
                   products below then carry the assignment's index
                   where they carry the token's (``src_token`` is its
                   quotient) — as many of them while ``T . topk`` fits
                   the digits ``T`` needs, one more at a decode step

    ``dest`` (assignment -> row) is dense compare-and-sum arithmetic over
    ``[T*topk, n_held]``, with no sort and no scatter (both serialise on
    the chip).  The INVERSE (row -> token, and which rows are live) is one
    small product: a row is ``(tile, position)`` and a tile is one
    expert's, so "assignment j sits in row r" factors into a tile one-hot
    ``[n_tiles, T*topk]`` times a position one-hot ``[T*topk, block_m]``
    — ``T*topk x (n_tiles + block_m)`` compares and one MXU product a
    :data:`_DIGIT` of the token index, where comparing every row with
    every assignment is ``m_pad x T*topk`` (a 2,048-token chunk at 64
    held experts, tile 256: 6 M against 537 M).  At most one assignment
    sits in a row, so each sum has one term and comes out exact."""
    T, topk = experts.shape
    n = T * topk
    flat = experts.reshape(-1).astype(jnp.int32) - offset
    local = (flat >= 0) & (flat < n_held)
    onehot = (flat[:, None] == jnp.arange(n_held, dtype=jnp.int32)[None])
    before = jnp.cumsum(onehot.astype(jnp.int32), axis=0)      # inclusive
    counts = before[-1]
    rank = jnp.sum(jnp.where(onehot, before - 1, 0), axis=1)
    padded = round_up(counts, block_m)
    ends = jnp.cumsum(padded)
    m_pad = padded_rows(n, n_held, block_m)
    start = jnp.sum(jnp.where(onehot, (ends - padded)[None], 0), axis=1)
    dest = jnp.where(local, start + rank, m_pad).astype(jnp.int32)
    n_tiles = m_pad // block_m
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(n_tiles) * block_m,
                         side="right"), n_held - 1).astype(jnp.int32)
    # a non-local assignment's tile is n_tiles: no row of ``in_tile``
    in_tile = (dest // block_m)[None, :] == jnp.arange(
        n_tiles, dtype=jnp.int32)[:, None]                 # [n_tiles, n]
    at_pos = ((dest % block_m)[:, None] == jnp.arange(
        block_m, dtype=jnp.int32)[None, :]).astype(jnp.bfloat16)
    # token (or assignment) + 1 a row, digit by digit (0: no assignment
    # sits there)
    per = 1 if assignment else topk
    index1 = jnp.arange(n, dtype=jnp.int32) // per + 1
    found = jnp.zeros((n_tiles, block_m), jnp.int32)
    for shift in range(0, (n // per).bit_length(), _DIGIT):
        digit = (index1 >> shift) & ((1 << _DIGIT) - 1)
        found += jnp.dot(
            jnp.where(in_tile, digit[None, :], 0).astype(jnp.bfloat16),
            at_pos, preferred_element_type=jnp.float32,
        ).astype(jnp.int32) << shift
    found = found.reshape(m_pad)
    valid = found > 0
    source = jnp.where(valid, found - 1, 0)
    return {"dest": dest, "tile_expert": tile_expert, "valid_rows": valid,
            "m_pad": m_pad, "local": local,
            "n_live_tiles": (ends[-1] // block_m).astype(jnp.int32),
            "counts": counts,
            **({"src_token": source // topk, "src_assignment": source}
               if assignment else {"src_token": source})}


def gather_sorted(x, dest, m_pad: int):
    """Scatter token rows into the expert-sorted padded buffer.

    x: [T, D]; dest: [T*topk] rows.  Padding rows stay zero so they
    contribute nothing downstream.
    """
    T, D = x.shape
    topk = dest.shape[0] // T
    token_of = jnp.arange(dest.shape[0]) // topk
    return jnp.zeros((m_pad, D), x.dtype).at[dest].set(x[token_of])


def combine_topk(y_sorted, dest, weights, out_dtype=None):
    """out[t] = sum_k weights[t, k] * y_sorted[dest[t, k]].

    Reference: the topk-reduce in consumer_reduce_scatter_reduce_2d
    (moe_reduce_rs.py:817+).
    """
    T, topk = weights.shape
    gathered = y_sorted[dest.reshape(T, topk)]          # [T, topk, F]
    out = jnp.einsum("tk,tkf->tf", weights.astype(jnp.float32),
                     gathered.astype(jnp.float32))
    return out.astype(out_dtype or y_sorted.dtype)
