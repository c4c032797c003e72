"""MoE routing / sort-align invariants.

Reference analog: the host-side checks implied by csrc/moe_utils.cu's
contract (moe_ag_scatter_align_block_size): destination rows are unique,
every row tile is single-expert, padding rows stay zero, and the end-to-end
topk combine matches a dense mixture-of-experts reference.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.analysis.jaxpr_audit import _iter_subjaxprs
from triton_dist_tpu.kernels import moe_combine
from triton_dist_tpu.kernels.gemm import PallasShapeError
from triton_dist_tpu.kernels.moe_utils import (
    combine_topk,
    gather_sorted,
    padded_rows,
    sort_align,
    sort_align_held,
    topk_routing,
)


def test_sort_align_invariants():
    T, E, topk, block_m = 64, 8, 2, 16
    logits = jax.random.normal(jax.random.key(0), (T, E))
    _, experts = topk_routing(logits, topk)
    plan = sort_align(experts, E, block_m)
    dest = np.asarray(plan["dest"])
    tile_expert = np.asarray(plan["tile_expert"])
    valid = np.asarray(plan["valid_rows"])
    m_pad = plan["m_pad"]

    assert m_pad == padded_rows(T * topk, E, block_m)
    assert m_pad % block_m == 0
    # Destination rows are unique and in range.
    assert len(set(dest.tolist())) == T * topk
    assert dest.min() >= 0 and dest.max() < m_pad
    # Every assignment lands in a tile labeled with its expert.
    flat_exp = np.asarray(experts).reshape(-1)
    for i, d in enumerate(dest):
        assert tile_expert[d // block_m] == flat_exp[i], (i, d)
    # valid marks exactly the destination rows.
    assert valid.sum() == T * topk
    assert valid[dest].all()


def test_sort_align_stable_within_expert():
    """Assignments of one expert keep their original (token, k) order."""
    experts = jnp.array([[0], [1], [0], [1], [0]], jnp.int32)
    plan = sort_align(experts, 2, 4)
    dest = np.asarray(plan["dest"])
    # Expert 0 rows: tokens 0, 2, 4 -> rows 0, 1, 2.
    assert dest[0] < dest[2] < dest[4]
    assert dest[1] < dest[3]


def test_gather_sorted_padding_rows_zero():
    T, D, E, topk, block_m = 16, 8, 4, 2, 8
    x = jax.random.normal(jax.random.key(1), (T, D))
    _, experts = topk_routing(jax.random.normal(jax.random.key(2), (T, E)),
                              topk)
    plan = sort_align(experts, E, block_m)
    xs = np.asarray(gather_sorted(x, plan["dest"], plan["m_pad"]))
    valid = np.asarray(plan["valid_rows"])
    assert np.all(xs[~valid] == 0)
    # Each valid row holds its source token's data.
    token_of = np.arange(T * topk) // topk
    for i, d in enumerate(np.asarray(plan["dest"])):
        np.testing.assert_array_equal(xs[d], np.asarray(x)[token_of[i]])


@pytest.mark.parametrize("topk", [1, 2])
def test_end_to_end_moe_matches_dense(topk):
    """sort -> per-tile expert GEMM -> combine == dense per-token expert mix."""
    T, D, F, E, block_m = 32, 16, 24, 4, 8
    key = jax.random.key(3)
    x = jax.random.normal(key, (T, D))
    w = jax.random.normal(jax.random.key(4), (E, D, F))
    logits = jax.random.normal(jax.random.key(5), (T, E))
    weights, experts = topk_routing(logits, topk)

    plan = sort_align(experts, E, block_m)
    xs = gather_sorted(x, plan["dest"], plan["m_pad"])
    # Per-tile single-expert GEMM (stand-in for the pallas group GEMM).
    tiles = xs.reshape(-1, block_m, D)
    ys = jnp.einsum("nbd,ndf->nbf", tiles,
                    w[plan["tile_expert"]]).reshape(plan["m_pad"], F)
    out = combine_topk(ys, plan["dest"], weights)

    dense = jnp.einsum(
        "tk,tkf->tf", weights,
        jnp.einsum("td,tkdf->tkf", x, w[experts]))
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# sort_align_held: the plan of a layer that holds some of the experts
# ---------------------------------------------------------------------------


def _held_plan_by_hand(ids, n_held, block_m, offset, assignment=False):
    """The plan's contract as a plain loop over assignments."""
    T, topk = ids.shape
    n = T * topk
    flat = ids.reshape(-1).astype(np.int64) - offset
    local = (flat >= 0) & (flat < n_held)
    counts = np.zeros(n_held, np.int64)
    rank = np.zeros(n, np.int64)
    for j in range(n):
        if local[j]:
            rank[j] = counts[flat[j]]
            counts[flat[j]] += 1
    padded = -(-counts // block_m) * block_m
    starts = np.cumsum(padded) - padded
    m_pad = -(-(n + n_held * (block_m - 1)) // block_m) * block_m
    dest = np.full(n, m_pad)
    valid = np.zeros(m_pad, bool)
    src = np.zeros(m_pad, np.int64)
    sat = np.zeros(m_pad, np.int64)
    for j in range(n):
        if local[j]:
            dest[j] = starts[flat[j]] + rank[j]
            valid[dest[j]] = True
            src[dest[j]] = j // topk
            sat[dest[j]] = j
    tile_expert = np.full(m_pad // block_m, n_held - 1)   # dead tiles
    for e in range(n_held):
        tile_expert[starts[e] // block_m:
                    (starts[e] + padded[e]) // block_m] = e
    return {"dest": dest, "tile_expert": tile_expert, "valid_rows": valid,
            "m_pad": m_pad, "local": local, "src_token": src,
            "n_live_tiles": padded.sum() // block_m, "counts": counts,
            **({"src_assignment": sat} if assignment else {})}


def _routed(T, topk, n_experts, seed=0):
    """Distinct experts a token, as a router's top-k gives them."""
    rng = np.random.default_rng(seed)
    return np.argsort(rng.random((T, n_experts)), axis=1)[:, :topk]


# name -> (ids [T, topk], n_held, block_m, offset)
_HELD_CASES = {
    # the shapes the expert cells compile (chunk and decode step)
    "mellum2_chunk": (_routed(2048, 8, 64), 64, 256, 0),
    "mellum2_decode": (_routed(64, 8, 64), 64, 32, 0),
    "glm5_chunk": (_routed(2048, 8, 256), 16, 32, 0),
    "glm5_decode": (_routed(32, 8, 256), 16, 32, 0),
    "gc3_chunk": (_routed(512, 8, 256), 16, 32, 0),
    "gc3_decode": (_routed(64, 8, 256), 16, 32, 0),
    "all_to_one_held_expert": (np.full((40, 2), 11), 8, 16, 8),
    "no_local_assignment": (16 + _routed(24, 4, 48), 16, 8, 0),
    "ids_on_both_sides_of_the_held_range": (_routed(300, 8, 256), 16, 32,
                                            120),
    "a_count_that_fills_its_tiles": (np.repeat([[0, 1], [2, 3]], 8, axis=0),
                                     4, 8, 0),
    "one_token": (_routed(1, 8, 64), 64, 32, 0),
    # 256 tokens: the token index + 1 needs a second bf16 digit
    "a_token_index_past_one_digit": (_routed(256, 2, 8), 8, 16, 0),
}


@pytest.mark.parametrize("case", _HELD_CASES)
def test_sort_align_held_matches_a_loop_over_assignments(case):
    ids, n_held, block_m, offset = _HELD_CASES[case]
    got = jax.jit(sort_align_held, static_argnums=(1, 2, 3))(
        jnp.asarray(ids, jnp.int32), n_held, block_m, offset)
    want = _held_plan_by_hand(ids, n_held, block_m, offset)
    assert set(got) == set(want)
    for key, ref in want.items():
        np.testing.assert_array_equal(np.asarray(got[key]), ref, err_msg=key)
    for key, dtype in (("dest", jnp.int32), ("src_token", jnp.int32),
                       ("tile_expert", jnp.int32), ("valid_rows", bool),
                       ("local", bool), ("n_live_tiles", jnp.int32)):
        assert got[key].dtype == dtype, key
    if case == "a_count_that_fills_its_tiles":
        assert (np.asarray(got["counts"]) % block_m == 0).all()
    if case == "no_local_assignment":
        assert not np.asarray(got["valid_rows"]).any()


@pytest.mark.parametrize("case", _HELD_CASES)
def test_sort_align_held_carries_the_assignment(case):
    """With ``assignment`` every field is as without, each buffer row
    names the assignment that sits in it, and the weight read there is
    ``w[t, k]`` bit for bit."""
    ids, n_held, block_m, offset = _HELD_CASES[case]
    got = jax.jit(sort_align_held, static_argnums=(1, 2, 3),
                  static_argnames=("assignment",))(
        jnp.asarray(ids, jnp.int32), n_held, block_m, offset,
        assignment=True)
    want = _held_plan_by_hand(ids, n_held, block_m, offset, True)
    assert set(got) == set(want)
    for key, ref in want.items():
        np.testing.assert_array_equal(np.asarray(got[key]), ref, err_msg=key)
    assert got["src_assignment"].dtype == got["src_token"].dtype == jnp.int32
    w = np.random.default_rng(1).random(ids.shape, np.float32)
    w_row = np.asarray(jnp.asarray(w).reshape(-1)[got["src_assignment"]])
    dest, local = np.asarray(got["dest"]), np.asarray(got["local"])
    assert (w_row[dest[local]].view(np.uint32)
            == w.reshape(-1)[local].view(np.uint32)).all()


def _avals(jaxpr):
    """Every intermediate of a jaxpr, sub-computations included."""
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            yield eqn.primitive.name, var.aval
        for sub in _iter_subjaxprs(eqn.params):
            yield from _avals(sub)


def test_sort_align_held_is_linear_in_the_rows():
    """Nothing of ``[m_pad, T*topk]`` (every buffer row against every
    assignment: 537 M at this shape, rows SQUARED) is ever formed: the
    largest intermediate is an assignment against a tile, a position or a
    held expert."""
    T, topk, n_held, block_m = 2048, 8, 64, 256
    n_tiles = padded_rows(T * topk, n_held, block_m) // block_m
    limit = T * topk * max(n_tiles, block_m, n_held)
    closed = jax.make_jaxpr(lambda ids: {
        k: v for k, v in sort_align_held(ids, n_held, block_m).items()
        if k != "m_pad"})(jnp.zeros((T, topk), jnp.int32))
    sizes = [(int(np.prod(aval.shape)), name, aval.shape)
             for name, aval in _avals(closed.jaxpr) if hasattr(aval, "shape")]
    assert len(sizes) > 20 and max(sizes)[0] <= limit, max(sizes)


# ---------------------------------------------------------------------------
# The routed sum by row: the walk over the live tiles (kernels/moe_combine)
# ---------------------------------------------------------------------------


def _sum_by_hand(y, ids, w, n_held, offset, plan):
    """The routed sum as a plain loop, in float64."""
    T, topk = ids.shape
    out = np.zeros((T, y.shape[1]))
    dest = np.asarray(plan["dest"]).reshape(T, topk)
    for t in range(T):
        for k in range(topk):
            if offset <= ids[t, k] < offset + n_held:
                out[t] += float(w[t, k]) * y[dest[t, k]].astype(np.float64)
    return out


def _all_picks_local(T, topk, n_experts, n_held):
    """Token 0's picks are all held experts, the others' as routed."""
    ids = _routed(T, topk, n_experts, seed=3)
    ids[0] = np.arange(topk)
    return ids


# name -> (ids [T, topk], n_held, block_m, offset, D, result block bytes,
#          the buffer's dtype)
_WALK_CASES = {
    "random_tile_32": (_routed(24, 4, 16), 4, 32, 4, 256, None, "bfloat16"),
    "random_tile_128": (_routed(32, 2, 8), 2, 128, 0, 128, None, "bfloat16"),
    "a_float32_buffer_tile_8": (_routed(24, 4, 16), 4, 8, 0, 128, None,
                                "float32"),
    # 15 tiles of 8 rows of a PACKED dtype: the whole buffer a step
    "a_bfloat16_buffer_tile_8": (_routed(23, 4, 16), 4, 8, 0, 128, None,
                                 "bfloat16"),
    "every_assignment_local": (_routed(16, 2, 4), 4, 32, 0, 128, None,
                               "bfloat16"),
    "no_local_assignment": (16 + _routed(24, 4, 48), 16, 32, 0, 128, None,
                            "bfloat16"),
    # 80 rows of one expert: three tiles of 32
    "one_expert_takes_every_row": (np.full((40, 2), 11), 2, 32, 10, 128,
                                   None, "bfloat16"),
    "a_token_with_every_pick_local": (_all_picks_local(20, 4, 16, 4), 4, 32,
                                      0, 128, None, "bfloat16"),
    "tokens_no_multiple_of_the_tile": (_routed(25, 2, 8), 2, 32, 4, 128,
                                       None, "bfloat16"),
    # a result block of 16 tokens: two passes over the live tiles
    "a_width_that_needs_token_blocks": (_routed(32, 2, 8), 4, 32, 0, 256,
                                        16 * 256 * 4, "bfloat16"),
}


@pytest.mark.parametrize("case", _WALK_CASES)
def test_combine_walk_matches_the_gather_and_a_loop(case, monkeypatch):
    """The Mosaic call in the interpreter against the gather form (its
    oracle and fallback, compiled) and a plain loop."""
    ids, n_held, block_m, offset, D, out_bytes, dtype = _WALK_CASES[case]
    if out_bytes:
        monkeypatch.setattr(moe_combine, "_OUT_BLOCK_BYTES", out_bytes)
    rng = np.random.default_rng(2)
    T, topk = ids.shape
    w = jnp.asarray(rng.random(ids.shape) + 0.05, jnp.float32)
    plan = sort_align_held(jnp.asarray(ids, jnp.int32), n_held, block_m,
                           offset, assignment=True)
    m_pad = plan["m_pad"]
    tb = moe_combine._blocks(T, m_pad, D, block_m)[1]
    if case == "a_bfloat16_buffer_tile_8":
        assert m_pad // block_m % 2 == 1
    assert (T // tb > 1) == bool(out_bytes)
    # rows of dead tiles hold anything: they must stay out of the sum
    y = rng.standard_normal((m_pad, D)).astype(np.float32)
    live = int(plan["n_live_tiles"]) * block_m
    y[:live] *= np.asarray(plan["valid_rows"])[:live, None]
    y = jnp.asarray(y, dtype)
    walk = moe_combine.combine_live(y, plan, w, block_m=block_m,
                                    impl="pallas", interpret=True)
    gather = moe_combine.combine_gather(y, plan, w)
    fallback = jax.jit(lambda y, w: moe_combine.combine_live(
        y, plan, w, block_m=block_m))(y, w)
    assert walk.dtype == jnp.float32 and walk.shape == (T, D)
    np.testing.assert_array_equal(np.asarray(fallback), np.asarray(gather))
    np.testing.assert_allclose(np.asarray(walk), np.asarray(gather),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(walk),
        _sum_by_hand(np.asarray(y.astype(jnp.float32)), ids, np.asarray(w),
                     n_held, offset, plan), rtol=1e-5, atol=1e-5)
    if case == "no_local_assignment":
        assert int(plan["n_live_tiles"]) == 0 and not np.asarray(walk).any()


def test_combine_gap_says_why():
    # the three cells' chunk geometries: (D, row tile) of a bfloat16 buffer
    for width, block_m in ((3072, 128), (3072, 64), (3072, 32), (6144, 32),
                           (7168, 32)):
        assert moe_combine.combine_gap(width, block_m) is None
    assert "D%128" in moe_combine.combine_gap(192, 32)
    assert "block_m%8" in moe_combine.combine_gap(256, 12)
    # a step holds whole packed sublane tiles (16 rows), at tile 8 too:
    # 15 tiles of 8 go in one step, 16 tiles in one of 128 rows
    assert moe_combine._blocks(24, 120, 128, 8)[0] == 120
    assert moe_combine._blocks(24, 128, 128, 8)[0] == 128
    assert moe_combine._blocks(2048, 24576, 3072, 128)[0] == 512
    ids = jnp.asarray(_routed(8, 2, 8), jnp.int32)
    plan = sort_align_held(ids, 4, 8, 0, assignment=True)
    y = jnp.zeros((plan["m_pad"], 192), jnp.float32)
    with pytest.raises(PallasShapeError, match="D%128"):
        moe_combine.combine_live(y, plan, jnp.ones((8, 2)), block_m=8,
                                 impl="pallas", interpret=True)
