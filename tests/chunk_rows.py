"""What the tests need of a ``prefill_chunk`` call now that it returns ONE
row of logits (``generate._chunk_forward``): every row's, for the tests that
hold a prompt's every position against a reference, and the all-rows
program itself, for the tests that hold the kept row against it.
"""

import jax
import jax.numpy as jnp
import numpy as np

from triton_dist_tpu.models import generate as G


def all_chunk_rows(fn, args, kw) -> np.ndarray:
    """Logits [width, V] of the call ``fn(*args, **kw)`` an engine is about
    to make through ``_device_call("prefill_chunk", rids, fn, ...)``, on a
    copy of its scratch (the call donates it): the same jitted program
    with no ``n_valid`` keeps all rows.  Padding rows are then fed as
    tokens, which no valid row can see (causality), so rows ``< n_valid``
    are the model's output at their positions; what the call leaves in its
    scratch is not looked at.  ``fn.fn``: the jit under the engine's
    ``CountingJit``, so the engine counts no program."""
    params, buf, scratch, pos = args
    out = fn.fn(params, buf, jax.tree.map(jnp.copy, scratch), pos,
                quantized=kw["quantized"], extent=kw["extent"])
    return np.asarray(out[1][0])


def filed_chunk_call(rows: dict, seam, op, rids, fn, args, kw):
    """Make the engine's ``prefill_chunk`` call through ``seam`` (its
    ``_device_call``) and file the logits of the call's valid rows in
    ``rows`` by position: every row's from :func:`all_chunk_rows`, and —
    where the engine will read it: a prompt's last call, a positive
    ``n_valid`` — the last valid row's from the call itself, which keeps
    that row alone.  Returns what the call returned."""
    every = all_chunk_rows(fn, args, kw)       # before: args[2] is donated
    out = seam(op, rids, fn, *args, **kw)
    pos, n = int(args[3]), abs(int(kw["n_valid"]))
    rows.update((pos + j, every[j]) for j in range(n))
    if kw["n_valid"] > 0:
        (rows[pos + n - 1],), = np.asarray(out[1])
    return out


def keep_no_row(monkeypatch) -> None:
    """Patch the layer loop to be told of no row to keep: every
    ``prefill_chunk`` traced from here on is the all-rows program (over
    the same ``n_valid`` masks) that a chunk was before it kept a row."""
    stack = G._layer_stack
    monkeypatch.setattr(
        G, "_layer_stack",
        lambda *a, keep=None, read=None, **kw: stack(*a, **kw))
