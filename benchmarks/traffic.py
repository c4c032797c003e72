"""The one general traffic generator: a traffic file of parameters in, a
request stream out.

A traffic file (``benchmarks/traffic/<name>.json``) states each length
distribution as a LAW and its parameters.  The generator does not draw
from the law: it cuts it into a fixed grid of ``cycle`` quantiles, so
every seed offers the SAME multiset of (prompt, output, sampled) triples,
cycle after cycle, and for an open loop the same multiset of arrival gaps.
The seed decides the order, the token ids and the sampler seeds — never
how much work a second of traffic holds.  (PR 22 drew lengths per seed;
with some tens of requests to a window the prompt tokens prefilled moved
by several percent from seed to seed, and that was read as noise in the
chip.)

``order`` (required) says how far the seed decides the order, and each
value is there because a cell measured steadier under it than under the
other (chip runs, PR 23; PERF.md §4).  ``permute``: a fresh permutation of
every cycle — the closed loop, where one fixed order makes the window's
median depend on which stretch of the cycle it holds (``tpot_p50_ms``
spread 8% against 1.6%).  ``rotate``: one base order of the cycle and of
its gaps, fixed by ``ORDER_SEED``, repeated cycle after cycle and entered
at a phase the seed picks — the open loop, where freshly permuted bursts
land differently against the engine's long fused steps with every seed
(``tpot_p50_ms`` spread 2.5-4.7% against 1.0-1.3%).

Laws: ``log_uniform`` (lo, hi), ``gamma`` (shape; mean fixed by the
caller).  A new mix is a new file; a new law is a new entry in ``LAWS``.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ORDER_SEED = 0      # the base order of a ``rotate`` file's cycle


def _gamma_ppf(q: np.ndarray, shape: float) -> np.ndarray:
    from scipy.special import gammaincinv

    return gammaincinv(shape, q)


def _grid(n: int) -> np.ndarray:
    """Mid-point quantiles of an n-cell grid: (i + 1/2) / n."""
    return (np.arange(n) + 0.5) / n


LAWS = {
    "log_uniform": lambda q, p: np.exp(
        math.log(p["lo"]) + q * (math.log(p["hi"]) - math.log(p["lo"]))),
    "gamma": lambda q, p: _gamma_ppf(q, float(p["shape"])),
}


def quantile_grid(law: dict, n: int) -> np.ndarray:
    """The law's values at the n mid-point quantiles, ascending floats."""
    if law["law"] not in LAWS:
        raise ValueError(f"unknown law {law['law']!r}; known: {sorted(LAWS)}")
    return np.asarray(LAWS[law["law"]](_grid(n), law), np.float64)


def length_grid(law: dict, n: int) -> np.ndarray:
    """Integer lengths on the grid, clipped into [lo, hi] when stated."""
    v = np.rint(quantile_grid(law, n)).astype(np.int64)
    if "lo" in law:
        v = np.clip(v, int(law["lo"]), int(law["hi"]))
    return np.maximum(v, 1)


@dataclasses.dataclass(frozen=True)
class Spec:
    """One request of the stream, before it becomes the program's
    ``Request``: the generator knows nothing of the program."""

    index: int
    prompt: np.ndarray          # int32 token ids
    max_new: int
    sampled: bool
    sampler_seed: int
    gap_s: float                # open loop: time after the previous arrival


class Traffic:
    """The stream of one traffic file under one seed.

    ``pairs`` is the cycle's multiset — row i = (prompt length, output
    length, sampled) — and does not depend on the seed.  Prompt quantile i
    is paired with output quantile ``(i * pairing_stride) % cycle`` (a
    stride coprime to the cycle, so long prompts do not all get long
    answers), and every ``sampled_every``-th pair is sampled.
    """

    def __init__(self, params: dict, seed: int, *, vocab: int,
                 scale: float = 1.0):
        self.params = params
        self.loop = params["loop"]
        if self.loop not in ("closed", "open"):
            raise ValueError(f"loop must be closed or open, got {self.loop}")
        k = self.cycle = int(params["cycle"])
        stride = int(params.get("pairing_stride", 1))
        if math.gcd(stride, k) != 1:
            raise ValueError(f"pairing_stride {stride} must be coprime to "
                             f"cycle {k}")
        p = length_grid(_scaled(params["prompt"], scale), k)
        o = length_grid(_scaled(params["output"], scale), k)
        every = int(params.get("sampled_every", 0))
        self.pairs = [(int(p[i]), int(o[(i * stride) % k]),
                       bool(every) and i % every == 1) for i in range(k)]
        self.sampler = params.get("sampler", {})
        self.gaps = None
        if self.loop == "open":
            g = quantile_grid(params["gaps"], k)
            # the multiset's mean is exactly 1/rate, whatever the law's
            self.gaps = g / g.mean() / float(params["rate_per_s"])
        self.vocab = int(vocab)
        self.order = params["order"]
        if self.order not in ("permute", "rotate"):
            raise ValueError(f"order must be permute or rotate, got "
                             f"{self.order}")
        # the seed may pass 2**31; SeedSequence takes any non-negative int
        self._order = np.random.default_rng([int(seed), 1])
        if self.order == "rotate":
            base = np.random.default_rng([ORDER_SEED, k])
            phase = int(self._order.integers(k))
            self._base = np.roll(base.permutation(k), -phase)
            self._base_gaps = (np.roll(base.permutation(self.gaps), -phase)
                               if self.gaps is not None else np.zeros(k))
        self._tokens = np.random.default_rng([int(seed), 2])
        self._seed = int(seed)
        self._n = 0
        self._queue: list = []

    def multiset(self) -> dict:
        """What a cycle offers, for the self-check and the report line."""
        out = {"pairs": sorted(self.pairs)}
        if self.gaps is not None:
            out["gaps"] = sorted(float(x) for x in self.gaps)
        return out

    def next(self) -> Spec:
        if not self._queue:
            if self.order == "rotate":
                order, gaps = self._base, self._base_gaps
            else:
                order = self._order.permutation(self.cycle)
                gaps = (self._order.permutation(self.gaps)
                        if self.gaps is not None else np.zeros(self.cycle))
            self._queue = [(int(i), float(g)) for i, g in zip(order, gaps)]
        i, gap = self._queue.pop(0)
        n_prompt, n_out, sampled = self.pairs[i]
        spec = Spec(index=self._n,
                    prompt=self._tokens.integers(
                        0, self.vocab, size=n_prompt).astype(np.int32),
                    max_new=n_out, sampled=sampled,
                    sampler_seed=(self._seed + self._n) % (2 ** 31),
                    gap_s=gap)
        self._n += 1
        return spec


def _scaled(law: dict, scale: float) -> dict:
    """The toy rehearsal shrinks lengths; a chip run passes scale 1."""
    if scale == 1.0:
        return law
    out = dict(law)
    for key in ("lo", "hi"):
        if key in out:
            out[key] = max(1, int(out[key] * scale))
    return out


def load(name: str) -> dict:
    """``benchmarks/traffic/<name>.json``."""
    path = os.path.join(HERE, "traffic", f"{name}.json")
    with open(path) as f:
        return json.load(f)
