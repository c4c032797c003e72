"""Contextual autotuner for whole multi-kernel distributed ops.

Reference analog: ``python/triton_dist/autotuner.py`` — ``contextual_autotune``
monkey-patches ``Autotuner.run`` so that a *whole op* (which may invoke
several autotuned Triton kernels, each needing the op's surrounding context:
symm buffers, barriers, streams) is re-executed until every inner autotuner's
config sweep completes, one config-iteration per outer call (:105-127,
:160-245); in ``is_dist`` mode timings are all-reduced (MAX) so every rank
picks the same config (:225-231); per-rank logs go to ``.autotune_logs/``.

TPU-native design: same two-level protocol, with the measurement layer
re-based on JAX:

- A config is a plain dict of keyword overrides (``{"bm": 256, "bn": 512}``)
  merged into the wrapped function's kwargs — our Pallas kernels take block
  sizes as kwargs, not compile-time metaparameters.
- Timing is host-side ``perf_counter`` around ``jax.block_until_ready`` (no
  CUDA events on TPU; dispatch is async the same way, so the block is the
  fence).
- The lockstep property the reference gets from one-bench-iteration-per-
  outer-call is preserved: inside a ``contextual_autotune`` region each call
  of the outer thunk advances every unfinished inner tuner by exactly one
  (config, iteration) step, so multi-process shard_map collectives stay in
  step across ranks (same config order is guaranteed because configs are a
  static list and failures — Mosaic compile errors — are deterministic).
- Distributed agreement: after a tuner's sweep completes, per-config mean
  times are all-reduced with MAX across processes via a one-element global
  sum (``multihost_utils``) so every process selects the same config.
"""

from __future__ import annotations

import math
import os
import time
from typing import Any, Callable, Sequence

import jax
import numpy as np

__all__ = ["autotune", "contextual_autotune", "Config"]


def Config(**kwargs) -> dict:
    """A tunable config: keyword overrides for the wrapped function.

    (Reference: ``triton.Config``; ours is a plain dict since Pallas block
    sizes are ordinary kwargs.)
    """
    return dict(kwargs)


def _allreduce_max(times: Sequence[float]) -> list[float]:
    """MAX-allreduce per-config times across processes (identity single-host).

    Reference: autotuner.py:225-231 (torch.distributed.all_reduce MAX).
    """
    if jax.process_count() == 1:
        return list(times)
    from jax.experimental import multihost_utils

    arr = np.asarray(times, np.float64)
    gathered = multihost_utils.process_allgather(arr)  # [n_proc, n_cfg]
    return np.max(gathered, axis=0).tolist()


class _TuningState:
    """Per-(tuner, key) sweep state. Reference: ``_TuningContext``."""

    def __init__(self, configs: list[dict]):
        self.configs = configs
        self.cfg_i = 0
        self.iter_j = 0
        self.cur_times: list[float] = []
        self.okay: list[tuple[int, dict]] = []
        self.times: list[float] = []
        self.finished = False


class ContextualAutotuner:
    """Callable wrapping a whole op; active instance gates inner tuners."""

    _INSTANCE: "ContextualAutotuner | None" = None

    def __init__(self, fn: Callable, is_dist: bool = False, n_repeat: int = 5,
                 n_warmup: int = 3, log_dir: str = ".autotune_logs"):
        self.fn = fn
        self.is_dist = is_dist
        self.n_repeat = n_repeat
        self.n_warmup = n_warmup
        self.log_dir = log_dir
        self._log_file = None
        # (owner AutotunedFunction, cache key, state) per active sweep.
        self._states: list[tuple] = []

    def log(self, *args):
        if self._log_file is None:
            os.makedirs(self.log_dir, exist_ok=True)
            rank = jax.process_index()
            self._log_file = open(
                os.path.join(self.log_dir, f"rank-{rank}.log"), "a")
        print(f"[rank-{jax.process_index()}]", *args, file=self._log_file,
              flush=True)

    def __call__(self, *args, **kwargs):
        if ContextualAutotuner._INSTANCE is not None:  # nested: run plainly
            return self.fn(*args, **kwargs)
        ContextualAutotuner._INSTANCE = self
        self._states = []
        try:
            ret = self.fn(*args, **kwargs)  # discovers inner tuners
            if not self._states:
                return ret  # nothing to tune (all cached already)
            while not all(st.finished for _, _, st in self._states):
                ret = self.fn(*args, **kwargs)
            # The sweep's last call ran whatever config came last, not the
            # winner; one more call hits every inner tuner's best-config
            # cache so the returned value matches the selected configs.
            return self.fn(*args, **kwargs)
        finally:
            # Purge unfinished sweeps from their owners so an aborted
            # region (kernel bug, no-valid-config) can't poison the next
            # one with stale per-key state.
            for owner, key, st in self._states:
                if not st.finished:
                    owner._states.pop(key, None)
            ContextualAutotuner._INSTANCE = None
            self._states = []


def contextual_autotune(is_dist: bool = False, n_repeat: int = 5,
                        n_warmup: int = 3):
    """Decorator: tune all inner ``@autotune`` functions within one op.

    Reference: autotuner.py:96-101.
    """

    def decor(fn):
        return ContextualAutotuner(fn, is_dist=is_dist, n_repeat=n_repeat,
                                   n_warmup=n_warmup)

    return decor


class AutotunedFunction:
    """``@autotune``-wrapped function with a per-key best-config cache."""

    def __init__(self, fn: Callable, configs: Sequence[dict],
                 key: Sequence[str] = (), prune: Callable | None = None,
                 measure: Callable | None = None):
        self.fn = fn
        self.configs = [dict(c) for c in configs]
        self.key_names = tuple(key)
        self.prune = prune
        self.measure = measure
        self.cache: dict[tuple, dict] = {}
        self._states: dict[tuple, _TuningState] = {}
        self.__name__ = getattr(fn, "__name__", "autotuned")

    # -- key: named kwargs + shape/dtype of array args + every scalar kwarg
    # (autotuner.py:173-183; scalar kwargs matter because e.g. interpret=True
    # timings must never be reused for hardware calls)
    def _key(self, args, kwargs) -> tuple:
        parts: list[Any] = [kwargs.get(k) for k in self.key_names]
        for a in args:
            if hasattr(a, "shape") and hasattr(a, "dtype"):
                parts.append((tuple(a.shape), str(a.dtype)))
        for k in sorted(kwargs):
            if k in self.key_names:
                continue
            v = kwargs[k]
            if hasattr(v, "shape") and hasattr(v, "dtype"):
                parts.append((tuple(v.shape), str(v.dtype)))
            else:
                parts.append((k, str(v)))
        return tuple(parts)

    def _configs_for(self, args, kwargs) -> list[dict]:
        if self.prune is None:
            return list(self.configs)
        pruned = self.prune(self.configs, args, kwargs)
        return list(pruned) if pruned else list(self.configs)

    def _run(self, args, kwargs, config):
        return self.fn(*args, **{**kwargs, **config})

    def _timed(self, args, kwargs, config) -> tuple[Any, float]:
        """(result, milliseconds) for one config invocation.

        The default is one call fenced by ``block_until_ready``.  Pass a
        custom ``measure`` where one call is too short to time against
        host dispatch (e.g. a chain of dependent calls timed as one:
        ``scripts/autotune_onchip.py`` at git ``d7c7cac`` did so).
        """
        if self.measure is not None:
            return self.measure(self.fn, args, kwargs, config)
        t0 = time.perf_counter()
        ret = self._run(args, kwargs, config)
        jax.block_until_ready(ret)
        return ret, (time.perf_counter() - t0) * 1e3

    def __call__(self, *args, **kwargs):
        if len(self.configs) <= 1:
            cfg = self.configs[0] if self.configs else {}
            return self._run(args, kwargs, cfg)
        key = self._key(args, kwargs)
        best = self.cache.get(key)
        if best is not None:
            return self._run(args, kwargs, best)
        tuner = ContextualAutotuner._INSTANCE
        if tuner is None:
            return self._tune_eager(key, args, kwargs)
        return self._tune_step(tuner, key, args, kwargs)

    # -- eager path: full sweep in one call (plain Autotuner.run analog).
    # No cross-process agreement here: eager calls need not be collective
    # (the contextual path with is_dist=True is the lockstep one).
    def _tune_eager(self, key, args, kwargs):
        configs = self._configs_for(args, kwargs)
        okay, times = [], []
        last = None
        last_exc = None
        for i, cfg in enumerate(configs):
            try:
                if self.measure is not None:
                    # Custom hooks own their warmup/compile handling; a
                    # second full protocol run would only replay identical
                    # inputs (which a content-caching backend elides).
                    last, ms = self._timed(args, kwargs, cfg)
                else:
                    for _ in range(2):  # warmup (compile) + 1 measure
                        last, ms = self._timed(args, kwargs, cfg)
                okay.append((i, cfg))
                times.append(ms)
            except Exception as e:  # bad config; keep cause for diagnosis
                last_exc = e
                continue
        if not okay:
            raise RuntimeError(
                f"{self.__name__}: no valid config among {configs}"
            ) from last_exc
        (_, best), _ = min(zip(okay, times), key=lambda t: t[-1])
        self.cache[key] = best
        return self._run(args, kwargs, best) if last is None else last

    # -- contextual path: one (config, iter) step per outer-thunk call
    def _tune_step(self, tuner: ContextualAutotuner, key, args, kwargs):
        st = self._states.get(key)
        if st is None:
            st = self._states[key] = _TuningState(
                self._configs_for(args, kwargs))
            tuner._states.append((self, key, st))

        n_iters = tuner.n_warmup + tuner.n_repeat
        while st.cfg_i < len(st.configs):
            cfg = st.configs[st.cfg_i]
            try:
                ret, ms = self._timed(args, kwargs, cfg)
                if ret is None:
                    # Measure hooks may time a surrogate (e.g. a chain) and
                    # return no result; the surrounding contextual op still
                    # needs a real output this iteration.
                    ret = self._run(args, kwargs, cfg)
            except Exception as e:  # bad config (e.g. Mosaic tiling error)
                tuner.log(f"func: {self.__name__} | config {st.cfg_i} "
                          f"{cfg} | error: {e}")
                self._advance_config(tuner, key, ok=False)
                if st.finished:
                    return self._run(args, kwargs, self.cache[key])
                continue
            if st.iter_j >= tuner.n_warmup:
                st.cur_times.append(ms)
            tuner.log(f"func: {self.__name__} | config {st.cfg_i} {cfg} | "
                      f"iter {st.iter_j} | {ms:.4f} ms")
            st.iter_j += 1
            if st.iter_j >= n_iters:
                self._advance_config(tuner, key, ok=True)
            return ret
        raise AssertionError("unreachable")

    def _advance_config(self, tuner, key, ok: bool):
        st = self._states[key]
        if ok:
            st.okay.append((st.cfg_i, st.configs[st.cfg_i]))
            st.times.append(float(np.mean(st.cur_times)))
        st.cur_times = []
        st.iter_j = 0
        st.cfg_i += 1
        if st.cfg_i < len(st.configs):
            return
        # sweep complete: agree on the best config
        if not st.okay:
            raise RuntimeError(
                f"{self.__name__}: no valid config among {st.configs}")
        times = _allreduce_max(st.times) if tuner.is_dist else st.times
        (best_i, best), best_ms = min(
            zip(st.okay, times), key=lambda t: t[-1])
        tuner.log(f"func: {self.__name__} | best-config-id: {best_i} | "
                  f"best-config: {best} | best-latency: {best_ms:.4f} ms")
        self.cache[key] = best
        st.finished = True
        del self._states[key]

    @property
    def best_config(self) -> dict | None:
        """Most recently selected config (None before any tuning)."""
        return next(iter(reversed(self.cache.values())), None)


def autotune(configs: Sequence[dict], key: Sequence[str] = (),
             prune: Callable | None = None, measure: Callable | None = None):
    """Decorator marking a function tunable over ``configs``.

    Reference: ``triton.autotune``; config kwargs are merged into the call's
    kwargs, later tuners pick per-``key`` cached bests.  ``prune(configs,
    args, kwargs)`` may drop redundant configs per call (reference:
    ``prune_configs_by``) — e.g. dedupe block sizes that clamp identically
    for a small shape.  ``measure(fn, args, kwargs, config) -> (ret, ms)``
    overrides the timing protocol (see ``_timed`` for when you must).
    """

    def decor(fn):
        return AutotunedFunction(fn, configs, key, prune, measure)

    return decor
