"""Llama model: forward parity (xla vs pallas impls), train step sanity.

The model is the flagship integration test for the overlapped kernels:
forward AND backward run through ag_gemm / gemm_rs custom VJPs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from triton_dist_tpu.models.llama import (
    LlamaConfig,
    init_params,
    make_forward,
    make_train_step,
    place_params,
)
from triton_dist_tpu.runtime import assert_allclose


@pytest.fixture(scope="module")
def cfg():
    return LlamaConfig.tiny()


def _data(mesh, cfg, dp=False):
    key = jax.random.key(0)
    S, B = 128, 4
    tokens = jax.random.randint(key, (S, B), 0, cfg.vocab, jnp.int32)
    spec = P("tp", "dp") if dp else P("tp")
    return jax.device_put(tokens, NamedSharding(mesh, spec))


def test_forward_xla_vs_pallas_interpret(mesh4, cfg):
    params = init_params(cfg, jax.random.key(1))
    params = place_params(params, cfg, mesh4)
    tokens = _data(mesh4, cfg)

    logits_xla = make_forward(cfg, mesh4, impl="xla")(params, tokens)
    logits_pl = make_forward(cfg, mesh4, impl="pallas", interpret=True)(
        params, tokens)
    assert logits_xla.shape == (128, 4, cfg.vocab)
    assert_allclose(logits_pl, logits_xla, atol=2e-3, rtol=2e-3)


def test_train_step_decreases_loss(mesh4, cfg):
    params = init_params(cfg, jax.random.key(1))
    params = place_params(params, cfg, mesh4)
    tokens = _data(mesh4, cfg)
    targets = jnp.roll(tokens, -1, axis=0)

    step, _ = make_train_step(cfg, mesh4, impl="xla", lr=1e-2)
    losses = []
    for _ in range(4):
        params, loss = step(params, tokens, targets)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses
    assert np.isfinite(losses).all(), losses


def test_train_step_2d_mesh(mesh2d, cfg):
    """dp x tp mesh: the dryrun_multichip configuration."""
    params = init_params(cfg, jax.random.key(1))
    params = place_params(params, cfg, mesh2d)
    tokens = _data(mesh2d, cfg, dp=True)
    targets = jnp.roll(tokens, -1, axis=0)

    step, _ = make_train_step(cfg, mesh2d, axis="tp", dp_axis="dp", impl="xla",
                              lr=1e-2)
    params2, loss = step(params, tokens, targets)
    assert np.isfinite(float(loss))
    # One more step must also be finite (params stayed consistent).
    _, loss2 = step(params2, tokens, targets)
    assert np.isfinite(float(loss2))
    assert float(loss2) < float(loss)


def test_grads_match_single_device_reference(mesh2, cfg):
    """shard_map grads == plain jit grads on a replicated reference."""
    from triton_dist_tpu.models.llama import loss_shard, param_specs

    params = init_params(cfg, jax.random.key(1))
    S, B = 64, 2
    tokens = jax.random.randint(jax.random.key(2), (S, B), 0, cfg.vocab,
                                jnp.int32)
    targets = jnp.roll(tokens, -1, axis=0)

    # Distributed loss+grad (world=2, xla impl).
    step, _ = make_train_step(cfg, mesh2, impl="xla", lr=1.0)
    p_sharded = place_params(params, cfg, mesh2)
    t_sh = jax.device_put(tokens, NamedSharding(mesh2, P("tp")))
    y_sh = jax.device_put(targets, NamedSharding(mesh2, P("tp")))
    new_params, loss = step(p_sharded, t_sh, y_sh)

    # Single-logical-device reference: same math with world=1 semantics.
    import numpy as onp
    from jax.sharding import Mesh
    mesh1 = Mesh(onp.array(jax.devices()[:1]), ("tp",))
    step1, _ = make_train_step(cfg, mesh1, impl="xla", lr=1.0)
    p1 = place_params(params, cfg, mesh1)
    t1 = jax.device_put(tokens, NamedSharding(mesh1, P("tp")))
    y1 = jax.device_put(targets, NamedSharding(mesh1, P("tp")))
    new_params1, loss1 = step1(p1, t1, y1)

    assert_allclose(loss, loss1, atol=1e-5, rtol=1e-5)
    # Updated params must match: same grads regardless of sharding.
    flat, _ = jax.tree.flatten(new_params)
    flat1, _ = jax.tree.flatten(new_params1)
    for a, b in zip(flat, flat1):
        assert_allclose(a, b, atol=5e-4, rtol=5e-4)


def test_config_presets_match_reference_shapes():
    """Presets mirror the reference's --shape_id table
    (test_ag_gemm.py:149-154): K = dim, N = ffn_dim."""
    from triton_dist_tpu.models.llama import LlamaConfig
    from triton_dist_tpu.models.moe import MoEConfig

    table = {
        "llama3_8b": (4096, 14336),
        "llama3_70b": (8192, 28672),
        "llama3_405b": (16384, 53248),
        "mistral_7b": (4096, 14336),
        "qwen2_72b": (8192, 29568),
    }
    for name, (k, n) in table.items():
        cfg = getattr(LlamaConfig, name)()
        assert (cfg.dim, cfg.ffn_dim) == (k, n), name
        assert cfg.dim % cfg.n_heads == 0 and cfg.n_heads % cfg.n_kv_heads == 0

    a2a = MoEConfig.a2a_e128_k8_d7168()     # a shape preset, not a model
    assert (a2a.dim, a2a.n_experts, a2a.topk) == (7168, 128, 8)
