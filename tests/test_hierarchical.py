"""Hierarchical collectives + multi-slice topology + launcher tests.

Reference analog: the inter-node 2D variants (allgather.py:470-591,
reduce_scatter.py:842-860) and launch.sh's multi-node contract.
"""

import functools
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from triton_dist_tpu.kernels.allgather import AllGatherMethod
from triton_dist_tpu.kernels.hierarchical import (
    hier_all_gather_shard,
    hier_reduce_scatter_shard,
    hier_rs_band_index,
)
from triton_dist_tpu.kernels.reduce_scatter import ReduceScatterMethod
from triton_dist_tpu.runtime import topology

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def mesh2x4():
    devs = np.array(jax.devices()[:8]).reshape(2, 4)
    return Mesh(devs, ("dcn", "tp"))


def test_hier_allgather_flat_order(mesh2x4, key):
    x = jax.random.normal(key, (16 * 8, 128), jnp.float32)
    fn = jax.jit(jax.shard_map(
        functools.partial(hier_all_gather_shard, slow_axis="dcn",
                          fast_axis="tp", interpret=True,
                          fast_method=AllGatherMethod.RING_BIDIR),
        mesh=mesh2x4, in_specs=P(("dcn", "tp"), None),
        out_specs=P(None, None), check_vma=False))
    np.testing.assert_allclose(np.asarray(fn(x)), np.asarray(x))


def test_hier_reduce_scatter_band_order(mesh2x4, key):
    world = 8
    parts = jax.random.normal(key, (world, world * 8, 128), jnp.float32)

    def shard_fn(p):
        band = hier_reduce_scatter_shard(
            p[0], slow_axis="dcn", fast_axis="tp", interpret=True,
            fast_method=ReduceScatterMethod.RING_1D)
        return band, hier_rs_band_index("dcn", "tp")[None]

    fn = jax.jit(jax.shard_map(
        shard_fn, mesh=mesh2x4, in_specs=P(("dcn", "tp")),
        out_specs=(P(("dcn", "tp")), P(("dcn", "tp"))), check_vma=False))
    bands, idx = fn(parts)
    bands, idx = np.asarray(bands), np.asarray(idx)
    want = np.sum(np.asarray(parts), axis=0)
    # device (i, j) (linear d = i*4+j) holds flat band j*2+i
    rows = want.shape[0] // world
    for d in range(world):
        b = int(idx[d])
        np.testing.assert_allclose(bands[d * rows:(d + 1) * rows],
                                   want[b * rows:(b + 1) * rows],
                                   rtol=1e-3, atol=1e-3,
                                   err_msg=f"device {d} band {b}")


def test_hier_ag_xla_impl_matches(mesh2x4, key):
    """XLA per-axis impls give the same flat order (the multi-process path)."""
    x = jax.random.normal(key, (16 * 8, 128), jnp.float32)
    fn = jax.jit(jax.shard_map(
        functools.partial(hier_all_gather_shard, slow_axis="dcn",
                          fast_axis="tp",
                          slow_method=AllGatherMethod.XLA,
                          fast_method=AllGatherMethod.XLA),
        mesh=mesh2x4, in_specs=P(("dcn", "tp"), None),
        out_specs=P(None, None), check_vma=False))
    np.testing.assert_allclose(np.asarray(fn(x)), np.asarray(x))


def test_create_hybrid_mesh_single_process():
    mesh = topology.create_hybrid_mesh({"tp": jax.device_count()})
    assert mesh.axis_names == ("dcn", "tp")
    assert mesh.devices.shape == (1, jax.device_count())


def test_slice_index_defaults_zero():
    assert topology.slice_index(jax.devices()[0]) == 0
    assert topology.n_slices() == 1


def test_launcher_two_process_hier_allgather():
    """Full multi-process story: launch.py spawns 2 JAX processes that build
    a hybrid mesh over gloo-connected CPU devices and run the hierarchical
    AG cross-process (reference: torchrun multi-node tests)."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # workers set their own device counts
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "launch.py"),
         "--nproc", "2", "--devices-per-proc", "2",
         os.path.join(REPO, "tests", "workers", "mp_worker.py")],
        capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.count("MP_WORKER_OK") == 2, out.stdout


def test_launcher_refuses_many_processes_on_one_tpu_host():
    """``--real-tpu --nproc N``: N local children would each open every
    chip of the host, and a chip belongs to one process at a time — the
    second hangs in backend init.  Refused up front, naming the form
    that works."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "launch.py"),
         "--real-tpu", "--nproc", "2", "worker.py"],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 2, out.stderr[-2000:]
    assert "one process at a time" in out.stderr and \
        "one N-chip mesh" in out.stderr, out.stderr[-2000:]


def test_launcher_tears_down_on_worker_failure(tmp_path):
    """A worker that dies must not leave the launcher (or peers) hanging."""
    bad = tmp_path / "bad_worker.py"
    bad.write_text("import sys, os\n"
                   "if os.environ['JAX_PROCESS_ID'] == '1':\n"
                   "    sys.exit(3)\n"
                   "import time\n"
                   "time.sleep(60)\n")
    env = dict(os.environ)
    t0 = __import__("time").time()
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "launch.py"),
         "--nproc", "2", str(bad)],
        capture_output=True, text=True, timeout=120, env=env)
    assert out.returncode != 0
    assert __import__("time").time() - t0 < 30, "launcher failed to tear down"


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_hier_all_to_all_matches_flat(impl, mesh2d, key):
    """Two-tier a2a == flat fast_all_to_all on a 2x4 (dp x tp) mesh."""
    from triton_dist_tpu.kernels.all_to_all import fast_all_to_all_shard
    from triton_dist_tpu.kernels.hierarchical import hier_all_to_all_shard
    from triton_dist_tpu.runtime.jit_cache import cached_shard_jit

    world, T, H = 8, 4, 32
    x = jax.random.normal(key, (world * world, T, H), jnp.float32)
    splits = jax.random.randint(jax.random.fold_in(key, 1),
                                (world * world,), 0, T + 1, jnp.int32)

    def flat(send, sp, *, impl, interpret):
        return fast_all_to_all_shard(
            send, sp, axis=("dp", "tp"), impl="xla", interpret=interpret)

    def hier(send, sp, *, impl, interpret):
        return hier_all_to_all_shard(send, sp, slow_axis="dp",
                                     fast_axis="tp", impl=impl,
                                     interpret=interpret)

    specs = (P(("dp", "tp")), P(("dp", "tp")))
    out_specs = (P(("dp", "tp")), P(("dp", "tp")))
    f_flat = cached_shard_jit(flat, mesh2d, specs, out_specs,
                              impl="xla", interpret=False)
    f_hier = cached_shard_jit(hier, mesh2d, specs, out_specs,
                              impl=impl, interpret=(impl == "pallas"))
    r_ref, s_ref = f_flat(x, splits)
    r_got, s_got = f_hier(x, splits)
    np.testing.assert_array_equal(np.asarray(s_got), np.asarray(s_ref))
    # Valid rows must match the flat reference exactly; the two-tier
    # path's padding rows are defined ZERO (r3 compacting repack — the
    # xla flat reference instead preserves send padding, so a full-buffer
    # compare would test send garbage).
    r_ref = np.asarray(r_ref)
    r_got = np.asarray(r_got)
    s_np = np.asarray(s_ref)
    for b in range(world * world):
        k = int(s_np[b])
        np.testing.assert_allclose(r_got[b, :k], r_ref[b, :k],
                                   rtol=0, atol=0)
        np.testing.assert_array_equal(r_got[b, k:], 0.0)


def test_hier_all_reduce_matches_psum(mesh2x4, key):
    """RS[fast] -> psum[slow] -> AG[fast] == a flat psum over both axes."""
    from triton_dist_tpu.kernels.hierarchical import hier_all_reduce_shard

    x = jax.random.normal(key, (2, 4, 32, 128), jnp.float32)

    def shard_fn(parts):
        i = jax.lax.axis_index("dcn")
        j = jax.lax.axis_index("tp")
        mine = parts[i, j]
        hier = hier_all_reduce_shard(mine, slow_axis="dcn", fast_axis="tp",
                                     interpret=True)
        flat = jax.lax.psum(mine, ("dcn", "tp"))
        return hier, flat

    got, want = jax.jit(jax.shard_map(
        shard_fn, mesh=mesh2x4, in_specs=P(), out_specs=(P(), P()),
        check_vma=False))(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_hier_grad_allreduce_tree(mesh2x4, key):
    """Tree bucketing: ragged leaf shapes/dtypes, one banded reduction."""
    from triton_dist_tpu.kernels.hierarchical import hier_grad_allreduce

    ks = jax.random.split(key, 3)
    tree = {
        "w": jax.random.normal(ks[0], (2, 4, 17, 5), jnp.float32),
        "b": jax.random.normal(ks[1], (2, 4, 3), jnp.float32),
        "e": jax.random.normal(ks[2], (2, 4, 2, 2, 7), jnp.bfloat16),
    }

    def shard_fn(parts):
        i = jax.lax.axis_index("dcn")
        j = jax.lax.axis_index("tp")
        mine = jax.tree.map(lambda p: p[i, j], parts)
        hier = hier_grad_allreduce(mine, slow_axis="dcn", fast_axis="tp",
                                   interpret=True)
        flat = jax.tree.map(lambda g: jax.lax.psum(g, ("dcn", "tp")), mine)
        return hier, flat

    got, want = jax.jit(jax.shard_map(
        shard_fn, mesh=mesh2x4, in_specs=(P(),), out_specs=(P(), P()),
        check_vma=False))(tree)
    for name in tree:
        np.testing.assert_allclose(np.asarray(got[name], dtype=np.float32),
                                   np.asarray(want[name], dtype=np.float32),
                                   rtol=1e-2, atol=1e-2, err_msg=name)


def test_pp_hybrid_hier_dp_matches_plain(key):
    """The hybrid dcn x pp x tp MoE step with the hierarchical dp grad
    path == the plain psum dp step (same function, re-bracketed sums)."""
    from triton_dist_tpu.models import moe as MoE
    from triton_dist_tpu.models import pp as PP

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 2, 2),
                ("dcn", "pp", "tp"))
    cfg = MoE.MoEConfig.tiny()
    tokens = jax.random.randint(jax.random.key(7), (16, 8), 0, cfg.vocab)
    targets = jnp.roll(tokens, -1, axis=0)
    losses = {}
    for hier in (None, "tp"):
        params = PP.place_pp_params(PP.init_pp_params(cfg, key), cfg, mesh)
        step, _ = PP.make_pp_train_step(
            cfg, mesh, dp_axis="dcn", n_micro=2, impl="xla",
            interpret=True, lr=0.3, hier_dp_fast_axis=hier)
        params, l0 = step(params, tokens, targets)
        _, l1 = step(params, tokens, targets)
        losses[hier] = (float(l0), float(l1))
    np.testing.assert_allclose(losses["tp"], losses[None], rtol=2e-4)
