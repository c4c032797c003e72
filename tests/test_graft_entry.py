"""The driver-facing hooks in ``__graft_entry__.py`` must work in ANY env.

The driver calls ``dryrun_multichip(8)`` in an environment that may name an
accelerator platform, where the first eager op would initialize that backend.
These tests pin the two properties the hook rests on:

1. importing the package initializes NO JAX backend (late pinning only works
   if nothing touches a device before ``dryrun_multichip`` runs);
2. ``dryrun_multichip`` run in a subprocess whose env *demands* a non-CPU
   platform still self-pins a virtual CPU mesh and completes.

Both run in subprocesses: backend state is process-global and the parent
pytest process already holds a CPU backend.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, env: dict) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=900)


def _hostile_env() -> dict:
    """An env that, untouched, would initialize a non-CPU backend."""
    env = dict(os.environ)
    # Undo conftest's pinning, then actively demand a wrong platform: jax
    # dies on platform resolution if the dryrun fails to override
    # JAX_PLATFORMS.
    env["JAX_PLATFORMS"] = "nonexistent_platform"
    env["XLA_FLAGS"] = ""  # no forced device count either
    return env


def test_package_import_initializes_no_backend():
    code = (
        "import jax\n"
        "from jax._src import xla_bridge as xb\n"
        "import triton_dist_tpu.models.llama, triton_dist_tpu.models.moe\n"
        "import triton_dist_tpu.models.pp, triton_dist_tpu.models.generate\n"
        "import triton_dist_tpu.models.speculative\n"
        "import triton_dist_tpu.layers.ep_a2a, triton_dist_tpu.autotuner\n"
        "import triton_dist_tpu.kernels.allgather_gemm\n"
        "import __graft_entry__\n"
        "assert not xb._backends, 'import initialized a backend'\n"
        "print('OK')\n")
    env = dict(os.environ)
    r = _run(code, env)
    assert r.returncode == 0 and "OK" in r.stdout, r.stderr[-2000:]


def test_dryrun_multichip_self_pins_cpu_mesh():
    code = ("from __graft_entry__ import dryrun_multichip\n"
            "dryrun_multichip(8)\n")
    r = _run(code, _hostile_env())
    assert r.returncode == 0, r.stderr[-2000:]
    assert "dcn=2 pp=2 tp=2" in r.stdout, r.stdout
    assert "DCN axis" in r.stdout, r.stdout


def test_dryrun_multichip_fails_loudly_when_backend_preinitialized():
    code = (
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "jax.devices()  # initialize a 1-device CPU backend first\n"
        "from __graft_entry__ import dryrun_multichip\n"
        "try:\n"
        "    dryrun_multichip(8)\n"
        "except RuntimeError as e:\n"
        "    assert 'already initialized' in str(e), e\n"
        "    print('LOUD')\n"
        "else:\n"
        "    raise SystemExit('expected RuntimeError')\n")
    env = _hostile_env()
    env["JAX_PLATFORMS"] = "cpu"
    r = _run(code, env)
    assert r.returncode == 0 and "LOUD" in r.stdout, (r.stdout, r.stderr[-2000:])
