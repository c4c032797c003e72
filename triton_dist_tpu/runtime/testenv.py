"""Canonical virtual-mesh ("fake cluster") environment recipe.

IMPORT-FREE ON PURPOSE: this module must be loadable before jax exists in
the process (conftest.py and tutorials/_common.py load it by file path with
importlib so the package __init__ — which imports jax — never runs).  Keep
it free of any imports beyond the stdlib ``os``.

One source of truth for every place that fabricates the multi-device CPU
test world: tests/conftest.py, tutorials/_common.py, scripts/launch.py.
"""

import os


def virtual_mesh_env(env: dict | None = None, n_devices: int = 16) -> dict:
    """Return ``env`` (default: a copy of os.environ) updated for an
    ``n_devices``-device virtual CPU mesh:

    - ``JAX_PLATFORMS=cpu`` — never touch a real accelerator (a chip
      belongs to one process at a time, so a child that must not take it
      is pinned to the CPU here);
    - append ``--xla_force_host_platform_device_count=N`` to XLA_FLAGS.
    """
    env = dict(os.environ) if env is None else env
    flag = f"--xla_force_host_platform_device_count={n_devices}"
    if flag not in env.get("XLA_FLAGS", ""):
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + flag).strip()
    env["JAX_PLATFORMS"] = "cpu"
    return env


def apply_virtual_mesh_env(n_devices: int = 16) -> None:
    """In-place variant for os.environ (call BEFORE any jax import)."""
    os.environ.update(
        {k: v for k, v in virtual_mesh_env(dict(os.environ),
                                           n_devices).items()})
