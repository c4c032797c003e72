"""Shared tutorial setup: import this FIRST (before using jax).

Gives every tutorial the virtual multi-device CPU mesh (the "fake cluster"
test story the reference lacks — its tutorials need real GPUs under
torchrun, launch.sh:1-40; ours run anywhere).  On a real multi-chip TPU
deployment set ``TDT_TUTORIAL_REAL_TPU=1`` and the same code runs on
hardware with ``interpret=False``.

If jax was imported before this module (a site hook, an interactive
session), environment edits here would be too late — in that case we re-exec
the interpreter once with the corrected environment.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_N = int(os.environ.get("TDT_TUTORIAL_DEVICES", "16"))
_FLAG = f"--xla_force_host_platform_device_count={_N}"

INTERPRET = os.environ.get("TDT_TUTORIAL_REAL_TPU", "0") != "1"

if INTERPRET and not os.environ.get("_TDT_TUTORIAL_REEXEC"):
    import importlib.util

    _TESTENV = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "triton_dist_tpu", "runtime", "testenv.py")
    _spec = importlib.util.spec_from_file_location("_tdt_testenv", _TESTENV)
    _testenv = importlib.util.module_from_spec(_spec)
    _spec.loader.exec_module(_testenv)

    _env_ok = (
        _FLAG in os.environ.get("XLA_FLAGS", "")
        and os.environ.get("JAX_PLATFORMS") == "cpu"
        and "jax" not in sys.modules
    )
    if not _env_ok:
        env = _testenv.virtual_mesh_env(dict(os.environ), _N)
        env["_TDT_TUTORIAL_REEXEC"] = "1"
        os.execve(sys.executable, [sys.executable] + sys.argv, env)
