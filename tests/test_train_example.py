"""examples/train.py: the end-to-end resumable trainer CLI."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "examples", "train.py")


def _run(tmp, *extra):
    # Fresh env recipe (the conftest-initialized in-process jax can't be
    # reused across a fork safely): same knobs as runtime/testenv.py.
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    out = subprocess.run(
        [sys.executable, SCRIPT, "--ckpt-dir", str(tmp), "--seq", "16",
         "--batch", "2", *extra],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout


def test_train_and_resume_llama(tmp_path):
    first = _run(tmp_path, "--model", "llama", "--steps", "4",
                 "--ckpt-every", "2")
    assert "step    3" in first and "done" in first
    assert "resumed" not in first
    second = _run(tmp_path, "--model", "llama", "--steps", "6",
                  "--ckpt-every", "2")
    assert "resumed from step 3" in second
    assert "step    4" in second and "step    5" in second
    # Heartbeat file was maintained next to the checkpoints.
    assert (tmp_path / "heartbeat.0").exists()


def test_train_moe_dp(tmp_path):
    out = _run(tmp_path, "--model", "moe", "--dp", "2", "--steps", "3",
               "--ckpt-every", "10")
    assert "mesh {'dp': 2, 'tp': 4}" in out and "done" in out
