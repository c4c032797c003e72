"""Profiling subsystem: span metadata + cross-host trace gather.

Reference analog: ``group_profile`` / launch_metadata hooks
(utils.py:417-501, allgather_gemm.py:120-130).
"""

def test_annotate_metadata_lands_in_lowered_program():
    """VERDICT r3 #8: spans carry flops/bytes + roofline in the label, and
    the label is baked into the lowered program via named_scope (so device
    timelines show it, not just the host thread)."""
    import jax
    import jax.numpy as jnp

    from triton_dist_tpu.runtime.profiling import annotate

    def f(x):
        with annotate("myop", flops=123, bytes_accessed=456):
            return x * 2

    txt = jax.jit(f).lower(jnp.ones((4,), jnp.float32)).as_text(
        debug_info=True)
    assert "myop#flops=123#bytes=456" in txt, txt[:500]


def test_trace_gather_two_process_merged_timeline(tmp_path):
    """Cross-host gather: two processes with PRIVATE trace dirs; rank 0's
    merged timeline must contain both ranks' events (shipped over
    jax.distributed, no shared filesystem)."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "scripts", "launch.py"),
         "--nproc", "2", "--devices-per-proc", "1",
         os.path.join(repo, "tests", "workers", "profile_worker.py"),
         str(tmp_path)],
        capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-3000:])
    assert out.stdout.count("PROFILE_WORKER_OK") == 2, out.stdout
