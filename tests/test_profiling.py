"""Profiling subsystem: span metadata.

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
