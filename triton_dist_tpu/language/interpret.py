"""Interpret-mode configuration for CPU-mesh testing of distributed kernels.

The Mosaic TPU interpreter (``pltpu.InterpretParams``) simulates multi-device
Pallas — including cross-chip remote DMA and semaphores — on a virtual CPU
mesh.  This is the framework's "fake cluster" test backend (SURVEY.md §4: the
reference has no such thing; every reference test needs real GPUs).

We default to ``dma_execution_mode="eager"``: data movement happens at
``.start()``, matching the hardware guarantee that a receive-semaphore
increment implies the data has landed.  The default ``"on_wait"`` mode defers
DMA execution to semaphore waits, which breaks chained-RDMA patterns (ring
collectives forwarding a just-received chunk) that are correct on hardware.

Race detection (reference analog: the deliberate comm-stream slowdown
``_add_noise_workload_debug``, allgather.py:72-77) is available by running a
kernel with ``interpret_params(detect_races=True)`` — the interpreter's
vector-clock race detector reports unsynchronized accesses.
"""

from __future__ import annotations

from jax.experimental.pallas import tpu as pltpu


def _register_virtual_tpu_info() -> None:
    """Teach Pallas's hardware-info query about the CPU interpreter.

    ``pltpu.emit_pipeline`` (and other Mosaic helpers) query
    ``tpu_info.get_tpu_info()`` for tiling decisions; on the virtual CPU mesh
    there is no TPU device kind, so we register a virtual chip — modeled on
    TPU v5p (the bench target) — via the module's public ``registry`` hook.
    """
    try:
        from jax._src.pallas.mosaic import tpu_info as _ti
    except ImportError:  # pragma: no cover - jax internals moved
        return
    reg = getattr(_ti, "registry", None)
    if reg is None or "cpu" in reg:
        return

    def _virtual_v5p() -> "_ti.TpuInfo":
        return _ti.TpuInfo(
            chip_version=_ti.ChipVersion.TPU_V5P,
            generation=5,
            num_cores=1,
            num_lanes=128,
            num_sublanes=8,
            mxu_column_size=128,
            vmem_capacity_bytes=64 * 1024 * 1024,
            cmem_capacity_bytes=0,
            smem_capacity_bytes=1024 * 1024,
            hbm_capacity_bytes=95_000_000_000 // 2,
            mem_bw_bytes_per_second=int(2.76e12) // 2,
            bf16_ops_per_second=int(4.59e14) // 2,
            int8_ops_per_second=int(9.18e14) // 2,
            fp8_ops_per_second=0,
            int4_ops_per_second=0,
        )

    reg["cpu"] = _virtual_v5p


_register_virtual_tpu_info()


def interpret_params(detect_races: bool = False) -> "pltpu.InterpretParams":
    return pltpu.InterpretParams(
        dma_execution_mode="eager",
        detect_races=detect_races,
    )


def maybe_interpret(interpret: bool, detect_races: bool = False):
    """The value to pass to ``pallas_call(interpret=...)``."""
    return interpret_params(detect_races) if interpret else False
