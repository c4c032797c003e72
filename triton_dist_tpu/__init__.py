"""triton_dist_tpu — a TPU-native distributed compute/communication-overlap framework.

This package provides the capabilities of ByteDance's Triton-distributed
(reference: github.com/ByteDance-Seed/Triton-distributed) re-designed from
scratch for TPU hardware:

- **Runtime** (`triton_dist_tpu.runtime`): bootstrap, device mesh management,
  symmetric-memory abstraction, topology introspection, benchmarking and
  profiling utilities.  (Reference analog: ``python/triton_dist/utils.py`` +
  ``pynvshmem``.)
- **Language** (`triton_dist_tpu.language`): the distributed primitive toolkit
  usable inside Pallas kernels — ``wait`` / ``notify`` / ``symm_at`` /
  ``putmem_*`` / barriers — built on Mosaic device semaphores and async remote
  DMA over ICI.  (Reference analog: the MLIR ``distributed`` dialect +
  ``triton_dist.language`` + ``libshmem_device``.)
- **Kernels** (`triton_dist_tpu.kernels`): the distributed kernel library —
  allgather (ring/pull/push/low-latency), reduce-scatter, overlapped
  AllGather-GEMM and GEMM-ReduceScatter, MoE dispatch/combine all-to-all,
  distributed flash-decode.  (Reference analog:
  ``python/triton_dist/kernels/nvidia``.)
- **Layers** (`triton_dist_tpu.layers`): model-facing modules
  (sequence-parallel decode attention, EP all-to-all layer, allgather layer,
  TP linear layers).  (Reference analog: ``python/triton_dist/layers``.)
- **Models** (`triton_dist_tpu.models`): end-to-end model families (Llama-style
  dense transformer, Mixtral/DeepSeek-style MoE) wired through the kernels.
- **Tools** (`triton_dist_tpu.tools`): contextual autotuner, AOT export,
  analytic performance models.

Design stance (TPU-first, not a port):

* SPMD over ``jax.sharding.Mesh`` + ``shard_map`` replaces
  torchrun/NCCL/NVSHMEM process groups.  Rank = ``jax.lax.axis_index``.
* The NVSHMEM symmetric heap maps to SPMD symmetry: under ``shard_map`` every
  device holds an identically-shaped shard, so "symmetric buffers" are just
  sharded arrays; remote addressing is Mosaic remote DMA by logical device id.
* CUDA streams map to Mosaic async DMA queued against MXU compute *inside one
  fused Pallas kernel* (TPU exposes no user streams; overlap lives in-kernel).
* Every collective op has two interchangeable backends: ``"xla"`` (lax
  collectives — XLA's latency-hiding scheduler is the baseline to beat) and
  ``"pallas"`` (hand-scheduled kernels with remote DMA + semaphores).
"""

__version__ = "0.1.0"

# Importing this package initialises no JAX backend (tests/test_chip_smoke.py
# holds it to that): on a TPU host the first process to touch a backend owns
# the chip, so entry points decide when that happens, not an import.
from triton_dist_tpu.runtime import (  # noqa: F401
    initialize_distributed,
    get_mesh,
    assert_allclose,
    dist_print,
    perf_func,
)
