"""Bootstrap: process/mesh initialization for single- and multi-host TPU.

Reference analog: ``triton_dist.utils.initialize_distributed``
(/root/reference/python/triton_dist/utils.py:91-111) which does
torchrun env → ``torch.distributed.init_process_group("nccl")`` → seed →
``pynvshmem.init_nvshmem_by_uniqueid``.

TPU-native design: there is no separate SHMEM bootstrap — XLA's runtime owns
the ICI/DCN fabric.  ``initialize_distributed()``:

1. calls ``jax.distributed.initialize()`` when multi-host env vars are present
   (coordinator address via ``JAX_COORDINATOR_ADDRESS`` or TPU metadata),
2. builds the global device ``Mesh`` (1-D ``("tp",)`` by default, or an
   explicit multi-axis shape for tp/sp/dp/pp/ep),
3. seeds deterministic RNG per-process,
4. registers the mesh as the process-wide default used by the kernel library.

The "TP group over all ranks" of the reference maps to the mesh axis; rank =
``jax.lax.axis_index(axis)`` inside shard_map, or ``jax.process_index()`` on
the host.
"""

from __future__ import annotations

import os
from typing import Mapping, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

_MESH: Mesh | None = None
_INITIALIZED: bool = False

#: The checkout this package was imported from (``<root>/triton_dist_tpu/
#: runtime/bootstrap.py`` → ``<root>``).
CHECKOUT_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a place that can be
    chosen from OUTSIDE the program, and return that place.

    Every entry point that can run on the chip calls this first.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and this
    sets nothing; otherwise the cache lives at the fixed, git-ignored
    ``<checkout>/.jax_cache``.  The directory is part of every cache key,
    so it must never derive from ``tempfile``, a pid or the clock — a
    cache that moves never hits."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(CHECKOUT_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def require_tpu(what: str, n_devices: int = 1) -> None:
    """Refuse to go on unless this process sees ``n_devices`` TPU chips.

    For entry points whose output is about the device (the chip
    smokes, ``scripts/sweep_gemm.py``): a time or a rate taken on
    the CPU backend is not a slower device number, it is a different
    quantity, so there is no fallback — the process exits non-zero.
    Initialises the backend (a TPU process owns the chip from here on)."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"{what}: needs a TPU and found platform "
            f"{devs[0].platform!r} ({devs[0].device_kind}); what it "
            f"reports is about the device and the CPU backend cannot "
            f"stand in. Run it on the chip through the chip tool "
            f"(.claude/skills/verify/SKILL.md).")
    if len(devs) < n_devices:
        raise SystemExit(
            f"{what}: needs {n_devices} TPU chips, this host has "
            f"{len(devs)} ({devs[0].device_kind}).")


def init_seed(seed: int = 42) -> jax.Array:
    """Seeded, deterministic RNG key (reference: utils.py:75-88 init_seed).

    XLA is deterministic by construction for a fixed HLO; we only need a
    per-process base key.  Returns a ``jax.random.key``.
    """
    np.random.seed(seed)
    return jax.random.key(seed)


def initialize_distributed(
    mesh_shape: Mapping[str, int] | Sequence[int] | None = None,
    axis_names: Sequence[str] = ("tp",),
    seed: int = 42,
) -> Mesh:
    """Initialize the distributed runtime and return the global device mesh.

    Args:
      mesh_shape: either a dict ``{"dp": 2, "tp": 4}`` or a tuple matching
        ``axis_names``.  Default: all devices on a single ``"tp"`` axis.
      axis_names: names for the mesh axes when ``mesh_shape`` is a tuple/None.
      seed: deterministic seed (reference seeds torch/cuda with RANK-dependent
        seeds; XLA PRNG is counter-based so one base seed suffices).
    """
    global _MESH, _INITIALIZED
    if not _INITIALIZED:
        # Multi-host: initialize the JAX distributed system if a coordinator
        # is configured (GKE/TPU-VM set these; single-host runs skip it).
        # This MUST happen before any backend comes up — do not touch
        # jax.devices()/process_count() first.
        coord = os.environ.get("JAX_COORDINATOR_ADDRESS") or os.environ.get(
            "COORDINATOR_ADDRESS"
        )
        if coord and "JAX_NUM_PROCESSES" in os.environ:
            try:
                jax.distributed.initialize(
                    coordinator_address=coord,
                    num_processes=int(os.environ["JAX_NUM_PROCESSES"]),
                    process_id=int(os.environ.get("JAX_PROCESS_ID", "0")),
                )
            except RuntimeError as e:
                # Already initialized (by the launcher) or backends already
                # up (single-host dev flow) — proceed with what we have.
                if "already" not in str(e) and "must be called before" not in str(e):
                    raise
        _INITIALIZED = True

    init_seed(seed)

    devices = jax.devices()
    if mesh_shape is None:
        shape = {axis_names[0]: len(devices)}
        for ax in axis_names[1:]:
            shape[ax] = 1
    elif isinstance(mesh_shape, Mapping):
        shape = dict(mesh_shape)
    else:
        shape = dict(zip(axis_names, mesh_shape))

    n = int(np.prod(list(shape.values())))
    if n > len(devices):
        raise ValueError(
            f"mesh shape {shape} needs {n} devices, have {len(devices)}"
        )
    dev_array = np.asarray(devices[:n]).reshape(tuple(shape.values()))
    mesh = Mesh(dev_array, tuple(shape.keys()))
    _MESH = mesh
    return mesh


def finalize_distributed() -> None:
    """Tear down the global mesh (reference: nvshmem finalize)."""
    global _MESH
    _MESH = None


def set_mesh(mesh: Mesh) -> None:
    """Register an externally-built mesh as the process default."""
    global _MESH
    _MESH = mesh


def get_mesh() -> Mesh:
    """Return the registered global mesh, initializing a default if needed."""
    if _MESH is None:
        return initialize_distributed()
    return _MESH


def default_mesh(n_devices: int | None = None, axis: str = "tp") -> Mesh:
    """Build (without registering) a 1-D mesh over the first ``n_devices``."""
    devices = jax.devices() if n_devices is None else jax.devices()[:n_devices]
    return Mesh(np.asarray(devices), (axis,))


def rank(axis: str | Sequence[str] = "tp") -> jax.Array:
    """Device rank along ``axis``; only valid inside shard_map/pjit tracing.

    Reference analog: ``dl.rank()`` (language.py:84-88) →
    ``distributed.get_rank`` → ``nvshmem_my_pe``.
    """
    return jax.lax.axis_index(axis)


def num_ranks(axis: str | Sequence[str] = "tp") -> int:
    """World size along ``axis`` inside shard_map (reference: dl.num_ranks)."""
    return jax.lax.axis_size(axis)
