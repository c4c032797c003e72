"""Topology introspection: TPU generation, ICI/DCN layout, roofline numbers.

Reference analog: NVLink/PCIe/NUMA detection in ``utils.py``
(`get_has_fullmesh_nvlink` :761-773, `get_nvlink_max_speed` :621-625,
`calculate_pcie_bandwidth` :667-702, `get_numa_world_size` :776-786).

TPU-native design: the interesting topology facts are (a) device generation
(sets MXU TFLOPS + HBM bandwidth), (b) ICI link bandwidth and whether a mesh
axis rides ICI (intra-slice) or DCN (cross-slice), (c) whether the axis wraps
(torus) — determines whether a ring uses 1 or 2 hops per step.  These feed
the perf models (`triton_dist_tpu.kernels.perf_model`) and kernel variant
auto-selection, just as NVLink-vs-PCIe selects AG variants in the reference
(allgather.py:54-69).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax

# Per-generation roofline tables (public figures; bf16 dense TFLOPS per chip,
# HBM GB/s per chip, ICI GB/s per link per direction).
# Analog of the tensor-core TFLOPS tables in gemm_perf_model.py:233+.
_TPU_SPECS = {
    # name-substring: (bf16 TFLOPS, HBM GB/s, ICI GB/s/link, ici links)
    "v6e": (918.0, 1640.0, 3584.0 / 8, 4),  # Trillium
    "v6": (918.0, 1640.0, 448.0, 4),
    "v5p": (459.0, 2765.0, 4800.0 / 48, 6),
    "v5e": (197.0, 819.0, 1600.0 / 4, 4),
    "v5 lite": (197.0, 819.0, 400.0, 4),
    "v4": (275.0, 1228.0, 2400.0 / 6, 6),
    "v3": (123.0, 900.0, 70.0, 4),
}
# Virtual-device test meshes: matched by PLATFORM ``cpu`` only, never as a
# default — feeds the perf-model unit tests, not a device metric.
_CPU_SPEC = (0.5, 50.0, 10.0, 2)


@dataclass(frozen=True)
class TopologyInfo:
    device_kind: str
    n_devices: int
    n_processes: int
    bf16_tflops: float
    hbm_gbps: float
    ici_gbps_per_link: float
    ici_links: int
    is_tpu: bool

    @property
    def ici_gbps(self) -> float:
        """Aggregate per-chip ICI bandwidth (all links, one direction)."""
        return self.ici_gbps_per_link * self.ici_links


def device_kind() -> str:
    return jax.devices()[0].device_kind


def is_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def _lookup(kind: str, platform: str):
    """The roofline row for a device.  An accelerator that is not in the
    table is an error, not a default: a peak guessed for an unknown chip
    turns every utilization computed from it into fiction."""
    if platform == "cpu":
        return _CPU_SPEC
    k = kind.lower()
    for sub, spec in _TPU_SPECS.items():
        if sub in k:
            return spec
    raise ValueError(
        f"no roofline row for device_kind {kind!r} (platform "
        f"{platform!r}); add its published peaks to "
        f"runtime/topology.py:_TPU_SPECS with their source")


def detect_topology() -> TopologyInfo:
    kind = device_kind()
    tflops, hbm, ici, links = _lookup(kind, jax.devices()[0].platform)
    return TopologyInfo(
        device_kind=kind,
        n_devices=jax.device_count(),
        n_processes=jax.process_count(),
        bf16_tflops=tflops,
        hbm_gbps=hbm,
        ici_gbps_per_link=ici,
        ici_links=links,
        is_tpu=is_tpu(),
    )


def peak_bf16_tflops() -> float:
    return detect_topology().bf16_tflops


# Best *measured* dense-dot TFLOPS on each chip kind at the bench shape
# (M=8192 K=8192 N=3584 bf16; docs/perf.md "AG-GEMM").  A harness uses it
# as a self-consistency bound: no honest chain that also pays AG dispatch
# can beat XLA's own dense dot on the same chip at the same shape, so any
# reading above it is elision, not performance.
_MEASURED_DOT_CEILING = {"v5e": 189.7, "v5 lite": 189.7}


def measured_dot_ceiling_tflops() -> float:
    """Measured XLA-dot ceiling for this chip kind (bench shape).  A kind
    the table has no measurement for raises: the ceiling is a guard
    against impossible readings, and a guessed guard guards nothing."""
    kind = device_kind().lower()
    for sub, v in _MEASURED_DOT_CEILING.items():
        if sub in kind:
            return v
    raise ValueError(
        f"no measured dense-dot ceiling for device_kind "
        f"{device_kind()!r}; measure it (docs/perf.md 'AG-GEMM') and add "
        f"it to runtime/topology.py:_MEASURED_DOT_CEILING")


def hbm_bandwidth_gbps() -> float:
    return detect_topology().hbm_gbps


def ici_bandwidth_gbps() -> float:
    return detect_topology().ici_gbps


def slice_index(device) -> int:
    """Slice id of a TPU device (0 on single-slice / non-TPU).

    Multi-slice TPU deployments expose ``slice_index`` on each device; the
    DCN tier is "between different slice_index groups" (the reference's
    node boundary, COMM_SCOPE INTER_NODE).
    """
    return int(getattr(device, "slice_index", 0) or 0)


def n_slices() -> int:
    return len({slice_index(d) for d in jax.devices()})


def create_hybrid_mesh(ici_axes: dict[str, int] | None = None,
                       dcn_axis: str = "dcn", n_slow: int | None = None):
    """Build a (dcn, *ici) mesh where the leading axis crosses slices.

    Real multi-slice TPU: delegates to ``mesh_utils.create_hybrid_device_mesh``
    (DCN-aware device ordering).  Single-slice or CPU test meshes: the
    process boundary plays the slice boundary (processes are connected by
    gRPC/gloo, the test-world DCN), falling back to a plain split when
    single-process.

    ``n_slow`` overrides the slow-tier width — single-process virtual
    rigs (the driver's multichip gate) use it to SIMULATE a 2-slice
    deployment: the mesh then has the hybrid SHAPE and the hierarchical
    programs compile against it, with the actual slow wire absent.

    Reference analog: the nnodes x local_world topology of launch.sh +
    NVSHMEM teams; here it is just a mesh whose leading axis is the slow
    tier.
    """
    import numpy as np
    from jax.sharding import Mesh

    devices = jax.devices()
    slices = n_slices()
    n_proc = jax.process_count()
    # The slow tier is the slice boundary.  On non-TPU backends the process
    # boundary plays that role (gRPC/gloo between procs).  A single-slice
    # multi-host TPU pod has NO slow tier — all hosts share one ICI fabric —
    # so n_slow collapses to 1 there (keeps axis_is_dcn consistent).
    if n_slow is not None:
        pass  # caller-pinned (virtual-rig simulation)
    elif slices > 1:
        n_slow = slices
    elif devices[0].platform != "tpu":
        n_slow = max(n_proc, 1)
    else:
        n_slow = 1
    if ici_axes is None:
        ici_axes = {"tp": len(devices) // n_slow}
    n_fast = int(np.prod(list(ici_axes.values())))

    if slices > 1:
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_hybrid_device_mesh(
            mesh_shape=tuple(ici_axes.values()),
            dcn_mesh_shape=(n_slow,) + (1,) * (len(ici_axes) - 1),
            devices=devices)
        dev_array = dev_array.reshape((n_slow,) + tuple(ici_axes.values()))
    else:
        # process-major ordering: jax.devices() already groups by process.
        # A prefix is only safe on a SINGLE-process virtual rig (the
        # driver gate's 2x interpreter-starvation headroom); in a real
        # multi-process world a short prefix would silently drop whole
        # processes from the mesh — keep the loud exact-match there.
        n_need = n_slow * n_fast
        if n_proc <= 1:
            assert n_need <= len(devices), (n_slow, n_fast, len(devices))
        else:
            assert n_need == len(devices), (n_slow, n_fast, len(devices))
        dev_array = np.asarray(devices[:n_need]).reshape(
            (n_slow,) + tuple(ici_axes.values()))
    return Mesh(dev_array, (dcn_axis,) + tuple(ici_axes.keys()))


def axis_is_dcn(mesh, axis: str) -> bool:
    """True when the mesh axis spans hosts via DCN rather than ICI.

    On multi-slice deployments an axis whose devices live in different
    processes crosses DCN.  (Analog: COMM_SCOPE INTER_NODE vs INTRA_NODE,
    DistributedAttrDefs.td:44-53.)
    """
    devs = mesh.devices
    import numpy as np

    ax = mesh.axis_names.index(axis)
    # Take a pencil of devices along `axis` and check their process indices.
    idx = [0] * devs.ndim
    pencil = [
        devs[tuple(idx[:ax] + [i] + idx[ax + 1:])] for i in range(devs.shape[ax])
    ]
    # A real multi-slice boundary (slice_index differs) is always DCN; a
    # process boundary is DCN on CPU/test backends (gRPC between procs) and
    # on multi-host TPU only when it also crosses slices (a v5p pod spans
    # many hosts on one ICI fabric).
    if len({slice_index(d) for d in pencil}) > 1:
        return True
    procs = {getattr(d, "process_index", 0) for d in pencil}
    return len(procs) > 1 and pencil[0].platform != "tpu"
