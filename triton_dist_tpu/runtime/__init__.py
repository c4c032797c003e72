"""Host runtime: bootstrap, mesh, symmetric memory, topology, benchmarking.

Reference analog: ``python/triton_dist/utils.py`` (initialize_distributed,
perf_func, dist_print, assert_allclose, topology detection) and
``shmem/nvshmem_bind/pynvshmem`` (symmetric tensors).
"""

from triton_dist_tpu.runtime.bootstrap import (  # noqa: F401
    initialize_distributed,
    finalize_distributed,
    get_mesh,
    set_mesh,
    default_mesh,
    rank,
    num_ranks,
    init_seed,
    configure_compile_cache,
    require_tpu,
)
from triton_dist_tpu.runtime.utils import (  # noqa: F401
    assert_allclose,
    dist_print,
    perf_func,
    make_tensor,
    generate_data,
)
from triton_dist_tpu.runtime.symm_mem import (  # noqa: F401
    create_symm_tensor,
    SymmetricWorkspace,
)
from triton_dist_tpu.runtime.topology import (  # noqa: F401
    TopologyInfo,
    detect_topology,
    is_tpu,
    device_kind,
    ici_bandwidth_gbps,
    hbm_bandwidth_gbps,
    peak_bf16_tflops,
)
from triton_dist_tpu.runtime.profiling import group_profile  # noqa: F401
from triton_dist_tpu.runtime.checkpoint import (  # noqa: F401
    CheckpointManager,
)
from triton_dist_tpu.runtime.watchdog import (  # noqa: F401
    Heartbeat,
    WatchdogTimeout,
    block_until_ready_with_timeout,
    run_with_watchdog,
)
from triton_dist_tpu.runtime.faults import (  # noqa: F401
    FaultInjector,
    InjectedFault,
)
