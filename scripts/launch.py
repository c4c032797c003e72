#!/usr/bin/env python
"""Multi-process launcher — the reference's launch.sh / torchrun analog.

Reference: launch.sh wraps torchrun with NVSHMEM env (NVSHMEM_SYMMETRIC_SIZE,
NVSHMEM_BOOTSTRAP=UID, CUDA_DEVICE_MAX_CONNECTIONS=1) and ARNOLD_* multi-node
vars (launch.sh:1-40).  The TPU analog:

* Single-host multi-process testing (the mode this script automates):
  spawn N local processes, each a JAX process with its own virtual CPU
  devices, connected by the JAX distributed runtime (gloo collectives over
  localhost — a faithful stand-in for DCN).  This is the "fake cluster"
  the reference cannot offer.
* Real TPU pods: one process per host is started by the platform (GKE /
  tpu-vm); `initialize_distributed()` picks up JAX_COORDINATOR_ADDRESS /
  JAX_NUM_PROCESSES / JAX_PROCESS_ID — the same env contract this script
  sets, so scripts are identical in both worlds.

Usage:
  python scripts/launch.py --nproc 2 [--devices-per-proc 4] script.py [args...]

Env given to each worker:
  JAX_COORDINATOR_ADDRESS, JAX_NUM_PROCESSES, JAX_PROCESS_ID (bootstrap
  contract), JAX_PLATFORMS=cpu, XLA_FLAGS device-count (test mesh), plus
  RANK/WORLD_SIZE aliases for reference-style scripts.
"""

import argparse
import importlib.util
import os
import signal
import socket
import subprocess
import sys
import time

# Load the canonical env recipe by file path: keeps the launcher jax-free
# (the package __init__ imports jax).
_TESTENV = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "triton_dist_tpu", "runtime", "testenv.py")
_spec = importlib.util.spec_from_file_location("_tdt_testenv", _TESTENV)
_testenv = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_testenv)
virtual_mesh_env = _testenv.virtual_mesh_env


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--nproc", type=int, default=2)
    p.add_argument("--devices-per-proc", type=int, default=4)
    p.add_argument("--coordinator", default=None,
                   help="host:port (default: localhost, fresh port)")
    p.add_argument("--real-tpu", action="store_true",
                   help="do not force the CPU backend (one process per "
                        "TPU HOST, started by the platform; on one host "
                        "only --nproc 1)")
    p.add_argument("script")
    p.add_argument("args", nargs=argparse.REMAINDER)
    a = p.parse_args()
    if a.real_tpu and a.nproc > 1:
        # Every local child would open every chip of this host, and a
        # chip belongs to one process: the second child hangs in backend
        # init.  Refuse instead.
        p.error(
            f"--real-tpu --nproc {a.nproc}: {a.nproc} processes on ONE "
            f"host would each open all of its chips, and a chip belongs "
            f"to one process at a time.  Supported on one TPU host: ONE "
            f"process driving N one-chip engines, or one N-chip mesh "
            f"(examples/serve.py --engine --mesh N).  Across hosts the "
            f"platform starts one process per host with the "
            f"JAX_COORDINATOR_ADDRESS contract this script documents.")

    coord = a.coordinator or f"127.0.0.1:{free_port()}"
    procs = []
    for r in range(a.nproc):
        env = dict(os.environ)
        env.update(
            JAX_COORDINATOR_ADDRESS=coord,
            JAX_NUM_PROCESSES=str(a.nproc),
            JAX_PROCESS_ID=str(r),
            RANK=str(r),
            WORLD_SIZE=str(a.nproc),
        )
        if not a.real_tpu:
            env = virtual_mesh_env(env, a.devices_per_proc)
        procs.append(subprocess.Popen(
            [sys.executable, a.script] + a.args, env=env))

    # Poll all workers: one dying (in distributed init, say) must tear the
    # rest down, or survivors block on the coordinator forever.
    rc = 0
    try:
        while any(pr.poll() is None for pr in procs):
            for pr in procs:
                code = pr.poll()
                if code is not None and code != 0:
                    rc = code
                    raise RuntimeError(f"worker exited with {code}")
            time.sleep(0.1)
        for pr in procs:
            rc = pr.returncode or rc
    except KeyboardInterrupt:
        rc = 130
    except RuntimeError as e:
        print(f"launch.py: {e}; terminating remaining workers",
              file=sys.stderr)
    finally:
        for pr in procs:
            if pr.poll() is None:
                pr.send_signal(signal.SIGTERM)
        deadline = time.time() + 5
        for pr in procs:
            while pr.poll() is None and time.time() < deadline:
                time.sleep(0.1)
            if pr.poll() is None:
                pr.kill()
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
