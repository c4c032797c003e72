"""chip_smoke.py: what the driver's chip check relies on, pinned on the CPU.

The chip run itself happens through the chip tool; here (slow tier, one
subprocess each — backend state is process-global):

- plain ``python chip_smoke.py`` off a TPU exits non-zero and prints no
  result line;
- ``--cpu-dryrun`` — the ONLY way it starts off a TPU — drives the engine leg
  end to end at a toy size with the attention kernels in the Pallas
  interpreter, and its last stdout line is the result object;
- in a directory that holds ``chip_smoke.py`` and nothing else of the repo it
  fails too;
- importing the package, ``serve`` and ``kernels`` initialises no JAX backend
  (on a TPU host the first process to touch a backend owns the chip).
"""

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _run(args, *, cwd=REPO, devices=1, cache_dir=None, timeout=900):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env.pop("PYTHONPATH", None)
    if cache_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def _result_lines(stdout):
    return [ln for ln in stdout.splitlines() if ln.startswith("{")]


def test_plain_chip_smoke_refuses_the_cpu():
    r = _run([SCRIPT])
    assert r.returncode != 0, r.stdout[-2000:]
    assert "needs a TPU" in r.stderr, r.stderr[-2000:]
    assert not _result_lines(r.stdout), r.stdout[-2000:]


def test_chip_smoke_alone_fails(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    r = _run(["chip_smoke.py", "--cpu-dryrun"], cwd=tmp_path)
    assert r.returncode != 0, r.stdout[-2000:]
    assert "triton_dist_tpu" in r.stderr, r.stderr[-2000:]
    assert not _result_lines(r.stdout), r.stdout[-2000:]


def _default_cache_entries():
    path = os.path.join(REPO, ".jax_cache")
    return sorted(os.listdir(path)) if os.path.isdir(path) else []


def test_cpu_dryrun_serves_the_engine_leg(tmp_path):
    before = _default_cache_entries()
    r = _run([SCRIPT, "--cpu-dryrun", "--seed", "3"], cache_dir=tmp_path)
    assert r.returncode == 0, (r.stdout[-3000:], r.stderr[-3000:])
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "cpu_dryrun": True,
                    "device": {"platform": "cpu", "kind": "cpu",
                               "count": 1}}
    report = json.loads(next(
        ln for ln in r.stdout.splitlines()
        if ln.startswith("[chip_smoke] report ")).split("report ", 1)[1])
    assert report["legs_run"] == ["one_chip"] and report["seed"] == 3
    leg = report["legs"]["one_chip"]
    assert leg["requests"] == 3 and leg["sampled_requests"] == 1
    assert leg["new_tokens"] == 30 and leg["warmup_programs"] > 0
    # the interpreter stands in for Mosaic: nothing to count in the text
    assert leg["mosaic_calls"] == {}
    assert report["compile_cache_dir"] == str(tmp_path)
    # the cache was placed from outside: the default place is untouched
    assert _default_cache_entries() == before


def test_importing_the_package_initialises_no_backend():
    code = ("import jax\n"
            "import triton_dist_tpu, triton_dist_tpu.serve\n"
            "import triton_dist_tpu.kernels\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, xla_bridge._backends\n"
            "print('NO BACKEND')\n")
    r = _run(["-c", code])
    assert r.returncode == 0 and "NO BACKEND" in r.stdout, r.stderr[-2000:]
