"""Kernel overlap scoreboard CLI (docs/observability.md "Kernel
observability").

Runs ``runtime/kprobe`` probes — fused vs compute-only vs comm-only
legs plus the phase-sliced per-ring-step replay under
``profiling.annotate`` spans — for the overlapped kernels and emits:

- one JSON overlap report per kernel
  (``{out}/{kernel}.overlap.json``): per-step phase timings, overlap
  efficiency ``(T_compute + T_comm) / T_fused``, critical-path
  attribution, and the ``kernels/perf_model`` predicted-vs-measured
  table;
- one reconstructed Perfetto track per rank
  (``{out}/rank{r}/kprobe_{kernel}.trace.json.gz``), merged by
  ``profiling.merge_rank_traces`` into ``{out}/merged.trace.json.gz``
  — the same ui.perfetto.dev file a ``group_profile`` device capture
  or an engine ``FlightRecorder.export_profile`` dropped into the
  same directory joins;
- ONE summary JSON line on stdout.

Examples::

    # 2-device virtual CPU mesh (sandbox; structural numbers)
    python scripts/kernel_report.py --cpu 2 --kernel ag_gemm

    # every covered kernel, bench-ish shape, merged Perfetto artifact
    python scripts/kernel_report.py --cpu 2 --kernel all --out prof/kr

    # on hardware: run under the real mesh (no --cpu), then load
    # {out}/merged.trace.json.gz in ui.perfetto.dev
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--kernel", default="ag_gemm",
                   help="ag_gemm | gemm_rs | moe_reduce_rs | sp_decode "
                        "| all")
    p.add_argument("--world", type=int, default=2,
                   help="mesh size along the probed axis (clamped to "
                        "the available device count)")
    p.add_argument("--cpu", type=int, default=None, metavar="N",
                   help="fabricate an N-device virtual CPU mesh before "
                        "backend init (sandbox runs; omit on hardware)")
    p.add_argument("--out", default=None,
                   help="artifact directory (default: no files, "
                        "summary line only)")
    p.add_argument("--trials", type=int, default=3)
    p.add_argument("--impl", default="auto")
    p.add_argument("--seed", type=int, default=0)
    # ag_gemm / gemm_rs shape (ag: N per chip = n-loc; rs: global N)
    p.add_argument("-M", type=int, default=512)
    p.add_argument("-K", type=int, default=256)
    p.add_argument("--n-loc", type=int, default=128)
    p.add_argument("-N", type=int, default=256)
    p.add_argument("--bench-shape", action="store_true",
                   help="ag_gemm at the driver bench shape (M=8192 "
                        "K=8192 n_loc=3584) — minutes on CPU")
    # moe_reduce_rs shape
    p.add_argument("-T", type=int, default=32)
    p.add_argument("-D", type=int, default=128)
    p.add_argument("--experts", type=int, default=4)
    p.add_argument("--topk", type=int, default=2)
    # sp_decode shape
    p.add_argument("-B", type=int, default=4)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--kv-heads", type=int, default=2)
    p.add_argument("-S", type=int, default=512)
    p.add_argument("--head-dim", type=int, default=64)
    args = p.parse_args()

    if args.cpu is not None:
        # must land before ANY jax backend init (device count is fixed
        # at client creation) — the same recipe as tests/conftest.py
        from triton_dist_tpu.runtime import testenv

        testenv.apply_virtual_mesh_env(args.cpu)

    import jax
    import numpy as np
    from jax.sharding import Mesh

    from triton_dist_tpu.runtime import configure_compile_cache, kprobe

    configure_compile_cache()
    from triton_dist_tpu.runtime.profiling import merge_rank_traces

    kernels = (list(kprobe.KERNELS) if args.kernel == "all"
               else [args.kernel])
    for kern in kernels:
        if kern not in kprobe.KERNELS:
            p.error(f"unknown --kernel {kern!r}; choose from "
                    f"{kprobe.KERNELS} or 'all'")
    world = max(1, min(args.world, len(jax.devices())))
    if world < args.world:
        print(f"# only {len(jax.devices())} device(s): world clamped "
              f"to {world} (use --cpu N for a virtual mesh)",
              file=sys.stderr)

    M = 8192 if args.bench_shape else args.M
    K = 8192 if args.bench_shape else args.K
    n_loc = 3584 if args.bench_shape else args.n_loc
    shape_kw = {
        "ag_gemm": dict(M=M, K=K, n_loc=n_loc),
        "gemm_rs": dict(M=args.M, K=args.K, N=args.N),
        "moe_reduce_rs": dict(T=args.T, D=args.D,
                              n_experts=args.experts, topk=args.topk),
        "sp_decode": dict(B=args.B, Hq=args.heads, Hkv=args.kv_heads,
                          S=args.S, D=args.head_dim),
    }

    summary = {"world": world, "backend": jax.default_backend(),
               "kernels": {}}
    for kern in kernels:
        axis = "sp" if kern == "sp_decode" else "tp"
        mesh = Mesh(np.array(jax.devices()[:world]), (axis,))
        rep = kprobe.run_probe(kern, mesh, axis=axis, impl=args.impl,
                               trials=args.trials, seed=args.seed,
                               **shape_kw[kern])
        d = rep.to_dict()
        summary["kernels"][kern] = {
            "overlap_efficiency": d["overlap_efficiency"],
            "model_vs_measured": d["model"]["model_vs_measured"],
            "fused_ms": d["timings_ms"]["fused"],
            "critical_bound": d["critical_path"]["bound"],
        }
        print(f"# {kern}: fused {d['timings_ms']['fused']:.3f} ms, "
              f"compute {d['timings_ms']['compute_only']:.3f} + comm "
              f"{d['timings_ms']['comm_only']:.3f} ms -> overlap eff "
              f"{d['overlap_efficiency']:.3f}, "
              f"{d['critical_path']['bound']}-bound, model/measured "
              f"{d['model']['model_vs_measured']:.3f}",
              file=sys.stderr)
        if args.out:
            path = rep.save(os.path.join(args.out,
                                         f"{kern}.overlap.json"))
            tracks = rep.export_profile(args.out)
            print(f"#   report {path}; {len(tracks)} rank tracks",
                  file=sys.stderr)
    if args.out:
        merged = merge_rank_traces(args.out)
        summary["merged_trace"] = merged
        if merged:
            print(f"# merged Perfetto timeline: {merged} (open in "
                  f"ui.perfetto.dev)", file=sys.stderr)
    print(json.dumps(summary))


if __name__ == "__main__":
    main()
