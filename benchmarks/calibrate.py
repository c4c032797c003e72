"""Calibration on the chip, many readings to one set-up (not run by the
benchmark's own runs):

    python benchmarks/calibrate.py --workload <cell> --seeds 11,12,13 --seconds 12 [--control]
    python benchmarks/calibrate.py --workload <cell> --seeds 11,12,13 --seconds 12 --control-engine
    python benchmarks/calibrate.py --workload <cell> --seeds 11 --seconds 20 --rates 2,2.5,3

The first form is how the limits of ``correct`` were read (PERF.md §2): for
each seed the weights are drawn anew into the warmed engine, the cell's
traffic is ramped and served for a short window at the cell's own load, and
the served sample is compared with the float32 reference; with
``--control`` the int8 control is read on the same prompts and tokens.
The second form serves the same windows from the program with its own
lower-precision path switched on (the configuration file's
``correct.control_engine``: int8 pools), so its ``sound`` numbers are that
engine's (PERF.md §2 has what they read).  The third is the sweep that
found the open-loop cell's knee: the same traffic file at each offered
rate.  Every line it prints as
``CAL {...}`` is one reading.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as bench  # noqa: E402


def redraw(cell: bench.Cell, seed: int) -> None:
    """New weights from ``seed`` into the same engine: its programs take
    the weights as an argument, so nothing recompiles.  The old ones are
    dropped first (two sets do not fit one chip)."""
    import jax

    from benchmarks import builders
    from triton_dist_tpu.models import llama

    cfg = builders.llama_config(cell.config)
    cell.engine.params = None
    gc.collect()
    params = llama.init_params(cfg, builders.weight_key(seed))
    cell.engine.params = jax.block_until_ready(params)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=12.0)
    p.add_argument("--control", action="store_true")
    p.add_argument("--control-engine", action="store_true")
    p.add_argument("--rates", default="")
    p.add_argument("--trace-first", action="store_true",
                   help="trace the first window and print its reduction")
    p.add_argument("--cpu-dryrun", action="store_true")
    args = p.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    rates = [float(r) for r in args.rates.split(",") if r]
    cell = bench.Cell(args.workload, seeds[0], dry=args.cpu_dryrun,
                      control=args.control_engine)
    bench.say(f"calibrate {args.workload}: set-up "
              f"{time.perf_counter() - bench.T_PROCESS:.1f} s "
              f"(warm-up {cell.t_warm - cell.t_built:.1f} s, "
              f"{cell.warm['programs']} programs; xla requests "
              f"{cell.tally.requests}, cache hits {cell.tally.cache_hits})")
    limits = cell.config["correct"]["limits"]
    n_sample = int(cell.tparams.get("check_sample", 4))
    beyond = 0 if args.cpu_dryrun else bench.BEYOND
    plan = [(s, None) for s in seeds] if not rates else \
        [(seeds[0], r) for r in rates]
    for i, (seed, rate) in enumerate(plan):
        if rate is not None:
            cell.tparams["rate_per_s"] = rate
        elif i:
            redraw(cell, seed)
        traced = args.trace_first and i == 0 and not args.cpu_dryrun
        w = bench.measure(cell, seed, args.seconds, traced=traced)
        drv = w["drv"]
        vals, counts, facts, recs = bench.end_to_end(
            drv, w["lo"], w["hi"], w["t_open"], beyond)
        bad = [x for x in (bench.malformed(r, cell.config["vocab_size"])
                           for r in recs) if x]
        line = {"cell": args.workload, "seed": seed, "rate": rate,
                "kv_dtype": cell.config["engine"]["kv_dtype"],
                "ramp_s": w["t_open"] - w["t_ramp"], **facts, **vals,
                "samples": counts, "failed": len(bad),
                "waiting_at_close": w["queue_depth"],
                "in_flight_at_close": len(drv.inflight),
                "late_max_ms": 1e3 * max(drv.late),
                "rows_mean": w["counters"]["decode.rows_mean"],
                "kv_util_peak": w["counters"]["kv.util_peak"],
                "preemptions": w["counters"]["engine.preemptions"]}
        if traced:
            red = w["trace"]
            line["trace"] = {k: red[k] for k in (
                "window_s", "busy_s", "idle_share", "module_s", "module_n",
                "idle_by_span_s")}
            line["trace"]["top_ops"] = sorted(
                red["module_op_s"].items(), key=lambda kv: -kv[1])[:25]
            line["per_layer"] = bench.per_layer(cell, w, drv)
        if rate is None:
            check = bench.check_outputs(cell.config, seed, recs, n_sample,
                                        limits)
            bench.say_check(check, limits, len(bad))
            line["sound"] = check["numbers"]
            line["sound_ok"] = check["ok"]
            line["check_tokens"] = check.get("tokens")
            line["check_s"] = check.get("seconds")
            if args.control:
                ctl = bench.check_outputs(cell.config, seed, recs, n_sample,
                                          limits, int8=True)
                line["control"] = ctl["numbers"]
                line["control_ok"] = ctl["ok"]
        print("CAL " + json.dumps(line), flush=True)
        bench.drain(cell.engine)
    print(json.dumps({"device": bench.device_info(cell.chips,
                                                  args.cpu_dryrun)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
