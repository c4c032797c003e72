#!/usr/bin/env python3
"""The int8 control of a cell, read on the requests the cell itself served.

    python3 benchmarks/control.py --workload <cell> --seed <n> --seconds 40

One run of ``run.py``'s ``run_cell``; then, with the engine freed, the same
sample of served requests goes through the cell's reference again with int8
operands and int8 cache rows (``check_outputs(int8=True)``), and the gaps of
the tokens THAT puts first are held to the file's ``correct.limits``.  This
is where a configuration's limits get their upper reading: a precision below
the one the configuration states has to fail at least one of them.  For any
configuration whose reference takes ``int8=`` (``calibrate.py`` redraws the
dense family's weights itself and needs no second process).

Prints the cell's result object, then ``CONTROL {...}`` as the last line;
exits 0 when the control fails a limit, as it must, and 1 when it passes.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import run as bench  # noqa: E402


def read_control(state: dict, n_sample: int) -> dict:
    """``state`` as ``run_cell(after_window=)`` hands it over -> the int8
    control's ``check_outputs`` on the same served records."""
    config = state["config"]
    return bench.check_outputs(config, state["seed"], state["recs"],
                               n_sample, config["correct"]["limits"],
                               int8=True)


def main(argv=None) -> int:
    from benchmarks import traffic

    args = bench.parse_args(argv)
    state = {}
    result = bench.run_cell(args, after_window=state.update)
    print(json.dumps(result), flush=True)
    cell = bench.load_cell(args.workload)["cell"]
    n_sample = int(traffic.load(cell["traffic"]).get("check_sample", 4))
    ctl = read_control(state, n_sample)
    print("CONTROL " + json.dumps({
        k: ctl[k] for k in ("numbers", "ok", "agree", "tokens", "seconds")}),
        flush=True)
    return 1 if ctl["ok"] else 0


if __name__ == "__main__":
    sys.exit(main())
