#!/usr/bin/env bash
# Full AOT flow on the real TPU: export with Python, execute with the
# native runtime (no Python in the serving process).
# Reference analog: scripts/gen_aot_code.sh + the AOT C runtime.
set -euo pipefail
DIR=${1:-/tmp/tdt_aot_artifacts}
REPO=$(cd "$(dirname "$0")/.." && pwd)

python - <<PY
import triton_dist_tpu.kernels.gemm  # registers "matmul"
import triton_dist_tpu.kernels.flash_decode  # registers "gqa_decode"
import triton_dist_tpu.kernels.quant  # registers "matmul_i8"
from triton_dist_tpu.tools import compile_aot
man = compile_aot.export_registered("$DIR")
print("exported", sum(len(v) for v in man["kernels"].values()), "variants")
PY

make -C "$REPO/csrc/aot_runtime"
# The PJRT plugin is the installed libtpu.so unless TDT_PJRT_PLUGIN names
# another; it needs no client create options.  The export above and the
# binary below each take the chip in turn: one process at a time.
PLUGIN=${TDT_PJRT_PLUGIN:-$(python -c "import libtpu, os; print(os.path.join(os.path.dirname(libtpu.__file__), 'libtpu.so'))")}
"$REPO/csrc/aot_runtime/build/tdt_aot_run" --selftest "$DIR"
"$REPO/csrc/aot_runtime/build/tdt_aot_run" \
  --plugin "$PLUGIN" --dir "$DIR" --kernel matmul --var 3 \
  --checksum
