#!/bin/bash
# Single-chip perf sweep (BASELINE.json primary metric; needs the chip).
# Each config runs in a fresh process, one after the other (a chip
# belongs to one process at a time); the fed plane and the serving legs
# are off here — this sweeps the device-step ceiling.
#
# Order is most-promising-first (bn bf16 at large batch — the predicted
# MFU lever), so a run cut short has the configs that matter before the
# baselines; the fp32 cells exist to isolate the bn-dtype delta, the
# remat cells to open HBM headroom past batch 1024.
# Mode (arg 1): "first" runs only the single most-promising cell;
# "rest" runs the remaining cells; "all" (default) runs everything.
set -u
set -o pipefail
MODE="${1:-all}"
FAILED=0
cd "$(dirname "$0")/.."
run_cfg() {
  echo "=== batch=$1 bn_dtype=$2 remat=${3:-0} ==="
  # The outer timeout is the bound. -k: escalate to SIGKILL for
  # processes wedged in C with a TERM handler installed (the handler
  # can never run in a stuck eval loop). A dead cell must FAIL the
  # script (pipefail keeps the bench's exit code through `tail`, and
  # bench.py exits non-zero without a chip or when a leg raised), not
  # be laundered into a silent empty line. Later cells still run; the
  # script's exit reports the sweep as a whole.
  local line
  line=$(TFOS_BENCH_FED=0 TFOS_BENCH_SERVING=0 TFOS_BENCH_BATCH=$1 \
    TFOS_BENCH_BN_DTYPE=$2 TFOS_BENCH_REMAT=${3:-0} \
    timeout -k 30 900 python bench.py 2>/dev/null | tail -1) \
    || { echo "CELL FAILED (exit $?)"; FAILED=1; return; }
  echo "$line"
}
if [ "$MODE" != "rest" ]; then
  run_cfg 512 bfloat16
fi
if [ "$MODE" != "first" ]; then
  run_cfg 1024 bfloat16
  run_cfg 256 bfloat16
  run_cfg 1024 bfloat16 1
  run_cfg 2048 bfloat16 1
  run_cfg 512 float32
  run_cfg 256 float32
  run_cfg 1024 float32
fi
exit $FAILED
