#!/bin/bash
# Launch N tpu-engine processes from a config file (fork cluster-on
# analogue). Usage: ./1-start-engines.sh [config/llama3-1chip.env]
#
# One process per chip: an engine claims every chip of its host and
# nothing pins a process to a device yet, so NUM_ENGINES > 1 on one
# host makes the second engine exit at start-up ("cannot claim the
# accelerator"). Use one engine per host (README "One process per
# chip").
set -euo pipefail
cd "$(dirname "$0")"
CONFIG="${1:-config/llama3-1chip.env}"
# shellcheck disable=SC1090
source "$CONFIG"

mkdir -p /tmp/tpu-stack
ENGINE_CMD="tpu-engine"
if ! command -v tpu-engine >/dev/null; then
    ENGINE_CMD="python -m production_stack_tpu.engine.server"
    export PYTHONPATH="$(cd .. && pwd):${PYTHONPATH:-}"
fi
for i in $(seq 0 $((NUM_ENGINES - 1))); do
    port=$((ENGINE_BASE_PORT + i))
    log="/tmp/tpu-stack/engine-$port.log"
    echo "==> engine :$port ($MODEL, tp=$TENSOR_PARALLEL_SIZE)"
    # shellcheck disable=SC2086
    nohup $ENGINE_CMD \
        --model "$MODEL" \
        --served-model-name "$SERVED_MODEL_NAME" \
        --port "$port" \
        --tensor-parallel-size "$TENSOR_PARALLEL_SIZE" \
        --max-model-len "$MAX_MODEL_LEN" \
        --max-num-seqs "$MAX_NUM_SEQS" \
        --num-pages "$NUM_PAGES" \
        --prefill-chunk-size "$PREFILL_CHUNK_SIZE" \
        --dtype "$DTYPE" \
        $EXTRA_FLAGS >"$log" 2>&1 &
    echo $! > "/tmp/tpu-stack/engine-$port.pid"
done
echo "logs: /tmp/tpu-stack/engine-*.log"
