#!/bin/bash
# North-star serving benchmark (BASELINE.md row 1): native etcd + master
# + ONE real worker + benchmarks.loadgen, percentiles through the full
# /v1/chat/completions path. Defaults drive the llama3-1b flagship on
# the device JAX gives (pin CPU with JAX_PLATFORMS=cpu for a harness
# smoke). Every process started here is stopped on the way out.
#
# Usage: tools/loadgen_stack.sh [model] [num_requests] [max_tokens] \
#            [request_rate] [mean_prompt_len]
set -u
cd "$(dirname "$0")/.."
MODEL="${1:-llama3-1b}"
NREQ="${2:-64}"
MAXTOK="${3:-64}"
RATE="${4:-4}"
PLEN="${5:-128}"
OUT="${LOADGEN_OUT:-loadgen_last.json}"

cleanup() {
  [ -n "${WPID:-}" ] && kill "$WPID" 2>/dev/null
  [ -n "${MPID:-}" ] && kill "$MPID" 2>/dev/null
  [ -n "${EPID:-}" ] && kill "$EPID" 2>/dev/null
  wait 2>/dev/null
}
trap cleanup EXIT

# 1. Native etcd coordination server on an ephemeral port.
ETCD_BIN=$(python -c "from xllm_service_tpu.service.etcd_native import build_binary; print(build_binary() or '')")
[ -n "$ETCD_BIN" ] || { echo "xllm_etcd build failed" >&2; exit 1; }
ETCD_FIFO=$(mktemp -u)
mkfifo "$ETCD_FIFO"
"$ETCD_BIN" 0 > "$ETCD_FIFO" &
EPID=$!
read -r _LISTENING ETCD_PORT < "$ETCD_FIFO"
rm -f "$ETCD_FIFO"
ETCD_ADDR="127.0.0.1:$ETCD_PORT"
echo "etcd at $ETCD_ADDR (pid $EPID)"

# 2. Master backed by it.
HTTP_PORT="${HTTP_PORT:-18988}"
RPC_PORT="${RPC_PORT:-18989}"
python -m xllm_service_tpu.service.master \
    --host 127.0.0.1 --http-port "$HTTP_PORT" --rpc-port "$RPC_PORT" \
    --etcd-addr "etcd://$ETCD_ADDR" > /tmp/loadgen_master.log 2>&1 &
MPID=$!
MOK=""
for i in $(seq 1 30); do
  grep -q XLLM_SERVICE_UP /tmp/loadgen_master.log 2>/dev/null && { MOK=1; break; }
  kill -0 "$MPID" 2>/dev/null || break
  sleep 1
done
[ -n "$MOK" ] || { echo "master failed to boot (see /tmp/loadgen_master.log)" >&2; exit 1; }

# 3. One real worker (owns the chip when a TPU is reachable).
python -m xllm_service_tpu.runtime.worker \
    --host 127.0.0.1 --port "${WORKER_PORT:-18990}" --model "$MODEL" \
    --service-addr "127.0.0.1:$RPC_PORT" \
    --store-addr "etcd://$ETCD_ADDR" \
    ${WORKER_ARGS:-} > /tmp/loadgen_worker.log 2>&1 &
WPID=$!

# 4. Wait for registration — the boot warm-up compiles every program.
READY=""
for i in $(seq 1 "${REGISTER_TRIES:-120}"); do
  if curl -sf "http://127.0.0.1:$HTTP_PORT/v1/models" | grep -q "\"$MODEL\""; then
    READY=1; break
  fi
  sleep 5
done
[ -n "$READY" ] || { echo "worker never registered" >&2; exit 1; }

# 5. The measured run (pipefail: a crashed loadgen must not exit 0
# through tee).
set -o pipefail
python -m benchmarks.loadgen --target "127.0.0.1:$HTTP_PORT" \
    --model "$MODEL" --num-requests "$NREQ" --max-tokens "$MAXTOK" \
    --request-rate "$RATE" --mean-prompt-len "$PLEN" | tee "$OUT"
