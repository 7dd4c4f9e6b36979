#!/usr/bin/env bash
# Acceptance config: elastic recovery — SIGKILL the global server
# mid-training, relaunch it, and the run completes (checkpoint resume +
# request replay).  Improvement over the reference, whose global-tier
# recovery is a TODO (ref: 3rdparty/ps-lite/src/van.cc:224).
#
# Env: BASE_PORT (9400), STEPS (25), CKPT_DIR (tmp)
set -euo pipefail
cd "$(dirname "$0")/.."
export JAX_PLATFORMS=cpu  # several role processes share this host; a chip belongs to one (see run_cluster.sh)

BASE_PORT="${BASE_PORT:-9400}"
STEPS="${STEPS:-25}"
CKPT_DIR="${CKPT_DIR:-$(mktemp -d)}"
export GEOMX_CHECKPOINT_DIR="$CKPT_DIR"
export GEOMX_AUTO_CKPT_UPDATES="${GEOMX_AUTO_CKPT_UPDATES:-1}"
export GEOMX_REQUEST_RETRY_S="${GEOMX_REQUEST_RETRY_S:-1.0}"

COMMON=(--parties 1 --workers 1 --base-port "$BASE_PORT" --steps "$STEPS")

pids=()
launch() {
  python -m geomx_tpu.launch --role "$1" "${COMMON[@]}" &
  pids+=($!)
}

launch "global_scheduler:0"
launch "global_server:0"
GS_PID="${pids[-1]}"
launch "scheduler:0@p0"
launch "server:0@p0"
launch "worker:0@p0"
trap 'kill "${pids[@]}" 2>/dev/null || true' EXIT

# wait for the first checkpoint, then kill + relaunch the global server
for _ in $(seq 1 240); do
  [[ -f "$CKPT_DIR/global_server_0.npz" ]] && break
  sleep 0.5
done
[[ -f "$CKPT_DIR/global_server_0.npz" ]] || { echo "no checkpoint"; exit 1; }
sleep 1
echo ">>> SIGKILL global_server:0 (pid $GS_PID)"
kill -9 "$GS_PID" 2>/dev/null || true
sleep 1
echo ">>> relaunching global_server:0"
launch "global_server:0"

fail=0
for pid in "${pids[@]}"; do
  [[ "$pid" == "$GS_PID" ]] && continue  # the killed incarnation
  wait "$pid" || fail=1
done
echo "recovery run exit=$fail"
exit $fail
